"""Run one cell of the on-chip benchmark and print its result line.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout. ``BENCHMARK.json`` names the cells; the
last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and the numbers compared with the reference last). With no
accelerator, or fewer chips than the cell asks for, it prints no result
and exits non-zero.
"""
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    import os
    import sys

    os.environ.setdefault("TPU_LOG_DIR", "disabled")    # no /tmp/tpu_logs
    from chipbench.harness import main
    sys.exit(main(t_start=T_START))
