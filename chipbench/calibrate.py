"""Readings for a cell's limits: the program's and the control's, on many
seeds, in one process.

    python3 -m chipbench.calibrate --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 3

Each seed is one run of the cell (set-up, a short window at the cell's own
load, the check), with the control (the reference in float8, in the
program's place) read as well on ``--control-seeds``. One JSON line per
seed; the limits in ``chipbench/limits/`` are set from these readings.
"""
import argparse
import json
import time

T_START = time.perf_counter()


def main() -> int:
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    cell = harness.load_cell(harness.ROOT, args.workload)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for s in (int(x) for x in args.seeds.split(",")):
        found = {}
        res = harness.run(cell, s, args.seconds, False, time.perf_counter(),
                          control="fp8" if s in controls else None,
                          readings=found)
        print(json.dumps({"seed": s, "readings": found,
                          "metrics": res["metrics"],
                          "peak": res["device"]["memory_peak_bytes"]}),
              flush=True)
    print(f"calibrate: {time.perf_counter() - T_START:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
