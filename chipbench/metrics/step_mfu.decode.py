"""Model FLOPs of the decode work done in the traced window over what the
chips' bf16 peak could do in it: matrix products at two FLOPs per weight
per token plus causal attention, counted from the configuration's sizes
(chipbench/counts.py), nothing recomputed."""


def read(m):
    if m.work["mode"] != "decode" or not m.work["flops"]:
        return None
    peak = m.trace.window_s * m.chips * m.peaks["bf16_flops_per_s"]
    return 100.0 * m.work["flops"] / peak
