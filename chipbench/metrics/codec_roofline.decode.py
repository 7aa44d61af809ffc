"""The wire codec kernels' share of the HBM roofline in decode: the logical
bytes of their calls (float32 message in and wire out for an encode, the
reverse for a decode; chipbench/counts.py) over their summed kernel time
times the HBM bandwidth. Read only where the trace holds exactly the calls
counted, so that bytes and time cover the same work."""


def read(m):
    if m.work["mode"] != "decode" or not m.work["codec_bytes"]:
        return None
    calls = m.work["codec_calls"]
    if any(round(m.trace.op_count.get(k, 0.0)) != n
           for k, n in calls.items()):
        return None
    t = m.trace.mean(m.trace.codec_s)
    if t <= 0:
        return None
    return 100.0 * m.work["codec_bytes"] / (t * m.peaks["hbm_bytes_per_s"])
