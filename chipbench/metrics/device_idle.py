"""Share of the traced window in which no operation runs on a device,
averaged over the cell's devices (profiler trace)."""


def read(m):
    return 100.0 * (1.0 - m.trace.mean_busy_s / m.trace.window_s)
