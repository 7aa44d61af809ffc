"""Device time per decode step at the tensor-parallel all-reduce site: the
union of the collective operations and the codec and RDMA kernels on each
device, averaged over devices, over the steps of the traced window."""


def read(m):
    if m.work["mode"] != "decode" or not m.work["steps"]:
        return None
    return 1e3 * m.trace.mean(m.trace.tp_site_s) / m.work["steps"]
