"""ECBatcher: host work per dispatch on the worker thread over the
window: staging (pack, pad, device_put, launch) and unpacking on the
device engine, the whole native call on the host engine (perf
``ec_host_lat`` sum / count, one sample per dispatch)."""


def read(w):
    n = w.delta("osd.ec_host_lat.count")
    if n <= 0:
        return None
    return 1e3 * w.delta("osd.ec_host_lat.sum") / n
