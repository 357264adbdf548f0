"""OSD / PG: mean client-op latency inside the OSDs over the window, all
OSDs together (perf ``op_latency`` sum / count)."""


def read(w):
    n = w.delta("osd.op_latency.count")
    if n <= 0:
        return None
    return 1e3 * w.delta("osd.op_latency.sum") / n
