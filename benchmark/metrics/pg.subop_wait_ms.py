"""OSD / PG: sub-op fan-out time per client op over the window, each
fan-out timed once from its first send to the last reply it waits for
(perf ``op_subop_lat`` sum over ``op_latency`` count), a part of
osd.op_latency_ms."""


def read(w):
    n = w.delta("osd.op_latency.count")
    if n <= 0 or w.delta("osd.op_subop_lat.count") <= 0:
        return None
    return 1e3 * w.delta("osd.op_subop_lat.sum") / n
