"""OSD op queue: mean time of a client op from its arrival at the OSD
(before the ingest byte throttle) to its dequeue by an op worker, over
the window, all OSDs together (perf ``op_queue_lat`` sum / count)."""


def read(w):
    n = w.delta("osd.op_queue_lat.count")
    if n <= 0:
        return None
    return 1e3 * w.delta("osd.op_queue_lat.sum") / n
