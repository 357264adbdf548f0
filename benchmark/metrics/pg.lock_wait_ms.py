"""OSD / PG: PG lock wait per client op over the window (perf
``op_pg_lock_lat`` sum over ``op_latency`` count), a part of
osd.op_latency_ms."""


def read(w):
    n = w.delta("osd.op_latency.count")
    if n <= 0 or w.delta("osd.op_pg_lock_lat.count") <= 0:
        return None
    return 1e3 * w.delta("osd.op_pg_lock_lat.sum") / n
