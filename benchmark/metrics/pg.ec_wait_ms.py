"""OSD / PG: time per client op the PG awaits the ECBatcher (encode,
decode, repair) over the window (perf ``op_ec_lat`` sum over
``op_latency`` count), a part of osd.op_latency_ms."""


def read(w):
    n = w.delta("osd.op_latency.count")
    if n <= 0 or w.delta("osd.op_ec_lat.count") <= 0:
        return None
    return 1e3 * w.delta("osd.op_ec_lat.sum") / n
