"""OSD / PG: the PG's self time per client op over the window:
osd.op_latency_ms less the PG lock wait, the ECBatcher wait and the
sub-op fan-outs (perf ``op_latency`` less ``op_pg_lock_lat``,
``op_ec_lat`` and ``op_subop_lat``, over ``op_latency`` count). A
negative value means a stage is counted twice."""

STAGES = ("op_pg_lock_lat", "op_ec_lat", "op_subop_lat")


def read(w):
    n = w.delta("osd.op_latency.count")
    if n <= 0 or any(f"{s}.sum" not in w.after["osd"] for s in STAGES):
        return None
    rest = w.delta("osd.op_latency.sum") - sum(
        w.delta(f"osd.{s}.sum") for s in STAGES)
    return 1e3 * rest / n
