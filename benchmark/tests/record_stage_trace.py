"""Record the small device trace with the EC dispatch stages that
test_stage_metrics.py reads.

On the chip: ``python benchmark/tests/record_stage_trace.py <out_dir>
[<data_dir>]``. It runs three encode and three decode dispatches of
the k8m3 cells' smallest batch (128 stripes of 4 KiB cells) through the
ECBatcher, so each dispatch leaves its ``ec.*`` stage spans, and three
ring spans (utils/trace, the OSDs' ``dump_tracing`` ring) each around
the same call as a profiler ``host_span`` named ``clock_check``, with
the profiler options the harness uses. Given a data directory it copies
the trace there as ``tpu_v5e_ec_stages.xplane.pb`` and writes the ring
spans' stamps beside it as ``tpu_v5e_ec_stages.clock.json``. It prints
the gap between each ring span and its profiler event, and what one
``host_span`` costs with the profiler off and on.
"""
from __future__ import annotations

import asyncio
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)),
                os.path.dirname(HERE)]

NAME = "tpu_v5e_ec_stages"
CLOCK_SPAN = "clock_check"


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def profile_start_ns(pd) -> int:
    """The session's start on the host's wall clock: the profiler's
    event times are nanoseconds after it."""
    for plane in pd.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                return int(value)
    raise KeyError("profile_start_time")


def clock_gaps(pd, ring: list) -> list:
    """(start gap, end gap) in ns of each ring span against the
    profiler event of the same name, in order."""
    t0 = profile_start_ns(pd)
    events = sorted((t0 + ev.start_ns, t0 + ev.end_ns)
                    for plane in pd.planes for line in plane.lines
                    for ev in line.events if ev.name == CLOCK_SPAN)
    return [(abs(s - r["start_ns"]),
             abs(e - (r["start_ns"] + r["duration_ns"])))
            for (s, e), r in zip(events, ring)]


def host_span_cost_ns(n: int) -> float:
    from ceph_tpu.utils import trace

    t = time.perf_counter_ns()
    for _ in range(n):
        with trace.host_span("ec.cost"):
            pass
    return (time.perf_counter_ns() - t) / n


def main(out_dir: str, data_dir: str | None = None) -> None:
    import jax
    from jax.profiler import ProfileData

    from ceph_tpu.cluster.ecbatch import ECBatcher
    from ceph_tpu.ec.registry import load_codec
    from ceph_tpu.utils import config as cfg
    from ceph_tpu.utils import trace
    from ceph_tpu.utils.perf import PerfCounters
    from harness import xplane

    codec = load_codec({"plugin": "rs_tpu", "k": "8", "m": "3",
                        "backend": "device"})
    conf = cfg.proxy()
    conf.apply({"osd_ec_cold_shape_bytes": 0})
    perf = PerfCounters("record")
    ECBatcher.declare_counters(perf)
    batcher = ECBatcher(perf, conf=conf)
    cells = np.random.default_rng(0).integers(0, 256, (128, 8, 4096),
                                              dtype=np.uint8)
    present = (0, 2, 3, 4, 5, 7, 8, 9)
    x = np.random.default_rng(1).integers(0, 2**32, (128, 8, 1024),
                                          dtype=np.uint32)
    ring_tracer = trace.get_tracer("clock-check")

    async def dispatches(n: int) -> None:
        for _ in range(n):
            await batcher.encode_cells(codec, cells)
            await batcher.decode_cells(codec, present, (1, 6), cells)

    asyncio.run(dispatches(1))  # compiles
    jax.block_until_ready(codec.encode_crc_batch(x, 4096))
    ring = []
    jax.profiler.start_trace(out_dir, profiler_options=profile_options())
    asyncio.run(dispatches(3))
    for _ in range(3):
        with ring_tracer.start_span(CLOCK_SPAN) as sp, \
                trace.host_span(CLOCK_SPAN):
            jax.block_until_ready(codec.encode_crc_batch(x, 4096))
        ring.append({"start_ns": sp.start_ns,
                     "duration_ns": sp.duration_ns})
    jax.profiler.stop_trace()
    path = xplane.find_trace(out_dir)
    print(f"trace {path}: {os.path.getsize(path)} bytes")
    pd = ProfileData.from_file(path)
    gaps = clock_gaps(pd, ring)
    print(f"ring span vs profiler event, (start, end) gap ns: {gaps}")
    s = xplane.reduce(path)
    print(f"busy_s {s.busy_s()} modules {s.module_seconds()}")
    print(f"breakdown {s.breakdown()}")
    names = sorted({n for _, _, n in s.host_events if n.startswith("ec.")})
    print(f"ec spans {names}; counters "
          f"{ {k: v for k, v in perf.dump().items() if k.endswith('_lat')} }")

    n = 200_000
    off = host_span_cost_ns(n)
    cost_dir = tempfile.mkdtemp(prefix="host-span-cost-")
    jax.profiler.start_trace(cost_dir, profiler_options=profile_options())
    on = host_span_cost_ns(n // 10)
    jax.profiler.stop_trace()
    shutil.rmtree(cost_dir, ignore_errors=True)
    print(f"host_span cost: {off} ns profiler off, {on} ns on "
          f"({n} and {n // 10} spans)")

    if data_dir is not None:
        shutil.copy(path, os.path.join(data_dir, f"{NAME}.xplane.pb"))
        with open(os.path.join(data_dir, f"{NAME}.clock.json"), "w") as f:
            json.dump({"span": CLOCK_SPAN, "ring": ring}, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main(*sys.argv[1:3])
