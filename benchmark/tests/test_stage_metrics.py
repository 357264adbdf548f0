"""The per-layer metrics that read the OSD op stages and the EC dispatch
stages: the counter readers on synthetic windows, the idle share
without a dispatch on a trace recorded on a TPU v5e
(record_stage_trace.py), and the wall clock the ring spans share with
the profiler's host events."""
from __future__ import annotations

import json
import os

import pytest
from jax.profiler import ProfileData

from harness import xplane
from harness.registry import Bench
from harness.runner import Window

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
STAGE_TRACE = os.path.join(DATA, "tpu_v5e_ec_stages.xplane.pb")
STAGE_CLOCK = os.path.join(DATA, "tpu_v5e_ec_stages.clock.json")
OLD_TRACE = os.path.join(DATA, "tpu_v5e_encode_decode.xplane.pb")

#: (sum s, count) of each counter before and after a synthetic window:
#: 20 client ops, 10 dispatches
BEFORE = {"op_latency": (1.0, 10), "op_queue_lat": (0.1, 10),
          "op_pg_lock_lat": (0.2, 8), "op_ec_lat": (0.3, 9),
          "op_subop_lat": (0.4, 12), "ec_host_lat": (0.05, 5),
          "ec_device_wait_lat": (0.01, 5), "ec_readback_lat": (0.02, 5),
          "ec_handoff_lat": (0.03, 5)}
AFTER = {"op_latency": (3.0, 30), "op_queue_lat": (0.5, 30),
         "op_pg_lock_lat": (0.4, 28), "op_ec_lat": (0.9, 29),
         "op_subop_lat": (1.0, 40), "ec_host_lat": (0.25, 15),
         "ec_device_wait_lat": (0.06, 15), "ec_readback_lat": (0.07, 15),
         "ec_handoff_lat": (0.13, 15)}

COUNTER_METRICS = {
    "osd.queue_wait_ms": 20.0,      # 0.4 s over 20 dequeued ops
    "pg.lock_wait_ms": 10.0,        # 0.2 s over 20 ops
    "pg.ec_wait_ms": 30.0,          # 0.6 s over 20 ops
    "pg.subop_wait_ms": 30.0,       # 0.6 s over 20 ops
    "pg.self_ms": 30.0,             # (2.0 - 0.2 - 0.6 - 0.6) s / 20
    "ecbatch.host_ms": 20.0,        # 0.2 s over 10 dispatches
    "ecbatch.device_wait_ms": 5.0,
    "ecbatch.readback_ms": 5.0,
    "ecbatch.handoff_ms": 10.0,
}
NEW_METRICS = sorted(COUNTER_METRICS) + ["device.idle_no_dispatch_pct"]


def _snap(counters: dict) -> dict:
    osd = {}
    for key, (s, n) in counters.items():
        osd[f"{key}.sum"] = s
        osd[f"{key}.count"] = n
    return {"osd": osd}


def _reader(name: str):
    return Bench().metric_reader(name)


@pytest.mark.parametrize("name", sorted(COUNTER_METRICS))
def test_counter_reader(name):
    w = Window(seconds=20.0, before=_snap(BEFORE), after=_snap(AFTER))
    assert _reader(name)(w) == pytest.approx(COUNTER_METRICS[name])


@pytest.mark.parametrize("name", sorted(COUNTER_METRICS))
def test_counter_reader_reads_nothing_without_its_counters(name):
    """A program without the stage counters (the parent of the change
    that added them) reports op_latency alone: nothing to read."""
    only = {"op_latency"}
    w = Window(seconds=20.0,
               before=_snap({k: v for k, v in BEFORE.items() if k in only}),
               after=_snap({k: v for k, v in AFTER.items() if k in only}))
    assert _reader(name)(w) is None


def test_new_metrics_are_declared_for_every_cell():
    spec = Bench().spec
    cells = [c["name"] for c in spec["workloads"]]
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == cells, name


def _stage_window(path: str) -> Window:
    s = xplane.reduce(path)
    return Window(seconds=1.0, before={}, after={}, trace=s,
                  trace_s=(s.span_ns[1] - s.span_ns[0]) / 1e9)


def test_idle_without_dispatch_on_recorded_trace():
    """Idle stretches under an open ec.* stage are taken out: the share
    lies between 0 and device.idle_pct, and below it."""
    w = _stage_window(STAGE_TRACE)
    names = {n for _, _, n in w.trace.host_events}
    assert {"ec.stage", "ec.device_wait", "ec.readback",
            "ec.unpack"} <= names
    idle = _reader("device.idle_pct")(w)
    idle_nd = _reader("device.idle_no_dispatch_pct")(w)
    assert 0 <= idle_nd < idle


def test_idle_without_dispatch_reads_nothing_without_stage_spans():
    assert _reader("device.idle_no_dispatch_pct")(
        _stage_window(OLD_TRACE)) is None


def test_ring_spans_share_the_profiler_clock():
    """A ring span (utils/trace, integer time.time_ns stamps) and the
    profiler host_span around the same call agree within 50 us."""
    import record_stage_trace as rec

    with open(STAGE_CLOCK) as f:
        clock = json.load(f)
    gaps = rec.clock_gaps(ProfileData.from_file(STAGE_TRACE),
                          clock["ring"])
    assert len(gaps) == len(clock["ring"]) == 3
    assert all(s <= 50_000 and e <= 50_000 for s, e in gaps), gaps
