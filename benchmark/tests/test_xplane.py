"""The trace reduction, on a synthetic trace whose answer is known and
on a small trace recorded on a TPU v5e (record_trace.py)."""
from __future__ import annotations

import glob
import os

import pytest
from jax.profiler import ProfileData

from harness import xplane
from harness.registry import Bench

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# device ops [1000, 3000] and [2000, 4000] overlap inside one execution
# of module jit__unknown(42), which the host dispatch
# PjitFunction(encode_with_crcs) at 1000 names; op [11000, 12000] runs
# outside any module, after a 7000 ns gap that the host event [5000,
# 10000] overlaps by 5000 ns
SYNTHETIC = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit__unknown(42)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "host" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "host_dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(encode_with_crcs)" } }
}
'''


def test_synthetic_trace():
    s = xplane.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    assert len(s.devices) == 1
    assert s.busy_s() == pytest.approx(4000e-9)  # [1000,4000] + [11000,12000]
    assert s.op_seconds() == pytest.approx({
        "encode_with_crcs/fusion.1": 2000e-9,
        "encode_with_crcs/copy.2": 2000e-9, "?/fusion.1": 1000e-9})
    assert s.module_seconds() == pytest.approx({"encode_with_crcs": 3000e-9})
    b = s.breakdown()
    assert b["device_ops"][2] == ["?/fusion.1", pytest.approx(1000e-9)]
    assert b["idle_gaps"] == [["host_dispatch", pytest.approx(7000e-9)]]


def _recorded():
    paths = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    if not paths:
        pytest.skip("no recorded TPU trace in benchmark/tests/data")
    return paths[0]


def test_recorded_tpu_trace():
    s = xplane.reduce(_recorded())
    assert [d.name for d in s.devices] == ["/device:TPU:0"]
    busy = s.busy_s()
    assert 0 < busy <= (s.span_ns[1] - s.span_ns[0]) / 1e9
    assert sum(s.op_seconds().values()) >= busy
    # the roofline readers find their programs by these names
    bench = Bench()
    modules = s.module_seconds()
    for metric in ("ec_encode_crc_roofline", "ec_decode_roofline"):
        programs = bench.metric_reader(metric).__globals__["PROGRAMS"]
        assert any(p in name for name in modules for p in programs), (
            metric, sorted(modules))
    assert len(s.breakdown()["idle_gaps"]) > 0
