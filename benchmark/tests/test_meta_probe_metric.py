"""pg.meta_probe_pct: the share of EC existence decisions that took a
metadata probe, read from the OSD counters ``ec_meta_probe`` and
``ec_meta_local`` on synthetic windows."""
from __future__ import annotations

import pytest

from harness.registry import Bench
from harness.runner import Window

NAME = "pg.meta_probe_pct"


def _window(before: dict, after: dict) -> Window:
    return Window(seconds=20.0, before={"osd": before},
                  after={"osd": after})


def _read(w: Window):
    return Bench().metric_reader(NAME)(w)


def test_share_of_probes_over_the_window():
    w = _window({"ec_meta_probe": 7, "ec_meta_local": 10},
                {"ec_meta_probe": 8, "ec_meta_local": 13})
    assert _read(w) == pytest.approx(25.0)


@pytest.mark.parametrize("before,after", [
    # a program without the counters (the parent of the change that
    # added them)
    ({"op_latency.count": 1}, {"op_latency.count": 9}),
    # a window that decided no existence: only held objects touched
    ({"ec_meta_probe": 3, "ec_meta_local": 5},
     {"ec_meta_probe": 3, "ec_meta_local": 5}),
])
def test_nothing_to_read(before, after):
    assert _read(_window(before, after)) is None


def test_declared_for_the_write_cells():
    spec = Bench().spec
    by_name = {m["name"]: m for m in spec["per_layer"]}
    writes = [c["name"] for c in spec["workloads"]
              if c["traffic"].endswith("write")
              or c["traffic"].endswith("write-4chip")]
    assert by_name[NAME]["workloads"] == writes
    assert by_name[NAME]["layer"] == by_name["pg.subop_wait_ms"]["layer"]
