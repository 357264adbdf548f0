"""ecbatch.osds_per_dispatch: the mean count of OSDs whose stripes rode
one EC dispatch, read from the OSD histogram ``ec_batch_osds`` on
synthetic windows."""
from __future__ import annotations

import pytest

from harness.registry import Bench
from harness.runner import Window

NAME = "ecbatch.osds_per_dispatch"


def _window(before: dict, after: dict) -> Window:
    return Window(seconds=20.0, before={"osd": before},
                  after={"osd": after})


def _read(w: Window):
    return Bench().metric_reader(NAME)(w)


def test_mean_over_the_window_from_histogram_deltas():
    w = _window({"ec_batch_osds.sum": 40, "ec_batch_osds.count": 30},
                {"ec_batch_osds.sum": 340, "ec_batch_osds.count": 130})
    assert _read(w) == pytest.approx(3.0)


@pytest.mark.parametrize("before,after", [
    # a program without the counter (the parent of the change that
    # added it)
    ({"ec_batches": 5, "ec_batch_stripes.sum": 5},
     {"ec_batches": 9, "ec_batch_stripes.sum": 10}),
    # a window with no dispatch
    ({"ec_batch_osds.sum": 12, "ec_batch_osds.count": 7},
     {"ec_batch_osds.sum": 12, "ec_batch_osds.count": 7}),
])
def test_nothing_to_read(before, after):
    assert _read(_window(before, after)) is None


def test_declared_for_every_cell():
    spec = Bench().spec
    by_name = {m["name"]: m for m in spec["per_layer"]}
    metric = by_name[NAME]
    assert metric["workloads"] == [c["name"] for c in spec["workloads"]]
    assert metric["layer"] == by_name["ecbatch.stripes_per_dispatch"]["layer"]
    assert (metric["moves"], metric["better"], metric["source"]) == (
        "client_mib_s", "higher", "program_counter")
    assert spec["per_layer"][-1] is metric
