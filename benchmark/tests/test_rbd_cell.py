"""The RBD cell and the four-chip degraded-read cell at their rehearsal
sizes on the CPU: correct when sound, not correct with each fault the
RBD cell can have planted under the timed path; and the RBD cell's
three metric readers on synthetic windows."""
from __future__ import annotations

import pytest

import plant
import rbd_plant
from harness import runner
from harness.registry import Bench

RBD_CELL = "rbd-k4m2-4k-randwrite"
FAULTS = {**plant.FAULTS, **rbd_plant.FAULTS}
SECONDS = 2.0


def _run(cell: str, seed: int) -> dict:
    return runner.run(cell, seed, SECONDS, False, rehearse=True)


@pytest.mark.parametrize("cell", [RBD_CELL, "k8m3-4m-degraded-read-4chip"])
def test_sound_run_is_correct(cell):
    r = _run(cell, 2**31 + 13)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("fault", ["answer_altered", "rbd_parity_dropped",
                                   "rmw_old_zeroed"])
def test_planted_fault_is_caught(fault):
    k = int(Bench().cell(RBD_CELL).config["pool"]["ec_profile"]["k"])
    with FAULTS[fault](k):
        r = _run(RBD_CELL, 2**31 + 17)
    assert not r["correct"], r["checks"]


def _window(deltas: dict | None = None) -> runner.Window:
    """A window whose OSD counters moved by ``deltas``."""
    return runner.Window(seconds=20.0, before={"osd": {}},
                         after={"osd": dict(deltas or {})})


def _reader(name: str):
    return Bench().metric_reader(name)


def test_rmw_read_ms_per_client_op():
    read = _reader("pg.rmw_read_ms")
    w = _window({"op_latency.count": 400, "op_rmw_read_lat.count": 400,
                 "op_rmw_read_lat.sum": 6.0})
    assert read(w) == pytest.approx(15.0)
    assert read(_window({"op_latency.count": 400})) is None
    assert read(_window({"op_rmw_read_lat.count": 3,
                         "op_rmw_read_lat.sum": 1.0})) is None


def test_amplification_readers():
    write_amp = _reader("ec.rmw_write_amp")
    read_amp = _reader("ec.rmw_read_amp")
    w = _window({"ec_user_bytes_written": 4096 * 100,
                 "ec_shard_bytes_written": 6 * 4096 * 100,
                 "ec_rmw_read_bytes": 4 * 4096 * 100})
    assert write_amp(w) == 6.0
    assert read_amp(w) == 4.0
    fresh = _window({"ec_user_bytes_written": 4 << 20,
                     "ec_shard_bytes_written": 6 << 20})
    assert write_amp(fresh) == 1.5 and read_amp(fresh) == 0.0
    none = _window()  # no EC write, or a program without the counters
    assert write_amp(none) is None and read_amp(none) is None
