"""Each cell, run at its rehearsal size on the CPU with the harness's
look for a chip skipped, comes out correct when sound and not correct
with each fault the cell can have planted under the timed path."""
from __future__ import annotations

import pytest

import plant
from harness import runner
from harness.registry import Bench

ONE_CHIP_FAULTS = ["parity_dropped", "state_unchanged", "half_batch",
                   "answer_altered"]
CASES = (
    [("k8m3-4m-write", f) for f in ONE_CHIP_FAULTS]
    + [("k4m2-4k-write", f) for f in ONE_CHIP_FAULTS]
    + [("k8m3-4m-degraded-read", f) for f in ONE_CHIP_FAULTS]
    + [("k8m3-4m-write-4chip", f)
       for f in ONE_CHIP_FAULTS + ["exchange_left_out"]])
SECONDS = 2.0


def _run(cell: str, seed: int) -> dict:
    return runner.run(cell, seed, SECONDS, False, rehearse=True)


def _k(cell: str) -> int:
    return int(Bench().cell(cell).config["pool"]["ec_profile"]["k"])


@pytest.mark.parametrize("cell", sorted({c for c, _ in CASES}))
def test_sound_run_is_correct(cell):
    r = _run(cell, 2**31 + 7)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("cell,fault", CASES)
def test_planted_fault_is_caught(cell, fault):
    with plant.FAULTS[fault](_k(cell)):
        r = _run(cell, 2**31 + 11)
    assert not r["correct"], r["checks"]
