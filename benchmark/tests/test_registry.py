"""The harness finds configurations, traffic and metrics by name: a cell
added as new files runs without an edit to any file that was there;
an unknown device kind is refused; a checkout without the program
prints no result."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import runner
from harness.registry import HERE, ROOT, Bench

NEW_METRIC = '''
"""Test metric: ops the OSDs counted per window second."""


def read(w):
    return w.delta("osd.op") / w.seconds
'''

NEW_KIND = '''
"""Test traffic kind: the write kind under another name."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "write_kind", os.path.join(os.path.dirname(__file__), "write.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)


class Traffic(_mod.Traffic):
    pass
'''


@pytest.fixture
def dropped_in(tmp_path):
    """A copy of the benchmark with one configuration, traffic kind,
    traffic mix and metric added as new files, and BENCHMARK.json
    entries naming them."""
    bench = tmp_path / "benchmark"
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "data"))
    cfg = json.load(open(bench / "configs" / "rbench-k4m2-4k.json"))
    cfg["name"] = "drop-k4m2"
    json.dump(cfg, open(bench / "configs" / "drop-k4m2.json", "w"))
    (bench / "traffic" / "write_again.py").write_text(NEW_KIND)
    mix = json.load(open(bench / "workloads" / "k4m2-4k-write.json"))
    mix["kind"] = "write_again"
    json.dump(mix, open(bench / "workloads" / "drop-cell.json", "w"))
    (bench / "metrics" / "test.osd_ops_per_s.py").write_text(NEW_METRIC)
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"].append({"name": "drop-k4m2", "source": "test",
                            "file": "benchmark/configs/drop-k4m2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "drop-cell", "config": "drop-k4m2",
                              "traffic": "drop-cell", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "test.osd_ops_per_s", "unit": "op/s",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "OSD and PG", "moves": "client_mib_s",
                              "workloads": ["drop-cell"]})
    json.dump(spec, open(tmp_path / "BENCHMARK.json", "w"))
    return Bench(root=str(tmp_path), bench_dir=str(bench))


def test_dropped_in_cell_runs(dropped_in):
    r = runner.run("drop-cell", 5, 2.0, True, rehearse=True,
                   bench=dropped_in)
    assert r["correct"], r["checks"]
    assert r["metrics"]["test.osd_ops_per_s"]["value"] > 0
    r = runner.run("drop-cell", 5, 2.0, False, rehearse=True,
                   bench=dropped_in)
    assert set(r["metrics"]) == {"client_mib_s", "op_p95_ms", "setup_s"}


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="not in benchmark/peaks.json"):
        Bench().peaks("TPU v99 imaginary")
    assert Bench().peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_tpu_no_result():
    with pytest.raises(SystemExit, match="no TPU"):
        runner.run("k8m3-4m-write", 1, 1.0, False)


def test_checkout_without_program_prints_no_result(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "k8m3-4m-write",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
