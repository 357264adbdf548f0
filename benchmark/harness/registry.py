"""Finds what a cell is made of, by the names in BENCHMARK.json.

Every configuration, traffic mix, traffic kind and per-layer metric is a
file of its own, found by name, so a later PR adds a cell or a metric by
adding files and never edits one:

- configuration  ``configs/<config>.json``
- traffic mix    ``workloads/<cell>.json`` (names its ``kind``)
- traffic kind   ``traffic/<kind>.py`` (defines ``Traffic``)
- metric         ``metrics/<metric>.py`` (defines ``read(window)``)
- peaks          ``peaks.json``, keyed by JAX's ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

#: the benchmark's own directory (benchmark/)
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the checkout's root, where BENCHMARK.json and the program live
ROOT = os.path.dirname(HERE)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, label: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{label}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{label.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with what it names, loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


class Bench:
    def __init__(self, root: str = ROOT, bench_dir: str = HERE):
        self.root = root
        self.dir = bench_dir
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))

    def _path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def cell(self, name: str) -> Cell:
        entries = {w["name"]: w for w in self.spec["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(entries)})")
        w = entries[name]
        config = _load_json(self._path("configs", f"{w['config']}.json"))
        traffic = _load_json(self._path("workloads", f"{name}.json"))

        def mine(metric: dict) -> bool:
            return name in metric.get("workloads", [name])

        return Cell(
            name=name, chips=int(w["chips"]), config=config,
            traffic=traffic,
            end_to_end=[m for m in self.spec["end_to_end"] if mine(m)],
            per_layer=[m for m in self.spec["per_layer"] if mine(m)])

    def traffic_kind(self, kind: str):
        """The generator class of one traffic kind."""
        return _load_module(self._path("traffic", f"{kind}.py"),
                            f"traffic.{kind}").Traffic

    def metric_reader(self, name: str):
        return _load_module(self._path("metrics", f"{name}.py"),
                            f"metrics.{name}").read

    def peaks(self, device_kind: str) -> dict:
        """The device's published peaks; an unknown kind is an error."""
        table = _load_json(self._path("peaks.json"))["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"benchmark/peaks.json (have {sorted(table)}); "
                           "add it with its source")
        return table[device_kind]
