"""One run of one cell: set-up, the measured window, the checks.

Process model: this process owns the chip(s). It builds the in-process
cluster (mon, OSDs and client on one asyncio loop), lets the cell's
traffic kind warm its shapes and prepare its data, then drives a closed
loop of ``concurrency`` callers for ``seconds``, each waiting for its
reply before it sends the next op. After the window it reads the
device memory peak, collects what the window produced, stops the
cluster and compares that with the plain reference.
"""
from __future__ import annotations

import asyncio
import json
import math
import os
import resource
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

from . import xplane
from .cluster import Cluster, device_memory_peak, process_cpu_s
from .registry import Bench

#: fires for every program JAX builds, whether XLA compiles it or the
#: persistent cache supplies it; CACHE_HIT fires for the latter
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
#: the traced stretch starts this far into the window and lasts at most
#: TRACE_MAX_S, leaving a second of window after it
TRACE_DELAY_S = 1.0
TRACE_MAX_S = 4.0
#: a failed op's latency: it missed every limit
FAILED_LATENCY_MS = 1e9


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Compiles:
    """Programs JAX built, from its monitoring hooks: how many, their
    seconds, how many the persistent cache supplied, and their names."""

    def __init__(self) -> None:
        self.n = 0
        self.s = 0.0
        self.hits = 0
        self.names: list[str] = []

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1
            self.s += duration
            self.names.append(str(kw.get("fun_name", "?")))

    def on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.hits += 1

    def mark(self) -> tuple:
        return (self.n, self.hits, len(self.names))

    def since(self, mark: tuple) -> str:
        n, hits, i = mark
        return (f"{self.n - n} programs built ({self.hits - hits} from the "
                f"persistent cache) {sorted(set(self.names[i:]))}")


@dataclass
class Op:
    t_sub: float
    t_done: float = 0.0
    nbytes: int = 0
    ok: bool = False


@dataclass
class Window:
    """What a per-layer metric reads: counter deltas over the window and
    over the traced stretch, the trace's reduction, the device's peaks,
    and the cell's own numbers."""
    seconds: float
    before: dict
    after: dict
    trace: xplane.Summary | None = None
    trace_before: dict | None = None
    trace_after: dict | None = None
    trace_s: float = 0.0
    peaks: dict = field(default_factory=dict)
    #: numbers of the cell's geometry and traffic (k, m, su, ...)
    cell: dict = field(default_factory=dict)

    def delta(self, key: str, span: str = "window") -> float:
        a, b = ((self.before, self.after) if span == "window"
                else (self.trace_before, self.trace_after))
        return _get(b, key) - _get(a, key)


def _snapshot(cluster: Cluster) -> dict:
    """The program's counters and this process's CPU seconds."""
    return {**cluster.snapshot(), "cpu_s": process_cpu_s()}


def _get(snap: dict, key: str) -> float:
    if key.startswith("osd."):
        return float(snap["osd"].get(key[4:], 0))
    return float(snap.get(key, 0))


def percentile(values: list[float], p: float) -> float:
    """Nearest-rank percentile."""
    vs = sorted(values)
    return vs[max(0, math.ceil(p / 100 * len(vs)) - 1)]


async def _window(cluster: Cluster, traffic, seconds: float,
                  trace_dir: str | None, compiles: Compiles) -> dict:
    loop = asyncio.get_running_loop()
    ops: list[Op] = []
    state = {"next": 0}
    t0 = loop.time()
    deadline = t0 + seconds
    snaps: dict = {"before": _snapshot(cluster),
                   "compiles0": compiles.mark()}

    def close_window() -> None:
        snaps["after"] = _snapshot(cluster)
        snaps["compiles"] = compiles.since(snaps["compiles0"])

    loop.call_at(deadline, close_window)

    async def caller() -> None:
        while loop.time() < deadline:
            j = state["next"]
            state["next"] += 1
            op = Op(t_sub=loop.time())
            ops.append(op)
            try:
                op.nbytes = await traffic.op(j)
                op.ok = True
            except Exception as e:  # an op that fails is counted, not fatal
                traffic.failures.append(f"op {j}: {type(e).__name__}: {e}")
            op.t_done = loop.time()

    async def tracer() -> None:
        import jax

        await asyncio.sleep(TRACE_DELAY_S)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        span = max(0.5, min(TRACE_MAX_S, seconds - TRACE_DELAY_S - 1.0))
        t = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_on = time.perf_counter()
        snaps["trace_before"] = _snapshot(cluster)
        await asyncio.sleep(span)
        snaps["trace_after"] = _snapshot(cluster)
        t_off = time.perf_counter()
        snaps["trace_s"] = t_off - t_on
        # off the loop: writing the trace takes a while, and the OSDs'
        # sub-op timers keep running
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        snaps["trace_cost"] = (t_on - t, time.perf_counter() - t_off)

    tasks = [loop.create_task(caller())
             for _ in range(int(traffic.concurrency))]
    if trace_dir is not None:
        tasks.append(loop.create_task(tracer()))
    results = await asyncio.gather(*tasks, return_exceptions=True)
    for r in results:
        if isinstance(r, BaseException):
            raise r
    snaps.update(ops=ops, deadline=deadline)
    return snaps


async def _run(cell, seed: int, seconds: float, trace: bool,
               rehearse: bool, bench: Bench, compiles: Compiles,
               t_start: float) -> dict:
    cluster = Cluster(cell.config, rehearse)
    params = dict(cell.traffic)
    if rehearse:
        params.update(params.pop("rehearsal", {}))
    params.pop("rehearsal", None)
    traffic = bench.traffic_kind(params["kind"])(cluster, params, seed)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        await cluster.start()
        await traffic.setup()
        setup_s = time.perf_counter() - t_start
        log(f"set-up: {setup_s} s; {compiles.since((0, 0, 0))}, "
            f"{compiles.s} s")
        snaps = await _window(cluster, traffic, seconds, trace_dir, compiles)
        memory_peak = device_memory_peak()
        collected = await traffic.collect()
    finally:
        await cluster.stop()
    try:
        checks = traffic.compare(collected)
        summary = None
        if trace_dir is not None:
            summary = xplane.reduce(xplane.find_trace(trace_dir))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return dict(snaps=snaps, setup_s=setup_s, memory_peak=memory_peak,
                checks=checks, summary=summary, traffic=traffic)


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, t_start: float | None = None,
        bench: Bench | None = None) -> dict:
    """One run; returns the result object the last line prints."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    bench = bench or Bench()
    cell = bench.cell(cell_name)
    devs = jax.devices()
    platform = devs[0].platform
    if not rehearse and platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {platform!r} ({len(devs)} "
                         "device(s)); the benchmark runs on the chip only")
    if len(devs) < cell.chips:
        raise SystemExit(f"{cell_name} needs {cell.chips} chips, JAX "
                         f"found {len(devs)}")
    kind = devs[0].device_kind
    peaks = {} if rehearse else bench.peaks(kind)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles.on_duration)
    jax.monitoring.register_event_listener(compiles.on_event)
    log(f"device: platform {platform}, kind {kind}, count {len(devs)}; "
        f"cell {cell_name}, seed {seed}, seconds {seconds}, trace "
        f"{int(trace)}, rehearse {int(rehearse)}")

    r = asyncio.run(_run(cell, seed, seconds, trace, rehearse, bench,
                         compiles, t_start))
    snaps, traffic = r["snaps"], r["traffic"]
    ops: list[Op] = snaps["ops"]
    deadline = snaps["deadline"]
    done_bytes = sum(o.nbytes for o in ops if o.ok and o.t_done <= deadline)
    lat = [(o.t_done - o.t_sub) * 1e3 if o.ok else FAILED_LATENCY_MS
           for o in ops]
    failed = sum(1 for o in ops if not o.ok)
    cpu_s = snaps["after"]["cpu_s"] - snaps["before"]["cpu_s"]
    log(f"window: {len(ops)} ops submitted, {failed} failed, "
        f"{sum(1 for o in ops if o.ok and o.t_done <= deadline)} "
        f"completed inside it; inside it {snaps['compiles']}; "
        f"host cpu {cpu_s} s")
    for line in traffic.notes(snaps):
        log(line)
    for msg in traffic.failures[:5]:
        log(f"failed: {msg}")
    log(f"host: peak RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}"
        f" KiB, {os.cpu_count()} cpus")
    win = Window(seconds=seconds, before=snaps["before"],
                 after=snaps["after"], peaks=peaks,
                 cell=traffic.geometry())
    if trace:
        win.trace = r["summary"]
        win.trace_before = snaps["trace_before"]
        win.trace_after = snaps["trace_after"]
        win.trace_s = snaps["trace_s"]

    checks = dict(r["checks"])
    checks["failed_ops"] = (failed, 0)
    correct = all(v <= lim for v, lim in checks.values())
    device = {"platform": platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": r["memory_peak"]}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = bench.metric_reader(m["name"])(win)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = win.trace.busy_s()
        device["window_s"] = win.trace_s
        log(f"trace: {len(win.trace.devices)} device plane(s) ran ops; "
            f"busy {device['busy_s']} s of {win.trace_s} s; start_trace "
            f"{snaps['trace_cost'][0]} s, stop_trace {snaps['trace_cost'][1]} s"
            f"; programs {win.trace.module_seconds()}")
    else:
        e2e = {"client_mib_s": done_bytes / seconds / 2**20,
               "op_p95_ms": percentile(lat, 95) if lat else
               FAILED_LATENCY_MS,
               "setup_s": r["setup_s"]}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": correct, "attempted": len(ops), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = win.trace.breakdown()
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="One run of one benchmark cell (BENCHMARK.json).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on JAX's CPU backend; prints "
                         "platform cpu. Never a measurement.")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        setup_cache()
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 rehearse=args.rehearse_cpu, t_start=t_start)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


def setup_cache() -> str:
    """JAX's persistent compile cache in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), storing every program however
    small or quick to compile."""
    import jax

    from ceph_tpu.utils import compile_cache

    d = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"compile cache: {d}")
    return d
