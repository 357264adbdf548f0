"""Reduction of one profiler trace (``.xplane.pb``) to device numbers.

The JAX profiler writes one XSpace per session: a plane per device
(``/device:TPU:<n>``) whose ``XLA Ops`` line holds one event per
operation that ran, and ``XLA Modules`` one per program execution; and
host planes whose lines hold the host's TraceMe annotations. From these:

- busy: the union of the operation intervals of each device;
- programs: device seconds per program. The device names a program by
  its XLA module (``jit__unknown(<id>)`` for the program's jitted
  partials), so each module id takes the name of the host dispatch
  (``PjitFunction(<name>)``) that starts nearest its executions, by
  majority over them; the host and device clocks differ by a fraction
  of a millisecond, dispatches by milliseconds;
- ops: device seconds per ``<program>/<op>``;
- gaps: the idle intervals between busy stretches, each labelled by
  the host annotation that overlaps it most ("unattributed" if none).

Times are the trace's own nanoseconds; only differences are used.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import Counter
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNATTRIBUTED = "unattributed"
DISPATCH = "PjitFunction("


@dataclass
class Device:
    name: str
    busy_ns: float = 0.0
    #: merged busy intervals, sorted
    intervals: list = field(default_factory=list)
    op_ns: dict = field(default_factory=dict)
    module_ns: dict = field(default_factory=dict)


@dataclass
class Summary:
    devices: list
    #: (start, end) of every idle gap between busy stretches, all devices
    gaps: list
    #: (start, end, name) of every host event
    host_events: list
    #: [first, last] event time over every plane: the traced span
    span_ns: tuple

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def op_seconds(self) -> dict:
        out: dict = {}
        for d in self.devices:
            for k, v in d.op_ns.items():
                out[k] = out.get(k, 0.0) + v / 1e9
        return out

    def module_seconds(self) -> dict:
        out: dict = {}
        for d in self.devices:
            for k, v in d.module_ns.items():
                out[k] = out.get(k, 0.0) + v / 1e9
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[_label(s, e, self.host_events), (e - s) / 1e9]
                              for s, e in gaps]}


def _merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def find_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def reduce(path: str) -> Summary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def _op_name(hlo_text: str) -> str:
    """``%fusion.2 = (...) fusion(...), ...`` -> ``fusion.2``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def reduce_profile(pd) -> Summary:
    """Reduce a ``jax.profiler.ProfileData``."""
    host_events: list = []
    planes: list = []  # (name, ops [(s, e, name)], modules [(s, e, name)])
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        device = _is_device(plane.name)
        ops: list = []
        modules: list = []
        for line in plane.lines:
            for ev in line.events:
                s, e = float(ev.start_ns), float(ev.end_ns)
                lo, hi = min(lo, s), max(hi, e)
                if not device:
                    host_events.append((s, e, ev.name))
                elif line.name == OPS_LINE:
                    ops.append((s, e, ev.name))
                elif line.name == MODULES_LINE:
                    modules.append((s, e, ev.name))
        if device and ops:
            planes.append((plane.name, ops, sorted(modules)))
    label = _module_labels(planes, host_events)
    devices: list[Device] = []
    for name, ops, modules in planes:
        dev = Device(name)
        for s, e, m in modules:
            k = label.get(m, m)
            dev.module_ns[k] = dev.module_ns.get(k, 0.0) + e - s
        starts = [s for s, _, _ in modules]
        for s, e, op in ops:
            i = bisect.bisect_right(starts, s) - 1
            prog = (label.get(modules[i][2], modules[i][2])
                    if i >= 0 and s < modules[i][1] else "?")
            k = f"{prog}/{_op_name(op)}"
            dev.op_ns[k] = dev.op_ns.get(k, 0.0) + e - s
        dev.intervals = _merge([(s, e) for s, e, _ in ops])
        dev.busy_ns = sum(e - s for s, e in dev.intervals)
        devices.append(dev)
    gaps = []
    for dev in devices:
        iv = dev.intervals
        for (_, e0), (s1, _) in zip(iv, iv[1:]):
            gaps.append((e0, s1))
    return Summary(devices=devices, gaps=gaps, host_events=host_events,
                   span_ns=(lo, hi) if devices else (0.0, 0.0))


def _module_labels(planes: list, host_events: list) -> dict:
    """XLA module name -> the host dispatch name nearest its starts."""
    dispatch = sorted((s, name[len(DISPATCH):-1]) for s, _, name in host_events
                      if name.startswith(DISPATCH) and name.endswith(")"))
    if not dispatch:
        return {}
    starts = [s for s, _ in dispatch]
    votes: dict = {}
    for _, _, modules in planes:
        for s, _, m in modules:
            i = bisect.bisect_left(starts, s)
            near = min((j for j in (i - 1, i) if 0 <= j < len(starts)),
                       key=lambda j: abs(starts[j] - s))
            votes.setdefault(m, Counter())[dispatch[near][1]] += 1
    return {m: c.most_common(1)[0][0] for m, c in votes.items()}


def _label(s: float, e: float, host_events: list) -> str:
    """The host annotation overlapping [s, e] the most."""
    best, best_ov = UNATTRIBUTED, 0.0
    for hs, he, name in host_events:
        ov = min(e, he) - max(s, hs)
        if ov > best_ov:
            best, best_ov = name, ov
    return best
