"""Distributed tracing: spans with cross-daemon context propagation
(the reference's blkin/Zipkin + opentelemetry tracer roles,
src/common/tracer.h:18, ECBackend.cc:831-858 pg_trace threading).

A Span is (trace_id, span_id, parent_id, service, name, start,
duration, tags); the (trace_id, span_id) pair is the propagated
context — it rides op messages as a u64 pair exactly the way the
reference threads `pg_trace` through EC sub-ops. Each daemon owns a
Tracer (a bounded ring of finished spans, dumpable over its admin
socket as `dump_tracing`); an in-process registry lets tests and the
exporter assemble the full tree the way a Zipkin collector would.

Zero-config: tracing is always on with a bounded ring (finished spans
only), matching the OpTracker stance. A span costs one object, two
``time.time_ns()`` reads, one lock-free id per span (two for a root)
and one deque append; tags are stored as given and formatted only by
``dump()``, so nothing is decoded or stringified on the op path.

Stamps are integer ``time.time_ns()``: the wall clock the profiler
stamps its host events with, so a ring span lines up with a device
trace recorded over the same stretch.

:func:`host_span` is the other half: a leaf span for thread-synchronous
work (the EC dispatch stages) that goes to the profiler as a TraceMe
when JAX is loaded — landing in the ``.xplane.pb`` beside the device's
own events — and times the same interval for the caller's counter. Op-
lifetime spans stay ring-only: a span covering a whole op would
overlap every idle gap in a device trace and hide the stage that
actually ran there.
"""
from __future__ import annotations

import collections
import contextvars
import itertools
import os
import sys
import time

#: span ids: a random per-process high half and a counter low half —
#: unique across threads without a lock (itertools.count's next() is
#: atomic under the GIL) and without a clock read
_ID_PREFIX = (int.from_bytes(os.urandom(4), "little") | 1) << 32
_seq = itertools.count(1)

#: ambient span context for the executing op (asyncio tasks inherit it,
#: so sub-op constructors deep in the PG pick up the op's span without
#: threading it through every call — the pg_trace member role)
current = contextvars.ContextVar("ceph_tpu_trace_ctx", default=(0, 0))


def _new_id() -> int:
    return _ID_PREFIX | (next(_seq) & 0xFFFFFFFF)


NO_CTX = (0, 0)  # wire value for "not traced"


def _fmt_tag(value) -> str:
    if isinstance(value, (bytes, bytearray, memoryview)):
        return bytes(value[:64]).decode(errors="replace")
    return str(value)


class Span:
    __slots__ = ("trace_id", "span_id", "parent_id", "service", "name",
                 "start_ns", "duration_ns", "tags", "_tracer")

    def __init__(self, tracer: "Tracer", trace_id: int, parent_id: int,
                 name: str):
        self._tracer = tracer
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.service = tracer.service
        self.name = name
        self.start_ns = time.time_ns()
        self.duration_ns: int | None = None
        self.tags: dict = {}

    @property
    def ctx(self) -> tuple[int, int]:
        """Wire context to put on an outgoing message."""
        return (self.trace_id, self.span_id)

    def tag(self, key: str, value) -> "Span":
        """Attach a tag; the value is kept as given (bytes oids too)
        and formatted by dump()."""
        self.tags[key] = value
        return self

    def finish(self) -> None:
        if self.duration_ns is None:
            self.duration_ns = time.time_ns() - self.start_ns
            self._tracer._record(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.tag("error", exc_type.__name__)
        self.finish()

    def dump(self) -> dict:
        return {
            "traceId": f"{self.trace_id:016x}",
            "id": f"{self.span_id:016x}",
            "parentId": (f"{self.parent_id:016x}"
                         if self.parent_id else None),
            "localEndpoint": {"serviceName": self.service},
            "name": self.name,
            "timestamp": self.start_ns // 1000,  # zipkin micros
            "duration": (self.duration_ns or 0) // 1000,
            "tags": {k: _fmt_tag(v) for k, v in self.tags.items()},
        }


class Tracer:
    def __init__(self, service: str, ring_size: int = 512):
        self.service = service
        self._ring: collections.deque[Span] = collections.deque(
            maxlen=ring_size)
        _REGISTRY[service] = self

    def start_span(self, name: str,
                   parent: tuple[int, int] | Span | None = None) -> Span:
        """New span; parent is a wire ctx, a local Span, or None (root).
        A NO_CTX wire parent starts a fresh trace."""
        if isinstance(parent, Span):
            ctx = parent.ctx
        elif parent is None or tuple(parent) == NO_CTX:
            ctx = (_new_id(), 0)
        else:
            ctx = tuple(parent)
        return Span(self, ctx[0], ctx[1], name)

    def _record(self, span: Span) -> None:
        self._ring.append(span)

    def dump(self, trace_id: int | None = None, limit: int = 200) -> list:
        if limit <= 0:
            return []
        spans = [s for s in self._ring
                 if trace_id is None or s.trace_id == trace_id]
        return [s.dump() for s in spans[-limit:]]


#: in-process collector view: service -> Tracer (tests / exporter)
_REGISTRY: dict[str, Tracer] = {}


def get_tracer(service: str) -> Tracer:
    t = _REGISTRY.get(service)
    if t is None:
        t = Tracer(service)
    return t


def dump_all(trace_id: int | None = None) -> list:
    """Collector view across every in-process service."""
    out = []
    for svc in sorted(_REGISTRY):
        out.extend(_REGISTRY[svc].dump(trace_id))
    return out


# --------------------------------------------------------- host spans

class _NullAnnotation:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL = _NullAnnotation()


def _profiler_annotation(name: str):
    """A profiler TraceMe when JAX is loaded (importing JAX for a span
    would put its start-up on a daemon that never touches the chip)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name)


class host_span:
    """``with host_span("ec.readback") as sp:`` — a leaf span of
    thread-synchronous work, emitted to the profiler as a TraceMe (a
    no-op object when no profiler session is active) and timed on two
    ``time.perf_counter_ns()`` reads: ``sp.ns`` after exit, for the
    caller's counter. About 1.3 us a span on a TPU v5e host, profiler
    on or off.

    Leaf spans only: never wrap a span around awaited or multi-stage
    work, which would overlap the stages beneath it in the trace."""

    __slots__ = ("_ann", "_t0", "ns")

    def __init__(self, name: str):
        self._ann = _profiler_annotation(name)
        self.ns = 0

    def __enter__(self) -> "host_span":
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
