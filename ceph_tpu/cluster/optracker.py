"""OpTracker: in-flight + historic op timelines (the src/osd/
OpRequest.h / OpTracker role).

Every client op gets a TrackedOp carrying an event timeline
(queued -> dequeued -> reached_pg -> pg_locked -> rmw_read_done /
ec_done / sub_ops_done -> done, each with a timestamp); completed ops
roll into a bounded history ring. The admin socket dumps both
(`dump_ops_in_flight` / `dump_historic_ops`), and slow ops (age > warn
threshold) surface in health.

Stamps are integer ``time.time_ns()``. A stage boundary takes ONE
clock read: :meth:`TrackedOp.mark` returns the stamp it recorded, and
the caller turns it into the stage's perf counter (:func:`stage`), so
the operator's timeline and the counters the benchmark reads agree.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import time

#: the TrackedOp of the client op the running task executes (None
#: outside client ops). asyncio tasks inherit it, so the PG's stage
#: timers find their op without threading it through every call;
#: daemon background tasks start with it cleared (OSDLite.spawn)
current: contextvars.ContextVar = contextvars.ContextVar(
    "ceph_tpu_tracked_op", default=None)


class TrackedOp:
    __slots__ = ("seq", "desc", "start_ns", "events", "done_ns")

    def __init__(self, seq: int, desc: str, start_ns: int | None = None):
        self.seq = seq
        self.desc = desc
        self.start_ns = time.time_ns() if start_ns is None else start_ns
        self.events: list[tuple[int, str]] = [(self.start_ns, "queued")]
        self.done_ns: int | None = None

    def mark(self, event: str) -> int:
        """Record ``event`` now; returns the stamp (ns) it recorded."""
        t = time.time_ns()
        self.events.append((t, event))
        return t

    @property
    def age(self) -> float:
        return ((self.done_ns or time.time_ns()) - self.start_ns) / 1e9

    def dump(self) -> dict:
        return {
            "seq": self.seq,
            "description": self.desc,
            "age": round(self.age, 6),
            "duration": (round((self.done_ns - self.start_ns) / 1e9, 6)
                         if self.done_ns else None),
            "events": [
                {"time": t / 1e9, "event": e} for t, e in self.events
            ],
        }


@contextlib.contextmanager
def stage(perf, key: str, event: str):
    """Time one stage of the current client op: one clock read on
    entry, the ``event`` mark on success (a failed stage is timed but
    not marked), the interval ``tinc``'d into ``key``. Outside a client
    op, or after it finished (a task that outlived it), a no-op."""
    op = current.get()
    if op is None or op.done_ns is not None:
        yield
        return
    t0 = time.time_ns()
    ok = False
    try:
        yield
        ok = True
    finally:
        t1 = op.mark(event) if ok else time.time_ns()
        perf.tinc(key, (t1 - t0) * 1e-9)


class OpTracker:
    def __init__(self, history_size: int = 256,
                 slow_op_warn_secs: float = 5.0):
        self._seq = itertools.count(1)
        self.in_flight: dict[int, TrackedOp] = {}
        self.history: collections.deque[TrackedOp] = collections.deque(
            maxlen=history_size
        )
        self.slow_op_warn_secs = slow_op_warn_secs

    def create(self, desc: str, start_ns: int | None = None) -> TrackedOp:
        """Track a new op; ``start_ns`` backdates its "queued" stamp to
        the message's arrival when the caller took it earlier."""
        op = TrackedOp(next(self._seq), desc, start_ns)
        self.in_flight[op.seq] = op
        return op

    def finish(self, op: TrackedOp) -> None:
        op.done_ns = op.mark("done")
        self.in_flight.pop(op.seq, None)
        self.history.append(op)

    # ------------------------------------------------------------- dumps

    def dump_ops_in_flight(self) -> dict:
        ops = sorted(self.in_flight.values(), key=lambda o: o.seq)
        return {"num_ops": len(ops), "ops": [o.dump() for o in ops]}

    def dump_historic_ops(self, limit: int = 20) -> dict:
        ops = list(self.history)[-limit:]
        return {"num_ops": len(ops), "ops": [o.dump() for o in ops]}

    def slow_ops(self) -> list[TrackedOp]:
        now = time.time_ns()
        warn = self.slow_op_warn_secs * 1e9
        return [o for o in self.in_flight.values()
                if now - o.start_ns > warn]
