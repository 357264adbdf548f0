"""ECBatcher: the coalescing EC encode/decode dispatcher of the OSD
data path.

The TPU amortizes host<->device latency only when many stripes ride one
dispatch, but the op stream hands the daemon stripes a few at a time.
This module closes that gap NIC-interrupt-coalescing style:

- **Cross-tick adaptive coalescing.** Stripes are held up to a size
  target (``osd_ec_batch_target_stripes``) or a deadline
  (``osd_ec_batch_window`` seconds) instead of flushing every reactor
  tick. An mClock-aware fast-flush keeps latency honest: when the op
  scheduler reports nothing else queued that could contribute stripes,
  waiting out the window is pure added latency and the batch goes now.
- **One queue per event loop.** The batchers of every OSD on one
  running loop (an in-process cluster's 12 daemons) share its queues:
  a bucket gathers the small stripes of all of them into one dispatch
  instead of one each. Each queued stripe group remembers the batcher
  that submitted it (its owner), so waits, the idle probe, ``close``
  and the ``ec_batch`` fault site stay per owner, and each dispatch
  counts on one owner's perf. A process with one OSD has one owner.
- **Double buffering.** While one batch is in flight on the executor,
  the next accumulates; completion drains it immediately, so the
  in-flight time itself is the accumulation window under load. A
  queue that reaches the size target goes at once, in flight or not.
- **Fused encode+CRC.** The device path dispatches ONE program that
  returns parity cells AND the per-cell CRC32Cs of data+parity (the
  bench's fused_stacked trick in the data path) — no second host pass
  over the encoded cells. The host engine keeps its two-pass shape so
  the engine-economics probe stays apples-to-apples.
- **Batched decode.** Degraded reads, recovery and scrub repair submit
  (B, k', su) rebuild batches through the same bucket/pow2-pad
  machinery instead of one ``codec.decode`` per object; wanted parity
  rows fold into the recovery matrix host-side (one stacked matmul).
- **Mesh mode** (``osd_ec_mesh_devices`` > 1, parallel/runtime.py).
  Each bucket's staging batch is pinned device-resident under a
  (stripe, width) mesh — stripes land sharded via one device_put, the
  fused encode+CRC dispatch runs jitted UNDER the mesh so every shard
  row's cells and CRCs are produced on the chip that owns them, and
  results come back through per-device shard views
  (``shard_rows_to_host``), never a whole-array host gather
  (``runtime.STATS.host_gathers`` proves it). ``parallel_repair_mode``
  (off/allgather/psum_bits) additionally routes the decode side
  through shard_comm's distributed GF matmul: recovery partials
  combine via mesh collectives instead of messenger fan-in. Both mesh
  paths are byte-identical to the single-device dispatch and degrade
  to it when the platform cannot supply the mesh.

Buckets are keyed by a stable codec *profile* tuple, never ``id(codec)``
— a GC'd codec's address can be reused by a different one, and two
codecs from the same profile must share a bucket anyway.

Perf counters (declared by :meth:`ECBatcher.declare_counters`) record
batch occupancy, flush reason, queue wait, and failures, so the bench
can report WHY batches are the size they are — and where a dispatch's
time goes: every dispatch runs in timed worker-thread stages, each a
profiler span (utils/trace.host_span) that lands in a device trace on
the device's clock: ``ec.stage`` (pack, pow2 pad, device_put, the
jitted call returning), ``ec.device_wait`` (block_until_ready),
``ec.readback`` (device-to-host) and ``ec.unpack``; ``ec.host`` is the
host engine's whole native call. The handoff between the loop and the
worker thread is timed on ``time.perf_counter_ns`` from both sides.
"""
from __future__ import annotations

import asyncio
import os
import threading
import time
import weakref

import numpy as np

from .. import native
from ..utils import trace
from ..utils.fault import InjectedError

_FAILED = object()

#: flush reasons, each with an ``ec_flush_<reason>`` counter:
#: size      — the queued stripe count reached the target
#: deadline  — the batch window expired
#: fast      — mClock queue idle: nothing else could contribute stripes
#: tick      — per-reactor-tick flush (window disabled)
#: drain     — an in-flight batch completed and the next buffer flushed
FLUSH_REASONS = ("size", "deadline", "fast", "tick", "drain")

#: a decode/repair survivor pattern promotes from the host engine to
#: the device engine only after it has moved this many bytes through
#: the batcher — where a 0.1-1.5 s fresh-shape kernel compile (the
#: DEVICE_MIN_BYTES math in the CLAY plugin) amortizes against the
#: per-byte device advantage. A quarter-GiB of ONE erasure pattern is
#: a recovery storm rebuilding a whole OSD, not a run of degraded
#: reads: storms cross this within their first stacked rounds, while
#: the one-off patterns hedge substitution manufactures never do and
#: never pay the compile. Override: osd_ec_cold_shape_bytes (0
#: disables the shield).
COLD_SHAPE_BYTES = 256 << 20


class _StageClock:
    """Worker-thread stage times (ns) of one dispatch, flushed into the
    batcher's time_avg counters once per dispatch on the loop."""

    __slots__ = ("host", "device")

    def __init__(self) -> None:
        self.host: list[int] = []
        #: (device_wait, readback) per device-engine block
        self.device: list[tuple[int, int]] = []

    def add(self, host_ns: int, wait_ns: int | None = None,
            readback_ns: int = 0) -> None:
        self.host.append(host_ns)
        if wait_ns is not None:
            self.device.append((wait_ns, readback_ns))

    def flush(self, perf) -> None:
        if self.host:
            perf.tinc("ec_host_lat", sum(self.host) * 1e-9)
        if self.device:
            perf.tinc("ec_device_wait_lat",
                      sum(w for w, _ in self.device) * 1e-9)
            perf.tinc("ec_readback_lat",
                      sum(r for _, r in self.device) * 1e-9)


#: the stage clock of the dispatch the current worker thread runs
_worker = threading.local()


def _stage_clock() -> _StageClock:
    """This dispatch's clock (a throwaway one outside _worker_call)."""
    clock = getattr(_worker, "clock", None)
    return clock if clock is not None else _StageClock()


def _worker_call(fn, *args):
    """The executor side of one dispatch: run ``fn`` under a fresh
    stage clock; returns (result, clock, start ns, return ns)."""
    t_start = time.perf_counter_ns()
    clock = _worker.clock = _StageClock()
    try:
        out = fn(*args)
    finally:
        _worker.clock = None
    return out, clock, t_start, time.perf_counter_ns()


class _LoopQueues:
    """The dispatch queues of one event loop, shared by every ECBatcher
    that submits on it."""

    __slots__ = ("pending", "timers", "scheduled", "inflight")

    def __init__(self) -> None:
        #: bucket key -> [(codec, cells, fut, t_enqueue, owner)]
        self.pending: dict[tuple, list] = {}
        #: bucket key -> (reason, TimerHandle) for an armed flush timer
        self.timers: dict[tuple, tuple] = {}
        self.scheduled: set[tuple] = set()
        #: bucket key -> dispatches in flight (no entry when none)
        self.inflight: dict[tuple, int] = {}


#: running loop -> its queues; a finished loop's queues go with it
_QUEUES: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def _loop_queues(loop) -> _LoopQueues:
    q = _QUEUES.get(loop)
    if q is None:
        q = _QUEUES[loop] = _LoopQueues()
    return q


def codec_profile_key(codec) -> tuple:
    """Stable bucket identity of a codec: exactly the fields that
    determine its generator matrix and execution engine. ``id(codec)``
    can alias two codecs if one is GC'd and a new one reuses the
    address — the profile tuple cannot. Codecs whose geometry goes
    beyond (k, m) — bitmatrix w, Clay d, LRC layer layout — append it
    via ``profile_key_extra`` so two different codes never share a
    bucket (or a compiled plan)."""
    extra = getattr(codec, "profile_key_extra", None)
    return (
        codec.profile.get("plugin", type(codec).__name__),
        getattr(codec, "technique", ""),
        codec.k,
        codec.m,
        getattr(codec, "backend", ""),
    ) + (tuple(extra()) if extra is not None else ())


class ECBatcher:
    """Collects EC stripe work per (codec profile, cell geometry)
    bucket and runs each bucket as one batched dispatch on the engine
    the codec resolves to (device kernels, or the multithreaded C++
    host core when the accelerator link loses the measured-economics
    probe — ec/engine.py). Dispatch + readback run in a worker thread
    so the reactor keeps serving ops while batches are in flight."""

    def __init__(self, perf=None, conf=None, idle_probe=None,
                 fault=None) -> None:
        #: ops currently parked on a batcher future (queued OR riding
        #: an in-flight dispatch) — the daemon's idle probe compares
        #: this against its op-tracker to tell "everyone who could
        #: contribute stripes is already aboard" from "more coming"
        self._parked = 0
        self.perf = perf
        self.conf = conf
        #: () -> bool: True when the op scheduler has nothing queued
        #: that could contribute more stripes (mClock-aware fast flush)
        self.idle_probe = idle_probe
        #: optional FaultInjector (the owning OSD's): site "ec_batch"
        #: fails a dispatch, exercising the fail-closed isolation path
        self.fault = fault
        #: (devices, width) of the configured serving mesh, or None
        self._mesh_shape = self._configured_mesh()
        #: serving-mesh resolution state: resolved lazily on the first
        #: device-engine dispatch (jax/device init must not ride the
        #: daemon constructor) and cached; a platform that cannot
        #: supply the configured mesh raises from the dispatch
        self._mesh_resolved = False
        self._mesh_cached = None
        #: cumulative bytes dispatched per decode/repair survivor
        #: pattern — the cold-shape shield's ledger (see _cold_shape)
        self._shape_bytes: dict[tuple, int] = {}
        #: promotion state per pattern: False = device kernel compile
        #: warming in the background, True = warm (device path open)
        self._shape_warm: dict[tuple, bool] = {}

    @staticmethod
    def declare_counters(perf) -> None:
        """Declare every counter this batcher mutates (shared by the
        daemon and the unit tests so the two can never drift)."""
        perf.add_u64_counter("ec_batches", "batched EC encode dispatches")
        perf.add_histogram("ec_batch_stripes", "stripes per EC encode batch")
        perf.add_u64_counter("ec_batch_failures",
                             "EC batch dispatches that failed")
        perf.add_u64_counter("ec_batch_failures_injected",
                             "op stripe-groups failed by an INJECTED "
                             "dispatch error (fault site ec_batch)")
        perf.add_u64_counter("ec_batch_failures_dispatch",
                             "op stripe-groups failed by an organic "
                             "device/executor dispatch error")
        perf.add_u64_counter("ec_batch_isolated",
                             "stripe-groups that recovered via "
                             "per-item isolation after a batch failure")
        perf.add_u64_counter("ec_mesh_encode_dispatches",
                             "fused encode+CRC dispatches run sharded "
                             "under the device mesh")
        perf.add_u64_counter("ec_mesh_decode_dispatches",
                             "decode/repair dispatches run as mesh "
                             "collectives (parallel_repair_mode)")
        perf.add_u64_counter("ec_decode_cold_host",
                             "decode/repair rounds dispatched on the "
                             "host engine because their survivor "
                             "pattern was still cold (cold-shape "
                             "shield: a waiting read never stalls on "
                             "a fresh-kernel device compile)")
        perf.add_u64_counter("ec_decode_batches",
                             "batched EC decode dispatches")
        perf.add_histogram("ec_decode_stripes",
                           "stripes per EC decode batch")
        perf.add_histogram("ec_batch_osds",
                           "distinct OSDs whose stripes rode one "
                           "successful EC encode or decode dispatch")
        perf.add_histogram("ec_queue_wait_us",
                           "per-stripe-group wait in the batch queue (us)")
        # one sample per successful dispatch (host_lat on either
        # engine, device_wait/readback on the device engine only)
        perf.add_time_avg("ec_host_lat",
                          "dispatch host work on the worker thread: "
                          "ec.stage + ec.unpack, or the host engine's "
                          "whole native call")
        perf.add_time_avg("ec_device_wait_lat",
                          "dispatch wait for the device outputs "
                          "(ec.device_wait)")
        perf.add_time_avg("ec_readback_lat",
                          "dispatch device-to-host readback of ready "
                          "outputs (ec.readback)")
        perf.add_time_avg("ec_handoff_lat",
                          "dispatch loop<->worker handoff: executor "
                          "start delay plus the lag from the worker "
                          "returning to the loop resuming")
        for reason in FLUSH_REASONS:
            perf.add_u64_counter(f"ec_flush_{reason}",
                                 f"EC batch flushes triggered by {reason}")

    # ------------------------------------------------------------ knobs

    def _target_stripes(self) -> int:
        if self.conf is None:
            return 0
        try:
            return int(self.conf["osd_ec_batch_target_stripes"])
        except Exception:
            return 0

    def _window(self) -> float:
        if self.conf is None:
            return 0.0
        try:
            return float(self.conf["osd_ec_batch_window"])
        except Exception:
            return 0.0

    def _cold_shape_bytes(self) -> int:
        if self.conf is None:
            return COLD_SHAPE_BYTES
        try:
            return int(self.conf["osd_ec_cold_shape_bytes"])
        except Exception:
            return COLD_SHAPE_BYTES

    def _repair_mode(self) -> str:
        if self.conf is None:
            return "off"
        try:
            mode = str(self.conf["parallel_repair_mode"])
        except Exception:
            return "off"
        return mode if mode in ("allgather", "psum_bits") else "off"

    def _configured_mesh(self) -> tuple | None:
        n = w = 0
        if self.conf is not None:
            try:
                n = int(self.conf["osd_ec_mesh_devices"])
                w = int(self.conf["osd_ec_mesh_width"])
            except Exception:
                n = 0
        return (n, max(1, w)) if n > 1 else None

    def mesh(self):
        """The serving mesh this batcher stages onto, or None (single-
        device path). Resolved once from the osd_ec_mesh_* knobs via
        parallel/runtime.py — the process-level cache means every OSD
        in a test cluster shares one mesh, like chips on a host."""
        if not self._mesh_resolved:
            if self._mesh_shape is not None:
                from ..parallel import runtime

                self._mesh_cached = runtime.serving_mesh(*self._mesh_shape)
            self._mesh_resolved = True
        return self._mesh_cached

    # ------------------------------------------------------- submission

    async def encode_cells(self, codec, cells: np.ndarray):
        """(B, k, su) uint8 data cells -> (parity, crcs):
        parity (B, m, su) uint8; crcs (B, k+m) uint32 per-cell CRC32Cs
        of data+parity from the fused device dispatch, or None on the
        host engine (whose callers keep their own multithreaded CRC
        pass — the engine economics stay apples-to-apples).

        The fixed stripe_unit layout (cluster/stripe.py) means every
        caller in the cluster shares one cell shape, so stripes from
        different objects/PGs/ticks merge into ONE dispatch of ONE
        compiled kernel shape."""
        key = ("enc", codec_profile_key(codec), cells.shape[-1])
        return await self._submit(key, codec, cells)

    async def decode_cells(self, codec, present, want,
                           cells: np.ndarray) -> np.ndarray:
        """(B, k', su) uint8 surviving cells -> (B, len(want), su)
        uint8 rebuilt cells. ``present`` are the generator indices of
        the survivor rows (exactly k of them), ``want`` the generator
        indices to rebuild — parity rows fold into the recovery matrix
        host-side, so a wanted parity chunk is STILL one matmul."""
        key = ("dec", codec_profile_key(codec), cells.shape[-1],
               tuple(present), tuple(want))
        return await self._submit(key, codec, cells)

    async def repair_cells(self, codec, present, want,
                           cells: np.ndarray) -> np.ndarray:
        """Bandwidth-optimal sub-chunk repair (regenerating codes):
        (B, d, su/q) uint8 helper SLICES — each row a cell's repair
        planes — rebuild the single lost cell (B, 1, su) uint8. A
        recovery storm's stripes amortize into one stacked dispatch
        per (pattern, slice-geometry) bucket; counted with the decode
        counters (it IS the degraded path's dispatch)."""
        key = ("rep", codec_profile_key(codec), cells.shape[-1],
               tuple(present), tuple(want))
        return await self._submit(key, codec, cells)

    def parked(self) -> int:
        """Ops of this batcher's owner currently awaiting a batcher
        future (see _parked).

        Counts BOTH client encode/decode waits and background
        (recovery/scrub) decode waits — the idle probe compares this
        against the client-only op tracker, so a parked background
        decode can make the probe read "idle" one op early and settle-
        flush a slightly smaller batch. That erring direction costs a
        little occupancy, never latency, and the size/deadline triggers
        still bound both."""
        return self._parked

    @property
    def _inflight(self) -> dict:
        """Dispatches in flight per bucket on the running loop, of
        every batcher that shares its queues."""
        return _loop_queues(asyncio.get_running_loop()).inflight

    def close(self) -> None:
        """Daemon shutdown: take this batcher's queued stripes out of
        the shared queues and fail their waiters, so nothing hangs a
        caller; other batchers' stripes stay queued, and a bucket left
        empty loses its armed flush timer. In-flight executor batches
        finish on their own, this batcher's stripes in them included."""
        for q in list(_QUEUES.values()):
            for key, items in list(q.pending.items()):
                mine = [it for it in items if it[4] is self]
                if not mine:
                    continue
                rest = [it for it in items if it[4] is not self]
                if rest:
                    q.pending[key] = rest
                else:
                    del q.pending[key]
                    timer = q.timers.pop(key, None)
                    if timer is not None:
                        timer[1].cancel()
                for _, _, fut, _, _ in mine:
                    if not fut.done():
                        fut.set_result(_FAILED)

    async def _submit(self, key: tuple, codec, cells: np.ndarray):
        # cells pass through AS A VIEW (the zero-copy staging contract:
        # callers hand over ownership and never mutate after submit) —
        # the RMW path submits the (T, k, su) transpose of its
        # shard-major staging buffer, and the host engine's shard-major
        # flatten reads that same contiguous storage back without a
        # copy; forcing contiguity here would re-buy the transpose copy
        # this layout exists to kill
        loop = asyncio.get_running_loop()
        q = _loop_queues(loop)
        # where the dispatch runs is part of the bucket, so a mesh
        # batcher and a single-device one never share a batch (the
        # engine is the profile's backend, which "auto" resolves once
        # per process)
        key += (self._mesh_shape,)
        fut = loop.create_future()
        q.pending.setdefault(key, []).append(
            (codec, cells, fut, loop.time(), self))
        self._parked += 1
        try:
            self._poke(q, key)
            result = await fut
        finally:
            self._parked -= 1
        if result is _FAILED:
            raise RuntimeError("batched EC dispatch failed")
        return result

    # ---------------------------------------------------- flush policy

    @staticmethod
    def _owners(items: list) -> list:
        """The distinct owners of queued items, in queue order."""
        return list(dict.fromkeys(it[4] for it in items))

    def _full(self, items: list) -> bool:
        """The queued stripes reached the size target (0: no target)."""
        target = self._target_stripes()
        return target > 0 and sum(len(it[1]) for it in items) >= target

    def _poke(self, q: _LoopQueues, key: tuple,
              drain: bool = False) -> None:
        """Decide whether the bucket flushes now, later, or not yet."""
        queue = q.pending.get(key)
        if not queue:
            return
        if self._full(queue):
            # a full batch waits for no one: not for a batch in flight,
            # and not for the next tick, where the other OSDs' full
            # batches of the same tick would join it and every op in
            # the merged batch would wait for all of it
            self._flush(q, key, "size")
            return
        if key in q.scheduled or key in q.inflight:
            return  # double-buffer: accumulate; completion drains us
        if drain:
            self._arm_now(q, key, "drain")
            return
        window = self._window()
        if window <= 0:
            self._arm_now(q, key, "tick")
            return
        armed = q.timers.get(key)
        if all(o.idle_probe is not None and o.idle_probe()
               for o in self._owners(queue)):
            # no owner aboard has anything else queued that could
            # contribute stripes: do NOT wait out the window — but
            # settle for a few ms first, so a cohort still in client
            # transit (invisible to the op tracker until it arrives)
            # can land in the same batch (adaptive interrupt
            # coalescing, not a bare fast path). An already-armed fast
            # timer stays: re-arming on every arrival would defer the
            # flush unboundedly.
            if armed is None or armed[0] == "deadline":
                if armed is not None:
                    armed[1].cancel()
                settle = min(window * 0.1, 0.005)
                q.timers[key] = ("fast",
                                 asyncio.get_running_loop().call_later(
                                     settle, self._flush, q, key, "fast"))
            return
        if armed is None:
            q.timers[key] = ("deadline",
                             asyncio.get_running_loop().call_later(
                                 window, self._flush, q, key, "deadline"))

    def _arm_now(self, q: _LoopQueues, key: tuple, reason: str) -> None:
        """Flush on the next tick (coalesces same-tick submissions)."""
        q.scheduled.add(key)
        asyncio.get_running_loop().call_soon(self._flush, q, key, reason)

    def _flush(self, q: _LoopQueues, key: tuple, reason: str) -> None:
        q.scheduled.discard(key)
        timer = q.timers.pop(key, None)
        if timer is not None:
            timer[1].cancel()
        items = q.pending.pop(key, None)
        if not items:
            return
        if key in q.inflight and not self._full(items):
            # a deadline fired while a batch held the bucket: put the
            # work back; completion will drain it
            q.pending.setdefault(key, [])[:0] = items
            return
        q.inflight[key] = q.inflight.get(key, 0) + 1
        # the oldest stripes' owner runs the dispatch and counts it
        lead = items[0][4]
        if lead.perf is not None:
            lead.perf.inc(f"ec_flush_{reason}")
        asyncio.get_running_loop().create_task(lead._run(q, key, items))

    def _release(self, q: _LoopQueues, key: tuple) -> None:
        """A dispatch of the bucket is off the executor: drop its
        in-flight mark and drain what queued behind it."""
        n = q.inflight[key] - 1
        if n:
            q.inflight[key] = n
        else:
            del q.inflight[key]
        self._poke(q, key, drain=True)

    # ------------------------------------------------------- execution

    def _injected(self, key: tuple, stripes: int) -> bool:
        """This owner's armed ``ec_batch`` fault site fires."""
        return self.fault is not None and self.fault.hit(
            "ec_batch", kind=key[0], stripes=stripes)

    async def _dispatch_once(self, loop, key: tuple, codec,
                             cells: np.ndarray):
        """One executor dispatch of a cell batch (shared by the normal
        batched path and the per-item isolation retries); the armed
        ``ec_batch`` fault site fails it with an InjectedError."""
        if self._injected(key, len(cells)):
            raise InjectedError("injected EC batch dispatch failure")
        if key[0] == "enc":
            fn, args = self._encode_sync, (codec, cells)
        elif key[0] == "rep":
            fn, args = self._repair_sync, (codec, key[3], key[4], cells)
        else:
            fn, args = self._decode_sync, (codec, key[3], key[4], cells)
        t_sub = time.perf_counter_ns()
        out, clock, t_start, t_ret = await loop.run_in_executor(
            None, _worker_call, fn, *args)
        if self.perf is not None:
            self.perf.tinc("ec_handoff_lat",
                           (t_start - t_sub + time.perf_counter_ns()
                            - t_ret) * 1e-9)
            clock.flush(self.perf)
        return out

    def _count_cause(self, exc: BaseException) -> None:
        if self.perf is not None:
            self.perf.inc("ec_batch_failures_injected"
                          if isinstance(exc, InjectedError)
                          else "ec_batch_failures_dispatch")

    def _count_dispatch(self, kind: str, stripes: int, owners: int) -> None:
        """Throughput counters of one successful dispatch."""
        if self.perf is None:
            return
        if kind == "enc":
            self.perf.inc("ec_batches")
            self.perf.observe("ec_batch_stripes", stripes)
        else:
            self.perf.inc("ec_decode_batches")
            self.perf.observe("ec_decode_stripes", stripes)
        self.perf.observe("ec_batch_osds", owners)

    async def _fail_closed(self, loop, key: tuple, items: list,
                           batch_exc: BaseException) -> None:
        """Fail closed: a poisoned batch must fail ONLY the stripes of
        the ops that still fail alone. Each submission group is retried
        as its own dispatch of its owner, against its owner's fault
        site, so one op's bad stripes never reject its batch-mates,
        every waiter resolves exactly once, and the coalescing queue
        keeps flowing (callers never hold a PG lock across batcher
        awaits, so no lock can leak either way)."""
        for codec, cells, fut, _t0, owner in items:
            if fut.done():
                continue
            if len(items) == 1:
                # alone in the batch: the batch failure IS this op's
                owner._count_cause(batch_exc)
                fut.set_result(_FAILED)
                continue
            try:
                out = await owner._dispatch_once(loop, key, codec, cells)
            except Exception as e:
                owner._count_cause(e)
                fut.set_result(_FAILED)
                continue
            if owner.perf is not None:
                owner.perf.inc("ec_batch_isolated")
            owner._count_dispatch(key[0], len(cells), 1)
            fut.set_result(out)

    async def _run(self, q: _LoopQueues, key: tuple, items: list) -> None:
        loop = asyncio.get_running_loop()
        now = loop.time()
        for _, _, _, t0, owner in items:
            if owner.perf is not None:
                owner.perf.observe("ec_queue_wait_us",
                                   max(0.0, (now - t0) * 1e6))
        kind = key[0]
        codec = items[0][0]
        cells = (items[0][1] if len(items) == 1
                 else np.concatenate([it[1] for it in items]))
        owners = self._owners(items)
        released = False
        try:
            # the armed fault site of any owner aboard fails the batch
            # (this batcher's own fires in _dispatch_once)
            if any(o._injected(key, len(cells))
                   for o in owners if o is not self):
                raise InjectedError("injected EC batch dispatch failure")
            out = await self._dispatch_once(loop, key, codec, cells)
        except Exception as e:
            # failed dispatches are NOT throughput: count the failure
            # (split by cause per finally-failed group), never the
            # batch, and resolve every waiter exactly once — innocent
            # batch-mates recover via per-item isolation. Release the
            # bucket FIRST: fresh stripes must keep dispatching while
            # the serial isolation retries grind through the wreck —
            # and release exactly ONCE: by the time _fail_closed
            # returns, a fresh batch for this key may be in flight,
            # and a second release would drop its mark and let a third
            # _run launch concurrently.
            if self.perf is not None:
                self.perf.inc("ec_batch_failures")
            released = True
            self._release(q, key)
            await self._fail_closed(loop, key, items, e)
            return
        finally:
            if not released:
                self._release(q, key)
        # perf accounting strictly after success
        self._count_dispatch(kind, len(cells), len(owners))
        row = 0
        for _, c, fut, _, _ in items:
            b = len(c)
            if not fut.done():
                if kind == "enc":
                    parity, crcs = out
                    fut.set_result((
                        parity[row : row + b],
                        None if crcs is None else crcs[row : row + b]))
                else:
                    fut.set_result(out[row : row + b])
            row += b

    # ------------------------------------------------- sync kernels
    # (worker-thread only: both the C++ core — ctypes releases the
    # GIL — and the jax transfer/readback overlap the reactor; a
    # reactor-thread readback would stall the whole OSD for the
    # batch's copy + execution time)

    @staticmethod
    def _pow2_pad(batch: np.ndarray, mesh=None) -> np.ndarray:
        """Pad the batch axis to the jit shape-bucketing target: jit
        specializes per shape, and each fresh batch size costs a
        compile of up to seconds — pow2 bucketing caps
        that at log2(max batch) compiles (zero stripes encode/decode
        to zero cells and are sliced away by the caller). With a mesh,
        the SAME single pad also lands on a stripe-axis-divisible
        shape (parallel.pad_batch_pow2 — padding twice would
        double-pad)."""
        from ..parallel import pad_batch_pow2

        n = len(batch)
        target = pad_batch_pow2(n, mesh)
        if target == n:
            return batch
        pad = np.zeros((target - n,) + batch.shape[1:], dtype=batch.dtype)
        return np.concatenate([batch, pad])

    @staticmethod
    def _device_stages(clock: _StageClock, stage, wait, fetch, finish):
        """One device-engine dispatch in its four timed worker stages:
        ``stage()`` stages the batch and launches the program,
        ``wait`` blocks on its outputs, ``fetch`` reads them back and
        ``finish`` unpacks and slices; each step's value feeds the
        next. Each stage is a profiler span and a clock sample."""
        with trace.host_span("ec.stage") as st:
            x = stage()
        with trace.host_span("ec.device_wait") as dw:
            x = wait(x)
        with trace.host_span("ec.readback") as rb:
            x = fetch(x)
        with trace.host_span("ec.unpack") as up:
            x = finish(x)
        clock.add(st.ns + up.ns, dw.ns, rb.ns)
        return x

    def _encode_sync(self, codec, cells: np.ndarray):
        """(B, k, su) u8 -> (parity (B, m, su) u8, crcs | None)."""
        engine = getattr(codec, "resolved_backend", lambda: "device")()
        b, k, su = cells.shape
        clock = _stage_clock()
        if engine == "host" or not hasattr(codec, "encode_crc_batch"):
            with trace.host_span("ec.host") as h:
                out = self._host_encode(codec, cells)
            clock.add(h.ns)
            return out
        mesh = self.mesh()
        if mesh is not None and hasattr(codec, "encode_crc_batch_mesh"):
            return self._mesh_encode_sync(codec, cells, mesh)
        import jax

        from ..ops import rs

        return self._device_stages(
            clock,
            lambda: codec.encode_crc_batch(
                ECBatcher._pow2_pad(rs.pack_u32(cells)), su),
            jax.block_until_ready,
            lambda out: (np.asarray(out[0]), np.asarray(out[1])),
            lambda host: (rs.unpack_u32(host[0][:b]), host[1][:b]))

    @staticmethod
    def _host_encode(codec, cells: np.ndarray):
        """The host engine's encode: (parity, None)."""
        b, k, su = cells.shape
        if getattr(codec, "bytewise_linear", False):
            # GF(2^8) matrix codes: ONE multithreaded C++ matmul
            # over the shard-major flatten (reads the RMW staging
            # buffer's contiguous storage back without a copy)
            flat = np.ascontiguousarray(
                cells.transpose(1, 0, 2)).reshape(k, b * su)
            par = native.rs_encode(codec.matrix, flat,
                                   threads=os.cpu_count() or 1)
            parity = np.ascontiguousarray(
                par.reshape(codec.m, b, su).transpose(1, 0, 2))
            return parity, None
        # cellwise codecs (bitmatrix, CLAY): the plugin's own
        # vectorized host batch; CRCs stay the caller's separate
        # multithreaded pass, like every host engine
        host = getattr(codec, "encode_cells_host", None)
        if host is not None:
            return host(cells), None
        return np.stack([codec.encode_chunks(c) for c in cells]), None

    def _mesh_encode_sync(self, codec, cells: np.ndarray, mesh):
        """Device-resident shard staging: ONE pad (pow2 + stripe-
        divisible), one sharded device_put so batched stripes land on
        their owning chips, one fused encode+CRC dispatch jitted under
        the mesh — each of the k+m shard rows' cells and CRCs are
        produced where they live, and the results come back as
        per-device shard views with NO whole-array host gather."""
        from ..ops import rs
        from ..parallel import runtime

        b, k, su = cells.shape
        # the runtime stages, launches AND waits for the program under
        # its dispatch lock, so ec.stage here is the pack and pad, and
        # ec.device_wait the whole locked mesh dispatch
        out = self._device_stages(
            _stage_clock(),
            lambda: ECBatcher._pow2_pad(rs.pack_u32(cells), mesh),
            lambda batch: codec.encode_crc_batch_mesh(batch, su, mesh),
            lambda out: (runtime.shard_rows_to_host(out[0]),
                         runtime.shard_rows_to_host(out[1])),
            lambda host: (rs.unpack_u32(host[0][:b]), host[1][:b]))
        runtime.STATS.bump(encode_stripes=b)
        if self.perf is not None:
            self.perf.inc("ec_mesh_encode_dispatches")
        return out

    def _cold_shape(self, key: tuple, nbytes: int, warm) -> bool:
        """True while a decode/repair survivor pattern is still cold —
        the cold-shape shield. Device decode kernels specialize per
        (pattern, geometry): dispatching a novel pattern risks the
        0.1-1.5 s fresh-shape compile clay's DEVICE_MIN_BYTES
        documents, and a hedged read that just cut an 80 ms straggler
        wait must not spend the savings on a compile stall (hedge
        substitution is exactly what manufactures novel survivor
        patterns at client-latency-critical time). A pattern stays on
        the host engine until its cumulative bytes cross
        osd_ec_cold_shape_bytes — the volume where the compile
        amortizes — and even then the promotion runs ``warm`` (one
        device dispatch) on a background thread first, so the compile
        itself never sits on a waiting read: rounds keep landing host
        until the kernel is warm. Storm patterns (one erasure hit
        across a PG's objects) promote within a few stacked rounds;
        the one-off patterns hedging manufactures never do, and never
        pay the compile."""
        threshold = self._cold_shape_bytes()
        if threshold <= 0:
            return False
        seen = self._shape_bytes.get(key, 0)
        if seen < threshold:
            self._shape_bytes[key] = seen + nbytes
            return True
        state = self._shape_warm.get(key)
        if state is True:
            return False
        if state is None:
            self._shape_warm[key] = False

            def _warm_kernel():
                try:
                    warm()
                finally:
                    # even a failed warm opens the device path: the
                    # real dispatch will surface the error (and the
                    # shield must not pin a pattern to the host
                    # forever on a transient)
                    self._shape_warm[key] = True
            threading.Thread(target=_warm_kernel, daemon=True,
                             name="ec-shape-warm").start()
        return True

    @staticmethod
    def _timed_host(clock: _StageClock, run):
        """A host-engine block dispatcher timed as one ``ec.host``
        stage."""
        def _dispatch_block(blk: np.ndarray) -> np.ndarray:
            with trace.host_span("ec.host") as h:
                out = run(blk)
            clock.add(h.ns)
            return out
        return _dispatch_block

    def _host_decode_block(self, codec, present: tuple, want: tuple,
                           kp: int, su: int, clock: _StageClock):
        """Host-engine row-block dispatcher for decode, or None when
        the codec has no host hook."""
        if getattr(codec, "bytewise_linear", False):
            mat = codec.decode_matrix_for(present, want)

            def _matmul(blk: np.ndarray) -> np.ndarray:
                bb = len(blk)
                flat = np.ascontiguousarray(
                    blk.transpose(1, 0, 2)).reshape(kp, bb * su)
                out = native.rs_matmul(mat, flat,
                                       threads=os.cpu_count() or 1)
                return np.ascontiguousarray(
                    out.reshape(len(want), bb, su)
                    .transpose(1, 0, 2))
            return self._timed_host(clock, _matmul)
        host = getattr(codec, "decode_cells_host", None)
        if host is None:
            return None
        return self._timed_host(clock,
                                lambda blk: host(present, want, blk))

    def _host_repair_block(self, codec, present: tuple, want: tuple,
                           clock: _StageClock):
        """Host-engine row-block dispatcher for sub-chunk repair, or
        None when the codec has no host hook."""
        host = getattr(codec, "repair_cells_host", None)
        if host is None:
            return None
        return self._timed_host(clock,
                                lambda blk: host(present, want, blk))

    def _decode_sync(self, codec, present: tuple, want: tuple,
                     cells: np.ndarray) -> np.ndarray:
        """(B, k', su) u8 survivors -> (B, len(want), su) u8."""
        engine = getattr(codec, "resolved_backend", lambda: "device")()
        b, kp, su = cells.shape
        clock = _stage_clock()
        if engine == "host" or not hasattr(codec, "decode_batch"):
            _dispatch_block = self._host_decode_block(codec, present,
                                                      want, kp, su, clock)
            if _dispatch_block is None:
                raise RuntimeError(
                    f"codec {type(codec).__name__} has no batched "
                    "decode")
        else:
            mesh = self.mesh()
            mode = self._repair_mode()
            if (mesh is not None and mode != "off"
                    and hasattr(codec, "decode_batch_mesh")):
                # the collective path distributes ONE matmul across
                # every chip with its own combine, and skips the
                # cold-shape shield: mesh rounds are storm-sized
                return self._mesh_decode_sync(codec, present, want,
                                              cells, mesh, mode)
            import jax

            from ..ops import rs

            def _dispatch_block(blk: np.ndarray) -> np.ndarray:
                bb = len(blk)
                return self._device_stages(
                    clock,
                    lambda: codec.decode_batch(
                        present, ECBatcher._pow2_pad(rs.pack_u32(blk)),
                        want=want),
                    jax.block_until_ready,
                    lambda out: np.asarray(out),
                    lambda host: rs.unpack_u32(host[:bb]))
            if ((getattr(codec, "bytewise_linear", False)
                    or getattr(codec, "decode_cells_host", None)
                    is not None)
                    and self._cold_shape(
                        ("dec", codec_profile_key(codec), su,
                         present, want), cells.nbytes,
                        lambda blk=cells: _dispatch_block(blk))):
                shield = self._host_decode_block(codec, present, want,
                                                 kp, su, clock)
                if self.perf is not None:
                    self.perf.inc("ec_decode_cold_host")
                return shield(cells)
        return _dispatch_block(cells)

    def _repair_sync(self, codec, present: tuple, want: tuple,
                     cells: np.ndarray) -> np.ndarray:
        """(B, d, su/q) u8 helper slices -> (B, 1, su) u8 rebuilt
        cells — the regenerating-code sub-chunk repair dispatch
        (padded zero stripes repair to zero cells: all-linear)."""
        engine = getattr(codec, "resolved_backend", lambda: "device")()
        clock = _stage_clock()
        if engine == "host" or not hasattr(codec, "repair_batch"):
            _dispatch_block = self._host_repair_block(codec, present,
                                                      want, clock)
            if _dispatch_block is None:
                raise RuntimeError(
                    f"codec {type(codec).__name__} has no batched "
                    "sub-chunk repair")
        else:
            import jax

            from ..ops import rs

            def _dispatch_block(blk: np.ndarray) -> np.ndarray:
                bb = len(blk)
                return self._device_stages(
                    clock,
                    lambda: codec.repair_batch(
                        present, ECBatcher._pow2_pad(rs.pack_u32(blk)),
                        want),
                    jax.block_until_ready,
                    lambda out: np.asarray(out),
                    lambda host: rs.unpack_u32(host[:bb]))
            if (getattr(codec, "repair_cells_host", None) is not None
                    and self._cold_shape(
                        ("rep", codec_profile_key(codec),
                         cells.shape[-1], present, want), cells.nbytes,
                        lambda blk=cells: _dispatch_block(blk))):
                shield = self._host_repair_block(codec, present, want,
                                                 clock)
                if self.perf is not None:
                    self.perf.inc("ec_decode_cold_host")
                return shield(cells)
        return _dispatch_block(cells)

    def _mesh_decode_sync(self, codec, present: tuple, want: tuple,
                          cells: np.ndarray, mesh,
                          method: str) -> np.ndarray:
        """Collective repair: survivors staged one chunk-group per
        width device, the stacked recovery matmul distributed across
        the mesh with partials XOR-combined by ``method`` — the
        messenger-fan-in-free decode side of the serving mesh."""
        from ..ops import rs
        from ..parallel import runtime

        b, kp, su = cells.shape
        # as _mesh_encode_sync: the locked mesh dispatch is the wait
        out = self._device_stages(
            _stage_clock(),
            lambda: ECBatcher._pow2_pad(rs.pack_u32(cells), mesh),
            lambda batch: codec.decode_batch_mesh(present, batch, want,
                                                  mesh, method),
            runtime.shard_rows_to_host,
            lambda host: rs.unpack_u32(host[:b]))
        runtime.STATS.bump(decode_stripes=b)
        if self.perf is not None:
            self.perf.inc("ec_mesh_decode_dispatches")
        return out
