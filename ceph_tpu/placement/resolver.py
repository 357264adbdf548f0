"""PlacementResolver: the batched placement service of the serving
plane.

Every client op needs (up, acting, primary) for its pgid, and the
round-9 config-6 profile attributes a measurable slice of per-op Python
dispatch to recomputing host straw2 for it.  Within an epoch CRUSH is a
pure function of the map, so the resolver memoizes results EPOCH-KEYED
(one dict hit per op in steady state, invalidated wholesale the instant
the map moves) and resolves misses through the device bulk-CRUSH engine
(placement/bulk.py, the north-star config-5 kernel; its device rate is
not measured on this code) in coalesced batches behind the
same window/size trigger discipline the ECBatcher uses: misses arriving
within ``client_placement_batch_window`` seconds — or until
``client_placement_batch_target`` pgids are queued — ride ONE device
dispatch instead of N host descents.

Placement is never a liveness dependency:

- the sync surface (``up_acting``/``full``) serves hits from the memo
  and misses from the host pipeline immediately — it is the drop-in
  replacement for the old ``PlacementMemo`` and what daemons use;
- the async surface parks misses on the coalescing window, but an
  unsupported map shape (``CompiledMap`` rejects it), an epoch that
  moved mid-dispatch, or a batch below ``client_placement_batch_min``
  (a cold jit compile would cost more than it saves, the
  DEVICE_MIN_BYTES stance) serves the affected waiters from the host
  pipeline. A device that fails a dispatch is an error for exactly
  those waiters, never a quiet switch to the host;
- ``CEPH_TPU_PLACEMENT_BATCH=0`` is the A/B lever: the async surface
  becomes pure memo+host, so a bench pair attributes the win.

Device rows feed ``OSDMap.raw_to_up_acting`` — the SAME post-CRUSH
host code (upmap, up-filter, affinity, pg_temp) the per-pg path runs,
so batched results are bit-identical by construction (and asserted in
tests/test_placement_resolver.py).

Counters (``stats``): placement_cache_hits / placement_cache_misses /
placement_batch_lookups (device dispatches) / placement_batched_pgids /
placement_host_resolves / placement_epoch_invalidations — the evidence
bench configs 6 and 10 report.
"""
from __future__ import annotations

import asyncio
import os

import numpy as np

from . import crushmap as cm

#: the device engine (placement/bulk.py) imports jax; daemons and
#: clients import THIS module at boot, and most processes (tests,
#: tools, every mon/osd subprocess) never dispatch a batch — so the
#: engine loads lazily on the first actual compile, not at import
#: (a ~1 s jax import on every daemon boot measurably slowed the
#: multiprocess suite and is exactly the stall the mon-quorum flake
#: lives on)
bulk = None


def _load_bulk():
    global bulk
    if bulk is None:
        from . import bulk as _bulk
        bulk = _bulk
    return bulk

def _batch_enabled() -> bool:
    return os.environ.get("CEPH_TPU_PLACEMENT_BATCH", "1") != "0"


class _MapCompile:
    """Per-CrushMap compile cache entry. Holds a strong reference to
    the CrushMap so an id() can never alias a GC'd map, and remembers
    a rejection (unsupported shape) so it is not re-attempted."""

    __slots__ = ("crush", "compiled", "rejected", "warm", "warming",
                 "cold_seen", "failed")

    def __init__(self, crush):
        self.crush = crush
        self.compiled: bulk.CompiledMap | None = None
        self.rejected = False
        #: (ruleno, numrep, padded-batch-len) combos whose jit IS warm:
        #: only these dispatch on the op path — a cold combo's first
        #: compile (~1 s on the CPU stand-in) must never stall parked
        #: ops, so cold flushes host-serve and warm in the background
        self.warm: set[tuple] = set()
        #: the error a background warm dispatch raised: the next batch
        #: against this map fails with it instead of host-serving
        self.failed: BaseException | None = None
        #: combos with a background warm in flight (dedup)
        self.warming: set[tuple] = set()
        #: cold miss-storms seen per combo: the background warm only
        #: kicks on the SECOND storm — a workload whose misses are a
        #: one-shot warm-up burst (config 6: stable map, pure hits
        #: after the first window) never pays a compile at all, while
        #: epoch-churning workloads (swarm under thrash) warm on their
        #: second storm and dispatch device from the third
        self.cold_seen: dict[tuple, int] = {}


def _pad_len(n: int, target: int) -> int:
    """ONE jit shape per (map, rule, numrep) combo: every batch pads
    to the flush size-target (the normal ceiling — the size trigger
    flushes there), with pow2 growth above it for the rare oversized
    flush. Shape-stable batches mean exactly one compile per combo,
    paid once in the background (or by prewarm), never per batch
    size (the ECBatcher _pow2_pad stance, tightened)."""
    out = max(8, target)
    while out < n:
        out <<= 1
    return out


def _pad_to(xs: np.ndarray, target: int) -> np.ndarray:
    """Pad lanes repeat a real pgid (lane 0) — GF-inert zeros would be
    wrong here, but a duplicated input is just a duplicated answer."""
    want = _pad_len(len(xs), target)
    if want == len(xs):
        return xs
    return np.concatenate([xs, np.full(want - len(xs), xs[0],
                                       xs.dtype)])


class PlacementStats:
    """Plain-int counter block (resolver instances live on the event
    loop; no lock needed)."""

    FIELDS = ("placement_cache_hits", "placement_cache_misses",
              "placement_batch_lookups", "placement_batched_pgids",
              "placement_host_resolves",
              "placement_epoch_invalidations",
              "placement_bg_warms")

    def __init__(self) -> None:
        for f in self.FIELDS:
            setattr(self, f, 0)

    def dump(self) -> dict[str, int]:
        return {f: getattr(self, f) for f in self.FIELDS}

    @property
    def hit_rate(self) -> float:
        total = self.placement_cache_hits + self.placement_cache_misses
        return self.placement_cache_hits / total if total else 0.0

    @staticmethod
    def aggregate(dumps) -> dict:
        """Sum per-resolver counter dumps (clients + daemons) and
        derive the combined hit_rate — the ONE home for the roll-up
        the bench and swarm payloads report."""
        total: dict = {}
        for d in dumps:
            for key, val in d.items():
                total[key] = total.get(key, 0) + val
        hits = total.get("placement_cache_hits", 0)
        misses = total.get("placement_cache_misses", 0)
        total["hit_rate"] = (round(hits / (hits + misses), 4)
                             if hits + misses else 0.0)
        return total


class PlacementResolver:
    """Epoch-keyed memoized CRUSH with batched device miss resolution.

    Owned by clients and daemons whose map only changes through epochs
    (same contract as the old PlacementMemo — NOT for the mon or tools
    that edit map objects in place without bumping the epoch)."""

    def __init__(self, conf=None, batch: bool | None = None) -> None:
        self.conf = conf
        self.stats = PlacementStats()
        self._map = None
        self._epoch = -1
        self._memo: dict[tuple[int, int], tuple] = {}
        #: miss coalescing window: pool id -> [(pgid, fut)]
        self._pending: dict[int, list] = {}
        self._timers: dict[int, object] = {}
        self._scheduled: set[int] = set()
        #: compile cache, keyed by id(crushmap) with a strong map ref
        #: inside the entry (no GC aliasing)
        self._compiles: dict[int, _MapCompile] = {}
        self._batch = _batch_enabled() if batch is None else batch

    # -------------------------------------------------------- knobs

    def _window(self) -> float:
        if self.conf is None:
            return 0.002
        try:
            return float(self.conf["client_placement_batch_window"])
        except Exception:
            return 0.002

    def _target(self) -> int:
        if self.conf is None:
            return 64
        try:
            return int(self.conf["client_placement_batch_target"])
        except Exception:
            return 64

    def _min_batch(self) -> int:
        if self.conf is None:
            return 16
        try:
            return int(self.conf["client_placement_batch_min"])
        except Exception:
            return 16

    # ------------------------------------------------------ sync path

    def _sync_epoch(self, osdmap) -> None:
        if self._map is not osdmap or osdmap.epoch != self._epoch:
            if self._map is not None:
                self.stats.placement_epoch_invalidations += 1
            self._map = osdmap
            self._epoch = osdmap.epoch
            self._memo.clear()

    def full(self, osdmap, pgid: tuple[int, int]
             ) -> tuple[list[int], int, list[int], int]:
        """(up, up_primary, acting, acting_primary) — memo hit or an
        immediate host resolve (the PlacementMemo-compatible surface;
        fresh lists per call, callers mutate their vectors)."""
        self._sync_epoch(osdmap)
        hit = self._memo.get(pgid)
        if hit is not None:
            self.stats.placement_cache_hits += 1
            up_t, upp, act_t, ap = hit
            return list(up_t), upp, list(act_t), ap
        self.stats.placement_cache_misses += 1
        self.stats.placement_host_resolves += 1
        up, upp, acting, ap = osdmap.pg_to_up_acting_full(pgid)
        self._memo[pgid] = (tuple(up), upp, tuple(acting), ap)
        return up, upp, acting, ap

    def up_acting(self, osdmap, pgid: tuple[int, int]
                  ) -> tuple[list[int], int]:
        _up, _upp, acting, ap = self.full(osdmap, pgid)
        return acting, ap

    # ----------------------------------------------------- async path

    async def afull(self, osdmap, pgid: tuple[int, int]
                    ) -> tuple[list[int], int, list[int], int]:
        """Like ``full`` but misses park on the coalescing window and
        resolve through one batched device lookup; hits return
        inline. Never raises on engine trouble — host fallback."""
        self._sync_epoch(osdmap)
        hit = self._memo.get(pgid)
        if hit is not None:
            self.stats.placement_cache_hits += 1
            up_t, upp, act_t, ap = hit
            return list(up_t), upp, list(act_t), ap
        self.stats.placement_cache_misses += 1
        if not self._batch:
            return self._host_fill(osdmap, pgid)
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        pool_id = pgid[0]
        queue = self._pending.setdefault(pool_id, [])
        queue.append((osdmap, pgid, fut))
        self._poke(pool_id, len(queue))
        up, upp, acting, ap = await fut
        return list(up), upp, list(acting), ap

    async def aup_acting(self, osdmap, pgid: tuple[int, int]
                         ) -> tuple[list[int], int]:
        _up, _upp, acting, ap = await self.afull(osdmap, pgid)
        return acting, ap

    def _host_fill(self, osdmap, pgid) -> tuple:
        self.stats.placement_host_resolves += 1
        up, upp, acting, ap = osdmap.pg_to_up_acting_full(pgid)
        if self._map is osdmap and self._epoch == osdmap.epoch:
            self._memo[pgid] = (tuple(up), upp, tuple(acting), ap)
        return up, upp, acting, ap

    # ------------------------------------------------- window policy

    def _poke(self, pool_id: int, queued: int) -> None:
        if pool_id in self._scheduled:
            return
        if queued >= self._target():
            self._arm_now(pool_id)
            return
        window = self._window()
        if window <= 0:
            self._arm_now(pool_id)
            return
        if pool_id not in self._timers:
            loop = asyncio.get_running_loop()
            self._timers[pool_id] = loop.call_later(
                window, self._flush, pool_id)
            # idle probe (the ECBatcher fast-flush stance): once the
            # loop drains its current ready set with no new miss
            # joining, nothing else can contribute this tick — flush
            # now instead of sleeping out the window. A serial caller
            # (tests, tools, cold single ops) pays ~one loop tick,
            # not 2 ms per miss; a same-tick burst still coalesces
            # whole, and a growing cross-tick storm keeps re-arming
            # until the size target or the window deadline fires.
            loop.call_soon(self._idle_probe, pool_id, queued)

    def _idle_probe(self, pool_id: int, seen: int) -> None:
        items = self._pending.get(pool_id)
        if items is None or pool_id in self._scheduled:
            return
        if len(items) == seen:
            self._flush(pool_id)
        else:
            asyncio.get_running_loop().call_soon(
                self._idle_probe, pool_id, len(items))

    def _arm_now(self, pool_id: int) -> None:
        self._scheduled.add(pool_id)
        asyncio.get_running_loop().call_soon(self._flush, pool_id)

    def _flush(self, pool_id: int) -> None:
        self._scheduled.discard(pool_id)
        timer = self._timers.pop(pool_id, None)
        if timer is not None:
            timer.cancel()
        items = self._pending.pop(pool_id, None)
        if not items:
            return
        asyncio.get_running_loop().create_task(
            self._run_batch(pool_id, items))

    # ---------------------------------------------------- batch body

    def _compile_for(self, crush) -> bulk.CompiledMap | None:
        entry = self._compiles.get(id(crush))
        if entry is None or entry.crush is not crush:
            # a new crush map supersedes the old entries: drop them
            # (each pins the full CrushMap + device arrays for the
            # process lifetime otherwise, and only the current map is
            # ever looked up again). In-flight batches/warms hold
            # their entry by reference and finish unharmed; losing a
            # stale warm-set just means the next storm re-warms —
            # jax's jit cache is shape-keyed and survives anyway.
            self._compiles.clear()
            entry = _MapCompile(crush)
            self._compiles[id(crush)] = entry
        if entry.rejected:
            return None
        if entry.compiled is None:
            try:
                entry.compiled = _load_bulk().CompiledMap(crush)
            except ValueError:
                # unsupported map shape: host oracle territory, and
                # stays that way for this map (never re-probed)
                entry.rejected = True
                return None
        return entry.compiled

    async def _run_batch(self, pool_id: int, items: list) -> None:
        # one flush can hold entries against different map objects
        # (client reconnect churn); group them
        by_map: dict[int, list] = {}
        for osdmap, pgid, fut in items:
            by_map.setdefault(id(osdmap), []).append(
                (osdmap, pgid, fut))
        for group in by_map.values():
            osdmap = group[0][0]
            pool = osdmap.pools.get(pool_id)
            compiled = (None if pool is None
                        else self._compile_for(osdmap.crush))
            # dedup pgids: N waiters for one pgid cost one lane
            pgids = sorted({pgid for _m, pgid, _f in group})
            if (compiled is None or len(pgids) < self._min_batch()):
                self._resolve_host(group)
                continue
            entry = self._compiles[id(osdmap.crush)]
            if entry.failed is not None:
                self._fail(group, entry.failed)
                continue
            key = (pool.crush_rule, pool.size,
                   _pad_len(len(pgids), self._target()))
            if key not in entry.warm:
                # cold jit for this (map, rule, shape): the ~1 s
                # compile must NEVER stall parked ops (measured: it
                # ate ~15% of an 8 s config-6 window) — host-serve
                # the waiters now; a SECOND cold storm means the
                # workload re-misses (epoch churn), so warm then
                self._resolve_host(group)
                entry.cold_seen[key] = entry.cold_seen.get(key, 0) + 1
                if (entry.cold_seen[key] >= 2
                        and self.stats.placement_epoch_invalidations):
                    # warm ONLY for epoch-churning workloads: on a
                    # stable map every miss is one-shot warm-up (pure
                    # memo hits forever after), and the bulk engine's
                    # jit compile — measured stealing ~40 CPU-seconds
                    # from a 2-core serving box MID-RUN — buys nothing
                    # back. Map churn is what makes misses recur; it
                    # is also the gate (startup warming that wants the
                    # device path regardless calls prewarm()).
                    self._kick_warm(entry, osdmap, pool, key)
                continue
            epoch0 = osdmap.epoch
            try:
                rows = await self._device_rows(osdmap, pool, compiled,
                                               pgids)
            except Exception as e:
                self._fail(group, e)
                continue
            if (osdmap.epoch != epoch0
                    or self._map is not osdmap
                    or self._epoch != epoch0):
                # the epoch moved mid-dispatch, or the
                # resolver has seen a DIFFERENT map object since this
                # batch was queued (a mon gap-fill REPLACES the map
                # wholesale, so its epoch alone can't witness the
                # change) — in every case the computed rows describe a
                # map that no longer exists; never memoize them, and
                # never roll the resolver's view back to the batch's
                # map: the waiters get fresh host answers on their own
                # (current) maps instead
                self._resolve_host(group)
                continue
            self.stats.placement_batch_lookups += 1
            self.stats.placement_batched_pgids += len(pgids)
            table: dict[tuple[int, int], tuple] = {}
            for pgid, (raw, pps) in zip(pgids, rows):
                up, upp, acting, ap = osdmap.raw_to_up_acting(
                    pgid, raw, pps)
                memo_row = (tuple(up), upp, tuple(acting), ap)
                table[pgid] = memo_row
                self._memo[pgid] = memo_row
            for _m, pgid, fut in group:
                if not fut.done():
                    fut.set_result(table[pgid])

    def _kick_warm(self, entry: _MapCompile, osdmap, pool,
                   key: tuple) -> None:
        """Compile the bulk engine for one (rule, numrep, shape) combo
        off the op path: a throwaway dispatch of the exact shape later
        batches will use (inputs are irrelevant to the jit cache, the
        weights VECTOR LENGTH is part of the shape). Marks the combo
        warm on success; a failure fails the next batch on this map."""
        if key in entry.warming or key in entry.warm:
            return
        entry.warming.add(key)
        ruleno, numrep, length = key
        xs = np.arange(length, dtype=np.uint32)
        weights = np.array(osdmap.out_weights(), dtype=np.uint32,
                           copy=True)
        loop = asyncio.get_running_loop()

        async def warm() -> None:
            try:
                await loop.run_in_executor(
                    None, bulk.do_rule_bulk, entry.compiled, ruleno,
                    xs, numrep, weights)
            except Exception as e:
                entry.failed = e
            else:
                entry.warm.add(key)
                self.stats.placement_bg_warms += 1
            finally:
                entry.warming.discard(key)

        loop.create_task(warm())

    @staticmethod
    def _fail(group: list, exc: BaseException) -> None:
        for _m, _pgid, fut in group:
            if not fut.done():
                fut.set_exception(exc)

    def _resolve_host(self, group: list) -> None:
        for osdmap, pgid, fut in group:
            if fut.done():
                continue
            try:
                fut.set_result(tuple(self._host_fill(osdmap, pgid)))
            except Exception as e:  # pool vanished mid-window
                fut.set_exception(e)

    async def _device_rows(self, osdmap, pool, compiled, pgids,
                           ) -> list[tuple[list[int], int]]:
        """One bulk-CRUSH dispatch over the miss batch. Inputs (pps
        seeds, reweight vector, epoch) are snapshotted on the loop;
        the executor runs only the pure device dispatch."""
        pps = np.array([pool.raw_pg_to_pps(ps) for _p, ps in pgids],
                       dtype=np.uint32)
        weights = osdmap.out_weights()
        rule = compiled.compile_rule(pool.crush_rule, pool.size)
        firstn = rule.op in (cm.OP_CHOOSE_FIRSTN, cm.OP_CHOOSELEAF_FIRSTN)
        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(
            None, self._bulk_sync, compiled, pool.crush_rule,
            pps, pool.size, weights, self._target())
        rows: list[tuple[list[int], int]] = []
        for i in range(len(pgids)):
            raw = [int(v) for v in out[i]]
            if firstn:
                # the device engine NONE-pads short firstn rows at the
                # tail; the host pipeline expects the compacted form
                while raw and raw[-1] == cm.ITEM_NONE:
                    raw.pop()
            rows.append((raw, int(pps[i])))
        return rows

    @staticmethod
    def _bulk_sync(compiled, ruleno, pps, numrep, weights,
                   target: int) -> np.ndarray:
        padded = _pad_to(pps, target)
        out = bulk.do_rule_bulk(compiled, ruleno, padded, numrep,
                                weights)
        return out[: len(pps)]

    # -------------------------------------------------------- prewarm

    async def prewarm(self, osdmap, pool_ids) -> int:
        """Compile the bulk engine and device-resolve EVERY pgid of
        the given pools — the serving-process startup warm (config 10
        calls it before the measured phase so cold jit compiles never
        ride a client op). Returns the number of pgids resolved (0
        with batching off); a device error raises.
        """
        if not self._batch:
            return 0
        self._sync_epoch(osdmap)
        warmed = 0
        target = self._target()
        for pool_id in pool_ids:
            pool = osdmap.pools.get(pool_id)
            if pool is None:
                continue
            compiled = self._compile_for(osdmap.crush)
            if compiled is None:
                continue
            entry = self._compiles[id(osdmap.crush)]
            all_pgids = [(pool_id, ps) for ps in range(pool.pg_num)]
            # chunk by the flush size-target so the shape warmed here
            # is EXACTLY the shape op-path flushes dispatch
            for lo in range(0, len(all_pgids), target):
                chunk = all_pgids[lo: lo + target]
                self._sync_epoch(osdmap)  # adopt bumps between chunks
                epoch0 = osdmap.epoch
                rows = await self._device_rows(osdmap, pool, compiled,
                                               chunk)
                entry.warm.add((pool.crush_rule, pool.size,
                                _pad_len(len(chunk), target)))
                if (self._map is not osdmap
                        or self._epoch != epoch0
                        or osdmap.epoch != epoch0):
                    # the map moved (in place or by replacement) while
                    # the dispatch was out: the jit is warm — that was
                    # the point — but these rows describe a dead map
                    # state and must NOT be memoized under the new
                    # epoch (they would serve stale primaries as cache
                    # HITS until the next bump)
                    continue
                self.stats.placement_batch_lookups += 1
                self.stats.placement_batched_pgids += len(chunk)
                for pgid, (raw, pps) in zip(chunk, rows):
                    up, upp, acting, ap = osdmap.raw_to_up_acting(
                        pgid, raw, pps)
                    self._memo[pgid] = (tuple(up), upp, tuple(acting),
                                        ap)
                warmed += len(chunk)
        return warmed

    def close(self) -> None:
        """Cancel armed windows and fail parked waiters cleanly."""
        for t in self._timers.values():
            t.cancel()
        self._timers.clear()
        self._scheduled.clear()
        pending, self._pending = self._pending, {}
        for items in pending.values():
            for _m, _p, fut in items:
                if not fut.done():
                    fut.cancel()
