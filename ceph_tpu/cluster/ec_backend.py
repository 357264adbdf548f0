"""ECBackend: the erasure-coded data path of one PG.

The ECBackend role (src/osd/ECBackend.cc), kept apart from the PG's op
execution, peering and recovery orchestration the way the reference
keeps it apart from PrimaryLogPG. It owns:

- the EC write: the read-modify-write of the touched stripes, encoded
  as one batched dispatch through the OSD's ECBatcher, and the
  sub-write fan-out of per-shard transactions with their per-cell CRC
  patches (hash_info role);
- the EC read: the shard gather (minimum_to_decode plan, hinfo-verified
  local and remote sub-reads, hedged fan-out, version cross-check) and
  the batched decode of the missing cells;
- the shard rebuild for recovery and repair (Clay sub-chunk repair
  first, the full gather otherwise), the read-triggered repair, and
  the scrub judgement of which shard copies diverge;
- the shard-side handlers of MECSubWrite and MECSubRead.

It reads its PG through this set of attributes and nothing else:
``osd``, ``pool``, ``pgid``, ``shard``, ``cid``, ``live_members()``,
``is_primary()``, ``state``, ``lock``, ``missing``, the log (``log``,
``_persist_log()``, ``_persist_missing()``, ``acked_head``),
``_repeer_on_subop_failure()``, the pg_temp migration hooks of a
write fan-out (``_dual_write_extras()``, ``_mig_fanout_done()``,
``_gather_extras()``) and the sub-write fences (``_subop_fenced()``,
``_subop_misdirected()``). It never calls the PG's op execution,
peering or recovery; the PG reaches it through its public methods.
"""
from __future__ import annotations

import asyncio
import os as _os
from contextlib import nullcontext
from functools import partial
from typing import TYPE_CHECKING

import numpy as np

from .. import native
from ..store import NotFound
from ..store import transaction as tx
from ..utils import denc
from ..utils import trace as tr
from . import messages as M
from . import stripe as st
from .hedge import hedged_fanout
from .optracker import stage
from .osd_types import (ATTR_HINFO, ATTR_SIZE, ATTR_SS, ATTR_V,
                        ATTR_WHITEOUT, USER_ATTR, OpError, dec_entries,
                        dec_ver, enc_ver, filter_remote_ops, shard_version)
from .pglog import ZERO, Entry

if TYPE_CHECKING:
    from .pg import PG, _OpState


def _is_recovery_attr(k: str) -> bool:
    """Attrs a shard read/reconstruction must carry besides the data:
    user xattrs plus the shard-invariant head metadata. A shard
    recovered without its SnapSet would later, as primary, read a
    stale snapset and mis-file clone history (round-4 EC thrash bug)."""
    return k.startswith(USER_ATTR) or k in (ATTR_SS, ATTR_WHITEOUT)


def _pad_to(arr: np.ndarray, n: int) -> np.ndarray:
    """Zero-extend a 1-D uint8 array to ``n`` bytes (view passthrough
    when already full — the view-friendly replacement for the old
    bytes.ljust copies on the staging/decode paths)."""
    if arr.size >= n:
        return arr
    out = np.zeros(n, dtype=np.uint8)
    out[: arr.size] = arr
    return out


def _pack_subruns(runs: list[tuple[int, int]]) -> bytes:
    """[(sub_chunk_offset, count)] -> packed LE u32 pairs (the
    MECSubRead.subruns wire form; a few pairs of control ints)."""
    return b"".join(o.to_bytes(4, "little") + c.to_bytes(4, "little")
                    for o, c in runs)


def _unpack_subruns(raw: bytes) -> list[tuple[int, int]]:
    a = np.frombuffer(raw, dtype="<u4").reshape(-1, 2)
    return [(int(o), int(c)) for o, c in a]


def _slice_subruns(chunk: bytes, su: int, subruns: bytes,
                   codec) -> memoryview:
    """Per-cell sub-chunk selection: for every su-cell of ``chunk``,
    keep the (offset, count) sub-chunk runs and concatenate — the
    shard-side half of the regenerating-code repair plan (the full
    cells were already hinfo-verified by the caller). Returns a view
    over the gathered storage: the reply body and the repair staging
    both consume it un-copied (buffer plane)."""
    runs = _unpack_subruns(subruns)
    subs = codec.get_sub_chunk_count()
    sc = su // subs
    arr = np.frombuffer(chunk, dtype=np.uint8)
    if arr.size % su:
        raise IOError(
            f"shard length {arr.size} not cell-aligned for sub-chunk "
            "repair")
    cells = arr.reshape(-1, su)
    parts = [cells[:, off * sc : (off + cnt) * sc] for off, cnt in runs]
    return memoryview(
        np.ascontiguousarray(np.concatenate(parts, axis=1))
        .reshape(-1)).toreadonly()


def _stripe_runs(tlist: list[int]) -> list[tuple[int, int]]:
    """[(a, b)] index ranges of ``tlist`` that one shard write each
    carries: runs of consecutive stripe numbers."""
    ncol = len(tlist)
    if not ncol:
        return []
    if tlist[-1] - tlist[0] == ncol - 1:
        return [(0, ncol)]  # one run: a whole object, or one stripe
    cut = (np.flatnonzero(np.diff(tlist) != 1) + 1).tolist()
    return list(zip([0] + cut, cut + [ncol]))


class HinfoError(IOError):
    """A chunk failed its stored per-cell hinfo CRC (bit rot) — kept
    distinct from plain EIO so the read path can count it
    (ec_read_crc_err) and kick a repair."""


def _best_version_group(pool: dict, vers: dict, k: int) -> dict | None:
    """Newest version group with >= k members among fetched shards.

    The fallback when completing the newest generation to k members is
    impossible: an interrupted write fan-out leaves a minority of
    shards one version ahead — that generation was never ack-able (the
    client never saw it commit), so the newest generation that CAN
    decode (>= k same-version members) is the correct, consistent
    read; the client's retry re-applies the interrupted write. None
    when no generation has k members (genuinely unreconstructable)."""
    groups: dict[tuple, list] = {}
    for j in pool:
        groups.setdefault(vers.get(j, ZERO), []).append(j)
    ok = [v for v, members in groups.items() if len(members) >= k]
    if not ok:
        return None
    v = max(ok)
    return {j: pool[j] for j in groups[v]}


def _assemble_generation(copies: list, k: int):
    """Newest generation with >= k distinct shard positions across a
    MULTI-SOURCE candidate pool — current holders plus prior-interval
    strays, so one position may appear at several versions (unlike
    _best_version_group's one-copy-per-position dict). ``copies`` is
    [(ver, pos, chunk, size or None, attrs dict)]. Returns the rebuilt
    (chunks, vers, sizes, attrs) dicts for that generation, or None
    when no generation reaches k positions."""
    groups: dict[tuple, dict[int, tuple]] = {}
    for ver, pos, chunk, size, attrs in copies:
        ver = tuple(ver)
        if ver == ZERO:
            continue
        groups.setdefault(ver, {}).setdefault(pos, (chunk, size, attrs))
    ok = [v for v, members in groups.items() if len(members) >= k]
    if not ok:
        return None
    v = max(ok)
    chunks: dict[int, bytes] = {}
    vers: dict[int, tuple[int, int]] = {}
    sizes: dict[int, int] = {}
    attrs_by: dict[int, dict] = {}
    for pos, (chunk, size, attrs) in groups[v].items():
        chunks[pos] = chunk
        vers[pos] = v
        if size is not None:
            sizes[pos] = size
        attrs_by[pos] = attrs
    return chunks, vers, sizes, attrs_by


class _ShardBook:
    """What one shard gather holds, by shard position: the plan
    members' chunks, the version-demoted copies, the hedge spares, and
    each fetched copy's version, size and recovery attrs."""

    def __init__(self, exclude=()):
        self.chunks: dict[int, bytes] = {}
        #: version-demoted shards: excluded from the fetch plan but
        #: their data is KEPT for the group fallback
        self.demoted: dict[int, bytes] = {}
        self.vers: dict[int, tuple[int, int]] = {}
        self.sizes: dict[int, int] = {}
        self.attrs: dict[int, dict[str, bytes]] = {}
        self.failed: set[int] = set(exclude)
        #: hedge replies from shards OUTSIDE the minimal plan, kept
        #: aside so the next re-plan consumes them for free instead of
        #: re-fetching (chunks itself stays plan-members-only: all-row
        #: codecs decode exactly the plan)
        self.spare: dict[int, tuple] = {}
        #: shards whose fetch a hedge out-raced (cancelled losers):
        #: slow-not-dead — deprioritized from later plans, never
        #: excluded outright (planning relaxes when it would starve)
        self.slow: set[int] = set()
        self.enoent = 0  # shards that answered "never had it"

    def record(self, j: int, r: tuple) -> bytes:
        """Book an OK reply's version, size and attrs; return its data."""
        self.vers[j], self.sizes[j], self.attrs[j] = r[2:]
        return r[1]

    def file(self, j: int, r: tuple, need) -> None:
        """File one fetched reply: a plan member's data joins the
        chunks, an out-of-plan hedge's is kept spare, a failure
        excludes the shard from later plans."""
        if r[0] == M.OK:
            if j in need and j not in self.chunks:
                self.chunks[j] = self.record(j, r)
            else:
                self.spare[j] = r
            return
        if r[0] == M.ENOENT:
            self.enoent += 1
        self.failed.add(j)

    def group_fallback(self, k: int) -> bool:
        """Serve the newest generation with >= k fetched members (see
        _best_version_group); False when none has."""
        fb = _best_version_group({**self.demoted, **self.chunks},
                                 self.vers, k)
        if fb is None:
            return False
        self.chunks = fb
        return True

    def restart(self) -> None:
        """Drop what was fetched for a re-planned byte range. Shards
        that failed for real (EIO, hinfo, ENOENT) stay excluded, but
        version-demoted ones must rejoin the plan: when the group
        fallback just chose THEIR generation, leaving them in
        ``failed`` would strand the only decodable copy."""
        self.failed.difference_update(self.demoted)
        for d in (self.chunks, self.demoted, self.vers, self.sizes,
                  self.attrs, self.spare):
            d.clear()


class ECBackend:
    #: EC reads cross-check ATTR_V across fetched shards and exclude
    #: version-lagging ones (the ROADMAP stale-shard fix). Class-level
    #: so the regression test can flip it off to demonstrate the seed
    #: read path serving mixed-generation cells.
    _version_check = True

    def __init__(self, pg: "PG"):
        self.pg = pg
        self.osd = pg.osd
        self.pgid = pg.pgid
        self.shard = pg.shard
        self.cid = pg.cid
        #: (oid, shard) repairs currently in flight — a burst of reads
        #: hitting one rotten shard must queue ONE repair, not a storm
        self._repairing: set[tuple[bytes, int]] = set()
        #: oid -> our own shard's ATTR_V at which a quorum probe last
        #: confirmed the local size attr is authoritative. A past-EOF
        #: read that finds the entry matching the CURRENT local version
        #: skips the probe: the stale-size hazard needs a revived-stale
        #: primary, and revival starts with this (in-memory) cache cold
        #: while any local write bumps ATTR_V past the cached value.
        #: Capped (oldest-out) so a long-lived primary's memory stays
        #: bounded by the hot set, not the object population.
        self._size_probe_ok: dict[bytes, tuple[int, int]] = {}

    @property
    def pool(self):
        return self.pg.pool

    def shard_cid(self, pos: int) -> str:
        return f"{self.pgid[0]}.{self.pgid[1]}s{pos}"

    async def write(self, oid: bytes, st8: _OpState,
                    entries: list[Entry]) -> None:
        """EC delta write (ECBackend.cc:1898 start_rmw role): read the
        touched stripes' old data, re-encode ONLY those stripes (one
        batched device dispatch), ship per-cell deltas + CRC patches to
        each shard. A whole-object write is the degenerate case where
        every stripe is touched; a 4 KiB write into a 4 MiB object
        moves O(stripe) bytes end-to-end."""
        osd = self.osd
        codec = osd.codec_for(self.pool)
        si = osd.sinfo_for(self.pool)
        k, n = codec.k, codec.get_chunk_count()
        live = {s: o for o, s in self.pg.live_members()}
        if len(live) < k:
            # degraded below k: the write CANNOT be made durable right
            # now. A clean retryable error (not a raw exception) so the
            # client refreshes its map and retries — the PG usually
            # heals within a few epochs (min_size gate role)
            raise OpError(M.EAGAIN,
                          f"pg {self.pgid}: {len(live)} < k={k} shards")

        if st8.deleted and not st8.whiteout_delete:
            shard_txns = {}
            for g in range(n):
                pos = codec.chunk_index(g)
                t = tx.Transaction()
                self._clone_ops(t, pos, oid, st8)
                t.remove(self.shard_cid(pos), oid)
                shard_txns[pos] = t
            await self.fanout(oid, entries, shard_txns, hpatch=b"",
                              ncells=0, size=0, live=live,
                              extras=self.pg._dual_write_extras(oid, st8))
            return
        if st8.deleted:  # whiteout: keep head shell for its clones
            shard_txns = {}
            for g in range(n):
                pos = codec.chunk_index(g)
                cid = self.shard_cid(pos)
                t = tx.Transaction()
                self._clone_ops(t, pos, oid, st8)
                t.truncate(cid, oid, 0)
                t.rmattrs(cid, oid)
                t.setattr(cid, oid, ATTR_WHITEOUT, b"1")
                for name, val in st8.sys_attrs.items():
                    t.setattr(cid, oid, name, val)
                shard_txns[pos] = t
            await self.fanout(oid, entries, shard_txns, hpatch=b"",
                              ncells=0, size=0, live=live,
                              extras=self.pg._dual_write_extras(oid, st8))
            return

        if st8.full_replace:
            # cls rebuilt the object: degenerate overlay = full rewrite
            ov = st.Overlay(st8.size0 if st8.exists0 else 0)
            ov.truncate(0)
            if st8._data:
                ov.write(0, st8._data)  # Overlay snapshots bytearrays
        else:
            ov = st8.ov
        old_size = st8.size0 if st8.exists0 else 0
        new_size = ov.size
        old_nst = si.nstripes(old_size)
        new_nst = si.nstripes(new_size)

        touched: set[int] = set()
        user_bytes = 0
        for off, ln in ov.written_ranges():
            user_bytes += ln
            s0, s1 = si.stripe_span(off, ln)
            touched.update(range(s0, min(s1, new_nst)))
        if new_size < old_size and new_size % si.width and new_nst:
            # the cut stripe's pad tail must re-encode as zeros
            touched.add(new_nst - 1)

        # old stripe data needed where the overlay doesn't fully cover
        need_old = sorted(
            s for s in touched
            if s * si.width < old_size and not ov.covers(
                s * si.width,
                min((s + 1) * si.width, new_size) - s * si.width,
            )
        )
        old_runs: list[tuple[int, bytes]] = []
        run_start = None
        runs: list[tuple[int, int]] = []
        for s in need_old:
            if run_start is None:
                run_start, prev = s, s
            elif s == prev + 1:
                prev = s
            else:
                runs.append((run_start, prev + 1))
                run_start, prev = s, s
        if run_start is not None:
            runs.append((run_start, prev + 1))
        read_bytes = 0
        if runs:
            # the old-stripe read, a stage of its own (its sub-read
            # fan-out is also inside op_subop_lat)
            with stage(osd.perf, "op_rmw_read_lat", "rmw_read_done"):
                for a, b in runs:
                    start = a * si.width
                    end = min(b * si.width, old_size)
                    data, _sz = await self.read(oid, start,
                                                    end - start)
                    old_runs.append((a, data))
                    read_bytes += end - start

        tlist = sorted(touched)
        ncol = len(tlist)
        # Shard-major device STAGING buffer (the bufferlist seam of the
        # RMW path): rows are shard files — (k+m, T, su), data rows
        # first. The batcher consumes the data rows' (T, k, su)
        # transpose VIEW, whose shard-major flatten inside the host
        # engine reads this same contiguous buffer back — so the old
        # ascontiguousarray transposes and the per-run tobytes copies
        # are gone: each shard's write runs below slice contiguous
        # (run, su) views straight out of staging into the shard
        # transactions, and the store lands them at its own commit
        # boundary. A zero cell's CRC equals zero_cell_crc, so no
        # special-casing.
        staging = np.zeros((n, ncol, si.su), dtype=np.uint8)
        data_sh = staging[:k]                      # (k, T, su)
        par_sh = staging[k:]                       # (m, T, su)
        if tlist:
            # vectorized overlay: ONE materialization of the whole
            # op's extents straight into the staging rows (old stripe
            # data laid first, extents shadow it) — the per-stripe
            # apply_range bytearray round-trip is gone, and the
            # ov_apply_calls counter proves it stays one per op
            n_ext, n_cols = ov.scatter(data_sh, tlist, si, old_runs)
            osd.perf.inc("ov_apply_calls")
            osd.perf.inc("ov_apply_extents", n_ext)
            osd.perf.inc("ov_apply_stripes", n_cols)
            with stage(osd.perf, "op_ec_lat", "ec_done"):
                parity, fused = await osd.ec_batcher.encode_cells(
                    codec, data_sh.transpose(1, 0, 2))
            par_sh[:] = parity.transpose(1, 0, 2)
            if fused is not None:
                # device engine: the per-cell hash_info CRCs came back
                # from the SAME fused dispatch as the parity — no
                # second pass over the encoded cells on the host
                crcs = fused.T                             # (k+m, T)
            else:
                # host engine: ONE multithreaded native CRC batch over
                # the whole shard-major staging (same bytes the old
                # two-call shape hashed, same engine economics)
                nthr = _os.cpu_count() or 1
                crcs = native.crc32c_batch(
                    staging.reshape(-1, si.su), threads=nthr
                ).reshape(n, ncol)
            # the CRC patch table of every shard at once: row g is
            # shard g's (stripe, crc) pairs, each hpatch a view of it
            patch = np.empty((n, ncol, 2), dtype="<u4")
            patch[:, :, 0] = tlist
            patch[:, :, 1] = crcs
        # every shard writes every touched cell, in the same runs
        runs = _stripe_runs(tlist)
        shard_txns: dict[int, tx.Transaction] = {}
        hpatches: dict[int, bytes] = {}
        for g in range(n):
            pos = codec.chunk_index(g)
            cid = self.shard_cid(pos)
            t = tx.Transaction()
            self._clone_ops(t, pos, oid, st8)
            if st8.full_replace and st8.exists0:
                t.rmattrs(cid, oid)
            if not st8.exists0:
                t.touch(cid, oid)
            # contiguous staging views, not tobytes copies
            for a, b in runs:
                t.write(cid, oid, tlist[a] * si.su, staging[g, a:b])
            if new_nst != old_nst:
                # after the writes, so a store extends the file only
                # past what they wrote: a shrink drops cells, a grow
                # zero-fills the stripes no write reached (parity of
                # zero data is zero for these linear codes, so zero
                # cells are already consistent codewords), and a
                # longer stale copy is cut to the new end
                t.truncate(cid, oid, new_nst * si.su)
            for m_ in st8.xattr_muts:
                if m_[0] == "set":
                    t.setattr(cid, oid, USER_ATTR + m_[1], m_[2])
                else:
                    t.rmattr(cid, oid, USER_ATTR + m_[1])
            if st8.full_replace:
                for xk, xv in st8.xattrs().items():
                    t.setattr(cid, oid, USER_ATTR + xk, xv)
            if st8.was_whiteout:
                t.rmattr(cid, oid, ATTR_WHITEOUT)
            for name, val in st8.sys_attrs.items():
                t.setattr(cid, oid, name, val)
            shard_txns[pos] = t
            # a view over the (T, 2) patch table, not a tobytes copy:
            # the wire codec flattens at ITS boundary, local fan-out
            # consumes it via np.frombuffer either way (buffer plane).
            # T=0 (xattr-only mutation) stays b"" — memoryview.cast
            # rejects zero-sized shapes, and "no patch" is the wire
            # contract for untouched data anyway
            hpatches[pos] = (memoryview(patch[g]).toreadonly().cast("B")
                             if ncol else b"")
        # the byte counters move together, so their ratios hold in
        # any window
        osd.perf.inc("ec_user_bytes_written", user_bytes)
        osd.perf.inc("ec_shard_bytes_written", n * ncol * si.su)
        osd.perf.inc("ec_rmw_read_bytes", read_bytes)
        await self.fanout(oid, entries, shard_txns, hpatch=hpatches,
                          ncells=new_nst, size=new_size, live=live,
                          extras=self.pg._dual_write_extras(oid, st8))

    def _clone_ops(self, t: tx.Transaction, pos: int, oid: bytes,
                   st8: _OpState) -> None:
        """Per-shard lazy clone (make_writeable role): clone the shard
        file — data, hinfo, size, user attrs ride along."""
        if st8.clone_req is None:
            return
        cid = self.shard_cid(pos)
        coid, cv = st8.clone_req
        t.clone(cid, oid, coid)
        t.setattr(cid, coid, ATTR_V, enc_ver(cv))

    async def fanout(self, oid: bytes, entries: list[Entry],
                     shard_txns: dict[int, tx.Transaction],
                     hpatch, ncells: int, size: int,
                     live: dict[int, int], extras=()) -> None:
        """Apply the local shard's transaction and fan sub-writes out to
        the other shards (plus any incoming pg_temp-migration members);
        ack when every live shard commits."""
        osd = self.osd
        version = entries[-1].version
        # the primary's own shard honors the SAME missing-base bounce
        # handle_write gives peers: a delta over a base we never
        # recovered (head converged over a skipped unfound push) would
        # stamp the new version + copied hinfo over absent cells —
        # zeros that HASH as zero cells, corruption neither the CRC nor
        # the ATTR_V cross-check can convict. Bounce before anything is
        # sent; re-peering recovers (or honestly re-records) the base
        # and the client's retry lands on a whole object.
        if oid in self.pg.missing:
            for pos, t in shard_txns.items():
                if live.get(pos) != osd.id:
                    continue
                hp = hpatch[pos] if isinstance(hpatch, dict) else hpatch
                if not self._write_covers_base(t, oid, hp, ncells):
                    self.pg._mig_fanout_done(oid, ok=False)
                    self.pg._repeer_on_subop_failure()
                    raise RuntimeError(
                        f"own shard {pos} of {oid!r} misses its base: "
                        "delta write bounced pending recovery")
        waits = []
        extra_waits = []
        sends = []
        local_barriers = []
        for pos, t in shard_txns.items():
            targets = []
            if live.get(pos) is not None:
                targets.append((live[pos], False))
            targets += [(o, True) for o, p in extras if p == pos]
            if not targets:
                continue  # degraded write: the hole recovers via peering
            hp = hpatch[pos] if isinstance(hpatch, dict) else hpatch
            for target, is_extra in targets:
                if target == osd.id:
                    local_barriers.append(self._apply_shard_write(
                        self.shard_cid(pos), t, entries, hp, ncells,
                        size, version))
                    continue
                subtid = osd.new_subtid()
                fut = osd.expect_reply(subtid)
                wait = (target, subtid, fut)
                (extra_waits if is_extra else waits).append(wait)
                sends.append((is_extra, wait, osd.send(
                    f"osd.{target}",
                    M.MECSubWrite(tid=subtid, pgid=self.pgid, shard=pos,
                                  txn=t,
                                  entry=entries,
                                  epoch=osd.osdmap.epoch, hpatch=hp,
                                  ncells=ncells, size=size,
                                  prev_head=self.pg.acked_head,
                                  trace=tr.current.get()),
                )))
        # first send to the last reply: timed once per fan-out, never
        # per sub-op (the sub-op waits overlap)
        with stage(osd.perf, "op_subop_lat", "sub_ops_done"):
            extras_ok, acting_exc = True, None
            if sends:
                # one concurrent burst, not k+m serialized awaits: a corked
                # wire messenger turns the whole fan-out into one write +
                # one drain per peer connection. Failures classify per
                # target: acting sends fail the op via the cleanup path
                # below; extra (migration) sends stay best-effort — but a
                # failed extra's wait is dropped NOW, or _gather_extras
                # would stall a whole subop_timeout on a reply that can
                # never come
                results = await asyncio.gather(*(s for *_x, s in sends),
                                               return_exceptions=True)
                for (is_extra, wait, _s), res in zip(sends, results):
                    if isinstance(res, BaseException):
                        if is_extra:
                            extras_ok = False
                            extra_waits.remove(wait)
                            osd.drop_reply(wait[1])
                        elif acting_exc is None:
                            acting_exc = res
            try:
                if acting_exc is not None:
                    raise acting_exc
                await osd.gather(waits)
                # the primary's OWN shard must be as durable as the acks it
                # just gathered before the client sees success
                for barrier in local_barriers:
                    await osd.txn_durable(barrier)
            except BaseException:
                for _t, subtid, _f in waits + extra_waits:
                    osd.drop_reply(subtid)
                self.pg._mig_fanout_done(oid, ok=False)
                self.pg._repeer_on_subop_failure()
                raise
            # see PG._rep_fanout: acting all-acked; extras best-effort
            if version > self.pg.acked_head:
                self.pg.acked_head = version
            await self.pg._gather_extras(oid, extra_waits, ok=extras_ok)

    @staticmethod
    def _write_covers_base(t: tx.Transaction, oid: bytes,
                           hpatch: bytes, ncells: int) -> bool:
        """True when an EC sub-write needs no pre-existing base: it
        removes the object, or its CRC patch covers EVERY cell (a full
        rewrite replaces the whole shard file)."""
        if any(op.code == tx.OP_REMOVE and op.oid == oid
               for op in t.ops):
            return True
        if not hpatch or not ncells:
            return False
        cols = np.frombuffer(hpatch, dtype="<u4").reshape(-1, 2)[:, 0]
        return len(np.unique(cols[cols < ncells])) >= ncells

    def _apply_shard_write(self, cid: str, t: tx.Transaction,
                           entries: list[Entry], hpatch: bytes,
                           ncells: int, size: int, version) -> None:
        """Shard-side apply of one EC sub-write (primary's own shard and
        handle_write share it): run the mutation ops, patch the
        per-cell CRC attr (hash_info role) and size/version attrs —
        targeting the LAST entry's object, the mutated head — and
        persist the log, one atomic transaction."""
        osd = self.osd
        full = tx.Transaction()
        if cid not in osd.store.list_collections():
            full.create_collection(cid)
        full.ops.extend(filter_remote_ops(self.osd.store, t))
        oid = entries[-1].oid
        removing = any(op.code == tx.OP_REMOVE and op.oid == oid
                       for op in t.ops)
        if not removing:
            si = osd.sinfo_for(self.pool)
            try:
                old = st.dec_hinfo(osd.store.getattr(cid, oid,
                                                     ATTR_HINFO))
            except Exception:
                old = np.zeros(0, dtype="<u4")
            arr = np.full(ncells, st.zero_cell_crc(si.su), dtype="<u4")
            ncopy = min(len(old), ncells)
            arr[:ncopy] = old[:ncopy]
            if hpatch:
                pairs = np.frombuffer(hpatch, dtype="<u4").reshape(-1, 2)
                in_range = pairs[:, 0] < ncells
                arr[pairs[in_range, 0]] = pairs[in_range, 1]
            full.setattrs(cid, oid, {
                ATTR_HINFO: st.enc_hinfo(arr),
                ATTR_SIZE: denc.enc_u64(size),
                ATTR_V: enc_ver(version),
            })
        if oid in self.pg.missing and self._write_covers_base(
                t, oid, hpatch, ncells):
            # delete, or full rewrite of every cell: the base content
            # we were missing no longer matters. (Partial deltas were
            # already bounced in handle_write and stay missing.)
            self.pg.missing.pop(oid, None)
            self.pg._persist_missing(full, cid)
        for entry in entries:
            if entry.version > self.pg.log.head:
                self.pg.log.append(entry)
        self.pg.log.trim(osd.log_keep)
        self.pg._persist_log(full, cid)
        if osd.fault.hit("torn_write", oid=oid):
            # torn write: only a prefix of the shard transaction
            # reaches disk (pulled-plug shape) — the data lands without
            # its CRC/size/version attrs or log suffix, and scrub /
            # peering must detect and repair the divergence
            full.ops = full.ops[: max(1, len(full.ops) // 2)]
        # the returned barrier (group-commit stores only) must be
        # awaited before ANY ack built on this write leaves the daemon
        return osd.queue_txn(full)

    def absent_on_own_shard(self, oid: bytes) -> bool:
        """True when the primary's own shard, lacking ``oid``'s size
        attr, is authoritative that the object does not exist (the
        get_object_context role: a local ENOENT decides unless the
        object is missing). An active primary recovered its own shard
        before going active, and whatever it could not rebuild is on
        record in ``missing``; a shard file without the size attr is a
        torn or partial write, not an absence."""
        return (self.pg.is_primary() and self.pg.state == "active"
                and oid not in self.pg.missing
                and not self.osd.store.exists(self.cid, oid))

    async def object_meta(self, oid: bytes):
        """(size, user-attrs) of an EC object whose size attr the
        primary's own shard lacks, or None when it is absent. The own
        shard decides alone when it can (``absent_on_own_shard``);
        otherwise — the PG is not an active primary, the object is on
        its ``missing`` record (a hole being recovered), or a shard
        file exists without the attr (a torn write) — every peer shard
        is probed with a metadata-only sub-read (length=0), issued
        concurrently."""
        if self.absent_on_own_shard(oid):
            self.osd.perf.inc("ec_meta_local")
            return None
        self.osd.perf.inc("ec_meta_probe")
        peers = sorted((s, o) for o, s in self.pg.live_members()
                       if o != self.osd.id)
        # every peer answers: a plain concurrent fan-out (no hedges),
        # whose sends the corked messenger turns into one burst
        with stage(self.osd.perf, "op_subop_lat", "sub_ops_done"):
            out = await hedged_fanout(
                self.osd,
                [(pos, o, partial(self._sub_read, pos, o, oid, 0, 0))
                 for pos, o in peers],
                [], lambda out: len(out) == len(peers))
        found = None
        for pos, _o in peers:
            r = out[pos]
            if isinstance(r, BaseException):
                raise r
            if r[0] == M.OK and found is None:
                found = (r[3], r[4])
        return found

    def _hedge_extra(self) -> int:
        """Hedge width: extra candidates a fan-out may launch beyond
        the minimal plan (0 when hedging is off — plan-exact)."""
        if not self.osd.hedge_enabled():
            return 0
        try:
            return int(self.osd.conf["osd_hedge_max_extra"])
        except Exception:
            return 2

    async def _sub_read(self, j: int, target: int, oid: bytes,
                        coff: int = 0, clen: int = -1,
                        subruns: bytes = b"") -> tuple:
        """The sub-read client: ask ``target`` for shard ``j``'s
        [coff, coff+clen) (clen -1 = the whole shard file) and return
        its reply as (result, data, ver, size, attrs). The reply
        expectation is cleaned up on ANY exit — cancellation included,
        so a hedged loser leaves no pending future behind (a late
        reply to a dropped key is a no-op in OSD._resolve). A transport
        failure (peer flapping, send raced a kill) re-raises: it is
        TRANSIENT, and a caller that took it for an unreadable shard
        could misclassify a reachable object as unfound debris."""
        osd = self.osd
        subtid = osd.new_subtid()
        fut = osd.expect_reply(subtid)
        try:
            await osd.send(
                f"osd.{target}",
                M.MECSubRead(tid=subtid, pgid=self.pgid, shard=j,
                             oid=oid, offset=coff, length=clen,
                             subruns=subruns, trace=tr.current.get()),
            )
            r = await osd.await_reply(subtid, fut, target)
        except BaseException:
            osd.drop_reply(subtid)
            raise
        return (r.result, r.data, tuple(r.ver), r.size, r.attrs)

    def _read_local(self, j: int, oid: bytes, coff: int = 0,
                    clen: int = -1, subruns: bytes = b"",
                    client: bool = False) -> tuple:
        """The read of a shard file this OSD holds, answered the way a
        sub-read is: (result, data, ver, size, attrs), ``attrs`` the
        recovery attrs. clen -1 is the whole file, 0 metadata only,
        else a cell-aligned range whose cells verify against hinfo
        when ``osd_ec_verify_on_read`` is set; whole-file and sub-chunk
        reads always verify (a rotted cell must never be rebuilt into
        another shard). With ``subruns`` only the selected sub-chunk
        slices of each verified cell come back. ``client`` marks a
        client op's read: it passes the ``ec_local_read`` fault site
        and kicks a repair of a copy failing its hinfo."""
        osd = self.osd
        cid = self.shard_cid(j)
        try:
            if client and osd.fault.hit("ec_local_read", oid=oid,
                                        shard=j):
                raise IOError("injected local EIO")
            if clen == 0:
                if not osd.store.exists(cid, oid):
                    raise NotFound(repr(oid))
                chunk = b""
            else:
                chunk = bytes(osd.store.read(cid, oid, coff, clen))
                chunk = self._maybe_bitflip(chunk, oid, j)
                si = osd.sinfo_for(self.pool)
                if (osd.conf["osd_ec_verify_on_read"] or clen == -1
                        or subruns):
                    self.verify_hinfo(cid, oid, chunk,
                                      first_cell=coff // si.su)
                if subruns:
                    chunk = _slice_subruns(chunk, si.su, subruns,
                                           osd.codec_for(self.pool))
            size = denc.dec_u64(osd.store.getattr(cid, oid, ATTR_SIZE),
                                0)[0]
            attrs = {k: v for k, v in osd.store.getattrs(cid, oid).items()
                     if _is_recovery_attr(k)}
            return (M.OK, chunk, shard_version(osd.store, cid, oid),
                    size, attrs)
        except HinfoError:
            osd.perf.inc("ec_read_crc_err")
            if client:
                self._kick_read_repair(oid, j, osd.id,
                                       shard_version(osd.store, cid, oid))
            return (M.EIO, b"", ZERO, 0, {})
        except (NotFound, KeyError):
            return (M.ENOENT, b"", ZERO, 0, {})
        except Exception:
            # EIO/corruption: distinct from "never had it" so the
            # primary can count true absence (handle_sub_read's EIO arc)
            return (M.EIO, b"", ZERO, 0, {})

    async def _fetch_shard(self, j: int, target: int, oid: bytes,
                           subruns: bytes = b"") -> tuple:
        """Whole-file fetch of shard ``j`` from ``target``, this OSD or
        a peer: the reply tuple of ``_read_local`` / ``_sub_read``."""
        if target == self.osd.id:
            return self._read_local(j, oid, subruns=subruns)
        return await self._sub_read(j, target, oid, subruns=subruns)

    async def _gather(self, oid: bytes, want: list[int],
                      live: dict[int, int], book: "_ShardBook",
                      coff: int, clen: int, starve,
                      client: bool) -> None:
        """The shard gather of the read and the full rebuild: fill
        ``book`` with a decodable set of shards for ``want``, each one
        the [coff, coff+clen) range of its shard file.

        minimum_to_decode plans the fetch over the usable shards (hedge
        spares first; hedge-cancelled stragglers only when nothing else
        plans), local shards are read in place and the rest fan out as
        hedged sub-reads, completing on the first decodable subset. A
        failed shard (EIO, hinfo mismatch, lost chunk) is excluded and
        the fetch re-planned from survivors — the reconstruct-on-read
        arc of test-erasure-eio.sh. Fetched shards cross-check ATTR_V:
        a revived stale shard is self-consistent against its own stale
        hinfo, so version lag is the ONLY signal that excludes it, and
        laggards are demoted exactly like hinfo failures (their data
        kept for the group fallback). When no decodable plan is left,
        ``starve(book)`` decides: it resolves ``book.chunks`` or
        raises. ``client`` marks a client op's read: it passes the
        ``ec_local_read`` fault site, kicks a repair of every bad shard
        it meets, and times its fan-outs as the op's ``op_subop_lat``.
        A rebuild does none of these: it reinstalls what it rebuilds,
        and a read-repair task runs in a copy of the client op's
        context, so its fan-outs must not count against that op."""
        osd = self.osd
        codec = osd.codec_for(self.pool)
        b = book
        while True:
            usable = [s for s in sorted(live)
                      if s not in b.failed
                      and (s not in b.slow or s in b.chunks
                           or s in b.spare)]
            try:
                need = codec.minimum_to_decode(want, usable)
            except Exception:
                if b.slow and not all(
                        s in b.chunks or s in b.spare for s in b.slow):
                    # deprioritizing the hedge-cancelled stragglers
                    # starved the plan: rejoin them (the fan-out below
                    # awaits them in full)
                    b.slow.clear()
                    continue
                await starve(b)
                break
            primary = []
            for j in sorted(need):
                if j in b.chunks:
                    continue
                if j in b.spare:
                    # a hedge already fetched this shard: consume
                    b.chunks[j] = b.record(j, b.spare.pop(j))
                elif live[j] == osd.id:
                    b.file(j, self._read_local(j, oid, coff, clen,
                                               client=client), need)
                else:
                    primary.append((j, live[j], partial(
                        self._sub_read, j, live[j], oid, coff, clen)))
            # hedge candidates: usable shards OUTSIDE the plan (d > k
            # fan-out), fastest EWMA peers first — launched by
            # hedged_fanout only if the plan drags past the per-peer
            # hedge delay
            extras = []
            if primary:
                cand = sorted(
                    (s for s in usable
                     if s not in need and s not in b.chunks
                     and s not in b.spare and live[s] != osd.id),
                    key=lambda s: (osd.peer_ewma.latency(live[s]), s))
                extras = [(s, live[s], partial(self._sub_read, s, live[s],
                                               oid, coff, clen))
                          for s in cand[: self._hedge_extra()]]

            def _ok(r) -> bool:
                return not isinstance(r, BaseException) and r[0] == M.OK

            def _suff(out: dict) -> bool:
                # first decodable subset: what we hold + what the
                # fan-out returned OK plans a decode for `want`
                have = set(b.chunks) | set(b.spare) | {
                    j for j, r in out.items() if _ok(r)}
                try:
                    plan = codec.minimum_to_decode(want, sorted(have))
                except Exception:
                    return False
                return all(p in have for p in plan)

            out = {}
            if primary:
                with (stage(osd.perf, "op_subop_lat", "sub_ops_done")
                      if client else nullcontext()):
                    out = await hedged_fanout(
                        osd, primary, extras, _suff,
                        nbytes=lambda r: len(r[1]) if _ok(r) else 0)
            exc = None
            for j in sorted(out):
                r = out[j]
                if isinstance(r, BaseException):
                    # transport failure: transient, triaged below
                    exc = exc if exc is not None else r
                    continue
                if client and r[0] == M.EIO:
                    # shard-side hinfo/IO failure: repair it
                    self._kick_read_repair(oid, j, live[j])
                b.file(j, r, need)
            if not all(j in b.chunks for j in need):
                # plan members absent from the outcome map were
                # hedge-cancelled losers: slow, not dead
                b.slow.update(j for j in need
                              if j not in b.chunks and j not in b.failed
                              and j not in out)
                if exc is not None and not _suff(out):
                    # a transport failure AND no decodable subset:
                    # keep the legacy transient-abort contract
                    raise exc
                continue
            if self._demote_version_laggards(b):
                continue  # re-plan from the surviving quorum
            break
        self._count_stale_demotions(b, oid, live if client else None)

    async def read(self, oid: bytes, offset: int = 0,
                   length: int = -1) -> tuple[bytes, int]:
        """Bytes of [offset, offset+length) (clamped to the object) and
        the object size — fetching only the cells of the touched
        stripes from k shards.

        The objects_read_and_reconstruct role (ECBackend.cc:2405): the
        shard gather (``_gather``) fetches a decodable, generation-
        consistent set of shards, and the batched decode rebuilds the
        missing data cells. When the newest generation cannot reach k
        members (a write fan-out died mid-flight), the read falls back
        to the newest generation that can — see _best_version_group.
        The authoritative size is the served generation's, and a fetch
        planned on a stale local size attr is re-planned. Shards left
        behind the served generation get an async repair kicked."""
        osd = self.osd
        codec = osd.codec_for(self.pool)
        si = osd.sinfo_for(self.pool)
        k = codec.k
        live = {s: o for o, s in self.pg.live_members()}
        want = [codec.chunk_index(i) for i in range(k)]
        size = None
        try:
            size = denc.dec_u64(
                osd.store.getattr(self.cid, oid, ATTR_SIZE), 0
            )[0]
        except Exception:
            pass
        book = _ShardBook()

        async def starve(b: _ShardBook) -> None:
            # not enough non-demoted shards left: fall back to the
            # newest generation with >= k fetched members
            if b.group_fallback(k):
                return
            if b.enoent and not b.chunks and not b.demoted:
                raise KeyError(oid)  # object genuinely absent
            raise IOError(
                f"cannot reconstruct {oid!r}: shards "
                f"{sorted(b.failed)} unreadable"
            )

        for _replan in range(4):
            if size is not None:
                end = size if length < 0 else min(offset + length, size)
                if end <= offset:
                    if not (self._version_check and live):
                        return b"", size
                    myver = shard_version(osd.store, self.cid, oid)
                    if (myver != ZERO
                            and self._size_probe_ok.get(oid) == myver):
                        return b"", size
                    # the local size attr may itself be the stale one
                    # (this primary can be the revived shard): probe
                    # one cell of offset's stripe — even an empty-range
                    # reply carries the shard's true size and version —
                    # before declaring the range past EOF. The post-
                    # fetch authoritative size settles it either way.
                    s0, s1 = si.stripe_span(offset, 1)
                    coff, clen = s0 * si.su, (s1 - s0) * si.su
                else:
                    s0, s1 = si.stripe_span(offset, end - offset)
                    coff, clen = s0 * si.su, (s1 - s0) * si.su
            else:
                # size unknown (no local shard): fetch whole shard files
                s0, s1 = 0, 0
                coff, clen = 0, -1
            await self._gather(oid, want, live, book, coff, clen, starve,
                               client=True)
            chunks, vers = book.chunks, book.vers
            if size is None:
                size = next(iter(book.sizes.values()), None)
            # authoritative size: the served generation's size attr
            # (the primary's own attr may be the stale one)
            if vers and chunks:
                best = max(chunks, key=lambda j: vers.get(j, ZERO))
                bsize = book.sizes.get(best)
                if bsize is not None and vers.get(best, ZERO) != ZERO:
                    size = bsize
            if size is None:
                raise KeyError(oid)
            end = size if length < 0 else min(offset + length, size)
            if end <= offset:
                # the quorum confirmed our local attrs are current:
                # later past-EOF reads of this oid can skip the probe
                # until a local write bumps our shard's ATTR_V
                myver = shard_version(osd.store, self.cid, oid)
                if myver != ZERO and chunks and myver == max(
                        vers.get(j, ZERO) for j in chunks):
                    self._size_probe_ok.pop(oid, None)
                    self._size_probe_ok[oid] = myver
                    while len(self._size_probe_ok) > 4096:
                        del self._size_probe_ok[
                            next(iter(self._size_probe_ok))]
                return b"", size
            if clen != -1 and end > s1 * si.width:
                # the fetch was planned on a stale (smaller) size: the
                # range misses stripes of the authoritative object —
                # refetch wider
                book.restart()
                continue
            break
        else:
            raise IOError(f"cannot plan a stable read of {oid!r}")
        # equalize lengths defensively (lagging shards), then decode
        want_missing = [p for p in want if p not in chunks]
        if want_missing:
            # batched rebuild of ONLY the missing rows: the touched
            # stripes become a (ncells, k, su) batch through the
            # ECBatcher's bucket/pow2 machinery, merging with every
            # other degraded read / recovery decode in flight instead
            # of one codec.decode dispatch per object; already-fetched
            # shards pass through untouched
            maxlen = max(len(c) for c in chunks.values())
            missing_g = tuple(codec._position_to_generator(p)
                              for p in want_missing)
            rebuilt = await self._decode_cells_batched(
                codec, si, chunks, maxlen, want_generators=missing_g)
            decoded = {
                p: rebuilt[:, i, :].reshape(-1)
                for i, p in enumerate(want_missing)
            }
            for p in want:
                if p in chunks:
                    decoded[p] = np.frombuffer(chunks[p],
                                               dtype=np.uint8)
        else:
            decoded = {
                p: np.frombuffer(chunks[p], dtype=np.uint8)
                for p in want
            }
        # cells -> logical bytes: (ncells, k, su), stripe-major
        ncells_r = max(len(decoded[p]) for p in want) // si.su
        stack = np.zeros((k, ncells_r * si.su), dtype=np.uint8)
        for i in range(k):
            d = decoded[codec.chunk_index(i)]
            stack[i, : d.size] = d
        logical = np.ascontiguousarray(
            stack.reshape(k, ncells_r, si.su).transpose(1, 0, 2)
        ).reshape(-1)
        lo = offset - s0 * si.width
        return bytes(logical[lo : lo + (end - offset)]), size

    async def _decode_cells_batched(self, codec, si, chunks: dict,
                                    maxlen: int,
                                    want_generators: tuple) -> np.ndarray:
        """Rebuild ``want_generators`` rows from the survivor chunks via
        the ECBatcher decode side: chunk byte-ranges become a
        (ncells, k, su) cell batch (short chunks zero-extended to
        ``maxlen``), so concurrent degraded reads, recovery pulls and
        scrub repairs merge into one stacked-matrix device dispatch.
        Codecs without the batched bytewise API (bitmatrix, CLAY, ...)
        fall back to one scalar ``codec.decode`` here, so every caller
        shares ONE eligibility rule. Returns (ncells, len(want), su)
        uint8."""
        ncells = -(-maxlen // si.su)
        if ncells == 0:  # nothing fetched anywhere: nothing to rebuild
            return np.zeros((0, len(want_generators), si.su),
                            dtype=np.uint8)
        if ((getattr(codec, "bytewise_linear", False)
                or getattr(codec, "cellwise_codeword", False))
                and hasattr(codec, "decode_batch")):
            order = sorted(chunks)
            if not getattr(codec, "decode_uses_all_rows", False):
                # any k rows decode (MDS); LRC/CLAY instead consume
                # every fetched row (locality plans fetch fewer than
                # k, Clay's erasure set is the complement)
                order = order[: codec.k]
            present = tuple(codec._position_to_generator(p)
                            for p in order)
            surv = np.zeros((len(order), ncells * si.su), dtype=np.uint8)
            for row, p in enumerate(order):
                c = np.frombuffer(chunks[p], dtype=np.uint8)
                surv[row, : c.size] = c
            surv = np.ascontiguousarray(
                surv.reshape(len(order), ncells, si.su).transpose(1, 0, 2))
            with stage(self.osd.perf, "op_ec_lat", "ec_done"):
                return await self.osd.ec_batcher.decode_cells(
                    codec, present, want_generators, surv)
        # chunk-codeword codecs without a batched API: one scalar
        # codec.decode over whole (padded) chunks
        arrs = {
            p: _pad_to(np.frombuffer(c, dtype=np.uint8), maxlen)
            for p, c in chunks.items()
        }
        positions = [codec.chunk_index(g) for g in want_generators]
        decoded = codec.decode(positions, arrs)
        out = np.zeros((ncells, len(positions), si.su), dtype=np.uint8)
        for i, p in enumerate(positions):
            row = np.zeros(ncells * si.su, dtype=np.uint8)
            row[: decoded[p].size] = decoded[p]
            out[:, i, :] = row.reshape(ncells, si.su)
        return out

    def _demote_version_laggards(self, b: "_ShardBook") -> bool:
        """ATTR_V cross-check of the gather (the stale-shard
        hardening): every fetched shard lagging the max fetched version
        is demoted exactly like a hinfo-CRC failure — excluded from the
        plan, its data KEPT for the group fallback — and the gather
        re-plans from survivors when this returns True."""
        if not (self._version_check and b.vers and b.chunks):
            return False
        vmax = max(b.vers.get(j, ZERO) for j in b.chunks)
        stale = [j for j in b.chunks if b.vers.get(j, ZERO) < vmax]
        for j in stale:
            b.demoted[j] = b.chunks.pop(j)
            b.failed.add(j)
        return bool(stale)

    def _count_stale_demotions(self, b: "_ShardBook", oid: bytes,
                               live: dict | None) -> None:
        """True laggards — behind the generation actually SERVED — are
        counted (ec_read_stale_shard); shards a group fallback judged
        ahead of the served generation are not stale. With ``live``
        set, each counted laggard also gets an async repair kicked
        (the read path does; a rebuild's caller reinstalls the rebuilt
        shard itself)."""
        sel_ver = max((b.vers.get(j, ZERO) for j in b.chunks),
                      default=ZERO)
        for j in b.demoted:
            if j not in b.chunks and b.vers.get(j, ZERO) < sel_ver:
                self.osd.perf.inc("ec_read_stale_shard")
                if live is not None:
                    self._kick_read_repair(oid, j, live.get(j),
                                           b.vers.get(j))

    def _maybe_bitflip(self, chunk: bytes, oid: bytes,
                       shard: int) -> bytes:
        """``ec_read_bitflip`` fault site for local shard reads: rot
        must land BEFORE hinfo verification so the CRC check is what
        catches it."""
        if self.osd.fault.hit("ec_read_bitflip", oid=oid, shard=shard):
            from .faults import flip_bit

            chunk = flip_bit(chunk)
        return chunk

    def verify_hinfo(self, cid: str, oid: bytes, chunk: bytes,
                     first_cell: int = 0) -> None:
        """Per-cell CRC verification of a shard-file range starting at
        cell ``first_cell`` (hash_info role, per-cell so partial
        overwrites never re-hash the whole shard)."""
        if not chunk:
            return
        si = self.osd.sinfo_for(self.pool)
        stored = st.dec_hinfo(
            self.osd.store.getattr(cid, oid, ATTR_HINFO)
        )
        cells = np.frombuffer(chunk, dtype=np.uint8).reshape(-1, si.su)
        for idx in range(len(cells)):
            actual = native.crc32c(np.ascontiguousarray(cells[idx]))
            if stored[first_cell + idx] != actual:
                raise HinfoError(
                    f"hinfo mismatch on {cid}/{oid!r} cell "
                    f"{first_cell + idx}: {stored[first_cell + idx]:#x}"
                    f" != {actual:#x}"
                )

    def _kick_read_repair(self, oid: bytes, shard: int,
                          target: "int | None",
                          observed: "tuple | None" = None) -> None:
        """A read unmasked a bad copy of ``shard`` on ``target`` (bit
        rot failing hinfo, or a version-lagging revived shard): queue
        ONE asynchronous rebuild+reinstall instead of serving degraded
        until the next scrub (the read-triggered repair arc of
        test-erasure-eio.sh). Never blocks the read. ``observed`` is
        the bad copy's version when known — the repair push CAS-es on
        it so a racing write always wins."""
        if not self.pg.is_primary() or self.pg.state != "active":
            return
        if target is None or (oid, shard) in self._repairing:
            return
        self._repairing.add((oid, shard))
        self.osd.spawn(self._repair_shard(oid, shard, target, observed))

    async def _repair_shard(self, oid: bytes, shard: int, target: int,
                            observed: "tuple | None" = None) -> None:
        """Rebuild shard ``shard`` from the surviving quorum and
        reinstall it on its holder (self or peer). The reconstruct's
        own version cross-check guarantees generation-consistent cells;
        its attrs carry the version the rebuild represents."""
        try:
            async with self.pg.lock:
                chunk, attrs = await self.rebuild(oid, shard)
            version = (dec_ver(attrs[ATTR_V]) if ATTR_V in attrs
                       else shard_version(self.osd.store, self.cid, oid))
            # CAS anchor: replace the version the read observed (rot
            # keeps the version, so the rebuild's own label is the
            # right anchor when the observation carried none)
            expect = observed if observed is not None else version
            if target == self.osd.id:
                cid = self.shard_cid(shard)
                t = tx.Transaction()
                if cid not in self.osd.store.list_collections():
                    t.create_collection(cid)
                t.truncate(cid, oid, 0)
                t.write(cid, oid, 0, chunk)
                t.rmattrs(cid, oid)
                t.setattrs(cid, oid,
                           {**attrs, ATTR_V: enc_ver(version)})
                self.osd.store.queue_transaction(t)
            else:
                tid = self.osd.new_subtid()
                key = ("pushr", self.pgid, shard, oid, target, tid)
                fut = self.osd.expect_reply(key)
                await self.osd.send(
                    f"osd.{target}",
                    M.MPushOp(pgid=self.pgid, shard=shard, oid=oid,
                              version=version, data=chunk, attrs=attrs,
                              epoch=self.osd.epoch, force=1,
                              last_update=self.pg.log.head, tid=tid,
                              expect=expect),
                )
                try:
                    await asyncio.wait_for(fut, self.osd.subop_timeout)
                except asyncio.TimeoutError:
                    self.osd.drop_reply(key)
                    return
            self.osd.perf.inc("ec_read_repairs")
        except asyncio.CancelledError:
            raise
        except Exception:
            pass  # unreconstructable right now: scrub/peering retries
        finally:
            self._repairing.discard((oid, shard))

    async def handle_write(self, src: str, m: M.MECSubWrite) -> None:
        t = (m.txn if isinstance(m.txn, tx.Transaction)
             else tx.Transaction.decode(m.txn)[0])
        entries = (m.entry if isinstance(m.entry, list)
                   else dec_entries(m.entry))
        oid = entries[-1].oid
        # a DELTA that patches cells of a base we do not hold (head
        # converged over a skipped unfound push) would stamp current
        # attrs over zero-filled content that even hinfo cannot convict
        # (absent cells hash as zero cells): bounce it, like a fenced or
        # misdirected sub-write, so the primary re-peers and recovers
        # (or keeps us honestly missing); a full rewrite passes
        if ((oid in self.pg.missing
             and not self._write_covers_base(t, oid, m.hpatch, m.ncells))
                or self.pg._subop_fenced(src, m.prev_head)
                or self.pg._subop_misdirected(oid)):
            await self.osd.send(
                src,
                M.MECSubWriteReply(tid=m.tid, pgid=self.pgid,
                                   shard=m.shard, result=M.ESTALE),
            )
            return
        barrier = self._apply_shard_write(self.cid, t, entries, m.hpatch,
                                          m.ncells, m.size,
                                          entries[-1].version)
        # group-commit store: the OK below feeds the primary's all-ack
        # and ultimately the client's — it must not outrun the flush
        await self.osd.txn_durable(barrier)
        self.osd.perf.inc("subop_w")
        await self.osd.send(
            src,
            M.MECSubWriteReply(tid=m.tid, pgid=self.pgid, shard=m.shard,
                               result=M.OK),
        )

    async def handle_read(self, src: str, m: M.MECSubRead) -> None:
        """Serve a shard read (``_read_local``: whole file, metadata
        only, or a cell-aligned range verified against hinfo). With
        ``subruns`` set (regenerating-code repair), the FULL cells are
        read and hinfo-verified locally — rot must never ride a repair
        — but only the selected sub-chunk slices of each cell go on
        the wire (the repair-traffic reduction the sub-chunk plan
        exists for)."""
        # slow-OSD arm (FaultPlane.slow_osd): lognormal service-time
        # inflation on the shard-serving path — the straggler the
        # hedged read fan-outs route around. No PG lock is held here
        # (shard-side serving), so the stall slows this sub-read only.
        await self.osd.fault.pause("straggle", osd=self.osd.id,
                                   shard=m.shard)
        if self.osd.fault.hit("ec_sub_read", oid=m.oid, osd=self.osd.id,
                              shard=m.shard):
            r = (M.EIO, b"", ZERO, 0, {})
        else:
            r = self._read_local(m.shard, m.oid, m.offset, m.length,
                                 m.subruns)
        result, data, ver, size, attrs = r
        digest = native.crc32c(np.frombuffer(data, np.uint8)) \
            if data else 0
        await self.osd.send(
            src,
            M.MECSubReadReply(tid=m.tid, pgid=self.pgid, shard=m.shard,
                              result=result, data=data, digest=digest,
                              size=size, attrs=attrs, ver=ver))

    async def _repair_chunk_subchunks(self, oid: bytes, shard: int):
        """Bandwidth-optimal single-shard rebuild for regenerating
        codecs (repair_one_lost_chunk over the wire): d helpers each
        ship only their repair-plane SUB-CHUNKS (1/q of every cell,
        MECSubRead.subruns) and the batched repair dispatch rebuilds
        the full shard — repair traffic d/q cell-volumes instead of
        the k whole chunks an MDS rebuild reads. Returns None whenever
        the optimal path does not strictly apply (plan not partial,
        helper failure, version disagreement) so the caller's hardened
        full rebuild takes over."""
        codec = self.osd.codec_for(self.pool)
        si = self.osd.sinfo_for(self.pool)
        live = {s: o for o, s in self.pg.live_members()}
        usable = [s for s in sorted(live) if s != shard]
        if not codec.is_repair({shard}, set(usable)):
            return None
        need = codec.minimum_to_decode([shard], usable)
        if shard in need or len(need) < codec.d:
            return None
        runs = next(iter(need.values()))
        subs = codec.get_sub_chunk_count()
        fetched = sum(c for _, c in runs)
        if fetched >= subs or any(r != runs for r in need.values()):
            return None  # not actually a partial single-loss plan
        packed = _pack_subruns(runs)

        def _mk(j: int):
            return partial(self._fetch_shard, j, live[j], oid,
                           subruns=packed)

        def _ok(r) -> bool:
            return not isinstance(r, BaseException) and r[0] == M.OK

        d = len(need)
        helpers = sorted(need)
        # hedge candidates: helpers beyond the d-of-n plan ship the
        # SAME repair-plane sub-runs; the first d consistent arrivals
        # rebuild the shard and the stragglers are cancelled
        cand = sorted((s for s in usable
                       if s not in need and s != shard),
                      key=lambda s: (
                          self.osd.peer_ewma.latency(live[s])
                          if live.get(s) != self.osd.id else -1.0, s))
        extras = [(s, live[s], _mk(s))
                  for s in cand[: self._hedge_extra()]]
        out = await hedged_fanout(
            self.osd, [(j, live[j], _mk(j)) for j in helpers], extras,
            lambda out: sum(1 for r in out.values() if _ok(r)) >= d,
            nbytes=lambda r: len(r[1]) if _ok(r) else 0)
        ok = sorted(j for j, r in out.items() if _ok(r))
        if len(ok) < d:
            # helper failure/transient either way: the full path
            # re-plans with its own retry/fallback machinery
            return None
        chosen = ok[:d]
        if chosen != helpers:
            # hedge substitution: re-derive the repair plan over the
            # ACTUAL helper set and demand the same sub-run layout —
            # any disagreement (helper-set-dependent planes) falls
            # back to the hardened full path
            try:
                need2 = codec.minimum_to_decode([shard], chosen)
            except Exception:
                return None
            if (shard in need2 or sorted(need2) != chosen
                    or any(r != runs for r in need2.values())):
                return None
        b = _ShardBook()
        for j in chosen:
            b.chunks[j] = b.record(j, out[j])
        chunks, vers = b.chunks, b.vers
        # one consistent generation or bust: the full path owns every
        # version-skew story (fallback groups, strays, demotions)
        if len({vers[j] for j in chunks}) != 1:
            return None
        lens = {len(c) for c in chunks.values()}
        if len(lens) != 1:
            return None
        slice_bytes = si.su * fetched // subs
        total = lens.pop()
        if slice_bytes == 0 or total == 0 or total % slice_bytes:
            return None
        ncells = total // slice_bytes
        order = sorted(chunks)
        surv = np.stack([
            np.frombuffer(chunks[j], dtype=np.uint8)
            .reshape(ncells, slice_bytes) for j in order
        ], axis=1)  # (ncells, d, su/q)
        present_g = tuple(codec._position_to_generator(p)
                          for p in order)
        want_g = (codec._position_to_generator(shard),)
        with stage(self.osd.perf, "op_ec_lat", "ec_done"):
            rebuilt = await self.osd.ec_batcher.repair_cells(
                codec, present_g, want_g, surv)
        chunk_arr = np.ascontiguousarray(
            rebuilt[:, 0, :]).reshape(-1)
        self.osd.perf.inc("ec_repair_subchunk")
        self.osd.perf.inc("ec_repair_bytes_fetched",
                          sum(len(c) for c in chunks.values()))
        self.osd.perf.inc("ec_repair_bytes_rebuilt", chunk_arr.size)
        return self._rebuilt(b, chunk_arr)

    def _rebuilt(self, b: _ShardBook, chunk_arr: np.ndarray):
        """(chunk view, attrs) of a rebuilt shard: the size and
        recovery attrs of the generation it was rebuilt from — the
        max-version contributor's, whose values win conflicts in the
        union of every contributor's attrs — its fresh hinfo, and the
        ATTR_V of that generation (callers that know a newer
        authoritative version override it). The chunk stays an array
        view end-to-end: the hinfo CRC pass reads it in place, and both
        consumers — the push message body and the store transaction —
        take views (buffer plane)."""
        si = self.osd.sinfo_for(self.pool)
        best = max(b.chunks, key=lambda j: b.vers.get(j, ZERO))
        user_attrs: dict[str, bytes] = {}
        for j in sorted(b.chunks, key=lambda j: b.vers.get(j, ZERO)):
            user_attrs.update(b.attrs.get(j, {}))
        out_attrs = {
            **user_attrs,
            ATTR_SIZE: denc.enc_u64(b.sizes.get(best, 0)),
            ATTR_HINFO: st.enc_hinfo(
                st.StripeInfo.cell_crcs(chunk_arr, si.su)),
        }
        vbest = b.vers.get(best, ZERO)
        if vbest != ZERO:
            out_attrs[ATTR_V] = enc_ver(vbest)
        return memoryview(chunk_arr).toreadonly(), out_attrs

    async def _collect_stray_copies(self, oid: bytes,
                                    live: dict[int, int]) -> list:
        """Probe every up OSD for stray shard copies of ``oid`` left by
        prior-interval placements (might_have_unfound role). Current
        holders are skipped (the caller already fetched them). Returns
        [(ver, pos, chunk, size, attrs)] hinfo-verified; probing an OSD
        that never held the shard is cheap (ENOENT)."""
        codec = self.osd.codec_for(self.pool)
        osdmap = self.osd.osdmap

        async def _probe(pos: int, o: int):
            try:
                r = await self._fetch_shard(pos, o, oid)
            except Exception:
                return None  # transient peer failure: best-effort
            if r[0] != M.OK or r[2] == ZERO:
                return None
            return (r[2], pos, r[1], r[3], r[4])

        # all probes fly CONCURRENTLY: callers hold the PG lock across
        # the sweep, and chunk_count x n_osds serial round-trips (each
        # up to a subop timeout when a peer dies mid-probe) would stall
        # every client op on the PG; one concurrent round bounds the
        # sweep at a single round-trip/timeout. Result order stays the
        # deterministic (pos, osd) iteration order.
        probes = [_probe(pos, o)
                  for pos in range(codec.get_chunk_count())
                  for o in range(osdmap.n_osds)
                  if osdmap.is_up(o) and o != live.get(pos)]
        found = await asyncio.gather(*probes)
        out = [f for f in found if f is not None]
        if out:
            self.osd.perf.inc("ec_stray_reads", len(out))
        return out

    async def rebuild(self, oid: bytes, shard: int):
        """Rebuild shard `shard`'s chunk from k survivors (the recovery
        read-reconstruct path, ECBackend continue_recovery_op role):
        the shard gather of the read (``_gather``) over the whole shard
        files, the shard itself excluded. Its version cross-check
        matters more here than for a read: a rebuild mixing a revived
        stale shard's cells with current ones would PERSIST wrong bytes
        under fresh self-consistent CRCs. Returns (chunk view, attrs),
        the attrs carrying the size/recovery attrs AND the ATTR_V of
        the (max-version) generation the rebuild represents.

        Regenerating codecs (Clay) first try the bandwidth-optimal
        SUB-CHUNK repair: d helpers ship 1/q of their cells instead of
        k shipping whole chunks (_repair_chunk_subchunks). Any wrinkle
        — helper failure, version disagreement, a plan that is not
        actually partial — falls back to the full gather."""
        codec = self.osd.codec_for(self.pool)
        if hasattr(codec, "repair_batch"):
            try:
                out = await self._repair_chunk_subchunks(oid, shard)
            except Exception:
                out = None  # full path below re-plans from scratch
            if out is not None:
                return out
        live = {s: o for o, s in self.pg.live_members()}

        async def starve(b: _ShardBook) -> None:
            # newest generation can't reach k members (interrupted
            # fan-out): rebuild the newest generation that can — see
            # _best_version_group; the retry re-applies the unacked
            # write on top. The TARGET's own stored copy (hinfo-
            # verified) joins the candidate pool here: when the target
            # already holds the authoritative older generation, it
            # completes that group (the scrub-rollback arc needs
            # exactly this).
            try:
                r = await self._fetch_shard(shard, live[shard], oid)
            except Exception:
                r = None  # best-effort last-ditch candidate
            if r is not None and r[0] == M.OK:
                b.demoted[shard] = b.record(shard, r)
            # prior-interval STRAY copies (might_have_unfound role):
            # shard positions remapped during flaps leave acked chunks
            # in old holders' stores, so the current up set alone can
            # hold an acked generation below k — and scrub would roll
            # it back as orphan debris (acked-write loss). Probe every
            # up OSD's store before giving that generation up.
            stray = await self._collect_stray_copies(oid, live)
            if stray:
                pool = [(b.vers.get(p, ZERO), p, c, b.sizes.get(p),
                         b.attrs.get(p, {}))
                        for p, c in {**b.demoted, **b.chunks}.items()]
                gen = _assemble_generation(pool + stray, codec.k)
                if gen is not None:
                    b.chunks, b.vers, b.sizes, b.attrs = gen
                    return
            if not b.group_fallback(codec.k):
                raise RuntimeError(
                    f"cannot reconstruct shard {shard} of {oid!r}: "
                    f"unreadable {sorted(b.failed - {shard})}")

        b = _ShardBook(exclude={shard})
        await self._gather(oid, [shard], live, b, 0, -1, starve,
                           client=False)
        maxlen = max(len(c) for c in b.chunks.values())
        # repair economics ledger: survivor bytes fetched per shard
        # bytes rebuilt (k-to-1 here; the sub-chunk path does better)
        self.osd.perf.inc("ec_repair_bytes_fetched",
                          sum(len(c) for c in b.chunks.values()))
        self.osd.perf.inc("ec_repair_bytes_rebuilt", maxlen)
        # batched rebuild through the ECBatcher (one stacked-matrix
        # dispatch shared with every other decode in flight); a wanted
        # PARITY shard folds into the recovery matrix, so it is still
        # a single matmul, not decode-then-re-encode
        g = codec._position_to_generator(shard)
        rebuilt = await self._decode_cells_batched(
            codec, self.osd.sinfo_for(self.pool), b.chunks, maxlen,
            want_generators=(g,))
        return self._rebuilt(
            b, np.ascontiguousarray(rebuilt[:, 0, :]).reshape(-1)[:maxlen])

    def scrub_divergent(self, oid, maps, bad):
        """EC scrub judgement: (target, divergent) for ``oid`` over the
        members' ScrubMaps — ``target`` the authoritative version, and
        ``divergent`` the member keys whose copy lags it, fails its own
        hinfo (bit rot, ``bad``) or is missing.

        The authoritative generation is the newest one that can DECODE
        (>= k healthy members), which may be BEHIND ``newest``: a write
        fan-out that died mid-flight leaves a < k minority one
        generation ahead — never ack-able, so the orphans ROLL BACK to
        the decodable generation (the divergent-entry rollback of the
        reference's merge_log)."""
        copies = {key: m_[oid] for key, m_ in maps.items() if oid in m_}
        newest = max(v for v, _ in copies.values())
        k = self.osd.codec_for(self.pool).k
        vcount: dict = {}
        for key, (v, _dig) in copies.items():
            if oid not in bad[key]:
                vcount[v] = vcount.get(v, 0) + 1
        decodable = [v for v, n in vcount.items() if n >= k]
        target = max(decodable) if decodable else newest
        divergent = []
        for key, m_ in maps.items():
            ent = m_.get(oid)
            if ent is None or ent[0] != target or oid in bad[key]:
                divergent.append(key)
        return target, divergent
