"""ECBatcher: cross-tick coalescing, fused encode+CRC, batched decode.

Unit tier drives the batcher directly (flush policy, failure fan-out,
bucket identity, bit-exactness of the fused CRCs and the stacked-matrix
decode). The cluster tier proves the acceptance shape: under concurrent
writers with the coalescing knobs on and CEPH_TPU_EC_ENGINE=device, the
mean stripes-per-batch beats the single-tick baseline by >= 4x and the
write path performs NO separate host CRC pass over encoded cells.
"""
import asyncio
import threading
import time

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.cluster.ecbatch import ECBatcher, codec_profile_key
from ceph_tpu.ec import load_codec
from ceph_tpu.ops import gf8
from ceph_tpu.utils import config as cfg
from ceph_tpu.utils.perf import PerfCounters

DEV_PROFILE = {"plugin": "rs_tpu", "k": "3", "m": "2", "backend": "device"}


def run(coro):
    asyncio.run(asyncio.wait_for(coro, 120))


def make_perf() -> PerfCounters:
    perf = PerfCounters("test")
    ECBatcher.declare_counters(perf)
    return perf


def make_conf(**overrides) -> cfg.ConfigProxy:
    conf = cfg.proxy()
    conf.apply(overrides)
    return conf


def rand_cells(b: int, k: int = 3, su: int = 256,
               seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (b, k, su), dtype=np.uint8)


def host_parity(codec, cells: np.ndarray) -> np.ndarray:
    """(B, k, su) -> (B, m, su) via the numpy GF reference."""
    b, k, su = cells.shape
    flat = np.ascontiguousarray(cells.transpose(1, 0, 2)).reshape(k, -1)
    par = gf8.gf_matmul(codec.matrix, flat)
    return np.ascontiguousarray(
        par.reshape(codec.m, b, su).transpose(1, 0, 2))


# ------------------------------------------------------------ unit tier


def test_fused_crcs_match_native_bit_for_bit():
    """Device-path CRCs come back from the fused dispatch and must
    equal native.crc32c over every data AND parity cell."""
    codec = load_codec(dict(DEV_PROFILE))
    perf = make_perf()

    async def t():
        batcher = ECBatcher(perf)
        cells = rand_cells(5, seed=1)
        parity, crcs = await batcher.encode_cells(codec, cells)
        assert crcs is not None and crcs.shape == (5, 5)
        assert (parity == host_parity(codec, cells)).all()
        every = np.concatenate([cells, parity], axis=1)  # (5, k+m, su)
        for b in range(5):
            for j in range(5):
                want = native.crc32c(np.ascontiguousarray(every[b, j]))
                assert int(crcs[b, j]) == want

    run(t())
    assert perf.dump()["ec_batches"] == 1


def test_host_engine_returns_no_crcs():
    """The host engine keeps its two-pass shape: parity only, CRCs stay
    the caller's separate native pass (engine economics unchanged)."""
    codec = load_codec({**DEV_PROFILE, "backend": "host"})

    async def t():
        batcher = ECBatcher()
        cells = rand_cells(4, seed=2)
        parity, crcs = await batcher.encode_cells(codec, cells)
        assert crcs is None
        assert (parity == host_parity(codec, cells)).all()

    run(t())


@pytest.mark.parametrize("backend", ["device", "host"])
def test_batched_decode_matches_codec_decode(backend):
    """decode_cells must agree with per-object codec.decode for data
    rows AND for a wanted parity row (stacked recovery matrix)."""
    codec = load_codec({**DEV_PROFILE, "backend": backend})

    async def t():
        batcher = ECBatcher()
        cells = rand_cells(6, seed=3)
        parity, _ = await batcher.encode_cells(codec, cells)
        every = np.concatenate([cells, parity], axis=1)
        # lose data shard 1 and parity shard 3: survivors 0, 2, 4
        present = (0, 2, 4)
        surv = np.ascontiguousarray(every[:, list(present), :])
        out = await batcher.decode_cells(codec, present, (0, 1, 2, 3),
                                         surv)
        assert (out[:, :3, :] == cells).all()
        assert (out[:, 3, :] == every[:, 3, :]).all()
        # cross-check one object against the scalar codec.decode
        arrs = {p: every[0, p].copy() for p in present}
        ref = codec.decode([1], arrs)
        assert (out[0, 1, :] == ref[1]).all()

    run(t())


def test_cross_tick_submissions_merge_into_one_batch():
    """With a batch window armed, stripes submitted on DIFFERENT
    reactor ticks coalesce into one dispatch."""
    codec = load_codec(dict(DEV_PROFILE))
    perf = make_perf()
    conf = make_conf(osd_ec_batch_window=0.2,
                     osd_ec_batch_target_stripes=2)

    async def t():
        batcher = ECBatcher(perf, conf=conf, idle_probe=lambda: False)
        t1 = asyncio.ensure_future(
            batcher.encode_cells(codec, rand_cells(1, seed=4)))
        await asyncio.sleep(0.01)  # a later tick, window still open
        t2 = asyncio.ensure_future(
            batcher.encode_cells(codec, rand_cells(1, seed=5)))
        await asyncio.gather(t1, t2)

    run(t())
    d = perf.dump()
    assert d["ec_batches"] == 1
    assert d["ec_batch_stripes"]["sum"] == 2
    assert d["ec_flush_size"] == 1
    assert d["ec_queue_wait_us"]["count"] == 2


def test_deadline_flush_fires_on_sparse_queue():
    """A lone stripe with a busy op queue (idle_probe False) waits out
    the window, then the deadline flushes it."""
    codec = load_codec(dict(DEV_PROFILE))
    perf = make_perf()
    conf = make_conf(osd_ec_batch_window=0.05,
                     osd_ec_batch_target_stripes=1000)

    async def t():
        batcher = ECBatcher(perf, conf=conf, idle_probe=lambda: False)
        t0 = time.perf_counter()
        await batcher.encode_cells(codec, rand_cells(1, seed=6))
        assert time.perf_counter() - t0 >= 0.04

    run(t())
    d = perf.dump()
    assert d["ec_flush_deadline"] == 1
    assert d["ec_batches"] == 1


def test_mclock_idle_fast_flush_skips_the_window():
    """When the op scheduler reports idle, nothing else can contribute
    stripes — the batch must NOT wait out the window."""
    codec = load_codec(dict(DEV_PROFILE))
    perf = make_perf()
    conf = make_conf(osd_ec_batch_window=5.0,
                     osd_ec_batch_target_stripes=1000)

    async def t():
        batcher = ECBatcher(perf, conf=conf, idle_probe=lambda: True)
        t0 = time.perf_counter()
        await batcher.encode_cells(codec, rand_cells(1, seed=7))
        assert time.perf_counter() - t0 < 1.0

    run(t())
    assert perf.dump()["ec_flush_fast"] == 1


def test_double_buffer_accumulates_while_in_flight():
    """Stripes arriving while a batch is on the executor accumulate and
    dispatch as ONE drain batch at completion."""
    codec = load_codec(dict(DEV_PROFILE))
    perf = make_perf()
    real = codec.encode_crc_batch

    def slow(data, cell_bytes):
        time.sleep(0.3)
        return real(data, cell_bytes)

    codec.encode_crc_batch = slow

    async def t():
        batcher = ECBatcher(perf)
        first = asyncio.ensure_future(
            batcher.encode_cells(codec, rand_cells(1, seed=8)))
        for _ in range(400):  # wait until batch 1 is ON the executor
            if batcher._inflight:
                break
            await asyncio.sleep(0.005)
        assert batcher._inflight
        rest = [asyncio.ensure_future(
            batcher.encode_cells(codec, rand_cells(1, seed=9 + i)))
            for i in range(3)]
        await asyncio.gather(first, *rest)

    run(t())
    d = perf.dump()
    assert d["ec_batches"] == 2
    assert d["ec_flush_drain"] == 1
    # the drain batch carried all three accumulated stripes
    assert d["ec_batch_stripes"]["sum"] == 4


def test_failure_rejects_every_waiter_exactly_once():
    """A failed dispatch must reject all waiters, count a failure, and
    contribute NOTHING to the throughput counters."""
    codec = load_codec(dict(DEV_PROFILE))
    perf = make_perf()
    codec.encode_crc_batch = lambda data, cell_bytes: (_ for _ in ()).throw(
        RuntimeError("injected"))

    async def t():
        batcher = ECBatcher(perf)
        waits = [asyncio.ensure_future(
            batcher.encode_cells(codec, rand_cells(1, seed=20 + i)))
            for i in range(3)]
        results = await asyncio.gather(*waits, return_exceptions=True)
        assert all(isinstance(r, RuntimeError) for r in results)
        # the bucket is not wedged: a healthy codec encodes fine after
        healthy = load_codec(dict(DEV_PROFILE))
        parity, _ = await batcher.encode_cells(healthy,
                                               rand_cells(1, seed=30))
        assert parity.shape == (1, 2, 256)

    run(t())
    d = perf.dump()
    assert d["ec_batch_failures"] == 1
    assert d["ec_batches"] == 1  # only the healthy dispatch counted
    assert d["ec_batch_stripes"]["sum"] == 1


def test_bucket_key_is_profile_stable_not_id_based():
    """Two codec instances from the same profile share a bucket (and a
    batch); id()-reuse aliasing cannot happen by construction."""
    c1 = load_codec(dict(DEV_PROFILE))
    c2 = load_codec(dict(DEV_PROFILE))
    assert c1 is not c2
    assert codec_profile_key(c1) == codec_profile_key(c2)
    other = load_codec({**DEV_PROFILE, "k": "4"})
    assert codec_profile_key(other) != codec_profile_key(c1)
    perf = make_perf()

    async def t():
        batcher = ECBatcher(perf)
        a = asyncio.ensure_future(
            batcher.encode_cells(c1, rand_cells(1, seed=40)))
        b = asyncio.ensure_future(
            batcher.encode_cells(c2, rand_cells(1, seed=41)))
        (pa, _), (pb, _) = await asyncio.gather(a, b)
        assert (pa == host_parity(c1, rand_cells(1, seed=40))).all()
        assert (pb == host_parity(c2, rand_cells(1, seed=41))).all()

    run(t())
    assert perf.dump()["ec_batches"] == 1


# ------------------------------------------ one queue per event loop


def slow_codec(delay: float, profile=None):
    """A device codec whose fused dispatch sleeps ``delay`` s on the
    worker thread, recording how many dispatches overlap."""
    codec = load_codec(dict(profile or DEV_PROFILE))
    real = codec.encode_crc_batch
    state = {"active": 0, "most": 0}
    lock = threading.Lock()

    def slow(data, cell_bytes):
        with lock:
            state["active"] += 1
            state["most"] = max(state["most"], state["active"])
        try:
            time.sleep(delay)
            return real(data, cell_bytes)
        finally:
            with lock:
                state["active"] -= 1

    codec.encode_crc_batch = slow
    return codec, state


async def until_in_flight(batcher) -> None:
    for _ in range(400):
        if batcher._inflight:
            return
        await asyncio.sleep(0.005)
    raise AssertionError("no batch went in flight")


def test_batchers_on_one_loop_share_a_drain_dispatch():
    """Two batchers on one loop stand in for two OSDs: their 1-stripe
    encodes, queued while a batch is in flight, leave as ONE drain
    dispatch counted on one owner's perf; each caller gets its own
    rows back byte-exact."""
    codec, _ = slow_codec(0.3)
    pa, pb = make_perf(), make_perf()
    cells = {i: rand_cells(1, seed=60 + i) for i in range(4)}

    async def t():
        a, b = ECBatcher(pa), ECBatcher(pb)
        first = asyncio.ensure_future(a.encode_cells(codec, cells[0]))
        await until_in_flight(a)
        rest = [asyncio.ensure_future(o.encode_cells(codec, cells[i]))
                for i, o in ((1, a), (2, b), (3, b))]
        outs = await asyncio.gather(first, *rest)
        for i, (parity, crcs) in enumerate(outs):
            assert (parity == host_parity(codec, cells[i])).all()
            every = np.concatenate([cells[i], parity], axis=1)[0]
            assert [int(c) for c in crcs[0]] == [
                native.crc32c(np.ascontiguousarray(cell)) for cell in every]
        assert a.parked() == 0 and b.parked() == 0

    run(t())
    da, db = pa.dump(), pb.dump()
    # the oldest queued stripes' owner runs and counts both dispatches
    assert da["ec_batches"] == 2 and db["ec_batches"] == 0
    assert da["ec_flush_drain"] == 1
    assert da["ec_batch_stripes"]["sum"] == 4
    assert da["ec_batch_osds"]["count"] == 2
    assert da["ec_batch_osds"]["sum"] == 1 + 2
    assert da["ec_handoff_lat"]["avgcount"] == 2
    assert db["ec_handoff_lat"]["avgcount"] == 0
    assert db["ec_batch_osds"]["count"] == 0
    # each owner samples its own stripe groups' queue wait
    assert da["ec_queue_wait_us"]["count"] == 2
    assert db["ec_queue_wait_us"]["count"] == 2


def test_full_queue_dispatches_while_a_batch_is_in_flight():
    """A queue at the size target goes at once, even behind a batch in
    flight: the two dispatches overlap on the executor."""
    codec, state = slow_codec(0.3)
    conf = make_conf(osd_ec_batch_target_stripes=4)
    pa, pb = make_perf(), make_perf()

    async def t():
        a, b = ECBatcher(pa, conf=conf), ECBatcher(pb, conf=conf)
        first = asyncio.ensure_future(
            a.encode_cells(codec, rand_cells(1, seed=70)))
        await until_in_flight(a)
        big = rand_cells(4, seed=71)
        parity, _ = await b.encode_cells(codec, big)
        assert (parity == host_parity(codec, big)).all()
        await first

    run(t())
    assert state["most"] == 2
    da, db = pa.dump(), pb.dump()
    assert da["ec_flush_tick"] == 1 and da["ec_batches"] == 1
    assert db["ec_flush_size"] == 1 and db["ec_batches"] == 1
    assert db["ec_batch_stripes"]["sum"] == 4


def test_full_batches_of_one_tick_dispatch_apart():
    """Two OSDs' batches that each reach the size target in the same
    tick dispatch apart, at once: a full batch does not wait for the
    next tick, where the other would join it."""
    codec = load_codec(dict(DEV_PROFILE))
    conf = make_conf(osd_ec_batch_target_stripes=4)
    pa, pb = make_perf(), make_perf()
    ca, cb = rand_cells(4, seed=75), rand_cells(4, seed=76)

    async def t():
        a, b = ECBatcher(pa, conf=conf), ECBatcher(pb, conf=conf)
        (qa, _), (qb, _) = await asyncio.gather(a.encode_cells(codec, ca),
                                                b.encode_cells(codec, cb))
        assert (qa == host_parity(codec, ca)).all()
        assert (qb == host_parity(codec, cb)).all()

    run(t())
    for d in (pa.dump(), pb.dump()):
        assert d["ec_flush_size"] == 1 and d["ec_batches"] == 1
        assert d["ec_batch_stripes"]["sum"] == 4


def test_close_fails_only_its_own_queued_waiters():
    """close() of one batcher (a crash-stopped OSD) fails its queued
    waiters; another batcher's stripes in the same bucket stay queued
    and complete, and the closed one's batch in flight completes."""
    codec, _ = slow_codec(0.3)
    pa, pb = make_perf(), make_perf()

    async def t():
        a, b = ECBatcher(pa), ECBatcher(pb)
        first = asyncio.ensure_future(
            a.encode_cells(codec, rand_cells(1, seed=80)))
        await until_in_flight(a)
        doomed = asyncio.ensure_future(
            a.encode_cells(codec, rand_cells(1, seed=81)))
        kept_cells = rand_cells(1, seed=82)
        kept = asyncio.ensure_future(b.encode_cells(codec, kept_cells))
        await asyncio.sleep(0)
        a.close()
        with pytest.raises(RuntimeError, match="dispatch failed"):
            await doomed
        parity, _ = await kept
        assert (parity == host_parity(codec, kept_cells)).all()
        parity, _ = await first
        assert parity.shape == (1, 2, 256)

    run(t())
    assert pa.dump()["ec_batches"] == 1
    assert pb.dump()["ec_batches"] == 1
    assert pb.dump()["ec_flush_drain"] == 1


@pytest.mark.parametrize("armed", ["lead", "other"])
def test_fault_of_one_owner_fails_only_its_group(armed):
    """The armed ec_batch fault of any owner aboard fails the shared
    batch; isolation then retries each group against its own owner's
    fault site, so only the armed owner's stripes fail."""
    from ceph_tpu.utils.fault import FaultInjector

    codec = load_codec({**DEV_PROFILE, "backend": "host"})
    pa, pb = make_perf(), make_perf()
    fa, fb = FaultInjector(), FaultInjector()
    (fa if armed == "lead" else fb).arm("ec_batch")
    ca, cb = rand_cells(1, seed=90), rand_cells(1, seed=91)

    async def t():
        a, b = ECBatcher(pa, fault=fa), ECBatcher(pb, fault=fb)
        return await asyncio.gather(a.encode_cells(codec, ca),
                                    b.encode_cells(codec, cb),
                                    return_exceptions=True)

    ra, rb = asyncio.run(asyncio.wait_for(t(), 120))
    bad, good, cells = ((ra, rb, cb) if armed == "lead"
                        else (rb, ra, ca))
    assert isinstance(bad, RuntimeError)
    assert (good[0] == host_parity(codec, cells)).all()
    da, db = pa.dump(), pb.dump()
    # a submitted first, so a led the batch and counts its failure
    assert da["ec_batch_failures"] == 1 and db["ec_batch_failures"] == 0
    armed_d, clean_d = (da, db) if armed == "lead" else (db, da)
    assert armed_d["ec_batch_failures_injected"] == 1
    assert armed_d["ec_batch_isolated"] == 0
    assert clean_d["ec_batch_isolated"] == 1
    assert clean_d["ec_batches"] == 1
    assert clean_d["ec_batch_osds"]["sum"] == 1
    assert clean_d["ec_batch_failures_injected"] == 0


def test_single_batcher_counts_as_before():
    """One batcher alone on its loop (one OSD per process) counts as it
    always did: a same-tick burst is one dispatch of one owner."""
    codec = load_codec(dict(DEV_PROFILE))
    perf = make_perf()
    cells = [rand_cells(1, seed=100 + i) for i in range(3)]

    async def t():
        b = ECBatcher(perf)
        outs = await asyncio.gather(*(b.encode_cells(codec, c)
                                      for c in cells))
        for c, (parity, _) in zip(cells, outs):
            assert (parity == host_parity(codec, c)).all()

    run(t())
    d = perf.dump()
    assert d["ec_batches"] == 1 and d["ec_flush_tick"] == 1
    assert d["ec_batch_stripes"]["sum"] == 3
    assert d["ec_batch_osds"] == {**d["ec_batch_osds"], "count": 1,
                                  "sum": 1}
    assert d["ec_queue_wait_us"]["count"] == 3
    assert d["ec_handoff_lat"]["avgcount"] == 1


# --------------------------------------------------------- cluster tier


def test_ec_read_is_atomic_against_concurrent_write():
    """With ops dispatched concurrently (osd_op_concurrency > 1), an EC
    read racing a write's multi-shard fanout must never return a torn
    mix of old and new cells — reads serialize on the PG lock."""
    from ceph_tpu.cluster.vstart import TestCluster
    from ceph_tpu.ec import rs_plugin
    from ceph_tpu.placement.osdmap import Pool

    old = b"A" * 24576  # two full stripes at k=3, su=4096
    new = b"B" * 24576

    async def t():
        c = TestCluster(n_osds=5)
        await c.start()
        await c.client.create_pool(Pool(
            id=2, name="ec", size=5, min_size=3, pg_num=8, crush_rule=1,
            type="erasure", ec_profile={"plugin": "rs_tpu", "k": "3",
                                        "m": "2", "backend": "device"}))
        await c.wait_active(30)
        await c.client.write_full(2, "obj", old)
        # slow the encode so the overwrite sits mid-fanout while the
        # read races it
        real = rs_plugin.RSCodec.encode_crc_batch

        def slow(self, data, cell_bytes):
            time.sleep(0.15)
            return real(self, data, cell_bytes)

        rs_plugin.RSCodec.encode_crc_batch = slow
        try:
            w = asyncio.ensure_future(c.client.write_full(2, "obj", new))
            await asyncio.sleep(0.05)
            got = await c.client.read(2, "obj")
            await w
        finally:
            rs_plugin.RSCodec.encode_crc_batch = real
        assert got in (old, new), "torn EC read: mixed old/new cells"
        assert await c.client.read(2, "obj") == new
        await c.stop()

    run(t())


def test_cluster_coalescing_beats_single_tick_baseline(monkeypatch):
    """Acceptance: with CEPH_TPU_EC_ENGINE=device, concurrent writers
    and the coalescing knobs on, mean stripes_per_batch >= 4x the
    single-tick baseline (which the OSDs' shared queue already lifts
    above one stripe per dispatch) — and the write path performs no
    separate host CRC pass over encoded cells (CRCs ride the fused
    dispatch)."""
    from ceph_tpu.cluster.vstart import TestCluster
    from ceph_tpu.ec import engine
    from ceph_tpu.placement.osdmap import Pool

    import jax.numpy as jnp

    from ceph_tpu.ops import rs

    monkeypatch.setenv("CEPH_TPU_EC_ENGINE", "device")
    engine.reset_probe()

    # pre-warm the fused kernel at every pow2 batch shape the burst
    # can hit: first-use compiles inside the timed burst otherwise
    # serialize the whole cluster on this box's few cores
    warm = rs.jit_encode_with_crcs(gf8.vandermonde_rs_matrix(3, 2), 4096)
    for b in (1, 2, 4, 8, 16, 32):
        warm(jnp.zeros((b, 3, 1024), jnp.uint32))

    crc_calls = {"n": 0}
    real_crc_batch = native.crc32c_batch

    def counting_crc_batch(*a, **kw):
        crc_calls["n"] += 1
        return real_crc_batch(*a, **kw)

    async def run_one(osd_conf: dict, writers: int,
                      objs: int) -> float:
        c = TestCluster(n_osds=5, osd_conf=osd_conf)
        await c.start()
        c.client.op_timeout = 60.0
        # many PGs: writes serialize per-PG (the reference ordering
        # contract), so the count of concurrently-busy PGs per OSD
        # bounds how many stripes can park at once — the real knob
        # behind coalescing depth
        await c.client.create_pool(Pool(
            id=2, name="ec", size=5, min_size=3, pg_num=128,
            crush_rule=1, type="erasure",
            ec_profile={"plugin": "rs_tpu", "k": "3", "m": "2",
                        "backend": "auto"}))
        await c.wait_active(60)
        # exactly one stripe per object: width = k * stripe_unit
        payload = np.random.default_rng(13).integers(
            0, 256, 3 * 4096, dtype=np.uint8).tobytes()

        async def writer(w: int) -> None:
            for i in range(objs):
                await c.client.write_full(2, f"o{w}-{i}", payload)

        await asyncio.gather(*(writer(w) for w in range(writers)))
        batches = stripes = 0
        for osd in c.osds:
            d = osd.perf.dump()
            batches += int(d["ec_batches"])
            stripes += int(d["ec_batch_stripes"]["sum"])
        await c.stop()
        assert stripes == writers * objs
        return stripes / max(batches, 1)

    async def t():
        base = await run_one(
            {"osd_op_concurrency": 1, "osd_ec_batch_window": 0.0,
             "osd_ec_batch_target_stripes": 0},
            writers=4, objs=3)
        # one tick, no window: the OSDs' stripes still merge, since they
        # share the loop's queue and accumulate behind its batch in
        # flight
        assert base > 1.0, base
        monkeypatch.setattr(native, "crc32c_batch", counting_crc_batch)
        try:
            coalesced = await run_one(
                {"osd_op_concurrency": 128,
                 "osd_ec_batch_window": 0.05,
                 "osd_ec_batch_target_stripes": 12},
                writers=96, objs=2)
        finally:
            monkeypatch.setattr(native, "crc32c_batch", real_crc_batch)
        # no separate host CRC pass anywhere in the device write path
        assert crc_calls["n"] == 0
        assert coalesced >= 4 * base, (coalesced, base)

    try:
        run(t())
    finally:
        engine.reset_probe()


# ------------------------------------------------- dispatch stage timing


@pytest.mark.parametrize("backend", ["device", "host"])
def test_each_dispatch_times_its_stages_once(backend):
    """Every successful dispatch adds ONE sample to the handoff and host
    counters, and — on the device engine only — one to the device-wait
    and readback counters: count == ec_batches + ec_decode_batches."""
    codec = load_codec({**DEV_PROFILE, "backend": backend})
    perf = make_perf()

    async def t():
        # cold-shape shield off: the device decode stays on the device
        b = ECBatcher(perf, conf=make_conf(osd_ec_cold_shape_bytes=0))
        cells = rand_cells(6, seed=5)
        parity, _ = await b.encode_cells(codec, cells)
        await b.encode_cells(codec, rand_cells(3, seed=6))
        every = np.concatenate([cells, parity], axis=1)
        present = (0, 2, 4)
        out = await b.decode_cells(
            codec, present, (1,),
            np.ascontiguousarray(every[:, list(present), :]))
        assert (out[:, 0, :] == cells[:, 1, :]).all()

    run(t())
    d = perf.dump()
    n = d["ec_batches"] + d["ec_decode_batches"]
    assert n == 3
    on_device = n if backend == "device" else 0
    for key, want in (("ec_host_lat", n), ("ec_handoff_lat", n),
                      ("ec_device_wait_lat", on_device),
                      ("ec_readback_lat", on_device)):
        assert d[key]["avgcount"] == want, key
        assert (d[key]["sum"] > 0) == (want > 0), key


def test_dispatch_stages_are_profiler_leaf_spans(tmp_path):
    """Under a profiler session, an EC write through a cluster leaves
    the four dispatch stages in the .xplane.pb as host events — and no
    op-lifetime span (pg.do_op, a client verb, a sub-op), which would
    overlap every device idle gap and hide the stage beneath it."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from ceph_tpu.cluster.vstart import TestCluster
    from ceph_tpu.placement.osdmap import Pool

    async def t():
        c = TestCluster(n_osds=5)
        await c.start()
        await c.client.create_pool(Pool(
            id=2, name="ec", size=5, min_size=3, pg_num=4, crush_rule=1,
            type="erasure", ec_profile={"plugin": "rs_tpu", "k": "3",
                                        "m": "2", "backend": "device"}))
        await c.wait_active(30)
        await c.client.write_full(2, "warm", b"w" * 12288)
        jax.profiler.start_trace(str(tmp_path))
        try:
            await c.client.write_full(2, "traced", b"t" * 24576)
            assert await c.client.read(2, "traced") == b"t" * 24576
        finally:
            jax.profiler.stop_trace()
        await c.stop()

    run(t())
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert path, "no trace written"
    names = {ev.name for plane in ProfileData.from_file(path[0]).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events}
    assert {"ec.stage", "ec.device_wait", "ec.readback",
            "ec.unpack"} <= names
    assert not [n for n in names if n.startswith("pg.do_op")]
    assert not names & {"writefull", "read", "ec_sub_write",
                        "ec_sub_read"}


def test_cluster_osds_share_dispatches_of_small_writes():
    """12 OSDs on one loop, 16 concurrent 4 KiB writes to a k4m2 pool
    (one stripe each): the primaries' stripes share dispatches, over
    1.5 stripes per dispatch summed over the OSDs, and every object
    reads back byte-exact."""
    from ceph_tpu.cluster.vstart import TestCluster
    from ceph_tpu.placement.osdmap import Pool

    rng = np.random.default_rng(27)
    datas = {f"o{i}": rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
             for i in range(16)}

    async def t():
        c = TestCluster(n_osds=12)
        await c.start()
        c.client.op_timeout = 60.0
        await c.client.create_pool(Pool(
            id=2, name="ec", size=6, min_size=5, pg_num=32, crush_rule=1,
            type="erasure", ec_profile={"plugin": "rs_tpu", "k": "4",
                                        "m": "2", "stripe_unit": "4096",
                                        "backend": "device"}))
        await c.wait_active(60)
        await c.client.write_full(2, "warm", b"w" * 4096)
        before = [o.perf.dump() for o in c.osds]
        await asyncio.gather(*(c.client.write_full(2, n, d)
                               for n, d in datas.items()))
        after = [o.perf.dump() for o in c.osds]
        for n, d in datas.items():
            assert await c.client.read(2, n) == d, n
        await c.stop()
        batches = sum(a["ec_batches"] - b["ec_batches"]
                      for a, b in zip(after, before))
        stripes = sum(a["ec_batch_stripes"]["sum"]
                      - b["ec_batch_stripes"]["sum"]
                      for a, b in zip(after, before))
        assert stripes == 16
        assert stripes / batches > 1.5, (stripes, batches)

    run(t())
