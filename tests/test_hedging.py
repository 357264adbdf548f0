"""Straggler-proof dispatch: hedged EC fan-outs with loser
cancellation, per-peer EWMA hedge delays, the batched decode and
repair dispatches the hedged reads feed, the slow-OSD fault arm, and
the seeded straggler thrash.

The contract under test: hedging changes WHEN bytes arrive, never
WHICH bytes — hedged reads are byte-exact vs unhedged under injected
stragglers, cancelled losers leak neither tasks nor reply
expectations (``ec_hedges_canceled == fired - won`` by construction),
and the batched device dispatch is bit-identical to the codec for
every (k, m, erasure) draw.
"""
import asyncio
import random
import time

import numpy as np
import pytest

from ceph_tpu.cluster import TestCluster
from ceph_tpu.cluster.ecbatch import ECBatcher
from ceph_tpu.cluster.faults import Thrasher, build_schedule
from ceph_tpu.cluster.hedge import (PeerLatencyEWMA, hedge_enabled,
                                    hedged_fanout)
from ceph_tpu.ec import load_codec
from ceph_tpu.placement.osdmap import Pool

EC_PROFILE = {"plugin": "rs_tpu", "k": "3", "m": "2",
              "backend": "device"}

HEDGE_KEYS = ("ec_hedges_fired", "ec_hedges_won", "ec_hedges_canceled",
              "ec_hedges_wasted_bytes")


def run(coro, timeout=180):
    asyncio.run(asyncio.wait_for(coro, timeout))


async def make_ec_cluster(n=5, seed=0, pg_num=8, profile=None):
    c = TestCluster(n_osds=n, fault_seed=seed)
    await c.start()
    await c.client.create_pool(
        Pool(id=2, name="ec", size=5, min_size=3, pg_num=pg_num,
             crush_rule=1, type="erasure",
             ec_profile=dict(profile or EC_PROFILE)))
    await c.wait_active(20)
    return c


def hedge_totals(c) -> dict:
    tot = {k: 0 for k in HEDGE_KEYS}
    for o in c.osds:
        if o is None:
            continue
        d = o.perf.dump()
        for k in HEDGE_KEYS:
            tot[k] += int(d.get(k, 0))
    return tot


# --------------------------------------------------- EWMA hedge delay


def test_ewma_adapts_and_defaults():
    e = PeerLatencyEWMA(alpha=0.25)
    assert e.latency(3) == 0.0  # never-seen peer
    e.observe(3, 0.1)
    assert e.latency(3) == pytest.approx(0.1)  # first sample seeds
    e.observe(3, 0.2)
    assert e.latency(3) == pytest.approx(0.125)  # prev + a*(x - prev)
    # adaptation converges toward a shifted latency regime
    for _ in range(40):
        e.observe(3, 0.5)
    assert e.latency(3) == pytest.approx(0.5, rel=0.01)


def test_hedge_delay_clamped_to_backoff_bounds():
    e = PeerLatencyEWMA()  # conf-less: base 0.05, cap 2.0, factor 2.0
    # unknown peers: floor at the backoff base (cheap insurance)
    assert e.hedge_delay([1, 2]) == pytest.approx(0.05)
    e.observe(1, 0.001)
    assert e.hedge_delay([1]) == pytest.approx(0.05)  # fast peer: floor
    e.observe(2, 0.2)
    # two-peer plan: upper median == slower peer, 2 x 0.2
    assert e.hedge_delay([1, 2]) == pytest.approx(0.4)
    e.observe(2, 100.0)
    for _ in range(20):
        e.observe(2, 100.0)
    assert e.hedge_delay([1, 2]) == pytest.approx(2.0)  # cap


def test_one_straggler_cannot_postpone_the_hedge():
    """The delay keys on the MEDIAN planned peer: a single known-slow
    peer in a healthy plan must not inflate the deadline — that is
    the exact plan the hedge exists to cut short."""
    e = PeerLatencyEWMA()
    for p in (1, 2, 3, 4):
        e.observe(p, 0.01)
    e.observe(5, 5.0)  # the straggler the fan-out routes around
    assert e.hedge_delay([1, 2, 3, 4, 5]) == pytest.approx(0.05)


def test_hedge_enabled_env_lever():
    """Hedging follows the osd_hedge_reads knob, and is on where no
    conf says otherwise."""
    assert hedge_enabled(None)
    assert hedge_enabled({})
    assert hedge_enabled({"osd_hedge_reads": True})
    assert not hedge_enabled({"osd_hedge_reads": False})


# ------------------------------------------------ hedged_fanout unit


class _Perf:
    def __init__(self):
        self.c = {}

    def inc(self, name, v=1):
        self.c[name] = self.c.get(name, 0) + v


class _FakeOsd:
    def __init__(self, delay=0.01):
        self.conf = None
        self.perf = _Perf()
        self._delay = delay

    def hedge_delay(self, peers):
        return self._delay


def _cand(key, peer, result, delay, log):
    async def _one():
        try:
            await asyncio.sleep(delay)
            log.append(("done", key))
            return result
        except asyncio.CancelledError:
            log.append(("cancelled", key))
            raise
    return (key, peer, _one)


def test_hedged_fanout_first_sufficient_cancels_losers():
    """A straggling primary is routed around: the hedge completes,
    the fan-out resolves on the first sufficient subset, the loser is
    cancelled (its CancelledError cleanup RUNS), and the ledger closes
    with canceled == fired - won."""
    async def t():
        osd = _FakeOsd(delay=0.01)
        log = []
        before = len(asyncio.all_tasks())
        out = await hedged_fanout(
            osd,
            [_cand("a", 1, b"A", 0.0, log),
             _cand("slow", 2, b"S", 5.0, log)],
            [_cand("h", 3, b"H", 0.0, log)],
            sufficient=lambda o: len(o) >= 2,
            nbytes=len)
        assert out == {"a": b"A", "h": b"H"}  # loser ABSENT
        assert ("cancelled", "slow") in log
        assert osd.perf.c["ec_hedges_fired"] == 1
        assert osd.perf.c["ec_hedges_won"] == 1
        assert osd.perf.c.get("ec_hedges_canceled", 0) == 0
        # task census returns to baseline: losers were awaited dead
        assert len(asyncio.all_tasks()) == before
    run(t(), timeout=30)


def test_hedged_fanout_cancels_unfinished_hedges():
    """Primaries resolving after the hedge wave fired but before the
    hedges complete: every fired hedge is cancelled and the invariant
    canceled == fired - won holds."""
    async def t():
        osd = _FakeOsd(delay=0.01)
        log = []
        out = await hedged_fanout(
            osd,
            [_cand("a", 1, b"A", 0.05, log)],
            [_cand("h1", 2, b"H", 5.0, log),
             _cand("h2", 3, b"H", 5.0, log)],
            sufficient=lambda o: "a" in o)
        assert out == {"a": b"A"}
        assert osd.perf.c["ec_hedges_fired"] == 2
        assert osd.perf.c.get("ec_hedges_won", 0) == 0
        assert osd.perf.c["ec_hedges_canceled"] == 2
        assert ("cancelled", "h1") in log and ("cancelled", "h2") in log
    run(t(), timeout=30)


def test_hedged_fanout_env_off_is_plan_exact():
    """osd_hedge_reads = False: extras never launch, no hedge counters
    move — the plan-exact fan-out."""
    async def t():
        osd = _FakeOsd(delay=0.0)
        osd.conf = {"osd_hedge_reads": False}
        log = []
        out = await hedged_fanout(
            osd,
            [_cand("a", 1, b"A", 0.02, log)],
            [_cand("h", 2, b"H", 0.0, log)],
            sufficient=lambda o: "a" in o)
        assert out == {"a": b"A"}
        assert osd.perf.c == {}
        assert not any(k == "h" for _e, k in log)
    run(t(), timeout=30)


def test_hedged_fanout_records_exceptions_as_outcomes():
    """A raising factory records the exception AS the outcome —
    callers keep their own transient-vs-failed triage."""
    async def t():
        osd = _FakeOsd()

        async def boom():
            raise IOError("transport")

        out = await hedged_fanout(
            osd, [("x", 1, boom)], [],
            sufficient=lambda o: len(o) >= 1)
        assert isinstance(out["x"], IOError)
    run(t(), timeout=30)


# ------------------------------- hedged read vs stragglers (cluster)


def test_hedged_read_byte_exact_and_leak_free():
    """Under a persistently slow OSD, hedged EC reads return the exact
    written bytes, route around the straggler (hedges fire AND win),
    cancel losers without leaking reply expectations, and the unhedged
    arm (osd_hedge_reads = False) reads the same bytes the slow way."""
    async def t():
        c = await make_ec_cluster(seed=7)
        try:
            rng = random.Random(99)
            payloads = {f"hedge-{i}": rng.randbytes(16 << 10)
                        for i in range(6)}
            for name, data in payloads.items():
                await c.client.write_full(2, name, data)
            # one persistently slow daemon: lognormal service-time
            # inflation on its shard-serving path, median well above
            # the 50 ms hedge-delay floor
            c.faults.slow_osd([1], scale=0.3, sigma=0.2)
            for name, data in payloads.items():
                got = await c.client.read(2, name)
                assert got == data, f"hedged read tore {name}"
            tot = hedge_totals(c)
            assert tot["ec_hedges_fired"] > 0
            assert tot["ec_hedges_won"] > 0
            assert tot["ec_hedges_canceled"] == \
                tot["ec_hedges_fired"] - tot["ec_hedges_won"]
            # leak-free: every reply expectation drained (cancelled
            # losers ran their drop_reply cleanup); straggler replies
            # to dropped subtids are no-ops
            deadline = asyncio.get_running_loop().time() + 15.0
            while asyncio.get_running_loop().time() < deadline:
                if all(not o.pending for o in c.osds if o is not None):
                    break
                await asyncio.sleep(0.1)
            assert all(not o.pending for o in c.osds if o is not None)
            # unhedged arm: the same bytes, just without firing hedges
            for o in c.osds:
                o.conf.set("osd_hedge_reads", False)
            fired0 = hedge_totals(c)["ec_hedges_fired"]
            for name, data in payloads.items():
                assert await c.client.read(2, name) == data
            assert hedge_totals(c)["ec_hedges_fired"] == fired0
        finally:
            await c.stop()
    run(t(), timeout=240)


# ------------------------------- device tier: the batched dispatches


def _conf(**kw):
    # plain dict: absent knobs raise KeyError and the batcher falls
    # back to its defaults (window 0, mesh off, repair off)
    return dict(kw)


def _su_for(codec, base=1024):
    """A stripe_unit that is a fixed point of get_chunk_size — what
    osd.sinfo_for would compute for the pool."""
    su = base
    for _ in range(8):
        got = codec.get_chunk_size(codec.k * su)
        if got == su:
            return su
        su = got
    raise AssertionError("stripe unit did not stabilize")


class _BatchPerf:
    def __init__(self):
        self.c = {}

    def add_u64_counter(self, name, *a, **k):
        self.c[name] = 0

    def add_histogram(self, *a, **k):
        pass

    def add_time_avg(self, *a, **k):
        pass

    def inc(self, name, v=1):
        self.c[name] = self.c.get(name, 0) + v

    def observe(self, *a, **k):
        pass

    def tinc(self, *a, **k):
        pass


def test_overdecompose_decode_parity_random_draws():
    """The one batched decode dispatch on the device engine is
    bit-identical to the host engine across random (k, m, erasure)
    draws, and both rebuild the erased data rows exactly."""
    async def t():
        rng = np.random.default_rng(20260806)
        # shield off: every round takes the engine it was asked for
        batcher = ECBatcher(perf=None,
                            conf=_conf(osd_ec_cold_shape_bytes=0))
        for trial in range(5):
            k = int(rng.integers(2, 6))
            m = int(rng.integers(1, 4))
            prof = {"plugin": "rs_tpu", "k": str(k), "m": str(m)}
            host = load_codec({**prof, "backend": "host"})
            dev = load_codec({**prof, "backend": "device"})
            su = _su_for(host)
            b = int(rng.integers(9, 48))
            cells = rng.integers(0, 256, (b, k, su), dtype=np.uint8)
            parity, _ = await batcher.encode_cells(host, cells)
            every = np.concatenate([cells, parity], axis=1)
            # erase a random data row (plus up to m-1 others), decode
            # the erased data from exactly k survivors
            lost = int(rng.integers(0, k))
            others = [x for x in range(k + m) if x != lost]
            present = tuple(sorted(
                rng.choice(others, size=k, replace=False).tolist()))
            want = tuple(j for j in range(k) if j not in present)
            surv = np.ascontiguousarray(every[:, list(present), :])
            base = await batcher.decode_cells(host, present, want, surv)
            got = await batcher.decode_cells(dev, present, want, surv)
            np.testing.assert_array_equal(
                base, got, err_msg=f"trial {trial} k={k} m={m} "
                                   f"present={present}")
            for i, j in enumerate(want):
                np.testing.assert_array_equal(got[:, i, :],
                                              cells[:, j, :])
    run(t(), timeout=120)


def test_overdecompose_repair_parity_clay():
    """The batched sub-chunk repair dispatch rebuilds the same bytes as
    the codec's own per-stripe Clay repair from the same helper
    slices — and those are the lost chunk's cells."""
    async def t():
        codec = load_codec({"plugin": "clay", "k": "3", "m": "2",
                            "backend": "host"})
        su = _su_for(codec)
        rng = np.random.default_rng(11)
        cells = rng.integers(0, 256, (13, codec.k, su), dtype=np.uint8)
        parity = np.stack([codec.encode_chunks(c) for c in cells])
        every = np.concatenate([cells, parity], axis=1)
        lost = 0
        avail = sorted(set(range(5)) - {lost})
        assert codec.is_repair({lost}, set(avail))
        plan = codec.minimum_to_decode([lost], avail)
        sub = su // codec.get_sub_chunk_count()
        order = sorted(plan)
        runs = plan[order[0]]
        surv = np.stack([
            np.concatenate([every[:, ch, o * sub:(o + cnt) * sub]
                            for o, cnt in runs], axis=1)
            for ch in order], axis=1)
        batcher = ECBatcher(perf=None, conf=_conf())
        got = await batcher.repair_cells(codec, tuple(order), (lost,),
                                         surv)
        ref = np.stack([
            codec.repair([lost], {ch: surv[s, i]
                                  for i, ch in enumerate(order)})[lost]
            for s in range(len(surv))])
        np.testing.assert_array_equal(got[:, 0, :], ref)
        np.testing.assert_array_equal(got[:, 0, :], every[:, lost, :])
    run(t(), timeout=120)


class _EngineProbe:
    """Minimal device-engine codec recording which engine each decode
    round ran on — host hook vs device batch."""
    profile = {"plugin": "probe"}
    technique = ""
    k, m = 2, 1
    backend = "device"
    bytewise_linear = False

    def __init__(self):
        self.calls = []

    def resolved_backend(self):
        return "device"

    def decode_cells_host(self, present, want, blk):
        self.calls.append("host")
        return np.ascontiguousarray(blk[:, :len(want), :])

    def decode_batch(self, present, surviving, want=None):
        from ceph_tpu.ops import rs
        self.calls.append("device")
        cells = rs.unpack_u32(np.asarray(surviving))
        return rs.pack_u32(np.ascontiguousarray(
            cells[:, :len(want), :]))


def test_cold_shape_shield_promotes_after_volume():
    """A decode survivor pattern stays on the host engine until its
    cumulative bytes cross osd_ec_cold_shape_bytes; the promotion
    pre-warms the device kernel on a background thread (rounds keep
    landing host meanwhile — the compile never sits on a waiting
    read), and only then does the pattern take the device path. Each
    pattern keeps its own ledger, and 0 disables the shield
    outright."""
    perf = _BatchPerf()
    ECBatcher.declare_counters(perf)
    b = ECBatcher(perf=perf, conf=_conf(osd_ec_cold_shape_bytes=100))
    codec = _EngineProbe()
    cells = np.arange(4 * 2 * 8, dtype=np.uint8).reshape(4, 2, 8)
    key = ("dec", ("probe", "", 2, 1, "device"), 8, (0, 1), (2,))
    for _ in range(2):  # 64 B/round: cold at 0 and at 64 cumulative
        out = b._decode_sync(codec, (0, 1), (2,), cells)
        np.testing.assert_array_equal(out, cells[:, :1, :])
    assert codec.calls == ["host", "host"]
    assert perf.c["ec_decode_cold_host"] == 2
    # crossing the threshold: THIS round still lands host while the
    # background warm runs the device dispatch once off the read path
    out = b._decode_sync(codec, (0, 1), (2,), cells)  # 128 >= 100
    np.testing.assert_array_equal(out, cells[:, :1, :])
    # the counter proves the round itself landed host (the warm
    # thread's device call interleaves into `calls` at its own pace)
    assert perf.c["ec_decode_cold_host"] == 3
    assert codec.calls.count("host") == 3
    for _ in range(200):  # the warm thread flips the promotion flag
        if b._shape_warm.get(key) is True:
            break
        time.sleep(0.01)
    assert b._shape_warm[key] is True
    assert codec.calls.count("device") == 1  # the warm dispatch itself
    out = b._decode_sync(codec, (0, 1), (2,), cells)  # promoted
    np.testing.assert_array_equal(out, cells[:, :1, :])
    assert codec.calls.count("device") == 2
    assert perf.c["ec_decode_cold_host"] == 3
    # a different survivor pattern is its own ledger: cold again
    b._decode_sync(codec, (0, 2), (1,), cells)
    assert codec.calls[-1] == "host"
    # threshold 0 = shield off: straight to the device engine
    off = ECBatcher(perf=None, conf=_conf(osd_ec_cold_shape_bytes=0))
    fresh = _EngineProbe()
    off._decode_sync(fresh, (0, 1), (2,), cells)
    assert fresh.calls == ["device"]


# --------------------------------------------- lint fixtures (+ / -)


def lint(src: str, path: str, only=None):
    import textwrap

    from ceph_tpu import analysis

    return analysis.lint_source(textwrap.dedent(src), path, only)


def test_hedge_fanout_rule_flags_gather_over_reply_waits():
    bad = """
    import asyncio

    async def read_shards(osd, waits):
        return await asyncio.gather(
            *(osd.await_reply(t, f, o) for t, f, o in waits))
    """
    fs = lint(bad, "ceph_tpu/cluster/ec_backend.py",
              only=["hedge-fanout-discipline"])
    assert len(fs) == 1 and "hedged_fanout" in fs[0].message

    bad2 = """
    import asyncio

    async def rebuild(self, need):
        return await asyncio.gather(
            *(self._sub_read(j, live[j], oid) for j in need))
    """
    assert lint(bad2, "ceph_tpu/cluster/ec_backend.py",
                only=["hedge-fanout-discipline"])


def test_hedge_fanout_rule_negative_fixtures():
    # all-ack write fan-outs and send bursts legitimately gather
    ok = """
    import asyncio

    async def ship_all(sends):
        await asyncio.gather(*sends)

    async def probe_all(probes):
        return await asyncio.gather(*(p() for p in probes))
    """
    assert lint(ok, "ceph_tpu/cluster/pg.py",
                only=["hedge-fanout-discipline"]) == []
    # out of scope: non-cluster tiers
    bad_elsewhere = """
    import asyncio

    async def f(osd, waits):
        return await asyncio.gather(
            *(osd.await_reply(t, f, o) for t, f, o in waits))
    """
    assert lint(bad_elsewhere, "ceph_tpu/rgw/gateway.py",
                only=["hedge-fanout-discipline"]) == []


def test_hedge_task_rule_flags_orphaned_hedge_tasks():
    bad = """
    import asyncio

    def fire(loop, factory):
        loop.create_task(run_hedge(factory))
    """
    fs = lint(bad, "ceph_tpu/cluster/pg.py",
              only=["hedge-task-discipline"])
    assert len(fs) == 1 and "orphaned hedge task" in fs[0].message

    ok = """
    import asyncio

    def fire(loop, factory, tasks):
        t = loop.create_task(run_hedge(factory))
        tasks.add(t)
        loop.create_task(flush_log())
    """
    assert lint(ok, "ceph_tpu/cluster/pg.py",
                only=["hedge-task-discipline"]) == []


# ------------------------------------------- seeded straggler thrash


def test_straggler_thrash_converges_with_hedges():
    """Tier-1 straggler thrash: a ~5 s seeded schedule with up to two
    persistently slow OSDs under concurrent oracle writers converges
    byte-exact, the verdict's hedge ledger proves hedges fired AND won
    while the leak-free invariant holds, and the schedule replays
    draw-for-draw (legacy availability draws untouched)."""

    async def t():
        c = await make_ec_cluster(seed=4321)
        c.client.op_timeout = 150.0
        # straggle_scale: median inflation 150 ms — far above the
        # 50 ms hedge floor (hedges fire AND win) yet far below the
        # sub-op timeout, so a cold-cache/loaded run cannot tip slow
        # shards into spurious unreadability mid-recovery
        thr = Thrasher(c, 2, seed=4321, duration=5.0, max_unavail=2,
                       bitrot_p=0.0, partitions=False, n_objects=6,
                       obj_size=16 << 10, writers=3,
                       settle_timeout=120.0, stragglers=2,
                       straggle_scale=0.15, straggle_sigma=0.2)
        assert thr.schedule == build_schedule(
            4321, 5.0, 5, max_unavail=2, partitions=False,
            stragglers=2)
        # the straggler stream must not shift the availability draws
        legacy = build_schedule(4321, 5.0, 5, max_unavail=2,
                                partitions=False)
        assert [e for e in thr.schedule
                if e.kind not in ("straggle", "unstraggle")] == legacy
        assert any(e.kind == "straggle" for e in thr.schedule)
        verdict = await thr.run()
        assert verdict["passed"], verdict
        assert verdict["converged"]
        assert verdict["oracle_mismatches"] == []
        assert verdict["stragglers"]["applied"] > 0
        hedge = verdict["hedge_counters"]
        assert hedge["ec_hedges_fired"] > 0
        assert hedge["ec_hedges_won"] > 0, hedge
        assert hedge["ec_hedges_canceled"] == \
            hedge["ec_hedges_fired"] - hedge["ec_hedges_won"]
        # post-thrash task/reply census back at baseline
        for _ in range(40):
            if all(not o.pending for o in c.osds if o is not None):
                break
            await asyncio.sleep(0.1)
        assert all(not o.pending for o in c.osds if o is not None)
        await c.stop()
    run(t(), timeout=300)
