"""PlacementResolver: batched device lookups vs the host pipeline.

The batched path must be bit-identical to pg_to_up_acting_full by
construction (device raw rows feed the SAME raw_to_up_acting host
code), the epoch-keyed memo must invalidate the instant the map moves,
and placement must never become a liveness dependency (host fallback
on every wrinkle). The cluster-tier test proves the serving-plane
contract: a map-epoch bump mid-flight re-targets resends onto the
post-remap primary with the batched resolver armed.
"""
import asyncio

import pytest

from ceph_tpu.placement import bulk
from ceph_tpu.placement import crushmap as cm
from ceph_tpu.placement.osdmap import Incremental, OSDMap, Pool
from ceph_tpu.placement.resolver import PlacementResolver
from ceph_tpu.utils import config as cfg


def _map(n=8):
    crush = cm.build_flat(n)
    crush.add_rule(cm.flat_firstn_rule(0))
    crush.add_rule(cm.ec_rule(1, root=-1, failure_domain_type=0))
    om = OSDMap(crush, n)
    om.add_pool(Pool(id=1, name="r", size=3, pg_num=32, crush_rule=0))
    om.add_pool(Pool(id=2, name="e", size=5, pg_num=16, crush_rule=1,
                     type="erasure"))
    return om


def _conf(min_batch=4):
    c = cfg.proxy()
    c.set("client_placement_batch_min", min_batch)
    return c


def _full_tuple(got):
    up, upp, acting, ap = got
    return tuple(up), upp, tuple(acting), ap


async def _sweep(r, om, pools=((1, 32), (2, 16))):
    """One concurrent miss sweep; asserts bit-identity vs host."""
    for pool_id, n_pg in pools:
        got = await asyncio.gather(*(
            r.afull(om, (pool_id, ps)) for ps in range(n_pg)))
        for ps, g in enumerate(got):
            want = om.pg_to_up_acting_full((pool_id, ps))
            assert _full_tuple(g) == _full_tuple(want), (pool_id, ps)


def test_batched_resolve_bit_identical_to_host():
    """The cold→warm→device arc: the first two miss storms host-serve
    (a jit compile never stalls parked ops; the second storm kicks the
    background warm), and once warm, storms dispatch through the
    device bulk engine — every stage bit-identical to the host
    pipeline."""
    async def run():
        om = _map()
        r = PlacementResolver(conf=_conf(), batch=True)
        await _sweep(r, om)                    # storm 1: host, no warm
        assert r.stats.placement_batch_lookups == 0
        om.apply_incremental(Incremental(epoch=2))
        await _sweep(r, om)                    # storm 2: host + warm
        for _ in range(200):                   # compile finishes async
            if r.stats.placement_bg_warms >= 2:
                break
            await asyncio.sleep(0.05)
        assert r.stats.placement_bg_warms >= 2
        om.apply_incremental(Incremental(epoch=3))
        await _sweep(r, om)                    # storm 3: device
        assert r.stats.placement_batch_lookups >= 2
        assert r.stats.placement_batched_pgids >= 48
        # steady state: pure cache hits, no further dispatches
        n = r.stats.placement_batch_lookups
        await _sweep(r, om, pools=((1, 32),))
        assert r.stats.placement_batch_lookups == n
        assert r.stats.placement_cache_hits >= 32

    asyncio.run(run())


def test_batched_resolve_with_overrides_and_weights():
    """upmap / pg_temp / primary-temp / reweight all ride the shared
    post-CRUSH host pipeline — batched results must carry them."""
    async def run():
        om = _map()
        om.osds[2].weight = 0          # out: CRUSH reroutes
        om.osds[5].up = False          # down: filtered from up
        om.pg_upmap_items[(1, 3)] = [(0, 7)]
        om.pg_temp[(2, 1)] = [1, 3, 4, 6, 7]
        om.primary_temp[(2, 1)] = 4
        om._out_weights_cache = None
        r = PlacementResolver(conf=_conf(), batch=True)
        # prewarm compiles AND marks the op-path shapes warm; the
        # epoch bump then invalidates the memo so the sweep below is
        # a genuine device-dispatched miss storm
        assert await r.prewarm(om, [1, 2]) == 48
        n0 = r.stats.placement_batch_lookups
        om.apply_incremental(Incremental(epoch=2))
        await _sweep(r, om)
        assert r.stats.placement_batch_lookups > n0

    asyncio.run(run())


def test_epoch_bump_invalidates_cache():
    async def run():
        om = _map()
        r = PlacementResolver(conf=_conf(), batch=True)
        await asyncio.gather(*(r.afull(om, (1, ps))
                               for ps in range(32)))
        before = _full_tuple(await r.afull(om, (1, 0)))
        om.apply_incremental(Incremental(epoch=2, down=[before[1]],
                                         weights={before[1]: 0}))
        # sync surface sees the new epoch immediately
        got = r.full(om, (1, 0))
        want = om.pg_to_up_acting_full((1, 0))
        assert _full_tuple(got) == _full_tuple(want)
        assert r.stats.placement_epoch_invalidations >= 1
        # async surface re-resolves under the new epoch too
        got = await r.afull(om, (1, 0))
        assert _full_tuple(got) == _full_tuple(want)

    asyncio.run(run())


def test_epoch_bump_mid_window_resolves_on_current_map():
    """Misses parked on the window when the epoch bumps must not be
    served from rows computed on the dead epoch."""
    async def run():
        om = _map()
        conf = _conf()
        conf.set("client_placement_batch_window", 0.02)
        r = PlacementResolver(conf=conf, batch=True)
        futs = [asyncio.ensure_future(r.afull(om, (1, ps)))
                for ps in range(32)]
        # bump while the window is still open
        om.apply_incremental(Incremental(epoch=2, down=[0],
                                         weights={0: 0}))
        got = await asyncio.gather(*futs)
        for ps, g in enumerate(got):
            want = om.pg_to_up_acting_full((1, ps))
            assert _full_tuple(g) == _full_tuple(want)

    asyncio.run(run())


def test_device_failure_fails_the_waiters(monkeypatch):
    """A failing device is an error for the waiters of that batch, not
    a quiet switch to host placement."""
    async def run():
        om = _map()
        r = PlacementResolver(conf=_conf(), batch=True)
        assert await r.prewarm(om, [1]) > 0  # warm: storms dispatch
        om.apply_incremental(Incremental(epoch=2))  # drop the memo

        def boom(*a, **kw):
            raise RuntimeError("no accelerator")

        monkeypatch.setattr(bulk, "do_rule_bulk", boom)
        got = await asyncio.gather(*(r.afull(om, (1, ps))
                                     for ps in range(32)),
                                   return_exceptions=True)
        assert all(isinstance(g, RuntimeError) for g in got), got
        with pytest.raises(RuntimeError, match="no accelerator"):
            await r.prewarm(om, [1])

    asyncio.run(run())


def test_unsupported_map_rejected_once_host_serves():
    async def run():
        crush = cm.build_flat(6)
        crush.add_rule(cm.flat_firstn_rule(0))
        crush.tunables.choose_local_tries = 2  # device engine rejects
        om = OSDMap(crush, 6)
        om.add_pool(Pool(id=1, name="r", size=3, pg_num=32,
                         crush_rule=0))
        r = PlacementResolver(conf=_conf(), batch=True)
        got = await asyncio.gather(*(r.afull(om, (1, ps))
                                     for ps in range(32)))
        for ps, g in enumerate(got):
            want = om.pg_to_up_acting_full((1, ps))
            assert _full_tuple(g) == _full_tuple(want)
        assert r.stats.placement_batch_lookups == 0
        entry = r._compiles[id(om.crush)]
        assert entry.rejected

    asyncio.run(run())


def test_ab_lever_disables_batching(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_PLACEMENT_BATCH", "0")

    async def run():
        om = _map()
        r = PlacementResolver(conf=_conf())  # reads the env lever
        got = await asyncio.gather(*(r.afull(om, (1, ps))
                                     for ps in range(32)))
        for ps, g in enumerate(got):
            want = om.pg_to_up_acting_full((1, ps))
            assert _full_tuple(g) == _full_tuple(want)
        assert r.stats.placement_batch_lookups == 0

    asyncio.run(run())


def test_below_min_batch_resolves_host():
    async def run():
        om = _map()
        r = PlacementResolver(conf=_conf(min_batch=64), batch=True)
        got = await asyncio.gather(*(r.afull(om, (1, ps))
                                     for ps in range(8)))
        for ps, g in enumerate(got):
            want = om.pg_to_up_acting_full((1, ps))
            assert _full_tuple(g) == _full_tuple(want)
        assert r.stats.placement_batch_lookups == 0

    asyncio.run(run())


def test_prewarm_fills_whole_pool_tables():
    async def run():
        om = _map()
        r = PlacementResolver(conf=_conf(), batch=True)
        warmed = await r.prewarm(om, [1, 2])
        assert warmed == 48
        assert r.stats.placement_batch_lookups >= 2
        # every subsequent lookup is a hit
        m0 = r.stats.placement_cache_misses
        for ps in range(32):
            r.up_acting(om, (1, ps))
        assert r.stats.placement_cache_misses == m0

    asyncio.run(run())


def test_resend_lands_on_post_remap_primary():
    """Cluster tier: with the batched resolver armed on the op path,
    a primary dying mid-workload must re-target the resend onto the
    post-remap primary (the swarm-shaped epoch-correctness contract).
    """
    from ceph_tpu.cluster.vstart import TestCluster

    async def run():
        c = TestCluster(n_osds=5, out_interval=1.0)
        await c.start()
        c.client.conf.set("client_placement_batch_min", 1)
        pool_id = await c.client.create_pool(
            Pool(id=7, name="remap", size=3, min_size=2, pg_num=8,
                 crush_rule=0))
        await c.wait_active(30)
        await c.client._placement.prewarm(c.client.osdmap, [pool_id])
        payload = b"x" * 4096
        await c.client.write_full(pool_id, "obj", payload)
        pgid = c.client.osdmap.object_to_pg(pool_id, b"obj")
        _up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        await c.kill_osd(primary)
        # the next write's tick-resend must land on the NEW primary
        # once the map moves (down -> out reroutes the PG)
        c.client.op_timeout = 30.0
        await c.client.write_full(pool_id, "obj", payload * 2)
        got = await c.client.read(pool_id, "obj")
        assert got == payload * 2
        stats = c.client.placement_stats()
        assert stats["placement_epoch_invalidations"] >= 1
        new_primary = c.client._calc_target(
            c.client.osdmap.object_to_pg(pool_id, b"obj"))
        assert new_primary != primary
        await c.stop()

    asyncio.run(run())
