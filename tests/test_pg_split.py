"""PG split + pg_autoscaler: live pg_num growth (PG.cc:546 split_into
role) with IO continuing, pgp_num re-placement, and the mgr loop.

Acceptance (round-2 review item 6): a pool goes 8 -> 32 PGs under load
with no lost or misplaced-forever objects.
"""
import asyncio

import numpy as np
import pytest

from ceph_tpu.cluster import autoscaler
from ceph_tpu.cluster.vstart import TestCluster
from ceph_tpu.placement.osdmap import Pool

EC_PROFILE = {"plugin": "rs_tpu", "k": "3", "m": "2"}


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 180))
    finally:
        loop.close()


async def make(pool_type="replicated", n=5, pg_num=8):
    c = TestCluster(n_osds=n)
    await c.start()
    if pool_type == "replicated":
        await c.client.create_pool(
            Pool(id=1, name="p", size=3, pg_num=pg_num, crush_rule=0))
        pid = 1
    else:
        await c.client.create_pool(
            Pool(id=2, name="p", size=5, min_size=3, pg_num=pg_num,
                 crush_rule=1, type="erasure",
                 ec_profile=dict(EC_PROFILE)))
        pid = 2
    await c.wait_active(20)
    return c, pid


@pytest.mark.parametrize("pool_type", ["replicated", "erasure"])
def test_split_8_to_32_under_load(pool_type):
    async def t():
        c, pid = await make(pool_type)
        rng = np.random.default_rng(3)
        objs = {}
        for i in range(40):
            name = f"pre{i}"
            objs[name] = bytes(rng.integers(0, 256, 2000 + 17 * i,
                                            dtype=np.uint8))
            await c.client.write_full(pid, name, objs[name])

        stop = asyncio.Event()
        written_during: dict[str, bytes] = {}

        async def writer(wid):
            i = 0
            while not stop.is_set():
                name = f"live{wid}-{i}"
                data = bytes(rng.integers(0, 256, 1500,
                                          dtype=np.uint8))
                await c.client.write_full(pid, name, data)
                written_during[name] = data
                i += 1
                await asyncio.sleep(0)

        writers = [asyncio.ensure_future(writer(w)) for w in range(3)]
        await asyncio.sleep(0.1)
        # the live split: 8 -> 32 while writes keep flowing
        await c.client.set_pool_param(pid, "pg_num", 32)
        await c.wait_active(30)
        await asyncio.sleep(0.2)
        stop.set()
        await asyncio.gather(*writers)
        assert c.mon.osdmap.pools[pid].pg_num == 32

        objs.update(written_during)
        assert len(written_during) > 0
        # every object readable, nothing lost or duplicated
        for name, data in objs.items():
            assert await c.client.read(pid, name) == data, name
        listed = await c.client.list_objects(pid)
        assert sorted(listed) == sorted(n.encode() for n in objs)

        # phase 2: re-place the children and verify again
        await c.client.set_pool_param(pid, "pgp_num", 32)
        await c.wait_active(40)
        for name, data in objs.items():
            assert await c.client.read(pid, name) == data, name
        await c.stop()

    run(t())


def test_split_preserves_snapshots():
    async def t():
        c, pid = await make("replicated")
        v1 = b"epoch-one" * 300
        await c.client.write_full(pid, "o", v1)
        snapid = await c.client.selfmanaged_snap_create(pid)
        await c.client.write_full(pid, "o", b"epoch-two" * 100,
                                  snapc=(snapid, [snapid]))
        await c.client.set_pool_param(pid, "pg_num", 32)
        await c.client.set_pool_param(pid, "pgp_num", 32)
        await c.wait_active(40)
        # the clone migrated WITH its head (head-oid hashing)
        assert await c.client.read(pid, "o") == b"epoch-two" * 100
        assert await c.client.read(pid, "o", snapid=snapid) == v1
        await c.stop()

    run(t())


def test_split_survives_member_failure():
    async def t():
        c, pid = await make("replicated")
        rng = np.random.default_rng(9)
        objs = {f"k{i}": bytes(rng.integers(0, 256, 3000, dtype=np.uint8))
                for i in range(24)}
        for n_, d in objs.items():
            await c.client.write_full(pid, n_, d)
        await c.client.set_pool_param(pid, "pg_num", 16)
        await c.client.set_pool_param(pid, "pgp_num", 16)
        await c.wait_active(40)
        victim = 1
        await c.kill_osd(victim)
        await c.wait_down(victim, 20)
        for n_, d in objs.items():
            assert await c.client.read(pid, n_) == d
        await c.revive_osd(victim)
        await c.wait_active(40)
        for n_, d in objs.items():
            assert await c.client.read(pid, n_) == d
        await c.stop()

    run(t())


def test_pg_num_validation():
    async def t():
        c, pid = await make("replicated")
        with pytest.raises(IOError):
            await c.client.set_pool_param(pid, "pg_num", 4)  # shrink
        with pytest.raises(IOError):
            await c.client.set_pool_param(pid, "pg_num", 24)  # not pow2
        with pytest.raises(IOError):
            await c.client.set_pool_param(pid, "pgp_num", 64)  # > pg_num
        await c.stop()

    run(t())


# --------------------------------------------------------- autoscaler


class _FakePool:
    def __init__(self, pid, pg_num, pgp_num, size):
        self.id, self.pg_num, self.pgp_num, self.size = \
            pid, pg_num, pgp_num, size


class _FakeOSDState:
    def __init__(self):
        self.up, self.weight = True, 0x10000


class _FakeMap:
    def __init__(self, pools, n_osds):
        self.pools = {p.id: p for p in pools}
        self.osds = [_FakeOSDState() for _ in range(n_osds)]


def test_autoscaler_plan():
    # 32 OSDs, one size-3 pool at pg_num 8: budget 32*100/1 / 3 ~ 1066
    # -> pow2 1024 >= 3*8: grow
    m = _FakeMap([_FakePool(1, 8, 8, 3)], 32)
    assert autoscaler.plan(m, 100) == [(1, "pg_num", 1024)]
    # pgp lag: catch-up action, no further growth this round
    m = _FakeMap([_FakePool(1, 32, 8, 3)], 32)
    assert autoscaler.plan(m, 100) == [(1, "pgp_num", 32)]
    # close to ideal: no flapping
    m = _FakeMap([_FakePool(1, 512, 512, 3)], 32)
    assert autoscaler.plan(m, 100) == []


def test_autoscaler_end_to_end():
    async def t():
        c, pid = await make("replicated", pg_num=4)
        for i in range(10):
            await c.client.write_full(pid, f"o{i}", b"x" * 500)
        # round 1 grows pg_num; round 2 catches pgp_num up
        r1 = await c.mgr.autoscale_once(target_per_osd=64)
        assert any(a[1] == "pg_num" for a in r1["actions"])
        await c.wait_active(40)
        r2 = await c.mgr.autoscale_once(target_per_osd=64)
        assert any(a[1] == "pgp_num" for a in r2["actions"])
        await c.wait_active(40)
        pool = c.mon.osdmap.pools[pid]
        assert pool.pg_num > 4 and pool.pgp_num == pool.pg_num
        for i in range(10):
            assert await c.client.read(pid, f"o{i}") == b"x" * 500
        await c.stop()

    run(t())


@pytest.mark.parametrize("pool_type", ["replicated", "erasure"])
def test_merge_32_to_8_round_trip_under_load(pool_type):
    """round-3 review #5 (PG.cc:571 merge_from role): 8 -> 32 -> 8 round
    trip with writers flowing; pgp_num collapses first (co-location),
    then pg_num halves fold collections. No object lost, listing
    exact."""
    async def t():
        c, pid = await make(pool_type)
        rng = np.random.default_rng(13)
        objs = {}
        for i in range(40):
            name = f"pre{i}"
            objs[name] = bytes(rng.integers(0, 256, 2500 + 11 * i,
                                            dtype=np.uint8))
            await c.client.write_full(pid, name, objs[name])
        # grow 8 -> 32 (split + re-place)
        await c.client.set_pool_param(pid, "pg_num", 32)
        await c.client.set_pool_param(pid, "pgp_num", 32)
        await c.wait_active(40)

        stop = asyncio.Event()
        written_during: dict[str, bytes] = {}

        async def writer(wid):
            i = 0
            while not stop.is_set():
                name = f"live{wid}-{i}"
                data = bytes(rng.integers(0, 256, 1200, dtype=np.uint8))
                await c.client.write_full(pid, name, data)
                written_during[name] = data
                i += 1
                await asyncio.sleep(0)

        writers = [asyncio.ensure_future(writer(w)) for w in range(3)]
        await asyncio.sleep(0.1)
        # the shrink: placement collapses, data migrates off the
        # pins, THEN collections fold (the mon refuses earlier)
        await c.client.set_pool_param(pid, "pgp_num", 8)
        await c.wait_clean(60)
        await c.client.set_pool_param(pid, "pg_num", 8)
        await c.wait_active(40)
        await asyncio.sleep(0.2)
        stop.set()
        await asyncio.gather(*writers)
        assert c.mon.osdmap.pools[pid].pg_num == 8
        assert c.mon.osdmap.pools[pid].pgp_num == 8

        objs.update(written_during)
        assert len(written_during) > 0
        for name, data in objs.items():
            assert await c.client.read(pid, name) == data, name
        listed = await c.client.list_objects(pid)
        assert sorted(listed) == sorted(n.encode() for n in objs)
        # and the pool still takes IO on the merged PGs
        await c.client.write_full(pid, "post-merge", b"alive")
        assert await c.client.read(pid, "post-merge") == b"alive"
        await c.stop()

    run(t())


def test_merge_preserves_snapshots():
    """Clones ride the merge with their heads and snap reads still
    resolve afterwards."""
    async def t():
        c, pid = await make("replicated", pg_num=16)
        v1 = b"first-era" * 400
        await c.client.write_full(pid, "o", v1)
        snapid = await c.client.selfmanaged_snap_create(pid)
        await c.client.write_full(pid, "o", b"second-era" * 150,
                                  snapc=(snapid, [snapid]))
        await c.client.set_pool_param(pid, "pgp_num", 4)
        await c.wait_clean(60)
        await c.client.set_pool_param(pid, "pg_num", 4)
        await c.wait_active(40)
        assert await c.client.read(pid, "o") == b"second-era" * 150
        assert await c.client.read(pid, "o", snapid=snapid) == v1
        await c.stop()

    run(t())


def test_autoscaler_plans_shrink_sequence():
    """The planner emits pgp_num-then-pg_num for oversized pools."""
    from ceph_tpu.cluster import autoscaler
    from ceph_tpu.placement import crushmap as cm
    from ceph_tpu.placement.osdmap import OSDMap

    crush = cm.build_flat(3)
    crush.add_rule(cm.flat_firstn_rule(0))
    m = OSDMap(crush, 3)
    m.add_pool(Pool(id=1, name="fat", size=3, pg_num=512, pgp_num=512,
                    crush_rule=0))
    # 3 osds * 100 target / 1 pool / size 3 = 100 -> ideal 64 << 512/3
    acts = autoscaler.plan(m, target_per_osd=100)
    assert acts == [(1, "pgp_num", 64)]
    m.pools[1].pgp_num = 64
    acts = autoscaler.plan(m, target_per_osd=100)
    assert acts == [(1, "pg_num", 64)]
    m.pools[1].pg_num = 64
    assert autoscaler.plan(m, target_per_osd=100) == []
