"""An EC primary decides a fresh object's absence on its own shard.

The reference's primary reads the object info from its own shard
(PrimaryLogPG::get_object_context over a local attr read) and never asks
peers whether an object exists: a local ENOENT is authoritative unless
the object is missing. The metadata probe (length-0 sub-reads to every
peer) runs only where the primary's shard cannot decide: the PG is not
an active primary, the object is on the ``missing`` record, or a shard
file exists without its size attr (a torn write).
"""
import asyncio

import numpy as np

from ceph_tpu.cluster.client import ObjectOperation
from ceph_tpu.cluster.vstart import TestCluster
from ceph_tpu.placement.osdmap import Pool
from ceph_tpu.store import transaction as tx

POOL = 2


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 120))
    finally:
        loop.close()


async def make_ec():
    c = TestCluster(n_osds=5)
    await c.start()
    await c.client.create_pool(
        Pool(id=POOL, name="ec", size=5, min_size=3, pg_num=4,
             crush_rule=1, type="erasure",
             ec_profile={"plugin": "rs_tpu", "k": "3", "m": "2"}))
    await c.wait_active(20)
    return c


class SubReadSpy:
    """Counts the MECSubRead messages the bus carries."""

    def __init__(self, bus):
        self.orig = bus.send
        self.sub_reads = 0
        bus.send = self.send

    async def send(self, src, dst, msg):
        if type(msg).__name__ == "MECSubRead":
            self.sub_reads += 1
        await self.orig(src, dst, msg)


def meta_counts(c) -> tuple[int, int]:
    """(ec_meta_probe, ec_meta_local) summed over live OSDs."""
    probe = local = 0
    for o in c.osds:
        if o is None:
            continue
        d = o.perf.dump()
        probe += d["ec_meta_probe"]
        local += d["ec_meta_local"]
    return probe, local


def primary_pg(c, name: bytes):
    """The primary OSD's PG instance that serves ``name``."""
    pgid = c.mon.osdmap.object_to_pg(POOL, name)
    up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
    osd = c.osds[primary]
    return osd.pgs[(pgid[0], pgid[1], up.index(primary))]


def payload(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def test_fresh_write_sends_no_probe():
    """A write_full of a fresh name decides absence on the primary's
    own shard: no sub-read leaves it, and the object reads back
    byte-exact with its size."""
    data = payload(1, 3 * 4096 * 2 + 123)

    async def t():
        c = await make_ec()
        spy = SubReadSpy(c.bus)
        p0, l0 = meta_counts(c)
        await c.client.write_full(POOL, "fresh", data)
        p1, l1 = meta_counts(c)
        assert spy.sub_reads == 0
        assert (p1 - p0, l1 - l0) == (0, 1)
        assert await c.client.read(POOL, "fresh") == data
        assert await c.client.stat(POOL, "fresh") == len(data)
        await c.stop()

    run(t())


def test_absent_read_and_stat_are_local_enoent():
    """A read and a stat of an absent EC object still answer ENOENT,
    decided on the primary's shard with no probe."""
    async def t():
        c = await make_ec()
        spy = SubReadSpy(c.bus)
        p0, l0 = meta_counts(c)
        for op in (c.client.read, c.client.stat):
            try:
                await op(POOL, "never-written")
            except KeyError:
                pass
            else:
                raise AssertionError(f"{op.__name__} found no ENOENT")
        p1, l1 = meta_counts(c)
        assert spy.sub_reads == 0
        assert (p1 - p0, l1 - l0) == (0, 2)
        await c.stop()

    run(t())


def test_missing_object_still_probes_and_keeps_peer_xattrs():
    """A primary whose own shard lacks an object its ``missing`` set
    holds asks the peers: the write sees the object's size and the
    user xattrs the peers hold, and the new bytes read back."""
    old = payload(2, 3 * 4096 * 3)
    new = payload(3, 3 * 4096 + 17)

    async def t():
        c = await make_ec()
        await c.client.write_full(POOL, "held", old)
        await c.client.setxattr(POOL, "held", "color", b"blue")
        pg = primary_pg(c, b"held")
        version = pg._object_version(b"held")
        t_rm = tx.Transaction()
        t_rm.remove(pg.cid, b"held")
        pg.osd.store.queue_transaction(t_rm)
        pg.missing[b"held"] = version
        assert not pg.ec.absent_on_own_shard(b"held")
        spy = SubReadSpy(c.bus)
        p0, l0 = meta_counts(c)
        outs = await c.client.operate(
            POOL, "held", ObjectOperation().write_full(new).getxattr("color"))
        p1, l1 = meta_counts(c)
        assert (p1 - p0, l1 - l0) == (1, 0)
        assert spy.sub_reads == 4  # every other member of k+m = 5
        assert outs[-1] == b"blue"
        assert b"held" not in pg.missing  # the full rewrite covered it
        assert await c.client.read(POOL, "held") == new
        assert await c.client.stat(POOL, "held") == len(new)
        await c.stop()

    run(t())


def test_torn_shard_without_size_still_probes():
    """A shard file without the size attr (the torn-write shape) is not
    an absence: the primary probes, and a write over it lands whole."""
    data = payload(4, 3 * 4096 + 5)

    async def t():
        c = await make_ec()
        pg = primary_pg(c, b"torn")
        t_touch = tx.Transaction()
        if pg.cid not in pg.osd.store.list_collections():
            t_touch.create_collection(pg.cid)
        t_touch.touch(pg.cid, b"torn")
        pg.osd.store.queue_transaction(t_touch)
        assert not pg.ec.absent_on_own_shard(b"torn")
        p0, l0 = meta_counts(c)
        await c.client.write_full(POOL, "torn", data)
        p1, l1 = meta_counts(c)
        assert (p1 - p0, l1 - l0) == (1, 0)
        assert await c.client.read(POOL, "torn") == data
        await c.stop()

    run(t())


def test_only_an_active_primary_decides_alone():
    """The guard holds only on an active primary: a replica's shard or
    a primary still peering cannot rule an object out."""
    async def t():
        c = await make_ec()
        pg = primary_pg(c, b"x")
        assert pg.ec.absent_on_own_shard(b"x")
        replicas = [p for o in c.osds if o is not None
                    for p in o.pgs.values()
                    if p.pgid == pg.pgid and not p.is_primary()]
        assert replicas
        assert not any(p.ec.absent_on_own_shard(b"x") for p in replicas)
        pg.state = "peering"
        try:
            assert not pg.ec.absent_on_own_shard(b"x")
        finally:
            pg.state = "active"
        await c.stop()

    run(t())
