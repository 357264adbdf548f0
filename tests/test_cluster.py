"""Cluster integration: the SURVEY §7 minimum end-to-end slice and the
thrash scenarios (kill/revive/blackhole) of the qa tier, in-process.

Every test assembles mon + OSDs + client on a LocalBus; the EC pool path
runs striped writes through the batched device encode (on the virtual
CPU mesh under pytest) and repairs through minimum_to_decode + decode —
the ECBackend.cc:1539/2405 arc end to end.
"""
import asyncio

import numpy as np
import pytest

from ceph_tpu.cluster import TestCluster
from ceph_tpu.cluster.pg import NONE
from ceph_tpu.placement.osdmap import Pool

EC_PROFILE = {"plugin": "rs_tpu", "k": "3", "m": "2", "backend": "device"}


def run(coro):
    asyncio.run(asyncio.wait_for(coro, 120))


async def make_cluster(n=5):
    c = TestCluster(n_osds=n)
    await c.start()
    return c


async def make_ec_cluster(n=5):
    c = await make_cluster(n)
    await c.client.create_pool(
        Pool(id=2, name="ec", size=5, min_size=3, pg_num=8, crush_rule=1,
             type="erasure", ec_profile=dict(EC_PROFILE))
    )
    await c.wait_active(20)
    return c


def test_boot_and_health():
    async def t():
        c = await make_cluster(4)
        assert all(st.up for st in c.mon.osdmap.osds)
        await c.stop()

    run(t())


def test_replicated_write_read_delete():
    async def t():
        c = await make_cluster(4)
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=8, crush_rule=0)
        )
        await c.wait_active(20)
        payload = b"the quick brown fox" * 123
        await c.client.write_full(1, "obj", payload)
        assert await c.client.read(1, "obj") == payload
        assert await c.client.stat(1, "obj") == len(payload)
        # overwrite bumps the version and replaces content everywhere
        await c.client.write_full(1, "obj", b"short")
        assert await c.client.read(1, "obj") == b"short"
        await c.client.delete(1, "obj")
        with pytest.raises(KeyError):
            await c.client.read(1, "obj")
        await c.stop()

    run(t())


def test_replicated_survives_replica_loss():
    async def t():
        c = await make_cluster(4)
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=8, crush_rule=0)
        )
        await c.wait_active(20)
        await c.client.write_full(1, "obj", b"D" * 4096)
        pgid = c.client.osdmap.object_to_pg(1, b"obj")
        up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        replica = next(o for o in up if o != primary)
        await c.kill_osd(replica)
        await c.wait_down(replica, 20)
        assert await c.client.read(1, "obj") == b"D" * 4096
        # failure detection produced a new epoch marking it down
        assert not c.mon.osdmap.osds[replica].up
        await c.stop()

    run(t())


def test_replicated_primary_loss_client_resends():
    async def t():
        c = await make_cluster(5)
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=8, crush_rule=0)
        )
        await c.wait_active(20)
        await c.client.write_full(1, "obj", b"P" * 1000)
        pgid = c.client.osdmap.object_to_pg(1, b"obj")
        _, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        await c.kill_osd(primary)
        await c.wait_down(primary, 20)
        await c.wait_active(20)
        # Objecter recalculates the target from the new map and resends
        assert await c.client.read(1, "obj") == b"P" * 1000
        await c.stop()

    run(t())


def test_pool_create_spec_conflict_rejected():
    """A retried create with the SAME spec is idempotent; a same-name
    create with a DIFFERENT spec must fail EEXIST, not silently ack the
    existing pool's id (round-4 advisor finding)."""
    async def t():
        c = await make_cluster(4)
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=8, crush_rule=0)
        )
        # identical spec: idempotent OK, same id
        pid = await c.client.create_pool(
            Pool(id=-1, name="rep", size=3, pg_num=8, crush_rule=0)
        )
        assert pid == 1
        with pytest.raises(FileExistsError):
            await c.client.create_pool(
                Pool(id=-1, name="rep", size=2, pg_num=8, crush_rule=0)
            )
        await c.stop()

    run(t())


def test_duplicate_op_not_reexecuted():
    """The client tick-resends in-flight ops; a duplicate (src, tid)
    reaching the primary must NOT re-execute a non-idempotent verb
    (reqid reply-cache role). Drive the PG directly with two identical
    MOSDOp append messages and check the append applied once."""
    async def t():
        from ceph_tpu.cluster import messages as M

        c = await make_cluster(4)
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=8, crush_rule=0)
        )
        await c.wait_active(20)
        await c.client.write_full(1, "obj", b"base-")
        pgid = c.client.osdmap.object_to_pg(1, b"obj")
        _, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        osd = c.osds[primary]
        msg = M.MOSDOp(tid=990_001, pgid=pgid, oid=b"obj",
                       ops=[M.osd_op("append", data=b"tail")],
                       epoch=c.client.osdmap.epoch)
        pg = osd._pg_for_primary(pgid)
        await pg.do_op("client.0", msg)
        # network duplicate: same src, same tid — answered from the
        # reply cache, not re-applied
        await pg.do_op("client.0", msg)
        assert await c.client.read(1, "obj") == b"base-tail"
        # a FRESH tid is a genuinely new op and does apply
        msg2 = M.MOSDOp(tid=990_002, pgid=pgid, oid=b"obj",
                        ops=[M.osd_op("append", data=b"!")],
                        epoch=c.client.osdmap.epoch)
        await pg.do_op("client.0", msg2)
        assert await c.client.read(1, "obj") == b"base-tail!"
        await c.stop()

    run(t())


def test_ec_write_read_unaligned():
    async def t():
        c = await make_ec_cluster()
        data = bytes(range(256)) * 37  # 9472 B: pads within the stripe
        await c.client.write_full(2, "obj", data)
        assert await c.client.read(2, "obj") == data
        assert await c.client.stat(2, "obj") == len(data)
        # every live shard holds a chunk with a valid hinfo CRC
        pgid = c.client.osdmap.object_to_pg(2, b"obj")
        up, _ = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        held = 0
        for shard, osd_id in enumerate(up):
            if osd_id == NONE:
                continue
            store = c.stores[osd_id]
            cid = f"{pgid[0]}.{pgid[1]}s{shard}"
            if store.exists(cid, b"obj"):
                held += 1
        assert held == 5
        await c.stop()

    run(t())


def test_ec_degraded_read_two_losses():
    async def t():
        c = await make_ec_cluster()
        data = np.random.default_rng(3).integers(
            0, 256, 3 * 4096, dtype=np.uint8
        ).tobytes()
        await c.client.write_full(2, "obj", data)
        pgid = c.client.osdmap.object_to_pg(2, b"obj")
        up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        victims = [o for o in up if o != primary][:2]
        for v in victims:
            await c.kill_osd(v)
            await c.wait_down(v, 20)
        # k=3 of 5 shards remain: reconstruct on read, bit-exact
        assert await c.client.read(2, "obj") == data
        await c.stop()

    run(t())


def test_ec_recovery_on_revive():
    async def t():
        c = await make_ec_cluster()
        datas = {f"o{i}": bytes([i]) * (1024 * (i + 1)) for i in range(4)}
        for name, d in datas.items():
            await c.client.write_full(2, name, d)
        # find an OSD holding shards of pg of o0; kill it, write more,
        # revive: the PGLog delta drives chunk reconstruction pushes
        pgid = c.client.osdmap.object_to_pg(2, b"o0")
        up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        victim = next(o for o in up if o != primary)
        shard = up.index(victim)
        await c.kill_osd(victim)
        await c.wait_down(victim, 20)
        await c.client.write_full(2, "o0", b"NEW" * 2048)  # degraded write
        await c.revive_osd(victim)
        await c.wait_active(30)
        # revived shard must converge: its chunk decodes with the rest
        assert await c.client.read(2, "o0") == b"NEW" * 2048

        # the revived OSD's own shard was re-reconstructed bit-exact:
        # kill two OTHER members and force a read that needs it
        up2, primary2 = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        others = [o for o in up2
                  if o not in (victim, primary2) and o != NONE][:2]
        for o in others:
            await c.kill_osd(o)
            await c.wait_down(o, 20)
        assert await c.client.read(2, "o0") == b"NEW" * 2048
        await c.stop()

    run(t())


def test_replicated_delta_recovery_and_delete():
    async def t():
        c = await make_cluster(4)
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=4, crush_rule=0)
        )
        await c.wait_active(20)
        for i in range(6):
            await c.client.write_full(1, f"k{i}", b"x" * 512 + bytes([i]))
        pgid = c.client.osdmap.object_to_pg(1, b"k0")
        up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        victim = next(o for o in up if o != primary)
        await c.kill_osd(victim)
        await c.wait_down(victim, 20)
        await c.client.write_full(1, "k0", b"fresh")
        await c.client.delete(1, "k1")
        await c.revive_osd(victim)
        await c.wait_active(30)
        store = c.stores[victim]
        cid = f"{pgid[0]}.{pgid[1]}"
        # recovered write visible, recovered delete applied
        if store.exists(cid, b"k0"):
            assert bytes(store.read(cid, b"k0")) == b"fresh"
            assert not store.exists(cid, b"k1")
        assert await c.client.read(1, "k0") == b"fresh"
        with pytest.raises(KeyError):
            await c.client.read(1, "k1")
        await c.stop()

    run(t())


def test_backfill_after_log_trim():
    async def t():
        c = TestCluster(n_osds=4)
        await c.start()
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=1, crush_rule=0)
        )
        await c.wait_active(20)
        for o in c.osds:
            if o is not None:
                o.log_keep = 4  # tiny logs force the backfill path
        await c.client.write_full(1, "base", b"B")
        up, primary = c.mon.osdmap.pg_to_up_acting_osds((1, 0))
        victim = next(o for o in up if o != primary)
        await c.kill_osd(victim)
        await c.wait_down(victim, 20)
        # push far more writes than the log keeps -> delta impossible
        for i in range(12):
            await c.client.write_full(1, f"n{i}", bytes([i]) * 128)
        o = await c.revive_osd(victim)
        o.log_keep = 4
        await c.wait_active(30)
        store = c.stores[victim]
        have = set(store.list_objects("1.0")) - {b"_pgmeta"}
        assert {f"n{i}".encode() for i in range(12)} <= have
        await c.stop()

    run(t())


def test_mark_out_replaces_member():
    async def t():
        c = TestCluster(n_osds=5, out_interval=1.0)
        await c.start()
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=4, crush_rule=0)
        )
        await c.wait_active(20)
        await c.client.write_full(1, "obj", b"keepme" * 100)
        pgid = c.client.osdmap.object_to_pg(1, b"obj")
        up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        victim = next(o for o in up if o != primary)
        await c.kill_osd(victim)
        await c.wait_down(victim, 20)

        async def wait_out():
            while c.mon.osdmap.osds[victim].weight != 0:
                await asyncio.sleep(0.05)
        await asyncio.wait_for(wait_out(), 30)
        await c.wait_active(30)
        up2, _ = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        assert victim not in up2 and len([o for o in up2 if o != NONE]) == 3
        # the replacement member was backfilled
        assert await c.client.read(1, "obj") == b"keepme" * 100
        newcomer = next(o for o in up2 if o not in up)
        assert c.stores[newcomer].exists(f"{pgid[0]}.{pgid[1]}", b"obj")
        await c.stop()

    run(t())


def test_primary_crash_mid_fanout_survivors_converge():
    """round-3 review #6: kill the primary after SOME (not all) replicas
    committed a rep-op. The unacked entry lives on one survivor only;
    the new interval must converge both survivors to one authoritative
    state, the client's resend must land exactly once, and a scrub must
    come back clean — acks were never lied about."""
    async def t():
        c = await make_cluster(5)
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=4, crush_rule=0)
        )
        await c.wait_active(20)
        base = b"stable" * 500
        await c.client.write_full(1, "torn", base)
        pgid = c.mon.osdmap.object_to_pg(1, b"torn")
        acting, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        r1, r2 = [o for o in acting if o != primary]

        # blackhole r2: the primary's fan-out commits on r1 only
        c.bus.blackholes.add(f"osd.{r2}")
        newdata = b"half-committed" * 400
        wtask = asyncio.ensure_future(
            c.client.write_full(1, "torn", newdata))
        # let the rep-op land on r1 (but never on r2), then crash the
        # primary before it can gather all-ack or answer the client
        for _ in range(200):
            await asyncio.sleep(0.005)
            osd1 = c.osds[r1]
            pgs = [pg for pg in osd1.pgs.values()
                   if (pg.pgid[0], pg.pgid[1]) == pgid]
            if pgs and any(e.oid == b"torn" and e.version[1] >= 2
                           for e in pgs[0].log.entries):
                break
        await c.kill_osd(primary)
        c.bus.blackholes.discard(f"osd.{r2}")
        await c.wait_down(primary, 30)

        # the client's pending write must complete via the new interval
        await asyncio.wait_for(wtask, 60)
        assert await c.client.read(1, "torn") == newdata

        # survivors converged: same log head, same object bytes
        await c.wait_active(40)
        heads, versions = set(), set()
        for o in (r1, r2):
            for pg in c.osds[o].pgs.values():
                if (pg.pgid[0], pg.pgid[1]) == pgid:
                    heads.add(pg.log.head)
                    versions.add(
                        bytes(c.osds[o].store.read(pg.cid, b"torn")))
        assert len(heads) == 1, f"divergent survivor logs: {heads}"
        assert versions == {newdata}

        # the revived old primary (which applied locally pre-crash)
        # must also converge, not resurrect its unacked ordering
        await c.revive_osd(primary)
        await c.wait_active(40)
        assert await c.client.read(1, "torn") == newdata
        report = await c.scrub_pg(pgid)
        assert report["inconsistent"] == [], report
        await c.stop()

    run(t())


def test_primary_crash_no_replica_committed():
    """Same crash, but NO replica saw the rep-op (both blackholed):
    the entry exists only on the dead primary. The new interval serves
    the PRIOR state until the client's resend re-applies the write."""
    async def t():
        c = await make_cluster(5)
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=4, crush_rule=0)
        )
        await c.wait_active(20)
        base = b"old-state" * 300
        await c.client.write_full(1, "obj", base)
        pgid = c.mon.osdmap.object_to_pg(1, b"obj")
        acting, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        replicas = [o for o in acting if o != primary]
        for r in replicas:
            c.bus.blackholes.add(f"osd.{r}")
        newdata = b"never-acked" * 350
        wtask = asyncio.ensure_future(
            c.client.write_full(1, "obj", newdata))
        await asyncio.sleep(0.05)  # primary applied locally, fanout dark
        await c.kill_osd(primary)
        for r in replicas:
            c.bus.blackholes.discard(f"osd.{r}")
        await c.wait_down(primary, 30)
        await asyncio.wait_for(wtask, 60)  # resend lands on new primary
        assert await c.client.read(1, "obj") == newdata
        await c.wait_active(40)
        report = await c.scrub_pg(pgid)
        assert report["inconsistent"] == [], report
        await c.stop()

    run(t())
