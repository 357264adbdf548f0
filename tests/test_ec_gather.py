"""The shard gather shared by the EC read and the full shard rebuild.

Both run one plan -> fetch -> hedged fan-out -> demote loop; what
differs is data (the wanted positions, the excluded shard, the byte
range) and what to do when the plan starves. Each case breaks data
shard 2 of a k=4 m=2 object in a different way and checks, for both
callers, which shards the primary asked for and the bytes that came
back. Shard 0 is the primary's own and is read locally, so it never
shows up as a sub-read; the two ``local_*`` cases break it, where the
read and the rebuild differ on purpose: only a client read passes the
``ec_local_read`` fault site and kicks a repair of a rotten copy.
"""
import asyncio

import numpy as np
import pytest

from ceph_tpu.cluster import messages as M
from ceph_tpu.cluster.vstart import TestCluster
from ceph_tpu.placement.osdmap import Pool
from ceph_tpu.store import transaction as tx

POOL = 2
OID = b"obj"
BROKEN = 2  # the data shard the remote cases break
LOCAL = 0  # the primary's own shard, which the local cases break
TARGET = 5  # the parity shard the rebuild caller rebuilds


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 120))
    finally:
        loop.close()


class SubReadLog:
    """Records the shard position of every MECSubRead the primary
    sends; while ``fail`` is set those sends raise (a transport
    failure) and the failure reports to the mon are held back, so the
    map stays as it is."""

    def __init__(self, bus, primary: int):
        self.orig = bus.send
        self.src = f"osd.{primary}"
        self.shards: list[int] = []
        self.fail = False
        bus.send = self.send

    async def send(self, src, dst, msg):
        if src == self.src and isinstance(msg, M.MECSubRead):
            self.shards.append(msg.shard)
            if self.fail:
                raise ConnectionError(f"link to {dst} down")
        if self.fail and isinstance(msg, M.MFailure):
            return
        await self.orig(src, dst, msg)


async def call(pg, caller: str):
    """The caller under test, under the PG lock as in the cluster: the
    read returns the object's bytes, the rebuild shard TARGET's."""
    async with pg.lock:
        if caller == "read":
            data, _size = await pg.ec.read(OID, 0, -1)
            return bytes(data)
        chunk, _attrs = await pg.ec.rebuild(OID, TARGET)
        return bytes(chunk)


def repairs_queued(pg) -> set:
    return set(pg.ec._repairing)


def counter(c, name: str) -> int:
    return sum(o.perf.dump().get(name, 0) for o in c.osds if o is not None)


async def make_cluster():
    c = TestCluster(n_osds=6, fault_seed=3, out_interval=60.0)
    await c.start()
    await c.client.create_pool(
        Pool(id=POOL, name="ec", size=6, min_size=4, pg_num=1,
             crush_rule=1, type="erasure",
             ec_profile={"plugin": "rs_tpu", "k": "4", "m": "2"}))
    await c.wait_active(20)
    return c


def shard_state(c, up, pgid, pos: int):
    store = c.stores[up[pos]]
    cid = f"{pgid[0]}.{pgid[1]}s{pos}"
    return cid, store


#: case -> (remote sub-reads of the read, of the rebuild, error raised).
#: A plan member that fails leaves the first fan-out short, so it waits
#: out the hedge delay and fires every usable shard outside the plan:
#: the read has two (4, 5), the rebuild one (4; 5 is its target). A
#: version laggard answers in time, is demoted after the fan-out, and
#: the re-plan fetches 4 alone. A rebuild never meets the injected
#: local EIO, so its plan stays the three remote data shards.
CASES = {
    "eio": ([1, 2, 3, 4, 5], [1, 2, 3, 4], None),
    "hinfo_rot": ([1, 2, 3, 4, 5], [1, 2, 3, 4], None),
    "version_laggard": ([1, 2, 3, 4], [1, 2, 3, 4], None),
    "hedge_substitution": ([1, 2, 3, 4, 5], [1, 2, 3, 4], None),
    "transport_failure": ([1, 2, 3, 4, 5], [1, 2, 3, 4],
                          ConnectionError),
    "local_hinfo_rot": ([1, 2, 3, 4, 5], [1, 2, 3, 4], None),
    "local_eio": ([1, 2, 3, 4, 5], [1, 2, 3], None),
}
#: cases whose read kicks a repair, and of which shard
KICKED = {"eio": BROKEN, "hinfo_rot": BROKEN, "version_laggard": BROKEN,
          "local_hinfo_rot": LOCAL}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("caller", ["read", "rebuild"])
def test_gather_plan_and_bytes(caller, case):
    rng = np.random.default_rng(28)
    old = rng.integers(0, 256, 5 * 4 * 4096 + 77, dtype=np.uint8).tobytes()
    new = rng.integers(0, 256, 5 * 4 * 4096 + 77, dtype=np.uint8).tobytes()
    plan_read, plan_rebuild, error = CASES[case]

    async def t():
        c = await make_cluster()
        try:
            pgid = c.mon.osdmap.object_to_pg(POOL, OID)
            up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
            assert up[0] == primary
            pg = c.osds[primary].pgs[(pgid[0], pgid[1], 0)]
            holder = up[BROKEN]
            cid_b, store_b = shard_state(c, up, pgid, BROKEN)
            await c.client.write_full(POOL, "obj", old)
            saved = (bytes(store_b.read(cid_b, OID)),
                     dict(store_b.getattrs(cid_b, OID)))
            await c.client.write_full(POOL, "obj", new)
            cid_t, store_t = shard_state(c, up, pgid, TARGET)
            want = new if caller == "read" else bytes(
                store_t.read(cid_t, OID))

            if case == "eio":
                c.osds[holder].fault.arm("ec_sub_read", count=1, oid=OID)
            elif case == "hinfo_rot":
                c.osds[holder].fault.arm("ec_read_bitflip", count=1,
                                         oid=OID)
            elif case == "version_laggard":
                data, attrs = saved
                t_old = tx.Transaction()
                t_old.truncate(cid_b, OID, 0)
                t_old.write(cid_b, OID, 0, data)
                t_old.rmattrs(cid_b, OID)
                t_old.setattrs(cid_b, OID, attrs)
                store_b.queue_transaction(t_old)
            elif case == "hedge_substitution":
                c.faults.slow_osd([holder], scale=1.0, sigma=0.01)
            elif case == "local_hinfo_rot":
                c.osds[primary].fault.arm("ec_read_bitflip", count=1,
                                          oid=OID)
            elif case == "local_eio":
                c.osds[primary].fault.arm("ec_local_read", count=1,
                                          oid=OID)
            log = SubReadLog(c.bus, primary)
            log.fail = case == "transport_failure"
            crc0 = counter(c, "ec_read_crc_err")
            stale0 = counter(c, "ec_read_stale_shard")
            won0 = counter(c, "ec_hedges_won")

            if error is not None:
                with pytest.raises(error):
                    await call(pg, caller)
            else:
                assert await call(pg, caller) == want
            sent = sorted(log.shards)
            queued = repairs_queued(pg)
            log.fail = False
            c.faults.slow_osd([])

            assert sent == (plan_read if caller == "read"
                            else plan_rebuild)
            # a client read kicks a repair of the bad shard it met; a
            # rebuild reinstalls what it rebuilds and kicks nothing
            if case in KICKED and caller == "read":
                assert queued == {(OID, KICKED[case])}
            else:
                assert queued == set()
            if case in ("hinfo_rot", "local_hinfo_rot"):
                assert counter(c, "ec_read_crc_err") == crc0 + 1
            if case == "version_laggard":
                assert counter(c, "ec_read_stale_shard") == stale0 + 1
            if case == "hedge_substitution":
                assert counter(c, "ec_hedges_won") > won0
            for _ in range(100):  # let a queued repair finish
                if not repairs_queued(pg):
                    break
                await asyncio.sleep(0.05)
            assert await c.client.read(POOL, "obj") == new
        finally:
            await c.stop()

    run(t())
