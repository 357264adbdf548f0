"""RBD images whose data objects live in a pool of their own (``rbd
create --data-pool``): header, object map and lock in a replicated
pool, ``rbd_data.*`` in an erasure-coded k=4 m=2 pool, written through
the EC read-modify-write path. Checked against a bytearray image and a
numpy GF(2^8) reference encode (``benchmark/harness/reference.py``,
which imports nothing of the program)."""
import asyncio
import importlib.util
import os

import numpy as np
import pytest

from ceph_tpu.cluster.vstart import TestCluster
from ceph_tpu.osdc.striper import FileLayout
from ceph_tpu.placement.osdmap import Pool
from ceph_tpu.services import RBD
from ceph_tpu.services.rbd import ATTR_DATA_POOL
from ceph_tpu.utils import trace

META, DATA = 1, 2
K, M, SU = 4, 2, 4096
#: 4 stripes of k * SU per object
LAYOUT = FileLayout(stripe_unit=K * SU * 4, stripe_count=1,
                    object_size=K * SU * 4)
OBJ = LAYOUT.object_size


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "harness", "reference.py")
    spec = importlib.util.spec_from_file_location("ec_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 120))
    finally:
        loop.close()


async def make():
    c = TestCluster(n_osds=6)
    await c.start()
    await c.client.create_pool(
        Pool(id=META, name="rbd", size=3, min_size=2, pg_num=8,
             crush_rule=0))
    await c.client.create_pool(
        Pool(id=DATA, name="rbd-ec", size=K + M, min_size=K + 1, pg_num=8,
             crush_rule=1, type="erasure",
             ec_profile={"plugin": "rs_tpu", "k": str(K), "m": str(M),
                         "stripe_unit": str(SU)}))
    await c.wait_active(20)
    return c, RBD(c.client, META)


def payload(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def osd_sum(c, key: str):
    """A perf counter summed over live OSDs (time_avg: (sum, count))."""
    tot = [0.0, 0]
    for o in c.osds:
        if o is None:
            continue
        v = o.perf.dump()[key]
        if isinstance(v, dict):
            tot[0] += v["sum"]
            tot[1] += v["avgcount"]
        else:
            tot[0] += v
    return tot if isinstance(v, dict) else tot[0]


def stored_shards(c, pool: int, oid: bytes) -> dict:
    """{position: [(shard bytes, hinfo bytes)]} over every store."""
    out: dict = {}
    for st in c.stores:
        for cid in st.list_collections():
            if not cid.startswith(f"{pool}.") or "s" not in cid:
                continue
            if oid in {bytes(o) for o in st.list_objects(cid)}:
                pos = int(cid.rsplit("s", 1)[1])
                out.setdefault(pos, []).append(
                    (bytes(st.read(cid, oid)),
                     bytes(st.getattr(cid, oid, "hinfo"))))
    return out


def test_objects_split_between_pools():
    async def t():
        c, rbd = await make()
        await rbd.create("vol", 4 * OBJ, LAYOUT, data_pool=DATA)
        img = await rbd.open("vol")
        assert img.data_pool_id == DATA
        await img.write(OBJ - 100, payload(1, 300))
        await img.release_lock()
        meta = await c.client.list_objects(META)
        data = await c.client.list_objects(DATA)
        assert b"rbd_header.vol" in meta
        assert b"rbd_object_map.vol" in meta
        assert not [o for o in meta if o.startswith(b"rbd_data.")]
        assert sorted(data) == [b"rbd_data.vol.0000000000000000",
                                b"rbd_data.vol.0000000000000001"]
        hdr = await c.client.getxattrs(META, "rbd_header.vol")
        assert ATTR_DATA_POOL in hdr
        await c.stop()

    run(t())


def test_random_io_matches_bytearray():
    size = 3 * OBJ

    async def t():
        c, rbd = await make()
        await rbd.create("vol", size, LAYOUT, data_pool=DATA)
        img = await rbd.open("vol")
        want = bytearray(size)
        rng = np.random.default_rng(20261016)
        for i in range(24):
            if i % 2:  # 4 KiB aligned
                off = int(rng.integers(size // 4096)) * 4096
                n = 4096
            else:  # anywhere, any length, across objects
                off = int(rng.integers(size - 1))
                n = int(rng.integers(1, min(3 * SU * K, size - off) + 1))
            data = payload(100 + i, n)
            await img.write(off, data)
            want[off:off + n] = data
            roff = int(rng.integers(size - 1))
            rn = int(rng.integers(1, size - roff + 1))
            assert await img.read(roff, rn) == bytes(want[roff:roff + rn])
        assert await img.read(0, size) == bytes(want)
        await img.release_lock()
        await c.stop()

    run(t())


def test_shards_and_hinfo_match_reference_encode():
    ref = _reference()

    async def t():
        c, rbd = await make()
        await rbd.create("vol", 2 * OBJ, LAYOUT, data_pool=DATA)
        img = await rbd.open("vol")
        want = bytearray(2 * OBJ)
        await img.write(0, payload(7, OBJ))
        want[:OBJ] = payload(7, OBJ)
        # partial-stripe overwrites in object 0, a sparse object 1
        for off, n in ((4096, 4096), (SU * K + 10, 999), (OBJ + 5000, 7)):
            data = payload(off, n)
            await img.write(off, data)
            want[off:off + n] = data
        await img.release_lock()
        for objno in (0, 1):
            oid = f"rbd_data.vol.{objno:016x}".encode()
            n = await c.client.stat(DATA, oid)
            obj = bytes(want[objno * OBJ:objno * OBJ + n])
            shards = ref.shards(obj, K, M, SU)
            crcs = ref.hinfo(shards, SU)
            have = stored_shards(c, DATA, oid)
            assert sorted(have) == list(range(K + M))
            for pos in range(K + M):
                [(data, hinfo)] = have[pos]
                assert data == shards[pos].tobytes(), (objno, pos)
                assert hinfo == crcs[pos], (objno, pos)
        await c.stop()

    run(t())


def test_snapshot_id_comes_from_data_pool():
    async def t():
        c, rbd = await make()
        await rbd.create("vol", OBJ, LAYOUT, data_pool=DATA)
        img = await rbd.open("vol")
        old = payload(3, OBJ)
        await img.write(0, old)
        seq_meta = c.mon.osdmap.pools[META].snap_seq
        seq_data = c.mon.osdmap.pools[DATA].snap_seq
        await img.snap_create("s1")
        assert c.mon.osdmap.pools[META].snap_seq == seq_meta
        assert c.mon.osdmap.pools[DATA].snap_seq == seq_data + 1
        assert img.snap_ids["s1"] == seq_data + 1
        await img.write(4096, b"N" * 4096)
        snap = await rbd.open("vol", snap="s1")
        assert await snap.read(0, OBJ) == old
        head = await img.read(0, OBJ)
        assert head == old[:4096] + b"N" * 4096 + old[8192:]
        await img.release_lock()
        await c.stop()

    run(t())


def test_clone_copies_up_from_parent_data_pool():
    async def t():
        c, rbd = await make()
        await rbd.create("base", 2 * OBJ, LAYOUT, data_pool=DATA)
        base = await rbd.open("base")
        old = payload(5, 2 * OBJ)
        await base.write(0, old)
        await base.snap_create("gold")
        await base.write(0, b"X" * 100)  # after the snap: not cloned
        await base.release_lock()
        await rbd.clone("base", "gold", "child")
        child = await rbd.open("child")
        assert child.data_pool_id == DATA
        assert await child.read(OBJ, 50) == old[OBJ:OBJ + 50]
        await child.write(OBJ + 10, b"c" * 20)  # copy-up of object 1
        want = old[OBJ:OBJ + 10] + b"c" * 20 + old[OBJ + 30:2 * OBJ]
        assert await child.read(OBJ, OBJ) == want
        assert await child.read(0, 200) == old[:200]
        assert b"rbd_data.child.0000000000000001" in \
            await c.client.list_objects(DATA)
        await child.release_lock()
        await c.stop()

    run(t())


def test_deep_copy_and_migration_into_a_data_pool():
    async def t():
        c, rbd = await make()
        await rbd.create("src", 2 * OBJ, LAYOUT)
        src = await rbd.open("src")
        old = payload(12, OBJ + 999)
        await src.write(0, old)
        await src.snap_create("s")
        await src.write(0, b"H" * 10)
        await src.release_lock()
        await rbd.deep_copy("src", "copy", data_pool=DATA)
        copy = await rbd.open("copy")
        assert copy.data_pool_id == DATA
        assert await copy.read(0, len(old)) == b"H" * 10 + old[10:]
        snap = await rbd.open("copy", snap="s")
        assert await snap.read(0, len(old)) == old
        await rbd.migration_prepare("src", "moved", data_pool=DATA)
        await rbd.migration_execute("moved")
        await rbd.migration_commit("moved")
        moved = await rbd.open("moved")
        assert await moved.read(0, len(old)) == b"H" * 10 + old[10:]
        data = await c.client.list_objects(DATA)
        assert b"rbd_data.copy.0000000000000001" in data
        assert b"rbd_data.moved.0000000000000001" in data
        assert not [o for o in await c.client.list_objects(META)
                    if o.startswith((b"rbd_data.copy", b"rbd_data.moved"))]
        await c.stop()

    run(t())


def test_rmw_counters_and_stage_for_a_4k_overwrite():
    async def t():
        c, rbd = await make()
        await rbd.create("vol", OBJ, LAYOUT, data_pool=DATA)
        img = await rbd.open("vol")
        await img.write(0, payload(9, OBJ))
        keys = ("ec_user_bytes_written", "ec_shard_bytes_written",
                "ec_rmw_read_bytes")
        before = [osd_sum(c, k) for k in keys]
        lat0 = osd_sum(c, "op_rmw_read_lat")
        await img.write(SU * K + 4096, payload(10, 4096))
        user, shard, read = (osd_sum(c, k) - b
                             for k, b in zip(keys, before))
        lat1 = osd_sum(c, "op_rmw_read_lat")
        assert user == 4096
        assert shard / user == 6.0  # one stripe's k+m cells
        assert read / user == 4.0   # the stripe's k old cells
        assert lat1[1] - lat0[1] == 1 and lat1[0] > lat0[0]
        events = [e["event"] for o in c.osds if o is not None
                  for op in o.optracker.dump_historic_ops()["ops"]
                  for e in op["events"]]
        assert "rmw_read_done" in events
        spans = [s for s in trace.get_tracer(c.client.name).dump()
                 if s["name"] == "rbd.write"]
        assert spans[-1]["tags"] == {"image": "vol",
                                     "offset": str(SU * K + 4096),
                                     "length": "4096"}
        await img.release_lock()
        await c.stop()

    run(t())


def test_image_without_data_pool_as_before():
    async def t():
        c, rbd = await make()
        await rbd.create("plain", 2 * OBJ, LAYOUT)
        img = await rbd.open("plain")
        assert img.data_pool_id == META
        data = payload(11, OBJ + 123)
        await img.write(77, data)
        assert await img.read(77, len(data)) == data
        seq_meta = c.mon.osdmap.pools[META].snap_seq
        await img.snap_create("s")
        assert c.mon.osdmap.pools[META].snap_seq == seq_meta + 1
        await img.release_lock()
        hdr = await c.client.getxattrs(META, "rbd_header.plain")
        assert ATTR_DATA_POOL not in hdr
        assert b"rbd_data.plain.0000000000000000" in \
            await c.client.list_objects(META)
        assert await c.client.list_objects(DATA) == []
        with pytest.raises(KeyError):
            await rbd.create("nowhere", OBJ, LAYOUT, data_pool=99)
        await c.stop()

    run(t())
