"""The shard transactions of an EC write, built once per op.

``ECBackend.write`` builds every shard's transaction from per-op
tables: the CRC patch table of all shards at once, and one run list of
the touched stripes that every shard writes whole, zero cells
included, with the truncate to a new stripe count after the writes.
Each case below checks what a store holds afterwards, shard by shard,
against the plain numpy reference of ``benchmark/harness/reference.py``
(every data and parity shard file, every hinfo CRC, the size and
version attrs, and the client's read-back), and what the transactions
carried (writes, truncates, the shard byte counter).
"""
import asyncio
import importlib.util
import os

import numpy as np
import pytest

from ceph_tpu.cluster.ec_backend import ECBackend, _stripe_runs
from ceph_tpu.cluster.osd_types import (ATTR_HINFO, ATTR_SIZE, ATTR_V,
                                        USER_ATTR)
from ceph_tpu.cluster.vstart import TestCluster
from ceph_tpu.placement.osdmap import Pool
from ceph_tpu.store import transaction as tx
from ceph_tpu.utils import denc

POOL = 2
SU = 4096


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "harness", "reference.py")
    spec = importlib.util.spec_from_file_location("ec_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 120))
    finally:
        loop.close()


async def make_ec(k: int, m: int, backend: str = "device"):
    c = TestCluster(n_osds=k + m)
    await c.start()
    await c.client.create_pool(
        Pool(id=POOL, name="ec", size=k + m, min_size=k + 1, pg_num=4,
             crush_rule=1, type="erasure",
             ec_profile={"plugin": "rs_tpu", "k": str(k), "m": str(m),
                         "backend": backend}))
    await c.wait_active(20)
    return c


def payload(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(
        1, 256, n, dtype=np.uint8).tobytes()


def shard_bytes(c) -> int:
    """ec_shard_bytes_written over live OSDs."""
    return sum(o.perf.dump()["ec_shard_bytes_written"]
               for o in c.osds if o is not None)


class TxnSpy:
    """Records the shard transactions of every EC write fan-out:
    [{position: [(op code, args)]}] in fan-out order."""

    def __init__(self, monkeypatch):
        self.writes: list[dict[int, list]] = []
        orig = ECBackend.fanout

        async def fanout(be, oid, entries, shard_txns, *a, **kw):
            self.writes.append({
                pos: [(op.code, dict(op.args)) for op in t.ops]
                for pos, t in shard_txns.items()})
            return await orig(be, oid, entries, shard_txns, *a, **kw)

        monkeypatch.setattr(ECBackend, "fanout", fanout)

    def last(self) -> dict[int, list]:
        return self.writes[-1]


def truncates(ops) -> list[int]:
    return [a["size"] for code, a in ops if code == tx.OP_TRUNCATE]


def truncate_after_writes(ops) -> bool:
    """No write follows the transaction's truncate."""
    codes = [code for code, _a in ops]
    return tx.OP_WRITE not in codes[codes.index(tx.OP_TRUNCATE):]


def write_cells(ops) -> list[int]:
    """Stripe numbers the shard transaction's writes carry."""
    out = []
    for code, a in ops:
        if code == tx.OP_WRITE:
            s0 = a["offset"] // SU
            out += range(s0, s0 + len(a["data"]) // SU)
    return out


def check_stored(c, oid: bytes, want: bytes, k: int, m: int) -> None:
    """Every shard file, hinfo, size and version attr of ``oid``
    against the reference encode of ``want``."""
    shards = REF.shards(want, k, m, SU)
    crcs = REF.hinfo(shards, SU)
    have: dict[int, list] = {}
    for store in c.stores:
        for cid in store.list_collections():
            if not cid.startswith(f"{POOL}.") or "s" not in cid:
                continue
            if oid not in {bytes(o) for o in store.list_objects(cid)}:
                continue
            have.setdefault(int(cid.rsplit("s", 1)[1]), []).append((
                bytes(store.read(cid, oid)),
                bytes(store.getattr(cid, oid, ATTR_HINFO)),
                denc.dec_u64(store.getattr(cid, oid, ATTR_SIZE), 0)[0],
                bytes(store.getattr(cid, oid, ATTR_V))))
    assert sorted(have) == list(range(k + m))
    versions = set()
    for pos in range(k + m):
        [(data, hinfo, size, ver)] = have[pos]
        assert data == shards[pos].tobytes(), pos
        assert hinfo == crcs[pos], pos
        assert size == len(want), pos
        versions.add(ver)
    assert len(versions) == 1


# (case, k, m, touched stripes of the last write, old stripe count):
# each case is a sequence of client ops on one object; every shard of
# its last write writes the touched stripes, zero cells included, and
# truncates to the new stripe count after the writes when that count
# changes
CASES = [
    # fresh 5-stripe object, no zero cell
    ("fresh_whole", 8, 3, [0, 1, 2, 3, 4], 0),
    # fresh, all-zero data cells in the middle and at the unaligned
    # tail: written like any other cell
    ("fresh_zero_cells", 8, 3, [0, 1, 2, 3, 4], 0),
    # append from an unaligned old end to a stripe boundary: the old
    # last stripe is re-encoded, every new stripe written
    ("grow_append", 8, 3, [2, 3, 4], 3),
    # a write that leaves a hole past the old end: the truncate
    # zero-fills stripe 3
    ("grow_sparse", 8, 3, [4], 3),
    ("shrink", 8, 3, [1], 3),
    # 4 KiB overwrite inside the object: the read-modify-write
    ("rmw_4k", 8, 3, [1], 3),
    # xattr-only mutation: no stripe touched (T = 0)
    ("xattr_only", 8, 3, [], 3),
    # the 4 KiB fresh object of a k4m2 pool: three zero data cells
    ("fresh_4k_k4m2", 4, 2, [0], 0),
]


@pytest.mark.parametrize("case,k,m,touched,old_nst", CASES,
                         ids=[c[0] for c in CASES])
def test_shard_txns_match_reference(case, k, m, touched, old_nst,
                                    monkeypatch):
    width = k * SU

    async def t():
        c = await make_ec(k, m)
        spy = TxnSpy(monkeypatch)
        name = "obj"
        want = b""
        if case == "fresh_whole":
            want = payload(1, 5 * width)
        elif case == "fresh_zero_cells":
            b = bytearray(payload(2, 4 * width + 3 * SU + 100))
            b[width + 2 * SU: width + 3 * SU] = bytes(SU)  # stripe 1
            b[2 * width + SU: 2 * width + 3 * SU] = bytes(2 * SU)
            want = bytes(b)
        elif case == "fresh_4k_k4m2":
            want = payload(3, 4096)
        else:
            want = payload(4, 2 * width + 5000)
            await c.client.write_full(POOL, name, want)
        b0 = shard_bytes(c)
        if case.startswith("fresh"):
            await c.client.write_full(POOL, name, want)
        elif case == "grow_append":
            tail = payload(5, 3 * width - 5000)  # ends on a stripe
            await c.client.append(POOL, name, tail)
            want += tail
        elif case == "grow_sparse":
            off = len(want) + 2 * width
            await c.client.write(POOL, name, off, payload(6, 3000))
            want = want + bytes(off - len(want)) + payload(6, 3000)
        elif case == "shrink":
            await c.client.truncate(POOL, name, width + 777)
            want = want[:width + 777]
        elif case == "rmw_4k":
            off = width + 3 * SU + 512
            await c.client.write(POOL, name, off, payload(7, 4096))
            b = bytearray(want)
            b[off:off + 4096] = payload(7, 4096)
            want = bytes(b)
        elif case == "xattr_only":
            await c.client.setxattr(POOL, name, "color", b"blue")
        txns = spy.last()
        assert sorted(txns) == list(range(k + m))
        assert shard_bytes(c) - b0 == (k + m) * len(touched) * SU

        new_nst = -(-len(want) // width)
        for ops in txns.values():
            assert write_cells(ops) == touched
            if new_nst == old_nst:
                assert truncates(ops) == []
            else:
                assert truncates(ops) == [new_nst * SU]
                assert truncate_after_writes(ops)
        if case == "xattr_only":
            for ops in txns.values():
                assert (tx.OP_SETATTR, {
                    "name": USER_ATTR + "color", "value": b"blue"}) in ops
            assert await c.client.getxattr(POOL, name, "color") == b"blue"
        check_stored(c, name.encode(), want, k, m)
        assert await c.client.read(POOL, name) == want
        await c.stop()

    run(t())


def test_host_engine_fresh_write(monkeypatch):
    """The host engine hands no fused CRCs: the native CRC batch feeds
    the same per-op build."""
    async def t():
        c = await make_ec(8, 3, backend="host")
        spy = TxnSpy(monkeypatch)
        want = payload(11, 4 * 8 * SU)
        await c.client.write_full(POOL, "obj", want)
        for ops in spy.last().values():
            assert write_cells(ops) == [0, 1, 2, 3]
            assert truncates(ops) == [4 * SU]
            assert truncate_after_writes(ops)
        check_stored(c, b"obj", want, 8, 3)
        assert await c.client.read(POOL, "obj") == want
        await c.stop()

    run(t())


def _walk_runs(tlist):
    """The per-cell run walk as the write path had it before the
    per-op build: the runs each shard write carries."""
    runs = []
    run_i = prev_s = -1
    for i, s in enumerate(tlist):
        if run_i >= 0 and s != prev_s + 1:
            runs.append((run_i, i))
            run_i = -1
        if run_i < 0:
            run_i = i
        prev_s = s
    if run_i >= 0:
        runs.append((run_i, len(tlist)))
    return runs


@pytest.mark.parametrize("seed", range(6))
def test_stripe_runs_match_cell_walk(seed):
    rng = np.random.default_rng(seed)
    for _ in range(50):
        n = int(rng.integers(0, 40))
        tlist = sorted(set(rng.integers(0, 60, n).tolist()))
        assert _stripe_runs(tlist) == _walk_runs(tlist)


def test_torn_fresh_write_is_convicted_by_scrub():
    """A torn fresh write keeps the first half of its shard
    transaction: the touch, the data and the truncate, never the size,
    hinfo and version attrs set after them. Scrub convicts and repairs
    the shard."""
    async def t():
        c = await make_ec(8, 3)
        width = 8 * SU
        await c.client.write_full(POOL, "seed", payload(20, width))
        pgid = c.client.osdmap.object_to_pg(POOL, b"torn")
        up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        victim = next(o for o in up if o != primary)
        c.osds[victim].fault.arm("torn_write", count=1, oid=b"torn")
        want = payload(21, 4 * width)
        await c.client.write_full(POOL, "torn", want)
        assert c.osds[victim].fault.fired("torn_write")
        cid = f"{pgid[0]}.{pgid[1]}s{up.index(victim)}"
        store = c.osds[victim].store
        # the data landed, its size/hinfo/version attrs did not
        assert store.stat(cid, b"torn") == 4 * SU
        assert ATTR_HINFO not in store.getattrs(cid, b"torn")
        assert await c.client.read(POOL, "torn") == want
        report = await c.scrub_pg(pgid)
        assert b"torn" in report["inconsistent"], report
        report2 = await c.scrub_pg(pgid)
        assert report2["inconsistent"] == [], report2
        check_stored(c, b"torn", want, 8, 3)
        await c.stop()

    run(t())



def test_peer_that_missed_a_shrink_is_cut_by_a_full_rewrite():
    """A peer that missed a shrink still holds the older, longer shard
    file when a full rewrite that grows the object reaches it. The
    rewrite leaves nothing of the old copy past the new end: a rebuild
    that cannot do without that peer's whole shard succeeds, and scrub
    repairs the shards taken away and then finds every copy
    consistent."""
    k, m, stale = 4, 2, 1
    width = k * SU

    async def t():
        c = await make_ec(k, m)
        pgid = c.client.osdmap.object_to_pg(POOL, b"obj")
        up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        assert up[0] == primary

        def shard(pos):
            return f"{pgid[0]}.{pgid[1]}s{pos}", c.stores[up[pos]]

        await c.client.write_full(POOL, "obj", payload(30, 8 * width))
        cid, store = shard(stale)
        saved = (bytes(store.read(cid, b"obj")),
                 dict(store.getattrs(cid, b"obj")))
        await c.client.truncate(POOL, "obj", width + 300)
        # the peer missed the shrink: it holds the 8-stripe file and
        # attrs, and records the object missing at the shrink's version
        peer = c.osds[up[stale]].pgs[(pgid[0], pgid[1], stale)]
        t_old = tx.Transaction()
        t_old.write(cid, b"obj", 0, saved[0])
        t_old.rmattrs(cid, b"obj")
        t_old.setattrs(cid, b"obj", saved[1])
        store.queue_transaction(t_old)
        peer.missing[b"obj"] = peer.log.head
        assert store.stat(cid, b"obj") == 8 * SU

        want = payload(31, 4 * width)
        await c.client.write_full(POOL, "obj", want)
        assert b"obj" not in peer.missing
        check_stored(c, b"obj", want, k, m)

        # two other shards lost: a rebuild needs the peer's whole file
        for pos in (2, 3):
            lcid, lstore = shard(pos)
            t_rm = tx.Transaction()
            t_rm.remove(lcid, b"obj")
            lstore.queue_transaction(t_rm)
        pg = c.osds[primary].pgs[(pgid[0], pgid[1], 0)]
        async with pg.lock:
            chunk, _attrs = await pg.ec.rebuild(b"obj", 2)
        assert bytes(chunk) == REF.shards(want, k, m, SU)[2].tobytes()
        report = await c.scrub_pg(pgid)
        assert report["inconsistent"] == [b"obj"], report
        report = await c.scrub_pg(pgid)
        assert report["inconsistent"] == [], report
        check_stored(c, b"obj", want, k, m)
        assert await c.client.read(POOL, "obj") == want
        await c.stop()

    run(t())
