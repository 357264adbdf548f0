"""Wire layer: frames, typed messages, TCP messenger, map encodings.

The direct_messenger / msgr test role (SURVEY §4.2, src/test/msgr/).
"""
import asyncio

import numpy as np
import pytest

from ceph_tpu.cluster import messages as M
from ceph_tpu.msg import frames
from ceph_tpu.msg.messenger import TcpMessenger
from ceph_tpu.placement import crushmap as cm
from ceph_tpu.placement import encoding as menc
from ceph_tpu.placement.osdmap import Incremental, OSDMap, Pool


def test_frame_roundtrip():
    f = frames.Frame(type=7, payload=b"hello world" * 100)
    wire = frames.encode_frame(f)
    got, used = frames.decode_frame(wire)
    assert used == len(wire)
    assert got.type == 7 and got.payload == f.payload


def test_frame_crc_detects_corruption():
    wire = bytearray(frames.encode_frame(frames.Frame(1, b"payload")))
    wire[14] ^= 0x40
    with pytest.raises(frames.FrameError):
        frames.decode_frame(bytes(wire))


def test_frame_incomplete():
    wire = frames.encode_frame(frames.Frame(1, b"x" * 64))
    with pytest.raises(frames.IncompleteFrame):
        frames.decode_frame(wire[:10])
    with pytest.raises(frames.IncompleteFrame):
        frames.decode_frame(wire[:-1])


def test_message_roundtrips():
    samples = [
        M.MOSDBoot(osd=3),
        M.MOSDMapMsg(full=b"mapbytes", incrementals=[b"a", b"bb"], epoch=9),
        M.MOSDOp(tid=5, pgid=(1, 7), oid=b"obj",
                 ops=[M.osd_op("writefull", data=b"\x00\x01" * 50),
                      M.osd_op("setxattr", key=b"k", data=b"v"),
                      M.osd_op("omap_setkeys", kv={b"a": b"1"}),
                      M.osd_op("omap_rmkeys", keys=[b"z"])],
                 epoch=4),
        M.MOSDOpReply(tid=5, result=0, data=b"x", size=1,
                      outs=[(0, b"x"), (-2, b"")], epoch=4),
        M.MECSubWrite(tid=1, pgid=(2, 3), shard=4, txn=b"t", entry=b"e",
                      epoch=2),
        M.MECSubReadReply(tid=1, pgid=(2, 3), shard=4, result=0,
                          data=b"chunk", digest=0xDEADBEEF, size=123,
                          attrs={"u:meta": b"m"}),
        M.MPushOp(pgid=(1, 2), shard=-1, oid=b"o", version=(3, 9),
                  data=b"d", attrs={"v": b"\x01", "hinfo": b"\x02"},
                  epoch=3, last_update=(3, 11)),
        M.MPGScanReply(pgid=(1, 2), shard=0,
                       objects={b"a": (1, 2), b"b": (3, 4)}),
    ]
    from ceph_tpu.msg.messages import decode_message

    for msg in samples:
        got = decode_message(msg.TYPE, msg.encode())
        assert got == msg, msg


def test_tcp_messenger_roundtrip():
    async def run():
        got = []
        done = asyncio.Event()

        async def dispatch_a(src, msg):
            got.append(("a", src, msg))
            done.set()

        async def dispatch_b(src, msg):
            got.append(("b", src, msg))
            await b.send(src, M.MOSDBoot(osd=99))

        a = TcpMessenger("client.1", dispatch_a)
        b = TcpMessenger("osd.0", dispatch_b)
        host, port_b = await b.listen()
        host_a, port_a = await a.listen()
        a.addrbook["osd.0"] = (host, port_b)
        b.addrbook["client.1"] = (host_a, port_a)
        await a.send("osd.0", M.MOSDOp(tid=1, pgid=(1, 0), oid=b"x",
                                       ops=[M.osd_op("read")], epoch=1))
        await asyncio.wait_for(done.wait(), 5)
        await a.close()
        await b.close()
        assert got[0][0] == "b" and got[0][1] == "client.1"
        assert isinstance(got[0][2], M.MOSDOp)
        assert got[1] == ("a", "osd.0", M.MOSDBoot(osd=99))

    asyncio.run(run())


def test_crushmap_encoding_roundtrip():
    m = cm.build_hierarchy(osds_per_host=3, n_hosts=4)
    m.add_rule(cm.replicated_rule(0, root=-1, failure_domain_type=1))
    m.add_rule(cm.ec_rule(1, root=-1, failure_domain_type=1))
    m2, used = menc.decode_crushmap(menc.encode_crushmap(m))
    assert used == len(menc.encode_crushmap(m))
    # placement-equivalent: identical do_rule results
    w = np.full(m.max_devices, 0x10000, dtype=np.uint32)
    for x in range(50):
        assert m.do_rule(0, x, 3, w) == m2.do_rule(0, x, 3, w)
        assert m.do_rule(1, x, 5, w) == m2.do_rule(1, x, 5, w)


def test_osdmap_encoding_roundtrip():
    crush = cm.build_flat(6)
    crush.add_rule(cm.flat_firstn_rule(0))
    m = OSDMap(crush, 6)
    m.add_pool(Pool(id=1, name="p", size=3, pg_num=16, crush_rule=0))
    m.add_pool(Pool(id=2, name="e", size=5, pg_num=8, crush_rule=0,
                    type="erasure", ec_profile={"k": "3", "m": "2"}))
    m.osds[2].up = False
    m.osds[4].weight = 0x8000
    m.pg_upmap[(1, 3)] = [5, 0, 1]
    m.pg_upmap_items[(1, 4)] = [(0, 5)]
    m.pg_upmap_primaries[(1, 5)] = 2
    m2, _ = menc.decode_osdmap(menc.encode_osdmap(m))
    assert m2.epoch == m.epoch and len(m2.osds) == 6
    assert m2.pools[2].ec_profile == {"k": "3", "m": "2"}
    for pool in (1, 2):
        for ps in range(m.pools[pool].pg_num):
            assert m.pg_to_up_acting_osds((pool, ps)) == \
                m2.pg_to_up_acting_osds((pool, ps))


def test_incremental_encoding_roundtrip():
    inc = Incremental(epoch=4, up=[1], down=[2, 3],
                      weights={0: 0, 5: 0x10000},
                      new_pools=[Pool(id=9, name="x", size=2, pg_num=4)],
                      new_pg_upmap={(1, 2): [3, 4]},
                      new_pg_upmap_items={(1, 3): [(0, 1)]},
                      new_pg_upmap_primaries={(1, 4): 2, (1, 5): None})
    inc2, _ = menc.decode_incremental(menc.encode_incremental(inc))
    assert inc2 == inc


def test_lazy_subop_fields_wire_roundtrip():
    """MECSubWrite/MOSDRepOp accept LIVE Transaction/entry-list objects
    (LocalBus ships them by reference); the WIRE encode must marshal
    them identically to pre-encoded bytes, or the process tier would
    corrupt shard sub-ops (round-5 zero-copy change)."""
    from ceph_tpu.cluster import messages as M
    from ceph_tpu.cluster.pglog import Entry
    from ceph_tpu.cluster.osd_types import enc_entries
    from ceph_tpu.store import transaction as tx

    t = tx.Transaction()
    t.touch("1.0s0", b"obj")
    t.write("1.0s0", b"obj", 0, b"payload-bytes" * 100)
    t.setattr("1.0s0", b"obj", "k", b"v")
    entries = [Entry("modify", b"obj", (3, 7), (3, 6),
                     reqid=("client.0", 42))]

    live = M.MECSubWrite(tid=1, pgid=(1, 0), shard=0, txn=t,
                         entry=entries, epoch=3, hpatch=b"hp",
                         ncells=1, size=1300, prev_head=(3, 6))
    pre = M.MECSubWrite(tid=1, pgid=(1, 0), shard=0, txn=t.encode(),
                        entry=enc_entries(entries), epoch=3,
                        hpatch=b"hp", ncells=1, size=1300,
                        prev_head=(3, 6))
    assert live.encode() == pre.encode()
    dec = M.MECSubWrite.decode(live.encode())
    t2, _ = tx.Transaction.decode(dec.txn)
    assert [op.code for op in t2.ops] == [op.code for op in t.ops]

    live_r = M.MOSDRepOp(tid=2, pgid=(1, 1), txn=t, entry=entries,
                         epoch=3, prev_head=(3, 6))
    pre_r = M.MOSDRepOp(tid=2, pgid=(1, 1), txn=t.encode(),
                        entry=enc_entries(entries), epoch=3,
                        prev_head=(3, 6))
    assert live_r.encode() == pre_r.encode()
