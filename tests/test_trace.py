"""Distributed tracing (utils/trace): span mechanics, cross-daemon
context propagation through real cluster ops (the blkin pg_trace arc:
client -> primary PG -> EC sub-ops), admin-socket dump, and the
standalone exporter's admin-socket scrape."""
import asyncio
import importlib.util
import os
import sys
import threading
import time

from ceph_tpu.cluster import TestCluster
from ceph_tpu.placement.osdmap import Pool
from ceph_tpu.utils import trace


def run(coro):
    asyncio.run(asyncio.wait_for(coro, 120))


def test_span_basics():
    t = trace.get_tracer("svc-a")
    with t.start_span("root") as root:
        root.tag("k", "v")
        child = t.start_span("child", parent=root)
        child.finish()
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert root.parent_id == 0
    dumped = t.dump(trace_id=root.trace_id)
    names = {d["name"] for d in dumped}
    assert names == {"root", "child"}
    by_name = {d["name"]: d for d in dumped}
    assert by_name["child"]["parentId"] == f"{root.span_id:016x}"
    assert by_name["root"]["tags"] == {"k": "v"}


def test_wire_ctx_round_trip():
    t = trace.get_tracer("svc-b")
    parent = t.start_span("parent")
    # NO_CTX parent starts a fresh trace
    fresh = t.start_span("fresh", parent=trace.NO_CTX)
    assert fresh.parent_id == 0 and fresh.trace_id != parent.trace_id
    # a wire ctx tuple parents correctly
    remote = t.start_span("remote", parent=parent.ctx)
    assert remote.trace_id == parent.trace_id
    assert remote.parent_id == parent.span_id
    parent.finish(), fresh.finish(), remote.finish()


def test_trace_propagates_through_ec_write():
    """One client write to an EC pool must produce client, pg.do_op and
    ec_sub_write spans sharing one trace id, parented as a tree."""
    async def t():
        c = TestCluster(n_osds=5)
        await c.start()
        await c.client.create_pool(
            Pool(id=2, name="ec", size=5, min_size=3, pg_num=4,
                 crush_rule=1, type="erasure",
                 ec_profile={"plugin": "rs_tpu", "k": "3", "m": "2"}))
        await c.wait_active(20)
        await c.client.write_full(2, b"traced-obj", b"z" * 20000)
        got = await c.client.read(2, b"traced-obj")
        assert got == b"z" * 20000
        await c.stop()

    run(t())
    client_spans = [s for s in trace.get_tracer("client.0").dump()
                    if s["name"] == "writefull"
                    and s["tags"].get("oid") == "traced-obj"]
    assert client_spans, "client span missing"
    root = client_spans[-1]
    spans = trace.dump_all()
    tree = [s for s in spans if s["traceId"] == root["traceId"]]
    names = {s["name"] for s in tree}
    assert "pg.do_op writefull" in names
    assert "ec_sub_write" in names
    # parenting: do_op under the client span, sub-writes under do_op
    do_op = next(s for s in tree if s["name"] == "pg.do_op writefull")
    assert do_op["parentId"] == root["id"]
    subs = [s for s in tree if s["name"] == "ec_sub_write"]
    assert subs and all(s["parentId"] == do_op["id"] for s in subs)
    # spans come from more than one daemon (distributed, not local)
    services = {s["localEndpoint"]["serviceName"] for s in tree}
    assert len(services) >= 3


def test_admin_socket_dump_tracing_and_exporter(tmp_path):
    async def t():
        c = TestCluster(n_osds=3)
        await c.start()
        await c.client.create_pool(
            Pool(id=1, name="rep", size=3, pg_num=4, crush_rule=0))
        await c.wait_active(20)
        await c.client.write_full(1, b"obj", b"x" * 500)
        sock_dir = str(tmp_path / "asok")
        os.makedirs(sock_dir)
        for i, osd in enumerate(c.osds):
            await osd.start_admin(os.path.join(sock_dir, f"osd.{i}.sock"))
        from ceph_tpu.utils.admin import admin_command

        dumps = []
        for i in range(3):
            dumps.extend(await admin_command(
                os.path.join(sock_dir, f"osd.{i}.sock"), "dump_tracing"))
        assert any(s["name"].startswith("pg.do_op") for s in dumps)

        # the standalone exporter scrapes the same sockets
        spec = importlib.util.spec_from_file_location(
            "exporter", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools", "exporter.py"))
        exporter = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(exporter)
        text = await exporter.scrape(sock_dir)
        assert 'ceph_tpu_daemon_up{ceph_daemon="osd.1"} 1' in text
        assert "ceph_tpu_op" in text  # op counters made it through
        await c.stop()

    run(t())


def test_span_ids_unique_across_threads_and_ns_stamps():
    """Ids need no lock: spans started on several threads at once never
    collide. Stamps are integer time.time_ns()."""
    t = trace.get_tracer("svc-ids")
    ids: list[int] = []
    n_threads = 2 * (os.cpu_count() or 1) + 2

    def work():
        spans = [t.start_span("x") for _ in range(1000)]
        for sp in spans:
            sp.finish()
        ids.extend(sp.span_id for sp in spans)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert len(ids) == 1000 * n_threads
    assert len(set(ids)) == len(ids)
    before = time.time_ns()
    sp = t.start_span("y")
    sp.finish()
    assert isinstance(sp.start_ns, int) and isinstance(sp.duration_ns, int)
    assert before <= sp.start_ns <= time.time_ns()
    assert sp.duration_ns >= 0


def test_dump_keeps_zipkin_shape_and_formats_raw_tags():
    t = trace.get_tracer("svc-zipkin")
    with t.start_span("op") as sp:
        sp.tag("oid", b"obj\xff").tag("pgid", (2, 3)).tag("result", 0)
    d = t.dump(trace_id=sp.trace_id)[-1]
    assert set(d) == {"traceId", "id", "parentId", "localEndpoint",
                      "name", "timestamp", "duration", "tags"}
    assert d["traceId"] == f"{sp.trace_id:016x}"
    assert d["id"] == f"{sp.span_id:016x}" and d["parentId"] is None
    assert d["localEndpoint"] == {"serviceName": "svc-zipkin"}
    assert d["timestamp"] == sp.start_ns // 1000  # zipkin micros
    assert d["duration"] == sp.duration_ns // 1000
    assert d["tags"] == {"oid": "obj\ufffd", "pgid": "(2, 3)",
                         "result": "0"}


def test_host_span_times_its_block():
    with trace.host_span("ec.test") as hs:
        time.sleep(0.002)
    assert isinstance(hs.ns, int) and hs.ns >= 2_000_000


def _stage_sums(osds) -> dict:
    keys = ("op_latency", "op_queue_lat", "op_pg_lock_lat", "op_ec_lat",
            "op_subop_lat")
    out = {k: [0.0, 0] for k in keys}
    for osd in osds:
        if osd is None:  # killed
            continue
        d = osd.perf.dump()
        for k in keys:
            out[k][0] += d[k]["sum"]
            out[k][1] += d[k]["avgcount"]
    return out


def test_ec_op_stages_count_and_mark_in_order():
    """An EC write and a degraded EC read bump the OSD op-stage
    counters; the PG's stages never sum past the op latency they sit
    in (a fan-out timed per sub-op would); and the historic op holds
    the stage marks in the order the overwrite ran them."""
    data = bytes(range(256)) * 96  # two stripes at k=3, su=4096

    async def t():
        c = TestCluster(n_osds=5)
        await c.start()
        await c.client.create_pool(
            Pool(id=2, name="ec", size=5, min_size=3, pg_num=4,
                 crush_rule=1, type="erasure",
                 ec_profile={"plugin": "rs_tpu", "k": "3", "m": "2"}))
        await c.wait_active(20)
        # the first write of a name decides its absence on the
        # primary's own shard; the overwrite runs lock, encode, fan-out
        await c.client.write_full(2, b"staged", data[::-1])
        await c.client.write_full(2, b"staged", data)
        w = _stage_sums(c.osds)
        assert w["op_queue_lat"][1] >= 1
        for k in ("op_pg_lock_lat", "op_ec_lat", "op_subop_lat"):
            assert w[k][1] >= 1 and w[k][0] > 0, k
        osdmap = c.mon.osdmap
        acting = list(osdmap.pg_to_up_acting_osds(
            osdmap.object_to_pg(2, b"staged"))[0])
        ops = c.osds[acting[0]].optracker.dump_historic_ops()["ops"]
        op = [o for o in ops if "staged" in o["description"]][-1]
        events = [e["event"] for e in op["events"]]
        order = ["queued", "dequeued", "reached_pg", "pg_locked",
                 "ec_done", "sub_ops_done", "done"]
        assert [e for e in events if e in order] == order, events
        stamps = [e["time"] for e in op["events"]]
        assert stamps == sorted(stamps)
        # degraded read: data shard 1's OSD down, the read decodes
        victim = acting[1]
        await c.kill_osd(victim)
        await c.wait_down(victim)
        await c.wait_active(20)
        assert await c.client.read(2, b"staged") == data
        r = _stage_sums(c.osds)
        await c.stop()
        return r

    r = asyncio.run(asyncio.wait_for(t(), 120))
    for k in ("op_queue_lat", "op_pg_lock_lat", "op_ec_lat",
              "op_subop_lat"):
        assert r[k][1] >= 1, k
    stages = sum(r[k][0] for k in ("op_pg_lock_lat", "op_ec_lat",
                                   "op_subop_lat"))
    assert 0 < stages <= r["op_latency"][0]
