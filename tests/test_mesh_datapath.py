"""Multi-chip data plane: the serving-path mesh (parallel/runtime.py +
ECBatcher mesh mode).

Unit tier pins the acceptance contract directly: mesh-sharded fused
encode+CRC and collective repair are BYTE-IDENTICAL to the
single-device dispatch over random stripes, results cross to the host
only as per-device shard views (host_gathers stays 0), occupancy lands
evenly across chips, and a platform that cannot supply the configured
mesh is an error, never a quiet 1-device run. Cluster tier proves OSD
traffic actually crosses the mesh: a live TestCluster with the mesh
knobs on serves writes through sharded dispatches and a degraded read
through the collective repair path. Everything runs on the 8-device
virtual CPU platform conftest pins.
"""
import asyncio

import numpy as np
import pytest

from ceph_tpu.cluster.ecbatch import ECBatcher
from ceph_tpu.ec import load_codec
from ceph_tpu.parallel import runtime
from ceph_tpu.utils import config as cfg

DEV_PROFILE = {"plugin": "rs_tpu", "k": "3", "m": "2",
               "backend": "device"}


def run(coro, timeout=180):
    asyncio.run(asyncio.wait_for(coro, timeout))


def mesh_conf(n=8, width=2, repair="allgather") -> cfg.ConfigProxy:
    conf = cfg.proxy()
    conf.apply({"osd_ec_mesh_devices": n, "osd_ec_mesh_width": width,
                "parallel_repair_mode": repair})
    return conf


def rand_cells(b, k=3, su=256, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (b, k, su), dtype=np.uint8)


# ------------------------------------------------------------ unit tier


@pytest.mark.parametrize("width", [1, 2, 4])
def test_mesh_encode_byte_identical_and_gather_free(width):
    """mesh={stripe, width} fused encode+CRC == the single-device
    dispatch, bit for bit, at every width factoring — and the write
    path never gathers the sharded result through one host buffer."""
    codec = load_codec(dict(DEV_PROFILE))
    cells = rand_cells(11, seed=1)
    runtime.STATS.reset()

    async def t():
        meshed = ECBatcher(conf=mesh_conf(width=width))
        single = ECBatcher()
        pm, cm = await meshed.encode_cells(codec, cells)
        ps, cs = await single.encode_cells(codec, cells)
        assert (pm == ps).all()
        assert (cm == cs).all()
        assert meshed.mesh() is not None

    run(t())
    d = runtime.STATS.dump()
    assert d["mesh_encode_dispatches"] == 1
    assert d["mesh_host_gathers"] == 0
    assert d["mesh_encode_stripes"] == 11
    # occupancy is EVEN: the padded batch splits exactly across the
    # stripe rows, every device owns the same share
    per_dev = set(d["mesh_stripes_per_device"].values())
    assert len(per_dev) == 1


@pytest.mark.parametrize("method", ["allgather", "psum_bits"])
def test_collective_repair_matches_single_device(method):
    """decode_cells under parallel_repair_mode rebuilds data AND
    wanted-parity rows identically to the single-device stacked-matrix
    decode — including the k'=3-over-width=2 shape, where the chunk
    axis zero-pads to the mesh width."""
    codec = load_codec(dict(DEV_PROFILE))
    cells = rand_cells(6, seed=2)
    runtime.STATS.reset()

    async def t():
        meshed = ECBatcher(conf=mesh_conf(width=2, repair=method))
        single = ECBatcher()
        parity, _ = await single.encode_cells(codec, cells)
        every = np.concatenate([cells, parity], axis=1)
        present = (0, 2, 4)  # lost data 1 and parity 3
        surv = np.ascontiguousarray(every[:, list(present), :])
        want = (0, 1, 2, 3)
        got = await meshed.decode_cells(codec, present, want, surv)
        ref = await single.decode_cells(codec, present, want, surv)
        assert (got == ref).all()
        assert (got[:, :3, :] == cells).all()

    run(t())
    d = runtime.STATS.dump()
    assert d["mesh_decode_dispatches"] == 1
    assert d["mesh_host_gathers"] == 0


def test_mesh_single_stripe_pads_to_stripe_row():
    """batch < devices: one stripe still dispatches (padded to a full
    stripe row) and comes back byte-exact."""
    codec = load_codec(dict(DEV_PROFILE))
    cells = rand_cells(1, seed=3)

    async def t():
        meshed = ECBatcher(conf=mesh_conf(width=4))
        single = ECBatcher()
        pm, cm = await meshed.encode_cells(codec, cells)
        ps, cs = await single.encode_cells(codec, cells)
        assert (pm == ps).all() and (cm == cs).all()

    run(t())


def test_mesh_unavailable_fails_the_dispatch():
    """A config asking for more devices than the platform has fails the
    dispatch: serving single-device instead would report an all-zero
    mesh ledger from a run the operator asked to shard."""
    codec = load_codec(dict(DEV_PROFILE))
    cells = rand_cells(4, seed=4)
    runtime.reset_meshes()

    async def t():
        b = ECBatcher(conf=mesh_conf(n=4096))
        with pytest.raises(RuntimeError, match="dispatch failed"):
            await b.encode_cells(codec, cells)
        with pytest.raises(RuntimeError, match="need 4096 devices"):
            b.mesh()

    run(t())


def test_host_engine_ignores_mesh_knobs():
    """The mesh is a device-engine lever: the host C++ core keeps its
    two-pass shape (no CRCs from the dispatch) regardless of knobs."""
    codec = load_codec({**DEV_PROFILE, "backend": "host"})
    cells = rand_cells(3, seed=5)
    runtime.STATS.reset()

    async def t():
        b = ECBatcher(conf=mesh_conf())
        parity, crcs = await b.encode_cells(codec, cells)
        assert crcs is None
        assert parity.shape == (3, 2, 256)

    run(t())
    assert runtime.STATS.dump()["mesh_encode_dispatches"] == 0


def test_repair_mode_off_keeps_single_device_decode():
    codec = load_codec(dict(DEV_PROFILE))
    cells = rand_cells(4, seed=6)
    runtime.STATS.reset()

    async def t():
        b = ECBatcher(conf=mesh_conf(repair="off"))
        parity, _ = await b.encode_cells(codec, cells)
        every = np.concatenate([cells, parity], axis=1)
        out = await b.decode_cells(codec, (0, 1, 4), (2,),
                                   np.ascontiguousarray(
                                       every[:, [0, 1, 4], :]))
        assert (out[:, 0, :] == cells[:, 2, :]).all()

    run(t())
    d = runtime.STATS.dump()
    assert d["mesh_encode_dispatches"] == 1  # encode still meshes
    assert d["mesh_decode_dispatches"] == 0  # decode stays 1-device


def test_shard_rows_to_host_dedupes_replicas():
    """Width-replicated results (per-stripe CRCs, repair output) are
    read once per unique shard, not once per replica device."""
    import jax

    from ceph_tpu import parallel

    mesh = parallel.make_mesh(parallel.get_devices(8), width=4)
    arr = jax.device_put(np.arange(8, dtype=np.uint32),
                         parallel.per_stripe_sharding(mesh))
    runtime.STATS.reset()
    out = runtime.shard_rows_to_host(arr)
    assert (out == np.arange(8, dtype=np.uint32)).all()
    # 2 stripe rows x 4 width replicas = 8 shards, 2 unique reads
    assert runtime.STATS.shard_reads == 2
    # and the counted escape hatch counts
    runtime.host_gather(arr)
    assert runtime.STATS.host_gathers == 1


# --------------------------------------------------------- cluster tier


def test_cluster_serves_writes_and_degraded_reads_over_mesh():
    """OSD traffic CROSSES the mesh (the whole point of this PR): a
    live cluster with the mesh knobs on serves client writes through
    sharded fused encode+CRC dispatches — zero host gathers — and a
    degraded read (one OSD down) rebuilds its chunk through the
    collective repair path, byte-exact."""
    from ceph_tpu.cluster.vstart import TestCluster
    from ceph_tpu.placement.osdmap import Pool

    runtime.STATS.reset()
    payload = np.random.default_rng(7).integers(
        0, 256, 3 * 4096 * 2, dtype=np.uint8).tobytes()  # two stripes

    async def t():
        c = TestCluster(n_osds=5, osd_conf={
            "osd_ec_mesh_devices": 8,
            "osd_ec_mesh_width": 2,
            "parallel_repair_mode": "allgather",
        })
        await c.start()
        c.client.op_timeout = 60.0
        await c.client.create_pool(Pool(
            id=2, name="mesh", size=5, min_size=3, pg_num=8,
            crush_rule=1, type="erasure",
            ec_profile={"plugin": "rs_tpu", "k": "3", "m": "2",
                        "backend": "device"}))
        await c.wait_active(30)
        for i in range(4):
            await c.client.write_full(2, f"obj-{i}", payload)
        assert await c.client.read(2, "obj-0") == payload
        gathers_after_writes = runtime.STATS.host_gathers
        # degraded read: kill one OSD, the rebuilt chunk must come
        # through the collective decode and still read byte-exact
        await c.kill_osd(4)
        for i in range(4):
            assert await c.client.read(2, f"obj-{i}") == payload
        await c.stop()
        return gathers_after_writes

    gathers = [None]

    async def outer():
        gathers[0] = await asyncio.wait_for(t(), 150)

    asyncio.run(outer())
    d = runtime.STATS.dump()
    assert d["mesh_encode_dispatches"] > 0, d
    assert gathers[0] == 0, "write path gathered through the host"
    assert d["mesh_decode_dispatches"] > 0, \
        "degraded reads did not use collective repair"
    assert d["mesh_host_gathers"] == 0, d
