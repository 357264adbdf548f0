#!/usr/bin/env python3
"""Smoke test of the EC data path on a TPU. Not a benchmark.

Drives the system once through the entry points a user calls, at a size
Ceph users run: the ``rados bench`` default traffic (4 MiB objects, 16
ops in flight) on a k=8, m=3 erasure-coded pool with the default 4 KiB
stripe unit, on a 12-OSD in-process cluster whose pool is pinned to the
device engine.

``python chip_smoke.py`` (one chip) runs, in one process:

1. cluster: client -> OSD -> PG -> ECBatcher -> fused encode+CRC on the
   device -> memstore. 256 seeded objects (1 GiB) are written through
   the client's aio window and read back byte-exact; two OSDs holding
   data shards are killed and at least 64 objects are read degraded
   (device decode), byte-exact;
2. kernels: the math the cluster batches, at the headline shape (k=8,
   m=3, 512 KiB chunks, 24 stripes = 96 MiB per dispatch), bit-exact
   against the C++ core; plus CRC32C over 64 KiB blobs and straw2 over
   a 1 K-OSD bucket.

``python chip_smoke.py --chips 4`` runs only the mesh serving path: the
cluster phase with the EC batches sharded over a 2x2 (stripe, width)
mesh of four chips, and the same seeded objects through the 1-device
pipeline in the same process, with the stored shard bytes compared.

Any mismatch, failed phase or a platform other than ``tpu`` exits
non-zero. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import hashlib
import json
import os
import sys
import time

import numpy as np

K, M = 8, 3
OBJ_BYTES = 4 << 20
N_OBJECTS = 256
N_DEGRADED = 64
INFLIGHT = 16
N_OSDS = 12
#: data shards lost by the degraded phase
ERASED = (1, 6)

PROFILE = {"plugin": "rs_tpu", "k": str(K), "m": str(M),
           "backend": "device"}
#: the EC batching knobs; the cold-shape shield is off
#: so every decode round of the degraded phase takes the device
OSD_CONF = {
    "osd_ec_batch_window": 0.01,
    "osd_ec_batch_target_stripes": 48,
    "osd_op_concurrency": 32,
    "osd_ec_cold_shape_bytes": 0,
}
MESH_CONF = {
    **OSD_CONF,
    "osd_ec_mesh_devices": 4,
    "osd_ec_mesh_width": 2,
    "parallel_repair_mode": "allgather",
}

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        _compile_s[0] += duration


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


@contextlib.contextmanager
def phase(name: str, report: dict):
    c0, t0 = _compile_s[0], time.perf_counter()
    log(f"phase {name} ...")
    yield
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    report[name] = {"wall_s": wall, "compile_s": comp}
    log(f"phase {name}: wall {wall} s, compile {comp} s")


def object_bytes(seed: int, i: int, nbytes: int) -> bytes:
    """The oracle: object i's content, made from the seed."""
    return np.random.default_rng((seed, i)).bytes(nbytes)


def perf_sum(osds) -> dict:
    tot: dict = {}
    for osd in osds:
        if osd is None:
            continue
        for key, val in osd.perf.dump().items():
            if isinstance(val, (int, float)):
                tot[key] = tot.get(key, 0) + val
    return tot


def store_digest(cluster, prefix: bytes) -> dict:
    """sha256 of every stored shard of the smoke's objects, keyed by
    (osd, collection, object) — the bytes the EC write path produced."""
    out = {}
    for i, st in enumerate(cluster.stores):
        for cid in st.list_collections():
            for oid in st.list_objects(cid):
                if prefix in oid:
                    out[(i, cid, oid)] = hashlib.sha256(
                        st.read(cid, oid)).hexdigest()
    return out


async def run_cluster(osd_conf: dict, n_objects: int, obj_bytes: int,
                      n_degraded: int, seed: int, digest: bool = False,
                      on_written=None) -> dict:
    """Write n_objects seeded objects, read them back, kill two OSDs
    holding data shards, read >= n_degraded objects degraded. Every
    read is compared byte-exact with the oracle. Returns the counters
    (encode side before the kills, decode side after)."""
    from ceph_tpu.cluster.vstart import TestCluster
    from ceph_tpu.placement.osdmap import Pool

    c = TestCluster(n_osds=N_OSDS, hb_grace=5.0, out_interval=600.0,
                    osd_conf=osd_conf)
    await c.start()
    try:
        c.client.op_timeout = 600.0  # first-shape compiles ride ops
        c.client.conf.set("client_max_inflight", INFLIGHT)
        await c.client.create_pool(Pool(
            id=2, name="smoke", size=K + M, min_size=K + 1, pg_num=32,
            crush_rule=1, type="erasure", ec_profile=dict(PROFILE)))
        await c.wait_active(60)
        names = [f"smoke-{i}" for i in range(n_objects)]

        t0 = time.perf_counter()
        comps = []
        for i, name in enumerate(names):
            comps.append(await c.client.aio_write_full(
                2, name, object_bytes(seed, i, obj_bytes)))
        await c.client.writes_wait()
        for comp in comps:
            comp.result()
        write_s = time.perf_counter() - t0
        if on_written is not None:
            on_written()

        sem = asyncio.Semaphore(INFLIGHT)

        async def read_check(i: int) -> None:
            async with sem:
                got = await c.client.read(2, names[i])
            check(got == object_bytes(seed, i, obj_bytes),
                  f"{names[i]}: read back differs from what was written")

        t0 = time.perf_counter()
        await asyncio.gather(*(read_check(i) for i in range(n_objects)))
        read_s = time.perf_counter() - t0
        enc = perf_sum(c.osds)
        digests = store_digest(c, b"smoke-") if digest else {}

        # kill the OSDs holding data shards ERASED of the first
        # object's PG; then read objects whose PGs lost a data shard
        acting = {}
        for i, name in enumerate(names):
            pgid = c.client.osdmap.object_to_pg(2, name)
            acting[i] = c.mon.osdmap.pg_to_up_acting_osds(pgid)[0]
        victims = [acting[0][s] for s in ERASED]
        for v in victims:
            await c.kill_osd(v)
        for v in victims:
            await c.wait_down(v, timeout=60)
        await c.wait_active(120)
        degraded = [i for i in range(n_objects)
                    if any(o in victims for o in acting[i][:K])]
        degraded = degraded[:2 * n_degraded]
        check(len(degraded) >= n_degraded,
              f"only {len(degraded)} objects lost a data shard")
        t0 = time.perf_counter()
        await asyncio.gather(*(read_check(i) for i in degraded))
        degraded_s = time.perf_counter() - t0
        dec = perf_sum(c.osds)
    finally:
        await c.stop()
    return {
        "objects": n_objects, "object_bytes": obj_bytes,
        "write_s": write_s, "read_s": read_s,
        "killed_osds": victims, "degraded_reads": len(degraded),
        "degraded_read_s": degraded_s,
        "ec_batches": int(enc.get("ec_batches", 0)),
        "ec_batch_failures": int(enc.get("ec_batch_failures", 0))
        + int(dec.get("ec_batch_failures", 0)),
        "ec_decode_batches": int(dec.get("ec_decode_batches", 0)),
        "ec_decode_cold_host": int(dec.get("ec_decode_cold_host", 0)),
        "ec_mesh_encode_dispatches": int(
            enc.get("ec_mesh_encode_dispatches", 0)),
        "ec_mesh_decode_dispatches": int(
            dec.get("ec_mesh_decode_dispatches", 0)),
        "digests": digests,
    }


def cluster_phase(n_objects: int = N_OBJECTS, obj_bytes: int = OBJ_BYTES,
                  n_degraded: int = N_DEGRADED, seed: int = 0) -> dict:
    r = asyncio.run(run_cluster(OSD_CONF, n_objects, obj_bytes,
                                n_degraded, seed))
    r.pop("digests")
    log(f"cluster: {json.dumps(r)}")
    check(r["ec_batches"] > 0, "no batched encode dispatch")
    check(r["ec_decode_batches"] > 0, "no batched decode dispatch")
    check(r["ec_batch_failures"] == 0, "EC batch dispatches failed")
    check(r["ec_decode_batches"] > r["ec_decode_cold_host"],
          "no decode round reached the device")
    log(f"cluster: {r['ec_decode_cold_host']} of "
        f"{r['ec_decode_batches']} decode rounds went to the host "
        "(cold-shape shield)")
    return r


def engine_probe() -> None:
    """The EC engine cost model's verdict on this device (information,
    not a pass condition)."""
    from ceph_tpu.ec import engine

    engine.reset_probe()
    verdict = engine.data_path_engine()
    log(f"engine probe: {verdict} {json.dumps(engine.last_probe)}")
    engine.reset_probe()


def _timed(fn):
    """(result, seconds) of fn() after one warm call that compiles."""
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def kernel_phase(batch: int = 24, chunk: int = 512 * 1024,
                 n_blobs: int = 1024, blob: int = 64 * 1024,
                 n_osds: int = 1000, n_xs: int = 65536,
                 seed: int = 0) -> dict:
    import jax

    from ceph_tpu import native
    from ceph_tpu.ec import load_codec
    from ceph_tpu.ops import crc32c as crc_ops
    from ceph_tpu.ops import crush as crush_ops
    from ceph_tpu.ops import rs

    threads = os.cpu_count() or 1
    rng = np.random.default_rng(seed)
    codec = load_codec(dict(PROFILE))
    words = chunk // 4
    data = rng.integers(0, 2**32, (batch, K, words), dtype=np.uint32)
    x = jax.device_put(data)
    times = {}

    # fused encode + per-cell CRC: the write path's dispatch
    (parity, crcs), times["encode_crc"] = _timed(
        lambda: codec.encode_crc_batch(x, chunk))
    host_in = rs.unpack_u32(data)  # (B, K, chunk) u8
    flat = np.ascontiguousarray(host_in.transpose(1, 0, 2)).reshape(
        K, batch * chunk)
    want = native.rs_encode(codec.matrix, flat, threads=threads)
    host_par = rs.unpack_u32(np.asarray(parity))
    got = np.ascontiguousarray(host_par.transpose(1, 0, 2)).reshape(
        M, batch * chunk)
    check((got == want).all(), "device parity differs from native")
    cells = np.concatenate([host_in, host_par], axis=1).reshape(-1, chunk)
    want_crc = native.crc32c_batch(cells, threads=threads)
    check((np.asarray(crcs).reshape(-1) == want_crc).all(),
          "device cell CRCs differ from native")

    # 2-erasure decode: the repair path's dispatch
    present = tuple([i for i in range(K) if i not in ERASED] + [K, K + 1])
    surv = jax.device_put(np.concatenate(
        [data[:, [i for i in present if i < K], :],
         np.asarray(parity)[:, :len(ERASED), :]], axis=1))
    decoded, times["decode_2_erasures"] = _timed(
        lambda: codec.decode_batch(present, surv))
    check((np.asarray(decoded) == data).all(),
          "device decode differs from the original data")

    # batched CRC32C over blobs (BlueStore csum shape)
    blobs = rng.integers(0, 256, (n_blobs, blob), dtype=np.uint8)
    got_crc, times["crc32c_blobs"] = _timed(
        lambda: crc_ops.crc32c_batch(blobs))
    check((got_crc == native.crc32c_batch(blobs, threads=threads)).all(),
          "device crc32c differs from native")

    # straw2 bulk placement over one wide bucket
    items = np.arange(n_osds, dtype=np.int32)
    weights = rng.integers(1, 4 * 0x10000, n_osds, dtype=np.uint32)
    xs = rng.integers(0, 2**32, n_xs, dtype=np.uint32)
    placed, times["straw2_bulk"] = _timed(
        lambda: crush_ops.straw2_bulk(items, weights, xs))
    check((placed == native.straw2_bulk(items, weights, xs,
                                        threads=threads)).all(),
          "device straw2 differs from native")
    shapes = {
        "encode_crc": f"({batch}, {K}, {words}) u32",
        "decode_2_erasures": f"({batch}, {K}, {words}) u32",
        "crc32c_blobs": f"{n_blobs} x {blob} B",
        "straw2_bulk": f"{n_xs} objects x {n_osds} OSDs",
    }
    for name, dt in times.items():
        log(f"smoke timing (not a benchmark): {name} {shapes[name]}: "
            f"{dt} s")
    return times


def mesh_phase(n_objects: int = N_OBJECTS, obj_bytes: int = OBJ_BYTES,
               n_degraded: int = N_DEGRADED, seed: int = 0) -> dict:
    """The cluster phase over a 4-device mesh, and the same seeded
    objects through the 1-device pipeline; stored shards compared."""
    from ceph_tpu.ec import load_codec
    from ceph_tpu.parallel import runtime

    writes: dict = {}

    def snap() -> None:
        writes.update(runtime.STATS.dump())

    runtime.STATS.reset()
    runtime.reset_meshes()
    meshed = asyncio.run(run_cluster(MESH_CONF, n_objects, obj_bytes,
                                     n_degraded, seed, digest=True,
                                     on_written=snap))
    after = runtime.STATS.dump()
    mesh = runtime.serving_mesh(MESH_CONF["osd_ec_mesh_devices"],
                                MESH_CONF["osd_ec_mesh_width"])
    # where one sharded dispatch's outputs actually live
    codec = load_codec(dict(PROFILE))
    probe = np.random.default_rng(seed).integers(
        0, 2**32, (8, K, 1024), dtype=np.uint32)
    parity, _ = codec.encode_crc_batch_mesh(probe, 4096, mesh)
    shard_devs = sorted(s.device.id for s in parity.addressable_shards)
    runtime.reset_meshes()
    single = asyncio.run(run_cluster(OSD_CONF, n_objects, obj_bytes,
                                     n_degraded, seed, digest=True))
    digests = meshed.pop("digests")
    check(len(digests) == n_objects * (K + M),
          f"{len(digests)} stored shards, want {n_objects * (K + M)}")
    same = digests == single.pop("digests")
    per_dev = writes.get("mesh_stripes_per_device", {})
    log(f"mesh: {json.dumps(meshed)}")
    log(f"mesh write phase: {json.dumps(writes)}")
    log(f"mesh after degraded reads: {json.dumps(after)}")
    log(f"single-device: {json.dumps(single)}")
    log(f"mesh devices {[d.id for d in mesh.devices.flat]}, probe "
        f"parity shards on {shard_devs}")
    check(same, "mesh and 1-device pipelines stored different shards")
    check(writes.get("mesh_encode_dispatches", 0) > 0,
          "no write crossed the mesh")
    check(after["mesh_decode_dispatches"] > 0,
          "no degraded read used collective repair")
    check(after["mesh_host_gathers"] == 0, "mesh path gathered to host")
    check(len(per_dev) == 4 and len(set(per_dev.values())) == 1,
          f"stripes not spread evenly over 4 devices: {per_dev}")
    check(sorted(set(shard_devs)) == sorted(
        d.id for d in mesh.devices.flat) and len(set(shard_devs)) == 4,
          f"sharded parity lives on {shard_devs}")
    for r in (meshed, single):
        check(r["ec_batch_failures"] == 0, "EC batch dispatches failed")
    return {"mesh": meshed, "single": single, "write_phase": writes,
            "after": after}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh serving path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {platform!r} "
              f"({len(devs)} device(s)); this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 2
    from ceph_tpu.utils import compile_cache

    cache = compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    kind = devs[0].device_kind
    log(f"device: platform {platform}, kind {kind}, count {len(devs)}")
    log(f"compile cache: {cache}")

    report: dict = {}
    if args.chips == 4:
        with phase("mesh_cluster", report):
            mesh_phase(seed=args.seed)
    else:
        with phase("cluster", report):
            cluster_phase(seed=args.seed)
        with phase("engine_probe", report):
            engine_probe()
        with phase("kernels", report):
            kernel_phase(seed=args.seed)
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            log(f"device {d.id} peak_bytes_in_use "
                f"{stats['peak_bytes_in_use']}")
    log(f"phases: {json.dumps(report)}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
