"""Each cell's EC programs, at the cell's real shapes, compiled for a
described TPU v5e (one chip, and the 2x2 mesh of the four-chip cell).
Nothing runs; the topology is described inside a fixture."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from ceph_tpu import parallel
from ceph_tpu.ops import gf8, rs
from ceph_tpu.parallel import runtime

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell(name):
    w = json.load(open(os.path.join(BENCH, "workloads", f"{name}.json")))
    spec = json.load(open(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json")))
    cfg_name = {x["name"]: x["config"] for x in spec["workloads"]}[name]
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      f"{cfg_name}.json")))
    return w, cfg


def _batches(w, cfg):
    p = cfg["pool"]["ec_profile"]
    k, su = int(p["k"]), int(p["stripe_unit"])
    spo = -(-w["object_bytes"] // (k * su))
    out, n = [], spo
    while n < spo * w["concurrency"]:
        out.append(n)
        n *= 2
    return out + [spo * w["concurrency"]]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def swar(monkeypatch):
    # default_backend() is the CPU here; steer `auto` to the TPU's impl
    monkeypatch.setattr(rs, "IMPL", "swar")


@pytest.mark.parametrize("cell", ["k8m3-4m-write", "k4m2-4k-write",
                                  "k8m3-4m-degraded-read"])
def test_one_chip_encode(topo, swar, cell):
    w, cfg = _cell(cell)
    p = cfg["pool"]["ec_profile"]
    k, m, su = int(p["k"]), int(p["m"]), int(p["stripe_unit"])
    matrix = gf8.vandermonde_rs_matrix(k, m)
    one = SingleDeviceSharding(topo.devices[0])
    fn = jax.jit(lambda x: rs.encode_with_crcs(matrix, su, x))
    for b in _batches(w, cfg):
        x = jax.ShapeDtypeStruct((b, k, su // 4), jnp.uint32, sharding=one)
        fn.lower(x).compile()


def test_one_chip_decode(topo, swar):
    w, cfg = _cell("k8m3-4m-degraded-read")
    p = cfg["pool"]["ec_profile"]
    k, m, su = int(p["k"]), int(p["m"]), int(p["stripe_unit"])
    rmat = gf8.decode_matrix(gf8.vandermonde_rs_matrix(k, m), k,
                             [0, 2, 3, 4, 5, 7, 8, 9])[[1, 6]]
    one = SingleDeviceSharding(topo.devices[0])
    fn = jax.jit(lambda x: rs.gf_matmul(rmat, x))
    for b in (128, 256, 512):
        x = jax.ShapeDtypeStruct((b, k, su // 4), jnp.uint32, sharding=one)
        fn.lower(x).compile()


def test_mesh_encode(topo, swar):
    w, cfg = _cell("k8m3-4m-write-4chip")
    p = cfg["pool"]["ec_profile"]
    k, m, su = int(p["k"]), int(p["m"]), int(p["stripe_unit"])
    conf = cfg["osd_conf"]
    devs = np.array(topo.devices[:conf["osd_ec_mesh_devices"]])
    width = conf["osd_ec_mesh_width"]
    mesh = jax.sharding.Mesh(devs.reshape(-1, width),
                             (parallel.STRIPE_AXIS, parallel.WIDTH_AXIS))
    matrix = gf8.vandermonde_rs_matrix(k, m)
    fn = runtime._jit_mesh_encode(mesh, matrix.tobytes(), m, k, su)
    sh = parallel.chunk_batch_sharding(mesh)
    for n in _batches(w, cfg):
        b = parallel.pad_batch_pow2(n, mesh)
        x = jax.ShapeDtypeStruct((b, k, su // 4), jnp.uint32, sharding=sh)
        fn.lower(x).compile()
