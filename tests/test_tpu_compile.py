"""Compiles of the main path's device programs for a described TPU v5e.

Nothing runs: each test lowers a program at the shape chip_smoke.py and
the cluster use and compiles it with the TPU compiler for a ``v5e:2x2``
that is described, not attached. That finds what the chip's compiler
refuses (unaligned Pallas slices, scoped-memory overruns, unpartitionable
kernels, i32/i64 verifier errors) at no chip time. The topology is
described inside a fixture, never at import: only one process at a time
may load libtpu.
"""
from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding

from ceph_tpu import parallel
from ceph_tpu.ops import crc32c as crc_ops
from ceph_tpu.ops import crush, gf8, rs
from ceph_tpu.parallel import runtime

K, M = 8, 3
HEADLINE = (24, K, 131072)  # 24 stripes x 512 KiB chunks, u32 words
CLUSTER = (64, K, 1024)  # 4 KiB stripe unit cells


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def swar(monkeypatch):
    # default_backend() is the CPU here; steer `auto` to the TPU's impl
    monkeypatch.setattr(rs, "IMPL", "swar")


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", [HEADLINE, CLUSTER],
                         ids=["headline", "cluster"])
def test_fused_encode_crc_compiles(one_chip, swar, shape):
    matrix = gf8.vandermonde_rs_matrix(K, M)
    fn = jax.jit(lambda x: rs.encode_with_crcs(matrix, shape[-1] * 4, x))
    compiled = fn.lower(_arg(shape, jnp.uint32, one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_two_erasure_decode_compiles(one_chip, swar):
    matrix = gf8.vandermonde_rs_matrix(K, M)
    present = [0, 2, 3, 4, 5, 7, 8, 9]  # data shards 1 and 6 lost
    rmat = gf8.decode_matrix(matrix, K, present)
    fn = jax.jit(lambda x: rs.gf_matmul(rmat, x))
    fn.lower(_arg(HEADLINE, jnp.uint32, one_chip)).compile()


def test_crc0_fold_compiles(one_chip):
    fn = jax.jit(crc_ops._crc0_words)
    fn.lower(_arg((256, 16384), jnp.uint32, one_chip)).compile()


def test_gf_pallas_kernel_compiles(one_chip):
    matrix = gf8.vandermonde_rs_matrix(K, M)
    nb = rs._bytes_per_dot(K)
    bm = rs._lift_bitmatrix_packed(matrix, nb)
    fn = jax.jit(lambda x, b: rs._gf_pallas_raw(x, b, M, interpret=False))
    compiled = fn.lower(_arg((4, K, 16384), jnp.uint32, one_chip),
                        _arg(bm.shape, jnp.bfloat16, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_crc_pallas_kernel_compiles(one_chip):
    fn = jax.jit(lambda w: crc_ops.crc32c_words_pallas(w, interpret=False))
    compiled = fn.lower(_arg((256, 16384), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_encode_on_2x2_mesh_compiles(topo, swar):
    mesh = parallel.make_mesh(list(topo.devices), width=2)
    matrix = gf8.vandermonde_rs_matrix(K, M)
    fn = runtime._jit_mesh_encode(mesh, matrix.tobytes(), M, K, 4096)
    sh = NamedSharding(mesh, parallel.chunk_batch_spec())
    compiled = fn.lower(_arg(CLUSTER, jnp.uint32, sh)).compile()
    assert compiled.memory_analysis() is not None


def test_straw2_compiles(one_chip):
    n, objs = 64, 4096
    with crush.enable_x64():
        args = (_arg((n,), jnp.int32, one_chip),
                _arg((n,), jnp.int32, one_chip),
                _arg((n,), jnp.uint32, one_chip),
                _arg((objs,), jnp.uint32, one_chip),
                _arg((), jnp.uint32, one_chip))
        crush._jit_straw2.lower(*args).compile()


def test_collective_repair_on_2x2_mesh_compiles(topo, swar):
    from ceph_tpu.parallel import shard_comm

    mesh = parallel.make_mesh(list(topo.devices), width=2)
    matrix = gf8.vandermonde_rs_matrix(K, M)
    rmat = gf8.decode_matrix(matrix, K, [0, 2, 3, 4, 5, 7, 8, 9])[[1, 6]]
    fn = shard_comm._jit_distributed_matmul(mesh, rmat.tobytes(), 2, K,
                                            "allgather")
    sh = shard_comm.shard_placement_sharding(mesh)
    compiled = fn.lower(_arg(CLUSTER, jnp.uint32, sh)).compile()
    assert "all-gather" in compiled.as_text()
