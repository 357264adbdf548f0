"""Device-resident EC shard communication over the mesh (the TPU-native
half of SURVEY §2.5's "Communication backend": where the reference fans
sub-ops to shard OSDs over TCP sockets, here each mesh device HOLDS a
shard and reconstruction is an ICI collective).

Placement: chunk batches (B, k, W) with the CHUNK axis sharded over the
`width` mesh axis — one (or k/n) erasure-code shards per device, the
shard-to-device binding that replaces per-connection sockets. Repair of
missing shards (and parity generation) is then a distributed GF(2^8)
matrix-vector product: each device computes its LOCAL partial (its
matrix columns times its resident chunks, on the MXU), and partials
combine across the mesh with XOR — GF(2^8) addition.

XLA's reduction collectives have no XOR combiner, so two strategies:

- ``allgather``: lax.all_gather the partials and XOR-fold locally.
  Comm per device O(n_dev * B * W) — right for the small shard groups
  real pools use (k+m <= ~20 over a few devices).
- ``psum_bits``: expand partials into 32 one-bit planes, psum them
  (integer add on disjoint planes carries XOR as parity: sum & 1),
  repack. Comm O(32 * B * W) INDEPENDENT of device count — the
  bandwidth-optimal reduce for wide meshes, the all-to-all/ring analog
  of the survey's long-context mapping.

Both are bit-exact vs the host oracle; tests pin them against each
other and the single-device kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import gf8, rs
from . import STRIPE_AXIS, WIDTH_AXIS


def shard_placement_spec() -> P:
    """(B, k, W) with erasure-code shards resident one-per-device
    along the width axis (batch still over stripe)."""
    return P(STRIPE_AXIS, WIDTH_AXIS, None)


def shard_placement_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, shard_placement_spec())


def _block_bitmatrices(matrix: np.ndarray, n_dev: int) -> np.ndarray:
    """Split an (R, C) GF matrix into n_dev column blocks and lift each
    to its GF(2) bit-matrix: (n_dev, 8R, 8*C/n_dev) int8."""
    _rows, c = matrix.shape
    if c % n_dev:
        raise ValueError(f"{c} chunks do not split over {n_dev} devices")
    cl = c // n_dev
    return np.stack([
        rs._lift_bitmatrix(np.ascontiguousarray(
            matrix[:, d * cl:(d + 1) * cl]))
        for d in range(n_dev)
    ])


@functools.lru_cache(maxsize=4096)  # sized like rs._jit_matmul_impl
def _jit_distributed_matmul(mesh: Mesh, matrix_bytes: bytes, rows: int,
                            cols: int, method: str):
    """One lifted-and-jitted program per (mesh, matrix, method) — the
    erasure-pattern-keyed cache the single-device decode path gets from
    rs.jit_gf_matmul; without it every repair re-lifts the bit-matrix
    and re-traces the shard_map."""
    matrix = np.frombuffer(matrix_bytes, np.uint8).reshape(rows, cols)
    n_w = mesh.shape[WIDTH_AXIS]
    bm_blocks = jnp.asarray(_block_bitmatrices(matrix, n_w))

    def local_fn(bm_all, x_local):
        # x_local: (B/stripe, C/n_w, W) — this device's resident shards
        me = jax.lax.axis_index(WIDTH_AXIS)
        bm = jax.lax.dynamic_index_in_dim(bm_all, me, keepdims=False)
        partial = rs.gf_matmul_bm(bm, x_local)  # (Bl, R, W) GF partial
        if method == "allgather":
            parts = jax.lax.all_gather(partial, WIDTH_AXIS)
            out = parts[0]
            for i in range(1, n_w):
                out = out ^ parts[i]
            return out
        # one collective: stack the 32 one-bit planes and psum together
        # (integer add on disjoint planes carries XOR as parity)
        shifts = jnp.arange(32, dtype=jnp.uint32)
        planes = ((partial[None] >> shifts[:, None, None, None])
                  & jnp.uint32(1)).astype(jnp.int32)
        s = jax.lax.psum(planes, WIDTH_AXIS)
        par = (s & 1).astype(jnp.uint32)
        return jnp.sum(par << shifts[:, None, None, None], axis=0,
                       dtype=jnp.uint32)

    # no-check flag: the XOR-of-collective result IS replicated along
    # width, but the replication checker can't see through the algebra
    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), shard_placement_spec()),
        out_specs=P(STRIPE_AXIS, None, None),
        check_vma=False,
    )
    return jax.jit(functools.partial(fn, bm_blocks))


def _distributed_matmul(mesh: Mesh, matrix: np.ndarray,
                        chunks: jax.Array, method: str) -> jax.Array:
    """(B, C, W) sharded shard_placement_spec() -> (B, R, W) GF product,
    batch-sharded, replicated along width."""
    if method not in ("allgather", "psum_bits"):
        raise ValueError(f"unknown method {method!r}")
    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    if m.shape[1] % mesh.shape[WIDTH_AXIS]:
        raise ValueError(
            f"{m.shape[1]} chunks do not split over "
            f"{mesh.shape[WIDTH_AXIS]} devices")
    return _jit_distributed_matmul(
        mesh, m.tobytes(), m.shape[0], m.shape[1], method)(chunks)


def pad_chunk_axis(matrix: np.ndarray,
                   chunks: np.ndarray,
                   n_dev: int) -> tuple[np.ndarray, np.ndarray]:
    """Zero-pad the survivor/chunk axis so it splits evenly over the
    width devices: zero matrix COLUMNS multiply zero chunk ROWS, and a
    GF zero column contributes nothing to any output — the padded
    product is bit-identical to the unpadded one. This is what lets
    collective repair serve any k' (e.g. k'=3 survivors over width=2,
    or a k+m that is not a multiple of the mesh width)."""
    c = matrix.shape[1]
    pad = (-c) % n_dev
    if not pad:
        return matrix, chunks
    m = np.concatenate(
        [matrix, np.zeros((matrix.shape[0], pad), dtype=np.uint8)],
        axis=1)
    z = np.zeros(chunks.shape[:-2] + (pad, chunks.shape[-1]),
                 dtype=chunks.dtype)
    return m, np.concatenate([chunks, z], axis=-2)


def distributed_matmul(mesh: Mesh, matrix: np.ndarray, chunks,
                       method: str = "allgather"):
    """Public serving-path entry: (B, C, W) uint32 chunks — a jax
    array already resident shard_placement_sharding(mesh), or a host
    array to be staged that way — times an (R, C) GF matrix, partials
    combined across the width axis by ``method``. Returns (B, R, W)
    batch-sharded, whole on every width-group device. The chunk axis
    must already divide the mesh width (pad_chunk_axis)."""
    if not isinstance(chunks, jax.Array):
        chunks = jax.device_put(
            np.ascontiguousarray(chunks),
            shard_placement_sharding(mesh))
    return _distributed_matmul(mesh, matrix, chunks, method)


def distributed_repair(mesh: Mesh, matrix: np.ndarray, k: int,
                       present: list[int], chunks: jax.Array,
                       method: str = "allgather") -> jax.Array:
    """Reconstruct all k data chunks from survivors resident across the
    mesh (ECBackend.cc:2405's cross-OSD reconstruct, as ICI collectives
    instead of sub-op sockets).

    matrix: (m, k) coding matrix (host). present: survivor chunk ids in
    the order they are stacked on chunks' axis 1. chunks: (B, k, W)
    uint32 sharded shard_placement_spec(). Returns (B, k, W) data,
    batch-sharded, whole on every width-group device.
    """
    rmat = gf8.decode_matrix(matrix, k, list(present))
    return _distributed_matmul(mesh, rmat, chunks, method)


def distributed_encode(mesh: Mesh, matrix: np.ndarray, data: jax.Array,
                       method: str = "allgather") -> jax.Array:
    """Parity for data shards resident across the width axis: each
    device contributes its columns' partial parity. Returns (B, m, W)
    replicated along width (each shard-holder persists its row)."""
    return _distributed_matmul(mesh, matrix, data, method)
