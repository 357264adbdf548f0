"""GF(2) bit-plane kernels for the bitmatrix erasure-code family.

A bitmatrix code (blaum_roth / liberation / liber8tion / cauchy_bm,
ec/bitmatrix_plugin.py) computes every output packet row as the XOR of
a fixed subset of input packet rows — row r of the (R, C) binary
matrix selects the inputs. The host reference walks the matrix row by
row; the device shape here is the XOR-schedule optimization of
arXiv:2108.02692 precomputed into tensors:

- **XOR plan** (:func:`xor_plan`): at ``init()`` the binary matrix is
  lowered to a dense (R, T) gather-index tensor, T = max row popcount.
  Rows with fewer terms pad with index C, which addresses an appended
  all-zero row — XOR-inert, so no masking is needed in the kernel.
- **One fused dispatch** (:func:`jit_gf2_apply`): the whole stripe
  batch reshapes to packet rows, one ``take`` gathers every term of
  every output row, and a fold of XORs reduces the term axis. The
  Python fold is static (T is a host constant), so XLA fuses the
  gather + XOR chain into a single elementwise kernel over uint32
  lanes — the same trace-safety discipline as ops/rs.py: integer-only,
  no data-dependent shapes, every constant baked at trace time.
- **Fused encode+CRC** (:func:`jit_encode_with_crcs`): like
  rs.jit_encode_with_crcs, parity AND the per-cell CRC32Cs of
  data+parity come back from ONE program, so the write path persists
  hinfo straight from the encode dispatch.

dtype discipline (tpulint `dtype` family): packed lanes are uint32,
gather indices int32, and nothing may promote to int64 inside the
trace — an int64 hop would double the lane traffic and break on
x64-disabled backends.

Layout contract: a cell of ``su`` bytes packs to W = su/4 uint32 words
and splits into w packet rows of W/w words (su % (4*w) == 0 — the
plugin's k*w*4 alignment guarantees it). Packing little-endian bytes
first and then reshaping words is identical to splitting bytes first
and packing each row, because rows are word-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def xor_plan(matrix: np.ndarray) -> np.ndarray:
    """(R, C) binary matrix -> (R, T) int32 gather-index plan.

    T is the max row popcount; short rows pad with index C (the
    appended zero row). An all-zero matrix row becomes a row of pads
    and correctly produces zeros."""
    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    rows, cols = m.shape
    terms = [np.nonzero(m[r])[0] for r in range(rows)]
    t = max((len(ix) for ix in terms), default=0) or 1
    plan = np.full((rows, t), cols, dtype=np.int32)
    for r, ix in enumerate(terms):
        plan[r, : len(ix)] = ix.astype(np.int32)
    return plan


def gf2_apply(plan: jax.Array, rows: jax.Array) -> jax.Array:
    """XOR-combine packet rows per a precomputed gather plan.

    plan: (R, T) int32 indices into axis -2 of ``rows`` (index C =
    zero row). rows: (..., C, W) uint32. Returns (..., R, W) uint32
    where out[r] = XOR over t of rows_ext[plan[r, t]].

    Traceable: the zero row is appended inside the trace and the term
    fold is a static Python loop over T (a host constant), so the
    whole thing is one fused gather+XOR kernel."""
    rows = rows.astype(jnp.uint32)
    zero = jnp.zeros(rows.shape[:-2] + (1, rows.shape[-1]), jnp.uint32)
    ext = jnp.concatenate([rows, zero], axis=-2)
    gathered = jnp.take(ext, plan, axis=-2)  # (..., R, T, W)
    terms = gathered.shape[-2]
    acc = gathered[..., 0, :]
    for t in range(1, terms):
        acc = acc ^ gathered[..., t, :]
    return acc


def gf2_encode_cells(plan: jax.Array, w: int, out_rows: int,
                     data: jax.Array) -> jax.Array:
    """Cell-level entry: data (..., k, W) uint32 cells -> coding
    (..., R/w, W) uint32 cells, splitting each cell into its w packet
    rows first (W % w == 0 by the plugin's alignment)."""
    lead = data.shape[:-2]
    c, words = data.shape[-2], data.shape[-1]
    rows = data.reshape(*lead, c * w, words // w)
    out = gf2_apply(plan, rows)
    return out.reshape(*lead, out_rows, words)


def encode_with_crcs(plan: np.ndarray, w: int, m_rows: int,
                     cell_bytes: int,
                     data: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused bitmatrix encode + per-cell CRC32C: data (..., k, W)
    uint32 -> (parity (..., m, W) uint32, crcs (..., k+m) uint32) in
    ONE program — the GF(2) analog of rs.encode_with_crcs."""
    from . import crc32c as crc_ops

    parity = gf2_encode_cells(jnp.asarray(plan), w, m_rows, data)
    cells = jnp.concatenate([data, parity], axis=-2)
    return parity, crc_ops.crc32c_cells_device(cells, cell_bytes)


@functools.lru_cache(maxsize=1024)
def _jit_apply(plan_bytes: bytes, rows: int, terms: int, w: int,
               out_rows: int):
    plan = np.frombuffer(plan_bytes, dtype=np.int32).reshape(rows, terms)
    return jax.jit(functools.partial(gf2_encode_cells,
                                     jnp.asarray(plan), w, out_rows))


def jit_gf2_apply(plan: np.ndarray, w: int):
    """Cached jitted cell-level GF(2) gather+XOR specialized to a host
    plan: (..., C, W) uint32 cells -> (..., R/w, W) uint32 cells."""
    p = np.ascontiguousarray(plan, dtype=np.int32)
    if p.shape[0] % w:
        raise ValueError(
            f"plan rows {p.shape[0]} not a multiple of w={w}")
    return _jit_apply(p.tobytes(), p.shape[0], p.shape[1], w,
                      p.shape[0] // w)


@functools.lru_cache(maxsize=256)
def _jit_encode_with_crcs(plan_bytes: bytes, rows: int, terms: int,
                          w: int, cell_bytes: int):
    plan = np.frombuffer(plan_bytes, dtype=np.int32).reshape(rows, terms)
    return jax.jit(functools.partial(encode_with_crcs, plan, w,
                                     rows // w, int(cell_bytes)))


def jit_encode_with_crcs(plan: np.ndarray, w: int, cell_bytes: int):
    """Cached jitted fused encode+CRC specialized to a host plan and a
    static cell length (same caching contract as rs.jit_encode_with_
    crcs: evicting one costs a full XLA recompile)."""
    p = np.ascontiguousarray(plan, dtype=np.int32)
    if p.shape[0] % w:
        raise ValueError(
            f"plan rows {p.shape[0]} not a multiple of w={w}")
    return _jit_encode_with_crcs(p.tobytes(), p.shape[0], p.shape[1],
                                 w, int(cell_bytes))


# -------------------- numpy reference (host engine) --------------------


def gf2_apply_np(plan: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Host-batched reference with the same plan semantics: rows
    (..., C, L) uint8/uint32 -> (..., R, L). One vectorized gather +
    XOR-reduce — the bit-exactness oracle the device path is pinned
    against, and the batcher's host-engine shape for these codecs."""
    zero = np.zeros(rows.shape[:-2] + (1, rows.shape[-1]),
                    dtype=rows.dtype)
    ext = np.concatenate([rows, zero], axis=-2)
    return np.bitwise_xor.reduce(np.take(ext, plan, axis=-2), axis=-2)


def gf2_apply_np_blocked(plan: np.ndarray, rows: np.ndarray,
                         block: int = 256) -> np.ndarray:
    """Batch-blocked host apply: byte-identical to ``gf2_apply_np``,
    but the (..., R, terms, L) gather intermediate is materialized at
    most ``block`` stripes at a time — a recovery storm's host decode
    keeps bounded scratch instead of scaling it with the batch."""
    if rows.ndim < 3 or len(rows) <= block:
        return gf2_apply_np(plan, rows)
    return np.concatenate([gf2_apply_np(plan, rows[i:i + block])
                           for i in range(0, len(rows), block)])


def gf2_encode_cells_np(plan: np.ndarray, w: int,
                        cells: np.ndarray) -> np.ndarray:
    """Host cell-level entry: cells (..., k, su) uint8 -> coding
    (..., R/w, su) uint8."""
    lead = cells.shape[:-2]
    c, su = cells.shape[-2], cells.shape[-1]
    rows = cells.reshape(*lead, c * w, su // w)
    out = gf2_apply_np_blocked(plan, rows)
    return out.reshape(*lead, plan.shape[0] // w, su)
