"""Reed-Solomon GF(2^8) encode/decode as JAX/XLA TPU kernels.

Design (SURVEY.md §7 "Kernel strategy"): a GF(2^8) multiply by a constant
coefficient c is linear over GF(2), so

    y = mul(c, x) = XOR_{b=0..7} bit_b(x) * mul(c, 1 << b)

With four bytes packed per uint32 lane (SWAR), ``bit_b`` of all four bytes
is isolated by ``(x >> b) & 0x01010101`` and the per-byte multiply by the
constant byte ``mc = mul(c, 1<<b) < 256`` is an ordinary integer multiply —
no cross-byte carries are possible. The whole encode is therefore a fused
chain of shift/and/mul/xor on uint32 vectors: integer-only, bit-exact by
construction, no gathers, and entirely in XLA's elementwise-fusion sweet
spot. This replaces the reference's SIMD GF tables (gf-complete
"split-table" methods, ISA-L ec_encode_data — ErasureCodeJerasure.cc:105,
ErasureCodeIsa.cc:120) with the TPU-native equivalent.

Decode = host-side inversion of the surviving-rows generator submatrix
(ops/gf8.py, mirroring jerasure_matrix_decode/ErasureCodeIsa.cc:302) +
the same device kernel with the recovery matrix.

Data layout: chunks are uint32 arrays of shape (..., k, W) where W =
chunk_bytes / 4, little-endian byte packing. The leading batch dims are
the stripe batch — the axis the data path shards over the device mesh.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import gf8

_LOW_BITS = np.uint32(0x01010101)


def _bitplanes(x: jax.Array) -> list[jax.Array]:
    """Isolate bit b of each packed byte, for b in 0..7."""
    m = jnp.uint32(_LOW_BITS)
    return [(jax.lax.shift_right_logical(x, jnp.uint32(b)) & m) for b in range(8)]


def gf_matmul_u32(matrix: np.ndarray, chunks: jax.Array) -> jax.Array:
    """GF(2^8) matrix-vector product over packed byte streams.

    matrix: (R, C) uint8 host constants (coding or recovery matrix).
    chunks: (..., C, W) uint32. Returns (..., R, W) uint32 where
    out[r] = XOR_c mul(matrix[r, c], chunks[c]) bytewise.

    The Python loops are static: they unroll into one fused XLA kernel.
    Bit-planes of each input chunk are computed once and reused across all
    output rows (the dominant term is then 2 vector ops per (row, chunk,
    bit) triple).
    """
    rows, cols = matrix.shape
    if chunks.shape[-2] != cols:
        raise ValueError(f"chunks axis -2 is {chunks.shape[-2]}, matrix wants {cols}")
    chunks = chunks.astype(jnp.uint32)
    planes: list[list[jax.Array] | None] = [None] * cols
    need_planes = [
        any(matrix[r, c] not in (0, 1) for r in range(rows)) for c in range(cols)
    ]
    for c in range(cols):
        if need_planes[c]:
            planes[c] = _bitplanes(chunks[..., c, :])

    outs = []
    for r in range(rows):
        acc = None
        for c in range(cols):
            coeff = int(matrix[r, c])
            if coeff == 0:
                continue
            if coeff == 1:
                term = chunks[..., c, :]
            else:
                term = None
                for b in range(8):
                    mc = gf8.gf_mul(coeff, 1 << b)
                    part = planes[c][b] * jnp.uint32(mc)
                    term = part if term is None else term ^ part
            acc = term if acc is None else acc ^ term
        if acc is None:
            acc = jnp.zeros(chunks.shape[:-2] + (chunks.shape[-1],), jnp.uint32)
        outs.append(acc)
    return jnp.stack(outs, axis=-2)


def _lift_bitmatrix(matrix: np.ndarray) -> np.ndarray:
    """(R, C) GF(2^8) matrix -> (R*8, C*8) GF(2) bit-matrix.

    Block (r, c) is the multiply-by-matrix[r,c] bit matrix: column j
    holds the bits of matrix[r,c] * x^j (jerasure_matrix_to_bitmatrix
    semantics) — so out_bit[r*8+i] = XOR over (c, j) of
    block[i, j] * in_bit[c*8+j], exactly GF(2^8) algebra over GF(2).
    """
    rows, cols = matrix.shape
    out = np.zeros((rows * 8, cols * 8), dtype=np.int8)
    for r in range(rows):
        for c in range(cols):
            e = int(matrix[r, c])
            v = e
            for j in range(8):
                for i in range(8):
                    out[r * 8 + i, c * 8 + j] = (v >> i) & 1
                v = gf8.gf_mul(v, 2)
    return out


def gf_matmul_u32_mxu(matrix: np.ndarray, chunks: jax.Array) -> jax.Array:
    """Same contract as gf_matmul_u32, computed on the MXU.

    GF(2^8) is linear over GF(2): slice the packed bytes into 8 bit
    planes, multiply by the lifted (R*8, C*8) bit-matrix as ONE int8
    systolic-array matmul with int32 accumulation, take parity (&1),
    and repack. The SWAR kernel burns ~16 vector ops per (row, col,
    bit) triple on the VPU; here the whole contraction runs on the
    matrix unit and the VPU only does the bit slice/pack, which is why
    this is the TPU-first shape for the hot encode path.
    """
    rows, cols = matrix.shape
    if chunks.shape[-2] != cols:
        raise ValueError(
            f"chunks axis -2 is {chunks.shape[-2]}, matrix wants {cols}"
        )
    return gf_matmul_bm(jnp.asarray(_lift_bitmatrix(matrix)), chunks)


def gf_matmul_bm(bm: jax.Array, chunks: jax.Array) -> jax.Array:
    """einsum GF matmul over a DEVICE-RESIDENT (R*8, C*8) bit-matrix
    (standard _lift_bitmatrix row order). Unlike the host-constant
    paths, bm may be a traced value — e.g. a per-device block selected
    with lax.axis_index inside shard_map (parallel/shard_comm)."""
    if bm.shape[0] % 8 or bm.shape[1] % 8:
        raise ValueError(
            f"bm shape {bm.shape} is not a lifted bit-matrix (pass the "
            "(R*8, C*8) _lift_bitmatrix form, not the raw GF matrix)")
    rows = bm.shape[0] // 8
    cols = bm.shape[1] // 8
    if chunks.shape[-2] * 8 != bm.shape[1]:
        raise ValueError(
            f"chunks axis -2 is {chunks.shape[-2]}, bit-matrix wants "
            f"{bm.shape[1] // 8}")
    x = chunks.astype(jnp.uint32)
    lead = x.shape[:-2]
    w = x.shape[-1]
    # u32 words -> little-endian bytes (..., C, 4W)
    bytes_ = jnp.stack(
        [(x >> jnp.uint32(8 * i)) & jnp.uint32(0xFF) for i in range(4)],
        axis=-1,
    ).reshape(*lead, cols, 4 * w)
    # bytes -> bit planes (..., C*8, 4W) int8; row c*8+b = bit b
    bits = jnp.stack(
        [(bytes_ >> jnp.uint32(b)) & jnp.uint32(1) for b in range(8)],
        axis=-2,
    ).reshape(*lead, cols * 8, 4 * w).astype(jnp.int8)
    acc = jnp.einsum(
        "rc,...cn->...rn", bm, bits,
        preferred_element_type=jnp.int32,
    ) & 1  # (..., R*8, 4W) parity bits
    acc = acc.reshape(*lead, rows, 8, 4 * w).astype(jnp.uint32)
    out_bytes = sum(
        acc[..., b, :] << jnp.uint32(b) for b in range(8)
    )  # (..., R, 4W)
    # bytes -> u32 words (little-endian)
    ob = out_bytes.reshape(*lead, rows, w, 4)
    return (
        ob[..., 0]
        | (ob[..., 1] << jnp.uint32(8))
        | (ob[..., 2] << jnp.uint32(16))
        | (ob[..., 3] << jnp.uint32(24))
    )


def _lift_bitmatrix_planar(matrix: np.ndarray) -> np.ndarray:
    """Bit-matrix with bit-major (planar) row/col order for the Pallas
    kernel: BM2[i*R + r, j*C + c] = BM[r*8 + i, c*8 + j].

    The kernel builds its bit planes by concatenating whole (C, T) planes
    along the sublane axis (row index j*C + c) — no per-byte row
    interleave, which Mosaic would have to do with sublane shuffles. The
    column/row permutation is absorbed here, on the host, for free.
    """
    bm = _lift_bitmatrix(matrix)
    rows, cols = matrix.shape
    out = np.zeros((rows * 8, cols * 8), dtype=np.int8)
    for r in range(rows):
        for i in range(8):
            for c in range(cols):
                for j in range(8):
                    out[i * rows + r, j * cols + c] = bm[r * 8 + i, c * 8 + j]
    return out


def _bytes_per_dot(cols: int) -> int:
    """How many of a word's 4 bytes one MXU pass handles.

    The GF bit-matrix contraction is only 8*C deep (<=64 for k=8) and
    8*R tall (24 for m=3) — a fraction of the 128x128 systolic array, so
    a one-byte-per-dot kernel is issue-bound at <10% MXU utilization
    (measured: it pins the r2 headline at ~57 GiB/s). Bytes are
    independent streams through the SAME bit-matrix, so pack nb of them
    block-diagonally and contract nb*8C <= 128 lanes in one pass —
    nb x fewer MXU passes per word."""
    nb = max(1, 128 // (8 * cols))
    return 4 if nb >= 4 else (2 if nb >= 2 else 1)


def _row_pad(rows: int) -> int:
    """Output rows per (byte, bit) plane, padded to the 8-sublane tile.

    The pack stage slices the product at plane boundaries; with rows=m=3
    those slices straddle sublanes and Mosaic inserts shuffles that cost
    more than the matmul itself (measured: 7.4 ms of a 17 ms kernel).
    Zero-padding each plane to 8 rows makes every slice tile-aligned —
    the padding rows multiply by zero weights and vanish."""
    return -(-rows // 8) * 8


def _lift_bitmatrix_packed(matrix: np.ndarray, nb: int) -> np.ndarray:
    """Block-diagonal stack of nb planar bit-matrices with sublane-
    aligned output planes: byte b's bit plane i lands in output rows
    [(b*8 + i) * rpad, ...+rows). Off-diagonal zeros keep per-row sums
    <= 8C, so bf16 x bf16 -> f32 accumulation stays exact."""
    bm = _lift_bitmatrix(matrix)
    rows, cols = matrix.shape
    rpad = _row_pad(rows)
    out = np.zeros((nb * 8 * rpad, nb * 8 * cols), dtype=np.int8)
    for b in range(nb):
        for i in range(8):
            for r in range(rows):
                for j in range(8):
                    for c in range(cols):
                        out[(b * 8 + i) * rpad + r,
                            (b * 8 + j) * cols + c] = bm[r * 8 + i,
                                                         c * 8 + j]
    return out


def _pallas_tile(w: int, max_t: int = 8192) -> int | None:
    """Largest lane-tile <= max_t that divides W and is a multiple of 128."""
    t = min(w, max_t)
    while t >= 128:
        if w % t == 0 and t % 128 == 0:
            return t
        t -= 128
    return None


def gf_matmul_pallas(matrix: np.ndarray, chunks: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """Same contract as gf_matmul_u32, as a fused Pallas TPU kernel.

    The einsum MXU path (gf_matmul_u32_mxu) materializes the int8 bit
    planes (8x the data) and the int32 accumulator (32x the parity bits)
    in HBM — ~50x the minimal traffic. Here each (C, T) input tile is
    unpacked to bit planes, contracted on the MXU (bf16 x bf16 -> f32;
    row sums <= 8C < 2^8 are exact), reduced mod 2, and repacked to
    uint32 entirely in VMEM, so HBM sees only the data in and parity
    out. Traffic-minimal is not time-minimal, though: measured on v5e,
    the VPU unpack/pack stages bound this kernel at ~50 GiB/s data-in,
    while the fully-fused XLA SWAR path reaches 134-240 GiB/s at the
    same (k=8, m=3) shape — the GF contraction is too narrow (8k x 8m
    of a 128x128 array) for the MXU to pay for the packing. Kept as
    the reference MXU formulation and for codes wide enough to fill
    the array; `auto` resolves to SWAR on TPU (ErasureCodeIsa.cc:120
    ec_encode_data is the host analog of that choice).

    ``interpret`` comes from the caller only: off the TPU, a call
    without ``interpret=True`` fails to lower rather than quietly
    running the Pallas interpreter.
    """
    rows, cols = matrix.shape
    if chunks.shape[-2] != cols:
        raise ValueError(
            f"chunks axis -2 is {chunks.shape[-2]}, matrix wants {cols}"
        )
    x = chunks.astype(jnp.uint32)
    lead = x.shape[:-2]
    w = x.shape[-1]
    b = int(np.prod(lead)) if lead else 1
    x3 = x.reshape(b, cols, w)
    nb = _bytes_per_dot(cols)
    bm = jnp.asarray(_lift_bitmatrix_packed(matrix, nb),
                     dtype=jnp.bfloat16)
    if interpret:
        out = _gf_pallas_raw(x3, bm, rows, interpret=True)
    else:
        out = _partitioned_gf_pallas(rows)(x3, bm)
    return out.reshape(*lead, rows, w)


_PARTITIONED_GF_PALLAS: dict[int, object] = {}


def _partitioned_gf_pallas(rows: int):
    """custom_partitioning wrapper: pallas_call is opaque to GSPMD, but
    this op is independent along the batch and word axes, so under a
    sharded jit each device just runs the kernel on its local (b, C, w)
    shard — zero collectives, matching parallel.chunk_batch_sharding's
    (stripe, width) mesh layout. The chunk axis (C in, R out) and the
    bit-matrix stay replicated. Cached per output-row count (the row
    count is not derivable from the padded bit-matrix shape)."""
    cached = _PARTITIONED_GF_PALLAS.get(rows)
    if cached is not None:
        return cached
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec

    @custom_partitioning
    def fn(x3, bm):
        return _gf_pallas_raw(x3, bm, rows)

    def _shardings(mesh, arg_shapes):
        spec = arg_shapes[0].sharding.spec
        b = spec[0] if len(spec) > 0 else None
        w = spec[2] if len(spec) > 2 else None
        x_sh = NamedSharding(mesh, PartitionSpec(b, None, w))
        bm_sh = NamedSharding(mesh, PartitionSpec(None, None))
        return x_sh, bm_sh

    def infer(mesh, arg_shapes, result_shape):
        return _shardings(mesh, arg_shapes)[0]

    def partition(mesh, arg_shapes, result_shape):
        x_sh, bm_sh = _shardings(mesh, arg_shapes)

        def lower_fn(x3, bm):
            return _gf_pallas_raw(x3, bm, rows)

        return mesh, lower_fn, x_sh, (x_sh, bm_sh)

    fn.def_partition(infer_sharding_from_operands=infer,
                     partition=partition,
                     sharding_rule="b c w, rr cc -> b r w")
    _PARTITIONED_GF_PALLAS[rows] = fn
    return fn


def _gf_pallas_raw(x3: jax.Array, bm: jax.Array, rows: int,
                   interpret: bool = False) -> jax.Array:
    """The pallas_call itself: x3 (B, C, W) u32, bm the packed planar
    bit-matrix from _lift_bitmatrix_packed -> (B, rows, W) u32. Kept
    const-free (bm is an argument) so custom_partitioning can wrap it
    for GSPMD multichip lowering; a non-128-multiple W (e.g. an uneven
    per-shard slice) is zero-padded to the next lane boundary and sliced
    back — GF zero rows produce zero outputs, so padding is invisible."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, cols, w = x3.shape
    nb = bm.shape[1] // (8 * cols)  # bytes packed per MXU pass
    rpad = bm.shape[0] // (8 * nb)  # sublane-aligned rows per bit plane
    t = _pallas_tile(w)
    if t is None:
        wpad = -(-w // 128) * 128
        padded = jnp.pad(x3, ((0, 0), (0, 0), (0, wpad - w)))
        return _gf_pallas_raw(padded, bm, rows,
                              interpret=interpret)[..., :w]

    def kernel(x_ref, bm_ref, out_ref):
        xt = x_ref[0]  # (C, T) uint32
        bmv = bm_ref[:]  # (nb*8*rpad, nb*8C) bf16 block-diagonal
        out = jnp.zeros((rpad, t), jnp.uint32)
        for g in range(4 // nb):
            # bit planes of nb bytes stacked down the contraction axis:
            # row b*8C + j*C + c  <-  bit j of byte g*nb+b of chunk c
            bits = jnp.concatenate(
                [
                    (xt >> jnp.uint32(8 * (g * nb + byte) + j))
                    & jnp.uint32(1)
                    for byte in range(nb)
                    for j in range(8)
                ],
                axis=0,
            ).astype(jnp.int32).astype(jnp.bfloat16)  # (nb*8C, T)
            # (Mosaic has no uint32->bf16 cast; int32 hop is free here)
            prod = jnp.dot(bmv, bits, preferred_element_type=jnp.float32)
            par = prod.astype(jnp.int32).astype(jnp.uint32) & jnp.uint32(1)
            for byte in range(nb):
                ob = jnp.zeros((rpad, t), jnp.uint32)
                for i in range(8):
                    # rpad-aligned slice: no sublane shuffles
                    plane = par[(byte * 8 + i) * rpad
                                : (byte * 8 + i + 1) * rpad]
                    ob = ob | (plane << jnp.uint32(i))
                out = out | (ob << jnp.uint32(8 * (g * nb + byte)))
        out_ref[0] = out[:rows]

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, rows, w), jnp.uint32),
        grid=(b, w // t),
        in_specs=[
            pl.BlockSpec((1, cols, t), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(bm.shape, lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, rows, t), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(x3, bm)


#: GF matmul implementation: "auto" (Pallas fused kernel on TPU, einsum
#: bit-matrix on CPU), "pallas", "mxu" (einsum bit-matrix — portable but
#: materializes bit planes in HBM), or "swar" (packed-lane shifts/xors
#: on the VPU). All bit-exact.
IMPL = os.environ.get("CEPH_TPU_GF_IMPL", "auto")

_IMPLS = {
    "pallas": gf_matmul_pallas,
    "mxu": gf_matmul_u32_mxu,
    "swar": gf_matmul_u32,
}


def _resolve_impl(impl: str | None) -> str:
    impl = impl or IMPL
    if impl == "auto":
        # Measured on v5e (k=8,m=3, 4 MiB stripes): the GF contraction
        # is only 8k<=64 deep x 8m=24 wide — a sliver of the 128x128
        # MXU — so the Pallas bit-plane kernel is bound by its VPU
        # unpack/pack stages (~49 GiB/s data-in), while the SWAR
        # shift/mask/xor path fuses into one XLA elementwise kernel at
        # ~134-240 GiB/s data-in, 2.7-5x faster. The MXU only pays off
        # for contractions that fill it; these codes never do.
        return "swar" if jax.default_backend() == "tpu" else "mxu"
    if impl not in _IMPLS:
        raise ValueError(
            f"unknown GF matmul impl {impl!r} (CEPH_TPU_GF_IMPL?); "
            f"expected one of {'auto', *sorted(_IMPLS)}"
        )
    return impl


# Sized above the erasure-pattern count for supported k+m (e.g. C(11,8)=165
# recovery matrices for k=8,m=3 before present-orderings): evicting a jitted
# kernel costs a full XLA recompile.
@functools.lru_cache(maxsize=4096)
def _jit_matmul_impl(matrix_bytes: bytes, rows: int, cols: int, impl: str):
    matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(rows, cols)
    return jax.jit(functools.partial(_IMPLS[impl], matrix))


def jit_gf_matmul(matrix: np.ndarray, impl: str | None = None):
    """Cached jitted GF matmul specialized to a host coding matrix."""
    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    return _jit_matmul_impl(m.tobytes(), m.shape[0], m.shape[1],
                            _resolve_impl(impl))


def gf_matmul(matrix: np.ndarray, chunks: jax.Array,
              impl: str | None = None) -> jax.Array:
    """Traceable GF matmul dispatching on the configured backend (for
    use inside larger jitted programs like datapath.write_step)."""
    return _IMPLS[_resolve_impl(impl)](matrix, chunks)


def encode(matrix: np.ndarray, data: jax.Array) -> jax.Array:
    """Parity chunks for systematic RS: data (..., k, W) -> (..., m, W)."""
    return jit_gf_matmul(matrix)(data)


def encode_with_crcs(matrix: np.ndarray, cell_bytes: int,
                     data: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused encode + per-cell checksum: data (..., k, W) uint32 ->
    (parity (..., m, W) uint32, crcs (..., k+m) uint32).

    One XLA program computes the parity AND the CRC32Cs of every data
    and parity cell — the bench's fused_stacked lesson applied to the
    write path: the CRC fold reads the parity straight out of the same
    dispatch instead of a second full host pass over the encoded cells
    (the hash_info the EC backend persists per shard)."""
    from . import crc32c as crc_ops

    parity = gf_matmul(matrix, data)
    cells = jnp.concatenate([data, parity], axis=-2)
    return parity, crc_ops.crc32c_cells_device(cells, cell_bytes)


@functools.lru_cache(maxsize=256)
def _jit_encode_with_crcs(matrix_bytes: bytes, rows: int, cols: int,
                          cell_bytes: int):
    matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(rows, cols)
    return jax.jit(functools.partial(encode_with_crcs, matrix, cell_bytes))


def jit_encode_with_crcs(matrix: np.ndarray, cell_bytes: int):
    """Cached jitted fused encode+CRC specialized to a host matrix and
    static cell length."""
    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    return _jit_encode_with_crcs(m.tobytes(), m.shape[0], m.shape[1],
                                 int(cell_bytes))


def decode(
    matrix: np.ndarray,
    k: int,
    present: list[int],
    chunks: jax.Array,
) -> jax.Array:
    """Recover all k data chunks from any k surviving chunks.

    matrix: the m x k coding matrix. present: chunk indices (0..k-1 data,
    k..k+m-1 parity) of the surviving chunks, in the exact order they are
    stacked on chunks' axis -2 (any order works). chunks: (..., k, W).
    Returns data (..., k, W). Mirrors decode_chunks
    (ErasureCodeInterface.h:411).
    """
    r = gf8.decode_matrix(matrix, k, list(present))
    return jit_gf_matmul(r)(chunks)


# -------------------- numpy reference (tests only) --------------------


def encode_np(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Bytewise numpy reference: data (k, L) uint8 -> (m, L) uint8."""
    return gf8.gf_matmul(matrix, data)


def pack_u32(chunks_bytes: np.ndarray) -> np.ndarray:
    """(..., L) uint8 with L % 4 == 0 -> (..., L/4) uint32 little-endian."""
    a = np.ascontiguousarray(chunks_bytes, dtype=np.uint8)
    return a.view("<u4").reshape(a.shape[:-1] + (a.shape[-1] // 4,))


def unpack_u32(words: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(words, dtype="<u4")
    return a.view(np.uint8).reshape(a.shape[:-1] + (a.shape[-1] * 4,))
