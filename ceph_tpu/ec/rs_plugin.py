"""Reed-Solomon codec plugin ("rs_tpu") — the jerasure-role plugin.

Covers the reference's matrix techniques (ErasureCodeJerasure.h:23-246):
reed_sol_van, reed_sol_r6_op, cauchy_orig, cauchy_good.

Wire-compatibility note: reed_sol_van and reed_sol_r6_op are byte-wise
GF(2^8) matrix codes here exactly as in the reference, so chunk bytes
match jerasure's output. cauchy_orig/cauchy_good use the SAME Cauchy
generator matrices but apply them byte-wise, whereas the reference runs
them as bitmatrix *schedule* codes with a packetsize-dependent bit-sliced
layout (ErasureCodeJerasure.cc:261-307, jerasure_schedule_encode) — so
identically-named cauchy profiles are NOT wire-compatible with
reference-written shards (same erasure tolerance, different chunk bytes).
The bit-matrix RAID6 family (liberation, blaum_roth, liber8tion) is a
distinct code family, tracked as a follow-up.

Execution backends per profile key "backend":
- "device" (default): batched GF(2^8) SWAR kernels on TPU (ops/rs.py);
- "host": the C++ native core (the jerasure/ISA-L role), chosen by
  profile or by the engine probe's cost model, never as a stand-in
  for a missing device.

Beyond the byte-oriented ErasureCodeInterface surface, the plugin exposes
the batched device API the EC backend uses: encode_batch/decode_batch over
(B, k, W) uint32 stripe batches — one XLA dispatch for the whole batch
instead of the reference's per-stripe jerasure_matrix_encode calls
(ErasureCodeJerasure.cc:105-162).
"""
from __future__ import annotations

import functools

import numpy as np

from .. import native
from ..ops import gf8  # numpy-only; ops.rs (jax) is imported lazily
from . import ECError, ErasureCode
from .registry import register

TECHNIQUES = ("reed_sol_van", "reed_sol_r6_op", "cauchy_orig", "cauchy_good")


@functools.lru_cache(maxsize=256)
def _matrix_for(technique: str, k: int, m: int) -> np.ndarray:
    if technique == "reed_sol_van":
        return gf8.vandermonde_rs_matrix(k, m)
    if technique == "reed_sol_r6_op":
        return gf8.raid6_matrix(k)
    if technique == "cauchy_orig":
        return gf8.cauchy_rs_matrix(k, m)
    if technique == "cauchy_good":
        return gf8.cauchy_good_matrix(k, m)
    raise ECError(
        f"technique {technique!r} not supported (know {TECHNIQUES})"
    )


@functools.lru_cache(maxsize=4096)
def _decode_matrix_cached(
    technique: str, k: int, m: int, present: tuple[int, ...]
) -> np.ndarray:
    """Per-erasure-pattern recovery matrix (the ErasureCodeIsaTableCache
    role: matrix inversion amortized across ops with the same pattern)."""
    return gf8.decode_matrix(_matrix_for(technique, k, m), k, present)


@functools.lru_cache(maxsize=4096)
def _want_matrix_cached(
    technique: str, k: int, m: int,
    present: tuple[int, ...], want: tuple[int, ...],
) -> np.ndarray:
    """Recovery matrix producing exactly the ``want`` rows (generator
    indices; parity rows allowed) from k survivors in ``present`` order.
    A wanted parity row j is coding_matrix[j-k] @ recovery_matrix — the
    composition folds host-side (tiny k x k work), so rebuilding a lost
    parity chunk is STILL one device matmul (the bench fused_stacked
    trick: stack the matrices, not the dispatches)."""
    rmat = _decode_matrix_cached(technique, k, m, present)
    mat = _matrix_for(technique, k, m)
    rows = [
        rmat[w] if w < k
        else gf8.gf_matmul(mat[w - k : w - k + 1], rmat)[0]
        for w in want
    ]
    return np.ascontiguousarray(np.stack(rows))


LARGEST_VECTOR_WORDSIZE = 16  # reference ErasureCodeJerasure.cc:30


class RSCodec(ErasureCode):
    """Systematic RS over GF(2^8) with pluggable matrix technique."""

    DEFAULT_K = 7
    DEFAULT_M = 3
    DEFAULT_TECHNIQUE = "reed_sol_van"
    W = 8

    #: GF(2^8) matrix codes act independently on every byte position, so
    #: any slicing of chunks (cells, ranges) encodes/decodes identically
    #: to the whole — the property the stripe-RMW data path relies on.
    bytewise_linear = True

    def init(self, profile) -> None:
        super().init(profile)
        self.technique = self.profile.get(
            "technique", self.DEFAULT_TECHNIQUE
        )
        # jerasure's bit-matrix technique family dispatches to the
        # bitmatrix codec (ErasureCodeJerasure.h:163-246 techniques)
        from .bitmatrix_plugin import BitmatrixCodec

        if self.technique in BitmatrixCodec.DEFAULT_W:
            self.__class__ = BitmatrixCodec
            return self.init(profile)
        self.profile.setdefault("technique", self.technique)
        self.k = self.to_int("k", self.DEFAULT_K)
        self.m = self.to_int("m", self.DEFAULT_M)
        if self.technique == "reed_sol_r6_op":
            self.m = 2  # RAID6 P+Q (ErasureCodeJerasureReedSolomonRAID6)
            self.profile["m"] = "2"
        w = self.to_int("w", 8)
        if w != 8:
            raise ECError(f"only w=8 is supported, got w={w}")
        if self.k < 1 or self.m < 1 or self.k + self.m > 256:
            raise ECError(f"bad k={self.k} m={self.m} (k+m <= 256)")
        self.backend = self.profile.get("backend", "auto")
        if self.backend not in ("device", "host", "auto"):
            raise ECError(
                f"backend must be device|host|auto, not {self.backend!r}")
        self.per_chunk_alignment = self.to_bool(
            "jerasure-per-chunk-alignment", False
        )
        self.matrix = _matrix_for(self.technique, self.k, self.m)
        self._parse_mapping()

    def get_alignment(self) -> int:
        """Reference-exact (ErasureCodeJerasure.cc:174-184 for the matrix
        techniques; our byte-wise cauchy shares the matrix-RS layout, see
        module docstring): w=8 makes (w*4) % 16 == 0, so the shared branch
        is k*w*sizeof(int) = 32k and per-chunk is w*16 = 128."""
        if self.per_chunk_alignment:
            return self.W * LARGEST_VECTOR_WORDSIZE
        return self.k * self.W * 4

    # ----------------------------------------------------- byte interface

    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        """Scalar byte API: always host-native. jit specializes per
        shape, and scalar callers (recovery, scrub repair, tools) come
        with arbitrary per-object chunk lengths — on the device every
        fresh shape would cost a compile of up to seconds. The
        "device" backend applies to the BATCHED uniform-shape APIs
        (encode_batch/decode_batch), which is where the device wins.
        Both paths are bit-exact (tests/test_rs.py pins them equal)."""
        data_chunks = np.ascontiguousarray(data_chunks, dtype=np.uint8)
        return native.rs_encode(self.matrix, data_chunks)

    def decode_chunks(self, present, chunks: np.ndarray):
        present = list(present)
        chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
        # scalar path: host-native (see encode_chunks — shapes vary)
        data = native.rs_decode(self.matrix, present, chunks)
        out = {i: data[i] for i in range(self.k)}
        missing_parity = set(range(self.k, self.k + self.m)) - set(present)
        if missing_parity:
            coding = self.encode_chunks(data)
            for j in missing_parity:
                out[j] = coding[j - self.k]
        for row, idx in enumerate(present):
            if idx >= self.k:
                out[idx] = chunks[row]
        return out

    # --------------------------------------------------- batched (device)

    def resolved_backend(self) -> str:
        """The engine batched data-path encodes actually run on:
        "device"/"host" as configured, or the measured-economics probe
        for "auto" (ec/engine.py — link bandwidth decides)."""
        if self.backend == "auto":
            from . import engine

            return engine.data_path_engine()
        return self.backend

    def encode_batch(self, data):
        """(B, k, W) uint32 -> (B, m, W) uint32 parity, one dispatch."""
        from ..ops import rs

        return rs.encode(self.matrix, data)

    def encode_crc_batch(self, data, cell_bytes: int):
        """(B, k, W) uint32 -> (parity (B, m, W) uint32, crcs (B, k+m)
        uint32): parity AND the per-cell CRC32Cs of data+parity in ONE
        fused device dispatch — the write path's hash_info comes back
        with the parity instead of a second host pass over the cells."""
        from ..ops import rs

        return rs.jit_encode_with_crcs(self.matrix, cell_bytes)(data)

    def encode_crc_batch_mesh(self, data, cell_bytes: int, mesh):
        """encode_crc_batch jitted UNDER a (stripe, width) device
        mesh: the (B, k, W) uint32 batch is staged device-resident
        (chunk_batch_sharding), the fused encode+CRC program runs
        sharded so each chip produces the shard rows and CRCs it owns,
        and both results come back as MESH-SHARDED jax arrays for
        per-device consumption (parallel/runtime.py) — the serving-
        path form of the dryrun-only MULTICHIP shape."""
        from ..parallel import runtime

        return runtime.mesh_encode_crc_batch(mesh, self.matrix,
                                             cell_bytes, data)

    def decode_batch_mesh(self, present: tuple[int, ...], surviving,
                          want: tuple[int, ...], mesh, method: str):
        """Collective repair: the stacked recovery matmul for ``want``
        rows from ``present`` survivors, distributed over the mesh —
        survivors resident one chunk-group per width device, partials
        XOR-combined by ``method`` (allgather / psum_bits) instead of
        gathered through messenger fan-in. Returns the (B, R, W)
        result batch-sharded."""
        from ..parallel import runtime

        rmat = self.decode_matrix_for(present, want)
        return runtime.mesh_decode_cells(mesh, rmat, surviving, method)

    def decode_batch(self, present: tuple[int, ...], surviving,
                     want: tuple[int, ...] | None = None):
        """(B, k, W) uint32 survivors (rows in `present` order) ->
        (B, k, W) uint32 recovered data, or — with ``want`` — exactly
        those generator rows (parity rows fold into the matrix)."""
        from ..ops import rs

        if want is None:
            rmat = _decode_matrix_cached(
                self.technique, self.k, self.m, tuple(present)
            )
        else:
            rmat = self.decode_matrix_for(present, want)
        return rs.jit_gf_matmul(rmat)(surviving)

    def decode_matrix_for(self, present, want) -> np.ndarray:
        """Host recovery matrix mapping survivors (``present`` order,
        generator indices) to the ``want`` generator rows — shared by
        the device decode path and the host engine's batched matmul."""
        return _want_matrix_cached(self.technique, self.k, self.m,
                                   tuple(present), tuple(want))


register("rs_tpu", RSCodec)
register("jerasure", RSCodec)  # reference profile-name compatibility
