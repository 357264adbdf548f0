"""Plain reference of what an rs_tpu erasure-coded pool stores.

Written from the published definitions, and importing nothing of the
program under test:

- GF(2^8) with the primitive polynomial x^8+x^4+x^3+x^2+1 (0x11D), the
  field of jerasure w=8, gf-complete and ISA-L;
- the systematic Vandermonde code ("reed_sol_van"): the (k+m) x k
  matrix V[i][j] = i^j, column-reduced until its top k x k block is the
  identity; its bottom m rows are the coding matrix;
- Ceph's stripe layout (ECUtil stripe_info_t): an object is zero-padded
  to whole stripes of k * stripe_unit bytes, and cell j of stripe s
  lies at offset s * stripe_unit of shard j;
- CRC32C (Castagnoli, reflected polynomial 0x82F63B78) as Ceph's
  ``ceph_crc32c(-1, data, len)`` gives it: register seeded with
  0xFFFFFFFF and returned without a final xor. Ceph's HashInfo keeps
  it per shard; here it is kept per stripe_unit cell of every shard,
  little-endian u32 per cell.

Everything here is numpy on the host, run after the window has closed.
"""
from __future__ import annotations

import numpy as np

GF_POLY = 0x11D
CRC32C_POLY = 0x82F63B78


def _gf_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _gf_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[_LOG[a] + _LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(_EXP[255 - _LOG[a]])


def gf_pow(a: int, n: int) -> int:
    out = 1
    for _ in range(n):
        out = gf_mul(out, a)
    return out


def mul_table() -> np.ndarray:
    """(256, 256) uint8: row c maps a byte b to c*b."""
    t = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            t[a, b] = _EXP[_LOG[a] + _LOG[b]]
    return t


_MUL = mul_table()


def vandermonde_coding_matrix(k: int, m: int) -> np.ndarray:
    """The m x k coding rows of the systematic Vandermonde code."""
    v = [[gf_pow(i, j) for j in range(k)] for i in range(k + m)]
    for col in range(k):
        if v[col][col] == 0:
            swap = next(c for c in range(col + 1, k) if v[col][c])
            for row in v:
                row[col], row[swap] = row[swap], row[col]
        inv = gf_inv(v[col][col])
        for row in v:
            row[col] = gf_mul(row[col], inv)
        for c in range(k):
            f = v[col][c]
            if c != col and f:
                for row in v:
                    row[c] ^= gf_mul(f, row[col])
    return np.array(v[k:], dtype=np.uint8)


def shards(data: bytes, k: int, m: int, stripe_unit: int) -> list[np.ndarray]:
    """The k + m shard files of one object, each nstripes * su bytes."""
    width = k * stripe_unit
    nstripes = -(-len(data) // width)
    buf = np.zeros(nstripes * width, dtype=np.uint8)
    buf[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    cells = buf.reshape(nstripes, k, stripe_unit)
    out = [np.ascontiguousarray(cells[:, j, :]).reshape(-1)
           for j in range(k)]
    coding = vandermonde_coding_matrix(k, m)
    for r in range(m):
        acc = np.zeros(nstripes * stripe_unit, dtype=np.uint8)
        for j in range(k):
            c = int(coding[r, j])
            if c == 1:
                acc ^= out[j]
            elif c:
                acc ^= _MUL[c][out[j]]
        out.append(acc)
    return out


def _crc_table() -> np.ndarray:
    t = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ CRC32C_POLY if c & 1 else c >> 1
        t[i] = c
    return t


_CRC = _crc_table()


def crc32c_cells(cells: np.ndarray) -> np.ndarray:
    """CRC32C of each row of a (n, cell_bytes) uint8 array, vectorized
    across rows (one table step per byte position)."""
    cells = np.asarray(cells, dtype=np.uint8)
    crc = np.full(cells.shape[0], 0xFFFFFFFF, dtype=np.uint32)
    for pos in range(cells.shape[1]):
        crc = _CRC[(crc ^ cells[:, pos]) & 0xFF] ^ (crc >> 8)
    return crc


def hinfo(shard_files: list[np.ndarray], stripe_unit: int) -> list[bytes]:
    """The per-cell CRC32C attribute of each shard, LE u32 per cell."""
    rows = np.stack([s.reshape(-1, stripe_unit) for s in shard_files])
    n, ncells, _ = rows.shape
    crcs = crc32c_cells(rows.reshape(n * ncells, stripe_unit))
    return [crcs[i * ncells:(i + 1) * ncells].astype("<u4").tobytes()
            for i in range(n)]
