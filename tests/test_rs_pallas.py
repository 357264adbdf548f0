"""Pallas GF(2^8) matmul kernel: bit-exactness vs the host byte oracle.

The real kernel targets the TPU; under the CPU test mesh it runs in
Pallas interpreter mode — same jaxpr, same semantics. Nothing checks it
on the chip yet: the serving path picks the SWAR kernel there (ROADMAP,
Design).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from ceph_tpu import native
from ceph_tpu.ops import gf8, rs


@pytest.mark.parametrize(
    "r,c,w",
    [(3, 8, 1024), (1, 2, 128), (8, 8, 512), (4, 6, 384), (2, 5, 256)],
)
def test_pallas_matches_host_oracle(r, c, w):
    rng = np.random.default_rng(r * 100 + c)
    mat = rng.integers(0, 256, (r, c), dtype=np.uint8)
    data = rng.integers(0, 256, (3, c, w * 4), dtype=np.uint8)
    want = np.stack([gf8.gf_matmul(mat, d) for d in data])
    got = rs.gf_matmul_pallas(mat, jnp.asarray(rs.pack_u32(data)),
                              interpret=True)
    assert (rs.unpack_u32(np.asarray(got)) == want).all()


def test_pallas_2d_no_batch():
    rng = np.random.default_rng(9)
    mat = native.rs_matrix_vandermonde(4, 2)
    data = rng.integers(0, 256, (4, 2048), dtype=np.uint8)
    want = gf8.gf_matmul(mat, data)
    got = rs.gf_matmul_pallas(mat, jnp.asarray(rs.pack_u32(data)),
                              interpret=True)
    assert (rs.unpack_u32(np.asarray(got)) == want).all()


def test_pallas_unaligned_width_falls_back():
    # W=100 words has no 128-multiple tile; must still be correct (einsum).
    rng = np.random.default_rng(3)
    mat = native.rs_matrix_vandermonde(3, 2)
    data = rng.integers(0, 256, (3, 400), dtype=np.uint8)
    want = gf8.gf_matmul(mat, data)
    got = rs.gf_matmul_pallas(mat, jnp.asarray(rs.pack_u32(data)),
                              interpret=True)
    assert (rs.unpack_u32(np.asarray(got)) == want).all()


def test_pallas_off_tpu_without_interpret_is_an_error():
    """The caller picks interpret mode; off the TPU, the kernel refuses
    to lower instead of quietly running the interpreter."""
    mat = native.rs_matrix_vandermonde(3, 2)
    data = np.zeros((3, 512), dtype=np.uint8)
    with pytest.raises(Exception, match="interpret"):
        rs.gf_matmul_pallas(mat, jnp.asarray(rs.pack_u32(data)))


def test_lift_bitmatrix_planar_permutation():
    rng = np.random.default_rng(5)
    mat = rng.integers(0, 256, (3, 4), dtype=np.uint8)
    bm = rs._lift_bitmatrix(mat)
    bmp = rs._lift_bitmatrix_planar(mat)
    r, c = mat.shape
    for rr in range(r):
        for i in range(8):
            for cc in range(c):
                for j in range(8):
                    assert bmp[i * r + rr, j * c + cc] == bm[rr * 8 + i, cc * 8 + j]


def test_pallas_tile_selection():
    assert rs._pallas_tile(1024) == 1024
    assert rs._pallas_tile(131072) == 8192
    assert rs._pallas_tile(100) is None
    assert rs._pallas_tile(384) == 384
    t = rs._pallas_tile(1280)
    assert t is not None and 1280 % t == 0 and t % 128 == 0


def test_crc_pallas_matches_tree():
    """The MXU matmul CRC (kept as a documented alternative; the VPU
    tree measured faster and stays default) is bit-exact vs the host."""
    import jax.numpy as jnp

    from ceph_tpu.ops import crc32c as crc_ops

    rng = np.random.default_rng(7)
    for nb, blob in [(5, 1024), (130, 4096), (8, 65536)]:
        blobs = rng.integers(0, 256, (nb, blob), dtype=np.uint8)
        words = jnp.asarray(crc_ops.pack_blobs(blobs))
        got = np.asarray(
            crc_ops.crc32c_words_pallas(words, interpret=True))
        want = native.crc32c_batch(blobs) ^ np.uint32(
            crc_ops.zeros_shift(0xFFFFFFFF, blob))
        assert (got == want).all(), (nb, blob)
