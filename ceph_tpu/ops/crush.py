"""CRUSH straw2 placement as vmapped JAX/XLA kernels.

The reference computes placement one object at a time in C
(bucket_straw2_choose, src/crush/mapper.c:339-363; Jenkins hash
src/crush/hash.c; fixed-point crush_ln + tables src/crush/mapper.c:226,
crush_ln_table.h). The math is integer-only and embarrassingly parallel
over objects, so the TPU-native form is a batched kernel: every op below
takes arrays of placement inputs ``x`` and computes all draws with uint32/
int64 vector arithmetic — no data-dependent control flow, one fused XLA
program, bit-exact against the C++ host reference (ceph_tpu.native).

This is north-star config 5 (BASELINE.json): 10 M objects x 1 K-OSD map
bulk placement. The full rule engine (firstn/indep retries over a bucket
hierarchy, mapper.c:438,633) lives in ceph_tpu/placement/ and is built on
these primitives.

int64 note: crush_ln is 16.44 fixed point and straw2 draws are signed
64-bit (div64_s64 in the reference). Rather than flipping the process-wide
jax_enable_x64 flag (which would change default dtypes for unrelated user
code), every public entry point here runs under a scoped
``jax.enable_x64()`` context — callers embedding these primitives in their
own ``jit`` must do the same (ceph_tpu/placement does).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..native import gen_tables  # (table single-source)

HASH_SEED = np.uint32(1315423911)
_U32 = jnp.uint32
_I64 = jnp.int64
INT64_MIN = -(1 << 63)

#: the scoped 64-bit context manager; the rest of the tree imports
#: THIS symbol (ops.crush.enable_x64)
enable_x64 = jax.enable_x64


def _x64(fn):
    """Run fn under scoped 64-bit mode (int64 constants trace correctly)."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with enable_x64():
            return fn(*args, **kwargs)

    return wrapper


# ------------------------------------------------------------------ tables


@functools.lru_cache(maxsize=None)
def _ln_tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(RH[129], LH[129], LL[256]) int64, same source as the C header."""
    rhlh = gen_tables.rh_lh_tables()
    ll = gen_tables.ll_table()
    rh = np.array([a for a, _ in rhlh], dtype=np.int64)
    lh = np.array([b for _, b in rhlh], dtype=np.int64)
    return rh, lh, np.array(ll, dtype=np.int64)


# ------------------------------------------------------------- jenkins hash


def _hashmix(a, b, c):
    """Robert Jenkins' 96-bit mix; uint32 wraparound arithmetic."""
    a = (a - b - c) ^ jax.lax.shift_right_logical(c, _U32(13))
    b = (b - c - a) ^ (a << _U32(8))
    c = (c - a - b) ^ jax.lax.shift_right_logical(b, _U32(13))
    a = (a - b - c) ^ jax.lax.shift_right_logical(c, _U32(12))
    b = (b - c - a) ^ (a << _U32(16))
    c = (c - a - b) ^ jax.lax.shift_right_logical(b, _U32(5))
    a = (a - b - c) ^ jax.lax.shift_right_logical(c, _U32(3))
    b = (b - c - a) ^ (a << _U32(10))
    c = (c - a - b) ^ jax.lax.shift_right_logical(b, _U32(15))
    return a, b, c


def hash32_2(a: jax.Array, b: jax.Array) -> jax.Array:
    """Vectorized crush_hash32_2 (reference src/crush/hash.c)."""
    a = a.astype(_U32)
    b = b.astype(_U32)
    h = _U32(HASH_SEED) ^ a ^ b
    x = jnp.full_like(h, 231232, dtype=_U32)
    y = jnp.full_like(h, 1232, dtype=_U32)
    a, b, h = _hashmix(a, b, h)
    x, a, h = _hashmix(x, a, h)
    b, y, h = _hashmix(b, y, h)
    return h


def hash32_3(a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    """Vectorized crush_hash32_3 — the straw2 draw hash."""
    a = a.astype(_U32)
    b = b.astype(_U32)
    c = c.astype(_U32)
    h = _U32(HASH_SEED) ^ a ^ b ^ c
    x = jnp.full_like(h, 231232, dtype=_U32)
    y = jnp.full_like(h, 1232, dtype=_U32)
    a, b, h = _hashmix(a, b, h)
    c, x, h = _hashmix(c, x, h)
    y, a, h = _hashmix(y, a, h)
    b, x, h = _hashmix(b, x, h)
    y, c, h = _hashmix(y, c, h)
    return h


# ---------------------------------------------------------------- crush_ln


#: None = auto (gather on CPU where it is fast, one-hot elsewhere);
#: True/False forces a path (tests pin both paths equal).
LUT_USE_GATHER: bool | None = None


def _use_gather_luts() -> bool:
    if LUT_USE_GATHER is not None:
        return LUT_USE_GATHER
    return jax.default_backend() == "cpu"


def _lut_nogather(idx: jax.Array, *tables: np.ndarray) -> list[jax.Array]:
    """Bit-exact small-table lookups without gathers.

    TPU vector units have no gather instruction, so jnp.take from even a
    129-entry table serializes (measured ~70x slowdown of the whole straw2
    kernel). Instead: one-hot compare against an iota, multiply-accumulate
    the table values split into 17-bit limbs in f32 (a one-hot sum selects
    exactly one term, and ints < 2^24 are exact in f32, so the result is
    bit-exact). The (..., T) one-hot never materializes in HBM — XLA fuses
    compare -> mul -> reduce into one elementwise pass; multiple tables
    share the same one-hot. Values must be non-negative and < 2^51.
    """
    iota = jnp.arange(len(tables[0]), dtype=jnp.int32)
    onehot = (idx[..., None] == iota).astype(jnp.float32)
    outs = []
    for tbl in tables:
        t = np.asarray(tbl, dtype=np.int64)
        assert t.shape == tables[0].shape
        assert (t >= 0).all() and int(t.max()) < (1 << 51), "limb overflow"
        val = None
        for j in range(3):
            limb = ((t >> (17 * j)) & 0x1FFFF).astype(np.float32)
            if not limb.any():
                continue
            part = jnp.sum(onehot * jnp.asarray(limb), axis=-1)
            part = part.astype(_I64) << _I64(17 * j)
            val = part if val is None else val + part
        outs.append(val if val is not None else jnp.zeros(idx.shape, _I64))
    return outs


@_x64
def crush_ln(u: jax.Array) -> jax.Array:
    """2^44 * log2(x+1) in 16.44 fixed point (mapper.c:226), elementwise.

    u is the 16-bit hash value (hash & 0xffff); returns int64. Matches
    ct_crush_ln bit-for-bit, including the x == 0x10000 int64-wraparound
    quirk of the reference. Table lookups use the gather-free one-hot
    reduction (_lut_nogather) — the straw2 hot path is gather-bound
    otherwise.
    """
    rh_t, lh_t, ll_t = _ln_tables()
    x = (u.astype(_U32) & _U32(0xFFFF)) + _U32(1)  # 1..0x10000
    # floor(log2(x)) without clz: count of k in 1..16 with x >> k != 0.
    hb = jnp.zeros(x.shape, dtype=jnp.int32)
    for k in range(1, 17):
        hb = hb + (jax.lax.shift_right_logical(x, _U32(k)) > 0).astype(jnp.int32)
    big = x >= _U32(0x8000)
    shift = jnp.where(big, 0, 15 - hb).astype(_U32)
    xs = x << shift
    iexpon = jnp.where(big, 15, hb).astype(_I64)
    idx1 = (jax.lax.shift_right_logical(xs, _U32(8)) - _U32(128)).astype(jnp.int32)
    if _use_gather_luts():
        rh = jnp.asarray(rh_t)[idx1]
        lh = jnp.asarray(lh_t)[idx1]
    else:
        rh, lh = _lut_nogather(idx1, rh_t, lh_t)
    # (int64)x * RH can wrap at x == 0x10000 — intentional, matches C.
    xl64 = (xs.astype(_I64) * rh) >> _I64(48)
    idx2 = (xl64 & _I64(0xFF)).astype(jnp.int32)
    if _use_gather_luts():
        ll = jnp.asarray(ll_t)[idx2]
    else:
        (ll,) = _lut_nogather(idx2, ll_t)
    return (iexpon << _I64(44)) + ((lh + ll) >> _I64(4))


# ------------------------------------------------------------------ straw2


def _div_u48(n: jax.Array, w: jax.Array) -> jax.Array:
    """Exact floor(n / w) for int64 n in [0, 2^48], w in [1, 2^32).

    XLA lowers emulated-int64 `//` to bit-serial long division (~64
    dependent steps/lane) — the round-3 straw2 ceiling. This replaces
    it with three float32 reciprocal rounds plus exact int64 remainder
    corrections (wraparound-safe: every q*w is congruent mod 2^64 to
    the true product, and the true remainder fits):

      round 1: q ~= n/w      quotient <= 2^48, fp32 rel err 2^-23
               -> remainder |r| <~ 2^26
      round 2: refine on r   -> |r| <~ 8*w
      round 3: refine again  -> quotient off by at most ~1
      two conditional +-1 steps land it exactly.

    Bit-exactness is pinned by tests/test_crush_ops.py against the C++
    host core across the full (n, w) corner lattice.
    """
    wf = w.astype(jnp.float32)
    q = jnp.floor(n.astype(jnp.float32) / wf).astype(_I64)
    r = n - q * w
    q = q + jnp.trunc(r.astype(jnp.float32) / wf).astype(_I64)
    r = n - q * w
    q = q + jnp.trunc(r.astype(jnp.float32) / wf).astype(_I64)
    r = n - q * w
    q = q + (r >= w).astype(_I64) - (r < 0).astype(_I64)
    r = n - q * w
    q = q + (r >= w).astype(_I64) - (r < 0).astype(_I64)
    return q


@_x64
def straw2_draw(
    x: jax.Array, item_id: jax.Array, r: jax.Array, weight: jax.Array
) -> jax.Array:
    """Per-(x, item, r) straw length (mapper.c:313-337), int64.

    weight is 16.16 fixed point (uint32). Zero weight draws INT64_MIN so
    the item can never win (reference skips via `if (weights[i])`).
    """
    u = hash32_3(x, item_id, r) & _U32(0xFFFF)
    ln = crush_ln(u)
    # draw = (ln - 2^48) / weight with C truncation; numerator <= 0 so
    # trunc == -((2^48 - ln) // w) with nonneg floor division.
    neg = _I64(0x1000000000000) - ln
    w = weight.astype(_I64)
    q = -_div_u48(neg, jnp.maximum(w, _I64(1)))
    return jnp.where(w == 0, _I64(INT64_MIN), q)


@_x64
def straw2_choose(
    items: jax.Array,
    ids: jax.Array,
    weights: jax.Array,
    x: jax.Array,
    r: jax.Array,
) -> jax.Array:
    """Vectorized bucket_straw2_choose (mapper.c:339): argmax of draws.

    items/ids/weights: (n,) bucket contents (ids are the hash inputs,
    items the returned values — split mirrors choose_args remapping).
    x: (...,) placement inputs; r: scalar or (...,) replica rank.
    Returns (...,) chosen items. First-wins ties, like the C loop.
    """
    xs = x.astype(_U32)[..., None]
    rs = jnp.broadcast_to(jnp.asarray(r, dtype=_U32), x.shape)[..., None]
    draws = straw2_draw(xs, ids[None, :], rs, weights[None, :])
    # two-pass max + first-match instead of a direct int64 argmax: the
    # boolean argmax keeps first-wins tie semantics and measures ~17%
    # faster on v5e (emulated-i64 argmax index tracking is the cost)
    mx = jnp.max(draws, axis=-1, keepdims=True)
    win = jnp.argmax(draws == mx, axis=-1)
    return items[win]


# One jitted entry; jax.jit's shape-keyed cache specializes per (n, N).
_jit_straw2 = jax.jit(straw2_choose)


def straw2_bulk(
    items: np.ndarray,
    weights: np.ndarray,
    xs: np.ndarray,
    r: int = 0,
    ids: np.ndarray | None = None,
) -> np.ndarray:
    """Bulk placement: one straw2 choose per x. Matches native.straw2_bulk.

    items (n,) int32, weights (n,) uint32 16.16 fixed point, xs (N,)
    uint32. The jit is cached per bucket size; the whole batch is one
    device dispatch (the 10 M x 1 K north-star shape).
    """
    items_d = jnp.asarray(np.ascontiguousarray(items, dtype=np.int32))
    ids_d = (
        items_d
        if ids is None
        else jnp.asarray(np.ascontiguousarray(ids, dtype=np.int32))
    )
    weights_d = jnp.asarray(np.ascontiguousarray(weights, dtype=np.uint32))
    xs_d = jnp.asarray(np.ascontiguousarray(xs, dtype=np.uint32))
    with enable_x64():
        out = _jit_straw2(
            items_d, ids_d, weights_d, xs_d, jnp.asarray(r, dtype=jnp.uint32)
        )
    return np.asarray(out, dtype=np.int32)
