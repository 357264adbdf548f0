"""Device-mesh layouts for the TPU-native data path.

The reference scales with processes and sockets (AsyncMessenger fan-out of
sub-ops to shard OSDs, SURVEY.md §2.5); the TPU build scales with a
`jax.sharding.Mesh` and lets XLA insert collectives. Two mesh axes cover
the storage analogs of dp/sp:

- ``stripe`` — the stripe-batch axis (hash-sharding analog: many objects'
  stripes processed as one batch, one shard of the batch per device).
- ``width`` — the intra-chunk byte axis (striping / sequence-parallel
  analog: one chunk's words split across devices, the way
  Striper::file_to_extents RAID-0s a byte range, osdc/Striper.h:28).

The EC shard axis (k+m chunks) stays *unsharded* on purpose: coding
chunks are linear combinations of all k data chunks, so sharding it would
force an all-gather per parity row; keeping it local makes encode purely
elementwise over (stripe, width) — the layout that rides ICI only where
reductions genuinely need it (CRC tree folds, scrub digests).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

STRIPE_AXIS = "stripe"
WIDTH_AXIS = "width"


def pin_virtual_cpu(n: int) -> None:
    """Pin jax to an n-device virtual CPU platform BEFORE any backend init.

    The explicit way to ask for the virtual CPU mesh: tests (conftest),
    the driver's multi-chip dry run, bench config 8's child and
    ``thrash --chip-loss`` call it; nothing falls back to it. XLA parses
    XLA_FLAGS once per process, so this cannot rescue a process whose
    backends already initialized with fewer CPU devices — it raises
    with a clear message instead (run in a fresh process).
    """
    import os
    import re

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flag_re = r"--xla_force_host_platform_device_count=(\d+)"
    m = re.search(flag_re, flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = re.sub(
            flag_re, f"--xla_force_host_platform_device_count={n}", flags
        )
    jax.config.update("jax_platforms", "cpu")
    try:
        cpus = jax.devices("cpu")
    except RuntimeError:
        cpus = []
    if len(cpus) < n:
        raise RuntimeError(
            f"virtual CPU mesh has {len(cpus)} devices; need {n} — a jax "
            "backend initialized before pin_virtual_cpu could set "
            "XLA_FLAGS; call it first (or use a fresh process)"
        )


def get_devices(n: int):
    """The first n devices of the default backend. Raises when it has
    fewer: a mesh never quietly lands on other devices than the ones
    the process runs on. Callers that want the virtual CPU mesh ask for
    it with :func:`pin_virtual_cpu` first."""
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices; the {jax.default_backend()} backend has "
            f"{len(devs)}")
    return devs[:n]


def make_mesh(devices=None, width: int = 1) -> Mesh:
    """2D mesh over all (or given) devices: (stripe, width).

    width divides the device count; the remainder goes to the stripe
    axis. width=1 (default) is the pure batch-parallel layout.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % width:
        raise ValueError(f"width={width} does not divide device count {n}")
    arr = np.array(devices).reshape(n // width, width)
    return Mesh(arr, (STRIPE_AXIS, WIDTH_AXIS))


def chunk_batch_spec() -> P:
    """PartitionSpec for (B, k, W) chunk batches: batch over stripe,
    chunk axis replicated, words over width."""
    return P(STRIPE_AXIS, None, WIDTH_AXIS)


def chunk_batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, chunk_batch_spec())


def per_stripe_spec() -> P:
    """PartitionSpec for per-stripe scalars/ids: (B, ...) over stripe."""
    return P(STRIPE_AXIS)


def per_stripe_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, per_stripe_spec())


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_batch(n: int, mesh: Mesh) -> int:
    """Smallest batch >= n divisible by the stripe-axis size."""
    s = mesh.shape[STRIPE_AXIS]
    return math.ceil(n / s) * s


def pad_batch_pow2(n: int, mesh: Mesh | None = None) -> int:
    """ONE pad decision for the batched data path: the smallest batch
    >= n that satisfies BOTH the jit shape-bucketing cap
    (ECBatcher._pow2_pad's reason to exist: log-many compiled shapes)
    and, when a mesh is given, divisibility by the stripe-axis size.
    Computing the two pads in sequence double-pads (n=5, stripe=6:
    pow2 pads 5->8, then the mesh pad 8->12, where 6 was already
    enough). Folded form: stripe_size * next_pow2(ceil(n / stripe)) —
    every PER-DEVICE batch length is a power of two, shape count stays
    O(log B), and the mesh pad is minimal. Without a mesh this is the
    plain next power of two."""
    if mesh is None:
        return 1 << max(0, (n - 1)).bit_length()
    s = mesh.shape[STRIPE_AXIS]
    per_dev = math.ceil(n / s)
    return s * (1 << max(0, (per_dev - 1)).bit_length())
