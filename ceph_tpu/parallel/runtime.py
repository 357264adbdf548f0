"""Serving-path mesh runtime: the piece that promotes the ``parallel``
layouts from dryrun validation to the OSD data path.

The ECBatcher (cluster/ecbatch.py) talks to the mesh exclusively
through this module:

- :func:`serving_mesh` resolves the configured device mesh once per
  process. A platform that cannot supply the configured devices is an
  error, not a quiet single-device run; ``osd_ec_mesh_devices <= 1``
  is how a deployment asks for the single-device path.
- :func:`mesh_encode_crc_batch` runs the fused encode+CRC program
  jitted UNDER the mesh: stripe batches are staged device-resident
  (``chunk_batch_sharding`` — batch over ``stripe``, chunk words over
  ``width``), parity comes back with the same placement and the
  per-cell CRCs batch-sharded, so each chip produces the shard cells
  and checksums it owns. No collective appears in the GF math (the
  chunk axis is replicated by design — see ``parallel.__init__``);
  the CRC tree fold is the one place reductions ride the ICI.
- :func:`mesh_decode_cells` is collective repair: survivors resident
  one chunk-group per width device (``shard_placement_sharding``),
  recovery as shard_comm's distributed GF matmul with partials
  combined by ``allgather`` or ``psum_bits`` — mesh collectives where
  the reference fans recovery sub-ops over sockets.
- :func:`shard_rows_to_host` is the SANCTIONED device->host boundary:
  it materializes a sharded result by reading each device's resident
  shard view (`addressable_shards`) — per-device readbacks, the thing
  each shard's owning OSD does to persist its own rows — never one
  whole-array gather through a single host buffer. ``host_gather`` is
  the counted escape hatch; the write phase of bench config 8 proves
  its counter stays 0.

Everything here is CPU-testable: tier-1 pins an 8-device virtual CPU
platform (tests/conftest.py), and `XLA_FLAGS=
--xla_force_host_platform_device_count=N` is the recipe on any host.
"""
from __future__ import annotations

import functools
import threading

import numpy as np

from . import (STRIPE_AXIS, WIDTH_AXIS, chunk_batch_sharding, get_devices,
               make_mesh, per_stripe_sharding)

#: combine strategies the repair knob accepts (cluster config
#: ``parallel_repair_mode``); "off" keeps the single-device decode
REPAIR_MODES = ("off", "allgather", "psum_bits")


class MeshStats:
    """Process-wide mesh data-plane ledger (the buffer plane's STATS
    shape): dispatch counts, per-device stripe occupancy, and the
    host-gather counter the write-path acceptance demands stay zero.
    Mutation goes through :meth:`bump` under the ledger's own lock —
    every OSD's batcher worker writes here concurrently, and a bare
    ``+=`` across threads loses increments."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.encode_dispatches = 0
            self.decode_dispatches = 0
            self.encode_stripes = 0          # real (pre-pad) stripes
            self.encode_stripes_padded = 0   # device-resident incl. pad
            self.decode_stripes = 0          # real (pre-pad) stripes
            self.decode_stripes_padded = 0
            self.host_gathers = 0            # whole-array gathers (MUST
            #                                  be 0 on the write path)
            self.shard_reads = 0             # per-device shard reads
            #: device id -> stripes that device owned across dispatches
            self.stripes_per_device: dict[int, int] = {}

    def bump(self, **deltas: int) -> None:
        with self._lock:
            for key, d in deltas.items():
                setattr(self, key, getattr(self, key) + d)

    def _occupancy(self, mesh, per_dev: int) -> None:
        with self._lock:
            for dev in mesh.devices.flat:
                d = self.stripes_per_device
                d[dev.id] = d.get(dev.id, 0) + per_dev

    def dump(self) -> dict:
        with self._lock:
            return {
                "mesh_encode_dispatches": self.encode_dispatches,
                "mesh_decode_dispatches": self.decode_dispatches,
                "mesh_encode_stripes": self.encode_stripes,
                "mesh_encode_stripes_padded": self.encode_stripes_padded,
                "mesh_decode_stripes": self.decode_stripes,
                "mesh_decode_stripes_padded": self.decode_stripes_padded,
                "mesh_host_gathers": self.host_gathers,
                "mesh_shard_reads": self.shard_reads,
                "mesh_stripes_per_device": dict(
                    sorted(self.stripes_per_device.items())),
            }


STATS = MeshStats()

_mesh_lock = threading.Lock()
_meshes: dict[tuple[int, int], object] = {}

#: ONE mesh program in flight at a time, forced to completion before
#: release: XLA's cross-device collectives rendezvous per (executable,
#: run) and are NOT safe against concurrent host threads launching
#: programs over overlapping device groups — the CPU backend deadlocks
#: outright (observed: three run_ids parked at the same all-reduce
#: rendezvous under the chip-loss thrash), and multi-controller chips
#: have the same hazard. Every OSD's batcher worker funnels its
#: sharded dispatch through this lock; single-device dispatches are
#: unaffected.
_dispatch_lock = threading.Lock()


def serving_mesh(n_devices: int, width: int = 1):
    """The (stripe, width) mesh the OSD serving path runs on, or
    ``None`` when the config disables the mesh (n_devices <= 1).

    A mesh the operator configured is served or refused, never
    degraded: a width that does not divide the device count, or a
    platform with fewer than ``n_devices`` devices, raises — serving
    single-device instead would report an all-zero mesh ledger from a
    run the operator asked to shard.

    Resolution is cached per (n, width) and shared by every OSD in the
    process — chips are a host resource, not a daemon one."""
    if n_devices <= 1 or width < 1:
        return None
    if n_devices % width:
        raise ValueError(
            f"osd_ec_mesh_width={width} does not divide "
            f"osd_ec_mesh_devices={n_devices}")
    key = (int(n_devices), int(width))
    with _mesh_lock:
        if key not in _meshes:
            _meshes[key] = make_mesh(get_devices(key[0]), width=key[1])
        return _meshes[key]


def reset_meshes() -> None:
    """Test hook: drop cached meshes (a later test may force a
    different virtual platform)."""
    with _mesh_lock:
        _meshes.clear()


# ------------------------------------------------------------- encode


@functools.lru_cache(maxsize=256)  # sized like rs._jit_encode_with_crcs
def _jit_mesh_encode(mesh, matrix_bytes: bytes, rows: int, cols: int,
                     cell_bytes: int):
    """Fused encode+CRC jitted under the mesh, cached per (mesh,
    matrix, cell length). out_shardings PIN the placement: parity
    stays chunk_batch-sharded (each chip holds the rows it computed),
    CRCs come back per-stripe-sharded — nothing in the program forces
    a gather onto one device."""
    import jax

    from ..ops import rs

    matrix = np.frombuffer(matrix_bytes, np.uint8).reshape(rows, cols)
    return jax.jit(
        functools.partial(rs.encode_with_crcs, matrix, int(cell_bytes)),
        in_shardings=(chunk_batch_sharding(mesh),),
        out_shardings=(chunk_batch_sharding(mesh),
                       per_stripe_sharding(mesh)),
    )


def mesh_encode_crc_batch(mesh, matrix: np.ndarray, cell_bytes: int,
                          batch: np.ndarray):
    """(B, k, W) uint32 host batch, B divisible by the stripe axis ->
    (parity (B, m, W), crcs (B, k+m)) as MESH-SHARDED jax arrays: the
    staging device_put lands each stripe block on its owning chip, one
    sharded XLA dispatch produces every shard row's cells and CRCs on
    the chip that owns them. Consumption goes through
    shard_rows_to_host (per-device views), never a whole-array
    gather."""
    import jax

    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    fn = _jit_mesh_encode(mesh, m.tobytes(), m.shape[0], m.shape[1],
                          int(cell_bytes))
    xs = jax.device_put(np.ascontiguousarray(batch),
                        chunk_batch_sharding(mesh))
    with _dispatch_lock:
        parity, crcs = fn(xs)
        jax.block_until_ready((parity, crcs))
    STATS.bump(encode_dispatches=1, encode_stripes_padded=len(batch))
    STATS._occupancy(mesh, len(batch) // mesh.shape[STRIPE_AXIS])
    return parity, crcs


# ------------------------------------------------------------- decode


def mesh_decode_cells(mesh, rmat: np.ndarray, batch: np.ndarray,
                      method: str):
    """Collective repair: (B, k', W) uint32 survivor batch times the
    (R, k') recovery matrix as shard_comm's distributed GF matmul —
    survivors resident one chunk-group per width device, partials
    XOR-combined across the mesh by ``method`` (allgather /
    psum_bits). The chunk axis is zero-padded to the width when k'
    does not divide it (GF zero columns are inert). Returns the
    (B, R, W) result as a batch-sharded jax array."""
    from . import shard_comm

    import jax

    n_w = mesh.shape[WIDTH_AXIS]
    rmat, batch = shard_comm.pad_chunk_axis(
        np.ascontiguousarray(rmat, dtype=np.uint8), batch, n_w)
    with _dispatch_lock:
        out = shard_comm.distributed_matmul(mesh, rmat, batch, method)
        jax.block_until_ready(out)
    STATS.bump(decode_dispatches=1, decode_stripes_padded=len(batch))
    return out


# ---------------------------------------------------- host boundaries


def shard_rows_to_host(arr, out: np.ndarray | None = None) -> np.ndarray:
    """SANCTIONED device->host boundary of the mesh data path: read
    each device's RESIDENT shard view and scatter it into the host
    staging — the per-device readback each shard row's owning OSD
    performs to persist its own cells, in place of one whole-array
    gather through a single host buffer. Replicated placements (the
    width-replicated repair result, per-stripe CRCs under width > 1)
    deduplicate by shard index: one owner reads, replicas are skipped.
    """
    if out is None:
        out = np.empty(arr.shape, arr.dtype)
    seen: set = set()
    for shard in arr.addressable_shards:
        key = tuple((s.start, s.stop) for s in shard.index)
        if key in seen:
            continue
        seen.add(key)
        out[shard.index] = np.asarray(shard.data)
    STATS.bump(shard_reads=len(seen))
    return out


def host_gather(arr) -> np.ndarray:
    """The UNSANCTIONED whole-array gather, kept only as a counted
    escape hatch: every call is a host gather the write path is not
    allowed to make (bench config 8 proves the counter stays 0 in the
    write phase)."""
    STATS.bump(host_gathers=1)
    return np.asarray(arr)
