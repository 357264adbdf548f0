"""Faults planted under the timed path, for the control and the fault
tests. Each is a context manager that patches one program class for
its duration; nothing in the harness knows about them.

- ``parity_dropped`` (the control): the pool acknowledges writes whose
  parity shards never reach a store, breaking the configuration's
  guarantee that a write is acknowledged only after all k+m shards
  commit.
- ``state_unchanged``: a write is acknowledged but no shard changes;
  a decode returns its input survivors unchanged.
- ``half_batch``: only the first half of each encode or decode batch
  is computed; the rest comes back zero.
- ``answer_altered``: one byte of each batch's parity, or of each
  decode's output, is flipped where it is produced.
- ``exchange_left_out``: the mesh's per-device readback keeps only the
  first device's rows (the four-chip cell).
"""
from __future__ import annotations

import contextlib

import numpy as np

#: the harness's object names (workloads/*.json name_prefix)
PREFIX = b"rbench-"


def _shard_pos(cid: str) -> int | None:
    head, sep, tail = cid.rpartition("s")
    return int(tail) if sep and tail.isdigit() and "." in head else None


@contextlib.contextmanager
def _patch(obj, attr: str, make):
    orig = getattr(obj, attr)
    setattr(obj, attr, make(orig))
    try:
        yield
    finally:
        setattr(obj, attr, orig)


def _drop_ops(keep):
    """MemStore.queue_transaction that drops the ops ``keep`` refuses."""
    from ceph_tpu.store import transaction as tx

    def make(orig):
        def queue_transaction(self, t, on_commit=None):
            ops = [op for op in t.ops if keep(op)]
            if len(ops) != len(t.ops):
                t = tx.Transaction(ops=ops)
            return orig(self, t, on_commit)
        return queue_transaction
    return make


def _is_bench_object(op) -> bool:
    return op.oid is not None and bytes(op.oid).startswith(PREFIX)


@contextlib.contextmanager
def parity_dropped(k: int):
    from ceph_tpu.store.memstore import MemStore

    def keep(op):
        pos = _shard_pos(op.cid)
        return not (_is_bench_object(op) and pos is not None and pos >= k)

    with _patch(MemStore, "queue_transaction", _drop_ops(keep)):
        yield


@contextlib.contextmanager
def state_unchanged(k: int):
    from ceph_tpu.cluster.ecbatch import ECBatcher
    from ceph_tpu.store.memstore import MemStore

    def keep(op):
        return not (_is_bench_object(op) and op.code == "write")

    def make_decode(orig):
        def _decode_sync(self, codec, present, want, cells):
            return np.ascontiguousarray(cells[:, : len(want), :])
        return _decode_sync

    with _patch(MemStore, "queue_transaction", _drop_ops(keep)), \
            _patch(ECBatcher, "_decode_sync", make_decode):
        yield


@contextlib.contextmanager
def half_batch(k: int):
    from ceph_tpu.cluster.ecbatch import ECBatcher

    def make_encode(orig):
        def _encode_sync(self, codec, cells):
            parity, crcs = orig(self, codec, cells)
            parity = np.array(parity)
            parity[len(parity) // 2:] = 0
            return parity, crcs
        return _encode_sync

    def make_decode(orig):
        def _decode_sync(self, codec, present, want, cells):
            out = np.array(orig(self, codec, present, want, cells))
            out[len(out) // 2:] = 0
            return out
        return _decode_sync

    with _patch(ECBatcher, "_encode_sync", make_encode), \
            _patch(ECBatcher, "_decode_sync", make_decode):
        yield


@contextlib.contextmanager
def answer_altered(k: int):
    from ceph_tpu.cluster.ecbatch import ECBatcher

    def make_encode(orig):
        def _encode_sync(self, codec, cells):
            parity, crcs = orig(self, codec, cells)
            parity = np.array(parity)
            parity[0, 0, 0] ^= 1
            return parity, crcs
        return _encode_sync

    def make_decode(orig):
        def _decode_sync(self, codec, present, want, cells):
            out = np.array(orig(self, codec, present, want, cells))
            out[0, 0, 0] ^= 1
            return out
        return _decode_sync

    with _patch(ECBatcher, "_encode_sync", make_encode), \
            _patch(ECBatcher, "_decode_sync", make_decode):
        yield


@contextlib.contextmanager
def exchange_left_out(k: int):
    from ceph_tpu.parallel import runtime

    def make(orig):
        def shard_rows_to_host(arr, out=None):
            first = arr.addressable_shards[0]
            host = np.zeros(arr.shape, arr.dtype)
            host[first.index] = np.asarray(first.data)
            return host
        return shard_rows_to_host

    with _patch(runtime, "shard_rows_to_host", make):
        yield


FAULTS = {
    "parity_dropped": parity_dropped,
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "answer_altered": answer_altered,
    "exchange_left_out": exchange_left_out,
}
