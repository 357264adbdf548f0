"""Faults planted under the timed path of the RBD cell, beside
``plant.py``'s, and the control run with them on the chip:

    python benchmark/tests/rbd_plant.py --workload rbd-k4m2-4k-randwrite \
        --seconds 10 --seeds 1,2,3 --fault rmw_old_zeroed

(the options are ``control.py``'s; ``--fault`` also takes every fault
of ``plant.py``).

- ``rbd_parity_dropped``: the pool acknowledges writes whose parity
  shards of ``rbd_data.`` objects never reach a store, breaking the
  guarantee that a write is acknowledged only after all k+m shards of
  every touched stripe committed.
- ``rmw_old_zeroed``: the read-modify-write lays zeros where the old
  stripe data it read belongs, as if the read were skipped: the other
  cells of every overwritten stripe are lost.
"""
from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE),
                os.path.dirname(os.path.dirname(HERE))]

import plant  # noqa: E402

#: the RBD cell's data objects
PREFIX = b"rbd_data."


@contextlib.contextmanager
def rbd_parity_dropped(k: int):
    from ceph_tpu.store.memstore import MemStore

    def keep(op):
        pos = plant._shard_pos(op.cid)
        return not (op.oid is not None and bytes(op.oid).startswith(PREFIX)
                    and pos is not None and pos >= k)

    with plant._patch(MemStore, "queue_transaction", plant._drop_ops(keep)):
        yield


@contextlib.contextmanager
def rmw_old_zeroed(k: int):
    from ceph_tpu.cluster.stripe import Overlay

    def make(orig):
        def scatter(self, dst, tlist, si, old_runs):
            zeros = [(s, bytes(len(data))) for s, data in old_runs]
            return orig(self, dst, tlist, si, zeros)
        return scatter

    with plant._patch(Overlay, "scatter", make):
        yield


FAULTS = {
    "rbd_parity_dropped": rbd_parity_dropped,
    "rmw_old_zeroed": rmw_old_zeroed,
}


if __name__ == "__main__":
    import control

    plant.FAULTS.update(FAULTS)
    sys.exit(control.main())
