"""Object contents made from the seed, and the comparisons of what the
cluster stored or returned with the plain reference.

Payloads: one random buffer drawn from the seed during set-up; op ``j``
carries the zero-copy view that starts ``(j mod VIEWS) * stride`` bytes
into it, so every op of a run (up to VIEWS of them) carries different
bytes and nothing is generated on the event loop during the window.
"""
from __future__ import annotations

import numpy as np

from . import reference

#: distinct payload views per run (op j reuses view j mod VIEWS)
VIEWS = 16384


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any whole number)."""
    return np.random.default_rng([seed % 2**64, *stream])


class Payloads:
    def __init__(self, seed: int, object_bytes: int):
        self.object_bytes = object_bytes
        self.stride = min(object_bytes, 4096) + 64
        self.buf = rng(seed, 0).bytes(object_bytes + VIEWS * self.stride)

    def view(self, j: int) -> memoryview:
        off = (j % VIEWS) * self.stride
        return memoryview(self.buf)[off: off + self.object_bytes]


def compare_readback(got: dict, want: dict) -> int:
    """Objects whose read-back is missing or differs: {name: bytes|None}."""
    return sum(1 for n, w in want.items() if got.get(n) != bytes(w))


def compare_shards(stored: dict, want: dict, k: int, m: int,
                   su: int) -> tuple[int, int]:
    """(shards bad, shards whose per-cell CRCs are bad) over the sampled
    objects. A shard is bad when it is missing, stored more than once,
    or differs from the reference; its CRCs are bad when the stored
    hinfo differs from the reference CRC32C of every cell."""
    bad = crc_bad = 0
    for name, data in want.items():
        ref = reference.shards(bytes(data), k, m, su)
        ref_crc = reference.hinfo(ref, su)
        have = stored.get(name, {})
        for pos in range(k + m):
            copies = have.get(pos, [])
            if len(copies) != 1:
                bad += 1
                crc_bad += 1
                continue
            shard, hinfo = copies[0]
            if shard != ref[pos].tobytes():
                bad += 1
            if hinfo != ref_crc[pos]:
                crc_bad += 1
        bad += sum(1 for pos in have if pos >= k + m)
    return bad, crc_bad
