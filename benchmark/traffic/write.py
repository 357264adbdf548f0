"""Traffic kind ``write``: ``rados bench write``.

A closed loop of ``concurrency`` callers, each sending ``write_full``
of an ``object_bytes`` object through the client's aio window and
waiting for its acknowledgement before it sends the next. Op ``j``
writes ``<name_prefix>-<j mod namespace_objects>`` with the seed's
payload view ``j``, so names cycle over a bounded namespace (host RAM
bounds the memstore) and a run overwrites only past that many ops.

Checked after the window, on ``check_objects`` acknowledged objects
drawn from the seed: the client's read-back, every stored data and
parity shard, and every cell's stored CRC32C, against the reference.
"""
from __future__ import annotations

from harness.objects import Payloads, compare_readback, compare_shards, rng


class Traffic:
    def __init__(self, cluster, params: dict, seed: int):
        self.cluster = cluster
        self.seed = seed
        self.object_bytes = int(params["object_bytes"])
        self.concurrency = int(params["concurrency"])
        self.namespace = int(params["namespace_objects"])
        self.prefix = params["name_prefix"]
        self.check_objects = int(params["check_objects"])
        self.failures: list[str] = []
        #: name -> index of the newest acknowledged write to it
        self.acked: dict[str, int] = {}

    def stripes_per_object(self) -> int:
        c = self.cluster
        return -(-self.object_bytes // (c.k * c.su))

    def geometry(self) -> dict:
        c = self.cluster
        return {"k": c.k, "m": c.m, "su": c.su,
                "object_bytes": self.object_bytes}

    async def setup(self) -> None:
        spo = self.stripes_per_object()
        self.cluster.warm_encode(spo, spo * self.concurrency)
        self.payloads = Payloads(self.seed, self.object_bytes)

    def name(self, j: int) -> str:
        return f"{self.prefix}-{j % self.namespace}"

    async def op(self, j: int) -> int:
        name = self.name(j)
        comp = await self.cluster.client.aio_write_full(
            self.cluster.pool_id, name, self.payloads.view(j))
        await comp.wait()
        if j > self.acked.get(name, -1):
            self.acked[name] = j
        return self.object_bytes

    async def collect(self) -> dict:
        names = sorted(self.acked)
        pick = rng(self.seed, 1).choice(
            len(names), size=min(self.check_objects, len(names)),
            replace=False)
        sample = [names[i] for i in sorted(pick)]
        readback = {}
        for n in sample:
            try:
                readback[n] = await self.cluster.client.read(
                    self.cluster.pool_id, n)
            except Exception as e:
                self.failures.append(f"read-back {n}: {e!r}")
                readback[n] = None
        return {"readback": readback,
                "stored": self.cluster.stored_shards(sample),
                "want": {n: self.payloads.view(self.acked[n])
                         for n in sample}}

    def compare(self, col: dict) -> dict:
        c = self.cluster
        shard_bad, crc_bad = compare_shards(col["stored"], col["want"],
                                            c.k, c.m, c.su)
        return {
            "readback_bad": (compare_readback(col["readback"],
                                              col["want"]), 0),
            "shard_bad": (shard_bad, 0),
            "crc_bad": (crc_bad, 0),
            "nothing_checked": (int(not col["want"]), 0),
        }

    def notes(self, snaps: dict) -> list[str]:
        ops = snaps["ops"]
        b, a = snaps["before"]["osd"], snaps["after"]["osd"]

        def d(key: str) -> float:
            return a.get(key, 0) - b.get(key, 0)

        return [
            f"writes: {len(ops)} in the window, "
            f"{max(0, len(ops) - self.namespace)} of them overwrites "
            f"(namespace {self.namespace} objects)",
            f"ec encode in the window: ec_batches {d('ec_batches')}, "
            f"stripes {d('ec_batch_stripes.sum')}, ec_batch_failures "
            f"{d('ec_batch_failures')}",
        ]
