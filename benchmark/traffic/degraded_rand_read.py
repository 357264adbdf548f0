"""Traffic kind ``degraded_rand_read``: ``rados bench rand`` on a pool
that has lost two OSDs.

Set-up writes ``prefill_objects`` objects, crash-stops the OSDs that
hold data shards ``fail_data_shards`` of the first object's PG, marks
them down through the mon (not out: no recovery runs), warms the decode
program of every degraded object's erasure pattern at the batch sizes
``warm_batch_objects`` objects' stripes make, and reads every degraded
object once serially and once at the window's concurrency. A pattern
is Ceph's ``_minimum_to_decode``: the first k surviving shard
positions rebuild the lost data positions. The window is a closed
loop of ``concurrency`` callers, each reading through the client's aio
window an object drawn uniformly from the degraded ones by the seed.

Checked after the window: every ``check_every``-th read's bytes (the
offset drawn from the seed), and every set-up read, against the
payload the object was written with.
"""
from __future__ import annotations

import asyncio

from harness.objects import Payloads, rng

#: pre-drawn read choices (op j reads choice j mod ORDER)
ORDER = 1 << 16


class Traffic:
    def __init__(self, cluster, params: dict, seed: int):
        self.cluster = cluster
        self.seed = seed
        self.object_bytes = int(params["object_bytes"])
        self.concurrency = int(params["concurrency"])
        self.prefill = int(params["prefill_objects"])
        self.prefix = params["name_prefix"]
        self.fail_shards = [int(s) for s in params["fail_data_shards"]]
        self.check_every = int(params["check_every"])
        self.warm_batch_objects = [int(n) for n in
                                   params["warm_batch_objects"]]
        self.failures: list[str] = []
        self.kept: list[tuple[int, bytes]] = []
        self.setup_bad = 0

    def geometry(self) -> dict:
        c = self.cluster
        return {"k": c.k, "m": c.m, "su": c.su,
                "object_bytes": self.object_bytes,
                "erased_rows_mean": self.erased_rows_mean}

    def name(self, i: int) -> str:
        return f"{self.prefix}-{i}"

    async def _read(self, i: int) -> bytes:
        from ceph_tpu.cluster.client import ObjectOperation

        comp = await self.cluster.client.aio_operate(
            self.cluster.pool_id, self.name(i), ObjectOperation().read())
        reply = await comp.wait()
        return bytes(reply.outs[0][1])

    async def setup(self) -> None:
        c = self.cluster
        spo = -(-self.object_bytes // (c.k * c.su))
        c.warm_encode(spo, spo * self.concurrency)
        self.payloads = Payloads(self.seed, self.object_bytes)
        comps = []
        for i in range(self.prefill):
            comps.append(await c.client.aio_write_full(
                c.pool_id, self.name(i), self.payloads.view(i)))
        await c.client.writes_wait()
        for comp in comps:
            comp.result()
        acting = {i: c.acting(self.name(i)) for i in range(self.prefill)}
        victims = [acting[0][s] for s in self.fail_shards]
        await c.fail_osds(victims)
        lost = {i: sum(1 for o in acting[i][: c.k] if o in victims)
                for i in range(self.prefill)}
        self.degraded = [i for i in range(self.prefill) if lost[i]]
        self.erased_rows_mean = (sum(lost[i] for i in self.degraded)
                                 / len(self.degraded))
        patterns = []
        for i in self.degraded:
            alive = [p for p, o in enumerate(acting[i]) if o not in victims]
            patterns.append((tuple(alive[: c.k]),
                             tuple(p for p in range(c.k)
                                   if acting[i][p] in victims)))
        self.warmed = c.warm_decode(
            patterns, [spo * n for n in self.warm_batch_objects])
        for i in self.degraded:
            await self._check_setup(i)
        sem = asyncio.Semaphore(self.concurrency)

        async def warm(i: int) -> None:
            async with sem:
                await self._check_setup(i)

        await asyncio.gather(*(warm(i) for i in self.degraded))
        self.order = rng(self.seed, 2).integers(len(self.degraded),
                                                size=ORDER)
        self.keep_phase = int(rng(self.seed, 3).integers(self.check_every))

    async def _check_setup(self, i: int) -> None:
        """A set-up read, compared like the window's."""
        try:
            got = await self._read(i)
        except Exception as e:
            self.failures.append(f"set-up read {i}: {e!r}")
            got = None
        if got != bytes(self.payloads.view(i)):
            self.setup_bad += 1

    async def op(self, j: int) -> int:
        i = self.degraded[self.order[j % ORDER]]
        data = await self._read(i)
        if j % self.check_every == self.keep_phase:
            self.kept.append((i, data))
        return len(data)

    async def collect(self) -> dict:
        return {"kept": self.kept}

    def compare(self, col: dict) -> dict:
        bad = sum(1 for i, data in col["kept"]
                  if data != bytes(self.payloads.view(i)))
        return {"read_bad": (bad, 0),
                "setup_read_bad": (self.setup_bad, 0),
                "nothing_checked": (int(not col["kept"]), 0)}

    def notes(self, snaps: dict) -> list[str]:
        ops = snaps["ops"]
        done = sum(1 for o in ops if o.ok and o.t_done <= snaps["deadline"])
        b, a = snaps["before"]["osd"], snaps["after"]["osd"]

        def d(key: str) -> float:
            return a.get(key, 0) - b.get(key, 0)

        stripes = d("ec_decode_stripes.sum")
        spo = -(-self.object_bytes // (self.cluster.k * self.cluster.su))
        return [
            f"degraded objects: {len(self.degraded)} of {self.prefill}, "
            f"mean erased data shards {self.erased_rows_mean}; "
            f"{self.warmed} decode programs warmed",
            f"ec decode in the window: ec_decode_batches "
            f"{d('ec_decode_batches')}, stripes {stripes}, "
            f"ec_decode_cold_host {d('ec_decode_cold_host')}, "
            f"ec_batch_failures {d('ec_batch_failures')}",
            f"decode stripes per degraded read: "
            f"{stripes / done if done else 0.0} (stripes per object {spo})",
        ]
