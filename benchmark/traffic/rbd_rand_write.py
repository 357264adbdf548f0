"""Traffic kind ``rbd_rand_write``: ``rbd bench --io-type write
--io-size 4K --io-threads 16 --io-pattern rand`` on an RBD image whose
data objects live in the cell's EC pool (``rbd create --data-pool``).

Set-up creates the replicated metadata pool the configuration names,
creates the image there with the EC pool as its data pool, opens it,
warms the encode shapes of the prefill and of the window, prefills
every object with a sequential ``object_bytes`` write (16 in flight),
and draws from the seed a permutation of the image's ``io_bytes``
blocks. Op ``j`` writes block ``j`` of the permutation, so no block is
written twice and the expected image does not depend on the order in
which writes complete. Every window write is a partial-stripe
overwrite of an existing object: the EC read-modify-write path.

Checked after the window, against this file's copy of the reference
(the expected image of the checked objects and the RBD default
layout's object/offset map) and ``harness/reference.py`` for the
shards: every ``check_every``-th acknowledged write read back through
the image, and ``check_objects`` data objects the window touched, each
with every stored data and parity shard and every cell's CRC32C.
"""
from __future__ import annotations

import asyncio

from harness.cluster import ACTIVE_TIMEOUT_S
from harness.objects import Payloads, compare_shards, rng

#: distinct window payloads (op j carries view j mod WINDOW_VIEWS)
WINDOW_VIEWS = 16384


def object_extent(byte_off: int, object_bytes: int) -> tuple[int, int]:
    """The RBD default layout (stripe_count 1, stripe_unit = object
    size): image byte -> (object number, offset in the object)."""
    return byte_off // object_bytes, byte_off % object_bytes


def data_object_name(image: str, objno: int) -> str:
    return f"rbd_data.{image}.{objno:016x}"


class Traffic:
    def __init__(self, cluster, params: dict, seed: int):
        self.cluster = cluster
        self.seed = seed
        self.image_name = params["image"]
        self.image_bytes = int(params["image_bytes"])
        self.object_bytes = int(params["object_bytes"])
        self.io_bytes = int(params["io_bytes"])
        self.concurrency = int(params["concurrency"])
        self.check_objects = int(params["check_objects"])
        self.check_every = int(params["check_every"])
        self.n_objects = self.image_bytes // self.object_bytes
        self.n_blocks = self.image_bytes // self.io_bytes
        self.failures: list[str] = []
        #: window op indices whose write was acknowledged
        self.acked: list[int] = []

    def geometry(self) -> dict:
        c = self.cluster
        return {"k": c.k, "m": c.m, "su": c.su,
                "object_bytes": self.object_bytes,
                "io_bytes": self.io_bytes}

    async def setup(self) -> None:
        from ceph_tpu.osdc.striper import FileLayout
        from ceph_tpu.placement.osdmap import Pool
        from ceph_tpu.services.rbd import RBD

        c = self.cluster
        meta = dict(c.config["metadata_pool"])
        await c.client.create_pool(Pool(
            id=int(meta["id"]), name=meta["name"], size=int(meta["size"]),
            min_size=int(meta["min_size"]), pg_num=int(meta["pg_num"]),
            crush_rule=int(meta["crush_rule"]), type=meta["type"]))
        await c.c.wait_active(ACTIVE_TIMEOUT_S)
        rbd = RBD(c.client, int(meta["id"]))
        await rbd.create(self.image_name, self.image_bytes,
                         FileLayout(stripe_unit=self.object_bytes,
                                    stripe_count=1,
                                    object_size=self.object_bytes),
                         data_pool=c.pool_id)
        self.image = await rbd.open(self.image_name)
        spo = -(-self.object_bytes // (c.k * c.su))
        c.warm_encode(spo, spo * self.concurrency)
        c.warm_encode(1, self.concurrency)
        self.prefill = Payloads(self.seed, self.object_bytes)
        sem = asyncio.Semaphore(self.concurrency)

        async def fill(i: int) -> None:
            async with sem:
                await self.image.write(i * self.object_bytes,
                                       self.prefill.view(i))

        await asyncio.gather(*(fill(i) for i in range(self.n_objects)))
        self.wbuf = memoryview(rng(self.seed, 4).bytes(
            WINDOW_VIEWS * self.io_bytes))
        self.order = rng(self.seed, 2).permutation(self.n_blocks)
        self.keep_phase = int(rng(self.seed, 3).integers(self.check_every))

    def payload(self, j: int) -> memoryview:
        off = (j % WINDOW_VIEWS) * self.io_bytes
        return self.wbuf[off: off + self.io_bytes]

    async def op(self, j: int) -> int:
        if j >= self.n_blocks:
            raise RuntimeError(f"op {j}: every block of the image has "
                               "been written once")
        await self.image.write(int(self.order[j]) * self.io_bytes,
                               self.payload(j))
        self.acked.append(j)
        return self.io_bytes

    # ------------------------------------------------------ reference

    def expected_object(self, objno: int, writes: list[int]) -> bytes:
        """The prefill view of one object with the window's
        acknowledged writes to it laid over it."""
        obj = bytearray(self.prefill.view(objno))
        for j in writes:
            _, off = object_extent(int(self.order[j]) * self.io_bytes,
                                   self.object_bytes)
            obj[off: off + self.io_bytes] = self.payload(j)
        return bytes(obj)

    async def collect(self) -> dict:
        by_object: dict[int, list[int]] = {}
        for j in self.acked:
            objno, _ = object_extent(int(self.order[j]) * self.io_bytes,
                                     self.object_bytes)
            by_object.setdefault(objno, []).append(j)
        touched = sorted(by_object)
        pick = rng(self.seed, 1).choice(
            len(touched), size=min(self.check_objects, len(touched)),
            replace=False)
        sample = [touched[i] for i in sorted(pick)]
        names = [data_object_name(self.image_name, o) for o in sample]
        kept = [j for j in self.acked
                if j % self.check_every == self.keep_phase]
        sem = asyncio.Semaphore(self.concurrency)

        async def read_back(j: int) -> bool:
            async with sem:
                try:
                    got = await self.image.read(
                        int(self.order[j]) * self.io_bytes, self.io_bytes)
                except Exception as e:
                    self.failures.append(f"read-back {j}: {e!r}")
                    return False
                return got == bytes(self.payload(j))

        same = await asyncio.gather(*(read_back(j) for j in kept))
        return {"readback_bad": sum(1 for ok in same if not ok),
                "readbacks": len(kept),
                "stored": self.cluster.stored_shards(names),
                "want": {n: self.expected_object(o, by_object[o])
                         for n, o in zip(names, sample)}}

    def compare(self, col: dict) -> dict:
        c = self.cluster
        shard_bad, crc_bad = compare_shards(col["stored"], col["want"],
                                            c.k, c.m, c.su)
        return {
            "readback_bad": (col["readback_bad"], 0),
            "shard_bad": (shard_bad, 0),
            "crc_bad": (crc_bad, 0),
            "nothing_checked": (int(not col["want"]
                                    or not col["readbacks"]), 0),
        }

    def notes(self, snaps: dict) -> list[str]:
        ops = len(snaps["ops"])
        b, a = snaps["before"]["osd"], snaps["after"]["osd"]

        def d(key: str) -> float:
            return a.get(key, 0) - b.get(key, 0)

        stripes = d("ec_batch_stripes.sum")
        return [
            f"writes: {ops} in the window, each to a {self.io_bytes}-byte "
            f"block written once, over {self.n_objects} objects of "
            f"{self.object_bytes} bytes",
            f"rmw: old-stripe reads {d('op_rmw_read_lat.count')}, bytes "
            f"{d('ec_rmw_read_bytes')}; user bytes "
            f"{d('ec_user_bytes_written')}, shard bytes "
            f"{d('ec_shard_bytes_written')}",
            f"ec encode in the window: ec_batches {d('ec_batches')}, "
            f"stripes {stripes} ({stripes / ops if ops else 0.0} per "
            f"write), ec_batch_failures {d('ec_batch_failures')}",
        ]
