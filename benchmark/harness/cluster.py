"""The system under test: an in-process cluster built from a
configuration file, and what the harness reads back out of it.

This is the one module that imports the program. It builds the
cluster through the entry points a user calls (TestCluster, the
client's pool create, the mon's ``osd down`` command), warms the
device programs a cell's traffic will use, snapshots the program's
counters, and fetches stored shard bytes for the comparison.
"""
from __future__ import annotations

import os

import numpy as np

#: a cluster that has not peered in this long is broken, not slow
ACTIVE_TIMEOUT_S = 300.0


class Cluster:
    def __init__(self, config: dict, rehearse: bool):
        self.config = config
        self.pool_cfg = dict(config["pool"])
        if rehearse:
            self.pool_cfg.update(config.get("rehearsal", {}).get("pool", {}))
        prof = self.pool_cfg["ec_profile"]
        self.k = int(prof["k"])
        self.m = int(prof["m"])
        self.su = int(prof["stripe_unit"])
        self.pool_id = int(self.pool_cfg["id"])
        self.c = None

    # ------------------------------------------------------- lifecycle

    async def start(self) -> None:
        from ceph_tpu.cluster.vstart import TestCluster
        from ceph_tpu.placement.osdmap import Pool

        cl = self.config["cluster"]
        self.c = TestCluster(
            n_osds=int(cl["n_osds"]),
            hb_grace=float(cl["osd_heartbeat_grace"]),
            out_interval=float(cl["mon_osd_down_out_interval"]),
            objectstore=cl["objectstore"],
            osd_conf=dict(self.config.get("osd_conf", {})))
        await self.c.start()
        p = self.pool_cfg
        await self.c.client.create_pool(Pool(
            id=self.pool_id, name=p["name"], size=int(p["size"]),
            min_size=int(p["min_size"]), pg_num=int(p["pg_num"]),
            crush_rule=int(p["crush_rule"]), type=p["type"],
            ec_profile=dict(p["ec_profile"])))
        await self.c.wait_active(ACTIVE_TIMEOUT_S)

    async def stop(self) -> None:
        if self.c is not None:
            await self.c.stop()
            self.c = None

    @property
    def client(self):
        return self.c.client

    # ---------------------------------------------------------- warm-up

    def codec(self):
        from ceph_tpu.ec.registry import load_codec

        return load_codec(dict(self.pool_cfg["ec_profile"]))

    def warm_encode(self, min_stripes: int, max_stripes: int) -> list[int]:
        """Compile (or load from the cache) the fused encode+CRC program
        at every batch shape the ECBatcher can form from between
        ``min_stripes`` (one op's stripes) and ``max_stripes`` (every
        in-flight op's) queued stripes. Returns the shapes."""
        import jax

        from ceph_tpu.parallel import pad_batch_pow2

        codec = self.codec()
        osd_conf = self.config.get("osd_conf", {})
        n_mesh = int(osd_conf.get("osd_ec_mesh_devices", 0))
        mesh = None
        if n_mesh > 1:
            from ceph_tpu.parallel import runtime

            mesh = runtime.serving_mesh(
                n_mesh, int(osd_conf.get("osd_ec_mesh_width", 1)))
        shapes = sorted({pad_batch_pow2(n, mesh)
                         for n in _pow2_range(min_stripes, max_stripes)})
        for b in shapes:
            x = np.zeros((b, self.k, self.su // 4), dtype=np.uint32)
            if mesh is None:
                out = codec.encode_crc_batch(x, self.su)
            else:
                out = codec.encode_crc_batch_mesh(x, self.su, mesh)
            jax.block_until_ready(out)
        return shapes

    def warm_decode(self, patterns, batches) -> int:
        """Compile (or load) the decode program of every (present, want)
        survivor pattern at every batch size given. Returns the count."""
        import jax

        codec = self.codec()
        n = 0
        for present, want in sorted(set(patterns)):
            for b in batches:
                x = np.zeros((b, self.k, self.su // 4), dtype=np.uint32)
                jax.block_until_ready(codec.decode_batch(present, x,
                                                         want=want))
                n += 1
        return n

    # --------------------------------------------------------- failures

    def acting(self, name: str) -> list[int]:
        osdmap = self.c.mon.osdmap
        pgid = osdmap.object_to_pg(self.pool_id, name)
        return list(osdmap.pg_to_up_acting_osds(pgid)[0])

    async def fail_osds(self, osds: list[int]) -> None:
        """Crash-stop the OSDs and mark them down through the mon's
        ``osd down`` command (not out: no recovery starts), so set-up
        does not wait out the heartbeat grace; then wait for peering."""
        for o in osds:
            await self.c.kill_osd(o)
        rc, outs, _ = await self.c.client.mon_command(
            {"prefix": "osd down", "ids": list(osds)})
        if rc != 0:
            raise RuntimeError(f"osd down {osds}: {rc} {outs}")
        for o in osds:
            await self.c.wait_down(o, timeout=ACTIVE_TIMEOUT_S)
        await self.c.wait_active(ACTIVE_TIMEOUT_S)

    # --------------------------------------------------------- counters

    def snapshot(self) -> dict:
        """The program's counters, summed over live OSDs, flattened to
        ``key`` / ``key.sum`` / ``key.count``."""
        osd: dict[str, float] = {}
        for o in self.c.osds:
            if o is None:
                continue
            for key, val in o.perf.dump().items():
                if isinstance(val, dict):
                    for sub, name in (("sum", "sum"), ("count", "count"),
                                      ("avgcount", "count")):
                        if sub in val:
                            k = f"{key}.{name}"
                            osd[k] = osd.get(k, 0) + val[sub]
                elif isinstance(val, (int, float)):
                    osd[key] = osd.get(key, 0) + val
        ws = self.c.client.window_stats
        return {
            "osd": osd,
            "client.window_sum": ws["sum"],
            "client.window_count": ws["count"],
            "client.max_inflight": int(
                self.c.client.conf["client_max_inflight"]),
            "bus.frames_delivered": self.c.bus.frames_delivered,
            "bus.delivery_bursts": self.c.bus.delivery_bursts,
        }

    # ---------------------------------------------------- stored shards

    def stored_shards(self, names: list[str]) -> dict:
        """{name: {position: (shard bytes, hinfo bytes)}} for every
        stored shard of the named objects on every live OSD. A shard
        position stored twice is kept as a list, so it shows."""
        from ceph_tpu.store.base import NotFound

        want = {n.encode(): n for n in names}
        out: dict = {n: {} for n in names}
        prefix = f"{self.pool_id}."
        for st in self.c.stores:
            for cid in st.list_collections():
                if not cid.startswith(prefix) or "s" not in cid:
                    continue
                pos = int(cid.rsplit("s", 1)[1])
                for oid in st.list_objects(cid):
                    name = want.get(bytes(oid))
                    if name is None:
                        continue
                    data = bytes(st.read(cid, oid))
                    try:
                        hinfo = bytes(st.getattr(cid, oid, "hinfo"))
                    except NotFound:
                        hinfo = b""
                    out[name].setdefault(pos, []).append((data, hinfo))
        return out


def _pow2_range(lo: int, hi: int) -> list[int]:
    """Stripe counts whose pow2 buckets span [lo, hi]."""
    out, n = [], lo
    while n < hi:
        out.append(n)
        n *= 2
    out.append(hi)
    return out


def device_memory_peak() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def process_cpu_s() -> float:
    t = os.times()
    return t.user + t.system

