"""Daemon entry point for multi-process clusters (src/ceph_osd.cc /
src/ceph_mon.cc main() role).

Runs ONE daemon — a mon (single or paxos rank) or an OSD — as its own
OS process on a NetBus (msg/netbus.py), with a durable store. Spawned
by procstart.ProcCluster (the vstart.sh:100-125 launch role) or by
hand:

    python -m ceph_tpu.cluster.daemon --role osd --id 3 \
        --book /tmp/cluster/book --store-dir /tmp/cluster \
        --n-osds 4 --objectstore walstore

A keyring file ``keyring`` in the book dir (lines ``entity hexsecret``)
switches every connection to the cephx-role authenticated mode; pass
--secure for AES-GCM on the wire.

SIGTERM stops cleanly; kill -9 is the crash the stores and the rest of
the cluster are built to survive.
"""
from __future__ import annotations

import argparse
import asyncio
import os
import signal
import sys


def load_keyring(book_dir: str):
    """keyring file -> KeyServer | None (CephxKeyServer role)."""
    path = os.path.join(book_dir, "keyring")
    if not os.path.exists(path):
        return None
    from ..msg.auth import KeyServer

    ks = KeyServer()
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            entity, hexsecret = line.split()
            ks.add(entity, bytes.fromhex(hexsecret))
    return ks


def make_keyring(book_dir: str, entities) -> None:
    """Generate a shared keyring for a dev cluster (vstart auth role)."""
    import secrets

    path = os.path.join(book_dir, "keyring")
    with open(path, "w") as f:
        for e in entities:
            f.write(f"{e} {secrets.token_hex(32)}\n")


def _pin_platform(platform: str) -> None:
    """Pin this daemon's jax to the requested platform BEFORE any
    backend init.

    Dev-cluster daemons default to CPU jax: libtpu lets one process
    at a time hold a chip (it takes a lock file when it loads), so a
    cluster of daemon processes that all reached for the default
    backend would leave every one but the first failing at start-up.
    ``--platform default`` opts exactly one OSD into the real chip —
    the one process that owns it for device-EC runs — and points its
    persistent compile cache at the checkout (utils/compile_cache)."""
    if platform == "cpu":
        from ..parallel import pin_virtual_cpu

        pin_virtual_cpu(1)
    else:
        from ..utils import compile_cache

        compile_cache.enable()


async def _amain(args) -> None:
    from ..msg.netbus import NetBus
    from .. import store as store_mod

    keys = load_keyring(args.book)
    bus = NetBus(args.book, keys=keys, secure=args.secure,
                 backend=args.msg_backend)
    await bus.start()

    stop_ev = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop_ev.set)

    if args.role == "mon":
        from .monstore import MonStore

        store = MonStore(os.path.join(args.store_dir,
                                      f"mon.{args.id}.kv"))
        if args.n_mons > 1:
            from .paxos_mon import PaxosMon

            daemon = PaxosMon(bus, args.n_osds, rank=args.id,
                              n_mons=args.n_mons, store=store,
                              hb_grace=args.hb_grace,
                              out_interval=args.out_interval)
        else:
            from .mon import MonLite

            daemon = MonLite(bus, args.n_osds, store=store,
                             hb_grace=args.hb_grace,
                             out_interval=args.out_interval)
    elif args.role == "osd":
        from ..utils import config as cfg
        from .osd import OSDLite

        conf = cfg.proxy()
        if args.conf:
            # launcher-provided overrides (the vstart.sh `-o key=val`
            # role over process boundaries): the fabric bench needs the
            # EC coalescing / op-concurrency knobs on REAL daemons
            conf.apply({k: v for k, v in
                        (kv.split("=", 1) for kv in args.conf)})
        store_kw = {}
        if args.objectstore != "memstore":
            # store-side group commit rides the daemon config (the
            # store_commit_window_ms/store_commit_max_txns knob pair)
            store_kw = dict(
                commit_window_ms=float(conf["store_commit_window_ms"]),
                commit_max_txns=int(conf["store_commit_max_txns"]))
        store = store_mod.create(
            args.objectstore,
            os.path.join(args.store_dir, f"osd.{args.id}"), **store_kw)
        daemon = OSDLite(bus, args.id, store=store,
                         hb_interval=args.hb_interval, conf=conf)
    elif args.role == "mds":
        # metadata daemon (src/ceph_mds.cc main role): its own RADOS
        # client on the bus; metadata pool via --pool. Spawned AFTER
        # the pool exists (ProcCluster.start_mds orchestration).
        from ..services.mds import MDSLite
        from .client import RadosClient

        client = RadosClient(bus, name=f"client.mds{args.id}")
        await client.connect()
        daemon = MDSLite(
            bus, client, args.pool, name=f"mds.{args.id}",
            data_pool=args.data_pool if args.data_pool >= 0 else None)
    else:
        raise SystemExit(f"unknown role {args.role!r}")

    await daemon.start()
    if hasattr(daemon, "start_admin"):
        # `ceph daemon <name> <cmd>` surface, one socket per daemon
        await daemon.start_admin(os.path.join(
            args.store_dir, f"{args.role}.{args.id}.asok"))
    # readiness marker for the launcher (systemd-notify role)
    ready = os.path.join(args.book, f"{args.role}.{args.id}.ready")
    with open(ready, "w") as f:
        f.write(str(os.getpid()))

    async def watch_parent() -> None:
        # exit with the launcher: a dev-cluster daemon orphaned by a
        # killed test run must not linger and cross-talk with the next
        # cluster sharing the same book paths
        ppid = os.getppid()
        while os.getppid() == ppid:
            await asyncio.sleep(0.5)
        stop_ev.set()

    parent_task = loop.create_task(watch_parent())
    try:
        await stop_ev.wait()
        parent_task.cancel()
    finally:
        try:
            await asyncio.wait_for(daemon.stop(), 5)
        except Exception:
            pass
        await bus.close()
        try:
            os.unlink(ready)
        except OSError:
            pass


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="ceph-tpu-daemon")
    ap.add_argument("--role", required=True,
                    choices=["mon", "osd", "mds"])
    ap.add_argument("--id", type=int, default=0,
                    help="osd id / mon rank / mds rank")
    ap.add_argument("--pool", type=int, default=1,
                    help="mds: metadata pool id")
    ap.add_argument("--data-pool", type=int, default=-1,
                    help="mds: data pool id (-1 = metadata pool)")
    ap.add_argument("--book", required=True,
                    help="shared address-book directory")
    ap.add_argument("--store-dir", required=True)
    ap.add_argument("--n-osds", type=int, required=True)
    ap.add_argument("--n-mons", type=int, default=1)
    ap.add_argument("--objectstore", default="walstore")
    ap.add_argument("--secure", action="store_true",
                    help="AES-GCM on-wire (needs a keyring)")
    ap.add_argument("--msg-backend", default="tcp",
                    choices=["tcp", "shm"],
                    help="inter-process transport: tcp (CRC-framed "
                         "sockets) or shm (shared-memory rings with "
                         "unix-socket doorbells — same-host only)")
    ap.add_argument("--conf", action="append", default=[],
                    metavar="KEY=VAL",
                    help="config override applied before the daemon "
                         "boots (repeatable; the vstart -o role)")
    ap.add_argument("--platform", default="cpu",
                    choices=["cpu", "default"],
                    help="jax platform: cpu (pinned, the dev-cluster "
                         "default) or default (whatever jax picks — "
                         "opt ONE daemon into the real chip)")
    ap.add_argument("--hb-interval", type=float, default=0.15)
    ap.add_argument("--hb-grace", type=float, default=2.0)
    ap.add_argument("--out-interval", type=float, default=4.0)
    args = ap.parse_args(argv)
    _pin_platform(args.platform)
    try:
        asyncio.run(_amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main(sys.argv[1:])
