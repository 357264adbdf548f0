"""ProcCluster: a REAL multi-process dev cluster (src/vstart.sh:100-125
role) — mon(s) + N OSDs as separate OS processes over TCP (NetBus),
durable stores, optional cephx/secure wire, and the qa-tier chaos verbs
(kill -9 an OSD process, revive it, watch the cluster heal).

The test process hosts the RadosClient and a lightweight mgr-report
sink on the same NetBus, so the TestCluster wait helpers keep their
shape: ``wait_down`` reads the client's map, ``wait_active`` reads the
OSDs' own MMgrReport state counts.
"""
from __future__ import annotations

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

from ..msg.netbus import NetBus
from . import messages as M
from .client import RadosClient
from .daemon import load_keyring, make_keyring

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


def _proc_cpu_s(pid: int) -> float:
    """utime+stime of one live process, in seconds (/proc stat fields
    14/15). 0.0 where /proc is absent or the pid is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            parts = f.read().rsplit(") ", 1)[-1].split()
        return (int(parts[11]) + int(parts[12])) / _CLK_TCK
    except (OSError, IndexError, ValueError):
        return 0.0


class ProcCluster:
    def __init__(self, data_dir: str, n_osds: int = 3, n_mons: int = 1,
                 objectstore: str = "walstore", auth: bool = False,
                 secure: bool = False, spawn_timeout: float = 30.0,
                 tpu_osd: int | None = None, backend: str = "tcp",
                 osd_conf: dict | None = None):
        self.data_dir = data_dir
        self.book = os.path.join(data_dir, "book")
        self.n_osds = n_osds
        self.n_mons = n_mons
        self.objectstore = objectstore
        self.secure = secure
        self.spawn_timeout = spawn_timeout
        #: inter-process transport every daemon AND the client bus use:
        #: "tcp" (CRC-framed sockets) or "shm" (shared-memory rings —
        #: msg/shmring.py; same-host only, which a ProcCluster is)
        self.backend = backend
        #: config overrides for every OSD daemon (vstart osd_conf
        #: parity over process boundaries, via `daemon --conf`)
        self.osd_conf = dict(osd_conf or {})
        #: opt-in: this ONE OSD runs jax on the default platform (the
        #: real chip when present) instead of pinned CPU — the only safe
        #: way to put the chip in a process-tier data path, since libtpu
        #: admits one process per chip
        self.tpu_osd = tpu_osd
        os.makedirs(self.book, exist_ok=True)
        if auth or secure:
            entities = (["mon"]
                        + [f"mon.{r}" for r in range(n_mons)]
                        + [f"osd.{i}" for i in range(n_osds)]
                        + [f"client.{i}" for i in range(4)]
                        + [f"mds.{r}" for r in range(4)]
                        + [f"client.mds{r}" for r in range(4)]
                        + [f"fsclient.{i}" for i in range(4)]
                        + ["mgr", "node"])
            # the node key authenticates the PROCESS link; every
            # envelope is additionally signed with its src ENTITY's key
            # (netbus._env_sig) so one authenticated process cannot
            # speak as another's entities
            make_keyring(self.book, entities)
        self.procs: dict[str, subprocess.Popen | None] = {}
        self._logs: dict[str, object] = {}  # open daemon log handles
        self.bus: NetBus | None = None
        self.client: RadosClient | None = None
        #: mgr-report sink: osd -> {"epoch": int, "pgs": {state: n}}
        self.reports: dict[int, dict] = {}
        #: cpu-seconds consumed by daemons that already EXITED (reaped
        #: into the ledger at kill/stop so cpu_seconds() stays a
        #: monotonic total across flaps)
        self._cpu_reaped = 0.0

    # ----------------------------------------------------------- lifecycle

    def _spawn(self, role: str, ident: int,
               extra: list[str] | None = None) -> subprocess.Popen:
        ready = os.path.join(self.book, f"{role}.{ident}.ready")
        try:
            os.unlink(ready)
        except OSError:
            pass
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get(
            "PYTHONPATH", "")
        # daemons default to pinned CPU jax (enforced INSIDE daemon.py
        # before any backend init): libtpu admits one process per
        # chip, so at most the one opted-in OSD touches the real chip
        platform = ("default"
                    if role == "osd" and ident == self.tpu_osd
                    else "cpu")
        if platform == "default":
            # the launcher itself may be CPU-pinned (pytest conftest
            # sets JAX_PLATFORMS/XLA_FLAGS in os.environ); the chip
            # opt-in must not inherit that pin, or it lands on the CPU.
            # A launcher that has put the chip to work itself holds it,
            # and this daemon then cannot take it
            env.pop("JAX_PLATFORMS", None)
            env.pop("XLA_FLAGS", None)
        args = [
            sys.executable, "-m", "ceph_tpu.cluster.daemon",
            "--role", role, "--id", str(ident),
            "--book", self.book, "--store-dir", self.data_dir,
            "--n-osds", str(self.n_osds),
            "--n-mons", str(self.n_mons),
            "--objectstore", self.objectstore,
            "--platform", platform,
            "--msg-backend", self.backend,
        ]
        if role == "osd":
            for k, v in self.osd_conf.items():
                args.extend(["--conf", f"{k}={v}"])
        if extra:
            args.extend(extra)
        if self.secure:
            args.append("--secure")
        name = f"{role}.{ident}"
        old = self._logs.pop(name, None)
        if old is not None:
            old.close()  # a flapped daemon must not leak its old fd
        log = open(os.path.join(self.data_dir, f"{name}.log"), "ab")
        self._logs[name] = log
        proc = subprocess.Popen(args, env=env, stdout=log, stderr=log)
        self.procs[name] = proc
        return proc

    def _reap_cpu(self, proc: subprocess.Popen) -> None:
        """Fold a dead daemon's cpu time into the ledger (utime+stime
        ticks from its /proc stat are gone once reaped, so the chaos
        verbs call this BEFORE wait())."""
        self._cpu_reaped += _proc_cpu_s(proc.pid)

    def cpu_seconds(self) -> float:
        """Total daemon CPU burned so far (live + exited), the
        cpu-seconds-per-MiB denominator of the fabric bench."""
        live = sum(_proc_cpu_s(p.pid) for p in self.procs.values()
                   if p is not None and p.poll() is None)
        return self._cpu_reaped + live

    async def _wait_ready(self, role: str, ident: int) -> None:
        ready = os.path.join(self.book, f"{role}.{ident}.ready")
        deadline = time.monotonic() + self.spawn_timeout
        while not os.path.exists(ready):
            proc = self.procs[f"{role}.{ident}"]
            if proc is not None and proc.poll() is not None:
                raise RuntimeError(
                    f"{role}.{ident} exited rc={proc.returncode} "
                    f"(see {self.data_dir}/{role}.{ident}.log)")
            if time.monotonic() > deadline:
                raise TimeoutError(f"{role}.{ident} never became ready")
            await asyncio.sleep(0.05)

    async def start(self) -> None:
        for r in range(self.n_mons):
            self._spawn("mon", r)
        for r in range(self.n_mons):
            await self._wait_ready("mon", r)
        for i in range(self.n_osds):
            self._spawn("osd", i)
        for i in range(self.n_osds):
            await self._wait_ready("osd", i)
        self.bus = NetBus(self.book, keys=load_keyring(self.book),
                          secure=self.secure, backend=self.backend)
        await self.bus.start()
        self.bus.register("mgr", self._mgr_sink)
        # boot-generous op deadline: connect()'s first-osdmap wait and
        # the caller's first mon ops race freshly spawned mon processes
        # through their first election — on a loaded box 10 s starves
        # (the tick-resend cap keeps retry latency bounded regardless)
        self.client = RadosClient(self.bus, op_timeout=30.0)
        await self.client.connect()
        if self.n_mons > 1:
            # hand back a FORMED quorum: mon processes race their first
            # election (a loaded box can starve one mon's ack past the
            # round), and a caller's immediate mon op would otherwise
            # burn its whole retry budget on the churn of the rejoin
            # elections. Best-effort deadline — a genuinely degraded
            # quorum still comes up, just not waited for.
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    _rc, _outs, outb = await self.client.mon_command(
                        ["quorum_status"])
                    if len(json.loads(outb)["quorum"]) == self.n_mons:
                        break
                except (IOError, asyncio.TimeoutError):
                    pass
                await asyncio.sleep(0.25)

    async def _mgr_sink(self, _src: str, msg) -> None:
        if isinstance(msg, M.MMgrReport):
            self.reports[msg.osd] = {
                "ts": time.time(), "epoch": msg.epoch,
                "pgs": dict(msg.pgs),
                "perf": json.loads(msg.perf.decode() or "{}"),
            }

    async def stop(self) -> None:
        """Clean teardown: SIGTERM every daemon at once, drain the
        whole fleet against ONE deadline, SIGKILL stragglers, then
        close the client bus and every launcher-held fd. Safe to call
        twice (the bench reuses one cluster across cells and stops it
        in a finally)."""
        if self.client is not None:
            try:
                await self.client.close()
            except Exception:
                pass
            self.client = None
        for name, proc in self.procs.items():
            if proc is not None and proc.poll() is None:
                self._reap_cpu(proc)
                proc.terminate()
        deadline = time.monotonic() + 10
        for name, proc in self.procs.items():
            if proc is None:
                continue
            while proc.poll() is None and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            if proc.poll() is None:
                # a daemon wedged past the drain window: the crash
                # path (kill -9) is what the stores are built for
                proc.kill()
                proc.wait()
            ready = os.path.join(self.book, f"{name}.ready")
            try:
                os.unlink(ready)
            except OSError:
                pass
        self.procs.clear()
        for log in self._logs.values():
            log.close()
        self._logs.clear()
        if self.bus is not None:
            await self.bus.close()
            self.bus = None

    # ------------------------------------------------------------- chaos

    def kill_osd(self, i: int, sig: int = signal.SIGKILL) -> None:
        """Crash-stop the OSD *process* (OSDThrasher kill_osd role —
        kill -9, no goodbye; the mon notices by heartbeat timeout)."""
        proc = self.procs.get(f"osd.{i}")
        assert proc is not None and proc.poll() is None, f"osd.{i} gone"
        self._reap_cpu(proc)
        proc.send_signal(sig)
        proc.wait()
        self.procs[f"osd.{i}"] = None
        self.reports.pop(i, None)

    async def revive_osd(self, i: int) -> None:
        self._spawn("osd", i)
        await self._wait_ready("osd", i)

    async def flap_osd(self, i: int, downtime: float = 0.5,
                       sig: int = signal.SIGKILL) -> None:
        """Kill -9 + revive in one verb (the process-tier thrasher
        flap): the revived daemon mounts the same durable store and
        recovers — mirrors TestCluster.flap_osd so thrash scenarios
        port between the in-process and process tiers."""
        self.kill_osd(i, sig)
        try:
            await self.wait_down(i, timeout=max(10.0, downtime * 4))
        except asyncio.TimeoutError:
            pass  # mon mid-failover may lag; revive regardless
        if downtime > 0:
            await asyncio.sleep(downtime)
        await self.revive_osd(i)

    async def start_mds(self, rank: int, pool: int,
                        data_pool: int | None = None) -> None:
        """Spawn an MDS daemon process (after its metadata pool exists
        and the fs is mkfs'd — the ceph-mds launch ordering)."""
        if not hasattr(self, "_mds_args"):
            self._mds_args: dict[int, list[str]] = {}
        self._mds_args[rank] = [
            "--pool", str(pool), "--data-pool",
            str(-1 if data_pool is None else data_pool)]
        self._spawn("mds", rank, extra=self._mds_args[rank])
        await self._wait_ready("mds", rank)

    def kill_mds(self, rank: int, sig: int = signal.SIGKILL) -> None:
        """Crash-stop the MDS process; its journal is the recovery
        story (MDLog replay on revive)."""
        proc = self.procs.get(f"mds.{rank}")
        assert proc is not None and proc.poll() is None
        self._reap_cpu(proc)
        proc.send_signal(sig)
        proc.wait()
        self.procs[f"mds.{rank}"] = None

    async def revive_mds(self, rank: int) -> None:
        self._spawn("mds", rank, extra=self._mds_args[rank])
        await self._wait_ready("mds", rank)

    def kill_mon(self, rank: int, sig: int = signal.SIGKILL) -> None:
        proc = self.procs.get(f"mon.{rank}")
        assert proc is not None and proc.poll() is None
        self._reap_cpu(proc)
        proc.send_signal(sig)
        proc.wait()
        self.procs[f"mon.{rank}"] = None

    async def revive_mon(self, rank: int) -> None:
        """Cold-restart a killed mon from its durable MonStore; it
        rejoins the quorum and catches up via the collect round."""
        self._spawn("mon", rank)
        await self._wait_ready("mon", rank)

    def leader_mon_rank(self) -> int:
        """Which rank currently holds the public ``mon`` alias (the
        paxos leader), resolved through the shared address book."""
        def addr(name: str) -> str:
            # compare raw book entries: the shm backend publishes
            # `shm <sock> <host> <port>` lines, tcp `host port` — the
            # alias check only needs equality, not parsing
            with open(os.path.join(self.book, name)) as f:
                return f.read().strip()

        try:
            alias = addr("mon")
        except (OSError, ValueError):
            # mid-election the alias is briefly unbound
            raise RuntimeError("mon alias bound to no known rank") \
                from None
        for r in range(self.n_mons):
            try:
                if addr(f"mon.{r}") == alias:
                    return r
            except (OSError, ValueError):
                continue
        raise RuntimeError("mon alias bound to no known rank")

    # ------------------------------------------------------ admin surface

    async def asok(self, name: str, prefix: str, **args):
        """`ceph daemon <name> <cmd>` against a live daemon's admin
        socket (utils/admin.py client half)."""
        from ..utils.admin import admin_command

        return await admin_command(
            os.path.join(self.data_dir, f"{name}.asok"), prefix, **args)

    async def scrub_all(self) -> dict:
        """Deep-scrub every primary PG on every live OSD via the asok
        ``scrub`` verb; merged pgid -> {clean, inconsistent, repaired}.
        The process-tier thrash verdict's zero-inconsistencies check."""
        out: dict[str, dict] = {}
        for i in range(self.n_osds):
            proc = self.procs.get(f"osd.{i}")
            if proc is None or proc.poll() is not None:
                continue
            out.update(await self.asok(f"osd.{i}", "scrub"))
        return out

    # -------------------------------------------------------- wait helpers

    async def _refresh_map(self) -> None:
        try:
            await self.client._mon_send(
                M.MMonGetMap(have=0), deadline_s=0.5)
        except Exception:
            pass

    async def wait_down(self, osd_id: int, timeout: float = 30.0) -> None:
        async def _wait():
            while True:
                await self._refresh_map()
                m = self.client.osdmap
                if m is not None and not m.osds[osd_id].up:
                    return
                await asyncio.sleep(0.1)
        await asyncio.wait_for(_wait(), timeout)

    async def wait_up(self, osd_id: int, timeout: float = 30.0) -> None:
        async def _wait():
            while True:
                await self._refresh_map()
                m = self.client.osdmap
                if m is not None and m.osds[osd_id].up:
                    return
                await asyncio.sleep(0.1)
        await asyncio.wait_for(_wait(), timeout)

    async def wait_active(self, timeout: float = 30.0) -> None:
        """Every live OSD reports all its PGs active on the current
        epoch (the wait-for-clean role, via the OSDs' own MMgrReport)."""
        live = [i for i in range(self.n_osds)
                if self.procs.get(f"osd.{i}") is not None]

        async def _wait():
            while True:
                await self._refresh_map()
                m = self.client.osdmap
                now = time.time()
                if m is not None and all(
                    (rep := self.reports.get(i)) is not None
                    and now - rep["ts"] < 2.0
                    and rep["epoch"] == m.epoch
                    and all(s == "active" for s in rep["pgs"])
                    for i in live
                ):
                    return
                await asyncio.sleep(0.1)
        await asyncio.wait_for(_wait(), timeout)
