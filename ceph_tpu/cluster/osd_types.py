"""Object-level vocabulary shared by the PG and its EC backend.

The osd_types.h role: the store attr names an object carries, the
codec of its version attr and of log entry lists, the op error that
aborts an op vector, and the filter a member applies to a sub-write's
transaction. ``pg.py`` and ``ec_backend.py`` both import from here, so
neither has to import the other.
"""
from __future__ import annotations

from ..utils import denc
from ..store import transaction as tx
from .pglog import ZERO, Entry

ATTR_V = "v"
ATTR_SIZE = "size"
ATTR_HINFO = "hinfo"
ATTR_SS = "ss"  # head SnapSet (the SS_ATTR role)
ATTR_WHITEOUT = "wh"  # deleted head kept for its clones (snapdir role)
USER_ATTR = "u:"  # user xattr namespace within store attrs


class OpError(Exception):
    """Aborts the whole op vector with an errno-style code (a failing
    op fails the transaction, PrimaryLogPG::do_osd_ops contract)."""

    def __init__(self, code: int, what: str = ""):
        super().__init__(what or str(code))
        self.code = code


def enc_ver(v: tuple[int, int]) -> bytes:
    return denc.enc_u32(v[0]) + denc.enc_u64(v[1])


def dec_ver(b: bytes) -> tuple[int, int]:
    e, off = denc.dec_u32(b, 0)
    s, _ = denc.dec_u64(b, off)
    return (e, s)


def enc_entries(entries: list[Entry]) -> bytes:
    return denc.enc_list(entries, lambda e: e.encode())


def dec_entries(buf: bytes) -> list[Entry]:
    out, _ = denc.dec_list(buf, 0, Entry.decode)
    return out


def shard_version(store, cid: str, oid: bytes) -> tuple[int, int]:
    """The ATTR_V of ``oid`` in collection ``cid``; ZERO when absent."""
    try:
        return dec_ver(store.getattr(cid, oid, ATTR_V))
    except Exception:
        return ZERO


def filter_remote_ops(store, t: tx.Transaction) -> list:
    """Drop ops that cannot apply on a diverged member: removes of
    objects we do not hold, and clones whose source is missing (a
    revived replica pending recovery must still ack the txn; the
    skipped objects converge via recovery/scrub). Ops targeting a
    skipped clone are dropped with it so no empty shell appears."""
    ops = []
    skipped_dests: set[tuple[str, bytes]] = set()
    for op in t.ops:
        if op.code == tx.OP_REMOVE and not store.exists(op.cid, op.oid):
            continue
        if op.code == tx.OP_CLONE and not store.exists(op.cid, op.oid):
            skipped_dests.add((op.cid, op.args["dest"]))
            continue
        if (op.cid, op.oid) in skipped_dests:
            continue
        ops.append(op)
    return ops
