"""Repair-economics device pipeline (ISSUE 9): bitmatrix and Clay
batched cell codecs must be BYTE-IDENTICAL to their per-stripe
reference implementations (property-style random draws, including
Clay's is_repair sub-chunk plans), route through the ECBatcher like
rs_tpu, and serve the cluster's degraded path — with Clay's rebuild
fetching sub-chunks instead of whole chunks (the d/q < k repair-
traffic amplification the codec exists for)."""
import asyncio

import numpy as np
import pytest

from ceph_tpu.ec import load_codec
from ceph_tpu.ops import rs

RNG = np.random.default_rng(20260804)


def run(coro, timeout=120):
    asyncio.run(asyncio.wait_for(coro, timeout))


def _su_for(codec, base=1024):
    """A stripe_unit that is a fixed point of get_chunk_size — what
    osd.sinfo_for would compute for the pool."""
    su = base
    for _ in range(8):
        got = codec.get_chunk_size(codec.k * su)
        if got == su:
            return su
        su = got
    raise AssertionError("stripe unit did not stabilize")


# --------------------------------------- batched-vs-reference parity


BM_DRAWS = [
    ("blaum_roth", 3, 2, 4), ("blaum_roth", 5, 2, 6),
    ("liberation", 4, 2, 5), ("liberation", 6, 2, 7),
    ("liber8tion", 5, 2, 8), ("cauchy_bm", 4, 3, 8),
]


@pytest.mark.parametrize("tech,k,m,w", BM_DRAWS)
def test_bitmatrix_batched_parity(tech, k, m, w):
    """encode_crc_batch/decode_batch == per-stripe encode_chunks/
    decode_chunks, byte for byte, across random erasure draws — and
    the host-engine hooks agree with the device path."""
    from ceph_tpu import native

    codec = load_codec({"plugin": "bitmatrix", "technique": tech,
                        "k": str(k), "m": str(m), "w": str(w)})
    su = _su_for(codec)
    rng = np.random.default_rng(hash((tech, k, m, w)) % 2**32)
    cells = rng.integers(0, 256, (4, k, su), dtype=np.uint8)
    ref = np.stack([codec.encode_chunks(c) for c in cells])
    parity_w, crcs = codec.encode_crc_batch(rs.pack_u32(cells), su)
    parity = rs.unpack_u32(np.asarray(parity_w))
    np.testing.assert_array_equal(parity, ref)
    every = np.concatenate([cells, parity], axis=1)
    want_crc = np.stack([native.crc32c_batch(e) for e in every])
    np.testing.assert_array_equal(np.asarray(crcs), want_crc)
    np.testing.assert_array_equal(codec.encode_cells_host(cells), ref)
    # random erasure sets up to m losses, mixed data/parity wants
    n = k + m
    for _ in range(4):
        r = int(rng.integers(1, m + 1))
        erase = tuple(sorted(rng.choice(n, size=r, replace=False)))
        present = tuple(i for i in range(n) if i not in erase)[:k]
        surv = np.ascontiguousarray(every[:, list(present), :])
        got = rs.unpack_u32(np.asarray(codec.decode_batch(
            present, rs.pack_u32(surv), want=erase)))
        for b in range(len(cells)):
            dec = codec.decode_chunks(list(present), surv[b])
            for wi, g in enumerate(erase):
                np.testing.assert_array_equal(
                    got[b, wi], dec[g],
                    err_msg=f"{tech} erase={erase} chunk {g}")
        np.testing.assert_array_equal(
            codec.decode_cells_host(present, erase, surv), got)


CLAY_DRAWS = [(4, 2, 5), (3, 2, 4), (4, 3, 6), (3, 3, 4)]


@pytest.mark.parametrize("k,m,d", CLAY_DRAWS)
def test_clay_batched_parity(k, m, d):
    """Clay encode_crc_batch/decode_batch == per-stripe reference
    across random erasure draws, shortened (nu > 0) geometries
    included; host hooks agree with the device path."""
    from ceph_tpu import native

    codec = load_codec({"plugin": "clay", "k": str(k), "m": str(m),
                        "d": str(d)})
    su = _su_for(codec)
    rng = np.random.default_rng(k * 1009 + m * 31 + d)
    cells = rng.integers(0, 256, (3, k, su), dtype=np.uint8)
    ref = np.stack([codec.encode_chunks(c) for c in cells])
    parity_w, crcs = codec.encode_crc_batch(rs.pack_u32(cells), su)
    parity = rs.unpack_u32(np.asarray(parity_w))
    np.testing.assert_array_equal(parity, ref)
    every = np.concatenate([cells, parity], axis=1)
    want_crc = np.stack([native.crc32c_batch(e) for e in every])
    np.testing.assert_array_equal(np.asarray(crcs), want_crc)
    np.testing.assert_array_equal(codec.encode_cells_host(cells), ref)
    n = k + m
    for _ in range(3):
        r = int(rng.integers(1, m + 1))
        erase = tuple(sorted(rng.choice(n, size=r, replace=False)))
        present = tuple(i for i in range(n) if i not in erase)
        surv = np.ascontiguousarray(every[:, list(present), :])
        got = rs.unpack_u32(np.asarray(codec.decode_batch(
            present, rs.pack_u32(surv), want=erase)))
        for b in range(len(cells)):
            dec = codec.decode_chunks(list(present), surv[b])
            for wi, g in enumerate(erase):
                np.testing.assert_array_equal(
                    got[b, wi], dec[g],
                    err_msg=f"clay k={k} m={m} d={d} erase={erase}")
        np.testing.assert_array_equal(
            codec.decode_cells_host(present, erase, surv), got)


@pytest.mark.parametrize("k,m,d", CLAY_DRAWS)
def test_clay_repair_batch_parity(k, m, d):
    """repair_batch over is_repair sub-chunk plans == the scalar
    repair() per stripe, for every single-loss chunk the plan covers
    — each helper ships exactly 1/q of its cells."""
    codec = load_codec({"plugin": "clay", "k": str(k), "m": str(m),
                        "d": str(d)})
    su = _su_for(codec)
    rng = np.random.default_rng(k * 7907 + m * 17 + d)
    cells = rng.integers(0, 256, (3, k, su), dtype=np.uint8)
    parity = np.stack([codec.encode_chunks(c) for c in cells])
    every = np.concatenate([cells, parity], axis=1)
    n = k + m
    sub = su // codec.get_sub_chunk_count()
    for lost in range(n):
        avail = sorted(set(range(n)) - {lost})
        if not codec.is_repair({lost}, set(avail)):
            continue
        plan = codec.minimum_to_decode([lost], avail)
        assert lost not in plan and len(plan) == codec.d
        order = sorted(plan)
        runs = plan[order[0]]
        surv = np.stack([
            np.concatenate([every[:, c, o * sub : (o + cnt) * sub]
                            for o, cnt in runs], axis=1)
            for c in order
        ], axis=1)  # (B, d, su/q)
        assert surv.shape[-1] == su // codec.q
        got = rs.unpack_u32(np.asarray(codec.repair_batch(
            tuple(order), rs.pack_u32(surv), (lost,))))
        np.testing.assert_array_equal(got[:, 0, :], every[:, lost, :],
                                      err_msg=f"lost={lost}")
        # host hook agrees
        np.testing.assert_array_equal(
            codec.repair_cells_host(tuple(order), (lost,), surv), got)


def test_lrc_batched_local_and_global_parity():
    """LRC's composite generator rides the rs-style batched hooks:
    local repairs consume FEWER than k rows, global decodes any
    spanning set — both byte-identical to the layered decode()."""
    codec = load_codec({"plugin": "lrc", "k": "4", "m": "2", "l": "3"})
    n = codec.k + codec.m
    su = _su_for(codec)
    rng = np.random.default_rng(4242)
    objs = [rng.integers(0, 256, codec.k * su, dtype=np.uint8)
            for _ in range(3)]
    by_pos: dict[int, list] = {}
    for o in objs:
        enc = codec.encode(list(range(n)), o.tobytes())
        for p, c in enc.items():
            by_pos.setdefault(p, []).append(c)
    for lost_set in ([0], [1], [0, 1], [0, 4]):
        avail = sorted(set(range(n)) - set(lost_set))
        need = sorted(codec.minimum_to_decode(lost_set, avail))
        if len(lost_set) == 1:
            assert len(need) < codec.k  # locality: cheaper than MDS
        pg = tuple(codec._position_to_generator(p) for p in need)
        wg = tuple(codec._position_to_generator(p) for p in lost_set)
        surv = np.stack([np.stack(by_pos[p]) for p in need], axis=1)
        got = rs.unpack_u32(np.asarray(codec.decode_batch(
            pg, rs.pack_u32(surv), want=wg)))
        for i, p in enumerate(lost_set):
            np.testing.assert_array_equal(
                got[:, i, :], np.stack(by_pos[p]),
                err_msg=f"lrc lost={lost_set} pos={p}")


# ------------------------------------------------ ECBatcher routing


def test_batcher_routes_cellwise_codecs():
    """Cellwise codecs dispatch through the SAME bucket machinery as
    rs_tpu on both engines, the repair kind included, and distinct
    geometries (two w's) never share a bucket."""
    from ceph_tpu.cluster.ecbatch import ECBatcher, codec_profile_key
    from ceph_tpu.utils.perf import PerfCounters

    k1 = codec_profile_key(load_codec(
        {"plugin": "bitmatrix", "technique": "liberation",
         "k": "4", "m": "2", "w": "5"}))
    k2 = codec_profile_key(load_codec(
        {"plugin": "bitmatrix", "technique": "liberation",
         "k": "4", "m": "2", "w": "7"}))
    assert k1 != k2
    kc1 = codec_profile_key(load_codec(
        {"plugin": "clay", "k": "3", "m": "2", "d": "3"}))
    kc2 = codec_profile_key(load_codec(
        {"plugin": "clay", "k": "3", "m": "2", "d": "4"}))
    assert kc1 != kc2

    async def t(backend):
        perf = PerfCounters("t")
        ECBatcher.declare_counters(perf)
        b = ECBatcher(perf)
        out = {}
        for plug, prof in (
            ("bm", {"plugin": "bitmatrix", "technique": "blaum_roth",
                    "k": "3", "m": "2", "w": "4",
                    "backend": backend}),
            ("clay", {"plugin": "clay", "k": "3", "m": "2",
                      "backend": backend}),
        ):
            codec = load_codec(prof)
            su = _su_for(codec)
            cells = np.random.default_rng(3).integers(
                0, 256, (2, codec.k, su), dtype=np.uint8)
            parity, crcs = await b.encode_cells(codec, cells)
            ref = np.stack([codec.encode_chunks(c) for c in cells])
            np.testing.assert_array_equal(parity, ref)
            if backend == "device":
                assert crcs is not None and crcs.shape == (2, 5)
            else:
                assert crcs is None  # host engines keep their own pass
            every = np.concatenate([cells, parity], axis=1)
            present = (0, 2, 3)
            dec = await b.decode_cells(
                codec, present, (1,),
                np.ascontiguousarray(every[:, list(present), :]))
            np.testing.assert_array_equal(dec[:, 0, :], cells[:, 1, :])
            out[plug] = codec
        # the sub-chunk repair kind, through the batcher
        codec = out["clay"]
        su = _su_for(codec)
        cells = np.random.default_rng(5).integers(
            0, 256, (2, codec.k, su), dtype=np.uint8)
        parity, _ = await b.encode_cells(codec, cells)
        every = np.concatenate([cells, parity], axis=1)
        lost = 0
        avail = sorted(set(range(5)) - {lost})
        plan = codec.minimum_to_decode([lost], avail)
        sub = su // codec.get_sub_chunk_count()
        order = sorted(plan)
        runs = plan[order[0]]
        surv = np.stack([
            np.concatenate([every[:, c, o * sub : (o + cnt) * sub]
                            for o, cnt in runs], axis=1)
            for c in order], axis=1)
        got = await b.repair_cells(codec, tuple(order), (lost,), surv)
        np.testing.assert_array_equal(got[:, 0, :], every[:, lost, :])
        d = perf.dump()
        assert d["ec_batches"] >= 2
        assert d["ec_decode_batches"] >= 3  # 2 decodes + 1 repair

    run(t("device"))
    run(t("host"))


def test_slice_subruns_selects_per_cell():
    from ceph_tpu.cluster.ec_backend import (_pack_subruns,
                                             _slice_subruns,
                                             _unpack_subruns)

    codec = load_codec({"plugin": "clay", "k": "4", "m": "2"})
    subs = codec.get_sub_chunk_count()  # 8
    su = 8 * 16
    chunk = np.arange(2 * su, dtype=np.uint8).tobytes()  # 2 cells
    runs = [(0, 2), (4, 2)]
    raw = _pack_subruns(runs)
    assert _unpack_subruns(raw) == runs
    out = np.frombuffer(_slice_subruns(chunk, su, raw, codec),
                        dtype=np.uint8)
    cells = np.frombuffer(chunk, dtype=np.uint8).reshape(2, subs, 16)
    want = np.concatenate(
        [cells[:, 0:2, :], cells[:, 4:6, :]], axis=1).reshape(-1)
    np.testing.assert_array_equal(out, want)


# -------------------------------------------- cluster serving path


def test_cluster_clay_subchunk_recovery_storm():
    """Kill + out one member of a Clay pool: the backfill rebuild of
    its shards must ride the SUB-CHUNK repair path (counter-proven:
    ec_repair_subchunk > 0 and fetched/rebuilt < k), through batched
    decode dispatches, and every object stays byte-exact."""
    from ceph_tpu.cluster import TestCluster
    from ceph_tpu.placement.osdmap import Pool

    async def t():
        c = TestCluster(n_osds=7, out_interval=1.0)
        await c.start()
        await c.client.create_pool(Pool(
            id=2, name="p", size=5, min_size=3, pg_num=4,
            crush_rule=1, type="erasure",
            ec_profile={"plugin": "clay", "k": "3", "m": "2",
                        "backend": "device", "stripe_unit": "4096"}))
        await c.wait_active(30)
        rng = np.random.default_rng(11)
        datas = {}
        for i in range(4):
            d = rng.integers(0, 256, 40000, dtype=np.uint8).tobytes()
            datas[f"o{i}"] = d
            await c.client.write_full(2, f"o{i}", d)
        pgid = c.client.osdmap.object_to_pg(2, b"o0")
        up, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
        victim = next(o for o in up if o != primary)
        await c.kill_osd(victim)
        await c.wait_down(victim, 20)
        await asyncio.sleep(1.5)  # past out_interval: remap + backfill
        await c.wait_clean(60)
        for n, d in datas.items():
            assert await c.client.read(2, n) == d, n
        tot = {}
        for o in c.osds:
            if o is None:
                continue
            for key, v in o.perf.dump().items():
                if isinstance(v, (int, float)):
                    tot[key] = tot.get(key, 0) + v
        assert tot.get("ec_repair_subchunk", 0) > 0, tot
        fetched = tot.get("ec_repair_bytes_fetched", 0)
        rebuilt = tot.get("ec_repair_bytes_rebuilt", 0)
        assert rebuilt > 0
        # clay k=3 m=2 d=4 q=2: sub-chunk amp d/q = 2.0 < k = 3; the
        # mixed ledger (some full-path rebuilds ride along) must still
        # beat the MDS bound
        assert fetched / rebuilt < 3.0, (fetched, rebuilt)
        assert tot.get("ec_decode_batches", 0) > 0
        await c.stop()

    run(t(), timeout=180)
