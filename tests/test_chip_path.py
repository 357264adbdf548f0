"""The chip path refuses to carry on without the chip, and chip_smoke.py
rehearses on the CPU.

- no silent fallbacks: too few devices, a failing device under the EC
  engine probe, and a CPU platform under chip_smoke.py all raise or
  exit non-zero;
- the compile cache directory is placeable from outside and otherwise
  fixed;
- chip_smoke.py's phases run end to end at a tiny size on the virtual
  CPU devices conftest pins (the script itself refuses the CPU).
"""
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from ceph_tpu import parallel
from ceph_tpu.ec import engine
from ceph_tpu.ops import rs
from ceph_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra) -> dict:
    env = dict(os.environ)
    env.pop(compile_cache.ENV, None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_get_devices_raises_when_too_few():
    assert len(parallel.get_devices(8)) == 8
    with pytest.raises(RuntimeError, match="need 4096 devices"):
        parallel.get_devices(4096)


def test_engine_probe_propagates_device_error(monkeypatch):
    def broken(*_a, **_kw):
        def run(_batch):
            raise RuntimeError("device lost")
        return run

    monkeypatch.delenv("CEPH_TPU_EC_ENGINE", raising=False)
    monkeypatch.setattr(rs, "jit_encode_with_crcs", broken)
    engine.reset_probe()
    try:
        with pytest.raises(RuntimeError, match="device lost"):
            engine._probe()
        with pytest.raises(RuntimeError, match="device lost"):
            engine.data_path_engine()
    finally:
        engine.reset_probe()


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_compile_cache_dir_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.cache_dir()
    assert first == compile_cache.cache_dir()
    assert first == os.path.join(REPO, ".jax_cache")
    code = ("from ceph_tpu.utils import compile_cache as c; "
            "print(c.cache_dir())")
    outs = {subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           env=_cpu_env(), capture_output=True, text=True,
                           check=True).stdout.strip() for _ in range(2)}
    assert outs == {first}


@pytest.mark.parametrize("where", ["repo", "bare"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    """JAX_PLATFORMS=cpu, or a directory holding chip_smoke.py and
    nothing else of the repo: non-zero exit, no result line."""
    cwd = REPO
    if where == "bare":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                       env=_cpu_env(PYTHONPATH=""), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr


def test_chip_smoke_kernel_phase_rehearsal():
    times = chip_smoke.kernel_phase(batch=2, chunk=8192, n_blobs=8,
                                    blob=4096, n_osds=16, n_xs=256)
    assert set(times) == {"encode_crc", "decode_2_erasures",
                          "crc32c_blobs", "straw2_bulk"}


def test_chip_smoke_cluster_phase_rehearsal():
    r = chip_smoke.cluster_phase(n_objects=8, obj_bytes=128 * 1024,
                                 n_degraded=2)
    assert r["degraded_reads"] >= 2
    assert r["ec_batches"] > 0 and r["ec_decode_batches"] > 0


def test_chip_smoke_mesh_phase_rehearsal():
    r = chip_smoke.mesh_phase(n_objects=8, obj_bytes=128 * 1024,
                              n_degraded=2)
    assert r["write_phase"]["mesh_encode_dispatches"] > 0
    assert r["after"]["mesh_decode_dispatches"] > 0
