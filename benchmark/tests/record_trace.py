"""Record the small device trace that test_xplane.py reduces.

On the chip: ``python benchmark/tests/record_trace.py <out_dir>``. It
traces three calls each of the fused encode+CRC program and one decode
program at the k8m3 cells' smallest batch (128 stripes of 4 KiB cells),
with the profiler options the harness uses, and prints what the trace
holds: planes, lines, and the names of device events.
"""
from __future__ import annotations

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)),
                os.path.dirname(HERE)]


def main(out_dir: str) -> None:
    import jax

    from ceph_tpu.ec.registry import load_codec
    from harness import xplane

    codec = load_codec({"plugin": "rs_tpu", "k": "8", "m": "3",
                        "backend": "device"})
    x = np.random.default_rng(0).integers(0, 2**32, (128, 8, 1024),
                                          dtype=np.uint32)
    present = (0, 2, 3, 4, 5, 7, 8, 9)
    enc = lambda: codec.encode_crc_batch(x, 4096)  # noqa: E731
    dec = lambda: codec.decode_batch(present, x, want=(1, 6))  # noqa: E731
    jax.block_until_ready((enc(), dec()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(3):
        jax.block_until_ready(enc())
        jax.block_until_ready(dec())
    jax.profiler.stop_trace()
    path = xplane.find_trace(out_dir)
    print(f"trace {path}: {os.path.getsize(path)} bytes")
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = [(ln.name, len(list(ln.events))) for ln in plane.lines]
        print(f"plane {plane.name!r}: lines {lines}")
        if xplane._is_device(plane.name):
            for ln in plane.lines:
                names = sorted({ev.name for ev in ln.events})
                print(f"  {ln.name}: {names[:20]}")
    s = xplane.reduce(path)
    print(f"busy_s {s.busy_s()} modules {s.module_seconds()}")
    print(f"breakdown {s.breakdown()}")


if __name__ == "__main__":
    main(sys.argv[1])
