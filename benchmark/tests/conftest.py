"""The benchmark's own tests, run by hand (not part of tier-1):

    python -m pytest benchmark/tests -q

They import the harness as run.py does: benchmark/ and the checkout's
root on sys.path.
"""
import os
import sys

# the CPU rehearsal of the four-chip cell needs four (virtual) devices,
# set before JAX starts
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
