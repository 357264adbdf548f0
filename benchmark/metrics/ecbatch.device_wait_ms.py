"""ECBatcher: wait per device dispatch for the program's outputs to be
ready (``jax.block_until_ready`` on the worker thread) over the window
(perf ``ec_device_wait_lat`` sum / count). Far above the device
seconds per dispatch, the wait is for the GIL, not the device."""


def read(w):
    n = w.delta("osd.ec_device_wait_lat.count")
    if n <= 0:
        return None
    return 1e3 * w.delta("osd.ec_device_wait_lat.sum") / n
