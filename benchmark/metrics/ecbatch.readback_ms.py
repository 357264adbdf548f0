"""ECBatcher: device-to-host readback per device dispatch of outputs
already ready, over the window (perf ``ec_readback_lat`` sum /
count)."""


def read(w):
    n = w.delta("osd.ec_readback_lat.count")
    if n <= 0:
        return None
    return 1e3 * w.delta("osd.ec_readback_lat.sum") / n
