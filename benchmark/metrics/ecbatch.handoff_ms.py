"""ECBatcher: loop-to-worker handoff per dispatch over the window: the
executor's start delay plus the lag from the worker returning to the
asyncio loop resuming the batch (perf ``ec_handoff_lat`` sum /
count). Both are queueing for a thread or for the GIL."""


def read(w):
    n = w.delta("osd.ec_handoff_lat.count")
    if n <= 0:
        return None
    return 1e3 * w.delta("osd.ec_handoff_lat.sum") / n
