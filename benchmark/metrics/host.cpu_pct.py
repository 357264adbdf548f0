"""Host process: CPU seconds (user + system, every thread: the asyncio
loop and the EC dispatch threads) per second of the traced stretch, in
% of one core. Taken over the traced stretch, like the device's idle
share, so the profiler's own write-out after it is not counted; the
host tracer's overhead inside it is."""


def read(w):
    if w.trace_s <= 0:
        return None
    return 100.0 * w.delta("cpu_s", span="trace") / w.trace_s
