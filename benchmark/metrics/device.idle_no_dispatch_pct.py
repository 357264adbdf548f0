"""Device: share of the traced stretch in which the chip is idle AND no
EC dispatch stage (a host span named ``ec.*``, on any thread) is open,
averaged over the chips that ran anything, like device.idle_pct. Close
to device.idle_pct, the chip idles because no batch is ready: the host
code upstream of the ECBatcher sets the pace. Nothing is read from a
trace without ``ec.*`` spans."""

PREFIX = "ec."


def _covered_ns(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def read(w):
    if w.trace is None or not w.trace.devices or w.trace_s <= 0:
        return None
    spans = [(s, e) for s, e, name in w.trace.host_events
             if name.startswith(PREFIX)]
    if not spans:
        return None
    covered = [_covered_ns([tuple(iv) for iv in d.intervals] + spans)
               for d in w.trace.devices]
    return 100.0 * (1.0 - sum(covered) / len(covered) / 1e9 / w.trace_s)
