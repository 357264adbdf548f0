"""Device: share of the traced stretch in which no operation ran on the
device (1 - union of the op intervals / traced seconds), averaged over
the chips that ran anything."""


def read(w):
    if w.trace is None or not w.trace.devices or w.trace_s <= 0:
        return None
    return 100.0 * (1.0 - w.trace.busy_s() / w.trace_s)
