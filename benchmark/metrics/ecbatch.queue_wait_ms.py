"""ECBatcher: mean wait of a stripe group in the batch queue over the
window (``ec_queue_wait_us``)."""


def read(w):
    n = w.delta("osd.ec_queue_wait_us.count")
    if n <= 0:
        return None
    return w.delta("osd.ec_queue_wait_us.sum") / n / 1e3
