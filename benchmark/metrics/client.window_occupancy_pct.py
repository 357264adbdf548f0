"""Client aio window: mean ops in flight at each submission over the
window, as a share of client_max_inflight (cluster/client.py
``window_stats``)."""


def read(w):
    n = w.delta("client.window_count")
    if n <= 0:
        return None
    mean = w.delta("client.window_sum") / n
    return 100.0 * mean / w.after["client.max_inflight"]
