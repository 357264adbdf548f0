"""OSD / PG: EC write amplification over the window: data and parity
bytes the EC write path put into shard transactions, over all k+m
shards (perf ``ec_shard_bytes_written``), per byte the client ops
wrote (``ec_user_bytes_written``). A 4 KiB write into a k=4 stripe of
4 KiB cells rewrites the stripe's six cells: 6.0. None where no EC
write ran, or on a program without the counters."""


def read(w):
    user = w.delta("osd.ec_user_bytes_written")
    if user <= 0:
        return None
    return w.delta("osd.ec_shard_bytes_written") / user
