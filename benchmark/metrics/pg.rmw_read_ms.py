"""OSD / PG: time of the EC read-modify-write's old-stripe reads per
client op over the window (perf ``op_rmw_read_lat`` sum over
``op_latency`` count). It overlaps pg.subop_wait_ms, since the read's
sub-read fan-out is timed there too. None where no write read old
stripes, as in a window of whole-object or fresh-name writes, or on a
program without the stage."""


def read(w):
    n = w.delta("osd.op_latency.count")
    if n <= 0 or w.delta("osd.op_rmw_read_lat.count") <= 0:
        return None
    return 1e3 * w.delta("osd.op_rmw_read_lat.sum") / n
