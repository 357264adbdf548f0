"""OSD / PG: the share of EC object-existence decisions that took a
metadata probe fan-out to every peer, over the window: perf
``ec_meta_probe`` over ``ec_meta_probe`` + ``ec_meta_local`` (absences
the primary decided on its own shard). None where neither ran, as in a
window that touches only objects the primaries hold, or on a program
without the counters."""


def read(w):
    probe = w.delta("osd.ec_meta_probe")
    local = w.delta("osd.ec_meta_local")
    if probe + local <= 0:
        return None
    return 100.0 * probe / (probe + local)
