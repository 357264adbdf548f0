"""ECBatcher: distinct OSDs whose stripes rode one successful encode or
decode dispatch, mean over the window (histogram ``ec_batch_osds``, its
sum over its count). 1 where each OSD dispatches alone; above 1 where
the OSDs of one process share a dispatch. None where no dispatch ran,
or on a program without the counter."""


def read(w):
    dispatches = w.delta("osd.ec_batch_osds.count")
    if dispatches <= 0:
        return None
    return w.delta("osd.ec_batch_osds.sum") / dispatches
