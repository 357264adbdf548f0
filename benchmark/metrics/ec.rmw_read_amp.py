"""OSD / PG: EC read-modify-write read amplification over the window:
old-stripe bytes the RMW read (perf ``ec_rmw_read_bytes``) per byte the
client ops wrote (``ec_user_bytes_written``). A 4 KiB write into a k=4
stripe of 4 KiB cells reads the stripe's four data cells: 4.0. None
where no EC write ran, or on a program without the counters."""


def read(w):
    user = w.delta("osd.ec_user_bytes_written")
    if user <= 0:
        return None
    return w.delta("osd.ec_rmw_read_bytes") / user
