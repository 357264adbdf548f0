"""Kernels: the decode program's share of its HBM roofline.

Numerator: the least HBM traffic the decode math needs for the stripes
the ECBatcher decoded in the traced stretch (counter
``ec_decode_stripes``): per stripe k*su survivor bytes read and e*su
rebuilt bytes written, e the mean erased data shards of the cell's
degraded objects. Denominator: peak HBM bandwidth times the device
seconds of the programs whose names match PROGRAMS.
"""

#: XLA module names of the decode matmul programs (ops/rs.py)
PROGRAMS = ("gf_matmul",)


def read(w):
    if w.trace is None or "erased_rows_mean" not in w.cell:
        return None
    t = sum(s for name, s in w.trace.module_seconds().items()
            if any(p in name for p in PROGRAMS))
    stripes = w.delta("osd.ec_decode_stripes.sum", span="trace")
    if t <= 0 or stripes <= 0:
        return None
    c = w.cell
    nbytes = stripes * (c["k"] + c["erased_rows_mean"]) * c["su"]
    return 100.0 * nbytes / (w.peaks["hbm_bytes_per_s"] * t)
