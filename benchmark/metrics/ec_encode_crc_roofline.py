"""Kernels: the fused encode+CRC program's share of its HBM roofline.

Numerator: the least HBM traffic the EC math needs for the stripes the
ECBatcher encoded in the traced stretch (counter ``ec_batch_stripes``):
per stripe k*su bytes read, m*su parity and (k+m)*4 CRC bytes written.
Counted from the algorithm, not the implementation, so a PR that fuses
or splits kernels is counted the same. Denominator: peak HBM bandwidth
times the device seconds of the programs whose names match PROGRAMS.
Pow2 batch padding is work the device does that this does not count.
"""

#: XLA module names of the fused encode+CRC program (ops/rs.py)
PROGRAMS = ("encode_with_crcs",)


def read(w):
    if w.trace is None:
        return None
    t = sum(s for name, s in w.trace.module_seconds().items()
            if any(p in name for p in PROGRAMS))
    stripes = w.delta("osd.ec_batch_stripes.sum", span="trace")
    if t <= 0 or stripes <= 0:
        return None
    c = w.cell
    nbytes = stripes * ((c["k"] + c["m"]) * c["su"] + (c["k"] + c["m"]) * 4)
    return 100.0 * nbytes / (w.peaks["hbm_bytes_per_s"] * t)
