"""ECBatcher: stripes per batched encode or decode dispatch over the
window (``ec_batch_stripes`` + ``ec_decode_stripes`` over
``ec_batches`` + ``ec_decode_batches``)."""


def read(w):
    batches = w.delta("osd.ec_batches") + w.delta("osd.ec_decode_batches")
    if batches <= 0:
        return None
    stripes = (w.delta("osd.ec_batch_stripes.sum")
               + w.delta("osd.ec_decode_stripes.sum"))
    return stripes / batches
