"""Share of its roofline the admission program reaches: the least time the
chip needs for the valid prompt tokens admitted while traced, over the
device time of the ``_admit_impl`` programs.  Padding to buckets and the
rewrite of the whole cache count as waste.  Layer: kernels.  Moves
``tok_s``, which every cell reports (``mfu`` is the whole step's share
beside it)."""


def read(r):
    m = r.trace.module("_admit_impl")
    if m is None or r.peaks is None or not r.admitted:
        return None
    flops, nbytes = r.work.prefill(r.cell.config, r.admitted, calls=m[1])
    return 100.0 * r.peaks.least_time(flops, nbytes)[0] / m[0]
