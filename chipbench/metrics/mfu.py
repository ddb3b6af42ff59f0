"""Model flops utilisation of the whole traced window: the operations that
every prompt and output token processed in it needs, over the window's
length times the chip's peak bf16 rate.  Layer: whole step.  Moves
``tok_s``."""


def read(r):
    if r.peaks is None or not r.decode_steps:
        return None
    flops = sum(r.work.decode_step(r.cell.config, rows)[0] for rows in r.decode_steps)
    if r.admitted:
        m = r.trace.module("_admit_impl")
        flops += r.work.prefill(r.cell.config, r.admitted, calls=m[1] if m else 1)[0]
    return 100.0 * flops / (r.trace.window_s * r.peaks.bf16_flops_per_s)
