"""Share of its roofline the decode program reaches: the least time the
chip needs for the work the traced decode steps need (the weights, and the
keys and values or the state of the rows that decode), over the device time
of the ``decode_step`` program, per step.  Layer: kernels.  Moves ``tok_s``."""


def read(r):
    m = r.trace.module("decode_step")
    if m is None or r.peaks is None or not r.decode_steps:
        return None
    least = sum(r.peaks.least_time(*r.work.decode_step(r.cell.config, rows))[0]
                for rows in r.decode_steps)
    return 100.0 * (least / len(r.decode_steps)) / (m[0] / m[1])
