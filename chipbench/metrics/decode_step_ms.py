"""Device time of one execution of the jitted ``decode_step`` program, in
ms, from the profiler trace.  Layer: model step.  Moves ``tpot_p90_ms``."""


def read(r):
    m = r.trace.module("decode_step")
    return None if m is None else m[0] / m[1] * 1e3
