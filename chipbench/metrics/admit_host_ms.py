"""Device idle time inside the engine's admission phases (its ``admit.*``
host spans: packing each bucket group, its prefill call and first-token
readback, or a compile in its place, and the wave's bookkeeping), per
execution of the ``_admit_impl`` program, in ms.  Gaps are cut exactly at
the span edges.  Layer: serve engine.  Moves ``ttft_p90_ms``."""
from chipbench import host_spans


def read(r):
    m = r.trace.module("_admit_impl")
    idle = host_spans.idle_s(r, "admit.")
    if m is None or idle is None:
        return None
    return idle / m[1] * 1e3
