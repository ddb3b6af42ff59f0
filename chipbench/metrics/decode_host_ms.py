"""Device idle time inside the engine's decode phases (its ``decode.*``
host spans: the KV guard and token upload, the dispatch, the argmax and
readback, the per-row bookkeeping), per execution of the ``decode_step``
program, in ms.  Gaps are cut exactly at the span edges.  What the host
costs each decode step beyond the device's own work.  Layer: serve engine.
Moves ``tpot_p90_ms``."""
from chipbench import host_spans


def read(r):
    m = r.trace.module("decode_step")
    idle = host_spans.idle_s(r, "decode.")
    if m is None or idle is None:
        return None
    return idle / m[1] * 1e3
