"""The arithmetic of the end-to-end metrics, over all requests of a window.

``percentile`` is a copy of ``repro.runner.latency.percentile`` (linear
interpolation between closest ranks), kept here so that no change to the
program can change how the benchmark reads a tail.
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of ``values``, linear interpolation."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q={q} outside [0, 100]")
    vals = sorted(float(v) for v in values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    if len(vals) == 1:
        return vals[0]
    rank = (q / 100.0) * (len(vals) - 1)
    lo = int(rank)
    frac = rank - lo
    if frac == 0.0 or lo + 1 >= len(vals):
        return vals[lo]
    return vals[lo] * (1.0 - frac) + vals[lo + 1] * frac


def end_to_end(requests: Iterable, window_s: float) -> Dict[str, float]:
    """``tok_s``, ``ttft_p90_ms`` and ``tpot_p90_ms`` of a window.

    ``requests`` are every request served in the window (objects with
    ``out``, ``t_arrival``, ``t_first`` and ``t_done`` as the engine stamps
    them); ``window_s`` is the window's wall time.  TTFT counts from the
    moment the request became due; the time per output token is a
    request's whole decode span over its decode tokens, admission stalls
    between its steps included.
    """
    reqs = list(requests)
    if window_s <= 0 or not reqs:
        raise ValueError("an empty window has no end-to-end metrics")
    tokens = sum(len(r.out) for r in reqs)
    ttft = [(r.t_first - r.t_arrival) * 1e3 for r in reqs]
    tpot = [(r.t_done - r.t_first) * 1e3 / (len(r.out) - 1)
            for r in reqs if len(r.out) >= 2]
    return {"tok_s": tokens / window_s,
            "ttft_p90_ms": percentile(ttft, 90.0),
            "tpot_p90_ms": percentile(tpot, 90.0)}

