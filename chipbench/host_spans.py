"""Device idle time inside the engine's host phases.

The engine logs its host work as named spans (``decode.*`` per decode step,
``admit.*`` per admission group and wave) that do not overlap, and the
harness puts them on the trace's clock.  An idle gap of the device that
falls inside such a span is time the device waited on that phase; the part
of a gap outside every span is the engine loop's own.  Gaps are cut
exactly at span edges, so the idle inside each set of phases and the idle
outside them add up to the window's idle time.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

from chipbench.trace_reduce import union


def overlap_ns(gaps: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[float, float]]) -> float:
    """Length the sorted, disjoint ``gaps`` share with the union of
    ``spans``."""
    spans = union(list(spans))
    total, j = 0.0, 0
    for g0, g1 in gaps:
        while j < len(spans) and spans[j][1] <= g0:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < g1:
            total += min(g1, spans[k][1]) - max(g0, spans[k][0])
            k += 1
    return total


def idle_s(r, prefix: Union[str, Tuple[str, ...]]) -> Optional[float]:
    """Seconds of device idle inside the reading's engine spans whose name
    starts with ``prefix``; None where the run logged no such span."""
    spans = [(s[1], s[2]) for s in r.spans if s[0].startswith(prefix)]
    if not spans:
        return None
    return overlap_ns(r.trace.gaps(), spans) * 1e-9
