"""Load-generation helpers for ``task="loadgen"`` cells.

A loadgen cell replays a (shard of a) deterministic trace against the
continuous-batching serve engine at a scaled *offered load*, so a matrix
sweeping ``loads=(0.5, 1.0, 2.0, 4.0)`` measures a TTFT/p99-vs-load
curve; sweeping ``splits=("0/2", "1/2")`` replays each trace shard as a
cell of its own.

Both transforms act on the generated ``Request`` list, never on the
spec: the prompt tokens stay a pure function of (trace spec, params), so
shard digests are stable and the shards' union equals the whole trace.

``find_knee`` post-processes a measured curve: offered load is swept up,
throughput saturates, and the knee is the last point whose marginal
throughput gain over the previous point still exceeds ~5% — past it the
engine only queues (TTFT and p99 climb with no tok/s to show for it).

``auto_slots`` closes the loop: it turns a persisted measured curve
(``benchmarks/loadgen_curve.py`` -> ``results/loadgen_curve.json``) into a
slot count, so ``ScenarioMatrix(slots=("auto",))`` picks the decode batch
width from the measured knee instead of by hand.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

from repro.runner.traces import Request
from repro.telemetry.spans import warn

#: marginal-throughput-gain threshold that defines saturation
KNEE_GAIN = 0.05

#: schema tag of results/loadgen_curve.json consumed by auto_slots (bumped
#: whenever benchmarks/loadgen_curve.py changes the file layout — an old
#: file is then *stale* and auto_slots falls back to the default)
CURVE_SCHEMA = 2

#: environment override for the curve location (tests, ad-hoc curves)
CURVE_PATH_ENV = "REPRO_LOADGEN_CURVE"

#: fallback slot count when no usable curve exists (the Scenario default)
DEFAULT_SLOTS = 4

#: autoscaler bounds and headroom: the measured width is scaled by
#: HEADROOM/knee_load and clamped to [1, AUTO_SLOTS_MAX]
AUTO_SLOTS_MAX = 16
AUTO_SLOTS_HEADROOM = 1.25


def auto_slots_info(arch: str, curve_path: Optional[str] = None,
                    default: int = DEFAULT_SLOTS) -> Tuple[int, str]:
    """``(slots, fallback_reason)`` for ``arch`` from the measured curve.

    The reason is ``""`` when the knee policy actually ran, else one of
    ``"missing"`` (no curve file), ``"unreadable"`` (exists but not valid
    JSON), ``"stale-schema"`` (written by an older
    ``benchmarks/loadgen_curve.py`` layout), ``"foreign-arch"`` (curve
    measured for a different arch) or ``"degenerate-curve"`` (no usable
    knee/slot numbers).  Every fallback emits one structured
    ``telemetry.warn("slots_fallback", ...)`` line — a stale curve
    silently shaping a matrix is exactly the failure this surfaces.
    """
    path = (curve_path or os.environ.get(CURVE_PATH_ENV)
            or os.path.join("results", "loadgen_curve.json"))

    def fallback(reason: str) -> Tuple[int, str]:
        warn("slots_fallback", arch=arch, path=path, reason=reason,
             slots=default)
        return default, reason

    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except OSError:
        return fallback("missing")
    except ValueError:
        return fallback("unreadable")
    if not isinstance(data, dict) or data.get("schema") != CURVE_SCHEMA:
        return fallback("stale-schema")
    if data.get("arch") != arch:
        return fallback("foreign-arch")
    knee = ((data.get("curves") or {}).get("batched") or {}).get("knee") or {}
    knee_load = knee.get("knee_load") or 0.0
    measured = data.get("slots") or 0
    if knee_load <= 0 or measured <= 0:
        return fallback("degenerate-curve")
    target = measured * AUTO_SLOTS_HEADROOM / knee_load
    return max(1, min(AUTO_SLOTS_MAX, int(math.ceil(target)))), ""


def auto_slots(arch: str, curve_path: Optional[str] = None,
               default: int = DEFAULT_SLOTS) -> int:
    """Knee-driven slot count for ``arch`` from the measured load curve.

    Reads ``results/loadgen_curve.json`` (or ``$REPRO_LOADGEN_CURVE`` /
    ``curve_path``), written by ``benchmarks/loadgen_curve.py`` with the
    slot count it measured at and the batched-admission saturation knee.
    The policy scales the measured width to the knee: a knee at offered
    load 1.0 means the width just keeps up with the native arrival rate —
    keep it (times ``AUTO_SLOTS_HEADROOM``); a knee below 1.0 means the
    engine saturates under native load — scale up proportionally; a knee
    well above 1.0 means the width is oversized — scale down.

    Falls back to ``default`` on a missing file, unreadable JSON, a stale
    schema tag, or a curve measured for a different arch — a wrong curve
    must never silently shape another arch's matrix.  The fallback is
    *not* silent: ``auto_slots_info`` (this function's implementation)
    names the reason in a structured warning, and ``ScenarioMatrix``
    forwards it to the affected cells as ``extra["slots_fallback"]``.
    """
    return auto_slots_info(arch, curve_path, default)[0]


def parse_split(split: str) -> Tuple[int, int]:
    """``"i/n"`` -> (i, n), validated (0 <= i < n, n >= 1)."""
    try:
        i_s, n_s = split.split("/")
        i, n = int(i_s), int(n_s)
    except ValueError:
        raise ValueError(f"split must be 'i/n', got {split!r}") from None
    if n < 1 or not (0 <= i < n):
        raise ValueError(f"split {split!r} needs 0 <= i < n")
    return i, n


def shard_requests(requests: List[Request], split: str) -> List[Request]:
    """Shard ``i/n``: keep every n-th request by rid order, starting at i.

    Deterministic in the request ids alone (not list order, not arrival
    times), so the same split expression always names the same shard, and
    the n shards partition the trace exactly.
    """
    if not split:
        return requests
    i, n = parse_split(split)
    by_rid = sorted(requests, key=lambda r: r.rid)
    keep = {r.rid for j, r in enumerate(by_rid) if j % n == i}
    return [r for r in requests if r.rid in keep]


def scale_arrivals(requests: List[Request], load: float) -> List[Request]:
    """Offered load: compress (load > 1) or stretch (load < 1) the virtual
    arrival clock — ``arrival' = floor(arrival / load)``.  load=1.0 is the
    identity; the transform mutates arrival steps in place and returns the
    list for chaining.  Tokens are unaffected (arrivals only schedule slot
    admission; each request's output depends only on its own prompt)."""
    if load <= 0:
        raise ValueError(f"load must be > 0, got {load}")
    if load != 1.0:
        for r in requests:
            r.arrival_step = int(math.floor(r.arrival_step / load))
    return requests


def find_knee(points: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """The saturation knee of a measured load curve.

    ``points`` are dicts with at least ``load`` and ``tok_per_s`` (one per
    swept offered load, any order).  Returns ``{"knee_load", "knee_tok_s"}``
    — the highest offered load whose step still bought a >= ``KNEE_GAIN``
    marginal throughput gain (scanning all steps, so one noisy mid-curve
    plateau doesn't end the search early).  With 0 or 1 points, or when
    no step ever bought throughput, the first point is the knee.
    """
    pts = sorted(points, key=lambda p: p["load"])
    if not pts:
        return {"knee_load": 0.0, "knee_tok_s": 0.0}
    knee = pts[0]
    for prev, cur in zip(pts, pts[1:]):
        base = prev["tok_per_s"]
        gain = (cur["tok_per_s"] - base) / base if base > 0 else 0.0
        if gain >= KNEE_GAIN:
            knee = cur
    return {"knee_load": float(knee["load"]),
            "knee_tok_s": float(knee["tok_per_s"])}
