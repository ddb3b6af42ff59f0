"""Measured per-step phase timelines — the profiler's raw signal.

The measurement harness can only see two phase boundaries on this backend:
the jitted call *returning* (end of host dispatch — argument validation,
cache lookup, async enqueue) and ``block_until_ready`` completing (end of
device execution).  A ``Timeline`` is the per-sample record of that split:

* step cells (train / infer_prefill / infer_decode): one ``PhaseSample``
  per measured iteration of ``harness.measure`` (warmup excluded);
* serve cells: one ``PhaseSample`` per batched decode step of the
  measured trace replay, plus ``idle_us`` — replay wall time spent
  *outside* decode steps (admission, per-request prefill, host queue
  management), which has no step-cell analogue.

Device memory stats (peak / in-use bytes) ride along when the backend
exposes ``Device.memory_stats()`` (TPU/GPU; the CPU backend returns None
and the fields are simply absent from the profile).

Backend-native traces (``jax.profiler``) are a future extension point —
see ROADMAP.md; this module is deliberately trace-free so it works on any
host the benchmark suite runs on.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

#: cap on the per-sample timeline recorded into ``extra["prof_timeline"]``
#: (serve replays can run thousands of decode steps; aggregates are exact,
#: the sample list is a debugging aid)
TIMELINE_CAP = 128


@dataclasses.dataclass
class PhaseSample:
    """One measured step, split at the dispatch/execution boundary (us)."""
    dispatch_us: float
    device_us: float

    @property
    def total_us(self) -> float:
        return self.dispatch_us + self.device_us


@dataclasses.dataclass
class Timeline:
    """Per-step phase capture for one profiled cell."""
    kind: str                                   # "step" | "decode_step"
    samples: List[PhaseSample] = dataclasses.field(default_factory=list)
    #: serve only: replay wall time outside the decode steps (us)
    idle_us: float = 0.0
    #: backend memory stats snapshot, when available
    memory: Optional[Dict[str, int]] = None

    @classmethod
    def from_phase_log(cls, log: Sequence[Tuple[float, float]], *,
                       kind: str = "step", wall_s: float = 0.0,
                       memory: Optional[Dict[str, int]] = None) -> "Timeline":
        """Build from a ``phase_log`` — (dispatch_s, device_s) tuples in
        **seconds**, as ``harness.measure`` appends them or
        ``launch.serve.decode_phase_log`` derives them from the serve
        engine's spans.  ``wall_s`` (serve) is the measured replay
        wall; any part of it not inside the logged steps becomes idle."""
        samples = [PhaseSample(d * 1e6, v * 1e6) for d, v in log]
        idle = 0.0
        if wall_s:
            stepped = sum(s.total_us for s in samples)
            idle = max(0.0, wall_s * 1e6 - stepped)
        return cls(kind=kind, samples=samples, idle_us=idle, memory=memory)

    # ---- aggregates ------------------------------------------------------

    @property
    def steps(self) -> int:
        return len(self.samples)

    @property
    def dispatch_us(self) -> float:
        return sum(s.dispatch_us for s in self.samples)

    @property
    def device_us(self) -> float:
        return sum(s.device_us for s in self.samples)

    @property
    def total_us(self) -> float:
        """Everything the profile accounts for: steps + (serve) idle."""
        return self.dispatch_us + self.device_us + self.idle_us

    def to_extra(self) -> Dict[str, object]:
        """The timeline's share of the well-known ``extra["prof_*"]`` keys
        (see ``repro/runner/results.py``)."""
        n = max(1, self.steps)
        out: Dict[str, object] = {
            "prof_kind": self.kind,
            "prof_steps": self.steps,
            "prof_dispatch_us_mean": self.dispatch_us / n,
            "prof_device_us_mean": self.device_us / n,
            "prof_timeline": [[round(s.dispatch_us, 2), round(s.device_us, 2)]
                              for s in self.samples[:TIMELINE_CAP]],
        }
        if self.idle_us:
            out["prof_idle_us"] = self.idle_us
        if self.memory:
            if self.memory.get("peak_bytes"):
                out["prof_device_peak_bytes"] = self.memory["peak_bytes"]
            if self.memory.get("bytes_in_use"):
                out["prof_device_bytes_in_use"] = self.memory["bytes_in_use"]
        return out


def device_memory_stats() -> Optional[Dict[str, int]]:
    """Peak/live device bytes when the backend exposes them, else None
    (the CPU backend has no allocator stats — readers must tolerate
    absence, exactly like every other well-known extra)."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — any backend without the API
        return None
    if not stats:
        return None
    return {"peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_in_use": int(stats.get("bytes_in_use", 0))}
