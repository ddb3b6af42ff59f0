"""Tick-driven sweep scheduler for the fleet service.

One tick = one nightly-shaped pass over the configured matrix through
the shared :class:`~repro.runner.BenchmarkRunner`, with every
measured ``RunResult`` appended to the :class:`~repro.core.regression
.MetricStore` history log as a provenance-stamped time-series point
(``extra["fleet_tick"]`` records which tick measured it), followed by
the ``telemetry/history.trajectory`` drift pass.  On a configurable
tick stride the scheduler also drains ``results/tuning_queue.json``
through ``repro.tuning.bridge.drain_queue`` — the scheduled version of
``benchmarks/profile_report --drain-queue`` — recording drained-job
counts in the metrics registry.

Time is injectable: pass a :class:`VirtualClock` and ticks advance
instantly in tests and ``scripts/fleet.py --fast`` demo runs; the
default :class:`WallClock` sleeps for real.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from repro.core.regression import THRESHOLD, MetricStore
from repro.fleet.metrics import registry
from repro.runner.results import RunResult
from repro.runner.scenario import Scenario, ScenarioMatrix
from repro.telemetry.history import trajectory


class WallClock:
    """Real time (the default outside tests)."""

    def time(self) -> float:
        return time.time()

    def sleep(self, seconds: float) -> None:
        if seconds > 0:
            time.sleep(seconds)


class VirtualClock:
    """Injectable clock: ``sleep`` advances the virtual time instantly,
    so a 2-tick nightly cadence demo completes in wall-milliseconds."""

    def __init__(self, start: float = 0.0):
        self._t = float(start)

    def time(self) -> float:
        return self._t

    def sleep(self, seconds: float) -> None:
        self._t += max(0.0, float(seconds))


@dataclasses.dataclass
class FleetConfig:
    """What one fleet tick measures, and on what cadence."""

    archs: Sequence[str] = ("gemma-2b",)
    tasks: Sequence[str] = ("train",)
    batches: Sequence[int] = (1,)
    seqs: Sequence[int] = (16,)
    dtypes: Sequence[str] = ("fp32",)
    runs: int = 3
    interval_s: float = 0.0        # clock.sleep between ticks (virtual ok)
    window: int = 5                # drift pass: rolling-baseline window
    threshold: float = THRESHOLD   # drift + triage confirmation threshold
    min_points: int = 2            # drift pass: series length floor
    drain_stride: int = 2          # drain tuning queue every Nth tick (0=off)
    drain_max_candidates: Optional[int] = None   # bound sweep cost per drain
    queue_path: str = ""           # "" -> tuning.bridge.default_queue_path()

    def matrix(self) -> ScenarioMatrix:
        return ScenarioMatrix(archs=list(self.archs), tasks=tuple(self.tasks),
                              batches=tuple(self.batches),
                              seqs=tuple(self.seqs),
                              dtypes=tuple(self.dtypes))


@dataclasses.dataclass
class TickResult:
    tick: int
    results: List[RunResult]
    drift: Dict[str, Any]          # trajectory() report (build_report shape)
    drained_cases: int             # kernel cases swept by this tick's drain
    wall_s: float


class FleetScheduler:
    """Runs the matrix, logs history, detects drift, drains the queue.

    The runner should be constructed with ``store=None`` — history
    points land exclusively through ``MetricStore.log_result`` here, so
    each cell contributes exactly one point per tick.

    ``hooks_for_tick(tick)`` returns the ``run_matrix`` hooks dict for a
    given tick (or None) — the injection point for regression demos and
    crash-recovery tests.
    """

    def __init__(self, config: FleetConfig, store: MetricStore, runner,
                 *, clock=None,
                 hooks_for_tick: Optional[Callable[[int], Optional[dict]]] = None):
        self.cfg = config
        self.store = store
        self.runner = runner
        self.clock = clock if clock is not None else WallClock()
        self.hooks_for_tick = hooks_for_tick or (lambda tick: None)
        self.matrix = config.matrix()
        self.scenarios: Dict[str, Scenario] = {sc.name: sc
                                               for sc in self.matrix.expand()}

    def tick(self, tick: int) -> TickResult:
        """One scheduled pass: sweep, log, drift, (stride-gated) drain."""
        reg = registry()
        t0 = time.monotonic()
        hooks = self.hooks_for_tick(tick)
        results = self.runner.run_matrix(self.matrix, hooks=hooks,
                                         runs=self.cfg.runs)
        for rr in results:
            rr.extra["fleet_tick"] = tick
            self.store.log_result(rr)
        reg.inc("fleet_ticks_total")
        reg.inc("fleet_history_points_total", len(results))
        reg.set_gauge("fleet_last_tick", tick)
        drift = trajectory(self.store, window=self.cfg.window,
                           threshold=self.cfg.threshold,
                           min_points=self.cfg.min_points)
        drained = 0
        if self.cfg.drain_stride and (tick + 1) % self.cfg.drain_stride == 0:
            drained = self.drain()
        return TickResult(tick=tick, results=results, drift=drift,
                          drained_cases=drained,
                          wall_s=time.monotonic() - t0)

    def drain(self) -> int:
        """Drain the autotuner's pending-job queue through the shared
        runner (the ``profile_report --drain-queue`` path, on schedule)."""
        from repro.tuning.bridge import drain_queue
        out = drain_queue(self.runner,
                          queue_path=self.cfg.queue_path or None,
                          max_candidates=self.cfg.drain_max_candidates)
        reg = registry()
        if out["jobs"]:
            reg.inc("fleet_drained_jobs_total", out["jobs"])
        if out["cases"]:
            reg.inc("fleet_drained_cases_total", out["cases"])
        return out["cases"]
