"""Nightly CI driver (paper §4.2.1): run the measured suite in all four
configurations (train/inference x with/without donation as the CPU/GPU
proxy), compare against the baseline store, file issues, and bisect.

Execution goes through the unified ``BenchmarkRunner``: pass a shared
runner to reuse arch builds and compiled executables across nights (the
per-night wall time drops to pure measurement after night 0).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

from repro.configs import ARCHS
from repro.core.harness import RegressionHook
from repro.core.regression import Issue, MetricStore, detect
from repro.runner.runner import BenchmarkRunner
from repro.runner.scenario import ScenarioMatrix


@dataclasses.dataclass
class NightlyReport:
    ran: int
    issues: List[Issue]
    wall_s: float

    def to_dict(self) -> dict:
        return {"ran": self.ran, "wall_s": self.wall_s,
                "issues": [i.to_dict() for i in self.issues]}


def run_nightly(store: MetricStore, *, archs: Optional[List[str]] = None,
                tasks=("train", "infer_decode"), runs: int = 5,
                batches=(2,), seqs=(64,),
                update_baseline: bool = False,
                hooks: Optional[Dict[str, RegressionHook]] = None,
                runner: Optional[BenchmarkRunner] = None) -> NightlyReport:
    """Measure the night's matrix on ``runner`` (a fresh one by default;
    pass one in to keep its caches warm across repeated nights).
    ``batches``/``seqs`` pick the probe cells — noisy shared hosts want
    small ones, so an injected regression dwarfs host jitter.

    Every measured result (ok or error, baseline night or not) is also
    appended to the store's history log as a provenance-stamped
    time-series point (``MetricStore.log_result``) — the raw material
    ``repro.telemetry.history`` turns into per-environment nightly
    trajectories — without touching the baseline pointer."""
    t0 = time.perf_counter()
    issues: List[Issue] = []
    runner = runner or BenchmarkRunner(runs=runs)
    matrix = ScenarioMatrix(archs=sorted(archs or ARCHS), tasks=tasks,
                            batches=batches, seqs=seqs)
    ran = 0
    for rr in runner.run_matrix(matrix, hooks=hooks, runs=runs):
        ran += 1
        store.log_result(rr)
        if rr.status != "ok":
            issues.append(Issue(benchmark=rr.bench, metric="status",
                                baseline=0.0, observed=0.0, increase=0.0,
                                culprit=rr.error))
            continue
        obs = rr.metrics()
        if update_baseline:
            store.update(rr.bench, obs)
        else:
            issues.extend(detect(store, rr.bench, obs))
    return NightlyReport(ran=ran, issues=issues,
                         wall_s=time.perf_counter() - t0)
