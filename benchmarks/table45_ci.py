"""Paper §4.2 / Tables 4-5: CI regression detection + nightly bisection.

End-to-end demo with REAL measurements: record a baseline, inject two
regression classes (runtime inflation via a slow hook, memory bloat via a
leaked buffer), verify detection at the 7% threshold, then bisect a
synthetic day of 12 commits to the culprit in O(log n) measurements.

Every measurement is one scenario re-run through the shared
``BenchmarkRunner`` — the executable cache means the ~10 re-measures of
the same cell (baseline, two injections, bisection probes) compile once."""
from __future__ import annotations

import json
import tempfile

from benchmarks.common import emit, make_runner, results_path
from repro.core.harness import RegressionHook
from repro.core.regression import Commit, MetricStore, bisect_commits, detect
from repro.runner.scenario import Scenario


def _ok(rr):
    """CI math needs real numbers: a failed measurement must fail the table
    loudly, not flow through as median_us=0."""
    if rr.status != "ok":
        raise RuntimeError(f"{rr.name}: {rr.error}")
    return rr


def main(fast: bool = False, runner=None) -> None:
    runner = runner or make_runner()
    sc = Scenario(arch="gemma-2b", task="train", batch=2, seq=32)
    store = MetricStore(tempfile.mktemp(suffix=".json"))

    base = _ok(runner.run(sc, runs=4))
    store.update(sc.bench, base.metrics())
    emit("table45/baseline", base.median_us,
         f"recorded;executable_reused={base.cache.get('executable_reused', False)}")

    # regression class 1: runtime inflation (paper PR #61056 et al.)
    slow = _ok(runner.run(sc, runs=4, hook=RegressionHook(slowdown_s=0.03)))
    issues = detect(store, sc.bench, {"median_us": slow.median_us})
    emit("table45/runtime_inflation", slow.median_us,
         f"detected={bool(issues)};increase={issues[0].increase:.2f}" if issues else "detected=False")

    # regression class 2: memory bloat (paper PR #85447)
    bloat = _ok(runner.run(sc, runs=4, hook=RegressionHook(leak_bytes=1 << 22)))
    issues_m = detect(store, sc.bench,
                      {"host_peak_bytes": bloat.host_peak_bytes,
                       "device_bytes_delta": bloat.device_bytes_delta},
                      metrics=("host_peak_bytes", "device_bytes_delta"))
    emit("table45/memory_bloat", 0.0, f"detected={bool(issues_m)}")

    # nightly bisection over a synthetic commit day — the runner's executable
    # cache turns each probe into a pure re-measure (no rebuild, no re-jit)
    def commit_runner(bad):
        def run(_bench):
            h = RegressionHook(slowdown_s=0.03) if bad else None
            return {"median_us": _ok(runner.run(sc, runs=2, hook=h)).median_us}
        return run

    commits = [Commit(sha=f"c{i:02d}", timestamp=i, run=commit_runner(i >= 8)) for i in range(12)]
    trace: list = []
    # bisect hunts a regression whose size the nightly already measured —
    # classify at half that size so host noise can't flag a good commit
    threshold = max(0.07, issues[0].increase / 2) if issues else 0.07
    culprit = bisect_commits(commits, sc.bench, "median_us", base.median_us,
                             threshold=threshold, trace=trace)
    emit("table45/bisect", 0.0,
         f"culprit={culprit.sha if culprit else None};measured={len(trace)}_of_12")
    emit("table45/runner_reuse", 0.0,
         f"executable_cache_hits={runner.stats.executable_cache_hits};"
         f"model_builds={runner.stats.model_builds}")
    with open(results_path("table45_ci.json"), "w") as f:
        json.dump({"trace": trace, "culprit": culprit.sha if culprit else None,
                   "runtime_issues": [i.to_dict() for i in issues],
                   "memory_issues": [i.to_dict() for i in issues_m],
                   "runner_stats": runner.stats.to_dict()}, f, indent=1)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    main(fast=args.fast, runner=make_runner())
