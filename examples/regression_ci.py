"""The paper's CI use case (§4.2) end-to-end, on the provenance-keyed
nightly workflow: nightly suite run, baseline store, an injected "bad
commit", detection at the 7% threshold, and binary-search bisection to
the culprit.

    PYTHONPATH=src python examples/regression_ci.py

Each ``run_nightly`` call does two things with the ``MetricStore``:

* ``update``/``detect`` against the **baseline pointer** — the paper's
  original latest-vs-baseline check, unchanged; and
* ``log_result`` every measured record into the **history log** as a
  provenance-stamped time-series point (``extra["prov_commit"]``,
  backend, host... — see ``repro/runner/results.py``), WITHOUT moving
  the baseline pointer.

The second stream is what ``repro.telemetry.history`` consumes: points
group into one series per (scenario, provenance key), so night-over-
night trajectories never mix commits, backends, or hosts — a laptop
rerun of the suite lands in its own series instead of polluting the CI
host's rolling baseline.  After the two nights below, the trajectory
report (rendered at the end, same machinery as
``benchmarks/history_report.py``) shows a >=2-point series per probe
cell with the injected regression visible as its drift finding.
"""
import os
import sys
import tempfile

sys.path.insert(0, "src")

from repro.core.ci import run_nightly  # noqa: E402
from repro.core.harness import RegressionHook  # noqa: E402
from repro.core.regression import Commit, MetricStore, bisect_commits  # noqa: E402
from repro.runner import BenchmarkRunner, Scenario  # noqa: E402


def main() -> int:
    # a private mkdtemp dir, not the race-prone mktemp: the directory is
    # ours atomically, so the store path inside it can't be hijacked
    store = MetricStore(os.path.join(
        tempfile.mkdtemp(prefix="regression_ci_"), "store.json"))
    archs = ["gemma-2b", "mamba2-2.7b"]
    # one runner for the whole CI day: nights and bisection probes share
    # cached arch builds and compiled executables
    runner = BenchmarkRunner(runs=3)
    # small probe cells: a ~10ms step means the injected 50ms/step
    # regression is a 4-5x blowup that shared-host timing jitter (easily
    # +-50% on busy boxes) can never mask at the 7% threshold
    probe = dict(tasks=("train",), batches=(1,), seqs=(16,), runs=3)

    print("== night 0: record baselines ==")
    rep = run_nightly(store, archs=archs, update_baseline=True,
                      runner=runner, **probe)
    print(f"ran {rep.ran} benchmarks in {rep.wall_s:.1f}s")

    print("\n== night 1: a commit slows gemma-2b training by ~50ms/step ==")
    hooks = {"gemma-2b/train": RegressionHook(slowdown_s=0.05)}
    rep = run_nightly(store, archs=archs, hooks=hooks, runner=runner,
                      **probe)
    print(f"ran {rep.ran} benchmarks in {rep.wall_s:.1f}s (cached executables)")
    for issue in rep.issues:
        print(f"ISSUE: {issue.benchmark} {issue.metric} +{issue.increase:.0%} "
              f"(baseline {issue.baseline:.0f}, observed {issue.observed:.0f})")
    sc = Scenario(arch="gemma-2b", task="train", batch=1, seq=16)
    assert any(i.benchmark == sc.bench and i.metric == "median_us"
               for i in rep.issues)

    print("\n== bisect the day's 12 commits ==")
    base = store.baseline(sc.bench)["median_us"]

    def commit_runner(bad):
        def run(_name):
            hook = RegressionHook(slowdown_s=0.05) if bad else None
            return {"median_us": runner.run(sc, runs=2, hook=hook).median_us}
        return run

    commits = [Commit(f"c{i:02d}", i, commit_runner(i >= 8)) for i in range(12)]
    trace: list = []
    # classify at half the size of the regression we're hunting — THIS
    # bench's nightly increase, not the max across the suite (another
    # bench's noise blip must not inflate the bisection threshold) — so
    # host noise on shared boxes can't flag a good commit as the culprit
    inc = max(i.increase for i in rep.issues
              if i.benchmark == sc.bench and i.metric == "median_us")
    culprit = bisect_commits(commits, sc.bench, "median_us", base,
                             threshold=max(0.07, inc / 2), trace=trace)
    for t in trace:
        print(" ", t)
    print(f"culprit: {culprit.sha} (found with {len(trace)} measurements of 12 commits)")
    assert culprit.sha == "c08"

    print("\n== provenance-keyed nightly trajectory ==")
    from repro.profiler.report import format_table  # noqa: E402
    from repro.telemetry.history import trajectory  # noqa: E402
    traj = trajectory(store, min_points=2)
    for line in format_table(traj).splitlines():
        print(" ", line)
    assert traj["meta"]["series"], "expected >=2-point provenance series"

    print("\n== fleet triage: drift -> re-measure -> bisect, ranked ==")
    # the same trajectory drift findings, pushed through the fleet
    # service's triage pass: each perf_drift cell is re-measured under
    # the night's hooks (confirm or refute), confirmed ones bisected
    # over the day's commits — the nightly pipeline scripts/fleet.py
    # runs on every tick
    from repro.fleet.triage import triage  # noqa: E402
    scenarios = {sc.name: sc}
    report = triage(traj, runner=runner, scenarios=scenarios, hooks=hooks,
                    commits_for=lambda fd, s: commits,
                    meta={"kind": "regression_ci"})
    for line in format_table(report).splitlines():
        print(" ", line)
    assert any(f["rule"] == "regression_bisected"
               and f["evidence"]["culprit"] == "c08"
               for f in report["findings"]), "triage must re-find c08"
    print(f"runner stats: {runner.stats.to_dict()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
