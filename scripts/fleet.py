#!/usr/bin/env python
"""The fleet perf-CI service CLI: supervised scheduled sweeps with live
metrics, drift triage, and automatic re-measure + bisect.

    PYTHONPATH=src python scripts/fleet.py --ticks N [--fast]
        [--results-dir DIR] [--interval-s S]

``--fast`` is the bounded demo/CI mode on a virtual clock: a 2-cell
matrix (gemma-2b train, fp32 + bf16), an injected ``RegressionHook``
slowdown from tick 2 onwards, a synthetic 12-commit day (c00..c11, bad
from c08) measured through the same runner for the bisection stage, and
one pre-enqueued tuning job so the stride-gated autotuner drain has
work.  After the run the triage report, status heartbeat, and
Prometheus snapshot are under ``--results-dir``:

* ``fleet_status.json``  — schema-tagged liveness probe: last tick,
  open findings, restarts, per-tick counter snapshots, full metrics
  snapshot (rewritten after every tick);
* ``fleet_report.json``  — ranked triage outcomes
  (confirmed / refuted / bisected);
* ``fleet_metrics.prom`` — Prometheus text exposition snapshot.

Without ``--fast`` the service runs the default nightly-probe matrix on
a wall clock at ``--interval-s`` between ticks, indefinitely up to
``--ticks``.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.core.harness import RegressionHook  # noqa: E402
from repro.core.regression import Commit, MetricStore  # noqa: E402
from repro.fleet.scheduler import FleetConfig, VirtualClock  # noqa: E402
from repro.fleet.service import FleetService  # noqa: E402
from repro.profiler.report import format_table  # noqa: E402
from repro.runner import BenchmarkRunner  # noqa: E402

SLOWDOWN_S = 0.05      # the injected regression: ~5x on a ~10ms probe step
BAD_COMMIT = 8         # c08.. are bad in the synthetic 12-commit day


def _fast_hooks(tick: int):
    """Ticks 0..n-2 are healthy baselines; the final ticks carry the
    injected slowdown on every gemma-2b train cell (keyed by bench, so
    both dtype cells regress)."""
    if tick >= 1:
        return {"gemma-2b/train": RegressionHook(slowdown_s=SLOWDOWN_S)}
    return None


def _fast_commits_for(runner):
    """The synthetic commit day for the bisection stage: each commit
    re-measures the flagged cell through the shared runner (cached
    executables — regression_ci's idiom), bad from c08 onwards."""
    def commits_for(finding, scenario):
        def commit_runner(bad):
            def run(_name):
                hook = RegressionHook(slowdown_s=SLOWDOWN_S) if bad else None
                rr = runner.run(scenario, runs=2, hook=hook, record=False)
                return rr.metrics()
            return run
        return [Commit(f"c{i:02d}", i, commit_runner(i >= BAD_COMMIT))
                for i in range(12)]
    return commits_for


def _seed_tuning_queue(queue_path: str) -> None:
    """One small flash-attention job so the demo's stride drain has work
    (profile_report's detectors would enqueue these in production)."""
    from repro.tuning import enqueue_jobs, make_case
    case = make_case("flash_attention", B=1, S=32, H=2, K=2, D=32)
    enqueue_jobs([{"kernel": case.kernel, "case": case.case_id,
                   "signature": case.signature, "dtype": case.dtype,
                   "source_rule": "manual", "severity": "info",
                   "in_db": False}], queue_path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=2,
                    help="supervised scheduler ticks to run")
    ap.add_argument("--fast", action="store_true",
                    help="bounded demo: virtual clock, 2-cell matrix, "
                         "injected regression + synthetic commit day")
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--interval-s", type=float, default=0.0,
                    help="clock sleep between ticks (virtual under --fast)")
    ap.add_argument("--results-dir", default="results")
    args = ap.parse_args(argv)

    os.makedirs(args.results_dir, exist_ok=True)
    store_path = os.path.join(args.results_dir, "fleet_store.json")
    queue_path = os.path.join(args.results_dir, "tuning_queue.json")
    if args.fast:
        # demo determinism: drift on tick 2 must be judged against THIS
        # run's tick-1 baseline, not a previous invocation's history
        for stale in (store_path, store_path[:-len(".json")] + ".jsonl"):
            try:
                os.remove(stale)
            except OSError:
                pass
        cfg = FleetConfig(archs=("gemma-2b",), tasks=("train",),
                          batches=(1,), seqs=(16,),
                          dtypes=("fp32", "bf16"), runs=args.runs,
                          interval_s=args.interval_s or 3600.0,
                          drain_stride=2, drain_max_candidates=2,
                          queue_path=queue_path)
        clock = VirtualClock()
        _seed_tuning_queue(queue_path)
    else:
        cfg = FleetConfig(runs=args.runs, interval_s=args.interval_s,
                          queue_path=queue_path)
        clock = None

    store = MetricStore(store_path)
    runner = BenchmarkRunner(runs=args.runs, coverage=True)
    service = FleetService(
        cfg, store=store, runner=runner, results_dir=args.results_dir,
        clock=clock,
        hooks_for_tick=_fast_hooks if args.fast else None,
        commits_for=_fast_commits_for(runner) if args.fast else None,
        backoff_s=0.5)
    summary = service.run(args.ticks)

    print(f"fleet: {summary['ticks']} ticks, {summary['restarts']} restarts, "
          f"{summary['open_findings']} open findings")
    for ev in summary["events"]:
        print(f"  event: {ev}")
    if service.last_report is not None:
        for line in format_table(service.last_report).splitlines():
            print(f"  {line}")
    print(f"status:  {summary['status_path']}")
    print(f"report:  {summary['report_path']}")
    print(f"metrics: {summary['prom_path']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
