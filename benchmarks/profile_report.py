"""Profile-driven inefficiency findings over the measured suite (the
paper's use case 1: profile the benchmarks, find the optimization
targets).

Sweeps a step matrix + a serve cell through the shared BenchmarkRunner
with ``profile=True``, runs
the rule-based detectors (``repro.profiler.detectors``) over the profiled
RunResults, and emits a ranked findings report — CSV rows per finding,
a human table on stderr-safe comment lines, and the full JSON (records'
prof summaries + findings + tallies) in ``results/profile_report.json``.

    PYTHONPATH=src python -m benchmarks.profile_report [--fast]

``--drain-queue`` closes the detect -> tune loop from the other side:
instead of profiling, it turns the queued jobs a previous report wrote
to ``results/tuning_queue.json`` into an actual launch-parameter sweep
(``repro.tuning.run_sweep``) and empties the queue — winners land in the
tuning DB, where the ops layer serves them on the next trace.
"""
from __future__ import annotations

import json

from benchmarks.common import emit, make_runner, results_path
from repro.profiler import build_report, detect, format_table
from repro.runner import ScenarioMatrix
from repro.tuning import enqueue_jobs, jobs_from_findings
from repro.tuning import drain_queue as tuning_drain_queue

STEP_ARCHS = ["gemma-2b", "mamba2-2.7b", "recurrentgemma-9b", "mixtral-8x7b"]


def _step_matrix(fast: bool = False) -> ScenarioMatrix:
    return ScenarioMatrix(archs=STEP_ARCHS[: 2 if fast else 4],
                          tasks=("train", "infer_decode"),
                          batches=(2,), seqs=(32,))


def _serve_matrix(fast: bool = False) -> ScenarioMatrix:
    # a bursty trace over few slots: the queue-saturation detector's beat
    return ScenarioMatrix(archs=["gemma-2b"], tasks=("serve",),
                          batches=(4 if fast else 8,), seqs=(8,),
                          slots=(2,), traces=("bursty",))


def scenario_matrices(fast: bool = False):
    """The matrices this report executes (``benchmarks.run --list`` hook)."""
    return [_step_matrix(fast), _serve_matrix(fast)]


def _prof_summary(rec: dict) -> dict:
    """A record's profile, minus the bulky timeline (JSON report diet)."""
    extra = rec.get("extra") or {}
    keep = {k: v for k, v in extra.items()
            if k.startswith("prof_") and k != "prof_timeline"}
    return {"name": rec["name"], "status": rec["status"],
            "median_us": rec.get("median_us"),
            "compile_us": rec.get("compile_us"), **keep}


def drain_queue(runner=None, queue_path=None) -> dict:
    """Sweep every queued tuning job and empty the queue.

    Thin formatter over ``repro.tuning.drain_queue`` (the core is in the
    tuning layer so the fleet scheduler drains the same queue on its own
    cadence): emits the CSV rows and human comments this script's
    contract promises."""
    queue_path = queue_path or results_path("tuning_queue.json")
    out = tuning_drain_queue(runner or make_runner(), queue_path=queue_path)
    emit("profile_report/drain_queue", 0.0,
         f"jobs={out['jobs']};cases={out['cases']};queue={queue_path}")
    if not out["cases"]:
        print(f"# tuning queue empty ({queue_path}); nothing to drain")
        return {"jobs": out["jobs"], "cases": 0}
    for c in out["case_rows"]:
        ratio = c.get("ratio")
        note = f"status={c['status']}"
        if ratio:
            note += f";ratio={ratio:.3f}"
        emit(f"profile_report/drained/{c['case']}",
             c.get("winner_us") or 0.0, note)
    print(f"# drained {out['cases']} tuning jobs -> {out['db_path']} "
          f"({out['recorded']} winners recorded)")
    return {"jobs": out["jobs"], "cases": out["cases"],
            "recorded": out["recorded"], "db": out["db_path"]}


def main(fast: bool = False, runner=None) -> None:
    runner = runner or make_runner()
    results = runner.run_matrix(_step_matrix(fast), profile=True)
    results += runner.run_matrix(_serve_matrix(fast), profile=True)
    recs = [rr.to_dict() for rr in results]
    findings = detect(recs)
    report = build_report(recs, findings,
                          meta={"fast": fast,
                                "cells": [r["name"] for r in recs]})
    for f in report["findings"]:
        emit(f"profile_report/{f['rule']}/{f['cell']}", 0.0,
             f"severity={f['severity']};score={f['score']:.2f}")
    emit("profile_report/findings", 0.0,
         f"n={len(report['findings'])};"
         f"crit={report['by_severity'].get('crit', 0)};"
         f"warn={report['by_severity'].get('warn', 0)};"
         f"info={report['by_severity'].get('info', 0)};"
         f"profiled={report['cells_profiled']}/{report['cells']}")
    # detector -> autotuner bridge: data_movement_bound / low_util findings
    # become tuning jobs for the Pallas kernels their arch uses, enqueued
    # for the next sweep (repro.tuning.run_sweep over cases_from_jobs)
    jobs = jobs_from_findings(findings, recs)
    queue_path = results_path("tuning_queue.json")
    if jobs:
        enqueue_jobs(jobs, queue_path)
    emit("profile_report/tuning_jobs", 0.0,
         f"n={len(jobs)};queue={queue_path}")
    report["tuning_jobs"] = jobs
    report["tuning_queue"] = str(queue_path)
    report["profiles"] = [_prof_summary(r) for r in recs]
    with open(results_path("profile_report.json"), "w") as f:
        json.dump(report, f, indent=1)
    for line in format_table(report).splitlines():
        print(f"# {line}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--drain-queue", action="store_true",
                    help="sweep results/tuning_queue.json jobs instead of "
                         "profiling, then empty the queue")
    args = ap.parse_args()
    r = make_runner()
    if args.drain_queue:
        drain_queue(runner=r)
    else:
        main(fast=args.fast, runner=r)
