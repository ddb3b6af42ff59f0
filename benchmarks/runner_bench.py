"""Runner-level benchmarks: reuse vs the seed path, serve throughput,
profiling and metrics overhead, kernel autotuning, and admission policies.
Every cell runs in this process.

Part 1 — reuse (PR 1): a repeated-arch sweep in the shape regression CI
produces every night — all three tasks of one arch, then the train cell
re-measured three more times (baseline + injection probes).  The seed path
rebuilt the model and re-jitted for every measurement; the unified runner
shares one arch build across tasks and replays cached executables on
re-measures.

Part 2 — serve throughput: one small continuous-batching cell per sweep
arch (``task="serve"``, bursty trace) run through ``run_matrix``,
reporting tok/s per cell and the sweep wall.

Part 3 — profiling overhead: the same cell measured unprofiled then
profiled (``profile=True``) on a warm executable cache; the reported
ratio of median step times is the profiler's measurement tax (the
acceptance bound is <10% — the phase split is two extra perf_counter
reads per step, and the attribution compile happens outside the timed
loop), so overhead regressions show up in the perf trajectory.

Part 4 — kernel autotuning (``repro.tuning``): flash-attention + rglru
(+ ssd when not ``--fast``) launch-parameter sweeps run as
``task="kernel"`` cells, winners recorded in the tuning DB
(``results/tuning_db.json``), and the tuned-vs-default median ratio
reported per kernel.  The ratio is >= 1.0 by construction — the ops
default is always a swept candidate and the winner is the argmin (ties
to the default), so the DB never serves a config slower than the
default it replaces.  The detector bridge is then demonstrated end to
end: the three sweep archs are profiled, ``low_util`` is forced to fire
(``util_rel=1.0`` flags every below-median cell — deterministic with 3+
distinct cells), and the resulting findings enqueue tuning jobs into
``results/tuning_queue.json``.

Part 5 — admission policies: the same queue-forming trace (bursty
bimodal arrivals, offered load compressed so admission waves actually
form) replayed under ``admission="batched"`` (one jitted prefill per
wave, bucketed padded shapes) and ``admission="single"`` (the
one-prefill-per-request baseline).  Reported: the TTFT p99 ratio, the
jitted prefill-call counts, and the token-digest equality gate — batched
admission must be a pure scheduling change, byte-identical tokens.

Part 6 — metrics-registry overhead: the same warm cell measured with
the fleet metrics registry (``repro.fleet.metrics``) enabled — the
default; every execution feeds it — and disabled.  The registry's
``record_result`` is a handful of dict increments under one lock,
entirely outside the timed measurement loop, so both the median-step
ratio and the end-to-end ``run()`` wall ratio must be ~1.0x — the
"near-zero cost when unexported" acceptance bound, kept in the perf
trajectory like the profiler tax of part 3.

Numbers land in ``results/runner_bench.json``."""
from __future__ import annotations

import gc
import json
import time

from benchmarks.common import emit, results_path
from repro.core.harness import measure
from repro.core.suite import get_benchmark
from repro.runner import BenchmarkRunner, Scenario, ScenarioMatrix
from repro.profiler import Thresholds, detect
from repro.tuning import (TuningDB, enqueue_jobs, jobs_from_findings,
                          make_case, run_sweep, sweep_matrix)

ARCH = "gemma-2b"
BATCH, SEQ = 2, 32

SWEEP_ARCHS = ["gemma-2b", "mamba2-2.7b", "recurrentgemma-9b", "mixtral-8x7b"]


def _workload(fast: bool):
    tasks = ("train", "infer_decode") if fast else ("train", "infer_prefill", "infer_decode")
    sweep = [Scenario(arch=ARCH, task=t, batch=BATCH, seq=SEQ) for t in tasks]
    probes = [Scenario(arch=ARCH, task="train", batch=BATCH, seq=SEQ)] * (2 if fast else 3)
    return sweep + probes


def seed_path(scenarios, runs: int) -> float:
    """The pre-runner protocol: fresh build + fresh jit per measurement."""
    t0 = time.perf_counter()
    for sc in scenarios:
        bench = get_benchmark(sc.arch, sc.task)
        step, args, donate = bench.make(batch=sc.batch, seq=sc.seq)
        measure(bench.name, step, args, donate, runs=runs)
    return time.perf_counter() - t0


def runner_path(scenarios, runs: int) -> tuple:
    runner = BenchmarkRunner(runs=runs)
    t0 = time.perf_counter()
    for sc in scenarios:
        rr = runner.run(sc, record=False)
        if rr.status != "ok":
            raise RuntimeError(f"{sc.name}: {rr.error}")
    return time.perf_counter() - t0, runner.stats


def _serve_matrix(fast: bool) -> ScenarioMatrix:
    """A small serving cell per sweep arch: the serve-throughput row."""
    archs = SWEEP_ARCHS[:1] if fast else SWEEP_ARCHS[:2]
    return ScenarioMatrix(archs=archs, tasks=("serve",),
                          batches=(6,), seqs=(SEQ // 2,), slots=(2,),
                          traces=("bursty",))


def scenario_matrices(fast: bool = False):
    """The matrices this benchmark executes (``benchmarks.run --list`` hook)."""
    return [_serve_matrix(fast), _tuning_matrix(fast)]


# ---- part 4: kernel autotuning --------------------------------------------

def _tuning_cases(fast: bool):
    """Small tuning cases sized like the probe cells above: one per Pallas
    kernel (ssd only on the full run — its interpret-mode chunks are the
    slowest cells of the sweep)."""
    cases = [make_case("flash_attention", B=2, S=64, H=2, K=2, D=32),
             make_case("rglru", B=1, S=64, D=64)]
    if not fast:
        cases.append(make_case("ssd", B=1, S=64, H=2, P=16, N=16))
    return cases


def _tuning_candidates(fast: bool) -> int:
    return 3 if fast else 6


def _tuning_matrix(fast: bool) -> ScenarioMatrix:
    return sweep_matrix(_tuning_cases(fast),
                        max_candidates=_tuning_candidates(fast))


def main(fast: bool = False, runner=None) -> None:
    runs = 2 if fast else 3
    scenarios = _workload(fast)
    seed_s = seed_path(scenarios, runs)
    runner_s, stats = runner_path(scenarios, runs)
    speedup = seed_s / runner_s if runner_s else 0.0
    emit("runner_bench/seed_path_s", seed_s * 1e6, f"{len(scenarios)}_measurements")
    emit("runner_bench/runner_path_s", runner_s * 1e6,
         f"model_builds={stats.model_builds};exec_cache_hits={stats.executable_cache_hits}")
    emit("runner_bench/reuse_speedup", 0.0, f"{speedup:.2f}x")

    # serve-throughput row: continuous-batching cells through run_matrix
    serve_matrix = _serve_matrix(fast)
    serve_runner = BenchmarkRunner()
    t0 = time.perf_counter()
    serve_results = serve_runner.run_matrix(serve_matrix)
    serve_wall = time.perf_counter() - t0
    del serve_runner
    gc.collect()
    serve_rows = []
    for rr in serve_results:
        if rr.status != "ok":
            raise RuntimeError(f"{rr.name}: {rr.error}")
        serve_rows.append({"name": rr.name,
                           "tok_per_s": rr.extra["tok_per_s"],
                           "ttft_p50_us": rr.extra.get("ttft_p50"),
                           "tok_lat_p99_us": rr.extra.get("tok_lat_p99")})
        emit(f"runner_bench/serve_tok_per_s/{rr.arch}", 0.0,
             f"{rr.extra['tok_per_s']:.1f}tok_s;trace={rr.extra['trace']};"
             f"slots={rr.extra['slots']}")
    emit("runner_bench/serve_sweep_s", serve_wall * 1e6,
         f"{len(serve_rows)}_serve_cells")

    # profiling overhead: unprofiled vs profiled median step time on a
    # warm executable (fresh compile settled by the first run)
    prof_runner = BenchmarkRunner(runs=max(3, runs))
    sc = Scenario(arch=ARCH, task="train", batch=BATCH, seq=SEQ)
    prof_runner.run(sc, record=False)                        # compile + settle
    base_rr = prof_runner.run(sc, record=False)
    prof_rr = prof_runner.run(sc, record=False, profile=True)
    overhead = (prof_rr.median_us / base_rr.median_us
                if base_rr.median_us else 0.0)
    emit("runner_bench/profile_overhead", 0.0,
         f"{overhead:.3f}x;profiled={prof_rr.median_us:.0f}us;"
         f"base={base_rr.median_us:.0f}us")
    del prof_runner
    gc.collect()

    # metrics-registry overhead: the same warm-cell protocol as the
    # profiler tax above, enabled vs disabled registry; run() wall is
    # timed too because record_result lands outside the measured loop
    from repro.fleet.metrics import set_enabled
    met_runner = BenchmarkRunner(runs=max(3, runs))
    met_runner.run(sc, record=False)                     # compile + settle
    t0 = time.perf_counter()
    on_rr = met_runner.run(sc, record=False)
    on_wall = time.perf_counter() - t0
    prev_enabled = set_enabled(False)
    try:
        t0 = time.perf_counter()
        off_rr = met_runner.run(sc, record=False)
        off_wall = time.perf_counter() - t0
    finally:
        set_enabled(prev_enabled)
    metrics_ratio = (on_rr.median_us / off_rr.median_us
                     if off_rr.median_us else 0.0)
    metrics_wall_ratio = on_wall / off_wall if off_wall else 0.0
    emit("runner_bench/metrics_overhead", 0.0,
         f"{metrics_ratio:.3f}x;wall={metrics_wall_ratio:.3f}x;"
         f"enabled={on_rr.median_us:.0f}us;disabled={off_rr.median_us:.0f}us")
    del met_runner
    gc.collect()

    # kernel autotuning: per-kernel candidate sweeps, winners recorded in
    # the tuning DB, tuned-vs-default ratio per kernel
    cases = _tuning_cases(fast)
    tuning_db = TuningDB.load(results_path("tuning_db.json"))
    tune_runner = BenchmarkRunner(runs=max(3, runs))
    t0 = time.perf_counter()
    tuning = run_sweep(cases, tune_runner, db=tuning_db,
                       max_candidates=_tuning_candidates(fast))
    tuning_wall = time.perf_counter() - t0
    del tune_runner
    gc.collect()
    for row in tuning["cases"]:
        if row["status"] != "ok":
            raise RuntimeError(f"tuning sweep failed for {row['case']}")
        winner = " ".join(f"{k}={v}" for k, v in row["winner"].items())
        emit(f"runner_bench/tuning_ratio/{row['kernel']}", 0.0,
             f"{row['ratio']:.2f}x;winner={winner};"
             f"default_us={row['default_us']:.0f}")
    emit("runner_bench/tuning_sweep_s", tuning_wall * 1e6,
         f"{sum(r['candidates'] for r in tuning['cases'])}"
         f"_candidates;db={tuning['db_path']}")

    # detector bridge: profile the three kernel-bearing archs, force
    # low_util to fire (util_rel=1.0 flags every below-median cell —
    # deterministic once 3+ cells have distinct utilizations), and turn
    # the findings into enqueued tuning jobs
    bridge_runner = BenchmarkRunner(runs=max(3, runs))
    bridge_recs = [bridge_runner.run(Scenario(arch=a, task="train", batch=1,
                                              seq=16, mode="jit"),
                                     record=False, profile=True)
                   for a in ("gemma-2b", "mamba2-2.7b", "recurrentgemma-9b")]
    del bridge_runner
    gc.collect()
    bridge_findings = detect(bridge_recs, Thresholds(util_rel=1.0))
    tuning_jobs = jobs_from_findings(bridge_findings, bridge_recs,
                                     db=tuning_db)
    queue_path = results_path("tuning_queue.json")
    enqueue_jobs(tuning_jobs, queue_path)
    emit("runner_bench/tuning_jobs", 0.0,
         f"n={len(tuning_jobs)};findings={len(bridge_findings)};"
         f"queue={queue_path}")

    # admission policies: batched wave prefill vs per-request baseline on
    # the same queue-forming trace (loadgen at a compressed offered load —
    # native bursty arrivals rarely queue >1 request against free slots)
    adm_runner = BenchmarkRunner()
    adm_cells = {}
    try:
        for adm in ("batched", "single"):
            sc = Scenario(arch=ARCH, task="loadgen", batch=8, seq=16,
                          slots=4, trace="bursty+bimodal", load=4.0,
                          admission=adm)
            rr = adm_runner.run(sc, record=False)
            if rr.status != "ok":
                raise RuntimeError(f"{sc.name}: {rr.error}")
            ex = rr.extra
            adm_cells[adm] = {"name": rr.name,
                              "ttft_p99_us": ex["ttft_p99"],
                              "tok_per_s": ex["tok_per_s"],
                              "prefill_calls": ex["admit_calls"],
                              "admit_batch_mean": ex["admit_batch_mean"],
                              "admit_batch_max": ex["admit_batch_max"],
                              "admit_shapes": ex["admit_shapes"],
                              "tokens_digest": ex["tokens_digest"]}
    finally:
        del adm_runner
        gc.collect()
    adm_digest_ok = (adm_cells["batched"]["tokens_digest"]
                     == adm_cells["single"]["tokens_digest"])
    adm_ttft_ratio = (adm_cells["batched"]["ttft_p99_us"]
                      / adm_cells["single"]["ttft_p99_us"]
                      if adm_cells["single"]["ttft_p99_us"] else 0.0)
    emit("runner_bench/admission_ttft_p99_ratio", 0.0,
         f"{adm_ttft_ratio:.2f}x;digests_match={adm_digest_ok};"
         f"prefill_calls={adm_cells['batched']['prefill_calls']}"
         f"vs{adm_cells['single']['prefill_calls']};"
         f"batch_max={adm_cells['batched']['admit_batch_max']}")

    with open(results_path("runner_bench.json"), "w") as f:
        json.dump({"scenarios": [s.name for s in scenarios], "runs": runs,
                   "seed_path_s": seed_s, "runner_path_s": runner_s,
                   "speedup": speedup, "runner_stats": stats.to_dict(),
                   "serve": {"wall_s": serve_wall, "cells": serve_rows},
                   "profile": {"cell": sc.name,
                               "base_median_us": base_rr.median_us,
                               "profiled_median_us": prof_rr.median_us,
                               "overhead_ratio": overhead},
                   "metrics": {"cell": sc.name,
                               "enabled_median_us": on_rr.median_us,
                               "disabled_median_us": off_rr.median_us,
                               "overhead_ratio": metrics_ratio,
                               "wall_ratio": metrics_wall_ratio},
                   "admission": {"cells": adm_cells,
                                 "digests_match": adm_digest_ok,
                                 "ttft_p99_ratio": adm_ttft_ratio},
                   "tuning": {"wall_s": tuning_wall,
                              "db_path": tuning["db_path"],
                              "cases": tuning["cases"],
                              "recorded": tuning["recorded"],
                              "bridge": {
                                  "profiled": [rr.name for rr in bridge_recs],
                                  "findings": [
                                      {"rule": fi.rule, "cell": fi.cell,
                                       "severity": fi.severity}
                                      for fi in bridge_findings],
                                  "enqueued": tuning_jobs,
                                  "queue_path": str(queue_path)}}},
                  f, indent=1)


if __name__ == "__main__":
    main()
