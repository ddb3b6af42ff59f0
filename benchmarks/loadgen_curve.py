"""Latency-vs-offered-load curves: ``task="loadgen"`` cells through the
unified runner, swept over the ``loads`` axis for BOTH admission
policies side by side, post-processed into per-policy saturation knees.

Each cell replays the same mixed-prompt-length trace against the serve
engine with its virtual arrival clock scaled by the offered load; TTFT
and per-token p99 climb as the queue saturates while tok/s flattens —
``repro.runner.loadgen.find_knee`` marks the last load that still bought
throughput.  The ``admissions`` axis runs every load twice: ``batched``
(one jitted prefill per admission wave, bucketed padded shapes) against
``single`` (the one-prefill-per-request baseline).  Batched admission
only has something to batch once the queue forms — the high-load half of
the sweep, which is exactly where the knee lives — so the comparison
reads as "how much saturation headroom does wave prefill buy".  The two
policies must also agree token-for-token: the digest check below is the
numerical-equivalence gate, run on every swept load.

Rows + per-policy knees land in ``results/loadgen_curve.json`` under the
schema consumed by ``repro.runner.loadgen.auto_slots`` (the knee-driven
``slots="auto"`` resolver), and a summary record carrying ``knee_load``
/ ``knee_tok_s`` (batched curve — the production policy) in its
``extra`` is appended to the shared ResultStore so CI baselines can
track the knee like any other scalar.

    PYTHONPATH=src python -m benchmarks.loadgen_curve [--fast]
"""
from __future__ import annotations

import json
import time

from benchmarks.common import emit, make_runner, results_path
from repro.runner.loadgen import CURVE_SCHEMA, DEFAULT_SLOTS, find_knee
from repro.runner.results import RunResult
from repro.runner.scenario import ScenarioMatrix

ARCH = "gemma-2b"
TRACE = "bursty+bimodal"
LOADS_FULL = (0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
LOADS_FAST = (0.5, 1.0, 2.0, 4.0)


def scenario_matrices(fast: bool = False):
    """The matrices this table executes (``benchmarks.run --list`` hook)."""
    requests, prompt = (8, 8) if fast else (16, 16)
    return [ScenarioMatrix(archs=[ARCH], tasks=("loadgen",),
                           batches=(requests,), seqs=(prompt,),
                           slots=(DEFAULT_SLOTS,), traces=(TRACE,),
                           loads=LOADS_FAST if fast else LOADS_FULL,
                           admissions=("batched", "single"))]


def _row(rr) -> dict:
    ex = rr.extra
    return {"name": rr.name, "arch": rr.arch, "slots": ex["slots"],
            "trace": ex["trace"], "load": ex["offered_load"],
            "split": ex.get("split", ""), "requests": rr.runs,
            "admission": ex["admission"],
            "admit_calls": ex["admit_calls"],
            "admit_batch_mean": ex["admit_batch_mean"],
            "admit_batch_max": ex["admit_batch_max"],
            "tok_per_s": ex["tok_per_s"],
            "decode_steps": ex["decode_steps"],
            "queue_depth_mean": ex["queue_depth_mean"],
            "queue_depth_max": ex["queue_depth_max"],
            "prompt_len_p50": ex.get("prompt_len_p50"),
            "prompt_len_p95": ex.get("prompt_len_p95"),
            "tokens_digest": ex["tokens_digest"],
            **{k: ex[k] for k in ("ttft_p50", "ttft_p95", "ttft_p99",
                                  "tok_lat_p50", "tok_lat_p95",
                                  "tok_lat_p99") if k in ex}}


def _at_load(rows, load):
    return next(r for r in rows if r["load"] == load)


def main(fast: bool = False, runner=None) -> None:
    runner = runner or make_runner()
    [matrix] = scenario_matrices(fast)
    by_adm = {"batched": [], "single": []}
    for rr in runner.run_matrix(matrix):
        if rr.status != "ok":
            emit(f"loadgen/{rr.name}", 0.0,
                 f"status={rr.status};error={(rr.error or '')[:60]}")
            continue
        ex = rr.extra
        emit(f"loadgen/{rr.name}", rr.median_us,
             f"load={ex['offered_load']:g};tok_per_s={ex['tok_per_s']:.1f};"
             f"ttft_p99={ex['ttft_p99']:.0f};tok_lat_p99={ex['tok_lat_p99']:.0f};"
             f"qmax={ex['queue_depth_max']};admit_calls={ex['admit_calls']};"
             f"admit_batch_max={ex['admit_batch_max']}")
        by_adm[ex["admission"]].append(_row(rr))

    # numerical-equivalence gate: batched admission must generate the
    # byte-identical token streams of the per-request baseline, per load
    digests_match = bool(by_adm["batched"]) and all(
        b["tokens_digest"] == _at_load(by_adm["single"], b["load"])["tokens_digest"]
        for b in by_adm["batched"])

    curves = {}
    for adm, rows in by_adm.items():
        knee = find_knee(rows)
        at_knee = _at_load(rows, knee["knee_load"]) if rows else {}
        curves[adm] = {"knee": knee,
                       "ttft_p99_at_knee": at_knee.get("ttft_p99", 0.0),
                       "admit_calls_total": sum(r["admit_calls"] for r in rows)}
        emit(f"loadgen/knee/{adm}", knee["knee_tok_s"],
             f"knee_load={knee['knee_load']:g};"
             f"ttft_p99_at_knee={at_knee.get('ttft_p99', 0.0):.0f}")

    bk, sk = curves["batched"]["knee"], curves["single"]["knee"]
    ttft_ratio = (curves["batched"]["ttft_p99_at_knee"]
                  / curves["single"]["ttft_p99_at_knee"]
                  if curves["single"]["ttft_p99_at_knee"] else 0.0)
    comparison = {
        "digests_match": digests_match,
        "knee_load_batched": bk["knee_load"], "knee_load_single": sk["knee_load"],
        "knee_tok_s_ratio": (bk["knee_tok_s"] / sk["knee_tok_s"]
                             if sk["knee_tok_s"] else 0.0),
        "ttft_p99_ratio_at_knee": ttft_ratio,
        "prefill_calls_batched": curves["batched"]["admit_calls_total"],
        "prefill_calls_single": curves["single"]["admit_calls_total"],
    }
    emit("loadgen/admission_comparison", 0.0,
         f"digests_match={digests_match};"
         f"knee={bk['knee_load']:g}vs{sk['knee_load']:g};"
         f"tok_s_ratio={comparison['knee_tok_s_ratio']:.2f}x;"
         f"ttft_p99_ratio={ttft_ratio:.2f}x;"
         f"prefill_calls={comparison['prefill_calls_batched']}"
         f"vs{comparison['prefill_calls_single']}")

    if runner.store is not None and by_adm["batched"]:
        # the batched curve's summary as an ordinary record: knee metrics
        # under extra, latest-wins like any emitted scalar (results.py docs)
        rows = by_adm["batched"]
        runner.store.append(RunResult(
            name=f"{ARCH}/loadgen_curve", bench=f"{ARCH}/loadgen",
            arch=ARCH, task="loadgen", batch=rows[0]["requests"],
            seq=0, dtype="fp32", mode="jit_donated", status="ok",
            median_us=0.0, mean_us=0.0, p10_us=0.0, p90_us=0.0,
            compile_us=0.0, runs=len(rows), wall_s=0.0, ts=time.time(),
            extra={"knee_load": bk["knee_load"],
                   "knee_tok_s": bk["knee_tok_s"],
                   "admission": "batched",
                   "loads": [r["load"] for r in rows],
                   "curve_tok_per_s": [r["tok_per_s"] for r in rows],
                   "comparison": comparison}))
    with open(results_path("loadgen_curve.json"), "w") as f:
        json.dump({"schema": CURVE_SCHEMA, "arch": ARCH,
                   "slots": DEFAULT_SLOTS, "fast": fast,
                   "rows": by_adm["batched"] + by_adm["single"],
                   "curves": curves, "comparison": comparison}, f, indent=1)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    main(fast=args.fast, runner=make_runner())
