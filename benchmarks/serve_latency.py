"""Serving-latency table: continuous-batching workloads as first-class
scenario cells (``task="serve"``) through the unified runner.

Per (arch x slots x trace) cell we report the latency distribution a
production user compares — TTFT and per-token p50/p95/p99 plus tok/s —
computed by the serve engine (``repro.launch.serve``) over deterministic
load profiles (``repro.runner.traces``).  The sweep is one ``run_matrix``
call, and every cell lands in the shared ResultStore under the well-known serve
extra keys (``repro/runner/results.py``).

Rows land in ``results/serve_latency.json``.
"""
from __future__ import annotations

import json

from benchmarks.common import emit, make_runner, results_path
from repro.runner.scenario import ScenarioMatrix

ARCHS_FULL = ["gemma-2b", "mixtral-8x7b", "mamba2-2.7b"]
ARCHS_FAST = ["gemma-2b"]


def scenario_matrices(fast: bool = False):
    """The matrices this table executes (``benchmarks.run --list`` hook)."""
    archs = ARCHS_FAST if fast else ARCHS_FULL
    slots = (2,) if fast else (2, 4)
    traces = ("uniform", "bursty") if fast else ("uniform", "bursty", "mixed")
    requests, prompt = (6, 8) if fast else (16, 16)
    return [ScenarioMatrix(archs=archs, tasks=("serve",), batches=(requests,),
                           seqs=(prompt,), slots=slots, traces=traces)]


def main(fast: bool = False, runner=None) -> None:
    runner = runner or make_runner()
    [matrix] = scenario_matrices(fast)
    rows = []
    for rr in runner.run_matrix(matrix):
        if rr.status != "ok":
            emit(f"serve/{rr.name}", 0.0,
                 f"status={rr.status};error={(rr.error or '')[:60]}")
            continue
        ex = rr.extra
        emit(f"serve/{rr.name}", rr.median_us,
             f"tok_per_s={ex['tok_per_s']:.1f};ttft_p50={ex['ttft_p50']:.0f};"
             f"ttft_p99={ex['ttft_p99']:.0f};tok_lat_p99={ex['tok_lat_p99']:.0f};"
             f"qmax={ex['queue_depth_max']}")
        rows.append({"name": rr.name, "arch": rr.arch, "slots": ex["slots"],
                     "trace": ex["trace"], "requests": rr.runs,
                     "admission": ex["admission"],
                     "admit_calls": ex["admit_calls"],
                     "admit_batch_mean": ex["admit_batch_mean"],
                     "tok_per_s": ex["tok_per_s"],
                     "decode_steps": ex["decode_steps"],
                     "queue_depth_mean": ex["queue_depth_mean"],
                     "queue_depth_max": ex["queue_depth_max"],
                     "tokens_digest": ex["tokens_digest"],
                     **{k: ex[k] for k in ("ttft_p50", "ttft_p95", "ttft_p99",
                                           "tok_lat_p50", "tok_lat_p95",
                                           "tok_lat_p99") if k in ex}})
    with open(results_path("serve_latency.json"), "w") as f:
        json.dump({"fast": fast, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
