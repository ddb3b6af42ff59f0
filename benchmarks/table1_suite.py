"""Paper Table 1 + §2.3: suite overview and API-surface coverage.

Reports, per benchmark: domain, task, criteria, measured step time on the
reduced config, and the primitive/StableHLO surface; plus the suite-level
coverage multiple vs the single-dense-LM baseline (the paper's "2.3x
MLPerf" claim, reproduced quantitatively).

Measurement goes through the shared ``BenchmarkRunner``: the coverage
tracer and the timing pass reuse one arch build each, every row lands in
the persistent ResultStore, and the timing sweep is one ``run_matrix``
call."""
from __future__ import annotations

import json

from benchmarks.common import emit, make_runner, results_path
from repro.configs import ARCHS
from repro.core.coverage import coverage_report
from repro.core.suite import get_benchmark
from repro.runner.scenario import ScenarioMatrix


def scenario_matrices(fast: bool = False):
    """The matrices this table executes (``benchmarks.run --list`` hook)."""
    tasks = ("train", "infer_decode") if fast else ("train", "infer_prefill", "infer_decode")
    return [ScenarioMatrix(archs=sorted(ARCHS), tasks=tasks, batches=(2,), seqs=(32,))]


def main(fast: bool = False, runner=None) -> None:
    runner = runner or make_runner()
    [matrix] = scenario_matrices(fast)
    scenarios = runner.select(matrix)
    benches = [get_benchmark(s.arch, s.task) for s in scenarios]
    rep = coverage_report(benches, batch=1, seq=16, runner=runner)
    rows = []
    for b, rr in zip(benches, runner.run_matrix(matrix, runs=3)):
        if rr.status != "ok":
            emit(f"table1/{b.name}", 0.0, f"status={rr.status};error={(rr.error or '')[:60]}")
            continue
        surf = rep["per_benchmark"][b.name]
        emit(f"table1/{b.name}", rr.median_us,
             f"domain={b.domain};criteria={b.criteria};prims={surf['n_primitives']};hlo_ops={surf['n_stablehlo_ops']}")
        rows.append({"benchmark": b.name, "domain": b.domain, "criteria": b.criteria,
                     "median_us": rr.median_us, **{k: surf[k] for k in ("n_primitives", "n_stablehlo_ops")}})
    emit("table1/coverage_x_primitives", 0.0, f"{rep['coverage_x_primitives']:.2f}x_vs_single_dense_LM")
    emit("table1/coverage_x_stablehlo", 0.0, f"{rep['coverage_x_stablehlo']:.2f}x_vs_single_dense_LM")
    with open(results_path("table1_suite.json"), "w") as f:
        json.dump({"rows": rows, "coverage": {k: rep[k] for k in rep if k != "per_benchmark"}}, f, indent=1)


if __name__ == "__main__":
    main()
