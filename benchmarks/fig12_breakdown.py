"""Paper Figs. 1-2 + Table 2: execution-time breakdown per benchmark and per
domain.  Two provenances, labeled per row (``source=measured|analytic``):

* **measured** (preferred): a profiled runner sweep over the measured
  suite cells — real phase timelines + op-class attribution from
  ``src/repro/profiler/`` (the paper's profiler-driven Figs. 1-2, on the
  hardware we actually have);
* **analytic** (production-shape fallback): the dry-run roofline terms
  for the full-config cells this container can only compile, not run.
  Fallback cells compile through the runner's cached dry-run path, so
  cells shared with fig5/roofline cost one subprocess total.

Everything executes through the shared ``BenchmarkRunner`` — the measured
sweep honors the session filters like every other table, and the dry-run
path reuses the store-level cell cache."""
from __future__ import annotations

import json

from benchmarks.common import emit, load_dryrun, make_runner, results_path
from repro.core.breakdown import (breakdown_rows, domain_table,
                                  measured_breakdown_rows)
from repro.runner import ScenarioMatrix

FALLBACK_CELLS = [("gemma-2b", "train_4k"), ("mamba2-2.7b", "train_4k"),
                  ("gemma-2b", "decode_32k")]

MEASURED_ARCHS = ["gemma-2b", "mamba2-2.7b"]


def _measured_matrix(fast: bool = False) -> ScenarioMatrix:
    return ScenarioMatrix(archs=MEASURED_ARCHS[: 1 if fast else 2],
                          tasks=("train", "infer_decode"),
                          batches=(2,), seqs=(32,))


def scenario_matrices(fast: bool = False):
    """The matrices this table executes (``benchmarks.run --list`` hook)."""
    return [_measured_matrix(fast)]


def _emit_rows(rows, prefix: str) -> None:
    for r in rows:
        overhead = ""
        if r["source"] == "measured":
            overhead = (f";dispatch={r['dispatch_frac']:.2f}"
                        f";idle={r['idle_frac']:.2f}")
        emit(f"{prefix}/{r['arch']}/{r['shape']}", 0.0,
             f"source={r['source']};compute={r['compute_frac']:.2f};"
             f"memory={r['memory_frac']:.2f};"
             f"collective={r['collective_frac']:.2f};"
             f"dominant={r['dominant']}{overhead}")


def main(fast: bool = False, runner=None) -> None:
    runner = runner or make_runner()
    # measured rows first: a profiled sweep through the shared runner
    measured = runner.run_matrix(_measured_matrix(fast), profile=True)
    rows = measured_breakdown_rows(measured)
    # analytic fallback for the production shapes we can only compile
    results = load_dryrun()
    if results is None:
        results = runner.dryrun_cells(FALLBACK_CELLS[: 2 if fast else 3])
    rows += breakdown_rows(results)
    _emit_rows(rows, "fig12")
    for kind, flt in [("train", lambda r: r["shape"].startswith("train")),
                      ("inference", lambda r: not r["shape"].startswith("train"))]:
        for src in ("measured", "analytic"):
            sel = [r for r in rows if r["source"] == src]
            for d in domain_table(sel, flt):
                emit(f"table2/{kind}/{d['domain']}", 0.0,
                     f"source={src};n={d['n']};compute={d['compute_frac']:.2f};"
                     f"memory={d['memory_frac']:.2f};"
                     f"collective={d['collective_frac']:.2f}")
    with open(results_path("fig12_breakdown.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
