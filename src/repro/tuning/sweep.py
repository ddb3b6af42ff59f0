"""The sweep engine: (kernel, shape, dtype) cases -> an ordinary
``ScenarioMatrix`` of ``task="kernel"`` micro-bench cells -> DB winners.

Tuning is deliberately NOT a bespoke timing loop: each candidate becomes
one scenario (``arch`` = the candidate id, see ``tuning.space``) and the
whole sweep runs through ``BenchmarkRunner.run_matrix`` — so each
candidate's time is a normal ``RunResult`` in the ``ResultStore``, and
the measurement protocol (median-of-N, compile excluded) is exactly the
one every other table uses.

Winner selection: argmin of ``median_us`` over the case's OK cells, ties
resolved toward the default.  Because the ops default is always
candidate #0 of the search space, the recorded winner can never be
slower than the default it replaces — the tuned-vs-default ratio
(``default_us / winner_us``) is >= 1.0 by construction.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from repro.runner.results import RunResult
from repro.runner.scenario import ScenarioMatrix
from repro.tuning import space
from repro.tuning.db import TuningDB


def _sweep_backend() -> str:
    """Backend provenance stamped on every recorded winner: tile-size
    economics measured on cpu say nothing about tpu (and vice versa), so
    the consult path (``db.tuned_params``) drops mismatched entries."""
    try:
        import jax
        return str(jax.default_backend())
    except Exception:  # noqa: BLE001 — unstampable, entry serves anywhere
        return ""


def _case_cells(case: space.KernelCase,
                max_candidates: Optional[int] = None) -> List[Tuple[str, Dict[str, int]]]:
    """(candidate id, params) pairs for one case, default first."""
    return [(space.candidate_id(case, p), p)
            for p in space.candidates(case, max_candidates)]


def sweep_matrix(cases: Sequence[space.KernelCase], *,
                 max_candidates: Optional[int] = None) -> ScenarioMatrix:
    """Expand tuning cases into one ``ScenarioMatrix`` of kernel cells.

    The axes are unions across heterogeneous cases (candidate ids x
    batches x seqs x dtypes); exact-name ``filter`` regexes then keep
    precisely one cell per candidate — its own case's (batch, seq,
    dtype) — so the cartesian product never cross-multiplies cases.
    """
    archs: List[str] = []
    batches, seqs, dtypes = [], [], []
    filters: List[str] = []
    for case in cases:
        b, s = case.dim("B"), case.dim("S")
        for cid, _ in _case_cells(case, max_candidates):
            archs.append(cid)
            filters.append(f"^{re.escape(cid)}/kernel/b{b}/s{s}/{case.dtype}/jit$")
        for coll, v in ((batches, b), (seqs, s), (dtypes, case.dtype)):
            if v not in coll:
                coll.append(v)
    return ScenarioMatrix(archs=archs, tasks=("kernel",), batches=batches,
                          seqs=seqs, dtypes=dtypes, modes=("jit",),
                          filter=filters)


def run_sweep(cases: Sequence[space.KernelCase], runner, *,
              db: Optional[TuningDB] = None,
              max_candidates: Optional[int] = None,
              runs: Optional[int] = None,
              warmup: Optional[int] = None,
              save: bool = True) -> Dict:
    """Sweep every case through the runner and record winners in the DB.

    Returns a summary dict (one entry per case: winner params, winner /
    default medians, the tuned-vs-default ratio, and the per-candidate
    results) — what ``benchmarks/runner_bench.py`` persists under its
    ``"tuning"`` section.
    """
    cases = list(cases)
    if db is None:
        db = TuningDB.load()
    matrix = sweep_matrix(cases, max_candidates=max_candidates)
    results = runner.run_matrix(matrix, runs=runs, warmup=warmup)
    by_arch: Dict[str, RunResult] = {r.arch: r for r in results}
    summary: Dict = {"db_path": str(db.path), "cases": []}
    recorded = 0
    for case in cases:
        cells = _case_cells(case, max_candidates)
        default_id, default_params = cells[0]
        rows = []
        for cid, params in cells:
            r = by_arch.get(cid)
            rows.append({
                "candidate": cid, "params": params,
                "default": params == default_params,
                "status": r.status if r else "missing",
                "median_us": r.median_us if r and r.status == "ok" else None,
                "error": (r.error if r else "no result") or None,
            })
        ok = [row for row in rows if row["median_us"] is not None]
        entry = {"case": case.case_id, "kernel": case.kernel,
                 "signature": case.signature, "dtype": case.dtype,
                 "candidates": len(cells), "results": rows}
        if not ok:
            entry["status"] = "error"
            summary["cases"].append(entry)
            continue
        # argmin with ties toward the default: the DB never serves a
        # config that did not beat the default it replaces
        winner = min(ok, key=lambda row: (row["median_us"], not row["default"]))
        default_row = next((row for row in rows if row["default"]), None)
        default_us = default_row["median_us"] if default_row else None
        entry.update(status="ok", winner=winner["params"],
                     winner_us=winner["median_us"], default_us=default_us,
                     ratio=(default_us / winner["median_us"]
                            if default_us and winner["median_us"] else None))
        db.record(case.kernel, case.signature, case.dtype,
                  params=winner["params"], median_us=winner["median_us"],
                  default_params=default_params,
                  default_us=default_us or 0.0,
                  case=case.case_id, candidates=len(cells),
                  backend=_sweep_backend())
        recorded += 1
        summary["cases"].append(entry)
    if save and recorded:
        db.save()
    summary["recorded"] = recorded
    return summary
