"""Kernel autotuning: detector-driven Pallas launch-parameter search
through the unified runner.

The offline/online autotuner pattern (sweep candidate configs, persist
winners, serve them transparently on later traces) applied to the Pallas
kernels' launch parameters:

* ``tuning.space``  — per-kernel search spaces: valid, VMEM-bounded
  candidates derived from the input shape, encoded as scenario archs;
* ``tuning.sweep``  — case expansion into a ``task="kernel"``
  ``ScenarioMatrix`` run through ``BenchmarkRunner.run_matrix`` + winner
  selection into the DB;
* ``tuning.db``     — the schema-tagged JSON DB ``kernels/*/ops.py``
  consult at trace time when callers pass no explicit block sizes;
* ``tuning.bridge`` — profiler findings (``data_movement_bound`` /
  ``low_util``) -> enqueued tuning jobs, closing profile -> optimize.
"""
from repro.tuning.bridge import (TUNE_RULES, cases_for_record,
                                 cases_from_jobs, drain_queue, enqueue_jobs,
                                 jobs_from_findings, kernels_for_arch,
                                 load_queue)
from repro.tuning.db import TuningDB, tuned_params
from repro.tuning.space import (KernelCase, candidate_id, candidates,
                                default_params, make_case, parse_candidate,
                                parse_case, vmem_bytes)
from repro.tuning.sweep import run_sweep, sweep_matrix

__all__ = [
    "TUNE_RULES", "TuningDB", "KernelCase", "candidate_id", "candidates",
    "cases_for_record", "cases_from_jobs", "default_params", "drain_queue",
    "enqueue_jobs",
    "jobs_from_findings", "kernels_for_arch", "load_queue", "make_case",
    "parse_candidate", "parse_case", "run_sweep", "sweep_matrix",
    "tuned_params", "vmem_bytes",
]
