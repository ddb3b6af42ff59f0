"""Unified benchmark execution subsystem: scenario matrix -> runner -> store.

Public surface:

    Scenario, ScenarioMatrix     declarative execution matrix
    BenchmarkRunner, RunnerStats in-process execution + build/executable reuse
    RunResult, ResultStore       versioned records, JSONL log + latest pointer
    TraceSpec, generate_trace    deterministic serving load profiles
    save_spec, load_spec         recorded traces (trace="file:PATH")
    percentile, latency_summary  shared latency-distribution helpers
"""
from repro.runner.latency import latency_summary, percentile
from repro.runner.results import SCHEMA_VERSION, ResultStore, RunResult
from repro.runner.runner import (BenchmarkRunner, RunnerStats,
                                 dryrun_cell_subprocess)
from repro.runner.scenario import (MODES, SERVE_MODES, STEP_TASKS, TASKS,
                                   Scenario, ScenarioMatrix)
from repro.runner.traces import (PROFILES, Request, TraceSpec, load_spec,
                                 save_spec)
from repro.runner.traces import generate as generate_trace

__all__ = ["Scenario", "ScenarioMatrix", "MODES", "SERVE_MODES", "TASKS",
           "STEP_TASKS", "BenchmarkRunner", "RunnerStats", "RunResult",
           "ResultStore", "SCHEMA_VERSION", "dryrun_cell_subprocess",
           "PROFILES", "Request", "TraceSpec", "generate_trace", "save_spec",
           "load_spec", "percentile", "latency_summary"]
