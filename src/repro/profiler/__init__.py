"""Measured profiling subsystem: where did a benchmark cell's time go?

Four layers, all flowing through the unified BenchmarkRunner
(``runner.run(..., profile=True)`` / ``benchmarks.run --profile``):

    timeline     per-step phase capture — host dispatch vs device
                 execution via block_until_ready deltas; per-decode-step
                 timelines for serve cells; device memory stats when the
                 backend exposes them
    attribution  merge the measured timeline with trip-count-aware HLO
                 op-class costs (``core.hloanalysis``) into measured
                 matmul/attention/collective/elementwise/other shares and
                 compute/memory/collective/dispatch/idle fractions that
                 sum to 1.0
    detectors    rule-based inefficiency findings (the paper's
                 optimization-catalog spirit): data-movement-bound,
                 low relative utilization, compile outliers, serve queue
                 saturation, dispatch-bound
    report       ranked findings with severity + evidence, JSON + table

The profile lands under the well-known ``extra["prof_*"]`` keys
documented in ``repro/runner/results.py`` (schema stays v1) — so every
downstream surface (``fig12_breakdown``, ``profile_report``, regression
CI) reads profiles from the same ResultStore records as timings.
"""
from repro.profiler.attribution import (Attribution, attribute, class_times,
                                        cost_for_executable)
from repro.profiler.detectors import Finding, Thresholds, detect
from repro.profiler.report import build_report, format_table
from repro.profiler.timeline import (TIMELINE_CAP, PhaseSample, Timeline,
                                     device_memory_stats)

__all__ = ["Timeline", "PhaseSample", "TIMELINE_CAP", "device_memory_stats",
           "Attribution", "attribute", "class_times", "cost_for_executable",
           "Finding", "Thresholds", "detect",
           "build_report", "format_table"]
