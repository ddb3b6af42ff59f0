"""The perf-CI fleet service: live metrics, scheduled sweeps, triage.

``repro.fleet`` turns the one-shot nightly pieces (``core/ci``,
``telemetry/history``, ``benchmarks/profile_report --drain-queue``) into
a long-running, supervised service:

* :mod:`repro.fleet.metrics` — the process-wide metrics registry
  (counters / gauges / histograms) instrumenting the runner and the
  serve engine; JSON + Prometheus export;
* :mod:`repro.fleet.scheduler` — the tick-driven sweep loop (virtual
  clock injectable) appending provenance-stamped history points and
  running the drift pass + tuning-queue drain on a stride;
* :mod:`repro.fleet.triage` — drift findings graduate to confirmed
  regressions via automatic re-measure, then commit bisection;
* :mod:`repro.fleet.service` — the ``runtime/supervisor``-wrapped loop
  behind ``scripts/fleet.py``, with the heartbeat status file.

Only the metrics module is imported eagerly — it is stdlib-only, so the
runner and serve layers can ``import repro.fleet.metrics`` without
dragging the scheduler's runner dependency into a cycle; everything else
resolves lazily through ``__getattr__``.
"""
from repro.fleet.metrics import (METRICS_SCHEMA_KEY, METRICS_SCHEMA_VERSION,
                                 MetricsRegistry, registry, set_enabled)

__all__ = [
    "METRICS_SCHEMA_KEY", "METRICS_SCHEMA_VERSION", "MetricsRegistry",
    "registry", "set_enabled",
    "FleetConfig", "FleetScheduler", "TickResult", "VirtualClock",
    "triage", "FleetService", "FLEET_STATUS_SCHEMA_KEY",
    "FLEET_STATUS_SCHEMA_VERSION",
]

_LAZY = {
    "FleetConfig": "repro.fleet.scheduler",
    "FleetScheduler": "repro.fleet.scheduler",
    "TickResult": "repro.fleet.scheduler",
    "VirtualClock": "repro.fleet.scheduler",
    "triage": "repro.fleet.triage",
    "FleetService": "repro.fleet.service",
    "FLEET_STATUS_SCHEMA_KEY": "repro.fleet.service",
    "FLEET_STATUS_SCHEMA_VERSION": "repro.fleet.service",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.fleet' has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(mod), name)
