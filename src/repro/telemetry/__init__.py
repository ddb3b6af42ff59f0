"""Observability for the benchmark runner: span tracing, provenance
stamping, and provenance-keyed result history.

- ``spans``       low-overhead ``Tracer`` (one trace per ``run_matrix``)
- ``export``      Chrome trace-event JSON (Perfetto) + terminal flame text
- ``provenance``  ``prov_*`` extras: commit sha/dirty, backend, host,
                  jax/python versions, stamped on every ``RunResult``
- ``history``     (scenario, provenance)-keyed time series over
                  ``ResultStore.history()`` with rolling-baseline drift
"""
from repro.telemetry.spans import (  # noqa: F401
    NULL_TRACER,
    Span,
    Tracer,
    group_label,
    recent_warnings,
    warn,
)
from repro.telemetry.provenance import (  # noqa: F401
    PROV_KEYS,
    collect as collect_provenance,
    provenance_key,
    stamp as stamp_provenance,
)
from repro.telemetry.export import (  # noqa: F401
    chrome_trace,
    flame_summary,
    save_trace,
)

__all__ = [
    "NULL_TRACER",
    "Span",
    "Tracer",
    "group_label",
    "warn",
    "recent_warnings",
    "PROV_KEYS",
    "collect_provenance",
    "provenance_key",
    "stamp_provenance",
    "chrome_trace",
    "flame_summary",
    "save_trace",
]
