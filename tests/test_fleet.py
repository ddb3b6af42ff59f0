"""The fleet perf-CI service: metrics registry, tick scheduler, drift
triage with re-measure + bisect, and supervised crash recovery.

The module-wide registry (``repro.fleet.metrics.registry()``) is
process-global and fed by every runner execution in this test session,
so instrumentation assertions here always compare before/after deltas
with ``>=`` — never absolute counts.
"""
import json
import re

import pytest

from repro.core.harness import RegressionHook
from repro.core.regression import Commit, MetricStore
from repro.fleet.metrics import (METRICS_SCHEMA_KEY, METRICS_SCHEMA_VERSION,
                                 MetricsRegistry, registry, set_enabled)
from repro.fleet.scheduler import FleetConfig, FleetScheduler, VirtualClock
from repro.fleet.service import FLEET_STATUS_SCHEMA_KEY, FleetService
from repro.fleet.triage import triage
from repro.runner import BenchmarkRunner, Scenario

ARCH, SEQ = "gemma-2b", 8


@pytest.fixture(scope="module")
def runner():
    return BenchmarkRunner(runs=1, warmup=0)


@pytest.fixture(scope="module")
def cell():
    return Scenario(arch=ARCH, task="train", batch=1, seq=SEQ)


def _counters():
    return registry().snapshot()["counters"]


# ---- registry unit behavior (fresh instances, no jax) ----------------------

def test_snapshot_schema_and_instruments():
    reg = MetricsRegistry()
    reg.inc("fleet_cells_total")
    reg.inc("fleet_cells_total", 2)
    reg.inc("fleet_cells_total", -5)          # negative deltas ignored
    reg.set_gauge("serve_queue_depth", 3)
    reg.observe("fleet_measure_seconds", 0.5)
    snap = reg.snapshot()
    assert snap[METRICS_SCHEMA_KEY] == METRICS_SCHEMA_VERSION
    assert snap["ts"] > 0
    assert snap["counters"]["fleet_cells_total"] == 3
    assert snap["gauges"]["serve_queue_depth"] == 3.0
    hist = snap["histograms"]["fleet_measure_seconds"]
    assert hist["count"] == 1 and hist["sum"] == 0.5 and hist["max"] == 0.5


def test_histogram_quantiles():
    reg = MetricsRegistry()
    for v in range(1, 101):
        reg.observe("h", float(v))
    h = reg.snapshot()["histograms"]["h"]
    assert h["count"] == 100 and h["sum"] == 5050.0
    assert h["p50"] == 50.0 and h["p95"] == 95.0 and h["max"] == 100.0


def test_disabled_registry_is_a_noop():
    reg = MetricsRegistry(enabled=False)
    reg.inc("c")
    reg.set_gauge("g", 1)
    reg.observe("h", 1.0)

    class FakeRR:
        status, cache, compile_us, runs, median_us = "ok", {}, 0.0, 1, 5.0
    reg.record_result(FakeRR())
    snap = reg.snapshot()
    assert not snap["counters"] and not snap["gauges"] and not snap["histograms"]


def test_prometheus_exposition_parses():
    reg = MetricsRegistry()
    reg.inc("fleet_cells_total", 4)
    reg.set_gauge("serve_kv_occupancy", 0.25)
    reg.observe("fleet_compile_seconds", 1.5)
    reg.set_gauge("serve_slots_busy_slot0:weird name", 1)  # needs sanitizing
    text = reg.to_prometheus()
    sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? '
                        r'[-+0-9.eE]+$')
    lines = [ln for ln in text.splitlines() if ln and not ln.startswith("#")]
    assert lines
    for ln in lines:
        assert sample.match(ln), ln
    assert "fleet_cells_total 4" in text
    assert 'fleet_compile_seconds{quantile="0.5"} 1.5' in text
    assert "fleet_compile_seconds_count 1" in text


# ---- runner instrumentation ------------------------------------------------

def test_runner_records_executions(runner, cell):
    before = _counters()
    rr = runner.run(cell, record=False)
    assert rr.status == "ok", rr.error
    after = _counters()
    assert after.get("fleet_cells_total", 0) >= before.get(
        "fleet_cells_total", 0) + 1
    cache_events = (after.get("fleet_exec_cache_hits_total", 0)
                    + after.get("fleet_exec_cache_misses_total", 0))
    cache_before = (before.get("fleet_exec_cache_hits_total", 0)
                    + before.get("fleet_exec_cache_misses_total", 0))
    assert cache_events >= cache_before + 1


def test_coverage_extras(cell):
    r = BenchmarkRunner(runs=1, warmup=0, coverage=True)
    rr = r.run(cell, record=False)
    assert rr.status == "ok", rr.error
    assert rr.extra["cov_primitives"] > 0
    # a fresh runner's first cell IS the union frontier
    assert rr.extra["cov_new_primitives"] == rr.extra["cov_primitives"]
    gauge = registry().snapshot()["gauges"].get("fleet_cov_union_primitives", 0)
    assert gauge >= rr.extra["cov_primitives"]
    # the same scenario again adds nothing new (cached trace, same union)
    rr2 = r.run(cell, record=False)
    assert rr2.extra["cov_new_primitives"] == 0
    assert rr2.extra["cov_primitives"] == rr.extra["cov_primitives"]


# ---- scheduler + triage ----------------------------------------------------

def _fleet_cfg(tmp_path, **over):
    kw = dict(archs=(ARCH,), tasks=("train",), batches=(1,), seqs=(SEQ,),
              runs=1, drain_stride=0,
              queue_path=str(tmp_path / "queue.json"))
    kw.update(over)
    return FleetConfig(**kw)


def test_scheduler_ticks_and_drift(tmp_path, runner):
    store = MetricStore(str(tmp_path / "store.json"))
    hooks_for_tick = (lambda tick:
                      {f"{ARCH}/train": RegressionHook(slowdown_s=0.05)}
                      if tick >= 1 else None)
    sched = FleetScheduler(_fleet_cfg(tmp_path), store, runner,
                           clock=VirtualClock(),
                           hooks_for_tick=hooks_for_tick)
    before = _counters()
    t0 = sched.tick(0)
    assert len(t0.results) == 1 and t0.results[0].status == "ok"
    assert not [f for f in t0.drift["findings"]
                if f["rule"] == "perf_drift"]
    t1 = sched.tick(1)
    drifted = [f for f in t1.drift["findings"] if f["rule"] == "perf_drift"]
    assert drifted, t1.drift["findings"]
    assert drifted[0]["cell"] == t1.results[0].name
    assert float(drifted[0]["evidence"]["baseline"]) > 0
    after = _counters()
    assert after.get("fleet_ticks_total", 0) >= before.get(
        "fleet_ticks_total", 0) + 2
    # each tick logged exactly one provenance point, stamped with its tick
    points = [rec for rec in store._store.history()
              if rec.get("name") == t1.results[0].name]
    assert [p["extra"]["fleet_tick"] for p in points] == [0, 1]


def test_triage_confirm_refute_unverified_bisect(tmp_path, runner, cell):
    scenarios = {cell.name: cell}

    def commits_for(fd, sc):
        def mk(bad):
            return lambda name: {"median_us": 1e6 if bad else 1.0}
        return [Commit(f"c{i}", i, mk(i >= 5)) for i in range(8)]

    drift = {"findings": [
        {"rule": "perf_drift", "cell": cell.name, "severity": "crit",
         "score": 5.0, "evidence": {"metric": "median_us", "baseline": 1.0}},
        {"rule": "perf_drift", "cell": cell.name, "severity": "warn",
         "score": 1.0, "evidence": {"metric": "median_us", "baseline": 1e12}},
        {"rule": "perf_drift", "cell": "no/such/cell", "severity": "warn",
         "score": 1.0, "evidence": {"metric": "median_us", "baseline": 10.0}},
        {"rule": "low_util", "cell": cell.name},   # not a drift rule: skipped
    ]}
    report = triage(drift, runner=runner, scenarios=scenarios,
                    commits_for=commits_for, meta={"tick": 7})
    rules = [f["rule"] for f in report["findings"]]
    assert rules.count("regression_confirmed") == 1
    assert rules.count("regression_bisected") == 1
    assert rules.count("drift_refuted") == 1
    assert rules.count("drift_unverified") == 1
    bisected = next(f for f in report["findings"]
                    if f["rule"] == "regression_bisected")
    assert bisected["evidence"]["culprit"] == "c5"
    assert bisected["evidence"]["measurements"] < len(commits_for(None, None))
    # ranked crit-first; meta folds the caller's context in
    assert report["findings"][0]["severity"] == "crit"
    assert report["meta"]["kind"] == "fleet_triage"
    assert report["meta"]["tick"] == 7
    assert report["meta"]["confirmed"] == 1 and report["meta"]["refuted"] == 1
    assert registry().snapshot()["gauges"]["fleet_open_findings"] == 2


def test_scheduler_drains_tuning_queue(tmp_path, runner, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_DB", str(tmp_path / "db.json"))
    from repro.tuning import enqueue_jobs, make_case
    case = make_case("flash_attention", B=1, S=32, H=2, K=2, D=32)
    queue_path = tmp_path / "queue.json"
    enqueue_jobs([{"kernel": case.kernel, "case": case.case_id,
                   "signature": case.signature, "dtype": case.dtype}],
                 queue_path)
    store = MetricStore(str(tmp_path / "store.json"))
    sched = FleetScheduler(
        _fleet_cfg(tmp_path, drain_stride=1, drain_max_candidates=1),
        store, runner, clock=VirtualClock())
    before = _counters()
    tres = sched.tick(0)
    assert tres.drained_cases == 1
    after = _counters()
    assert after.get("fleet_drained_jobs_total", 0) >= before.get(
        "fleet_drained_jobs_total", 0) + 1
    queue = json.loads(queue_path.read_text())
    assert queue["jobs"] == []


# ---- supervisor backoff + supervised crash recovery ------------------------

def test_supervisor_backoff_schedule(tmp_path):
    from repro.fleet.service import _TickCheckpoint
    from repro.runtime.supervisor import Supervisor
    delays = []
    boom = {"left": 3}

    def step(state, i):
        if i == 1 and boom["left"]:
            boom["left"] -= 1
            raise RuntimeError("flaky step")
        return {"n": state["n"] + 1}

    sup = Supervisor(_TickCheckpoint(str(tmp_path / "ck.json")), save_every=1,
                     max_restarts=5, backoff_s=0.5, sleep=delays.append)
    state, steps = sup.run({"n": 0}, step, 3)
    assert steps == 3 and state["n"] == 3 and sup.restarts == 3
    assert delays == [0.5, 1.0, 2.0]          # exponential, base 0.5
    assert any(e.startswith("backoff@1:") for e in sup.events)

    # backoff_s=0 (the default everywhere else) never sleeps
    delays2 = []
    boom["left"] = 1
    sup2 = Supervisor(_TickCheckpoint(str(tmp_path / "ck2.json")),
                      save_every=1, max_restarts=5, sleep=delays2.append)
    sup2.run({"n": 0}, step, 3)
    assert delays2 == []


def test_service_crash_recovery_no_lost_history(tmp_path):
    """A tick that raises mid-run restarts under the supervisor with
    backoff; completed ticks' history points survive, and the replayed
    tick logs its own."""
    fault = {"armed": True}

    def hooks_for_tick(tick):
        # fail the first consult of tick 1 (the sweep's), once — the
        # supervisor must replay the tick and the retry consults again
        if tick == 1 and fault["armed"]:
            fault["armed"] = False
            raise RuntimeError("injected tick fault")
        return None

    store = MetricStore(str(tmp_path / "store.json"))
    runner = BenchmarkRunner(runs=1, warmup=0)
    delays = []
    service = FleetService(
        _fleet_cfg(tmp_path), store=store, runner=runner,
        results_dir=str(tmp_path), clock=VirtualClock(),
        hooks_for_tick=hooks_for_tick, backoff_s=0.25, sleep=delays.append)
    summary = service.run(2)

    assert summary["ticks"] == 2 and summary["restarts"] == 1
    assert delays == [0.25]
    assert any(e.startswith("backoff@1:") for e in summary["events"])
    # tick 0's point survived the tick-1 crash; the replay logged tick 1
    cell_name = next(iter(service.scheduler.scenarios))
    ticks_logged = [rec["extra"]["fleet_tick"]
                    for rec in store._store.history()
                    if rec.get("name") == cell_name]
    assert ticks_logged == [0, 1]
    # heartbeat is fresh and consistent with the supervised outcome
    with open(summary["status_path"]) as f:
        status = json.load(f)
    assert status[FLEET_STATUS_SCHEMA_KEY] == 1
    assert status["ticks_done"] == 2 and status["restarts"] == 1
    assert len(status["ticks"]) == 2


def test_service_metrics_disabled_toggle_restores():
    prev = set_enabled(False)
    try:
        assert set_enabled(True) is False
    finally:
        set_enabled(True)
        assert registry().enabled
