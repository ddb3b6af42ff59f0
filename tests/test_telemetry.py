"""The telemetry subsystem: span tracing stitched across dispatch
transports, Chrome trace export, provenance stamping, the slots="auto"
staleness warning, and the provenance-keyed result history."""
import json
import subprocess
import sys

import pytest

from repro.runner import (BenchmarkRunner, ResultStore, RunResult, Scenario,
                          ScenarioMatrix)
from repro.runner.loadgen import DEFAULT_SLOTS, auto_slots_info
from repro.telemetry.export import chrome_trace, flame_summary, save_trace
from repro.telemetry.history import drift, rolling_baseline, series, trajectory
from repro.telemetry.provenance import (PROV_KEYS, collect, provenance_key,
                                        stamp)
from repro.telemetry.spans import (NULL_TRACER, Tracer, recent_warnings,
                                   group_label, warn)


# ---- spans + export (no jax execution) ------------------------------------

def _synthetic_tracer() -> Tracer:
    tr = Tracer()
    tr.begin_trace()
    with tr.span("matrix", kind="matrix") as root:
        with tr.span("group:g0", kind="group"):
            with tr.span("cell:a/train", kind="cell", cell="a/train") as c:
                tr.add("build", ts=c.ts, dur_s=0.25, parent=c)
                tr.add("measure", ts=c.ts + 0.25, dur_s=0.75, parent=c)
    del root
    return tr


def test_tracer_nesting_and_export():
    tr = _synthetic_tracer()
    spans = tr.export()
    assert len(spans) == 5
    by_name = {sp["name"]: sp for sp in spans}
    assert by_name["group:g0"]["parent_id"] == by_name["matrix"]["span_id"]
    assert by_name["cell:a/train"]["parent_id"] == by_name["group:g0"]["span_id"]
    assert by_name["build"]["parent_id"] == by_name["cell:a/train"]["span_id"]
    # export is start-ordered
    assert [sp["ts"] for sp in spans] == sorted(sp["ts"] for sp in spans)


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x") as sp:
        pass
    NULL_TRACER.finish(sp)
    assert NULL_TRACER.context(sp) is None
    assert NULL_TRACER.export() == []


def test_chrome_trace_lanes_and_args():
    tr = _synthetic_tracer()
    tr.ingest([{"name": "cell:a/train", "span_id": "w-1.1",
                "parent_id": None, "kind": "cell", "ts": 1.0,
                "dur_s": 0.5, "tid": 7}], proc="shard0")
    doc = chrome_trace(tr.export())
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = {e["args"]["name"]: e["pid"]
            for e in doc["traceEvents"] if e["ph"] == "M"}
    assert meta["coordinator"] == 0 and "shard0" in meta
    assert len({e["pid"] for e in events}) == 2
    cell = next(e for e in events if e["args"]["span_id"] == "w-1.1")
    assert cell["pid"] == meta["shard0"]
    assert cell["dur"] == pytest.approx(0.5e6)
    # attrs ride in args so the tree reconstructs from the file alone
    coord_cell = next(e for e in events
                      if e["name"] == "cell:a/train" and e["pid"] == 0)
    assert coord_cell["args"]["cell"] == "a/train"
    json.dumps(doc)   # must be JSON-serializable as-is


def test_flame_summary_tree():
    text = flame_summary(_synthetic_tracer().export())
    lines = text.splitlines()
    assert lines[0].startswith("matrix")
    assert lines[1].startswith("  group:g0")
    assert "      build 250.0ms" in text and "measure 750.0ms" in text


def test_worker_tracer_stitches_under_wire_parent():
    """The full wire round-trip: a worker-side tracer built from the job's
    trace context roots its spans under the coordinator's dispatch span,
    and ingest relabels the lane to the worker's identity."""
    coord = Tracer()
    coord.begin_trace()
    ds = coord.start("dispatch:a/train", kind="dispatch")
    ctx = coord.context(ds)
    worker = Tracer(trace_id=ctx["trace_id"], proc="worker",
                    root_parent=ctx["parent"] or None)
    with worker.span("cell:a/train", kind="cell") as c:
        worker.add("build", ts=c.ts, dur_s=0.1, parent=c)
    assert worker.trace_id == coord.trace_id
    coord.ingest(worker.export(), proc="local0")
    coord.finish(ds)
    spans = coord.export()
    cell = next(sp for sp in spans if sp["kind"] == "cell")
    build = next(sp for sp in spans if sp["kind"] == "phase")
    assert cell["parent_id"] == ds.span_id
    assert build["parent_id"] == cell["span_id"]   # intra-worker untouched
    assert cell["proc"] == build["proc"] == "local0"


def test_group_label_is_stable():
    assert group_label(("gemma-2b", "fp32")) == group_label(("gemma-2b", "fp32"))
    assert group_label(("gemma-2b", "fp32")) != group_label(("gemma-2b", "bf16"))


# ---- provenance ------------------------------------------------------------

def test_provenance_stamp_and_key():
    extra = {}
    stamp(extra)
    assert set(PROV_KEYS) <= set(extra)
    assert extra["prov_python"].count(".") == 2
    key = provenance_key(extra)
    assert key.endswith(f"/{extra['prov_backend']}/{extra['prov_host']}")
    # setdefault semantics: a worker's stamp must not be overwritten
    pre = {"prov_host": "measured-there"}
    stamp(pre)
    assert pre["prov_host"] == "measured-there"
    assert provenance_key(pre).endswith("/measured-there")


def test_provenance_collect_is_cached():
    assert collect() is collect()


# ---- slots="auto" staleness (satellite 1) ---------------------------------

def _write_curve(path, **over):
    data = {"schema": 2, "arch": "gemma-2b", "slots": 4,
            "curves": {"batched": {"knee": {"knee_load": 2.0}}}}
    data.update(over)
    path.write_text(json.dumps(data))
    return str(path)


def test_auto_slots_info_fallback_reasons(tmp_path):
    p = tmp_path / "curve.json"
    assert auto_slots_info("gemma-2b", str(p)) == (DEFAULT_SLOTS, "missing")
    p.write_text("{not json")
    assert auto_slots_info("gemma-2b", str(p))[1] == "unreadable"
    _write_curve(p, schema=1)
    assert auto_slots_info("gemma-2b", str(p))[1] == "stale-schema"
    _write_curve(p, arch="mamba2-2.7b")
    assert auto_slots_info("gemma-2b", str(p))[1] == "foreign-arch"
    _write_curve(p, slots=0)
    assert auto_slots_info("gemma-2b", str(p))[1] == "degenerate-curve"
    _write_curve(p)   # healthy: 4 slots * 1.25 headroom / knee_load 2.0
    assert auto_slots_info("gemma-2b", str(p)) == (3, "")
    # every fallback emitted a structured warning into the ring
    reasons = [w["reason"] for w in recent_warnings("slots_fallback")]
    for r in ("missing", "unreadable", "stale-schema", "foreign-arch",
              "degenerate-curve"):
        assert r in reasons, reasons


def test_matrix_slots_fallback_marks_auto_cells(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_LOADGEN_CURVE", str(tmp_path / "nope.json"))
    m = ScenarioMatrix(archs=["gemma-2b"], tasks=("serve",), batches=(2,),
                       seqs=(8,), slots=("auto",), modes=("jit",))
    cells = m.expand()
    assert cells and all(s.slots == DEFAULT_SLOTS for s in cells)
    fb = m.slots_fallback()
    assert fb == {s.name: "missing" for s in cells}
    # fixed-width cells never carry a marker
    fixed = ScenarioMatrix(archs=["gemma-2b"], tasks=("serve",), batches=(2,),
                           seqs=(8,), slots=(2,), modes=("jit",))
    fixed.expand()
    assert fixed.slots_fallback() == {}


def test_warn_ring_filters_by_event(capsys):
    warn("test_event_a", x=1)
    warn("test_event_b", x=2)
    got = recent_warnings("test_event_a")
    assert got and all(w["event"] == "test_event_a" for w in got)
    err = capsys.readouterr().err
    assert "[telemetry]" in err and "test_event_b" in err


# ---- history over the run log ---------------------------------------------

def _hist_record(name, median, ts, commit="aaa", status="ok"):
    return {"name": name, "status": status, "median_us": median, "ts": ts,
            "extra": {"prov_commit": commit, "prov_dirty": False,
                      "prov_backend": "cpu", "prov_host": "h1"}}


def test_series_groups_by_name_and_provenance(tmp_path):
    store = ResultStore(str(tmp_path / "s"))
    for i in range(3):
        store.append(_hist_record("a/train/b1", 100.0 + i, ts=float(i)))
    store.append(_hist_record("a/train/b1", 500.0, ts=9.0, commit="bbb"))
    store.append({"name": "a/train/b1", "median_us": 1.0})  # no prov: skipped
    ser = series(store)
    assert len(ser) == 2
    (k1, pts1), (k2, pts2) = sorted(ser.items())
    assert k1[0] == k2[0] == "a/train/b1" and k1[1] != k2[1]
    assert [p["median_us"] for p in pts1] == [100.0, 101.0, 102.0]
    assert [p["ts"] for p in pts1] == sorted(p["ts"] for p in pts1)
    assert len(pts2) == 1


def test_drift_flags_newest_point_only():
    pts = [{"status": "ok", "ts": float(i), "median_us": 100.0}
           for i in range(5)]
    assert drift(pts, benchmark="b") == []
    pts.append({"status": "ok", "ts": 5.0, "median_us": 130.0})
    issues = drift(pts, benchmark="b")
    assert [i.metric for i in issues] == ["median_us"]
    assert issues[0].increase == pytest.approx(0.30)
    assert rolling_baseline(pts[:-1])["median_us"] == 100.0


def test_trajectory_report_shape(tmp_path):
    store = ResultStore(str(tmp_path / "s"))
    for i in range(4):
        store.append(_hist_record("a/train/b1", 100.0, ts=float(i)))
    store.append(_hist_record("a/train/b1", 150.0, ts=4.0))
    store.append(_hist_record("a/infer/b1", 50.0, ts=0.0))  # 1 point: omitted
    rep = trajectory(store, min_points=2)
    assert [s["name"] for s in rep["meta"]["series"]] == ["a/train/b1"]
    s = rep["meta"]["series"][0]
    assert s["points"] == 5 and s["trend"] == pytest.approx(0.5)
    assert [f["rule"] for f in rep["findings"]] == ["perf_drift"]
    assert rep["findings"][0]["evidence"]["metric"] == "median_us"


def test_metric_store_log_result_keeps_baseline_pointer(tmp_path):
    from repro.core.regression import MetricStore
    store = MetricStore(str(tmp_path / "m"))
    store.update("a/train/b1", {"median_us": 100.0})
    base = store.baseline("a/train/b1")
    sc = Scenario(arch="a", task="train", batch=1, seq=8)
    rr = RunResult.from_error(sc, "n/a")
    rr.name, rr.status, rr.median_us, rr.error = "a/train/b1", "ok", 400.0, None
    store.log_result(rr)
    # the history got the point, the baseline pointer did not move
    assert store.baseline("a/train/b1") == base
    hist = list(store._store.history("a/train/b1"))
    assert any(r.get("median_us") == 400.0 for r in hist)


def test_concurrent_provenance_appends_two_processes(tmp_path):
    """Two stamped appenders (distinct commits via REPRO_COMMIT) into one
    store: zero corrupt lines, and each provenance series replays complete
    and time-ordered."""
    path = str(tmp_path / "store")
    ResultStore(path)
    script = (
        "import sys, time\n"
        "from repro.runner import ResultStore\n"
        "from repro.telemetry.provenance import stamp\n"
        "store = ResultStore(sys.argv[1])\n"
        "for i in range(20):\n"
        "    extra = stamp({})\n"
        "    store.append({'name': 'a/train/b1', 'status': 'ok',\n"
        "                  'median_us': float(i), 'ts': time.time(),\n"
        "                  'extra': extra})\n"
    )
    from repro.runner.pool import _subprocess_env
    procs = []
    for commit in ("c1" * 20, "c2" * 20):
        env = _subprocess_env()
        env["REPRO_COMMIT"] = commit
        procs.append(subprocess.Popen([sys.executable, "-c", script, path],
                                      env=env))
    for p in procs:
        assert p.wait(timeout=60) == 0
    fresh = ResultStore(path)
    assert fresh.corrupt_lines == 0
    ser = series(fresh)
    assert len(ser) == 2
    for (name, prov), pts in ser.items():
        assert name == "a/train/b1" and len(pts) == 20
        assert [p["ts"] for p in pts] == sorted(p["ts"] for p in pts)
    assert {k[1][:12] for k in ser} == {"c1" * 6, "c2" * 6}


# ---- traced execution through the runner (jax) ----------------------------

def test_jobs2_trace_stitches_worker_spans(tmp_path):
    """A traced --jobs 2 matrix exports ONE Chrome trace where every
    worker-side cell span nests under its coordinator dispatch span."""
    matrix = ScenarioMatrix(archs=["gemma-2b"], tasks=("train",),
                            batches=(1,), seqs=(8,),
                            dtypes=("fp32", "bf16"))
    runner = BenchmarkRunner(store=ResultStore(str(tmp_path / "s")),
                             runs=1, warmup=0, jobs=2)
    runner.tracer = Tracer()
    try:
        results = runner.run_matrix(matrix)
    finally:
        runner.close()
    assert [rr.status for rr in results] == ["ok", "ok"]
    for rr in results:
        assert rr.extra["span_trace"] == runner.tracer.trace_id
        assert rr.extra["span_dispatch"]
        assert rr.extra["prov_commit"]
    path = save_trace(runner.tracer.export(), str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_id = {e["args"]["span_id"]: e for e in events}
    assert len({e["pid"] for e in events}) >= 3   # coordinator + 2 shards
    worker_cells = [e for e in events
                    if e["args"].get("kind") == "cell" and e["pid"] != 0]
    assert len(worker_cells) >= 2
    dispatched = set()
    for cell in worker_cells:
        parent = by_id[cell["args"]["parent"]]
        assert parent["args"]["kind"] == "dispatch"
        assert parent["pid"] == 0                  # coordinator lane
        assert parent["args"]["cell"] == cell["args"]["cell"]
        dispatched.add(parent["args"]["cell"])
    assert dispatched == {rr.name for rr in results}


def test_span_overhead_on_warm_executable():
    """Tracing a warm cell records a fixed set of spans whatever the number
    of measured iterations: the measure loop logs its protocol phases, never
    one span per step, so tracing costs no work per step."""
    sc = Scenario(arch="gemma-2b", task="train", batch=1, seq=8)
    runner = BenchmarkRunner(runs=1, warmup=1)
    counts = {}
    try:
        runner.run(sc, record=False)   # build + compile once
        for runs in (1, 5):
            runner.tracer = Tracer()
            rr = runner.run(sc, runs=runs, record=False)
            assert rr.status == "ok" and rr.runs == runs, rr.error
            names = [sp["name"] for sp in runner.tracer.export()]
            counts[runs] = {n: names.count(n) for n in names}
    finally:
        runner.close()
    assert counts[1] == counts[5], counts
    assert counts[1]["measure"] == 1


def test_provenance_on_every_status(tmp_path):
    """Mixed ok/error matrix: every stored record carries the prov_*
    stamps, whichever path created it."""
    matrix = ScenarioMatrix(archs=["gemma-2b", "no-such-arch"],
                            tasks=("train",), batches=(1,), seqs=(8,))
    store = ResultStore(str(tmp_path / "s"))
    runner = BenchmarkRunner(store=store, runs=1, warmup=0)
    try:
        results = runner.run_matrix(matrix)
    finally:
        runner.close()
    assert {rr.status for rr in results} == {"ok", "error"}
    recs = list(store.history())
    assert len(recs) == 2
    for rec in recs:
        for k in PROV_KEYS:
            assert k in rec["extra"], (rec["name"], k)
        assert provenance_key(rec["extra"]) == provenance_key(collect())
