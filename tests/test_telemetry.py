"""The telemetry subsystem: span tracing of a run_matrix call, Chrome
trace export, provenance stamping, the slots="auto" staleness warning,
and the provenance-keyed result history."""
import json
import subprocess
import sys

import pytest

from repro.runner import (BenchmarkRunner, ResultStore, RunResult, Scenario,
                          ScenarioMatrix)
from repro.runner.loadgen import DEFAULT_SLOTS, auto_slots_info
from repro.runner.runner import _subprocess_env
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
    assert sp.to_dict() == {}
    assert NULL_TRACER.export() == []


def test_chrome_trace_lanes_and_args():
    """Every span comes from the process that runs the cells, so the
    export has one lane; ids, parents and attrs ride in args."""
    doc = chrome_trace(_synthetic_tracer().export())
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(meta) == 1 and meta[0]["pid"] == 0
    assert len(events) == 5
    assert {(e["pid"], e["tid"]) for e in events} == {(0, 0)}
    cell = next(e for e in events if e["name"] == "cell:a/train")
    measure = next(e for e in events if e["name"] == "measure")
    assert measure["dur"] == pytest.approx(0.75e6)
    assert measure["args"]["parent"] == cell["args"]["span_id"]
    # attrs ride in args so the tree reconstructs from the file alone
    assert cell["args"]["cell"] == "a/train"
    json.dumps(doc)   # must be JSON-serializable as-is


def test_flame_summary_tree():
    text = flame_summary(_synthetic_tracer().export())
    lines = text.splitlines()
    assert lines[0].startswith("matrix")
    assert lines[1].startswith("  group:g0")
    assert "      build 250.0ms" in text and "measure 750.0ms" in text


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
    # setdefault semantics: an existing stamp must not be overwritten
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

#: the traced serial matrices: one per task kind, plus a two-dtype matrix
#: whose matrix order interleaves its two build keys
TRACED = {
    "train": dict(tasks=("train",), batches=(1, 2)),
    "prefill": dict(tasks=("infer_prefill",), batches=(1, 2)),
    "decode": dict(tasks=("infer_decode",), batches=(1, 2)),
    "serve": dict(tasks=("serve",), batches=(2, 3), slots=(2,)),
    "two_dtypes": dict(tasks=("train",), batches=(1, 2),
                       dtypes=("fp32", "bf16")),
}


@pytest.fixture(scope="module")
def traced_runner():
    return BenchmarkRunner(runs=1, warmup=0)


@pytest.mark.parametrize("kind", list(TRACED))
def test_serial_matrix_exports_one_nested_trace(traced_runner, tmp_path,
                                                kind):
    """A traced run_matrix call exports one Chrome trace: the matrix
    root, one group per build key (stitched after the loop, since cells
    interleave across keys), the cells under their group, and each
    cell's phases under the cell."""
    matrix = ScenarioMatrix(archs=["gemma-2b"], seqs=(8,), **TRACED[kind])
    traced_runner.tracer = tracer = Tracer()
    scenarios = traced_runner.select(matrix)
    results = traced_runner.run_matrix(matrix)
    assert [rr.status for rr in results] == ["ok"] * len(scenarios), \
        [rr.error for rr in results]
    keys = [sc.build_key() for sc in scenarios]
    if kind == "two_dtypes":
        assert len(scenarios) == 4
        assert keys[0] == keys[2] != keys[1] == keys[3]
    path = save_trace(tracer.export(), str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {(e["pid"], e["tid"]) for e in events} == {(0, 0)}
    by_id = {e["args"]["span_id"]: e for e in events}

    def parent(e):
        return by_id.get(e["args"]["parent"])

    roots = [e for e in events if parent(e) is None]
    assert len(roots) == 1
    root = roots[0]
    assert root["name"] == "matrix" and root["args"]["kind"] == "matrix"
    assert root["args"]["cells"] == len(scenarios)
    assert "transport" not in root["args"]
    groups = [e for e in events if e["args"]["kind"] == "group"]
    assert sorted(g["name"] for g in groups) == \
        sorted({"group:" + group_label(k) for k in keys})
    assert all(parent(g) is root for g in groups)
    cells = [e for e in events if e["args"]["kind"] == "cell"]
    assert len(cells) == len(scenarios)
    for sc, rr in zip(scenarios, results):
        assert rr.extra["span_trace"] == tracer.trace_id
        cell = by_id[rr.extra["span_cell"]]
        assert cell["args"]["cell"] == sc.name
        assert parent(cell)["name"] == "group:" + group_label(sc.build_key())
        phases = [e for e in events if parent(e) is cell]
        assert {p["args"]["kind"] for p in phases} == {"phase"}
        assert {"build", "measure"} <= {p["name"] for p in phases}
    # whatever lies deeper (the serve engine's admit.*/decode.* spans)
    # nests under a phase
    for e in events:
        if e["args"]["kind"] not in ("matrix", "group", "cell", "phase"):
            assert parent(e)["args"]["kind"] == "phase", e["name"]


def test_span_overhead_on_warm_executable():
    """Tracing a warm cell records a fixed set of spans whatever the number
    of measured iterations: the measure loop logs its protocol phases, never
    one span per step, so tracing costs no work per step."""
    sc = Scenario(arch="gemma-2b", task="train", batch=1, seq=8)
    runner = BenchmarkRunner(runs=1, warmup=1)
    counts = {}
    runner.run(sc, record=False)   # build + compile once
    for runs in (1, 5):
        runner.tracer = Tracer()
        rr = runner.run(sc, runs=runs, record=False)
        assert rr.status == "ok" and rr.runs == runs, rr.error
        names = [sp["name"] for sp in runner.tracer.export()]
        counts[runs] = {n: names.count(n) for n in names}
    assert counts[1] == counts[5], counts
    assert counts[1]["measure"] == 1


def test_provenance_on_every_status(tmp_path):
    """Mixed ok/error matrix: every stored record carries the prov_*
    stamps, whichever path created it."""
    matrix = ScenarioMatrix(archs=["gemma-2b", "no-such-arch"],
                            tasks=("train",), batches=(1,), seqs=(8,))
    store = ResultStore(str(tmp_path / "s"))
    runner = BenchmarkRunner(store=store, runs=1, warmup=0)
    results = runner.run_matrix(matrix)
    assert {rr.status for rr in results} == {"ok", "error"}
    recs = list(store.history())
    assert len(recs) == 2
    for rec in recs:
        for k in PROV_KEYS:
            assert k in rec["extra"], (rec["name"], k)
        assert provenance_key(rec["extra"]) == provenance_key(collect())
