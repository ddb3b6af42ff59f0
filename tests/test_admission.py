"""Batched prefill admission (PR 8): token equivalence against the
per-request baseline, alone and through run_matrix, the recompile bound
from prompt-length bucketing, the knee-driven ``slots="auto"`` resolver,
and the ``admission`` scenario axis."""
import json
import os

import pytest

from repro.runner import (BenchmarkRunner, Scenario, ScenarioMatrix,
                          TraceSpec, generate_trace)
from repro.runner.loadgen import (AUTO_SLOTS_MAX, CURVE_PATH_ENV,
                                  CURVE_SCHEMA, DEFAULT_SLOTS, auto_slots)

#: a queue-forming loadgen cell: bimodal (mixed-length) prompts, bursty
#: arrivals compressed 8x so several requests queue against several free
#: slots — the regime where batched admission actually batches
LOADGEN = dict(arch="gemma-2b", task="loadgen", batch=8, seq=8, slots=4,
               trace="bursty+bimodal", load=8.0)


# ---- token equivalence across admission policies ---------------------------

def test_admission_policies_agree_on_tokens(tmp_path):
    """The tentpole invariant: admission="single" (per-request baseline)
    and batched admission generate byte-identical tokens on a
    mixed-length trace, run alone and as a run_matrix sweep — batched
    admission is a pure scheduling change."""
    serial = BenchmarkRunner(runs=1, warmup=0)
    rb = serial.run(Scenario(**LOADGEN), record=False)
    rs = serial.run(Scenario(**LOADGEN, admission="single"), record=False)
    assert rb.status == "ok", rb.error
    assert rs.status == "ok", rs.error
    assert rb.extra["tokens"] == rs.extra["tokens"]
    assert rb.extra["tokens_digest"] == rs.extra["tokens_digest"]
    # the compressed load really formed waves: batched admission made
    # fewer, larger prefill calls than the one-per-request baseline
    assert rb.extra["admit_batch_max"] >= 2
    assert rb.extra["admit_calls"] < rs.extra["admit_calls"]
    assert rs.extra["admit_batch_max"] == 1
    assert rs.extra["admit_calls"] == LOADGEN["batch"]
    # both policies share the arch build (admission is engine protocol,
    # not model config) but get distinct cached engines
    assert Scenario(**LOADGEN).build_key() == \
        Scenario(**LOADGEN, admission="single").build_key()
    assert serial.stats.executable_builds == 2

    matrix = ScenarioMatrix(
        archs=[LOADGEN["arch"]], tasks=("loadgen",),
        batches=(LOADGEN["batch"],), seqs=(LOADGEN["seq"],),
        slots=(LOADGEN["slots"],), traces=(LOADGEN["trace"],),
        loads=(LOADGEN["load"],), admissions=("batched", "single"))
    assert len(matrix) == 2
    by_name = {rb.name: rb.extra["tokens"], rs.name: rs.extra["tokens"]}

    for rr in serial.run_matrix(matrix):
        assert rr.status == "ok", f"{rr.name}: {rr.error}"
        assert rr.extra["tokens"] == by_name[rr.name], rr.name


# ---- recompile bound: buckets, not distinct lengths -----------------------

def test_batched_admission_compiles_per_bucket_not_per_length():
    """Prompt lengths are padded into power-of-two buckets before the
    jitted admission call, so a longtail trace with many distinct lengths
    compiles a handful of (rows, padded_len) shapes — the per-request
    baseline would compile one prefill per distinct exact length."""
    from repro.core.suite import build_arch
    from repro.launch.serve import ADMIT_MIN_BUCKET, ServeEngine
    from repro.runner.traces import cache_len_bound
    spec = TraceSpec("uniform", 16, 24, 2, seed=5,
                     prompt_profile="longtail")
    reqs = generate_trace(spec, vocab=500)
    distinct = {len(r.prompt) for r in reqs}
    assert len(distinct) >= 5          # longtail: many exact lengths
    built = build_arch("gemma-2b")
    eng = ServeEngine(built, slots=4, max_len=cache_len_bound(reqs))
    out = eng.run(reqs)
    shapes = [tuple(s) for s in out["admit_shapes"]]
    assert out["admit_batch_max"] >= 2     # uniform arrivals: full waves
    assert len(shapes) < len(distinct)
    cap = eng.max_len                      # bucket grid is capped there
    for rows, lpad in shapes:
        assert rows & (rows - 1) == 0      # row counts rounded to pow2
        assert lpad == cap or (lpad & (lpad - 1) == 0
                               and lpad >= ADMIT_MIN_BUCKET)
    # single-admission on the same trace compiles one shape per length
    eng_s = ServeEngine(built, slots=4, max_len=cache_len_bound(reqs),
                        admission="single")
    out_s = eng_s.run(reqs)
    assert len(out_s["admit_shapes"]) == len(distinct)
    assert out_s["tokens_by_rid"] == out["tokens_by_rid"]


# ---- the knee-driven slots="auto" resolver --------------------------------

def _write_curve(path, **over):
    data = {"schema": CURVE_SCHEMA, "arch": "gemma-2b", "slots": 4,
            "curves": {"batched": {"knee": {"knee_load": 2.0,
                                            "knee_tok_s": 100.0}}}}
    data.update(over)
    with open(path, "w") as f:
        json.dump(data, f)
    return str(path)


def test_auto_slots_policy_scales_measured_width_to_knee(tmp_path):
    p = tmp_path / "curve.json"
    # knee at 2x offered load: the measured width is oversized — shrink
    # (ceil(4 * 1.25 / 2) = 3)
    assert auto_slots("gemma-2b", _write_curve(p)) == 3
    # knee at native load: keep the width plus headroom (ceil(5) = 5)
    _write_curve(p, curves={"batched": {"knee": {"knee_load": 1.0}}})
    assert auto_slots("gemma-2b", str(p)) == 5
    # knee below native load: the engine saturates early — scale up
    _write_curve(p, curves={"batched": {"knee": {"knee_load": 0.5}}})
    assert auto_slots("gemma-2b", str(p)) == 10
    # clamped to the autoscaler bounds
    _write_curve(p, curves={"batched": {"knee": {"knee_load": 0.01}}})
    assert auto_slots("gemma-2b", str(p)) == AUTO_SLOTS_MAX


def test_auto_slots_falls_back_on_missing_stale_or_foreign_curve(tmp_path):
    missing = str(tmp_path / "nope.json")
    assert auto_slots("gemma-2b", missing) == DEFAULT_SLOTS
    assert auto_slots("gemma-2b", missing, default=7) == 7
    # a pre-PR-8 schema is stale: never trust its layout
    stale = _write_curve(tmp_path / "stale.json", schema=CURVE_SCHEMA - 1)
    assert auto_slots("gemma-2b", stale) == DEFAULT_SLOTS
    # a curve measured for another arch must not shape this matrix
    other = _write_curve(tmp_path / "other.json", arch="mixtral-8x7b")
    assert auto_slots("gemma-2b", other) == DEFAULT_SLOTS
    # unreadable JSON degrades the same way
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert auto_slots("gemma-2b", str(bad)) == DEFAULT_SLOTS


def test_matrix_resolves_auto_slots_at_expansion(tmp_path, monkeypatch):
    curve = _write_curve(tmp_path / "curve.json")   # knee_load=2 -> 3 slots
    monkeypatch.setenv(CURVE_PATH_ENV, curve)
    m = ScenarioMatrix(archs=["gemma-2b"], tasks=("serve",), slots=("auto",))
    [sc] = m.expand()
    assert sc.slots == 3 and "/x3/" in sc.name
    # no usable curve for this arch -> the default width
    m2 = ScenarioMatrix(archs=["mixtral-8x7b"], tasks=("serve",),
                        slots=("auto",))
    assert m2.expand()[0].slots == DEFAULT_SLOTS
    # "auto" is a matrix-only value: a bare Scenario must reject it
    with pytest.raises(ValueError, match="auto"):
        Scenario(arch="gemma-2b", task="serve", slots="auto")


# ---- the admission scenario axis ------------------------------------------

def test_admission_axis_normalization_and_validation():
    sc = Scenario(arch="gemma-2b", task="serve")
    assert sc.admission == "batched"          # the default policy
    assert sc.name.endswith("/uniform")       # default stays out of names
    single = Scenario(arch="gemma-2b", task="serve", admission="single")
    assert single.name.endswith("/adm-single")
    assert Scenario.from_dict(single.to_dict()) == single
    with pytest.raises(ValueError, match="admission"):
        Scenario(arch="gemma-2b", task="serve", admission="wavefront")
    with pytest.raises(ValueError, match="serve/loadgen-only"):
        Scenario(arch="gemma-2b", task="train", admission="single")


def test_matrix_admissions_axis_multiplies_serve_cells_only():
    m = ScenarioMatrix(archs=["gemma-2b"], tasks=("train", "serve"),
                       admissions=("batched", "single"))
    scs = m.expand()
    assert len([s for s in scs if s.task == "train"]) == 1
    serve = [s for s in scs if s.task == "serve"]
    assert {s.admission for s in serve} == {"batched", "single"}
    assert len(serve) == 2
