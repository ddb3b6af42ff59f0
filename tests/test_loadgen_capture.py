"""PR 7's production-shaped serving stack: mixed prompt lengths through
per-slot KV positions, live trace capture -> byte-identical replay,
recorded trace-spec files as a serve scenario axis, and ``task="loadgen"``
offered-load sweeps — every replay must agree token-for-token."""
import dataclasses
import json

import pytest

from repro.runner import (BenchmarkRunner, ResultStore, Scenario,
                          ScenarioMatrix, TraceSpec, generate_trace,
                          load_spec, save_spec)
from repro.runner.loadgen import (find_knee, parse_split, scale_arrivals,
                                  shard_requests)
from repro.runner.traces import capture_spec, spec_for_scenario, split_trace

#: the mixed-prompt-length serve cell reused across tests: 4 requests
#: spanning 2 distinct prompt lengths in one continuous-batching replay
MIXED = dict(arch="gemma-2b", task="serve", batch=4, seq=8, slots=2,
             trace="bursty+bimodal")


# ---- trace layer: prompt-length profiles + capture ------------------------

def test_prompt_profiles_mix_lengths_deterministically():
    spec = TraceSpec("bursty", 8, 8, 4, seed=0, prompt_profile="bimodal")
    a, b = generate_trace(spec, vocab=100), generate_trace(spec, vocab=100)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    lens = {len(r.prompt) for r in a}
    assert lens == {4, 16}                       # P//2 and 2P, both drawn
    lt = generate_trace(TraceSpec("uniform", 16, 8, 4, seed=1,
                                  prompt_profile="longtail"), vocab=100)
    assert all(4 <= len(r.prompt) <= 32 for r in lt)   # clipped to [P//2, 4P]
    assert len({len(r.prompt) for r in lt}) > 1
    # the length profile never shifts prompt content for a given length
    # layout: fixed spec and an explicit pin of the same lengths agree
    pinned = TraceSpec("bursty", 8, 8, 4, seed=0,
                       prompt_lens=tuple(len(r.prompt)
                                         for r in sorted(a, key=lambda r: r.rid)))
    c = generate_trace(pinned, vocab=100)
    assert [r.prompt.tolist() for r in sorted(c, key=lambda r: r.rid)] == \
        [r.prompt.tolist() for r in sorted(a, key=lambda r: r.rid)]


def test_split_trace_axis_syntax():
    assert split_trace("bursty") == ("bursty", "fixed")
    assert split_trace("bursty+bimodal") == ("bursty", "bimodal")
    with pytest.raises(ValueError):
        Scenario(arch="a", task="serve", trace="bursty+flashcrowd")
    with pytest.raises(ValueError):
        Scenario(arch="a", task="serve", trace="flashcrowd+bimodal")


def test_capture_spec_roundtrips_through_save_load(tmp_path):
    spec = TraceSpec("mixed", 6, 8, 4, seed=3, prompt_profile="uniform")
    reqs = generate_trace(spec, vocab=100)
    cap = capture_spec(reqs, seed=3, source="test")
    assert cap.prompt_lens and cap.arrivals and cap.budgets
    # the captured spec regenerates the exact prompts without storing them
    replay = generate_trace(cap, vocab=100)
    assert [(r.rid, r.arrival_step, r.max_new, r.prompt.tolist())
            for r in replay] == \
        [(r.rid, r.arrival_step, r.max_new, r.prompt.tolist()) for r in reqs]
    path = str(tmp_path / "cap.json")
    save_spec(cap, path)
    assert load_spec(path) == cap
    # pre-capture files (no optional fields) still load
    with open(path) as f:
        d = json.load(f)
    for k in ("prompt_profile", "prompt_lens", "arrivals", "budgets",
              "source"):
        d.pop(k, None)
    bare = str(tmp_path / "bare.json")
    with open(bare, "w") as f:
        json.dump(d, f)
    assert load_spec(bare).profile == cap.profile


# ---- loadgen helpers ------------------------------------------------------

def test_parse_split_and_shard_partition():
    assert parse_split("0/2") == (0, 2)
    for bad in ("2/2", "1", "a/b", "-1/2"):
        with pytest.raises(ValueError):
            parse_split(bad)
    reqs = generate_trace(TraceSpec("bursty", 9, 8, 4, seed=1), vocab=50)
    shards = [shard_requests(list(reqs), f"{i}/3") for i in range(3)]
    rids = sorted(r.rid for s in shards for r in s)
    assert rids == sorted(r.rid for r in reqs)            # exact partition
    assert shard_requests(reqs, "") is reqs               # no-op


def test_scale_arrivals_compresses_the_clock():
    reqs = generate_trace(TraceSpec("bursty", 8, 8, 4, seed=1), vocab=50)
    orig = [r.arrival_step for r in reqs]
    assert any(a > 0 for a in orig)
    scaled = scale_arrivals(reqs, 2.0)
    assert [r.arrival_step for r in scaled] == [a // 2 for a in orig]
    with pytest.raises(ValueError):
        scale_arrivals(reqs, 0.0)


def test_find_knee_marks_saturation():
    pts = [{"load": 0.5, "tok_per_s": 100.0},
           {"load": 1.0, "tok_per_s": 200.0},
           {"load": 2.0, "tok_per_s": 390.0},
           {"load": 4.0, "tok_per_s": 400.0},   # +2.6%: saturated
           {"load": 8.0, "tok_per_s": 395.0}]
    knee = find_knee(pts)
    assert knee == {"knee_load": 2.0, "knee_tok_s": 390.0}
    assert find_knee([])["knee_load"] == 0.0
    assert find_knee(pts[:1])["knee_load"] == 0.5


# ---- scenario layer -------------------------------------------------------

def test_loadgen_scenario_axes_and_validation():
    sc = Scenario(arch="gemma-2b", task="loadgen", batch=4, seq=8, slots=2,
                  trace="bursty+bimodal", load=2.0, split="1/2")
    assert sc.name == ("gemma-2b/loadgen/b4/s8/fp32/jit_donated"
                       "/x2/bursty+bimodal/L2/1of2")
    # loadgen shares the serve engine group
    assert sc.build_key() == Scenario(**MIXED).build_key()
    assert Scenario.from_dict(sc.to_dict()) == sc
    bare = Scenario(arch="gemma-2b", task="loadgen")
    assert bare.load == 1.0 and bare.slots == 4
    with pytest.raises(ValueError):
        Scenario(arch="a", task="loadgen", load=-1.0)
    with pytest.raises(ValueError):
        Scenario(arch="a", task="loadgen", split="2of4")
    with pytest.raises(ValueError):
        Scenario(arch="a", task="serve", load=2.0)      # loadgen-only axis
    with pytest.raises(ValueError):
        Scenario(arch="a", task="train", split="0/2")


def test_matrix_expands_load_and_split_axes_for_loadgen_only():
    m = ScenarioMatrix(archs=["a1"], tasks=("serve", "loadgen"),
                       batches=(4,), seqs=(8,), slots=(2,),
                       traces=("bursty+bimodal",), loads=(1.0, 2.0),
                       splits=("0/2", "1/2"))
    serve = [s for s in m if s.task == "serve"]
    loadgen = [s for s in m if s.task == "loadgen"]
    assert len(serve) == 1                    # loads/splits stay inert
    assert len(loadgen) == 4
    assert {(s.load, s.split) for s in loadgen} == \
        {(1.0, "0/2"), (1.0, "1/2"), (2.0, "0/2"), (2.0, "1/2")}


# ---- execution ------------------------------------------------------------

def test_mixed_prompt_serve_records_capture_and_length_percentiles():
    r = BenchmarkRunner()
    rr = r.run(Scenario(**MIXED), record=False)
    assert rr.status == "ok", rr.error
    cap = rr.extra["capture"]
    assert len(set(cap["prompt_lens"])) >= 2   # the mixed-length invariant
    assert cap["source"].startswith("capture:gemma-2b/serve/")
    assert rr.extra["prompt_len_p50"] > 0
    assert rr.extra["prompt_len_p95"] >= rr.extra["prompt_len_p50"]


def test_loadgen_cell_tokens_invariant_under_offered_load():
    """Per-slot positions make each request's tokens a function of its own
    prompt alone — so scaling the arrival clock (which reshuffles slot
    assignment and co-residency) must not move a single token."""
    r = BenchmarkRunner()
    base = r.run(Scenario(**MIXED), record=False)
    for load in (0.5, 4.0):
        rr = r.run(Scenario(**{**MIXED, "task": "loadgen"}, load=load),
                   record=False)
        assert rr.status == "ok", rr.error
        assert rr.extra["offered_load"] == load
        assert rr.extra["tokens"] == base.extra["tokens"], load


def test_loadgen_shards_union_to_the_whole_trace():
    r = BenchmarkRunner()
    whole = r.run(Scenario(**MIXED), record=False)
    toks = []
    for i in range(2):
        rr = r.run(Scenario(**{**MIXED, "task": "loadgen"}, split=f"{i}/2"),
                   record=False)
        assert rr.status == "ok", rr.error
        assert rr.extra["split"] == f"{i}/2" and rr.runs == 2
        toks.extend(rr.extra["tokens"])
    # shard 0 takes rids {0, 2}, shard 1 {1, 3} -> interleave back
    merged = [toks[0], toks[2], toks[1], toks[3]]
    assert merged == whole.extra["tokens"]


def test_capture_replay_matches_serial_sharded_and_clustered(tmp_path):
    """The acceptance invariant, end-to-end: a live mixed-prompt run's
    captured TraceSpec, replayed via trace="file:..." through run_matrix,
    reproduces the original tokens byte-for-byte — on the runner that
    captured it and on a fresh runner with its own store."""
    r = BenchmarkRunner()
    live = r.run(Scenario(**MIXED), record=False)
    assert live.status == "ok", live.error
    path = str(tmp_path / "cap.json")
    save_spec(TraceSpec(**live.extra["capture"]), path)
    matrix = ScenarioMatrix(archs=["gemma-2b"], tasks=("serve",),
                            batches=(4,), seqs=(8,), slots=(2, 3),
                            traces=(f"file:{path}",))
    serial_rrs = r.run_matrix(matrix, runs=1)
    runner = BenchmarkRunner(store=ResultStore(str(tmp_path / "fresh")))
    rrs = runner.run_matrix(matrix)
    for rr in rrs:
        assert rr.status == "ok", rr.error
    serial = [rr.extra["tokens_digest"] for rr in serial_rrs]
    assert [rr.extra["tokens_digest"] for rr in rrs] == serial
    # and the replay IS the live run, token for token (both slot widths:
    # co-residency does not leak into outputs)
    for d in serial:
        assert d == live.extra["tokens_digest"]


def test_co_resident_requests_do_not_perturb_each_other(tmp_path):
    """Same slot count, different co-residency: staggering arrivals so
    each request decodes alone must not move a single token relative to
    the all-at-once run where mixed-length requests share decode batches
    (the old lockstep engine failed exactly this — refilled rows attended
    zeroed keys and wrong RoPE offsets)."""
    together = TraceSpec("uniform", 4, 8, 4, seed=0,
                         prompt_profile="bimodal")
    # budgets are 4 and the longest prompt is 16: 30-step gaps guarantee
    # each request finishes before the next arrives
    alone = dataclasses.replace(together, arrivals=(0, 30, 60, 90))
    r = BenchmarkRunner()
    rrs = {}
    for name, spec in (("together", together), ("alone", alone)):
        path = str(tmp_path / f"{name}.json")
        save_spec(spec, path)
        rr = r.run(Scenario(**{**MIXED, "trace": f"file:{path}"}),
                   record=False)
        assert rr.status == "ok", (name, rr.error)
        rrs[name] = rr
    # same seed + same length layout -> same prompts; only co-residency
    # differs, so per-request tokens must agree exactly
    assert rrs["alone"].extra["tokens"] == rrs["together"].extra["tokens"]
    assert rrs["alone"].extra["tokens_digest"] == \
        rrs["together"].extra["tokens_digest"]


# ---- tuning backend provenance --------------------------------------------

def test_tuning_db_ignores_mismatched_backend(tmp_path, monkeypatch):
    from repro.tuning import db as tdb
    monkeypatch.setenv("REPRO_TUNING_DB", str(tmp_path / "db.json"))
    tdb.invalidate_cache()
    here = tdb._current_backend()
    assert here                                  # jax is importable in tests
    db = tdb.TuningDB.load()
    db.record("flash_attention", "Sq8,Sk8,D4", "fp32",
              params={"block_q": 8}, median_us=1.0, backend=here)
    db.record("rglru", "S8,D4", "fp32",
              params={"block_s": 8}, median_us=1.0,
              backend="tpu" if here != "tpu" else "cpu")
    db.record("ssd", "S8,P4,N4", "fp32",
              params={"block_s": 8}, median_us=1.0)   # no provenance
    db.save()
    # matching backend serves; mismatched is ignored; unstamped serves
    assert tdb.tuned_params("flash_attention", "Sq8,Sk8,D4", "fp32") == \
        {"block_q": 8}
    assert tdb.tuned_params("rglru", "S8,D4", "fp32") is None
    assert tdb.tuned_params("ssd", "S8,P4,N4", "fp32") == {"block_s": 8}
    tdb.invalidate_cache()


# ---- recorded trace specs (trace="file:...") ------------------------------

def test_trace_spec_save_load_roundtrip(tmp_path):
    spec = TraceSpec(profile="mixed", requests=5, prompt_len=8, max_new=4,
                     seed=11)
    path = save_spec(spec, str(tmp_path / "prod_trace.json"))
    assert load_spec(path) == spec
    a, b = generate_trace(spec, vocab=64), generate_trace(load_spec(path),
                                                          vocab=64)
    assert [(r.rid, r.arrival_step, r.max_new, r.prompt.tolist())
            for r in a] == \
        [(r.rid, r.arrival_step, r.max_new, r.prompt.tolist()) for r in b]
    # the file carries a schema tag; junk JSON is rejected loudly
    with open(path) as f:
        assert json.load(f)["trace_spec"] == 1
    bad = tmp_path / "bad.json"
    bad.write_text('{"profile": "uniform"}')
    with pytest.raises(ValueError):
        load_spec(str(bad))
    # strict shape: a misspelled field must fail loudly, not silently
    # replay a default workload under the intended trace's name
    typo = tmp_path / "typo.json"
    typo.write_text(json.dumps({"trace_spec": 1, "profile": "bursty",
                                "requests": 5, "prompt_len": 8, "seed": 0,
                                "max_new_tokens": 256}))
    with pytest.raises(ValueError, match="max_new"):
        load_spec(str(typo))


def test_file_trace_scenario_axis(tmp_path):
    spec = TraceSpec(profile="bursty", requests=3, prompt_len=8, max_new=4,
                     seed=9)
    path = save_spec(spec, str(tmp_path / "t.json"))
    sc = Scenario(arch="gemma-2b", task="serve", batch=3, seq=8, slots=2,
                  trace=f"file:{path}")
    # the file defines the workload; the scenario axes stay labels
    assert spec_for_scenario(sc) == spec
    assert sc.name.endswith(f"/x2/file:{path}")
    # file traces are serve-only, like every trace; empty path rejected
    with pytest.raises(ValueError):
        Scenario(arch="gemma-2b", task="serve", trace="file:")
    with pytest.raises(ValueError):
        Scenario(arch="gemma-2b", task="train", trace=f"file:{path}")
    # matrices expand file traces like any other profile
    m = ScenarioMatrix(archs=["gemma-2b"], tasks=("serve",), batches=(3,),
                       seqs=(8,), slots=(2,),
                       traces=("uniform", f"file:{path}"))
    assert len(m) == 2


def test_file_trace_replays_identically_to_inline_profile(tmp_path):
    """A recorded spec file replays the exact same workload as the inline
    profile it was recorded from: same requests in, byte-identical tokens
    out (the missing-file case degrades to that cell's error record)."""
    inline = Scenario(arch="gemma-2b", task="serve", batch=3, seq=8,
                      slots=2, trace="bursty")
    path = save_spec(spec_for_scenario(inline), str(tmp_path / "rec.json"))
    recorded = Scenario(arch="gemma-2b", task="serve", batch=3, seq=8,
                        slots=2, trace=f"file:{path}")
    runner = BenchmarkRunner(runs=1, warmup=0)
    rr_inline = runner.run(inline, record=False)
    rr_file = runner.run(recorded, record=False)
    assert rr_inline.status == "ok" and rr_file.status == "ok"
    assert rr_file.extra["tokens"] == rr_inline.extra["tokens"]
    assert rr_file.extra["tokens_digest"] == rr_inline.extra["tokens_digest"]
    assert rr_file.extra["trace"] == f"file:{path}"
    # same (build_key, mode, max_len): the second replay reused the engine
    assert rr_file.cache["executable_reused"]
    missing = Scenario(arch="gemma-2b", task="serve", batch=3, seq=8,
                       slots=2, trace="file:/nonexistent/trace.json")
    rr = runner.run(missing, record=False)
    assert rr.status == "error" and "trace.json" in rr.error
