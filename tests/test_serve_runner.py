"""Serving as a first-class scenario axis: trace determinism, the shared
percentile helper, serve scenario/matrix semantics, latency-metric
recording, and token equality whatever a cell's place in the matrix."""
import os
import subprocess
import sys

import pytest

from repro.runner import (BenchmarkRunner, ResultStore, Scenario,
                          ScenarioMatrix, TraceSpec, generate_trace,
                          percentile)
from repro.runner.latency import latency_summary
from repro.runner.traces import tokens_by_rid, tokens_digest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: one small serve cell reused across tests (cheap on the reduced config)
SERVE = dict(arch="gemma-2b", task="serve", batch=4, seq=8, slots=2)


# ---- percentile helper ----------------------------------------------------

def test_percentile_single_sample_is_every_percentile():
    assert percentile([7.0], 0) == percentile([7.0], 50) == \
        percentile([7.0], 99) == 7.0


def test_percentile_odd_and_even_counts():
    odd = [3.0, 1.0, 2.0]                    # sorted: 1 2 3
    assert percentile(odd, 50) == 2.0
    assert percentile(odd, 0) == 1.0 and percentile(odd, 100) == 3.0
    even = [4.0, 1.0, 3.0, 2.0]              # sorted: 1 2 3 4
    assert percentile(even, 50) == 2.5       # interpolated middle
    assert percentile(even, 25) == 1.75
    # linear interpolation between closest ranks (numpy semantics)
    assert percentile([0.0, 10.0], 95) == pytest.approx(9.5)


def test_percentile_rejects_bad_inputs():
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_latency_summary_keys_and_scaling():
    s = latency_summary([0.001, 0.002, 0.003], "ttft", scale=1e6)
    assert set(s) == {"ttft_p50", "ttft_p95", "ttft_p99"}
    assert s["ttft_p50"] == pytest.approx(2000.0)
    assert latency_summary([], "ttft") == {}


# ---- trace generation -----------------------------------------------------

def test_trace_same_seed_same_trace():
    spec = TraceSpec(profile="mixed", requests=8, prompt_len=6, max_new=4,
                     seed=3)
    a, b = generate_trace(spec, vocab=100), generate_trace(spec, vocab=100)
    assert [(r.rid, r.arrival_step, r.max_new, r.prompt.tolist())
            for r in a] == \
           [(r.rid, r.arrival_step, r.max_new, r.prompt.tolist())
            for r in b]
    # a different seed moves the prompts
    c = generate_trace(TraceSpec(profile="mixed", requests=8, prompt_len=6,
                                 max_new=4, seed=4), vocab=100)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


def test_trace_profiles_shape_the_load():
    uni = generate_trace(TraceSpec("uniform", 8, 6, 4), vocab=50)
    assert all(r.arrival_step == 0 and r.max_new == 4 for r in uni)
    bursty = generate_trace(TraceSpec("bursty", 8, 6, 4, seed=1), vocab=50)
    assert any(r.arrival_step > 0 for r in bursty)      # staggered arrivals
    assert all(r.max_new == 4 for r in bursty)
    mixed = generate_trace(TraceSpec("mixed", 16, 6, 4, seed=1), vocab=50)
    assert len({r.max_new for r in mixed}) > 1          # varied budgets
    assert all(1 <= r.max_new <= 8 for r in mixed)
    spec = TraceSpec("mixed", 16, 6, 4)
    assert spec.max_new_cap == 8
    with pytest.raises(ValueError):
        TraceSpec("flash-crowd", 8, 6, 4)


def test_tokens_digest_is_order_canonical():
    reqs = generate_trace(TraceSpec("bursty", 4, 6, 4, seed=2), vocab=50)
    for i, r in enumerate(reqs):
        r.out = [i, i + 1]
    forward = tokens_digest(tokens_by_rid(reqs))
    assert forward == tokens_digest(tokens_by_rid(list(reversed(reqs))))


# ---- scenario / matrix semantics ------------------------------------------

def test_serve_scenario_axes_and_validation():
    sc = Scenario(**SERVE, trace="bursty")
    assert sc.name == "gemma-2b/serve/b4/s8/fp32/jit_donated/x2/bursty"
    assert sc.build_key()[-2:] == ("serve", 2)
    # bare serve normalizes its axes
    bare = Scenario(arch="gemma-2b", task="serve")
    assert bare.slots == 4 and bare.trace == "uniform"
    # round-trips through dict (the store's record payload)
    assert Scenario.from_dict(sc.to_dict()) == sc
    with pytest.raises(ValueError):
        Scenario(arch="gemma-2b", task="serve", mode="eager")
    with pytest.raises(ValueError):
        Scenario(arch="gemma-2b", task="serve", trace="flash-crowd")
    with pytest.raises(ValueError):
        Scenario(arch="gemma-2b", task="train", slots=2)   # serve-only axis
    # serve cells of one (arch, slots) group share a build key; the step
    # cells of the same arch keep their own (serve extends the key)
    step = Scenario(arch="gemma-2b", task="train")
    assert step.build_key() != sc.build_key()
    assert sc.build_key() == Scenario(**SERVE, trace="uniform").build_key()


def test_matrix_expands_serve_axes_only_for_serve():
    m = ScenarioMatrix(archs=["a1"], tasks=("train", "serve"), batches=(4,),
                       seqs=(8,), modes=("eager", "jit_donated"),
                       slots=(2, 4), traces=("uniform", "bursty"))
    names = [s.name for s in m.expand()]
    # train: 2 modes x 1 cell; serve: jit_donated only, 2 slots x 2 traces
    assert len([n for n in names if "/train/" in n]) == 2
    serve = [s for s in m if s.task == "serve"]
    assert len(serve) == 4
    assert all(s.mode == "jit_donated" for s in serve)
    assert {(s.slots, s.trace) for s in serve} == \
        {(2, "uniform"), (2, "bursty"), (4, "uniform"), (4, "bursty")}


# ---- execution: metrics + determinism -------------------------------------

def test_serve_run_records_latency_metrics(tmp_path):
    store = ResultStore(str(tmp_path / "s"))
    r = BenchmarkRunner(store=store)
    rr = r.run(Scenario(**SERVE, trace="bursty"))
    assert rr.status == "ok", rr.error
    ex = rr.extra
    for key in ("ttft_p50", "ttft_p95", "ttft_p99", "tok_lat_p50",
                "tok_lat_p95", "tok_lat_p99", "tok_per_s", "tokens_digest"):
        assert key in ex, key
    assert ex["trace"] == "bursty" and ex["slots"] == 2
    assert ex["tok_per_s"] > 0 and ex["ttft_p50"] > 0
    assert ex["ttft_p50"] <= ex["ttft_p95"] <= ex["ttft_p99"]
    assert len(ex["tokens"]) == 4 and rr.runs == 4
    assert ex["tokens_digest"] == tokens_digest(ex["tokens"])
    # per-token latency view occupies the core timing fields
    assert rr.median_us == pytest.approx(ex["tok_lat_p50"])
    # a fresh engine's jit is paid by the untimed warm replay and recorded
    # as compile_us, keeping the latency samples steady-state
    assert rr.compile_us > 0
    # persisted through the store like any other cell
    assert store.latest_result(rr.name).extra["tokens"] == ex["tokens"]
    # the engine (compiled decode) is cached: a re-run reuses it and
    # regenerates the identical trace -> identical tokens
    rr2 = r.run(Scenario(**SERVE, trace="bursty"))
    assert rr2.cache == {"model_reused": True, "executable_reused": True}
    assert rr2.extra["tokens"] == ex["tokens"]
    assert rr2.compile_us == 0.0   # nothing compiled on an engine cache hit
    assert r.stats.executable_builds == 1
    # the same engine serves the other trace profile of this shape (the
    # trace changes load timing, not what gets compiled)
    rr3 = r.run(Scenario(**SERVE, trace="uniform"))
    assert rr3.status == "ok" and r.stats.executable_builds == 1
    assert rr3.extra["trace"] == "uniform" and rr3.extra["queue_depth_max"] >= 0


def test_serve_many_refill_waves_fit_the_cache():
    """requests >> slots: per-slot positions rewind on refill, so the KV
    cache needs exactly the largest single-request footprint (prompt +
    budget) — no lockstep slack, however many refill waves the replay
    has.  One token less and the engine must refuse to decode past its
    cache instead of corrupting attention."""
    from repro.core.suite import build_arch
    from repro.launch.serve import ServeEngine
    from repro.runner.traces import cache_len_bound
    spec = TraceSpec("uniform", 6, 8, 4)
    reqs = generate_trace(spec, vocab=1000)
    built = build_arch("gemma-2b")
    bound = cache_len_bound(reqs)
    assert bound == 8 + 4        # tight: max(prompt + max_new), no +8 slack
    out = ServeEngine(built, slots=2, max_len=bound).run(reqs)
    assert out["tokens"] == 6 * 4 and out["decode_steps"] <= 18
    # the last KV write of a request lands at prompt + max_new - 2 (the
    # final emitted token is never written back), so two positions short
    # must raise rather than silently clamp writes
    small = ServeEngine(built, slots=2, max_len=bound - 2)
    with pytest.raises(RuntimeError, match="KV cache exhausted"):
        small.run(generate_trace(spec, vocab=1000))


def test_serve_mode_axis_gets_its_own_engine():
    """jit vs jit_donated share a build_key (neither overrides the config)
    but compile different decode donation — the engine cache must not
    alias them."""
    r = BenchmarkRunner()
    a = r.run(Scenario(**SERVE, mode="jit_donated"), record=False)
    b = r.run(Scenario(**SERVE, mode="jit"), record=False)
    assert a.status == "ok" and b.status == "ok"
    assert r.stats.executable_builds == 2
    assert a.extra["tokens"] == b.extra["tokens"]   # donation is not semantics


def test_serve_hook_slowdown_lands_in_latency_metrics():
    """An injected per-step slowdown must move the recorded per-token
    latencies (what regression.detect compares), like harness.measure."""
    from repro.core.harness import RegressionHook
    r = BenchmarkRunner()
    sc = Scenario(**SERVE, trace="uniform")
    clean = r.run(sc, record=False)
    slow = r.run(sc, hook=RegressionHook(slowdown_s=0.05), record=False)
    assert clean.status == "ok" and slow.status == "ok"
    assert slow.median_us > clean.median_us + 40_000   # >= ~50ms/step visible
    assert slow.extra["tok_lat_p50"] > clean.extra["tok_lat_p50"] + 40_000


@pytest.fixture(scope="module")
def shared_runner():
    return BenchmarkRunner()


def _serve_pair(batches, profile, admission) -> ScenarioMatrix:
    """Two serve cells that differ only in request count, in the order
    of *batches*: they share a build key, so the runner serves both from
    one arch build (and one engine where their cache lengths agree)."""
    return ScenarioMatrix(archs=[SERVE["arch"]], tasks=("serve",),
                          batches=batches, seqs=(SERVE["seq"],),
                          slots=(SERVE["slots"],), traces=(profile,),
                          admissions=(admission,))


@pytest.mark.parametrize("admission", ["batched", "single"])
@pytest.mark.parametrize("profile", ["uniform", "bursty", "mixed"])
def test_serve_tokens_independent_of_matrix_position(shared_runner, profile,
                                                     admission):
    """A serve cell's tokens_digest does not depend on its place in the
    matrix: one runner runs [cell, neighbour], a fresh runner runs
    [neighbour, cell], and both give the cell the same tokens — the
    runner's cached build and engine carry no state from one replay into
    the next."""
    before = _serve_pair((4, 6), profile, admission).expand()
    after = _serve_pair((6, 4), profile, admission).expand()
    cell, neighbour = before
    assert after == [neighbour, cell]
    assert cell.build_key() == neighbour.build_key()
    rrs_before = shared_runner.run_matrix(_serve_pair((4, 6), profile,
                                                      admission))
    fresh = BenchmarkRunner()
    rrs_after = fresh.run_matrix(_serve_pair((6, 4), profile, admission))
    assert [rr.name for rr in rrs_before] == [cell.name, neighbour.name]
    assert [rr.name for rr in rrs_after] == [neighbour.name, cell.name]
    for rr in rrs_before + rrs_after:
        assert rr.status == "ok", f"{rr.name}: {rr.error}"
        assert rr.extra["admission"] == admission
    # the second cell of the fresh runner took the first one's arch build
    assert rrs_after[1].cache["model_reused"]
    assert fresh.stats.model_builds == 1
    assert rrs_before[0].extra["tokens_digest"] == \
        rrs_after[1].extra["tokens_digest"]
    assert rrs_before[0].extra["tokens"] == rrs_after[1].extra["tokens"]
    assert rrs_before[1].extra["tokens_digest"] == \
        rrs_after[0].extra["tokens_digest"]


def test_run_py_list_flag_prints_without_executing(tmp_path):
    """`benchmarks.run --list` prints selected scenario names (post
    filter/exclude) and runs nothing — no store writes, no measurements."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--list", "--fast",
         "--only", "serve_latency", "--exclude", "bursty"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith("serve_latency ")]
    assert lines, r.stdout
    assert all("/serve/" in ln and "uniform" in ln for ln in lines)
    assert not any("bursty" in ln for ln in lines)   # --exclude applied
