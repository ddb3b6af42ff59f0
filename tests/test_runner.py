"""The unified BenchmarkRunner subsystem: scenario-matrix expansion
(filter/exclude/skip), ResultStore round-trips (incl. concurrent appenders
and torn-line recovery), build/executable reuse accounting, donation
threading, sharded process-pool dispatch, and regression detection driven
through the store-backed MetricStore."""
import json
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.core.harness import RegressionHook, measure
from repro.core.regression import MetricStore, detect
from repro.runner import (BenchmarkRunner, ResultStore, RunResult, RunnerStats,
                          Scenario, ScenarioMatrix, ShardScheduler,
                          assign_shards)


# ---- scenario matrix ------------------------------------------------------

def test_matrix_expansion_is_full_product():
    m = ScenarioMatrix(archs=["a1", "a2"], tasks=("train", "infer_decode"),
                       batches=(1, 4), seqs=(16,), modes=("jit", "eager"))
    names = [s.name for s in m.expand()]
    assert len(names) == len(set(names)) == 2 * 2 * 2 * 1 * 2
    assert "a1/train/b1/s16/fp32/jit" in names
    assert len(m) == 16


def test_matrix_filter_exclude_skip():
    m = ScenarioMatrix(archs=["gemma-2b", "mamba2-2.7b", "mixtral-8x7b"],
                       tasks=("train", "infer_decode"),
                       filter=[r"gemma|mamba"],          # keep two archs
                       exclude=[r"infer_"],              # drop inference
                       skip=["mamba2-2.7b/train"])       # exact bench skip
    names = [s.name for s in m.expand()]
    assert names == ["gemma-2b/train/b2/s64/fp32/jit_donated"]
    # bare-arch skip (the torchbench SKIP-set idiom)
    m2 = ScenarioMatrix(archs=["gemma-2b", "mamba2-2.7b"], tasks=("train",),
                        skip=["mamba2-2.7b"])
    assert [s.arch for s in m2.expand()] == ["gemma-2b"]


def test_scenario_validation_and_roundtrip():
    with pytest.raises(ValueError):
        Scenario(arch="gemma-2b", task="nope")
    with pytest.raises(ValueError):
        Scenario(arch="gemma-2b", mode="tpu_magic")
    sc = Scenario(arch="gemma-2b", task="train", batch=4, seq=128, mode="jit")
    assert Scenario.from_dict(json.loads(json.dumps(sc.to_dict()))) == sc


def test_runner_session_filter():
    r = BenchmarkRunner()
    r.default_exclude = (r"infer_",)
    m = ScenarioMatrix(archs=["gemma-2b"])
    assert [s.task for s in r.select(m)] == ["train"]


def test_matrix_expansion_is_memoized(monkeypatch):
    """__len__/__iter__/expand share one cached expansion until a field
    changes (the product + regex selection used to re-run every call)."""
    import repro.runner.scenario as scenario_mod
    calls = {"n": 0}
    real = scenario_mod.select_scenarios

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(scenario_mod, "select_scenarios", counting)
    m = ScenarioMatrix(archs=["a1", "a2"], tasks=("train",), filter=[r"a\d"])
    first = m.expand()
    assert len(m) == 2 and list(m) == first and m.expand() == first
    assert calls["n"] == 1
    # mutating a field invalidates the cache
    m.archs = ["a1"]
    assert len(m) == 1
    assert calls["n"] == 2
    # expand() hands out copies: callers can't poison the cache
    m.expand().clear()
    assert len(m) == 1


# ---- sharded dispatch -----------------------------------------------------

def test_assign_shards_deterministic_by_build_key():
    scs = [Scenario(arch=a, task=t, batch=1, seq=8, dtype=d)
           for a in ("a1", "a2", "a3")
           for d in ("fp32", "bf16")
           for t in ("train", "infer_decode")]
    shards = assign_shards(scs, 2)
    # deterministic: same input, same partition
    assert shards == assign_shards(list(scs), 2)
    # complete and disjoint
    assert sorted(i for s in shards for i in s) == list(range(len(scs)))
    # all scenarios of one build_key land on one shard
    for key in {sc.build_key() for sc in scs}:
        owners = {j for j, shard in enumerate(shards)
                  for i in shard if scs[i].build_key() == key}
        assert len(owners) == 1, (key, owners)
    # more jobs than groups leaves the surplus shards empty, loses nothing
    wide = assign_shards(scs[:2], 4)
    assert sorted(i for s in wide for i in s) == [0, 1]
    assert sum(bool(s) for s in wide) == 1   # one build_key -> one worker


def test_runner_stats_merge():
    a = RunnerStats(model_builds=1, scenarios_run=2, errors=1)
    a.merge({"model_builds": 2, "executable_builds": 3, "bogus_key": 9})
    a.merge(RunnerStats(scenarios_run=1))
    assert a.model_builds == 3 and a.executable_builds == 3
    assert a.scenarios_run == 3 and a.errors == 1


def test_shard_worker_crash_becomes_error_records():
    """A dying worker costs its in-flight cell (error record), not the
    sweep: the scheduler respawns it for the shard's remaining cells."""
    sched = ShardScheduler(2, runs=1, warmup=0)
    try:
        for w in sched._workers:   # doomed stand-in for a crashy worker
            w.argv = [sys.executable, "-c",
                      "import sys; sys.stdin.readline(); sys.exit(7)"]
        scs = [Scenario(arch="gemma-2b", task="train", batch=1, seq=8),
               Scenario(arch="gemma-2b", task="train", batch=1, seq=8,
                        dtype="bf16")]
        results, stats = sched.run(scs)
    finally:
        sched.close()
    assert [r.status for r in results] == ["error", "error"]
    assert all("exit 7" in r.error for r in results)
    assert {r.extra["shard"] for r in results} == {0, 1}
    assert stats.scenarios_run == 2 and stats.errors == 2


def test_sharded_matrix_matches_serial(tmp_path):
    """jobs=2 returns the same scenario set/statuses as the serial path,
    merges worker stats into the parent, and records shard metadata."""
    m = ScenarioMatrix(archs=["gemma-2b"], tasks=("train",),
                       batches=(1,), seqs=(8,), dtypes=("fp32", "bf16"))
    serial = BenchmarkRunner(runs=1, warmup=0)
    serial_rrs = serial.run_matrix(m)

    store = ResultStore(str(tmp_path / "s"))
    sharded = BenchmarkRunner(store=store, runs=1, warmup=0, jobs=2)
    try:
        shard_rrs = sharded.run_matrix(m)
        rerun = sharded.run_matrix(m)   # same persistent pool, warm caches
    finally:
        sharded.close()

    assert [(r.name, r.status) for r in shard_rrs] == \
        [(r.name, r.status) for r in serial_rrs]
    assert all(r.status == "ok" and r.median_us > 0 for r in shard_rrs)
    # one build_key per dtype -> one worker each, results in matrix order
    assert {r.extra["shard"] for r in shard_rrs} == {0, 1}
    assert all(r.extra["isolated"] for r in shard_rrs)
    # worker builds/compiles are visible in the parent's merged stats;
    # the second run_matrix hit the persistent workers' caches (no new
    # builds) and merged only the DELTA, not the cumulative worker
    # counters again
    assert all(r.status == "ok" for r in rerun)
    assert sharded.stats.model_builds == 2
    assert sharded.stats.executable_builds == 2
    assert sharded.stats.executable_cache_hits == 2
    assert sharded.stats.scenarios_run == 4 and sharded.stats.errors == 0
    # every cell landed in the store from the worker-reader threads
    assert len(list(store.history())) == 4


def test_isolated_run_propagates_worker_stats(tmp_path):
    """isolate=True merges the worker's RunnerStats and ships them in
    extra["worker_stats"] (out-of-process builds used to be invisible)."""
    r = BenchmarkRunner(store=ResultStore(str(tmp_path / "s")),
                        runs=1, warmup=0, isolate=True)
    rr = r.run(Scenario(arch="gemma-2b", task="train", batch=1, seq=8))
    assert rr.status == "ok" and rr.extra["isolated"]
    assert rr.extra["worker_stats"]["model_builds"] == 1
    assert r.stats.model_builds == 1 and r.stats.scenarios_run == 1
    assert r.stats.errors == 0


# ---- result store ---------------------------------------------------------

def test_result_store_roundtrip_and_latest_pointer(tmp_path):
    store = ResultStore(str(tmp_path / "store"))
    sc = Scenario(arch="gemma-2b", task="train", batch=1, seq=8)
    class _M:  # minimal Measurement stand-in
        median_us, mean_us, p10_us, p90_us = 10.0, 11.0, 9.0, 12.0
        compile_us, host_peak_bytes, device_bytes_delta, runs = 100.0, 7, 3, 2
    store.append(RunResult.from_measurement(sc, _M))
    store.append(RunResult.from_measurement(sc, type("M2", (_M,), {"median_us": 20.0})))
    # latest pointer holds the second record; the log holds both
    fresh = ResultStore(str(tmp_path / "store"))
    latest = fresh.latest_result(sc.name)
    assert latest is not None and latest.median_us == 20.0
    assert latest.schema == 1 and latest.status == "ok"
    assert [r["median_us"] for r in fresh.history(sc.name)] == [10.0, 20.0]
    assert [r.name for r in fresh.results()] == [sc.name]


def test_metric_store_on_result_store(tmp_path):
    """regression.detect driven through the ResultStore-backed MetricStore."""
    path = str(tmp_path / "metrics.json")
    store = MetricStore(path)
    store.update("bench/a", {"median_us": 100.0, "host_peak_bytes": 1000})
    store.update("bench/a", {"median_us": 110.0, "host_peak_bytes": 1000})
    # the latest pointer file keeps the historical single-JSON format
    with open(path) as f:
        assert json.load(f)["bench/a"]["median_us"] == 110.0
    # the JSONL log replays both baselines
    assert [r["median_us"] for r in store.history("bench/a")] == [100.0, 110.0]
    # reload + detect against the latest baseline
    store2 = MetricStore(path)
    assert detect(store2, "bench/a", {"median_us": 115.0}) == []
    issues = detect(store2, "bench/a", {"median_us": 130.0})
    assert len(issues) == 1 and issues[0].increase > 0.07
    assert store2.baseline("missing") is None


def test_result_store_skips_corrupt_jsonl_lines(tmp_path):
    """A torn/truncated log line (writer killed mid-append) must not abort
    the history replay — skip and count it."""
    store = ResultStore(str(tmp_path / "store"))
    store.append({"name": "a", "median_us": 1.0})
    with open(store.log_path, "a") as f:
        f.write('{"name": "torn", "median_us": 2.\n')   # killed mid-write
        f.write("[1, 2, 3]\n")                          # non-record JSON
    store.append({"name": "b", "median_us": 3.0})
    replay = list(store.history())
    assert [r["name"] for r in replay] == ["a", "b"]
    assert store.corrupt_lines == 2


def test_result_store_concurrent_append_two_processes(tmp_path):
    """Two processes appending to one store: every log line stays intact
    (single O_APPEND writes) and the latest pointer merges both writers."""
    path = str(tmp_path / "store")
    ResultStore(path)   # create the layout up front
    script = (
        "import sys\n"
        "from repro.runner import ResultStore\n"
        "store = ResultStore(sys.argv[1])\n"
        "tag = sys.argv[2]\n"
        "for i in range(20):\n"
        "    store.append({'name': f'{tag}/{i}', 'median_us': float(i)})\n"
    )
    from repro.runner.pool import _subprocess_env
    procs = [subprocess.Popen([sys.executable, "-c", script, path, tag],
                              env=_subprocess_env())
             for tag in ("w1", "w2")]
    for p in procs:
        assert p.wait(timeout=60) == 0
    fresh = ResultStore(path)
    replay = list(fresh.history())
    assert len(replay) == 40 and fresh.corrupt_lines == 0
    assert len(fresh.latest) == 40
    assert {r["name"] for r in replay} == set(fresh.latest)


# ---- execution + reuse ----------------------------------------------------

def test_runner_reuse_accounting(tmp_path):
    r = BenchmarkRunner(store=ResultStore(str(tmp_path / "s")), runs=2, warmup=0)
    sc = Scenario(arch="gemma-2b", task="train", batch=1, seq=8)
    r1 = r.run(sc)
    assert r1.status == "ok" and r1.median_us > 0
    assert r.stats.model_builds == 1 and r.stats.executable_cache_hits == 0
    assert r1.cache == {"model_reused": False, "executable_reused": False}
    # same scenario again: executable cache hit, no new build/compile
    r2 = r.run(sc)
    assert r2.status == "ok"
    assert r.stats.model_builds == 1 and r.stats.executable_cache_hits == 1
    assert r2.cache == {"model_reused": True, "executable_reused": True}
    assert r2.compile_us == 0.0   # nothing compiled on a cache hit
    # different task of the same arch: model build reused, new executable
    r3 = r.run(Scenario(arch="gemma-2b", task="infer_decode", batch=1, seq=8))
    assert r3.status == "ok"
    assert r.stats.model_builds == 1 and r.stats.model_cache_hits >= 1
    assert r3.cache["model_reused"] and not r3.cache["executable_reused"]
    # all three runs landed in the store
    assert len(list(r.store.history())) == 3


def test_runner_error_containment():
    r = BenchmarkRunner(runs=1, warmup=0)
    rr = r.run(Scenario(arch="no-such-arch"))
    assert rr.status == "error" and "no-such-arch" in rr.error
    assert r.stats.errors == 1


class _ExplodingHook(RegressionHook):
    def fire(self):
        raise RuntimeError("boom mid-measure")


def test_runner_evicts_poisoned_donated_executable():
    """A mid-measure failure may leave the cached executable's donated args
    consumed; the entry must be evicted so the next run rebuilds cleanly."""
    r = BenchmarkRunner(runs=2, warmup=0)
    sc = Scenario(arch="gemma-2b", task="train", batch=1, seq=8)
    assert r.run(sc).status == "ok"
    bad = r.run(sc, hook=_ExplodingHook())
    assert bad.status == "error" and "boom" in bad.error
    ok = r.run(sc)   # must not reuse the half-consumed cached args
    assert ok.status == "ok" and ok.median_us > 0


def test_measure_donation_consumes_and_threads():
    """The donate satellite: donate_argnums is actually passed, the donated
    input is consumed, and the threaded state keeps subsequent calls valid."""
    def step(state, x):
        return state + x, state.sum()

    args = (jnp.ones(8), jnp.ones(8))
    m = measure("donated", step, args, donate=(0,), runs=3)
    assert m.runs == 3 and m.median_us > 0
    assert args[0].is_deleted()        # state buffer was donated
    assert not args[1].is_deleted()    # batch arg was not


def test_runner_donated_scenario_repeats(tmp_path):
    """Cached executables stay callable across re-measures even though their
    state buffers are donated (the threaded args are kept in the cache)."""
    r = BenchmarkRunner(runs=2, warmup=0)
    sc = Scenario(arch="gemma-2b", task="train", batch=1, seq=8,
                  mode="jit_donated")
    for _ in range(3):
        assert r.run(sc).status == "ok"
    assert r.stats.executable_cache_hits == 2


# ---- one process per chip ---------------------------------------------------

@pytest.mark.parametrize("kw", [{"jobs": 2}, {"cluster": "local:2"},
                                {"isolate": True}],
                         ids=["jobs", "cluster", "isolate"])
def test_multiprocess_dispatch_refused_on_tpu(monkeypatch, kw):
    """Worker processes cannot get a chip the parent holds: on a TPU
    backend the runner refuses them instead of failing or hanging."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPU"):
        BenchmarkRunner(**kw)
    runner = BenchmarkRunner()   # in process: fine
    if "isolate" not in kw:      # run_matrix's per-call dispatch overrides
        m = ScenarioMatrix(archs=["gemma-2b"], tasks=("train",))
        with pytest.raises(RuntimeError, match="TPU"):
            runner.run_matrix(m, **kw)
