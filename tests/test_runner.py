"""The unified BenchmarkRunner subsystem: scenario-matrix expansion
(filter/exclude/skip), ResultStore round-trips (incl. concurrent appenders
and torn-line recovery), build/executable reuse accounting, donation
threading, run_matrix's per-cell fault containment and store order, and
regression detection driven through the store-backed MetricStore."""
import json
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.core.harness import RegressionHook, measure
from repro.core.regression import MetricStore, detect
from repro.core.suite import Benchmark
from repro.runner import (TASKS, BenchmarkRunner, ResultStore, RunnerStats,
                          RunResult, Scenario, ScenarioMatrix)
from repro.runner.runner import _subprocess_env
from repro.telemetry.provenance import PROV_KEYS


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


# ---- run_matrix: every cell in this process, in matrix order ---------------

@pytest.fixture(scope="module")
def matrix_runner():
    return BenchmarkRunner(runs=1, warmup=0, compile_warmup=0)


@pytest.fixture
def counted(matrix_runner, tmp_path):
    """The shared runner (warm caches) with fresh counters and a fresh
    store, so each case reads its own stats and records."""
    matrix_runner.stats = RunnerStats()
    matrix_runner.store = ResultStore(str(tmp_path / "store"))
    return matrix_runner


def _break_step_build(monkeypatch):
    real = Benchmark.make

    def make(self, **kw):
        if kw.get("batch") == 3:
            raise RuntimeError("step build broke")
        return real(self, **kw)

    monkeypatch.setattr(Benchmark, "make", make)


#: stage -> (matrix axes whose first cell fails at that stage, hooks by
#: cell index, expected error text)
FAILING_CELL = {
    "unknown_arch": (dict(archs=["no-such-arch", "gemma-2b"],
                          tasks=("train",), batches=(1,)),
                     {}, "no-such-arch"),
    "step_build": (dict(archs=["gemma-2b"], tasks=("train",),
                        batches=(3, 1)),
                   {}, "step build broke"),
    "measure": (dict(archs=["gemma-2b"], tasks=("train",), batches=(2, 1)),
                {0: _ExplodingHook}, "boom mid-measure"),
    "serve_replay": (dict(archs=["gemma-2b"], tasks=("serve",),
                          batches=(2, 3), slots=(2,)),
                     {0: _ExplodingHook}, "boom mid-measure"),
}


@pytest.mark.parametrize("stage", list(FAILING_CELL))
def test_failing_cell_is_contained_in_matrix_order(counted, monkeypatch,
                                                   stage):
    """A cell that fails at any stage — unknown arch, step build,
    measurement, serve replay — becomes an error record in its place in
    the matrix; the next cell still runs, and both land in the store."""
    axes, hook_at, needle = FAILING_CELL[stage]
    if stage == "step_build":
        _break_step_build(monkeypatch)
    matrix = ScenarioMatrix(seqs=(8,), **axes)
    cells = counted.select(matrix)
    assert len(cells) == 2
    hooks = {cells[i].name: h() for i, h in hook_at.items()}
    results = counted.run_matrix(matrix, hooks=hooks)
    assert [rr.name for rr in results] == [sc.name for sc in cells]
    bad, good = results
    assert bad.status == "error" and needle in bad.error, bad.error
    assert good.status == "ok", good.error
    assert [rec["name"] for rec in counted.store.history()] == \
        [sc.name for sc in cells]
    assert [rec["status"] for rec in counted.store.history()] == \
        ["error", "ok"]
    assert counted.stats.errors == 1
    assert counted.stats.scenarios_run == 2


#: task -> a two-cell matrix of that task (the kernel cells are tuning
#: candidates of one rglru case)
def _task_matrix(task: str) -> ScenarioMatrix:
    if task == "kernel":
        from repro.tuning import make_case, sweep_matrix
        return sweep_matrix([make_case("rglru", B=1, S=64, D=64)],
                            max_candidates=2)
    serving = task in ("serve", "loadgen")
    return ScenarioMatrix(archs=["gemma-2b"], tasks=(task,),
                          batches=(2, 3) if serving else (1, 2), seqs=(8,),
                          slots=(2,))


@pytest.mark.parametrize("task", TASKS)
def test_run_matrix_records_every_task_in_matrix_order(counted, task):
    """run_matrix appends every cell to the store in matrix order, each
    record stamped with its provenance, and counts every cell run."""
    matrix = _task_matrix(task)
    cells = counted.select(matrix)
    assert len(cells) == 2 and {sc.task for sc in cells} == {task}
    results = counted.run_matrix(matrix)
    assert [rr.status for rr in results] == ["ok", "ok"], \
        [rr.error for rr in results]
    recs = list(counted.store.history())
    assert [rec["name"] for rec in recs] == [sc.name for sc in cells]
    for rec in recs:
        for k in PROV_KEYS:
            assert k in rec["extra"], (rec["name"], k)
    assert counted.stats.scenarios_run == 2
    assert counted.stats.errors == 0
