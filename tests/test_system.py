"""End-to-end behaviour: training converges, fault-injected training is
bit-identical to fault-free, serving generates, CI nightly detects injected
regressions across the measured suite, and run_matrix hands each
regression hook to the cells it names."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ci import run_nightly
from repro.core.harness import RegressionHook
from repro.core.regression import MetricStore
from repro.launch.train import train


def test_training_loss_decreases():
    out = train("gemma-2b", steps=30, batch=4, seq=64, reduced=True)
    first = out["history"][0]["loss"]
    last = out["history"][-1]["loss"]
    assert last < first - 0.05, (first, last)


def test_fault_tolerant_training_is_exact(tmp_path):
    """Injected fault + restore from checkpoint == fault-free run, exactly
    (deterministic data pipeline + checkpoint replay)."""
    clean = train("mamba2-2.7b", steps=24, batch=2, seq=32,
                  ckpt_dir=str(tmp_path / "clean"), save_every=8)
    faulty = train("mamba2-2.7b", steps=24, batch=2, seq=32,
                   ckpt_dir=str(tmp_path / "faulty"), save_every=8,
                   inject_fault_at=13)
    assert any(e.startswith("fault@13") for e in faulty["events"])
    assert any(e.startswith("restore@8") for e in faulty["events"])
    assert clean["final_loss"] == pytest.approx(faulty["final_loss"], rel=1e-6)


def test_serving_generates_tokens():
    from repro.launch.serve import Request, Server
    from repro.configs import get_arch
    cfg = get_arch("gemma-2b").reduced()
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab, 8).astype(np.int32), 4) for i in range(4)]
    srv = Server(cfg, slots=2, max_len=24)
    out = srv.run(reqs)
    assert out["tokens"] >= 4 * 3   # every request generated
    assert all(r.done for r in reqs)
    assert out["decode_steps"] >= 4


def test_nightly_ci_detects_injected_regression(tmp_path):
    from repro.runner import BenchmarkRunner
    store = MetricStore(str(tmp_path / "metrics.json"))
    archs = ["gemma-2b"]
    # one runner for all three nights: nights 1-2 re-measure night 0's
    # cached executable instead of rebuilding + recompiling
    runner = BenchmarkRunner(runs=3)
    # night 0: record baseline
    rep0 = run_nightly(store, archs=archs, tasks=("train",), runs=3,
                       update_baseline=True, runner=runner)
    assert rep0.ran == 1 and not rep0.issues
    # night 1: healthy — at most scheduler-noise-level drift (the CI boxes
    # this runs on are shared; the detector's 7% threshold absorbs normal
    # noise but a loaded host can exceed it, so bound it rather than pin 0)
    rep1 = run_nightly(store, archs=archs, tasks=("train",), runs=3, runner=runner)
    noise = max((i.increase for i in rep1.issues if i.metric == "median_us"), default=0.0)
    assert runner.stats.executable_cache_hits >= 1
    # night 2: a commit lands that slows the step by ~50 ms — detection must
    # fire and dominate whatever noise night 1 showed
    hooks = {"gemma-2b/train": RegressionHook(slowdown_s=0.05)}
    rep2 = run_nightly(store, archs=archs, tasks=("train",), runs=3, hooks=hooks,
                       runner=runner)
    hits = [i for i in rep2.issues if i.metric == "median_us" and i.benchmark == "gemma-2b/train"]
    assert hits and hits[0].increase > max(0.07, 2 * noise)


# ---- run_matrix's hook lookup -----------------------------------------------

class _HookedCell(RegressionHook):
    def fire(self):
        raise RuntimeError("hooked cell")


@pytest.fixture(scope="module")
def hook_runner():
    from repro.runner import BenchmarkRunner
    return BenchmarkRunner(runs=1, warmup=0, compile_warmup=0)


#: kind -> (matrix axes, the hooked cell's task); each matrix holds two
#: cells of the hooked cell's bench and two of another bench
HOOKED = {
    "step": (dict(tasks=("train", "infer_decode"), batches=(1, 2)), "train"),
    "serve": (dict(tasks=("serve", "train"), batches=(2, 3), slots=(2,)),
              "serve"),
}


@pytest.mark.parametrize("key", ["name", "bench"])
@pytest.mark.parametrize("kind", list(HOOKED))
def test_run_matrix_hook_lookup(hook_runner, kind, key):
    """Hooks are keyed by full scenario name or by bench ("arch/task"): a
    name hook reaches its one cell, a bench hook every cell of the bench,
    and no other cell sees either."""
    from repro.runner import ScenarioMatrix
    axes, task = HOOKED[kind]
    matrix = ScenarioMatrix(archs=["gemma-2b"], seqs=(8,), **axes)
    cells = hook_runner.select(matrix)
    target = next(sc for sc in cells if sc.task == task)
    results = hook_runner.run_matrix(
        matrix, hooks={getattr(target, key): _HookedCell()})
    assert [rr.name for rr in results] == [sc.name for sc in cells]
    hooked = {sc.name for sc in cells
              if getattr(sc, key) == getattr(target, key)}
    assert len(hooked) == (1 if key == "name" else 2)
    for rr in results:
        if rr.name in hooked:
            assert rr.status == "error" and "hooked cell" in rr.error, rr.name
        else:
            assert rr.status == "ok", f"{rr.name}: {rr.error}"
