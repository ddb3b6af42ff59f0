"""The harness end to end on the CPU at a tiny size: a sound run is correct,
a run with the timed path broken underneath is not, new files make a new
cell, and ``run.py`` refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from benchroot import BENCH, ROOT, make_root

from chipbench import harness, spec

SEED = 2**31 + 17


def run(root, name, engine_wrap=None, seed=SEED):
    cell = spec.load_cell(name, root=root)
    return harness.run_cell(cell, seed, 0.5, False, time.perf_counter(),
                            engine_wrap=engine_wrap)


@pytest.mark.parametrize("name", ["tiny-dense.tiny", "tiny-ssm.tiny"])
def test_sound_run_is_correct(tiny_root, name, capsys):
    res = run(tiny_root, name)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tok_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    cap = capsys.readouterr()
    err = cap.err.strip().splitlines()
    assert err[-2].startswith("check max_logit_gap") and "limit" in err[-2]
    assert "compiles_in_window" in json.loads(cap.out.strip().splitlines()[-1])["window"]


def altered_token(engine):
    """Every token decoded for slot 0 is replaced, where it is produced,
    by the next id."""
    model = engine.model

    def step(params, toks, cache):
        logits, cache = model.decode_step(params, toks, cache)
        top = jnp.argmax(logits[0, 0])
        bump = jax.nn.one_hot((top + 1) % logits.shape[-1], logits.shape[-1]) * 1e4
        return logits.at[0, 0].add(bump.astype(logits.dtype)), cache
    engine._decode = jax.jit(step)


def state_unchanged(engine):
    """The decode step returns the cache it was given."""
    model = engine.model
    engine._decode = jax.jit(lambda p, t, c: (model.decode_step(p, t, c)[0], c))


def half_the_rows(engine):
    """The decode step computes the first half of the slots and hands the
    other half the same logits."""
    model = engine.model

    def step(params, toks, cache):
        logits, cache = model.decode_step(params, toks, cache)
        half = logits.shape[0] // 2
        return jnp.concatenate([logits[:half], logits[:half]]), cache
    engine._decode = jax.jit(step)


@pytest.mark.parametrize("fault", [altered_token, state_unchanged, half_the_rows])
@pytest.mark.parametrize("name", ["tiny-dense.tiny", "tiny-ssm.tiny"])
def test_broken_timed_path_is_not_correct(tiny_root, name, fault):
    res = run(tiny_root, name, engine_wrap=fault)
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_new_files_make_a_new_cell(tmp_path):
    """A configuration, a mix, a cell and a metric added as new files plus
    new BENCHMARK.json entries run with no existing file edited."""
    root = make_root(str(tmp_path))
    before = {p: open(os.path.join(BENCH, p), "rb").read()
              for p in ("spec.py", "harness.py", "traffic.py", "run.py")}
    bench = os.path.join(root, "chipbench")
    shutil.copy(os.path.join(bench, "configs", "tiny-dense.json"),
                os.path.join(bench, "configs", "tiny-dense-b.json"))
    mix = json.load(open(os.path.join(bench, "traffic", "tiny.json")))
    mix["load"] = 0.5
    json.dump(mix, open(os.path.join(bench, "traffic", "tiny-half.json"), "w"))
    shutil.copy(os.path.join(bench, "cells", "tiny-dense.tiny.json"),
                os.path.join(bench, "cells", "tiny-dense-b.tiny-half.json"))
    with open(os.path.join(bench, "metrics", "window_tokens.py"), "w") as f:
        f.write("def read(r):\n    return float(r.counts['tokens'])\n")
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    b["configs"].append({"name": "tiny-dense-b", "source": "test",
                         "file": "chipbench/configs/tiny-dense-b.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "tiny-dense-b.tiny-half", "config": "tiny-dense-b",
                           "traffic": "tiny-half", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "window_tokens", "unit": "tok", "better": "higher",
                           "source": "program_counter", "layer": "serve engine",
                           "moves": "tok_s", "workloads": ["tiny-dense-b.tiny-half"]})
    json.dump(b, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = spec.load_cell("tiny-dense-b.tiny-half", root=root)
    assert cell.mix.load == 0.5
    assert [m.name for m in cell.per_layer] == ["window_tokens"]
    read = cell.reader("window_tokens")
    assert read(type("R", (), {"counts": {"tokens": 3}})) == 3.0
    res = run(root, "tiny-dense-b.tiny-half")
    assert res["correct"] is True
    for p, data in before.items():
        assert open(os.path.join(BENCH, p), "rb").read() == data


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    """An end-to-end metric with a ``workloads`` key reaches only those
    cells, and every cell of a per-layer metric reports the end-to-end
    metric that it moves."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: spec.load_cell(w["name"]) for w in bench["workloads"]}
    reports = {n: {m.name for m in c.end_to_end} for n, c in cells.items()}
    for m in bench["end_to_end"]:
        want = set(m.get("workloads", cells))
        assert {n for n, r in reports.items() if m["name"] in r} == want, m["name"]
    for m in bench["per_layer"]:
        for n in m.get("workloads", cells):
            assert m["moves"] in reports[n], (m["name"], n)


def _run_py(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                           "internlm2-20b-6L.chat", "--seed", "1", "--seconds", "1",
                           "--trace", "0"], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_py_exits_nonzero_without_a_tpu():
    p = _run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "TPU" in p.stderr


def test_run_py_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = _run_py(str(tmp_path), env)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
