"""Work counts tie to the program's own parameters; the peaks table."""
import json
import math
import os

import jax
import numpy as np
import pytest

from chipbench import peaks, spec
from chipbench.spec import BENCH_DIR, ROOT


def cell_of(config):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    name = next(w["name"] for w in bench["workloads"] if w["config"] == config)
    return spec.load_cell(name)


@pytest.mark.parametrize("config", ["internlm2-20b-6L", "mamba2-2.7b"])
def test_weight_bytes_equal_the_programs_parameters(config):
    from repro.models import build_model
    cell = cell_of(config)
    model = build_model(cell.program_config())
    leaves = jax.tree.leaves(model.abstract_params())
    program = sum(math.prod(a.shape) * np.dtype(a.dtype).itemsize for a in leaves)
    assert cell.work().weight_bytes(cell.config) == program


@pytest.mark.parametrize("config", ["internlm2-20b-6L", "mamba2-2.7b"])
def test_benchmark_weights_have_the_programs_layout(config):
    from repro.models import build_model
    cell = cell_of(config)
    program = build_model(cell.program_config()).abstract_params()
    ours = jax.eval_shape(lambda: cell.reference().init_weights(cell.config, 1))
    assert jax.tree.structure(ours) == jax.tree.structure(program)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(program)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_published_sizes():
    dense = cell_of("internlm2-20b-6L")
    w = dense.work()
    p = w.params(dense.config)
    assert p["layer_matmul"] + p["layer_norm"] == pytest.approx(390.07e6, rel=1e-4)
    assert w.weight_bytes(dense.config) == pytest.approx(6.96e9, rel=1e-3)
    assert w.kv_bytes_per_token(dense.config) == 24 * 1024
    # a decode step reads every weight but the embedding table
    flops, nbytes = w.decode_step(dense.config, [1])
    assert nbytes == pytest.approx(5.82e9, rel=2e-3)
    ssm = cell_of("mamba2-2.7b")
    assert ssm.work().weight_bytes(ssm.config) == pytest.approx(5.40e9, rel=5e-3)
    assert ssm.work().state_bytes_per_row(ssm.config) == pytest.approx(169.8e6, rel=1e-3)


def test_work_grows_with_valid_tokens_only():
    dense = cell_of("internlm2-20b-6L")
    w = dense.work()
    f1, b1 = w.prefill(dense.config, [100, 200], calls=1)
    f2, b2 = w.prefill(dense.config, [100, 200, 300], calls=1)
    assert f2 > f1 and b2 > b1
    d1 = w.decode_step(dense.config, [10, 20])
    d2 = w.decode_step(dense.config, [10, 20, 30])
    assert d2[0] > d1[0] and d2[1] > d1[1]


def test_peaks_by_device_kind():
    p = peaks.for_device("TPU v5 lite")
    assert (p.bf16_flops_per_s, p.hbm_bytes_per_s, p.hbm_bytes) == (197e12, 819e9, 16e9)
    assert p.least_time(197e12, 1.0) == (1.0, "flops")
    assert p.least_time(1.0, 819e9) == (1.0, "bytes")
    with pytest.raises(KeyError):
        peaks.for_device("cpu")
    with pytest.raises(KeyError):
        peaks.for_device("TPU v6 lite")
    assert os.path.exists(os.path.join(BENCH_DIR, "peaks.json"))
