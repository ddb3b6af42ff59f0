"""The plain references against the program's cached prefill and decode, at
a CPU size, in float32 at the highest matmul precision."""
import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchroot import DATA

#: float32 through two layers, summed in other orders (online softmax
#: against a plain one, chunked SSD against the token-by-token recurrence):
#: about 1e-6 of the logits' unit scale.  bf16 anywhere on the path would
#: differ by 1e-2, so these bounds still see a drop in precision.
ATOL = RTOL = 2e-4


def f32_config(name):
    with open(os.path.join(DATA, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg["torch_dtype"] = "float32"
    return cfg


def program(cfg):
    from repro.configs import get_arch
    from repro.models import build_model
    kw = dict(cfg["program"]["overrides"], param_dtype=jnp.float32,
              compute_dtype=jnp.float32)
    return build_model(dataclasses.replace(get_arch(cfg["program"]["arch"]), **kw))


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-ssm"])
def test_reference_matches_cached_prefill_and_decode(name):
    cfg = f32_config(name)
    ref = importlib.import_module(f"chipbench.reference.{cfg['family']}")
    w = ref.init_weights(cfg, 2**32 + 3)
    model = program(cfg)
    rng = np.random.default_rng(0)
    lens, steps, pad = [11, 7], 4, 16
    seqs = [rng.integers(0, cfg["vocab_size"], n + steps).astype(np.int32) for n in lens]
    tokens = np.zeros((2, pad), np.int32)
    for i, (s, n) in enumerate(zip(seqs, lens)):
        tokens[i, :n] = s[:n]
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(2, 32)
        got = []
        lg, cache = model.prefill(w, {"tokens": jnp.asarray(tokens)}, cache,
                                  lengths=jnp.asarray(lens))
        got.append(np.asarray(lg[:, 0]))
        for t in range(steps - 1):
            nxt = np.array([[s[n + t]] for s, n in zip(seqs, lens)], np.int32)
            lg, cache = model.decode_step(w, jnp.asarray(nxt), cache)
            got.append(np.asarray(lg[:, 0]))
    full = np.zeros((2, 24), np.int32)
    for i, s in enumerate(seqs):
        full[i, : len(s)] = s
    h = ref.hidden(cfg, w, full)
    for t in range(steps):
        x = jnp.stack([h[i, n - 1 + t] for i, n in enumerate(lens)])
        want = np.asarray(ref.logits(cfg, w, x))
        np.testing.assert_allclose(got[t], want, atol=ATOL, rtol=RTOL)


def test_fp8_control_departs_from_float32():
    cfg = f32_config("tiny-dense")
    ref = importlib.import_module("chipbench.reference.dense")
    w = ref.init_weights(cfg, 5)
    tokens = np.arange(16, dtype=np.int32)[None] % cfg["vocab_size"]
    a = np.asarray(ref.hidden(cfg, w, tokens))
    b = np.asarray(ref.hidden(cfg, w, tokens, quant=True))
    assert np.abs(a - b).max() > 100 * ATOL


def test_seed_key_uses_every_bit():
    from chipbench.reference.common import key_from_seed
    k = [jax.random.key_data(key_from_seed(s)) for s in (7, 2**32 + 7, 2**33 + 7)]
    assert not np.array_equal(k[0], k[1]) and not np.array_equal(k[1], k[2])
