"""Per-architecture smoke tests (assignment requirement): reduced config,
one forward/train step on CPU, output shapes + finiteness; plus
prefill+decode consistency against the cache-free forward pass."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs
from repro.models import build_model

ALL_ARCHS = list(list_archs())

#: every registered arch, plus a hybrid stack deep enough to leave a
#: ``tail`` of recurrent layers after its groups (its reduced depth has none)
DECODE_CASES = [pytest.param(a, {}, id=a) for a in ALL_ARCHS] + [
    pytest.param("recurrentgemma-9b", {"n_layers": 7},
                 id="recurrentgemma-9b-tail")]


def _batch_for(cfg, B, S, key=2):
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab)
    batch = {"tokens": toks}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(jax.random.key(key), (B, cfg.enc_seq, cfg.d_model)) * 0.1
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(jax.random.key(key), (B, cfg.n_prefix, cfg.d_model)) * 0.02
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, S = 2, 64
    batch = _batch_for(cfg, B, S)
    logits = model.forward(params, batch)
    expect_s = S + (cfg.n_prefix or 0)
    assert logits.shape == (B, expect_s, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step_no_nans(arch):
    from repro.launch.steps import make_train_step
    from repro.optim.adamw import adamw_init
    cfg = get_arch(arch).reduced()
    step, model = make_train_step(cfg)
    params = model.init(jax.random.key(0))
    state = (params, adamw_init(params))
    batch = _batch_for(cfg, 2, 32)
    (params2, opt2), metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    delta = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert delta > 0


@pytest.mark.parametrize("arch,overrides", DECODE_CASES)
def test_prefill_decode_matches_forward(arch, overrides):
    cfg = get_arch(arch).reduced(**overrides)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, S = 2, 48
    toks = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab)
    batch = _batch_for(cfg, B, S)
    batch["tokens"] = toks[:, :S]
    full = model.forward(params, {**batch, "tokens": toks})
    cache = model.init_cache(B, S + 8 + (cfg.n_prefix or 0))
    lg_pre, cache = model.prefill(params, batch, cache)
    lg_dec, cache = model.decode_step(params, toks[:, S:S + 1], cache)
    npfx = cfg.n_prefix or 0
    np.testing.assert_allclose(
        np.asarray(lg_pre[:, 0], np.float32), np.asarray(full[:, npfx + S - 1], np.float32),
        atol=0.35, rtol=0.05)
    np.testing.assert_allclose(
        np.asarray(lg_dec[:, 0], np.float32), np.asarray(full[:, npfx + S], np.float32),
        atol=0.35, rtol=0.05)


@pytest.mark.parametrize("arch", ["gemma3-12b", "mixtral-8x7b", "recurrentgemma-9b"])
def test_local_ring_cache_long_decode(arch):
    """Decode past the local window: ring cache must match full forward."""
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B = 1
    W = cfg.local_window
    S = W + 24   # prompt exceeds the window -> ring wraps
    toks = jax.random.randint(jax.random.key(1), (B, S + 4), 0, cfg.vocab)
    full = model.forward(params, {"tokens": toks})
    cache = model.init_cache(B, S + 16)
    lg, cache = model.prefill(params, {"tokens": toks[:, :S]}, cache)
    for i in range(3):
        lg, cache = model.decode_step(params, toks[:, S + i:S + i + 1], cache)
        np.testing.assert_allclose(
            np.asarray(lg[:, 0], np.float32), np.asarray(full[:, S + i], np.float32),
            atol=0.35, rtol=0.05)


def test_all_archs_registered_with_exact_assigned_sizes():
    spec = {
        "gemma-2b": (18, 2048, 8, 1, 16384, 256000),
        "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "deepseek-v2-236b": (60, 5120, 128, 128, 12288, 102400),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
    }
    assert set(spec) == set(ALL_ARCHS)
    for a, (L, d, H, K, ff, V) in spec.items():
        cfg = get_arch(a)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab) == (L, d, H, K, ff, V), a
