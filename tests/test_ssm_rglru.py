"""SSD + RG-LRU model-layer invariants: chunked == sequential, decode-step
chain == full scan (the cache-correctness property for SSM/hybrid serving)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd.decode import busy_rows, ssd_decode
from repro.models.rglru import rglru_scan, rglru_step
from repro.models.ssm import ssd_chunked, ssd_sequential, ssd_step, ssd_step_stacked


def _ssd_inputs(B=2, S=64, H=3, P=16, N=32, seed=0):
    x = jax.random.normal(jax.random.key(seed), (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(seed + 1), (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.key(seed + 2), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.key(seed + 3), (B, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.key(seed + 4), (B, S, N)) * 0.3
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("chunk", [8, 16, 64, 128])
def test_ssd_chunked_equals_sequential(chunk):
    x, dt, A, Bm, Cm = _ssd_inputs()
    yc, hc = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    ys, hs = ssd_sequential(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(ys), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hc), np.asarray(hs), atol=1e-4, rtol=1e-4)


def test_ssd_decode_chain_matches_scan():
    x, dt, A, Bm, Cm = _ssd_inputs(B=1, S=24)
    ys, hT = ssd_sequential(x, dt, A, Bm, Cm)
    state = jnp.zeros_like(hT)
    outs = []
    for t in range(x.shape[1]):
        y, state = ssd_step(state, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        outs.append(y)
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ys), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(hT), atol=1e-4, rtol=1e-4)


def test_ssd_carried_state_across_segments():
    """prefill(S) == prefill(S/2) + continue(S/2) — the serving property."""
    x, dt, A, Bm, Cm = _ssd_inputs(B=1, S=64)
    y_full, h_full = ssd_chunked(x, dt, A, Bm, Cm, 16)
    y1, h1 = ssd_chunked(x[:, :32], dt[:, :32], A, Bm[:, :32], Cm[:, :32], 16)
    y2, h2 = ssd_chunked(x[:, 32:], dt[:, 32:], A, Bm[:, 32:], Cm[:, 32:], 16, init_state=h1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), atol=1e-4, rtol=1e-4)


def _decode_inputs(L, B, H, P=64, N=128):
    """A stacked f32 state and one decode step's bf16 inputs."""
    k = jax.random.split(jax.random.key(7), 6)
    stack = jax.random.normal(k[0], (L, B, H, P, N), jnp.float32)
    x = jax.random.normal(k[1], (B, H, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[2], (B, H)))
    A = -jnp.exp(jax.random.normal(k[3], (H,)) * 0.3)
    bm = jax.random.normal(k[4], (B, N)).astype(jnp.bfloat16)
    cm = jax.random.normal(k[5], (B, N)).astype(jnp.bfloat16)
    return stack, x, dt, A, bm, cm


def _stepper(branch):
    """``(stack, layer, x, dt, A, bm, cm, active=None) -> (y, stack)``,
    ``active`` a (B,) bool mask of the busy rows: the Pallas kernel
    (interpreted here), or the XLA branch that ``ssd_step_stacked`` takes
    off the TPU."""
    step = ssd_step_stacked if branch == "xla" else partial(ssd_decode, interpret=True)
    return jax.jit(lambda *a, active=None: step(
        *a, busy=None if active is None else busy_rows(active)))


#: busy-row masks over four slots, by case name
MASKS = {"all": [1, 1, 1, 1], "none": [0, 0, 0, 0], "ends-free": [0, 1, 1, 0],
         "one-in-the-middle": [0, 0, 1, 0], "alternating": [1, 0, 1, 0]}


@pytest.mark.parametrize("branch,H,layer,mask", [
    pytest.param(branch, H, layer, None, id=f"{branch}-{H}-{layer}")
    for branch in ("kernel", "xla") for H in (16, 80) for layer in (0, 1, 2)] + [
    pytest.param(branch, 16, 1, mask, id=f"{branch}-{name}")
    for branch in ("kernel", "xla") for name, mask in MASKS.items()])
def test_ssd_decode_steps_one_layer_of_the_stack(branch, H, layer, mask):
    """One decode step on one layer of a stacked f32 state, at mamba2's
    P=64, N=128 with bf16 inputs (80 heads is mamba2-2.7b's count): the
    Pallas kernel (interpreted here) and the XLA branch that
    ``ssd_step_stacked`` takes off the TPU both match ``ssd_step`` on that
    layer's slice, and leave every other layer's slice bit for bit.

    Given a busy-row mask, both step only the busy rows: a free row's
    slice is the input bit for bit and its y is zero.  With every row
    busy the result is the unmasked step's."""
    B = 2 if mask is None else len(mask)
    stack, x, dt, A, bm, cm = _decode_inputs(3, B, H)
    step = _stepper(branch)
    active = None if mask is None else jnp.asarray(mask, bool)
    y, new = step(stack, layer, x, dt, A, bm, cm, active=active)
    rows = np.arange(B) if mask is None else np.flatnonzero(mask)
    y_ref, h_ref = ssd_step(stack[layer], x, dt, A, bm, cm)
    assert y.dtype == jnp.bfloat16 and new.dtype == jnp.float32
    # the same f32 products and sums, up to their order and fusion
    h_ref = np.asarray(h_ref)[rows]
    assert np.max(np.abs(np.asarray(new[layer])[rows] - h_ref),
                  initial=0.0) <= 1e-5 * np.max(np.abs(h_ref), initial=0.0)
    # y is rounded to bf16 from f32 sums that may differ in order: each side
    # rounds by at most 2^-8 of the value
    np.testing.assert_allclose(np.asarray(y, np.float32)[rows],
                               np.asarray(y_ref, np.float32)[rows],
                               rtol=2 ** -7, atol=1e-6)
    free = np.setdiff1d(np.arange(B), rows)
    np.testing.assert_array_equal(np.asarray(new[layer])[free],
                                  np.asarray(stack[layer])[free])
    assert not np.asarray(y, np.float32)[free].any()
    for other in set(range(3)) - {layer}:
        np.testing.assert_array_equal(np.asarray(new[other]), np.asarray(stack[other]))
    if mask is not None and all(mask):
        y0, new0 = step(stack, layer, x, dt, A, bm, cm)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(new0))
        np.testing.assert_array_equal(np.asarray(y, np.float32), np.asarray(y0, np.float32))


def test_rglru_scan_matches_step_chain():
    B, S, D = 2, 40, 16
    x = jax.random.normal(jax.random.key(0), (B, S, D))
    a = jax.nn.sigmoid(jax.random.normal(jax.random.key(1), (B, S, D)) * 2)
    h, h_last = rglru_scan(x, a)
    state = jnp.zeros((B, D))
    for t in range(S):
        bt = jnp.sqrt(jnp.maximum(1 - a[:, t] ** 2, 1e-12)) * x[:, t]
        state = a[:, t] * state + bt
    np.testing.assert_allclose(np.asarray(h[:, -1]), np.asarray(state), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(state), atol=1e-5, rtol=1e-5)


def test_rglru_init_state_continuation():
    B, S, D = 1, 32, 8
    x = jax.random.normal(jax.random.key(0), (B, S, D))
    a = jax.nn.sigmoid(jax.random.normal(jax.random.key(1), (B, S, D)))
    h_full, _ = rglru_scan(x, a)
    h1, s1 = rglru_scan(x[:, :16], a[:, :16])
    h2, _ = rglru_scan(x[:, 16:], a[:, 16:], init_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], 1)),
                               np.asarray(h_full), atol=1e-5, rtol=1e-5)
