"""Plain float32 reference of Mamba-2 (attention-free SSD layers), and the
benchmark's weights for it.

The architecture, as published (Dao and Gu, arXiv:2405.21060; one group):

    x = rmsnorm(h, w_ln)
    z, xBC, dt = x @ W_in                     split d_inner, d_inner+2N, H
    xBC = silu(causal_depthwise_conv(xBC) + b_conv)       width d_conv
    x, B, C = xBC                             split d_inner, N, N
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T      per head, s: (P, N)
    y_t = s_t C_t + D x_t
    h' = h + (rmsnorm(y * silu(z), w_norm) @ W_out)
    logits = rmsnorm(h_L, w_final) @ embed^T        (tied head)

The recurrence runs token by token, in float32: no chunking, no cache.
Matmuls are at ``Precision.HIGHEST``.  Departures from the published
description, all storage conventions that change no value:

- RMSNorm weights are stored as offsets from one, ``w = 1 + g``.
- The published residual stream is kept in float32 (``residual_in_fp32``);
  here everything is float32.

``quant=True`` rounds the operands of the matmuls (in, out and head
projections) to float8 e4m3 with one scale per row of activations and per
output column of weights: the control below the served bf16.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench.reference.common import fp8_round, key_from_seed, rms_norm

HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    vocab: int
    d_model: int
    layers: int
    d_state: int
    d_conv: int
    d_inner: int
    headdim: int
    eps: float
    dtype: str

    @property
    def heads(self) -> int:
        return self.d_inner // self.headdim

    @property
    def conv_ch(self) -> int:
        return self.d_inner + 2 * self.d_state


def padded_vocab(cfg: dict) -> int:
    m = cfg["pad_vocab_size_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def dims(cfg: dict) -> Dims:
    s = cfg["ssm_cfg"]
    if s["ngroups"] != 1:
        raise ValueError("the reference covers one B/C group")
    return Dims(vocab=padded_vocab(cfg), d_model=cfg["d_model"],
                layers=cfg["n_layer"], d_state=s["d_state"], d_conv=s["d_conv"],
                d_inner=s["expand"] * cfg["d_model"], headdim=s["headdim"],
                eps=float(cfg["norm_epsilon"]), dtype=cfg["torch_dtype"])


def weight_shapes(d: Dims) -> dict:
    """Shape, initial standard deviation and dtype of every weight, in the
    program's layout (layers stacked on the leading axis).  ``A_log``,
    ``D`` and ``dt_bias`` are float32 and drawn as published (see
    ``_init``); their std here is unused."""
    n, E, N, H, di, C, V = (d.layers, d.d_model, d.d_state, d.heads,
                            d.d_inner, d.conv_ch, d.vocab)
    dt, f32 = d.dtype, "float32"
    return {
        # the tied head's logits come out at unit scale (0.0198 at 2560)
        "embed": ((V, E), 1 / math.sqrt(E), dt),
        "final_norm": ((E,), 0.1, dt),
        "blocks": {
            "ln": ((n, E), 0.1, dt),
            "mix": {"in_proj": ((n, E, 2 * di + 2 * N + H), 1 / math.sqrt(E), dt),
                    "conv_w": ((n, d.d_conv, C), 1 / math.sqrt(d.d_conv), dt),
                    "conv_b": ((n, C), 0.1, dt),
                    "A_log": ((n, H), 0.0, f32),
                    "D": ((n, H), 0.1, f32),
                    "dt_bias": ((n, H), 0.0, f32),
                    "out_norm": ((n, di), 0.1, dt),
                    "out_proj": ((n, di, E), 1 / math.sqrt(di), dt)},
        },
    }


def init_weights(cfg: dict, seed: int):
    """Every weight from ``seed``, on the device, in one jitted call."""
    return _init(dims(cfg), key_from_seed(seed))


@partial(jax.jit, static_argnums=0)
def _init(d: Dims, key):
    shapes = weight_shapes(d)
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda v: isinstance(v, tuple) and isinstance(v[0], tuple))
    keys = jax.random.split(key, len(leaves) + 2)
    out = [jax.random.normal(k, shape, jnp.dtype(dt)) * jnp.asarray(std, jnp.dtype(dt))
           for k, (shape, std, dt) in zip(keys, leaves)]
    w = jax.tree.unflatten(tree, out)
    mix = w["blocks"]["mix"]
    shape = mix["A_log"].shape
    # published initialisation: A uniform in [1, 16]; dt log-uniform in
    # [1e-3, 1e-1], floored at 1e-4, stored through the inverse softplus;
    # D around one
    mix["A_log"] = jnp.log(jax.random.uniform(keys[-2], shape, minval=1.0, maxval=16.0))
    dt = jnp.exp(jax.random.uniform(keys[-1], shape, minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    mix["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    mix["D"] = 1.0 + mix["D"]
    return w


def _mm(quant: bool, spec: str, x, w):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x = fp8_round(x, axes=(-1,))
        w = fp8_round(w, axes=(0,))
    return jnp.einsum(spec, x, w, precision=HI)


@partial(jax.jit, static_argnums=(0, 1))
def _layer(d: Dims, quant: bool, blocks, i, h):
    """Layer ``i`` on hidden states h (B, L, E), float32, from a zero state."""
    p = jax.tree.map(lambda a: a[i], blocks)["mix"]
    ln = blocks["ln"][i]
    B, L, _ = h.shape
    di, N, H, P, W = d.d_inner, d.d_state, d.heads, d.headdim, d.d_conv
    zxbcdt = _mm(quant, "ble,ef->blf", rms_norm(h, ln, d.eps), p["in_proj"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di: 2 * di + 2 * N]
    dt = jax.nn.softplus(zxbcdt[..., 2 * di + 2 * N:] + p["dt_bias"])
    cw = p["conv_w"].astype(jnp.float32)
    xp = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xp[:, j: j + L] * cw[j] for j in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    x = xbc[..., :di].reshape(B, L, H, P)
    Bm = xbc[..., di: di + N]
    Cm = xbc[..., di + N:]
    A = -jnp.exp(p["A_log"])

    def step(s, inp):
        xt, dtt, bt, ct = inp                  # (B,H,P) (B,H) (B,N) (B,N)
        s = (jnp.exp(dtt * A)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return s, jnp.sum(s * ct[:, None, None, :], axis=-1)

    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, s0, (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                                    Bm.swapaxes(0, 1), Cm.swapaxes(0, 1)))
    y = ys.swapaxes(0, 1) + x * p["D"][:, None]
    y = rms_norm(y.reshape(B, L, di) * jax.nn.silu(z), p["out_norm"], d.eps)
    return h + _mm(quant, "blf,fe->ble", y, p["out_proj"])


@partial(jax.jit, static_argnums=0)
def _embed(d: Dims, embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnums=0)
def _final(d: Dims, w, h):
    return rms_norm(h, w, d.eps)


def block_rows(cfg: dict, length: int) -> int:
    """Rows the reference runs together: the recurrence is sequential in
    the length, so all rows go at once (the state is small)."""
    return 64


def hidden(cfg: dict, w, tokens, quant: bool = False):
    """Final-normed hidden states (B, L, E), float32, of ``tokens`` (B, L)
    read as whole sequences from position 0."""
    d = dims(cfg)
    h = _embed(d, w["embed"], jnp.asarray(tokens))
    for i in range(d.layers):
        h = _layer(d, quant, w["blocks"], i, h)
    return _final(d, w["final_norm"], h)


@partial(jax.jit, static_argnums=(0, 1))
def _logits(d: Dims, quant: bool, w, x):
    x = x.astype(jnp.float32)
    e = w["embed"].astype(jnp.float32)
    if quant:
        x = fp8_round(x, axes=(-1,))
        e = fp8_round(e, axes=(1,))
    return jnp.einsum("te,ve->tv", x, e, precision=HI)


def logits(cfg: dict, w, x, quant: bool = False):
    """Logits (T, V), float32, of final-normed hidden states x (T, E)."""
    return _logits(dims(cfg), quant, w, x)
