"""Plain float32 reference of a dense decoder with grouped-query attention
(the InternLM2 / Llama form), and the benchmark's weights for it.

The architecture, as published:

    h_0 = embed[tokens]
    a   = attn(rmsnorm(h, w_ln1))            causal, RoPE (rotate-half), GQA
    h'  = h + a
    h'' = h' + W_down(silu(W_gate x) * (W_up x)),  x = rmsnorm(h', w_ln2)
    logits = rmsnorm(h_L, w_final) @ W_head         (untied head)

Everything is float32 with matmuls at ``Precision.HIGHEST``: no cache, no
batching of different requests into one row, no kernels.  Departures from
the published description, both storage conventions that change no value:

- RMSNorm weights are stored as offsets from one, ``w = 1 + g``, as the
  program under test stores them; the reference applies ``1 + g``.
- InternLM2 publishes a fused ``wqkv`` matrix; here Q, K and V are three
  matrices, which is the same product.

``quant=True`` rounds every matmul operand to float8 (e4m3) with one scale
per row of activations and per output column of weights: the control that
computes in the precision below the served bf16.

The weights are made here, from the seed, in the layout and dtype the
program serves; the reference reads the same arrays and nothing else of
the program.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench.reference.common import fp8_round, key_from_seed, rms_norm

HI = jax.lax.Precision.HIGHEST

#: largest attention-score block, in bytes, the reference makes at once
SCORE_BYTES = 2 << 30


class Dims(NamedTuple):
    vocab: int
    d_model: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    eps: float
    dtype: str


def dims(cfg: dict) -> Dims:
    return Dims(vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
                layers=cfg["num_hidden_layers"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                d_ff=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"])


def weight_shapes(d: Dims) -> dict:
    """Shape, initial standard deviation and dtype of every weight, in the
    program's layout (layers stacked on the leading axis)."""
    n, E, H, K, D, F, V = (d.layers, d.d_model, d.heads, d.kv_heads,
                           d.head_dim, d.d_ff, d.vocab)
    dt = d.dtype
    return {
        "embed": ((V, E), 0.02, dt),
        "final_norm": ((E,), 0.1, dt),
        "lm_head": ((E, V), 1 / math.sqrt(E), dt),
        "blocks": {
            "ln1": ((n, E), 0.1, dt),
            "ln2": ((n, E), 0.1, dt),
            "attn": {"wq": ((n, E, H, D), 1 / math.sqrt(E), dt),
                     "wk": ((n, E, K, D), 1 / math.sqrt(E), dt),
                     "wv": ((n, E, K, D), 1 / math.sqrt(E), dt),
                     "wo": ((n, H, D, E), 1 / math.sqrt(H * D), dt)},
            "mlp": {"w_gate": ((n, E, F), 1 / math.sqrt(E), dt),
                    "w_up": ((n, E, F), 1 / math.sqrt(E), dt),
                    "w_down": ((n, F, E), 1 / math.sqrt(F), dt)},
        },
    }


def init_weights(cfg: dict, seed: int):
    """Every weight from ``seed``, on the device, in one jitted call."""
    return _init(dims(cfg), key_from_seed(seed))


@partial(jax.jit, static_argnums=0)
def _init(d: Dims, key):
    shapes = weight_shapes(d)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda v: isinstance(v, tuple) and isinstance(v[0], tuple))
    keys = jax.random.split(key, len(leaves))
    out = [jax.random.normal(k, shape, jnp.dtype(dt)) * jnp.asarray(std, jnp.dtype(dt))
           for k, (shape, std, dt) in zip(keys, leaves)]
    return jax.tree.unflatten(tree, out)


def _mm(quant: bool, spec: str, x, w, w_axes):
    """einsum in float32 at HIGHEST; with ``quant`` both operands are
    first rounded to fp8, ``x`` per row (last axis reduced) and ``w`` over
    its contracted ``w_axes``."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x = fp8_round(x, axes=(-1,))
        w = fp8_round(w, axes=w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def _rope(x, pos, theta):
    """Rotate-half RoPE on x (B, L, heads, D) at positions pos (L,)."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnums=(0, 1))
def _layer(d: Dims, quant: bool, blocks, i, h):
    """Layer ``i`` on hidden states h (B, L, E), float32."""
    p = jax.tree.map(lambda a: a[i], blocks)
    B, L, _ = h.shape
    G = d.heads // d.kv_heads
    x = rms_norm(h, p["ln1"], d.eps)
    q = _mm(quant, "ble,ehd->blhd", x, p["attn"]["wq"], (0,))
    k = _mm(quant, "ble,ekd->blkd", x, p["attn"]["wk"], (0,))
    v = _mm(quant, "ble,ekd->blkd", x, p["attn"]["wv"], (0,))
    pos = jnp.arange(L)
    q = _rope(q, pos, d.rope_theta)
    k = _rope(k, pos, d.rope_theta)
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    if quant:
        q, k = fp8_round(q, axes=(-1,)), fp8_round(k, axes=(-1,))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(d.head_dim)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if quant:
        pr = fp8_round(pr, axes=(-1,))
        v = fp8_round(v, axes=(1,))
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HI)
    h = h + _mm(quant, "blhd,hde->ble", o, p["attn"]["wo"], (0, 1))
    x = rms_norm(h, p["ln2"], d.eps)
    g = _mm(quant, "ble,ef->blf", x, p["mlp"]["w_gate"], (0,))
    u = _mm(quant, "ble,ef->blf", x, p["mlp"]["w_up"], (0,))
    return h + _mm(quant, "blf,fe->ble", jax.nn.silu(g) * u, p["mlp"]["w_down"], (0,))


@partial(jax.jit, static_argnums=0)
def _embed(d: Dims, embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnums=0)
def _final(d: Dims, w, h):
    return rms_norm(h, w, d.eps)


def block_rows(cfg: dict, length: int) -> int:
    """Rows the reference runs together at sequence length ``length``, so
    that one block's attention scores stay under ``SCORE_BYTES``."""
    return max(1, SCORE_BYTES // (4 * cfg["num_attention_heads"] * length * length))


def hidden(cfg: dict, w, tokens, quant: bool = False):
    """Final-normed hidden states (B, L, E), float32, of ``tokens`` (B, L)
    read as whole causal sequences from position 0."""
    d = dims(cfg)
    h = _embed(d, w["embed"], jnp.asarray(tokens))
    for i in range(d.layers):
        h = _layer(d, quant, w["blocks"], i, h)
    return _final(d, w["final_norm"], h)


@partial(jax.jit, static_argnums=(0, 1))
def _logits(d: Dims, quant: bool, w, x):
    return _mm(quant, "te,ev->tv", x, w["lm_head"], (0,))


def logits(cfg: dict, w, x, quant: bool = False):
    """Logits (T, V), float32, of final-normed hidden states x (T, E)."""
    return _logits(dims(cfg), quant, w, x)
