"""Mamba-2 SSD (state-space duality) blocks.

``ssd_chunked`` is the pure-XLA chunked algorithm (also the oracle for the
Pallas kernel in ``repro.kernels.ssd``): quadratic attention-like math
*within* MXU-aligned chunks, a linear recurrence *across* chunks, carried by
``lax.scan``.  ``ssd_sequential`` is the slow per-token reference used in
tests.  ``ssd_step`` is the O(1)-per-token decode update, and
``ssd_step_stacked`` applies it to one layer of the stacked decode state.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import logical
from repro.kernels.ssd.decode import Busy, ssd_decode
from repro.models.layers import ParamDef, causal_conv1d, rms_norm


# ---------------------------------------------------------------------------
# Core SSD math.  Shapes: x (B,S,H,P), dt (B,S,H) (post-softplus),
# A (H,) negative, Bm/Cm (B,S,N) (n_groups=1, broadcast over heads).
# ---------------------------------------------------------------------------


def ssd_sequential(x, dt, A, Bm, Cm, init_state=None):
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    h0 = jnp.zeros((B, H, P, N), jnp.float32) if init_state is None else init_state

    def step(h, inp):
        xt, dtt, bt, ct = inp  # (B,H,P) (B,H) (B,N) (B,N)
        da = jnp.exp(dtt.astype(jnp.float32) * A)                    # (B,H)
        dbx = dtt[..., None, None] * xt[..., None] * bt[:, None, None, :]
        h = da[..., None, None] * h + dbx
        y = jnp.einsum("bhpn,bn->bhp", h, ct.astype(jnp.float32))
        return h, y

    hT, ys = jax.lax.scan(
        step, h0,
        (x.swapaxes(0, 1).astype(jnp.float32), dt.swapaxes(0, 1),
         Bm.swapaxes(0, 1).astype(jnp.float32), Cm.swapaxes(0, 1).astype(jnp.float32)),
    )
    return ys.swapaxes(0, 1).astype(x.dtype), hT  # (B,S,H,P), (B,H,P,N)


def _segsum(z):
    """z (..., L) -> (..., L, L) lower-tri cumulative sums: out[i,j]=sum(z[j+1..i])."""
    L = z.shape[-1]
    cs = jnp.cumsum(z, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, init_state=None):
    """Chunked SSD; exact (up to fp) match of ssd_sequential."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    S0 = S
    if S % L:
        # pad with identity steps (dt=0 -> decay 1, contribution 0)
        pad = L - S % L
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // L

    xc = x.reshape(B, nc, L, H, P).astype(jnp.float32)
    dtc = dt.reshape(B, nc, L, H).astype(jnp.float32)
    bc = Bm.reshape(B, nc, L, N).astype(jnp.float32)
    cc = Cm.reshape(B, nc, L, N).astype(jnp.float32)
    da = dtc * A  # (B,nc,L,H) log-decay per step

    h0 = jnp.zeros((B, H, P, N), jnp.float32) if init_state is None else init_state.astype(jnp.float32)

    def chunk_step(h, inp):
        xb, dtb, bb, cb, dab = inp  # (B,L,H,P) (B,L,H) (B,L,N) (B,L,N) (B,L,H)
        cum = jnp.cumsum(dab, axis=1)                      # (B,L,H)
        # intra-chunk: decay(i,j) = exp(cum_i - cum_j) for j <= i
        Lmat = jnp.exp(_segsum(dab.transpose(0, 2, 1)))    # (B,H,L,L)
        scores = jnp.einsum("bin,bjn->bij", cb, bb)        # (B,L,L)
        w = scores[:, None] * Lmat                         # (B,H,L,L)
        xdt = xb * dtb[..., None]                          # (B,L,H,P)
        y_intra = jnp.einsum("bhij,bjhp->bihp", w, xdt)
        # inter-chunk: contribution of carried state
        y_inter = jnp.einsum("bin,bhpn,bih->bihp", cb, h, jnp.exp(cum))
        # new carried state
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)       # (B,L,H)
        hc = jnp.einsum("bjn,bjhp,bjh->bhpn", bb, xdt, decay_to_end)
        h_new = jnp.exp(cum[:, -1])[:, :, None, None] * h + hc
        return h_new, y_intra + y_inter

    hT, ys = jax.lax.scan(
        chunk_step, h0,
        (xc.swapaxes(0, 1), dtc.swapaxes(0, 1), bc.swapaxes(0, 1),
         cc.swapaxes(0, 1), da.swapaxes(0, 1)),
    )
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)[:, :S0]
    return y.astype(x.dtype), hT


def ssd_step(state, xt, dtt, A, bt, ct):
    """One decode step.  state (B,H,P,N); xt (B,H,P); dtt (B,H); bt/ct (B,N)."""
    da = jnp.exp(dtt.astype(jnp.float32) * A)
    dbx = dtt[..., None, None] * xt.astype(jnp.float32)[..., None] * bt.astype(jnp.float32)[:, None, None, :]
    state = da[..., None, None] * state + dbx
    y = jnp.einsum("bhpn,bn->bhp", state, ct.astype(jnp.float32))
    return y.astype(xt.dtype), state


def ssd_step_stacked(stack, layer, xt, dtt, A, bt, ct, busy: Optional[Busy] = None):
    """``ssd_step`` on layer ``layer`` of the stacked state (L, B, H, P, N).

    Returns y and the stack with that layer's slice stepped in place.
    Lowered for a TPU, one Pallas kernel (``repro.kernels.ssd.decode``)
    reads and writes each state tile once; on any other platform the
    slice goes through ``ssd_step`` and is written back.  With ``busy``
    (``busy_rows``) a free row's slice keeps its values and its y is
    zero: on the TPU its tile is not touched at all."""
    def xla(stack, layer, *args):
        h0 = jax.lax.dynamic_index_in_dim(stack, layer, keepdims=False)
        y, h = ssd_step(h0, *args)
        if busy is not None:
            h = jnp.where(busy.mask[:, None, None, None], h, h0)
            y = jnp.where(busy.mask[:, None, None], y, 0)
        return y, jax.lax.dynamic_update_index_in_dim(stack, h, layer, 0)

    return jax.lax.platform_dependent(stack, layer, xt, dtt, A, bt, ct,
                                      tpu=partial(ssd_decode, busy=busy,
                                                  interpret=False),
                                      default=xla)


# ---------------------------------------------------------------------------
# Mamba-2 block
# ---------------------------------------------------------------------------


def mamba2_defs(cfg, layers_prefix: Tuple[int, ...] = ()) -> dict:
    lp = layers_prefix
    la = ("layers",) * len(lp)
    di, N, H = cfg.d_inner, cfg.d_state, cfg.n_ssm_heads
    G = cfg.ssm_ngroups
    d_in_proj = 2 * di + 2 * G * N + H   # z, x, B, C, dt
    conv_ch = di + 2 * G * N             # conv over x, B, C
    return {
        "in_proj": ParamDef(lp + (cfg.d_model, d_in_proj), la + ("w_embed", "w_mlp"), cfg.param_dtype),
        "conv_w": ParamDef(lp + (cfg.conv_width, conv_ch), la + ("w_conv", "w_mlp"), cfg.param_dtype, scale=0.2),
        "conv_b": ParamDef(lp + (conv_ch,), la + ("w_mlp",), cfg.param_dtype, "zeros"),
        "A_log": ParamDef(lp + (H,), la + ("w_state",), jnp.float32, "ones"),
        "D": ParamDef(lp + (H,), la + ("w_state",), jnp.float32, "ones"),
        "dt_bias": ParamDef(lp + (H,), la + ("w_state",), jnp.float32, "zeros"),
        "out_norm": ParamDef(lp + (di,), la + ("w_mlp",), cfg.param_dtype, "zeros"),
        "out_proj": ParamDef(lp + (di, cfg.d_model), la + ("w_mlp", "w_embed"), cfg.param_dtype),
    }


def mamba2_cache_defs(cfg, batch: int, layers_prefix: Tuple[int, ...] = ()) -> dict:
    lp = layers_prefix
    la = ("layers",) * len(lp)
    di, N, H, P = cfg.d_inner, cfg.d_state, cfg.n_ssm_heads, cfg.ssm_headdim
    conv_ch = di + 2 * cfg.ssm_ngroups * N
    return {
        "conv": ParamDef(lp + (batch, cfg.conv_width - 1, conv_ch), la + ("cache_batch", None, "cache_heads"), cfg.compute_dtype, "zeros"),
        "ssm": ParamDef(lp + (batch, H, P, N), la + ("cache_batch", "cache_heads", None, "cache_state"), jnp.float32, "zeros"),
        "len": ParamDef(lp + (batch,), la + ("cache_batch",), jnp.int32, "zeros"),
    }


def _split_in_proj(zxbcdt, cfg):
    di, N, H = cfg.d_inner, cfg.d_state, cfg.n_ssm_heads
    G = cfg.ssm_ngroups
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : 2 * di + 2 * G * N]
    dt = zxbcdt[..., 2 * di + 2 * G * N:]
    return z, xbc, dt


def mamba2_block(p: dict, u: jax.Array, cfg, cache: Optional[dict] = None,
                 seq_lens: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None,
                 busy: Optional[Busy] = None):
    """u (B, S, E) -> (y, new_cache).

    At decode (S == 1 with a cache) ``cache["ssm"]`` is the whole stacked
    state and ``layer`` this layer's index in it; the new cache holds the
    whole stack back (``Model._scan_stack``'s ``in_place``).  ``busy``
    (``repro.kernels.ssd.decode.busy_rows``) names the rows that decode:
    the state of every other row is left as it is.

    ``seq_lens`` (B,) marks each row's valid prefix under right-padded
    batched prefill: pad steps become identity SSD updates (dt=0 -> decay 1,
    contribution 0 — the same trick ``ssd_chunked`` uses for its own chunk
    padding), so the carried state h_T ignores every row's padded tail.
    """
    B, S, E = u.shape
    cdt = cfg.compute_dtype
    di, N, H, P = cfg.d_inner, cfg.d_state, cfg.n_ssm_heads, cfg.ssm_headdim

    zxbcdt = jnp.einsum("bse,ef->bsf", u, p["in_proj"].astype(cdt))
    z, xbc, dt_raw = _split_in_proj(zxbcdt, cfg)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    if seq_lens is not None:
        valid = jnp.arange(S)[None, :] < seq_lens[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)

    conv_state = cache["conv"] if cache is not None else None
    xbc, new_conv = causal_conv1d(xbc, p["conv_w"].astype(cdt), conv_state,
                                  lengths=seq_lens)
    xbc = jax.nn.silu(xbc + p["conv_b"].astype(cdt))
    x = xbc[..., :di]
    Bm = xbc[..., di : di + N]
    Cm = xbc[..., di + N :]

    x = x.reshape(B, S, H, P)
    x = logical(x, ("act_batch", "act_seq", "act_heads", None))
    A = -jnp.exp(p["A_log"])

    new_cache = None
    if cache is not None and S == 1:
        y, new_state = ssd_step_stacked(cache["ssm"], layer, x[:, 0], dt[:, 0], A,
                                        Bm[:, 0], Cm[:, 0], busy)
        y = y[:, None]
        new_cache = {"conv": new_conv, "ssm": new_state, "len": cache["len"] + 1}
    else:
        init = cache["ssm"] if cache is not None else None
        y, hT = ssd_chunked(x, dt, A, Bm, Cm, cfg.ssm_chunk, init_state=init)
        if cache is not None:
            adv = S if seq_lens is None else seq_lens
            new_cache = {"conv": new_conv, "ssm": hT, "len": cache["len"] + adv}

    y = y + x * p["D"][:, None].astype(cdt)
    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"], cfg.norm_eps)
    out = jnp.einsum("bsf,fe->bse", y, p["out_proj"].astype(cdt))
    return out, new_cache
