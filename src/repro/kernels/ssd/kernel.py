"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

The SSD duality splits the linear recurrence into an intra-chunk quadratic
part (chunk x chunk matmuls — MXU work) and an inter-chunk state recurrence
(rank-1 updates carried in VMEM scratch).  The CUDA reference keeps state in
registers across a persistent CTA; the TPU adaptation instead exploits the
sequential innermost grid dimension: state (P x N per head) lives in VMEM
scratch and carries across chunk iterations.

Grid: (B*H, n_chunks) — chunks execute sequentially per (batch, head).
Block shapes: x (chunk, P), dt (chunk, 1), B/C (chunk, N); chunk is a
multiple of 8 sublanes, P/N multiples of 128 lanes on real hardware (the
assigned mamba2-2.7b has P=64, N=128 — P=64 packs two heads per lane tile in
a production variant; kept simple here).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.validate import resolve_interpret, validate_block


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (L, P)
    dt = dt_ref[0].astype(jnp.float32)        # (L, 1)
    a = a_ref[0]                              # (1, 1) A (negative)
    bm = b_ref[0].astype(jnp.float32)         # (L, N)
    cm = c_ref[0].astype(jnp.float32)         # (L, N)

    da = dt * a                                # (L, 1) log-decay
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sums as masked reductions (the TPU lowering has no
    # cumsum): a row cum_row[j] = sum_{i<=j} da_i, then the same values as
    # a column through the diagonal (no vector transpose either)
    cum_row = jnp.sum(jnp.where(ii <= jj, da, 0.0), axis=0,
                      keepdims=True)           # (1, L)
    cum = jnp.sum(jnp.where(ii == jj, cum_row, 0.0), axis=1,
                  keepdims=True)               # (L, 1)
    # intra-chunk: w[i,j] = exp(cum_i - cum_j) * (C_i . B_j), j <= i
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (L, L)
    seg = cum - cum_row                        # (L, L) cum_i - cum_j
    w = jnp.where(jj <= ii, jnp.exp(seg) * scores, 0.0)
    xdt = x * dt                               # (L, P)
    y_intra = jax.lax.dot_general(w, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # inter-chunk: y_i += exp(cum_i) * C_i . state
    state = state_scr[...]                     # (N, P)
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        cm, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    # state' = exp(cum_L) * state + sum_j exp(cum_L - cum_j) B_j (x_j dt_j)
    decay_end = jnp.exp(cum[-1:] - cum)        # (L, 1)
    upd = jax.lax.dot_general(bm * decay_end, xdt, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)   # (N, P)
    state_scr[...] = jnp.exp(cum[-1:]) * state + upd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_bh(x, dt, a, bm, cm, *, chunk: int = 128,
           interpret: Optional[bool] = None):
    """x (BH, S, P), dt (BH, S, 1), a (BH, 1, 1), bm/cm (BH, S, N) -> y (BH, S, P).

    The carried state scratch makes the chunk grid sequential, so S must
    be a multiple of chunk — validated with a clear error (``ops.ssd``
    pads with identity steps first).  ``interpret=None`` auto-detects,
    uniformly with the flash/rglru kernels.
    """
    BH, S, P = x.shape
    N = bm.shape[-1]
    validate_block("ssd", "S", S, "chunk", chunk, divides=True)
    interpret = resolve_interpret(interpret)
    nc = S // chunk
    kern = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            # one scalar per (batch, head) as a full-dimension (1, 1)
            # block: a (1, 1) block of a (BH, 1) array is not aligned to
            # the (8, 128) tiling
            pl.BlockSpec((1, 1, 1), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, bm, cm)
