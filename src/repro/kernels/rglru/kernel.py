"""RG-LRU blocked-scan Pallas TPU kernel.

A diagonal gated linear recurrence h_t = a_t h_{t-1} + b_t.  The TPU
formulation avoids a per-token sequential loop: within a time block of
length L the solution is

    h_i = exp(cum_i) * h_prev + sum_{j<=i} exp(cum_i - cum_j) * b_j

computed as an (L x L x lane-tile) masked decay-weighted reduction (VPU
work, vectorized over the feature lanes); the carried state h_prev lives in
VMEM scratch across the sequential block grid dimension.  L is kept small
(16-32) so the L^2 term stays in VMEM and the exp(cum_i - cum_j) differences
stay in fp32 range.

Grid: (B, n_feature_tiles, n_time_blocks) — time innermost (sequential).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.validate import resolve_interpret, validate_block


def _rglru_kernel(a_ref, b_ref, h_ref, state_scr, *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    a = a_ref[0].astype(jnp.float32)          # (L, D)
    b = b_ref[0].astype(jnp.float32)          # (L, D)
    log_a = jnp.log(jnp.maximum(a, 1e-37))
    ii = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t, 1), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t, 1), 1)
    lower = jj <= ii
    # inclusive prefix sum over time as a lower-triangular masked reduction
    # (the TPU lowering has no cumsum)
    cum = jnp.sum(jnp.where(lower, log_a[None, :, :], 0.0), axis=1)  # (L, D)
    # decay(i, j) = exp(cum_i - cum_j) for j <= i  (per feature lane)
    seg = cum[:, None, :] - cum[None, :, :]   # (L, L, D)
    w = jnp.where(lower, jnp.exp(seg), 0.0)
    h = jnp.sum(w * b[None, :, :], axis=1)    # (L, D)
    h = h + jnp.exp(cum) * state_scr[...]
    h_ref[0] = h.astype(h_ref.dtype)
    state_scr[...] = h[block_t - 1:]          # (1, D) carried state


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "interpret"))
def rglru_scan_kernel(a, b, *, block_t: int = 16, block_d: int = 128,
                      interpret: Optional[bool] = None):
    """a, b (B, S, D) -> h (B, S, D); h_t = a_t h_{t-1} + b_t, h_0 = b_0.

    The carried state scratch makes the time grid sequential, so blocks
    must divide their dimensions exactly — validated with a clear error
    (``ops.rglru`` pads to a multiple first; direct callers and tuning
    candidates must pass dividing blocks).  ``interpret=None``
    auto-detects, uniformly with the flash/ssd kernels.
    """
    B, S, D = a.shape
    validate_block("rglru", "S", S, "block_t", block_t, divides=True)
    validate_block("rglru", "D", D, "block_d", block_d, divides=True)
    interpret = resolve_interpret(interpret)
    nt = S // block_t
    nd = D // block_d
    kern = functools.partial(_rglru_kernel, block_t=block_t)
    return pl.pallas_call(
        kern,
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda bb, d, t: (bb, t, d)),
            pl.BlockSpec((1, block_t, block_d), lambda bb, d, t: (bb, t, d)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_d), lambda bb, d, t: (bb, t, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(a, b)
