"""Mamba-2 SSD decode step as one Pallas TPU kernel over the stacked state.

One decode step of one layer updates that layer's float32 state,
``h' = exp(dt·A)·h + (dt·x)⊗B``, and reduces it with C, ``y = Σ_n h'·C``.
Written in XLA (``repro.models.ssm.ssd_step``), the update is an in-place
dynamic-update-slice of the stack and ``y`` a second fusion that recomputes
``h'`` from the old state, so each step reads the state twice.  This kernel
holds each slot's (H, P, N) tile of the state in VMEM once: it reads the
tile, writes ``h'`` back to the same place and reduces it with C.

It takes the whole stack (L, B, H, P, N) and the layer index as a scalar
prefetch, and aliases the stack to its output, so only the layer's slice
is written and every other slice keeps its values.  A slice of the stack
passed to a custom call would be materialised instead: a copy of the
layer's state in and out of every call.

Grid: (B,), one slot's state of the layer, a (H, P, N) tile, per step.
On one TPU v5e a 64-layer, 16-slot mamba2-2.7b stack (H=80) took 10.43,
8.90, 8.59 and 8.58 ms per decode step with tiles of 8, 16, 40 and 80
heads (40 steps back to back), against 12.21 ms for XLA's update and
separate ``y`` pass: small tiles pay for their many grid steps, and 40
and 80 heads tie, so a slot's whole layer is the tile.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.validate import resolve_interpret

#: Scoped VMEM for the pipeline: the state tile in and out, each double
#: buffered, is 4 × 2.6 MB at mamba2-2.7b's 80 heads (11.2 MB in all).
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _kernel(layer_ref, h_ref, x_ref, dt_ref, da_ref, b_ref, c_ref,
            h_out_ref, y_ref):
    del layer_ref                                  # used by the index maps
    xdt = x_ref[...].astype(jnp.float32) * dt_ref[...]          # (H, P)
    h = (da_ref[...][:, :, None] * h_ref[...]
         + xdt[:, :, None] * b_ref[...].astype(jnp.float32)[None])
    h_out_ref[...] = h                                         # (H, P, N)
    y = jnp.sum(h * c_ref[...].astype(jnp.float32)[None], axis=-1)
    y_ref[...] = y.astype(y_ref.dtype)


def ssd_decode(stack, layer, x, dt, A, bm, cm, *,
               interpret: Optional[bool] = None):
    """One SSD decode step on layer ``layer`` of a stacked state.

    stack (L, B, H, P, N) float32, layer a scalar int, x (B, H, P),
    dt (B, H) (post-softplus), A (H,), bm/cm (B, N) -> (y (B, H, P) in
    x's dtype, the stack with layer ``layer`` stepped).  Matches
    ``repro.models.ssm.ssd_step`` on that layer's slice."""
    B, H, P, N = stack.shape[1:]
    da = jnp.exp(dt.astype(jnp.float32) * A)
    tile = lambda b, l: (l[0], b, 0, 0, 0)             # noqa: E731
    row = lambda b, l: (b, 0, 0)                       # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, None, H, P, N), tile),
            pl.BlockSpec((None, H, P), row),
            # per-head scalars as (H, 1) columns
            pl.BlockSpec((None, H, 1), row),
            pl.BlockSpec((None, H, 1), row),
            pl.BlockSpec((None, 1, N), row),
            pl.BlockSpec((None, 1, N), row),
        ],
        out_specs=[
            pl.BlockSpec((None, None, H, P, N), tile),
            pl.BlockSpec((None, H, P), row),
        ])
    stack, y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(stack.shape, stack.dtype),
                   jax.ShapeDtypeStruct(x.shape, x.dtype)],
        input_output_aliases={1: 0},       # the stack, after the prefetch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name="ssd_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), stack, x,
      dt.astype(jnp.float32)[..., None], da[..., None],
      bm[:, None], cm[:, None])
    return y, stack
