"""Public SSD op: (B, S, H, P) model layout -> kernel layout + padding."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_bh
from repro.kernels.validate import dtype_name, validate_block


def _tuned_chunk(S: int, P: int, N: int, dtype):
    """Tuning-DB lookup keyed on the *unpadded* (S, P, N) signature (None
    on miss or if a stale entry no longer validates as a bound)."""
    from repro.tuning.db import tuned_params

    t = tuned_params("ssd", f"S{S},P{P},N{N}", dtype_name(dtype))
    if not t:
        return None
    try:
        return validate_block("ssd", "S", S, "chunk", t["chunk"])
    except (KeyError, ValueError):
        return None


def ssd(x, dt, A, Bm, Cm, *, chunk: Optional[int] = None,
        interpret: Optional[bool] = None):
    """Model-layout SSD: x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N).

    Matches repro.models.ssm.ssd_chunked / ssd_sequential (zero init state).

    ``chunk`` defaults to ``None``: the tuning DB is consulted for this
    (shape, dtype) at trace time, falling back to ``min(128, S)``.  An
    explicit chunk is validated as a bound (``1 <= chunk <= S``) and S is
    padded up to a multiple (identity steps), so the kernel's
    divisibility requirement always holds; an invalid chunk raises,
    never clamps.  ``interpret=None`` resolves in the kernel layer.
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    if chunk is None:
        chunk = _tuned_chunk(S, P, N, x.dtype)
    if chunk is None:
        L = min(128, S)
    else:
        L = validate_block("ssd", "S", S, "chunk", chunk)
    pad = (L - S % L) % L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, Sp, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, Sp, 1)
    af = jnp.broadcast_to(A[None, :], (B, H)).reshape(B * H, 1, 1)
    bf = jnp.repeat(Bm[:, None], H, axis=1).reshape(B * H, Sp, N)
    cf = jnp.repeat(Cm[:, None], H, axis=1).reshape(B * H, Sp, N)
    y = ssd_bh(xf, dtf, af, bf, cf, chunk=L, interpret=interpret)
    return y.reshape(B, H, Sp, P).transpose(0, 2, 1, 3)[:, :S]
