"""Operations and bytes that Mamba-2's serving work needs.

Counted from the configuration's shapes: a decode step reads every weight
once (the tied embedding as the head) and reads and writes the SSM and conv
state of the rows that are decoding; an admission reads the weights once
per call, runs the chunked SSD algorithm (at the published chunk size) over
the valid prompt tokens only, and writes each admitted row's state.
Padding and idle rows count for nothing.
"""
from __future__ import annotations

from typing import Sequence, Tuple

from chipbench.reference.ssm import dims

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
F32 = 4


def params(cfg: dict) -> dict:
    """Parameter counts: per layer (matmuls, the rest), embedding."""
    d = dims(cfg)
    E, N, H, di, C = d.d_model, d.d_state, d.heads, d.d_inner, d.conv_ch
    return {"layer_matmul": E * (2 * di + 2 * N + H) + di * E,
            "layer_other": E + d.d_conv * C + C + di,      # ln, conv, out norm
            "layer_f32": 3 * H,                            # A_log, D, dt_bias
            "embed": d.vocab * E, "final_norm": E}


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight as served."""
    d, p = dims(cfg), params(cfg)
    b = BYTES[d.dtype]
    return (d.layers * ((p["layer_matmul"] + p["layer_other"]) * b + p["layer_f32"] * F32)
            + (p["embed"] + p["final_norm"]) * b)


def state_bytes_per_row(cfg: dict) -> int:
    """SSM state (float32) and conv state (served dtype) of one row."""
    d = dims(cfg)
    return d.layers * (d.heads * d.headdim * d.d_state * F32
                       + (d.d_conv - 1) * d.conv_ch * BYTES[d.dtype])


def _ssd_flops(cfg: dict, n: int) -> int:
    """Chunked SSD operations for one row of ``n`` valid tokens in one
    layer: within each chunk of length l, C B^T and its causal weighting
    of x (l(l+1)/2 pairs each), and the chunk's state in and out."""
    d = dims(cfg)
    N, HP = d.d_state, d.heads * d.headdim
    q = cfg["ssm_cfg"]["chunk_size"]
    total = 0
    for start in range(0, n, q):
        ln = min(q, n - start)
        pairs = ln * (ln + 1) // 2
        total += 2 * pairs * (N + HP) + 4 * ln * HP * N
    return total


def decode_step(cfg: dict, kv_lens: Sequence[int]) -> Tuple[float, float]:
    """(flops, bytes) one decode step needs for ``len(kv_lens)`` decoding
    rows (their context lengths do not change an SSM's work)."""
    d, p = dims(cfg), params(cfg)
    rows = len(kv_lens)
    per_token = (2 * d.layers * p["layer_matmul"] + 2 * p["embed"]
                 + d.layers * (5 * d.heads * d.headdim * d.d_state
                               + 2 * d.d_conv * d.conv_ch))
    weights = weight_bytes(cfg)
    return float(rows * per_token), float(weights + 2 * rows * state_bytes_per_row(cfg))


def prefill(cfg: dict, prompt_lens: Sequence[int], calls: int) -> Tuple[float, float]:
    """(flops, bytes) that admitting prompts of ``prompt_lens`` tokens in
    ``calls`` admission calls needs."""
    d, p = dims(cfg), params(cfg)
    tokens = sum(prompt_lens)
    flops = (tokens * d.layers * (2 * p["layer_matmul"] + 2 * d.d_conv * d.conv_ch)
             + d.layers * sum(_ssd_flops(cfg, n) for n in prompt_lens)
             + len(prompt_lens) * 2 * p["embed"])
    return float(flops), float(calls * weight_bytes(cfg)
                               + len(prompt_lens) * state_bytes_per_row(cfg))
