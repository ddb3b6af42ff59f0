"""Operations and bytes that a dense GQA decoder's serving work needs.

Counted from the configuration's shapes, not from what the program does:
a decode step reads every weight once (the embedding only for the rows it
looks up) and the keys and values of the valid positions of the rows that
are decoding; an admission reads the weights once per call and writes the
keys and values of the valid prompt tokens.  Padding, idle rows and the
unused tail of the cache are waste, and count for nothing.
"""
from __future__ import annotations

from typing import Sequence, Tuple

from chipbench.reference.dense import dims

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def params(cfg: dict) -> dict:
    """Parameter counts: per layer (matmuls, norms), embedding, head."""
    d = dims(cfg)
    E, H, K, D, F = d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff
    return {"layer_matmul": E * H * D * 2 + E * K * D * 2 + 3 * E * F,
            "layer_norm": 2 * E, "embed": d.vocab * E, "head": E * d.vocab,
            "final_norm": E}


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight as served."""
    d, p = dims(cfg), params(cfg)
    n = d.layers * (p["layer_matmul"] + p["layer_norm"]) + p["embed"] + p["head"] + p["final_norm"]
    return n * BYTES[d.dtype]


def kv_bytes_per_token(cfg: dict) -> int:
    d = dims(cfg)
    return d.layers * 2 * d.kv_heads * d.head_dim * BYTES[d.dtype]


def _token_flops(cfg: dict) -> int:
    """Matmul operations per token through every layer, head excluded."""
    return 2 * dims(cfg).layers * params(cfg)["layer_matmul"]


def decode_step(cfg: dict, kv_lens: Sequence[int]) -> Tuple[float, float]:
    """(flops, bytes) one decode step needs for rows that attend over
    ``kv_lens`` keys each (the new token's included)."""
    d, p = dims(cfg), params(cfg)
    b = BYTES[d.dtype]
    rows = len(kv_lens)
    keys = sum(kv_lens)
    flops = (rows * (_token_flops(cfg) + 2 * p["head"])
             + 4 * d.layers * d.heads * d.head_dim * keys)
    weights = (d.layers * (p["layer_matmul"] + p["layer_norm"]) + p["head"]
               + p["final_norm"] + rows * d.d_model) * b
    # the cached keys are read and the new token's are written
    return float(flops), float(weights + keys * kv_bytes_per_token(cfg))


def prefill(cfg: dict, prompt_lens: Sequence[int], calls: int) -> Tuple[float, float]:
    """(flops, bytes) that admitting prompts of ``prompt_lens`` tokens in
    ``calls`` admission calls needs: causal attention over the valid
    tokens, the head at each row's last token, the weights read once per
    call, and the valid tokens' keys and values written."""
    d, p = dims(cfg), params(cfg)
    b = BYTES[d.dtype]
    tokens = sum(prompt_lens)
    pairs = sum(n * (n + 1) // 2 for n in prompt_lens)
    flops = (tokens * _token_flops(cfg) + len(prompt_lens) * 2 * p["head"]
             + 4 * d.layers * d.heads * d.head_dim * pairs)
    weights = calls * (d.layers * (p["layer_matmul"] + p["layer_norm"]) + p["head"]
                       + p["final_norm"]) * b + tokens * d.d_model * b
    return float(flops), float(weights + tokens * kv_bytes_per_token(cfg))

