"""Unified model API over all assigned architecture families.

``Model`` exposes:
  param_defs() / init(key)                  declaration + materialization
  loss(params, batch)                       training objective (next-token CE)
  forward(params, batch)                    logits (no cache)
  cache_defs(batch, max_len) / init_cache   decode-state declaration
  prefill(params, batch, cache)             fill cache, return last logits
  decode_step(params, tokens, cache)        one token with cache

Layers are stacked and iterated with ``jax.lax.scan`` (small HLO, fast
compile at 48-64 layers) with ``jax.checkpoint`` rematerialization.
Non-uniform stacks (gemma3 5:1 local:global, recurrentgemma rec-rec-attn,
deepseek first-dense-layer) scan over *groups* with the pattern unrolled
inside the group body.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import logical
from repro.kernels.ssd.decode import busy_rows
from repro.models import layers as L
from repro.models import mla, moe, rglru, ssm
from repro.models.layers import ParamDef


def _norm_def(cfg, lp=()):
    return ParamDef(lp + (cfg.d_model,), ("layers",) * len(lp) + ("w_embed",), cfg.param_dtype, "zeros")


# ---------------------------------------------------------------------------
# Block bodies (single layer).  p is that layer's (unstacked) params.
# ---------------------------------------------------------------------------


def _attn_ffn_block(p, x, cfg, *, kind: str, positions, cache, use_moe: bool,
                    d_ff: Optional[int] = None, seq_lens=None):
    mask = "causal" if kind == "global" else "local"
    if kind == "prefix":
        mask = "prefix"
    window = cfg.local_window if mask == "local" else 0
    with jax.named_scope("attn"):
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.use_mla:
            h, new_c = mla.mla_attention(p["attn"], h, cfg, positions=positions,
                                         cache=cache, seq_lens=seq_lens)
        else:
            h, new_c = L.gqa_attention(
                p["attn"], h, cfg, mask_type=mask, window=window,
                prefix_len=cfg.n_prefix if kind == "prefix" else 0,
                positions=positions, cache=cache, seq_lens=seq_lens)
        x = x + h
    with jax.named_scope("mlp"):
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if use_moe:
            # serving admission (seq_lens set): one dispatch group per row, so
            # expert capacity — a per-group resource — can't couple co-admitted
            # requests' routing (see moe_ffn)
            h = moe.moe_ffn(p["mlp"], h, cfg, row_groups=seq_lens is not None)
        else:
            h = L.ffn(p["mlp"], h, cfg)
        x = x + h
    return logical(x, ("act_batch", "act_seq", "act_embed")), new_c


def _attn_block_defs(cfg, lp, *, use_moe: bool, d_ff=None):
    attn = mla.mla_defs(cfg, lp) if cfg.use_mla else L.gqa_defs(cfg, lp)
    mlp = moe.moe_defs(cfg, lp) if use_moe else L.ffn_defs(cfg, d_ff, lp)
    return {"ln1": _norm_def(cfg, lp), "attn": attn, "ln2": _norm_def(cfg, lp), "mlp": mlp}


def _rec_block(p, x, cfg, *, cache, seq_lens=None):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    h, new_c = rglru.rglru_block(p["rec"], h, cfg, cache=cache,
                                 seq_lens=seq_lens)
    x = x + h
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + L.ffn(p["mlp"], h, cfg)
    return logical(x, ("act_batch", "act_seq", "act_embed")), new_c


def _rec_block_defs(cfg, lp):
    return {"ln1": _norm_def(cfg, lp), "rec": rglru.rglru_defs(cfg, lp),
            "ln2": _norm_def(cfg, lp), "mlp": L.ffn_defs(cfg, None, lp)}


def _mamba_block(p, x, cfg, *, cache, seq_lens=None, layer=None, busy=None):
    with jax.named_scope("ssm"):
        h = L.rms_norm(x, p["ln"], cfg.norm_eps)
        h, new_c = ssm.mamba2_block(p["mix"], h, cfg, cache=cache,
                                    seq_lens=seq_lens, layer=layer, busy=busy)
        x = x + h
    return logical(x, ("act_batch", "act_seq", "act_embed")), new_c


# ---------------------------------------------------------------------------
# Cache defs per layer kind
# ---------------------------------------------------------------------------


def _kv_cache_defs(cfg, batch: int, max_len: int, kind: str, lp=()):
    if cfg.use_mla:
        return mla.mla_cache_defs(cfg, batch, max_len, lp)
    la = ("layers",) * len(lp)
    D = cfg.head_dim
    K = cfg.n_kv_heads
    size = max_len
    if kind == "local" and 0 < cfg.local_window < max_len:
        size = cfg.local_window   # ring buffer
    cdt = cfg.compute_dtype
    return {
        "k": ParamDef(lp + (batch, size, K, D), la + ("cache_batch", "cache_seq", "cache_heads", None), cdt, "zeros"),
        "v": ParamDef(lp + (batch, size, K, D), la + ("cache_batch", "cache_seq", "cache_heads", None), cdt, "zeros"),
        # per-row position vector: each batch row (serve slot) decodes at
        # its own offset, so one decode batch can mix prompt lengths
        "len": ParamDef(lp + (batch,), la + ("cache_batch",), jnp.int32, "zeros"),
    }


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class Model:
    def __init__(self, cfg):
        self.cfg = cfg

    # ---------------- params ----------------

    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        d = {
            "embed": ParamDef((cfg.vocab, cfg.d_model), ("w_vocab", "w_embed_pod"),
                              cfg.param_dtype, "embed"),
            "final_norm": _norm_def(cfg),
        }
        if not cfg.tie_embeddings:
            d["lm_head"] = ParamDef((cfg.d_model, cfg.vocab), ("w_embed_pod", "w_vocab"), cfg.param_dtype)
        if cfg.pos_embed == "learned":
            d["pos_embed"] = ParamDef((cfg.max_position, cfg.d_model), (None, "w_embed_pod"),
                                      cfg.param_dtype, "embed", scale=0.02)

        fam = cfg.family
        if fam in ("dense", "vlm"):
            if cfg.global_every > 0:   # gemma3-style pattern
                n_local = cfg.global_every - 1
                G = cfg.n_layers // cfg.global_every
                d["groups"] = {
                    "local": _attn_block_defs(cfg, (G, n_local), use_moe=False),
                    "global": _attn_block_defs(cfg, (G,), use_moe=False),
                }
            else:
                d["blocks"] = _attn_block_defs(cfg, (cfg.n_layers,), use_moe=False)
        elif fam == "moe":
            nd = cfg.first_dense_layers
            if nd:
                d["dense_blocks"] = _attn_block_defs(cfg, (nd,), use_moe=False, d_ff=cfg.d_ff)
            d["blocks"] = _attn_block_defs(cfg, (cfg.n_layers - nd,), use_moe=True)
        elif fam == "ssm":
            d["blocks"] = {"ln": _norm_def(cfg, (cfg.n_layers,)),
                           "mix": ssm.mamba2_defs(cfg, (cfg.n_layers,))}
        elif fam == "hybrid":
            G = cfg.n_layers // (cfg.pattern_rec + 1)
            tail = cfg.n_layers - G * (cfg.pattern_rec + 1)
            d["groups"] = {
                "rec": _rec_block_defs(cfg, (G, cfg.pattern_rec)),
                "attn": _attn_block_defs(cfg, (G,), use_moe=False),
            }
            if tail:
                d["tail"] = _rec_block_defs(cfg, (tail,))
        elif fam == "encdec":
            d["enc_pos_embed"] = ParamDef((cfg.enc_seq, cfg.d_model), (None, "w_embed_pod"),
                                          cfg.param_dtype, "embed", scale=0.02)
            d["enc_blocks"] = _attn_block_defs(cfg, (cfg.n_enc_layers,), use_moe=False)
            d["enc_norm"] = _norm_def(cfg)
            blocks = _attn_block_defs(cfg, (cfg.n_layers,), use_moe=False)
            blocks["ln_cross"] = _norm_def(cfg, (cfg.n_layers,))
            blocks["cross"] = L.gqa_defs(cfg, (cfg.n_layers,))
            d["blocks"] = blocks
        else:  # pragma: no cover
            raise ValueError(fam)
        return d

    def init(self, key) -> Dict[str, Any]:
        return L.init_tree(self.param_defs(), key)

    def abstract_params(self):
        return L.abstract_tree(self.param_defs())

    # ---------------- embedding / head ----------------

    @jax.named_scope("embed")
    def _embed(self, params, tokens, positions=None):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.compute_dtype)
        if cfg.pos_embed == "learned":
            pos = positions if positions is not None else jnp.arange(tokens.shape[1])
            x = x + jnp.take(params["pos_embed"], pos, axis=0).astype(cfg.compute_dtype)
        return logical(x, ("act_batch", "act_seq", "act_embed"))

    @jax.named_scope("head")
    def _head(self, params, x):
        cfg = self.cfg
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bse,ev->bsv", x, w.astype(cfg.compute_dtype))
        if cfg.final_softcap > 0:
            logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
        return logical(logits, ("act_batch", "act_seq", "act_vocab"))

    # ---------------- stacks ----------------

    def _maybe_remat(self, fn):
        if self.cfg.remat == "none":
            return fn
        policy = None
        if self.cfg.remat == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)

    def _scan_stack(self, body, x, stacked_params, stacked_cache, extras=(),
                    in_place=frozenset()):
        """Scan ``body(p_i, x, c_i) -> (x, c_i')`` over the layer axis.

        At decode (one position per row) the stacked cache rides in the
        scan's carry and each layer writes its new slice back in place, at
        its own index.  Read as ``xs`` and rebuilt as ``ys``, the donated
        cache buffer would serve both, and XLA would copy the whole stack
        before the loop to keep the reads intact.  A longer input keeps the
        ``xs``/``ys`` form: prefill fills a cache made inside the same
        program, so there is no donated buffer to copy, and the carry would
        only add reads of it.

        ``in_place`` names cache leaves that the body steps in place
        itself: at decode it gets them whole, with the layer index as
        ``layer=``, and hands them back whole.  A kernel given the whole
        stack writes only the layer's slice; given a slice, it would read
        a copy."""
        if stacked_cache is not None and x.shape[1] == 1:
            def whole(path):
                return getattr(path[-1], "key", None) in in_place

            def step(carry, inp):
                x, stack = carry
                p_i, i = inp
                c_i = jax.tree_util.tree_map_with_path(
                    lambda path, c: c if whole(path) else
                    jax.lax.dynamic_index_in_dim(c, i, keepdims=False), stack)
                kw = {"layer": i} if in_place else {}
                y, c_new = body(p_i, x, c_i, *extras, **kw)
                # a leaf the layer hands back as it read it (encdec's cross
                # K/V) is left where it is, not written back
                stack = jax.tree_util.tree_map_with_path(
                    lambda path, c, old, new: c if new is old else
                    new if whole(path) else
                    jax.lax.dynamic_update_index_in_dim(c, new.astype(c.dtype), i, 0),
                    stack, c_i, c_new)
                return (y, stack), None

            n = jax.tree.leaves(stacked_params)[0].shape[0]
            (x, stacked_cache), _ = jax.lax.scan(
                self._maybe_remat(step), (x, stacked_cache),
                (stacked_params, jnp.arange(n)))
            return x, stacked_cache

        has_cache = stacked_cache is not None

        def f(carry, inp):
            if has_cache:
                p_i, c_i = inp
                y, c_new = body(p_i, carry, c_i, *extras)
                return y, c_new
            y, _ = body(inp, carry, None, *extras)
            return y, 0.0

        f = self._maybe_remat(f)
        xs = (stacked_params, stacked_cache) if has_cache else stacked_params
        x, ys = jax.lax.scan(f, x, xs)
        return x, (ys if has_cache else None)

    def _run_layers(self, params, x, positions, cache, kind_override=None,
                    enc_out=None, seq_lens=None, active=None):
        cfg = self.cfg
        fam = cfg.family
        new_cache: Dict[str, Any] = {}

        if fam in ("dense", "vlm", "moe"):
            prefix_kind = "prefix" if fam == "vlm" else None

            if cfg.global_every > 0:  # gemma3 grouped pattern
                def group_body(p_g, x, c_g):
                    def local_body(p_i, x, c_i):
                        return _attn_ffn_block(p_i, x, cfg, kind="local",
                                               positions=positions, cache=c_i,
                                               use_moe=False, seq_lens=seq_lens)
                    c_loc = c_g["local"] if c_g is not None else None
                    x, c_loc_new = self._scan_stack(local_body, x, p_g["local"], c_loc)
                    x, c_glob_new = _attn_ffn_block(
                        p_g["global"], x, cfg, kind="global", positions=positions,
                        cache=(c_g["global"] if c_g is not None else None),
                        use_moe=False, seq_lens=seq_lens)
                    if c_g is None:
                        return x, 0.0
                    return x, {"local": c_loc_new, "global": c_glob_new}

                c = cache.get("groups") if cache else None
                x, c_new = self._scan_stack(group_body, x, params["groups"], c)
                if cache is not None:
                    new_cache["groups"] = c_new
            else:
                def body(p_i, x, c_i, use_moe):
                    kind = prefix_kind or ("local" if cfg.local_window > 0 else "global")
                    return _attn_ffn_block(p_i, x, cfg, kind=kind, positions=positions,
                                           cache=c_i, use_moe=use_moe,
                                           seq_lens=seq_lens)

                if "dense_blocks" in params:  # deepseek first dense layer(s)
                    c = cache.get("dense_blocks") if cache else None
                    x, c_new = self._scan_stack(partial(body, use_moe=False), x,
                                                params["dense_blocks"], c)
                    if cache is not None:
                        new_cache["dense_blocks"] = c_new
                c = cache.get("blocks") if cache else None
                x, c_new = self._scan_stack(partial(body, use_moe=(fam == "moe")), x,
                                            params["blocks"], c)
                if cache is not None:
                    new_cache["blocks"] = c_new

        elif fam == "ssm":
            # the busy rows, compacted once for every layer's kernel
            busy = None if active is None else busy_rows(active)

            def body(p_i, x, c_i, layer=None):
                return _mamba_block(p_i, x, cfg, cache=c_i, seq_lens=seq_lens,
                                    layer=layer, busy=busy)
            c = cache.get("blocks") if cache else None
            x, c_new = self._scan_stack(body, x, params["blocks"], c,
                                        in_place={"ssm"})
            if cache is not None:
                new_cache["blocks"] = c_new

        elif fam == "hybrid":
            def group_body(p_g, x, c_g):
                def rec_body(p_i, x, c_i):
                    return _rec_block(p_i, x, cfg, cache=c_i, seq_lens=seq_lens)
                c_rec = c_g["rec"] if c_g is not None else None
                x, c_rec_new = self._scan_stack(rec_body, x, p_g["rec"], c_rec)
                x, c_attn_new = _attn_ffn_block(
                    p_g["attn"], x, cfg, kind="local", positions=positions,
                    cache=(c_g["attn"] if c_g is not None else None),
                    use_moe=False, seq_lens=seq_lens)
                if c_g is None:
                    return x, 0.0
                return x, {"rec": c_rec_new, "attn": c_attn_new}

            c = cache.get("groups") if cache else None
            x, c_new = self._scan_stack(group_body, x, params["groups"], c)
            if cache is not None:
                new_cache["groups"] = c_new
            if "tail" in params:
                def rec_body(p_i, x, c_i):
                    return _rec_block(p_i, x, cfg, cache=c_i, seq_lens=seq_lens)
                c = cache.get("tail") if cache else None
                x, c_new = self._scan_stack(rec_body, x, params["tail"], c)
                if cache is not None:
                    new_cache["tail"] = c_new

        elif fam == "encdec":
            def body(p_i, x, c_i):
                # self attention (causal, cached) + cross attention + ffn
                h = L.rms_norm(x, p_i["ln1"], cfg.norm_eps)
                sc = c_i["self"] if c_i is not None else None
                h, new_self = L.gqa_attention(p_i["attn"], h, cfg, mask_type="causal",
                                              positions=positions, cache=sc,
                                              seq_lens=seq_lens)
                x = x + h
                h = L.rms_norm(x, p_i["ln_cross"], cfg.norm_eps)
                cdt = cfg.compute_dtype
                if c_i is not None:
                    ck, cv = c_i["cross_k"].astype(cdt), c_i["cross_v"].astype(cdt)
                else:
                    ck = jnp.einsum("bse,ekd->bskd", enc_out, p_i["cross"]["wk"].astype(cdt))
                    cv = jnp.einsum("bse,ekd->bskd", enc_out, p_i["cross"]["wv"].astype(cdt))
                h, _ = L.gqa_attention(p_i["cross"], h, cfg, mask_type="full",
                                       positions=positions, cross_kv=(ck, cv))
                x = x + h
                h = L.rms_norm(x, p_i["ln2"], cfg.norm_eps)
                x = x + L.ffn(p_i["mlp"], h, cfg)
                x = logical(x, ("act_batch", "act_seq", "act_embed"))
                if c_i is None:
                    return x, 0.0
                return x, {"self": new_self, "cross_k": c_i["cross_k"], "cross_v": c_i["cross_v"]}

            c = cache.get("blocks") if cache else None
            x, c_new = self._scan_stack(body, x, params["blocks"], c)
            if cache is not None:
                new_cache["blocks"] = c_new
        else:  # pragma: no cover
            raise ValueError(fam)

        return x, (new_cache if cache is not None else None)

    # ---------------- encoder (whisper) ----------------

    def encode(self, params, frames):
        """frames (B, enc_seq, d_model) precomputed (stub frontend)."""
        cfg = self.cfg
        x = frames.astype(cfg.compute_dtype) + params["enc_pos_embed"].astype(cfg.compute_dtype)
        x = logical(x, ("act_batch", "act_frames", "act_embed"))

        def body(p_i, x, c_i):
            h = L.rms_norm(x, p_i["ln1"], cfg.norm_eps)
            h, _ = L.gqa_attention(p_i["attn"], h, cfg, mask_type="full")
            x = x + h
            h = L.rms_norm(x, p_i["ln2"], cfg.norm_eps)
            x = x + L.ffn(p_i["mlp"], h, cfg)
            return logical(x, ("act_batch", "act_frames", "act_embed")), None

        x, _ = self._scan_stack(body, x, params["enc_blocks"], None)
        return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)

    # ---------------- public API ----------------

    def forward(self, params, batch, positions=None):
        return self._head(params, self._hidden(params, batch, positions))

    def loss(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.opt_ce_chunk > 0:
            # chunked cross-entropy: never materialize the full (B, S, V)
            # fp32 logits — scan over sequence chunks, recomputing each
            # chunk's logits (cheap vs the HBM saved; §Perf cell C).
            hidden = self._hidden(params, batch)
            if cfg.family == "vlm":
                hidden = hidden[:, cfg.n_prefix:]
            hid = hidden[:, :-1]
            targets = tokens[:, 1:]
            B, Sm1, E = hid.shape
            C = min(cfg.opt_ce_chunk, Sm1)
            pad = (C - Sm1 % C) % C
            hid = jnp.pad(hid, ((0, 0), (0, pad), (0, 0)))
            tgt = jnp.pad(targets, ((0, 0), (0, pad)))
            valid = jnp.pad(jnp.ones((B, Sm1), jnp.float32), ((0, 0), (0, pad)))
            nc = (Sm1 + pad) // C
            hid = hid.reshape(B, nc, C, E).swapaxes(0, 1)
            tgt = tgt.reshape(B, nc, C).swapaxes(0, 1)
            valid = valid.reshape(B, nc, C).swapaxes(0, 1)

            def body(acc, inp):
                h, t, vl = inp
                lg = self._head(params, h).astype(jnp.float32)
                logz = jax.nn.logsumexp(lg, axis=-1)
                gold = jnp.take_along_axis(lg, t[..., None], axis=-1)[..., 0]
                return acc + jnp.sum((logz - gold) * vl), None

            total, _ = jax.lax.scan(body, jnp.float32(0.0), (hid, tgt, valid))
            loss = total / (B * Sm1)
            return loss, {"loss": loss, "ppl": jnp.exp(loss)}

        logits = self.forward(params, batch)
        if cfg.family == "vlm":  # predict text tokens only (after the prefix)
            logits = logits[:, cfg.n_prefix:]
        targets = tokens[:, 1:]
        lg = logits[:, :-1].astype(jnp.float32)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
        nll = logz - gold
        loss = jnp.mean(nll)
        return loss, {"loss": loss, "ppl": jnp.exp(loss)}

    def _hidden(self, params, batch, positions=None):
        """Final-norm'd hidden states (forward without the LM head)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens, positions)
        enc_out = None
        if cfg.family == "encdec":
            enc_out = self.encode(params, batch["frames"])
        if cfg.family == "vlm":
            pe = batch["patch_embeds"].astype(cfg.compute_dtype)
            x = jnp.concatenate([pe, x], axis=1)
            x = logical(x, ("act_batch", "act_seq", "act_embed"))
        if positions is None:
            positions = jnp.arange(x.shape[1])
        x, _ = self._run_layers(params, x, positions, None, enc_out=enc_out)
        return x

    # ---------------- caches ----------------

    def cache_defs(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        fam = cfg.family
        d: Dict[str, Any] = {}
        if fam in ("dense", "vlm", "moe"):
            if cfg.global_every > 0:
                G = cfg.n_layers // cfg.global_every
                n_local = cfg.global_every - 1
                d["groups"] = {
                    "local": _kv_cache_defs(cfg, batch, max_len, "local", (G, n_local)),
                    "global": _kv_cache_defs(cfg, batch, max_len, "global", (G,)),
                }
            else:
                kind = "local" if cfg.local_window else "global"
                nd = cfg.first_dense_layers
                if nd:
                    d["dense_blocks"] = _kv_cache_defs(cfg, batch, max_len, kind, (nd,))
                d["blocks"] = _kv_cache_defs(cfg, batch, max_len, kind, (cfg.n_layers - nd,))
        elif fam == "ssm":
            d["blocks"] = ssm.mamba2_cache_defs(cfg, batch, (cfg.n_layers,))
        elif fam == "hybrid":
            G = cfg.n_layers // (cfg.pattern_rec + 1)
            tail = cfg.n_layers - G * (cfg.pattern_rec + 1)
            d["groups"] = {
                "rec": rglru.rglru_cache_defs(cfg, batch, (G, cfg.pattern_rec)),
                "attn": _kv_cache_defs(cfg, batch, max_len, "local", (G,)),
            }
            if tail:
                d["tail"] = rglru.rglru_cache_defs(cfg, batch, (tail,))
        elif fam == "encdec":
            blocks = {"self": _kv_cache_defs(cfg, batch, max_len, "global", (cfg.n_layers,))}
            la = ("layers", "cache_batch", "cache_seq", "cache_heads", None)
            shp = (cfg.n_layers, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.head_dim)
            blocks["cross_k"] = ParamDef(shp, la, cfg.compute_dtype, "zeros")
            blocks["cross_v"] = ParamDef(shp, la, cfg.compute_dtype, "zeros")
            d["blocks"] = {"self": blocks["self"], "cross_k": blocks["cross_k"],
                           "cross_v": blocks["cross_v"]}
        return d

    def init_cache(self, batch: int, max_len: int):
        return jax.tree.map(lambda dd: jnp.zeros(dd.shape, dd.dtype),
                            self.cache_defs(batch, max_len),
                            is_leaf=lambda v: isinstance(v, ParamDef))

    def prefill(self, params, batch, cache, lengths=None):
        """Run the prompt through the model writing the cache.

        Returns (last-position logits, filled cache).

        ``lengths`` (B,) enables right-padded batched prefill (the serve
        engine's bucketed admission): row r's prompt occupies
        ``tokens[r, :lengths[r]]``, pad columns beyond it are masked out of
        attention / recurrent state, per-row cache ``len`` vectors advance
        by the *valid* length, and the returned logits are each row's
        last-valid-position logits.  ``lengths == S`` for every row
        reproduces the unpadded path value-for-value.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens)
        if cfg.family == "encdec":
            enc_out = self.encode(params, batch["frames"])
            cache = self._fill_cross(params, cache, enc_out)
        if cfg.family == "vlm":
            pe = batch["patch_embeds"].astype(cfg.compute_dtype)
            x = jnp.concatenate([pe, x], axis=1)
        positions = jnp.arange(x.shape[1])
        seq_lens = None
        if lengths is not None:
            # valid length in layer coordinates includes the vlm prefix
            seq_lens = lengths + (cfg.n_prefix if cfg.family == "vlm" else 0)
        x, cache = self._run_layers(params, x, positions, cache,
                                    seq_lens=seq_lens)
        if seq_lens is None:
            logits = self._head(params, x[:, -1:])
        else:
            last = jnp.take_along_axis(x, (seq_lens - 1)[:, None, None], axis=1)
            logits = self._head(params, last)
        return logits, cache

    def _fill_cross(self, params, cache, enc_out):
        cfg = self.cfg
        cdt = cfg.compute_dtype

        def proj(wk, wv):
            return (jnp.einsum("bse,ekd->bskd", enc_out, wk.astype(cdt)),
                    jnp.einsum("bse,ekd->bskd", enc_out, wv.astype(cdt)))

        ck, cv = jax.vmap(proj, in_axes=0, out_axes=0)(
            params["blocks"]["cross"]["wk"], params["blocks"]["cross"]["wv"])
        blocks = dict(cache["blocks"])
        blocks["cross_k"] = ck.astype(cache["blocks"]["cross_k"].dtype)
        blocks["cross_v"] = cv.astype(cache["blocks"]["cross_v"].dtype)
        return {**cache, "blocks": blocks}

    def decode_step(self, params, tokens, cache, active=None):
        """tokens (B, 1) -> (logits (B,1,V), new cache).

        Positions are per-row: each batch row decodes at its own cache
        offset (the ``len`` vector), so a continuous-batching decode step
        can mix rows whose prompts had different lengths.

        ``active`` (B,) bool marks the rows that decode: the ssm family
        leaves every other row's SSM state as it is (a free row's logits
        mean nothing); every other family ignores it, and a jit prunes it."""
        pos = self._cache_len(cache)            # (B,)
        positions = pos[:, None] + jnp.arange(1)  # (B, 1)
        x = self._embed(params, tokens, positions)
        x, cache = self._run_layers(params, x, positions, cache, active=active)
        return self._head(params, x), cache

    def _cache_len(self, cache):
        """The per-row position vector (B,) from the first "len" leaf
        (all layers' counters advance identically)."""
        lens = [v for k, v in jax.tree_util.tree_flatten_with_path(cache)[0]
                if k and getattr(k[-1], "key", None) == "len"]
        x = lens[0]
        return x.reshape(-1, x.shape[-1])[0] if x.ndim > 1 else x


def build_model(cfg) -> Model:
    return Model(cfg)
