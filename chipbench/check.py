"""The comparison that decides ``correct``: served tokens against the plain
float32 reference.

Once the window has closed, a sample of the requests it finished, drawn from
the seed and always holding the longest, is run through the reference as
whole sequences (prompt, then every served token but the last).  At each
position that produced a served token, the reference's logits say how far
that token lies below the reference's best one; the widest of these gaps
over the sample is the number compared.  Greedy decoding in the served
precision puts first a token that the exact model ranks first or nearly
ties, so the gap stays small; a cache written wrong, a state not carried,
or a token altered after it was chosen gives a gap of the size of the
logits' spread.

The control reads the same gap for the token that the reference computed in
float8 puts first at each of those positions, and ``verdict`` judges it
as it judges a run.

Every array the reference sees has a shape fixed by the cell (rows, the
mix's longest prompt plus output, its longest output), so nothing compiles
per sample.  (The positions are a multiple of ``LOGIT_CHUNK`` or fewer.)
"""
from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: the stream, beside the run's seed, that draws the sample
SAMPLE_STREAM = 7002

#: positions whose logits are computed together
LOGIT_CHUNK = 512


def failed_requests(requests: Sequence, vocab: int) -> int:
    """Requests that never finished, stopped short of their output budget,
    or were given a token outside the vocabulary."""
    return sum(1 for r in requests if not r.done or len(r.out) != r.max_new
               or any(not 0 <= t < vocab for t in r.out))


def verdict(cell, gap, failed: int):
    """``(correct, checks)``: correct when the widest gap lies within the
    cell's limit and no request failed; ``checks`` holds each number
    compared beside its limit."""
    limit = cell.check["limits"]["max_logit_gap"]
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    return gap is not None and gap <= limit and failed == 0, checks


def sample(requests: Sequence, rows: int, seed: int) -> List:
    """``rows`` finished requests: the longest (prompt plus output), and the
    rest drawn from ``seed``."""
    done = [r for r in requests if r.done]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].prompt) + len(done[i].out))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed, SAMPLE_STREAM])
    pick = rng.choice(len(rest), size=min(rows - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


@jax.jit
def _take(h, pos):
    """Hidden states h (B, L, E) at positions pos (B, P) -> (B * P, E)."""
    x = jnp.take_along_axis(h, pos[..., None], axis=1)
    return x.reshape(-1, h.shape[-1])


@jax.jit
def _gap(lg, tok):
    """How far the logit of ``tok`` lies below the row's best."""
    return jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]


def readings(cell, weights, reqs: Sequence, control: bool = False) -> Dict[str, float]:
    """``{"served": widest gap of a served token}``, and with ``control``
    also ``"control"``: the widest gap of the token the float8 reference
    puts first, at the same positions."""
    ref, cfg = cell.reference(), cell.config
    length, width = cell.mix.max_len(), cell.mix.output.max
    block = max(1, min(ref.block_rows(cfg, length), len(reqs)))
    rows = -(-len(reqs) // block) * block
    tokens = np.zeros((rows, length), np.int32)
    pos = np.zeros((rows, width), np.int32)
    tgt = np.zeros((rows, width), np.int32)
    valid = np.zeros((rows, width), bool)
    for i, r in enumerate(reqs):
        n, p = len(r.out), len(r.prompt)
        tokens[i, :p] = r.prompt
        tokens[i, p: p + n - 1] = r.out[:-1]
        pos[i, :n] = np.arange(p - 1, p - 1 + n)
        tgt[i, :n] = r.out
        valid[i, :n] = True
    served, ctrl = [], []
    for b in range(0, rows, block):
        sl = slice(b, b + block)
        x32 = _take(ref.hidden(cfg, weights, tokens[sl]), jnp.asarray(pos[sl]))
        x8 = (_take(ref.hidden(cfg, weights, tokens[sl], quant=True), jnp.asarray(pos[sl]))
              if control else None)
        t = jnp.asarray(tgt[sl].reshape(-1))
        for s in range(0, x32.shape[0], LOGIT_CHUNK):
            n = min(LOGIT_CHUNK, x32.shape[0])
            lg = ref.logits(cfg, weights, jax.lax.dynamic_slice_in_dim(x32, s, n))
            served.append(np.asarray(_gap(lg, jax.lax.dynamic_slice_in_dim(t, s, n))))
            if control:
                lg8 = ref.logits(cfg, weights, jax.lax.dynamic_slice_in_dim(x8, s, n),
                                 quant=True)
                ctrl.append(np.asarray(_gap(lg, jnp.argmax(lg8, axis=-1).astype(jnp.int32))))
    keep = valid.reshape(-1)
    out = {"served": float(np.concatenate(served)[keep].max())}
    if control:
        out["control"] = float(np.concatenate(ctrl)[keep].max())
    return out


def control_runs(cell, seeds: Iterable[int]) -> Iterator[Dict]:
    """For each seed: the cell's weights, one replay of its traffic through
    the engine, and ``verdict`` twice over the cell's sample, once for the
    served tokens (the program) and once for the tokens the float8
    reference puts first in the program's place (the control, which emits
    every token it is asked for).  One engine serves every seed."""
    import time

    from repro.core.suite import Built
    from repro.launch.serve import ServeEngine
    from repro.models import build_model

    from chipbench import traffic

    ref, pcfg = cell.reference(), cell.program_config()
    vocab = cell.config["vocab_size"]
    engine = None
    for seed in seeds:
        t0 = time.perf_counter()
        if engine is not None:
            engine.params = None
        weights = ref.init_weights(cell.config, seed)
        if engine is None:
            engine = ServeEngine(Built(cfg=pcfg, model=build_model(pcfg), params=weights),
                                 slots=cell.slots, max_len=cell.mix.max_len())
            engine.run(traffic.warm_requests(cell.mix, cell.slots, vocab))
        engine.params = weights
        reqs = traffic.replay(cell.mix, cell.slots, vocab, seed, 0)
        engine.run(reqs)
        t1 = time.perf_counter()
        picked = sample(reqs, int(cell.check["rows"]), seed)
        r = readings(cell, weights, picked, control=True)
        ok, checks = verdict(cell, r["served"], failed_requests(reqs, pcfg.vocab))
        ok_c, checks_c = verdict(cell, r["control"], 0)
        yield {"workload": cell.name, "seed": seed,
               "program": {"correct": ok, "checks": checks},
               "control": {"correct": ok_c, "checks": checks_c},
               "served_tokens": sum(len(q.out) for q in picked),
               "serve_s": t1 - t0, "reference_s": time.perf_counter() - t1}
        del weights
