"""Continuous-batching inference serving engine (the ``task="serve"``
workload).

A minimal production-shaped server: a request queue with virtual-time
arrivals, a batched prefill admission stage, and a batched decode loop
with per-slot completion and refill (continuous batching).  Runs reduced
configs on CPU (examples, tests) and full configs on a TPU mesh via the
same code path.

Admission (PR 8): each loop iteration admits one *wave* — every waiting
request paired with a free slot — through ONE jitted prefill call per
prompt-length bucket (``admission="batched"``, the default).  Prompts
are right-padded into power-of-two length buckets and row counts rounded
to powers of two, so the number of compiled prefill shapes is bounded by
the bucket grid (buckets x log2(slots)), not by the number of distinct
prompt lengths; per-request masks/gathers inside the model make the
padded rows exact, so tokens are byte-identical to the
``admission="single"`` per-request baseline (kept as an engine flag and
scenario axis for A/B measurement — ``benchmarks/loadgen_curve.py``
sweeps both policies side by side).

Layering (ISSUE 3):

* ``ServeEngine`` is the engine proper.  It accepts a prebuilt
  ``repro.core.suite.Built`` (config + model + params) so the
  BenchmarkRunner's arch-build cache is shared between serve cells and
  the train/infer cells of the same arch — the engine never builds
  models itself.
* Request traces come from ``repro.runner.traces``: deterministic load
  profiles (uniform / bursty / mixed arrivals, optionally crossed with a
  prompt-length profile as ``"bursty+bimodal"``) whose arrivals are
  expressed in decode-step *virtual time*, so generated tokens are a pure
  function of (trace spec, params) — identical on every replay.
  Per-slot position vectors in the KV cache let one decode batch mix
  prompt lengths; ``capture()`` turns a served trace back into a
  replayable spec.
* Latency distributions (TTFT and per-token p50/p95/p99) are produced by
  ``summarize_metrics`` on the engine's raw per-request timestamps,
  using the shared ``repro.runner.latency`` percentile helper.
* The CLI at the bottom is a thin shell: resolve config -> build ->
  generate trace -> run engine -> print the summary.  Benchmarked runs
  go through ``BenchmarkRunner`` (``Scenario(task="serve")``) instead.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b \
        --requests 16 --slots 4 --prompt-len 32 --trace bursty
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import ParamDef
from repro.runner.latency import latency_summary
from repro.runner.traces import (Request, TraceSpec, cache_len_bound,
                                 capture_spec, generate, save_spec,
                                 tokens_by_rid, tokens_digest)

#: smallest padded prompt-length bucket for batched admission; buckets
#: double from here, so compile count is bounded by
#: log2(max_len / ADMIT_MIN_BUCKET) x log2(slots), not by distinct lengths
ADMIT_MIN_BUCKET = 8

#: valid values of the engine's ``admission`` policy flag
ADMISSIONS = ("batched", "single")

#: the host phases of one decode step, in the order they run; together they
#: tile the step but for ``hook.fire()``, which lies between sync and commit
DECODE_PHASES = ("decode.prepare", "decode.dispatch", "decode.sync",
                 "decode.commit")
#: the host phases of admission: ``admit.pack`` then ``admit.prefill`` per
#: bucket group (``admit.compile`` in its place for a (rows, padded_len)
#: shape the engine has not run before), then one ``admit.commit`` per wave
ADMIT_PHASES = ("admit.pack", "admit.prefill", "admit.compile",
                "admit.commit")


def _mark(span_log: list, name: str, t0: float,
          attrs: Optional[Dict[str, int]] = None) -> float:
    """Append span ``name`` from ``t0`` to now; returns now."""
    t1 = time.time()
    span_log.append((name, t0, t1) if attrs is None else (name, t0, t1, attrs))
    return t1


def decode_phase_log(span_log: list) -> List[Tuple[float, float]]:
    """``(dispatch_s, device_s)`` per decode step of a ``run()`` span log:
    ``decode.prepare`` + ``decode.dispatch`` until the async call returns,
    then ``decode.sync``, the wait for the device and the readback."""
    out: List[Tuple[float, float]] = []
    host = 0.0
    for name, t0, t1, *_ in span_log:
        if name in ("decode.prepare", "decode.dispatch"):
            host += t1 - t0
        elif name == "decode.sync":
            out.append((host, t1 - t0))
            host = 0.0
    return out


class ServeEngine:
    """Slot-based continuous batching over a shared decode step.

    ``built`` is a ``repro.core.suite.Built`` (or anything with ``cfg`` /
    ``model`` / ``params`` attributes).  The engine jits its admission and
    decode steps once at construction; ``run()`` resets all per-trace
    state, so one engine instance (and its compiled executables) can
    replay any number of traces — the BenchmarkRunner caches engines per
    (build, slots, max_len, admission) exactly like step executables.

    Admission prefills waiting requests *directly into the live cache*:
    each wave gathers every admissible queued request, groups them by
    padded prompt-length bucket, and runs one jitted call per group —
    prefill on a fresh k-row mini cache, per-row last-valid-position
    argmax, then a masked row scatter into the target slots (the per-slot
    ``len`` position vectors land each row at its own prompt length).

    ``admission="batched"`` (default) pads prompts to power-of-two
    buckets (>= ``ADMIT_MIN_BUCKET``) and rounds the batch to a power of
    two, so the compile count is bounded by buckets, not distinct prompt
    lengths.  ``admission="single"`` is the pre-batching baseline kept
    runnable for comparison: one exact-length single-row call per request
    (recompiling per distinct length), token-identical to batched
    admission by construction.  The MoE family always uses exact-length
    groups even under ``"batched"``: expert capacity is sized from the
    token count, so pad tokens would compete with valid tokens for
    capacity slots and could change routing.
    """

    def __init__(self, built, *, slots: int, max_len: int,
                 donate: bool = True, admission: str = "batched"):
        if admission not in ADMISSIONS:
            raise ValueError(f"unknown admission {admission!r} "
                             f"(known: {ADMISSIONS})")
        self.cfg = built.cfg
        self.model = built.model
        self.params = built.params
        self.slots = slots
        self.max_len = max_len
        self.admission = admission
        # vlm prefill writes n_prefix patch tokens ahead of the prompt, so
        # a slot's cache position starts past the prefix after admission
        self._prefix = built.cfg.n_prefix if built.cfg.family == "vlm" else 0
        self._decode = jax.jit(self.model.decode_step,
                               donate_argnums=(2,) if donate else ())
        self._admit = jax.jit(self._admit_impl,
                              donate_argnums=(5,) if donate else ())
        # per-leaf batch axis of every cache leaf, from the declared
        # logical axes — the admission scatter needs it explicitly because
        # a full wave's mini cache has the same row count as the live one
        self._cache_axes = jax.tree.map(
            lambda d: d.axes.index("cache_batch"),
            self.model.cache_defs(slots, max_len),
            is_leaf=lambda v: isinstance(v, ParamDef))
        # distinct (rows, padded_len) shapes ever admitted — the host-side
        # mirror of the jit cache, cumulative over the engine's lifetime
        self._admit_shapes: set = set()
        self._reset()

    def _reset(self) -> None:
        self.cache = self.model.init_cache(self.slots, self.max_len)
        self.slot_req: List[Optional[Request]] = [None] * self.slots
        # host-side mirror of the per-layer "len" vectors: admission sets a
        # row to prefix + prompt_len, every decode step advances all rows.
        # Guarded in run(): an *active* row overflowing max_len would have
        # its KV write clamped to the cache edge, corrupting attention.
        self.slot_pos = np.zeros(self.slots, np.int32)
        self.steps = 0
        self._admit_calls = 0
        self._admit_batches: List[int] = []

    # ---- batched admission ------------------------------------------------

    def _bucket(self, n: int) -> int:
        """Padded prompt length for an ``n``-token prompt."""
        if self.admission == "single" or self.cfg.family == "moe":
            return n          # exact length (see class docstring)
        b = ADMIT_MIN_BUCKET
        while b < n:
            b *= 2
        return min(b, self.max_len - self._prefix)

    def _admit_impl(self, params, tokens, lengths, src, mask, cache):
        """One jitted admission: prefill ``tokens`` (kb, Lpad) with valid
        prefixes ``lengths`` (kb,) on a fresh kb-row mini cache, then
        scatter mini row ``src[s]`` into live-cache row ``s`` wherever
        ``mask[s]`` (``src``/``mask`` are runtime data, so the compile is
        keyed only by the (kb, Lpad) shape).  Returns each admitted row's
        first token and the updated cache."""
        kb = tokens.shape[0]
        mini = self.model.init_cache(kb, self.max_len)
        batch = {"tokens": tokens}
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (kb, self.cfg.n_prefix, self.cfg.d_model))
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (kb, self.cfg.enc_seq, self.cfg.d_model))
        logits, mini = self.model.prefill(params, batch, mini,
                                          lengths=lengths)
        first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)

        def scatter(big, small, ax):
            rows = jnp.take(small, src, axis=ax).astype(big.dtype)
            shape = [1] * big.ndim
            shape[ax] = self.slots
            return jnp.where(mask.reshape(shape), rows, big)

        with jax.named_scope("admit_scatter"):
            cache = jax.tree.map(scatter, cache, mini, self._cache_axes)
        return first, cache

    def _admit_wave(self, pairs: List[Tuple[int, Request]],
                    span_log: Optional[list] = None) -> List[int]:
        """Prefill a wave of (slot, request) pairs into the live cache;
        returns their first tokens in pair order.  Batched admission groups
        the wave by prompt-length bucket — one jitted call per group;
        single admission degrades to one exact-length call per request.
        ``span_log`` receives each group's ``admit.pack`` and
        ``admit.prefill``/``admit.compile`` spans (see ``run()``)."""
        if self.admission == "single":
            grouped = [[pr] for pr in pairs]
        else:
            by_bucket: Dict[int, List[Tuple[int, Request]]] = {}
            for pr in pairs:
                by_bucket.setdefault(self._bucket(len(pr[1].prompt)),
                                     []).append(pr)
            grouped = [by_bucket[b] for b in sorted(by_bucket)]
        first_by_slot: Dict[int, int] = {}
        t = 0.0
        for grp in grouped:
            if span_log is not None:
                t = time.time()
            lpad = self._bucket(max(len(r.prompt) for _, r in grp))
            kb = len(grp)
            if self.admission == "batched":
                kb = 1 << (kb - 1).bit_length()   # round rows to pow2
            tokens = np.zeros((kb, lpad), np.int32)
            # dummy rows keep lengths=lpad (their full-garbage state is
            # simply never gathered by src)
            lengths = np.full((kb,), lpad, np.int32)
            src = np.zeros((self.slots,), np.int32)
            mask = np.zeros((self.slots,), bool)
            for i, (s, r) in enumerate(grp):
                tokens[i, : len(r.prompt)] = r.prompt
                lengths[i] = len(r.prompt)
                src[s] = i
                mask[s] = True
            if span_log is not None:
                t = _mark(span_log, "admit.pack", t)
            first, self.cache = self._admit(
                self.params, jnp.asarray(tokens), jnp.asarray(lengths),
                jnp.asarray(src), jnp.asarray(mask), self.cache)
            first = np.asarray(first)
            if span_log is not None:
                new_shape = (kb, lpad) not in self._admit_shapes
                _mark(span_log, "admit.compile" if new_shape else "admit.prefill",
                      t, {"requests": len(grp), "rows": kb, "padded_len": lpad,
                          "valid_tokens": int(lengths[:len(grp)].sum())})
            self._admit_calls += 1
            self._admit_batches.append(len(grp))
            self._admit_shapes.add((kb, lpad))
            for i, (s, _) in enumerate(grp):
                first_by_slot[s] = int(first[i])
        return [first_by_slot[s] for s, _ in pairs]

    def lowered_decode(self):
        """Lower the jitted decode step against the engine's live state —
        the profiler's attribution source (lowering an already-traced call
        is ~1 ms; the caller pays/caches the AOT compile)."""
        toks = jnp.zeros((self.slots, 1), jnp.int32)
        return self._decode.lower(self.params, toks, self.cache)

    def run(self, requests: List[Request], *, hook=None,
            span_log: Optional[list] = None) -> Dict[str, Any]:
        """Replay a trace; returns throughput + raw latency samples.

        Admission is driven by the decode-step counter (virtual time):
        a request with ``arrival_step=k`` can be admitted only once ``k``
        decode steps have elapsed (the counter fast-forwards when slots
        drain), so slot assignment — and therefore every generated token
        — is deterministic regardless of host speed.  Wall-clock
        timestamps are stamped alongside for the latency metrics.

        ``hook`` is an optional ``RegressionHook`` fired once per decode
        step, after the readback and before the rows advance, so
        injected-slowdown CI probes work on serve cells too.

        ``span_log`` is the tracing hook: it receives ``(name, t0, t1)``
        or ``(name, t0, t1, attrs)`` tuples that tile the loop's host work
        without overlapping.  Each decode step logs ``DECODE_PHASES`` in
        order: ``decode.prepare`` (the KV-exhaustion guard and the token
        upload), ``decode.dispatch`` (the jitted decode call, until it
        returns), ``decode.sync`` (the argmax and its readback: the host
        waits on the device) and ``decode.commit`` (appending tokens,
        finishing requests, advancing the rows).  ``hook.fire()`` runs
        between sync and commit, outside every span: its time is the
        caller's.  Admission logs ``ADMIT_PHASES``: ``admit.pack`` and
        ``admit.prefill`` per bucket group — ``admit.compile`` for a
        (rows, padded_len) shape this engine had not run before — with
        ``requests``/``rows``/``padded_len``/``valid_tokens`` attrs, then
        one ``admit.commit`` per wave for slot assignment and the
        first-token and TTFT bookkeeping.  Times are ``time.time()``: a
        profiler stamps its events on another base, but both clocks
        advance at the same rate, so one annotation whose ``time.time()``
        is known puts every span on the trace's clock.  With
        ``span_log=None`` the engine reads no extra clock.
        """
        self._reset()
        shapes0 = len(self._admit_shapes)
        upcoming = sorted(requests, key=lambda r: (r.arrival_step, r.rid))
        for r in upcoming:
            r.out, r.done = [], False
            r.t_arrival = r.t_first = r.t_done = 0.0
        waiting: List[Request] = []
        next_tok = np.zeros(self.slots, np.int32)
        step = active = done_count = tokens_out = 0
        total = len(upcoming)
        ttft_s: List[float] = []
        tok_lat_s: List[float] = []
        qdepth: List[int] = []
        waves = 0
        t = 0.0      # start of the open span, when span_log is given
        t0 = time.perf_counter()
        while done_count < total:
            now = time.perf_counter()
            while upcoming and upcoming[0].arrival_step <= step:
                req = upcoming.pop(0)
                req.t_arrival = now
                waiting.append(req)
            if active == 0 and not waiting:
                # slots drained before the next burst: fast-forward the
                # virtual clock to the next arrival (no idle decode spins)
                step = upcoming[0].arrival_step
                continue
            if waiting:
                # one admission wave: free slots in ascending order take
                # waiting requests FIFO (the same assignment the old
                # per-request loop produced), then prefill per bucket group
                free = [s for s in range(self.slots)
                        if self.slot_req[s] is None or self.slot_req[s].done]
                pairs = list(zip(free, waiting))
                if pairs:
                    del waiting[: len(pairs)]
                    waves += 1
                    firsts = self._admit_wave(pairs, span_log)
                    if span_log is not None:
                        t = time.time()
                    tnow = time.perf_counter()
                    for (s, req), tok in zip(pairs, firsts):
                        self.slot_req[s] = req
                        self.slot_pos[s] = self._prefix + len(req.prompt)
                        req.out.append(tok)
                        tokens_out += 1
                        req.t_first = tnow
                        ttft_s.append(tnow - req.t_arrival)
                        next_tok[s] = tok
                        active += 1
                        if len(req.out) >= req.max_new:  # budget of 1: done
                            req.done = True              # at prefill
                            req.t_done = tnow
                            active -= 1
                            done_count += 1
                    if span_log is not None:
                        _mark(span_log, "admit.commit", t)
            qdepth.append(len(waiting))
            if active == 0:
                step += 1
                continue
            if span_log is not None:
                t = time.time()
            for s in range(self.slots):
                req = self.slot_req[s]
                if req is None or req.done:
                    continue   # idle rows may overflow harmlessly (clamped
                    #            write, row fully rewritten at next admit)
                if self.slot_pos[s] + 1 > self.max_len:
                    raise RuntimeError(
                        f"KV cache exhausted: slot {s} (rid {req.rid}) at "
                        f"position {int(self.slot_pos[s])} with max_len "
                        f"{self.max_len} — size the engine with "
                        f"traces.cache_len_bound() for the trace")
            ts = time.perf_counter()
            toks = jnp.asarray(next_tok[:, None])
            if span_log is not None:
                t = _mark(span_log, "decode.prepare", t)
            logits, self.cache = self._decode(self.params, toks, self.cache)
            if span_log is not None:
                t = _mark(span_log, "decode.dispatch", t)
            nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
            if span_log is not None:
                t = _mark(span_log, "decode.sync", t)
            if hook is not None:
                hook.fire()   # inside the timed sample, like harness.measure
                if span_log is not None:
                    t = time.time()
            dt = time.perf_counter() - ts
            self.steps += 1
            step += 1
            self.slot_pos += 1   # decode advances every row's len vector
            for s in range(self.slots):
                req = self.slot_req[s]
                if req is None or req.done:
                    continue
                req.out.append(int(nxt[s]))
                tokens_out += 1
                tok_lat_s.append(dt)
                next_tok[s] = nxt[s]
                if len(req.out) >= req.max_new:
                    req.done = True
                    req.t_done = time.perf_counter()
                    active -= 1
                    done_count += 1
            if span_log is not None:
                _mark(span_log, "decode.commit", t)
        wall = time.perf_counter() - t0
        ab = self._admit_batches
        # fleet metrics: folded ONCE per replay (never per decode step) —
        # admission control-path counters
        from repro.fleet.metrics import registry as metrics_registry
        reg = metrics_registry()
        reg.inc("serve_admit_waves_total", waves)
        reg.inc("serve_admit_calls_total", self._admit_calls)
        reg.inc("serve_bucket_compiles_total",
                len(self._admit_shapes) - shapes0)
        reg.inc("serve_decode_steps_total", self.steps)
        return {"requests": total, "decode_steps": self.steps,
                "tokens": tokens_out, "wall_s": wall,
                "tok_per_s": tokens_out / wall if wall else 0.0,
                "ttft_s": ttft_s, "tok_lat_s": tok_lat_s,
                "queue_depth_mean": (sum(qdepth) / len(qdepth)) if qdepth else 0.0,
                "queue_depth_max": max(qdepth) if qdepth else 0,
                "admission": self.admission,
                "admit_calls": self._admit_calls,
                "admit_batch_mean": (sum(ab) / len(ab)) if ab else 0.0,
                "admit_batch_max": max(ab) if ab else 0,
                "admit_shapes": sorted(list(s) for s in self._admit_shapes),
                # prefill shapes first compiled DURING this replay: > 0 means
                # the replay paid admission jits (queue dynamics at this load
                # reached bucket shapes no earlier replay had) and its wall/
                # TTFT samples are not steady-state — rerun to re-measure
                "admit_new_shapes": len(self._admit_shapes) - shapes0,
                "tokens_by_rid": tokens_by_rid(requests)}

    def capture(self, requests: List[Request], *, seed: int = 0,
                source: str = "live") -> TraceSpec:
        """A replayable ``TraceSpec`` of a served trace: per-request prompt
        lengths, arrivals, and budgets pinned, prompt *content* regenerated
        from ``(seed, lengths)`` — so a live run becomes a regression asset
        via the ordinary ``save_spec`` schema (``trace="file:..."``)."""
        return capture_spec(requests, seed=seed, source=source)


def summarize_metrics(out: Dict[str, Any]) -> Dict[str, Any]:
    """The well-known serve metric keys (see ``runner/results.py``) from an
    engine ``run()`` payload: TTFT / per-token latency p50/p95/p99 in us,
    throughput, queue depth, admission counters, and the token digest."""
    summary: Dict[str, Any] = {
        "tok_per_s": out["tok_per_s"],
        "decode_steps": out["decode_steps"],
        "queue_depth_mean": out["queue_depth_mean"],
        "queue_depth_max": out["queue_depth_max"],
        "tokens_digest": tokens_digest(out["tokens_by_rid"]),
    }
    for k in ("admission", "admit_calls", "admit_batch_mean",
              "admit_batch_max", "admit_shapes"):
        if k in out:
            summary[k] = out[k]
    summary.update(latency_summary(out["ttft_s"], "ttft", scale=1e6))
    summary.update(latency_summary(out["tok_lat_s"], "tok_lat", scale=1e6))
    return summary


def built_for_cfg(cfg, seed: int = 0):
    """Build (model + params) for an already-resolved config — the
    non-runner path shared by the ``Server`` shim and the ``--full`` CLI
    (the runner's ``built_for`` caches reduced builds instead)."""
    from repro.core.suite import Built
    from repro.models import build_model
    model = build_model(cfg)
    return Built(cfg=cfg, model=model, params=model.init(jax.random.key(seed)))


class Server(ServeEngine):
    """Compat shim over ``ServeEngine`` for direct (non-runner) callers:
    builds the model from a config, like the pre-runner serving driver.
    Serves through the same bucketed batched-admission path as the
    runner-cached engines (``admission`` passes through)."""

    def __init__(self, cfg, *, slots: int, max_len: int, seed: int = 0,
                 admission: str = "batched"):
        super().__init__(built_for_cfg(cfg, seed), slots=slots,
                         max_len=max_len, admission=admission)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--trace", default="uniform",
                    help="load profile: uniform | bursty | mixed")
    ap.add_argument("--prompt-profile", default="fixed",
                    help="prompt-length profile: fixed | uniform | bimodal "
                         "| longtail")
    ap.add_argument("--capture", default="",
                    help="write a replayable TraceSpec of this run to PATH")
    ap.add_argument("--admission", default="batched", choices=ADMISSIONS,
                    help="prefill admission policy: batched (bucketed "
                         "multi-request prefill) | single (per-request "
                         "baseline)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.core.suite import build_arch
    from repro.configs import get_arch
    if args.full:
        built = built_for_cfg(get_arch(args.arch))
    else:
        built = build_arch(args.arch)
    spec = TraceSpec(profile=args.trace, requests=args.requests,
                     prompt_len=args.prompt_len, max_new=args.max_new,
                     seed=args.seed, prompt_profile=args.prompt_profile)
    reqs = generate(spec, vocab=built.cfg.vocab)
    prefix = built.cfg.n_prefix if built.cfg.family == "vlm" else 0
    engine = ServeEngine(built, slots=args.slots,
                         max_len=cache_len_bound(reqs, prefix=prefix),
                         admission=args.admission)
    out = engine.run(reqs)
    m = summarize_metrics(out)
    if args.capture:
        save_spec(engine.capture(reqs, seed=args.seed,
                                 source=f"cli:{args.arch}"), args.capture)
        print(f"captured trace spec -> {args.capture}")
    print(f"served {args.requests} requests ({args.trace}): {out['tokens']} tokens "
          f"in {out['wall_s']:.2f}s ({m['tok_per_s']:.1f} tok/s, "
          f"{out['decode_steps']} steps, {args.admission} admission: "
          f"{out['admit_calls']} prefill calls)")
    print(f"  ttft_us    p50={m.get('ttft_p50', 0):.0f} "
          f"p95={m.get('ttft_p95', 0):.0f} p99={m.get('ttft_p99', 0):.0f}")
    print(f"  tok_lat_us p50={m.get('tok_lat_p50', 0):.0f} "
          f"p95={m.get('tok_lat_p95', 0):.0f} p99={m.get('tok_lat_p99', 0):.0f}")
    print(f"  queue_depth mean={m['queue_depth_mean']:.2f} max={m['queue_depth_max']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
