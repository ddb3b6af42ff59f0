#!/usr/bin/env python3
"""Chip smoke: drive the serving path once on one TPU chip.

    python3 chip_smoke.py

Phase A builds gemma-2b at its published widths (18 layers, d_model 2048,
vocab 256000, head_dim 256; random bf16 weights from a fixed seed), serves
an 8-request trace through ``ServeEngine`` exactly as ``repro.launch.serve
--full`` does, checks every request's tokens, and checks one prefill's
logits against the uncached forward pass.  Phase B runs one train cell and
one serve cell (both admission policies) through ``BenchmarkRunner`` in this
process.  Readings print on earlier lines; they are smoke readings, not
benchmark numbers.

Everything runs in this one process: a chip belongs to one process at a
time.  The script exits non-zero, without the ok line, when JAX finds no
TPU, when the rest of the repository is missing, or when any phase fails.
Its last line on success is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "tpu")

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

SEED = 0
SLOTS = 4


def _say(tag, **readings):
    print(f"{tag}: {json.dumps(readings, sort_keys=True)}", flush=True)


def serve_full_width(cfg):
    """Phase A on ``cfg``: serve 8 requests, check them, check a prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import ServeEngine, built_for_cfg, summarize_metrics
    from repro.runner.traces import TraceSpec, cache_len_bound, generate

    t0 = time.perf_counter()
    built = built_for_cfg(cfg, SEED)
    jax.block_until_ready(built.params)
    init_s = time.perf_counter() - t0

    spec = TraceSpec(profile="uniform", requests=8, prompt_len=32, max_new=16,
                     seed=SEED)
    reqs = generate(spec, vocab=cfg.vocab)
    engine = ServeEngine(built, slots=SLOTS, max_len=cache_len_bound(reqs),
                         admission="batched")
    # the first replay pays the admission and decode jits: its wall is the
    # compile reading, as the runner's compile_us is for a serve cell
    t0 = time.perf_counter()
    first = engine.run(reqs)
    compile_s = time.perf_counter() - t0
    out = engine.run(reqs)
    m = summarize_metrics(out)
    for r in reqs:
        if len(r.out) != spec.max_new:
            raise AssertionError(f"request {r.rid}: {len(r.out)} tokens, "
                                 f"wanted {spec.max_new}")
        bad = [t for t in r.out if not 0 <= t < cfg.vocab]
        if bad:
            raise AssertionError(f"request {r.rid}: tokens {bad[:4]} outside "
                                 f"[0, {cfg.vocab})")

    # one prefill on the device, against the uncached forward pass on the
    # same prompt; atol/rtol are tests/test_archs.py's cached-vs-forward
    # bounds, which bf16 compute over every layer stays well inside
    prompt = jnp.asarray(reqs[0].prompt[None, :])
    cache = built.model.init_cache(1, prompt.shape[1] + 1)
    logits, _ = jax.jit(built.model.prefill)(built.params, {"tokens": prompt},
                                             cache)
    logits = np.asarray(logits[:, 0], np.float32)
    if logits.shape != (1, cfg.vocab) or not np.isfinite(logits).all():
        raise AssertionError(f"prefill logits: shape {logits.shape}, "
                             f"finite {bool(np.isfinite(logits).all())}")
    full = jax.jit(built.model.forward)(built.params, {"tokens": prompt})
    ref = np.asarray(full[:, -1], np.float32)
    np.testing.assert_allclose(logits, ref, atol=0.35, rtol=0.05)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    _say("phase_a", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
         vocab=cfg.vocab, head_dim=cfg.head_dim,
         param_dtype=jnp.dtype(cfg.param_dtype).name,
         requests=out["requests"], tokens=out["tokens"], init_s=init_s,
         compile_s=compile_s, tok_per_s=m["tok_per_s"],
         ttft_p50_us=m["ttft_p50"], decode_steps=out["decode_steps"],
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         tokens_digest=m["tokens_digest"],
         replays_agree=first["tokens_by_rid"] == out["tokens_by_rid"],
         prefill_max_abs_diff=float(np.abs(logits - ref).max()),
         prefill_argmax_agrees=int(logits.argmax()) == int(ref.argmax()))


def runner_in_process():
    """Phase B: a train cell and a serve cell through BenchmarkRunner."""
    from repro.runner import BenchmarkRunner, Scenario

    runner = BenchmarkRunner()
    train = Scenario(arch="gemma-2b", task="train", batch=1, seq=8)
    serve = Scenario(arch="gemma-2b", task="serve", batch=4, seq=16,
                     trace="bursty", slots=2)
    single = dataclasses.replace(serve, admission="single")
    digests = {}
    for sc in (train, serve, single):
        rr = runner.run(sc, record=False)
        backend = rr.extra.get("prov_backend")
        _say("phase_b", cell=rr.name, status=rr.status, error=rr.error,
             backend=backend, median_us=rr.median_us,
             compile_us=rr.compile_us,
             tokens_digest=rr.extra.get("tokens_digest"))
        if rr.status != "ok" or backend != "tpu":
            raise AssertionError(f"{rr.name}: status {rr.status!r}, backend "
                                 f"{backend!r}: {rr.error}")
        digests[sc.admission] = rr.extra.get("tokens_digest")
    # byte-identical on the CPU; recorded here, not required
    _say("phase_b_digest", batched_equals_single=(
        digests["batched"] == digests["single"]))


def main() -> int:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no accelerator: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 2

    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = {"requests": 0, "hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
             "/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def count(event, **_):
        if event in names:
            cache_events[names[event]] += 1

    jax.monitoring.register_event_listener(count)
    _say("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), compile_cache=cache_dir)

    import jax.numpy as jnp
    # gemma-2b's checkpoints are published in bf16; f32 weights (the config
    # default) take 10 GB of arguments plus 4 GB of decode temporaries,
    # which leaves too little of one chip's 16 GB for the cache and the
    # admission program
    cfg = dataclasses.replace(get_arch("gemma-2b"), param_dtype=jnp.bfloat16)
    failed = []
    for name, phase in (("phase_a", lambda: serve_full_width(cfg)),
                        ("phase_b", runner_in_process)):
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            failed.append(name)
        _say(name + "_done", seconds=time.perf_counter() - t0,
             ok=name not in failed)
    _say("compile_cache", **cache_events)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
