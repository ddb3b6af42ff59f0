"""End-to-end training driver (example application and CI workhorse).

Runs on whatever devices exist: single CPU (reduced configs, real steps —
the measured path used by the regression CI) or a real TPU mesh (full
configs).  Wires together every substrate: data pipeline, model, optimizer,
checkpointing, supervisor (fault tolerance), metrics.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data.pipeline import DataConfig, SyntheticTokenDataset
from repro.distributed import merge_rules, sharding_ctx, spec_tree
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import TrainHyper, make_state_defs, make_train_step
from repro.models.layers import init_tree
from repro.optim.adamw import adamw_init
from repro.runtime import HeartbeatMonitor, Supervisor


def build_trainer(cfg, *, batch: int, seq: int, hyper: TrainHyper = TrainHyper(),
                  mesh=None, rules=None, seed: int = 0):
    """-> (state, jitted step fn, dataset)."""
    rules = merge_rules(rules)
    with sharding_ctx(mesh, rules):
        step, model = make_train_step(cfg, hyper)
        params = model.init(jax.random.key(seed))
        opt = adamw_init(params)
        state = (params, opt)
        if mesh is not None:
            shardings = spec_tree(make_state_defs(model), mesh, rules)
            state = jax.device_put(state, shardings)
            jstep = jax.jit(step, in_shardings=(shardings, None),
                            out_shardings=(shardings, None), donate_argnums=(0,))
        else:
            jstep = jax.jit(step, donate_argnums=(0,))
    ds = SyntheticTokenDataset(DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed))
    return state, jstep, ds, model


def _device_batch(cfg, ds, step_idx: int, seq: int):
    batch = {k: jnp.asarray(v) for k, v in ds.batch_at(step_idx).items()}
    if cfg.family == "encdec":
        b = batch["tokens"].shape[0]
        key = jax.random.key(step_idx)
        batch["frames"] = jax.random.normal(key, (b, cfg.enc_seq, cfg.d_model)) * 0.1
    if cfg.family == "vlm":
        b = batch["tokens"].shape[0]
        key = jax.random.key(step_idx)
        batch["patch_embeds"] = jax.random.normal(key, (b, cfg.n_prefix, cfg.d_model)) * 0.02
    return batch


def train(arch: str, *, steps: int, batch: int, seq: int, reduced: bool = True,
          ckpt_dir: Optional[str] = None, save_every: int = 20,
          log_every: int = 10, inject_fault_at: Optional[int] = None,
          seed: int = 0) -> Dict[str, Any]:
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    state, jstep, ds, model = build_trainer(cfg, batch=batch, seq=seq, seed=seed)

    history = []
    t_start = time.perf_counter()

    def one_step(st, i):
        if inject_fault_at is not None and i == inject_fault_at:
            if not getattr(one_step, "_fired", False):
                one_step._fired = True
                raise RuntimeError("injected node failure")
        b = _device_batch(cfg, ds, i, seq)
        st, metrics = jstep(st, b)
        if i % log_every == 0 or i == steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            history.append({"step": i, **m})
            print(f"step {i:5d} loss {m['loss']:.4f} ppl {m['ppl']:.1f} "
                  f"gnorm {m['grad_norm']:.3f} lr {m['lr']:.2e}")
        return st

    if ckpt_dir:
        ckpt = CheckpointManager(ckpt_dir, keep=2)
        sup = Supervisor(ckpt, save_every=save_every, monitor=HeartbeatMonitor(1))
        restored, rstep = ckpt.restore_latest(state)
        start = 0
        if restored is not None:
            state, start = restored, rstep
            print(f"resumed from step {start}")
        state, _ = sup.run(state, one_step, steps, start_step=start)
        events = sup.events
    else:
        for i in range(steps):
            state = one_step(state, i)
        events = []

    wall = time.perf_counter() - t_start
    return {"history": history, "wall_s": wall, "events": events,
            "final_loss": history[-1]["loss"] if history else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true", help="full (assigned) config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--inject-fault-at", type=int, default=None)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                reduced=not args.full, ckpt_dir=args.ckpt_dir,
                inject_fault_at=args.inject_fault_at)
    print(f"done in {out['wall_s']:.1f}s, final loss {out['final_loss']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
