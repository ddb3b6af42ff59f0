import os
import sys
import tempfile
_DUMP_DIR = os.path.join(tempfile.gettempdir(), f"repro_hlo_dump_{os.getpid()}")
# The 512 placeholder devices are needed only where cells actually compile:
# the ``python -m repro.launch.dryrun`` subprocess and scripts/dump_cell.py.
# Under pytest this module is imported for its pure helpers (cell_rules,
# input_specs) and the flags must NOT leak into the test process — tests
# measure on the single real CPU device (see tests/conftest.py).
# The dry run is a host-device compile by construction: it pins the CPU
# platform, so on a machine with a TPU it never tries to load libtpu
# (which the parent process may hold).
if "pytest" not in sys.modules:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        f"--xla_dump_to={_DUMP_DIR} --xla_dump_hlo_pass_re=spmd-partitioning"
    )

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The statements above MUST stay first in this module — jax
locks the device count at first backend init, and the production meshes
(16x16 and 2x16x16) need 512 placeholder host devices.  Nothing here
allocates real buffers: inputs are ShapeDtypeStructs, compilation is AOT.

Per cell this emits:
  * memory_analysis()  — per-device bytes: proves the cell fits HBM
  * cost_analysis()    — XLA's per-partition FLOPs/bytes (recorded raw)
  * trip-count-corrected FLOPs/bytes/collective bytes (repro.core.hloanalysis)
  * the three roofline terms (repro.core.roofline)

Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
          --shape train_4k [--multi-pod] [--json out.json]
      PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import dataclasses
import json
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_arch, get_shape, shape_applicable
from repro.core.hloanalysis import analyze_hlo
from repro.core.roofline import model_flops_estimate, roofline_from_cost
from repro.distributed import merge_rules, sharding_ctx, spec_tree
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step, make_state_defs, make_train_step
from repro.models.layers import ParamDef, abstract_tree


def input_specs(cfg, shape) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    from repro.data.pipeline import make_batch_specs
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)}
    return make_batch_specs(cfg, shape)


import jax.numpy as _jnp
OPT_CFG = dict(opt_bf16_probs=True, opt_ce_chunk=512, opt_gate_bf16=True,
               param_dtype=_jnp.bfloat16,   # bf16 weights, fp32 Adam moments:
               # halves FSDP all-gathers, grad reduce-scatters, weight reads
               attn_chunk=512)              # halves peak score-chunk footprint


# Small dense archs where TP16 never pays at train_4k: use the model axis as
# extra data parallelism (DP256 + 2D-FSDP weights, vocab stays TP).  §Perf C.
OPT_TRAIN_DP256 = {"gemma-2b", "paligemma-3b"}

# Prefill cells whose full-length GQA cache must shard over sequence to fit
# (KV heads don't divide the model axis; see cell_rules).
OPT_PREFILL_SEQ_CACHE = {"internlm2-20b", "nemotron-4-15b", "mixtral-8x7b",
                         "whisper-large-v3"}

DP256_RULES: Dict[str, Any] = {
    "act_batch": ("pod", "data", "model"),
    "act_mlp": None, "act_heads": None, "act_kv_heads": None,
    "act_q_seq": None,
    "w_mlp": None, "w_heads": None, "w_kv_heads": None, "w_expert_mlp": None,
    "w_embed": ("data", "model"),
}


# Per-(arch-family, shape-kind) sharding-rule overrides (see DESIGN.md).
def cell_rules(cfg, shape, opt: bool = False) -> Dict[str, Any]:
    rules: Dict[str, Any] = {}
    base_name = cfg.name.replace("-optimized", "")
    if opt and shape.kind == "train" and base_name in OPT_TRAIN_DP256:
        rules.update(DP256_RULES)
    elif opt and cfg.n_heads and cfg.n_heads % 16:
        # heads cannot use the 16-way model axis -> sequence-parallel
        # attention (q positions over 'model'); kv is tiny (MQA) or small.
        # (whisper: train only — at prefill/decode its cross-attention
        # resharding dominates and SP regresses; measured in §Perf.)
        if cfg.family != "encdec" or shape.kind == "train":
            rules["act_q_seq"] = ("model",)
    if shape.kind == "decode":
        # KV heads never divide the 16-way model axis on the assigned archs;
        # shard the cache (and its attention reduction) over sequence instead.
        rules["cache_seq"] = ("model",)
        rules["cache_heads"] = None
        if shape.global_batch < 16:
            # long_500k: batch 1 -> sequence parallelism over data too
            rules["cache_seq"] = ("model",)
            rules["cache_batch"] = None
    if shape.kind == "prefill" and shape.global_batch < 16:
        rules["act_seq"] = ("data",)
    if opt and shape.kind == "prefill" and cfg.name.split("-optimized")[0] in OPT_PREFILL_SEQ_CACHE:
        # KV heads don't divide the model axis: a head-sharded cache
        # replicates 16x on these large-KV archs.  Shard it over sequence
        # (40 -> 6.6 GB/dev on internlm2).  Not applied to MLA (deepseek:
        # tiny latent cache, resharding dominates) or ring-cache archs.
        rules["cache_seq"] = ("model",)
        rules["cache_heads"] = None
    return rules


# Gradient-accumulation factor per arch for train_4k: chosen so the per-
# device live set (params + opt state + microbatch activations + logits)
# fits 16 GB v5e HBM.  Tuned during the baseline sweep (EXPERIMENTS.md).
TRAIN_MICROBATCHES = {
    "gemma-2b": 4,
    "internlm2-20b": 16,
    "nemotron-4-15b": 16,
    "gemma3-12b": 4,
    "deepseek-v2-236b": 16,
    "mixtral-8x7b": 16,
    "whisper-large-v3": 8,
    "paligemma-3b": 4,
    "mamba2-2.7b": 8,
    "recurrentgemma-9b": 4,
}


def _analyze_post_spmd(compiled):
    """Cost the post-SPMD-partitioning, pre-fusion HLO dump.

    The CPU backend legalizes bf16 dots to f32 before fusion, which would
    misprice the TPU target's bytes and collective wire sizes by up to 2x;
    the post-partitioning dump has per-device shapes + collectives with the
    dtypes the program specifies.  Falls back to the compiled module text
    (fused, CPU-legalized) when the dump is unavailable.
    """
    import glob
    files = sorted(glob.glob(os.path.join(_DUMP_DIR, "*after_spmd-partitioning*.txt")),
                   key=os.path.getmtime)
    if files:
        with open(files[-1]) as f:
            return analyze_hlo(f.read(), fused_bytes=True), "post_spmd_partitioning"
    return analyze_hlo(compiled.as_text()), "compiled_fallback"


def _mesh_name(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             rules_override: Optional[Dict[str, Any]] = None,
             opt: bool = False, microbatches: Optional[int] = None,
             verbose: bool = True) -> Dict[str, Any]:
    import dataclasses as _dc
    cfg = get_arch(arch)
    if opt:
        cfg = _dc.replace(cfg, **OPT_CFG)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    out: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": _mesh_name(multi_pod)}
    if not ok:
        out["skipped"] = why
        if verbose:
            print(f"[dryrun] {arch} x {shape_name}: SKIPPED ({why})")
        return out

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rules = merge_rules(cell_rules(cfg, shape, opt), rules_override)

    t0 = time.time()
    with sharding_ctx(mesh, rules):
        if shape.kind == "train":
            from repro.launch.steps import TrainHyper
            mb = microbatches if microbatches is not None else TRAIN_MICROBATCHES.get(arch, 1)
            if opt and arch in OPT_TRAIN_DP256 and microbatches is None:
                mb = 1   # DP256 shards the batch over all 256/512 chips
            step, model = make_train_step(cfg, TrainHyper(microbatches=mb))
            out["microbatches"] = mb
            pdefs, odefs = make_state_defs(model)
            state_defs = (pdefs, odefs)
            state_shardings = spec_tree(state_defs, mesh, rules)
            state_abstract = abstract_tree(state_defs)
            batch = input_specs(cfg, shape)
            batch_shardings = {
                k: NamedSharding(mesh, P(*(("pod", "data") if "pod" in mesh.shape else ("data",))))
                if v.ndim > 1 else NamedSharding(mesh, P())
                for k, v in batch.items()}
            # tokens (B, S): shard batch dim only
            batch_shardings = {
                k: NamedSharding(mesh, P(("pod", "data") if "pod" in mesh.shape else "data"))
                for k in batch}
            jitted = jax.jit(step, in_shardings=(state_shardings, batch_shardings),
                             out_shardings=(state_shardings, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abstract, batch)
        else:
            model_tmp = make_decode_step(cfg)[1]
            max_len = shape.seq_len + (cfg.n_prefix or 0)
            cache_defs = model_tmp.cache_defs(shape.global_batch, max_len)
            cache_shardings = spec_tree(cache_defs, mesh, rules)
            cache_abstract = abstract_tree(cache_defs)
            pdefs = model_tmp.param_defs()
            p_shardings = spec_tree(pdefs, mesh, rules)
            p_abstract = abstract_tree(pdefs)
            if shape.kind == "prefill":
                step, model = make_prefill_step(cfg, shape.seq_len)
                batch = input_specs(cfg, shape)
                dspec = ("pod", "data") if "pod" in mesh.shape else "data"
                bsh = {k: NamedSharding(mesh, P(dspec)) for k in batch}
                jitted = jax.jit(step, in_shardings=(p_shardings, bsh, cache_shardings),
                                 out_shardings=(None, cache_shardings),
                                 donate_argnums=(2,))
                lowered = jitted.lower(p_abstract, batch, cache_abstract)
            else:
                step, model = make_decode_step(cfg)
                toks = input_specs(cfg, shape)["tokens"]
                dspec = ("pod", "data") if "pod" in mesh.shape else "data"
                tsh = NamedSharding(mesh, P(dspec if shape.global_batch >= 16 else None))
                jitted = jax.jit(step, in_shardings=(p_shardings, tsh, cache_shardings),
                                 out_shardings=(None, cache_shardings),
                                 donate_argnums=(2,))
                lowered = jitted.lower(p_abstract, toks, cache_abstract)

        compiled = lowered.compile()
    t1 = time.time()

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    cost, cost_src = _analyze_post_spmd(compiled)
    rl = roofline_from_cost(
        cost, arch=arch, shape=shape_name, mesh=_mesh_name(multi_pod),
        chips=chips, model_flops=model_flops_estimate(cfg, shape))

    out.update({
        "compile_s": round(t1 - t0, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0)),
        },
        "xla_cost_analysis": {"flops_per_partition": float(ca.get("flops", 0.0)),
                              "bytes_per_partition": float(ca.get("bytes accessed", 0.0))},
        "cost_source": cost_src,
        "roofline": rl.to_dict(),
        "hlo_notes": cost.notes[:10],
    })
    if verbose:
        m = out["memory"]
        per_dev = (m["argument_bytes"] + m["temp_bytes"]) / 1e9
        print(f"[dryrun] {arch} x {shape_name} x {out['mesh']}: compiled in {out['compile_s']}s | "
              f"args+temp {per_dev:.2f} GB/dev | "
              f"terms c/m/n = {rl.compute_s*1e3:.1f}/{rl.memory_s*1e3:.1f}/{rl.collective_s*1e3:.1f} ms | "
              f"dominant={rl.dominant} useful={rl.useful_ratio:.2f}")
        print(f"  memory_analysis: {mem}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--rules", default=None, help="JSON dict of logical-rule overrides")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper optimized configuration (see EXPERIMENTS.md §Perf)")
    ap.add_argument("--microbatches", type=int, default=None)
    args = ap.parse_args(argv)

    rules_override = json.loads(args.rules) if args.rules else None
    results = []
    if args.all:
        cells = [(a, s) for a in sorted(ARCHS) for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    failed = 0
    for arch, shp in cells:
        try:
            results.append(run_cell(arch, shp, multi_pod=args.multi_pod,
                                    rules_override=rules_override, opt=args.opt,
                                    microbatches=args.microbatches))
        except Exception as e:  # noqa: BLE001 — report all failures at end
            failed += 1
            results.append({"arch": arch, "shape": shp, "error": f"{type(e).__name__}: {e}"})
            print(f"[dryrun] {arch} x {shp}: FAILED {type(e).__name__}: {e}", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
