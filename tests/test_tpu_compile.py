"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than present.  It refuses what the CPU interpreter accepts:
primitives the Pallas TPU lowering lacks, blocks not aligned to the (8, 128)
tiling, programs that do not fit the device's memory.  Each case compiles
one kernel at a published width, or gemma-2b's full-width decode step, and
checks what the compiled program holds.

The topology is described inside a module fixture, never at import, so every
xdist worker collects the same tests and only the worker given this file
loads the TPU library.  Keep these cases in this one file.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.flash_attention.kernel import flash_attention_bh
from repro.kernels.rglru.kernel import rglru_scan_kernel
from repro.kernels.ssd.kernel import ssd_bh
from repro.models import build_model
from repro.models.layers import abstract_tree

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e": 16 GB)
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_gemma_2b_width(one_chip):
    """gemma-2b's 8 heads at head_dim 256, a 4096-token sequence, bf16."""
    cfg = get_arch("gemma-2b")
    qkv = _sds(one_chip, (cfg.n_heads, 4096, cfg.head_dim), jnp.bfloat16)
    c = _compile(functools.partial(flash_attention_bh, interpret=False),
                 qkv, qkv, qkv)
    assert "tpu_custom_call" in c.as_text()


def test_rglru_recurrentgemma_9b_width(one_chip):
    """recurrentgemma-9b's lru_width (4096) over a 4096-step sequence."""
    width = get_arch("recurrentgemma-9b").lru_width
    ab = _sds(one_chip, (1, 4096, width), jnp.float32)
    c = _compile(functools.partial(rglru_scan_kernel, interpret=False), ab, ab)
    assert "tpu_custom_call" in c.as_text()


def test_ssd_mamba2_2p7b_width(one_chip):
    """mamba2-2.7b's 80 heads with P=64, N=128, chunk 256."""
    cfg = get_arch("mamba2-2.7b")
    BH, S, P, N = cfg.n_ssm_heads, 4096, cfg.ssm_headdim, cfg.d_state
    assert (P, N, cfg.ssm_chunk) == (64, 128, 256)
    c = _compile(functools.partial(ssd_bh, chunk=cfg.ssm_chunk,
                                   interpret=False),
                 _sds(one_chip, (BH, S, P), jnp.bfloat16),
                 _sds(one_chip, (BH, S, 1), jnp.float32),
                 _sds(one_chip, (BH, 1, 1), jnp.float32),
                 _sds(one_chip, (BH, S, N), jnp.bfloat16),
                 _sds(one_chip, (BH, S, N), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_gemma_2b_full_width_decode_fits_one_chip(one_chip):
    """The serve engine's decode step at gemma-2b's published widths with
    bf16 weights (4 slots, max_len 64): arguments plus temporaries fit one
    chip's HBM with room left for the KV cache and the admission program."""
    cfg = dataclasses.replace(get_arch("gemma-2b"), param_dtype=jnp.bfloat16)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab, cfg.head_dim) == \
        (18, 2048, 256000, 256)
    model = build_model(cfg)

    def placed(tree):
        return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    params = placed(model.abstract_params())
    cache = placed(abstract_tree(model.cache_defs(4, 64)))
    toks = _sds(one_chip, (4, 1), jnp.int32)
    c = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params, toks, cache).compile()
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES / 2, used


def _decode_step(sharding, arch, layers, slots, masked=False):
    """The serve engine's decode step as the chip benchmark serves it (bf16
    weights, max_len 1024, the cache donated, with the busy-row mask if
    ``masked``), compiled for ``sharding``: (model, abstract cache, HLO
    text)."""
    cfg = dataclasses.replace(get_arch(arch), n_layers=layers,
                              param_dtype=jnp.bfloat16)
    model = build_model(cfg)

    def placed(tree):
        return jax.tree.map(lambda s: _sds(sharding, s.shape, s.dtype), tree)

    cache = placed(abstract_tree(model.cache_defs(slots, 1024)))
    mask = [_sds(sharding, (slots,), jnp.bool_)] if masked else []
    text = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        placed(model.abstract_params()), _sds(sharding, (slots, 1), jnp.int32),
        cache, *mask).compile().as_text()
    return model, cache, text


DECODE_CASES = pytest.mark.parametrize("arch,layers,slots", [
    ("internlm2-20b", 6, 32),   # dense: stacked K and V caches
    ("mamba2-2.7b", 64, 16),    # ssm: stacked f32 SSM state and conv state
])


@DECODE_CASES
def test_decode_step_updates_the_donated_cache_in_place(one_chip, arch,
                                                        layers, slots):
    """The serve engine donates the cache to its decode step.  No copy in
    the compiled program, in any computation and while bodies included,
    has the shape of a stacked cache leaf: the layer scan updates the
    donated buffer in place instead of copying it whole before the loop.
    (An asynchronous ``copy-start`` moves a buffer between memory spaces,
    a prefetch; it is not counted.)  With the busy-row mask too."""
    for masked in (False, True):
        _, cache, text = _decode_step(one_chip, arch, layers, slots, masked)
        stacked = {",".join(map(str, leaf.shape)) for leaf in jax.tree.leaves(cache)}
        copies = [m.group(0) for m in re.finditer(
            r"%\S+ = \w+\[([\d,]*)\]\S* copy\(", text)
            if m.group(1) in stacked]
        assert not copies, copies


#: one HLO instruction: name, result shape, opcode, operands
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (.+?) ([\w-]+)\((.*?)\)(?:,|$)", re.M)


@DECODE_CASES
def test_decode_step_steps_the_ssm_state_in_one_kernel(one_chip, arch,
                                                       layers, slots):
    """mamba2's compiled decode step steps the stacked f32 SSM state in the
    ``ssd_decode`` Pallas kernel, and no fusion reads the stack: XLA's
    update and its separate ``y`` pass, which read the state twice, are
    gone.  So it does with the serve engine's busy-row mask, which the
    kernel reads to skip free rows: the mask adds no pass over the state.
    The dense family's decode step holds no such kernel."""
    for masked in (False, True):
        model, cache, text = _decode_step(one_chip, arch, layers, slots, masked)
        shapes, readers = {}, []
        for name, shape, opcode, operands in _INSTRUCTION.findall(text):
            shapes[name] = shape
            readers.append((name, opcode,
                            [o.strip().lstrip("%") for o in operands.split(",")]))
        kernels = [name for name, opcode, _ in readers
                   if opcode == "custom-call" and name.startswith("ssd_decode")]
        if model.cfg.family != "ssm":
            assert not kernels
            continue
        # one kernel call, in the body of the layer scan: one per layer
        assert len(kernels) == 1, kernels
        state = "f32[%s]" % ",".join(map(str, cache["blocks"]["ssm"].shape))
        assert state == "f32[%d,%d,80,64,128]" % (layers, slots)
        reads = [(name, opcode) for name, opcode, operands in readers
                 if any(shapes.get(o, "").startswith(state + "{") for o in operands)]
        assert {name for name, _ in reads if name in kernels}, reads
        assert not [r for r in reads if r[1] == "fusion"], reads
