"""The decode step's busy-row mask: the serve engine tells the decode step
which rows decode, the ssm family leaves every other row's SSM state alone,
and no family serves different tokens for it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.suite import build_arch
from repro.launch.serve import ServeEngine
from repro.runner import TraceSpec, generate_trace
from repro.runner.traces import Request, cache_len_bound, tokens_by_rid

SLOTS = 3


@pytest.fixture(scope="module")
def mamba2():
    return build_arch("mamba2-2.7b")


def test_masked_decode_step_leaves_free_rows_state(mamba2):
    """At the model's decode step, a free row's SSM state comes back bit for
    bit, in every layer, and the busy rows get what the unmasked step
    gives them."""
    model, params = mamba2.model, mamba2.params
    toks = jnp.asarray([[3], [7], [11]], jnp.int32)
    cache = model.init_cache(SLOTS, 16)
    cache["blocks"]["ssm"] = jax.random.normal(jax.random.key(1),
                                               cache["blocks"]["ssm"].shape)
    active = jnp.asarray([True, False, True])
    step = jax.jit(model.decode_step)
    logits, masked = step(params, toks, cache, active)
    logits0, full = step(params, toks, cache)
    old, new, ref = (np.asarray(c["blocks"]["ssm"]) for c in (cache, masked, full))
    np.testing.assert_array_equal(new[:, 1], old[:, 1])
    assert not np.array_equal(ref[:, 1], old[:, 1])     # unmasked, it moves
    np.testing.assert_array_equal(new[:, [0, 2]], ref[:, [0, 2]])
    np.testing.assert_array_equal(np.asarray(logits, np.float32)[[0, 2]],
                                  np.asarray(logits0, np.float32)[[0, 2]])


def test_staggered_serve_matches_each_request_alone(mamba2):
    """The determinism witness: requests arriving over time, so slots go
    free and are admitted again mid-run while others decode, get the tokens
    each gets served alone.  The mask goes to the device once per change of
    the busy rows, not once per step."""
    reqs = generate_trace(TraceSpec("mixed", 8, 12, 8, seed=4,
                                    prompt_profile="bimodal"), vocab=500)
    engine = ServeEngine(mamba2, slots=SLOTS, max_len=cache_len_bound(reqs))
    masks = []
    jitted = engine._decode_step

    def spy(params, tokens, cache, active):
        masks.append(np.asarray(active))
        return jitted(params, tokens, cache, active)

    engine._decode_step = spy
    out = engine.run(reqs)
    assert any(0 < m.sum() < SLOTS for m in masks)      # free rows mid-run
    changes = 1 + sum(not np.array_equal(a, b) for a, b in zip(masks, masks[1:]))
    assert out["decode_mask_uploads"] == changes < out["decode_steps"]
    assert out["admit_calls"] > 1

    alone = []
    for r in sorted(reqs, key=lambda r: r.rid):
        solo = Request(r.rid, r.prompt, r.max_new)
        engine.run([solo])
        alone.append(solo.out)
    assert tokens_by_rid(reqs) == alone


def test_dense_decode_step_ignores_the_mask():
    """A family without SSM state lowers its decode step to the same
    program with and without the mask: the jit prunes the argument."""
    built = build_arch("gemma-2b")
    model = built.model
    cache = model.init_cache(SLOTS, 16)
    toks = jnp.zeros((SLOTS, 1), jnp.int32)
    step = jax.jit(model.decode_step, donate_argnums=(2,))
    assert step.lower(built.params, toks, cache).as_text() == \
        step.lower(built.params, toks, cache, jnp.ones(SLOTS, bool)).as_text()
