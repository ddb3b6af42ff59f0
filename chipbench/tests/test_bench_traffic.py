"""The traffic generator: rate, clipping, and the same work for every seed."""
import os

import numpy as np
import pytest

from chipbench import traffic
from chipbench.spec import BENCH_DIR


def mix(name):
    return traffic.Mix.load_file(os.path.join(BENCH_DIR, "traffic", name + ".json"), name)


@pytest.mark.parametrize("name", ["chat", "longdoc"])
def test_lengths_are_clipped(name):
    m = mix(name)
    prompts, outputs, _ = traffic.sizes(m, 16)
    assert prompts.min() >= m.prompt.min and prompts.max() <= m.prompt.max
    assert outputs.min() >= m.output.min and outputs.max() <= m.output.max
    # the clip is reached on both sides in a few hundred draws of sigma 0.6+
    assert m.prompt.min in prompts or m.prompt.max in prompts


@pytest.mark.parametrize("name,slots", [("chat", 32), ("chat", 16), ("longdoc", 8)])
def test_rate_holds_load_times_slots_busy(name, slots):
    m = mix(name)
    _, outputs, gaps = traffic.sizes(m, slots)
    rho = m.load * slots / outputs.mean()
    assert gaps.mean() == pytest.approx(1.0 / rho, rel=0.15)
    # Little's law in decode steps: arrival rate x mean output = busy slots
    assert outputs.mean() / gaps.mean() == pytest.approx(m.load * slots, rel=0.15)
    cv = gaps.std() / gaps.mean()
    assert cv == pytest.approx(m.gap_cv, rel=0.25)


def test_every_seed_asks_for_the_same_work_at_the_same_moments():
    m = mix("chat")
    a = traffic.replay(m, 16, 1000, seed=2**31 + 5, index=3)
    b = traffic.replay(m, 16, 1000, seed=7, index=3)
    assert len(a) == len(b) == m.requests_per_slot * 16
    assert [(len(x.prompt), x.max_new, x.arrival_step) for x in a] == \
        [(len(y.prompt), y.max_new, y.arrival_step) for y in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another replay of the run: the same sizes in another order
    c = traffic.replay(m, 16, 1000, seed=7, index=4)
    assert sorted(r.max_new for r in c) == sorted(r.max_new for r in b)
    assert [r.max_new for r in c] != [r.max_new for r in b]
    assert max(r.arrival_step for r in c) == max(r.arrival_step for r in b)


def test_same_seed_same_requests():
    m = mix("longdoc")
    a = traffic.replay(m, 8, 500, seed=2**33 + 1, index=1)
    b = traffic.replay(m, 8, 500, seed=2**33 + 1, index=1)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.arrival_step == y.arrival_step for x, y in zip(a, b))
    assert all(0 <= t < 500 for r in a for t in r.prompt)


def test_warm_trace_reaches_every_bucket_and_row_count():
    m = mix("chat")
    reqs = traffic.warm_requests(m, 32, 100)
    lengths = m.warm_prompt_lengths()
    assert lengths == [32, 64, 128, 256, 512]
    groups = {}
    for r in reqs[:-1]:
        groups.setdefault(r.arrival_step, []).append(len(r.prompt))
    shapes = {(len(g), g[0]) for g in groups.values()}
    assert shapes == {(k, n) for n in lengths for k in (1, 2, 4, 8, 16, 32)}
    assert reqs[-1].max_new == 2
