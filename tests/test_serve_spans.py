"""The serve engine's host phase spans: every decode step and admission group
is tiled by named, ordered, non-overlapping spans; a compile shows by name
the first time a shape runs and never on a replay; an untraced run reads no
clock; the profiler's clock and ``time.time()`` advance together, which
puts the spans on a trace's clock; and the lowered programs carry the
model's layer scopes in their op metadata."""
import glob
import os
import re
import time
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core.suite import build_arch
from repro.launch import serve
from repro.launch.serve import (ADMIT_PHASES, DECODE_PHASES, ServeEngine,
                                decode_phase_log)
from repro.runner import TraceSpec, generate_trace
from repro.runner.traces import cache_len_bound
from repro.telemetry.spans import Tracer

ARCHS = {"dense": "gemma-2b", "ssm": "mamba2-2.7b"}


@pytest.fixture(scope="module", params=sorted(ARCHS))
def replays(request):
    """A fresh engine of the family, its trace, and the span logs and
    outputs of two replays of that trace."""
    reqs = generate_trace(TraceSpec("bursty", 10, 16, 5, seed=3,
                                    prompt_profile="bimodal"), vocab=500)
    engine = ServeEngine(build_arch(ARCHS[request.param]), slots=4,
                         max_len=cache_len_bound(reqs))
    runs = []
    for _ in range(2):
        log = []
        runs.append((log, engine.run(reqs, span_log=log)))
    return types.SimpleNamespace(engine=engine, reqs=reqs, runs=runs)


def _check_tiling(log, out):
    names = [s[0] for s in log]
    assert set(names) <= set(DECODE_PHASES + ADMIT_PHASES)
    for a, b in zip(log, log[1:]):
        assert a[1] <= a[2] <= b[1], (a, b)          # ordered, no overlap
    steps = [i for i, n in enumerate(names) if n == DECODE_PHASES[0]]
    assert len(steps) == out["decode_steps"] > 0
    for i in steps:
        step = log[i: i + len(DECODE_PHASES)]
        assert tuple(s[0] for s in step) == DECODE_PHASES
        for a, b in zip(step, step[1:]):
            assert a[2] == b[1]                      # contiguous (no hook)
    groups = [i for i, n in enumerate(names) if n == "admit.pack"]
    assert len(groups) == out["admit_calls"]
    for i in groups:
        assert names[i + 1] in ("admit.prefill", "admit.compile")
        assert log[i][2] == log[i + 1][1]


def test_phases_tile_every_decode_step_and_admission(replays):
    for log, out in replays.runs:
        _check_tiling(log, out)


def test_compile_spans_mark_first_runs_of_a_shape(replays):
    (first, out1), (second, out2) = replays.runs
    compiles = [s for s in first if s[0] == "admit.compile"]
    assert len(compiles) == out1["admit_new_shapes"] == len(out1["admit_shapes"])
    assert {(s[3]["rows"], s[3]["padded_len"]) for s in compiles} == \
        {tuple(x) for x in out1["admit_shapes"]}
    assert not [s for s in second if s[0] == "admit.compile"]
    calls = [s for s in second if s[0] == "admit.prefill"]
    assert len(calls) == out2["admit_calls"]
    prompts = sorted(len(r.prompt) for r in replays.reqs)
    assert sum(s[3]["requests"] for s in calls) == len(prompts)
    assert sum(s[3]["valid_tokens"] for s in calls) == sum(prompts)
    for s in calls:
        assert s[3]["requests"] <= s[3]["rows"]
        assert s[3]["valid_tokens"] <= s[3]["rows"] * s[3]["padded_len"]


def test_no_clock_read_without_a_span_log(replays, monkeypatch):
    calls = []

    def counted():
        calls.append(1)
        return time.time()
    monkeypatch.setattr(serve, "time", types.SimpleNamespace(
        time=counted, perf_counter=time.perf_counter))
    replays.engine.run(replays.reqs)
    assert calls == []
    replays.engine.run(replays.reqs, span_log=[])
    assert calls                                     # the patch is in force


def test_profiler_clock_keeps_its_offset_from_wall_time(tmp_path):
    """Two annotations 200 ms apart under a profiler trace: the offset
    between ``time.time()`` read inside each and the annotation's start in
    the trace is the same to within 100 us, so one annotation aligns a
    whole window of spans."""
    from jax.profiler import ProfileData
    wall = {}
    jax.profiler.start_trace(str(tmp_path))
    try:
        for name in ("clock.a", "clock.b"):
            with jax.profiler.TraceAnnotation(name):
                wall[name] = time.time()
            time.sleep(0.2)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[-1]
    start = {e.name: e.start_ns for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events if e.name in wall}
    assert set(start) == set(wall)
    trace_s = (start["clock.b"] - start["clock.a"]) * 1e-9
    assert trace_s > 0.19
    assert abs((wall["clock.b"] - wall["clock.a"]) - trace_s) < 100e-6


@pytest.mark.parametrize("family", sorted(ARCHS))
def test_lowered_programs_carry_layer_scopes(family):
    engine = ServeEngine(build_arch(ARCHS[family]), slots=2, max_len=32)

    def scopes(lowered):
        locs = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
        return {part for loc in locs for part in loc.split("/")}

    layers = {"dense": {"attn", "mlp"}, "ssm": {"ssm"}}[family]
    want = {"embed", "head"} | layers
    assert want <= scopes(engine.lowered_decode())
    admit = engine._admit.lower(
        engine.params, jnp.zeros((2, 8), jnp.int32), jnp.full((2,), 8, jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), bool), engine.cache)
    assert want | {"admit_scatter"} <= scopes(admit)


def test_runner_caps_decode_phases_by_step_and_splits_each_step():
    log = []
    t = 0.0
    for name in ("admit.pack", "admit.prefill", "admit.commit") + DECODE_PHASES * 3:
        log.append((name, t, t + 1.0))
        t += 1.0
    from repro.runner.runner import BenchmarkRunner
    tr = Tracer()
    with tr.span("measure") as ms:
        BenchmarkRunner._add_serve_spans(tr, ms, log, cap=2)
    names = [sp["name"] for sp in tr.export() if sp["name"] != "measure"]
    assert names == ["admit.pack", "admit.prefill", "admit.commit"] + list(DECODE_PHASES) * 2
    assert ms.attrs["decode_steps_dropped"] == 1
    assert ms.attrs["decode_steps_dropped_s"] == 4.0
    assert decode_phase_log(log) == [(2.0, 1.0)] * 3
