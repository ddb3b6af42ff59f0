"""Trace reduction: interval arithmetic, and a small trace recorded on a
TPU v5e (``chipbench/tests/record_trace.py``: five rounds of a jitted
four-layer scan and a jitted argmax, with host sleeps between them)."""
import glob
import os

import pytest

from chipbench import harness, trace_reduce
from chipbench.trace_reduce import Device, Summary

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "testdata")


def test_union_and_clip():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace_reduce.clip([(0, 4), (5, 9)], 2, 6) == [(2, 4), (5, 6)]


def fake():
    dev = Device("/device:TPU:0", busy=[(10.0, 20.0), (30.0, 35.0), (50.0, 90.0)],
                 modules={"jit_decode_step": [40.0, 2], "jit__admit_impl": [15.0, 1]},
                 ops={"fusion.1": 30.0, "fusion.2": 25.0})
    return Summary(window=(0.0, 60.0), devices=[dev], host=[])


def test_busy_gaps_and_modules():
    s = fake()
    assert s.busy_s() == pytest.approx(25e-9)
    assert s.gaps() == [(0.0, 10.0), (20.0, 30.0), (35.0, 50.0)]
    assert sum(e - a for a, e in s.gaps()) * 1e-9 + s.busy_s() == pytest.approx(s.window_s)
    assert s.module("decode_step") == (pytest.approx(40e-9), 2)
    assert s.module("_admit_impl") == (pytest.approx(15e-9), 1)
    assert s.module("nothing") is None
    assert s.top_ops(1) == [("fusion.1", pytest.approx(30e-9))]


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    spans = [("admit_wave", 18.0, 32.0), ("decode_step", 34.0, 60.0)]
    got = dict(harness._attribute(fake(), spans))
    assert got == {"engine_loop (1 gaps)": pytest.approx(10e-9),
                   "admit_wave (1 gaps)": pytest.approx(10e-9),
                   "decode_step (1 gaps)": pytest.approx(15e-9)}


def recorded():
    paths = glob.glob(os.path.join(TESTDATA, "*.xplane.pb"))
    assert paths, "the recorded TPU trace is missing from chipbench/testdata"
    return trace_reduce.load(paths[0])


def test_recorded_tpu_trace():
    s = recorded()
    assert len(s.devices) == 1
    assert 0 < s.busy_s() < s.window_s
    step = s.module("jit_layers")
    pick = s.module("lambda")
    assert step is not None and step[1] == 5
    assert pick is not None and pick[1] == 5
    # the window holds the five rounds and their host sleeps of 2 ms
    assert s.window_s > 5 * 0.002
    idle = sum(e - a for a, e in s.gaps()) * 1e-9
    assert idle + s.busy_s() == pytest.approx(s.window_s, rel=1e-6)
    assert idle > 4 * 0.002
    assert [n for n, _, _ in s.host].count("chipbench.step") == 5
    assert s.top_ops(3) and all(t > 0 for _, t in s.top_ops(3))
