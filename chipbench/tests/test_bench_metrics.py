"""The per-layer readers on a synthetic reading: each finds its number, and
a reader that finds nothing to read returns nothing (never 0)."""
import json
import os

import pytest

from chipbench import harness, peaks, spec
from chipbench.spec import ROOT
from chipbench.trace_reduce import Device, Summary

MS = 1e6  # ns


def reading(modules, cell="internlm2-20b-6L.chat", steps=None, admitted=None):
    c = spec.load_cell(cell)
    dev = Device("/device:TPU:0", busy=[(0.0, 900 * MS)], modules=modules, ops={})
    summary = Summary(window=(0.0, 1000 * MS), devices=[dev], host=[])
    return harness.Reading(
        cell=c, work=c.work(), peaks=peaks.for_device("TPU v5 lite"), trace=summary,
        decode_steps=steps if steps is not None else [[300] * 24] * 50,
        admitted=admitted if admitted is not None else [200, 180, 400],
        spans=[("admit_wave", 100 * MS, 160 * MS), ("decode_step", 160 * MS, 180 * MS)],
        counts={"decode_steps": 100, "decode_tokens": 2400, "slots": 32, "tokens": 2500})


def metric_names():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return [m["name"] for m in bench["per_layer"]]


def read(r, name):
    return r.cell.reader(name)(r)


def test_every_declared_metric_has_a_reader():
    c = spec.load_cell("internlm2-20b-6L.chat")
    for name in metric_names():
        assert callable(c.reader(name))


def test_readings_on_a_full_trace():
    r = reading({"jit_decode_step": [50 * 12 * MS, 50], "jit__admit_impl": [60 * MS, 2]})
    assert read(r, "decode_occupancy") == pytest.approx(75.0)
    assert read(r, "decode_step_ms") == pytest.approx(12.0)
    assert read(r, "prefill_us_per_tok") == pytest.approx(60e3 / 780)
    assert read(r, "idle_share") == pytest.approx(10.0)
    for name in ("decode_roofline", "prefill_roofline", "mfu"):
        assert 0 < read(r, name) <= 100, name
    # 5.82 GB of weights and 24 rows x 300 keys x 24 KiB at 819 GB/s, over 12 ms
    least = (5.82e9 + 24 * 300 * 24 * 1024) / 819e9
    assert read(r, "decode_roofline") == pytest.approx(100 * least / 12e-3, rel=2e-3)


def test_readers_return_nothing_without_their_program():
    r = reading({"jit_something_else": [1.0, 1]})
    for name in ("decode_step_ms", "prefill_us_per_tok", "decode_roofline",
                 "prefill_roofline"):
        assert read(r, name) is None, name


def test_mamba_decode_roofline_counts_state_traffic():
    r = reading({"jit_decode_step": [20 * MS, 1]}, cell="mamba2-2.7b.chat",
                steps=[[100] * 12])
    w = r.work.weight_bytes(r.cell.config)
    state = r.work.state_bytes_per_row(r.cell.config)
    assert read(r, "decode_roofline") == pytest.approx(
        100 * (w + 2 * 12 * state) / 819e9 / 20e-3, rel=1e-6)
