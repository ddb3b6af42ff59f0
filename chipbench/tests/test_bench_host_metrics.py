"""The readers of device idle inside the engine's host phases, on a synthetic
reading whose gaps and spans are laid out by hand: each reader finds the
hand-computed number, finds nothing without its spans or its program, and
the idle they split adds up to the window's."""
import random

import pytest

from chipbench import harness, host_spans, peaks, spec
from chipbench.trace_reduce import Device, Summary

MS = 1e6  # ns

#: one admission group that compiled, then two decode steps; the loop's own
#: work fills 21-22 ms and 61-100 ms
SPANS = [("admit.pack", 0, 2), ("admit.compile", 2, 20), ("admit.commit", 20, 21),
         ("decode.prepare", 22, 23), ("decode.dispatch", 23, 24),
         ("decode.sync", 24, 40), ("decode.commit", 40, 41),
         ("decode.prepare", 41, 42), ("decode.dispatch", 42, 43),
         ("decode.sync", 43, 60), ("decode.commit", 60, 61)]
#: the admission program, then the two decode steps; the window is 0-100 ms,
#: so the gaps are 0-3, 18-24.5, 39-44 and 59.5-100 (55 ms idle)
BUSY = [(3, 18), (24.5, 39), (44, 59.5)]
MODULES = {"jit_decode_step": [30 * MS, 2], "jit__admit_impl": [15 * MS, 1]}


def reading(spans=SPANS, modules=MODULES, cell="internlm2-20b-6L.longdoc"):
    c = spec.load_cell(cell)
    dev = Device("/device:TPU:0", busy=[(s * MS, e * MS) for s, e in BUSY],
                 modules=modules, ops={})
    summary = Summary(window=(0.0, 100 * MS), devices=[dev], host=[])
    return harness.Reading(
        cell=c, work=c.work(), peaks=peaks.for_device("TPU v5 lite"), trace=summary,
        decode_steps=[[300] * 8] * 2, admitted=[900],
        spans=[(n, s * MS, e * MS) for n, s, e in spans],
        counts={"decode_steps": 2, "decode_tokens": 16, "slots": 8, "tokens": 17})


def read(r, name):
    return r.cell.reader(name)(r)


def test_each_reader_gives_the_hand_computed_value():
    r = reading()
    # admit spans 0-21 hold gaps 0-3 and 18-21: 6 ms over one admission
    assert read(r, "admit_host_ms") == pytest.approx(6.0)
    # decode spans 22-61 hold 22-24.5, 39-44 and 59.5-61: 9 ms over two steps
    assert read(r, "decode_host_ms") == pytest.approx(4.5)


def test_readers_find_nothing_without_their_spans_or_program():
    # the span names of an engine that logs one span per step and per wave
    old = [("admit_wave", 0, 21), ("decode_step", 22, 41), ("decode_step", 41, 61)]
    r = reading(spans=old)
    assert read(r, "decode_host_ms") is None
    assert read(r, "admit_host_ms") is None
    r = reading(modules={"jit_something_else": [1.0, 1]})
    assert read(r, "decode_host_ms") is None
    assert read(r, "admit_host_ms") is None


def test_the_split_adds_up_to_the_window_idle():
    r = reading()
    outside = 1.0 + 39.0        # 21-22 ms and 61-100 ms, by hand
    engine = host_spans.idle_s(r, ("decode.", "admit.")) * 1e3
    assert engine + outside == pytest.approx(55.0)
    decode_n = r.trace.module("decode_step")[1]
    admit_n = r.trace.module("_admit_impl")[1]
    window_idle = read(r, "idle_share") / 100 * r.trace.window_s * 1e3
    assert (read(r, "decode_host_ms") * decode_n + read(r, "admit_host_ms") * admit_n
            + outside) == pytest.approx(window_idle)


@pytest.mark.parametrize("seed", range(5))
def test_overlap_matches_a_count_of_unit_cells(seed):
    """Random disjoint gaps against random, possibly overlapping spans on a
    grid of whole units: the overlap is the number of cells both cover."""
    rng = random.Random(seed)
    cuts = sorted(rng.sample(range(200), 40))
    gaps = [(cuts[i], cuts[i + 1]) for i in range(0, 40, 2)]
    spans = []
    for _ in range(30):
        s = rng.randrange(200)
        spans.append((s, s + rng.randrange(1, 20)))
    in_gap = {c for g0, g1 in gaps for c in range(g0, g1)}
    in_span = {c for s0, s1 in spans for c in range(s0, s1)}
    assert host_spans.overlap_ns(gaps, spans) == len(in_gap & in_span)
