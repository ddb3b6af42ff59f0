"""The measured profiling subsystem: phase timelines, op-class
attribution (fractions sum to 1), detector rules on synthetic profiles,
report ranking, and runner integration (step cells, serve, overhead
bound)."""
import json

import pytest

from repro.core.hloanalysis import HloCost, analyze_hlo, op_class
from repro.profiler import (Thresholds, Timeline, attribute, build_report,
                            detect, format_table)
from repro.profiler.timeline import PhaseSample
from repro.runner import BenchmarkRunner, Scenario

PROF_FRACS = ("prof_frac_compute", "prof_frac_memory",
              "prof_frac_collective", "prof_frac_dispatch", "prof_frac_idle")


def _frac_sum(rr):
    return sum(rr.extra[k] for k in PROF_FRACS)


# ---- op classes -----------------------------------------------------------

def test_op_class_mapping():
    assert op_class("dot") == "matmul"
    assert op_class("convolution") == "matmul"
    assert op_class("all-reduce") == "collective"
    assert op_class("all-gather-start") == "collective"
    assert op_class("add") == "elementwise"
    assert op_class("reduce") == "other"
    assert op_class("custom-call", 'custom_call_target="flash_attention"') == "attention"
    assert op_class("custom-call", 'custom_call_target="topk"') == "other"


def test_hlo_class_tallies_sum_to_totals():
    hlo = """
HloModule m

ENTRY %main (x: f32[64,64], y: f32[64,64]) -> f32[64,64] {
  %x = f32[64,64] parameter(0)
  %y = f32[64,64] parameter(1)
  %d = f32[64,64] dot(%x, %y), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %a = f32[64,64] add(%d, %x)
}
"""
    c = analyze_hlo(hlo)
    assert c.flops > 0 and c.bytes_accessed > 0
    assert abs(sum(c.flops_by_class.values()) - c.flops) < 1e-6
    assert abs(sum(c.bytes_by_class.values()) - c.bytes_accessed) < 1e-6
    assert c.flops_by_class["matmul"] == 2.0 * 64 * 64 * 64


# ---- attribution ----------------------------------------------------------

def _timeline(dispatch=100.0, device=900.0, n=3, idle=0.0):
    return Timeline(kind="step",
                    samples=[PhaseSample(dispatch, device)] * n,
                    idle_us=idle)


def test_attribute_fractions_sum_and_split():
    cost = HloCost()
    cost.tally_flops("matmul", 1e12)       # strongly compute-bound class
    cost.tally_bytes("matmul", 1e6)
    cost.tally_flops("elementwise", 1e3)   # strongly memory-bound class
    cost.tally_bytes("elementwise", 1e9)
    att = attribute(_timeline(), cost)
    assert abs(sum(att.fractions().values()) - 1.0) < 1e-9
    assert abs(att.frac_dispatch - 0.1) < 1e-9
    assert abs(sum(att.class_frac.values()) - 1.0) < 1e-9
    # both classes carry device time, and the split respects boundedness:
    # matmul's share is mostly compute, elementwise's mostly memory
    assert att.class_us["matmul"] > 0 and att.class_us["elementwise"] > 0
    assert att.frac_compute > 0 and att.frac_memory > 0
    assert att.frac_idle == 0.0


def test_attribute_empty_cost_lands_in_idle():
    att = attribute(_timeline(), HloCost())
    assert abs(sum(att.fractions().values()) - 1.0) < 1e-9
    assert abs(att.frac_idle - 0.9) < 1e-9      # all device time unexplained
    assert att.frac_compute == att.frac_memory == 0.0
    assert att.util == 0.0


def test_attribute_serve_idle_share():
    # serve: 10 decode steps of 1ms + 10ms outside them (prefill/queue)
    tl = Timeline.from_phase_log([(1e-4, 9e-4)] * 10, kind="decode_step",
                                 wall_s=0.02)
    assert abs(tl.idle_us - 1e4) < 1e-6
    cost = HloCost()
    cost.tally_flops("matmul", 1e9)
    cost.tally_bytes("matmul", 1e6)
    att = attribute(tl, cost)
    assert abs(sum(att.fractions().values()) - 1.0) < 1e-9
    assert abs(att.frac_idle - 0.5) < 1e-9
    assert abs(att.frac_dispatch - 0.05) < 1e-9


# ---- detectors on synthetic profiles --------------------------------------

def _rec(name, task="train", status="ok", compile_us=0.0, wall_s=1.0, **extra):
    return {"name": name, "task": task, "status": status,
            "compile_us": compile_us, "wall_s": wall_s, "extra": extra}


def _prof(mem=0.2, comp=0.6, disp=0.1, util=1e-3, **kw):
    return dict(prof_frac_memory=mem, prof_frac_compute=comp,
                prof_frac_collective=0.0, prof_frac_dispatch=disp,
                prof_frac_idle=max(0.0, 1.0 - mem - comp - disp),
                prof_util=util, **kw)


def test_detector_data_movement_fires_and_stays_silent():
    hot = _rec("a/train/x", **_prof(mem=0.8, comp=0.1))
    cold = _rec("b/train/x", **_prof(mem=0.3, comp=0.6))
    rules = [f.rule for f in detect([hot, cold])]
    hits = [f for f in detect([hot, cold]) if f.rule == "data_movement_bound"]
    assert [f.cell for f in hits] == ["a/train/x"]
    assert hits[0].severity == "crit"        # > 0.75
    assert "data_movement_bound" in rules


def test_detector_dispatch_bound():
    hot = _rec("a/x", **_prof(mem=0.2, comp=0.2, disp=0.5))
    cold = _rec("b/x", **_prof(disp=0.1))
    hits = [f for f in detect([hot, cold]) if f.rule == "dispatch_bound"]
    assert [f.cell for f in hits] == ["a/x"]


def test_detector_low_util_is_relative_to_sweep():
    recs = [_rec(f"c{i}/x", **_prof(util=1e-3)) for i in range(4)]
    slow = _rec("slow/x", **_prof(util=1e-5))
    hits = [f for f in detect(recs + [slow]) if f.rule == "low_util"]
    assert [f.cell for f in hits] == ["slow/x"]
    # too few cells for a meaningful median: silent
    assert not [f for f in detect([slow, recs[0]]) if f.rule == "low_util"]


def test_detector_compile_outlier():
    recs = [_rec(f"c{i}/x", compile_us=2e5) for i in range(3)]
    big = _rec("big/x", compile_us=5e6)
    hits = [f for f in detect(recs + [big]) if f.rule == "compile_outlier"]
    assert [f.cell for f in hits] == ["big/x"]
    # large multiple but tiny absolute compile time: silent
    small = [_rec("s0/x", compile_us=10.0), _rec("s1/x", compile_us=10.0),
             _rec("sbig/x", compile_us=400.0)]
    assert not [f for f in detect(small) if f.rule == "compile_outlier"]


def test_detector_queue_saturation():
    sat = _rec("s/serve/x", task="serve", slots=2, queue_depth_mean=5.0,
               queue_depth_max=9, trace="bursty")
    okq = _rec("ok/serve/x", task="serve", slots=4, queue_depth_mean=1.0,
               queue_depth_max=3, trace="uniform")
    hits = [f for f in detect([sat, okq]) if f.rule == "queue_saturation"]
    assert [f.cell for f in hits] == ["s/serve/x"]
    assert hits[0].severity == "crit"        # 5.0 > 2 * slots


def test_report_ranks_by_severity_then_score_and_formats():
    recs = [
        _rec("crit/x", **_prof(mem=0.9, comp=0.05)),            # crit
        _rec("warn/x", **_prof(mem=0.6, comp=0.2)),             # warn
        _rec("c0/x", compile_us=1e5), _rec("c1/x", compile_us=1e5),
        _rec("big/x", compile_us=9e6),                          # info
    ]
    findings = detect(recs)
    sev = [f.severity for f in findings]
    assert sev == sorted(sev, key=["crit", "warn", "info"].index)
    report = build_report(recs, findings, meta={"fast": True})
    assert report["cells"] == 5 and report["cells_profiled"] == 2
    assert report["by_severity"]["crit"] == 1
    assert json.loads(json.dumps(report)) == report
    table = format_table(report)
    assert "crit" in table and "data_movement_bound" in table


# ---- runner integration (real cells) --------------------------------------

@pytest.fixture(scope="module")
def prof_runner():
    return BenchmarkRunner(runs=2, warmup=0)


def test_profiled_real_cell_fractions_sum_to_one(prof_runner):
    sc = Scenario(arch="gemma-2b", task="train", batch=1, seq=8)
    rr = prof_runner.run(sc, profile=True, record=False)
    assert rr.status == "ok", rr.error
    assert abs(_frac_sum(rr) - 1.0) < 0.05
    assert rr.extra["prof_kind"] == "step"
    assert rr.extra["prof_steps"] == 2
    assert len(rr.extra["prof_timeline"]) == 2
    assert rr.extra["prof_flops"] > 0
    # a transformer train step is matmul-heavy in its op-class split
    assert rr.extra["prof_class_frac"]["matmul"] > 0.01
    assert abs(sum(rr.extra["prof_class_frac"].values()) - 1.0) < 1e-6
    # the record stays JSON-serializable (store round-trip)
    assert json.loads(json.dumps(rr.to_dict()))["extra"]["prof_steps"] == 2


def test_unprofiled_run_records_no_prof_keys(prof_runner):
    sc = Scenario(arch="gemma-2b", task="train", batch=1, seq=8)
    rr = prof_runner.run(sc, record=False)
    assert rr.status == "ok"
    assert not any(k.startswith("prof_") for k in rr.extra)


def test_profiled_serve_cell_records_decode_timeline(prof_runner):
    sc = Scenario(arch="gemma-2b", task="serve", batch=4, seq=8,
                  slots=2, trace="bursty")
    rr = prof_runner.run(sc, profile=True, record=False)
    assert rr.status == "ok", rr.error
    assert rr.extra["prof_kind"] == "decode_step"
    assert rr.extra["prof_steps"] == rr.extra["decode_steps"]
    assert abs(_frac_sum(rr) - 1.0) < 0.05
    # admission + per-request prefill happen outside decode steps
    assert rr.extra["prof_idle_us"] > 0


def test_profile_overhead_within_tolerance(prof_runner):
    """Profiled and unprofiled median step times must agree: the phase
    split is two extra perf_counter reads per step and attribution runs
    outside the timed loop.  (Generous bound — shared CI hosts are noisy;
    runner_bench reports the honest ratio.)"""
    sc = Scenario(arch="gemma-2b", task="train", batch=2, seq=32)
    prof_runner.run(sc, record=False, runs=2)            # compile + settle
    base = prof_runner.run(sc, record=False, runs=3)
    prof = prof_runner.run(sc, record=False, runs=3, profile=True)
    assert base.status == prof.status == "ok"
    assert prof.median_us < base.median_us * 1.5
