"""One run of one cell: set up, warm up, measure a window, read the trace,
check the served tokens, and build the result line.

The system under test is ``repro.launch.serve.ServeEngine``; the benchmark
gives it weights made from the seed and requests made from the seed and the
cell's mix, and reads back its tokens, timestamps and counts.  Whether a
chip is there is ``run.py``'s business: this module runs on whatever device
JAX has, so the tests can drive it on the CPU.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List

from chipbench import check, stats, trace_reduce, traffic

#: how long the traced run's profiler window lasts, in seconds
TRACE_SECONDS = 4.0
#: where in the first replay the profiler starts, as a share of the decode
#: steps the replay is expected to take (past the ramp from empty slots)
TRACE_START = 0.25


class CompileCounter:
    """Counts traces and backend compiles while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on = False
        self.counts = {e.rsplit("/", 1)[-1]: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if self.on and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[-1]] += 1


class TraceHook:
    """Fired by the engine once per decode step: starts the profiler part
    way into the replay, records what each traced decode step worked on
    (the key count of every decoding row), and stops after
    ``TRACE_SECONDS``."""

    def __init__(self, engine, requests, start_step: int, out_dir: str):
        self.engine, self.requests = engine, requests
        self.start_step, self.out_dir = start_step, out_dir
        self.fired = 0
        self.state = "waiting"
        self.steps: List[List[int]] = []
        self.admitted: List[int] = []
        self.spans: List = []

    def _admitted_rids(self):
        return {r.rid for r in self.requests if r.out}

    def fire(self):
        import jax
        self.fired += 1
        if self.state == "waiting" and self.fired >= self.start_step:
            self._before = self._admitted_rids()
            jax.profiler.start_trace(self.out_dir)
            with jax.profiler.TraceAnnotation("chipbench.sync"):
                self.t_sync = time.time()
            self._window = jax.profiler.TraceAnnotation("chipbench.window")
            self._window.__enter__()
            self.t0 = time.perf_counter()
            self.state = "tracing"
        elif self.state == "tracing":
            e = self.engine
            self.steps.append([int(e.slot_pos[s]) + 1 for s in range(e.slots)
                               if e.slot_req[s] is not None and not e.slot_req[s].done])
            if time.perf_counter() - self.t0 >= TRACE_SECONDS:
                self.stop()

    def stop(self):
        import jax
        if self.state != "tracing":
            return
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        by_rid = {r.rid: r for r in self.requests}
        self.admitted = [len(by_rid[i].prompt)
                         for i in sorted(self._admitted_rids() - self._before)]
        self.state = "done"


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader gets."""
    cell: Any
    work: Any
    peaks: Any
    trace: trace_reduce.Summary
    decode_steps: List[List[int]]     # per traced decode step: key counts
    admitted: List[int]               # prompt lengths admitted while traced
    spans: List                       # engine spans, on the trace's clock (ns)
    counts: Dict[str, int]            # the whole window's counts


def _attribute(summary: trace_reduce.Summary, spans) -> List[List]:
    """Idle time of the first device by what the host was doing: inside
    an engine span (``admit_wave``, ``decode_step``), or between them."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    totals: Dict[str, List[float]] = {}
    for g0, g1 in summary.gaps():
        best, name = 0.0, "engine_loop"
        i = max(0, bisect.bisect_right(starts, g1) - 1)
        for s in spans[max(0, i - 2): i + 1]:
            ov = min(g1, s[2]) - max(g0, s[1])
            if ov > best and ov >= 0.5 * (g1 - g0):
                best, name = ov, s[0]
        t = totals.setdefault(name, [0.0, 0])
        t[0] += (g1 - g0) * 1e-9
        t[1] += 1
    return sorted(([f"{k} ({int(n)} gaps)", s] for k, (s, n) in totals.items()),
                  key=lambda kv: -kv[1])[:10]


def _device(jax, devices) -> Dict[str, Any]:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak)}


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             engine_wrap=None) -> Dict[str, Any]:
    """One run; returns the result line's object.  ``engine_wrap``, for
    tests, may replace the engine's jitted programs before the run."""
    import jax

    from repro.core.suite import Built
    from repro.launch.serve import ServeEngine
    from repro.models import build_model

    from chipbench import peaks as peaks_mod

    devices = jax.devices()[: cell.chips]
    ref, work = cell.reference(), cell.work()
    pcfg = cell.program_config()
    vocab = cell.config["vocab_size"]
    mix, slots = cell.mix, cell.slots

    weights = ref.init_weights(cell.config, seed)
    jax.block_until_ready(weights)
    engine = ServeEngine(Built(cfg=pcfg, model=build_model(pcfg), params=weights),
                         slots=slots, max_len=mix.max_len())
    if engine_wrap is not None:
        engine_wrap(engine)
    engine.run(traffic.warm_requests(mix, slots, vocab))
    jax.block_until_ready(engine.cache)
    counter = CompileCounter()
    setup_s = time.perf_counter() - t_start

    hook = None
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    counter.on = True
    served: List = []
    counts = {"decode_steps": 0, "tokens": 0, "requests": 0}
    t0 = time.perf_counter()
    index = 0
    while True:
        reqs = traffic.replay(mix, slots, vocab, seed, index)
        spans = None
        if trace and index == 0:
            _, outs, _ = traffic.sizes(mix, slots)
            expect = float((outs - 1).sum()) / (mix.load * slots)
            hook = TraceHook(engine, reqs, max(1, int(TRACE_START * expect)), trace_dir)
            spans = hook.spans
        out = engine.run(reqs, hook=hook if index == 0 else None, span_log=spans)
        if hook is not None and index == 0:
            hook.stop()
        counts["decode_steps"] += out["decode_steps"]
        counts["tokens"] += out["tokens"]
        counts["requests"] += out["requests"]
        served += reqs
        index += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    counter.on = False
    device = _device(jax, devices)
    compiles = dict(counter.counts)
    note = {"window_replays": index, "window_s": window_s, "compiles_in_window": compiles}
    print(f"window {note}", file=sys.stderr, flush=True)
    print(json.dumps({"window": note}), flush=True)

    engine.cache = None          # the program's state goes before the reference
    del engine

    failed = check.failed_requests(served, pcfg.vocab)
    result: Dict[str, Any] = {"correct": False, "attempted": len(served), "failed": failed}
    units = {m.name: m.unit for m in cell.end_to_end + cell.per_layer}
    if trace:
        summary = trace_reduce.load(_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        counts["decode_tokens"] = counts["tokens"] - counts["requests"]
        counts["slots"] = slots
        # engine spans are on time.time(); the sync annotation pins that
        # clock to the trace's
        sync = _sync_ns(summary)
        spans = [(s[0], sync + (s[1] - hook.t_sync) * 1e9, sync + (s[2] - hook.t_sync) * 1e9)
                 for s in hook.spans]
        reading = Reading(cell=cell, work=work,
                          peaks=peaks_mod.for_device(devices[0].device_kind)
                          if devices[0].platform == "tpu" else None,
                          trace=summary, decode_steps=hook.steps,
                          admitted=hook.admitted, spans=spans, counts=counts)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m.name)(reading)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": [list(x) for x in summary.top_ops(10)],
                               "idle_gaps": _attribute(summary, spans)}
    else:
        e2e = stats.end_to_end(served, window_s)
        e2e["setup_s"] = setup_s
        result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in e2e.items()
                             if k in units}
        result["device"] = device

    picked = check.sample(served, int(cell.check["rows"]), seed)
    gap = check.readings(cell, weights, picked)["served"] if picked else None
    result["correct"], checks = check.verdict(cell, gap, failed)
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr, flush=True)
    return result


def _xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _sync_ns(summary: trace_reduce.Summary) -> float:
    for name, s, _ in summary.host:
        if name == "chipbench.sync":
            return s
    raise ValueError("the trace has no chipbench.sync annotation")
