"""The unified BenchmarkRunner: one execution path for the suite tables,
figures, and regression CI.

Responsibilities (previously hand-rolled per ``benchmarks/*`` script):

* resolve ``Scenario``s against the suite registry (``core.suite``);
* reuse expensive state across scenarios —
  - **arch builds** (config + model + initialised params) are cached per
    (arch, dtype, mode-overrides) and shared across every task/batch/seq
    of that arch;
  - **compiled executables** (jitted step + live threaded args) are cached
    per scenario, so re-measuring the same cell (regression CI, bisection)
    never re-jits or re-compiles;
* run every cell in the calling process — a chip belongs to one
  process, so no cell is ever handed to a worker — with per-cell fault
  containment (a failing cell becomes an error record);
* emit a versioned ``RunResult`` per execution into a ``ResultStore``;
* own the **derived** (compile-only dry-run) path with the same store-level
  caching, so figures that share a cell pay for one subprocess, not N.

``runner.stats`` counts builds/compiles/cache hits — the reuse speedup is
benchmarked by ``benchmarks/runner_bench.py``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.core.harness import RegressionHook, measure, measure_eager, prepare
from repro.core.suite import Built, build_arch, get_benchmark
from repro.fleet.metrics import registry as metrics_registry
from repro.profiler.attribution import attribute, cost_for_executable
from repro.profiler.timeline import Timeline, device_memory_stats
from repro.runner.latency import percentile
from repro.runner.traces import cache_len_bound, spec_for_scenario
from repro.runner.traces import generate as generate_trace
from repro.runner.results import ResultStore, RunResult
from repro.runner.scenario import Scenario, ScenarioMatrix, select_scenarios
from repro.telemetry.provenance import stamp as stamp_provenance
from repro.telemetry.spans import NULL_TRACER, Tracer, group_label


@dataclasses.dataclass
class RunnerStats:
    model_builds: int = 0
    model_cache_hits: int = 0
    executable_builds: int = 0
    executable_cache_hits: int = 0
    dryrun_runs: int = 0
    dryrun_cache_hits: int = 0
    scenarios_run: int = 0
    errors: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _ExecEntry:
    jitted: Optional[Callable]      # None for eager mode
    step: Callable
    args: Tuple                     # threaded, donation-valid arguments
    donate: Tuple[int, ...]


class BenchmarkRunner:
    def __init__(self, store: Optional[ResultStore] = None, *,
                 runs: int = 5, warmup: int = 1, compile_warmup: int = 3,
                 reuse: bool = True, profile: bool = False,
                 tracer: Optional[Tracer] = None,
                 coverage: bool = False):
        self.store = store
        self.runs = runs
        self.warmup = warmup
        # extra warmup steps after a fresh compile: the first post-compile
        # iterations run well above steady state (thread-pool/allocator
        # churn), which would skew a fresh measurement vs a cache-hit
        # re-measure and break baseline comparability in regression CI
        self.compile_warmup = compile_warmup
        self.reuse = reuse
        # measured profiling (src/repro/profiler/): per-step phase
        # timelines + op-class attribution under extra["prof_*"]; per-call
        # override via run(..., profile=...)
        self.profile = profile
        # span tracing (src/repro/telemetry/): an enabled Tracer records
        # matrix -> group -> cell -> phase spans; the default NULL_TRACER
        # makes every span site a cheap no-op
        self.tracer = tracer or NULL_TRACER
        # API-surface coverage annotations (opt-in, step cells only):
        # trace each scenario's step once through
        # core.coverage.jaxpr_primitives and attach extra["cov_*"] counts;
        # the process-wide union feeds the metrics-snapshot gauge.  The
        # trace is cached per scenario, so re-measures pay nothing.
        self.coverage = coverage
        self._cov_cache: Dict[Scenario, frozenset] = {}
        self._cov_union: set = set()
        # session-level scenario selection (the CLI --filter/--exclude
        # regexes), applied on top of each matrix's own selection
        self.default_filter: Tuple[str, ...] = ()
        self.default_exclude: Tuple[str, ...] = ()
        # force recompilation of cached dry-run cells (CLI --refresh)
        self.dryrun_refresh = False
        self.stats = RunnerStats()
        self._built: Dict[Tuple, Built] = {}
        self._execs: Dict[Scenario, _ExecEntry] = {}
        # serve engines (compiled prefill/decode + slot state) cached per
        # (build_key, max_len) — the serving analogue of _execs
        self._serve_engines: Dict[Tuple, Any] = {}
        self._dryrun_mem: Dict[str, dict] = {}
        # profiled cells' HLO op-class costs, keyed like the executable
        # they describe (scenario for step cells, engine key for serve) —
        # the attribution AOT compile is paid once per executable, not per
        # profiled re-measure
        self._prof_costs: Dict[Any, Any] = {}

    # ---- build / executable caches -------------------------------------

    def built_for(self, arch: str, *, dtype: str = "fp32",
                  mode: str = "jit_donated") -> Built:
        """The cached arch build for (arch, dtype, mode-overrides)."""
        sc = Scenario(arch=arch, dtype=dtype, mode=mode)
        key = sc.build_key()
        if key in self._built:
            self.stats.model_cache_hits += 1
            return self._built[key]
        built = build_arch(arch, sc.build_overrides())
        self.stats.model_builds += 1
        if self.reuse:
            self._built[key] = built
        return built

    def _resolve(self, scenario: Scenario) -> Tuple[_ExecEntry, Dict[str, bool]]:
        if self.reuse and scenario in self._execs:
            self.stats.executable_cache_hits += 1
            return self._execs[scenario], {"model_reused": True,
                                           "executable_reused": True}
        hits0 = self.stats.model_cache_hits
        built = self.built_for(scenario.arch, dtype=scenario.dtype,
                               mode=scenario.mode)
        bench = get_benchmark(scenario.arch, scenario.task)
        step, args, donate = bench.make(batch=scenario.batch, seq=scenario.seq,
                                        built=built)
        if scenario.mode == "eager":
            entry = _ExecEntry(jitted=None, step=step, args=args, donate=())
        else:
            d = donate if scenario.mode == "jit_donated" else ()
            entry = _ExecEntry(jitted=prepare(step, d), step=step,
                               args=args, donate=d)
            self.stats.executable_builds += 1
        if self.reuse:
            self._execs[scenario] = entry
        return entry, {"model_reused": self.stats.model_cache_hits > hits0,
                       "executable_reused": False}

    # ---- measured path --------------------------------------------------

    def run(self, scenario: Scenario, *, hook: Optional[RegressionHook] = None,
            runs: Optional[int] = None, warmup: Optional[int] = None,
            record: bool = True, profile: Optional[bool] = None,
            extra: Optional[Dict[str, Any]] = None) -> RunResult:
        """Execute one scenario and return its RunResult (never raises for
        benchmark failures — they come back as status="error" records).

        ``task="serve"`` cells run the continuous-batching engine over the
        scenario's trace instead of the ``measure()`` step protocol;
        ``runs``/``warmup`` don't apply there (the trace defines the work).

        ``profile`` (default: the runner's ``profile`` setting) captures a
        per-step phase timeline during the SAME timed loop and attributes
        it over HLO op classes (``repro.profiler``); the profile lands
        under ``extra["prof_*"]``.  Eager cells can't profile (no compiled
        module, synchronous dispatch) and record ``prof_skipped`` instead.

        ``extra`` is merged into the result's extras (ok or error) —
        ``run_matrix`` uses it to attach matrix-expansion context
        (e.g. ``slots_fallback``) to the record before it is stored.
        """
        prof = self.profile if profile is None else profile
        if scenario.task in ("serve", "loadgen"):
            return self._run_serve(scenario, hook=hook, record=record,
                                   profile=prof, extra=extra)
        if scenario.task == "kernel":
            return self._run_kernel(scenario, hook=hook, runs=runs,
                                    warmup=warmup, record=record,
                                    profile=prof, extra=extra)
        t0 = time.perf_counter()
        self.stats.scenarios_run += 1
        tr = self.tracer
        phase_log: Optional[List[Tuple[float, float]]] = None
        with tr.span("cell:" + scenario.name, kind="cell",
                     cell=scenario.name) as cs:
            try:
                with tr.span("build", kind="phase"):
                    entry, cache = self._resolve(scenario)
                # trace coverage before the measure: donated buffers are
                # still live here (the jaxpr trace is abstract, but fresh
                # args keep it valid on every mode)
                cov = self._coverage_extra(scenario, entry) \
                    if self.coverage else None
                if scenario.mode == "eager":
                    with tr.span("measure", kind="phase"):
                        m = measure_eager(scenario.name, entry.step,
                                          entry.args,
                                          runs=max(2, (runs or self.runs) // 2),
                                          hook=hook)
                else:
                    if prof:
                        phase_log = []
                    events: Optional[list] = [] if tr.enabled else None
                    final_args: List[Tuple] = []
                    wu = self.warmup if warmup is None else warmup
                    if not cache.get("executable_reused"):
                        wu += self.compile_warmup
                    m = measure(scenario.name, entry.step, entry.args,
                                entry.donate,
                                runs=runs or self.runs, warmup=wu,
                                hook=hook, jitted=entry.jitted,
                                final_args=final_args, phase_log=phase_log,
                                events=events)
                    if self.reuse and final_args:
                        # donated buffers were consumed: keep the threaded
                        # args so the cached executable stays callable next
                        # time
                        entry.args = final_args[0]
                    if events:
                        for ph, tw0, tw1 in events:
                            tr.add(ph, ts=tw0, dur_s=tw1 - tw0, parent=cs)
                rr = RunResult.from_measurement(
                    scenario, m, wall_s=time.perf_counter() - t0, cache=cache)
                if cache.get("executable_reused"):
                    # nothing compiled on a cache hit; measure()'s first call
                    # timed an ordinary step, which is not a compile time
                    rr.compile_us = 0.0
                if cov:
                    rr.extra.update(cov)
                if prof:
                    if scenario.mode == "eager":
                        rr.extra["prof_skipped"] = "eager"
                    else:
                        with tr.span("attribute", kind="phase"):
                            rr.extra.update(self._profile_extra(
                                scenario, phase_log,
                                lambda: entry.jitted.lower(*entry.args)))
            except Exception as e:  # noqa: BLE001 — fault containment per cell
                self.stats.errors += 1
                # a failed measure may have consumed donated buffers
                # mid-loop: evict the cached executable so the next run
                # rebuilds cleanly
                self._execs.pop(scenario, None)
                rr = RunResult.from_error(scenario, f"{type(e).__name__}: {e}",
                                          wall_s=time.perf_counter() - t0)
                cs.set(error=rr.error)
            cs.set(status=rr.status)
        return self._finalize(rr, cs, extra, record)

    def _finalize(self, rr: RunResult, cell_span: Any,
                  extra: Optional[Dict[str, Any]], record: bool) -> RunResult:
        """Shared result epilogue: merge matrix-provided extras, stamp
        span ids + provenance, record."""
        if extra:
            rr.extra.update(extra)
        tr = self.tracer
        if tr.enabled and getattr(cell_span, "span_id", ""):
            rr.extra["span_trace"] = tr.trace_id
            rr.extra["span_cell"] = cell_span.span_id
        stamp_provenance(rr)
        metrics_registry().record_result(rr)
        if record and self.store is not None:
            self.store.append(rr)
        return rr

    def _coverage_extra(self, scenario: Scenario,
                        entry: _ExecEntry) -> Dict[str, int]:
        """Per-scenario jaxpr-primitive counts (``extra["cov_*"]``) and the
        process-union gauge — the cheap seed for the coverage loop."""
        prims = self._cov_cache.get(scenario)
        if prims is None:
            from repro.core.coverage import jaxpr_primitives
            try:
                prims = frozenset(jaxpr_primitives(entry.step, *entry.args))
            except Exception:   # noqa: BLE001 — coverage is advisory
                prims = frozenset()
            self._cov_cache[scenario] = prims
        new = prims - self._cov_union
        self._cov_union |= prims
        metrics_registry().set_gauge("fleet_cov_union_primitives",
                                     len(self._cov_union))
        return {"cov_primitives": len(prims), "cov_new_primitives": len(new)}

    # ---- kernel micro-bench path (the autotuner's cells) -----------------

    def _run_kernel(self, scenario: Scenario, *,
                    hook: Optional[RegressionHook] = None,
                    runs: Optional[int] = None,
                    warmup: Optional[int] = None,
                    record: bool = True, profile: bool = False,
                    extra: Optional[Dict[str, Any]] = None) -> RunResult:
        """One tuning candidate (``task="kernel"``): decode the candidate
        id from the ``arch`` axis (``repro.tuning.space``), jit its
        ops-layer call, and measure it under the standard ``measure()``
        protocol — so a sweep's cells run and record exactly like model
        cells.  The candidate's identity lands under
        the well-known ``tuning_*`` extras (``runner/results.py``).

        The compiled candidate is cached in ``self._execs`` like a model
        executable: re-measuring a candidate (regression CI) hits the
        cache."""
        from repro.tuning import space as tuning_space
        t0 = time.perf_counter()
        self.stats.scenarios_run += 1
        tr = self.tracer
        phase_log: Optional[List[Tuple[float, float]]] = None
        with tr.span("cell:" + scenario.name, kind="cell",
                     cell=scenario.name) as cs:
            try:
                with tr.span("build", kind="phase"):
                    case, params = tuning_space.parse_candidate(
                        scenario.arch, dtype=scenario.dtype)
                    if self.reuse and scenario in self._execs:
                        self.stats.executable_cache_hits += 1
                        entry = self._execs[scenario]
                        cache = {"model_reused": True,
                                 "executable_reused": True}
                    else:
                        step, args = tuning_space.bench_callable(case, params)
                        entry = _ExecEntry(jitted=prepare(step), step=step,
                                           args=args, donate=())
                        self.stats.executable_builds += 1
                        if self.reuse:
                            self._execs[scenario] = entry
                        cache = {"model_reused": False,
                                 "executable_reused": False}
                if profile:
                    phase_log = []
                events: Optional[list] = [] if tr.enabled else None
                wu = self.warmup if warmup is None else warmup
                if not cache["executable_reused"]:
                    wu += self.compile_warmup
                m = measure(scenario.name, entry.step, entry.args,
                            entry.donate,
                            runs=runs or self.runs, warmup=wu, hook=hook,
                            jitted=entry.jitted, phase_log=phase_log,
                            events=events)
                if events:
                    for ph, tw0, tw1 in events:
                        tr.add(ph, ts=tw0, dur_s=tw1 - tw0, parent=cs)
                rr = RunResult.from_measurement(
                    scenario, m, wall_s=time.perf_counter() - t0, cache=cache,
                    extra=tuning_space.result_extra(case, params))
                if cache["executable_reused"]:
                    rr.compile_us = 0.0
                if profile:
                    with tr.span("attribute", kind="phase"):
                        rr.extra.update(self._profile_extra(
                            scenario, phase_log,
                            lambda: entry.jitted.lower(*entry.args)))
            except Exception as e:  # noqa: BLE001 — fault containment per cell
                self.stats.errors += 1
                self._execs.pop(scenario, None)
                rr = RunResult.from_error(scenario, f"{type(e).__name__}: {e}",
                                          wall_s=time.perf_counter() - t0)
                cs.set(error=rr.error)
            cs.set(status=rr.status)
        return self._finalize(rr, cs, extra, record)

    # ---- measured profiling ---------------------------------------------

    def _profile_extra(self, cost_key: Any, phase_log, lower, *,
                       kind: str = "step", wall_s: float = 0.0) -> Dict[str, Any]:
        """The ``extra["prof_*"]`` payload for one profiled execution:
        timeline from the measured ``phase_log`` plus op-class attribution
        from the executable's (cached) HLO cost.  Attribution failures
        degrade to a timeline-only profile with ``prof_error`` — profiling
        must never turn a good measurement into an error record."""
        tl = Timeline.from_phase_log(phase_log or [], kind=kind,
                                     wall_s=wall_s,
                                     memory=device_memory_stats())
        extra = tl.to_extra()
        try:
            cost = self._prof_costs.get(cost_key)
            if cost is None:
                cost = cost_for_executable(lower)
                if self.reuse:
                    self._prof_costs[cost_key] = cost
        except Exception as e:  # noqa: BLE001 — profile degrades, cell stays ok
            from repro.core.hloanalysis import HloCost
            cost = HloCost()
            extra["prof_error"] = f"{type(e).__name__}: {e}"
        extra.update(attribute(tl, cost).to_extra())
        return extra

    # ---- serving path ----------------------------------------------------

    def _serve_engine_for(self, scenario: Scenario, built: Built,
                          max_len: int) -> Tuple[Any, bool]:
        """The cached continuous-batching engine for a serve cell; returns
        (engine, reused).  Keyed by (build_key, mode, max_len, admission):
        the compiled decode step is shaped by (slots, max_len), its
        donation by mode — build_key alone can't tell jit from jit_donated
        — and the admission policy picks the engine's prefill protocol
        (batched wave vs per-request), while trace profiles of one shape
        share the engine (the trace never affects compilation)."""
        from repro.launch.serve import ServeEngine
        key = (scenario.build_key(), scenario.mode, max_len,
               scenario.admission)
        if self.reuse and key in self._serve_engines:
            self.stats.executable_cache_hits += 1
            return self._serve_engines[key], True
        engine = ServeEngine(built, slots=scenario.slots, max_len=max_len,
                             donate=scenario.mode == "jit_donated",
                             admission=scenario.admission)
        self.stats.executable_builds += 1
        if self.reuse:
            self._serve_engines[key] = engine
        return engine, False

    def _run_serve(self, scenario: Scenario, *,
                   hook: Optional[RegressionHook] = None,
                   record: bool = True, profile: bool = False,
                   extra: Optional[Dict[str, Any]] = None) -> RunResult:
        """One serving or loadgen cell: regenerate the scenario's trace,
        replay it through the (cached) engine, and fold the latency
        distribution into a RunResult — ``median_us``/``mean_us``/
        ``p10_us``/``p90_us`` are per-token decode latencies, and the
        TTFT/per-token p50/p95/p99 + throughput land under the well-known
        ``extra`` keys documented in ``runner/results.py``.

        ``task="loadgen"`` is serve under transformed load: the trace is
        sharded (``scenario.split``) then its virtual arrival clock scaled
        by the offered load (``scenario.load``) before replay — the cell
        additionally records ``offered_load``/``split`` so a swept matrix
        yields a latency-vs-load curve.

        ``profile=True`` records a per-decode-step phase timeline during
        the measured replay (from the engine's ``decode.*`` spans) and
        attributes it over the decode step's HLO op classes; replay wall
        time outside decode steps (admission, prefill, queue management)
        shows up as the profile's idle share."""
        from repro.launch.serve import decode_phase_log, summarize_metrics
        from repro.runner.loadgen import scale_arrivals, shard_requests
        from repro.runner.traces import capture_spec
        t0 = time.perf_counter()
        self.stats.scenarios_run += 1
        tr = self.tracer
        key = None
        with tr.span("cell:" + scenario.name, kind="cell",
                     cell=scenario.name) as cs:
            try:
                with tr.span("build", kind="phase"):
                    spec = spec_for_scenario(scenario)
                    hits0 = self.stats.model_cache_hits
                    built = self.built_for(scenario.arch,
                                           dtype=scenario.dtype,
                                           mode=scenario.mode)
                    model_reused = self.stats.model_cache_hits > hits0
                    reqs = generate_trace(spec, vocab=built.cfg.vocab)
                    if scenario.task == "loadgen":
                        reqs = scale_arrivals(
                            shard_requests(reqs, scenario.split),
                            scenario.load)
                        if not reqs:
                            raise ValueError(
                                f"split {scenario.split!r} leaves an empty "
                                f"shard of {spec.requests} requests")
                    # sized for the whole replay: per-slot positions mean a
                    # row never needs more than its own prompt + budget
                    # (+ vlm prefix)
                    prefix = (built.cfg.n_prefix
                              if built.cfg.family == "vlm" else 0)
                    max_len = cache_len_bound(reqs, prefix=prefix)
                    key = (scenario.build_key(), scenario.mode, max_len,
                           scenario.admission)
                    engine, engine_reused = self._serve_engine_for(
                        scenario, built, max_len)
                cache = {"model_reused": model_reused or engine_reused,
                         "executable_reused": engine_reused}
                compile_us = 0.0
                if not engine_reused:
                    # untimed warm replay on a fresh engine: pays the
                    # prefill/decode jit (recorded as compile_us, like a
                    # step cell's first measure call) so the measured
                    # replay's latency samples — and its TTFTs — are
                    # steady-state and stay comparable with cache-hit
                    # re-measures
                    with tr.span("compile", kind="phase"):
                        tc = time.perf_counter()
                        engine.run(reqs)
                        compile_us = (time.perf_counter() - tc) * 1e6
                # the engine's phase spans feed both the trace and the
                # profile's dispatch/device split
                logged = tr.enabled or profile
                span_log: Optional[list] = [] if logged else None
                with tr.span("measure", kind="phase") as ms:
                    out = engine.run(reqs, hook=hook, span_log=span_log)
                self._add_serve_spans(tr, ms, span_log)
                if out["admit_new_shapes"]:
                    # this replay's queue dynamics reached prefill bucket
                    # shapes no earlier replay on this engine had compiled
                    # (batched admission shapes are load-dependent), so it
                    # paid those jits inside the timed window: fold its
                    # wall into compile_us and re-measure steady-state —
                    # the rerun is shape-complete because the replay is
                    # deterministic
                    compile_us += out["wall_s"] * 1e6
                    span_log = [] if logged else None
                    with tr.span("measure", kind="phase",
                                 remeasure=True) as ms:
                        out = engine.run(reqs, hook=hook, span_log=span_log)
                    self._add_serve_spans(tr, ms, span_log)
                sx = summarize_metrics(out)
                plens = sorted(len(r.prompt) for r in reqs)
                sx.update(trace=scenario.trace, slots=scenario.slots,
                          tokens=out["tokens_by_rid"],
                          prompt_len_p50=percentile(plens, 50),
                          prompt_len_p95=percentile(plens, 95))
                # capture provenance: the replayed trace as a
                # save_spec-schema payload, so any recorded serve/loadgen
                # run is replayable via trace="file:PATH" (load sharding/
                # scaling already applied)
                sx["capture"] = dataclasses.asdict(capture_spec(
                    reqs, seed=spec.seed, source=f"capture:{scenario.name}"))
                if scenario.task == "loadgen":
                    sx.update(offered_load=scenario.load,
                              split=scenario.split)
                if profile:
                    with tr.span("attribute", kind="phase"):
                        sx.update(self._profile_extra(
                            ("serve-cost",) + key, decode_phase_log(span_log),
                            engine.lowered_decode, kind="decode_step",
                            wall_s=out["wall_s"]))
                lats = out["tok_lat_s"] or out["ttft_s"]
                rr = RunResult(
                    name=scenario.name, bench=scenario.bench,
                    arch=scenario.arch,
                    task=scenario.task, batch=scenario.batch,
                    seq=scenario.seq,
                    dtype=scenario.dtype, mode=scenario.mode, status="ok",
                    median_us=percentile(lats, 50) * 1e6,
                    mean_us=sum(lats) / len(lats) * 1e6,
                    p10_us=percentile(lats, 10) * 1e6,
                    p90_us=percentile(lats, 90) * 1e6,
                    compile_us=compile_us, runs=out["requests"],
                    wall_s=time.perf_counter() - t0, cache=cache,
                    ts=time.time(), extra=sx)
            except Exception as e:  # noqa: BLE001 — fault containment per cell
                self.stats.errors += 1
                # the engine's donated KV cache may be half-consumed:
                # evict it
                if key is not None:
                    self._serve_engines.pop(key, None)
                rr = RunResult.from_error(scenario, f"{type(e).__name__}: {e}",
                                          wall_s=time.perf_counter() - t0)
                cs.set(error=rr.error)
            cs.set(status=rr.status)
        return self._finalize(rr, cs, extra, record)

    @staticmethod
    def _add_serve_spans(tr: Tracer, parent: Any, span_log: Optional[list],
                         cap: int = 64) -> None:
        """Attach the engine's phase spans (``admit.*``, ``decode.*``) as
        children of the serve cell's measure span.  The phases of decode
        steps beyond *cap* are elided (step count + their total time noted
        on the parent) so a long replay doesn't bloat the trace."""
        from repro.launch.serve import DECODE_PHASES
        if not tr.enabled or not span_log:
            return
        steps = 0
        dropped_s = 0.0
        for ev in span_log:
            name, tw0, tw1 = ev[0], ev[1], ev[2]
            attrs = ev[3] if len(ev) > 3 else {}
            if name == DECODE_PHASES[0]:
                steps += 1
            if name in DECODE_PHASES and steps > cap:
                dropped_s += tw1 - tw0
                continue
            tr.add(name, ts=tw0, dur_s=tw1 - tw0, parent=parent,
                   kind="engine", **attrs)
        if steps > cap:
            parent.set(decode_steps_dropped=steps - cap,
                       decode_steps_dropped_s=round(dropped_s, 6))

    def select(self, matrix: ScenarioMatrix) -> List[Scenario]:
        """Matrix expansion with the runner's session-level filter/exclude
        applied after the matrix's own selection (both must pass)."""
        return select_scenarios(matrix.expand(),
                                self.default_filter, self.default_exclude)

    def run_matrix(self, matrix: ScenarioMatrix, *,
                   hooks: Optional[Dict[str, RegressionHook]] = None,
                   runs: Optional[int] = None,
                   warmup: Optional[int] = None,
                   profile: Optional[bool] = None) -> List[RunResult]:
        """Run every scenario of the matrix in this process, in matrix
        order; hooks are keyed by benchmark name ("arch/task") or full
        scenario name.  ``profile`` (default: the runner's setting)
        profiles every cell.

        An enabled ``tracer`` records ONE trace per call: a matrix root
        span, a group span per build key, and a cell span per scenario
        with its phase children.
        """
        scenarios = self.select(matrix)
        extras = self._matrix_extras(matrix, scenarios)
        tr = self.tracer
        if tr.enabled:
            tr.begin_trace()
        with tr.span("matrix", kind="matrix", cells=len(scenarios)) as root:
            out = []
            for sc in scenarios:
                hook = (hooks or {}).get(sc.name) or (hooks or {}).get(sc.bench)
                out.append(self.run(sc, hook=hook, runs=runs, warmup=warmup,
                                    profile=profile,
                                    extra=extras.get(sc.name)))
            if tr.enabled:
                self._stitch_serial_groups(tr, scenarios, out, root)
            return out

    @staticmethod
    def _matrix_extras(matrix: ScenarioMatrix,
                       scenarios: List[Scenario]) -> Dict[str, Dict[str, Any]]:
        """Per-cell extras derived from matrix expansion (currently the
        ``slots_fallback`` staleness marker from ``slots="auto"``
        resolution) — attached to each result before it is recorded."""
        fb = getattr(matrix, "slots_fallback", None)
        fb = fb() if callable(fb) else {}
        if not fb:
            return {}
        return {sc.name: {"slots_fallback": fb[sc.name]}
                for sc in scenarios if sc.name in fb}

    @staticmethod
    def _stitch_serial_groups(tr: Tracer, scenarios: List[Scenario],
                              results: List[RunResult], root: Any) -> None:
        """Cells interleave across build keys in matrix order, so their
        group spans are synthesized after the loop from the recorded cell
        spans."""
        by_key: Dict[Tuple, List[str]] = {}
        for sc, rr in zip(scenarios, results):
            sid = rr.extra.get("span_cell")
            if sid and tr.find(sid) is not None:
                by_key.setdefault(sc.build_key(), []).append(sid)
        for bkey, ids in by_key.items():
            tr.group("group:" + group_label(bkey), ids, parent=root)

    # ---- derived (compile-only dry-run) path -----------------------------

    def run_dryrun(self, arch: str, shape: str, *, multi_pod: bool = False,
                   rules: Optional[dict] = None, refresh: bool = False,
                   timeout: int = 1200) -> Dict[str, Any]:
        """One dry-run cell (compile-only, subprocess so THIS process keeps
        its single CPU device), cached in the ResultStore: figures sharing a
        cell pay for one compile across tables AND across invocations.

        The cache key is (arch, shape, mesh) only — after config/rule/model
        changes pass ``refresh=True`` (CLI: ``benchmarks.run --refresh``)
        to recompile.  Rule-overridden cells are never cached."""
        name = f"{arch}/{shape}/{'2x16x16' if multi_pod else '16x16'}/dryrun"
        if not (refresh or self.dryrun_refresh or rules):
            cached = self._dryrun_mem.get(name)
            if cached is None and self.store is not None:
                rec = self.store.latest.get(name)
                if rec and rec.get("status") == "ok" and rec.get("extra", {}).get("cell"):
                    cached = rec["extra"]["cell"]
            if cached is not None:
                self.stats.dryrun_cache_hits += 1
                self._dryrun_mem[name] = cached
                return cached
        self.stats.dryrun_runs += 1
        cell = dryrun_cell_subprocess(arch, shape, multi_pod=multi_pod,
                                      rules=rules, timeout=timeout)
        if rules:
            return cell   # rule-varied cells don't overwrite the canonical cache
        self._dryrun_mem[name] = cell
        if self.store is not None:
            status = "skipped" if "skipped" in cell else \
                     ("error" if "error" in cell else "ok")
            self.store.append(stamp_provenance(RunResult(
                name=name, bench=f"{arch}/{shape}", arch=arch, task="train",
                batch=0, seq=0, dtype="fp32", mode="jit_donated",
                status=status, error=cell.get("error"),
                ts=time.time(), extra={"cell": cell, "derived": True})))
        return cell

    def dryrun_cells(self, cells: Sequence[Tuple[str, str]], *,
                     multi_pod: bool = False) -> List[Dict[str, Any]]:
        return [self.run_dryrun(a, s, multi_pod=multi_pod) for a, s in cells]


def _src_dir() -> str:
    import repro
    pkg = (repro.__file__ and os.path.dirname(repro.__file__)) or \
        list(repro.__path__)[0]
    return os.path.dirname(os.path.abspath(pkg))


def _subprocess_env() -> Dict[str, str]:
    env = dict(os.environ)
    src = _src_dir()
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    return env


def dryrun_cell_subprocess(arch: str, shape: str, *, multi_pod: bool = False,
                           rules: Optional[dict] = None,
                           timeout: int = 1200) -> Dict[str, Any]:
    """Compile one (arch x shape) cell in a subprocess and return its record
    (the dry-run forces 512 host devices, which must not leak into us)."""
    fd, out = tempfile.mkstemp(suffix=".json", prefix="repro_dryrun_")
    os.close(fd)
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--json", out]
    if multi_pod:
        cmd.append("--multi-pod")
    if rules:
        cmd += ["--rules", json.dumps(rules)]
    try:
        r = subprocess.run(cmd, env=_subprocess_env(), capture_output=True,
                           text=True, timeout=timeout)
        if r.returncode != 0:
            raise RuntimeError(f"dryrun {arch}x{shape} failed:\n{r.stderr[-2000:]}")
        with open(out) as f:
            return json.load(f)[0]
    finally:
        if os.path.exists(out):
            os.remove(out)
