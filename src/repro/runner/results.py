"""Persistent, schema'd benchmark results.

``RunResult`` — one versioned record per scenario execution (schema v1):

    schema       int    record version (this file: SCHEMA_VERSION)
    name         str    scenario id "arch/task/bN/sN/dtype/mode"
    bench        str    suite benchmark name "arch/task"
    arch/task/batch/seq/dtype/mode   the scenario axes
    status       str    "ok" | "error" | "skipped"
    median_us, mean_us, p10_us, p90_us, compile_us   timing (us)
    host_peak_bytes, device_bytes_delta              memory
    runs         int    measured iterations (after warmup)
    wall_s       float  end-to-end wall time incl. build/compile
    cache        dict   {"model_reused": bool, "executable_reused": bool}
    ts           float  unix timestamp
    error        str?   exception text when status == "error"
    extra        dict   free-form payload (dry-run cells, hook params, ...)

Provenance stamps (``repro.telemetry.provenance``, still schema v1): the
runner stamps EVERY record — ok and error — with the environment that
produced it, so a result history can be grouped into comparable series
(``repro.telemetry.history`` keys baselines and drift detection on
them).  These stay optional ``extra`` keys of schema v1, so readers must
tolerate their absence:

    extra["prov_commit"]   str    git commit sha of the benchmarked tree
                                  (``$REPRO_COMMIT`` overrides when the
                                  deployed tree is not a git checkout)
    extra["prov_dirty"]    bool   the working tree had uncommitted changes
    extra["prov_backend"]  str    ``jax.default_backend()`` ("cpu"/"gpu"/
                                  "tpu") of the measuring process
    extra["prov_host"]     str    hostname of the measuring process
    extra["prov_jax"]      str    jax version
    extra["prov_python"]   str    python version

Span-tracing stamps (``repro.telemetry.spans``; present only when the
run was traced — a ``Tracer`` was passed to the runner or
``benchmarks.run --trace-out`` was used):

    extra["span_trace"]    str    trace id of the ``run_matrix`` call this
                                  record was measured in (one id per call)
    extra["span_cell"]     str    span id of the cell span that timed this
                                  record

Matrix-expansion annotations:

    extra["slots_fallback"] str   the cell's ``slots="auto"`` resolution
                                  fell back to the default width; the
                                  value names why ("missing" |
                                  "unreadable" | "stale-schema" |
                                  "foreign-arch" | "degenerate-curve",
                                  see ``runner/loadgen.auto_slots_info``).
                                  Absent when a real measured curve was
                                  used.

Fleet / coverage annotations (still schema v1; the fleet perf-CI
service ``src/repro/fleet/`` and the coverage-enabled runner):

    extra["fleet_tick"]    int    the fleet scheduler tick that measured
                                  this history point (``FleetScheduler``
                                  stamps every record it logs into the
                                  store, so a trajectory series can be
                                  re-cut by tick as well as by time)
    extra["cov_primitives"]     int   distinct jaxpr primitives the cell's
                                  step traces to (``core/coverage``,
                                  abstract trace — cached per scenario;
                                  only on step cells of a
                                  ``BenchmarkRunner(coverage=True)``)
    extra["cov_new_primitives"] int   of those, how many this cell added
                                  to the runner's suite-union frontier
                                  (first cell of a sweep pays the whole
                                  union; later cells count marginal
                                  coverage — the paper's breadth metric
                                  as a per-cell number).  The running
                                  union size is the
                                  ``fleet_cov_union_primitives`` gauge.

Every execution also feeds the process-wide metrics registry
(``repro.fleet.metrics``; counters/gauges/histograms, exported as the
``{"fleet_metrics": 1, "ts", "counters", "gauges", "histograms"}``
snapshot in ``results/fleet_status.json`` and as Prometheus text in
``results/fleet_metrics.prom``).  Registry counters are *execution*
counts, not record counts: a cell run with ``record=False`` counts in
``fleet_cells_total`` but leaves no record.

Serving cells (``task="serve"``, the continuous-batching engine in
``repro.launch.serve``) additionally carry the latency-distribution
metrics production users compare (all latencies in **microseconds**,
computed by ``repro.runner.latency``); for these records the core timing
fields ``median_us``/``mean_us``/``p10_us``/``p90_us`` hold *per-token
decode latencies* (not step times) and ``runs`` is the request count:

    extra["ttft_p50"|"ttft_p95"|"ttft_p99"]          time-to-first-token
                                  percentiles: request became admissible
                                  -> first (prefill) token emitted (a
                                  fresh engine's prefill/decode jit is
                                  paid by an untimed warm replay and
                                  recorded in ``compile_us``, so these
                                  are steady-state like step timings)
    extra["tok_lat_p50"|"tok_lat_p95"|"tok_lat_p99"] per-token decode
                                  latency percentiles across all tokens
    extra["tok_per_s"]     float  generated-token throughput (incl. first
                                  tokens) over the trace replay wall time
    extra["decode_steps"]  int    batched decode steps executed
    extra["queue_depth_mean"|"queue_depth_max"]      arrived-but-unadmitted
                                  requests sampled once per decode step
    extra["trace"]         str    load-profile name (runner/traces.py)
    extra["slots"]         int    decode batch width (continuous batching);
                                  always the resolved integer — a matrix
                                  ``slots=("auto",)`` axis entry is turned
                                  into a measured width at expansion time
                                  (``runner/loadgen.auto_slots``)
    extra["tokens"]        list   generated tokens per request, rid order —
                                  the determinism witness across admission
                                  policies and replays
    extra["tokens_digest"] str    sha256 of extra["tokens"]
    extra["prompt_len_p50"|"prompt_len_p95"]         prompt-length
                                  percentiles of the replayed trace (mixed
                                  lengths per batch are first-class: the
                                  KV cache keeps per-slot position vectors)
    extra["capture"]       dict   capture provenance: the replayed trace
                                  as a ``traces.save_spec``-schema payload
                                  (per-request lengths/arrivals/budgets
                                  pinned, ``source="capture:<cell name>"``)
                                  — write it to a file and replay it with
                                  ``trace="file:PATH"`` for a byte-
                                  identical regression run
    extra["admission"]     str    prefill policy: "batched" (one jitted
                                  prefill per admission wave, bucketed
                                  padded shapes) or "single" (the
                                  one-prefill-per-request baseline)
    extra["admit_calls"]   int    jitted prefill calls this replay made —
                                  batched admission's headline saving over
                                  one-call-per-request
    extra["admit_batch_mean"|"admit_batch_max"]      requests admitted per
                                  prefill call (mean/max over the replay);
                                  mean 1.0 under admission="single"
    extra["admit_shapes"]  list   distinct compiled (rows, padded_len)
                                  prefill shapes over the ENGINE lifetime
                                  (cumulative across replays, mirroring
                                  the jit cache) — bounded by the bucket
                                  grid, not by distinct prompt lengths

Loadgen cells (``task="loadgen"``: a serve replay under transformed
load — trace sharded by ``scenario.split``, virtual arrival clock scaled
by ``scenario.load``; see ``repro.runner.loadgen``) carry all the serve
keys above plus:

    extra["offered_load"]  float  the arrival-clock multiplier this cell
                                  replayed at (>1 compresses arrivals)
    extra["split"]         str    trace shard "i/n" ("" = whole trace)

and a swept curve's summary record (``benchmarks/loadgen_curve.py``)
carries the post-processed saturation knee:

    extra["knee_load"|"knee_tok_s"]   highest offered load that still
                                  bought >= ~5% marginal throughput, and
                                  the throughput measured there

Kernel micro-bench cells (``task="kernel"``, the autotuner's candidate
timings — ``repro.tuning``; still schema v1): the scenario ``arch`` axis
holds a tuning *candidate id* (``kernel@DIMS@PARAMS``, e.g.
``flash_attention@B2,S128,H4,K2,D64@block_q=64,block_k=128``) instead of
a registry arch, ``mode`` is always ``"jit"``, ``batch``/``seq`` mirror
the case's B/S dims, and the timing fields follow the normal step-cell
``measure()`` protocol (median-of-N over the jitted ops-layer call,
compile excluded).  Their decoded identity rides in ``extra``:

    extra["tuning_kernel"]    str   kernel name ("flash_attention" |
                                  "rglru" | "ssd")
    extra["tuning_case"]      str   case id "kernel@DIMS" — the (kernel,
                                  shape) tuning problem this candidate
                                  belongs to
    extra["tuning_signature"] str   the tuning-DB shape signature (what
                                  ``kernels/*/ops.py`` recomputes at
                                  trace time, e.g. "Sq128,Sk128,D64")
    extra["tuning_params"]    dict  this candidate's launch parameters
                                  (e.g. {"block_q": 64, "block_k": 128})
    extra["tuning_default"]   bool  this candidate IS the ops-layer
                                  default (always swept, so a recorded
                                  winner is never slower than it)

Profiled cells (``run(..., profile=True)`` / ``benchmarks.run --profile``;
the measured profiling subsystem ``src/repro/profiler/``) additionally
carry the phase timeline + op-class attribution (still schema v1; eager
cells record only ``extra["prof_skipped"]="eager"`` — no compiled module):

    extra["prof_kind"]     str    "step" (train/infer cells: one sample per
                                  measured iteration) | "decode_step"
                                  (serve: one per batched decode step)
    extra["prof_steps"]    int    profiled samples
    extra["prof_timeline"] list   [dispatch_us, device_us] per sample,
                                  capped at profiler.TIMELINE_CAP (128)
    extra["prof_dispatch_us_mean"|"prof_device_us_mean"]   phase means:
                                  host dispatch (jitted call returning) vs
                                  device execution (block_until_ready wait)
    extra["prof_idle_us"]  float  serve only: measured replay wall outside
                                  decode steps (admission, prefill, queue)
    extra["prof_frac_compute"|"prof_frac_memory"|"prof_frac_collective"
         |"prof_frac_dispatch"|"prof_frac_idle"]
                           float  measured time decomposition; the five
                                  fractions sum to 1.0 per cell (device
                                  time is split over HLO op classes by
                                  their roofline weights, then into
                                  compute vs memory per class; device time
                                  the HLO costs can't explain lands in
                                  idle, never vanishes)
    extra["prof_class_us"|"prof_class_frac"]   dict   measured device time
                                  per op class (hloanalysis.OP_CLASSES:
                                  matmul/attention/collective/elementwise/
                                  other), us and fraction-of-device-time
    extra["prof_flops"|"prof_bytes"|"prof_collective_bytes"]   the
                                  trip-count-aware HLO costs backing the
                                  attribution
    extra["prof_bound_us"] float  the cell's analytic roofline device
                                  bound (modeled hardware)
    extra["prof_util"]     float  bound/measured device time — roofline-
                                  utilization proxy; compare across cells
                                  of one sweep, not across hosts
    extra["prof_device_peak_bytes"|"prof_device_bytes_in_use"]   backend
                                  memory stats, present only when the
                                  device exposes memory_stats() (TPU/GPU;
                                  absent on CPU)
    extra["prof_error"]    str    attribution failed (timeline-only
                                  profile); the cell's status stays "ok"
    extra["prof_skipped"]  str    why no profile was recorded ("eager")

``ResultStore`` — the persistence layer:

    * an append-only JSONL run log (full history, one record per line);
    * an atomically-rewritten latest-pointer JSON mapping name -> record.

Two layouts: a directory (``<root>/runs.jsonl`` + ``<root>/latest.json``,
the runner's layout) or a ``*.json`` file path (the latest pointer IS that
file, log beside it as ``*.jsonl`` — the layout ``core.regression.MetricStore``
sits on, keeping its historical single-file format readable).

Concurrency: one store file set may be appended to by several processes at
once (CI sweeps in separate processes share the same store).  Log appends
are a single ``O_APPEND`` write, the latest pointer is advanced under an
exclusive lock file with a read-merge-replace cycle, and ``history()``
skips (and counts) torn lines left by a writer killed mid-append.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

try:
    import fcntl
except ImportError:          # non-POSIX: fall back to best-effort updates
    fcntl = None  # type: ignore[assignment]

SCHEMA_VERSION = 1


@dataclasses.dataclass
class RunResult:
    name: str
    bench: str
    arch: str
    task: str
    batch: int
    seq: int
    dtype: str
    mode: str
    status: str = "ok"
    median_us: float = 0.0
    mean_us: float = 0.0
    p10_us: float = 0.0
    p90_us: float = 0.0
    compile_us: float = 0.0
    host_peak_bytes: int = 0
    device_bytes_delta: int = 0
    runs: int = 0
    wall_s: float = 0.0
    cache: Dict[str, bool] = dataclasses.field(default_factory=dict)
    ts: float = 0.0
    error: Optional[str] = None
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)
    schema: int = SCHEMA_VERSION

    @classmethod
    def from_measurement(cls, scenario, m, *, wall_s: float = 0.0,
                         cache: Optional[Dict[str, bool]] = None,
                         extra: Optional[Dict[str, Any]] = None) -> "RunResult":
        return cls(name=scenario.name, bench=scenario.bench,
                   arch=scenario.arch, task=scenario.task,
                   batch=scenario.batch, seq=scenario.seq,
                   dtype=scenario.dtype, mode=scenario.mode,
                   status="ok", median_us=m.median_us, mean_us=m.mean_us,
                   p10_us=m.p10_us, p90_us=m.p90_us, compile_us=m.compile_us,
                   host_peak_bytes=m.host_peak_bytes,
                   device_bytes_delta=m.device_bytes_delta, runs=m.runs,
                   wall_s=wall_s, cache=dict(cache or {}),
                   ts=time.time(), extra=dict(extra or {}))

    @classmethod
    def from_error(cls, scenario, error: str, *, wall_s: float = 0.0) -> "RunResult":
        return cls(name=scenario.name, bench=scenario.bench,
                   arch=scenario.arch, task=scenario.task,
                   batch=scenario.batch, seq=scenario.seq,
                   dtype=scenario.dtype, mode=scenario.mode,
                   status="error", error=error, wall_s=wall_s, ts=time.time())

    def metrics(self) -> Dict[str, float]:
        """The regression-CI metric view of this record."""
        return {"median_us": self.median_us,
                "host_peak_bytes": float(self.host_peak_bytes),
                "device_bytes_delta": float(self.device_bytes_delta)}

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "RunResult":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


class ResultStore:
    """JSONL run log + latest-pointer map, atomic on update and safe for
    concurrent appenders (threads in one process AND separate processes)."""

    def __init__(self, path: str):
        if path.endswith(".json"):
            self.latest_path = path
            self.log_path = path[: -len(".json")] + ".jsonl"
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        else:
            os.makedirs(path, exist_ok=True)
            self.latest_path = os.path.join(path, "latest.json")
            self.log_path = os.path.join(path, "runs.jsonl")
        self.lock_path = self.latest_path + ".lock"
        #: torn/corrupt log lines skipped by the last ``history()`` replay
        self.corrupt_lines = 0
        self._tlock = threading.Lock()
        self.latest: Dict[str, dict] = {}
        if os.path.exists(self.latest_path):
            with open(self.latest_path) as f:
                self.latest = json.load(f)

    def append(self, record, *, advance_latest: bool = True) -> dict:
        """Append one record (RunResult or plain dict with a "name" key) to
        the log and move the latest pointer; returns the stored dict.

        ``advance_latest=False`` appends to the history log only — for
        time-series points (``MetricStore.log_result``) that must not
        shadow the latest-pointer view other readers key baselines on."""
        rec = record.to_dict() if hasattr(record, "to_dict") else dict(record)
        rec.setdefault("schema", SCHEMA_VERSION)
        rec.setdefault("ts", time.time())
        # one O_APPEND write syscall per record: concurrent appenders never
        # interleave bytes within a line
        line = (json.dumps(rec) + "\n").encode()
        fd = os.open(self.log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                     0o644)
        try:
            os.write(fd, line)
        finally:
            os.close(fd)
        if advance_latest:
            self._advance_latest(rec)
        return rec

    def _advance_latest(self, rec: dict) -> None:
        """Move the latest pointer under an exclusive lock, merging with
        whatever other writers put on disk since we last read it."""
        with self._tlock:
            lock_fd = os.open(self.lock_path,
                              os.O_WRONLY | os.O_CREAT, 0o644)
            try:
                if fcntl is not None:
                    fcntl.flock(lock_fd, fcntl.LOCK_EX)
                disk: Dict[str, dict] = {}
                if os.path.exists(self.latest_path):
                    try:
                        with open(self.latest_path) as f:
                            disk = json.load(f)
                    except ValueError:
                        disk = {}
                merged = {**self.latest, **disk}
                merged[rec["name"]] = rec
                self.latest = merged
                tmp = f"{self.latest_path}.{os.getpid()}.tmp"
                with open(tmp, "w") as f:
                    json.dump(merged, f, indent=1)
                os.replace(tmp, self.latest_path)
            finally:
                os.close(lock_fd)

    def latest_result(self, name: str) -> Optional[RunResult]:
        rec = self.latest.get(name)
        return None if rec is None else RunResult.from_dict(rec)

    def history(self, name: Optional[str] = None) -> Iterator[dict]:
        """Replay the append log (optionally filtered to one scenario).

        Torn/truncated lines — a writer killed mid-append, a partial tail
        from a crash — are skipped, not fatal; ``self.corrupt_lines`` holds
        the count from the latest replay."""
        self.corrupt_lines = 0
        if not os.path.exists(self.log_path):
            return
        with open(self.log_path, errors="replace") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    self.corrupt_lines += 1
                    continue
                if not isinstance(rec, dict):
                    self.corrupt_lines += 1
                    continue
                if name is None or rec.get("name") == name:
                    yield rec

    def results(self) -> List[RunResult]:
        """All latest records that parse as RunResults, sorted by name."""
        return [RunResult.from_dict(r) for _, r in sorted(self.latest.items())
                if isinstance(r, dict) and "arch" in r]
