"""Subprocess worker for isolated scenario execution.

Three modes, one cell-execution path (``_run_cell``):

Single-shot mode (``BenchmarkRunner(isolate=True)``):

    python -m repro.runner.worker --scenario '{"arch": "gemma-2b", ...}' \
        --runs 3 --warmup 1 --compile-warmup 3 --json out.json \
        [--no-reuse] [--slowdown-s S --leak-bytes N]

Runs ONE scenario in this interpreter via an in-process BenchmarkRunner and
writes ``{"result": <RunResult>, "stats": <RunnerStats>}`` JSON to
``--json``.  The parent treats a crash/timeout of this process as an error
record — fault containment per cell, the ``launch/dryrun`` subprocess idiom.
The full runner measurement config (runs/warmup/compile-warmup/reuse) is
forwarded on the command line so isolated measurements stay comparable with
in-process ones as regression baselines, and the worker's ``RunnerStats``
ride back in the payload so out-of-process builds/compiles stay visible.

Pool mode (``--serve``; the ``run_matrix(..., jobs=N)`` sharded dispatch,
see ``repro.runner.pool``):

    python -m repro.runner.worker --serve --runs 3 --warmup 1 ...

Cluster mode (``--connect``; the ``run_matrix(..., cluster=...)``
multi-host dispatch, see ``repro.runner.cluster``):

    python -m repro.runner.worker --connect HOST:PORT \
        [--host ID] [--capacity N] --runs 3 --warmup 1 ...

NAMING: three different "serve"/"connect" notions meet in this file —
keep them apart:

* ``--serve`` means "serve the *pool protocol*": a persistent worker
  interpreter fed JSONL jobs over stdin/stdout pipes by a same-host
  ``ShardScheduler``.
* ``--connect HOST:PORT`` speaks the SAME job/result protocol
  (``repro.runner.protocol``) over a TCP socket to a cluster
  ``Coordinator`` — possibly on another host.  It registers first
  (``--host`` id, ``--capacity`` max in-flight cells) and heartbeats
  from a side thread so the coordinator can tell a long compile from a
  dead host.
* ``Scenario(task="serve")`` is the serving *workload* — the
  continuous-batching engine in ``repro.launch.serve``.  It is unrelated
  to either flag: both pool and cluster workers can be handed scenarios
  of any task, including ``task="serve"`` cells.

Pool mode processes a *batch* of scenarios: one JSONL request per line on
stdin —

    {"op": "run", "scenario": {...}, "runs": R?, "warmup": W?,
     "hook": {"slowdown_s": S, "leak_bytes": N}?}

— one JSONL reply per request on stdout (``{"op": "result", "result": ...,
"stats": ...}``, the cumulative RunnerStats riding along with every
result), exiting 0 on stdin EOF.  The protocol
stream is the *original* stdout fd, dup'd away before any benchmark code
runs; fd 1 is then pointed at stderr so stray prints from model/measure
code can never corrupt the protocol.  One BenchmarkRunner serves the whole
batch, so the arch-build and compiled-executable caches keep paying off
across the shard's scenarios exactly as they do in-process.  Cluster mode
is the same loop over the socket (jobs additionally carry a ``cell`` id,
echoed back so the coordinator can pipeline), exiting 0 on a ``shutdown``
message or socket EOF.

``--measure-lock PATH`` enables the *measurement fence*: each cell first
does an unfenced warm pass (build + compile + donation threading — the
expensive, contention-tolerant work, free to overlap with other workers),
then takes an exclusive flock on PATH for the short timed loop only.
Two cells' timed loops therefore never overlap — the worst cross-worker
distortion — keeping sharded measurements usable as regression baselines
(see ``runner/pool.py`` for what the fence can and cannot isolate; the
flock only fences workers of ONE host, which is exactly the set sharing
CPUs).  The fenced re-measure reports the warm pass's
compile_us/cache provenance and counts as ONE logical execution in
``RunnerStats``.  Requires the cache (ignored under ``--no-reuse``).

The regression-hook parameters are plain numbers so injected-fault CI runs
can be isolated/sharded/clustered too.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import threading

try:
    import fcntl
except ImportError:          # non-POSIX: fence degrades to unfenced runs
    fcntl = None  # type: ignore[assignment]


@contextlib.contextmanager
def _file_lock(path):
    if not path or fcntl is None:
        yield
        return
    fd = os.open(path, os.O_WRONLY | os.O_CREAT, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)


def _build_runner(args):
    """The worker's in-process runner.  Every mode of ``main()`` builds it
    before anything compiles, so the compile cache is enabled here — after
    a cluster worker has registered, since it pulls in jax."""
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runner.runner import BenchmarkRunner
    enable_compile_cache()
    return BenchmarkRunner(runs=args.runs, warmup=args.warmup,
                           compile_warmup=args.compile_warmup,
                           reuse=args.reuse)


def _hook_from(slowdown_s: float, leak_bytes: int):
    if not (slowdown_s or leak_bytes):
        return None
    from repro.core.harness import RegressionHook
    return RegressionHook(slowdown_s=slowdown_s, leak_bytes=leak_bytes)


def _run_cell(runner, scenario, hook, runs, warmup, lock_path,
              profile=False, extra=None):
    """One cell, with the measurement fence when a lock path is given:
    warm pass unfenced (build/compile/threading overlap across workers),
    timed loop under the exclusive lock (contention-free measurement)."""
    # serve cells follow the same protocol: the warm pass replays the trace
    # once on a fresh engine (building + compiling unfenced, overlapping
    # other workers), and the fenced re-run replays it on the warm engine
    if not (lock_path and runner.reuse):
        return runner.run(scenario, hook=hook, runs=runs, warmup=warmup,
                          record=False, profile=profile, extra=extra)
    # a profiled warm pass pays the attribution AOT compile here, unfenced
    # (it caches per executable), so the fenced profiled re-measure below
    # never holds the lock through an XLA compile
    warm = runner.run(scenario, runs=1, warmup=0, record=False,
                      profile=profile, extra=extra)
    if warm.status != "ok":
        return warm
    with _file_lock(lock_path):
        rr = runner.run(scenario, hook=hook, runs=runs, warmup=warmup,
                        record=False, profile=profile, extra=extra)
    if rr.status == "ok":
        # the fenced re-measure hit the warm pass's cache: report the
        # cell's true build/compile provenance instead
        rr.compile_us = warm.compile_us
        rr.cache = warm.cache
    # keep the ledger at one logical execution per cell — the warm pass
    # is protocol, not workload
    runner.stats.scenarios_run -= 1
    runner.stats.executable_cache_hits -= 1
    return rr


def _handle_job(runner, msg: dict, args) -> dict:
    """One ``run`` request -> its ``result`` reply (shared by the pool and
    cluster loops).  The cumulative stats ride along with every result:
    one round trip per cell, and no window where a completed cell's
    builds/compiles can be lost to a dying worker.  A job's ``cell`` id is
    echoed back so a pipelining dispatcher can match results to cells."""
    from repro.runner.scenario import Scenario
    scenario = Scenario.from_dict(msg["scenario"])
    hook_params = msg.get("hook") or {}
    hook = _hook_from(hook_params.get("slowdown_s", 0.0),
                      hook_params.get("leak_bytes", 0))
    tctx = msg.get("trace")
    tracer = None
    if tctx:
        # a per-job tracer seeded with the dispatcher's span context: this
        # cell's spans parent to the coordinator-side dispatch span and
        # ship back in the reply (the dispatcher relabels the lane)
        from repro.telemetry.spans import Tracer
        tracer = Tracer(trace_id=tctx.get("trace_id"),
                        proc=f"worker-{os.getpid()}",
                        root_parent=tctx.get("parent") or None)
        runner.tracer = tracer
    try:
        rr = _run_cell(runner, scenario, hook, msg.get("runs"),
                       msg.get("warmup"), args.measure_lock,
                       profile=bool(msg.get("profile") or args.profile),
                       extra=msg.get("extra"))
    finally:
        if tracer is not None:
            from repro.telemetry.spans import NULL_TRACER
            runner.tracer = NULL_TRACER
    from repro.fleet.metrics import registry as metrics_registry
    reply = {"op": "result", "result": rr.to_dict(),
             "stats": runner.stats.to_dict(),
             # this process's metrics registry as flat cumulative counters,
             # delta-merged by the dispatcher exactly like the stats
             "metrics": metrics_registry().counters_cumulative()}
    if tracer is not None:
        reply["spans"] = tracer.export()
    if "cell" in msg:
        reply["cell"] = msg["cell"]
    return reply


def _serve_pool(args) -> int:
    """Pool mode: persistent batch loop — JSONL requests on stdin, replies
    on the original stdout; workload output is rerouted to stderr.  (This
    "serves" the pool protocol; the inference-serving workload is
    ``repro.launch.serve``, dispatched through here like any other task.)"""
    proto = os.fdopen(os.dup(sys.stdout.fileno()), "w", buffering=1)
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())

    runner = _build_runner(args)
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        msg = json.loads(line)
        reply = _handle_job(runner, msg, args)
        proto.write(json.dumps(reply) + "\n")
        proto.flush()
    return 0


def _serve_cluster(args) -> int:
    """Cluster mode: connect to the coordinator, register (host id +
    capacity), heartbeat from a side thread, and run jobs until a
    ``shutdown`` message or socket EOF.  The protocol lives on the socket,
    so stray workload prints on stdout are harmless here."""
    import socket

    from repro.runner.protocol import Channel

    host, _, port = args.connect.rpartition(":")
    sock = socket.create_connection((host or "127.0.0.1", int(port)),
                                    timeout=30)
    sock.settimeout(None)
    chan = Channel.over_socket(sock)
    host_id = args.host or f"{socket.gethostname()}-{os.getpid()}"
    # floor the ping interval: --heartbeat 0 would busy-loop the side
    # thread into flooding the coordinator
    args.heartbeat = max(0.5, args.heartbeat)
    # register BEFORE the heavy imports (_build_runner pulls in jax), so
    # the coordinator sees this worker — and can plan around it — while
    # the interpreter is still warming up
    # heartbeat rides in the registration so the coordinator can scale its
    # silence bound to THIS worker's ping interval instead of reaping a
    # slow-pinging healthy host mid-compile
    chan.send({"op": "register", "host": host_id,
               "capacity": max(1, args.capacity),
               "heartbeat": args.heartbeat})

    stop = threading.Event()

    def _heartbeat() -> None:
        while not stop.wait(args.heartbeat):
            try:
                chan.send({"op": "ping"})
            except OSError:
                return             # coordinator gone: main loop sees EOF

    beat = threading.Thread(target=_heartbeat, name="heartbeat", daemon=True)
    beat.start()
    runner = _build_runner(args)
    try:
        while True:
            msg = chan.recv(timeout=60.0)
            if msg is None:
                if chan.eof:
                    return 0       # coordinator closed: clean exit
                continue           # idle between batches
            op = msg.get("op")
            if op == "shutdown":
                return 0
            if op != "run":
                continue
            try:
                chan.send(_handle_job(runner, msg, args))
            except OSError:
                return 0           # coordinator gone mid-reply
    finally:
        stop.set()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", help="Scenario JSON dict (single-shot mode)")
    ap.add_argument("--serve", action="store_true",
                    help="pool mode: persistent worker, JSONL requests on "
                         "stdin, replies on stdout (unrelated to the "
                         "task=\"serve\" workload)")
    ap.add_argument("--connect", default="",
                    help="cluster mode: HOST:PORT of a coordinator "
                         "(repro.runner.cluster) to register with and pull "
                         "jobs from over TCP")
    ap.add_argument("--host", default="",
                    help="cluster host id reported at registration and in "
                         "extra['host'] (default: <hostname>-<pid>)")
    ap.add_argument("--capacity", type=int, default=1,
                    help="cluster mode: max in-flight cells the "
                         "coordinator may pipeline to this worker")
    ap.add_argument("--heartbeat", type=float, default=5.0,
                    help="cluster mode: seconds between liveness pings")
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--compile-warmup", type=int, default=3,
                    help="extra warmup after a fresh compile (parent's setting)")
    ap.add_argument("--no-reuse", dest="reuse", action="store_false",
                    default=True, help="disable build/executable caching")
    ap.add_argument("--profile", action="store_true",
                    help="measured profiling: record extra['prof_*'] "
                         "(timeline + op-class attribution) per cell")
    ap.add_argument("--measure-lock", default="",
                    help="flock path fencing the timed loop (pool/cluster "
                         "modes; fences same-host workers only)")
    ap.add_argument("--slowdown-s", type=float, default=0.0)
    ap.add_argument("--leak-bytes", type=int, default=0)
    ap.add_argument("--json", help="output path (single-shot mode)")
    args = ap.parse_args(argv)

    if args.serve and args.connect:
        ap.error("--serve (pipe pool) and --connect (cluster socket) are "
                 "mutually exclusive transports")
    if args.serve:
        return _serve_pool(args)
    if args.connect:
        return _serve_cluster(args)
    if not (args.scenario and args.json):
        ap.error("single-shot mode needs --scenario and --json "
                 "(or use --serve / --connect)")

    from repro.runner.scenario import Scenario

    scenario = Scenario.from_dict(json.loads(args.scenario))
    runner = _build_runner(args)
    rr = runner.run(scenario, hook=_hook_from(args.slowdown_s, args.leak_bytes),
                    record=False, profile=args.profile)
    with open(args.json, "w") as f:
        json.dump({"result": rr.to_dict(), "stats": runner.stats.to_dict()}, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
