"""Benchmark harness entry point: one function per paper table/figure, all
executed through the unified ``repro.runner.BenchmarkRunner``.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]
        [--filter RE ...] [--exclude RE ...] [--isolate] [--jobs N]
        [--cluster local:N|HOST:PORT] [--profile] [--list]
        [--trace-out PATH]

``--list`` prints the scenario names each matrix-driven table would run
(after filter/exclude/skip selection) and exits without executing —
cheap debugging for sharded sweeps.

One ``BenchmarkRunner`` + ``ResultStore`` (``results/store``) is shared by
every table: arch builds, compiled executables, and dry-run cells are
reused across figures, and every measurement lands as a versioned
``RunResult`` (schema documented in ``repro/runner/results.py``) in the
JSONL run log with a latest-pointer for ``scripts/report_tables.py``.

``--filter`` / ``--exclude`` are regexes over scenario names
("arch/task/bN/sN/dtype/mode"), applied to the measured-suite tables —
the torchbench driver's model-selection semantics.  ``--isolate`` runs
each scenario in its own subprocess (fault containment for crashy cells);
``--jobs N`` shards every ``run_matrix`` sweep across N persistent worker
subprocesses (see ``repro/runner/pool.py``); ``--cluster local:N`` (or
``--cluster HOST:PORT`` with workers launched elsewhere via ``python -m
repro.runner.worker --connect HOST:PORT``) dispatches every sweep across
socket-connected cluster workers instead (see ``repro/runner/cluster/``).

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="reduced sweep for CI")
    ap.add_argument("--only", default=None)
    ap.add_argument("--list", action="store_true",
                    help="print the selected scenario names (post "
                         "filter/exclude/skip) without executing anything")
    ap.add_argument("--filter", action="append", default=[],
                    help="regex over scenario names; keep matches")
    ap.add_argument("--exclude", action="append", default=[],
                    help="regex over scenario names; drop matches")
    ap.add_argument("--isolate", action="store_true",
                    help="one subprocess per scenario (fault containment)")
    ap.add_argument("--jobs", type=int, default=0,
                    help="shard matrix sweeps across N worker subprocesses")
    ap.add_argument("--cluster", default="",
                    help="dispatch matrix sweeps across cluster workers: "
                         "'local:N' spawns N localhost workers, 'HOST:PORT' "
                         "binds the coordinator there for external "
                         "worker --connect processes")
    ap.add_argument("--profile", action="store_true",
                    help="measured profiling on every matrix cell: phase "
                         "timelines + op-class attribution under "
                         "extra['prof_*'] (src/repro/profiler/)")
    ap.add_argument("--refresh", action="store_true",
                    help="recompile cached dry-run cells (after config/model changes)")
    ap.add_argument("--trace-out", default="",
                    help="trace every run_matrix call and write one "
                         "stitched Chrome trace-event JSON (Perfetto-"
                         "loadable) here; also prints a text flame "
                         "summary (src/repro/telemetry/)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (batchsize, fig5_hardware, fig12_breakdown,
                            fig34_compilers, history_report, loadgen_curve,
                            profile_report, roofline, runner_bench,
                            serve_latency, table1_suite, table45_ci)
    from benchmarks.common import make_runner
    runner = make_runner(isolate=args.isolate, jobs=args.jobs,
                         cluster=args.cluster, profile=args.profile)
    runner.default_filter = tuple(args.filter)
    runner.default_exclude = tuple(args.exclude)
    runner.dryrun_refresh = args.refresh
    if args.trace_out:
        from repro.telemetry.spans import Tracer
        runner.tracer = Tracer()
    tables = {
        "table1_suite": table1_suite.main,         # Table 1 + coverage (§2.3)
        "fig12_breakdown": fig12_breakdown.main,   # Figs 1-2 + Table 2
        "fig34_compilers": fig34_compilers.main,   # Figs 3-4
        "fig5_hardware": fig5_hardware.main,       # Fig 5 + Table 3
        "table45_ci": table45_ci.main,             # §4.2, Tables 4-5
        "batchsize": batchsize.main,               # §2.2 batch-size search
        "roofline": roofline.main,                 # §Roofline deliverable
        "serve_latency": serve_latency.main,       # serving-latency table
        "loadgen_curve": loadgen_curve.main,       # TTFT/p99 vs offered load
        "profile_report": profile_report.main,     # measured inefficiency findings
        "runner_bench": runner_bench.main,         # runner reuse speedup
        "history_report": history_report.main,     # provenance trajectories
    }
    if args.list:
        # sharded-sweep debugging: show exactly which cells each table's
        # matrices select under the session --filter/--exclude, zero
        # execution.  Tables without a scenario_matrices hook (dry-run /
        # single-probe tables) are reported as such.
        for name, fn in tables.items():
            if args.only and name != args.only:
                continue
            mod = sys.modules[fn.__module__]
            hook = getattr(mod, "scenario_matrices", None)
            if hook is None:
                print(f"# {name}: no scenario matrix (dry-run or probe cells)")
                continue
            for matrix in hook(fast=args.fast):
                for sc in runner.select(matrix):
                    print(f"{name} {sc.name}")
        return 0
    failed = 0
    try:
        for name, fn in tables.items():
            if args.only and name != args.only:
                continue
            print(f"# === {name} ===", flush=True)
            t0 = time.time()
            try:
                fn(fast=args.fast, runner=runner)
                print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
            except Exception:
                failed += 1
                print(f"# {name} FAILED:\n{traceback.format_exc()}", file=sys.stderr, flush=True)
    finally:
        runner.close()
    if args.trace_out and runner.tracer.spans:
        from repro.telemetry.export import flame_summary, save_trace
        save_trace(runner.tracer.export(), args.trace_out)
        print(f"# trace: {len(runner.tracer.spans)} spans -> "
              f"{args.trace_out}", flush=True)
        print("\n".join("# " + ln for ln in
                        flame_summary(runner.tracer.spans,
                                      max_depth=4).splitlines()),
              flush=True)
    print(f"# runner stats: {runner.stats.to_dict()}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
