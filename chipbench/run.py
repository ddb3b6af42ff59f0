#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic mix
and metrics come from ``BENCHMARK.json`` and the files under ``chipbench/``.
Everything runs in this one process, which holds the chip.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` it carries its per-layer metrics, read from a profiler trace,
and a breakdown of device time and idle gaps.  The last line of standard
output is the result, a JSON object; the numbers that decide ``correct``
are its last key and the last lines of standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program under test is missing.
"""
import os
import sys
import time

T_START = time.perf_counter()
os.environ.setdefault("JAX_PLATFORMS", "tpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT                           # not chipbench/: its dirs are no packages
sys.path.insert(1, os.path.join(ROOT, "src"))

import argparse  # noqa: E402
import json  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import spec
    cell = spec.load_cell(args.workload, root=ROOT)

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"run.py: JAX found no accelerator: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = devices[0]
    print(json.dumps({"device": {"platform": dev.platform, "kind": dev.device_kind,
                                 "count": len(devices)}, "compile_cache": cache_dir}),
          flush=True)

    from chipbench import harness
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
