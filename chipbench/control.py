#!/usr/bin/env python3
"""The program and its control against a cell's correctness limit, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

For each seed, in one process: the cell's weights from the seed, one replay
of the cell's traffic at its own load and size through the engine, the
cell's sample of finished requests, and ``check.verdict`` twice, as a run
applies it: to the served tokens (``program``) and to the tokens that the
reference computed in float8 puts first (``control``).  One JSON line per
seed gives both verdicts with the numbers compared beside the limit; a
sound limit reads ``program`` correct and ``control`` not correct on every
seed.  The benchmark's own runs do not run this.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "tpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

import argparse  # noqa: E402
import json  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench import check, spec
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload, root=ROOT)
    for line in check.control_runs(cell, (int(s) for s in args.seeds.split(","))):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
