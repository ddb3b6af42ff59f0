"""Paths of the benchmark and a checkout-like copy of it for tests."""
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = os.path.join(HERE, "data")
TINY_CELLS = ("tiny-dense.tiny", "tiny-ssm.tiny")


def make_root(dst: str) -> str:
    """A checkout-like root at ``dst``: a copy of the benchmark's files plus
    the CPU-sized configurations, mix and cells under ``tests/data``, with a
    ``BENCHMARK.json`` that adds the tiny cells to the real one by new
    entries only."""
    bench = os.path.join(dst, "chipbench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests", "testdata", "__pycache__"))
    for sub in ("configs", "traffic", "cells"):
        for f in os.listdir(os.path.join(DATA, sub)):
            shutil.copy(os.path.join(DATA, sub, f), os.path.join(bench, sub, f))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for name in ("tiny-dense", "tiny-ssm"):
        spec["configs"].append({"name": name, "source": "chipbench/tests/data",
                                "file": f"chipbench/configs/{name}.json",
                                "reduced": [], "why": "CPU test"})
        spec["workloads"].append({"name": f"{name}.tiny", "config": name,
                                  "traffic": "tiny", "chips": 1, "why": "CPU test"})
    for m in spec["per_layer"] + [m for m in spec["end_to_end"] if "workloads" in m]:
        m.setdefault("workloads", []).extend(TINY_CELLS)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dst

