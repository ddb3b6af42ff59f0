"""A cell as ``BENCHMARK.json`` and the benchmark's data files define it.

Nothing here names a configuration, mix, cell or metric: each is found by
the name that ``BENCHMARK.json`` gives it, so a new one is a new file and a
new entry, and no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

from chipbench.traffic import Mix

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    mix: Mix
    slots: int
    check: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    bench_dir: str

    @property
    def family(self) -> str:
        return self.config["family"]

    def reference(self):
        return importlib.import_module(f"chipbench.reference.{self.family}")

    def work(self):
        return importlib.import_module(f"chipbench.work.{self.family}")

    def program_config(self):
        """The program's ``ModelConfig``: its registered arch with the
        overrides the configuration file names."""
        import jax.numpy as jnp

        from repro.configs import get_arch
        prog = self.config["program"]
        kw = dict(prog.get("overrides", {}))
        for k in ("param_dtype", "compute_dtype"):
            if k in kw:
                kw[k] = jnp.dtype(kw[k])
        return dataclasses.replace(get_arch(prog["arch"]), name=self.config_name, **kw)

    def reader(self, metric: str):
        """The ``read`` function of ``metrics/<metric>.py``."""
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT,
              bench_dir: Optional[str] = None) -> Cell:
    """Cell ``name`` of ``<root>/BENCHMARK.json``, with its files read."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = bench_dir or os.path.join(root, "chipbench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cell_file = _load_json(os.path.join(bench_dir, "cells", name + ".json"))

    def metrics(key):
        return [Metric(m["name"], m["unit"]) for m in bench[key]
                if name in m.get("workloads", [name])]

    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=_load_json(os.path.join(root, cfg_entry["file"])),
                mix=Mix.load_file(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"),
                                  w["traffic"]),
                slots=int(cell_file["slots"]), check=cell_file["check"],
                end_to_end=metrics("end_to_end"), per_layer=metrics("per_layer"),
                bench_dir=bench_dir)
