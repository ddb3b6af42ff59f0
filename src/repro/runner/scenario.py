"""Declarative scenario matrix for the unified benchmark runner.

A ``Scenario`` is one fully-specified benchmark execution:

    arch x task x batch x seq x dtype x compiler-mode [x slots x trace]

The bracketed axes exist only under ``task="serve"`` (the
continuous-batching serving workload, ``repro.launch.serve``): ``slots``
is the decode batch width and ``trace`` the deterministic load profile
(``repro.runner.traces``) — a generative profile name or a recorded
spec file (``trace="file:PATH"``).  ``ScenarioMatrix`` expands the cartesian
product and applies the
torchbench-driver selection semantics (regex ``filter`` / ``exclude``
against the scenario name, plus an exact ``skip`` list — matching the
torchdynamo ``iter_models`` front door).
"""
from __future__ import annotations

import dataclasses
import itertools
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

#: single-step tasks measured by the harness ``measure()`` protocol
STEP_TASKS = ("train", "infer_prefill", "infer_decode")

#: all tasks: the step tasks, the continuous-batching serving workload
#: (a whole engine run per cell, ``repro.launch.serve``), the
#: load-generation mode over that same engine (``task="loadgen"``: replay
#: a trace shard at a scaled offered load), and the kernel micro-bench cells
#: of the autotuner (``repro.tuning``), whose ``arch`` axis names a
#: tuning candidate instead of a registry arch
TASKS = STEP_TASKS + ("serve", "loadgen", "kernel")

#: the only execution mode for kernel micro-bench cells: a tuning
#: candidate is one jitted ops-layer call — eager dispatch and the
#: model-level reduced-config/donation modes don't apply
KERNEL_MODES = ("jit",)

#: execution modes valid for the serving task: the continuous-batching
#: engine is a jitted decode loop — op-by-op dispatch (eager) and the
#: train-only reduced-config modes don't apply.  "jit_donated" donates
#: the KV cache into each decode step (the production protocol).
SERVE_MODES = ("jit", "jit_donated")

#: compiler-execution modes (paper Figs. 3-4 comparison; see core/compilers.py)
#:   eager        op-by-op dispatch (jax.disable_jit)
#:   jit          whole-step XLA compilation, no buffer donation
#:   jit_donated  + donated state buffers (the standard steady-state protocol)
#:   jit_unrolled layer scan unrolled  (cfg: scan_layers=False)
#:   jit_noremat  no rematerialization (cfg: remat="none")
MODES = ("eager", "jit", "jit_donated", "jit_unrolled", "jit_noremat")

#: reduced-config overrides per mode (applied at arch-build time)
MODE_OVERRIDES: Dict[str, Dict[str, Any]] = {
    "jit_unrolled": {"scan_layers": False},
    "jit_noremat": {"remat": "none"},
}

DTYPES = ("fp32", "bf16")


def dtype_overrides(dtype: str) -> Dict[str, Any]:
    if dtype == "fp32":
        return {}
    if dtype == "bf16":
        import jax.numpy as jnp
        return {"param_dtype": jnp.bfloat16}
    raise ValueError(f"unknown dtype {dtype!r} (known: {DTYPES})")


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One cell of the execution matrix (hashable: used as a cache key).

    The serving task carries three extra axes — ``slots`` (decode batch
    rows), ``trace`` (load-profile name, see ``runner/traces.py``) and
    ``admission`` (prefill policy: ``"batched"`` admits every waiting
    request of a wave in one jitted call, ``"single"`` keeps the
    one-prefill-per-request baseline) — which stay inert (0 / "") on
    every other task.  For ``task="serve"`` the shared axes are
    reinterpreted: ``batch`` is the trace's request count and ``seq``
    its prompt length.
    """
    arch: str
    task: str = "train"
    batch: int = 2
    seq: int = 64
    dtype: str = "fp32"
    mode: str = "jit_donated"
    slots: int = 0
    trace: str = ""
    load: float = 0.0
    split: str = ""
    admission: str = ""

    def __post_init__(self):
        if self.task not in TASKS:
            raise ValueError(f"unknown task {self.task!r} (known: {TASKS})")
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode!r} (known: {MODES})")
        if self.dtype not in DTYPES:
            raise ValueError(f"unknown dtype {self.dtype!r} (known: {DTYPES})")
        if self.task in ("serve", "loadgen"):
            if self.mode not in SERVE_MODES:
                raise ValueError(f"{self.task} supports modes {SERVE_MODES}, "
                                 f"not {self.mode!r}")
            if self.slots == "auto":
                raise ValueError(
                    "slots='auto' is a ScenarioMatrix axis value, resolved "
                    "to a measured slot count at matrix expansion "
                    "(repro.runner.loadgen.auto_slots); a bare Scenario "
                    "needs an int")
            # normalize the serve axes so Scenario(task="serve") works bare
            if self.slots == 0:
                object.__setattr__(self, "slots", 4)
            if not self.trace:
                object.__setattr__(self, "trace", "uniform")
            if not self.admission:
                object.__setattr__(self, "admission", "batched")
            from repro.launch.serve import ADMISSIONS
            if self.admission not in ADMISSIONS:
                raise ValueError(f"unknown admission {self.admission!r} "
                                 f"(known: {ADMISSIONS})")
            if self.slots < 1:
                raise ValueError(f"serve needs slots >= 1, got {self.slots}")
            from repro.runner.traces import (FILE_PREFIX, PROFILES,
                                             PROMPT_PROFILES, split_trace)
            if self.trace.startswith(FILE_PREFIX):
                # a recorded trace-spec file (traces.save_spec); resolved
                # lazily on the host that runs the cell — a missing file
                # becomes that cell's error record, not a matrix error
                if not self.trace[len(FILE_PREFIX):]:
                    raise ValueError("trace='file:' needs a path")
            else:
                arrival, plen = split_trace(self.trace)
                if arrival not in PROFILES:
                    raise ValueError(
                        f"unknown trace profile {arrival!r} (known: "
                        f"{PROFILES}, or 'file:PATH')")
                if plen not in PROMPT_PROFILES:
                    raise ValueError(
                        f"unknown prompt-length profile {plen!r} "
                        f"(known: {PROMPT_PROFILES})")
        if self.task == "loadgen":
            # offered-load multiplier over the trace's native arrival rate;
            # normalize 0 (the inert default) to 1.0 so bare loadgen works
            if self.load == 0.0:
                object.__setattr__(self, "load", 1.0)
            if not self.load > 0:
                raise ValueError(f"loadgen needs load > 0, got {self.load}")
            if self.split and not re.fullmatch(r"\d+/\d+", self.split):
                raise ValueError(
                    f"split must be 'i/n' (e.g. '0/2'), got {self.split!r}")
        elif self.task == "serve":
            if self.load or self.split:
                raise ValueError("load/split are loadgen-only axes "
                                 "(use task='loadgen')")
        elif self.slots or self.trace or self.load or self.split \
                or self.admission:
            raise ValueError(f"slots/trace/load/split/admission are "
                             f"serve/loadgen-only axes (task={self.task!r})")
        if self.task == "kernel":
            if self.mode not in KERNEL_MODES:
                raise ValueError(f"kernel cells support modes {KERNEL_MODES}, "
                                 f"not {self.mode!r}")
            # arch must be a tuning candidate id "kernel@DIMS@PARAMS"
            # (full decode happens lazily on the host that runs the cell,
            # like serve's trace files — an unknown kernel becomes that
            # cell's error record, not a matrix error)
            if self.arch.count("@") != 2:
                raise ValueError(
                    f"kernel cells need a candidate-id arch "
                    f"('kernel@DIMS@PARAMS', see repro.tuning.space), "
                    f"got {self.arch!r}")

    @property
    def bench(self) -> str:
        """The suite-registry benchmark name ("arch/task")."""
        return f"{self.arch}/{self.task}"

    @property
    def name(self) -> str:
        base = f"{self.arch}/{self.task}/b{self.batch}/s{self.seq}/{self.dtype}/{self.mode}"
        # batched admission is the default and stays out of the name, so
        # pre-existing serve/loadgen cell names (and skip lists) are stable
        adm = "/adm-single" if self.admission == "single" else ""
        if self.task == "serve":
            return f"{base}/x{self.slots}/{self.trace}{adm}"
        if self.task == "loadgen":
            name = f"{base}/x{self.slots}/{self.trace}/L{self.load:g}"
            if self.split:
                i, n = self.split.split("/")
                name += f"/{i}of{n}"
            return name + adm
        return base

    def build_overrides(self) -> Dict[str, Any]:
        """Reduced-config overrides implied by (mode, dtype)."""
        return {**dtype_overrides(self.dtype), **MODE_OVERRIDES.get(self.mode, {})}

    def build_key(self) -> Tuple:
        """Cache key for the arch build (model + params) this scenario needs.

        Serve cells extend the key with ("serve", slots): the compiled
        decode executable is shaped by the slot count.  The trace profile
        is deliberately NOT in the key — it changes the replayed load,
        never what gets built or compiled, so traces of one (arch, slots)
        group share one engine.  The key also names the matrix trace's
        group spans.
        """
        base = (self.arch, self.dtype, self.mode in MODE_OVERRIDES and self.mode)
        if self.task in ("serve", "loadgen"):
            # loadgen shares the serve group: same slots -> same compiled
            # decode executable and cached engine
            return base + ("serve", self.slots)
        if self.task == "kernel":
            # one group per candidate: kernel cells share no arch build
            return ("kernel", self.arch, self.dtype)
        return base

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Scenario":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls) if f.name in d})


def select_scenarios(scenarios: Iterable[Scenario],
                     filter: Sequence[str] = (),
                     exclude: Sequence[str] = ()) -> List[Scenario]:
    """The shared selection semantics: keep iff ANY ``filter`` regex matches
    the scenario name (empty keeps all); drop if ANY ``exclude`` matches."""
    flt = re.compile("|".join(filter)) if filter else None
    exc = re.compile("|".join(exclude)) if exclude else None
    return [s for s in scenarios
            if (flt is None or flt.search(s.name))
            and not (exc is not None and exc.search(s.name))]


@dataclasses.dataclass
class ScenarioMatrix:
    """Cartesian scenario expander with filter/exclude/skip selection.

    * ``filter``  — regex list; a scenario is kept iff ANY regex matches its
      name (empty list keeps everything);
    * ``exclude`` — regex list; a scenario is dropped if ANY regex matches;
    * ``skip``    — exact names: a full scenario name, a benchmark name
      ("arch/task"), or a bare arch (the torchbench SKIP-set idiom for
      known-broken models).

    ``slots`` / ``traces`` / ``admissions`` are the serve-only axes: they
    multiply out only under ``task="serve"`` / ``task="loadgen"`` (every
    other task gets exactly one scenario per (arch, batch, seq, dtype,
    mode) cell, with the serve axes inert); ``loads`` / ``splits``
    additionally multiply out under ``task="loadgen"`` only — an
    offered-load sweep over trace shards.  A slots entry may be the
    string ``"auto"``: it is resolved per arch at expansion time from the
    measured load curve (``repro.runner.loadgen.auto_slots``, reading
    ``results/loadgen_curve.json``), falling back to the default width 4
    when no usable curve exists.  Serve cells silently skip modes outside
    ``SERVE_MODES`` — a matrix mixing ``tasks=("train", "serve")`` with
    ``modes=("eager", ...)`` expands the eager cell for train only.
    ``task="kernel"`` (the autotuner's micro-bench cells, opt-in like
    serve; archs are tuning candidate ids) likewise expands only under
    ``mode="jit"``.

    Expansion (the cartesian product AND the regex selection) is memoized
    on the current field values — ``len(m)`` / ``for s in m`` / nested
    ``m.expand()`` calls pay for one expansion, and editing any field
    invalidates the cache.
    """
    archs: Sequence[str]
    tasks: Sequence[str] = STEP_TASKS     # serve is opt-in: tasks=("serve",)
    batches: Sequence[int] = (2,)
    seqs: Sequence[int] = (64,)
    dtypes: Sequence[str] = ("fp32",)
    modes: Sequence[str] = ("jit_donated",)
    slots: Sequence[int] = (4,)
    traces: Sequence[str] = ("uniform",)
    loads: Sequence[float] = (1.0,)       # loadgen-only: offered-load sweep
    splits: Sequence[str] = ("",)         # loadgen-only: trace shards "i/n"
    admissions: Sequence[str] = ("batched",)  # serve/loadgen admission policy
    filter: Sequence[str] = ()
    exclude: Sequence[str] = ()
    skip: Sequence[str] = ()

    def _fields_key(self) -> Tuple:
        return tuple(tuple(getattr(self, f.name))
                     for f in dataclasses.fields(self))

    def _expanded(self) -> List[Scenario]:
        key = self._fields_key()
        cached = getattr(self, "_expand_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        skip = set(self.skip)
        # per-arch (slots, fallback_reason) for slots="auto"; cells whose
        # resolution fell back carry the reason to the dispatch layer as
        # extra["slots_fallback"] (see BenchmarkRunner._matrix_extras)
        slot_cache: Dict[str, Tuple[int, str]] = {}
        fallbacks: Dict[str, str] = {}

        def resolve_slots(k, arch):
            if k != "auto":
                return k
            if arch not in slot_cache:
                from repro.runner.loadgen import auto_slots_info
                slot_cache[arch] = auto_slots_info(arch)
            return slot_cache[arch][0]

        def mark_auto(s: Scenario, k, arch) -> Scenario:
            if k == "auto" and slot_cache.get(arch, (0, ""))[1]:
                fallbacks[s.name] = slot_cache[arch][1]
            return s

        out: List[Scenario] = []
        for arch, task, batch, seq, dtype, mode in itertools.product(
                self.archs, self.tasks, self.batches, self.seqs,
                self.dtypes, self.modes):
            if task == "serve":
                if mode not in SERVE_MODES:
                    continue      # eager/reduced-config modes are train-only
                cells = [mark_auto(
                             Scenario(arch=arch, task=task, batch=batch,
                                      seq=seq, dtype=dtype, mode=mode,
                                      slots=resolve_slots(k, arch), trace=t,
                                      admission=adm), k, arch)
                         for k, t, adm in itertools.product(
                             self.slots, self.traces, self.admissions)]
            elif task == "loadgen":
                if mode not in SERVE_MODES:
                    continue      # loadgen drives the serve engine: same modes
                cells = [mark_auto(
                             Scenario(arch=arch, task=task, batch=batch,
                                      seq=seq, dtype=dtype, mode=mode,
                                      slots=resolve_slots(k, arch), trace=t,
                                      load=ld, split=sp, admission=adm),
                             k, arch)
                         for k, t, ld, sp, adm in itertools.product(
                             self.slots, self.traces, self.loads, self.splits,
                             self.admissions)]
            elif task == "kernel":
                if mode not in KERNEL_MODES:
                    continue      # kernel micro-bench cells are jit-only
                cells = [Scenario(arch=arch, task=task, batch=batch, seq=seq,
                                  dtype=dtype, mode=mode)]
            else:
                cells = [Scenario(arch=arch, task=task, batch=batch, seq=seq,
                                  dtype=dtype, mode=mode)]
            for s in cells:
                if {s.name, s.bench, s.arch} & skip:
                    continue
                out.append(s)
        out = select_scenarios(out, self.filter, self.exclude)
        names = {s.name for s in out}
        self._fallback_cache = {n: r for n, r in fallbacks.items()
                                if n in names}
        self._expand_cache = (key, out)
        return out

    def slots_fallback(self) -> Dict[str, str]:
        """Scenario name -> fallback reason for every expanded cell whose
        ``slots="auto"`` resolution fell back to the default width (see
        ``loadgen.auto_slots_info``).  Empty when every auto resolution
        used a real measured curve (or no cell asked for auto)."""
        self._expanded()
        return dict(getattr(self, "_fallback_cache", {}))

    def expand(self) -> List[Scenario]:
        return list(self._expanded())   # callers may mutate their copy

    def __iter__(self):
        return iter(self._expanded())

    def __len__(self) -> int:
        return len(self._expanded())
