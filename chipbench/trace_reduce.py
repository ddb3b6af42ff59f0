"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
time per XLA module and per op, and the idle gaps.

A TPU's plane is ``/device:TPU:<n>``.  Its ``XLA Modules`` line holds one
event per execution of a compiled program (named after the jitted function,
``jit_<name>(<id>)``), its ``XLA Ops`` line one event per operation run,
named by the HLO instruction's text.  Ops nest there: a ``while`` (a scan
over layers) spans the ops of its body, so an op's own time is its
duration less that of the ops inside it.  Busy time is the union of the op
intervals inside the window; the window is
the benchmark's ``chipbench.window`` annotation on the host plane where it
is there, and the span of the device events otherwise.  Host annotations
named ``chipbench.*`` come back too, so idle gaps can be laid beside what the
host was doing.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
WINDOW = "chipbench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return _SUFFIX.sub("", event_name).strip()


def op_name(event_name: str) -> str:
    """``%fusion.149 = bf16[32,16384]{1,0:...} fusion(...)`` ->
    ``fusion.149 bf16[32,16384]``: the instruction and its result's shape."""
    name, _, rest = event_name.partition(" = ")
    shape = rest.split("{", 1)[0] if rest[:1].isalpha() else "(tuple)"
    return f"{name.lstrip('%')} {shape}".strip()


def self_times(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Total own time per op name: each event's duration less that of the
    events nested inside it on the same line."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []                       # [name, end, child time]

    def close(item):
        out[item[0]] += item[3] - item[2]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
    while stack:
        close(stack.pop())
    return dict(out)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class Device:
    name: str
    busy: List[Tuple[float, float]]                  # merged op intervals (ns)
    modules: Dict[str, List[float]]                  # name -> [total ns, count]
    ops: Dict[str, float]                            # op name -> own ns


@dataclasses.dataclass
class Summary:
    window: Tuple[float, float]                      # ns, on the trace's clock
    devices: List[Device]
    host: List[Tuple[str, float, float]]             # chipbench.* annotations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        lo, hi = self.window
        per = [sum(e - s for s, e in clip(d.busy, lo, hi)) for d in self.devices]
        return sum(per) / len(per) * 1e-9

    def module(self, pattern: str) -> Optional[Tuple[float, int]]:
        """(seconds, executions) of the modules whose name contains
        ``pattern``, on the first device; None if there are none."""
        tot, n = 0.0, 0
        for name, (t, c) in self.devices[0].modules.items():
            if pattern in name:
                tot += t
                n += int(c)
        return (tot * 1e-9, n) if n else None

    def gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the first device inside the window."""
        lo, hi = self.window
        busy = clip(self.devices[0].busy, lo, hi)
        out, t = [], lo
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        ops = sorted(self.devices[0].ops.items(), key=lambda kv: -kv[1])
        return [(k, v * 1e-9) for k, v in ops[:n]]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def load(path: str) -> Summary:
    """Read one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[Device] = []
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            modules, ops = defaultdict(lambda: [0.0, 0]), []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    for name, s, e in _events(line):
                        m = modules[module_name(name)]
                        m[0] += e - s
                        m[1] += 1
                elif line.name == OP_LINE:
                    ops += [(op_name(name), s, e) for name, s, e in _events(line)]
            devices.append(Device(plane.name, union([(s, e) for _, s, e in ops]),
                                  dict(modules), self_times(ops)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.startswith("chipbench."):
                        host.append((name, s, e))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    win = [(s, e) for name, s, e in host if name == WINDOW]
    if win:
        window = (min(s for s, _ in win), max(e for _, e in win))
    else:
        spans = [iv for d in devices for iv in d.busy]
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    return Summary(window=window, devices=devices, host=sorted(host, key=lambda h: h[1]))
