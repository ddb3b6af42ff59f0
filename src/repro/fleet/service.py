"""The supervised fleet loop: ticks under restart/backoff semantics.

``FleetService`` wraps the :class:`~repro.fleet.scheduler.FleetScheduler`
tick as a ``runtime/supervisor.Supervisor`` step (one tick = one step,
checkpointed every step into a small JSON state file), so a tick that
raises mid-matrix restarts with exponential backoff from the last
completed tick — already-logged history points survive, because they
live in the ``MetricStore``'s append-only JSONL, not in service state.

After every completed tick the service:

* runs :func:`repro.fleet.triage.triage` over the tick's drift report
  and writes the ranked outcome to ``results/fleet_report.json``;
* rewrites the heartbeat status file ``results/fleet_status.json``
  (schema-tagged: last tick, open findings, restart count, per-tick
  counter snapshots, and the full metrics snapshot) — the liveness
  probe, fresh after each tick by construction;
* exports the Prometheus text snapshot to ``results/fleet_metrics.prom``.

``scripts/fleet.py`` is the CLI (``--ticks N --fast`` for bounded
virtual-clock demo runs).
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

from repro.core.regression import MetricStore
from repro.fleet.metrics import registry
from repro.fleet.scheduler import FleetConfig, FleetScheduler, WallClock
from repro.fleet.triage import triage
from repro.runtime.supervisor import Supervisor

FLEET_STATUS_SCHEMA_KEY = "fleet_status"
FLEET_STATUS_SCHEMA_VERSION = 1

#: the counters the status file tracks per tick (the smoke gate's
#: monotonicity probe); everything else is in the full snapshot
STATUS_COUNTER_PREFIXES = ("fleet_", "serve_")


class _TickCheckpoint:
    """A ``CheckpointManager``-shaped adapter over one JSON file: the
    supervisor's tiny service state (ticks done, open findings) doesn't
    need the async array-tree machinery of ``runtime/checkpoint``."""

    def __init__(self, path: str):
        self.path = path

    def save(self, state: Any, step: int) -> None:
        tmp = f"{self.path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump({"step": step, "state": state}, f)
        os.replace(tmp, self.path)

    def wait(self) -> None:
        pass

    def restore_latest(self, like: Any):
        try:
            with open(self.path) as f:
                payload = json.load(f)
            return payload["state"], int(payload["step"])
        except (OSError, ValueError, KeyError):
            return None, 0


class FleetService:
    """The long-running perf-CI service: supervised scheduler ticks with
    triage, status heartbeat, and metrics export after every tick."""

    def __init__(self, config: FleetConfig, *, store: MetricStore, runner,
                 results_dir: str = "results", clock=None,
                 hooks_for_tick: Optional[Callable[[int], Optional[dict]]] = None,
                 commits_for: Optional[Callable] = None,
                 max_restarts: int = 3, backoff_s: float = 0.0,
                 sleep: Optional[Callable[[float], None]] = None,
                 scheduler: Optional[FleetScheduler] = None):
        self.cfg = config
        self.store = store
        self.runner = runner
        self.clock = clock if clock is not None else WallClock()
        self.commits_for = commits_for
        self.max_restarts = max_restarts
        self.backoff_s = backoff_s
        self._sleep = sleep if sleep is not None else self.clock.sleep
        self.scheduler = scheduler or FleetScheduler(
            config, store, runner, clock=self.clock,
            hooks_for_tick=hooks_for_tick)
        os.makedirs(results_dir, exist_ok=True)
        self.status_path = os.path.join(results_dir, "fleet_status.json")
        self.report_path = os.path.join(results_dir, "fleet_report.json")
        self.prom_path = os.path.join(results_dir, "fleet_metrics.prom")
        self.ckpt_path = os.path.join(results_dir, "fleet_service_state.json")
        #: per-tick status-counter snapshots (rewritten into the status
        #: file every tick — the monotonicity record across the run)
        self.tick_log: List[Dict[str, Any]] = []
        self.last_report: Optional[Dict[str, Any]] = None
        self._sup: Optional[Supervisor] = None

    # ---- the supervised loop ---------------------------------------------

    def run(self, ticks: int) -> Dict[str, Any]:
        """Run ``ticks`` supervised scheduler ticks; returns a summary.

        A fresh service run starts from tick 0 (the checkpoint file is
        reset) — long-lived *history* lives in the MetricStore, not in
        service state.
        """
        ckpt = _TickCheckpoint(self.ckpt_path)
        try:
            os.remove(self.ckpt_path)
        except OSError:
            pass
        sup = Supervisor(ckpt, save_every=1, max_restarts=self.max_restarts,
                         backoff_s=self.backoff_s, sleep=self._sleep)
        self._sup = sup
        state = {"ticks_done": 0, "open_findings": 0}
        state, step = sup.run(state, self._step, ticks)
        return {"ticks": step, "restarts": sup.restarts,
                "events": list(sup.events),
                "open_findings": state.get("open_findings", 0),
                "status_path": self.status_path,
                "report_path": self.report_path,
                "prom_path": self.prom_path}

    def _step(self, state: Dict[str, Any], step: int) -> Dict[str, Any]:
        tres = self.scheduler.tick(step)
        report = triage(
            tres.drift, runner=self.runner,
            scenarios=self.scheduler.scenarios,
            hooks=self.scheduler.hooks_for_tick(step) or {},
            threshold=self.cfg.threshold,
            commits_for=self.commits_for,
            meta={"tick": step, "drained_cases": tres.drained_cases})
        self.last_report = report
        _write_json(self.report_path, report)
        state = dict(state)
        state["ticks_done"] = step + 1
        state["open_findings"] = sum(
            1 for f in report["findings"]
            if f["rule"] in ("regression_confirmed", "regression_bisected"))
        self._write_status(step, state, tres)
        with open(self.prom_path, "w") as f:
            f.write(registry().to_prometheus())
        if self.cfg.interval_s:
            self.clock.sleep(self.cfg.interval_s)
        return state

    # ---- heartbeat --------------------------------------------------------

    def _write_status(self, step: int, state: Dict[str, Any],
                      tres) -> None:
        snap = registry().snapshot()
        counters = {k: v for k, v in snap["counters"].items()
                    if k.startswith(STATUS_COUNTER_PREFIXES)}
        self.tick_log.append({"tick": step, "ts": time.time(),
                              "clock": self.clock.time(),
                              "wall_s": tres.wall_s,
                              "cells": len(tres.results),
                              "drift_findings": len(tres.drift["findings"]),
                              "drained_cases": tres.drained_cases,
                              "counters": counters})
        status = {
            FLEET_STATUS_SCHEMA_KEY: FLEET_STATUS_SCHEMA_VERSION,
            "ts": time.time(),
            "tick": step,
            "ticks_done": state["ticks_done"],
            "open_findings": state["open_findings"],
            "restarts": self._sup.restarts if self._sup else 0,
            "ticks": self.tick_log,
            "metrics": snap,
        }
        _write_json(self.status_path, status)


def _write_json(path: str, payload: Dict[str, Any]) -> None:
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)
