"""Trace export: Chrome trace-event JSON (Perfetto-loadable) + a text
flame summary for terminals.

The Chrome format is the ``traceEvents`` array flavour: one ``"X"``
(complete) event per span with microsecond ``ts``/``dur``, all on one
lane (every span comes from the process that runs the cells), and an
``"M"`` metadata event naming it.  Span ids, parents and attrs ride in
``args`` so the nesting test and the smoke gate can reconstruct the
tree from the file alone.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional

__all__ = ["chrome_trace", "save_trace", "flame_summary"]


def _as_dicts(spans: Iterable) -> List[Dict[str, Any]]:
    """Accept ``Tracer.export()`` dicts or live ``Span`` objects."""
    return [sp if isinstance(sp, dict) else sp.to_dict() for sp in spans]


def chrome_trace(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Convert ``Tracer.export()`` span dicts to a Chrome trace dict."""
    events: List[Dict[str, Any]] = [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": "runner"}}]
    for sp in _as_dicts(spans):
        args: Dict[str, Any] = {
            "span_id": sp.get("span_id"),
            "parent": sp.get("parent_id"),
            "kind": sp.get("kind"),
        }
        attrs = sp.get("attrs")
        if attrs:
            args.update(attrs)
        events.append({
            "ph": "X",
            "name": str(sp.get("name", "?")),
            "cat": str(sp.get("kind", "span")),
            "pid": 0,
            "tid": 0,
            "ts": float(sp.get("ts", 0.0)) * 1e6,
            "dur": max(0.0, float(sp.get("dur_s", 0.0))) * 1e6,
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def save_trace(spans: Iterable[Dict[str, Any]], path: str) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(spans), f)
    return path


def _fmt_dur(dur_s: float) -> str:
    if dur_s >= 1.0:
        return f"{dur_s:.2f}s"
    if dur_s >= 1e-3:
        return f"{dur_s * 1e3:.1f}ms"
    return f"{dur_s * 1e6:.0f}us"


def flame_summary(spans: Iterable[Dict[str, Any]], *, max_depth: int = 8,
                  max_children: int = 24) -> str:
    """Indented span tree, durations inline — a flame graph for
    terminals.  Children are shown in start order; long sibling runs
    (e.g. hundreds of decode steps) are elided with a count."""
    spans = sorted(_as_dicts(spans), key=lambda s: float(s.get("ts", 0.0)))
    by_id = {sp.get("span_id"): sp for sp in spans}
    kids: Dict[Optional[str], List[Dict[str, Any]]] = {}
    for sp in spans:
        parent = sp.get("parent_id")
        if parent not in by_id:
            parent = None
        kids.setdefault(parent, []).append(sp)

    lines: List[str] = []

    def emit(sp: Dict[str, Any], depth: int) -> None:
        pad = "  " * depth
        name = str(sp.get("name", "?"))
        lines.append(f"{pad}{name} {_fmt_dur(float(sp.get('dur_s', 0.0)))}")
        if depth + 1 >= max_depth:
            return
        children = kids.get(sp.get("span_id"), [])
        for child in children[:max_children]:
            emit(child, depth + 1)
        if len(children) > max_children:
            rest = children[max_children:]
            total = sum(float(c.get("dur_s", 0.0)) for c in rest)
            lines.append(f"{'  ' * (depth + 1)}... {len(rest)} more "
                         f"({_fmt_dur(total)})")

    roots = kids.get(None, [])
    if not roots:
        return "(no spans)"
    for root in roots:
        emit(root, 0)
    return "\n".join(lines)
