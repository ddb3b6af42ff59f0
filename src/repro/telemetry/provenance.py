"""Provenance stamping: who/what/where produced a ``RunResult``.

Every result-creating path in the runner stamps these well-known
``extra`` keys (schema stays v1 — see ``runner/results.py``):

    prov_commit     git HEAD sha ("unknown" outside a repo)
    prov_dirty      True when the working tree had local modifications
    prov_backend    ``jax.default_backend()`` of the measuring process
    prov_host       hostname of the measuring process
    prov_jax        jax.__version__
    prov_python     platform.python_version()

Stamping uses setdefault semantics: a record that already carries a
stamp (one loaded from a store, say) keeps it.

Collection is cached per process — two subprocess calls (git) and one
jax attribute read, once.
"""
from __future__ import annotations

import os
import platform
import socket
import subprocess
from typing import Any, Dict, Optional

__all__ = ["collect", "stamp", "provenance_key", "PROV_KEYS"]

PROV_KEYS = ("prov_commit", "prov_dirty", "prov_backend", "prov_host",
             "prov_jax", "prov_python")

_CACHE: Optional[Dict[str, Any]] = None


def _git(*args: str) -> Optional[str]:
    try:
        out = subprocess.run(
            ("git",) + args, cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            timeout=10, check=False)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode != 0:
        return None
    return out.stdout.decode("utf-8", "replace").strip()


def collect(refresh: bool = False) -> Dict[str, Any]:
    """The provenance dict for this process (cached)."""
    global _CACHE
    if _CACHE is not None and not refresh:
        return _CACHE
    commit = os.environ.get("REPRO_COMMIT") or _git("rev-parse", "HEAD") \
        or "unknown"
    status = _git("status", "--porcelain")
    dirty = bool(status) if status is not None else False
    try:
        import jax
        backend = jax.default_backend()
        jax_ver = jax.__version__
    except Exception:   # pragma: no cover - jax is a hard dep in practice
        backend, jax_ver = "unknown", "unknown"
    _CACHE = {
        "prov_commit": commit,
        "prov_dirty": dirty,
        "prov_backend": backend,
        "prov_host": socket.gethostname(),
        "prov_jax": jax_ver,
        "prov_python": platform.python_version(),
    }
    return _CACHE


def stamp(result: Any, *, overwrite: bool = False) -> Any:
    """Fill ``result.extra`` with provenance keys (setdefault unless
    *overwrite*).  Accepts a ``RunResult`` or a plain extras dict."""
    extra = result if isinstance(result, dict) else result.extra
    for k, v in collect().items():
        if overwrite:
            extra[k] = v
        else:
            extra.setdefault(k, v)
    return result


def provenance_key(extra: Dict[str, Any]) -> str:
    """Compact grouping key: ``<commit12>[+dirty]/<backend>/<host>``.

    Works on any dict carrying ``prov_*`` keys (a ``RunResult.extra`` or
    a serialized history record's ``extra``).
    """
    commit = str(extra.get("prov_commit", "unknown"))[:12]
    if extra.get("prov_dirty"):
        commit += "+dirty"
    return "/".join((commit, str(extra.get("prov_backend", "?")),
                     str(extra.get("prov_host", "?"))))
