"""Production mesh construction.

``make_production_mesh`` is a *function* (never a module-level constant) so
importing this module does not touch jax device state — smoke tests see one
CPU device; only ``dryrun.py`` forces 512 host devices.

Every mesh is built with ``Auto`` axes: ``jax.make_mesh`` defaults to
``Explicit`` axes, under which ``with_sharding_constraint`` refuses the
logical-rule specs the models emit.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh() -> Optional[Mesh]:
    """Whatever devices exist, as a 1-D 'data' mesh (CPU smoke paths)."""
    n = len(jax.devices())
    if n == 1:
        return None
    return make_mesh((n,), ("data",))
