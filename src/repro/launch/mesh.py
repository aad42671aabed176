"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state — smoke tests see one
CPU device; only the dry-run (which sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import) sees the full placeholder topology.

Topology (TPU v5e target): single pod = (data=16, model=16) — 256 chips;
multi-pod = (pod=2, data=16, model=16) — 512 chips.  The `model` axis is
mapped innermost so tensor-parallel collectives stay on intra-board ICI
links; the `pod` axis is outermost (DCI), carrying only data-parallel
gradient reductions (see parallel/collectives.py for the hierarchical
schedule).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh for tests/examples (e.g. (2,2) on 4 host devices)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def smallest_mesh() -> Optional[object]:
    """A (data=N, model=1) mesh over whatever devices exist; None if 1."""
    n = len(jax.devices())
    if n == 1:
        return None
    return make_mesh((n, 1), ("data", "model"))
