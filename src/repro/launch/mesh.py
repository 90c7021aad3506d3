"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state. The dry-run forces 512 host devices *before*
importing jax; everything else sees the real device count.
"""
from __future__ import annotations

import jax


def auto_axis_kwargs(n_axes: int) -> dict:
    """``make_mesh`` keyword arguments giving every one of ``n_axes``
    axes the ``Auto`` sharding type."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """v5e pod mesh: 16x16 (= 256 chips) per pod; 2 pods for multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **auto_axis_kwargs(len(axes)))


def make_host_mesh(model_parallel: int = 1) -> jax.sharding.Mesh:
    """Mesh over whatever devices actually exist (tests, examples)."""
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         **auto_axis_kwargs(2))
