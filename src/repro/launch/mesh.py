"""Production mesh factory.

A *function*, not a module-level constant, so importing this module never
touches jax device state (the dry-run forces 512 host devices via
XLA_FLAGS before any jax import; tests and benches see 1 device).

Single pod: 16 x 16 = 256 chips (v5e pod), axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); the ``pod``
axis carries data parallelism + the second FSDP level across pods (DCN in
real deployments), ``model`` stays intra-pod (ICI).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler), the one axis type the model code is written for."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over available (CPU) devices for tests/examples."""
    return _auto_mesh((data, model), ("data", "model"))
