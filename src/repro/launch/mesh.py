"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (required so smoke tests see 1 CPU device while the dry-run
process sees 512 host devices via XLA_FLAGS set before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the model stack places activations
    with `with_sharding_constraint`, which only refers to Auto axes (the
    installed jax makes Explicit axes by default)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod: 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a 1-axis 'data' mesh (CPU tests/examples)."""
    n = len(jax.devices())
    return _mesh((n,), ("data",))


def make_mesh_for(devices: int, model_parallel: int = 1, pods: int = 1):
    """Elastic re-meshing helper: arrange `devices` into (pod, data, model)."""
    assert devices % (model_parallel * pods) == 0
    data = devices // (model_parallel * pods)
    if pods > 1:
        return _mesh((pods, data, model_parallel), ("pod", "data", "model"))
    if model_parallel > 1:
        return _mesh((data, model_parallel), ("data", "model"))
    return _mesh((data,), ("data",))
