"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count`` before first jax init and then
calls this; tests import it with the default single device without side
effects.

Mesh layout (TPU v5e pods of 16×16 = 256 chips):
  single-pod:  (data=16, model=16)          — FSDP/batch × TP
  multi-pod:   (pod=2, data=16, model=16)   — pod = DCN data parallelism;
               within a pod, ICI FSDP × TP. The ``pod`` axis composes with
               ``data`` for the global batch dimension.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def _auto_mesh(shape, axes):
    # Auto axes: shardings come from jit's propagation of NamedSharding
    # inputs and ``with_sharding_constraint``, not from the explicit-sharding
    # type system that ``jax.make_mesh`` defaults to.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many real devices exist (tests/examples)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_sweep_mesh(n_devices: int = 0):
    """1-D mesh over local devices; ``repro.sweep.engine`` lays the sweep
    batch axis across it (data-parallel points, zero collectives)."""
    n = n_devices or len(jax.devices())
    return _auto_mesh((n,), ("sweep",))


def batch_axes(mesh) -> tuple:
    """Mesh axes that shard the global-batch dimension."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
