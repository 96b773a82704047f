"""Production mesh construction.

Mesh is built by a FUNCTION so importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).

Production target: TPU v5e pods, 256 chips each.
  single-pod : (16, 16)    axes ("data", "model")
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") — "pod" is the DCN
               axis; DP-over-pod by default, GPipe over "pod" available
               (dist/pipeline.py).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape: tuple, axes: tuple) -> Mesh:
    # Auto axes everywhere so GSPMD propagates through un-annotated ops
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_cpu_mesh(shape: tuple, axes: tuple) -> Mesh:
    """Small mesh over however many (possibly fake) CPU devices exist —
    used by the 8-device sharded integration tests."""
    return _mesh(shape, axes)


def make_tp_mesh(tp: int) -> Mesh:
    """Single-axis ("model",) mesh over ``tp`` local devices — the serving
    engine's tensor-parallel mesh (serving/engine.py ``mesh=``)."""
    n = len(jax.devices())
    if tp > n:
        raise ValueError(f"--tp {tp} needs {tp} devices, have {n} "
                         "(set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N for CPU fakes)")
    return _mesh((tp,), ("model",))
