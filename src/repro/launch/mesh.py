"""Production mesh construction (assignment-mandated shapes).

Defined as functions so importing this module never touches jax device
state; only ``launch/dryrun.py`` forces the 512-device host platform.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the model code shards through GSPMD rules (with_sharding_
    # constraint), which explicit axes — jax.make_mesh's default — reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1, axis_names=("data", "model")):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = jax.device_count()
    if n % model_parallel:
        raise ValueError(
            f"{n} devices do not split into model_parallel={model_parallel}"
        )
    return _auto_mesh((n // model_parallel, model_parallel), axis_names)
