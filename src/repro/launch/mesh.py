"""Production mesh builders.

make_production_mesh is a FUNCTION (not a module-level constant) so importing
this module never touches jax device state.

Every mesh here has Auto axes: the model code shards by GSPMD propagation
(`with_sharding_constraint` through `partition.constrain`), which is an
assertion on Explicit axes, the default of `jax.make_mesh`.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """`jax.make_mesh` over this host's devices, with Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_slot_mesh(devices, shape, axes=("data", "model")):
    """Mesh over an explicit device subset (a FOS slot)."""
    import numpy as np
    devs = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(devs, axes)


def make_host_mesh():
    """Whatever devices exist on this host, as a 1-D ("data",) mesh."""
    n = jax.device_count()
    return make_mesh((n,), ("data",))
