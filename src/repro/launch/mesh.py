"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharded steps place arrays with
    ``with_sharding_constraint`` hints that GSPMD propagates, which the
    Explicit axes ``make_mesh`` defaults to would turn into assertions."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2×16×16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests/examples)."""
    return _auto_mesh((data, model), ("data", "model"))


def parse_mesh(spec: str):
    """``--mesh DATAxMODEL`` (e.g. ``2x4``) → a local (data, model) mesh.

    Device count must satisfy data*model; on a CPU host force fake devices
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before*
    the first jax call (see docs/serving.md).
    """
    try:
        data, model = (int(p) for p in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"--mesh expects DATAxMODEL (e.g. 2x4), got {spec!r}") from None
    have = jax.device_count()
    if data * model > have:
        raise ValueError(
            f"--mesh {spec} needs {data * model} devices but only {have} "
            f"are visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={data * model}")
    return make_local_mesh(data=data, model=model)


def dp_axes(mesh) -> tuple:
    """The data-parallel axes: ('pod','data') multi-pod, ('data',) single."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def degraded_mesh(mesh, *, drop_data: int = 1):
    """Elastic-rescale helper: rebuild the mesh with fewer data rows
    (simulates losing a slice and re-lowering on the survivors)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sizes["data"] = sizes["data"] - drop_data
    n_needed = 1
    for v in sizes.values():
        n_needed *= v
    devs = mesh.devices.reshape(-1)[:n_needed]
    return jax.sharding.Mesh(
        devs.reshape(tuple(sizes.values())), tuple(sizes.keys()))
