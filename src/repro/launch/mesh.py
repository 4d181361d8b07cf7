"""Production meshes.  Defined as FUNCTIONS so importing never touches jax
device state (jax locks the device count on first backend init)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the repo shards through
    ``NamedSharding`` plus ``shard_map`` and lets the partitioner place
    everything else, which Explicit axes (the jax default) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16 data × 16 model).  Multi-pod: 2 × 256."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_model: Optional[int] = None,
                   n_data: Optional[int] = None):
    """A (data, model) mesh sized from the devices actually present —
    the mesh you can exercise on a laptop/CI host via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the
    production mesh hard-assumes 256 chips and cannot).

    With both degrees given they must multiply to ``jax.device_count()``;
    with one given the other is inferred; with neither, every device
    goes on the model axis (serving TP, the axis this repo shards
    today).
    """
    n = jax.device_count()
    for name, deg in (("n_model", n_model), ("n_data", n_data)):
        if deg is not None and deg < 1:
            raise ValueError(f"mesh degrees must be >= 1; got {name}={deg}")
    if n_model is None and n_data is None:
        n_model, n_data = n, 1
    elif n_model is None:
        if n % n_data:
            raise ValueError(
                f"n_data={n_data} does not divide device_count={n}")
        n_model = n // n_data
    elif n_data is None:
        if n % n_model:
            raise ValueError(
                f"n_model={n_model} does not divide device_count={n}")
        n_data = n // n_model
    if n_model * n_data != n:
        raise ValueError(
            f"mesh {n_data}x{n_model} (data x model) needs "
            f"{n_data * n_model} devices but jax.device_count()={n}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count "
            "accordingly BEFORE importing jax")
    return make_mesh((n_data, n_model), ("data", "model"))


def make_role_meshes(n_prefill: int, n_decode: int):
    """Disjoint (data, model) meshes for disaggregated serving roles:
    the first ``n_prefill`` devices become the prefill role's mesh, the
    next ``n_decode`` the decode role's.  Every device serves tensor-
    parallel on the model axis (the axis this repo shards today); the
    page-migration channel (repro.disagg.migrate) carries KV across the
    two device sets.  Each role needs at least one device and the split
    must fit the devices present."""
    for name, deg in (("n_prefill", n_prefill), ("n_decode", n_decode)):
        if deg < 1:
            raise ValueError(
                f"disaggregated roles need >= 1 device each; "
                f"got {name}={deg}")
    n = jax.device_count()
    if n_prefill + n_decode > n:
        raise ValueError(
            f"role split {n_prefill}+{n_decode} needs "
            f"{n_prefill + n_decode} devices but jax.device_count()={n}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count "
            "accordingly BEFORE importing jax")
    devs = jax.devices()
    import numpy as np
    pre = np.asarray(devs[:n_prefill]).reshape(1, n_prefill)
    dec = np.asarray(devs[n_prefill:n_prefill + n_decode]) \
        .reshape(1, n_decode)
    axes = ("data", "model")
    return jax.sharding.Mesh(pre, axes), jax.sharding.Mesh(dec, axes)


def make_pp_mesh():
    """Optional pipeline-parallel mesh (4 stages × 8 data × 8 model)."""
    return make_mesh((4, 8, 8), ("pipe", "data", "model"))


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> Tuple[str, ...]:
    """The batch-sharding axes for this mesh (pod folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    d = mesh_shape_dict(mesh)
    out = 1
    for a in dp_axes(mesh):
        out *= d[a]
    return out
