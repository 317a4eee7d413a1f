"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — device count is locked at first jax init, and the
dry-run needs to set XLA_FLAGS before that happens.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def parse_mesh_spec(spec: str):
    """``"PODxDATA"`` (the driver's ``--mesh`` flag) -> ``(pods, data)``.

    ``pods`` is the number of pods (inter-pod links are where
    ``--compress`` pays), ``data`` the data-parallel devices per pod
    (the "pod_size" of the byte accounting)."""
    parts = spec.lower().replace("×", "x").split("x")
    try:
        pods, data = (int(p) for p in parts)
    except ValueError:
        raise ValueError(
            f"--mesh expects PODSxDATA (e.g. 2x4), got {spec!r}") from None
    if pods < 1 or data < 1:
        raise ValueError(f"--mesh axes must be >= 1, got {spec!r}")
    return pods, data


def make_train_mesh(pods: int = 1, data: int = 1):
    """('pod', 'data') mesh for the data-parallel streaming train loop.

    Validated on simulated devices: run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
    initializes) to get N host devices."""
    n = pods * data
    if n > len(jax.devices()):
        raise ValueError(
            f"mesh {pods}x{data} needs {n} devices but only "
            f"{len(jax.devices())} are visible; simulate with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return jax.make_mesh(
        (pods, data), ("pod", "data"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over locally-visible devices (tests / examples)."""
    return jax.make_mesh(
        (n_data, n_model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
