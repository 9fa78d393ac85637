"""Production mesh construction.

A function (NOT a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init to obtain placeholder devices.

Single pod: 16 x 16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod: 2 x 16 x 16 = 512 chips, axes (pod, data, model) — the `pod`
axis carries only data parallelism (gradient all-reduce / batch sharding),
matching DCN-connected pods.
"""
from __future__ import annotations

import jax


def auto_mesh(shape, axes, devices=None):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings from
    the placement rules (``jax.make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, devices=None):
    """A (data, model) mesh over ``devices`` (default: every device of
    this host)."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    mp = model_parallel if n % model_parallel == 0 else 1
    return auto_mesh((n // mp, mp), ("data", "model"), devices=devices)


def mesh_axes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def replica_meshes(n_replicas: int, model_parallel: int = 1) -> list:
    """One (data, model) mesh per serving replica.

    Partitions this host's devices into ``n_replicas`` disjoint
    contiguous slices so each cluster replica (e.g. a disaggregated
    prefill or decode engine) owns its own devices.  A single-device
    host (the CPU test environment) has nothing to split: every replica
    shares its one mesh.  A multi-device host that cannot be split
    evenly is an error, not a silent pile-up on shared devices.
    """
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    devices = jax.devices()
    n = len(devices)
    if n == 1:
        return [make_host_mesh(model_parallel)] * n_replicas
    if n % n_replicas != 0:
        raise ValueError(
            f"{n} devices cannot be split evenly into {n_replicas} replicas"
        )
    per = n // n_replicas
    return [
        make_host_mesh(model_parallel, devices[i * per:(i + 1) * per])
        for i in range(n_replicas)
    ]
