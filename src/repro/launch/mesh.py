"""Production device meshes.

Target hardware: TPU v5e pods — 256 chips/pod as a (data=16, model=16) mesh;
the multi-pod configuration stacks a leading "pod" axis (2 pods = 512 chips).
Functions, not module constants: importing this module must never touch jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis compiler-managed (``Auto``): the
    SP-NGD schedules shard with explicit specs and ``shard_map`` regions,
    not with the ``Explicit`` axis types ``jax.make_mesh`` defaults to."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    >= data*model in the test process)."""
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes the global batch is sharded over."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))
