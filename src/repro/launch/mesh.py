"""Mesh construction — production, test, and data-parallel meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.

Every mesh in the repo is built through ``build_mesh`` (one validation +
device-slicing path): the production training mesh, the unit-test meshes,
and the movement-plane ``("data",)`` meshes `repro.runtime.mesh_plane`
shards the simulation lattice and the replicated store over. Device
counts are explicit everywhere — nothing hard-fails below 256 devices
anymore; the historical 16x16 / 2x16x16 pod shapes are just the defaults
`make_production_mesh` picks when no count is given.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def build_mesh(shape, axes, devices=None):
    """`jax.make_mesh` over the first prod(shape) of `devices` (defaults
    to `jax.devices()`), with a readable error when the host has fewer —
    the ONE validation path every mesh constructor below routes through.
    Works on real hardware and under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` alike.

    Every axis is `AxisType.Auto`: the installed JAX otherwise makes
    explicit-sharding meshes, under which plain slices and reshapes of
    sharded arrays (the lattice unpad, the MoE token regroup) raise
    `ShardingTypeError`."""
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} and axes {axes} disagree")
    n = math.prod(shape)
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {tuple(shape)}, have "
            f"{len(devices)}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before "
            "importing jax")
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def _factor_2d(n: int):
    """(data, model) factorization of an arbitrary device count: the
    model axis is the largest divisor of n that is <= sqrt(n) (capped at
    16, the historical pod column), data gets the rest. n=256 -> (16, 16),
    n=8 -> (4, 2), a prime n -> (n, 1)."""
    model = 1
    for d in range(1, min(int(math.isqrt(n)), 16) + 1):
        if n % d == 0:
            model = d
    return n // model, model


def make_production_mesh(*, multi_pod: bool = False, num_devices: int = None,
                         shape=None, axes=None):
    """Production training mesh.

    With no arguments: the historical fixed shapes — 16x16 (256
    chips/pod) single-pod or 2x16x16 (512 chips) multi-pod. An explicit
    `num_devices` builds a right-sized ("data", "model") mesh instead
    (factored via `_factor_2d`; `multi_pod` peels a leading pod=2 axis
    off an even count), and an explicit `shape`/`axes` pair overrides
    everything — so dry-runs and tests no longer need exactly 256/512
    forced host devices.
    """
    if shape is None:
        if num_devices is None:
            shape = (2, 16, 16) if multi_pod else (16, 16)
        elif multi_pod:
            if num_devices % 2:
                raise ValueError(
                    f"multi_pod needs an even device count, got "
                    f"{num_devices}")
            shape = (2,) + _factor_2d(num_devices // 2)
        else:
            shape = _factor_2d(num_devices)
    if axes is None:
        axes = (("pod", "data", "model") if len(shape) == 3
                else ("data", "model"))
    return build_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for unit tests (requires forced host devices) — same
    validation path as production (`build_mesh`)."""
    return build_mesh(shape, axes)


def make_data_mesh(num_devices: int = None, axis: str = "data"):
    """1-axis data-parallel mesh over the first `num_devices` devices
    (default: all) — what the movement-plane sharding
    (`repro.runtime.mesh_plane`) runs on. A 1-device data mesh is always
    constructible and falls back bit-identically to the vmap paths."""
    n = len(jax.devices()) if num_devices is None else num_devices
    return build_mesh((n,), (axis,))
