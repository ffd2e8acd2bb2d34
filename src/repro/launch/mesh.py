"""Production mesh builders (TPU v5e pods; CPU placeholder devices in CI).

A function, not a module-level constant, so importing this module never
touches jax device state (jax locks the device count on first init — the
dry-run sets XLA_FLAGS *before* any jax import).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, devices=None) -> Mesh:
    """The one mesh builder: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which host-side
    indexing of a sharded array and ``with_sharding_constraint`` raise.
    The solver shards by hand (``shard_map`` and ``NamedSharding``), so it
    wants the compiler to propagate shardings. ``devices`` defaults to
    the first ``prod(shape)`` devices.
    """
    shape, axes = tuple(shape), tuple(axes)
    if devices is None:
        devices = jax.devices()[:math.prod(shape)]
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod.

    Uses the first prod(shape) devices so the single-pod mesh also works
    in a 512-placeholder-device dry-run process.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"(launch/dryrun.py does this)")
    return make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh():
    """Whatever this host has (1 CPU device in CI) on a (data, model) mesh."""
    return make_mesh((1, len(jax.devices())), ("data", "model"))


# TPU v5e hardware constants for the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW_PER_LINK = 50e9          # B/s per link (~ v5e 2D torus neighbour)
