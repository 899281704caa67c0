"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — required for the dry-run's forced 512-device
host platform to initialize first.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    # Auto (GSPMD) axes: the sharding rules place arrays and the compiler
    # propagates.  jax.make_mesh defaults to Explicit axes, under which the
    # embedding gather refuses the rules' specs (duplicate "data").
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh.

    single-pod: 256 chips as (data=16, model=16).
    multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the "pod"
    axis is pure data parallelism whose gradient all-reduce crosses the
    slower inter-pod links (DCN/optical), which is why it is a distinct axis:
    cross-pod collectives are the ones gradient compression targets.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(*, seq_shards: int = 1, n_devices: int | None = None):
    """The first ``n_devices`` devices (default: all), as a ("data",
    "model") mesh (CPU smoke).

    ``seq_shards > 1`` sizes the "model" axis to carry sequence-sharded
    GOOM scans (the ``scan_seq`` logical axis maps there): the mesh becomes
    (n // seq_shards, seq_shards).  The device count must divide evenly.
    """
    devices = jax.devices()[:n_devices]
    n = len(devices)
    if seq_shards > 1:
        if n % seq_shards:
            raise ValueError(
                f"--seq-shards {seq_shards} does not divide {n} devices")
        return _make_mesh((n // seq_shards, seq_shards), ("data", "model"),
                          devices)
    return _make_mesh((n, 1), ("data", "model"), devices)
