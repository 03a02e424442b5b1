"""Production meshes. Defined as functions so importing this module never
touches jax device state (device count is locked at first jax init)."""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mini_mesh(*, multi_pod: bool = False):
    """Small mesh for CI-scale dry-run tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the launchers shard with
    ``with_sharding_constraint`` (launch/shardctx.py), which refers only to
    Auto axes, and ``make_mesh`` now defaults to Explicit ones."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_expert_mesh(num_shards: int):
    """1-D ``("expert",)`` mesh over the first ``num_shards`` devices —
    the expert-parallel dispatch mesh (launch/sharding.expert_dispatch_ffn).
    Built with an explicit device slice (not ``jax.make_mesh``) so a
    4-shard mesh works on an 8-device host: CI forces host devices via
    ``--xla_force_host_platform_device_count`` the way launch/dryrun.py
    does, and shard counts need not divide the device count."""
    devices = jax.devices()
    assert num_shards <= len(devices), (
        f"expert mesh needs {num_shards} devices, have {len(devices)}")
    return jax.sharding.Mesh(np.array(devices[:num_shards]), ("expert",))


def data_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# TPU v5e hardware constants (roofline targets)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # B/s per chip
ICI_BW = 50e9                   # B/s per link
