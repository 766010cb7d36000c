"""Production meshes.

Single pod: v5e-256 as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16) — the "pod"
axis is an extra data-parallel dim over DCN/ICI (batch shards over
("pod", "data")).

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    dp = max(1, n // model_parallel)
    return make_mesh_compat((dp, model_parallel), ("data", "model"))


def make_worker_mesh(num_workers: int | None = None):
    """1-D mesh for dSSFN ADMM: one paper "worker" per device slot.

    Used by ``core.backend.MeshBackend``.  On CPU, fake devices must be
    requested via ``XLA_FLAGS=--xla_force_host_platform_device_count=M``
    BEFORE jax initializes (the ``launch.train_dssfn`` CLI does this under
    ``JAX_PLATFORMS=cpu``); on TPU the slots are real chips and the
    ring-gossip mode maps each degree-k hop onto an ICI collective_permute.
    """
    devices = jax.devices()
    n = len(devices)
    if num_workers is None:
        num_workers = n
    if num_workers > n:
        platform = devices[0].platform
        hint = (
            "; on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{num_workers} before jax initializes (the launchers do so "
            "under JAX_PLATFORMS=cpu)"
            if platform == "cpu"
            else "; the mesh backend runs one worker per device — use "
            "--backend simulated to vmap more workers onto fewer devices"
        )
        raise ValueError(
            f"requested {num_workers} workers but only {n} {platform} "
            f"device(s) ({devices[0].device_kind}) are visible{hint}"
        )
    return make_mesh_compat((num_workers,), ("workers",))


def data_axes_for(mesh) -> tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data"))


HARDWARE = {
    # TPU v5e per chip.
    "peak_flops_bf16": 197e12,      # FLOP/s
    "hbm_bandwidth": 819e9,         # B/s
    "ici_link_bandwidth": 50e9,     # B/s per link
}
