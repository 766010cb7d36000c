"""Sharding rules: logical-axis annotations resolved against the active mesh.

We use GSPMD (pjit + sharding constraints).  Logical activation/param axes:

  batch  -> ("pod", "data") or ("data",)   (data parallel)
  fsdp   -> same axes as batch             (FSDP weight sharding)
  tensor -> "model"                        (tensor / expert parallel)

``set_rules``/``current_rules`` make the mesh context available to model
code without threading it through every call; when no rules are active
(unit tests, single CPU) all constraints are no-ops.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class AxisRules:
    mesh: Mesh | None = None
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str | None = "model"
    # Disable FSDP (weights replicated over data axes) if False.
    fsdp: bool = True
    # Axes carrying the FSDP/weight-row sharding; defaults to data_axes.
    # Setting fsdp_axes with data_axes=() gives the weight-stationary 2-D
    # TP decode layout: batch replicated, weights fully 2-D sharded, GSPMD
    # propagates partial-sum activations instead of gathering weights.
    fsdp_axes: tuple[str, ...] | None = None
    # Shard the sequence dim of activations over data axes (for batch=1
    # long-context decode this is the only way to use the data axis).
    sequence_sharding: bool = False
    # Mesh axis carrying the dSSFN ADMM worker dimension (the leading
    # (M, ...) axis of per-worker Y_m/T_m stacks); None outside
    # decentralized-training launches.
    worker_axis: str | None = None

    @property
    def weight_axes(self) -> tuple[str, ...]:
        return self.fsdp_axes if self.fsdp_axes is not None else self.data_axes


def shard_map_compat(fn, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with replication checking disabled."""
    kwargs: dict = dict(
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    return jax.shard_map(fn, **kwargs)


_state = threading.local()


def current_rules() -> AxisRules:
    return getattr(_state, "rules", AxisRules())


@contextlib.contextmanager
def use_rules(rules: AxisRules):
    prev = current_rules()
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def _resolve(logical: str | None, rules: AxisRules):
    if logical is None:
        return None
    if logical == "batch":
        if not rules.data_axes:
            return None
        return rules.data_axes if len(rules.data_axes) > 1 else rules.data_axes[0]
    if logical == "fsdp":
        if not rules.fsdp or not rules.weight_axes:
            return None
        w = rules.weight_axes
        return w if len(w) > 1 else w[0]
    if logical == "tensor":
        return rules.model_axis
    if logical == "workers":
        return rules.worker_axis
    raise ValueError(f"unknown logical axis {logical!r}")


def spec(*logical_axes: str | None) -> P:
    rules = current_rules()
    return P(*[_resolve(a, rules) for a in logical_axes])


def shard(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """with_sharding_constraint against the active mesh; no-op without one.

    Axes whose dimension does not divide evenly by the mesh-axis size are
    dropped (replicated) — GSPMD's padded shardings for e.g. 8 KV heads on
    a 16-way model axis trigger involuntary rematerialization and huge
    collectives; explicit replication is strictly better.
    """
    rules = current_rules()
    if rules.mesh is None:
        return x
    sizes = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape))
    resolved = []
    for dim, logical in zip(x.shape, logical_axes):
        names = _resolve(logical, rules)
        if names is not None:
            ns = names if isinstance(names, tuple) else (names,)
            total = 1
            for n in ns:
                total *= sizes[n]
            if dim % total != 0:
                names = None
        resolved.append(names)
    s = NamedSharding(rules.mesh, P(*resolved))
    return jax.lax.with_sharding_constraint(x, s)


def named_sharding(*logical_axes: str | None) -> NamedSharding:
    rules = current_rules()
    if rules.mesh is None:
        raise ValueError("no active mesh")
    return NamedSharding(rules.mesh, spec(*logical_axes))


# Name-based weight-sharding rules (trailing dims; leading stacked-layer
# dims are replicated).  "F" = FSDP over the data axes, "T" = tensor
# parallel over the model axis.  Shared with launch.specs for the jit
# in_shardings; used directly by shard_params_by_name to RE-ASSERT the
# sharding of per-layer parameter slices inside scan bodies — without
# this, GSPMD hoists the FSDP all-gather of the whole stacked (L, ...)
# array out of the loop (measured: 1.1 TB/device peak on mistral-123B).
PARAM_RULES: dict[str, tuple[str | None, ...]] = {
    "embed": ("T", "F"),
    "head": ("F", "T"),
    "patch_proj": ("F", None),
    "wq": ("F", "T"),
    "wk": ("F", "T"),
    "wv": ("F", "T"),
    "wo": ("T", "F"),
    "wg": ("F", "T"),
    "wu": ("F", "T"),
    "wd": ("T", "F"),
    "router": ("F", None),
    "in_x": ("F", "T"),
    "in_z": ("F", "T"),
    "in_b": ("F", None),
    "in_c": ("F", None),
    "in_dt": ("F", None),
    "conv_w": (None, "T"),
    "out": ("T", "F"),
    "wx": ("F", "T"),
    "wi": ("F", None),
    "wf": ("F", None),
}

_TAG_TO_LOGICAL = {"F": "fsdp", "T": "tensor", None: None}


def shard_params_by_name(tree):
    """Apply PARAM_RULES sharding constraints to a (sliced) param pytree.

    No-op without an active mesh.  Call at the top of a scan-over-layers
    body on the per-layer param slice.
    """
    rules = current_rules()
    if rules.mesh is None:
        return tree

    def leaf_name(path) -> str:
        for p in reversed(path):
            key = getattr(p, "key", None)
            if isinstance(key, str):
                return key
        return ""

    def constrain(path, leaf):
        rule = PARAM_RULES.get(leaf_name(path))
        if rule is None or leaf.ndim < len(rule):
            return leaf
        lead = leaf.ndim - len(rule)
        logical = [None] * lead + [_TAG_TO_LOGICAL[t] for t in rule]
        return shard(leaf, *logical)

    return jax.tree_util.tree_map_with_path(constrain, tree)
