"""Layer-wise training of SSFN: centralized and decentralized (Algorithm 1).

Both trainers share the same progressive-growth loop (paper §II-B):
  for l = 0..L:
    1. compute layer features Y_l (per worker in the decentralized case)
    2. solve the convex readout problem (6) for O_l
         - centralized: ADMM with M=1 (as in the SSFN paper [1])
         - decentralized: consensus ADMM (eq. 11) over M workers
    3. form W_{l+1} = [V_Q O_l ; R_{l+1}] and continue

The *only* difference between the two is where the data lives and how the
consensus mean in the Z-update is computed — which is the paper's central
claim of centralized equivalence.

Execution: every layer runs through the compile-once layer engine
(``core.engine``) — propagation, Gram/Cholesky and the K-iteration ADMM
scan fuse into one cached SPMD program per layer, traces accumulate on
device and are fetched once after the loop, and the self-size-estimation
stop costs exactly one scalar fetch per layer.
"""
from __future__ import annotations

import os
import time
import warnings
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import profiling
from repro.core import admm as admm_lib
from repro.core import engine as engine_lib
from repro.core import ssfn as ssfn_lib
from repro.core import topology as topology_lib
from repro.core.backend import ConsensusBackend, SimulatedBackend
from repro.core.policy import ConsensusPolicy

Array = jax.Array

_CKPT_PREFIX = "dssfn_layer_"


def checkpoint_path(directory: str, layer_next: int) -> str:
    """Per-layer checkpoint file: ``dssfn_layer_003.npz`` holds the full
    training state with layers 0..2 complete."""
    return os.path.join(directory, f"{_CKPT_PREFIX}{layer_next:03d}.npz")


def latest_checkpoint(directory: str) -> str | None:
    """Newest (deepest) COMPLETE checkpoint in ``directory``, or None.

    A kill mid-save can leave a truncated npz (pre-atomic-write
    checkpoints) or an npz without its metadata sidecar; those are
    skipped with a warning and the scan falls back to the next-deepest
    checkpoint instead of handing resume a corrupt file.
    """
    from repro.checkpoint.store import is_valid_checkpoint

    if not os.path.isdir(directory):
        return None
    names = [
        f for f in os.listdir(directory)
        if f.startswith(_CKPT_PREFIX) and f.endswith(".npz")
    ]
    for name in sorted(names, reverse=True):
        path = os.path.join(directory, name)
        if is_valid_checkpoint(path):
            return path
        warnings.warn(
            f"skipping partial/corrupt checkpoint {path!r} "
            "(interrupted save?)",
            RuntimeWarning,
            stacklevel=2,
        )
    return None


def _key_data(key: jax.Array) -> jax.Array:
    """PRNG key -> raw uint32 array (typed keys unwrap; raw pass through).

    The raw form round-trips through npz and is itself a valid legacy
    key, so resume can feed it straight back to ``init_random_matrices``.
    """
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


class _LoopState(NamedTuple):
    """Where the layer loop stands: what a checkpoint holds, and what
    resume and divergence rollback restore.

    ``layer`` is the next layer to solve and ``y_workers`` the features
    it reads.  ``o``, ``traces`` and ``jitter`` hold one entry per
    completed layer (the traces stay on the device until the loop ends).
    ``r`` is the whole draw of random matrices, future layers included:
    divergence rollback perturbs the key for future layers, so the key
    alone no longer determines them.  It is None only in a checkpoint
    written before the random matrices were stored.
    """

    layer: int
    key: jax.Array
    o: tuple = ()
    traces: tuple = ()
    jitter: tuple = ()
    comm: int = 0
    prev_cost: float | None = None
    y_workers: Array | None = None
    r: tuple | None = None


def _layer_weight(state: _LoopState, q: int) -> Array | None:
    """W_l = [V_Q O_{l-1} ; R_l] for the layer ``state`` stands before;
    None at layer 0, whose solve reads the input features directly."""
    if state.layer == 0:
        return None
    with profiling.span(profiling.BUILD_WEIGHT):
        return ssfn_lib.build_weight(state.o[-1], state.r[state.layer - 1], q)


def _save_checkpoint(
    directory: str, state: _LoopState, *,
    step: engine_lib.LayerStepResult, active_mask: np.ndarray,
) -> str:
    """Elastic-resume state after ``state.layer`` completed layers: the
    loop state, the last solve's worker primals/duals and the
    membership mask."""
    from repro.checkpoint.store import save_pytree

    tree = {
        "layer_next": np.int64(state.layer),
        "key": _key_data(state.key),
        "y_workers": np.asarray(jax.device_get(state.y_workers)),
        "o": {str(i): o for i, o in enumerate(state.o)},
        "o_workers": step.o_workers,
        "lam": step.lam,
        "comm": np.int64(state.comm),
        "prev_cost": np.float64(
            np.nan if state.prev_cost is None else state.prev_cost
        ),
        "membership": np.asarray(active_mask, np.float64),
        "r": {str(i): r for i, r in enumerate(state.r)},
    }
    if state.jitter:
        tree["jit"] = np.stack(
            [np.asarray(j, np.int32) for j in state.jitter]
        )
    if state.traces:
        fetched = [jax.tree.map(np.asarray, tr) for tr in state.traces]
        tree["tr"] = {
            "obj": np.stack([t.objective for t in fetched]),
            "primal": np.stack([t.primal_residual for t in fetched]),
            "dual": np.stack([t.dual_residual for t in fetched]),
            "cerr": np.stack([t.consensus_error for t in fetched]),
        }
    path = checkpoint_path(directory, state.layer)
    save_pytree(path, tree)
    return path


def _load_checkpoint(path: str) -> _LoopState:
    """Flat checkpoint -> the loop state it holds (inverse of
    ``_save_checkpoint``); the features are not yet on a backend."""
    from repro.checkpoint.store import load_pytree_flat

    flat = load_pytree_flat(path)
    layer = int(flat["layer_next"])
    prev_cost = float(flat["prev_cost"])
    traces = ()
    if "tr/obj" in flat:
        traces = tuple(
            admm_lib.ADMMTrace(
                flat["tr/obj"][i], flat["tr/primal"][i],
                flat["tr/dual"][i], flat["tr/cerr"][i],
            )
            for i in range(flat["tr/obj"].shape[0])
        )
    # Checkpoints written before the random matrices and the jitter
    # history were stored have neither key: r stays None for the
    # restorer to fill, and the jitter history restarts empty.
    r = None
    if "r/0" in flat:
        r = []
        while f"r/{len(r)}" in flat:
            r.append(jnp.asarray(flat[f"r/{len(r)}"]))
        r = tuple(r)
    jitter = ()
    if "jit" in flat:
        jitter = tuple(np.asarray(j) for j in flat["jit"])
    return _LoopState(
        layer=layer,
        key=jnp.asarray(flat["key"]),
        o=tuple(jnp.asarray(flat[f"o/{i}"]) for i in range(layer)),
        traces=traces,
        jitter=jitter,
        comm=int(flat["comm"]),
        prev_cost=None if np.isnan(prev_cost) else prev_cost,
        y_workers=jnp.asarray(flat["y_workers"]),
        r=r,
    )


def _active_mask(policy: ConsensusPolicy, num_workers: int) -> np.ndarray:
    """The membership mask a checkpoint records: the ``Masked`` topology's
    active set, or all-ones for full-membership policies."""
    topo = getattr(policy, "topology", None)
    if isinstance(topo, topology_lib.Masked):
        return topo.membership.mask()
    return np.ones(num_workers, np.float64)


@dataclass
class LayerwiseLog:
    #: Objective after each layer solve; EMPTY when trace collection is
    #: disabled (``trace_every=0`` — the collective-free hot path).
    layer_costs: list[float]
    admm_objective: np.ndarray          # (L+1, K/N) trace (paper Fig. 3)
    admm_primal: np.ndarray
    admm_dual: np.ndarray
    consensus_error: np.ndarray
    wall_time_s: float
    comm_scalars: int                   # total scalars exchanged (eq. 15)
    #: (layers, M) guarded-Cholesky jitter level per layer solve (int32;
    #: all-zero on a numerically healthy run).
    jitter_levels: np.ndarray = field(
        default_factory=lambda: np.zeros((0, 0), np.int32)
    )
    #: Divergence-guard rollbacks taken during this run (0 = clean).
    rollbacks: int = 0


def _mu_for_layer(cfg: ssfn_lib.SSFNConfig, layer: int) -> float:
    return cfg.mu0 if layer == 0 else cfg.mul


def _step_diverged(
    step: engine_lib.LayerStepResult,
    prev_cost: float | None,
    blowup: float = 1e3,
) -> bool:
    """The divergence monitor: a non-finite consensus iterate, a
    non-finite objective, or an objective that blew up past
    ``blowup`` x the previous layer's cost.  One scalar fetch."""
    if not bool(jnp.all(jnp.isfinite(step.o_star))):
        return True
    if step.trace is not None:
        obj = float(step.trace.objective[-1])
        if not np.isfinite(obj):
            return True
        if prev_cost is not None and obj > blowup * max(prev_cost, 1e-12):
            return True
    return False


def train_decentralized_ssfn(
    x_workers: Array,
    t_workers: Array,
    cfg: ssfn_lib.SSFNConfig,
    key: jax.Array,
    *,
    backend: ConsensusBackend | None = None,
    policy: ConsensusPolicy | None = None,
    size_estimation_tol: float | None = None,
    trace_every: int = 1,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    stop_after_layer: int | None = None,
    guard_divergence: bool = False,
    max_rollbacks: int = 2,
) -> tuple[ssfn_lib.SSFNParams, LayerwiseLog]:
    """Train dSSFN on M workers.

    x_workers: (M, P, J_m) column-stacked inputs per worker (disjoint shards).
    t_workers: (M, Q, J_m) one-hot targets per worker.
    backend: where the M workers execute (``SimulatedBackend`` or
        ``MeshBackend``); None = simulated.  In the mesh case the Y_m/T_m
        shards stay device-local through the whole layer-wise loop —
        feature propagation, the Gram factorization and the layer solves
        all run as ONE fused SPMD program per layer under the backend's
        executable cache.
    policy: how the workers reach consensus — a ``repro.core.policy``
        strategy object (``ExactMean``, ``Gossip`` over any
        ``repro.core.topology.Topology``, ``QuantizedGossip``,
        ``LossyGossip``, ``StaleMixing``); defaults to the backend's
        policy.  Drives the eq.-15 communication accounting via its
        ``comm_scalars``.
    size_estimation_tol: the SELF-SIZE-estimating behaviour (paper §I: "a
        decentralized estimation of the size of SSFN is possible"): stop
        growing layers once the relative cost improvement drops below this
        tolerance.  The decision uses the consensus objective every worker
        already tracks, so all workers stop at the same depth with NO extra
        communication.  None = fixed size (cfg.num_layers, paper §II).
    trace_every: convergence-trace stride (``engine.fused_layer_step``):
        1 = per-iteration ADMM traces (default), 0 = the collective-free
        hot path — the lowered layer programs contain ONLY the policy's
        own exchanges, and the log carries empty traces/layer_costs —
        N > 1 = every N-th iteration.  ``trace_every=0`` is incompatible
        with ``size_estimation_tol`` (the stop rule reads the consensus
        objective).
    checkpoint_dir: directory for elastic-resume checkpoints; None (the
        default) never touches disk.  State is saved after every
        ``checkpoint_every``-th completed layer (and always at a
        ``stop_after_layer`` stop): the layer features, per-layer
        readouts, the last solve's primals/duals, the RNG key, the
        membership mask and the accumulated traces — everything a fresh
        process needs to continue bit-exactly.
    resume: restore the latest ``checkpoint_dir`` checkpoint and continue
        from its next layer (a no-op when the directory has none).  The
        resumed run reproduces the uninterrupted run's iterates exactly:
        layer solves are deterministic functions of the restored features
        and the re-derived random matrices.
    stop_after_layer: complete this layer index, checkpoint, and return
        the partial model (the crash half of a kill/resume drill; also a
        cheap way to train the first layers now and the rest later).
    guard_divergence: the numerical self-healing monitor — after every
        layer solve, check for a non-finite consensus iterate, a
        non-finite objective, or an objective blow-up past 1000x the
        previous layer's cost.  On divergence the run rolls back to the
        last complete checkpoint (or the loop entry state when there is
        none), perturbs the RNG key so every not-yet-consumed random
        matrix re-draws (the consumed ones are restored from the
        checkpoint verbatim — completed layers keep their exact
        weights), and retries — instead of crashing or silently
        returning NaNs.  Costs one extra scalar fetch per layer.
    max_rollbacks: divergence-rollback budget; the run raises
        RuntimeError once a diverging layer has exhausted it.
    """
    if max_rollbacks < 0:
        raise ValueError(f"max_rollbacks must be >= 0, got {max_rollbacks}")
    if resume and checkpoint_dir is None:
        raise ValueError("resume=True needs a checkpoint_dir to restore from")
    if checkpoint_dir is not None and checkpoint_every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
    if trace_every == 0 and size_estimation_tol is not None:
        raise ValueError(
            "size_estimation_tol reads the per-layer consensus objective; "
            "it cannot be combined with trace_every=0 (no traces)"
        )

    q = cfg.num_classes
    t0 = time.perf_counter()

    engine_backend = backend or SimulatedBackend(x_workers.shape[0])
    policy = policy if policy is not None else engine_backend.policy
    num_workers = engine_backend.num_workers
    t_workers = engine_backend.shard_workers(t_workers)
    rollbacks = 0

    def restore(default: _LoopState | None, r: tuple | None = None):
        """Resume and rollback both restart here: the latest complete
        checkpoint's state with its features on the backend, or
        ``default`` when there is none.  A checkpoint without random
        matrices takes ``r`` (the live draw, on rollback) or re-derives
        them from its own key."""
        path = (
            latest_checkpoint(checkpoint_dir)
            if checkpoint_dir is not None else None
        )
        if path is None:
            return default
        saved = _load_checkpoint(path)
        if saved.r is None:
            if r is None:
                r = tuple(ssfn_lib.init_random_matrices(saved.key, cfg))
            saved = saved._replace(r=r)
        return saved._replace(
            y_workers=engine_backend.shard_workers(saved.y_workers)
        )

    # The divergence guard's restart point before the first checkpoint
    # exists (references only — none of these buffers is ever donated:
    # donation starts at layer 2 with engine-materialized carries).
    entry = restore(None) if resume else None
    if entry is None:
        entry = _LoopState(
            layer=0,
            key=key,
            y_workers=engine_backend.shard_workers(x_workers),   # y_0 = x
            r=tuple(ssfn_lib.init_random_matrices(key, cfg)),
        )

    state = entry
    while state.layer <= cfg.num_layers:
        layer = state.layer
        with profiling.span(profiling.LAYER, layer=layer):
            w = _layer_weight(state, q)
            with profiling.span(profiling.DISPATCH):
                step = engine_lib.fused_layer_step(
                    engine_backend,
                    state.y_workers,
                    t_workers,
                    w,
                    mu=_mu_for_layer(cfg, layer),
                    eps_radius=cfg.eps_radius,
                    num_iters=cfg.admm_iters,
                    use_kernels=cfg.use_kernels,
                    policy=policy,
                    trace_every=trace_every,
                    # From layer 2 on, the stacked Y is a fresh relu(W@Y) buffer
                    # the engine owns — safe to hand to XLA.  Layers 0 and 1 must
                    # NOT donate: layer 0's input is the caller's x_workers, and
                    # layer 0's pass-through output may alias it.
                    donate_y=layer > 1,
                )

            diverged = False
            if guard_divergence:
                with profiling.span(profiling.HOST_SYNC):
                    diverged = _step_diverged(step, state.prev_cost)
            if diverged:
                if rollbacks >= max_rollbacks:
                    raise RuntimeError(
                        f"layer {layer} diverged and the rollback budget "
                        f"(max_rollbacks={max_rollbacks}) is spent"
                    )
                rollbacks += 1
                state = restore(entry, r=state.r)
                warnings.warn(
                    f"layer solve diverged; rolling back to layer {state.layer} "
                    f"with a perturbed key (rollback {rollbacks}/"
                    f"{max_rollbacks})",
                    RuntimeWarning,
                    stacklevel=2,
                )
                # Perturb the key and re-draw every random matrix the
                # restart point has not consumed.  r[layer-1] only feeds the
                # NEXT propagation, so it is still free to change;
                # r[0..layer-2] shaped the restored features and must stay
                # verbatim.
                key = jax.random.fold_in(state.key, 7 + rollbacks)
                fresh = ssfn_lib.init_random_matrices(key, cfg)
                first_free = max(state.layer - 1, 0)
                state = state._replace(
                    key=key,
                    r=state.r[:first_free] + tuple(fresh[first_free:]),
                )
                continue

            jitter = state.jitter
            if step.jitter is not None:
                with profiling.span(profiling.HOST_SYNC):
                    jitter += (np.asarray(jax.device_get(step.jitter)),)
            state = state._replace(
                layer=layer + 1,
                o=state.o + (step.o_star,),
                traces=state.traces + (
                    (step.trace,) if step.trace is not None else ()
                ),
                jitter=jitter,
                # Communication accounting, eq. 15: Q * n_{l-1} scalars per
                # exchange, B exchanges per consensus, K communicating
                # consensus rounds per layer — the policy itself knows its
                # exchange count and how many of the K iterations actually
                # hit the wire.
                comm=state.comm + policy.comm_scalars(
                    scalars=q * step.y_workers.shape[1],
                    num_consensus=cfg.admm_iters,
                    num_workers=num_workers,
                ),
                y_workers=step.y_workers,
            )

            stopping = stop_after_layer is not None and layer >= stop_after_layer
            if checkpoint_dir is not None and (
                stopping or state.layer % checkpoint_every == 0
            ):
                with profiling.span(profiling.HOST_SYNC):
                    _save_checkpoint(
                        checkpoint_dir, state, step=step,
                        active_mask=_active_mask(policy, num_workers),
                    )
            if stopping:
                break

            # Self-size estimation: every worker sees the same consensus
            # objective, so this stop decision is itself consensual.  It
            # costs one scalar fetch per layer; without it (and without the
            # guard) the jitter fetch above is the loop's only host sync.
            if size_estimation_tol is not None:
                with profiling.span(profiling.HOST_SYNC):
                    cur = float(step.trace.objective[-1])
                prev_cost = state.prev_cost
                if (
                    prev_cost is not None
                    and prev_cost - cur < size_estimation_tol * max(prev_cost, 1e-12)
                ):
                    break
                state = state._replace(prev_cost=cur)
            elif guard_divergence and step.trace is not None:
                # Track the layer cost so the guard's blow-up check has a
                # reference even without size estimation.
                with profiling.span(profiling.HOST_SYNC):
                    state = state._replace(
                        prev_cost=float(step.trace.objective[-1])
                    )

    # One bulk fetch of every per-layer trace after the loop.  The
    # collective-free hot path (trace_every=0) has none: the log carries
    # empty (L+1, 0) trace arrays and no layer costs.
    traces = [jax.tree.map(np.asarray, tr) for tr in state.traces]
    layer_costs = [float(tr.objective[-1]) for tr in traces]

    def stacked(field: str) -> np.ndarray:
        if not traces:
            return np.zeros((len(state.o), 0), np.float32)
        return np.stack([getattr(tr, field) for tr in traces])

    # Early size-estimation stop leaves fewer readouts than random matrices.
    params = ssfn_lib.SSFNParams(
        o=state.o, r=tuple(state.r[: len(state.o) - 1])
    )
    log = LayerwiseLog(
        layer_costs=layer_costs,
        admm_objective=stacked("objective"),
        admm_primal=stacked("primal_residual"),
        admm_dual=stacked("dual_residual"),
        consensus_error=stacked("consensus_error"),
        wall_time_s=time.perf_counter() - t0,
        comm_scalars=state.comm,
        jitter_levels=(
            np.stack(state.jitter)
            if state.jitter else np.zeros((0, 0), np.int32)
        ),
        rollbacks=rollbacks,
    )
    return params, log


def train_centralized_ssfn(
    x: Array,
    t: Array,
    cfg: ssfn_lib.SSFNConfig,
    key: jax.Array,
) -> tuple[ssfn_lib.SSFNParams, LayerwiseLog]:
    """Centralized SSFN = the same loop with all data on one worker (M=1)."""
    return train_decentralized_ssfn(x[None], t[None], cfg, key)


def accuracy(params: ssfn_lib.SSFNParams, x: Array, labels: Array, q: int) -> float:
    pred = ssfn_lib.classify(params, x, q)
    return float(jnp.mean((pred == labels).astype(jnp.float32)))
