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

Execution: the backend path runs through the compile-once layer engine
(``core.engine``) — propagation, Gram/Cholesky and the K-iteration ADMM
scan fuse into one cached SPMD program per layer, traces accumulate on
device and are fetched once after the loop, and the self-size-estimation
stop costs exactly one scalar fetch per layer.  The legacy dense-H
``consensus_fn`` simulation keeps the original per-call loop.
"""
from __future__ import annotations

import os
import time
import warnings
from dataclasses import dataclass, field
from typing import Callable

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


def _save_checkpoint(
    directory: str, *, layer_next: int, key, y_workers, o_list,
    step: engine_lib.LayerStepResult, dev_traces, comm: int,
    prev_cost: float | None, active_mask: np.ndarray,
    r_list=None, jitter_list=None,
) -> str:
    """Elastic-resume state after ``layer_next`` completed layers: layer
    features, per-layer readouts, the last solve's worker primals/duals,
    the RNG key, the random matrices ACTUALLY used so far (divergence
    rollback perturbs the key for future layers, so the key alone no
    longer determines them), membership, and the device traces
    accumulated so far."""
    from repro.checkpoint.store import save_pytree

    state = {
        "layer_next": np.int64(layer_next),
        "key": _key_data(key),
        "y_workers": y_workers,
        "o": {str(i): o for i, o in enumerate(o_list)},
        "o_workers": step.o_workers,
        "lam": step.lam,
        "comm": np.int64(comm),
        "prev_cost": np.float64(np.nan if prev_cost is None else prev_cost),
        "membership": np.asarray(active_mask, np.float64),
    }
    if r_list is not None:
        state["r"] = {str(i): r for i, r in enumerate(r_list)}
    if jitter_list:
        state["jit"] = np.stack(
            [np.asarray(j, np.int32) for j in jitter_list]
        )
    if dev_traces:
        fetched = [jax.tree.map(np.asarray, tr) for tr in dev_traces]
        state["tr"] = {
            "obj": np.stack([t.objective for t in fetched]),
            "primal": np.stack([t.primal_residual for t in fetched]),
            "dual": np.stack([t.dual_residual for t in fetched]),
            "cerr": np.stack([t.consensus_error for t in fetched]),
        }
    path = checkpoint_path(directory, layer_next)
    save_pytree(path, state)
    return path


def _load_checkpoint(path: str) -> dict:
    """Flat checkpoint -> the resume state ``train_decentralized_ssfn``
    restores from (inverse of ``_save_checkpoint``)."""
    from repro.checkpoint.store import load_pytree_flat

    flat = load_pytree_flat(path)
    layer_next = int(flat["layer_next"])
    prev_cost = float(flat["prev_cost"])
    traces = []
    if "tr/obj" in flat:
        for i in range(flat["tr/obj"].shape[0]):
            traces.append(admm_lib.ADMMTrace(
                flat["tr/obj"][i], flat["tr/primal"][i],
                flat["tr/dual"][i], flat["tr/cerr"][i],
            ))
    r_list = None
    if "r/0" in flat:
        r_list = []
        while f"r/{len(r_list)}" in flat:
            r_list.append(jnp.asarray(flat[f"r/{len(r_list)}"]))
    jitter_list = None
    if "jit" in flat:
        jitter_list = [np.asarray(j) for j in flat["jit"]]
    return {
        "layer_next": layer_next,
        "key": jnp.asarray(flat["key"]),
        "y_workers": jnp.asarray(flat["y_workers"]),
        "o_list": [
            jnp.asarray(flat[f"o/{i}"]) for i in range(layer_next)
        ],
        "comm": int(flat["comm"]),
        "prev_cost": None if np.isnan(prev_cost) else prev_cost,
        "membership": flat["membership"],
        "traces": traces,
        # Pre-PR-7 checkpoints have neither key: r falls back to key
        # derivation and the jitter history restarts empty.
        "r_list": r_list,
        "jitter_list": jitter_list,
    }


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
    #: all-zero on a numerically healthy run).  Empty on the legacy
    #: consensus_fn path.
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
    consensus_fn: Callable[[Array], Array] | None = None,
    backend: ConsensusBackend | None = None,
    policy: ConsensusPolicy | None = None,
    gossip_rounds: int = 1,
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
        M-aware ``exchanges_for``.
    consensus_fn: legacy dense-H consensus primitive for the Z-update
        (mutually exclusive with ``backend``/``policy``).
    gossip_rounds: B, used only for the communication-load accounting when a
        gossip consensus_fn is supplied (B=1 for exact all-reduce; gossip
        backends account with their own ``num_rounds``).
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
    if consensus_fn is not None and (backend is not None or policy is not None):
        raise ValueError("pass either consensus_fn or backend/policy, not both")
    if consensus_fn is not None and (
        checkpoint_dir is not None or resume or stop_after_layer is not None
        or guard_divergence
    ):
        raise ValueError(
            "checkpoint/resume and the divergence guard run through the "
            "backend engine path; the legacy consensus_fn simulation does "
            "not support them"
        )
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
    if consensus_fn is not None:
        if trace_every != 1:
            raise ValueError(
                "trace_every is a backend-path knob; the legacy "
                "consensus_fn simulation always traces every iteration"
            )
        return _train_consensus_fn_path(
            x_workers, t_workers, cfg, key,
            consensus_fn=consensus_fn,
            gossip_rounds=gossip_rounds,
            size_estimation_tol=size_estimation_tol,
        )

    q = cfg.num_classes
    t0 = time.perf_counter()

    engine_backend = backend or SimulatedBackend(x_workers.shape[0])
    # eq.-15 accounting: the policy declares its own scalar count (its
    # M-aware exchange schedule AND its communication interval — an
    # ``AsyncGossip(interval=N)`` only touches the wire every N-th ADMM
    # iteration); the implicit simulated-exact default (no backend, no
    # policy) keeps the legacy ``gossip_rounds`` convention.
    explicit = backend is not None or policy is not None
    policy = policy if policy is not None else engine_backend.policy
    num_workers = engine_backend.num_workers
    t_workers = engine_backend.shard_workers(t_workers)

    o_list: list[Array] = []
    w_next: Array | None = None
    # Device-resident (K,) traces per layer; fetched once after the loop.
    dev_traces: list[admm_lib.ADMMTrace] = []
    jitter_list: list[np.ndarray] = []
    comm = 0
    prev_cost: float | None = None
    layer_start = 0
    rollbacks = 0

    restored = None
    if resume:
        ckpt = latest_checkpoint(checkpoint_dir)
        if ckpt is not None:
            restored = _load_checkpoint(ckpt)
    if restored is not None:
        layer_start = restored["layer_next"]
        key = restored["key"]
        o_list = list(restored["o_list"])
        dev_traces = list(restored["traces"])
        jitter_list = list(restored["jitter_list"] or [])
        comm = restored["comm"]
        prev_cost = restored["prev_cost"]
        y_workers = engine_backend.shard_workers(restored["y_workers"])
        r_list = (
            list(restored["r_list"])
            if restored["r_list"] is not None
            else list(ssfn_lib.init_random_matrices(key, cfg))
        )
        if layer_start <= cfg.num_layers:
            w_next = ssfn_lib.build_weight(
                o_list[-1], r_list[layer_start - 1], q
            )
    else:
        r_list = list(ssfn_lib.init_random_matrices(key, cfg))
        y_workers = engine_backend.shard_workers(x_workers)   # y_0 = x

    # The divergence guard's restart point before the first checkpoint
    # exists (references only — none of these buffers is ever donated:
    # donation starts at layer 2 with engine-materialized carries).
    entry_state = (
        layer_start, key, list(o_list), list(dev_traces), list(jitter_list),
        comm, prev_cost, y_workers, w_next, list(r_list),
    )

    layer = layer_start
    while layer <= cfg.num_layers:
        with profiling.span(profiling.LAYER, layer=layer):
            with profiling.span(profiling.DISPATCH):
                step = engine_lib.fused_layer_step(
                    engine_backend,
                    y_workers,
                    t_workers,
                    w_next,
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
                    diverged = _step_diverged(step, prev_cost)
            if diverged:
                if rollbacks >= max_rollbacks:
                    raise RuntimeError(
                        f"layer {layer} diverged and the rollback budget "
                        f"(max_rollbacks={max_rollbacks}) is spent"
                    )
                rollbacks += 1
                ckpt = (
                    latest_checkpoint(checkpoint_dir)
                    if checkpoint_dir is not None else None
                )
                if ckpt is not None:
                    restored = _load_checkpoint(ckpt)
                    layer = restored["layer_next"]
                    key = restored["key"]
                    o_list = list(restored["o_list"])
                    dev_traces = list(restored["traces"])
                    jitter_list = list(restored["jitter_list"] or [])
                    comm = restored["comm"]
                    prev_cost = restored["prev_cost"]
                    y_workers = engine_backend.shard_workers(
                        restored["y_workers"]
                    )
                    if restored["r_list"] is not None:
                        r_list = list(restored["r_list"])
                else:
                    (layer, key, o_list, dev_traces, jitter_list, comm,
                     prev_cost, y_workers, w_next, r_list) = entry_state
                    o_list = list(o_list)
                    dev_traces = list(dev_traces)
                    jitter_list = list(jitter_list)
                    r_list = list(r_list)
                warnings.warn(
                    f"layer solve diverged; rolling back to layer {layer} "
                    f"with a perturbed key (rollback {rollbacks}/"
                    f"{max_rollbacks})",
                    RuntimeWarning,
                    stacklevel=2,
                )
                # Perturb the key and re-draw every random matrix the
                # restart point has not consumed.  r[layer-1] only feeds the
                # NEXT propagation (it rebuilds w_next below), so it is
                # still free to change; r[0..layer-2] shaped the restored
                # features and must stay verbatim.
                key = jax.random.fold_in(key, 7 + rollbacks)
                fresh = ssfn_lib.init_random_matrices(key, cfg)
                first_free = max(layer - 1, 0)
                r_list[first_free:] = list(fresh[first_free:])
                if layer == 0:
                    w_next = None
                elif layer <= cfg.num_layers:
                    with profiling.span(profiling.BUILD_WEIGHT):
                        w_next = ssfn_lib.build_weight(
                            o_list[-1], r_list[layer - 1], q
                        )
                continue

            y_workers = step.y_workers
            o_list.append(step.o_star)
            if step.trace is not None:
                dev_traces.append(step.trace)
            if step.jitter is not None:
                with profiling.span(profiling.HOST_SYNC):
                    jitter_list.append(np.asarray(jax.device_get(step.jitter)))
            # Communication accounting, eq. 15: Q * n_{l-1} scalars per
            # exchange, B exchanges per consensus, K communicating consensus
            # rounds per layer — the policy itself knows its exchange count
            # and how many of the K iterations actually hit the wire.
            if explicit:
                comm += policy.comm_scalars(
                    scalars=q * y_workers.shape[1],
                    num_consensus=cfg.admm_iters,
                    num_workers=num_workers,
                )
            else:
                comm += q * y_workers.shape[1] * gossip_rounds * cfg.admm_iters

            stopping = stop_after_layer is not None and layer >= stop_after_layer
            if checkpoint_dir is not None and (
                stopping or (layer + 1) % checkpoint_every == 0
            ):
                with profiling.span(profiling.HOST_SYNC):
                    _save_checkpoint(
                        checkpoint_dir, layer_next=layer + 1, key=key,
                        y_workers=np.asarray(jax.device_get(y_workers)),
                        o_list=o_list, step=step, dev_traces=dev_traces,
                        comm=comm, prev_cost=prev_cost,
                        active_mask=_active_mask(policy, num_workers),
                        r_list=r_list, jitter_list=jitter_list,
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
                if (
                    prev_cost is not None
                    and prev_cost - cur < size_estimation_tol * max(prev_cost, 1e-12)
                ):
                    break
                prev_cost = cur
            elif guard_divergence and step.trace is not None:
                # Track the layer cost so the guard's blow-up check has a
                # reference even without size estimation.
                with profiling.span(profiling.HOST_SYNC):
                    prev_cost = float(step.trace.objective[-1])

            if layer < cfg.num_layers:
                with profiling.span(profiling.BUILD_WEIGHT):
                    w_next = ssfn_lib.build_weight(step.o_star, r_list[layer], q)
            layer += 1

    # One bulk fetch of every per-layer trace after the loop.  The
    # collective-free hot path (trace_every=0) has none: the log carries
    # empty (L+1, 0) trace arrays and no layer costs.
    traces = [jax.tree.map(np.asarray, tr) for tr in dev_traces]
    layer_costs = [float(tr.objective[-1]) for tr in traces]

    def stacked(field: str) -> np.ndarray:
        if not traces:
            return np.zeros((len(o_list), 0), np.float32)
        return np.stack([getattr(tr, field) for tr in traces])

    # Early size-estimation stop leaves fewer readouts than random matrices.
    params = ssfn_lib.SSFNParams(
        o=tuple(o_list), r=tuple(r_list[: len(o_list) - 1])
    )
    log = LayerwiseLog(
        layer_costs=layer_costs,
        admm_objective=stacked("objective"),
        admm_primal=stacked("primal_residual"),
        admm_dual=stacked("dual_residual"),
        consensus_error=stacked("consensus_error"),
        wall_time_s=time.perf_counter() - t0,
        comm_scalars=comm,
        jitter_levels=(
            np.stack(jitter_list)
            if jitter_list else np.zeros((0, 0), np.int32)
        ),
        rollbacks=rollbacks,
    )
    return params, log


def _train_consensus_fn_path(
    x_workers: Array,
    t_workers: Array,
    cfg: ssfn_lib.SSFNConfig,
    key: jax.Array,
    *,
    consensus_fn: Callable[[Array], Array],
    gossip_rounds: int,
    size_estimation_tol: float | None,
) -> tuple[ssfn_lib.SSFNParams, LayerwiseLog]:
    """Legacy batched dense-H simulation (arbitrary mixing matrix H)."""
    q = cfg.num_classes
    t0 = time.perf_counter()
    r_list = ssfn_lib.init_random_matrices(key, cfg)

    o_list: list[Array] = []
    y_workers = x_workers                      # y_0 = x
    layer_costs: list[float] = []
    traces = {"obj": [], "primal": [], "dual": [], "cerr": []}
    comm = 0

    for layer in range(cfg.num_layers + 1):
        res = admm_lib.admm_ridge_consensus(
            y_workers,
            t_workers,
            mu=_mu_for_layer(cfg, layer),
            eps_radius=cfg.eps_radius,
            num_iters=cfg.admm_iters,
            consensus_fn=consensus_fn,
        )
        o_l = res.o_star
        o_list.append(o_l)
        layer_costs.append(float(res.trace.objective[-1]))
        traces["obj"].append(np.asarray(res.trace.objective))
        traces["primal"].append(np.asarray(res.trace.primal_residual))
        traces["dual"].append(np.asarray(res.trace.dual_residual))
        traces["cerr"].append(np.asarray(res.trace.consensus_error))
        comm += q * y_workers.shape[1] * gossip_rounds * cfg.admm_iters

        if (
            size_estimation_tol is not None
            and len(layer_costs) >= 2
            and layer_costs[-2] - layer_costs[-1]
            < size_estimation_tol * max(layer_costs[-2], 1e-12)
        ):
            break

        if layer < cfg.num_layers:
            w_next = ssfn_lib.build_weight(o_l, r_list[layer], q)
            y_workers = jax.vmap(lambda ym: jax.nn.relu(w_next @ ym))(y_workers)

    params = ssfn_lib.SSFNParams(o=tuple(o_list), r=r_list[: len(o_list) - 1])
    log = LayerwiseLog(
        layer_costs=layer_costs,
        admm_objective=np.stack(traces["obj"]),
        admm_primal=np.stack(traces["primal"]),
        admm_dual=np.stack(traces["dual"]),
        consensus_error=np.stack(traces["cerr"]),
        wall_time_s=time.perf_counter() - t0,
        comm_scalars=comm,
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
