"""Consensus-ADMM solver for the paper's layer-wise convex problem.

Decentralized problem (paper eq. 9/10):

    min_{O_m, Z}  sum_m ||T_m - O_m Y_m||_F^2
    s.t.          ||Z||_F <= eps_radius,   O_m = Z  for all m

ADMM iterations (paper eq. 11):

    O_m^{k+1} = (T_m Y_m^T + (1/mu)(Z^k - Lam_m^k)) (Y_m Y_m^T + (1/mu) I)^{-1}
    Z^{k+1}   = P_eps( (1/M) sum_m (O_m^{k+1} + Lam_m^k) )       <- consensus
    Lam^{k+1} = Lam_m^k + O_m^{k+1} - Z^{k+1}

Notes on fidelity:
- The Gram factor (Y_m Y_m^T + I/mu) is constant over k, so we Cholesky-
  factorize it ONCE per layer (the Matlab reference does the same via a
  cached inverse).  This is the dominant per-layer compute and is backed
  by the ``gram`` Pallas kernel on TPU (repro.kernels.gram.ops).
- The paper defines P_eps with radius eps on the *Frobenius norm* even
  though the constraint is written ||Z||_F^2 <= eps; we follow the
  operational definition (radius), matching the released Matlab code and
  the choice eps = 2Q.
- The only cross-worker communication per iteration is the consensus mean
  of (O_m + Lam_m): Q x n floats, matching the paper's communication-load
  accounting Q * n_{l-1} * B * K (eq. 15).
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import profiling
from repro.core import consensus as consensus_lib
from repro.core.policy import ConsensusPolicy

if TYPE_CHECKING:  # avoid a circular import at runtime (backend imports policy)
    from repro.core.backend import ConsensusBackend

Array = jax.Array


def project_frobenius(z: Array, radius: float) -> Array:
    """P_eps: scale Z onto the Frobenius ball of given radius (paper eq. after 11)."""
    norm = jnp.linalg.norm(z)
    scale = jnp.where(norm > radius, radius / jnp.maximum(norm, 1e-30), 1.0)
    return z * scale


class ADMMState(NamedTuple):
    o: Array      # (M, Q, n) per-worker primal variables
    z: Array      # (Q, n) consensus variable (replicated)
    lam: Array    # (M, Q, n) scaled duals


class ADMMTrace(NamedTuple):
    objective: Array        # (K,) global objective sum_m ||T_m - Z Y_m||^2
    primal_residual: Array  # (K,) ||O_m - Z|| aggregated
    dual_residual: Array    # (K,) ||Z^{k+1} - Z^k||
    consensus_error: Array  # (K,) max deviation of the consensus estimate


class ADMMResult(NamedTuple):
    o_star: Array   # (Q, n) final consensus solution Z^K
    o_workers: Array
    lam: Array
    trace: "ADMMTrace | None"   # None when trace_every=0 (hot path)
    #: Per-worker guarded-Cholesky jitter level (int32; 0 = factored
    #: clean).  None on paths predating the guard (legacy consensus_fn).
    jitter: "Array | None" = None


def guarded_cholesky(
    g: Array, *, max_tries: int = 6, base_jitter: float = 1e-8
):
    """Cholesky with escalating diagonal jitter: the self-healing
    factorization for ill-conditioned / rank-deficient Gram matrices.

    ``jnp.linalg.cholesky`` signals a non-PD input by returning NaN
    (never raising), so recovery is a ``lax.while_loop`` on factor
    health: try G as-is, then G + eps_k I with
    ``eps_k = scale * base_jitter * 10**k`` (``scale`` = mean
    |diagonal|, so the jitter is relative to the matrix's magnitude),
    escalating until the factor is finite or ``max_tries`` retries are
    spent.  Traces cleanly under vmap and shard_map — it is data-
    dependent control flow, not Python control flow.

    Returns ``(chol, jitter_level)``: level 0 means the plain factor
    was healthy; level k >= 1 means the factor used ``eps_{k-1}``.  A
    still-non-finite factor after ``max_tries`` is returned as-is —
    the layerwise divergence guard owns that failure.
    """
    n = g.shape[-1]

    def cond(state):
        k, chol = state
        return (k < max_tries) & ~jnp.all(jnp.isfinite(chol))

    with profiling.scope(profiling.CHOLESKY):
        eye = jnp.eye(n, dtype=g.dtype)
        scale = jnp.maximum(
            jnp.mean(jnp.abs(jnp.diagonal(g))), jnp.asarray(1.0, g.dtype)
        )

        def body(state):
            k, _ = state
            eps = scale * base_jitter * jnp.asarray(10.0, g.dtype) ** k.astype(g.dtype)
            return k + 1, jnp.linalg.cholesky(g + eps * eye)

        k, chol = jax.lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32), jnp.linalg.cholesky(g))
        )
    return chol, k


def _worker_stats(y_workers: Array, t_workers: Array, mu: float, use_kernels: bool = False):
    """Per-worker A_m = T_m Y_m^T and guarded Cholesky of
    G_m = Y_m Y_m^T + I/mu (plus the per-worker jitter level).

    use_kernels=True routes the Gram product through the Pallas ``gram``
    kernel (TPU hot-path; interpret mode elsewhere).
    """
    n, j = y_workers.shape[1], y_workers.shape[2]
    if use_kernels and n % 128 == 0 and j % 128 == 0:
        from repro.kernels.gram import gram as gram_kernel

        gram = jax.vmap(lambda ym: gram_kernel(ym, mu=mu))(y_workers)
        gram = gram.astype(y_workers.dtype)
    else:
        gram = jnp.einsum("mij,mkj->mik", y_workers, y_workers)
        gram = gram + (1.0 / mu) * jnp.eye(n, dtype=y_workers.dtype)
    chol, jitter = jax.vmap(guarded_cholesky)(gram)
    a = jnp.einsum("mqj,mnj->mqn", t_workers, y_workers)
    return a, chol, jitter


def _o_update(a: Array, chol: Array, z: Array, lam: Array, mu: float) -> Array:
    """O_m = (A_m + (Z - Lam_m)/mu) G_m^{-1} via the cached Cholesky factor."""
    rhs = a + (z[None] - lam) / mu          # (M, Q, n)

    def solve_one(l_factor, r):
        # Solve X G = R  ->  G^T X^T = R^T ; G symmetric -> G X^T = R^T.
        return jax.scipy.linalg.cho_solve((l_factor, True), r.T).T

    return jax.vmap(solve_one)(chol, rhs)


def admm_ridge_consensus(
    y_workers: Array,
    t_workers: Array,
    *,
    mu: float,
    eps_radius: float,
    num_iters: int,
    consensus_fn: Callable[[Array], Array] | None = None,
    backend: "ConsensusBackend | None" = None,
    policy: ConsensusPolicy | None = None,
    z0: Array | None = None,
    use_kernels: bool = False,
    trace_every: int = 1,
) -> ADMMResult:
    """Run K iterations of consensus ADMM (paper Algorithm 1, lines 5-10).

    y_workers: (M, n, J_m) per-worker feature matrices (equal shard sizes,
        matching the paper's uniform division of the training set).
    t_workers: (M, Q, J_m) per-worker targets.
    backend: a ``ConsensusBackend`` deciding where the M workers execute —
        ``SimulatedBackend`` (vmap worker axis, single device) or
        ``MeshBackend`` (shard_map, one worker per mesh slot).  Defaults
        to ``SimulatedBackend(M)``.
    policy: the ``ConsensusPolicy`` deciding *how* they reach consensus
        (``ExactMean``; ``Gossip`` over any ``repro.core.topology``
        graph, with ``RingGossip`` as the paper's circular alias;
        ``QuantizedGossip``, ``LossyGossip``, ``StaleMixing``); defaults
        to the backend's own policy.  Policy state (quantizer keys,
        staleness buffers) is threaded through the ADMM scan carry.
    consensus_fn: legacy batched (M, Q, n) -> (M, Q, n) averaging
        primitive for simulations with an *arbitrary* dense mixing matrix
        H (``make_consensus_fn('gossip', h=...)``).  Mutually exclusive
        with ``backend``/``policy``; ring topologies should prefer a
        gossip-policy backend, which expresses the same mixing as peer
        exchanges.
    trace_every: convergence-trace stride (``worker_admm_iterations``):
        1 = per-iteration traces (default), 0 = no traces and NO
        trace collectives in the lowered program (``result.trace`` is
        None), N > 1 = every N-th iteration.  Backend path only.
    """
    if consensus_fn is not None and (backend is not None or policy is not None):
        raise ValueError("pass either consensus_fn or backend/policy, not both")
    if consensus_fn is None:
        from repro.core.backend import SimulatedBackend

        if backend is None:
            backend = SimulatedBackend(y_workers.shape[0])
        return _admm_backend_path(
            y_workers,
            t_workers,
            backend=backend,
            policy=policy,
            mu=mu,
            eps_radius=eps_radius,
            num_iters=num_iters,
            z0=z0,
            use_kernels=use_kernels,
            trace_every=trace_every,
        )
    if trace_every != 1:
        raise ValueError(
            "trace_every is a backend-path knob; the legacy consensus_fn "
            "simulation always traces every iteration"
        )
    m, n = y_workers.shape[0], y_workers.shape[1]
    q = t_workers.shape[1]
    dtype = y_workers.dtype

    a, chol, jitter = _worker_stats(y_workers, t_workers, mu, use_kernels=use_kernels)

    z_init = jnp.zeros((q, n), dtype) if z0 is None else z0.astype(dtype)
    state = ADMMState(
        o=jnp.zeros((m, q, n), dtype),
        z=z_init,
        lam=jnp.zeros((m, q, n), dtype),
    )

    def step(state: ADMMState, _):
        o_new = _o_update(a, chol, state.z, state.lam, mu)
        avg_in = o_new + state.lam                      # (M, Q, n)
        avg = consensus_fn(avg_in)                      # still (M, Q, n)
        consensus_err = consensus_lib.gossip_error(avg)
        # Every worker applies P_eps to its own consensus estimate; under
        # exact consensus these coincide.  We track worker 0's Z as "the" Z
        # and keep per-worker Z for the gossip-mode dual update.
        z_workers = jax.vmap(lambda v: project_frobenius(v, eps_radius))(avg)
        z_new = z_workers[0]
        lam_new = state.lam + o_new - z_workers
        obj = jnp.sum(
            jax.vmap(lambda t_m, y_m: jnp.sum((t_m - z_new @ y_m) ** 2))(
                t_workers, y_workers
            )
        )
        primal = jnp.linalg.norm(o_new - z_workers)
        dual = jnp.linalg.norm(z_new - state.z)
        new_state = ADMMState(o=o_new, z=z_new, lam=lam_new)
        return new_state, (obj, primal, dual, consensus_err)

    state, (objs, primals, duals, cerrs) = jax.lax.scan(
        step, state, None, length=num_iters
    )
    trace = ADMMTrace(objs, primals, duals, cerrs)
    return ADMMResult(
        o_star=state.z, o_workers=state.o, lam=state.lam, trace=trace,
        jitter=jitter,
    )


def _worker_stats_local(y_m: Array, t_m: Array, mu: float, use_kernels: bool):
    """Worker-local A_m = T_m Y_m^T and guarded Cholesky of
    G_m = Y_m Y_m^T + I/mu (plus this worker's jitter level).

    The local view of ``_worker_stats`` for SPMD execution: same math, no
    worker axis, same Pallas ``gram`` kernel routing on aligned shapes.
    """
    n, j = y_m.shape
    with profiling.scope(profiling.GRAM):
        if use_kernels and n % 128 == 0 and j % 128 == 0:
            from repro.kernels.gram import gram as gram_kernel

            gram = gram_kernel(y_m, mu=mu).astype(y_m.dtype)
        else:
            gram = y_m @ y_m.T + (1.0 / mu) * jnp.eye(n, dtype=y_m.dtype)
    chol, jitter = guarded_cholesky(gram)
    with profiling.scope(profiling.GRAM):
        a = t_m @ y_m.T
    return a, chol, jitter


def validate_trace_every(trace_every: int, num_iters: int) -> int:
    """Validate the trace-collection stride (shared by every entry point).

    ``1`` traces every ADMM iteration (the default), ``0`` disables
    trace collection entirely, ``N > 1`` traces every N-th iteration and
    requires ``num_iters % N == 0`` (traces are emitted at iterations
    N, 2N, ..., K).
    """
    trace_every = int(trace_every)
    if trace_every < 0:
        raise ValueError(f"trace_every must be >= 0, got {trace_every}")
    if trace_every > 1 and num_iters % trace_every != 0:
        raise ValueError(
            f"trace_every={trace_every} must divide num_iters={num_iters}"
        )
    return trace_every


def worker_admm_iterations(
    backend: "ConsensusBackend",
    a: Array,
    chol: Array,
    y_m: Array,
    t_m: Array,
    z_init: Array,
    *,
    mu: float,
    eps_radius: float,
    num_iters: int,
    policy: ConsensusPolicy | None = None,
    trace_every: int = 1,
):
    """K eq.-11 iterations as a worker-local scan over the cached factor.

    The shared inner loop of ``_admm_backend_path`` and the fused layer
    engine (``core.engine``): all cross-worker communication goes through
    ``policy.mix`` (default: the backend's policy) on the backend's
    collective context, and the policy's per-round state — quantizer PRNG
    keys, staleness buffers — rides in the scan carry.  Each worker
    evaluates the objective against its OWN consensus estimate Z_m (they
    coincide under exact consensus).

    ``trace_every`` gates the convergence traces: every trace scalar
    costs collectives (``psum`` objective, ``psum`` primal, and — for
    inexact policies — an ``exact_mean``+``pmax`` consensus-error probe),
    so ``trace_every=0`` drops them all and the lowered program contains
    ONLY the policy's own exchanges (the production hot path; the final
    iterate is bit-identical since no trace value feeds the carry).
    ``N > 1`` traces every N-th iteration (K/N-long traces).

    When the policy declares a ``communication_interval`` of N > 1
    (``AsyncGossip(interval=N)``), the scan is restructured into K/N
    chunks of N-1 purely LOCAL iterations (the z-update projects the
    worker's own ``o + lam``; no mixing, no policy-state advance)
    followed by one communicating iteration — the skipping is
    structural, so the lowered program carries 1/N of the collectives
    with no runtime branching.  Requires ``num_iters % N == 0`` and
    ``trace_every`` in {0, 1}.

    Returns ``(o, z, lam), traces`` where ``traces`` is the
    ``(objs, primals, duals, cerrs)`` tuple, or ``None`` when
    ``trace_every=0``.
    """
    policy = policy if policy is not None else backend.policy
    trace_every = validate_trace_every(trace_every, num_iters)
    interval = policy.communication_interval
    if interval > 1:
        if num_iters % interval != 0:
            raise ValueError(
                f"communication interval {interval} must divide "
                f"num_iters={num_iters}"
            )
        if trace_every > 1:
            raise ValueError(
                "trace_every > 1 does not compose with a communication "
                "interval; use trace_every of 0 or 1"
            )
    ctx = backend.ctx()
    q, n = a.shape
    dtype = a.dtype

    def solve(z, lam):
        """O_m = (A_m + (Z - Lam_m)/mu) G_m^{-1} via the cached factor."""
        with profiling.scope(profiling.SOLVE):
            rhs = a + (z - lam) / mu
            return jax.scipy.linalg.cho_solve((chol, True), rhs.T).T

    def update(o, lam, avg):
        """The projection onto the eps-ball and the dual step."""
        with profiling.scope(profiling.UPDATE):
            z_new = project_frobenius(avg, eps_radius)
            return z_new, lam + o - z_new

    def iterate(carry):
        """One eq.-11 iteration; also returns what tracing needs."""
        (_, z, lam), pstate = carry
        o = solve(z, lam)
        with profiling.scope(profiling.MIX):
            avg, pstate = policy.mix(o + lam, pstate, ctx)
        z_new, lam_new = update(o, lam, avg)
        return ((o, z_new, lam_new), pstate), (avg, z)

    def local_iterate(carry):
        """A skipped round: the same eq.-11 update against the worker's
        OWN estimate (avg = o + lam, no wire, no policy-state advance)."""
        (_, z, lam), pstate = carry
        o = solve(z, lam)
        with profiling.scope(profiling.UPDATE):
            avg = o + lam
        z_new, lam_new = update(o, lam, avg)
        return ((o, z_new, lam_new), pstate), (avg, z)

    def trace(carry, avg, z_prev):
        """The collective trio the hot path omits (plus the local dual)."""
        ((o, z_new, _), _) = carry
        if policy.is_exact:
            # avg IS the pmean: the deviation is zero by construction,
            # and computing it would cost two extra collectives per
            # iteration on the mesh hot path.
            cerr = jnp.zeros((), avg.dtype)
        else:
            cerr = backend.pmax(jnp.max(jnp.abs(avg - backend.exact_mean(avg))))
        obj = backend.psum(jnp.sum((t_m - z_new @ y_m) ** 2))
        primal = jnp.sqrt(backend.psum(jnp.sum((o - z_new) ** 2)))
        dual = jnp.linalg.norm(z_new - z_prev)
        return (obj, primal, dual, cerr)

    def step_untraced(carry, _):
        carry, _ = iterate(carry)
        return carry, None

    def step_traced(carry, _):
        carry, (avg, z_prev) = iterate(carry)
        return carry, trace(carry, avg, z_prev)

    def step_untraced_local(carry, _):
        carry, _ = local_iterate(carry)
        return carry, None

    def step_traced_local(carry, _):
        carry, (avg, z_prev) = local_iterate(carry)
        return carry, trace(carry, avg, z_prev)

    with profiling.scope(profiling.ADMM):
        zeros = jnp.zeros((q, n), dtype)
        init = ((zeros, z_init, zeros), policy.init_state(zeros, ctx))
        if interval > 1:
            # Communication-interval chunks: N-1 local rounds, one on the
            # wire.  The whole fault/membership story rides inside the
            # communicating iterate's policy.mix — still one executable.
            if trace_every == 0:
                def comm_chunk(carry, _):
                    carry, _ = jax.lax.scan(
                        step_untraced_local, carry, None, length=interval - 1
                    )
                    carry, _ = iterate(carry)
                    return carry, None

                (state, _), _ = jax.lax.scan(
                    comm_chunk, init, None, length=num_iters // interval
                )
                return state, None

            def comm_chunk(carry, _):
                carry, local_traces = jax.lax.scan(
                    step_traced_local, carry, None, length=interval - 1
                )
                carry, comm_trace = step_traced(carry, None)
                chunk_traces = jax.tree.map(
                    lambda ls, c: jnp.concatenate([ls, c[None]]),
                    local_traces, comm_trace,
                )
                return carry, chunk_traces

            (state, _), traces = jax.lax.scan(
                comm_chunk, init, None, length=num_iters // interval
            )
            # (K/N, N) chunked traces -> flat (K,) per-iteration traces.
            traces = jax.tree.map(
                lambda v: v.reshape((num_iters,) + v.shape[2:]), traces
            )
            return state, traces
        if trace_every == 0:
            (state, _), _ = jax.lax.scan(
                step_untraced, init, None, length=num_iters
            )
            return state, None
        if trace_every == 1:
            (state, _), traces = jax.lax.scan(
                step_traced, init, None, length=num_iters
            )
            return state, traces

        def chunk(carry, _):
            # trace_every - 1 collective-free iterations, then one traced.
            carry, _ = jax.lax.scan(
                step_untraced, carry, None, length=trace_every - 1
            )
            return step_traced(carry, None)

        (state, _), traces = jax.lax.scan(
            chunk, init, None, length=num_iters // trace_every
        )
        return state, traces


def _admm_backend_path(
    y_workers: Array,
    t_workers: Array,
    *,
    backend: "ConsensusBackend",
    mu: float,
    eps_radius: float,
    num_iters: int,
    z0: Array | None,
    use_kernels: bool,
    policy: ConsensusPolicy | None = None,
    trace_every: int = 1,
) -> ADMMResult:
    """Eq.-11 iteration as a worker-local SPMD program.

    The same traced program runs under ``SimulatedBackend`` (vmap) and
    ``MeshBackend`` (shard_map); traces report worker 0, matching the
    batched path.  The worker program is compiled through the backend's
    executable cache: ``z0`` rides along as a replicated operand (NOT a
    closed-over constant) so one cached executable serves every solve
    with the same hyper-parameters and operand shapes.
    """
    m = y_workers.shape[0]
    if m != backend.num_workers:
        raise ValueError(
            f"y_workers has {m} worker shards, backend expects {backend.num_workers}"
        )
    policy = policy if policy is not None else backend.policy
    policy.validate(backend.num_workers)
    trace_every = validate_trace_every(trace_every, num_iters)
    q, n = t_workers.shape[1], y_workers.shape[1]
    dtype = y_workers.dtype
    z_init = jnp.zeros((q, n), dtype) if z0 is None else z0.astype(dtype)

    def worker(y_m: Array, t_m: Array, z_init_rep: Array):
        a, chol, jitter = _worker_stats_local(y_m, t_m, mu, use_kernels)
        state, traces = worker_admm_iterations(
            backend, a, chol, y_m, t_m, z_init_rep,
            mu=mu, eps_radius=eps_radius, num_iters=num_iters, policy=policy,
            trace_every=trace_every,
        )
        return state, traces, jitter

    # trace_every changes the traced output pytree (no trace leaves at
    # 0, K/N-long leaves at N>1), so it must key the executable cache.
    cache_key = (
        "admm_ridge", float(mu), float(eps_radius), int(num_iters),
        bool(use_kernels), trace_every,
    )
    (o_w, z_w, lam_w), traces, jitter_w = backend.run(
        worker, y_workers, t_workers, replicated=(z_init,), key=cache_key,
        policy=policy,
    )
    trace = None
    if traces is not None:
        objs, primals, duals, cerrs = traces
        trace = ADMMTrace(objs[0], primals[0], duals[0], cerrs[0])
    return ADMMResult(
        o_star=z_w[0], o_workers=o_w, lam=lam_w, trace=trace, jitter=jitter_w
    )


def centralized_ridge_admm(
    y: Array,
    t: Array,
    *,
    mu: float,
    eps_radius: float,
    num_iters: int,
) -> ADMMResult:
    """Centralized SSFN layer solve = the same ADMM with M=1 (paper [1])."""
    return admm_ridge_consensus(
        y[None], t[None], mu=mu, eps_radius=eps_radius, num_iters=num_iters
    )


def exact_constrained_ridge(
    y: Array,
    t: Array,
    *,
    eps_radius: float,
    tol: float = 1e-10,
    max_bisect: int = 200,
) -> Array:
    """Reference solution of  min ||T - OY||_F^2  s.t. ||O||_F <= eps_radius.

    Solved exactly via the secular equation: O(lmb) = T Y^T (Y Y^T + lmb I)^{-1}
    with lmb >= 0 chosen by bisection so that ||O(lmb)||_F = eps_radius (or
    lmb = 0 if the unconstrained LS solution is already feasible).  Used as
    the oracle in equivalence tests.
    """
    n = y.shape[0]
    gram = y @ y.T
    a = t @ y.T
    eye = jnp.eye(n, dtype=y.dtype)

    def o_of(lmb):
        return jax.scipy.linalg.solve(gram + (lmb + 1e-12) * eye, a.T, assume_a="pos").T

    o0 = o_of(0.0)
    if float(jnp.linalg.norm(o0)) <= eps_radius + tol:
        return o0
    lo, hi = 0.0, 1.0
    while float(jnp.linalg.norm(o_of(hi))) > eps_radius:
        hi *= 4.0
        if hi > 1e18:
            break
    for _ in range(max_bisect):
        mid = 0.5 * (lo + hi)
        if float(jnp.linalg.norm(o_of(mid))) > eps_radius:
            lo = mid
        else:
            hi = mid
        if hi - lo < tol * max(1.0, hi):
            break
    return o_of(hi)
