"""Consensus-ADMM solver for the paper's layer-wise convex problem.

Decentralized problem (paper eq. 9/10):

    min_{O_m, Z}  sum_m ||T_m - O_m Y_m||_F^2
    s.t.          ||Z||_F <= eps_radius,   O_m = Z  for all m

ADMM iterations (paper eq. 11):

    O_m^{k+1} = (T_m Y_m^T + (1/mu)(Z^k - Lam_m^k)) (Y_m Y_m^T + (1/mu) I)^{-1}
    Z^{k+1}   = P_eps( (1/M) sum_m (O_m^{k+1} + Lam_m^k) )       <- consensus
    Lam^{k+1} = Lam_m^k + O_m^{k+1} - Z^{k+1}

Notes on fidelity:
- The Gram matrix G_m = Y_m Y_m^T + I/mu is constant over k, so we
  Cholesky-factorize it and turn the factor into G_m^{-1} ONCE per layer
  (the Matlab reference does the same via a cached inverse); each
  iteration's solve is then one float32 product R G_m^{-1}.  The Gram
  product is backed by the ``gram`` Pallas kernel on TPU
  (repro.kernels.gram.ops).
- The paper defines P_eps with radius eps on the *Frobenius norm* even
  though the constraint is written ||Z||_F^2 <= eps; we follow the
  operational definition (radius), matching the released Matlab code and
  the choice eps = 2Q.
- The only cross-worker communication per iteration is the consensus mean
  of (O_m + Lam_m): Q x n floats, matching the paper's communication-load
  accounting Q * n_{l-1} * B * K (eq. 15).
"""
from __future__ import annotations

from typing import TYPE_CHECKING, NamedTuple

import jax
import jax.numpy as jnp

from repro import profiling
from repro.core.policy import ConsensusPolicy

if TYPE_CHECKING:  # avoid a circular import at runtime (backend imports policy)
    from repro.core.backend import ConsensusBackend

Array = jax.Array


def project_frobenius(z: Array, radius: float) -> Array:
    """P_eps: scale Z onto the Frobenius ball of given radius (paper eq. after 11)."""
    norm = jnp.linalg.norm(z)
    scale = jnp.where(norm > radius, radius / jnp.maximum(norm, 1e-30), 1.0)
    return z * scale


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
    #: clean).
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


def gram_inverse(chol: Array) -> Array:
    """G^{-1} from the lower Cholesky factor of G: ``cho_solve`` against
    the identity, once per layer.  Kept out of the ADMM scan because
    XLA's blocked triangular solve inverts the factor's diagonal blocks
    inside the op, where loop-invariant hoisting cannot reach them.  A
    non-finite factor gives a non-finite inverse, so the layerwise
    divergence guard still sees the failure."""
    eye = jnp.eye(chol.shape[-1], dtype=chol.dtype)
    return jax.scipy.linalg.cho_solve((chol, True), eye)


def apply_gram_inverse(rhs: Array, g_inv: Array) -> Array:
    """O = R G^{-1} as one float32 product: (G^{-1} R^T)^T, the columns
    ``cho_solve((chol, True), R^T)`` returns.  ``HIGHEST`` keeps both
    operands float32; the default TPU precision would round them to
    bfloat16."""
    return jnp.einsum(
        "qj,ij->qi", rhs, g_inv, precision=jax.lax.Precision.HIGHEST
    )


def admm_ridge_consensus(
    y_workers: Array,
    t_workers: Array,
    *,
    mu: float,
    eps_radius: float,
    num_iters: int,
    backend: "ConsensusBackend | None" = None,
    policy: ConsensusPolicy | None = None,
    use_kernels: bool = False,
    trace_every: int = 1,
) -> ADMMResult:
    """Run K iterations of consensus ADMM (paper Algorithm 1, lines 5-10).

    A standalone solve runs the layer engine's l=0 program (no
    propagation, Z from zero), so it shares one executable-cache entry
    with a training layer 0 of the same hyper-parameters and shapes.
    Traces report worker 0.

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
    trace_every: convergence-trace stride (``worker_admm_iterations``):
        1 = per-iteration traces (default), 0 = no traces and NO
        trace collectives in the lowered program (``result.trace`` is
        None), N > 1 = every N-th iteration.
    """
    from repro.core import engine as engine_lib
    from repro.core.backend import SimulatedBackend

    if backend is None:
        backend = SimulatedBackend(y_workers.shape[0])
    step = engine_lib.fused_layer_step(
        backend, y_workers, t_workers, None,
        mu=mu, eps_radius=eps_radius, num_iters=num_iters,
        use_kernels=use_kernels, policy=policy, trace_every=trace_every,
    )
    return ADMMResult(
        o_star=step.o_star, o_workers=step.o_workers, lam=step.lam,
        trace=step.trace, jitter=step.jitter,
    )


def _worker_stats_local(y_m: Array, t_m: Array, mu: float, use_kernels: bool):
    """Worker-local A_m = T_m Y_m^T and guarded Cholesky of
    G_m = Y_m Y_m^T + I/mu (plus this worker's jitter level).

    use_kernels=True routes the Gram product through the Pallas ``gram``
    kernel on aligned shapes (interpret mode off-TPU).
    """
    n, j = y_m.shape
    if use_kernels:
        from repro.kernels.common import aligned

        use_kernels = aligned(n, j)
    with profiling.scope(profiling.GRAM):
        if use_kernels:
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
    """K eq.-11 iterations as a worker-local scan over the cached G^{-1}
    (``gram_inverse`` of the factor, formed before the scan).

    The inner loop of the layer engine's worker program
    (``core.engine``): all cross-worker communication goes through
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
    # Once per layer, outside the scan: each iteration only multiplies.
    with profiling.scope(profiling.ADMM), profiling.scope(profiling.SOLVE):
        g_inv = gram_inverse(chol)

    def solve(z, lam):
        """O_m = (A_m + (Z - Lam_m)/mu) G_m^{-1} via the cached inverse."""
        with profiling.scope(profiling.SOLVE):
            rhs = a + (z - lam) / mu
            return apply_gram_inverse(rhs, g_inv)

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
