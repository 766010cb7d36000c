"""Compile-once dSSFN layer engine: one fused SPMD program per layer step.

The paper's per-layer cost is O(n^2 J_m) for the Gram product plus one
Cholesky, and its per-iteration communication is one Q x n consensus
(eq. 15).  The pre-engine training loop paid far more than that in pure
overhead: every layer solve re-traced and recompiled the whole worker
program, feature propagation ran as a *separate* backend dispatch whose
activations round-tripped HBM between "propagate" and "solve", and the
host forced a device sync per layer to read the objective.

:func:`fused_layer_step` runs the whole per-layer pipeline as ONE traced
worker program under the ``ConsensusBackend`` executable cache:

    Y_l = relu(W_l @ Y_{l-1})          (feature propagation; skipped at l=0)
    G   = Y_l Y_l^T + I/mu, L = chol(G)  (the paper's dominant FLOPs)
    K x eq.-11 ADMM iterations           (lax.scan, consensus per iter)

so activations and shards never leave device between propagate and
solve, and an L-layer train with repeated hidden widths lowers each
distinct layer shape exactly once.  ``W_l`` rides along as a replicated
operand (never a baked jit constant), and the stacked Y carry is donated
to XLA off-CPU so each layer reuses the previous layer's activation
buffer.

Kernel routing (``use_kernels=True``, 128-aligned shapes only):

- propagation + Gram fuse into the ``propagate_gram`` Pallas kernel —
  one HBM read of Y per layer instead of two (emit Y_l and Y_l Y_l^T +
  I/mu in a single pass over the samples);
- the standalone ``gram`` kernel covers the l=0 step (no W yet);
- ``matmul_relu`` covers propagation when only the Gram shapes misalign.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import profiling
from repro.core import admm as admm_lib
from repro.core.backend import ConsensusBackend
from repro.core.policy import ConsensusPolicy

Array = jax.Array


class LayerStepResult(NamedTuple):
    o_star: Array     # (Q, n) consensus readout Z^K for this layer
    o_workers: Array  # (M, Q, n) per-worker primal variables
    lam: Array        # (M, Q, n) scaled duals
    y_workers: Array  # (M, n, J_m) this layer's features (post-propagation)
    #: (K/trace_every,) device-resident worker-0 traces; None when
    #: trace_every=0 (the collective-free hot path).
    trace: "admm_lib.ADMMTrace | None"
    #: (M,) per-worker guarded-Cholesky jitter level (int32; 0 = the
    #: Gram factored clean — see ``admm.guarded_cholesky``).
    jitter: "Array | None" = None


def _aligned(*dims: int) -> bool:
    return all(d % 128 == 0 for d in dims)


def _propagate_and_stats(w, y_m, t_m, mu: float, use_kernels: bool):
    """relu(W @ Y_m) then (A_m, chol(G_m), jitter) — fused on aligned
    shapes; the Cholesky is the guarded (self-healing) factorization."""
    n_out, n_in = w.shape
    j = y_m.shape[1]
    if use_kernels and _aligned(n_out, n_in, j):
        from repro.kernels.propagate_gram import propagate_gram

        # The fused kernel's propagation counts as Gram work.
        with profiling.scope(profiling.GRAM):
            y_new, gram = propagate_gram(w, y_m, mu=mu)
            y_new = y_new.astype(y_m.dtype)
            gram = gram.astype(y_m.dtype)
        chol, jitter = admm_lib.guarded_cholesky(gram)
        with profiling.scope(profiling.GRAM):
            a = t_m @ y_new.T
        return y_new, a, chol, jitter
    # Unfused: plain propagation, then the same stats construction (and
    # gram-kernel routing) the direct ADMM path uses.
    with profiling.scope(profiling.PROPAGATE):
        y_new = jax.nn.relu(w @ y_m)
    a, chol, jitter = admm_lib._worker_stats_local(y_new, t_m, mu, use_kernels)
    return y_new, a, chol, jitter


class LayerProgram(NamedTuple):
    """One layer's SPMD program bound to its operands, ready to run or to
    lower through the backend's executable cache (same key either way, so
    the texts and stats describe exactly the program :meth:`run` runs)."""

    backend: ConsensusBackend
    worker: object
    y_workers: Array
    t_workers: Array
    replicated: tuple
    key: tuple
    donate: tuple
    policy: ConsensusPolicy

    def _call(self, method):
        return method(
            self.worker, self.y_workers, self.t_workers,
            replicated=self.replicated, key=self.key, donate=self.donate,
            policy=self.policy,
        )

    def run(self):
        return self._call(self.backend.run)

    def lowering_texts(self) -> dict:
        """``ConsensusBackend.lowering_texts`` of this program."""
        return self._call(self.backend.lowering_texts)

    def lowering_stats(self) -> dict:
        """``ConsensusBackend.lowering_stats`` of this program."""
        return self._call(self.backend.lowering_stats)


def layer_program(
    backend: ConsensusBackend,
    y_workers: Array,
    t_workers: Array,
    w: Array | None,
    *,
    mu: float,
    eps_radius: float,
    num_iters: int,
    use_kernels: bool = False,
    donate_y: bool = False,
    policy: ConsensusPolicy | None = None,
    trace_every: int = 1,
) -> LayerProgram:
    """The program :func:`fused_layer_step` runs, unrun.  Operands may be
    ``jax.ShapeDtypeStruct``s when the program is only lowered (simulated
    backend).  Arguments as for :func:`fused_layer_step`."""
    m = y_workers.shape[0]
    if m != backend.num_workers:
        raise ValueError(
            f"y_workers has {m} worker shards, backend expects {backend.num_workers}"
        )
    policy = policy if policy is not None else backend.policy
    policy.validate(backend.num_workers)
    trace_every = admm_lib.validate_trace_every(trace_every, num_iters)
    # Interval-mixing policies chunk the ADMM scan structurally; surface
    # the incompatible-configuration errors here, before any tracing.
    interval = policy.communication_interval
    if interval > 1:
        if num_iters % interval:
            raise ValueError(
                f"communication_interval={interval} must divide "
                f"num_iters={num_iters} (whole local/communicate chunks)"
            )
        if trace_every > 1:
            raise ValueError(
                "communication_interval > 1 supports trace_every in {0, 1} "
                f"only, got {trace_every}"
            )

    def worker(y_m: Array, t_m: Array, *w_rep: Array):
        if w_rep:
            y_m, a, chol, jitter = _propagate_and_stats(
                w_rep[0], y_m, t_m, mu, use_kernels
            )
        else:
            a, chol, jitter = admm_lib._worker_stats_local(
                y_m, t_m, mu, use_kernels
            )
        q, n = a.shape
        z_init = jnp.zeros((q, n), a.dtype)
        (o, z, lam), traces = admm_lib.worker_admm_iterations(
            backend, a, chol, y_m, t_m, z_init,
            mu=mu, eps_radius=eps_radius, num_iters=num_iters, policy=policy,
            trace_every=trace_every,
        )
        return (o, z, lam, y_m), traces, jitter

    cache_key = (
        "dssfn_layer",
        float(mu),
        float(eps_radius),
        int(num_iters),
        bool(use_kernels),
        w is not None,
        trace_every,
    )
    return LayerProgram(
        backend=backend,
        worker=worker,
        y_workers=y_workers,
        t_workers=t_workers,
        replicated=() if w is None else (w,),
        key=cache_key,
        donate=(0,) if donate_y else (),
        policy=policy,
    )


def fused_layer_step(
    backend: ConsensusBackend,
    y_workers: Array,
    t_workers: Array,
    w: Array | None,
    *,
    mu: float,
    eps_radius: float,
    num_iters: int,
    use_kernels: bool = False,
    donate_y: bool = False,
    policy: ConsensusPolicy | None = None,
    trace_every: int = 1,
) -> LayerStepResult:
    """One dSSFN layer as a single cached SPMD program.

    y_workers: (M, n_{l-1}, J_m) previous-layer features (layer input x at
        l=0), stacked per worker.
    w: replicated layer weight W_l = [V_Q O_{l-1} ; R_l], or None at l=0
        (solve directly on the input features, no propagation).
    donate_y: donate the stacked Y buffer to XLA (off-CPU) — pass True
        only when the input Y is a buffer the engine itself materialized
        (layers >= 2: the relu(W@Y) carry).  Layer 0's input is the
        caller's array, and layer 0's pass-through output may alias it
        (jit forwards unchanged inputs), so layer 1 must not donate
        either.
    policy: consensus strategy for the ADMM scan inside this program
        (default: the backend's policy).  Part of the cache key — one
        lowering per (layer shape, policy), never a per-call re-trace.
        Gossip-family policies carry their ``Topology``, so the graph's
        exchange schedule is compiled into this fused program and two
        policies differing only in topology get distinct executables.
    trace_every: convergence-trace stride for the ADMM scan
        (``admm.worker_admm_iterations``): 1 = per-iteration traces
        (default), 0 = the collective-free hot path (``result.trace`` is
        None and the program contains only the policy's own exchanges),
        N > 1 = every N-th iteration.  Part of the cache key — the value
        changes the lowered program's output pytree.

    The executable cache key covers every closed-over trace-affecting
    value; W is an operand, so the (n, n)-shaped program compiled for
    layer 2 is reused verbatim by layers 3..L.
    """
    (o_w, z_w, lam_w, y_next), traces, jitter_w = layer_program(
        backend, y_workers, t_workers, w,
        mu=mu, eps_radius=eps_radius, num_iters=num_iters,
        use_kernels=use_kernels, donate_y=donate_y, policy=policy,
        trace_every=trace_every,
    ).run()
    trace = None
    if traces is not None:
        objs, primals, duals, cerrs = traces
        trace = admm_lib.ADMMTrace(objs[0], primals[0], duals[0], cerrs[0])
    return LayerStepResult(
        o_star=z_w[0], o_workers=o_w, lam=lam_w, y_workers=y_next,
        trace=trace, jitter=jitter_w,
    )
