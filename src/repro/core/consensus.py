"""Consensus averaging over the worker graph.

Three interchangeable implementations of the paper's "find the average
quantity over the graph" primitive (Algorithm 1, line 8):

1. ``gossip_average`` — the paper-faithful model: B synchronous rounds of
   x <- H x with a doubly-stochastic mixing matrix H.  Workers are a
   leading axis of a single array (the simulation layout used by the
   reproduction experiments and tests).
2. ``exact_average`` — the B -> infinity limit (1/M) * sum_m x_m.
3. ``ring_gossip_average`` — the TPU-native adaptation: the same degree-d
   circular-topology gossip expressed with ``jax.lax.ppermute`` along a
   mesh axis, for running the consensus on an actual device ring (ICI
   torus).  On production meshes one would instead use ``jax.lax.pmean``
   (a single all-reduce == exact consensus); we keep gossip to reproduce
   the paper's degree sweep.
4. ``schedule_gossip_step``/``schedule_gossip_average`` — the general
   in-program form: execute a ``repro.core.topology.ExchangeSchedule``
   (any doubly-stochastic H compiled to static ``(permutation, weight)``
   ppermute steps) along a mesh axis.  The ring functions above are the
   hand-written special case this generalizes; uniform equal-weight
   schedules run the identical sum-then-divide hop sequence, so
   ``Gossip(topology=Ring(d))`` stays bit-identical to the legacy
   ``RingGossip``.

This module holds the *reference implementations*; how they are selected
and composed per training run is the job of the ``ConsensusPolicy``
strategy objects in ``repro.core.policy`` (``ExactMean``, ``RingGossip``,
``QuantizedGossip``, ``LossyGossip``, ``StaleMixing``), which call back
into these primitives.  The SPMD-side extras — the lossy schedule hop and
the stochastic quantizer — live here for the same reason.  The dense-H
functions (``gossip_average``, ``exact_average``, ``gossip_error``) are
the plain reference the policies' peer exchanges are tested against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np



def exact_average(x_workers: jax.Array) -> jax.Array:
    """(1/M) sum over the leading (worker) axis, broadcast back to all."""
    mean = jnp.mean(x_workers, axis=0, keepdims=True)
    return jnp.broadcast_to(mean, x_workers.shape)


def gossip_average(
    x_workers: jax.Array, h: np.ndarray | jax.Array, num_rounds: int
) -> jax.Array:
    """B synchronous gossip rounds: x^{b+1}_i = sum_j h_ij x^b_j.

    x_workers: (M, ...) array, one slice per worker.
    """
    h = jnp.asarray(h, dtype=x_workers.dtype)
    m = x_workers.shape[0]
    flat = x_workers.reshape(m, -1)

    def body(_, acc):
        return h @ acc

    out = jax.lax.fori_loop(0, num_rounds, body, flat)
    return out.reshape(x_workers.shape)


def gossip_error(x_workers: jax.Array) -> jax.Array:
    """Max deviation from the true mean — consensus quality metric."""
    mean = jnp.mean(x_workers, axis=0, keepdims=True)
    return jnp.max(jnp.abs(x_workers - mean))


def ring_gossip_step(x: jax.Array, axis_name: str, degree: int, num_nodes: int) -> jax.Array:
    """One degree-d circular gossip round via collective_permute on a ring.

    To be called inside shard_map/pmapped code where ``x`` is this
    worker's local value.  h_ij = 1/(2d+1) equal weights (paper §III).
    """
    nbr = 2 * degree + 1
    acc = x
    for k in range(1, degree + 1):
        fwd = [(i, (i + k) % num_nodes) for i in range(num_nodes)]
        bwd = [(i, (i - k) % num_nodes) for i in range(num_nodes)]
        acc = acc + jax.lax.ppermute(x, axis_name, fwd)
        acc = acc + jax.lax.ppermute(x, axis_name, bwd)
    return acc / nbr


def ring_gossip_average(
    x: jax.Array, axis_name: str, degree: int, num_nodes: int, num_rounds: int
) -> jax.Array:
    """B rounds of degree-d ring gossip inside an spmd region."""
    def body(_, val):
        return ring_gossip_step(val, axis_name, degree, num_nodes)

    # ppermute with python-level loop inside fori_loop body is fine: the
    # permutation tables are static.
    return jax.lax.fori_loop(0, num_rounds, body, x)


#: Wire widths the low-precision gossip link formats support (bits per
#: exchanged scalar, the eq.-15 ``wire_bits`` of a wire_dtype policy).
WIRE_DTYPES = {"float32": 32, "bfloat16": 16, "float16": 16}

#: Spec-grammar shorthands (``--wire-dtype bf16``).
_WIRE_ALIASES = {"f32": "float32", "bf16": "bfloat16", "f16": "float16"}


def canonical_wire_dtype(name: str) -> str:
    """Normalize a wire-dtype spec (``f32/bf16/f16`` or the full jax
    dtype names) to the canonical dtype string, or raise ValueError."""
    full = _WIRE_ALIASES.get(name, name)
    if full not in WIRE_DTYPES:
        raise ValueError(
            f"unknown wire dtype {name!r}; expected one of "
            f"{sorted(WIRE_DTYPES)} (or {sorted(_WIRE_ALIASES)})"
        )
    return full


def schedule_gossip_step(
    x: jax.Array,
    axis_name: str,
    schedule,
    *,
    self_value: jax.Array | None = None,
    wire_dtype: str | None = None,
) -> jax.Array:
    """One gossip round of an arbitrary doubly-stochastic H, expressed as
    the static ppermute steps of a ``topology.ExchangeSchedule``:

        x' = self_weight * self + sum_k weight_k * ppermute(x, perm_k)

    ``self_value`` substitutes a different array for the worker's OWN
    contribution (peers still receive ``x``) — quantized gossip keeps the
    local value full-precision, stale mixing keeps it fresh.  Uniform
    equal-weight schedules (the paper's h_ij = 1/|N_i| rule) take the
    sum-then-divide path, which reproduces ``ring_gossip_step``'s float
    ops exactly — the bit-identity guarantee for ``Ring`` topologies.

    ``wire_dtype`` (``"bfloat16"``/``"float16"``) narrows the WIRE only:
    the outgoing payload is cast once before the hops, every received
    message is widened back and accumulated in the input precision, and
    the worker's own contribution never leaves full precision.  None (or
    ``"float32"``) keeps the bit-identical full-width path.
    """
    own = x if self_value is None else self_value
    if wire_dtype is not None and wire_dtype != str(x.dtype):
        wire = x.astype(wire_dtype)
        # Narrow links always take the weighted form: the sum-then-divide
        # shortcut would accumulate at wire precision.
        acc = jnp.asarray(schedule.self_weight, own.dtype) * own
        for perm, w in zip(schedule.perms, schedule.weights):
            msg = jax.lax.ppermute(wire, axis_name, perm).astype(own.dtype)
            acc = acc + w * msg
        return acc
    if schedule.uniform:
        acc = own
        for perm in schedule.perms:
            acc = acc + jax.lax.ppermute(x, axis_name, perm)
        return acc / (len(schedule.perms) + 1)
    acc = schedule.self_weight * own
    for perm, w in zip(schedule.perms, schedule.weights):
        acc = acc + w * jax.lax.ppermute(x, axis_name, perm)
    return acc


def schedule_gossip_average(
    x: jax.Array,
    axis_name: str,
    schedule,
    num_rounds: int,
    *,
    wire_dtype: str | None = None,
) -> jax.Array:
    """B rounds of exchange-schedule gossip inside an SPMD region."""
    def body(_, val):
        return schedule_gossip_step(
            val, axis_name, schedule, wire_dtype=wire_dtype
        )

    # The permutation tables are static, so a python-level loop inside
    # the fori_loop body is fine (same pattern as ring_gossip_average).
    return jax.lax.fori_loop(0, num_rounds, body, x)


def lossy_schedule_gossip_step(
    x: jax.Array,
    axis_name: str,
    schedule,
    *,
    drop_prob: float,
    key: jax.Array,
    wire_dtype: str | None = None,
) -> jax.Array:
    """One exchange-schedule gossip round over a lossy network: each
    incoming step fails independently with probability ``drop_prob`` and
    the receiver renormalizes its mixing row over the surviving weights
    (the self term never drops; ``drop_prob=0`` reduces to
    :func:`schedule_gossip_step` up to float association).  ``key`` must
    be a per-worker key (each node observes its own link failures).
    ``wire_dtype`` narrows the link payloads as in
    :func:`schedule_gossip_step` (receive widens back to ``x.dtype``)."""
    keys = jax.random.split(key, max(len(schedule.perms), 1))
    wire = x if wire_dtype is None else x.astype(wire_dtype)
    self_w = jnp.asarray(schedule.self_weight, x.dtype)
    acc = self_w * x
    wsum = self_w
    for i, (perm, w) in enumerate(zip(schedule.perms, schedule.weights)):
        msg = jax.lax.ppermute(wire, axis_name, perm).astype(x.dtype)
        alive = jax.random.bernoulli(keys[i], 1.0 - drop_prob).astype(x.dtype)
        acc = acc + alive * w * msg
        wsum = wsum + alive * w
    return acc / wsum


def faulty_schedule_gossip_step(
    x: jax.Array,
    axis_name: str,
    schedule,
    alive: jax.Array,
    *,
    worker_index: jax.Array | None = None,
    transmit: jax.Array | None = None,
    wire_dtype: str | None = None,
) -> jax.Array:
    """One exchange-schedule gossip round under a shared fault mask.

    ``alive`` is an (M,) 0/1 vector computed IDENTICALLY on every worker
    (the same seeded draw at the same trace point — see
    ``policy.FaultModel.alive_mask``), marking which workers are up this
    round.  A step's message survives only when both endpoints are up
    (gate g = alive[me] * alive[src]); the weight of every dead link is
    rerouted to the receiver's own value:

        x' = self_w * x + sum_k w_k [g_k * recv_k + (1 - g_k) * x]

    so every realized row sums to 1 regardless of the draw, and a down
    worker degenerates to an identity row (it holds its value).  When
    the schedule is inverse-closed (``topology.is_inverse_closed`` —
    all uniform vertex-transitive schedules are), the symmetric gate
    kills the (i -> j) and (j -> i) weights together, making the
    realized matrix column-stochastic on the up set as well: the mean
    over up workers is preserved exactly, the invariant the fault model
    is built on.

    ``transmit`` substitutes the value peers RECEIVE (straggler replay
    of a stale iterate); the worker's own contribution is always the
    fresh ``x``.  ``wire_dtype`` narrows the link payload as in
    :func:`schedule_gossip_step`.  Everything here is data — the mask
    rides through the cached SPMD program, so faults never retrace.
    """
    me = (
        jax.lax.axis_index(axis_name) if worker_index is None else worker_index
    )
    out = x if transmit is None else transmit
    wire = out if wire_dtype is None else out.astype(wire_dtype)
    alive = alive.astype(x.dtype)
    a_me = alive[me]
    acc = jnp.asarray(schedule.self_weight, x.dtype) * x
    lost = jnp.zeros((), x.dtype)
    m = schedule.num_workers
    for perm, w in zip(schedule.perms, schedule.weights):
        src = np.zeros(m, dtype=np.int32)
        for s, d in perm:
            src[d] = s
        g = a_me * alive[jnp.asarray(src)[me]]
        msg = jax.lax.ppermute(wire, axis_name, perm).astype(x.dtype)
        acc = acc + (w * g) * msg
        lost = lost + w * (1.0 - g)
    return acc + lost * x


def _receive_screened(
    x: jax.Array,
    axis_name: str,
    schedule,
    alive: jax.Array | None,
    *,
    worker_index: jax.Array | None = None,
    transmit: jax.Array | None = None,
    wire_dtype: str | None = None,
):
    """Gather one payload per schedule hop, screening every incoming
    message for health before it can touch the aggregate.

    Returns ``(payloads, oks, weights, self_weight)`` where ``payloads[k]``
    is hop k's received message with the whole message REPLACED by the
    receiver's own ``x`` when the link is down (``alive`` gate, the PR-6
    rerouting) OR the payload contains any non-finite entry (the
    numerical-health screen), and ``oks[k]`` is the scalar bool health
    gate itself (True = the raw message survived).  Both reroute cases
    degrade into the diagonal reroute of
    :func:`faulty_schedule_gossip_step`: a NaN-bombing peer is
    indistinguishable from a dropped link, never a poisoned mean.  The
    ``oks`` flags let order-statistic aggregators keep rerouted links out
    of their neighborhood-scale estimates (a rerouted link sits at
    distance zero, which would otherwise drag the scale down and get an
    honest link trimmed in its place).

    ``transmit`` substitutes what peers receive (Byzantine corruption /
    straggler replay); the local ``x`` used for rerouting stays fresh.
    The per-link health gate is computed with ``jnp.where`` on a scalar
    predicate — non-finite values never enter a multiply, so no
    ``NaN * 0`` leak.
    """
    me = (
        jax.lax.axis_index(axis_name) if worker_index is None else worker_index
    )
    out = x if transmit is None else transmit
    wire = out if wire_dtype is None else out.astype(wire_dtype)
    m = schedule.num_workers
    a_me = None
    if alive is not None:
        alive = alive.astype(x.dtype)
        a_me = alive[me]
    payloads = []
    oks = []
    for perm in schedule.perms:
        msg = jax.lax.ppermute(wire, axis_name, perm).astype(x.dtype)
        ok = jnp.all(jnp.isfinite(msg))
        if alive is not None:
            src = np.zeros(m, dtype=np.int32)
            for s, d in perm:
                src[d] = s
            up = (a_me * alive[jnp.asarray(src)[me]]) > 0.5
            ok = jnp.logical_and(ok, up)
        payloads.append(jnp.where(ok, msg, x))
        oks.append(ok)
    return payloads, oks, schedule.weights, schedule.self_weight


#: Neighborhood-scale factor of the trimmed-mean outlier screen: a link
#: is trimmable when its payload's distance from the receiver exceeds
#: this multiple of the median neighborhood distance.  Below 1 the screen
#: trims the top-f links essentially unconditionally (which mis-flags
#: honest extremes and wrecks the mixing rate); large values only catch
#: payloads far outside the honest spread and let attacks that hide
#: inside the ADMM dual disagreement through.  1.5 catches a signflip
#: attacker (whose payload sits ~2||x|| from every honest receiver)
#: while honest neighborhood distances stay within the screen.
TRIM_SCREEN_FACTOR = 1.5


def trimmed_mean_schedule_gossip_step(
    x: jax.Array,
    axis_name: str,
    schedule,
    *,
    trim: int,
    alive: jax.Array | None = None,
    worker_index: jax.Array | None = None,
    transmit: jax.Array | None = None,
    wire_dtype: str | None = None,
) -> jax.Array:
    """One robust gossip round: screened trimmed-mean aggregation.

    The classical coordinate-wise trimmed mean discards the extremes of
    EVERY neighborhood, so its fixed point is biased by the honest
    workers' own disagreement — in consensus ADMM (where local updates
    re-inject disagreement each iteration) that bias never vanishes.
    This step instead trims *adversarially deviant links only*: each of
    the ``trim`` most-deviant payloads (Frobenius distance from the
    receiver's own value) is rerouted to the diagonal — exactly the
    dead-link reroute of :func:`faulty_schedule_gossip_step` — but only
    when it stands out from the neighborhood scale,

        d_k > TRIM_SCREEN_FACTOR * median({d_j}) + 1e-6 * (1 + ||x||),

    a test no honest payload passes once values concentrate.  Honest
    links therefore mix with their exact gossip weights (the honest-
    subset mean is preserved — trims reroute weight to the receiver,
    never leak it), while a Byzantine payload beyond the honest spread
    loses its entire link weight.  Up to ``trim`` arbitrarily-corrupted
    neighbors per neighborhood are neutralized; ``trim`` within the
    classical breakdown bound 2*trim < |neighborhood| is enforced by the
    policy layer.  Requires a uniform equal-weight schedule (the paper's
    h_ij = 1/|N_i| rule, so "most deviant" is well-defined without
    weight asymmetry).
    """
    if not schedule.uniform:
        raise ValueError(
            "trimmed-mean gossip needs a uniform equal-weight schedule"
        )
    payloads, oks, _, _ = _receive_screened(
        x, axis_name, schedule, alive,
        worker_index=worker_index, transmit=transmit, wire_dtype=wire_dtype,
    )
    s = len(payloads) + 1
    if not 0 <= 2 * trim < s:
        raise ValueError(
            f"trim={trim} needs 2*trim < neighborhood size {s}"
        )
    if trim == 0:
        return jnp.mean(jnp.stack([x] + payloads, axis=0), axis=0)
    ok = jnp.stack(oks)
    raw = jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(p - x))) for p in payloads]
    )
    # A health-rerouted link sits at distance 0 (its payload IS x); rank
    # it as maximally deviant so it consumes the trim budget, and keep it
    # out of the neighborhood-scale median (nanmedian over healthy links
    # only) so it cannot drag the scale down onto an honest link.
    dists = jnp.where(ok, raw, jnp.inf)
    med = jnp.nanmedian(jnp.where(ok, raw, jnp.nan))
    floor = 1e-6 * (1.0 + jnp.sqrt(jnp.sum(jnp.square(x))))
    thresh = TRIM_SCREEN_FACTOR * med + floor
    # rank 0 = most deviant; flag the `trim` most deviant links, but only
    # those beyond the neighborhood-scale threshold.
    ranks = jnp.argsort(jnp.argsort(-dists))
    flags = jnp.logical_and(ranks < trim, dists > thresh)
    acc = x
    for k, p in enumerate(payloads):
        acc = acc + jnp.where(flags[k], x, p)
    return acc / s


def median_schedule_gossip_step(
    x: jax.Array,
    axis_name: str,
    schedule,
    *,
    alive: jax.Array | None = None,
    worker_index: jax.Array | None = None,
    transmit: jax.Array | None = None,
    wire_dtype: str | None = None,
) -> jax.Array:
    """One robust gossip round: coordinate-wise median of the
    neighborhood payload stack — the maximal-breakdown special case of
    the trimmed mean (tolerates just under half the neighborhood being
    corrupt).  Uniform schedules only, like the trimmed mean."""
    if not schedule.uniform:
        raise ValueError("median gossip needs a uniform equal-weight schedule")
    payloads, _, _, _ = _receive_screened(
        x, axis_name, schedule, alive,
        worker_index=worker_index, transmit=transmit, wire_dtype=wire_dtype,
    )
    stack = jnp.stack([x] + payloads, axis=0)
    return jnp.median(stack, axis=0)


def clipped_schedule_gossip_step(
    x: jax.Array,
    axis_name: str,
    schedule,
    *,
    tau: float,
    alive: jax.Array | None = None,
    worker_index: jax.Array | None = None,
    transmit: jax.Array | None = None,
    wire_dtype: str | None = None,
) -> jax.Array:
    """One robust gossip round with norm-clipped incoming payloads
    (Karimireddy et al.-style centered clipping): each screened payload's
    deviation from self is shrunk onto the Frobenius ball of radius
    ``tau`` before the standard weighted accumulation,

        recv_k' = x + min(1, tau / ||recv_k - x||) (recv_k - x)

    so one attacker can displace this worker by at most w_k * tau per
    round no matter how extreme its payload.  Payloads within the ball
    pass through UNTOUCHED (``jnp.where`` selects the raw message), which
    keeps the zero-attacker round bit-identical to the weighted
    :func:`schedule_gossip_step` path on non-uniform schedules and equal
    to it up to the uniform path's sum-then-divide association otherwise.
    Works on any schedule (weights are respected, not assumed equal)."""
    if tau <= 0.0:
        raise ValueError(f"clip radius tau must be > 0, got {tau}")
    payloads, _, weights, self_weight = _receive_screened(
        x, axis_name, schedule, alive,
        worker_index=worker_index, transmit=transmit, wire_dtype=wire_dtype,
    )
    acc = jnp.asarray(self_weight, x.dtype) * x
    for msg, w in zip(payloads, weights):
        delta = msg - x
        norm = jnp.sqrt(jnp.sum(delta * delta))
        clipped = x + (tau / jnp.maximum(norm, 1e-30)) * delta
        acc = acc + w * jnp.where(norm <= tau, msg, clipped)
    return acc


def quantize_stochastic(x: jax.Array, bits: int, key: jax.Array) -> jax.Array:
    """Unbiased per-tensor stochastic-rounding quantization to 2^bits
    levels over the tensor's dynamic range: E[q(x)] = x."""
    levels = 2 ** bits - 1
    lo = jnp.min(x)
    hi = jnp.max(x)
    scale = jnp.maximum(hi - lo, 1e-12) / levels
    t = (x - lo) / scale
    floor = jnp.floor(t)
    prob = t - floor
    up = jax.random.bernoulli(key, prob, x.shape)
    q = floor + up.astype(x.dtype)
    return lo + q * scale


def quantize_nearest(x: jax.Array, bits: int) -> jax.Array:
    """Deterministic round-to-nearest variant (biased, zero variance)."""
    levels = 2 ** bits - 1
    lo = jnp.min(x)
    hi = jnp.max(x)
    scale = jnp.maximum(hi - lo, 1e-12) / levels
    return lo + jnp.round((x - lo) / scale) * scale
