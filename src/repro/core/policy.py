"""ConsensusPolicy: one strategy object per way of reaching consensus.

The paper's Algorithm 1 is parameterized by *how* the workers average
(a doubly-stochastic mixing matrix H); everything else — the layer-wise
loop, the ADMM iterations, the mesh execution — is invariant.  This
module makes that parameterization a first-class object instead of a set
of string modes and parallel code paths:

    policy.mix(x, state, ctx) -> (x_mixed, state)

runs *inside* the SPMD worker program (under ``SimulatedBackend``'s vmap
axis or ``MeshBackend``'s shard_map region), communicates only through
the collectives on :class:`ConsensusContext`, and threads optional
per-round state (quantizer PRNG keys, staleness buffers) through the
ADMM scan carry.  Each policy declares its communication footprint —
``exchanges_per_round`` (peer messages per consensus call, the B factor
of the paper's eq. 15) and ``wire_bits`` (bits per exchanged scalar) —
so the accounting in ``layerwise``/``bench_mesh`` needs no per-mode
special cases.

Shipped policies
----------------
==================================  ==============================  ==========
policy                              exchanges/round                 wire bits
==================================  ==============================  ==========
``ExactMean()``                     1 (one all-reduce)              32
``Gossip(rounds, topology)``        rounds * topology edges         32/16
``RingGossip(rounds, degree)``      2 * degree * rounds             32/16
``QuantizedGossip(bits, ...)``      1 (or rounds * edges)           ``bits``
``LossyGossip(drop_prob, ...)``     rounds * topology edges         32/16
``StaleMixing(delay, ...)``         1 (or topology edges)           32/16
``AsyncGossip(rounds, interval)``   rounds * edges / interval       32/16
``TrimmedMeanGossip(f, ...)``       rounds * topology edges         32/16
``MedianGossip(rounds, ...)``       rounds * topology edges         32/16
``ClippedGossip(tau, ...)``         rounds * topology edges         32/16
==================================  ==============================  ==========

Byzantine resilience: :class:`FaultModel` injects seeded *corruption*
faults (``byzantine=(i,) + attack="signflip|scale:c|noise:s|nanbomb|
replay:d"``) alongside PR 6's omission faults — attackers substitute a
corrupted payload on the wire while their own mixing input stays honest.
The robust policies (``TrimmedMeanGossip``/``MedianGossip``/
``ClippedGossip``) bound what any ``f`` attackers per neighborhood can
do to the aggregate and screen every incoming payload for non-finite
values (a NaN bomb degrades into a dropped link); ``AsyncGossip`` under
the same fault model is the *vulnerable* baseline — it trusts payloads,
which is what the robustness tests diverge on purpose.

Wire efficiency: gossip-family policies take ``wire_dtype=`` (f32 /
bf16 / f16 link payloads, accumulated in full precision — ``wire_bits``
and the eq.-15 byte accounting track it), and plain ``Gossip`` compiles
its B rounds into ONE H^B mix by default (``compress=True``; see
:meth:`repro.core.topology.Topology.power_schedule`).

``ExactMean`` is the B -> infinity limit (bit-identical to the old
``mode='exact'``).  ``Gossip`` is the paper's H-matrix gossip over a
first-class :class:`repro.core.topology.Topology` — ``Ring``, ``Torus``,
``Hypercube``, ``FullyConnected``, ``RandomGeometric``, ``TimeVarying``
— whose static exchange schedule runs as ``ppermute`` hops inside the
worker program; ``RingGossip(rounds, degree)`` is the bit-identical
alias for ``Gossip(rounds, topology=Ring(degree))`` (the paper's
degree-d circular experiments).  The quantized / lossy / stale policies
(the paper's §IV future-work axis) also take ``topology=``: ``None``
keeps their original single-all-reduce / ring behaviour, a topology
object runs them over that graph's exchange schedule.

Because graph degree can depend on M (hypercube: log2 M), the eq.-15
accounting has an M-aware entry point ``exchanges_for(num_workers)``;
the legacy ``exchanges_per_round`` property remains for M-free policies.

The numeric primitives (ring hops, exchange-schedule execution,
stochastic quantization) live in ``repro.core.consensus`` — policies are
thin strategy objects over those reference implementations, which is
what keeps a new consensus variant at ~50 lines.

Policies are frozen dataclasses: hashable (they participate in the
backend executable-cache key — one lowering per (layer shape, policy)),
compare by value, and hold only static configuration.  Randomized
policies fold a static integer ``seed`` with the worker index at trace
time and advance the resulting key through the scan state, so repeated
``mix`` calls see fresh draws with no Python-side state.
"""
from __future__ import annotations

import abc
from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus as consensus_lib
from repro.core import topology as topology_lib
from repro.core.consensus import (  # noqa: F401  (canonical re-exports)
    quantize_nearest,
    quantize_stochastic,
)
from repro.core.topology import Ring, Topology, parse_topology

Array = jax.Array


@dataclass(frozen=True)
class ConsensusContext:
    """Collectives available to a policy inside the worker program.

    Valid under both runtimes: vmap-with-axis-name (``SimulatedBackend``)
    and shard_map over a mesh axis (``MeshBackend``).
    """

    axis_name: str
    num_workers: int

    def pmean(self, x: Array) -> Array:
        return jax.lax.pmean(x, self.axis_name)

    def psum(self, x: Array) -> Array:
        return jax.lax.psum(x, self.axis_name)

    def pmax(self, x: Array) -> Array:
        return jax.lax.pmax(x, self.axis_name)

    def ppermute(self, x: Array, perm) -> Array:
        return jax.lax.ppermute(x, self.axis_name, perm)

    def worker_index(self) -> Array:
        return jax.lax.axis_index(self.axis_name)


class ConsensusPolicy(abc.ABC):
    """Strategy object for the paper's graph-average primitive.

    Implementations must be hashable value objects (frozen dataclasses):
    they ride in executable-cache keys, so two equal policies must share
    one lowered program.
    """

    #: Short mode string, kept for the legacy ``backend.mode`` attribute
    #: and CLI round-tripping.
    mode_name: str = "policy"

    #: Bits per scalar actually put on the wire (eq.-15 byte accounting).
    wire_bits: int = 32

    @property
    @abc.abstractmethod
    def exchanges_per_round(self) -> int:
        """Peer messages each worker sends per ``mix`` call (eq. 15's B).

        Raises ValueError for policies whose graph degree depends on the
        worker count (hypercube, fully-connected, geometric) — callers
        that know M should use :meth:`exchanges_for`.
        """

    def exchanges_for(self, num_workers: int | None) -> int:
        """M-aware exchange count — the accounting entry point backends
        and trainers use (topology degree can depend on M)."""
        return self.exchanges_per_round

    @property
    def communication_interval(self) -> int:
        """Mix every N-th consensus call (Bagua-style local steps).

        1 for every synchronous policy; ``AsyncGossip(interval=N)``
        raises it, and the ADMM scan then runs N-1 purely local
        iterations per communicating one — structurally, so the lowered
        program's collective count scales by 1/N with no branching.
        """
        return 1

    @property
    def is_exact(self) -> bool:
        """True if ``mix`` returns the true mean on every worker —
        lets callers skip consensus-error collectives on the hot path."""
        return False

    def validate(self, num_workers: int) -> None:
        """Raise ValueError if this policy cannot run on M workers."""

    def init_state(self, x: Array, ctx: ConsensusContext) -> Any:
        """Per-worker scan-carry state (PRNG keys, staleness buffers).

        Called inside the worker program with an example message ``x``
        (its shape/dtype are what matter).  Stateless policies return ().
        """
        return ()

    @abc.abstractmethod
    def mix(
        self, x: Array, state: Any, ctx: ConsensusContext
    ) -> Tuple[Array, Any]:
        """One consensus round: this worker's estimate of the graph mean.

        Runs inside the SPMD worker program; all cross-worker traffic
        must go through ``ctx``.  Returns the mixed value and the
        advanced state.
        """

    def one_shot(self, x: Array, ctx: ConsensusContext) -> Array:
        """Single mix from a fresh state (diagnostics / compat paths).

        Policies whose fresh state means "no history yet" (staleness
        buffers) override this so a lone call still returns an average
        rather than an artifact of the empty state.
        """
        out, _ = self.mix(x, self.init_state(x, ctx), ctx)
        return out

    def comm_scalars(
        self, *, scalars: int, num_consensus: int,
        num_workers: int | None = None,
    ) -> int:
        """Eq.-15 scalars per worker on the wire: ``scalars`` floats per
        exchange, ``exchanges_for(M)`` exchanges per consensus call,
        ``num_consensus`` consensus calls.  Policies that skip rounds
        (``AsyncGossip``'s communication interval) override this so the
        accounting reflects what actually moves.
        """
        return scalars * self.exchanges_for(num_workers) * num_consensus

    def wire_bytes(
        self, *, scalars: int, num_consensus: int,
        num_workers: int | None = None,
    ) -> int:
        """Eq.-15 wire bytes per worker — :meth:`comm_scalars` at this
        policy's link width.  The single accounting used by layerwise
        logs and benchmarks.
        """
        return (
            self.comm_scalars(
                scalars=scalars, num_consensus=num_consensus,
                num_workers=num_workers,
            ) * self.wire_bits // 8
        )

    def describe(self) -> str:
        return repr(self)


def _worker_key(seed: int, ctx: ConsensusContext) -> Array:
    """Per-worker PRNG key from a static seed: distinct streams per
    worker, deterministic across runs and runtimes."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), ctx.worker_index())


def _cycle_exchanges(
    topology: Topology, rounds: int, num_workers: int | None
) -> int:
    """Eq.-15 peer messages for B gossip rounds over a (possibly
    time-varying) topology: round b talks on cycle[b % L]'s edges."""
    cycle = topology.cycle()
    return sum(
        cycle[b % len(cycle)].edges_per_node(num_workers)
        for b in range(rounds)
    )


def _cycle_schedules(topology: Topology, ctx: ConsensusContext) -> list:
    """Per-round exchange schedules; round b uses schedules[b % L]
    (memoized — irregular graphs pay a Birkhoff decomposition per
    schedule construction, and these run at trace time)."""
    return [
        topology_lib.cached_exchange_schedule(t, ctx.num_workers)
        for t in topology.cycle()
    ]


# --------------------------------------------------------------- exact

@dataclass(frozen=True)
class ExactMean(ConsensusPolicy):
    """One all-reduce: the B -> infinity limit of gossip (paper §III)."""

    mode_name = "exact"

    @property
    def exchanges_per_round(self) -> int:
        return 1

    @property
    def is_exact(self) -> bool:
        return True

    def mix(self, x, state, ctx):
        return ctx.pmean(x), state


# -------------------------------------------------------------- gossip

@dataclass(frozen=True)
class Gossip(ConsensusPolicy):
    """B rounds of doubly-stochastic gossip x <- H x over an arbitrary
    :class:`~repro.core.topology.Topology` (paper §III).

    The topology's static exchange schedule — ``(permutation, weight)``
    ppermute steps — is compiled into the SPMD worker program at trace
    time, so ``Torus``/``Hypercube``/``RandomGeometric``/``TimeVarying``
    graphs run through exactly the in-program peer-exchange path the
    paper's ring did, on both backends.  ``TimeVarying`` topologies cycle
    one sub-schedule per round.

    ``compress=True`` (default) collapses the B serial rounds into ONE
    mix with the precomputed power matrix H^B, compiled through the
    Birkhoff-von-Neumann path (:meth:`Topology.power_schedule`): the
    program executes ~|support(H^B)| weighted ppermute hops instead of
    B x edges sequential ones.  The result equals ``H**B @ x`` up to
    float reassociation; pass ``compress=False`` for the hop-by-hop
    serial schedule (bit-identical to the legacy ``RingGossip``).

    ``wire_dtype`` (``"float32"`` default, ``"bfloat16"``/``"float16"``)
    narrows every link payload: messages are cast once before going on
    the wire and accumulated in full precision on receive, halving
    eq.-15 bytes at 16-bit widths.  Eq.-15 exchange *counts* stay the
    mathematical B x edges figure regardless of compression (one
    compressed hop still carries a full Q x n payload).
    """

    rounds: int = 1
    topology: Topology = Ring(1)
    compress: bool = True
    wire_dtype: str = "float32"

    mode_name = "gossip"

    def __post_init__(self):
        if self.rounds < 1:
            raise ValueError(f"gossip rounds must be >= 1, got {self.rounds}")
        if not isinstance(self.topology, Topology):
            raise TypeError(
                f"topology must be a Topology, got {type(self.topology).__name__}"
            )
        object.__setattr__(
            self, "wire_dtype",
            consensus_lib.canonical_wire_dtype(self.wire_dtype),
        )

    @property
    def degree(self) -> int:
        """Legacy ``backend.degree`` view (ring topologies only)."""
        return getattr(self.topology, "degree", 1)

    @property
    def wire_bits(self) -> int:  # type: ignore[override]
        return consensus_lib.WIRE_DTYPES[self.wire_dtype]

    def validate(self, num_workers: int) -> None:
        self.topology.validate(num_workers)

    @property
    def exchanges_per_round(self) -> int:
        return self.exchanges_for(None)

    def exchanges_for(self, num_workers: int | None) -> int:
        return _cycle_exchanges(self.topology, self.rounds, num_workers)

    @property
    def _compressible(self) -> bool:
        # rounds=1 over a single graph IS its native schedule already.
        return self.compress and not (
            self.rounds == 1 and len(self.topology.cycle()) == 1
        )

    def _serial_hops(self, num_workers: int) -> int:
        # Build each distinct cycle entry's schedule ONCE (schedule
        # construction can mean a Birkhoff decomposition for irregular
        # graphs), then count hops over the round sequence.
        per_phase = [
            len(topology_lib.cached_exchange_schedule(t, num_workers).perms)
            for t in self.topology.cycle()
        ]
        return sum(
            per_phase[b % len(per_phase)] for b in range(self.rounds)
        )

    def _compressed_schedule_or_none(self, num_workers: int):
        """The H^B schedule IF it is actually shallower than B serial
        rounds.  Vertex-transitive graphs compress to <= M-1 hops, but
        the Birkhoff depth of an irregular (geometric) power can exceed
        the serial hop count — compression is a schedule optimization,
        so it only applies when it wins."""
        if not self._compressible:
            return None
        sched = topology_lib.compressed_schedule(
            self.topology, num_workers, self.rounds
        )
        if len(sched.perms) >= self._serial_hops(num_workers):
            return None
        return sched

    def hops_for(self, num_workers: int) -> int:
        """ppermute hops one ``mix`` actually executes — the compiled
        schedule depth (compressed mixes collapse B rounds into the
        permutation support of H^B; serial mixes hop every edge every
        round)."""
        sched = self._compressed_schedule_or_none(num_workers)
        if sched is not None:
            return len(sched.perms)
        return self._serial_hops(num_workers)

    def mix(self, x, state, ctx):
        wd = None if self.wire_dtype == "float32" else self.wire_dtype
        sched = self._compressed_schedule_or_none(ctx.num_workers)
        if sched is not None:
            # One mix with H^B: the whole B-round schedule as a single
            # minimal-depth weighted hop sequence (graph-build work is
            # memoized; this runs at trace time only).
            out = consensus_lib.schedule_gossip_step(
                x, ctx.axis_name, sched, wire_dtype=wd
            )
            return out, state
        scheds = _cycle_schedules(self.topology, ctx)
        if len(scheds) == 1:
            # fori_loop over the single schedule: the bit-identity path
            # for Ring (mirrors ring_gossip_average exactly).
            out = consensus_lib.schedule_gossip_average(
                x, ctx.axis_name, scheds[0], self.rounds, wire_dtype=wd
            )
        else:
            out = x
            for b in range(self.rounds):
                out = consensus_lib.schedule_gossip_step(
                    out, ctx.axis_name, scheds[b % len(scheds)], wire_dtype=wd
                )
        return out, state


def RingGossip(
    rounds: int = 1,
    degree: int = 1,
    *,
    compress: bool = True,
    wire_dtype: str = "float32",
) -> Gossip:
    """The paper's degree-d circular gossip: an alias for
    ``Gossip(rounds, topology=Ring(degree))``.  With ``compress=False``
    (and a full-width wire) uniform ring schedules execute the exact hop
    sequence of the PR-3 ``ring_gossip_average``, bit for bit; the
    default compressed form mixes once with H^B instead (equal up to
    float reassociation)."""
    return Gossip(
        rounds=rounds, topology=Ring(degree=degree),
        compress=compress, wire_dtype=wire_dtype,
    )


# ----------------------------------------------------------- quantized

@dataclass(frozen=True)
class QuantizedGossip(ConsensusPolicy):
    """k-bit links: every exchanged message is quantized before it goes
    on the wire (the first "class of algorithms" in the paper's
    literature review).  ``stochastic=True`` uses unbiased stochastic
    rounding — E[q(x)] = x — so the consensus preserves the
    doubly-stochastic mean in expectation; eq.-15 traffic scales by
    bits/32 (declared via ``wire_bits``).

    ``topology=None`` (default) keeps the original form: one quantized
    all-reduce per ``mix``.  With a topology, each of ``rounds`` gossip
    rounds quantizes the outgoing message and mixes it over the graph's
    exchange schedule — the receiver's own contribution stays
    full-precision (only the wire is narrow)."""

    bits: int = 8
    stochastic: bool = True
    seed: int = 0
    rounds: int = 1
    topology: Topology | None = None

    mode_name = "quantized"

    def __post_init__(self):
        if not 1 <= self.bits <= 32:
            raise ValueError(f"quantization bits must be in [1, 32], got {self.bits}")
        if self.rounds < 1:
            raise ValueError(f"gossip rounds must be >= 1, got {self.rounds}")

    def validate(self, num_workers: int) -> None:
        if self.topology is not None:
            self.topology.validate(num_workers)

    @property
    def wire_bits(self) -> int:  # type: ignore[override]
        return self.bits

    @property
    def exchanges_per_round(self) -> int:
        return self.exchanges_for(None)

    def exchanges_for(self, num_workers: int | None) -> int:
        if self.topology is None:
            return 1
        return _cycle_exchanges(self.topology, self.rounds, num_workers)

    def init_state(self, x, ctx):
        return _worker_key(self.seed, ctx)

    def _quantize(self, x, key):
        if self.stochastic:
            return consensus_lib.quantize_stochastic(x, self.bits, key)
        return consensus_lib.quantize_nearest(x, self.bits)

    def mix(self, x, state, ctx):
        if self.topology is None:
            key, sub = jax.random.split(state)
            return ctx.pmean(self._quantize(x, sub)), key
        scheds = _cycle_schedules(self.topology, ctx)
        key = state
        for b in range(self.rounds):
            key, sub = jax.random.split(key)
            q = self._quantize(x, sub)
            x = consensus_lib.schedule_gossip_step(
                q, ctx.axis_name, scheds[b % len(scheds)], self_value=x
            )
        return x, key


# --------------------------------------------------------------- lossy

@dataclass(frozen=True, init=False)
class LossyGossip(ConsensusPolicy):
    """Gossip over a lossy network: each incoming link fails
    independently with probability ``drop_prob`` per round, and the
    receiver renormalizes its mixing row over surviving links (self-link
    never drops) — row-stochasticity is preserved per round but double
    stochasticity is not, which is exactly why naive lossy gossip biases
    the mean (paper §IV / ref [16] relaxed ADMM).

    ``topology=`` is the authoritative graph; ``degree=d`` is a pure
    construction shorthand for ``topology=Ring(d)`` (the paper's ring
    link model) and is NOT a stored field — ``LossyGossip(degree=2)``
    and ``LossyGossip(topology=Ring(2))`` are the same value object,
    one executable-cache entry, one repr, and ``dataclasses.replace``
    round-trips cleanly (the hand-written ``__init__`` keeps ``degree``
    out of the dataclass fields entirely).  Passing both is an error.
    Per-round link failures never compress (each round draws its own
    survivors), but ``wire_dtype`` narrows the surviving payloads as in
    :class:`Gossip`."""

    drop_prob: float = 0.1
    rounds: int = 1
    seed: int = 0
    topology: Topology | None = None
    wire_dtype: str = "float32"

    mode_name = "lossy"

    def __init__(
        self,
        drop_prob: float = 0.1,
        rounds: int = 1,
        degree: int | None = None,
        seed: int = 0,
        topology: Topology | None = None,
        wire_dtype: str = "float32",
    ):
        if not 0.0 <= drop_prob < 1.0:
            raise ValueError(f"drop_prob must be in [0, 1), got {drop_prob}")
        if rounds < 1:
            raise ValueError(f"gossip rounds must be >= 1, got {rounds}")
        if degree is not None:
            if topology is not None:
                raise ValueError(
                    "pass either degree (the Ring shorthand) or topology=, "
                    "not both"
                )
            topology = Ring(degree)
        elif topology is None:
            topology = Ring(1)
        if not isinstance(topology, Topology):
            raise TypeError(
                f"topology must be a Topology, got {type(topology).__name__}"
            )
        object.__setattr__(self, "drop_prob", drop_prob)
        object.__setattr__(self, "rounds", rounds)
        object.__setattr__(self, "seed", seed)
        object.__setattr__(self, "topology", topology)
        object.__setattr__(
            self, "wire_dtype", consensus_lib.canonical_wire_dtype(wire_dtype)
        )

    @property
    def degree(self) -> int:
        """Legacy ring-degree view (mirrors ``Gossip.degree``); the
        stored ``topology`` is authoritative."""
        return getattr(self.topology, "degree", 1)

    @property
    def wire_bits(self) -> int:  # type: ignore[override]
        return consensus_lib.WIRE_DTYPES[self.wire_dtype]

    def validate(self, num_workers: int) -> None:
        self.topology.validate(num_workers)

    @property
    def exchanges_per_round(self) -> int:
        return self.exchanges_for(None)

    def exchanges_for(self, num_workers: int | None) -> int:
        return _cycle_exchanges(self.topology, self.rounds, num_workers)

    def init_state(self, x, ctx):
        return _worker_key(self.seed, ctx)

    def mix(self, x, state, ctx):
        wd = None if self.wire_dtype == "float32" else self.wire_dtype
        scheds = _cycle_schedules(self.topology, ctx)
        if len(scheds) == 1:
            # Single static schedule: scan the rounds (keeps the traced
            # program O(1) in B — rounds can be large for lossy links).
            def body(carry, _):
                val, key = carry
                key, sub = jax.random.split(key)
                val = consensus_lib.lossy_schedule_gossip_step(
                    val, ctx.axis_name, scheds[0],
                    drop_prob=self.drop_prob, key=sub, wire_dtype=wd,
                )
                return (val, key), None

            (out, key), _ = jax.lax.scan(
                body, (x, state), None, length=self.rounds
            )
            return out, key
        key = state
        for b in range(self.rounds):
            key, sub = jax.random.split(key)
            x = consensus_lib.lossy_schedule_gossip_step(
                x, ctx.axis_name, scheds[b % len(scheds)],
                drop_prob=self.drop_prob, key=sub, wire_dtype=wd,
            )
        return x, key


# --------------------------------------------------------------- stale

@dataclass(frozen=True)
class StaleMixing(ConsensusPolicy):
    """Bounded-staleness asynchrony model (ARock-style, paper ref [15]):
    peers never see this worker's current value — they see the average
    of its last ``delay`` *transmitted* iterates (message ages 1..delay,
    the way asynchronously-arriving gossip messages span a staleness
    window).  The buffer rides in the ADMM scan carry; each worker
    substitutes its own fresh value for its own stale contribution.

    ``delay=0`` is exactly ``ExactMean``; as the ADMM iterates converge,
    the stale window mean converges to the true mean, so the fixed point
    is unchanged.  Like any delayed-feedback loop, tolerance is bounded:
    large ``delay`` combined with a large ADMM coupling ``mu`` can
    oscillate (step-size-vs-staleness, the ARock condition) — delays up
    to ~3 are stable at this repo's default hyper-parameters.

    ``topology=None`` (default) mixes the stale messages with one exact
    all-reduce; a topology mixes them over its exchange schedule instead
    — each worker still substitutes its own FRESH value for its own
    stale contribution (the schedule executor's ``self_value`` hook).
    Time-varying topologies are rejected: one ``mix`` is one schedule
    application, there is no round index to cycle on.
    """

    delay: int = 1
    topology: Topology | None = None
    wire_dtype: str = "float32"

    mode_name = "stale"

    def __post_init__(self):
        if self.delay < 0:
            raise ValueError(f"staleness delay must be >= 0, got {self.delay}")
        object.__setattr__(
            self, "wire_dtype",
            consensus_lib.canonical_wire_dtype(self.wire_dtype),
        )

    def validate(self, num_workers: int) -> None:
        if self.topology is not None:
            if len(self.topology.cycle()) > 1:
                raise ValueError(
                    "StaleMixing applies one schedule per mix; time-varying "
                    "topologies have no round to cycle on"
                )
            self.topology.validate(num_workers)

    @property
    def exchanges_per_round(self) -> int:
        return self.exchanges_for(None)

    def exchanges_for(self, num_workers: int | None) -> int:
        if self.topology is None:
            return 1
        return self.topology.edges_per_node(num_workers)

    @property
    def is_exact(self) -> bool:
        return (
            self.delay == 0
            and self.topology is None
            and self.wire_dtype == "float32"
        )

    @property
    def wire_bits(self) -> int:  # type: ignore[override]
        return consensus_lib.WIRE_DTYPES[self.wire_dtype]

    def _mix_messages(self, msg: Array, fresh: Array, ctx: ConsensusContext):
        """Average the peers' (stale) messages, substituting this
        worker's fresh value for its own stale term."""
        wd = None if self.wire_dtype == "float32" else self.wire_dtype
        if self.topology is None:
            if wd is not None:
                # Model the narrow wire of the all-reduce form: every
                # transmitted message is cast once; this worker swaps its
                # own (narrowed) term for the full-precision fresh value.
                msg = msg.astype(wd).astype(fresh.dtype)
            if fresh is msg:  # delay=0: the message IS the fresh value
                return ctx.pmean(msg)
            return ctx.pmean(msg) + (fresh - msg) / ctx.num_workers
        sched = self.topology.exchange_schedule(ctx.num_workers)
        return consensus_lib.schedule_gossip_step(
            msg, ctx.axis_name, sched, self_value=fresh, wire_dtype=wd
        )

    def init_state(self, x, ctx):
        if self.delay == 0:
            return ()
        # The transmit buffer, oldest first: what peers can see over the
        # next `delay` rounds.  Zeros match the ADMM zero-initialization
        # (O^0 = Lam^0 = 0), i.e. "nothing sent yet".
        return jnp.zeros((self.delay,) + x.shape, x.dtype)

    def mix(self, x, state, ctx):
        if self.delay == 0:
            return self._mix_messages(x, x, ctx), state
        # Strictly pre-push: the current x is NOT in the message.
        msg = state.mean(axis=0)
        new_buf = jnp.concatenate([state[1:], x[None]], axis=0)
        return self._mix_messages(msg, x, ctx), new_buf

    def one_shot(self, x, ctx):
        # A fresh init_state means "nothing transmitted yet" (zeros),
        # which would make a lone mix return x/M — not an average.  For
        # one-shot use, seed the window as if x had been transmitted all
        # along: the steady state, whose mix is exactly the mean (or the
        # topology's one-round H-average of it).
        if self.delay == 0:
            return self._mix_messages(x, x, ctx)
        steady = jnp.broadcast_to(x, (self.delay,) + x.shape)
        out, _ = self.mix(x, steady, ctx)
        return out


# --------------------------------------------------------------- async

#: Byzantine attack kinds the fault model can inject (the ``attack=``
#: grammar): ``signflip`` / ``nanbomb`` take no argument, ``scale:c`` /
#: ``noise:s`` take a float, ``replay:d`` an integer delay >= 1.
_ATTACK_KINDS = ("signflip", "scale", "noise", "nanbomb", "replay")


def _parse_attack(spec: str):
    """``"scale:10"`` -> ``("scale", 10.0)``; validates kind and arg."""
    kind, _, arg = spec.partition(":")
    if kind not in _ATTACK_KINDS:
        raise ValueError(
            f"unknown attack {kind!r}; expected one of {_ATTACK_KINDS} "
            f"(attack spec {spec!r})"
        )
    if kind in ("signflip", "nanbomb"):
        if arg:
            raise ValueError(f"{kind} attack takes no ':' argument ({spec!r})")
        return kind, None
    if not arg:
        raise ValueError(
            f"{kind} attack needs an argument, e.g. '{kind}:2' ({spec!r})"
        )
    if kind == "replay":
        depth = int(arg)
        if depth < 1:
            raise ValueError(f"replay depth must be >= 1, got {depth}")
        return kind, depth
    return kind, float(arg)


@dataclass(frozen=True)
class FaultModel:
    """Deterministic, seeded fault process evaluated INSIDE the SPMD
    program — faults are data, never control flow, so the same cached
    executable serves every realized fault pattern.

    ``drop``: each worker independently misses each gossip round with
    this probability.  The draw folds ``(seed, iteration, round)`` into
    one PRNG key WITHOUT the worker index, so all M workers compute the
    identical (M,) mask at the same trace point — the shared-knowledge
    property the renormalization in
    ``consensus.faulty_schedule_gossip_step`` relies on (and what makes
    the run bit-reproducible across backends).

    ``failed``/``fail_at``: the listed worker slots go down permanently
    once the ADMM iteration counter reaches ``fail_at`` (identity rows
    from then on — the crash-stop model).

    ``stragglers``/``straggle``: the listed workers transmit the value
    they held ``straggle`` communicating rounds ago (zeros before the
    window fills, matching the ADMM zero init); their OWN mixing input
    stays fresh, mirroring :class:`StaleMixing`'s self-substitution.

    ``byzantine``/``attack``: the listed workers substitute a CORRUPTED
    payload on the wire every gossip round (the corruption half PR 6's
    omission faults left out).  ``attack`` is a spec string —
    ``signflip`` (transmit -x), ``scale:c`` (transmit c*x), ``noise:s``
    (transmit x + s*N(0,1), seeded per (iteration, round)), ``nanbomb``
    (transmit all-NaN), ``replay:d`` (transmit the payload from d mixes
    ago, zeros before the window fills).  An attacker's own mixing input
    stays honest — it lies to its peers, not to itself — and the
    corruption is pure data inside the cached SPMD program, so a
    (policy, fault-model) pair lowers exactly once.
    """

    drop: float = 0.0
    seed: int = 0
    fail_at: int | None = None
    failed: tuple[int, ...] = ()
    straggle: int = 1
    stragglers: tuple[int, ...] = ()
    byzantine: tuple[int, ...] = ()
    attack: str = "signflip"

    def __post_init__(self):
        if not 0.0 <= self.drop < 1.0:
            raise ValueError(f"drop must be in [0, 1), got {self.drop}")
        object.__setattr__(
            self, "failed", tuple(sorted(int(i) for i in self.failed))
        )
        object.__setattr__(
            self, "stragglers", tuple(sorted(int(i) for i in self.stragglers))
        )
        object.__setattr__(
            self, "byzantine", tuple(sorted(int(i) for i in self.byzantine))
        )
        if self.failed and self.fail_at is None:
            object.__setattr__(self, "fail_at", 0)
        if self.fail_at is not None and self.fail_at < 0:
            raise ValueError(f"fail_at must be >= 0, got {self.fail_at}")
        if self.straggle < 1:
            raise ValueError(
                f"straggle delay must be >= 1 round, got {self.straggle}"
            )
        _parse_attack(self.attack)  # validate the spec even when unarmed

    @property
    def is_null(self) -> bool:
        """No fault source configured — policies fall through to their
        fault-free (bit-identical) mixing path."""
        return (
            self.drop == 0.0
            and not self.failed
            and not self.stragglers
            and not self.byzantine
        )

    @property
    def attack_kind(self) -> str:
        return _parse_attack(self.attack)[0]

    @property
    def attack_param(self):
        return _parse_attack(self.attack)[1]

    @property
    def replay_depth(self) -> int:
        """Transmit-history window the replay attack needs (0 = none) —
        policies size their scan-carry buffer from this."""
        if self.byzantine and self.attack_kind == "replay":
            return self.attack_param
        return 0

    def validate(self, num_workers: int) -> None:
        for i in self.failed + self.stragglers + self.byzantine:
            if not 0 <= i < num_workers:
                raise ValueError(
                    f"fault model names worker {i}, mesh has {num_workers}"
                )
        if len(set(self.failed)) >= num_workers:
            raise ValueError("fault model permanently fails every worker")
        if len(set(self.byzantine)) >= num_workers:
            raise ValueError("fault model makes every worker Byzantine")

    def corrupted_payload(
        self, x, *, iteration, round_idx: int, replay=None
    ):
        """The wire payload a Byzantine worker transmits in place of
        ``x``.  Pure data — callers select it per worker with
        ``jnp.where`` (never a multiply: NaN * 0 is NaN)."""
        kind, param = _parse_attack(self.attack)
        if kind == "signflip":
            return -x
        if kind == "scale":
            return jnp.asarray(param, x.dtype) * x
        if kind == "nanbomb":
            return jnp.full_like(x, jnp.nan)
        if kind == "replay":
            if replay is None:
                raise ValueError(
                    "replay attack needs the transmit-history buffer "
                    "(policy must thread replay_depth state)"
                )
            return replay
        # noise:s — seeded like the drop draw but on a distinct stream
        # (extra fold), identical on every worker at the same trace point.
        key = jax.random.fold_in(
            jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self.seed), 0x4E5A),
                iteration,
            ),
            round_idx,
        )
        return x + jnp.asarray(param, x.dtype) * jax.random.normal(
            key, x.shape, x.dtype
        )

    def transmit_for(
        self, x, *, worker_index, num_workers: int, iteration,
        round_idx: int, replay=None,
    ):
        """What THIS worker puts on the wire: the corrupted payload on
        Byzantine slots, ``x`` everywhere else (selected with a scalar
        ``jnp.where`` so non-finite attack values never leak into honest
        transmissions)."""
        if not self.byzantine:
            return x
        byz = jnp.asarray(
            self._member_mask(self.byzantine, num_workers), jnp.bool_
        )
        bad = self.corrupted_payload(
            x, iteration=iteration, round_idx=round_idx, replay=replay
        )
        return jnp.where(byz[worker_index], bad, x)

    def _member_mask(self, workers: tuple[int, ...], num_workers: int):
        return np.isin(np.arange(num_workers), workers)

    def alive_mask(self, iteration, round_idx: int, num_workers: int, dtype):
        """(M,) 0/1 up-mask for one gossip round; ``iteration`` may be a
        traced int32 (it indexes the PRNG fold and the fail_at compare,
        both of which trace cleanly)."""
        alive = jnp.ones((num_workers,), dtype)
        if self.drop > 0.0:
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self.seed), iteration),
                round_idx,
            )
            alive = jax.random.bernoulli(
                key, 1.0 - self.drop, (num_workers,)
            ).astype(dtype)
        if self.failed:
            fail = jnp.asarray(
                self._member_mask(self.failed, num_workers), dtype
            )
            down = fail * (
                jnp.asarray(iteration, jnp.int32) >= self.fail_at
            ).astype(dtype)
            alive = alive * (1.0 - down)
        return alive


@dataclass(frozen=True)
class AsyncGossip(ConsensusPolicy):
    """Elastic asynchronous gossip: serial rounds over any topology, a
    per-worker communication interval (mix every ``interval``-th ADMM
    iteration, Bagua-style), and a seeded :class:`FaultModel` running
    inside the cached program.

    With ``interval=N`` the ADMM scan runs N-1 purely local iterations
    per communicating one — structurally (the chunked scan in
    ``admm.worker_admm_iterations``), so the lowered collective count
    and the declared eq.-15 accounting (:meth:`comm_scalars`) both
    scale by 1/N.  ``TimeVarying`` topologies rotate across
    communicating calls: call t starts on phase ``t % L``, giving the
    rotating peer-selection of asynchronous gossip.

    Faults renormalize on the fly (``faulty_schedule_gossip_step``):
    every realized mixing slice stays row-stochastic, and because only
    inverse-closed schedules are admitted under faults (validated), it
    stays mean-preserving on the up set too.  A null fault model falls
    through to the plain serial schedule path — bit-identical to
    ``Gossip(compress=False)`` over the same graph.  Faults and
    membership are VALUES (part of the policy, hence of the executable
    cache key): one lowering per (policy, fault model), no retraces.
    """

    rounds: int = 1
    interval: int = 1
    topology: Topology = Ring(1)
    faults: FaultModel = FaultModel()
    wire_dtype: str = "float32"

    mode_name = "async"

    def __post_init__(self):
        if self.rounds < 1:
            raise ValueError(f"gossip rounds must be >= 1, got {self.rounds}")
        if self.interval < 1:
            raise ValueError(
                f"communication interval must be >= 1, got {self.interval}"
            )
        if not isinstance(self.topology, Topology):
            raise TypeError(
                f"topology must be a Topology, got {type(self.topology).__name__}"
            )
        if not isinstance(self.faults, FaultModel):
            raise TypeError(
                f"faults must be a FaultModel, got {type(self.faults).__name__}"
            )
        object.__setattr__(
            self, "wire_dtype",
            consensus_lib.canonical_wire_dtype(self.wire_dtype),
        )

    @property
    def degree(self) -> int:
        """Legacy ``backend.degree`` view (ring topologies only)."""
        return getattr(self.topology, "degree", 1)

    @property
    def wire_bits(self) -> int:  # type: ignore[override]
        return consensus_lib.WIRE_DTYPES[self.wire_dtype]

    @property
    def communication_interval(self) -> int:
        return self.interval

    def validate(self, num_workers: int) -> None:
        self.topology.validate(num_workers)
        self.faults.validate(num_workers)
        if not self.faults.is_null:
            for phase in self.topology.cycle():
                sched = topology_lib.cached_exchange_schedule(
                    phase, num_workers
                )
                if not topology_lib.is_inverse_closed(sched):
                    raise ValueError(
                        "fault renormalization is mean-preserving only on "
                        "inverse-closed exchange schedules; "
                        f"{phase.describe()} compiles to an asymmetric hop "
                        "set (use a vertex-transitive or Masked topology)"
                    )

    @property
    def exchanges_per_round(self) -> int:
        return self.exchanges_for(None)

    def exchanges_for(self, num_workers: int | None) -> int:
        """Exchanges per COMMUNICATING mix (skipped rounds are accounted
        in :meth:`comm_scalars`, which divides the consensus count)."""
        return _cycle_exchanges(self.topology, self.rounds, num_workers)

    def comm_scalars(
        self, *, scalars: int, num_consensus: int,
        num_workers: int | None = None,
    ) -> int:
        # Only every interval-th consensus call touches the wire.
        return (
            scalars * self.exchanges_for(num_workers)
            * (num_consensus // self.interval)
        )

    def init_state(self, x, ctx):
        t0 = jnp.zeros((), jnp.int32)
        parts = [t0]
        if self.faults.stragglers:
            parts.append(
                jnp.zeros((self.faults.straggle,) + x.shape, x.dtype)
            )
        if self.faults.replay_depth:
            parts.append(
                jnp.zeros((self.faults.replay_depth,) + x.shape, x.dtype)
            )
        return tuple(parts)

    def mix(self, x, state, ctx):
        t = state[0]
        wd = None if self.wire_dtype == "float32" else self.wire_dtype
        scheds = _cycle_schedules(self.topology, ctx)
        faults = self.faults
        # The ADMM iteration this mix call lands on (communicating
        # iterations close each interval chunk) — what fail_at compares
        # against and what seeds the per-round drop draws.
        iteration = t * self.interval + (self.interval - 1)
        me = ctx.worker_index()
        transmit = None
        strag_idx = 1 if faults.stragglers else None
        replay_idx = (2 if faults.stragglers else 1) if faults.replay_depth else None
        if faults.stragglers:
            strag = jnp.asarray(
                faults._member_mask(faults.stragglers, ctx.num_workers),
                x.dtype,
            )
            # Stragglers replay the value transmitted `straggle` calls
            # ago; everyone else sends fresh.
            transmit = x + strag[me] * (state[strag_idx][0] - x)
        replay_val = state[replay_idx][0] if replay_idx is not None else None

        def one_mix(phase: int):
            # Healthy + fresh + single graph: the exact serial-Gossip
            # execution path (fori_loop), so a disabled fault model is
            # bit-identical to ``Gossip(compress=False)``.
            if faults.is_null and transmit is None and len(scheds) == 1:
                return consensus_lib.schedule_gossip_average(
                    x, ctx.axis_name, scheds[0], self.rounds, wire_dtype=wd
                )
            out = x
            for b in range(self.rounds):
                sched = scheds[(phase + b) % len(scheds)]
                tx = transmit if b == 0 else None
                if faults.byzantine:
                    # Attackers corrupt EVERY round's outgoing payload
                    # (what peers receive); the honest base is the
                    # straggler transmit on round 0, the current mixed
                    # value after that.  AsyncGossip trusts what it
                    # receives — it is the vulnerable baseline the
                    # robust policies are measured against.
                    tx = faults.transmit_for(
                        out if tx is None else tx,
                        worker_index=me, num_workers=ctx.num_workers,
                        iteration=iteration, round_idx=b, replay=replay_val,
                    )
                if faults.is_null:
                    if tx is None:
                        out = consensus_lib.schedule_gossip_step(
                            out, ctx.axis_name, sched, wire_dtype=wd
                        )
                    else:
                        out = consensus_lib.schedule_gossip_step(
                            tx, ctx.axis_name, sched, self_value=out,
                            wire_dtype=wd,
                        )
                else:
                    alive = faults.alive_mask(
                        iteration, b, ctx.num_workers, x.dtype
                    )
                    out = consensus_lib.faulty_schedule_gossip_step(
                        out, ctx.axis_name, sched, alive,
                        worker_index=me, transmit=tx, wire_dtype=wd,
                    )
            return out

        if len(scheds) == 1:
            out = one_mix(0)
        else:
            out = jax.lax.switch(
                t % len(scheds),
                [lambda ph=ph: one_mix(ph) for ph in range(len(scheds))],
            )
        new_state = [t + 1]
        for idx in (strag_idx, replay_idx):
            if idx is not None:
                buf = state[idx]
                new_state.append(
                    jnp.concatenate([buf[1:], x[None]], axis=0)
                )
        return out, tuple(new_state)


# ------------------------------------------------- robust aggregation

class _RobustGossipMixin:
    """Shared plumbing for the Byzantine-robust gossip family.

    The contract all three members honor:

    * **Null fault model → plain gossip, bit-for-bit.**  With no
      attackers (and no omission faults) the robust estimator would
      still distort the mean — a trimmed mean of honest payloads is not
      the mean — so the policies delegate to the exact serial-Gossip
      execution path instead, making the zero-attacker case bit-identical
      to ``Gossip(compress=False)`` over the same graph (the same
      fall-through discipline ``AsyncGossip`` uses for omission faults).
    * **Any non-null fault model → robust aggregation every round.**
      Byzantine members corrupt their outgoing payload via
      ``FaultModel.transmit_for`` (inside the cached program — faults
      are data), every incoming payload is screened for non-finite
      values and rerouted to the receiver's diagonal when unhealthy,
      and the surviving neighborhood stack goes through the robust
      estimator (trim / median / clip).
    * An attacker's own mixing input stays honest: it lies on the wire,
      not to itself.
    """

    # Concrete classes: dataclass fields (estimator knob first), a
    # ``mode_name``, and ``_aggregate`` — everything else lives here.

    def _robust_post_init(self):
        if self.rounds < 1:
            raise ValueError(f"gossip rounds must be >= 1, got {self.rounds}")
        if not isinstance(self.topology, Topology):
            raise TypeError(
                f"topology must be a Topology, got {type(self.topology).__name__}"
            )
        if not isinstance(self.faults, FaultModel):
            raise TypeError(
                f"faults must be a FaultModel, got {type(self.faults).__name__}"
            )
        object.__setattr__(
            self, "wire_dtype",
            consensus_lib.canonical_wire_dtype(self.wire_dtype),
        )

    @property
    def degree(self) -> int:
        """Legacy ``backend.degree`` view (ring topologies only)."""
        return getattr(self.topology, "degree", 1)

    @property
    def wire_bits(self) -> int:  # type: ignore[override]
        return consensus_lib.WIRE_DTYPES[self.wire_dtype]

    @property
    def exchanges_per_round(self) -> int:
        return self.exchanges_for(None)

    def exchanges_for(self, num_workers: int | None) -> int:
        return _cycle_exchanges(self.topology, self.rounds, num_workers)

    def validate(self, num_workers: int) -> None:
        self.topology.validate(num_workers)
        self.faults.validate(num_workers)
        if self.faults.stragglers:
            raise ValueError(
                f"{type(self).__name__} transmits fresh payloads only; "
                "model stragglers with AsyncGossip"
            )
        for phase in self.topology.cycle():
            sched = topology_lib.cached_exchange_schedule(phase, num_workers)
            self._validate_schedule(phase, sched)

    def _validate_schedule(self, phase, sched) -> None:
        """Per-phase schedule admission (estimator-specific)."""

    def init_state(self, x, ctx):
        t0 = jnp.zeros((), jnp.int32)
        if self.faults.replay_depth:
            buf = jnp.zeros(
                (self.faults.replay_depth,) + x.shape, x.dtype
            )
            return (t0, buf)
        return (t0,)

    def mix(self, x, state, ctx):
        t = state[0]
        wd = None if self.wire_dtype == "float32" else self.wire_dtype
        scheds = _cycle_schedules(self.topology, ctx)
        faults = self.faults
        me = ctx.worker_index()
        replay_val = state[1][0] if faults.replay_depth else None

        def one_mix(phase: int):
            # Healthy network: the exact serial-Gossip execution path
            # (robust estimation engages only under a non-null fault
            # model — see the class contract above).
            if faults.is_null and len(scheds) == 1:
                return consensus_lib.schedule_gossip_average(
                    x, ctx.axis_name, scheds[0], self.rounds, wire_dtype=wd
                )
            out = x
            for b in range(self.rounds):
                sched = scheds[(phase + b) % len(scheds)]
                if faults.is_null:
                    out = consensus_lib.schedule_gossip_step(
                        out, ctx.axis_name, sched, wire_dtype=wd
                    )
                    continue
                tx = faults.transmit_for(
                    out, worker_index=me, num_workers=ctx.num_workers,
                    iteration=t, round_idx=b, replay=replay_val,
                )
                alive = faults.alive_mask(t, b, ctx.num_workers, x.dtype)
                out = self._aggregate(
                    out, ctx, sched, alive, tx if faults.byzantine else None,
                    wd, me,
                )
            return out

        if len(scheds) == 1:
            out = one_mix(0)
        else:
            out = jax.lax.switch(
                t % len(scheds),
                [lambda ph=ph: one_mix(ph) for ph in range(len(scheds))],
            )
        if faults.replay_depth:
            buf = state[1]
            return out, (t + 1, jnp.concatenate([buf[1:], x[None]], axis=0))
        return out, (t + 1,)


@dataclass(frozen=True)
class TrimmedMeanGossip(_RobustGossipMixin, ConsensusPolicy):
    """Screened trimmed-mean gossip: each round every receiver trims —
    reroutes to its own diagonal — up to ``f`` neighborhood payloads,
    picked as the most-deviant links (Frobenius distance from the
    receiver) that stand beyond the neighborhood scale
    (``consensus.TRIM_SCREEN_FACTOR`` x the median link distance).  The
    surviving links mix with their exact gossip weights, so honest
    traffic is never distorted (the classical coordinate-wise trim
    biases EVERY neighborhood by its honest spread — in consensus ADMM,
    where local updates re-inject disagreement each iteration, that bias
    never vanishes); a Byzantine payload outside the honest spread loses
    its whole link weight, and the reroute keeps the realized mixing row
    stochastic.  Tolerates up to ``f`` attackers per neighborhood within
    the classical breakdown bound ``2f < |neighborhood|``.

    Requires uniform exchange schedules (equal hop weights), where
    "most deviant" needs no per-link weight normalization.
    """

    f: int = 1
    rounds: int = 1
    topology: Topology = Ring(1)
    faults: FaultModel = FaultModel()
    wire_dtype: str = "float32"

    mode_name = "trimmed"

    def __post_init__(self):
        if self.f < 1:
            raise ValueError(
                f"trimmed mean needs f >= 1 (use Gossip for f=0), got {self.f}"
            )
        self._robust_post_init()

    def _validate_schedule(self, phase, sched) -> None:
        if not sched.uniform:
            raise ValueError(
                "trimmed-mean gossip needs a uniform exchange schedule; "
                f"{phase.describe()} compiles to weighted hops"
            )
        stack = len(sched.perms) + 1
        if 2 * self.f >= stack:
            raise ValueError(
                f"trimmed mean with f={self.f} needs a neighborhood of "
                f"> {2 * self.f} payloads; {phase.describe()} gives {stack}"
            )

    def _aggregate(self, out, ctx, sched, alive, tx, wd, me):
        return consensus_lib.trimmed_mean_schedule_gossip_step(
            out, ctx.axis_name, sched, trim=self.f, alive=alive,
            worker_index=me, transmit=tx, wire_dtype=wd,
        )


@dataclass(frozen=True)
class MedianGossip(_RobustGossipMixin, ConsensusPolicy):
    """Coordinate-wise median gossip — the maximal-breakdown member of
    the trimmed-mean family (survives just under half the neighborhood
    being Byzantine, at the price of the largest honest-case bias).
    Uniform schedules only, like :class:`TrimmedMeanGossip`.
    """

    rounds: int = 1
    topology: Topology = Ring(1)
    faults: FaultModel = FaultModel()
    wire_dtype: str = "float32"

    mode_name = "median"

    def __post_init__(self):
        self._robust_post_init()

    def _validate_schedule(self, phase, sched) -> None:
        if not sched.uniform:
            raise ValueError(
                "median gossip needs a uniform exchange schedule; "
                f"{phase.describe()} compiles to weighted hops"
            )

    def _aggregate(self, out, ctx, sched, alive, tx, wd, me):
        return consensus_lib.median_schedule_gossip_step(
            out, ctx.axis_name, sched, alive=alive,
            worker_index=me, transmit=tx, wire_dtype=wd,
        )


@dataclass(frozen=True)
class ClippedGossip(_RobustGossipMixin, ConsensusPolicy):
    """Norm-clipped gossip (centered clipping): each incoming payload's
    offset from self is clipped to radius ``tau`` before the weighted
    mix, bounding any single attacker's per-round influence by
    ``w * tau`` while leaving nearby honest payloads untouched.  Works
    on ANY schedule (weighted hops included) since clipping is
    per-link, not order-statistic.
    """

    tau: float = 1.0
    rounds: int = 1
    topology: Topology = Ring(1)
    faults: FaultModel = FaultModel()
    wire_dtype: str = "float32"

    mode_name = "clipped"

    def __post_init__(self):
        if not self.tau > 0.0:
            raise ValueError(f"clip radius tau must be > 0, got {self.tau}")
        self._robust_post_init()

    def _aggregate(self, out, ctx, sched, alive, tx, wd, me):
        return consensus_lib.clipped_schedule_gossip_step(
            out, ctx.axis_name, sched, tau=self.tau, alive=alive,
            worker_index=me, transmit=tx, wire_dtype=wd,
        )


# ------------------------------------------------------------- parsing

#: Spec-grammar policy names (``parse_policy`` / ``dssfn.parse_spec``).
_MODES = (
    "exact", "gossip", "quantized", "lossy", "stale", "async",
    "trimmed", "median", "clipped",
)


#: Max positional ``:``-separated arguments each policy spec accepts —
#: extra segments are an error, never silently dropped.  ``key=value``
#: segments are counted separately (see ``parse_policy``).
_SPEC_MAX_ARGS = {
    "exact": 0, "gossip": 2, "quantized": 1, "lossy": 3, "stale": 1,
    "async": 0, "trimmed": 0, "median": 0, "clipped": 1,
}


#: One-line-per-entry grammar, quoted in full by unknown-token errors
#: (satellite: today's hint omitted the PR-6 entries).
_POLICY_GRAMMAR = """\
  exact                                   one all-reduce (true mean)
  gossip[:B[:d]]                          B gossip rounds, ring degree d
  quantized[:bits]                        stochastic k-bit quantized gossip
  lossy[:p[:B[:d]]]                       per-link drop probability p
  stale[:delay]                           delayed self-substitution mixing
  async[:key=value...]                    interval= rounds= seed= drop=
                                          fail= fail_at= stragglers=
                                          straggle= byz= attack=
  trimmed[:key=value...]                  f= rounds= + fault keys
  median[:key=value...]                   rounds= + fault keys
  clipped[:tau][:key=value...]            tau= rounds= + fault keys
Any gossip-family policy also takes wire=f32|bf16|f16, and attacks are
signflip | scale:c | noise:s | nanbomb | replay:d (byz= picks workers,
attack= alone defaults to byz=0).  Append @topology to pick the graph:
  ring[:d] | torus:RxC | hypercube | geometric:r[:seed] | full
  ('+'-join phases for a time-varying cycle, e.g. ring:1+hypercube)"""


def _int_list(text: str) -> tuple[int, ...]:
    """``"1+3+6"`` -> ``(1, 3, 6)`` (the spec grammar's worker lists)."""
    return tuple(int(s) for s in text.split("+") if s)


def _faults_from_kv(kv: dict) -> FaultModel:
    """Consume the fault-grammar keys shared by ``async`` and the robust
    policies (``drop``/``seed``/``fail``/``fail_at``/``stragglers``/
    ``straggle``/``byz``/``attack``) out of ``kv``.  ``attack=`` without
    ``byz=`` arms worker 0 — the one-attacker smoke spec."""
    fail_at = kv.pop("fail_at", None)
    attack = kv.pop("attack", None)
    byzantine = _int_list(kv.pop("byz", ""))
    if attack is not None and not byzantine:
        byzantine = (0,)
    return FaultModel(
        drop=float(kv.pop("drop", 0.0)),
        seed=int(kv.pop("seed", 0)),
        fail_at=None if fail_at is None else int(fail_at),
        failed=_int_list(kv.pop("fail", "")),
        straggle=int(kv.pop("straggle", 1)),
        stragglers=_int_list(kv.pop("stragglers", "")),
        byzantine=byzantine,
        attack=attack if attack is not None else "signflip",
    )


def parse_policy(
    spec: str,
    *,
    degree: int = 1,
    rounds: int = 1,
    topology: "Topology | str | None" = None,
) -> ConsensusPolicy:
    """CLI policy specs: ``exact | gossip[:B[:d]] | quantized:bits |
    lossy:p[:B[:d]] | stale:delay | async[:key=value...] |
    trimmed[:key=value...] | median[:key=value...] |
    clipped[:tau][:key=value...]``.

    ``degree``/``rounds`` are the fallbacks for segments the spec leaves
    out (the launcher feeds its legacy ``--degree``/``--rounds`` flags
    here, so ``lossy:0.1 --rounds 10`` means 10 lossy rounds).

    Besides the positional segments, ``key=value`` segments configure
    the orthogonal knobs: ``wire=bf16`` on any gossip-family policy, and
    the async/fault grammar ``async:interval=4:drop=0.1:rounds=2:
    seed=7:fail=2+5:fail_at=30:stragglers=1:straggle=3`` (worker lists
    are ``+``-joined).  The robust policies share the fault keys plus
    the Byzantine pair ``byz=0+3:attack=signflip`` (``attack=`` alone
    arms worker 0): ``trimmed:f=1:attack=signflip``, ``median``,
    ``clipped:tau=0.5:attack=nanbomb``.  Unknown keys are an error,
    never dropped.

    ``topology`` (a ``Topology`` object or ``parse_topology`` spec
    string — the launcher's ``--topology`` flag, or the ``@graph`` half
    of a full ``dssfn.parse_spec`` string) replaces the default ring for
    every gossip-family policy.  Combining it with an explicit
    ring-degree spec segment is ambiguous and rejected; combining it
    with ``exact`` is rejected (an all-reduce has no graph — use
    ``gossip`` with ``topology=FullyConnected()`` for the dense-graph
    gossip form).

    >>> parse_policy("gossip:3").topology
    Ring(degree=1)
    >>> parse_policy("quantized:4").wire_bits
    4
    >>> parse_policy("async:interval=4:drop=0.1").communication_interval
    4
    """
    if isinstance(topology, str):
        topology = parse_topology(topology)
    spec, at, graph = spec.partition("@")
    if at:
        if topology is not None:
            raise ValueError(
                f"policy spec {spec!r}@{graph!r} names an '@topology' AND "
                "one was passed explicitly; drop one of them"
            )
        topology = parse_topology(graph)
    segments = [s for s in spec.split(":") if s]
    name = segments[0] if segments else spec
    args: list[str] = []
    kv: dict[str, str] = {}
    last_key: str | None = None
    for seg in segments[1:]:
        if "=" in seg:
            k, _, v = seg.partition("=")
            if k in kv:
                raise ValueError(
                    f"bad consensus policy spec {spec!r}: duplicate key {k!r}"
                )
            kv[k] = v
            last_key = k
        elif last_key == "attack":
            # Attack specs carry their own ':'-argument (scale:10,
            # noise:0.5, replay:3) — rejoin the segment the outer split
            # took off.
            kv["attack"] += ":" + seg
            last_key = None
        else:
            args.append(seg)
            last_key = None
    if name not in _MODES:
        raise ValueError(
            f"unknown consensus policy {name!r} (spec {spec!r}); "
            f"the full grammar:\n{_POLICY_GRAMMAR}"
        )
    if len(args) > _SPEC_MAX_ARGS[name]:
        raise ValueError(
            f"bad consensus policy spec {spec!r}: {name} takes at most "
            f"{_SPEC_MAX_ARGS[name]} positional ':'-argument(s), got {len(args)}"
        )
    if topology is not None and name == "exact":
        raise ValueError(
            f"bad consensus policy spec {spec!r}: exact consensus is a "
            "single all-reduce and takes no topology (use a gossip-family "
            "policy)"
        )
    try:
        wire = kv.pop("wire", None)
        if wire is not None and name in ("exact", "quantized"):
            raise ValueError(f"{name} takes no wire= (it has no gossip link)")
        wire = consensus_lib.canonical_wire_dtype(wire or "float32")
        if name == "async":
            b = int(kv.pop("rounds", rounds))
            interval = int(kv.pop("interval", 1))
            faults = _faults_from_kv(kv)
            if kv:
                raise ValueError(f"unknown async key(s) {sorted(kv)}")
            return AsyncGossip(
                rounds=b, interval=interval,
                topology=topology if topology is not None else Ring(degree),
                faults=faults, wire_dtype=wire,
            )
        if name in ("trimmed", "median", "clipped"):
            b = int(kv.pop("rounds", rounds))
            graph = topology if topology is not None else Ring(degree)
            if name == "trimmed":
                f = int(kv.pop("f", 1))
                faults = _faults_from_kv(kv)
                if kv:
                    raise ValueError(f"unknown trimmed key(s) {sorted(kv)}")
                return TrimmedMeanGossip(
                    f=f, rounds=b, topology=graph, faults=faults,
                    wire_dtype=wire,
                )
            if name == "median":
                faults = _faults_from_kv(kv)
                if kv:
                    raise ValueError(f"unknown median key(s) {sorted(kv)}")
                return MedianGossip(
                    rounds=b, topology=graph, faults=faults, wire_dtype=wire,
                )
            tau_kv = kv.pop("tau", None)
            if tau_kv is not None and args:
                raise ValueError(
                    "pass the clip radius either positionally "
                    "(clipped:0.5) or as tau=, not both"
                )
            tau = float(
                tau_kv if tau_kv is not None else (args[0] if args else 1.0)
            )
            faults = _faults_from_kv(kv)
            if kv:
                raise ValueError(f"unknown clipped key(s) {sorted(kv)}")
            return ClippedGossip(
                tau=tau, rounds=b, topology=graph, faults=faults,
                wire_dtype=wire,
            )
        if kv:
            raise ValueError(f"unknown {name} key(s) {sorted(kv)}")
        if name == "exact":
            return ExactMean()
        if name == "gossip":
            b = int(args[0]) if args else rounds
            if topology is not None:
                if len(args) > 1:
                    raise ValueError(
                        "pass either a ring degree segment or topology=, "
                        "not both"
                    )
                return Gossip(rounds=b, topology=topology, wire_dtype=wire)
            deg = int(args[1]) if len(args) > 1 else degree
            return RingGossip(rounds=b, degree=deg, wire_dtype=wire)
        if name == "quantized":
            bits = int(args[0]) if args else 8
            if topology is not None:
                return QuantizedGossip(bits=bits, rounds=rounds, topology=topology)
            return QuantizedGossip(bits=bits)
        if name == "lossy":
            p = float(args[0]) if args else 0.1
            b = int(args[1]) if len(args) > 1 else rounds
            if topology is not None:
                if len(args) > 2:
                    raise ValueError(
                        "pass either a ring degree segment or topology=, "
                        "not both"
                    )
                return LossyGossip(
                    drop_prob=p, rounds=b, topology=topology, wire_dtype=wire
                )
            deg = int(args[2]) if len(args) > 2 else degree
            return LossyGossip(
                drop_prob=p, rounds=b, degree=deg, wire_dtype=wire
            )
        return StaleMixing(
            delay=int(args[0]) if args else 1, topology=topology,
            wire_dtype=wire,
        )
    except ValueError as e:
        # int()/float() parse failures and constructor validation errors,
        # re-raised with the offending spec attached.
        raise ValueError(f"bad consensus policy spec {spec!r}: {e}") from e
