"""Consensus execution backends: one SPMD worker program, two runtimes.

The paper's Algorithm 1 is a per-worker program that communicates only
through a single "average over the graph" primitive.  This module makes
that structure explicit: solvers are written as *worker-local* functions
(no leading worker axis) that talk to peers exclusively through the
collectives on :class:`ConsensusBackend`, and the backend decides how the
M worker instances actually execute:

- :class:`SimulatedBackend` — all workers live in one process as the
  leading axis of a single array; execution is ``jax.vmap`` with a named
  axis, so ``lax.pmean``/``lax.ppermute`` resolve against the batched
  axis.  This is the reproduction/test layout (what the repo previously
  hard-coded in ``core/admm.py``).
- :class:`MeshBackend` — real SPMD over a named mesh axis via
  ``jax.shard_map``: each worker's shard lives device-local, ``pmean``
  lowers to an all-reduce on the interconnect and ring gossip to
  ``collective_permute`` hops (ICI-torus native).

Because both backends execute the *same traced worker program*, the
centralized-equivalence tests transfer verbatim from the simulation to
the mesh — which is the point of the paper.

Consensus (both backends) is a pluggable :class:`~repro.core.policy.
ConsensusPolicy` strategy object: ``ExactMean`` (one all-reduce, the
B -> infinity limit), ``Gossip`` (B rounds of doubly-stochastic gossip
over a first-class ``repro.core.topology.Topology`` — ring, torus,
hypercube, fully-connected, random-geometric, time-varying — whose
static exchange schedule runs as ``lax.ppermute`` hops),
``QuantizedGossip``, ``LossyGossip``, ``StaleMixing`` and the
fault-tolerant ``AsyncGossip`` (each of which also takes ``topology=``).
``RingGossip`` is the bit-identical ring-topology alias.  Policy objects
(or spec strings via :func:`make_backend`) are the single entry point:
the pre-policy ``mode=``/``degree=``/``num_rounds=`` string aliases were
removed and now raise ``TypeError`` with a migration hint.

Executable cache
----------------
Both backends memoize their lowered executables.  ``run``/``map_workers``
wrap the worker program in ``jax.jit`` exactly once per cache key and
reuse that jit object on every later call, so an L-layer dSSFN train with
repeated hidden widths compiles each *distinct operand shape* exactly
once instead of re-tracing per layer solve (the pre-engine behaviour:
a fresh ``jax.jit(shard_map(...))`` per call).  The cache key is

    (explicit ``key`` or the worker-fn object itself,
     number of stacked/replicated operands, donation set)

and jit's own shape/dtype dispatch handles the rest.  Callers that
rebuild their worker closure per call (the dSSFN layer engine) MUST pass
an explicit ``key`` capturing every closed-over value that changes the
trace (mu, K, kernel routing, ...); array state must then be passed as an
operand — stacked or ``replicated`` — never closed over, because the
first trace would bake it into every later run.
"""
from __future__ import annotations

import abc
from collections import OrderedDict
from typing import Any, Callable, Hashable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import policy as policy_lib
from repro.core.policy import ConsensusContext, ConsensusPolicy

Array = jax.Array

#: Canonical mesh-axis name for the ADMM worker dimension.
WORKER_AXIS = "workers"

#: Bound on memoized executables per backend instance.  Callers that pass
#: a fresh closure per call without an explicit ``key`` create one entry
#: each; FIFO eviction keeps that pattern correct (just uncached).
_EXEC_CACHE_SIZE = 64


def _supports_donation() -> bool:
    """XLA ignores donation on CPU (with a warning) — skip it there."""
    return jax.default_backend() != "cpu"


def _put(x, sharding):
    """``jax.device_put`` that returns an array already in ``sharding``
    as it is: the layer loop hands the same placed operands to every
    layer program, and a put that moves nothing still costs the host."""
    if getattr(x, "sharding", None) == sharding:
        return x
    return jax.device_put(x, sharding)


def _reject_legacy_kwargs(name: str, kwargs: dict) -> None:
    """The PR-3 ``mode=`` string aliases are gone: fail with a migration
    hint (a clean ``TypeError``, the unknown-keyword contract) instead of
    silently accepting configuration that no longer does anything."""
    legacy = sorted(k for k in kwargs if k in ("mode", "degree", "num_rounds"))
    if legacy:
        raise TypeError(
            f"{name}() no longer accepts {', '.join(legacy)}: the string-"
            "mode aliases were removed. Pass policy=ExactMean() for "
            "mode='exact', policy=RingGossip(rounds=num_rounds, "
            "degree=degree) for mode='gossip', or a spec string such as "
            "'gossip:4:2' (repro.core.policy.parse_policy)."
        )
    if kwargs:
        raise TypeError(
            f"{name}() got unexpected keyword argument(s) {sorted(kwargs)}"
        )


def _closes_over_arrays(fn) -> bool:
    """True if ``fn`` captures jax/numpy arrays in its closure cells.

    Identity-keyed caching would bake such arrays into the first trace as
    constants and silently reuse them if the caller ever rebound the cell
    — so those fns are executed uncached unless an explicit ``key``
    (plus operand-passing) is used.  Arrays reached through *globals*
    cannot be detected this way; passing them as operands with an
    explicit key is the supported pattern.
    """
    import numpy as np

    cells = getattr(fn, "__closure__", None) or ()
    for cell in cells:
        try:
            contents = cell.cell_contents
        except ValueError:  # empty cell
            continue
        for leaf in jax.tree.leaves(contents):
            if isinstance(leaf, (jax.Array, np.ndarray)):
                return True
    return False


class ConsensusBackend(abc.ABC):
    """Executes per-worker SPMD functions and provides their collectives.

    A "worker function" passed to :meth:`run` receives this worker's LOCAL
    slices of the stacked ``(M, ...)`` operands (leading axis stripped),
    then any ``replicated`` operands whole, and may communicate with peers
    only through :meth:`consensus_mean`, :meth:`psum`, :meth:`pmax` and
    :meth:`worker_index`.  Static hyper-parameters may be closed over
    (fold them into ``key``); array state must be an operand.
    :meth:`run` returns every output re-stacked to ``(M, ...)``.
    """

    axis_name: str
    num_workers: int
    policy: ConsensusPolicy

    def _init_consensus(self, policy: ConsensusPolicy | None) -> None:
        if policy is None:
            policy = policy_lib.ExactMean()
        if not isinstance(policy, ConsensusPolicy):
            raise TypeError(
                f"policy must be a ConsensusPolicy, got {type(policy).__name__}"
            )
        policy.validate(self.num_workers)
        self.policy = policy
        # Executable cache: (key, n_stacked, n_replicated, donate, collective)
        # -> jitted callable.  ``lowerings`` counts actual traces; the
        # compile-count regression test asserts it equals the number of
        # distinct layer shapes, not the number of layer solves.
        self._exec_cache: OrderedDict[Hashable, Callable] = OrderedDict()
        self.lowerings = 0
        self.cache_hits = 0

    # Legacy attribute views over the policy (pre-policy API surface).
    @property
    def mode(self) -> str:
        return self.policy.mode_name

    @property
    def degree(self) -> int:
        return getattr(self.policy, "degree", 1)

    @property
    def num_rounds(self) -> int:
        return getattr(self.policy, "rounds", 1)

    def ctx(self) -> ConsensusContext:
        """The collectives handle policies mix through — valid inside a
        function passed to :meth:`run`."""
        return ConsensusContext(self.axis_name, self.num_workers)

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------
    def run(
        self,
        fn: Callable[..., Any],
        *stacked_args: Array,
        replicated: tuple = (),
        key: Hashable | None = None,
        donate: tuple[int, ...] = (),
        policy: ConsensusPolicy | None = None,
    ) -> Any:
        """Run ``fn`` once per worker; stacked (M, ...) in and out.

        replicated: extra operands every worker sees whole (shared weights).
        key: explicit executable-cache key; REQUIRED for correctness when
            the same logical program is re-wrapped in a fresh closure per
            call (it must capture every trace-affecting closed-over value).
        donate: indices into ``stacked_args`` whose buffers the caller no
            longer needs — donated to XLA off-CPU (the O/Λ/Y carries of
            the dSSFN layer engine).
        policy: the consensus policy this program runs under, when it is
            not the backend default.  ``fn`` must close over the policy
            object itself (policies are static config; see
            ``engine.layer_program``); passing it here makes it part
            of the executable-cache key, so one lowering per
            (program, policy) pair and no stale-executable reuse.
        """
        return self._cached_call(
            fn, stacked_args, replicated, key, donate, collective=True,
            policy=policy,
        )

    def map_workers(
        self,
        fn: Callable[..., Any],
        *stacked_args: Array,
        replicated: tuple = (),
        key: Hashable | None = None,
        donate: tuple[int, ...] = (),
    ) -> Any:
        """Like :meth:`run` for collective-free, purely local ``fn``."""
        return self._cached_call(
            fn, stacked_args, replicated, key, donate, collective=False
        )

    def shard_workers(self, x: Array) -> Array:
        """Place a stacked (M, ...) array in this backend's worker layout."""
        return x

    # ------------------------------------------------------------------
    # Executable cache
    # ------------------------------------------------------------------
    def _lookup_executable(
        self, fn, stacked_args, replicated, key, donate, collective, policy=None
    ):
        """The jitted callable for this program, via the FIFO cache."""
        self._check_stacked(stacked_args)
        donate = tuple(sorted(donate))
        if any(i < 0 or i >= len(stacked_args) for i in donate):
            raise ValueError(f"donate indices {donate} out of range")
        if key is None and _closes_over_arrays(fn):
            # Identity-keyed caching would freeze the closed-over arrays
            # into the first trace; keep the pre-cache per-call semantics
            # for this pattern (callers wanting the cache pass arrays as
            # operands with an explicit key — see the module docstring).
            return self._build_executable(
                fn, len(stacked_args), len(replicated), donate, collective
            )
        cache_key = (
            key if key is not None else fn,
            len(stacked_args),
            len(replicated),
            donate,
            collective,
            policy,
        )
        jitted = self._exec_cache.get(cache_key)
        if jitted is None:
            jitted = self._build_executable(
                fn, len(stacked_args), len(replicated), donate, collective
            )
            self._exec_cache[cache_key] = jitted
            while len(self._exec_cache) > _EXEC_CACHE_SIZE:
                self._exec_cache.popitem(last=False)
        else:
            self.cache_hits += 1
        return jitted

    def _cached_call(
        self, fn, stacked_args, replicated, key, donate, collective, policy=None
    ):
        jitted = self._lookup_executable(
            fn, stacked_args, replicated, key, donate, collective, policy
        )
        args = tuple(self.shard_workers(a) for a in stacked_args)
        return jitted(*args, *self._place_replicated(replicated))

    def lowering_stats(
        self,
        fn: Callable[..., Any],
        *stacked_args: Array,
        replicated: tuple = (),
        key: Hashable | None = None,
        donate: tuple[int, ...] = (),
        policy: ConsensusPolicy | None = None,
    ) -> dict:
        """Compile the worker program WITHOUT running it and report what
        the lowering actually contains.

        Returns ``{"collective_counts": {op: count}, "collective_wire_bytes":
        float, "flops": float}`` from the compiled (post-SPMD) HLO via
        ``repro.launch.hlo_analysis`` — counts include while-loop trip
        multipliers, so a K-iteration ADMM scan with one all-reduce per
        iteration reports ``K`` all-reduces — plus ``memory_bytes``, the
        arguments, outputs and temporaries the compiler allots the
        program on each device.  This is the assertion
        surface for the collective-free hot path: a ``trace_every=0``
        program must contain only the policy's own exchanges.

        Collectives resolve to HLO ops only under :class:`MeshBackend`
        (vmap's named-axis collectives are traced away); call it on the
        mesh backend you intend to run on.  Shares the executable cache
        with :meth:`run` — same arguments, same cached jit object.
        """
        from repro.launch.hlo_analysis import analyze_module

        jitted = self._lookup_executable(
            fn, stacked_args, replicated, key, donate, collective=True,
            policy=policy,
        )
        args = tuple(self.shard_workers(a) for a in stacked_args)
        compiled = jitted.lower(*args, *self._place_replicated(replicated)).compile()
        analysis = analyze_module(compiled.as_text())
        mem = compiled.memory_analysis()
        return {
            "collective_counts": analysis.collective_counts(),
            "collective_wire_bytes": analysis.collective_wire_bytes,
            "collective_by_type": analysis.collective_by_type(),
            "flops": analysis.flops,
            "memory_bytes": (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
            ),
        }

    def lowering_texts(
        self,
        fn: Callable[..., Any],
        *stacked_args: Array,
        replicated: tuple = (),
        key: Hashable | None = None,
        donate: tuple[int, ...] = (),
        policy: ConsensusPolicy | None = None,
    ) -> dict:
        """Lower the worker program WITHOUT running it and return both
        program texts: ``{"stablehlo": ..., "hlo": ...}``.

        ``stablehlo`` is the pre-optimization trace — traced dtypes
        survive verbatim, which is what ``repro.analysis.numerics``
        lints (the CPU compiler upcasts bf16/f16 arithmetic to f32, so
        the compiled text cannot show a half-precision accumulate).
        ``hlo`` is the compiled (post-SPMD) module the wire-budget
        checker counts collectives in.  Shares the executable cache
        with :meth:`run`/:meth:`lowering_stats`.
        """
        jitted = self._lookup_executable(
            fn, stacked_args, replicated, key, donate, collective=True,
            policy=policy,
        )
        args = tuple(self.shard_workers(a) for a in stacked_args)
        lowered = jitted.lower(*args, *self._place_replicated(replicated))
        return {
            "stablehlo": lowered.as_text(),
            "hlo": lowered.compile().as_text(),
        }

    def _count_trace(self) -> None:
        # Runs at trace time only: executions served from jit's dispatch
        # cache never re-enter the wrapped Python function.
        self.lowerings += 1

    def cache_info(self) -> dict:
        """Executable-cache counters, in the normalized schema shared
        with ``ServeEngine.cache_info`` (``repro.analysis.retrace``
        drives both): ``entries``/``lowerings``/``cache_hits`` plus
        ``keys``, the cache keys as repr strings (backend keys contain
        functions and policy objects, so reprs are the JSON-safe form).
        """
        return {
            "entries": len(self._exec_cache),
            "lowerings": self.lowerings,
            "cache_hits": self.cache_hits,
            "keys": [repr(k) for k in self._exec_cache],
        }

    @property
    def replicated_sharding(self) -> NamedSharding | None:
        """Where an operand every worker sees whole lives: None here (the
        default device), the mesh-replicated sharding on a mesh."""
        return None

    def _place_replicated(self, replicated: tuple) -> tuple:
        return replicated

    @abc.abstractmethod
    def _build_executable(
        self, fn, n_stacked: int, n_replicated: int, donate, collective: bool
    ) -> Callable:
        """Wrap ``fn`` into a jitted stacked-in/stacked-out callable."""

    def _check_stacked(self, stacked_args) -> None:
        for a in stacked_args:
            if a.shape[0] != self.num_workers:
                raise ValueError(
                    f"stacked operand has leading dim {a.shape[0]}, "
                    f"backend has {self.num_workers} workers"
                )

    # ------------------------------------------------------------------
    # Collectives — valid only inside a function passed to ``run``.
    # ------------------------------------------------------------------
    def consensus_mean(self, x: Array) -> Array:
        """The paper's graph-average primitive (Algorithm 1, line 8).

        One-shot mix under this backend's policy, from a fresh policy
        state.  Loops that call the policy repeatedly (the ADMM scan)
        should instead thread ``policy.mix``'s state through their carry
        — see ``admm.worker_admm_iterations``.
        """
        return self.policy.one_shot(x, self.ctx())

    def exact_mean(self, x: Array) -> Array:
        """True mean regardless of mode (diagnostics: consensus error)."""
        return jax.lax.pmean(x, self.axis_name)

    def psum(self, x: Array) -> Array:
        return jax.lax.psum(x, self.axis_name)

    def pmax(self, x: Array) -> Array:
        return jax.lax.pmax(x, self.axis_name)

    def worker_index(self) -> Array:
        return jax.lax.axis_index(self.axis_name)

    # ------------------------------------------------------------------
    # Communication accounting (paper eq. 15)
    # ------------------------------------------------------------------
    def exchanges_per_consensus(self) -> int:
        """Peer messages each worker sends per ``consensus_mean`` call.

        Exact consensus is one all-reduce (B=1 in the eq. 15 accounting);
        topology gossip sends to ``edges_per_node`` neighbours for each
        of B rounds.  Delegates to the policy's M-aware
        ``exchanges_for`` (graph degree can depend on the worker count —
        hypercube, fully-connected).
        """
        return self.policy.exchanges_for(self.num_workers)

    def describe(self) -> str:
        return (
            f"{type(self).__name__}(M={self.num_workers}, "
            f"policy={self.policy.describe()})"
        )


class SimulatedBackend(ConsensusBackend):
    """Workers as a vmapped leading axis of one array (single device).

    ``jax.vmap`` with ``axis_name`` gives the worker program a named axis,
    so the very same ``pmean``/``ppermute`` collectives the mesh backend
    lowers to hardware resolve here against the batched axis.
    """

    def __init__(
        self,
        num_workers: int,
        *,
        policy: ConsensusPolicy | None = None,
        axis_name: str = WORKER_AXIS,
        **removed,
    ):
        _reject_legacy_kwargs("SimulatedBackend", removed)
        if num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {num_workers}")
        self.num_workers = int(num_workers)
        self.axis_name = axis_name
        self._init_consensus(policy)

    def _build_executable(self, fn, n_stacked, n_replicated, donate, collective):
        def counted(*args):
            self._count_trace()
            return fn(*args)

        in_axes = (0,) * n_stacked + (None,) * n_replicated
        kwargs = {"axis_name": self.axis_name} if collective else {}
        mapped = jax.vmap(counted, in_axes=in_axes, **kwargs)
        donate_argnums = donate if _supports_donation() else ()
        return jax.jit(mapped, donate_argnums=donate_argnums)


class MeshBackend(ConsensusBackend):
    """Real SPMD workers: one per mesh slot along a named ``workers`` axis.

    Per-worker shards live device-local; ``consensus_mean`` is a hardware
    all-reduce (exact) or ``collective_permute`` ring hops (gossip).  On
    CPU, fake an M-device host mesh with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=M`` *before* jax
    initializes (see ``launch/train_dssfn.py``).
    """

    def __init__(
        self,
        mesh: Mesh | None = None,
        *,
        policy: ConsensusPolicy | None = None,
        axis_name: str = WORKER_AXIS,
        **removed,
    ):
        _reject_legacy_kwargs("MeshBackend", removed)
        if mesh is None:
            from repro.launch.mesh import make_worker_mesh

            mesh = make_worker_mesh()
        if axis_name not in mesh.axis_names:
            raise ValueError(
                f"mesh has axes {mesh.axis_names}, no {axis_name!r} axis"
            )
        self.mesh = mesh
        self.axis_name = axis_name
        self.num_workers = int(
            mesh.devices.shape[mesh.axis_names.index(axis_name)]
        )
        self._init_consensus(policy)

    def shard_workers(self, x: Array) -> Array:
        spec = [None] * jnp.ndim(x)
        spec[0] = self.axis_name
        return _put(x, NamedSharding(self.mesh, P(*spec)))

    @property
    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def _place_replicated(self, replicated: tuple) -> tuple:
        sharding = self.replicated_sharding
        return tuple(_put(r, sharding) for r in replicated)

    # On a mesh, a collective-free fn is just a shard_map whose program
    # happens to contain no collectives — the same execution path, so
    # ``collective`` does not change the built executable.
    def _build_executable(self, fn, n_stacked, n_replicated, donate, collective):
        from repro.sharding.rules import shard_map_compat

        def local(*local_args):
            self._count_trace()
            # shard_map hands each worker a (1, ...) slice of the stacked
            # operands; strip it so fn sees the same local view as vmap.
            # Replicated operands arrive whole.
            stacked = [a[0] for a in local_args[:n_stacked]]
            out = fn(*stacked, *local_args[n_stacked:])
            return jax.tree.map(lambda o: jnp.asarray(o)[None], out)

        in_specs = (P(self.axis_name),) * n_stacked + (P(),) * n_replicated
        mapped = shard_map_compat(
            local,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=P(self.axis_name),
        )
        donate_argnums = donate if _supports_donation() else ()
        return jax.jit(mapped, donate_argnums=donate_argnums)


def make_backend(
    kind: str,
    num_workers: int | None = None,
    *,
    mesh: Mesh | None = None,
    policy: ConsensusPolicy | str | None = None,
    degree: int = 1,
    **removed,
) -> ConsensusBackend:
    """CLI-friendly factory: kind in {'simulated', 'mesh'}.

    ``policy`` selects the consensus flavor — a ConsensusPolicy object or
    a spec string (``"exact"``, ``"gossip:4:2"``, ``"quantized:8"``,
    ``"lossy:0.1"``, ``"stale:2"``, ``"async:interval=4:drop=0.1"``; see
    ``policy.parse_policy``).  ``degree`` is the ring degree used when a
    spec string leaves it implicit.  The pre-PR-3 ``mode=``/``num_rounds=``
    keyword aliases were removed; passing them raises TypeError.
    """
    _reject_legacy_kwargs("make_backend", removed)
    if isinstance(policy, str):
        policy = policy_lib.parse_policy(policy, degree=degree)
    if kind == "simulated":
        if num_workers is None:
            raise ValueError("simulated backend requires num_workers")
        return SimulatedBackend(num_workers, policy=policy)
    if kind == "mesh":
        return MeshBackend(mesh, policy=policy)
    raise ValueError(f"unknown backend kind {kind!r}; expected 'simulated' or 'mesh'")
