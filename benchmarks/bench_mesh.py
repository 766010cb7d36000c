"""Simulated vs mesh consensus backends: per-ADMM-iteration cost, consensus
bytes moved, compile-once engine vs legacy re-trace, and parity.

The tentpole measurement for the compile-once layer engine: the SAME
worker program (``core.engine.layer_program`` at l=0) timed under

  - ``SimulatedBackend``  (vmap worker axis, single device), and
  - ``MeshBackend``       (shard_map, one worker per device slot),

in both exact (``lax.pmean``) and degree-d ring-gossip (``lax.ppermute``)
consensus modes, plus the Pallas kernel path (``use_kernels=True`` — the
shapes below are 128-aligned so the ``gram`` kernel really runs, and the
``mesh_layer_step[_kernels]`` rows time ``engine.fused_layer_step`` with
a weight operand so the fused ``propagate_gram`` kernel runs too).
Each backend is exercised twice: the steady-state call hits the
backend's executable cache, while a fresh backend per call measures the
cache-off cost — for the mesh rows that is exactly the pre-engine
behaviour (a new ``jax.jit(shard_map(...))`` per solve, reported as
``legacy_*``); the pre-engine sim path was an eager vmap, so sim rows
label the same figure ``uncached_*``.  Communication is reported with
the paper's eq.-15
accounting (Q * n scalars per exchange, B exchanges per consensus, K
consensus rounds), i.e. bytes each worker puts on the wire per solve.

Besides the CSV rows for ``python -m benchmarks.run``, emits a
machine-readable ``BENCH_mesh.json`` (repo root) so the perf trajectory
is tracked across PRs:

  compile_s         first mesh-exact solve (trace + compile + run)
  iter_ms           steady-state per-ADMM-iteration wall time (cached)
  legacy_iter_ms    the same solve with a per-call re-trace (pre-engine)
  bytes_per_worker  eq.-15 wire bytes per worker per solve

plus a ``policies`` section — one row per ConsensusPolicy (exact /
gossip / quantized / lossy / stale) through a single shared mesh backend
(one lowering per policy), with ``bytes_per_worker`` scaled by the
policy's declared ``wire_bits`` — and a ``topologies`` section relating
each first-class mixing graph (ring / torus / hypercube / full /
geometric) to its predicted spectral gap: per topology the predicted
``spectral_gap``/``rounds_for_tolerance``, the measured ``iter_ms`` and
``oracle_rel`` convergence of a fixed-round ``Gossip`` solve, and the
eq.-15 ``bytes_per_worker`` derived from ``edges_per_node``.

The ``wire`` section tracks the wire-efficient consensus engine:

  schedule     compressed (ONE H^B mix via power_schedule) vs serial
               (B hop-by-hop rounds) gossip at rounds=4 over the ring —
               iter_ms, hops_per_mix, and the compression speedup;
  dtypes       the same gossip under f32 / bf16 / f16 link payloads —
               iter_ms, wire_bits-scaled bytes_per_worker, oracle_rel;
  trace_every  traced (per-iteration psum/pmax trio) vs hot
               (trace_every=0, policy exchanges only) solve cost.

The ``faults`` section tracks elastic asynchronous consensus: an
``AsyncGossip`` sweep over drop rate x communication interval (one
cached executable per (drop, interval) policy value) reporting
``iter_ms``, interval-aware eq.-15 ``bytes_per_worker``, and the
``oracle_rel`` convergence cost of the injected faults.

The ``byzantine`` section tracks robust aggregation under seeded
attacks: an attack (none / signflip / nanbomb) x policy (trimmed /
median / clipped, plus the vulnerable async baseline) sweep through one
shared backend — per cell the ``iter_ms`` robustness overhead, the
``oracle_rel`` against the honest-data oracle (attacked rows measure
against the leave-one-out solution: a Byzantine worker's shard is
unlearnable since every payload it emits is corrupted), and the
``jitter_events`` count from the guarded Cholesky.  One lowering per
(policy, fault-model) value — attacks are data, not structure.

Regression gate: ``--check-regression`` (or env
``BENCH_CHECK_REGRESSION=1``, used by the CI smoke job) loads the
previously committed JSON before overwriting it and fails if any
backend's ``iter_ms`` regressed more than ``BENCH_REGRESSION_FACTOR``
(default 0.25 = +25%).

Standalone (fakes an 8-device host mesh before jax initializes)::

    python -m benchmarks.bench_mesh [--workers 8] [--json BENCH_mesh.json]
        [--check-regression]

Under ``python -m benchmarks.run`` the harness uses whatever devices
exist (the CI multi-device job exports XLA_FLAGS for 8).
"""
from __future__ import annotations

import os


# 128-aligned so the Pallas gram/propagate_gram kernel paths are actually
# exercised (J_m > n keeps local Grams full rank).
N_FEATURES = 128
NUM_CLASSES = 6
SAMPLES_PER_WORKER = 128
ADMM_ITERS = 60
GOSSIP_DEGREE = 2
GOSSIP_ROUNDS = 4
BYTES_PER_SCALAR = 4  # float32

DEFAULT_JSON = "BENCH_mesh.json"


def _consensus_bytes(policy, n: int, q: int, num_iters: int, m: int) -> int:
    """Eq.-15 wire bytes per worker for one ADMM solve, at the policy's
    declared link width (``ConsensusPolicy.wire_bytes``); M-aware since
    topology degree can depend on the worker count."""
    return policy.wire_bytes(scalars=q * n, num_consensus=num_iters, num_workers=m)


def _torus_shape(m: int) -> tuple[int, int] | None:
    """Most-square rows x cols factorization with both sides >= 2."""
    for r in range(int(m ** 0.5), 1, -1):
        if m % r == 0 and m // r >= 2:
            return r, m // r
    return None


#: The sections the regression gate walks (benchmarks.common holds the
#: shared check_regression/gate_and_write implementation).
GATE_SECTIONS = ("backends", "byzantine")


def check_regression(
    baseline: dict, fresh: dict, threshold: float = 0.25
) -> list[str]:
    """Per-backend iter_ms regressions beyond ``threshold`` (fractional);
    the shared ``benchmarks.common.check_regression`` over this bench's
    sections."""
    from benchmarks.common import check_regression as shared

    return shared(baseline, fresh, threshold, sections=GATE_SECTIONS)


def run(
    verbose: bool = True,
    num_workers: int | None = None,
    json_path: str | None = DEFAULT_JSON,
    check: bool | None = None,
) -> list[str]:
    import jax
    import jax.numpy as jnp

    from benchmarks.common import csv_row, timed
    from repro.core import admm
    from repro.core.backend import MeshBackend, SimulatedBackend
    from repro.core.policy import (
        ExactMean,
        LossyGossip,
        QuantizedGossip,
        RingGossip,
        StaleMixing,
    )
    from repro.launch.mesh import make_worker_mesh

    def steady(fn, *args, repeats=5):
        """Steady-state timing: best of ``repeats`` cached calls.  The
        shared CI runners throttle in bursts; the min is the robust
        estimator of the program's actual cost (and what keeps the
        --check-regression gate meaningful at a 25% threshold)."""
        out, best = timed(fn, *args)
        for _ in range(repeats - 1):
            out, dt = timed(fn, *args)
            best = min(best, dt)
        return out, best

    m = num_workers or len(jax.devices())
    n, q, k = N_FEATURES, NUM_CLASSES, ADMM_ITERS
    j = m * SAMPLES_PER_WORKER
    ky, kt = jax.random.split(jax.random.PRNGKey(0))
    y = jax.random.normal(ky, (n, j))
    t = jax.random.normal(kt, (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    eps = 2.0 * q
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=eps)

    def make(kind: str, **kw):
        if kind == "sim":
            return SimulatedBackend(m, **kw)
        return MeshBackend(make_worker_mesh(m), **kw)

    variants: dict[str, dict] = {
        "sim_exact": {"kind": "sim"},
        "mesh_exact": {"kind": "mesh"},
        "mesh_exact_kernels": {"kind": "mesh", "use_kernels": True},
    }
    # Gossip needs 2d+1 distinct ring neighbours; clamp to the device
    # count so the smoke also runs on a 1-device host.
    degree = min(GOSSIP_DEGREE, (m - 1) // 2)
    if degree >= 1:
        gossip = dict(policy=RingGossip(rounds=GOSSIP_ROUNDS, degree=degree))
        variants["sim_gossip"] = {"kind": "sim", **gossip}
        variants["mesh_gossip"] = {"kind": "mesh", **gossip}
    elif verbose:
        print(f"# gossip backends skipped: M={m} < 3 ring neighbours", flush=True)

    rows, objectives = [], {}
    report: dict = {
        "workers": m,
        "n_features": n,
        "num_classes": q,
        "samples_per_worker": SAMPLES_PER_WORKER,
        "admm_iters": k,
        "backends": {},
    }
    for name, spec in variants.items():
        spec = dict(spec)
        kind = spec.pop("kind")
        use_kernels = spec.pop("use_kernels", False)

        def solve(backend):
            return admm.admm_ridge_consensus(
                yw, tw, mu=1e-2, eps_radius=eps, num_iters=k,
                backend=backend, use_kernels=use_kernels,
            )

        # Compile-once engine: one backend, executable cached across calls.
        backend = make(kind, **spec)
        res, compile_s = timed(solve, backend)    # trace + compile + run
        res, dt = steady(solve, backend)          # steady state (cache hit)
        # Cache-off baseline: a fresh backend per call re-traces and
        # re-jits the whole worker program.  For the MESH rows this is
        # exactly the pre-engine behaviour (a per-call
        # ``jax.jit(shard_map(...))``), so it is reported as ``legacy_*``;
        # the pre-engine sim path was an eager (unjitted) vmap, so for
        # sim rows the same measurement is only a cache-off figure and is
        # reported as ``uncached_*``.
        _, fresh_s = timed(solve, make(kind, **spec))
        baseline_tag = "legacy" if kind == "mesh" else "uncached"

        iter_ms = dt / k * 1e3
        objectives[name] = float(res.trace.objective[-1])
        rel_oracle = float(
            jnp.linalg.norm(res.o_star - oracle) / jnp.linalg.norm(oracle)
        )
        nbytes = _consensus_bytes(backend.policy, n, q, k, m)
        report["backends"][name] = {
            "compile_s": round(compile_s, 4),
            "iter_ms": round(iter_ms, 4),
            "solve_s": round(dt, 4),
            f"{baseline_tag}_solve_s": round(fresh_s, 4),
            f"{baseline_tag}_iter_ms": round(fresh_s / k * 1e3, 4),
            f"solve_speedup_vs_{baseline_tag}": round(fresh_s / max(dt, 1e-9), 2),
            "bytes_per_worker": nbytes,
            "oracle_rel": rel_oracle,
            "lowerings": backend.lowerings,
        }
        derived = (
            f"M={m};iter_us={iter_ms * 1e3:.1f};"
            f"{baseline_tag}_iter_us={fresh_s / k * 1e6:.1f};"
            f"comm_bytes={nbytes};"
            f"oracle_rel={rel_oracle:.2e}"
        )
        rows.append(csv_row(f"mesh_backend_{name}", dt * 1e6, derived))
        if verbose:
            print(rows[-1], flush=True)

    # The fused layer step (propagate -> Gram/Cholesky -> ADMM scan as one
    # program) with kernel routing: this is the only path that exercises
    # the fused propagate_gram Pallas kernel, so time it explicitly.
    from repro.core import engine

    kw_shape = jax.random.normal(jax.random.PRNGKey(2), (n, n)) / jnp.sqrt(n)
    step_objs = {}
    for kernels in (False, True):
        name = "mesh_layer_step" + ("_kernels" if kernels else "")
        backend = make("mesh")

        def layer_step(w, backend=backend, kernels=kernels):
            return engine.fused_layer_step(
                backend, yw, tw, w, mu=1e-2, eps_radius=eps, num_iters=k,
                use_kernels=kernels,
            )

        res, compile_s = timed(layer_step, kw_shape)
        res, dt = steady(layer_step, kw_shape)
        step_objs[name] = float(res.trace.objective[-1])
        report["backends"][name] = {
            "compile_s": round(compile_s, 4),
            "iter_ms": round(dt / k * 1e3, 4),
            "solve_s": round(dt, 4),
            "lowerings": backend.lowerings,
        }
        rows.append(
            csv_row(name, dt * 1e6, f"M={m};iter_us={dt / k * 1e6:.1f}")
        )
        if verbose:
            print(rows[-1], flush=True)
    objectives.update(step_objs)

    # Per-policy rows: every ConsensusPolicy through ONE backend and one
    # cached layer program per policy (the pluggable-consensus seam).
    # bytes_per_worker scales with the policy's declared wire_bits —
    # quantized:4 moves 1/8th the bytes of f32 exact consensus.
    policies = {"exact": ExactMean()}
    if degree >= 1:
        policies["gossip"] = RingGossip(rounds=GOSSIP_ROUNDS, degree=degree)
        policies["lossy"] = LossyGossip(
            drop_prob=0.1, rounds=GOSSIP_ROUNDS, degree=degree
        )
    policies["quantized"] = QuantizedGossip(bits=4)
    policies["stale"] = StaleMixing(2)
    policy_backend = make("mesh")
    report["policies"] = {}
    for pname, pol in policies.items():
        def policy_solve(pol=pol):
            return admm.admm_ridge_consensus(
                yw, tw, mu=1e-2, eps_radius=eps, num_iters=k,
                backend=policy_backend, policy=pol,
            )

        res, p_compile_s = timed(policy_solve)   # trace + compile + run
        res, dt = steady(policy_solve)           # steady state (cache hit)
        nbytes = _consensus_bytes(pol, n, q, k, m)
        rel_oracle = float(
            jnp.linalg.norm(res.o_star - oracle) / jnp.linalg.norm(oracle)
        )
        report["policies"][pname] = {
            "policy": pol.describe(),
            "compile_s": round(p_compile_s, 4),
            "iter_ms": round(dt / k * 1e3, 4),
            "bytes_per_worker": nbytes,
            "wire_bits": pol.wire_bits,
            "exchanges_per_round": pol.exchanges_for(m),
            "oracle_rel": rel_oracle,
        }
        rows.append(csv_row(
            f"mesh_policy_{pname}", dt * 1e6,
            f"M={m};iter_us={dt / k * 1e6:.1f};comm_bytes={nbytes};"
            f"wire_bits={pol.wire_bits};oracle_rel={rel_oracle:.2e}",
        ))
        if verbose:
            print(rows[-1], flush=True)
    # One lowering per policy through the shared backend — the
    # compile-count invariant of the policy seam.
    report["policy_lowerings"] = policy_backend.lowerings
    assert policy_backend.lowerings == len(policies), policy_backend.cache_info()

    # Per-topology rows: the SAME fixed-round Gossip policy over every
    # first-class mixing graph that fits M workers, relating measured
    # convergence (oracle_rel after K iters) and cost (iter_ms, eq.-15
    # bytes) to the predicted spectral gap.  Denser graphs buy a larger
    # gap (faster mixing) with more bytes per round — the topology
    # seam's version of the paper's degree sweep.
    from repro.core.policy import Gossip
    from repro.core.topology import (
        FullyConnected,
        Hypercube,
        RandomGeometric,
        Ring,
        Torus,
    )

    candidates = {}
    if degree >= 1:
        candidates[f"ring:{degree}"] = Ring(degree)
    shape = _torus_shape(m)
    if shape is not None:
        candidates[f"torus:{shape[0]}x{shape[1]}"] = Torus(*shape)
    candidates["hypercube"] = Hypercube()
    candidates["full"] = FullyConnected()
    candidates["geometric:0.5"] = RandomGeometric(radius=0.5, seed=0)
    report["topologies"] = {}
    topo_backend = make("mesh")
    for tname, topo in candidates.items():
        try:
            topo.validate(m)
        except ValueError as e:
            if verbose:
                print(f"# topology {tname} skipped on M={m}: {e}", flush=True)
            continue
        tpol = Gossip(rounds=GOSSIP_ROUNDS, topology=topo)

        def topo_solve(tpol=tpol):
            return admm.admm_ridge_consensus(
                yw, tw, mu=1e-2, eps_radius=eps, num_iters=k,
                backend=topo_backend, policy=tpol,
            )

        res, t_compile_s = timed(topo_solve)     # trace + compile + run
        res, dt = steady(topo_solve)             # steady state (cache hit)
        nbytes = _consensus_bytes(tpol, n, q, k, m)
        gap = topo.spectral_gap(m)
        rel_oracle = float(
            jnp.linalg.norm(res.o_star - oracle) / jnp.linalg.norm(oracle)
        )
        report["topologies"][tname] = {
            "topology": topo.describe(),
            "spectral_gap": round(gap, 6),
            "rounds_for_tolerance_1e6": topo.rounds_for_tolerance(m, 1e-6),
            "edges_per_node": topo.edges_per_node(m),
            "gossip_rounds": GOSSIP_ROUNDS,
            "compile_s": round(t_compile_s, 4),
            "iter_ms": round(dt / k * 1e3, 4),
            "bytes_per_worker": nbytes,
            "oracle_rel": rel_oracle,
        }
        rows.append(csv_row(
            f"mesh_topology_{tname.replace(':', '_')}", dt * 1e6,
            f"M={m};iter_us={dt / k * 1e6:.1f};gap={gap:.4f};"
            f"comm_bytes={nbytes};oracle_rel={rel_oracle:.2e}",
        ))
        if verbose:
            print(rows[-1], flush=True)

    # Wire-efficient consensus: compressed-vs-serial schedules, low-
    # precision wire formats, and the collective-free hot path.  The
    # schedule/dtype rows run trace_every=0 — the production hot path
    # this engine ships — so the exchange schedule dominates what's
    # measured rather than the trace psum/pmax trio.
    report["wire"] = {}
    wire_backend = make("mesh")

    def wire_solve(pol, trace_every=0):
        return admm.admm_ridge_consensus(
            yw, tw, mu=1e-2, eps_radius=eps, num_iters=k,
            backend=wire_backend, policy=pol, trace_every=trace_every,
        )

    if degree >= 1:
        # (1) schedule compression: ONE H^B mix vs B serial rounds.
        sched_rows = {}
        for tag, pol in (
            ("serial", RingGossip(rounds=GOSSIP_ROUNDS, degree=degree,
                                  compress=False)),
            ("compressed", RingGossip(rounds=GOSSIP_ROUNDS, degree=degree)),
        ):
            res, w_compile_s = timed(wire_solve, pol)
            res, dt = steady(wire_solve, pol)
            rel_oracle = float(
                jnp.linalg.norm(res.o_star - oracle) / jnp.linalg.norm(oracle)
            )
            sched_rows[tag] = {
                "policy": pol.describe(),
                "compile_s": round(w_compile_s, 4),
                "iter_ms": round(dt / k * 1e3, 4),
                "hops_per_mix": pol.hops_for(m),
                "oracle_rel": rel_oracle,
            }
            rows.append(csv_row(
                f"mesh_wire_schedule_{tag}", dt * 1e6,
                f"M={m};iter_us={dt / k * 1e6:.1f};"
                f"hops={pol.hops_for(m)};oracle_rel={rel_oracle:.2e}",
            ))
            if verbose:
                print(rows[-1], flush=True)
        sched_rows["speedup"] = round(
            sched_rows["serial"]["iter_ms"]
            / max(sched_rows["compressed"]["iter_ms"], 1e-9), 2
        )
        sched_rows["gossip_rounds"] = GOSSIP_ROUNDS
        report["wire"]["schedule"] = sched_rows

        # (2) low-precision wire formats on the same gossip schedule.
        dtype_rows = {}
        for wd in ("float32", "bfloat16", "float16"):
            pol = RingGossip(rounds=GOSSIP_ROUNDS, degree=degree, wire_dtype=wd)
            res, w_compile_s = timed(wire_solve, pol)
            res, dt = steady(wire_solve, pol)
            nbytes = _consensus_bytes(pol, n, q, k, m)
            rel_oracle = float(
                jnp.linalg.norm(res.o_star - oracle) / jnp.linalg.norm(oracle)
            )
            dtype_rows[wd] = {
                "iter_ms": round(dt / k * 1e3, 4),
                "wire_bits": pol.wire_bits,
                "bytes_per_worker": nbytes,
                "oracle_rel": rel_oracle,
            }
            rows.append(csv_row(
                f"mesh_wire_dtype_{wd}", dt * 1e6,
                f"M={m};iter_us={dt / k * 1e6:.1f};comm_bytes={nbytes};"
                f"wire_bits={pol.wire_bits};oracle_rel={rel_oracle:.2e}",
            ))
            if verbose:
                print(rows[-1], flush=True)
        report["wire"]["dtypes"] = dtype_rows

    # (3) collective-free hot path: trace_every=0 drops the per-iteration
    # psum/pmax trio (and the cerr probe) from the lowered program.
    hot_rows = {}
    for tag, te in (("traced", 1), ("hot", 0)):
        res, w_compile_s = timed(wire_solve, ExactMean(), te)
        res, dt = steady(wire_solve, ExactMean(), te)
        hot_rows[f"{tag}_iter_ms"] = round(dt / k * 1e3, 4)
        rows.append(csv_row(
            f"mesh_wire_trace_{tag}", dt * 1e6,
            f"M={m};iter_us={dt / k * 1e6:.1f};trace_every={te}",
        ))
        if verbose:
            print(rows[-1], flush=True)
    hot_rows["speedup"] = round(
        hot_rows["traced_iter_ms"] / max(hot_rows["hot_iter_ms"], 1e-9), 2
    )
    report["wire"]["trace_every"] = hot_rows

    # Elastic asynchronous consensus: drop rate x communication interval,
    # all through ONE shared backend (each (drop, interval) pair is a new
    # policy VALUE -> a new cached executable; the faults run inside the
    # compiled program, so iter_ms measures the real fault-injection
    # overhead, not retraces).  bytes_per_worker reflects the eq.-15
    # accounting with interval-skipped rounds: interval=4 moves 1/4 the
    # bytes of every-iteration gossip.
    report["faults"] = {}
    if degree >= 1:
        from repro.dssfn import parse_spec

        faults_backend = make("mesh")
        for drop in (0.0, 0.2):
            for interval in (1, 4):
                assert k % interval == 0, (k, interval)
                # The unified spec grammar, same string the launcher and
                # CI legs use.
                fpol = parse_spec(
                    f"async:rounds={GOSSIP_ROUNDS}:interval={interval}"
                    f":drop={drop}:seed=0@ring:{degree}"
                )

                def fault_solve(fpol=fpol):
                    return admm.admm_ridge_consensus(
                        yw, tw, mu=1e-2, eps_radius=eps, num_iters=k,
                        backend=faults_backend, policy=fpol, trace_every=0,
                    )

                res, f_compile_s = timed(fault_solve)
                res, dt = steady(fault_solve)
                nbytes = _consensus_bytes(fpol, n, q, k, m)
                rel_oracle = float(
                    jnp.linalg.norm(res.o_star - oracle)
                    / jnp.linalg.norm(oracle)
                )
                fname = f"drop{drop}_int{interval}"
                report["faults"][fname] = {
                    "policy": fpol.describe(),
                    "drop": drop,
                    "interval": interval,
                    "compile_s": round(f_compile_s, 4),
                    "iter_ms": round(dt / k * 1e3, 4),
                    "bytes_per_worker": nbytes,
                    "oracle_rel": rel_oracle,
                }
                rows.append(csv_row(
                    f"mesh_faults_{fname.replace('.', 'p')}", dt * 1e6,
                    f"M={m};iter_us={dt / k * 1e6:.1f};drop={drop};"
                    f"interval={interval};comm_bytes={nbytes};"
                    f"oracle_rel={rel_oracle:.2e}",
                ))
                if verbose:
                    print(rows[-1], flush=True)
        # One lowering per (drop, interval) policy value, zero retraces.
        report["faults_lowerings"] = faults_backend.lowerings
        assert faults_backend.lowerings == len(report["faults"]), (
            faults_backend.cache_info()
        )

    # Byzantine robustness: attack x policy sweep through ONE shared
    # backend.  Every (policy, fault-model) pair is a policy VALUE —
    # attacks corrupt the transmitted payload inside the cached SPMD
    # program, so iter_ms measures the real robust-aggregation overhead
    # (order statistics + screening on every link), never a retrace.
    # Attacked rows score against the honest-data (leave-one-out)
    # oracle: a Byzantine worker's shard is unlearnable because every
    # payload it emits is corrupted.
    report["byzantine"] = {}
    if degree >= 1 and m >= 4:
        from repro.dssfn import parse_spec as parse_byz_spec

        byz = m // 2
        keep = [i for i in range(m) if i != byz]
        y_h = yw[jnp.array(keep)].transpose(1, 0, 2).reshape(n, -1)
        t_h = tw[jnp.array(keep)].transpose(1, 0, 2).reshape(q, -1)
        oracle_honest = admm.exact_constrained_ridge(y_h, t_h, eps_radius=eps)
        byz_backend = make("mesh")
        byz_cells = 0
        for pname, ptoken in (
            ("trimmed", "trimmed:f=1:rounds=3"),
            ("median", "median:rounds=3"),
            ("clipped", "clipped:tau=1.0:rounds=3"),
            ("async", "async:rounds=3"),   # the vulnerable baseline
        ):
            for attack in ("none", "signflip", "nanbomb"):
                spec = ptoken
                if attack != "none":
                    spec += f":byz={byz}:attack={attack}"
                bpol = parse_byz_spec(spec + "@hypercube")

                def byz_solve(bpol=bpol):
                    return admm.admm_ridge_consensus(
                        yw, tw, mu=1e-2, eps_radius=eps, num_iters=k,
                        backend=byz_backend, policy=bpol, trace_every=0,
                    )

                res, b_compile_s = timed(byz_solve)
                res, dt = steady(byz_solve)
                byz_cells += 1
                ref = oracle if attack == "none" else oracle_honest
                rel_oracle = float(
                    jnp.linalg.norm(res.o_star - ref) / jnp.linalg.norm(ref)
                )
                jitter = (
                    int((jnp.asarray(res.jitter) > 0).sum())
                    if res.jitter is not None else 0
                )
                bname = f"{pname}_{attack}"
                report["byzantine"][bname] = {
                    "policy": bpol.describe(),
                    "attack": attack,
                    "oracle": "full" if attack == "none" else "honest",
                    "compile_s": round(b_compile_s, 4),
                    "iter_ms": round(dt / k * 1e3, 4),
                    "bytes_per_worker": _consensus_bytes(bpol, n, q, k, m),
                    "oracle_rel": rel_oracle,
                    "jitter_events": jitter,
                }
                rows.append(csv_row(
                    f"mesh_byz_{bname}", dt * 1e6,
                    f"M={m};iter_us={dt / k * 1e6:.1f};attack={attack};"
                    f"oracle_rel={rel_oracle:.2e};jitter={jitter}",
                ))
                if verbose:
                    print(rows[-1], flush=True)
        # One lowering per (policy, fault-model) value, zero retraces.
        report["byzantine_lowerings"] = byz_backend.lowerings
        assert byz_backend.lowerings == byz_cells, byz_backend.cache_info()

    # Centralized-equivalence parity: same mode, different runtime.
    report["parity"] = {}
    for a_name, b_name, tag in (
        ("sim_exact", "mesh_exact", "exact"),
        ("sim_gossip", "mesh_gossip", "gossip"),
        ("mesh_exact", "mesh_exact_kernels", "kernels"),
        ("mesh_layer_step", "mesh_layer_step_kernels", "fused_kernels"),
    ):
        if a_name not in objectives or b_name not in objectives:
            continue
        a, b = objectives[a_name], objectives[b_name]
        rel = abs(a - b) / max(abs(a), 1e-30)
        report["parity"][tag] = rel
        rows.append(
            csv_row(f"mesh_backend_parity_{tag}", 0.0, f"rel_objective_gap={rel:.2e}")
        )
        if verbose:
            print(rows[-1], flush=True)

    # Headline keys the CI bench-json step requires: the mesh hot path.
    headline = report["backends"]["mesh_exact"]
    report["compile_s"] = headline["compile_s"]
    report["iter_ms"] = headline["iter_ms"]
    report["legacy_iter_ms"] = headline["legacy_iter_ms"]
    report["bytes_per_worker"] = headline["bytes_per_worker"]

    from benchmarks.common import gate_and_write

    gate_and_write(
        report, json_path, check,
        gates=tuple((s, "iter_ms") for s in GATE_SECTIONS), verbose=verbose,
    )
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--json", default=DEFAULT_JSON, help="output JSON path")
    ap.add_argument(
        "--check-regression",
        action="store_true",
        help="compare fresh results against the committed JSON (read "
        "before overwriting) and exit non-zero if any backend's iter_ms "
        "regressed more than BENCH_REGRESSION_FACTOR (default +25%%)",
    )
    args = ap.parse_args()
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.workers}".strip()
        )
    run(
        num_workers=args.workers, json_path=args.json,
        check=args.check_regression or None,
    )


if __name__ == "__main__":
    main()
