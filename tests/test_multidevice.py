"""Multi-device numerical tests (8 fake host devices via subprocess —
XLA_FLAGS must be set before jax initializes, so these run out of process).

Covers paths the single-device suite cannot execute numerically:
- the manual shard_map MoE (combine-before-psum) vs the plain path,
- ring-gossip consensus via lax.ppermute vs the dense-H reference,
- the distributed dSSFN ADMM solve on a real (2, 4) mesh,
- MeshBackend vs SimulatedBackend vs centralized-oracle parity on an
  M=8 ``workers`` mesh (the ConsensusBackend acceptance test).
"""
import os
import subprocess
import sys
import textwrap


REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(body: str) -> str:
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((2, 4), ("data", "model"))
        """
    ) + textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": REPO_SRC},
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_shardmap_matches_plain():
    out = run_subprocess("""
    from repro.sharding.rules import AxisRules, use_rules
    from repro.nn.moe import moe_ffn, _moe_core

    b, s, d, f, e, k = 4, 32, 16, 32, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, e))
    wg = jax.random.normal(ks[2], (e, d, f)) / np.sqrt(d)
    wu = jax.random.normal(ks[3], (e, d, f)) / np.sqrt(d)
    wd = jax.random.normal(ks[4], (e, f, d)) / np.sqrt(f)

    ref, ref_stats = _moe_core(x, router, wg, wu, wd, top_k=k,
                               capacity_factor=float(e), constrain=False)
    rules = AxisRules(mesh=mesh, data_axes=("data",), model_axis="model")
    with mesh, use_rules(rules):
        got, stats = jax.jit(lambda *a: moe_ffn(*a, top_k=k,
                                                capacity_factor=float(e)))(
            x, router, wg, wu, wd)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err < 1e-4, err
    assert abs(float(stats.aux_loss) - float(ref_stats.aux_loss)) < 1e-4
    # gradients agree too
    loss_plain = lambda w: jnp.sum(_moe_core(x, router, w, wu, wd, top_k=k,
        capacity_factor=float(e), constrain=False)[0] ** 2)
    with mesh, use_rules(rules):
        loss_sm = lambda w: jnp.sum(moe_ffn(x, router, w, wu, wd, top_k=k,
            capacity_factor=float(e))[0] ** 2)
        g_sm = jax.jit(jax.grad(loss_sm))(wg)
    g_ref = jax.grad(loss_plain)(wg)
    gerr = float(jnp.max(jnp.abs(g_sm - g_ref)) / (jnp.max(jnp.abs(g_ref)) + 1e-9))
    assert gerr < 1e-3, gerr
    print("MOE_OK", err, gerr)
    """)
    assert "MOE_OK" in out


def test_ring_gossip_ppermute_matches_dense():
    out = run_subprocess("""
    from functools import partial
    from jax.experimental.shard_map import shard_map
    from repro.core import consensus, topology

    m, degree, rounds = 8, 2, 5
    x = jax.random.normal(jax.random.PRNGKey(0), (m, 6))
    h = topology.circular_mixing_matrix(m, degree)
    want = consensus.gossip_average(x, h, rounds)

    ring_mesh = make_mesh_compat((8,), ("w",))
    fn = shard_map(
        partial(consensus.ring_gossip_average, axis_name="w", degree=degree,
                num_nodes=m, num_rounds=rounds),
        mesh=ring_mesh, in_specs=P("w"), out_specs=P("w"), check_rep=False)
    with ring_mesh:
        got = jax.jit(fn)(x)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-5, err
    print("GOSSIP_OK", err)
    """)
    assert "GOSSIP_OK" in out


def test_mesh_backend_matches_simulated_and_oracle():
    """The tentpole guarantee: the SAME worker program under MeshBackend
    (shard_map, device-local shards) and SimulatedBackend (vmap axis)
    produces the same dSSFN training run, and both match the centralized
    oracle — in exact AND ring-gossip consensus modes."""
    out = run_subprocess("""
    from repro.core import admm, layerwise, ssfn
    from repro.core.backend import MeshBackend, SimulatedBackend
    from repro.core.policy import QuantizedGossip, RingGossip, StaleMixing
    from repro.launch.mesh import make_worker_mesh

    m, n, q, j = 8, 16, 3, 256
    mesh = make_worker_mesh(m)
    y = jax.random.normal(jax.random.PRNGKey(0), (n, j))
    t = jax.random.normal(jax.random.PRNGKey(1), (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)

    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=300)
    sim = admm.admm_ridge_consensus(yw, tw, backend=SimulatedBackend(m), **kw)
    msh = admm.admm_ridge_consensus(yw, tw, backend=MeshBackend(mesh), **kw)
    rel_pair = float(jnp.linalg.norm(sim.o_star - msh.o_star)
                     / jnp.linalg.norm(sim.o_star))
    assert rel_pair < 1e-4, rel_pair
    rel_obj = float(jnp.abs(sim.trace.objective[-1] - msh.trace.objective[-1])
                    / sim.trace.objective[-1])
    assert rel_obj < 1e-4, rel_obj
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=6.0)
    rel_oracle = float(jnp.linalg.norm(msh.o_star - oracle) / jnp.linalg.norm(oracle))
    assert rel_oracle < 1e-3, rel_oracle

    gpol = RingGossip(rounds=6, degree=2)
    simg = admm.admm_ridge_consensus(
        yw, tw, backend=SimulatedBackend(m, policy=gpol), **kw)
    mshg = admm.admm_ridge_consensus(
        yw, tw, backend=MeshBackend(mesh, policy=gpol), **kw)
    rel_g = float(jnp.linalg.norm(simg.o_star - mshg.o_star)
                  / jnp.linalg.norm(simg.o_star))
    assert rel_g < 1e-4, rel_g

    # The stranded-in-robust.py policies now run on the REAL mesh: the
    # same stateful policy program (quantizer keys / staleness buffers in
    # the scan carry) under vmap and shard_map.  StaleMixing is
    # deterministic -> tight sim-vs-mesh parity; QuantizedGossip's
    # stochastic rounding sits on bit-level thresholds, so runtime
    # reduction-order ulps flip individual draws — assert statistical
    # closeness and oracle proximity instead.
    for pol, pair_tol in ((QuantizedGossip(bits=8), 2e-2), (StaleMixing(2), 1e-4)):
        simp = admm.admm_ridge_consensus(
            yw, tw, backend=SimulatedBackend(m), policy=pol, **kw)
        mshp = admm.admm_ridge_consensus(
            yw, tw, backend=MeshBackend(mesh), policy=pol, **kw)
        rel_p = float(jnp.linalg.norm(simp.o_star - mshp.o_star)
                      / jnp.linalg.norm(simp.o_star))
        assert rel_p < pair_tol, (pol, rel_p)
        rel_o = float(jnp.linalg.norm(mshp.o_star - oracle)
                      / jnp.linalg.norm(oracle))
        assert rel_o < 5e-2, (pol, rel_o)

    # Full layer-wise training: shards stay device-local end to end.
    cfg = ssfn.SSFNConfig(input_dim=10, num_classes=3, num_layers=2,
                          hidden=24, admm_iters=60)
    kx, kt, kinit = jax.random.split(jax.random.PRNGKey(2), 3)
    xw = jax.random.normal(kx, (m, 10, 24))
    labels = jax.random.randint(kt, (m, 24), 0, 3)
    tw2 = jax.nn.one_hot(labels, 3).transpose(0, 2, 1)
    ps, logs = layerwise.train_decentralized_ssfn(
        xw, tw2, cfg, kinit, backend=SimulatedBackend(m))
    pm, logm = layerwise.train_decentralized_ssfn(
        xw, tw2, cfg, kinit, backend=MeshBackend(mesh))
    rel_train = abs(logs.layer_costs[-1] - logm.layer_costs[-1]) / abs(
        logs.layer_costs[-1])
    assert rel_train < 1e-4, rel_train
    print("MESHBACKEND_OK", rel_pair, rel_g, rel_train)
    """)
    assert "MESHBACKEND_OK" in out


def test_topology_gossip_mesh_parity_on_8_devices():
    """The topology-seam acceptance test: non-ring mixing graphs (torus,
    hypercube, time-varying, Birkhoff-compiled geometric) run their
    exchange schedules as real collective_permutes on an M=8 ``workers``
    mesh, match the vmap simulation, match the dense H^B reference, and
    RingGossip stays bit-identical to the raw PR-3 ring hops."""
    out = run_subprocess("""
    from repro.core import admm, consensus
    from repro.core.backend import MeshBackend, SimulatedBackend
    from repro.core.policy import Gossip, RingGossip
    from repro.core.topology import (
        Hypercube, RandomGeometric, Ring, TimeVarying, Torus)
    from repro.launch.mesh import make_worker_mesh

    m, n, q, j = 8, 16, 3, 256
    wmesh = make_worker_mesh(m)
    y = jax.random.normal(jax.random.PRNGKey(0), (n, j))
    t = jax.random.normal(jax.random.PRNGKey(1), (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=300)
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=6.0)

    # Raw mixing parity on the mesh: schedule hops == dense H^B.
    x = jax.random.normal(jax.random.PRNGKey(2), (m, 4, 6))
    topos = (Torus(2, 4), Hypercube(), TimeVarying((Ring(1), Hypercube())),
             RandomGeometric(radius=0.5, seed=1))
    for topo in topos:
        rounds = 4
        pol = Gossip(rounds=rounds, topology=topo)
        mesh_be = MeshBackend(wmesh, policy=pol)
        got = mesh_be.run(mesh_be.consensus_mean, x)
        cycle = topo.cycle()  # round b mixes with cycle[b % L]'s H
        want = x
        for b in range(rounds):
            want = consensus.gossip_average(
                want, cycle[b % len(cycle)].mixing_matrix(m), 1)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 1e-5, (topo, err)

    # Full ADMM solves: sim-vs-mesh parity + oracle proximity per graph.
    for topo in (Torus(2, 4), Hypercube()):
        pol = Gossip(rounds=6, topology=topo)
        sim = admm.admm_ridge_consensus(
            yw, tw, backend=SimulatedBackend(m, policy=pol), **kw)
        msh = admm.admm_ridge_consensus(
            yw, tw, backend=MeshBackend(wmesh, policy=pol), **kw)
        rel = float(jnp.linalg.norm(sim.o_star - msh.o_star)
                    / jnp.linalg.norm(sim.o_star))
        assert rel < 1e-4, (topo, rel)
        rel_o = float(jnp.linalg.norm(msh.o_star - oracle)
                      / jnp.linalg.norm(oracle))
        assert rel_o < 5e-2, (topo, rel_o)

    # RingGossip(compress=False) == raw ring hops, bit for bit, on the
    # real mesh; the default compressed H^B mix matches to f32 tolerance.
    ring_be = MeshBackend(
        wmesh, policy=RingGossip(rounds=5, degree=2, compress=False))
    got = ring_be.run(ring_be.consensus_mean, x)
    def raw(v):
        return consensus.ring_gossip_average(
            v, ring_be.axis_name, degree=2, num_nodes=m, num_rounds=5)
    want = ring_be.run(raw, x, key="raw-ring")
    assert jnp.array_equal(got, want)
    comp_be = MeshBackend(wmesh, policy=RingGossip(rounds=5, degree=2))
    got_c = comp_be.run(comp_be.consensus_mean, x)
    assert float(jnp.max(jnp.abs(got_c - want))) < 1e-5
    print("TOPOLOGY8_OK")
    """)
    assert "TOPOLOGY8_OK" in out


def test_compressed_gossip_and_hot_path_on_8_devices():
    """The wire-efficiency acceptance tests on a real 8-worker mesh:

    - compressed ring & torus gossip solves match their serial-schedule
      twins (same H^B mixing, one mix instead of B rounds);
    - trace_every=0 keeps the final iterate bit-identical (ExactMean)
      while the lowered program's collectives reduce to EXACTLY the
      policy's own exchanges (no psum/pmax trio, no cerr probe) —
      asserted via the backend lowering stats / HLO collective counts.
    """
    out = run_subprocess("""
    from repro.core import admm, engine
    from repro.core.backend import MeshBackend
    from repro.core.policy import ExactMean, Gossip, RingGossip
    from repro.core.topology import Ring, Torus
    from repro.launch.mesh import make_worker_mesh

    m, n, q, j = 8, 16, 3, 256
    wmesh = make_worker_mesh(m)
    y = jax.random.normal(jax.random.PRNGKey(0), (n, j))
    t = jax.random.normal(jax.random.PRNGKey(1), (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=40)

    # Compressed vs serial schedule parity, full ADMM solve, per graph.
    for topo in (Ring(2), Torus(2, 4)):
        comp = admm.admm_ridge_consensus(
            yw, tw, backend=MeshBackend(
                wmesh, policy=Gossip(rounds=4, topology=topo)), **kw)
        serial = admm.admm_ridge_consensus(
            yw, tw, backend=MeshBackend(
                wmesh, policy=Gossip(rounds=4, topology=topo,
                                     compress=False)), **kw)
        rel = float(jnp.linalg.norm(comp.o_star - serial.o_star)
                    / jnp.linalg.norm(serial.o_star))
        assert rel < 1e-5, (topo, rel)

    # Hot path: bit-identical o_star, collective-free lowering.  The
    # expected counts come from the spmdlint wire model (repro.analysis)
    # — the same model `lint_dssfn --all-grammar` checks in CI.
    from repro import analysis

    K = 10
    def probe(policy, trace_every):
        return engine.layer_program(
            MeshBackend(wmesh, policy=policy), yw, tw, None, mu=1e-2,
            eps_radius=6.0, num_iters=K, policy=policy,
            trace_every=trace_every).lowering_stats()

    def expect_hot(policy):
        per_mix = analysis.expected_mix_collectives(policy, m)
        return {op: K * c for op, c in per_mix.items()}

    pol = RingGossip(rounds=4, degree=2)
    hot = probe(pol, 0)["collective_counts"]
    traced = probe(pol, 1)["collective_counts"]
    # trace_every=0: ONLY the policy's ppermutes — K mixes x hops each,
    # and not a single reduction collective.
    assert hot == expect_hot(pol), (hot, expect_hot(pol))
    # trace_every=1 adds the psum obj + psum primal + cerr pmean/pmax.
    # XLA's all-reduce combiner fuses three of them into one tuple op;
    # the analysis counts each combined operand, so this holds either way.
    assert traced.get("all-reduce", 0) == 4 * K, traced

    ex_hot = probe(ExactMean(), 0)["collective_counts"]
    assert ex_hot == expect_hot(ExactMean()), ex_hot

    # The full wire contract (counts, payload widths, eq.-15 declaration
    # arithmetic) holds for both policies on this mesh.
    for p in (pol, ExactMean()):
        found = analysis.check_wire_contract(
            p, MeshBackend(wmesh, policy=p), num_iters=K, subject=str(p))
        assert found == [], [f.render() for f in found]

    # And the final iterate is bit-identical with traces off.
    be = MeshBackend(wmesh)
    kw10 = dict(mu=1e-2, eps_radius=6.0, num_iters=K, backend=be)
    a = admm.admm_ridge_consensus(yw, tw, **kw10)
    b = admm.admm_ridge_consensus(yw, tw, trace_every=0, **kw10)
    assert jnp.array_equal(a.o_star, b.o_star)
    assert b.trace is None
    print("WIRE8_OK")
    """)
    assert "WIRE8_OK" in out


def test_layer_engine_on_8_devices():
    """Compile-once layer engine on a real M=8 ``workers`` mesh: kernel-path
    parity (use_kernels=True vs einsum, exact AND gossip consensus) and the
    compile-count invariant (lowerings == distinct layer shapes)."""
    out = run_subprocess("""
    import dataclasses
    from repro.core import layerwise, ssfn
    from repro.core.backend import MeshBackend, SimulatedBackend
    from repro.core.policy import ExactMean, RingGossip
    from repro.launch.mesh import make_worker_mesh

    m = 8
    wmesh = make_worker_mesh(m)
    cfg = ssfn.SSFNConfig(input_dim=128, num_classes=3, num_layers=2,
                          hidden=128, admm_iters=15)
    cfg_k = dataclasses.replace(cfg, use_kernels=True)
    kx, kt, kinit = jax.random.split(jax.random.PRNGKey(0), 3)
    xw = jax.random.normal(kx, (m, 128, 128))
    labels = jax.random.randint(kt, (m, 128), 0, 3)
    tw = jax.nn.one_hot(labels, 3).transpose(0, 2, 1)

    for pol in (ExactMean(), RingGossip(rounds=6, degree=2)):
        mesh_be = MeshBackend(wmesh, policy=pol)
        pk, _ = layerwise.train_decentralized_ssfn(
            xw, tw, cfg_k, kinit, backend=mesh_be)
        pr, _ = layerwise.train_decentralized_ssfn(
            xw, tw, cfg, kinit, backend=MeshBackend(wmesh, policy=pol))
        ps, _ = layerwise.train_decentralized_ssfn(
            xw, tw, cfg_k, kinit, backend=SimulatedBackend(m, policy=pol))
        for a, b in zip(pk.o, pr.o):   # kernels == einsum on the mesh
            rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
            assert rel < 1e-6, (pol, rel)
        for a, b in zip(pk.o, ps.o):   # sim == mesh through the engine
            rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
            assert rel < 1e-4, (pol, rel)
        # 3 layer solves, 3 distinct programs even though l=1 and l=2
        # share W shape (128,128) here: l=0 has no W, l=1 must not donate
        # the caller-reachable Y, l=2 donates the engine-owned carry.
        # The win shows from l=3 on (none here) and on repeat trains:
        assert mesh_be.lowerings == 3, mesh_be.cache_info()
        layerwise.train_decentralized_ssfn(
            xw, tw, cfg_k, kinit, backend=mesh_be)
        assert mesh_be.lowerings == 3, mesh_be.cache_info()  # fully cached
    print("ENGINE8_OK")
    """)
    assert "ENGINE8_OK" in out


def test_async_faults_and_elastic_resume_on_8_devices():
    """The elastic-consensus acceptance tests on a real M=8 mesh:

    - a disabled fault model leaves the lowered hot path UNCHANGED —
      AsyncGossip's collective counts equal serial Gossip's, and the
      solve is bit-identical;
    - under drop=0.2 the whole training run is deterministic (two mesh
      runs bit-equal), matches the vmap simulation, and compiles each
      layer shape exactly once (faults run INSIDE the cached program —
      no per-iteration retraces);
    - a mid-run checkpoint + kill + resume reproduces the uninterrupted
      run's final iterate on the mesh backend.
    """
    out = run_subprocess("""
    import tempfile
    from repro.core import admm, engine, layerwise, ssfn
    from repro.core.backend import MeshBackend, SimulatedBackend
    from repro.core.policy import AsyncGossip, FaultModel, Gossip
    from repro.core.topology import Hypercube
    from repro.launch.mesh import make_worker_mesh

    m, n, q, j = 8, 16, 3, 256
    wmesh = make_worker_mesh(m)
    y = jax.random.normal(jax.random.PRNGKey(0), (n, j))
    t = jax.random.normal(jax.random.PRNGKey(1), (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=40)

    # 1) Null fault model == serial Gossip: identical collectives in the
    # lowered hot path, bit-identical solve.
    K = 10
    def probe(policy):
        return engine.layer_program(
            MeshBackend(wmesh, policy=policy), yw, tw, None, mu=1e-2,
            eps_radius=6.0, num_iters=K, policy=policy,
            trace_every=0).lowering_stats()

    anull = AsyncGossip(rounds=3, topology=Hypercube())
    gser = Gossip(rounds=3, topology=Hypercube(), compress=False)
    ca = probe(anull)["collective_counts"]
    cg = probe(gser)["collective_counts"]
    assert ca == cg, (ca, cg)
    # Both equal the spmdlint wire model's per-mix expectation x K.
    from repro import analysis
    want = {op: K * c
            for op, c in analysis.expected_mix_collectives(anull, m).items()}
    assert ca == want, (ca, want)
    ra = admm.admm_ridge_consensus(
        yw, tw, backend=MeshBackend(wmesh, policy=anull), **kw)
    rg = admm.admm_ridge_consensus(
        yw, tw, backend=MeshBackend(wmesh, policy=gser), **kw)
    assert jnp.array_equal(ra.o_star, rg.o_star)

    # 2) Faulty solve: deterministic on the mesh, sim-vs-mesh parity.
    pol = AsyncGossip(rounds=3, topology=Hypercube(),
                      faults=FaultModel(drop=0.2, seed=11))
    mesh_be = MeshBackend(wmesh, policy=pol)
    f1 = admm.admm_ridge_consensus(yw, tw, backend=mesh_be, **kw)
    f2 = admm.admm_ridge_consensus(yw, tw, backend=mesh_be, **kw)
    assert jnp.array_equal(f1.o_star, f2.o_star)
    assert mesh_be.lowerings == 1, mesh_be.cache_info()
    fs = admm.admm_ridge_consensus(
        yw, tw, backend=SimulatedBackend(m, policy=pol), **kw)
    rel = float(jnp.linalg.norm(fs.o_star - f1.o_star)
                / jnp.linalg.norm(fs.o_star))
    assert rel < 1e-4, rel

    # 3) Full faulty training + mid-run kill/resume on the mesh.
    cfg = ssfn.SSFNConfig(input_dim=10, num_classes=3, num_layers=2,
                          hidden=24, admm_iters=60)
    kx, kt, kinit = jax.random.split(jax.random.PRNGKey(2), 3)
    xw = jax.random.normal(kx, (m, 10, 24))
    labels = jax.random.randint(kt, (m, 24), 0, 3)
    tw2 = jax.nn.one_hot(labels, 3).transpose(0, 2, 1)

    train_be = MeshBackend(wmesh, policy=pol)
    pf, logf = layerwise.train_decentralized_ssfn(
        xw, tw2, cfg, kinit, backend=train_be)
    # L=2 -> 3 layer solves, 3 distinct shapes, zero fault retraces.
    assert train_be.lowerings == 3, train_be.cache_info()

    ckpt = tempfile.mkdtemp()
    layerwise.train_decentralized_ssfn(
        xw, tw2, cfg, kinit, backend=train_be,
        checkpoint_dir=ckpt, stop_after_layer=0)   # 'crash' after layer 0
    pr, logr = layerwise.train_decentralized_ssfn(
        xw, tw2, cfg, kinit, backend=train_be,
        checkpoint_dir=ckpt, resume=True)
    for a, b in zip(pf.o, pr.o):
        assert jnp.array_equal(a, b)
    assert logf.comm_scalars == logr.comm_scalars
    assert np.array_equal(logf.admm_objective, logr.admm_objective)
    print("ELASTIC8_OK", rel)
    """)
    assert "ELASTIC8_OK" in out


def test_byzantine_robust_consensus_on_8_devices():
    """The robustness acceptance test on a real M=8 ``workers`` mesh:
    one signflip attacker on a 2x4 torus — ``trimmed:f=1`` converges to
    the honest-data solution while the non-robust gossip path fails the
    same bound (and a nanbomb attacker NaNs it outright); the attack
    schedule is deterministic inside ONE cached lowering; zero-attacker
    trimmed stays bit-identical to plain serial gossip on the mesh."""
    out = run_subprocess("""
    from repro.core import admm
    from repro.core.backend import MeshBackend, SimulatedBackend
    from repro.core.policy import AsyncGossip, Gossip, parse_policy
    from repro.core.topology import Torus
    from repro.launch.mesh import make_worker_mesh

    m, n, q, j = 8, 16, 3, 160
    wmesh = make_worker_mesh(m)
    ky, kt = jax.random.split(jax.random.PRNGKey(4))
    y = jax.random.normal(ky, (n, j))
    t = jax.random.normal(kt, (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=40)

    # Honest-data reference: the attacker's shard is unlearnable (every
    # payload it emits is corrupted), so worker 3's data leaves the pool.
    keep = np.array([i for i in range(m) if i != 3])
    oh = admm.admm_ridge_consensus(
        yw[keep], tw[keep], backend=SimulatedBackend(m - 1), **kw)
    def rel(res):
        return float(jnp.linalg.norm(res.o_star - oh.o_star)
                     / jnp.linalg.norm(oh.o_star))

    pol = parse_policy("trimmed:f=1:rounds=3:byz=3:attack=signflip@torus:2x4")
    mesh_be = MeshBackend(wmesh, policy=pol)
    rob = admm.admm_ridge_consensus(yw, tw, backend=mesh_be, **kw)
    rob2 = admm.admm_ridge_consensus(yw, tw, backend=mesh_be, **kw)
    # Deterministic attack schedule, one lowering for the (policy,
    # fault-model) pair even across repeat solves.
    assert jnp.array_equal(rob.o_star, rob2.o_star)
    assert mesh_be.lowerings == 1, mesh_be.cache_info()
    # Sim-vs-mesh parity under attack (same seeded draws both paths).
    sim = admm.admm_ridge_consensus(
        yw, tw, backend=SimulatedBackend(m, policy=pol), **kw)
    rel_pair = float(jnp.linalg.norm(sim.o_star - rob.o_star)
                     / jnp.linalg.norm(sim.o_star))
    assert rel_pair < 1e-4, rel_pair

    # Robust converges; the non-robust path fails the same bound.
    r_rob = rel(rob)
    vuln = AsyncGossip(rounds=3, topology=Torus(2, 4), faults=pol.faults)
    r_vul = rel(admm.admm_ridge_consensus(
        yw, tw, backend=MeshBackend(wmesh, policy=vuln), **kw))
    assert np.isfinite(r_rob) and r_rob < 0.15, r_rob
    assert (not np.isfinite(r_vul)) or r_vul > 4 * r_rob, (r_rob, r_vul)

    # nanbomb: robust screens the NaN payloads out entirely; the
    # non-robust mix is destroyed by them.
    nb = parse_policy("trimmed:f=1:rounds=3:byz=3:attack=nanbomb@torus:2x4")
    rob_nb = admm.admm_ridge_consensus(
        yw, tw, backend=MeshBackend(wmesh, policy=nb), **kw)
    assert np.isfinite(rel(rob_nb)) and rel(rob_nb) < 0.15, rel(rob_nb)
    vuln_nb = AsyncGossip(rounds=3, topology=Torus(2, 4), faults=nb.faults)
    r_vnb = rel(admm.admm_ridge_consensus(
        yw, tw, backend=MeshBackend(wmesh, policy=vuln_nb), **kw))
    assert not np.isfinite(r_vnb), r_vnb

    # Zero attackers: trimmed == plain serial gossip, bit for bit.
    clean = parse_policy("trimmed:f=1:rounds=3@torus:2x4")
    a = admm.admm_ridge_consensus(
        yw, tw, backend=MeshBackend(wmesh, policy=clean), **kw)
    b = admm.admm_ridge_consensus(
        yw, tw, backend=MeshBackend(
            wmesh, policy=Gossip(rounds=3, topology=Torus(2, 4),
                                 compress=False)), **kw)
    assert jnp.array_equal(a.o_star, b.o_star)
    print("BYZ8_OK", r_rob, r_vul)
    """)
    assert "BYZ8_OK" in out


def test_distributed_admm_on_8_devices():
    out = run_subprocess("""
    from functools import partial
    from jax.experimental.shard_map import shard_map
    from repro.core import admm
    from repro.core.readout import admm_solve_sharded

    n, q, j = 16, 3, 256   # J/8 workers = 32 samples > n: full-rank locals
    y = jax.random.normal(jax.random.PRNGKey(0), (n, j))
    t = jax.random.normal(jax.random.PRNGKey(1), (q, j))
    fn = shard_map(
        partial(admm_solve_sharded, mu=1e-2, eps_radius=6.0, num_iters=300,
                axis_names=("data", "model")),
        mesh=mesh,
        in_specs=(P(None, ("data", "model")), P(None, ("data", "model"))),
        out_specs=jax.tree.map(lambda _: P(), __import__(
            "repro.core.readout", fromlist=["ShardedADMMResult"]
        ).ShardedADMMResult(z=0, objective=0)),
        check_rep=False)
    with mesh:
        res = jax.jit(fn)(y, t)
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=6.0)
    rel = float(jnp.linalg.norm(res.z - oracle) / jnp.linalg.norm(oracle))
    assert rel < 1e-3, rel
    print("ADMM8_OK", rel)
    """)
    assert "ADMM8_OK" in out


def test_spmdlint_wire_mutations_on_8_devices():
    """The wire checker's acceptance mutations on a real M=8 mesh: a
    policy that lies about its wire width trips ``wire-payload``, one
    that misdeclares its eq.-15 scalar count trips ``wire-declaration``,
    and the corresponding honest policies stay clean."""
    out = run_subprocess("""
    import dataclasses
    from repro import analysis
    from repro.core.backend import MeshBackend
    from repro.core.policy import Gossip, parse_policy
    from repro.launch.mesh import make_worker_mesh

    m = 8
    wmesh = make_worker_mesh(m)
    backend = MeshBackend(wmesh)

    # Clean tree first: representative grammar entries honor the
    # declared budget end to end.
    for spec in ("exact", "gossip:3:2", "gossip:2:wire=bf16", "quantized:8"):
        pol = parse_policy(spec)
        found = analysis.check_wire_contract(
            pol, backend, num_iters=4, subject=spec)
        assert found == [], (spec, [f.render() for f in found])

    # Mutation 1: declare a 16-bit wire while shipping f32 payloads.
    @dataclasses.dataclass(frozen=True)
    class LyingGossip(Gossip):
        mode_name = "lying-gossip"

        @property
        def wire_bits(self):
            return 16

    found = analysis.check_wire_contract(
        LyingGossip(rounds=2), backend, num_iters=4, subject="lying")
    assert "wire-payload" in {f.check for f in found}, [
        f.render() for f in found]

    # Mutation 2: comm_scalars drifts off the closed form.
    @dataclasses.dataclass(frozen=True)
    class Misdeclared(Gossip):
        mode_name = "misdeclared-gossip"

        def comm_scalars(self, *, scalars, num_consensus, num_workers=None):
            return super().comm_scalars(
                scalars=scalars, num_consensus=num_consensus,
                num_workers=num_workers) + scalars

    found = analysis.check_wire_contract(
        Misdeclared(rounds=2), backend, num_iters=4, subject="misdeclared")
    assert "wire-declaration" in {f.check for f in found}, [
        f.render() for f in found]
    print("SPMDLINT8_OK")
    """)
    assert "SPMDLINT8_OK" in out
