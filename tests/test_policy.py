"""ConsensusPolicy API: strategy objects, parsing, deprecated aliases,
per-(program, policy) executable caching, and the quantization
properties (property-based via the repro.testing hypothesis shim)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admm, consensus, topology
from repro.core.backend import MeshBackend, SimulatedBackend, make_backend
from repro.core.policy import (
    AsyncGossip,
    ConsensusPolicy,
    ExactMean,
    FaultModel,
    Gossip,
    LossyGossip,
    QuantizedGossip,
    RingGossip,
    StaleMixing,
    parse_policy,
)
from repro.core.topology import (
    FullyConnected,
    Hypercube,
    RandomGeometric,
    Ring,
    TimeVarying,
    Torus,
)
from repro.testing import given, settings, st


def _problem(key, n=16, q=3, j=160, m=4):
    ky, kt = jax.random.split(key)
    y = jax.random.normal(ky, (n, j))
    t = jax.random.normal(kt, (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    return y, t, yw, tw


# ------------------------------------------------------------------
# Declared communication footprint (eq. 15)
# ------------------------------------------------------------------

def test_policy_declared_footprints():
    assert ExactMean().exchanges_per_round == 1
    assert ExactMean().wire_bits == 32
    assert RingGossip(rounds=3, degree=2).exchanges_per_round == 12
    assert QuantizedGossip(bits=4).exchanges_per_round == 1
    assert QuantizedGossip(bits=4).wire_bits == 4
    assert LossyGossip(drop_prob=0.1, rounds=2, degree=2).exchanges_per_round == 8
    assert StaleMixing(2).exchanges_per_round == 1
    assert ExactMean().is_exact and StaleMixing(0).is_exact
    assert not StaleMixing(1).is_exact and not RingGossip().is_exact


def test_policies_are_hashable_value_objects():
    assert ExactMean() == ExactMean()
    assert hash(RingGossip(2, 1)) == hash(RingGossip(2, 1))
    assert QuantizedGossip(bits=8) != QuantizedGossip(bits=4)
    assert isinstance(ExactMean(), ConsensusPolicy)


# ------------------------------------------------------------------
# Parsing + validation
# ------------------------------------------------------------------

def test_parse_policy_specs():
    assert parse_policy("exact") == ExactMean()
    assert parse_policy("gossip:3") == RingGossip(rounds=3, degree=1)
    assert parse_policy("gossip:3:2") == RingGossip(rounds=3, degree=2)
    assert parse_policy("gossip", degree=2) == RingGossip(rounds=1, degree=2)
    assert parse_policy("quantized:4") == QuantizedGossip(bits=4)
    assert parse_policy("lossy:0.1") == LossyGossip(drop_prob=0.1)
    assert parse_policy("lossy:0.2:3:2") == LossyGossip(
        drop_prob=0.2, rounds=3, degree=2
    )
    assert parse_policy("stale:2") == StaleMixing(delay=2)


def test_parse_policy_error_paths():
    with pytest.raises(ValueError, match="unknown consensus policy"):
        parse_policy("telepathy")
    with pytest.raises(ValueError, match="bad consensus policy spec"):
        parse_policy("gossip:many")
    with pytest.raises(ValueError, match="bad consensus policy spec"):
        parse_policy("lossy:1.5")
    # Trailing segments are an error, never silently dropped.
    with pytest.raises(ValueError, match="at most"):
        parse_policy("quantized:8:4")
    with pytest.raises(ValueError, match="at most"):
        parse_policy("exact:whatever")
    with pytest.raises(ValueError, match="at most"):
        parse_policy("stale:2:1")


def test_parse_policy_flag_fallbacks():
    """The launcher's --degree/--rounds flags fill unspecified segments
    for every gossip-family spec, not just bare 'gossip'."""
    assert parse_policy("gossip", rounds=10, degree=2) == RingGossip(
        rounds=10, degree=2
    )
    assert parse_policy("lossy:0.1", rounds=10, degree=2) == LossyGossip(
        drop_prob=0.1, rounds=10, degree=2
    )
    # Explicit spec segments beat the flag fallbacks.
    assert parse_policy("gossip:3", rounds=10) == RingGossip(rounds=3, degree=1)


def test_policy_validation():
    with pytest.raises(ValueError, match="degree"):
        RingGossip(rounds=1, degree=0)
    with pytest.raises(ValueError, match="rounds"):
        RingGossip(rounds=0)
    with pytest.raises(ValueError, match="bits"):
        QuantizedGossip(bits=0)
    with pytest.raises(ValueError, match="drop_prob"):
        LossyGossip(drop_prob=1.0)
    with pytest.raises(ValueError, match="delay"):
        StaleMixing(-1)
    with pytest.raises(ValueError, match="neighbours"):
        SimulatedBackend(4, policy=RingGossip(rounds=1, degree=2))
    with pytest.raises(ValueError, match="neighbours"):
        SimulatedBackend(4, policy=LossyGossip(drop_prob=0.1, degree=2))


# ------------------------------------------------------------------
# Removed string-mode aliases: clean TypeError with a migration hint
# ------------------------------------------------------------------

def test_mode_string_alias_is_removed():
    with pytest.raises(TypeError, match="mode.*removed.*parse_policy"):
        SimulatedBackend(8, mode="gossip", degree=2, num_rounds=5)
    with pytest.raises(TypeError, match="mode.*removed.*parse_policy"):
        make_backend("simulated", 4, mode="exact")
    with pytest.raises(TypeError, match="num_rounds.*removed"):
        SimulatedBackend(8, num_rounds=5)
    with pytest.raises(TypeError, match="mode.*removed"):
        MeshBackend(mode="exact")
    # The migration target works: spec strings / policy objects only.
    assert make_backend("simulated", 4, policy="exact").policy == ExactMean()
    # Unrelated unknown kwargs still fail like any Python signature.
    with pytest.raises(TypeError, match="unexpected keyword"):
        SimulatedBackend(4, flavor="exact")


def test_default_backend_has_exact_policy_without_warning():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        b = SimulatedBackend(4)
    assert b.policy == ExactMean()


# ------------------------------------------------------------------
# ExactMean == legacy 'exact' mode, bit for bit
# ------------------------------------------------------------------

def test_exact_mean_policy_bit_identical_to_default():
    _, _, yw, tw = _problem(jax.random.PRNGKey(0))
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=50)
    a = admm.admm_ridge_consensus(yw, tw, backend=SimulatedBackend(4), **kw)
    b = admm.admm_ridge_consensus(
        yw, tw, backend=SimulatedBackend(4), policy=ExactMean(), **kw
    )
    assert jnp.array_equal(a.o_star, b.o_star)
    assert jnp.array_equal(a.trace.objective, b.trace.objective)


def test_ring_gossip_policy_matches_dense_h():
    m, degree, rounds = 8, 2, 5
    x = jax.random.normal(jax.random.PRNGKey(2), (m, 4, 6))
    h = topology.circular_mixing_matrix(m, degree)
    want = consensus.gossip_average(x, h, rounds)
    backend = SimulatedBackend(m, policy=RingGossip(rounds=rounds, degree=degree))
    got = backend.run(backend.consensus_mean, x)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


# ------------------------------------------------------------------
# Executable cache: one lowering per (program, policy)
# ------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["simulated", "mesh"])
def test_one_lowering_per_policy_no_per_call_retrace(kind):
    if kind == "mesh":
        from repro.launch.mesh import make_worker_mesh

        backend = MeshBackend(make_worker_mesh(1))
        m = 1
    else:
        backend = SimulatedBackend(4)
        m = 4
    _, _, yw, tw = _problem(jax.random.PRNGKey(3), m=m)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=10, backend=backend)
    policies = [ExactMean(), StaleMixing(2), QuantizedGossip(bits=8)]
    for pol in policies:
        admm.admm_ridge_consensus(yw, tw, policy=pol, **kw)
    assert backend.lowerings == len(policies), backend.cache_info()
    # Second sweep over the same policies: zero new lowerings.
    for pol in policies:
        admm.admm_ridge_consensus(yw, tw, policy=pol, **kw)
    assert backend.lowerings == len(policies), backend.cache_info()
    assert backend.cache_hits == len(policies)


def test_fused_layer_step_policy_in_cache_key():
    from repro.core import engine

    m = 4
    _, _, yw, tw = _problem(jax.random.PRNGKey(4), m=m)
    backend = SimulatedBackend(m)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=10)
    engine.fused_layer_step(backend, yw, tw, None, **kw)
    engine.fused_layer_step(backend, yw, tw, None, policy=StaleMixing(1), **kw)
    assert backend.lowerings == 2, backend.cache_info()
    engine.fused_layer_step(backend, yw, tw, None, policy=StaleMixing(1), **kw)
    assert backend.lowerings == 2, backend.cache_info()


# ------------------------------------------------------------------
# Topology-first gossip: the mixing graph as a policy parameter
# ------------------------------------------------------------------

def test_ring_gossip_is_gossip_over_ring_topology():
    """The PR-3 constructor is now a value-equal alias of the
    topology-parameterized policy."""
    pol = RingGossip(rounds=3, degree=2)
    assert isinstance(pol, Gossip)
    assert pol == Gossip(rounds=3, topology=Ring(2))
    assert (pol.rounds, pol.degree) == (3, 2)
    assert hash(pol) == hash(Gossip(rounds=3, topology=Ring(2)))


def test_ring_gossip_alias_bit_identical_to_raw_ring_hops():
    """Gossip(B, Ring(d), compress=False) must produce the exact float
    sequence of the PR-3 ppermute implementation
    (consensus.ring_gossip_average); the default compressed form mixes
    once with H^B and only matches to float-reassociation tolerance."""
    m, degree, rounds = 8, 2, 5
    x = jax.random.normal(jax.random.PRNGKey(2), (m, 4, 6))
    backend = SimulatedBackend(
        m, policy=RingGossip(rounds=rounds, degree=degree, compress=False)
    )
    got = backend.run(backend.consensus_mean, x)

    def raw(v):
        return consensus.ring_gossip_average(
            v, backend.axis_name, degree=degree, num_nodes=m, num_rounds=rounds
        )

    want = backend.run(raw, x, key="raw-ring-hops")
    assert jnp.array_equal(got, want)
    # Compressed (the default): same mixing up to float reassociation.
    comp = SimulatedBackend(
        m, policy=RingGossip(rounds=rounds, degree=degree)
    )
    got_c = comp.run(comp.consensus_mean, x)
    assert float(jnp.max(jnp.abs(got_c - want))) < 1e-5
    # ...and a single round needs no compression: bit-identical as-is.
    one = SimulatedBackend(m, policy=RingGossip(rounds=1, degree=degree))
    raw1 = SimulatedBackend(
        m, policy=RingGossip(rounds=1, degree=degree, compress=False)
    )
    assert jnp.array_equal(
        one.run(one.consensus_mean, x), raw1.run(raw1.consensus_mean, x)
    )


@pytest.mark.parametrize(
    "topo",
    [
        Torus(2, 4),
        Hypercube(),
        FullyConnected(),
        RandomGeometric(radius=0.5, seed=1),
    ],
    ids=lambda t: t.name,
)
def test_gossip_topology_matches_dense_h(topo):
    """B rounds of in-program exchange-schedule gossip == H^B @ x."""
    m, rounds = 8, 3
    x = jax.random.normal(jax.random.PRNGKey(3), (m, 4, 6))
    backend = SimulatedBackend(m, policy=Gossip(rounds=rounds, topology=topo))
    got = backend.run(backend.consensus_mean, x)
    want = consensus.gossip_average(x, topo.mixing_matrix(m), rounds)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_time_varying_gossip_cycles_schedules():
    tv = TimeVarying((Ring(1), Hypercube()))
    m = 8
    x = jax.random.normal(jax.random.PRNGKey(4), (m, 3, 5))
    backend = SimulatedBackend(m, policy=Gossip(rounds=2, topology=tv))
    got = backend.run(backend.consensus_mean, x)
    want = consensus.gossip_average(
        consensus.gossip_average(x, Ring(1).mixing_matrix(m), 1),
        Hypercube().mixing_matrix(m), 1,
    )
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_lossy_gossip_over_topology_drop_zero_equals_gossip():
    m = 8
    x = jax.random.normal(jax.random.PRNGKey(5), (m, 4, 4))
    lossy = SimulatedBackend(
        m, policy=LossyGossip(drop_prob=0.0, rounds=3, topology=Torus(2, 4))
    )
    clean = SimulatedBackend(m, policy=Gossip(rounds=3, topology=Torus(2, 4)))
    a = lossy.run(lossy.consensus_mean, x)
    b = clean.run(clean.consensus_mean, x)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-6


def test_stale_mixing_over_topology_one_shot_is_h_average():
    """Steady-state stale mix over a graph = one H-average (the fresh-
    value substitution collapses when msg == x)."""
    m = 8
    x = jax.random.normal(jax.random.PRNGKey(6), (m, 3, 4))
    topo = Hypercube()
    backend = SimulatedBackend(m, policy=StaleMixing(2, topology=topo))
    got = backend.run(backend.consensus_mean, x)
    want = consensus.gossip_average(x, topo.mixing_matrix(m), 1)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_quantized_gossip_over_topology_tracks_mean():
    """High-bit quantized topology gossip stays within a few quantization
    steps of the plain gossip result."""
    m = 8
    x = jax.random.normal(jax.random.PRNGKey(7), (m, 4, 4))
    topo = FullyConnected()
    qb = SimulatedBackend(
        m, policy=QuantizedGossip(bits=16, rounds=1, topology=topo)
    )
    got = qb.run(qb.consensus_mean, x)
    want = consensus.gossip_average(x, topo.mixing_matrix(m), 1)
    step = float(x.max() - x.min()) / (2 ** 16 - 1)
    assert float(jnp.max(jnp.abs(got - want))) < 8 * step


def test_topology_exchange_accounting():
    assert Gossip(rounds=3, topology=Ring(2)).exchanges_per_round == 12
    assert Gossip(rounds=2, topology=Torus(2, 4)).exchanges_per_round == 6
    assert Gossip(rounds=2, topology=Hypercube()).exchanges_for(8) == 6
    assert Gossip(rounds=1, topology=FullyConnected()).exchanges_for(8) == 7
    tv = Gossip(rounds=4, topology=TimeVarying((Ring(1), Hypercube())))
    assert tv.exchanges_for(8) == 2 + 3 + 2 + 3
    assert QuantizedGossip(bits=4).exchanges_for(8) == 1
    assert QuantizedGossip(
        bits=4, rounds=2, topology=Hypercube()
    ).exchanges_for(8) == 6
    assert StaleMixing(1, topology=Torus(2, 4)).exchanges_for(8) == 3
    # M-dependent degree without M is an explicit error, never a guess.
    with pytest.raises(ValueError, match="num_workers"):
        Gossip(rounds=1, topology=Hypercube()).exchanges_per_round
    # wire_bytes threads M through.
    pol = Gossip(rounds=2, topology=Hypercube())
    assert pol.wire_bytes(scalars=10, num_consensus=5, num_workers=8) == (
        10 * 6 * 5 * 32 // 8
    )


def test_policy_topology_validation():
    with pytest.raises(ValueError, match="torus"):
        SimulatedBackend(8, policy=Gossip(topology=Torus(3, 3)))
    with pytest.raises(ValueError, match="power-of-two"):
        SimulatedBackend(6, policy=Gossip(topology=Hypercube()))
    with pytest.raises(ValueError, match="time-varying"):
        SimulatedBackend(
            8, policy=StaleMixing(1, topology=TimeVarying((Ring(1), Ring(2))))
        )
    with pytest.raises(TypeError, match="Topology"):
        Gossip(rounds=1, topology="ring:2")


def test_parse_policy_with_topology():
    topo = Torus(2, 4)
    assert parse_policy("gossip:4", topology=topo) == Gossip(4, topo)
    assert parse_policy("gossip:4", topology="torus:2x4") == Gossip(4, topo)
    assert parse_policy("quantized:4", topology=topo, rounds=2) == (
        QuantizedGossip(bits=4, rounds=2, topology=topo)
    )
    assert parse_policy("lossy:0.1:3", topology=topo) == LossyGossip(
        drop_prob=0.1, rounds=3, topology=topo
    )
    assert parse_policy("stale:2", topology=topo) == StaleMixing(
        delay=2, topology=topo
    )
    with pytest.raises(ValueError, match="no topology"):
        parse_policy("exact", topology=topo)
    with pytest.raises(ValueError, match="not both"):
        parse_policy("gossip:4:2", topology=topo)
    with pytest.raises(ValueError, match="not both"):
        parse_policy("lossy:0.1:3:2", topology=topo)


def test_gossip_topology_in_executable_cache_key():
    """Two policies differing only in topology lower separately and hit
    the cache on repeats — the graph is part of the compiled program."""
    m = 8
    _, _, yw, tw = _problem(jax.random.PRNGKey(5), m=m)
    backend = SimulatedBackend(m)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=10, backend=backend)
    pols = [
        Gossip(rounds=2, topology=Ring(2)),
        Gossip(rounds=2, topology=Torus(2, 4)),
        Gossip(rounds=2, topology=Hypercube()),
    ]
    for pol in pols:
        admm.admm_ridge_consensus(yw, tw, policy=pol, **kw)
    assert backend.lowerings == len(pols), backend.cache_info()
    for pol in pols:
        admm.admm_ridge_consensus(yw, tw, policy=pol, **kw)
    assert backend.lowerings == len(pols), backend.cache_info()


# ------------------------------------------------------------------
# Quantization properties (repro.testing hypothesis shim)
# ------------------------------------------------------------------

@given(bits=st.sampled_from([4, 8, 12]), seed=st.integers(0, 3))
@settings(max_examples=9, deadline=None)
def test_quantize_stochastic_unbiased_and_bounded(bits, seed):
    """E[q(x)] = x and |q(x) - x| <= one quantization step per draw."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (64, 64))
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), 32)
    qs = jnp.stack([consensus.quantize_stochastic(x, bits, k) for k in keys])
    step = float((x.max() - x.min()) / (2 ** bits - 1))
    assert float(jnp.max(jnp.abs(qs[0] - x))) <= step + 1e-6
    bias = float(jnp.max(jnp.abs(qs.mean(0) - x)))
    assert bias < 4 * step / np.sqrt(32) + 1e-3


@given(bits=st.sampled_from([4, 6, 8]), seed=st.integers(0, 2))
@settings(max_examples=6, deadline=None)
def test_quantized_gossip_preserves_mean_in_expectation(bits, seed):
    """The doubly-stochastic invariant in expectation: averaging the
    QuantizedGossip output over many PRNG draws recovers the true worker
    mean, because each message is unbiasedly quantized before the
    all-reduce."""
    m, reps = 4, 64
    policy = QuantizedGossip(bits=bits, seed=seed)
    backend = SimulatedBackend(m, policy=policy)
    ctx = backend.ctx()
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, 6, 5))

    def worker(x_m):
        state = policy.init_state(x_m, ctx)

        def body(s, _):
            y, s = policy.mix(x_m, s, ctx)
            return s, y

        _, ys = jax.lax.scan(body, state, None, length=reps)
        return ys.mean(0)

    out = backend.run(worker, x, key=("quant-mean", bits, seed, reps))
    exact = jnp.broadcast_to(x.mean(0), x.shape)
    # Per-worker quantization step bounds the variance of each draw.
    step = float(
        jnp.max(jnp.max(x, axis=(1, 2)) - jnp.min(x, axis=(1, 2)))
    ) / (2 ** bits - 1)
    tol = 4 * step / np.sqrt(reps) + 1e-3
    assert float(jnp.max(jnp.abs(out - exact))) < tol


def test_stale_one_shot_returns_the_mean():
    """consensus_mean (one_shot) under a stale policy must still be an
    average: the window is seeded at steady state, not with the empty
    zero buffer (which would return x/M)."""
    m = 4
    x = jnp.arange(float(m)).reshape(m, 1)
    for delay in (0, 1, 2):
        backend = SimulatedBackend(m, policy=StaleMixing(delay))
        out = backend.run(backend.consensus_mean, x)
        assert jnp.allclose(out, 1.5), (delay, out)


def test_deterministic_quantizer_has_zero_variance():
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 32))
    a = consensus.quantize_nearest(x, 6)
    b = consensus.quantize_nearest(x, 6)
    assert jnp.array_equal(a, b)
    step = float((x.max() - x.min()) / (2 ** 6 - 1))
    assert float(jnp.max(jnp.abs(a - x))) <= 0.5 * step + 1e-6


# ------------------------------------------------------------------
# Compressed gossip schedules (H^B as one mix)
# ------------------------------------------------------------------

@pytest.mark.parametrize(
    "topo",
    [Ring(2), Torus(2, 4), Hypercube(), RandomGeometric(radius=0.5, seed=1),
     TimeVarying((Ring(1), Hypercube()))],
    ids=lambda t: t.name,
)
def test_compressed_gossip_matches_serial(topo):
    """compress=True (default) mixes once with H^B; must equal the
    B-round serial schedule to f32 reassociation tolerance."""
    m, rounds = 8, 4
    x = jax.random.normal(jax.random.PRNGKey(8), (m, 4, 6))
    comp = SimulatedBackend(m, policy=Gossip(rounds=rounds, topology=topo))
    serial = SimulatedBackend(
        m, policy=Gossip(rounds=rounds, topology=topo, compress=False)
    )
    a = comp.run(comp.consensus_mean, x)
    b = serial.run(serial.consensus_mean, x)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_compressed_gossip_reduces_hops():
    """The whole point: |support(H^B)| hops in ONE round instead of
    B x edges serial ones (the eq.-15 exchange count is unchanged —
    compression is an execution-schedule optimization)."""
    pol = RingGossip(rounds=4, degree=2)
    serial = RingGossip(rounds=4, degree=2, compress=False)
    assert pol.hops_for(8) < serial.hops_for(8)
    assert serial.hops_for(8) == 16
    assert pol.hops_for(8) <= 7   # H^4 support on M=8 is at most dense
    assert pol.exchanges_for(8) == serial.exchanges_for(8) == 16
    # Single-round gossip has nothing to compress.
    assert RingGossip(rounds=1, degree=2).hops_for(8) == 4


def test_compress_flag_is_part_of_cache_key():
    m = 8
    _, _, yw, tw = _problem(jax.random.PRNGKey(9), m=m)
    backend = SimulatedBackend(m)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=10, backend=backend)
    pols = [
        Gossip(rounds=2, topology=Ring(2)),
        Gossip(rounds=2, topology=Ring(2), compress=False),
        Gossip(rounds=2, topology=Ring(2), wire_dtype="bf16"),
    ]
    for pol in pols:
        admm.admm_ridge_consensus(yw, tw, policy=pol, **kw)
    assert backend.lowerings == len(pols), backend.cache_info()
    for pol in pols:
        admm.admm_ridge_consensus(yw, tw, policy=pol, **kw)
    assert backend.lowerings == len(pols), backend.cache_info()


# ------------------------------------------------------------------
# Low-precision wire formats
# ------------------------------------------------------------------

def test_wire_dtype_accounting_and_aliases():
    assert Gossip(rounds=2, wire_dtype="bfloat16").wire_bits == 16
    assert Gossip(rounds=2, wire_dtype="bf16") == Gossip(
        rounds=2, wire_dtype="bfloat16"
    )
    assert Gossip(rounds=2, wire_dtype="f16").wire_bits == 16
    assert Gossip(rounds=2).wire_bits == 32
    assert LossyGossip(drop_prob=0.1, wire_dtype="bf16").wire_bits == 16
    assert StaleMixing(1, wire_dtype="f16").wire_bits == 16
    # bf16 wire halves the eq.-15 bytes at the same exchange count.
    full = RingGossip(rounds=4, degree=2)
    half = RingGossip(rounds=4, degree=2, wire_dtype="bf16")
    kw = dict(scalars=100, num_consensus=10, num_workers=8)
    assert half.wire_bytes(**kw) * 2 == full.wire_bytes(**kw)
    with pytest.raises(ValueError, match="wire dtype"):
        Gossip(rounds=1, wire_dtype="int8")


def test_wire_dtype_mix_close_to_full_precision():
    """bf16 links perturb the mix by at most a few bf16 ulps of the
    payload scale — the accumulation stays f32."""
    m = 8
    x = jax.random.normal(jax.random.PRNGKey(10), (m, 4, 6))
    for pol_lo, pol_hi in [
        (Gossip(rounds=3, topology=Ring(2), wire_dtype="bf16"),
         Gossip(rounds=3, topology=Ring(2))),
        (StaleMixing(2, wire_dtype="bf16"), StaleMixing(2)),
    ]:
        lo = SimulatedBackend(m, policy=pol_lo)
        hi = SimulatedBackend(m, policy=pol_hi)
        a = lo.run(lo.consensus_mean, x)
        b = hi.run(hi.consensus_mean, x)
        err = float(jnp.max(jnp.abs(a - b)))
        assert 0 < err < 0.05, (pol_lo, err)  # narrow but sane wire


def test_stale_wire_dtype_breaks_exactness():
    assert StaleMixing(0).is_exact
    assert not StaleMixing(0, wire_dtype="bf16").is_exact


# ------------------------------------------------------------------
# LossyGossip: topology= is authoritative, degree= a Ring shorthand
# ------------------------------------------------------------------

def test_lossy_degree_is_ring_shorthand():
    a = LossyGossip(drop_prob=0.1, rounds=2, degree=2)
    b = LossyGossip(drop_prob=0.1, rounds=2, topology=Ring(2))
    assert a == b and hash(a) == hash(b)
    assert a.topology == Ring(2)
    assert a.degree == 2          # legacy view reads the stored graph
    assert ", degree=" not in repr(a)  # no duplicated top-level field
    assert repr(a) == repr(b)
    with pytest.raises(ValueError, match="not both"):
        LossyGossip(drop_prob=0.1, degree=2, topology=Ring(2))
    # The bare default is the paper's degree-1 ring.
    assert LossyGossip(drop_prob=0.1).topology == Ring(1)


def test_lossy_round_trips_through_replace_and_apply():
    """degree= must stay out of the dataclass fields so replace() (and
    therefore apply_topology/apply_wire_dtype — the TrainSpec
    wire_dtype/topology path) reconstructs without a degree/topology
    conflict."""
    import dataclasses

    from repro.dssfn import apply_topology, apply_wire_dtype

    a = LossyGossip(drop_prob=0.1, rounds=2, degree=2)
    b = dataclasses.replace(a, wire_dtype="bfloat16")
    assert b.topology == Ring(2) and b.wire_bits == 16
    assert apply_topology(a, Torus(2, 4)).topology == Torus(2, 4)
    assert apply_wire_dtype(a, "f16").wire_dtype == "float16"


# ------------------------------------------------------------------
# spec -> policy -> repr round trip for the whole --consensus grammar
# ------------------------------------------------------------------

_GRAMMAR_SPECS = [
    "exact",
    "gossip", "gossip:3", "gossip:3:2",
    "quantized:4", "quantized:8",
    "lossy:0.1", "lossy:0.2:3", "lossy:0.2:3:2",
    "stale:0", "stale:2",
    "gossip:3:wire=bf16", "stale:2:wire=f16",
    "async", "async:interval=4", "async:rounds=2:drop=0.1:seed=7",
    "async:interval=2:fail=1+3:fail_at=30",
    "async:stragglers=0:straggle=2:drop=0.05",
]


@pytest.mark.parametrize("spec", _GRAMMAR_SPECS)
def test_spec_policy_repr_round_trip(spec):
    """Every --consensus grammar entry parses to a value object whose
    repr reconstructs an equal policy (no hidden/duplicated state), and
    re-parsing the spec is stable."""
    namespace = {
        "ExactMean": ExactMean, "Gossip": Gossip, "RingGossip": RingGossip,
        "QuantizedGossip": QuantizedGossip, "LossyGossip": LossyGossip,
        "StaleMixing": StaleMixing, "AsyncGossip": AsyncGossip,
        "FaultModel": FaultModel, "Ring": Ring, "Torus": Torus,
        "Hypercube": Hypercube, "FullyConnected": FullyConnected,
        "RandomGeometric": RandomGeometric, "TimeVarying": TimeVarying,
    }
    pol = parse_policy(spec)
    clone = eval(repr(pol), namespace)  # noqa: S307 - test-controlled reprs
    assert clone == pol
    assert hash(clone) == hash(pol)
    assert repr(clone) == repr(pol)
    assert parse_policy(spec) == pol
