"""Non-ideal networks as ConsensusPolicy objects (the paper's §IV
future-work axis — quantized / lossy / asynchronous peer-to-peer
consensus), running through the same backend + compile-once engine as
the ideal-network path.  Includes the centralized-proximity guarantees:
each policy's final solution stays within a stated tolerance of the
exact-consensus run on the synthetic task."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import admm, topology
from repro.core.backend import SimulatedBackend
from repro.core.policy import (
    ExactMean,
    LossyGossip,
    QuantizedGossip,
    RingGossip,
    StaleMixing,
)


def _problem(key, n=16, q=3, j=160, m=8):
    ky, kt = jax.random.split(key)
    y = jax.random.normal(ky, (n, j))
    t = jax.random.normal(kt, (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    return y, t, yw, tw


def _rel_to_oracle(res, oracle):
    return float(jnp.linalg.norm(res.o_star - oracle) / jnp.linalg.norm(oracle))


# --------------------------------------------------------- stale (async)

def test_stale_delay0_bit_identical_to_exact():
    _, _, yw, tw = _problem(jax.random.PRNGKey(0))
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=60, backend=SimulatedBackend(8))
    sync = admm.admm_ridge_consensus(yw, tw, policy=ExactMean(), **kw)
    st0 = admm.admm_ridge_consensus(yw, tw, policy=StaleMixing(0), **kw)
    assert jnp.array_equal(sync.o_star, st0.o_star)


def test_stale_mixing_converges_to_oracle():
    """Peers working from 2-rounds-stale values still reach the
    centralized solution — the asynchrony tolerance the paper projects
    for the ADMM route (ref [15] ARock)."""
    y, t, yw, tw = _problem(jax.random.PRNGKey(2))
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=6.0)
    res = admm.admm_ridge_consensus(
        yw, tw, mu=1e-2, eps_radius=6.0, num_iters=300,
        backend=SimulatedBackend(8), policy=StaleMixing(2),
    )
    assert _rel_to_oracle(res, oracle) < 1e-3


def test_stale_no_worse_than_exact_objective():
    _, _, yw, tw = _problem(jax.random.PRNGKey(4))
    k = 60
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=k, backend=SimulatedBackend(8))
    sync = admm.admm_ridge_consensus(yw, tw, policy=ExactMean(), **kw)
    stale = admm.admm_ridge_consensus(yw, tw, policy=StaleMixing(3), **kw)
    assert float(stale.trace.objective[-1]) >= float(sync.trace.objective[-1]) - 1e-3


# ----------------------------------------------------------- lossy links

def test_lossy_zero_drop_matches_ring_gossip():
    _, _, yw, tw = _problem(jax.random.PRNGKey(5))
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=40, backend=SimulatedBackend(8))
    clean = admm.admm_ridge_consensus(
        yw, tw, policy=RingGossip(rounds=5, degree=2), **kw
    )
    lossy = admm.admm_ridge_consensus(
        yw, tw, policy=LossyGossip(drop_prob=0.0, rounds=5, degree=2), **kw
    )
    np.testing.assert_allclose(
        np.asarray(lossy.o_star), np.asarray(clean.o_star), atol=1e-5
    )


def test_lossy_gossip_still_contracts():
    """With moderate loss, workers still agree (consensus) even though
    the per-round renormalization can bias the agreed value off the true
    mean — the failure mode the relaxed-ADMM literature (paper ref [16])
    addresses."""
    m = 8
    policy = LossyGossip(drop_prob=0.2, rounds=40, degree=3)
    backend = SimulatedBackend(m, policy=policy)
    x = jax.random.normal(jax.random.PRNGKey(2), (m, 4))
    out = backend.run(backend.consensus_mean, x)
    spread = float(jnp.max(jnp.abs(out - out.mean(0, keepdims=True))))
    assert spread < 1e-2, spread
    bias = float(jnp.max(jnp.abs(out.mean(0) - x.mean(0))))
    assert bias < 1.0  # bounded, generally nonzero


def test_lossy_centralized_proximity():
    """10% link drops: final solution within 10% of the exact-consensus
    run (and the exact run sits on the oracle)."""
    y, t, yw, tw = _problem(jax.random.PRNGKey(6))
    h = topology.circular_mixing_matrix(8, 2)
    rounds = topology.gossip_rounds_for_tolerance(h, 1e-8)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=200, backend=SimulatedBackend(8))
    exact = admm.admm_ridge_consensus(yw, tw, policy=ExactMean(), **kw)
    lossy = admm.admm_ridge_consensus(
        yw, tw, policy=LossyGossip(drop_prob=0.1, rounds=rounds + 10, degree=2), **kw
    )
    rel = float(
        jnp.linalg.norm(lossy.o_star - exact.o_star)
        / jnp.linalg.norm(exact.o_star)
    )
    assert rel < 0.10, rel


def test_dssfn_survives_lossy_network():
    """End-to-end dSSFN over a 10% lossy network through the fused layer
    engine: accuracy parity with the lossless run within a modest
    margin."""
    from repro.core import layerwise, ssfn
    from repro.data import make_classification, partition_workers

    data = make_classification(
        jax.random.PRNGKey(0), num_train=320, num_test=160,
        input_dim=12, num_classes=4,
    )
    cfg = ssfn.SSFNConfig(
        input_dim=12, num_classes=4, num_layers=3, hidden=48,
        mu0=1e-2, mul=1e-2, admm_iters=120,
    )
    m = 8
    xw, tw = partition_workers(data.x_train, data.t_train, m)
    h = topology.circular_mixing_matrix(m, 2)
    rounds = topology.gossip_rounds_for_tolerance(h, 1e-8)
    key = jax.random.PRNGKey(7)
    p_clean, _ = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, key, backend=SimulatedBackend(m),
        policy=RingGossip(rounds=rounds, degree=2),
    )
    p_lossy, _ = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, key, backend=SimulatedBackend(m),
        policy=LossyGossip(drop_prob=0.1, rounds=rounds + 10, degree=2),
    )
    acc_c = layerwise.accuracy(p_clean, data.x_test, data.y_test, 4)
    acc_l = layerwise.accuracy(p_lossy, data.x_test, data.y_test, 4)
    assert acc_l > acc_c - 0.10, (acc_c, acc_l)


# ------------------------------------------------------ quantized links

def test_quantized_consensus_admm_near_oracle():
    """8-bit links: ADMM still converges near the oracle, with 4x less
    traffic than f32 (eq. 15 scaled by wire_bits/32)."""
    y, t, yw, tw = _problem(jax.random.PRNGKey(6))
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=6.0)
    policy = QuantizedGossip(bits=8)
    res = admm.admm_ridge_consensus(
        yw, tw, mu=1e-2, eps_radius=6.0, num_iters=200,
        backend=SimulatedBackend(8), policy=policy,
    )
    assert _rel_to_oracle(res, oracle) < 5e-2
    assert policy.wire_bits == 8


def test_quantized_through_layerwise_training():
    """Quantized links through the whole layer-wise loop: comm accounting
    picks up the policy's exchange count and training still classifies."""
    from repro.core import layerwise, ssfn

    m = 4
    cfg = ssfn.SSFNConfig(
        input_dim=8, num_classes=3, num_layers=1, hidden=20, admm_iters=30
    )
    kx, kt, kinit = jax.random.split(jax.random.PRNGKey(8), 3)
    xw = jax.random.normal(kx, (m, 8, 16))
    labels = jax.random.randint(kt, (m, 16), 0, 3)
    tw = jax.nn.one_hot(labels, 3).transpose(0, 2, 1)
    backend = SimulatedBackend(m)
    p_exact, log_e = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, kinit, backend=backend
    )
    p_quant, log_q = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, kinit, backend=backend, policy=QuantizedGossip(bits=12)
    )
    # Same scalar count on the wire (the byte saving is wire_bits/32).
    assert log_q.comm_scalars == log_e.comm_scalars
    for a, b in zip(p_exact.o, p_quant.o):
        rel = float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(a), 1e-30))
        assert rel < 5e-2, rel
