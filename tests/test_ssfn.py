"""SSFN architecture + layer-wise training tests (paper §II-B claims)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import equivalence, layerwise, ssfn
from repro.core.policy import Gossip
from repro.core.topology import Ring
from repro.data import make_classification, partition_workers


@pytest.fixture(scope="module")
def dataset():
    return make_classification(
        jax.random.PRNGKey(42),
        num_train=400,
        num_test=200,
        input_dim=12,
        num_classes=5,
    )


@pytest.fixture(scope="module")
def cfg():
    return ssfn.SSFNConfig(
        input_dim=12, num_classes=5, num_layers=4, hidden=64,
        mu0=1e-2, mul=1e-2, admm_iters=200,
    )


def test_weight_structure(cfg):
    """W_{l+1} = [V_Q O_l ; R_{l+1}] with correct shapes (paper eq. 7)."""
    r = ssfn.init_random_matrices(jax.random.PRNGKey(0), cfg)
    assert len(r) == cfg.num_layers
    assert r[0].shape == (cfg.n - 2 * cfg.num_classes, cfg.input_dim)
    for rl in r[1:]:
        assert rl.shape == (cfg.n - 2 * cfg.num_classes, cfg.n)
    o0 = jnp.ones((cfg.num_classes, cfg.input_dim))
    w1 = ssfn.build_weight(o0, r[0], cfg.num_classes)
    assert w1.shape == (cfg.n, cfg.input_dim)
    # top 2Q rows are [O; -O]
    assert jnp.allclose(w1[: cfg.num_classes], o0)
    assert jnp.allclose(w1[cfg.num_classes : 2 * cfg.num_classes], -o0)


def test_lossless_flow_property(cfg):
    """g(V_Q u) retains u: relu(u) - relu(-u) = u — the basis of the
    monotone-cost guarantee."""
    u = jax.random.normal(jax.random.PRNGKey(1), (cfg.num_classes, 32))
    v = jax.nn.relu(ssfn.v_q(cfg.num_classes) @ u)
    recovered = v[: cfg.num_classes] - v[cfg.num_classes :]
    assert jnp.allclose(recovered, u, atol=1e-6)


def test_monotone_cost(dataset, cfg):
    """Training cost decreases monotonically with layer number (paper
    §II-B, Fig. 3 trend)."""
    params, log = layerwise.train_centralized_ssfn(
        dataset.x_train, dataset.t_train, cfg, jax.random.PRNGKey(7)
    )
    costs = log.layer_costs
    for a, b in zip(costs, costs[1:]):
        assert b <= a * (1 + 1e-3), costs


def test_centralized_decentralized_equivalence(dataset, cfg):
    """The paper claim, as the paper itself demonstrates it (Table II):
    dSSFN matches centralized SSFN's *performance*.  Exact per-layer
    solution equivalence is asserted separately in test_admm (the finite-K
    per-layer solver tolerance gets amplified through the ReLU cascade,
    which is why Table II's centralized/decentralized numbers also differ
    slightly)."""
    key = jax.random.PRNGKey(7)
    params_c, _ = layerwise.train_centralized_ssfn(
        dataset.x_train, dataset.t_train, cfg, key
    )
    xw, tw = partition_workers(dataset.x_train, dataset.t_train, 4)
    params_d, _ = layerwise.train_decentralized_ssfn(xw, tw, cfg, key)
    rep = equivalence.compare(params_c, params_d, dataset.x_test, cfg.num_classes)
    assert rep.agreement >= 0.85, rep
    acc_c = layerwise.accuracy(params_c, dataset.x_test, dataset.y_test, cfg.num_classes)
    acc_d = layerwise.accuracy(params_d, dataset.x_test, dataset.y_test, cfg.num_classes)
    assert abs(acc_c - acc_d) < 0.05, (acc_c, acc_d)


def test_learns_better_than_chance(dataset, cfg):
    params, _ = layerwise.train_centralized_ssfn(
        dataset.x_train, dataset.t_train, cfg, jax.random.PRNGKey(3)
    )
    acc = layerwise.accuracy(
        params, dataset.x_test, dataset.y_test, cfg.num_classes
    )
    assert acc > 0.5, acc  # 5 classes, chance = 0.2


def test_forward_shapes(cfg):
    r = ssfn.init_random_matrices(jax.random.PRNGKey(0), cfg)
    o = tuple(
        jnp.zeros((cfg.num_classes, cfg.input_dim if l == 0 else cfg.n))
        for l in range(cfg.num_layers + 1)
    )
    params = ssfn.SSFNParams(o=o, r=r)
    x = jnp.ones((cfg.input_dim, 17))
    pred = ssfn.predict(params, x, cfg.num_classes)
    assert pred.shape == (cfg.num_classes, 17)


def test_self_size_estimation(dataset, cfg):
    """Paper §I: decentralized size estimation — growth stops when the
    consensus cost converges, identically on all workers, with no extra
    communication."""
    xw, tw = partition_workers(dataset.x_train, dataset.t_train, 4)
    params, log = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, jax.random.PRNGKey(0), size_estimation_tol=0.5
    )
    depth = len(params.o) - 1
    assert depth < cfg.num_layers          # the loose tol must trigger early
    assert len(params.r) == depth          # consistent truncated network
    # truncated net still predicts
    pred = ssfn.predict(params, dataset.x_test, cfg.num_classes)
    assert pred.shape[1] == dataset.x_test.shape[1]


def test_comm_accounting(dataset, cfg):
    """eq. (15): total scalars = sum_l Q * n_{l-1} * B * K, with B the
    policy's exchanges per consensus."""
    m = 4
    xw, tw = partition_workers(dataset.x_train, dataset.t_train, m)
    policy = Gossip(rounds=3, topology=Ring(1))
    _, log = layerwise.train_decentralized_ssfn(
        xw, tw, cfg, jax.random.PRNGKey(0), policy=policy
    )
    q, n, k = cfg.num_classes, cfg.n, cfg.admm_iters
    b = policy.exchanges_for(m)
    assert b == 3 * 2          # 3 rounds over 2 ring neighbours
    expected = (q * cfg.input_dim + cfg.num_layers * q * n) * b * k
    assert log.comm_scalars == expected
