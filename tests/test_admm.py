"""ADMM solver tests: centralized equivalence is THE paper claim."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core
from repro.testing import given, settings, st

from repro.core import admm, topology
from repro.core.backend import SimulatedBackend
from repro.core.policy import Gossip, parse_policy


def _problem(key, n, q, j, m):
    ky, kt = jax.random.split(key)
    y = jax.random.normal(ky, (n, j))
    t = jax.random.normal(kt, (q, j))
    yw = y.reshape(n, m, j // m).transpose(1, 0, 2)
    tw = t.reshape(q, m, j // m).transpose(1, 0, 2)
    return y, t, yw, tw


def test_decentralized_matches_exact_oracle():
    y, t, yw, tw = _problem(jax.random.PRNGKey(0), 32, 5, 400, 4)
    eps = 10.0
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=eps)
    res = admm.admm_ridge_consensus(yw, tw, mu=1e-2, eps_radius=eps, num_iters=300)
    rel = float(jnp.linalg.norm(res.o_star - oracle) / jnp.linalg.norm(oracle))
    assert rel < 1e-4, rel


def test_centralized_equals_decentralized_at_convergence():
    y, t, yw, tw = _problem(jax.random.PRNGKey(1), 24, 4, 240, 6)
    eps = 8.0
    cen = admm.centralized_ridge_admm(y, t, mu=1e-2, eps_radius=eps, num_iters=400)
    dec = admm.admm_ridge_consensus(yw, tw, mu=1e-2, eps_radius=eps, num_iters=400)
    rel = float(
        jnp.linalg.norm(cen.o_star - dec.o_star) / jnp.linalg.norm(cen.o_star)
    )
    assert rel < 1e-4, rel


def test_gossip_consensus_preserves_equivalence():
    """dSSFN over a sparse circular graph (paper topology) still converges
    to the centralized solution once gossip rounds are sufficient."""
    y, t, yw, tw = _problem(jax.random.PRNGKey(2), 16, 3, 160, 8)
    eps = 6.0
    h = topology.circular_mixing_matrix(8, 2)
    rounds = topology.gossip_rounds_for_tolerance(h, 1e-9)
    dec = admm.admm_ridge_consensus(
        yw, tw, mu=1e-2, eps_radius=eps, num_iters=200,
        policy=Gossip(rounds=rounds, topology=topology.Ring(2)),
    )
    oracle = admm.exact_constrained_ridge(y, t, eps_radius=eps)
    rel = float(jnp.linalg.norm(dec.o_star - oracle) / jnp.linalg.norm(oracle))
    assert rel < 1e-3, rel


def test_projection_feasibility():
    """Z iterates always satisfy the Frobenius constraint."""
    _, _, yw, tw = _problem(jax.random.PRNGKey(3), 16, 3, 160, 4)
    eps = 0.5  # tight ball: projection active
    res = admm.admm_ridge_consensus(yw, tw, mu=1e-1, eps_radius=eps, num_iters=50)
    assert float(jnp.linalg.norm(res.o_star)) <= eps * (1 + 1e-5)


def test_objective_decreases_overall():
    _, _, yw, tw = _problem(jax.random.PRNGKey(4), 16, 3, 160, 4)
    res = admm.admm_ridge_consensus(yw, tw, mu=1e-2, eps_radius=10.0, num_iters=100)
    obj = np.asarray(res.trace.objective)
    assert obj[-1] < obj[0]
    # primal residual shrinks
    assert res.trace.primal_residual[-1] < res.trace.primal_residual[0]


@given(
    n=st.sampled_from([8, 16, 24]),
    q=st.sampled_from([2, 3, 5]),
    m=st.sampled_from([1, 2, 4]),
    mu=st.sampled_from([1e-3, 1e-2, 1e-1]),
)
@settings(max_examples=12, deadline=None)
def test_admm_solution_feasible_and_finite(n, q, m, mu):
    j = 40 * m
    _, _, yw, tw = _problem(jax.random.PRNGKey(n * q * m), n, q, j, m)
    eps = 2.0 * q
    res = admm.admm_ridge_consensus(yw, tw, mu=mu, eps_radius=eps, num_iters=60)
    assert bool(jnp.all(jnp.isfinite(res.o_star)))
    assert float(jnp.linalg.norm(res.o_star)) <= eps * (1 + 1e-4)


def test_projection_operator():
    z = jnp.ones((3, 4))
    out = admm.project_frobenius(z, 1.0)
    assert abs(float(jnp.linalg.norm(out)) - 1.0) < 1e-6
    z_small = 0.01 * jnp.ones((3, 4))
    assert jnp.allclose(admm.project_frobenius(z_small, 1.0), z_small)


def test_pallas_gram_path_matches_default():
    """ADMM with the Pallas gram kernel == einsum path."""
    _, _, yw, tw = _problem(jax.random.PRNGKey(5), 128, 3, 512, 2)
    kw = dict(mu=1e-2, eps_radius=6.0, num_iters=30)
    a = admm.admm_ridge_consensus(yw, tw, **kw)
    b = admm.admm_ridge_consensus(yw, tw, use_kernels=True, **kw)
    rel = float(jnp.linalg.norm(a.o_star - b.o_star) / jnp.linalg.norm(a.o_star))
    assert rel < 1e-4, rel


# ------------------------------------------------------------------
# The solve: G^{-1} formed once per layer, one float32 product a step
# ------------------------------------------------------------------

def _sub_jaxprs(params):
    for v in params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def _eqns(jaxpr, in_scan=False):
    """Every equation, nested ones too, with whether a scan holds it."""
    for eqn in jaxpr.eqns:
        yield eqn, in_scan
        for sub in _sub_jaxprs(eqn.params):
            yield from _eqns(sub, in_scan or eqn.primitive.name == "scan")


@pytest.mark.parametrize(
    "spec,trace_every,num_iters",
    [("gossip:2", 0, 5), ("gossip:2", 1, 5), ("async:interval=3", 0, 6),
     ("async:interval=3", 1, 6)],
    ids=["gossip-untraced", "gossip-traced", "interval-untraced",
         "interval-traced"],
)
def test_scan_solves_by_one_highest_product(spec, trace_every, num_iters):
    """The K-iteration scan multiplies by the n x n inverse at HIGHEST
    precision and solves nothing; the one cho_solve (two triangular
    solves) runs before it.  The interval policy's local iterations
    (``local_iterate``) go through the same solve."""
    m, n, q, j = 3, 24, 3, 40
    _, _, yw, tw = _problem(jax.random.PRNGKey(11), n, q, j * m, m)
    policy = parse_policy(spec)
    backend = SimulatedBackend(m, policy=policy)

    def worker(y_m, t_m):
        a, chol, _ = admm._worker_stats_local(y_m, t_m, 1e-2, False)
        return admm.worker_admm_iterations(
            backend, a, chol, y_m, t_m, jnp.zeros((q, n)), mu=1e-2,
            eps_radius=6.0, num_iters=num_iters, trace_every=trace_every,
        )

    jaxpr = jax.make_jaxpr(
        jax.vmap(worker, axis_name=backend.axis_name)
    )(yw, tw).jaxpr
    eqns = list(_eqns(jaxpr))
    solves = [in_scan for e, in_scan in eqns
              if e.primitive.name == "triangular_solve"]
    assert solves == [False, False]
    inverse_products = [
        e for e, in_scan in eqns
        if in_scan and e.primitive.name == "dot_general"
        and any(v.aval.shape[-2:] == (n, n) for v in e.invars)
    ]
    assert inverse_products
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    assert all(e.params["precision"] == highest for e in inverse_products)


@pytest.mark.parametrize("mu,tol", [(1e-3, 1.5e-6), (1.0, 4e-6)])
def test_gram_inverse_solve_matches_cho_solve(mu, tol):
    """``rhs @ G^{-1}`` against the per-iteration ``cho_solve`` it
    replaces and against a float64 solve, on a Gram built as the deep
    layers build theirs: relu features, n=96 > J_m=64, so at mu=1 the
    condition is about 3e5 (the paper-width layers read 3.4e5-4.8e5).

    Readings (CPU, float32, seeds 0-7 of this construction): against
    cho_solve 3.1e-7 to 3.3e-7 at mu=1e-3 (condition ~300) and 6.7e-7
    to 8.0e-7 at mu=1; against float64 the ratio of the product's error
    to cho_solve's own is 0.98-1.03 (1.0000 at mu=1).  The same product
    with bfloat16-rounded operands reads 2.2e-3 to 2.6e-3 against
    cho_solve."""
    n, j, q = 96, 64, 5
    k_w, k_x, k_r = jax.random.split(jax.random.PRNGKey(0), 3)
    w = jax.random.normal(k_w, (n, 32))
    y = 3.0 * jax.nn.relu(w @ jax.random.normal(k_x, (32, j)))
    g = y @ y.T + jnp.eye(n) / mu
    rhs = jax.random.normal(k_r, (q, n))
    chol = jnp.linalg.cholesky(g)
    g_inv = admm.gram_inverse(chol)

    def rel(x, ref):
        x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
        return np.linalg.norm(x - ref) / np.linalg.norm(ref)

    g64 = np.asarray(g, np.float64)
    if mu == 1.0:
        assert np.linalg.cond(g64) > 1e4
    new = admm.apply_gram_inverse(rhs, g_inv)
    old = jax.scipy.linalg.cho_solve((chol, True), rhs.T).T
    exact = np.linalg.solve(g64, np.asarray(rhs, np.float64).T).T
    assert new.dtype == jnp.float32
    assert rel(new, old) < tol, rel(new, old)
    assert rel(new, exact) < 1.1 * rel(old, exact), (rel(new, exact), rel(old, exact))
    # The tolerance tells the float32 product from a bfloat16 one.
    bf16 = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    assert rel(admm.apply_gram_inverse(bf16(rhs), bf16(g_inv)), old) > 100 * tol


def test_non_finite_factor_gives_non_finite_readout():
    """A Gram the guarded Cholesky cannot factor (retries spent) leaves
    a NaN factor; the solve must carry it into the readout, where the
    layerwise divergence guard looks for it."""
    m, n, q = 2, 8, 3
    _, _, yw, tw = _problem(jax.random.PRNGKey(12), n, q, 16 * m, m)
    backend = SimulatedBackend(m)

    def worker(y_m, t_m):
        a, _, _ = admm._worker_stats_local(y_m, t_m, 1e-2, False)
        chol, level = admm.guarded_cholesky(-jnp.eye(n))
        (o, z, _), _ = admm.worker_admm_iterations(
            backend, a, chol, y_m, t_m, jnp.zeros((q, n)), mu=1e-2,
            eps_radius=6.0, num_iters=4, trace_every=0,
        )
        return chol, level, o, z

    chol, level, o, z = backend.run(worker, yw, tw, key="nan-factor")
    assert not bool(jnp.all(jnp.isfinite(chol)))
    assert int(level[0]) == 6
    assert not bool(jnp.any(jnp.isfinite(o)))
    assert not bool(jnp.any(jnp.isfinite(z)))
