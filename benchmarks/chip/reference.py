"""Plain reference of decentralized SSFN (arXiv:2009.13982), independent
of the program under test.

It follows the paper's equations in straightforward ``jax.numpy``, with
no kernels, caches or collectives, at float32 and ``HIGHEST`` matmul
precision:

- ``y_0 = x``; ``y_{l+1} = relu(W_{l+1} y_l)`` with
  ``W_{l+1} = [O_l; -O_l; R_{l+1}]`` (``V_Q O_l`` stacked on the shared
  random matrix ``R_{l+1}``, eq. 7);
- each layer solves ``min sum_m ||T_m - O Y_m||^2 s.t. ||O||_F <= eps``
  by K iterations of consensus ADMM (eq. 11) over M workers, every
  worker factoring ``Y_m Y_m^T + I/mu`` once, with ``mu = mu0`` at layer
  0 and ``mul`` above, and the consensus step applying the mixing matrix
  ``H^B`` of B gossip rounds on the degree-d ring (equal weights
  ``1/(2d+1)``) to ``O_m + Lam_m``, each worker projecting its own mixed
  estimate onto the Frobenius ball; the layer's readout is worker 0's;
- the random matrices ``R_1..R_L`` are the shared ones of Algorithm 1,
  drawn from the train's key as ``split(key, L)``, one standard normal
  ``(n - 2Q) x fan_in`` matrix per layer scaled by ``1/sqrt(fan_in)``.

``operands`` is the precision the configuration states for a matmul:
with ``jnp.bfloat16`` each matmul of features, weights and targets
(propagation, Gram, ``T Y^T``, the readout) rounds its operands to
bfloat16 and sums the exact products in float32, which is what a TPU's
default precision does with float32 arrays.  The factorization, the
solves and the consensus average stay float32 at ``HIGHEST``, as on the
TPU.

``low`` computes the same in a lower precision: every array the
reference holds between two operations is rounded to that dtype (the
factorization and the solves read and return rounded arrays).  With
``jnp.bfloat16`` that is the control of both training and serving, whose
configurations state float32 arrays: the comparison must fail it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def operand_dtype(name: str):
    """The dtype a configuration's ``matmul_operands`` names (None for
    float32, whose operands are not rounded)."""
    return None if name == "float32" else jnp.dtype(name).type


def ring_mixing(workers: int, degree: int) -> np.ndarray:
    """H of one gossip round on the degree-``degree`` ring (float64)."""
    if 2 * degree + 1 > workers:
        raise ValueError(f"ring degree {degree} needs 2d+1 <= M={workers}")
    h = np.zeros((workers, workers))
    for m in range(workers):
        for k in range(-degree, degree + 1):
            h[m, (m + k) % workers] = 1.0 / (2 * degree + 1)
    return h


def rounds_for_tolerance(h: np.ndarray, tol: float) -> int:
    """Fewest rounds B with ``|lambda_2(H)|^B <= tol`` (Boyd et al.)."""
    lam2 = np.sort(np.abs(np.linalg.eigvalsh(h)))[-2]
    if lam2 <= 0:
        return 1
    return max(1, int(np.ceil(np.log(tol) / np.log(lam2))))


def gossip_matrix(workers: int, degree: int, tol: float) -> tuple[np.ndarray, int]:
    """``H^B`` as float32 and B, for the rounds that reach ``tol``."""
    h = ring_mixing(workers, degree)
    b = rounds_for_tolerance(h, tol)
    return np.linalg.matrix_power(h, b).astype(np.float32), b


def random_matrices(key, *, layers: int, n: int, p: int, q: int):
    """The shared R_1..R_L of Algorithm 1."""
    keys = jax.random.split(key, layers)
    out = []
    for layer, k in enumerate(keys):
        fan_in = p if layer == 0 else n
        out.append(jax.random.normal(k, (n - 2 * q, fan_in), jnp.float32)
                   / jnp.sqrt(jnp.asarray(fan_in, jnp.float32)))
    return out


def _rounder(dtype):
    """Round float32 values to ``dtype``'s precision and keep them float32.
    ``reduce_precision`` and not a pair of casts: the compiler may drop a
    cast pair as excess precision, and then nothing is rounded."""
    if dtype is None:
        return lambda a: a
    info = jnp.finfo(dtype)
    return lambda a: jax.lax.reduce_precision(a, exponent_bits=info.nexp,
                                              mantissa_bits=info.nmant)


@partial(jax.jit, static_argnames=("num_iters", "operands", "low"))
def solve_layer(y, t, mix, mu, eps, *, num_iters: int, operands=None, low=None):
    """Consensus-ADMM readout of one layer: worker 0's Z after K
    iterations.  y: (M, n, J_m), t: (M, Q, J_m), mix: (M, M)."""
    r, op = _rounder(low), _rounder(operands)
    m, n, _ = y.shape
    q = t.shape[1]
    with jax.default_matmul_precision("highest"):
        g = r(jnp.einsum("mij,mkj->mik", op(y), op(y), precision=HIGHEST)
              + jnp.eye(n, dtype=jnp.float32) / mu)
        a = r(jnp.einsum("mqj,mnj->mqn", op(t), op(y), precision=HIGHEST))
        chol = r(jax.vmap(jnp.linalg.cholesky)(g))

        def solve(c, rhs):
            return jax.scipy.linalg.cho_solve((c, True), rhs.T).T

        def body(_, state):
            o, z, lam = state
            o = r(jax.vmap(solve)(chol, a + (z - lam) / mu))
            avg = r(jnp.einsum("mk,kqn->mqn", mix, o + lam, precision=HIGHEST))
            norm = jnp.sqrt(jnp.sum(avg * avg, axis=(1, 2), keepdims=True))
            z = r(avg * jnp.where(norm > eps, eps / jnp.maximum(norm, 1e-30), 1.0))
            lam = r(lam + o - z)
            return o, z, lam

        zeros = jnp.zeros((m, q, n), jnp.float32)
        _, z, _ = jax.lax.fori_loop(0, num_iters, body, (zeros, zeros, zeros))
    return z[0]


@partial(jax.jit, static_argnames=("operands", "low"))
def propagate(o, rmat, y, *, operands=None, low=None):
    """relu([O; -O; R] y) for worker-stacked y: (M, n_in, J_m)."""
    r, op = _rounder(low), _rounder(operands)
    w = r(jnp.concatenate([o, -o, rmat], axis=0))
    return r(jax.nn.relu(
        jnp.einsum("ij,mjk->mik", op(w), op(y), precision=HIGHEST)))


def train(xw, tw, key, cfg: dict, mix, *, num_iters: int | None = None,
          operands=None, low=None):
    """Readouts O_0..O_L of one decentralized train, and the R_l it used.

    xw: (M, P, J_m) inputs, tw: (M, Q, J_m) one-hot targets; ``cfg`` holds
    the configuration's sizes; ``mix`` the (M, M) consensus matrix."""
    layers, n = cfg["num_layers"], cfg["hidden"]
    p, q = cfg["input_dim"], cfg["num_classes"]
    k = cfg["admm_iters"] if num_iters is None else num_iters
    eps = cfg["eps_scale"] * 2.0 * q
    rmats = random_matrices(key, layers=layers, n=n, p=p, q=q)
    mix = jnp.asarray(mix, jnp.float32)
    y = _rounder(low)(xw)
    readouts = []
    for layer in range(layers + 1):
        mu = cfg["mu0"] if layer == 0 else cfg["mul"]
        o = solve_layer(y, tw, mix, mu, eps, num_iters=k, operands=operands,
                        low=low)
        readouts.append(o)
        if layer < layers:
            y = propagate(o, rmats[layer], y, operands=operands, low=low)
    return readouts, rmats


@partial(jax.jit, static_argnames=("operands", "low"))
def forward(readouts, rmats, x, *, operands=None, low=None):
    """Logits ``O_L y_L`` of column-stacked inputs x: (P, J)."""
    r, op = _rounder(low), _rounder(operands)
    y = r(x)
    for o, rmat in zip(readouts[:-1], rmats):
        w = r(jnp.concatenate([o, -o, rmat], axis=0))
        y = r(jax.nn.relu(jnp.dot(op(w), op(y), precision=HIGHEST)))
    return r(jnp.dot(op(readouts[-1]), op(y), precision=HIGHEST))


def readout_gaps(got, want) -> list[float]:
    """``||O_l - O_l^ref||_F / ||O_l^ref||_F`` for every layer l."""
    return [
        float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64))
              / np.linalg.norm(np.asarray(b, np.float64)))
        for a, b in zip(got, want)
    ]
