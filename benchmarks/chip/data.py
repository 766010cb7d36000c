"""Inputs made from ``--seed``, on the device, in one jitted call each.

The training and test sets are the planted-teacher classification
problem of ``repro.data.synthetic.make_classification`` (copied here so
that the workload cannot change with the program): standard normal
inputs, labels from a two-layer tanh teacher of width 64 with 5% logit
noise, inputs standardized by the training split's mean and deviation.
The same seed gives the same arrays on every run.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_MASK = 0xFFFFFFFF


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for ``stream`` of run ``seed``.  Seeds wider than 32
    bits, and negative ones, are folded in whole."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & _MASK)
    key = jax.random.fold_in(key, (seed >> 32) & _MASK)
    return jax.random.fold_in(key, stream)


@partial(jax.jit, static_argnames=(
    "num_train", "num_test", "input_dim", "num_classes", "workers"))
def make_dataset(key, *, num_train: int, num_test: int, input_dim: int,
                 num_classes: int, workers: int):
    """Training inputs and one-hot targets split over ``workers`` as
    ``(M, P, J/M)`` and ``(M, Q, J/M)`` (worker ``m`` holds the ``m``-th
    contiguous block of samples), and the test inputs ``(P, J_test)`` with
    their labels."""
    kx, kn, kw = jax.random.split(key, 3)
    j = num_train + num_test
    x = jax.random.normal(kx, (input_dim, j))
    wkeys = jax.random.split(kw, 3)
    h, dim = x, input_dim
    for i in range(2):
        w = jax.random.normal(wkeys[i], (64, dim)) / jnp.sqrt(dim)
        h = jnp.tanh(w @ h)
        dim = 64
    w_out = jax.random.normal(wkeys[2], (num_classes, dim)) / jnp.sqrt(dim)
    logits = w_out @ h + 0.05 * jax.random.normal(kn, (num_classes, j))
    labels = jnp.argmax(logits, axis=0)
    t = jax.nn.one_hot(labels, num_classes).T
    mu = x[:, :num_train].mean(axis=1, keepdims=True)
    sd = x[:, :num_train].std(axis=1, keepdims=True) + 1e-6
    x = (x - mu) / sd
    per = num_train // workers
    used = per * workers

    def split(a):
        return a[:, :used].reshape(a.shape[0], workers, per).transpose(1, 0, 2)

    return split(x), split(t), x[:, num_train:], labels[num_train:]


@partial(jax.jit, static_argnames=(
    "input_dim", "num_classes", "hidden", "layers", "eps_radius"))
def make_stack(key, *, input_dim: int, num_classes: int, hidden: int,
               layers: int, eps_radius: float):
    """A served stack: readouts O_0..O_L, each a Gaussian matrix scaled to
    the Frobenius radius eps that training projects onto, and the random
    matrices R_1..R_L drawn as training draws them."""
    keys = jax.random.split(key, 2 * layers + 1)
    rows = hidden - 2 * num_classes
    rmats, readouts = [], []
    for layer in range(layers):
        fan_in = input_dim if layer == 0 else hidden
        rmats.append(jax.random.normal(keys[layer], (rows, fan_in))
                     / jnp.sqrt(jnp.float32(fan_in)))
    for layer in range(layers + 1):
        fan_in = input_dim if layer == 0 else hidden
        g = jax.random.normal(keys[layers + layer], (num_classes, fan_in))
        readouts.append(g * (eps_radius / jnp.linalg.norm(g)))
    return readouts, rmats
