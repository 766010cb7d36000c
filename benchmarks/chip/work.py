"""Operations and bytes a dSSFN train needs, counted from its shapes.

These are the work the algorithm requires, not what an implementation
happens to do, so that no implementation can read above 100% of a peak:

- propagation ``Y_l = relu(W_l Y_{l-1})``: ``2 n_out n_in J`` operations;
- the Gram ``Y Y^T`` is symmetric, so only its half is counted:
  ``n (n + 1) J`` operations;
- ``A = T Y^T``: ``2 Q n J``;
- one Cholesky per worker and layer: ``n^3 / 3``;
- each ADMM iteration solves two triangular systems with ``Q`` right-hand
  sides per worker: ``2 Q n^2``.

Bytes of the propagate + Gram stage are its inputs read once (``W``,
``Y_{l-1}``, ``T``) and its outputs written once (``Y_l``, the Gram,
``A``), at 4 bytes a float32 element.  Layer 0 has no propagation: its
features are the inputs themselves, of width ``P``.
"""
from __future__ import annotations

from typing import NamedTuple

F32 = 4


class Sizes(NamedTuple):
    """The shapes of one train: inputs P, classes Q, hidden n, layers L
    (readouts O_0..O_L), training samples J over M workers, K ADMM
    iterations per layer."""

    p: int
    q: int
    n: int
    layers: int
    samples: int
    workers: int
    admm_iters: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Sizes":
        return cls(
            p=cfg["input_dim"], q=cfg["num_classes"], n=cfg["hidden"],
            layers=cfg["num_layers"], samples=cfg["num_train"],
            workers=cfg["workers"], admm_iters=cfg["admm_iters"],
        )

    @property
    def per_worker(self) -> int:
        return self.samples // self.workers


class Work(NamedTuple):
    flops: float
    nbytes: float


def layer_width(s: Sizes, layer: int) -> int:
    """Width of the features layer ``layer`` solves on."""
    return s.p if layer == 0 else s.n


def gram_stage(s: Sizes, layer: int) -> Work:
    """Propagate + Gram (+ ``A``) of one layer program, all workers."""
    m, j, q = s.workers, s.per_worker, s.q
    width = layer_width(s, layer)
    flops = width * (width + 1) * j * m + 2 * q * width * j * m
    read = m * q * j
    written = m * (width * width + q * width)
    if layer == 0:
        read += m * s.p * j
    else:
        n_in = layer_width(s, layer - 1)
        flops += 2 * width * n_in * j * m
        read += width * n_in + m * n_in * j
        written += m * width * j
    return Work(float(flops), float(F32 * (read + written)))


def solve_flops(s: Sizes, layer: int) -> float:
    """Cholesky plus the K ADMM iterations' triangular solves of one
    layer program, all workers."""
    width = layer_width(s, layer)
    chol = width ** 3 / 3
    admm = s.admm_iters * 2 * s.q * width ** 2
    return float(s.workers * (chol + admm))


def train_flops(s: Sizes) -> float:
    """Operations of one whole train, layers 0..L."""
    return sum(
        gram_stage(s, layer).flops + solve_flops(s, layer)
        for layer in range(s.layers + 1)
    )
