"""Pallas TPU kernel: regularized Gram matrix  G = Y Y^T + (1/mu) I.

This is the dominant FLOPs of every dSSFN ADMM layer solve
(O(n^2 J_m) vs O(n^3) for the one-off Cholesky): computing the Gram
operand of eq. (11) at each layer.  The kernel tiles Y into
(block_n x block_j) VMEM blocks, accumulates partial products over the
J (sample) dimension in an f32 VMEM scratch accumulator, and fuses the
(1/mu) diagonal on the final reduction step — one HBM write per output
tile, no separate diag pass.

Grid: (n/bn, n/bn, J/bj), MXU-aligned 128-multiple tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    batch_leading,
    default_interpret,
    tpu_compiler_params,
)


def _gram_kernel(y1_ref, y2_ref, o_ref, acc_ref, *, inv_mu: float, nk: int, block_n: int):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        y1_ref[...],
        y2_ref[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _finalize():
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 0) + i * block_n
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1) + j * block_n
        diag = jnp.where(rows == cols, inv_mu, 0.0).astype(jnp.float32)
        o_ref[...] = (acc_ref[...] + diag).astype(o_ref.dtype)


def gram_pallas(
    y: jax.Array,
    *,
    mu: float,
    block_n: int = 128,
    block_j: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """G = Y Y^T + (1/mu) I for Y: (n, J); returns (n, n) in f32."""
    n, j = y.shape
    assert n % block_n == 0 and j % block_j == 0, (n, j, block_n, block_j)
    if interpret is None:
        interpret = default_interpret()
    nk = j // block_j
    kernel = functools.partial(
        _gram_kernel, inv_mu=1.0 / mu, nk=nk, block_n=block_n
    )
    return batch_leading(pl.pallas_call(
        kernel,
        grid=(n // block_n, n // block_n, nk),
        in_specs=[
            pl.BlockSpec((block_n, block_j), lambda i, jj, k: (i, k)),
            pl.BlockSpec((block_n, block_j), lambda i, jj, k: (jj, k)),
        ],
        out_specs=pl.BlockSpec((block_n, block_n), lambda i, jj, k: (i, jj)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, block_n), jnp.float32)],
        compiler_params=tpu_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    ))(y, y)
