"""Pallas TPU kernel: fused  Y = relu(W @ X)  — SSFN's LT+NLT layer step.

The SSFN forward pass applies a linear transform followed by ReLU at every
layer (paper Fig. 1); fusing the activation saves one HBM round-trip of the
(n x J) activation per layer.  Blocked (bm x bk) @ (bk x bn) with an f32
VMEM accumulator; ReLU applied on the final K step only.
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


def _matmul_relu_kernel(w_ref, x_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        w_ref[...], x_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _finalize():
        o_ref[...] = jnp.maximum(acc_ref[...], 0.0).astype(o_ref.dtype)


def matmul_relu_pallas(
    w: jax.Array,
    x: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """relu(W @ X): W (m, k), X (k, n) -> (m, n) in W's dtype."""
    m, kdim = w.shape
    k2, n = x.shape
    assert kdim == k2
    assert m % block_m == 0 and n % block_n == 0 and kdim % block_k == 0
    if interpret is None:
        interpret = default_interpret()
    nk = kdim // block_k
    return batch_leading(pl.pallas_call(
        functools.partial(_matmul_relu_kernel, nk=nk),
        grid=(m // block_m, n // block_n, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=tpu_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    ))(w, x)
