"""Pallas TPU kernel: fused layer step  Y' = relu(W @ Y),  G = Y' Y'^T + (1/mu) I.

The dSSFN layer engine's hot path does feature propagation immediately
followed by the Gram product of the *propagated* features (paper eq. 11:
the Gram operand of every layer-l solve is Y_l Y_l^T).  Run separately,
that is two HBM round-trips of the (n x J) activation: write Y' after the
matmul_relu, read it back for the Gram.  This kernel emits both outputs
in ONE pass over the samples: for each J-tile it computes the activation
block in VMEM, streams it out, and accumulates its self-outer-product
into an f32 VMEM accumulator — Y is read from HBM exactly once per layer
and Y' is written exactly once, never re-read.

Grid: (J/bj,) sequential over sample tiles.  W ((n, n_prev)) and the
(n, n) accumulator stay VMEM-resident across the whole pass.  Counting
the pipeline's double buffers of W, G and the Y/Y' tiles, the kernel
needs ``_vmem_bytes`` — 24 MiB with headroom at n = n_prev = 1024, over the 16 MiB
default scoped limit a v5e compile enforces once the kernel is batched
over workers — so the call raises its scoped limit to that need.
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


def _propagate_gram_kernel(
    w_ref, y_ref, ynew_ref, g_ref, acc_ref, *, inv_mu: float, nk: int, n: int
):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    y_new = jnp.maximum(
        jnp.dot(w_ref[...], y_ref[...], preferred_element_type=jnp.float32), 0.0
    )                                                    # (n, bj) f32
    ynew_ref[...] = y_new.astype(ynew_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        y_new, y_new, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _finalize():
        rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        diag = jnp.where(rows == cols, inv_mu, 0.0).astype(jnp.float32)
        g_ref[...] = (acc_ref[...] + diag).astype(g_ref.dtype)


def _vmem_bytes(n: int, n_prev: int, block_j: int) -> int:
    """Scoped VMEM the pipeline needs: double-buffered W, G and Y/Y'
    tiles plus the single (n, n) f32 accumulator, and 2 MiB headroom."""
    return 4 * (2 * n * n_prev + 3 * n * n + 2 * (n + n_prev) * block_j) + (2 << 20)


def propagate_gram_pallas(
    w: jax.Array,
    y: jax.Array,
    *,
    mu: float,
    block_j: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(relu(W @ Y), relu(W @ Y) relu(W @ Y)^T + (1/mu) I).

    W: (n, n_prev), Y: (n_prev, J); returns Y' (n, J) in W's dtype and
    G (n, n) in f32.  All of n, n_prev, J must be 128-aligned.
    """
    n, n_prev = w.shape
    n_prev2, j = y.shape
    assert n_prev == n_prev2, (w.shape, y.shape)
    assert n % 128 == 0 and n_prev % 128 == 0 and j % block_j == 0, (
        n, n_prev, j, block_j,
    )
    if interpret is None:
        interpret = default_interpret()
    nk = j // block_j
    kernel = functools.partial(
        _propagate_gram_kernel, inv_mu=1.0 / mu, nk=nk, n=n
    )
    return batch_leading(pl.pallas_call(
        kernel,
        grid=(nk,),
        in_specs=[
            pl.BlockSpec((n, n_prev), lambda k: (0, 0)),     # W resident
            pl.BlockSpec((n_prev, block_j), lambda k: (0, k)),
        ],
        out_specs=[
            pl.BlockSpec((n, block_j), lambda k: (0, k)),    # Y' streamed
            pl.BlockSpec((n, n), lambda k: (0, 0)),          # G on last step
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, j), w.dtype),
            jax.ShapeDtypeStruct((n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=tpu_compiler_params(
            ("arbitrary",), vmem_limit_bytes=_vmem_bytes(n, n_prev, block_j)
        ),
        interpret=interpret,
    ))(w, y)
