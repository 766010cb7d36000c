"""Shared kernel utilities."""
from __future__ import annotations

import jax
from jax.custom_batching import custom_vmap


def aligned(*dims: int) -> bool:
    """True when every dimension is a whole number of 128-wide tiles:
    the one shape rule that decides whether a Pallas kernel runs."""
    return all(d % 128 == 0 for d in dims)


def default_interpret() -> bool:
    """Pallas TPU kernels run in interpret mode everywhere but real TPU."""
    return jax.default_backend() != "tpu"


def tpu_compiler_params(
    dimension_semantics: tuple[str, ...], *, vmem_limit_bytes: int | None = None
):
    """Pallas TPU ``CompilerParams`` for a kernel's grid; a kernel whose
    pipeline outgrows the default scoped VMEM names its need."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=vmem_limit_bytes,
    )


def batch_leading(call):
    """Wrap a ``pallas_call`` so ``vmap`` batches it on a new leading axis.

    Left alone, vmap batches a pallas_call on whatever axis the producer
    left the batch in — ``W @ Y`` under vmap yields ``(n, M, J)`` — and
    the TPU lowering refuses a batch axis among an operand's last two
    (blocks must tile those by (8, 128)).  The rule moves every batched
    operand's batch axis to the front first.
    """
    wrapped = custom_vmap(call)

    @wrapped.def_vmap
    def _rule(axis_size, in_batched, *args):
        in_axes = tuple(0 if b else None for b in in_batched)
        out = jax.vmap(call, in_axes=in_axes)(*args)
        return out, jax.tree.map(lambda _: True, out)

    return wrapped


def cdiv(a: int, b: int) -> int:
    return -(-a // b)
