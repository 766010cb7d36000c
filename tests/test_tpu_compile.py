"""Compile-only checks for a described TPU v5e: the dSSFN kernels and the
kernel-path layer program at the widths the chip runs.

Nothing here runs on a chip.  The TPU compiler compiles for a v5e:2x2
topology that is described, not attached, and refuses what the chip
would refuse: blocks not tiled (8, 128), more scoped VMEM than the
kernel asked for, programs that do not fit.  Interpret mode (what the
CPU tests run) checks none of that.  Each test asserts the compiled
program holds a Mosaic kernel call (``tpu_custom_call``).

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker given this file
loads the TPU library.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core.backend import SimulatedBackend
from repro.kernels.gram.kernel import gram_pallas
from repro.kernels.matmul_relu.kernel import matmul_relu_pallas
from repro.kernels.propagate_gram.kernel import propagate_gram_pallas

KERNEL_MARKER = "tpu_custom_call"


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip can be written to the persistent
    cache but not read back without one; keep the cache out of them."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert KERNEL_MARKER in compiled.as_text()
    return compiled


def test_gram_compiles_for_v5e(one_chip):
    _compile(
        lambda y: gram_pallas(y, mu=1.0, interpret=False),
        _sds((1024, 3072), one_chip),
    )


def test_propagate_gram_compiles_for_v5e(one_chip):
    _compile(
        lambda w, y: propagate_gram_pallas(w, y, mu=1.0, interpret=False),
        _sds((1024, 1024), one_chip),
        _sds((1024, 3072), one_chip),
    )


def test_matmul_relu_compiles_at_serving_width(one_chip):
    _compile(
        lambda w, x: matmul_relu_pallas(w, x, interpret=False),
        _sds((1024, 1024), one_chip),
        _sds((1024, 128), one_chip),
    )


def test_propagate_gram_batched_off_the_leading_axis(one_chip):
    """vmap over workers with the batch on axis 1 (what ``W @ Y`` leaves
    under vmap): the kernel must still lower, inside its VMEM limit."""
    def layer(w, y):
        return jax.vmap(
            lambda ym: propagate_gram_pallas(w, ym, mu=1.0, interpret=False),
            in_axes=1,
        )(y)

    _compile(layer, _sds((1024, 1024), one_chip),
             _sds((1024, 2, 3072), one_chip))


def test_kernel_layer_program_compiles_and_fits(one_chip, monkeypatch):
    """The fused layer program the chip runs on the kernel path: M=20
    workers vmapped on one chip, n=1024, J_m=3072, K=100."""
    for name in ("gram", "propagate_gram", "matmul_relu"):
        module = importlib.import_module(f"repro.kernels.{name}.kernel")
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    m, n, j = 20, 1024, 3072
    prog = engine.layer_program(
        SimulatedBackend(m),
        _sds((m, n, j), one_chip), _sds((m, 10, j), one_chip),
        _sds((n, n), one_chip),
        mu=1.0, eps_radius=20.0, num_iters=100, use_kernels=True,
        donate_y=True,
    )
    assert KERNEL_MARKER in prog.lowering_texts()["hlo"]
    assert prog.lowering_stats()["memory_bytes"] < 16e9
