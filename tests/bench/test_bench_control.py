"""The comparison that decides ``correct``, at a size a test run holds.

Each test skips the harness's look for a chip and drives the rest of a
run through the cell's traffic generator on the CPU, then judges the outcome with
the run's own verdict.  A sound run is correct; the control (the plain
reference one precision lower, put in the program's place) and each
fault planted under the timed path are not.  The CPU's matmuls are
float32 throughout, so this tiny configuration states float32 operands.
"""
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness, reference
from benchmarks.chip.run import verdict

TINY = {"input_dim": 16, "num_classes": 4, "num_train": 512, "num_test": 256,
        "num_layers": 3, "hidden": 64, "admm_iters": 20, "mu0": 1e-3, "mul": 1.0,
        "eps_scale": 1.0, "workers": 4, "ring_degree": 1, "backend": "simulated",
        "matmul_operands": "float32"}
LIMITS = {"train": {"gap_l0": 1e-4, "gap_l1": 1e-3, "accuracy_gap": 0.02},
          "serve": {"logit_gap": 1e-4, "logit_gap_p10": 1e-4}}
SEED = 2 ** 31 + 11


def run(kind, patch=None):
    """One tiny run of the train or serve generator, judged."""
    name = {"train": "train_gossip", "serve": "serve_poisson"}[kind]
    traffic = harness.load_traffic(name)
    seconds = 0
    if kind == "serve":
        traffic = dict(traffic, rate_per_s=200, max_request=8, buckets=[1, 4, 8],
                       max_pending_samples=64)
        seconds = 0.5
    drv = harness.load_generator(kind)
    if patch is not None:
        patch(drv)
    cell = harness.Cell(f"tiny.{name}", 1, TINY, traffic, LIMITS[kind])
    out = drv.run(cell, seed=SEED, seconds=seconds, trace_dir=None, t_start=0.0)
    return verdict(out.compared, LIMITS[kind])


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_sound_run_is_correct(kind):
    correct, shown = run(kind)
    assert correct, shown


def test_control_train_is_not_correct(monkeypatch):
    mix, _ = reference.gossip_matrix(TINY["workers"], TINY["ring_degree"], 1e-6)

    def in_bf16(self, index):
        return reference.train(self.xw, self.tw, self.key(index), TINY, mix,
                               low=jnp.bfloat16)[0]

    correct, shown = run("train", lambda drv: monkeypatch.setattr(
        drv.Trainer, "train", in_bf16))
    assert not correct, shown


def test_control_serve_is_not_correct(monkeypatch):
    from repro.serve.engine import ServeEngine

    def in_bf16(self, x):
        p = self.artifact.params
        return reference.forward(list(p.o), list(p.r), jnp.asarray(x), low=jnp.bfloat16)

    monkeypatch.setattr(ServeEngine, "forward", in_bf16)
    correct, shown = run("serve")
    assert not correct, shown
    assert shown["logit_gap_p10"]["value"] > LIMITS["serve"]["logit_gap_p10"]


def _unchanged(monkeypatch):
    from repro.core import admm

    def no_steps(backend, a, chol, y_m, t_m, z_init, **kw):
        zeros = jnp.zeros_like(z_init)
        return (zeros, z_init, zeros), None

    monkeypatch.setattr(admm, "worker_admm_iterations", no_steps)


def _half_batch(monkeypatch):
    from repro.core import admm

    orig = admm._worker_stats_local

    def first_half_twice(y_m, t_m, mu, use_kernels):
        j = y_m.shape[1] // 2
        return orig(jnp.concatenate([y_m[:, :j]] * 2, axis=1),
                    jnp.concatenate([t_m[:, :j]] * 2, axis=1), mu, use_kernels)

    monkeypatch.setattr(admm, "_worker_stats_local", first_half_twice)


def _no_exchange(monkeypatch):
    from repro.core import policy

    monkeypatch.setattr(policy.Gossip, "mix", lambda self, x, state, ctx: (x, state))


def _altered(monkeypatch):
    from repro.core import engine

    orig = engine.fused_layer_step

    def swapped(*args, **kw):
        res = orig(*args, **kw)
        return res._replace(o_star=res.o_star[jnp.array([1, 0, 2, 3])])

    monkeypatch.setattr(engine, "fused_layer_step", swapped)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _no_exchange, _altered],
                         ids=["unchanged_state", "half_batch", "no_exchange", "altered_answer"])
def test_train_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    correct, shown = run("train")
    assert not correct, shown


def _serve_altered(monkeypatch):
    from repro.serve.engine import ServeEngine

    orig = ServeEngine.forward

    def swapped(self, x):
        out = orig(self, x)
        return out.at[jnp.array([0, 1]), 0].set(out[jnp.array([1, 0]), 0])

    monkeypatch.setattr(ServeEngine, "forward", swapped)


def _serve_half_batch(monkeypatch):
    from repro.serve.engine import ServeEngine

    orig = ServeEngine.forward

    def first_half(self, x):
        j = x.shape[1]
        if j < 2:
            return orig(self, x)
        out = orig(self, x[:, : j // 2])
        fill = jnp.broadcast_to(out.mean(axis=1, keepdims=True), (out.shape[0], j - j // 2))
        return jnp.concatenate([out, fill], axis=1)

    monkeypatch.setattr(ServeEngine, "forward", first_half)


@pytest.mark.parametrize("fault", [_serve_altered, _serve_half_batch],
                         ids=["altered_answer", "half_batch"])
def test_serve_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    correct, shown = run("serve")
    assert not correct, shown


def test_control_tool_reads_program_beside_control(monkeypatch):
    """``control.py``'s serving readings: the program's window and the
    control on the same sample fall either side of the limits, and each
    fault fails the widest gap."""
    from benchmarks.chip import control

    monkeypatch.setattr(control, "SERVE_SECONDS", 0.5)
    traffic = dict(harness.load_traffic("serve_poisson"), rate_per_s=200, max_request=8,
                   buckets=[1, 4, 8], max_pending_samples=64)
    cell = harness.Cell("tiny.serve_poisson", 1, TINY, traffic, LIMITS["serve"])
    got = control.serve_readings(cell, SEED)
    assert got["program"]["failed"] == 0
    assert verdict(got["program"], LIMITS["serve"])[0], got
    assert got["control"]["logit_gap_p10"] > LIMITS["serve"]["logit_gap_p10"], got
    for fault in ("altered", "half_batch"):
        assert got[fault]["logit_gap"] > LIMITS["serve"]["logit_gap"], (fault, got)
