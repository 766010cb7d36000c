"""Checkpoint/resume: bit-exact pytree round-trips and elastic
kill-and-continue training drills.

Satellite (c) of the elastic-consensus PR: ``save_pytree`` /
``load_pytree`` / ``load_pytree_flat`` must round-trip the FULL training
state (layer weights, ADMM duals, staleness buffers, RNG keys) bit for
bit, and a resumed ``train_decentralized_ssfn`` run must reproduce the
uninterrupted run's final iterate exactly.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dssfn
from repro.checkpoint.store import (
    CheckpointCorruptError,
    is_valid_checkpoint,
    load_pytree,
    load_pytree_flat,
    save_pytree,
)
from repro.core import layerwise, ssfn
from repro.core.layerwise import checkpoint_path, latest_checkpoint
from repro.core.policy import AsyncGossip, FaultModel
from repro.core.topology import Hypercube, Masked, Membership, Ring


def _data(key, m=4, p=8, q=3, jm=16):
    kx, kt = jax.random.split(key)
    xw = jax.random.normal(kx, (m, p, jm))
    labels = jax.random.randint(kt, (m, jm), 0, q)
    tw = jax.nn.one_hot(labels, q).transpose(0, 2, 1)
    return xw, tw


def _cfg(**kw):
    defaults = dict(
        input_dim=8, num_classes=3, num_layers=3, hidden=20, admm_iters=20
    )
    defaults.update(kw)
    return ssfn.SSFNConfig(**defaults)


# ------------------------------------------------------------------
# Pytree store round-trips
# ------------------------------------------------------------------

def test_save_load_pytree_flat_bit_exact(tmp_path):
    """The flat loader restores every leaf bit-exactly — including the
    dtypes npz cannot represent natively (bf16) and raw RNG key data."""
    key = jax.random.PRNGKey(42)
    tree = {
        "o": {"0": np.arange(12, dtype=np.float32).reshape(3, 4)},
        "lam": jax.random.normal(key, (2, 3, 4), dtype=jnp.float32),
        "buf": jnp.linspace(-1, 1, 8, dtype=jnp.bfloat16),
        "key": jax.random.key_data(key),
        "comm": np.int64(123456789),
        "step": np.int32(-7),
        "cost": np.float64(1.0 / 3.0),
    }
    path = os.path.join(tmp_path, "state.npz")
    save_pytree(path, tree)
    flat = load_pytree_flat(path)

    assert np.array_equal(flat["o/0"], np.asarray(tree["o"]["0"]))
    assert np.array_equal(flat["lam"], np.asarray(tree["lam"]))
    assert flat["buf"].dtype.name == "bfloat16"
    assert np.array_equal(
        flat["buf"].view(np.uint16), np.asarray(tree["buf"]).view(np.uint16)
    )
    assert np.array_equal(flat["key"], np.asarray(jax.random.key_data(key)))
    assert flat["comm"] == tree["comm"] and flat["comm"].dtype == np.int64
    assert flat["step"] == tree["step"]
    assert flat["cost"] == tree["cost"] and flat["cost"].dtype == np.float64


def test_save_load_pytree_template_bit_exact(tmp_path):
    """Template-based load (the non-elastic path) stays bit-exact over a
    training-state-shaped tree: duals, StaleMixing buffers, nested
    tuples."""
    k = jax.random.PRNGKey(0)
    state = {
        "duals": tuple(
            jax.random.normal(jax.random.fold_in(k, i), (3, 5))
            for i in range(2)
        ),
        # A StaleMixing-shaped state: delay-line buffer + int cursor.
        "stale": (jnp.zeros((2, 3, 5)), jnp.int32(1)),
        "key": jax.random.key_data(k),
    }
    path = os.path.join(tmp_path, "tpl.npz")
    save_pytree(path, state)
    back = load_pytree(path, state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).dtype == np.asarray(b).dtype


def test_latest_checkpoint_selects_highest_layer(tmp_path):
    d = str(tmp_path)
    assert latest_checkpoint(d) is None
    for ln in (1, 3, 2):
        save_pytree(checkpoint_path(d, ln), {"layer_next": np.int64(ln)})
    picked = latest_checkpoint(d)
    assert picked == checkpoint_path(d, 3)
    assert int(load_pytree_flat(picked)["layer_next"]) == 3


# ------------------------------------------------------------------
# Kill/resume drills: resumed == uninterrupted, bit for bit
# ------------------------------------------------------------------

def _assert_same_run(res_a, res_b):
    assert len(res_a.params.o) == len(res_b.params.o)
    for a, b in zip(res_a.params.o, res_b.params.o):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(res_a.params.r, res_b.params.r):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert res_a.log.comm_scalars == res_b.log.comm_scalars
    assert np.array_equal(res_a.log.admm_objective, res_b.log.admm_objective)
    assert np.array_equal(res_a.log.consensus_error, res_b.log.consensus_error)
    np.testing.assert_allclose(res_a.log.layer_costs, res_b.log.layer_costs)


@pytest.mark.parametrize(
    "policy",
    [
        None,  # ExactMean default
        AsyncGossip(
            rounds=2,
            topology=Hypercube(),
            interval=2,
            faults=FaultModel(drop=0.2, seed=5),
        ),
    ],
    ids=["exact", "async-faulty"],
)
def test_resume_matches_uninterrupted_run(tmp_path, policy):
    """Train to completion in one process; separately train to layer 1,
    'crash', and resume in a fresh spec.  Same final iterate, bit for
    bit — including under an active fault model (fault draws are seeded
    by the absolute iteration, so the schedule replays identically)."""
    xw, tw = _data(jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(7)
    base = dict(cfg=_cfg(), backend="simulated", workers=4, policy=policy)

    full = dssfn.train(dssfn.TrainSpec(**base), xw, tw, key)

    ckpt = os.path.join(tmp_path, "ckpt")
    first = dssfn.train(
        dssfn.TrainSpec(**base, checkpoint_dir=ckpt, stop_after_layer=1),
        xw, tw, key,
    )
    assert len(first.params.o) == 2  # O_0, O_1: the partial model
    assert latest_checkpoint(ckpt) == checkpoint_path(ckpt, 2)

    resumed = dssfn.train(
        dssfn.TrainSpec(**base, checkpoint_dir=ckpt, resume=True),
        xw, tw, key,
    )
    _assert_same_run(full, resumed)


def test_resume_matches_with_membership_mask(tmp_path):
    """Elastic membership rides the checkpoint: a masked-topology run
    resumes bit-exactly and the stored mask matches the active set."""
    xw, tw = _data(jax.random.PRNGKey(4), m=8)
    key = jax.random.PRNGKey(9)
    base = dict(
        cfg=_cfg(num_layers=2),
        backend="simulated",
        workers=8,
        policy=AsyncGossip(rounds=2, topology=Ring(2)),
        membership="11011111",
    )
    full = dssfn.train(dssfn.TrainSpec(**base), xw, tw, key)

    ckpt = os.path.join(tmp_path, "ckpt")
    dssfn.train(
        dssfn.TrainSpec(**base, checkpoint_dir=ckpt, stop_after_layer=0),
        xw, tw, key,
    )
    flat = load_pytree_flat(latest_checkpoint(ckpt))
    assert np.array_equal(
        flat["membership"], np.array([1, 1, 0, 1, 1, 1, 1, 1], np.float64)
    )
    resumed = dssfn.train(
        dssfn.TrainSpec(**base, checkpoint_dir=ckpt, resume=True),
        xw, tw, key,
    )
    _assert_same_run(full, resumed)
    # The masked policy actually reached the run.
    assert isinstance(resumed.policy.topology, Masked)
    assert resumed.policy.topology.membership == Membership(
        (True, True, False, True, True, True, True, True)
    )


def test_checkpoint_every_stride(tmp_path):
    xw, tw = _data(jax.random.PRNGKey(5))
    ckpt = os.path.join(tmp_path, "ckpt")
    dssfn.train(
        dssfn.TrainSpec(
            cfg=_cfg(num_layers=4),
            backend="simulated",
            workers=4,
            checkpoint_dir=ckpt,
            checkpoint_every=2,
        ),
        xw, tw, jax.random.PRNGKey(6),
    )
    # Layers 0..4 completed -> layer_next in {2, 4} only (every 2nd).
    names = sorted(os.listdir(ckpt))
    nexts = sorted(
        int(n.removeprefix("dssfn_layer_").removesuffix(".npz"))
        for n in names
        if n.endswith(".npz")
    )
    assert nexts == [2, 4]


def test_resume_with_empty_directory_trains_from_scratch(tmp_path):
    xw, tw = _data(jax.random.PRNGKey(8))
    key = jax.random.PRNGKey(2)
    plain = dssfn.train(
        dssfn.TrainSpec(cfg=_cfg(num_layers=1), backend="simulated", workers=4),
        xw, tw, key,
    )
    ckpt = os.path.join(tmp_path, "fresh")
    os.makedirs(ckpt)
    resumed = dssfn.train(
        dssfn.TrainSpec(
            cfg=_cfg(num_layers=1), backend="simulated", workers=4,
            checkpoint_dir=ckpt, resume=True,
        ),
        xw, tw, key,
    )
    _assert_same_run(plain, resumed)


# ------------------------------------------------------------------
# Corrupt-checkpoint handling: CheckpointCorruptError + resume skips
# ------------------------------------------------------------------

def _truncate(path, keep=40):
    with open(path, "rb") as f:
        head = f.read(keep)
    with open(path, "wb") as f:
        f.write(head)


def test_load_pytree_flat_corruption_modes(tmp_path):
    """Every way a checkpoint can be bad surfaces as a
    CheckpointCorruptError naming the file and the defect — never a raw
    KeyError / BadZipFile escaping into resume logic."""
    path = os.path.join(tmp_path, "st.npz")

    with pytest.raises(CheckpointCorruptError, match="does not exist"):
        load_pytree_flat(path)

    save_pytree(path, {"a": np.arange(4.0), "b": np.int64(3)})
    assert is_valid_checkpoint(path)

    # Missing metadata sidecar.
    os.rename(path + ".meta.json", path + ".meta.json.bak")
    with pytest.raises(CheckpointCorruptError, match="sidecar"):
        load_pytree_flat(path)
    assert not is_valid_checkpoint(path)
    os.rename(path + ".meta.json.bak", path + ".meta.json")

    # Garbage sidecar JSON.
    with open(path + ".meta.json", "r+") as f:
        f.write("{oops")
    with pytest.raises(CheckpointCorruptError, match="metadata sidecar"):
        load_pytree_flat(path)

    # Restore the sidecar, then check the key/shape screens.
    save_pytree(path, {"a": np.arange(4.0), "b": np.int64(3)})
    with pytest.raises(CheckpointCorruptError, match=r"missing required key\(s\).*\['c'\]"):
        load_pytree_flat(path, expect_keys=["a", "b", "c"])

    with open(path + ".meta.json") as f:
        meta = json.load(f)
    meta["a"]["shape"] = [5]
    with open(path + ".meta.json", "w") as f:
        json.dump(meta, f)
    with pytest.raises(CheckpointCorruptError, match="shape"):
        load_pytree_flat(path)

    # Truncated npz (the kill-mid-save signature on pre-atomic writers).
    save_pytree(path, {"a": np.arange(4.0), "b": np.int64(3)})
    _truncate(path)
    with pytest.raises(CheckpointCorruptError, match="npz archive"):
        load_pytree_flat(path)
    assert not is_valid_checkpoint(path)


def test_latest_checkpoint_skips_partial_with_warning(tmp_path):
    """A truncated deepest checkpoint is skipped (with a RuntimeWarning)
    and the scan falls back to the next-deepest complete one."""
    d = str(tmp_path)
    for ln in (1, 2, 3):
        save_pytree(checkpoint_path(d, ln), {"layer_next": np.int64(ln)})
    _truncate(checkpoint_path(d, 3))
    with pytest.warns(RuntimeWarning, match="partial/corrupt"):
        picked = latest_checkpoint(d)
    assert picked == checkpoint_path(d, 2)

    # An npz that lost its sidecar (kill between the two publishes of a
    # pre-sidecar-first writer) is equally skipped.
    os.remove(checkpoint_path(d, 2) + ".meta.json")
    with pytest.warns(RuntimeWarning, match="partial/corrupt"):
        picked = latest_checkpoint(d)
    assert picked == checkpoint_path(d, 1)


def test_atomic_save_never_exposes_partial_state(tmp_path, monkeypatch):
    """save_pytree publishes via tmp + os.replace: a save that dies
    mid-write leaves the previous checkpoint bit-intact and no stage
    debris behind."""
    path = os.path.join(tmp_path, "st.npz")
    save_pytree(path, {"a": np.arange(3.0)})

    class Boom(RuntimeError):
        pass

    def exploding_savez(f, **arrays):
        f.write(b"partial bytes that must never be published")
        raise Boom("disk full")

    monkeypatch.setattr(np, "savez", exploding_savez)
    with pytest.raises(Boom):
        save_pytree(path, {"a": np.arange(3.0) + 1})
    monkeypatch.undo()

    # Old checkpoint still loads; the failed stage file was unlinked.
    assert is_valid_checkpoint(path)
    assert np.array_equal(load_pytree_flat(path)["a"], np.arange(3.0))
    assert [n for n in os.listdir(tmp_path) if ".tmp." in n] == []


def test_resume_recovers_from_kill_mid_save(tmp_path):
    """Full drill: train to layer 1 with checkpoints, then fake a kill
    mid-way through saving the NEXT checkpoint (truncated npz at its
    final name + an orphaned stage file).  --resume must warn, fall back
    to the deepest complete checkpoint, and still reproduce the
    uninterrupted run bit for bit."""
    xw, tw = _data(jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(7)
    base = dict(cfg=_cfg(), backend="simulated", workers=4)
    full = dssfn.train(dssfn.TrainSpec(**base), xw, tw, key)

    ckpt = os.path.join(tmp_path, "ckpt")
    dssfn.train(
        dssfn.TrainSpec(**base, checkpoint_dir=ckpt, stop_after_layer=1),
        xw, tw, key,
    )
    good = checkpoint_path(ckpt, 2)
    assert latest_checkpoint(ckpt) == good

    # Forge the kill-mid-save crime scene around layer 3's checkpoint.
    with open(good, "rb") as f:
        blob = f.read()
    deeper = checkpoint_path(ckpt, 3)
    with open(deeper, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with open(deeper + ".tmp.abc123", "wb") as f:
        f.write(b"orphaned stage file")

    with pytest.warns(RuntimeWarning, match="partial/corrupt"):
        resumed = dssfn.train(
            dssfn.TrainSpec(**base, checkpoint_dir=ckpt, resume=True),
            xw, tw, key,
        )
    _assert_same_run(full, resumed)


def test_checkpoint_roundtrips_random_matrices(tmp_path):
    """The checkpoint stores the random matrices ACTUALLY used (r/<i>) —
    divergence rollback perturbs the key mid-run, so the key alone no
    longer determines them — and the resumed run reuses them verbatim."""
    xw, tw = _data(jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(7)
    ckpt = os.path.join(tmp_path, "ckpt")
    res = dssfn.train(
        dssfn.TrainSpec(
            cfg=_cfg(), backend="simulated", workers=4,
            checkpoint_dir=ckpt, stop_after_layer=1,
        ),
        xw, tw, key,
    )
    flat = load_pytree_flat(latest_checkpoint(ckpt))
    stored = 0
    while f"r/{stored}" in flat:
        stored += 1
    # The checkpoint carries the FULL draw (future layers included, so
    # a rollback can tell consumed from free); the partial model exposes
    # the consumed prefix, which must match verbatim.
    assert stored == _cfg().num_layers
    assert len(res.params.r) <= stored
    for i, r in enumerate(res.params.r):
        assert np.array_equal(flat[f"r/{i}"], np.asarray(r))


@pytest.mark.parametrize("drill", ["resume", "rollback"])
def test_checkpoint_without_stored_draws_restores(
    tmp_path, monkeypatch, drill
):
    """A checkpoint written before the random matrices and the jitter
    history were stored (no ``r/*``, no ``jit``) goes through the same
    restore.  Resume re-derives the draw from the checkpoint's key and
    matches the uninterrupted run bit for bit; a rollback onto it keeps
    the live draw, completes, and keeps the checkpointed layers'
    readouts verbatim."""
    xw, tw = _data(jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(7)
    base = dict(cfg=_cfg(), backend="simulated", workers=4)
    full = dssfn.train(dssfn.TrainSpec(**base), xw, tw, key)

    ckpt = os.path.join(tmp_path, "ckpt")
    dssfn.train(
        dssfn.TrainSpec(**base, checkpoint_dir=ckpt, stop_after_layer=1),
        xw, tw, key,
    )
    path = latest_checkpoint(ckpt)
    flat = load_pytree_flat(path)
    assert "r/0" in flat and "jit" in flat
    save_pytree(path, {
        k: v for k, v in flat.items() if not (k.startswith("r/") or k == "jit")
    })
    stripped = load_pytree_flat(path)
    assert "r/0" not in stripped and "jit" not in stripped

    resume = dict(**base, checkpoint_dir=ckpt, resume=True)
    if drill == "resume":
        resumed = dssfn.train(dssfn.TrainSpec(**resume), xw, tw, key)
        _assert_same_run(full, resumed)
        # The jitter history restarts at the resumed layer.
        assert resumed.log.jitter_levels.shape[0] == len(full.params.o) - 2
        return

    real = layerwise._step_diverged
    calls = {"n": 0}

    def fake(step, prev_cost, blowup=1e3):
        calls["n"] += 1
        if calls["n"] == 1:       # the first resumed layer, before it saves
            return True
        return real(step, prev_cost, blowup)

    monkeypatch.setattr(layerwise, "_step_diverged", fake)
    with pytest.warns(RuntimeWarning, match="rolling back to layer 2"):
        healed = dssfn.train(
            dssfn.TrainSpec(**resume, guard_divergence=True), xw, tw, key,
        )
    assert healed.log.rollbacks == 1
    assert len(healed.params.o) == len(full.params.o)
    for a, b in zip(full.params.o[:2], healed.params.o[:2]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # r[0] shaped the checkpointed features; r[1] was free and re-drawn.
    assert np.array_equal(
        np.asarray(full.params.r[0]), np.asarray(healed.params.r[0])
    )
    assert not np.array_equal(
        np.asarray(full.params.r[1]), np.asarray(healed.params.r[1])
    )
    for o in healed.params.o:
        assert bool(np.all(np.isfinite(np.asarray(o))))


# ------------------------------------------------------------------
# Divergence guard: rollback, key perturbation, budget exhaustion
# ------------------------------------------------------------------

class _FakeStep:
    def __init__(self, o_star, objective=None):
        self.o_star = jnp.asarray(o_star)
        self.trace = None
        if objective is not None:
            class _Tr:
                pass
            self.trace = _Tr()
            self.trace.objective = np.asarray(objective)


def test_step_diverged_predicate():
    ok = _FakeStep(np.ones((3, 4)), objective=[2.0, 1.0])
    assert not layerwise._step_diverged(ok, prev_cost=1.5)
    # Non-finite iterate.
    assert layerwise._step_diverged(
        _FakeStep(np.array([1.0, np.nan])), prev_cost=None
    )
    # Non-finite objective.
    assert layerwise._step_diverged(
        _FakeStep(np.ones(3), objective=[np.inf]), prev_cost=None
    )
    # Blow-up past 1000x the previous layer's cost.
    assert layerwise._step_diverged(
        _FakeStep(np.ones(3), objective=[5e3]), prev_cost=1.0
    )
    assert not layerwise._step_diverged(
        _FakeStep(np.ones(3), objective=[5e3]), prev_cost=None
    )


def test_divergence_guard_rolls_back_with_perturbed_key(
    tmp_path, monkeypatch
):
    """Force the monitor to flag the first solve as diverged: the run
    must warn, roll back, perturb the key (different random matrices
    than the clean run), and still converge — reporting rollbacks=1."""
    xw, tw = _data(jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(7)
    base = dict(cfg=_cfg(), backend="simulated", workers=4)
    clean = dssfn.train(dssfn.TrainSpec(**base), xw, tw, key)

    real = layerwise._step_diverged
    calls = {"n": 0}

    def fake(step, prev_cost, blowup=1e3):
        calls["n"] += 1
        if calls["n"] == 1:
            return True
        return real(step, prev_cost, blowup)

    monkeypatch.setattr(layerwise, "_step_diverged", fake)
    with pytest.warns(RuntimeWarning, match="rolling back"):
        healed = dssfn.train(
            dssfn.TrainSpec(**base, guard_divergence=True), xw, tw, key,
        )
    assert healed.log.rollbacks == 1
    assert len(healed.params.o) == len(clean.params.o)
    for o in healed.params.o:
        assert bool(np.all(np.isfinite(np.asarray(o))))
    # The retry re-drew the not-yet-consumed random matrices.
    assert not np.array_equal(
        np.asarray(healed.params.r[0]), np.asarray(clean.params.r[0])
    )


def test_divergence_guard_restores_checkpointed_layers_verbatim(
    tmp_path, monkeypatch
):
    """When a checkpoint exists, rollback restores the completed layers'
    weights bit-for-bit and only re-draws from the restart layer on."""
    xw, tw = _data(jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(7)
    ckpt = os.path.join(tmp_path, "ckpt")
    base = dict(
        cfg=_cfg(), backend="simulated", workers=4,
        checkpoint_dir=ckpt, checkpoint_every=1,
    )
    clean = dssfn.train(dssfn.TrainSpec(**base), xw, tw, key)

    import shutil
    shutil.rmtree(ckpt)

    real = layerwise._step_diverged
    calls = {"n": 0}

    def fake(step, prev_cost, blowup=1e3):
        calls["n"] += 1
        # Layers 0 and 1 succeed (and checkpoint); layer 2's first
        # attempt "diverges".
        if calls["n"] == 3:
            return True
        return real(step, prev_cost, blowup)

    monkeypatch.setattr(layerwise, "_step_diverged", fake)
    with pytest.warns(RuntimeWarning, match="rolling back to layer 2"):
        healed = dssfn.train(
            dssfn.TrainSpec(**base, guard_divergence=True), xw, tw, key,
        )
    assert healed.log.rollbacks == 1
    # Consumed layers (restored from the checkpoint) are bit-identical;
    # the restart layer drew a fresh random matrix.
    for a, b in zip(clean.params.o[:2], healed.params.o[:2]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(
        np.asarray(clean.params.r[0]), np.asarray(healed.params.r[0])
    )
    assert not np.array_equal(
        np.asarray(clean.params.r[1]), np.asarray(healed.params.r[1])
    )


def test_divergence_guard_budget_exhaustion_raises(monkeypatch):
    xw, tw = _data(jax.random.PRNGKey(3))
    monkeypatch.setattr(
        layerwise, "_step_diverged", lambda step, prev_cost, blowup=1e3: True
    )
    with pytest.raises(RuntimeError, match="rollback budget"):
        dssfn.train(
            dssfn.TrainSpec(
                cfg=_cfg(), backend="simulated", workers=4,
                guard_divergence=True, max_rollbacks=0,
            ),
            xw, tw, jax.random.PRNGKey(7),
        )


def test_checkpoint_validation_errors():
    xw, tw = _data(jax.random.PRNGKey(1))
    cfg = _cfg(num_layers=1)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        layerwise.train_decentralized_ssfn(xw, tw, cfg, key, resume=True)
    with pytest.raises(ValueError, match="checkpoint_every"):
        layerwise.train_decentralized_ssfn(
            xw, tw, cfg, key, checkpoint_dir="/tmp/x", checkpoint_every=0
        )
