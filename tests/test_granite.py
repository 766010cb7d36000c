"""Granite-4.0-H as a frozen token backbone of the serving engine, at a
tiny size on the CPU: the layer pattern, the Mamba2 scan against the
sequential recurrence, padding invariance of token buckets, and the
runtime's token admission and counters.  The comparison with the plain
reference lives in ``tests/bench/test_bench_granite.py``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import get_config
from repro.core import ssfn
from repro.kernels.ssm_scan.kernel import ssm_scan_pallas
from repro.models import granite
from repro.nn.ssm import chunked_ssm_scan
from repro.serve import ServeEngine
from repro.serve.export import ServeArtifact
from repro.serve.batcher import MicroBatcher, pack_fifo
from repro.serve.features import PAD_ID, parse_features, text_lengths
from repro.serve.runtime import ManualClock, ServeRuntime

TINY = dataclasses.replace(
    get_config("granite-4.0-h-micro"), num_layers=4, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512, ssm_state=16,
    ssm_heads=8, d_inner=128, ssm_chunk=16, attn_chunk=16, dtype="float32",
    layer_types=("mamba", "mamba", "attention", "mamba"),
)
Q = 5


@pytest.fixture
def tiny_backbone(monkeypatch):
    """``granite-h-micro`` specs name the tiny backbone."""
    real = configs.get_config
    monkeypatch.setattr(configs, "get_config",
                        lambda name: TINY if name == "granite-4.0-h-micro" else real(name))


def tiny_engine(buckets, seed=3):
    rng = np.random.default_rng(seed)
    n = 2 * Q + 20
    o = [jnp.asarray(rng.normal(size=(Q, 64 if i == 0 else n)), jnp.float32) for i in range(3)]
    r = [jnp.asarray(rng.normal(size=(n - 2 * Q, 64 if i == 0 else n)) / 8, jnp.float32)
         for i in range(2)]
    artifact = ServeArtifact(
        params=ssfn.SSFNParams(o=tuple(o), r=tuple(r)), num_classes=Q, input_dim=64,
        activation="relu", features=f"granite-h-micro:{seed}", version=1, manifest={})
    return ServeEngine(artifact, buckets=buckets)


def texts(rng, lengths):
    ids = np.full((max(lengths), len(lengths)), PAD_ID, np.int32)
    for j, n in enumerate(lengths):
        ids[:n, j] = rng.integers(1, TINY.vocab_size, n)
    return ids


def test_published_pattern_and_spec():
    period, runs = granite.period_runs(get_config("granite-4.0-h-micro").layer_types)
    assert period == 10 and runs == [("mamba", 5), ("attention", 1), ("mamba", 4)]
    ex = parse_features("granite-h-micro:7")
    assert ex.takes_tokens and ex.seed == 7 and ex.dim == 2048
    assert ex.describe() == "granite-h-micro:7" and parse_features("granite-h-micro").seed == 0
    with pytest.raises(ValueError, match="trailing"):
        parse_features("granite-h-micro:1:2")


def sequential_ssd(x, dt, a, bm, cm):
    """h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t (per head)."""
    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt * a)[:, None, None] * h + (dtt[:, None] * xt)[:, :, None] * bt[None, None]
        return h, jnp.einsum("hdn,n->hd", h, ct)

    def one(xb, dtb, bb, cb):
        h0 = jnp.zeros((x.shape[2], x.shape[3], bm.shape[-1]))
        return jax.lax.scan(step, h0, (xb, dtb, bb, cb))[1]

    return jax.vmap(one)(x, dt, bm, cm)


@pytest.mark.parametrize("scan", ["chunked", "pallas_interpret"])
def test_ssd_scan_matches_sequential_recurrence(scan):
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    b, s, h, dh, ds, chunk = 2, 64, 4, 8, 16, 16
    x = jax.random.normal(key[0], (b, s, h, dh))
    dt = jax.nn.softplus(jax.random.normal(key[1], (b, s, h)) - 1.0)
    a = -jnp.exp(jax.random.normal(key[2], (h,)))
    bm = jax.random.normal(key[3], (b, s, ds))
    cm = jax.random.normal(key[4], (b, s, ds))
    if scan == "chunked":
        y, _ = chunked_ssm_scan(x, dt, a, bm, cm, jnp.zeros((b, h, dh, ds)), chunk=chunk)
    else:
        y, _ = ssm_scan_pallas(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    want = sequential_ssd(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_text_alone_equals_text_padded_beside_longer_ones(tiny_backbone):
    """Causality: right padding into a longer length bucket, beside longer
    texts, leaves a text's logits as they are alone (f32 summation order
    differs between the two shapes, hence the tolerance)."""
    rng = np.random.default_rng(1)
    engine = tiny_engine(((1, 16), (4, 64)))
    short = texts(rng, [11])
    others = texts(rng, [50, 64])
    batch = np.concatenate([others[:, :1], np.pad(short, ((0, 53), (0, 0))), others[:, 1:]],
                           axis=1)
    alone = engine.forward(short)
    together = engine.forward(batch)
    assert engine.cache_info()["buckets"] == [(1, 16), (4, 64)]
    np.testing.assert_allclose(together[:, 1:2], alone, rtol=1e-5, atol=1e-5)


def test_plan_tokens_groups_texts_of_like_length(tiny_backbone):
    """Longest first; a text joins a group only where that costs no more
    bucket tokens than its own program."""
    engine = tiny_engine(((1, 16), (2, 16), (1, 32), (2, 32), (1, 64)))
    assert engine.buckets[0] == (1, 16) and engine.max_length == 64
    plan = [(cols.tolist(), bucket) for cols, bucket in engine.plan_tokens([10, 30, 12, 60, 5])]
    assert plan == [([3], (1, 64)), ([1], (1, 32)), ([2, 0], (2, 16)), ([4], (1, 16))]
    rng = np.random.default_rng(4)
    ids = texts(rng, [10, 30, 12, 60, 5])
    alone = np.concatenate([engine.forward(ids[:, j:j + 1]) for j in range(5)], axis=1)
    np.testing.assert_allclose(engine.forward(ids), alone, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="1..64"):
        engine.forward(np.ones((65, 1), np.int32))


def test_runtime_admits_token_columns_and_counts_tokens(tiny_backbone):
    rng = np.random.default_rng(2)
    engine = tiny_engine(((2, 32), (4, 64)))
    rt = ServeRuntime(engine, clock=ManualClock(), max_pending_samples=16).start()
    good = [rt.submit(texts(rng, [20, 7])), rt.submit(texts(rng, [40]))]
    poison = rt.submit(np.full((4, 1), TINY.vocab_size, np.int32))
    empty = rt.submit(np.zeros((4, 1), np.int32))
    floats = rt.submit(np.ones((4, 1), np.float32))
    rt.flush()
    assert [h.status for h in good] == ["completed", "completed"]
    assert rt.stats["rejected_poison"] == 3
    assert "vocabulary" in poison.error and "1..64" in empty.error and "integer" in floats.error
    assert rt.stats["batches"] == 1 and rt.stats["batch_tokens"] == 67
    assert rt.stats["bucket_tokens"] == 4 * 64
    for h, n in zip(good, (2, 1)):
        assert h.result().shape == (Q, n)


def test_a_batch_grows_while_its_plan_fits_the_largest_bucket(tiny_backbone):
    """Texts of 40 tokens: four share the (4, 64) bucket; a fifth would
    need a second program, past the largest bucket's 256 tokens."""
    rng = np.random.default_rng(3)
    engine = tiny_engine(((2, 32), (4, 64)))
    assert engine.max_tokens == 256
    xs = [engine.admit(texts(rng, [40])) for _ in range(5)]
    assert engine.batch_tokens(xs[:4]) == (160, 256) and engine.batch_fits(xs[:4], 4)
    assert not engine.batch_fits(xs, 4)
    batches = pack_fifo([(x, None) for x in xs], engine.max_batch, engine.batch_fits)
    assert [len(b) for b in batches] == [4, 1]
    rt = ServeRuntime(engine, clock=ManualClock(), max_pending_samples=16).start()
    handles = [rt.submit(x) for x in xs]
    rt.flush()
    assert all(h.ok() for h in handles)
    assert rt.stats["batches"] == 2 and rt.stats["bucket_tokens"] == 2 * 256
    with pytest.raises(ValueError, match="ServeRuntime"):
        MicroBatcher(engine)


def test_text_lengths_reads_the_last_real_token():
    ids = np.array([[5, 0, 0], [6, 0, 0], [0, 0, 7]])
    assert text_lengths(ids).tolist() == [2, 0, 3]
    last = granite.last_token_index(jnp.asarray(ids.T), PAD_ID)
    assert last.tolist() == [1, 2, 2]
