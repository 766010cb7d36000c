"""The Granite document-serving cell's files, and the program against the
plain reference (``reference_granite``) at a tiny size on the CPU:
the extractor and ``ServeEngine`` logits, the train -> export -> serve
path, and a whole run of the cell's generator, sound, with the fault
planted and under the controls.  The CPU's matmuls are float32
throughout, so the tiny configuration states float32 operands."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness, reference, reference_granite, work_granite
from benchmarks.chip.run import verdict

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks/chip/configs/granite_h_micro_ssfn.json").read_text())
SEED = 2 ** 31 + 17

#: The tiny backbone: hidden 64, one attention layer among three Mamba2
#: layers, vocabulary 512.
TINY_CFG = dict(
    CONFIG, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
    shared_intermediate_size=128, vocab_size=512, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"], num_classes=5,
    stack_hidden=30, stack_layers=3, matmul_operands="float32",
    formats=dict(CONFIG["formats"], weights="float32", activations="float32"),
)
TINY_TRAFFIC = {"kind": "serve_docs", "rate_per_s": 20, "zipf_a": 1.5, "max_texts": 3,
                "length_median": 20, "length_sigma": 0.8, "min_length": 4,
                "max_length": 64, "buckets": [[1, 32], [4, 64]],
                "max_pending_samples": 64, "deadline_s": 30.0, "flush_interval_s": 0.002}
LIMITS = {"logit_gap": 1e-4, "logit_gap_p10": 1e-4, "feature_gap": 1e-4, "ssd_gap": 1e-4}


def tiny_model(cfg=TINY_CFG):
    from repro.configs import get_config

    return dataclasses.replace(
        get_config("granite-4.0-h-micro"), num_layers=len(cfg["layer_types"]),
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["shared_intermediate_size"], vocab_size=cfg["vocab_size"],
        ssm_state=cfg["mamba_d_state"], ssm_heads=cfg["mamba_n_heads"],
        d_inner=cfg["mamba_n_heads"] * cfg["mamba_d_head"],
        ssm_groups=cfg["mamba_n_groups"], ssm_chunk=16, attn_chunk=16,
        layer_types=tuple(cfg["layer_types"]), dtype="float32",
    )


def use_tiny_backbone(monkeypatch, cfg=TINY_CFG):
    """``granite-h-micro`` specs name the tiny backbone of ``cfg``."""
    from repro import configs

    real, tiny = configs.get_config, tiny_model(cfg)
    monkeypatch.setattr(configs, "get_config",
                        lambda name: tiny if name == "granite-4.0-h-micro" else real(name))


def test_configuration_holds_the_published_values():
    c = CONFIG
    assert c["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    assert c["reduced"] == [] and {"num_classes", "pooling", "weights", "tokens", "formats"} <= set(c["assumed"])
    assert (c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]) == (40, 2048, 100352)
    assert [i for i, t in enumerate(c["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    assert c["layer_types"].count("mamba") == 36
    assert (c["num_attention_heads"], c["num_key_value_heads"]) == (32, 8)
    assert c["hidden_size"] // c["num_attention_heads"] == 64
    assert c["position_embedding_type"] == "nope" and c["attention_multiplier"] == 0.015625
    assert (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_expand"]) == (64, 64, 2)
    assert (c["mamba_d_state"], c["mamba_n_groups"], c["mamba_d_conv"]) == (128, 1, 4)
    assert c["mamba_conv_bias"] is True and c["mamba_proj_bias"] is False
    assert c["mamba_chunk_size"] == 256 and c["shared_intermediate_size"] == 8192
    assert (c["embedding_multiplier"], c["residual_multiplier"]) == (12, 0.22)
    assert c["rms_norm_eps"] == 1e-05 and c["tie_word_embeddings"] is True
    assert (c["num_classes"], c["stack_hidden"], c["stack_layers"]) == (20, 1040, 20)
    assert c["formats"]["weights"] == "bfloat16" and c["formats"]["ssm_state"] == "float32"


def test_program_configuration_matches_the_file():
    from repro.configs import get_config

    m, c = get_config("granite-4.0-h-micro"), CONFIG
    assert m.layer_types == tuple(c["layer_types"]) and m.num_layers == c["num_hidden_layers"]
    assert (m.d_model, m.num_heads, m.num_kv_heads, m.hd) == (2048, 32, 8, 64)
    assert (m.ssm_heads, m.d_inner // m.ssm_heads, m.ssm_state, m.ssm_groups) == (64, 64, 128, 1)
    assert (m.conv_kernel, m.ssm_chunk, m.d_ff, m.vocab_size) == (4, 256, 8192, 100352)
    assert m.position_embedding == "nope" and m.attention_multiplier == c["attention_multiplier"]
    assert m.embedding_multiplier == c["embedding_multiplier"]
    assert m.residual_multiplier == c["residual_multiplier"] and m.norm_eps == c["rms_norm_eps"]
    assert m.dtype == "bfloat16"


def test_work_counts_the_published_model():
    s = work_granite.Sizes.from_config(CONFIG)
    assert abs(work_granite.matmul_weights(s) - 2.98e9) < 0.01e9
    per_token = work_granite.text_flops(s, 1024) / 1024
    assert 5.9e9 < per_token < 6.3e9


def test_new_cell_found_by_name():
    c = harness.load_cell("granite_h_micro_ssfn.serve_docs")
    assert c.chips == 1 and set(c.limits)
    gen = harness.load_generator(c.traffic["kind"])
    assert set(gen.TRAFFIC_KEYS) <= set(c.traffic)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"} and len(c.end_to_end) >= 2
    for m in c.per_layer:
        assert callable(harness.load_reader(m["name"]))
    assert set(c.limits) == {"logit_gap", "logit_gap_p10", "feature_gap", "ssd_gap"}
    assert "serve_samples_per_s" not in {m["name"] for m in c.end_to_end}
    assert [m for m in c.per_layer if m["name"] == "batch_fill.serve"] == []
    assert len(c.traffic["buckets"]) <= 8


def test_program_draws_the_weights_the_reference_draws():
    """``granite-h-micro:<seed>`` names one draw: the program's stacked
    weights are the reference's published tensors, to within one float32
    rounding (the two programs may fold the draw's constant factors in
    another order)."""
    from repro.models import granite

    def assert_same(got, want, err_msg=""):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2.5e-7, atol=0,
                                   err_msg=err_msg)

    sz = reference_granite.Sizes.from_config(TINY_CFG)
    params = granite.init_params(jax.random.PRNGKey(11), tiny_model())
    assert_same(params["embed"], reference_granite.embed_table(11, sz=sz))
    di, gs, h = sz.d_inner, sz.groups * sz.d_state, sz.ssm_heads
    for layer, (p, k) in ((0, (0, 0)), (3, (0, 2))):     # mamba layers 0 and 3
        w = reference_granite.layer_weights(11, layer, kind="mamba", sz=sz)
        m = jax.tree.map(lambda a: a[p, k], params["mamba"])
        z, x, b, c, dt = np.split(np.asarray(w["mamba.in_proj.weight"]),
                                  [di, 2 * di, 2 * di + gs, 2 * di + 2 * gs])
        for name, want in (("in_z", z), ("in_x", x), ("in_b", b), ("in_c", c), ("in_dt", dt)):
            assert_same(m[name], want.T, err_msg=name)
        assert_same(m["conv_w"], np.asarray(w["mamba.conv1d.weight"])[:, 0].T)
        for name, pub in (("conv_b", "mamba.conv1d.bias"), ("dt_bias", "mamba.dt_bias"),
                          ("a_log", "mamba.A_log"), ("d_skip", "mamba.D")):
            assert_same(m[name], w[pub], err_msg=name)
        assert_same(m["out"], np.asarray(w["mamba.out_proj.weight"]).T)
        mlp = jax.tree.map(lambda a: a[0, layer], params["mlp"])
        gate, up = np.split(np.asarray(w["shared_mlp.input_linear.weight"]), 2)
        assert_same(mlp["wg"], gate.T)
        assert_same(mlp["wu"], up.T)
        assert_same(mlp["wd"], np.asarray(w["shared_mlp.output_linear.weight"]).T)
    w = reference_granite.layer_weights(11, 2, kind="attention", sz=sz)
    for name, pub in (("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o")):
        assert_same(params["attn"]["attn"][name][0, 0],
                                      np.asarray(w[f"self_attn.{pub}_proj.weight"]).T)
    assert h == params["mamba"]["a_log"].shape[-1]


@pytest.mark.parametrize("groups", [1, 2])
def test_engine_matches_the_plain_reference(groups, monkeypatch):
    from repro.core import ssfn
    from repro.serve import ServeEngine
    from repro.serve.export import ServeArtifact

    cfg = dict(TINY_CFG, mamba_n_groups=groups)
    use_tiny_backbone(monkeypatch, cfg)
    rng = np.random.default_rng(groups)
    readouts, rmats = ([jnp.asarray(a) for a in part] for part in _stack(rng, cfg))
    art = ServeArtifact(params=ssfn.SSFNParams(o=tuple(readouts), r=tuple(rmats)),
                        num_classes=cfg["num_classes"], input_dim=cfg["hidden_size"],
                        activation="relu", features="granite-h-micro:5", version=1,
                        manifest={})
    engine = ServeEngine(art, buckets=((2, 32), (4, 64)))
    texts = [rng.integers(1, cfg["vocab_size"], n) for n in (3, 31, 64, 17, 40)]
    ids = np.zeros((64, len(texts)), np.int32)
    for j, t in enumerate(texts):
        ids[:len(t), j] = t
    logits, phi = engine.forward_features(ids)
    sz = reference_granite.Sizes.from_config(cfg)
    want_phi = reference_granite.features(5, texts, sz, 0)["last"]
    want = reference.forward(list(readouts), list(rmats), jnp.asarray(want_phi))
    np.testing.assert_allclose(phi, want_phi, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(logits, np.asarray(want), rtol=1e-4, atol=1e-4)


def _stack(rng, cfg):
    q, n, p = cfg["num_classes"], cfg["stack_hidden"], cfg["hidden_size"]
    o = [rng.normal(size=(q, p if i == 0 else n)).astype(np.float32) for i in range(3)]
    r = [(rng.normal(size=(n - 2 * q, p if i == 0 else n)) / 8).astype(np.float32)
         for i in range(2)]
    return o, r


def test_train_export_serve_path_matches_the_reference(tmp_path, monkeypatch):
    """Features of the backbone -> dssfn.train -> export_artifact(features=...)
    -> ServeEngine, against the reference's features through the same stack."""
    from repro import dssfn
    from repro.core import ssfn
    from repro.serve import ServeEngine, export_artifact
    from repro.serve.features import parse_features

    use_tiny_backbone(monkeypatch)
    rng = np.random.default_rng(7)
    ex = parse_features("granite-h-micro:9")
    texts = [rng.integers(1, 512, int(n)) for n in rng.integers(4, 33, 32)]
    ids = np.zeros((32, len(texts)), np.int32)
    for j, t in enumerate(texts):
        ids[:len(t), j] = t
    phi = np.asarray(ex(jnp.asarray(ids)))
    labels = rng.integers(0, 3, len(texts))
    xw = jnp.asarray(phi.reshape(64, 4, 8).transpose(1, 0, 2))
    tw = jax.nn.one_hot(jnp.asarray(labels.reshape(4, 8)), 3).transpose(0, 2, 1)
    cfg = ssfn.SSFNConfig(input_dim=64, num_classes=3, num_layers=2, hidden=20, admm_iters=20)
    trained = dssfn.train(dssfn.TrainSpec(cfg=cfg, backend="simulated", workers=4), xw, tw,
                          jax.random.PRNGKey(1))
    path = str(tmp_path / "artifact")
    export_artifact(path, trained, features="granite-h-micro:9")
    engine = ServeEngine(path, buckets=((8, 32),))
    got = engine.forward(ids)
    sz = reference_granite.Sizes.from_config(TINY_CFG)
    want_phi = reference_granite.features(9, texts, sz, 0)["last"]
    want = reference.forward(list(trained.params.o), list(trained.params.r),
                             jnp.asarray(want_phi))
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4, atol=1e-4)


def run_tiny(monkeypatch, patch=None, controls=False):
    """One tiny run of the serve_docs generator on the CPU."""
    drv = harness.load_generator("serve_docs")
    use_tiny_backbone(monkeypatch)
    if patch is not None:
        patch()
    cell = harness.Cell("tiny.serve_docs", 1, TINY_CFG, TINY_TRAFFIC, LIMITS)
    out = drv.run(cell, seed=SEED, seconds=1, trace_dir=None, t_start=0.0,
                  controls=controls)
    return out, verdict(out.compared, LIMITS)


def test_serve_docs_sound_run_is_correct_and_controls_are_not(monkeypatch):
    out, (correct, shown) = run_tiny(monkeypatch, controls=True)
    assert correct and out.failed == 0, shown
    assert set(out.values) == {"setup_s", "serve_p50_ms", "serve_p95_ms"}
    for name in ("control_state", "wrong_token"):
        assert not verdict(out.controls[name], LIMITS)[0], (name, out.controls[name])
    # The bfloat16 state shows in the first layer's scan.
    assert out.controls["control_state"]["ssd_gap"] > 1e-3


def test_serve_docs_wrong_token_is_not_correct(monkeypatch):
    from repro.models import granite

    last = granite.last_token_index
    out, (correct, shown) = run_tiny(monkeypatch, lambda: monkeypatch.setattr(
        granite, "last_token_index", lambda ids, pad: jnp.maximum(last(ids, pad) - 1, 0)))
    assert not correct, shown
    assert shown["feature_gap"]["value"] > 0.1 and shown["logit_gap_p10"]["value"] > 1e-3


def test_serve_docs_layer_in_another_slot_is_not_correct(monkeypatch):
    """The program's first two Mamba2 layers swapped in its stack: the
    reference, drawing its own weights, sees it."""
    from repro.models import granite

    init = granite.init_params

    def swapped(key, cfg):
        params = init(key, cfg)
        params["mamba"] = jax.tree.map(lambda a: a[:, jnp.array([1, 0, 2])], params["mamba"])
        return params

    out, (correct, shown) = run_tiny(monkeypatch, lambda: monkeypatch.setattr(
        granite, "init_params", swapped))
    assert not correct, shown
    assert shown["feature_gap"]["value"] > 0.01


def test_serve_docs_weights_in_a_lower_format_are_not_correct(monkeypatch):
    """The program's weights stored in bfloat16 where the configuration
    (here float32) says otherwise."""
    from repro.models import granite

    init = granite.init_params

    def rounded(key, cfg):
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype), init(key, cfg))

    out, (correct, shown) = run_tiny(monkeypatch, lambda: monkeypatch.setattr(
        granite, "init_params", rounded))
    assert not correct, shown
