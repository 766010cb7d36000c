"""Data pipeline, optimizer, checkpoint, sharding-spec and HLO-analysis tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_pytree, save_pytree
from repro.data import TokenStream, make_classification, partition_workers
from repro.optim import AdamW, Sgd


# ---------------------------------------------------------------- data

def test_partition_disjoint_and_complete():
    data = make_classification(
        jax.random.PRNGKey(0), num_train=100, num_test=10,
        input_dim=4, num_classes=3,
    )
    xw, tw = partition_workers(data.x_train, data.t_train, 5)
    assert xw.shape == (5, 4, 20)
    recon = xw.transpose(1, 0, 2).reshape(4, -1)
    np.testing.assert_array_equal(np.asarray(recon), np.asarray(data.x_train[:, :100]))


def test_token_stream_deterministic():
    s1 = list(zip(range(2), TokenStream(vocab_size=64, seq_len=16, batch_size=2, seed=3)))
    s2 = list(zip(range(2), TokenStream(vocab_size=64, seq_len=16, batch_size=2, seed=3)))
    for (_, a), (_, b) in zip(s1, s2):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert s1[0][1]["tokens"].shape == (2, 16)
    # labels are next-token shifted
    np.testing.assert_array_equal(s1[0][1]["labels"][:, :-1], s1[0][1]["tokens"][:, 1:])


def test_token_stream_audio_grid():
    it = iter(TokenStream(vocab_size=32, seq_len=8, batch_size=2, num_codebooks=4))
    b = next(it)
    assert b["tokens"].shape == (2, 8, 4)
    assert b["labels"].shape == (2, 8, 4)


def test_token_stream_learnable_structure():
    """The planted bigram makes the stream predictable above chance."""
    it = iter(TokenStream(vocab_size=16, seq_len=256, batch_size=4, seed=0))
    b = next(it)
    toks, labels = b["tokens"], b["labels"]
    # For each current token value, the modal next token should dominate.
    correct = total = 0
    for v in range(16):
        mask = toks == v
        if mask.sum() < 10:
            continue
        nxt = labels[mask]
        vals, counts = np.unique(nxt, return_counts=True)
        correct += counts.max()
        total += counts.sum()
    assert correct / total > 0.5  # 85% follow the table; chance is 1/16


# ------------------------------------------------------------- optimizers

def test_adamw_decreases_quadratic():
    opt = AdamW(lr=0.1)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(120):
        g = jax.grad(loss)(params)
        params, state = opt.update(params, g, state)
    assert float(loss(params)) < 0.05


def test_sgd_momentum():
    opt = Sgd(lr=0.05, momentum=0.9)
    params = {"w": jnp.array(4.0)}
    state = opt.init(params)
    for _ in range(150):
        g = {"w": 2 * params["w"]}
        params, state = opt.update(params, g, state)
    assert abs(float(params["w"])) < 0.1


def test_adamw_preserves_dtype():
    opt = AdamW(lr=1e-2)
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    state = opt.init(params)
    g = {"w": jnp.ones((4,), jnp.bfloat16)}
    new, state = opt.update(params, g, state)
    assert new["w"].dtype == jnp.bfloat16
    assert state["m"]["w"].dtype == jnp.float32


# ------------------------------------------------------------- checkpoint

def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
        "t": (jnp.zeros((2,)), jnp.array(3)),
    }
    path = os.path.join(tmp_path, "ckpt.npz")
    save_pytree(path, tree)
    restored = load_pytree(path, jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        assert a.dtype == b.dtype


# ----------------------------------------------------------- hlo analysis

def test_hlo_analysis_counts_scan_flops():
    """Loop trip counts multiply FLOPs (XLA cost_analysis does not)."""
    from repro.launch.hlo_analysis import analyze_module

    def f(ws, x):
        def body(x, w):
            return jnp.maximum(x @ w, 0), 0.0
        x, _ = jax.lax.scan(body, x, ws)
        return x

    ws = jax.ShapeDtypeStruct((5, 32, 32), jnp.float32)
    xs = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    compiled = jax.jit(f).lower(ws, xs).compile()
    a = analyze_module(compiled.as_text())
    expected = 5 * 2 * 8 * 32 * 32
    assert abs(a.flops - expected) / expected < 0.05, (a.flops, expected)


def test_hlo_analysis_shape_parsing():
    from repro.launch.hlo_analysis import _type_bytes

    assert _type_bytes("f32[8,64]{1,0}") == 8 * 64 * 4
    assert _type_bytes("bf16[2,3]") == 12
    assert _type_bytes("(s32[], f32[4])") == 4 + 16
    assert _type_bytes("pred[]") == 1


def test_hlo_analysis_async_collective_forms():
    """`*-start` ops count under the base opcode with the payload (not
    the whole alias+context tuple); the matching `*-done` is skipped so
    an overlapped collective is counted exactly once."""
    from repro.launch.hlo_analysis import analyze_module

    text = """\
HloModule async_probe

ENTRY %main (p0: f32[4,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %ars = (f32[4,8], f32[4,8]) all-reduce-start(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %ard = f32[4,8]{1,0} all-reduce-done(%ars)
  %cps = (f32[4,8], f32[4,8], u32[], u32[]) collective-permute-start(%ard), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %cpd = f32[4,8]{1,0} collective-permute-done(%cps)
  ROOT %sync = f32[4,8]{1,0} all-reduce(%cpd), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    a = analyze_module(text)
    assert a.collective_counts() == {"all-reduce": 2, "collective-permute": 1}
    payload = 4 * 8 * 4
    # Start tuples carry operand alias + u32 context scalars: the payload
    # is the result, never the tuple sum.
    assert [o.result_bytes for o in a.collectives] == [payload] * 3
    by_type = a.collective_by_type()
    assert by_type["collective-permute"] == payload
    assert by_type["all-reduce"] == 2 * (2.0 * payload * 3 / 4)


def test_hlo_analysis_counts_combined_collectives_per_operand():
    """XLA's all-reduce combiner fuses same-kind reductions into one
    tuple op; each combined operand is still one logical collective, so
    counts do not change with the combiner (inside a K-trip loop too)."""
    from repro.launch.hlo_analysis import analyze_module

    text = """\
HloModule combined_probe

%body (p: (s32[], f32[], f32[], f32[4])) -> (s32[], f32[], f32[], f32[4]) {
  %p = (s32[], f32[], f32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %a = f32[] get-tuple-element(%p), index=1
  %b = f32[] get-tuple-element(%p), index=2
  %c = f32[4] get-tuple-element(%p), index=3
  %ar = (f32[], f32[], f32[4]) all-reduce(f32[] %a, f32[] %b, f32[4] %c), replica_groups={{0,1,2,3}}, to_apply=%add
  %mx = f32[] all-reduce(f32[] %a), replica_groups={{0,1,2,3}}, to_apply=%max
  ROOT %t = (s32[], f32[], f32[], f32[4]) tuple(%i, %a, %b, %c)
}

%cond (p: (s32[], f32[], f32[], f32[4])) -> pred[] {
  %p = (s32[], f32[], f32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

ENTRY %main (x: (s32[], f32[], f32[], f32[4])) -> (s32[], f32[], f32[], f32[4]) {
  %x = (s32[], f32[], f32[], f32[4]) parameter(0)
  ROOT %w = (s32[], f32[], f32[], f32[4]) while(%x), condition=%cond, body=%body
}
"""
    counts = analyze_module(text).collective_counts()
    assert counts == {"all-reduce": 4 * 10}, counts

    # Combined async forms: the bytes follow the count, one result per
    # operand, without the operand aliases or the u32 contexts.
    text = """\
HloModule combined_async_probe

ENTRY %main (a: f32[], b: f32[], c: f32[4]) -> f32[4] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  %c = f32[4]{0} parameter(2)
  %ars = (f32[], f32[], f32[4]) all-reduce-start(f32[] %a, f32[] %b, f32[4]{0} %c), replica_groups={{0,1,2,3}}, to_apply=%add
  %ard = (f32[], f32[], f32[4]) all-reduce-done(%ars)
  %ags = ((f32[4], f32[1]), (f32[16], f32[4]), u32[], u32[]) all-gather-start(f32[4]{0} %c, f32[1]{0} %b), replica_groups={{0,1,2,3}}, dimensions={0}
  %agd = (f32[16], f32[4]) all-gather-done(%ags)
  ROOT %r = f32[4]{0} get-tuple-element(%ard), index=2
}
"""
    a = analyze_module(text)
    assert a.collective_counts() == {"all-reduce": 3, "all-gather": 2}
    assert [o.result_bytes for o in a.collectives] == [4 + 4 + 16, 64 + 16]


# -------------------------------------------------------------- sharding

def test_shard_noop_without_mesh():
    from repro.sharding.rules import shard

    x = jnp.ones((4, 4))
    assert shard(x, "batch", None) is x


def test_param_specs_drop_nondivisible():
    from repro.launch.mesh import make_host_mesh
    from repro.launch.specs import param_spec_tree
    from repro.sharding.rules import AxisRules

    mesh = make_host_mesh(1)  # 1 device: (1, 1) mesh
    rules = AxisRules(mesh=mesh, data_axes=("data",), model_axis="model")
    shapes = {"layers": {"attn": {"wq": jax.ShapeDtypeStruct((3, 5), jnp.float32)}}}
    specs = param_spec_tree(shapes, rules, mesh)
    # (1,1) mesh: everything divides; spec carries the logical axes
    assert specs["layers"]["attn"]["wq"] is not None


# ------------------------------------------------- launch: devices, cache

def test_worker_mesh_error_names_platform_and_cpu_remedy():
    import pytest

    from repro.launch.mesh import make_worker_mesh

    want = len(jax.devices()) + 1
    with pytest.raises(ValueError) as err:
        make_worker_mesh(want)
    msg = str(err.value)
    assert f"{len(jax.devices())} {jax.devices()[0].platform} device" in msg
    if jax.devices()[0].platform == "cpu":
        assert f"xla_force_host_platform_device_count={want}" in msg


def test_ensure_devices_fakes_cpu_mesh_only_when_pinned(monkeypatch):
    from repro.launch.train_dssfn import ensure_devices

    monkeypatch.delenv("XLA_FLAGS", raising=False)
    for platforms in (None, "tpu", "cuda"):
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        ensure_devices(8)
        assert "XLA_FLAGS" not in os.environ, platforms
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    ensure_devices(8, allow_fake=False)
    assert "XLA_FLAGS" not in os.environ
    ensure_devices(8)
    assert os.environ["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"


def test_compile_cache_dir_fixed_in_checkout_unless_env(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.compile_cache_dir() == os.path.join(root, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_compile_cache_lands_where_configured(tmp_path):
    """In a fresh process: the env var's directory receives the entries;
    without it, JAX is pointed at the checkout's ``.jax_cache``."""
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = (
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "import os\n"
        "if os.environ.get('JAX_COMPILATION_CACHE_DIR'):\n"
        "    jax.jit(lambda x: x * 2.0)(1.0).block_until_ready()\n"
    )
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(PYTHONPATH=src, JAX_PLATFORMS="cpu",
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", code], env=base,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    chosen, configured = out.stdout.split()[-2:]
    assert chosen == configured
    assert chosen.endswith(os.path.join("", ".jax_cache"))
    env = dict(base, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-2:] == [str(tmp_path / "cache")] * 2
    assert os.listdir(tmp_path / "cache")
