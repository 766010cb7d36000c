"""Parameter init and application of the per-layer blocks."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import profiling
from repro.models.config import ModelConfig
from repro.nn import attention as attn_lib
from repro.nn import moe as moe_lib
from repro.nn import ssm as ssm_lib
from repro.nn import xlstm as xlstm_lib
from repro.nn.layers import dense_init, rms_norm
from repro.nn.mlp import swiglu
from repro.nn.rope import apply_rope
from repro.sharding.rules import shard

Array = jax.Array
Params = dict[str, Any]


# ---------------------------------------------------------------- attention

def init_attn_params(key: jax.Array, cfg: ModelConfig) -> Params:
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 4)
    dt = cfg.jnp_dtype
    return {
        "wq": dense_init(ks[0], (d, cfg.num_heads * hd), dt),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads * hd), dt),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads * hd), dt),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, d), dt),
    }


def apply_attention(
    p: Params,
    x: Array,
    positions: Array,
    cfg: ModelConfig,
    cache: attn_lib.KVCache | None,
    *,
    window: int | None,
) -> tuple[Array, attn_lib.KVCache | None]:
    b, s, d = x.shape
    hd = cfg.hd
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = (x @ p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (x @ p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    q = shard(q, "batch", None, "tensor", None)
    k = shard(k, "batch", None, "tensor", None)
    if cfg.position_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        kr = attn_lib.repeat_kv(k, cfg.num_heads)
        vr = attn_lib.repeat_kv(v, cfg.num_heads)
        if cfg.use_pallas_kernels and s % 128 == 0 and cfg.attention_multiplier is None:
            from repro.kernels.flash_attention import flash_attention

            out = flash_attention(
                q.transpose(0, 2, 1, 3),
                kr.transpose(0, 2, 1, 3),
                vr.transpose(0, 2, 1, 3),
                window=window,
            ).transpose(0, 2, 1, 3)
        else:
            out = attn_lib.chunked_causal_attention(
                q, kr, vr, chunk_size=min(cfg.attn_chunk, s), window=window,
                scale=cfg.attention_multiplier,
            )
        new_cache = None
    else:
        cache = attn_lib.cache_update(cache, k, v)
        out = attn_lib.decode_attention(
            q, cache, num_heads=cfg.num_heads, window=window
        )
        new_cache = cache
    out = shard(out, "batch", None, "tensor", None)
    y = out.reshape(b, s, cfg.num_heads * hd) @ p["wo"]
    return shard(y, "batch", None, None), new_cache


# ---------------------------------------------------------------- mlp / moe

def init_ffn_params(key: jax.Array, cfg: ModelConfig) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    dt = cfg.jnp_dtype
    if cfg.num_experts:
        ks = jax.random.split(key, 4)
        e = cfg.num_experts
        return {
            "router": dense_init(ks[0], (d, e), jnp.float32),
            "wg": dense_init(ks[1], (e, d, f), dt),
            "wu": dense_init(ks[2], (e, d, f), dt),
            "wd": dense_init(ks[3], (e, f, d), dt),
        }
    ks = jax.random.split(key, 3)
    return {
        "wg": dense_init(ks[0], (d, f), dt),
        "wu": dense_init(ks[1], (d, f), dt),
        "wd": dense_init(ks[2], (f, d), dt),
    }


def apply_ffn(p: Params, x: Array, cfg: ModelConfig) -> tuple[Array, Array]:
    """Returns (out, moe_aux_loss)."""
    if cfg.num_experts:
        out, stats = moe_lib.moe_ffn(
            x,
            p["router"],
            p["wg"],
            p["wu"],
            p["wd"],
            top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
        )
        return out, stats.aux_loss
    return swiglu(x, p["wg"], p["wu"], p["wd"]), jnp.zeros((), jnp.float32)


# ------------------------------------------------------- transformer layer

def init_transformer_layer(key: jax.Array, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    p = {
        "ln1": jnp.ones((d,), cfg.jnp_dtype),
        "ln2": jnp.ones((d,), cfg.jnp_dtype),
        "attn": init_attn_params(k1, cfg),
        "ffn": init_ffn_params(k2, cfg),
    }
    return p


def apply_transformer_layer(
    p: Params,
    x: Array,
    positions: Array,
    cfg: ModelConfig,
    cache: attn_lib.KVCache | None,
) -> tuple[Array, attn_lib.KVCache | None, Array]:
    window = cfg.window if cfg.attention == "swa" else None
    h, new_cache = apply_attention(
        p["attn"], rms_norm(x, p["ln1"]), positions, cfg, cache, window=window
    )
    x = x + h
    f, aux = apply_ffn(p["ffn"], rms_norm(x, p["ln2"]), cfg)
    if cfg.d_ff or cfg.num_experts:
        x = x + f
    return shard(x, "batch", None, None), new_cache, aux


# ------------------------------------------------------------ mamba2 layer

def init_mamba_layer(key: jax.Array, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    di = cfg.d_inner_eff
    ds, h, g = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_groups
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 8)
    return {
        "ln": jnp.ones((d,), dt),
        "in_x": dense_init(ks[0], (d, di), dt),
        "in_z": dense_init(ks[1], (d, di), dt),
        "in_b": dense_init(ks[2], (d, g * ds), dt),
        "in_c": dense_init(ks[3], (d, g * ds), dt),
        "in_dt": dense_init(ks[4], (d, h), dt),
        # One depthwise conv over the x, B and C channels (Mamba2's xBC).
        "conv_w": dense_init(ks[5], (cfg.conv_kernel, di + 2 * g * ds), dt, scale=0.5),
        "conv_b": jnp.zeros((di + 2 * g * ds,), dt),
        "a_log": jnp.log(
            jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)
        ),  # A = -exp(a_log)
        "dt_bias": jnp.full((h,), -2.0, jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),  # y += D * x
        "gn": jnp.ones((di,), dt),
        "out": dense_init(ks[6], (di, d), dt),
    }


def apply_mamba_layer(
    p: Params,
    x: Array,
    cfg: ModelConfig,
    state: ssm_lib.SSMState | None,
) -> tuple[Array, ssm_lib.SSMState | None]:
    """Pre-norm Mamba2 mixer with its residual; state=None -> training/
    prefill from zero state (full-sequence scan).

    x, B and C are one causal depthwise conv's channels (then SiLU); heads
    fall into ``ssm_groups`` groups that share B and C; the scan output
    gains the skip ``D * x``; the gated RMSNorm reads ``y * silu(z)``."""
    b, s, d = x.shape
    di = cfg.d_inner_eff
    h_heads, ds, groups = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    dh = di // h_heads
    res = x
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    xs = shard(xn @ p["in_x"], "batch", None, "tensor")
    z = shard(xn @ p["in_z"], "batch", None, "tensor")
    bm = xn @ p["in_b"]
    cm = xn @ p["in_c"]
    dt_pre = (xn @ p["in_dt"]).astype(jnp.float32) + p["dt_bias"]
    dt = jax.nn.softplus(dt_pre)
    a = -jnp.exp(p["a_log"])
    xbc = jnp.concatenate([xs, bm, cm], axis=-1)

    decode = state is not None and s == 1
    if decode:
        xbc, conv_new = ssm_lib.causal_conv1d(xbc, p["conv_w"], p["conv_b"], state.conv)
        xs, bm, cm = jnp.split(xbc, [di, di + groups * ds], axis=-1)
        y, h_new = _grouped(
            lambda xg, dtg, ag, bg, cg, hg: ssm_lib.ssm_decode_step(
                xg[:, 0], dtg[:, 0], ag, bg[:, 0], cg[:, 0], hg),
            xs.reshape(b, 1, h_heads, dh), dt, a, bm, cm, state.h, groups, ds,
        )
        y = y.reshape(b, 1, di)
        new_state = ssm_lib.SSMState(h=h_new, conv=conv_new)
    else:
        xbc, conv_new = ssm_lib.causal_conv1d(xbc, p["conv_w"], p["conv_b"])
        xs, bm, cm = jnp.split(xbc, [di, di + groups * ds], axis=-1)
        y, h_new = ssd_scan(xs, dt, a, bm, cm, cfg,
                            pallas=cfg.use_pallas_kernels and state is None)
        new_state = ssm_lib.SSMState(h=h_new, conv=conv_new) if state is not None else None
    y = _add_skip(y, xs, p["d_skip"], h_heads)
    y = rms_norm(y * jax.nn.silu(z), p["gn"], cfg.norm_eps)
    out = y @ p["out"]
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return shard(res + out, "batch", None, None), new_state


def ssd_scan(xs: Array, dt: Array, a: Array, bm: Array, cm: Array, cfg: ModelConfig,
             *, pallas: bool = False) -> tuple[Array, Array]:
    """The Mamba2 state-space scan of a whole sequence from a zero state,
    chunked by ``cfg.ssm_chunk`` (the Pallas ``ssm_scan`` with ``pallas``),
    under the ``backbone.ssd`` scope.  xs: (B, S, d_inner); dt: (B, S, H)
    float32; a: (H,); bm, cm: (B, S, G*ds) -> y (B, S, d_inner) before
    the D skip, and the last state (B, H, dh, ds) float32."""
    b, s, di = xs.shape
    h_heads, ds, groups = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    dh = di // h_heads
    h0 = jnp.zeros((b, h_heads, dh, ds), jnp.float32)
    chunk = min(cfg.ssm_chunk, s)
    pad = (-s) % chunk
    if pad:
        # dt=0 on padded steps: no decay (a=1), no input contribution.
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0)))
    if pallas:
        from repro.kernels.ssm_scan import ssm_scan

        def scan(xg, dtg, ag, bg, cg, hg):
            return ssm_scan(xg, dtg, ag, bg, cg, chunk=chunk)
    else:
        def scan(xg, dtg, ag, bg, cg, hg):
            return ssm_lib.chunked_ssm_scan(xg, dtg, ag, bg, cg, hg, chunk=chunk)
    with profiling.scope(profiling.SSD):
        y, h_new = _grouped(scan, xs.reshape(b, s + pad, h_heads, dh), dt, a,
                            bm, cm, h0, groups, ds)
    return y[:, :s].reshape(b, s, di), h_new


def _grouped(scan, x, dt, a, bm, cm, h, groups: int, ds: int):
    """``scan`` once per group of heads, each group with its own B and C.
    x: (B, S, H, dh); dt: (B, S, H); bm, cm: (B, S, G*ds); h: (B, H, dh, ds)."""
    if groups == 1:
        return scan(x, dt, a, bm, cm, h)
    per = x.shape[2] // groups
    ys, hs = [], []
    for g in range(groups):
        heads, cols = slice(g * per, (g + 1) * per), slice(g * ds, (g + 1) * ds)
        y, hg = scan(x[:, :, heads], dt[:, :, heads], a[heads], bm[..., cols],
                     cm[..., cols], h[:, heads])
        ys.append(y)
        hs.append(hg)
    return jnp.concatenate(ys, axis=2), jnp.concatenate(hs, axis=1)


def _add_skip(y: Array, x: Array, d_skip: Array, heads: int) -> Array:
    """y + D x, per head, summed in float32."""
    shape = y.shape
    yh = y.reshape(shape[:-1] + (heads, -1)).astype(jnp.float32)
    xh = x.reshape(shape[:-1] + (heads, -1)).astype(jnp.float32)
    return (yh + d_skip[:, None] * xh).reshape(shape).astype(y.dtype)


# ------------------------------------------------------------ xlstm layers

def init_mlstm_layer(key: jax.Array, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    h, hd = cfg.num_heads, cfg.hd
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 6)
    return {
        "ln": jnp.ones((d,), dt),
        "wq": dense_init(ks[0], (d, h * hd), dt),
        "wk": dense_init(ks[1], (d, h * hd), dt),
        "wv": dense_init(ks[2], (d, h * hd), dt),
        "wi": dense_init(ks[3], (d, h), jnp.float32),
        "wf": dense_init(ks[4], (d, h), jnp.float32),
        "gn": jnp.ones((h * hd,), dt),
        "out": dense_init(ks[5], (h * hd, d), dt),
    }


def apply_mlstm_layer(
    p: Params, x: Array, cfg: ModelConfig, state: xlstm_lib.MLSTMState | None
):
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.hd
    res = x
    xn = rms_norm(x, p["ln"])
    q = shard((xn @ p["wq"]).reshape(b, s, h, hd), "batch", None, "tensor", None)
    k = shard((xn @ p["wk"]).reshape(b, s, h, hd), "batch", None, "tensor", None)
    v = shard((xn @ p["wv"]).reshape(b, s, h, hd), "batch", None, "tensor", None)
    i_pre = (xn.astype(jnp.float32) @ p["wi"])
    f_pre = (xn.astype(jnp.float32) @ p["wf"]) + 3.0

    decode = state is not None and s == 1
    if decode:
        y, new_state = xlstm_lib.mlstm_decode_step(
            q[:, 0], k[:, 0], v[:, 0], i_pre[:, 0], f_pre[:, 0], state
        )
        y = y.reshape(b, 1, h * hd)
    else:
        st0 = state if state is not None else xlstm_lib.init_mlstm_state(b, h, hd, hd)
        chunk = min(cfg.ssm_chunk, s)
        pad = (-s) % chunk
        if pad:
            # Padded steps: forget gate ~1 (f_pre >> 0), input gate -inf.
            zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
            q, k, v = (jnp.pad(t, zpad) for t in (q, k, v))
            i_pre = jnp.pad(i_pre, ((0, 0), (0, pad), (0, 0)), constant_values=-1e9)
            f_pre = jnp.pad(f_pre, ((0, 0), (0, pad), (0, 0)), constant_values=1e9)
        if cfg.use_pallas_kernels and state is None:
            from repro.kernels.mlstm_scan import mlstm_scan

            y, _ = mlstm_scan(q, k, v, i_pre, f_pre, chunk=chunk)
            new_state = st0
        else:
            y, new_state = xlstm_lib.chunked_mlstm(
                q, k, v, i_pre, f_pre, st0, chunk=chunk
            )
        y = y[:, :s].reshape(b, s, h * hd)
        if state is None:
            new_state = None
    y = rms_norm(y, p["gn"])
    out = y @ p["out"]
    return shard(res + out, "batch", None, None), new_state


def init_slstm_layer(key: jax.Array, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    h = cfg.num_heads
    dh = d // h
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 3)
    return {
        "ln": jnp.ones((d,), dt),
        "wx": dense_init(ks[0], (d, 4 * d), dt),
        "rw": dense_init(ks[1], (4, h, dh, dh), jnp.float32, scale=1.0 / jnp.sqrt(dh)),
        "gn": jnp.ones((d,), dt),
        "out": dense_init(ks[2], (d, d), dt),
    }


def apply_slstm_layer(
    p: Params, x: Array, cfg: ModelConfig, state: xlstm_lib.SLSTMState | None
):
    b, s, d = x.shape
    res = x
    xn = rms_norm(x, p["ln"])
    x_gates = xn @ p["wx"]
    st0 = state if state is not None else xlstm_lib.init_slstm_state(b, d)
    hs, new_state = xlstm_lib.slstm_scan(x_gates, p["rw"], st0, cfg.num_heads)
    if state is None:
        new_state = None
    y = rms_norm(hs.astype(x.dtype), p["gn"]) @ p["out"]
    return shard(res + y, "batch", None, None), new_state
