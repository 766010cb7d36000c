"""Granite-4.0-H hybrid: Mamba2 and attention layers in a published
pattern, each followed by a dense SwiGLU MLP; prefill only, no cache.

    h = embedding_multiplier * embed(ids)
    per layer:  h += residual_multiplier * mixer(RMSNorm(h))
                h += residual_multiplier * SwiGLU(RMSNorm(h))
    features    = RMSNorm(h) at each text's last real token

The mixer is the shared Mamba2 layer (``blocks.apply_mamba_layer``) or
GQA attention (``blocks.apply_attention``, no RoPE under
``position_embedding="nope"``, scores scaled by ``attention_multiplier``),
as ``cfg.layer_types`` says.  The language-model head is never run: the
model serves as a frozen feature extractor (``serve/features.py``).

Layers run as a ``lax.scan`` over repeats of the pattern's period, and
inside a period as one scan per run of same-kind layers, so a compiled
program holds one copy of each layer kind whatever the depth.

IBM's weights are not in the repository.  ``init_params`` draws them
from a key, layer by layer under the published checkpoint's names and
layouts (``draw_layer``), and only then stacks them, so that a check
can draw the same weights without knowing this module's layout.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro import profiling
from repro.models import blocks
from repro.models.config import ModelConfig
from repro.nn.layers import embed_lookup, rms_norm
from repro.nn.mlp import swiglu

Array = jax.Array
Params = dict[str, Any]

KINDS = ("mamba", "attention")


def period_runs(layer_types: tuple[str, ...]) -> tuple[int, list[tuple[str, int]]]:
    """The shortest period the pattern repeats with, and the period as runs
    of ``(kind, count)`` of consecutive same-kind layers."""
    n = len(layer_types)
    if n == 0 or any(t not in KINDS for t in layer_types):
        raise ValueError(f"layer_types must be a non-empty sequence of {KINDS}, "
                         f"got {layer_types!r}")
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and tuple(layer_types[:p]) * (n // p) == tuple(layer_types))
    runs: list[tuple[str, int]] = []
    for kind in layer_types[:period]:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return period, runs


#: The drawn tensors of a layer, in the order their keys are folded
#: (``draw_layer``): the published checkpoint's names and ``(out, in)``
#: layouts, with the standard deviation of each normal draw (a string
#: names a width of ``cfg``: ``1/sqrt(width)``).
DRAWN = {
    "mamba": (("mamba.in_proj.weight", "d_model"), ("mamba.conv1d.weight", 0.5),
              ("mamba.conv1d.bias", 0.1), ("mamba.out_proj.weight", "d_inner"),
              ("mamba.dt_bias", None)),
    "attention": (("self_attn.q_proj.weight", "d_model"), ("self_attn.k_proj.weight", "d_model"),
                  ("self_attn.v_proj.weight", "d_model"), ("self_attn.o_proj.weight", "q_width")),
}
#: The MLP's drawn tensors, keyed after the mixer's (indices 5 and 6).
DRAWN_MLP = (("shared_mlp.input_linear.weight", "d_model"),
             ("shared_mlp.output_linear.weight", "d_ff"))
#: dt at init is log-uniform over this range (Mamba2's); ``dt_bias`` is
#: its inverse softplus.
DT_RANGE = (1e-3, 1e-1)
#: The dt rows of ``mamba.in_proj.weight`` are drawn at this share of
#: the other rows' scale, so that each head's dt stays near its bias and
#: the heads keep Mamba2's spread of time scales, the slowest holding
#: thousands of tokens in their state.
DT_ROWS_SCALE = 0.1


def _published_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    d, di, h = cfg.d_model, cfg.d_inner_eff, cfg.ssm_heads
    xbc = di + 2 * cfg.ssm_groups * cfg.ssm_state
    return {
        "mamba.in_proj.weight": (di + xbc + h, d),   # rows: z | x B C | dt
        "mamba.conv1d.weight": (xbc, 1, cfg.conv_kernel),
        "mamba.conv1d.bias": (xbc,),
        "mamba.out_proj.weight": (d, di),
        "mamba.dt_bias": (h,),
        "self_attn.q_proj.weight": (cfg.num_heads * cfg.hd, d),
        "self_attn.k_proj.weight": (cfg.num_kv_heads * cfg.hd, d),
        "self_attn.v_proj.weight": (cfg.num_kv_heads * cfg.hd, d),
        "self_attn.o_proj.weight": (d, cfg.num_heads * cfg.hd),
        "shared_mlp.input_linear.weight": (2 * cfg.d_ff, d),  # rows: gate | up
        "shared_mlp.output_linear.weight": (d, cfg.d_ff),
    }


def draw_layer(key: jax.Array, kind: str | None, cfg: ModelConfig, *, mlp: bool = True
               ) -> dict[str, Array]:
    """Layer weights of the seeded draw, by their published names and
    layouts, stored in ``cfg.dtype`` (the MLP's only with ``mlp``; a
    mixer ``kind`` of None draws the MLP alone).

    Tensor ``t`` of ``DRAWN[kind]`` is drawn from ``fold_in(key, t)``, the
    MLP's from ``fold_in(key, 5)`` and ``fold_in(key, 6)``: a normal draw
    in float32 times its standard deviation (``mamba.in_proj.weight``'s
    last ``ssm_heads`` rows, dt's, times ``DT_ROWS_SCALE`` besides),
    except ``mamba.dt_bias``, the inverse softplus of a dt drawn
    log-uniform over ``DT_RANGE``.
    The norms' weights and ``mamba.D`` are ones and ``mamba.A_log`` is
    ``log(1..heads)``, as Mamba2 initializes them."""
    shapes = _published_shapes(cfg)
    widths = {"d_model": cfg.d_model, "d_inner": cfg.d_inner_eff, "d_ff": cfg.d_ff,
              "q_width": cfg.num_heads * cfg.hd}
    drawn = list(enumerate(DRAWN[kind])) if kind is not None else []
    if mlp:
        drawn += [(5 + i, t) for i, t in enumerate(DRAWN_MLP)]
    out = {}
    for t, (name, std) in drawn:
        k = jax.random.fold_in(key, t)
        if name == "mamba.dt_bias":
            lo, hi = (math.log(v) for v in DT_RANGE)
            dt = jnp.exp(jax.random.uniform(k, shapes[name], jnp.float32, lo, hi))
            w = dt + jnp.log(-jnp.expm1(-dt))
        else:
            if isinstance(std, str):
                std = 1.0 / math.sqrt(widths[std])
            w = jax.random.normal(k, shapes[name], jnp.float32) * std
            if name == "mamba.in_proj.weight":
                w = w.at[-cfg.ssm_heads:].multiply(DT_ROWS_SCALE)
        out[name] = w.astype(cfg.jnp_dtype)
    return out


def _mamba_mixer(w: dict[str, Array], cfg: ModelConfig) -> Params:
    """A published Mamba2 layer's mixer in ``blocks.apply_mamba_layer``'s
    layout; A_log, dt_bias and D widen to float32."""
    di, h, gs = cfg.d_inner_eff, cfg.ssm_heads, cfg.ssm_groups * cfg.ssm_state
    dt = cfg.jnp_dtype
    z, x, b, c, d_t = jnp.split(w["mamba.in_proj.weight"],
                                [di, 2 * di, 2 * di + gs, 2 * di + 2 * gs])
    return {
        "ln": jnp.ones((cfg.d_model,), dt),
        "in_x": x.T, "in_z": z.T, "in_b": b.T, "in_c": c.T, "in_dt": d_t.T,
        "conv_w": w["mamba.conv1d.weight"][:, 0, :].T,
        "conv_b": w["mamba.conv1d.bias"],
        "a_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)).astype(dt).astype(jnp.float32),
        "dt_bias": w["mamba.dt_bias"].astype(jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "gn": jnp.ones((di,), dt),
        "out": w["mamba.out_proj.weight"].T,
    }


def _attention_mixer(w: dict[str, Array], cfg: ModelConfig) -> Params:
    return {"ln": jnp.ones((cfg.d_model,), cfg.jnp_dtype),
            "attn": {n: w[f"self_attn.{p}_proj.weight"].T
                     for n, p in (("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o"))}}


def _mlp_block(w: dict[str, Array], cfg: ModelConfig) -> Params:
    gate, up = jnp.split(w["shared_mlp.input_linear.weight"], 2)
    return {"ln": jnp.ones((cfg.d_model,), cfg.jnp_dtype), "wg": gate.T, "wu": up.T,
            "wd": w["shared_mlp.output_linear.weight"].T}


def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    """The seeded weights: layer ``i`` is ``draw_layer(fold_in(key, i))``,
    the embedding (``model.embed_tokens.weight``, normal times 0.02) is
    drawn from ``fold_in(key, num_layers)``, the final norm is ones.
    Stacked for ``hidden_states``: the mixers of each kind
    ``(periods, per period, ...)``, one MLP a layer ``(periods, period, ...)``."""
    period, _ = period_runs(cfg.layer_types)
    periods = cfg.num_layers // period
    d, dt = cfg.d_model, cfg.jnp_dtype
    embed = jax.random.normal(jax.random.fold_in(key, cfg.num_layers),
                              (cfg.vocab_size, d), jnp.float32) * 0.02
    params: Params = {"embed": embed.astype(dt), "ln_f": jnp.ones((d,), dt)}

    def stacked(kind, convert, layers):
        def one(i):
            return convert(draw_layer(jax.random.fold_in(key, i), kind, cfg,
                                      mlp=kind is None), cfg)
        return jax.vmap(jax.vmap(one))(jnp.asarray(layers, jnp.int32))

    for kind, name, convert in (("mamba", "mamba", _mamba_mixer),
                                ("attention", "attn", _attention_mixer)):
        slots = [j for j in range(period) if cfg.layer_types[j] == kind]
        if slots:
            params[name] = stacked(kind, convert,
                                   [[p * period + j for j in slots] for p in range(periods)])
    params["mlp"] = stacked(None, _mlp_block,
                            [[p * period + j for j in range(period)] for p in range(periods)])
    return params


def _residual(x: Array, out: Array, cfg: ModelConfig) -> Array:
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


def _mlp(p: Params, x: Array, cfg: ModelConfig) -> Array:
    with profiling.scope(profiling.MLP):
        return _residual(x, swiglu(rms_norm(x, p["ln"], cfg.norm_eps),
                                   p["wg"], p["wu"], p["wd"]), cfg)


def _attention(p: Params, x: Array, positions: Array, cfg: ModelConfig) -> Array:
    with profiling.scope(profiling.ATTENTION):
        h, _ = blocks.apply_attention(p["attn"], rms_norm(x, p["ln"], cfg.norm_eps),
                                      positions, cfg, None, window=None)
        return _residual(x, h, cfg)


def hidden_states(params: Params, ids: Array, cfg: ModelConfig) -> Array:
    """The residual stream after the last layer, before the final norm.
    ids: (B, S) int -> (B, S, d)."""
    _, runs = period_runs(cfg.layer_types)
    x = embed_lookup(params["embed"], ids)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    positions = jnp.arange(ids.shape[1])

    def layer(kind):
        def body(x, p):
            mixer, mlp = p
            if kind == "mamba":
                x, _ = blocks.apply_mamba_layer(mixer, x, cfg, None)
            else:
                x = _attention(mixer, x, positions, cfg)
            return _mlp(mlp, x, cfg), None
        return body

    def period_body(x, p):
        at = dict.fromkeys(KINDS + ("mlp",), 0)
        for kind, count in runs:
            key = "mamba" if kind == "mamba" else "attn"
            mixer = jax.tree.map(lambda a: a[at[kind]:at[kind] + count], p[key])
            mlp = jax.tree.map(lambda a: a[at["mlp"]:at["mlp"] + count], p["mlp"])
            at[kind] += count
            at["mlp"] += count
            if count == 1:
                x, _ = layer(kind)(x, jax.tree.map(lambda a: a[0], (mixer, mlp)))
            else:
                x, _ = jax.lax.scan(layer(kind), x, (mixer, mlp))
        return x, None

    stacks = {k: params[k] for k in ("mamba", "attn", "mlp") if k in params}
    x, _ = jax.lax.scan(period_body, x, stacks)
    return x


def last_token_index(ids: Array, pad_id: int) -> Array:
    """Each row's last position that holds no pad id (texts are padded on
    the right); a row of pads gives its last position."""
    real = ids != pad_id
    return ids.shape[1] - 1 - jnp.argmax(real[:, ::-1], axis=1)


def pooled_features(params: Params, ids: Array, cfg: ModelConfig, pad_id: int) -> Array:
    """The final-normed hidden state at each text's last real token.
    ids: (B, S) right-padded with ``pad_id`` -> (B, d)."""
    x = hidden_states(params, ids, cfg)
    last = last_token_index(ids, pad_id)
    h = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return rms_norm(h, params["ln_f"], cfg.norm_eps)
