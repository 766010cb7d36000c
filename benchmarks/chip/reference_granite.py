"""Plain reference of Granite-4.0-H-Micro as the frozen feature extractor
of a served SSFN classifier, independent of the program under test.

The published forward (``configs/granite_h_micro_ssfn.json``, HF
``GraniteMoeHybridForCausalLM``) in straightforward ``jax.numpy`` at
float32, under ``jax.default_matmul_precision("highest")``:

- ``h = embedding_multiplier * embed[ids]``;
- each layer ``h += residual_multiplier * mixer(RMSNorm(h))``, then
  ``h += residual_multiplier * SwiGLU(RMSNorm(h))``, the mixer by
  ``layer_types``;
- Mamba2: ``z, xBC, dt`` from the input projection; a causal depthwise
  conv of width ``mamba_d_conv`` with bias over xBC, then SiLU;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the plain
  sequential recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``,
  ``y_t = h_t C_t + D x_t`` per head, B and C shared by the heads of a
  group; gated RMSNorm ``norm(y * silu(z))``; the output projection;
- attention: GQA, causal, no positional encoding, scores scaled by
  ``attention_multiplier``, a plain masked softmax;
- the final RMSNorm at each text's last real token is the feature; the
  SSFN stack on top is ``reference.forward``.

Departures from the published model:

- No language-model head and no ``logits_scaling``: the model is used
  as a feature extractor, so only the pooled hidden state is computed.
- Weights are seeded random draws, not IBM's.  This file draws them
  itself, layer by layer, under the published checkpoint's names and
  ``(out, in)`` layouts, stored in the configuration's weight format and
  widened to float32 where the layer runs (``layer_weights``).  The
  draw is the one ``granite-h-micro:<seed>`` names (the program's
  ``models/granite.py`` documents it); nothing of the program's own
  weights or layout is read, so a weight stored in another format, or
  a layer in another layer's place, shows as a gap.
- Each text runs alone, right-padded with the pad id to the next power
  of two of at least ``MIN_PAD`` tokens, so that a few programs serve
  every length; the model is causal, the recurrence stops at the
  text's end, and only rows before it are read.
- No cache and no decoding: prefill only.

The control computes the same in a lower precision: ``low`` rounds the
SSM state, its decays and dt to its dtype at every step (bfloat16 is the
nearest precision below the float32 the configuration states for them).
Through forty layers of bfloat16 activations the features cannot tell
it from the program, so the first layer's scan is also checked alone,
on inputs the program and the recurrence share (``scan_check``).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: The shortest padded length a text runs at.
MIN_PAD = 256
#: The scale of ``mamba.in_proj.weight``'s dt rows against its other rows.
DT_ROWS_SCALE = 0.1


class Sizes(NamedTuple):
    """The published values the forward reads (HF ``config.json`` names)."""

    hidden: int
    heads: int
    kv_heads: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    groups: int
    conv: int
    eps: float
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    layer_types: tuple[str, ...]
    mlp: int
    vocab: int
    weights: str
    activations: str

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @classmethod
    def from_config(cls, cfg: dict) -> "Sizes":
        return cls(
            hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], ssm_heads=cfg["mamba_n_heads"],
            ssm_head_dim=cfg["mamba_d_head"], d_state=cfg["mamba_d_state"],
            groups=cfg["mamba_n_groups"], conv=cfg["mamba_d_conv"],
            eps=cfg["rms_norm_eps"], attention_multiplier=cfg["attention_multiplier"],
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=cfg["residual_multiplier"],
            layer_types=tuple(cfg["layer_types"]),
            mlp=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
            weights=cfg["formats"]["weights"], activations=cfg["formats"]["activations"],
        )


def _rounder(dtype):
    if dtype is None:
        return lambda a: a
    info = jnp.finfo(dtype)
    return lambda a: jax.lax.reduce_precision(a, exponent_bits=info.nexp,
                                              mantissa_bits=info.nmant)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _drawn(kind: str, sz: Sizes) -> dict[int, tuple[str, tuple[int, ...], float | None]]:
    """A layer's drawn tensors by the index their key folds in: name,
    shape and standard deviation (None for ``mamba.dt_bias``, see
    ``layer_weights``); the MLP's at 5 and 6 whatever the mixer."""
    d, di, h, hd = sz.hidden, sz.d_inner, sz.ssm_heads, sz.hidden // sz.heads
    xbc = di + 2 * sz.groups * sz.d_state
    mixer = {
        "mamba": [("mamba.in_proj.weight", (di + xbc + h, d), d ** -0.5),
                  ("mamba.conv1d.weight", (xbc, 1, sz.conv), 0.5),
                  ("mamba.conv1d.bias", (xbc,), 0.1),
                  ("mamba.out_proj.weight", (d, di), di ** -0.5),
                  ("mamba.dt_bias", (h,), None)],
        "attention": [("self_attn.q_proj.weight", (sz.heads * hd, d), d ** -0.5),
                      ("self_attn.k_proj.weight", (sz.kv_heads * hd, d), d ** -0.5),
                      ("self_attn.v_proj.weight", (sz.kv_heads * hd, d), d ** -0.5),
                      ("self_attn.o_proj.weight", (d, sz.heads * hd), (sz.heads * hd) ** -0.5)],
    }[kind]
    mlp = [("shared_mlp.input_linear.weight", (2 * sz.mlp, d), d ** -0.5),
           ("shared_mlp.output_linear.weight", (d, sz.mlp), sz.mlp ** -0.5)]
    return dict(enumerate(mixer)) | {5 + i: t for i, t in enumerate(mlp)}


def _stored(a, sz: Sizes):
    """As the configuration stores a weight, widened to float32."""
    return a.astype(jnp.dtype(sz.weights)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("kind", "sz"))
def layer_weights(seed, i, *, kind: str, sz: Sizes) -> dict:
    """Layer ``i``'s weights by their published names.  Tensor ``t`` is
    drawn from ``fold_in(fold_in(PRNGKey(seed), i), t)``: normal in
    float32 times its standard deviation (the last ``ssm_heads`` rows of
    ``mamba.in_proj.weight``, dt's, times ``DT_ROWS_SCALE`` besides);
    ``mamba.dt_bias`` is the inverse softplus of a dt log-uniform over
    [1e-3, 1e-1].  Norm weights and
    ``mamba.D`` are ones, ``mamba.A_log`` is log(1..heads)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
    w = {}
    for t, (name, shape, std) in _drawn(kind, sz).items():
        k = jax.random.fold_in(key, t)
        if std is None:
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            w[name] = _stored(dt + jnp.log(-jnp.expm1(-dt)), sz)
        else:
            drawn = jax.random.normal(k, shape, jnp.float32) * std
            if name == "mamba.in_proj.weight":
                drawn = drawn.at[-sz.ssm_heads:].multiply(DT_ROWS_SCALE)
            w[name] = _stored(drawn, sz)
    ones = jnp.ones((sz.hidden,), jnp.float32)
    w["input_layernorm.weight"] = w["post_attention_layernorm.weight"] = ones
    if kind == "mamba":
        w["mamba.A_log"] = _stored(jnp.log(jnp.arange(1, sz.ssm_heads + 1, dtype=jnp.float32)), sz)
        w["mamba.D"] = jnp.ones((sz.ssm_heads,), jnp.float32)
        w["mamba.norm.weight"] = jnp.ones((sz.d_inner,), jnp.float32)
    return w


@partial(jax.jit, static_argnames=("sz",))
def embed_table(seed, *, sz: Sizes):
    """``model.embed_tokens.weight``: normal times 0.02, from
    ``fold_in(PRNGKey(seed), num_layers)``, as stored."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), len(sz.layer_types))
    table = jax.random.normal(key, (sz.vocab, sz.hidden), jnp.float32) * 0.02
    return table.astype(jnp.dtype(sz.weights))


def _ssd_inputs(w, h, sz: Sizes):
    """A Mamba2 layer's gate z and its scan's inputs x, dt, A, B, C, from
    the residual stream h (S, d): z, x (S, d_inner), dt (S, H), A (H,),
    B and C (S, G*ds)."""
    s = h.shape[0]
    ds, g, di = sz.d_state, sz.groups, sz.d_inner
    x = _rms(h, w["input_layernorm.weight"], sz.eps)
    proj = x @ w["mamba.in_proj.weight"].T
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * g * ds], axis=-1)
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])
    # Causal depthwise conv: out_t = sum_j w_j * in_{t - (K-1) + j} + b.
    cw = w["mamba.conv1d.weight"][:, 0, :]                               # (C, K)
    padded = jnp.concatenate([jnp.zeros((sz.conv - 1, xbc.shape[1])), xbc])
    conv = sum(cw[:, j] * padded[j:j + s] for j in range(sz.conv))
    xbc = _silu(conv + w["mamba.conv1d.bias"])
    return (z, xbc[:, :di], dt, -jnp.exp(w["mamba.A_log"]),
            xbc[:, di:di + g * ds], xbc[:, di + g * ds:])


def _recurrence(xs, dt, a, bm, cm, n, sz: Sizes, low=None):
    """The sequential recurrence over the first ``n`` rows, per head, B and
    C shared by the heads of a group: ``y`` (S, d_inner) before the D skip,
    zero past ``n``.  ``low`` rounds dt, each decay and the state."""
    r = _rounder(low)
    s = xs.shape[0]
    nh, dh, ds, g = sz.ssm_heads, sz.ssm_head_dim, sz.d_state, sz.groups
    xs = xs.reshape(s, nh, dh)
    group = np.arange(nh) // (nh // g)
    bm = bm.reshape(s, g, ds)[:, group]                                 # (S, H, ds)
    cm = cm.reshape(s, g, ds)[:, group]
    dt = r(dt)

    def step(t, carry):
        state, ys = carry
        decay = r(jnp.exp(dt[t] * a))                                    # (H,)
        state = r(decay[:, None, None] * state
                  + (dt[t][:, None] * xs[t])[:, :, None] * bm[t][:, None, :])
        return state, ys.at[t].set(jnp.einsum("hdn,hn->hd", state, cm[t]))

    _, y = jax.lax.fori_loop(0, n, step, (jnp.zeros((nh, dh, ds)), jnp.zeros((s, nh, dh))))
    return y.reshape(s, nh * dh)


@partial(jax.jit, static_argnames=("sz", "low"))
def mamba_layer(w, h, n, *, sz: Sizes, low=None):
    """One Mamba2 layer and its residual on one text. h: (S, d); the
    recurrence runs over the first ``n`` rows."""
    with jax.default_matmul_precision("highest"):
        z, xs, dt, a, bm, cm = _ssd_inputs(w, h, sz)
        y = _recurrence(xs, dt, a, bm, cm, n, sz, low)
        y = y + jnp.repeat(w["mamba.D"], sz.ssm_head_dim) * xs
        y = _rms(y * _silu(z), w["mamba.norm.weight"], sz.eps)
        return h + sz.residual_multiplier * (y @ w["mamba.out_proj.weight"].T)


@partial(jax.jit, static_argnames=("sz", "activations"))
def scan_check(w, h, n, *, sz: Sizes, activations: str):
    """The first layer's scan inputs as the program holds them (x, B and C
    rounded to the ``activations`` format; dt and A float32), and on them
    the recurrence's output in float32 and with its state, decays and dt
    in bfloat16 (the control)."""
    with jax.default_matmul_precision("highest"):
        _, xs, dt, a, bm, cm = _ssd_inputs(w, h, sz)
        act = jnp.dtype(activations)
        xs, bm, cm = (v.astype(act) for v in (xs, bm, cm))
        wide = [v.astype(jnp.float32) for v in (xs, bm, cm)]
        y = _recurrence(wide[0], dt, a, wide[1], wide[2], n, sz)
        y_low = _recurrence(wide[0], dt, a, wide[1], wide[2], n, sz, jnp.bfloat16)
        return (xs, dt, a, bm, cm), y, y_low


@partial(jax.jit, static_argnames=("sz",))
def attention_layer(w, h, *, sz: Sizes):
    """One attention layer and its residual on one text. h: (S, d)."""
    with jax.default_matmul_precision("highest"):
        s = h.shape[0]
        hd = sz.hidden // sz.heads
        x = _rms(h, w["input_layernorm.weight"], sz.eps)
        q = (x @ w["self_attn.q_proj.weight"].T).reshape(s, sz.heads, hd)
        kk = (x @ w["self_attn.k_proj.weight"].T).reshape(s, sz.kv_heads, hd)
        v = (x @ w["self_attn.v_proj.weight"].T).reshape(s, sz.kv_heads, hd)
        per = sz.heads // sz.kv_heads
        causal = np.tril(np.ones((s, s), bool))

        def group(args):
            # The query heads that share one key/value head, in turn, so
            # that an 8k text's scores fit beside the weights.
            qg, kg, vg = args                       # (S, per, hd), (S, hd), (S, hd)
            scores = jnp.einsum("qhd,kd->hqk", qg, kg) * sz.attention_multiplier
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,kd->qhd", probs, vg)

        qg = q.reshape(s, sz.kv_heads, per, hd).transpose(1, 0, 2, 3)
        out = jax.lax.map(group, (qg, kk.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        out = out.transpose(1, 0, 2, 3).reshape(s, sz.heads * hd)
        return h + sz.residual_multiplier * (out @ w["self_attn.o_proj.weight"].T)


@partial(jax.jit, static_argnames=("sz",))
def mlp_layer(w, h, *, sz: Sizes):
    """One SwiGLU MLP and its residual on one text. h: (S, d)."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h, w["post_attention_layernorm.weight"], sz.eps)
        gate, up = jnp.split(x @ w["shared_mlp.input_linear.weight"].T, 2, axis=-1)
        return h + sz.residual_multiplier * ((_silu(gate) * up)
                                             @ w["shared_mlp.output_linear.weight"].T)


@partial(jax.jit, static_argnames=("sz",))
def embed(table, ids, *, sz: Sizes):
    return sz.embedding_multiplier * table[ids].astype(jnp.float32)


def padded_length(n: int) -> int:
    return max(MIN_PAD, 1 << (int(n) - 1).bit_length())


def _head_gap(got, want, heads: int) -> float:
    """The largest ``||y_h - y_ref_h|| / ||y_ref_h||`` over the heads h:
    each head's own scale, so that the slow heads, whose state holds the
    longest memory and whose outputs are the smallest, count as much as
    the fast ones."""
    got = np.asarray(got, np.float64).reshape(got.shape[0], heads, -1)
    want = np.asarray(want, np.float64).reshape(want.shape[0], heads, -1)
    num = np.linalg.norm(got - want, axis=(0, 2))
    return float(np.max(num / np.linalg.norm(want, axis=(0, 2))))


def features(seed: int, texts, sz: Sizes, pad_id: int, *, low=None, scan=None) -> dict:
    """Pooled features of each text (a list of 1-D real-token arrays), each
    (d, J) float32 numpy: ``last``, the final-normed hidden state at its
    last real token, and ``before``, one token early (the planted fault's
    feature).  Layer by layer over all texts, so that each layer's
    weights are drawn once.

    With ``scan`` (the program's state-space scan, ``scan(x, dt, A, B, C)``
    on one text's inputs, returning y before the D skip), the first
    Mamba2 layer's scan inputs of each text go through it and through the
    recurrence (``scan_check``): ``ssd_gap`` is the largest
    ``||y_h - y_ref_h|| / ||y_ref_h||`` over the texts and heads h, on
    the texts' real rows, and ``ssd_gap_control`` the same of the
    bfloat16-state recurrence."""
    table = embed_table(seed, sz=sz)
    lengths = [len(t) for t in texts]
    hs = []
    for ids in texts:
        tokens = np.full((padded_length(len(ids)),), pad_id, np.int32)
        tokens[:len(ids)] = ids
        hs.append(embed(table, jnp.asarray(tokens), sz=sz))
    del table
    out = {}
    for i, kind in enumerate(sz.layer_types):
        w = layer_weights(seed, i, kind=kind, sz=sz)
        if scan is not None and kind == "mamba" and "ssd_gap" not in out:
            gaps, control = [], []
            for h, n in zip(hs, lengths):
                inputs, y, y_low = scan_check(w, h, n, sz=sz, activations=sz.activations)
                want = np.asarray(y)[:n]
                got = np.asarray(scan(*inputs), np.float32)[:n]
                gaps.append(_head_gap(got, want, sz.ssm_heads))
                control.append(_head_gap(np.asarray(y_low)[:n], want, sz.ssm_heads))
            out["ssd_gap"], out["ssd_gap_control"] = max(gaps), max(control)
        for t, n in enumerate(lengths):
            if kind == "mamba":
                hs[t] = mamba_layer(w, hs[t], n, sz=sz, low=low)
            else:
                hs[t] = attention_layer(w, hs[t], sz=sz)
            hs[t] = mlp_layer(w, hs[t], sz=sz)
    # ``model.norm`` (its weight is ones) at the last two real rows.
    rows = [np.asarray(_rms(h[max(n - 2, 0):n], 1.0, sz.eps)) for h, n in zip(hs, lengths)]
    out["last"] = np.stack([r[-1] for r in rows], axis=1)
    out["before"] = np.stack([r[0] for r in rows], axis=1)
    return out
