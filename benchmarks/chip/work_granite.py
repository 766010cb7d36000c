"""Operations and bytes a Granite-4.0-H request needs, counted from its
shapes, as ``work.py`` counts a train's: the work the algorithm
requires, not what an implementation happens to do.

Per text of ``n`` tokens:

- every projection and MLP matmul: ``2 n`` times its weights (the
  embedding is a lookup; the language-model head is never run);
- the Mamba2 conv: ``2 K (d_inner + 2 G d_state) n``;
- the state-space recurrence, per token and layer: the state's decay,
  the ``dt x B^T`` update and ``C h`` with ``D x``, ``5 d_inner d_state``
  (:func:`ssd_work`);
- attention, per layer: ``Q K^T`` and ``P V`` over the causal half,
  ``2 * 2 * heads * head_dim * n (n + 1) / 2``;
- the SSFN stack on the pooled feature: ``2 n_out n_in`` per layer.

Bytes of the scan are its inputs read once and its output written once
at the widths the configuration states: x, B, C and y in bfloat16, dt
in float32; its state never leaves the chip.
"""
from __future__ import annotations

from typing import NamedTuple

from benchmarks.chip.work import Work

BF16, F32 = 2, 4


class Sizes(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    groups: int
    conv: int
    ffn: int
    layer_types: tuple[str, ...]
    num_classes: int
    stack_hidden: int
    stack_layers: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Sizes":
        return cls(
            hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
            ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
            d_state=cfg["mamba_d_state"], groups=cfg["mamba_n_groups"],
            conv=cfg["mamba_d_conv"], ffn=cfg["shared_intermediate_size"],
            layer_types=tuple(cfg["layer_types"]), num_classes=cfg["num_classes"],
            stack_hidden=cfg["stack_hidden"], stack_layers=cfg["stack_layers"],
        )

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def mamba_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def attention_layers(self) -> int:
        return self.layer_types.count("attention")


def matmul_weights(s: Sizes) -> int:
    """Weights every token multiplies: projections and MLPs of all layers."""
    d, di, gds = s.hidden, s.d_inner, s.groups * s.d_state
    mamba = d * (2 * di + 2 * gds + s.ssm_heads) + di * d
    attn = 2 * d * s.heads * s.head_dim + 2 * d * s.kv_heads * s.head_dim
    mlp = 3 * d * s.ffn
    return s.mamba_layers * mamba + s.attention_layers * attn + len(s.layer_types) * mlp


def ssd_work(s: Sizes, tokens: int) -> Work:
    """The recurrence of every Mamba2 layer over ``tokens`` positions."""
    per_layer_flops = 5 * s.d_inner * s.d_state
    per_layer_bytes = (BF16 * (2 * s.d_inner + 2 * s.groups * s.d_state)
                       + F32 * s.ssm_heads)
    layers = s.mamba_layers * tokens
    return Work(float(per_layer_flops * layers), float(per_layer_bytes * layers))


def stack_flops(s: Sizes) -> float:
    """The SSFN stack's propagation and readout for one pooled feature."""
    n = s.stack_hidden
    return float(2 * (n * s.hidden + (s.stack_layers - 1) * n * n + s.num_classes * n))


def text_flops(s: Sizes, n: int) -> float:
    """All the operations one text of ``n`` tokens needs, backbone and stack."""
    conv = 2 * s.conv * (s.d_inner + 2 * s.groups * s.d_state) * n * s.mamba_layers
    attn = 2 * 2 * s.heads * s.head_dim * (n * (n + 1) // 2) * s.attention_layers
    return (2.0 * n * matmul_weights(s) + conv + attn + ssd_work(s, n).flops
            + stack_flops(s))
