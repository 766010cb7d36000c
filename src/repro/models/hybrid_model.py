"""Zamba2-style hybrid: Mamba2 backbone with a single *shared* attention
block (weight-tied) invoked every ``shared_attn_period`` layers
(arXiv:2411.15242)."""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import blocks
from repro.models.config import ModelConfig
from repro.nn import attention as attn_lib
from repro.nn import ssm as ssm_lib
from repro.nn.layers import dense_init, embed_init, embed_lookup, rms_norm
from repro.sharding.rules import shard, shard_params_by_name

Array = jax.Array
Params = dict[str, Any]


class HybridCache(NamedTuple):
    ssm: ssm_lib.SSMState          # leading dims (P, per_period)
    attn: attn_lib.KVCache         # leading dim (P,) — one per shared-attn call


class HybridModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        period = cfg.shared_attn_period
        assert period and cfg.num_layers % period == 0
        self.num_periods = cfg.num_layers // period
        self.per_period = period

    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        v, d = cfg.padded_vocab, cfg.d_model
        k_embed, k_m, k_a, k_head = jax.random.split(key, 4)
        m_keys = jax.random.split(k_m, cfg.num_layers)
        mamba = jax.vmap(lambda k: blocks.init_mamba_layer(k, cfg))(m_keys)
        mamba = jax.tree.map(
            lambda a: a.reshape((self.num_periods, self.per_period) + a.shape[1:]),
            mamba,
        )
        return {
            "embed": embed_init(k_embed, v, d, cfg.jnp_dtype),
            "mamba": mamba,
            "shared_attn": blocks.init_transformer_layer(k_a, cfg),  # ONE copy
            "ln_f": jnp.ones((d,), cfg.jnp_dtype),
            "head": dense_init(k_head, (d, v), cfg.jnp_dtype),
        }

    def _run(self, params: Params, x: Array, cache: HybridCache | None, positions):
        cfg = self.cfg
        stateful = cache is not None
        shared = params["shared_attn"]

        def inner(x, inp):
            mp, st = inp
            mp = shard_params_by_name(mp)
            x, st_new = blocks.apply_mamba_layer(mp, x, cfg, st if stateful else None)
            return x, st_new if stateful else st

        def period_body(x, inp):
            mp, m_st, a_st = inp
            x, m_new = jax.lax.scan(inner, x, (mp, m_st))
            x, a_new, _ = blocks.apply_transformer_layer(
                shared, x, positions, cfg, a_st if stateful else None
            )
            return x, (m_new, a_new if stateful else a_st)

        if cfg.remat and not stateful:
            period_body = jax.checkpoint(period_body)

        if not stateful:
            cache = self.init_cache(x.shape[0], 1)
        xs = (params["mamba"], cache.ssm, cache.attn)
        x, (m_new, a_new) = jax.lax.scan(period_body, x, xs)
        new_cache = HybridCache(ssm=m_new, attn=a_new) if stateful else None
        return x, new_cache

    def _logits(self, params: Params, x: Array) -> Array:
        logits = rms_norm(x, params["ln_f"]) @ params["head"]
        return shard(logits, "batch", None, "tensor")

    def forward(self, params: Params, batch: dict):
        x = shard(embed_lookup(params["embed"], batch["tokens"]), "batch", None, None)
        positions = jnp.arange(x.shape[1])
        x, _ = self._run(params, x, None, positions)
        return self._logits(params, x), jnp.zeros((), jnp.float32)

    def init_cache(self, batch_size: int, max_len: int) -> HybridCache:
        cfg = self.cfg
        di = cfg.d_inner_eff
        dh = di // cfg.ssm_heads
        ssm_one = ssm_lib.SSMState(
            h=jnp.zeros((batch_size, cfg.ssm_heads, dh, cfg.ssm_state), jnp.float32),
            conv=jnp.zeros((batch_size, cfg.conv_kernel - 1,
                            di + 2 * cfg.ssm_groups * cfg.ssm_state), cfg.jnp_dtype),
        )
        slots = min(max(max_len, 1), cfg.window) if cfg.attention == "swa" else max(max_len, 1)
        attn_one = attn_lib.init_kv_cache(
            batch_size, slots, cfg.num_kv_heads, cfg.hd, cfg.jnp_dtype
        )
        pm = (self.num_periods, self.per_period)
        return HybridCache(
            ssm=jax.tree.map(lambda a: jnp.broadcast_to(a, pm + a.shape), ssm_one),
            attn=jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.num_periods,) + a.shape), attn_one
            ),
        )

    def prefill(self, params: Params, batch: dict, max_len: int | None = None):
        # Prefill with state: run the stateful path over the full sequence
        # (caches sized to the sequence/window).
        x = shard(embed_lookup(params["embed"], batch["tokens"]), "batch", None, None)
        s = x.shape[1]
        cache = self.init_cache(x.shape[0], s)
        positions = jnp.arange(s)
        x, cache = self._run_prefill(params, x, cache, positions, max_len)
        return self._logits(params, x[:, -1:]), cache

    def _run_prefill(self, params, x, cache: HybridCache, positions, max_len=None):
        """Stateful full-sequence pass: SSM states carried, attention KV
        collected into the decode cache."""
        cfg = self.cfg
        shared = params["shared_attn"]
        from repro.models.transformer import _attention_collect_kv, _kv_to_cache

        def inner(x, inp):
            mp, st = inp
            mp = shard_params_by_name(mp)
            x, st_new = blocks.apply_mamba_layer(mp, x, cfg, st)
            return x, st_new

        def period_body(x, inp):
            mp, m_st = inp
            x, m_new = jax.lax.scan(inner, x, (mp, m_st))
            window = cfg.window if cfg.attention == "swa" else None
            h, kv = _attention_collect_kv(shared, x, positions, cfg, window)
            x = x + h
            f, _ = blocks.apply_ffn(shared["ffn"], rms_norm(x, shared["ln2"]), cfg)
            x = x + f
            return shard(x, "batch", None, None), (m_new, kv)

        xs = (params["mamba"], cache.ssm)
        x, (m_new, kv_stack) = jax.lax.scan(period_body, x, xs)
        attn_cache = _kv_to_cache(kv_stack, positions.shape[0], cfg, max_len=max_len)
        # num_layers in _kv_to_cache indexes the stack dim; fix index length.
        attn_cache = attn_cache._replace(
            index=jnp.full((self.num_periods,), positions.shape[0], jnp.int32)
        )
        return x, HybridCache(ssm=m_new, attn=attn_cache)

    def decode_step(self, params: Params, batch: dict, cache: HybridCache):
        x = shard(embed_lookup(params["embed"], batch["tokens"]), "batch", None, None)
        positions = cache.attn.index[:1]
        x, cache = self._run(params, x, cache, positions)
        return self._logits(params, x), cache
