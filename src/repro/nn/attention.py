"""GQA attention: chunked-flash causal attention (training/prefill) and
single-token decode against a (optionally ring-buffered sliding-window)
KV cache.

The chunked path is the pure-JAX analogue of the ``flash_attention``
Pallas kernel (repro/kernels/flash_attention): an online-softmax scan over
KV chunks, O(S * chunk) score memory instead of O(S^2).  On the dry-run
mesh, batch shards over the data axes and heads over the model axis; the
sequence dim stays local.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array
NEG_INF = -1e30


def repeat_kv(kv: Array, num_heads: int) -> Array:
    """(B, S, KVH, hd) -> (B, S, H, hd) by repeating each KV head H/KVH times."""
    kvh = kv.shape[2]
    if kvh == num_heads:
        return kv
    reps = num_heads // kvh
    return jnp.repeat(kv, reps, axis=2)


def chunked_causal_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    chunk_size: int = 1024,
    window: int | None = None,
    q_offset: int = 0,
    scale: float | None = None,
) -> Array:
    """Causal (optionally sliding-window) attention via online softmax.

    q: (B, Sq, H, hd); k, v: (B, Sk, H, hd) — KV already repeated to H.
    q_offset: absolute position of q[0] relative to k[0] (prefill: 0).
    window: sliding-window size (attend to keys with 0 <= pq - pk < window).
    scale: the scores' multiplier (default 1/sqrt(hd)).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = q.astype(jnp.float32) * scale

    num_chunks = -(-sk // chunk_size)
    pad = num_chunks * chunk_size - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, num_chunks, chunk_size, h, hd)
    vc = v.reshape(b, num_chunks, chunk_size, h, hd)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, inputs):
        m, l, acc = carry
        kj, vj, j = inputs
        k_pos = j * chunk_size + jnp.arange(chunk_size)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj.astype(jnp.float32))
        causal = q_pos[:, None] >= k_pos[None, :]
        valid = k_pos[None, :] < sk
        mask = causal & valid
        if window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vj.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), jnp.arange(num_chunks))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)   # (B, Sq, H, hd)


class KVCache(NamedTuple):
    """Decode-time KV cache.

    k, v: (B, S_slots, KVH, hd) where S_slots = min(seq_len, window) for
    sliding-window archs (ring buffer) or seq_len for full attention.
    index: () int32 — number of tokens written so far (absolute position).
    """
    k: Array
    v: Array
    index: Array

    @property
    def slots(self) -> int:
        return self.k.shape[1]


def init_kv_cache(
    batch: int, slots: int, kv_heads: int, head_dim: int, dtype
) -> KVCache:
    shape = (batch, slots, kv_heads, head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype), index=jnp.zeros((), jnp.int32)
    )


def cache_update(cache: KVCache, k_new: Array, v_new: Array) -> KVCache:
    """Write one token (B, 1, KVH, hd) at position index (ring for SWA)."""
    slot = jnp.mod(cache.index, cache.slots)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, slot, axis=1)
    return KVCache(k=k, v=v, index=cache.index + 1)


def decode_attention(
    q: Array,
    cache: KVCache,
    *,
    num_heads: int,
    window: int | None = None,
) -> Array:
    """One-token attention: q (B, 1, H, hd) against the cache.

    Keys are stored post-RoPE, so softmax is order-independent and the ring
    layout needs no unrotation; masking keeps only written (and in-window)
    slots.  cache.index is the count *after* the current token was written.
    """
    b, _, h, hd = q.shape
    k = repeat_kv(cache.k, num_heads)
    v = repeat_kv(cache.v, num_heads)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32))
    slots = cache.slots
    slot_ids = jnp.arange(slots)
    written = slot_ids < jnp.minimum(cache.index, slots)
    if window is not None:
        # Absolute position stored in each ring slot.
        wraps = (cache.index - 1 - slot_ids) // slots + 1
        abs_pos = slot_ids + jnp.maximum(wraps, 0) * slots
        abs_pos = jnp.where(slot_ids < jnp.mod(cache.index, slots) , abs_pos, abs_pos - slots)
        # Simpler exact rule: slot holds position p = largest p < index with
        # p % slots == slot_id.
        last = cache.index - 1
        abs_pos = last - jnp.mod(jnp.mod(last, slots) - slot_ids, slots)
        written &= (last - abs_pos) < window
    s = jnp.where(written[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
