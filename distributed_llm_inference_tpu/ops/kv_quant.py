"""int8 KV-cache quantization (per-token, per-head symmetric scales).

The KV cache is the HBM budget that scales with context and slot count —
at Llama-2-7B/4096 a single bf16 KV row is ~2 GB, and the continuous
fleet multiplies that by n_slots. Storing K/V as int8 with one fp32 scale
per (token, kv-head) halves the cache's HBM footprint (int8 data +
1/head_dim scale overhead), which buys 2x the slots / context at the
same budget; on read the dequantize (int8 -> f32 multiply) fuses into
the attention matmuls the same way the weight-only path's does
(ops/quant.py: the same producer-fusion shape).

Why per-(token, head) granularity: K/V activation outliers are
token-local (a single position can spike), so one scale per token row
keeps the quantization error independent of sequence content elsewhere —
the standard KV-quant recipe (vs per-tensor, which a single outlier
token would poison).

`KVQuant` is a registered pytree whose leaves (q int8, s fp32) flow
through every cache-shaped tree.map in the engine unchanged: slot
splices and beam reorders index the batch axis, which sits at the same
position in both leaves ([L, B, KV, S, Dh] and [L, B, KV, S]). The
dense hook (models/llama.default_attn_hook) dispatches on the leaf type;
everything else — scan-over-layers, donation, while_loop carries —
treats the cache as an opaque pytree.

Scope: llama-family, EVERY topology — single device, the slot fleet
(dense OR block-paged pool), pp/tp/dp/1F1B pipeline meshes, and sp
(the ring/cp hooks quantize on write and dequantize their local slot
sets — parallel/context.py); the prefix snapshot store composes too,
its slices carry the scale leaves. The Pallas flash PREFILL kernel and
the fused paged DECODE kernel both dequantize int8 tiles/blocks in
their prologues (ops/flash_attention.py, ops/paged_attention.py — half
the cache HBM bytes). The reference has no KV cache at all
(/root/reference/Worker1.py:132-134); this is north-star serving scope.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .wire_quant import quantize_rows


@jax.tree_util.register_pytree_node_class
class KVQuant:
    """int8 cache leaf: q [..., S, Dh] int8, s [..., S] fp32 scales."""

    __slots__ = ("q", "s")

    def __init__(self, q, s):
        self.q = q
        self.s = s

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def tree_flatten(self):
        return (self.q, self.s), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"KVQuant(q={self.q.shape}@{self.q.dtype}, s={self.s.shape})"


def init_quant_cache(
    n_layers: int, batch: int, n_kv: int, max_seq: int, head_dim: int
) -> dict:
    """Zeroed int8 cache, same dict shape as the raw one ({"k", "v"})."""
    q = (n_layers, batch, n_kv, max_seq, head_dim)
    s = (n_layers, batch, n_kv, max_seq)
    return {
        "k": KVQuant(jnp.zeros(q, jnp.int8), jnp.zeros(s, jnp.float32)),
        "v": KVQuant(jnp.zeros(q, jnp.int8), jnp.zeros(s, jnp.float32)),
    }


def quantize_chunk(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 over the head_dim axis: x [B, T, KV, Dh] ->
    (q [B, T, KV, Dh] int8, s [B, T, KV] fp32). The symmetric per-row
    primitive is shared with the pp wire format (ops/wire_quant.py), so
    cache and wire quantization cannot drift numerically."""
    return quantize_rows(x)


def dequantize(leaf: KVQuant) -> jnp.ndarray:
    """[..., S, Dh] fp32 view — feeds attention's fp32 softmax path
    directly, so the int8 load + scale multiply is the producer XLA fuses
    into the score/value matmuls."""
    return leaf.q.astype(jnp.float32) * leaf.s[..., None]


def update_cache(
    leaf: KVQuant,
    x_new: jnp.ndarray,
    pos: jnp.ndarray,
    gate: Optional[jnp.ndarray] = None,
) -> KVQuant:
    """Quantize-and-write a chunk at scalar offset `pos` (prefill / shared
    decode). Mirrors ops/attention.update_kv_cache: same transposes, same
    clamp caveat, same gated read-modify-write of the written slice only."""
    zero = jnp.int32(0)
    qn, sn = quantize_chunk(x_new)
    qn = qn.transpose(0, 2, 1, 3)  # [B, KV, T, Dh]
    sn = sn.transpose(0, 2, 1)  # [B, KV, T]
    start_q = (zero, zero, pos, zero)
    start_s = (zero, zero, pos)
    if gate is not None:
        old_q = jax.lax.dynamic_slice(leaf.q, start_q, qn.shape)
        old_s = jax.lax.dynamic_slice(leaf.s, start_s, sn.shape)
        qn = jnp.where(gate, qn, old_q)
        sn = jnp.where(gate, sn, old_s)
    return KVQuant(
        jax.lax.dynamic_update_slice(leaf.q, qn, start_q),
        jax.lax.dynamic_update_slice(leaf.s, sn, start_s),
    )


def update_cache_slots(
    leaf: KVQuant,
    x_new: jnp.ndarray,
    pos: jnp.ndarray,
    gate: Optional[jnp.ndarray] = None,
) -> KVQuant:
    """Per-row quantize-and-write at per-row offsets pos [B] (continuous
    batching). Mirrors ops/attention.update_kv_cache_slots."""
    qn, sn = quantize_chunk(x_new)
    qn = qn.transpose(0, 2, 1, 3)  # [B, KV, T, Dh]
    sn = sn.transpose(0, 2, 1)  # [B, KV, T]

    def row_q(cq, kn, p):
        start = (jnp.int32(0), p, jnp.int32(0))
        if gate is not None:
            old = jax.lax.dynamic_slice(cq, start, kn.shape)
            kn = jnp.where(gate, kn, old)
        return jax.lax.dynamic_update_slice(cq, kn, start)

    def row_s(cs, sn_, p):
        start = (jnp.int32(0), p)
        if gate is not None:
            old = jax.lax.dynamic_slice(cs, start, sn_.shape)
            sn_ = jnp.where(gate, sn_, old)
        return jax.lax.dynamic_update_slice(cs, sn_, start)

    return KVQuant(
        jax.vmap(row_q)(leaf.q, qn, pos),
        jax.vmap(row_s)(leaf.s, sn, pos),
    )
