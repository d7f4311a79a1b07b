"""Causal attention with GQA and a static-shape KV cache.

Replaces the reference's per-layer HF `LlamaAttention` calls, which it runs
with `attention_mask=None, past_key_value=None, use_cache=False`
(/root/reference/Worker1.py:125-154) — i.e. full-sequence recompute per
decoded token. Here the KV cache is a static-shape HBM buffer written with
`lax.dynamic_update_slice`, so one compiled program covers both prefill
(chunk of length T at offset 0) and decode (T=1 at offset `pos`), and the
decode cost per token is O(seq) attention instead of O(seq²) recompute.

Shapes (B=batch, T=chunk len, S=max_seq, H=q heads, KV=kv heads, Dh=head_dim):
  q          [B, T, H, Dh]
  k_new/v_new[B, T, KV, Dh]
  cache_k/v  [B, KV, S, Dh]

The cache keeps the head axis OUTSIDE the sequence axis so each head's
[S, Dh] slab is contiguous — dense per-head reads for the Pallas flash
kernel (whose BlockSpec tiles the trailing [S, Dh] dims; Pallas TPU
requires the last two block dims be full-size or (8,128)-aligned) and for
XLA's attention matmuls alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def update_kv_cache(
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    pos: jnp.ndarray,
    gate=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write the new K/V chunk at offset `pos` (scalar int32). Static shapes.

    Caller contract: pos + T must be <= max_seq. `dynamic_update_slice`
    CLAMPS out-of-range starts instead of erroring, which would silently
    misplace K/V relative to `causal_mask`'s absolute positions — the decode
    engine enforces the bound (engine/generate.py caps max_new_tokens by the
    cache capacity) so this never triggers in serving.

    gate: optional traced bool — when False the write is a no-op. Used by
    the pipeline runtime, where a stage executes speculatively on
    microsteps when it holds no valid microbatch. Gating selects over the
    written SLICE only (read-modify-write of [B,KV,T,Dh]), not the whole
    cache — a whole-cache `where` would copy max_seq slots per layer per
    microstep.
    """
    zero = jnp.int32(0)
    # [B, T, KV, Dh] chunk -> [B, KV, T, Dh] to match the cache layout.
    k_new = k_new.transpose(0, 2, 1, 3)
    v_new = v_new.transpose(0, 2, 1, 3)
    start = (zero, zero, pos, zero)
    if gate is not None:
        old_k = jax.lax.dynamic_slice(cache_k, start, k_new.shape)
        old_v = jax.lax.dynamic_slice(cache_v, start, v_new.shape)
        k_new = jnp.where(gate, k_new, old_k)
        v_new = jnp.where(gate, v_new, old_v)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k_new, start)
    cache_v = jax.lax.dynamic_update_slice(cache_v, v_new, start)
    return cache_k, cache_v


def block_frontier(q_pos, block: int = 0):
    """The last position a query at q_pos attends. block 0: itself
    (causal). block B > 0 (block diffusion, cfg.diffusion_block): the last
    position of its own block of B at absolute positions, so position t
    sees s iff s < (t // B + 1) * B. One rule for every mask below, the
    paged kernels (ops/paged_attention._walk_kernel) and engine/paged's
    XLA twins."""
    return q_pos if not block else (q_pos // block + 1) * block - 1


def causal_mask(
    pos: jnp.ndarray, chunk_len: int, max_seq: int, window=None,
    block: int = 0,
) -> jnp.ndarray:
    """[T, S] boolean mask: query at absolute position pos+t may attend to
    cache slots 0..pos+t inclusive (earlier prompt + itself), or up to its
    block's end (`block_frontier`). With `window` (sliding-window
    attention, Mistral-style) only the last `window` positions qualify:
    q_pos - window < kv_pos <= q_pos."""
    q_pos = pos + jnp.arange(chunk_len, dtype=jnp.int32)  # [T]
    kv_pos = jnp.arange(max_seq, dtype=jnp.int32)  # [S]
    mask = kv_pos[None, :] <= block_frontier(q_pos, block)[:, None]
    if window is not None:
        mask &= kv_pos[None, :] > q_pos[:, None] - window
    return mask


def slot_causal_mask(
    pos: jnp.ndarray, chunk_len: int, max_seq: int, window=None,
    block: int = 0,
) -> jnp.ndarray:
    """[B, T, S] mask for PER-ROW query offsets (continuous batching).

    Each slot row b decodes at its own absolute position pos[b]+t — slots
    admitted at different times have different lengths, so there is no
    shared position frame to left-pad into. Row b's query at pos[b]+t may
    attend cache slots 0..pos[b]+t; stale K/V beyond a slot's position
    (from a longer previous tenant) sits strictly above it and is never
    attended before decode overwrites it — the same argument as padded
    prefill.
    """
    q_pos = pos[:, None] + jnp.arange(chunk_len, dtype=jnp.int32)[None, :]  # [B, T]
    kv_pos = jnp.arange(max_seq, dtype=jnp.int32)  # [S]
    mask = kv_pos[None, None, :] <= block_frontier(q_pos, block)[:, :, None]
    if window is not None:
        mask &= kv_pos[None, None, :] > q_pos[:, :, None] - window
    return mask


def update_kv_cache_slots(
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    pos: jnp.ndarray,
    gate=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row cache write at per-row offsets pos [B] (continuous batching:
    every slot is at its own sequence position). vmapped
    `dynamic_update_slice` over the batch axis — same clamp caveat as
    `update_kv_cache`, enforced per slot by the continuous engine.

    gate: optional traced bool (shared across rows) — when False the write
    is a no-op, selected over the written slices only. The pipeline slots
    program needs it: stages execute speculatively on microsteps where
    they don't own the fleet's buffer."""
    k_new = k_new.transpose(0, 2, 1, 3)  # [B, KV, T, Dh]
    v_new = v_new.transpose(0, 2, 1, 3)

    def row(ck, kn, p):
        if gate is not None:
            old = jax.lax.dynamic_slice(ck, (jnp.int32(0), p, jnp.int32(0)), kn.shape)
            kn = jnp.where(gate, kn, old)
        return jax.lax.dynamic_update_slice(ck, kn, (jnp.int32(0), p, jnp.int32(0)))

    cache_k = jax.vmap(row)(cache_k, k_new, pos)
    cache_v = jax.vmap(row)(cache_v, v_new, pos)
    return cache_k, cache_v


def ragged_causal_mask(
    pos: jnp.ndarray, chunk_len: int, max_seq: int, valid_start: jnp.ndarray,
    window=None, block: int = 0,
) -> jnp.ndarray:
    """[B, T, S] mask for LEFT-padded batches: causal AND slot >= the row's
    first real slot. Left-padding aligns ragged prompts to one shared
    position frame (RoPE is relative, so a per-row uniform shift is
    harmless); the pad slots in front must simply never be attended."""
    causal = causal_mask(pos, chunk_len, max_seq, window, block)  # [T, S]
    kv_pos = jnp.arange(max_seq, dtype=jnp.int32)
    valid = kv_pos[None, None, :] >= valid_start[:, None, None]  # [B, 1, S]
    return causal[None, :, :] & valid


def attend(
    q: jnp.ndarray,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    mask: jnp.ndarray,
    scale=None,
    softcap=None,
    sink=None,
) -> jnp.ndarray:
    """Grouped-query attention over the (already updated) cache.

    mask: [T, S] (shared) or [B, T, S] (per-row, ragged left-padded batch).
    Softmax in fp32; output cast back to q.dtype. Returns [B, T, H, Dv]:
    the values may be narrower than the keys (cache_v [B, KV, S, Dv]).
    sink: [H] float32, a learned logit a query head that joins the
    softmax's denominator and brings no value: p_ij = exp(s_ij) / (sum_j'
    exp(s_ij') + exp(sink_h)) (None: the plain softmax).
    scale: score scale (None = head_dim**-0.5; Gemma-2 overrides).
    softcap: Gemma-2 attention logit softcapping, cap*tanh(scores/cap),
    applied BEFORE masking (HF Gemma2Attention order).
    """
    B, T, H, Dh = q.shape
    KV = cache_k.shape[1]
    group = H // KV
    # [B, T, KV, group, Dh] so each kv head serves its query group without
    # materializing repeated K/V (XLA keeps this as a batched matmul).
    qg = q.reshape(B, T, KV, group, Dh)
    if scale is None:
        scale = Dh ** -0.5
    scores = jnp.einsum(
        "btkgd,bksd->bkgts", qg.astype(jnp.float32), cache_k.astype(jnp.float32)
    ) * scale  # [B, KV, group, T, S]
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    neg = jnp.finfo(jnp.float32).min
    bmask = mask[:, None, None, :, :] if mask.ndim == 3 else mask[None, None, None, :, :]
    scores = jnp.where(bmask, scores, neg)
    if sink is not None:  # one more column, dropped after the softmax
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, KV, group, 1, 1),
            scores.shape[:-1] + (1,))
        probs = jax.nn.softmax(
            jnp.concatenate([scores, col], axis=-1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bksd->btkgd", probs, cache_v.astype(jnp.float32))
    return out.reshape(B, T, H, -1).astype(q.dtype)
