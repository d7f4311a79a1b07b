"""GPT-2 family decoder in pure JAX (BASELINE configs 1-2).

Same stacked-layer pytree discipline as models/llama.py (scan over layers,
layer axis shardable over the pipeline mesh axis, KV cache threaded through)
with GPT-2 architecture: LayerNorm with bias, learned absolute position
embeddings, fused-qkv MHA with biases, gelu_new MLP, tied LM head.

Params pytree:
  embed      [V, D]      pos_embed [P, D]
  layers:
    ln1_w/ln1_b [L, D]   ln2_w/ln2_b [L, D]
    wq/wk/wv [L, D, D]   bq/bk/bv [L, D]
    wo [L, D, D]         bo [L, D]
    w_fc [L, D, F]  b_fc [L, F]  w_proj [L, F, D]  b_proj [L, D]
  final_norm_w / final_norm_b [D]
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import causal_mask, slot_causal_mask
from ..ops.norms import layer_norm
from ..ops.quant import matmul as mm

Params = dict
KVCache = dict


def gelu_new(x: jnp.ndarray) -> jnp.ndarray:
    """GPT-2's tanh-approximate GELU (HF activation 'gelu_new'), fp32."""
    xf = x.astype(jnp.float32)
    c = jnp.sqrt(2.0 / jnp.pi)
    out = 0.5 * xf * (1.0 + jnp.tanh(c * (xf + 0.044715 * xf ** 3)))
    return out.astype(x.dtype)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = cfg.jnp_dtype
    L, D, F, V, P = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size, cfg.max_seq_len
    ks = jax.random.split(key, 8)

    def normal(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    return {
        "embed": normal(ks[0], (V, D)),
        "pos_embed": normal(ks[1], (P, D), 0.01),
        "layers": {
            "ln1_w": jnp.ones((L, D), dt),
            "ln1_b": jnp.zeros((L, D), dt),
            "ln2_w": jnp.ones((L, D), dt),
            "ln2_b": jnp.zeros((L, D), dt),
            "wq": normal(ks[2], (L, D, D)),
            "wk": normal(ks[3], (L, D, D)),
            "wv": normal(ks[4], (L, D, D)),
            "bq": jnp.zeros((L, D), dt),
            "bk": jnp.zeros((L, D), dt),
            "bv": jnp.zeros((L, D), dt),
            "wo": normal(ks[5], (L, D, D)),
            "bo": jnp.zeros((L, D), dt),
            "w_fc": normal(ks[6], (L, D, F)),
            "b_fc": jnp.zeros((L, F), dt),
            "w_proj": normal(ks[7], (L, F, D)),
            "b_proj": jnp.zeros((L, D), dt),
        },
        "final_norm_w": jnp.ones((D,), dt),
        "final_norm_b": jnp.zeros((D,), dt),
    }


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_seq: Optional[int] = None, n_layers: Optional[int] = None
) -> KVCache:
    # MHA is GQA with n_kv_heads == n_heads (enforced by the GPT-2 configs),
    # so the cache-layout contract lives in one place: llama.init_kv_cache.
    from .llama import init_kv_cache as _llama_init_kv_cache

    return _llama_init_kv_cache(cfg, batch, max_seq=max_seq, n_layers=n_layers)


def decoder_layer(cfg, lp, x, cache_k, cache_v, pos, mask, update_gate=None,
                  tp_axis=None, attn_hook=None, layer=None):
    """One GPT-2 block on chunk x [B,T,D] at offset pos.

    Cache write + attention go through the SHARED hook seam
    (models/llama.default_attn_hook — GPT-2 is MHA, i.e. GQA with
    group=1, no window/softcap/scale override, so the default hook's
    behavior is exactly the old inline path), which is what lets the
    paged pool (engine/paged.make_paged_hook) and the int8 KV cache ride
    GPT-2 the same way they ride llama. Projections go through ops/quant
    `mm` so int8/int4 weight-only quantization applies transparently.

    Tensor parallelism mirrors models/llama.py: head-sliced qkv shards
    (with their per-output-column biases bq/bk/bv sharded alongside),
    row-sharded wo/w_proj partial outputs psummed over `tp_axis`; the
    row-projection biases bo/b_proj are replicated and added once, OUTSIDE
    the psum (inside it they'd be added tp times).
    """
    from .llama import default_attn_hook

    B, T, D = x.shape
    Dh = cfg.head_dim
    H = lp["wq"].shape[-1] // Dh

    h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
    q = (mm(h, lp["wq"]) + lp["bq"]).reshape(B, T, H, Dh)
    k = (mm(h, lp["wk"]) + lp["bk"]).reshape(B, T, H, Dh)
    v = (mm(h, lp["wv"]) + lp["bv"]).reshape(B, T, H, Dh)

    hook = attn_hook or default_attn_hook
    # layer: under a paged hook cache_k/v are the STACKED pool leaves and
    # this is the layer's index in them (llama.forward_layers)
    attn, new_k, new_v = hook(
        cfg, q, k, v, cache_k, cache_v, pos, mask, update_gate, None, None,
        *(() if layer is None else (layer,)),
    )
    attn_out = mm(attn.reshape(B, T, H * Dh), lp["wo"])
    if tp_axis is not None:
        attn_out = jax.lax.psum(attn_out, tp_axis)
    x = x + attn_out + lp["bo"]

    h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
    mlp_out = mm(gelu_new(mm(h, lp["w_fc"]) + lp["b_fc"]), lp["w_proj"])
    if tp_axis is not None:
        mlp_out = jax.lax.psum(mlp_out, tp_axis)
    x = x + mlp_out + lp["b_proj"]
    return x, new_k, new_v


def forward_layers(cfg, layers, x, cache, pos, update_gate=None, tp_axis=None,
                   attn_hook=None, valid_start=None, ep_axis=None,
                   attn_seq_len=None):
    """Scan the stacked GPT-2 blocks over a chunk (any contiguous slice).
    pos: scalar chunk offset, or a per-row [B] vector (continuous-batching
    slots — GPT-2 CAN slot-batch: unlike ragged left-padding, every slot
    starts at position 0, so learned absolute positions stay exact).
    attn_hook: the shared attention/cache seam (paged pool, int8 cache);
    attn_seq_len: paged logical mask length (see llama.forward_layers).
    valid_start/ep_axis reject loudly: learned absolute positions are not
    shift-invariant (no ragged left-padding), and GPT-2 has no MoE."""
    if valid_start is not None:
        raise NotImplementedError(
            "gpt2 does not support ragged (valid_start) batches: learned "
            "absolute position embeddings are not shift-invariant"
        )
    if ep_axis is not None:
        raise NotImplementedError("gpt2 has no MoE layers (ep_axis)")
    T = x.shape[1]
    S = attn_seq_len if attn_seq_len is not None else cache["k"].shape[3]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        mask = slot_causal_mask(pos, T, S)
    else:
        mask = causal_mask(pos, T, S)

    from .llama import scan_layers

    # a paged hook's pool rides the scan as a carry (llama.forward_layers)
    paged = getattr(attn_hook, "paged", False)

    def layer_step(xc, lp, kv, layer):
        xc, ck, cv = decoder_layer(cfg, lp, xc, *kv, pos, mask, update_gate,
                                   tp_axis, attn_hook,
                                   layer if paged else None)
        return xc, (ck, cv), None

    x, (new_k, new_v), _ = scan_layers(
        layer_step, x, layers, (cache["k"], cache["v"]), paged=paged
    )
    return x, {"k": new_k, "v": new_v}


def embed(cfg: ModelConfig, params: Params, tokens: jnp.ndarray, pos=0) -> jnp.ndarray:
    """Token + learned position embeddings. pos: chunk offset (scalar), or
    a per-row [B] vector (slots mode: each row at its own position)."""
    T = tokens.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B, T]
        return params["embed"][tokens] + params["pos_embed"][positions]
    positions = pos + jnp.arange(T, dtype=jnp.int32)
    return params["embed"][tokens] + params["pos_embed"][positions][None, :, :]


def unembed(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    x = layer_norm(x, params["final_norm_w"], params["final_norm_b"], cfg.norm_eps)
    return (x @ params["embed"].T).astype(jnp.float32)


def forward(cfg, params, tokens, cache, pos):
    x = embed(cfg, params, tokens, pos)
    x, cache = forward_layers(cfg, params["layers"], x, cache, pos)
    return unembed(cfg, params, x), cache
