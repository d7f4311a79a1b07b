"""Sliding-window layers with a learned sink beside global layers, the two
kinds with their own K/V head counts, keys wider than values, over routed
experts with no shared one (XiaomiMiMo/MiMo-V2.5, model_type mimo_v2) in
pure JAX, as ONE chip's share of an expert-parallel deployment where the
configuration says so.

The stack of two kinds of layer (cfg.layer_types) is models/stack.py's loop,
as are the FFN and the draw; here the family's leaves, its attention and
their binding. Each layer reads its own row of its KIND's stacked leaves (the
kinds' K/V projections differ in shape). RMSNorm with a weight, eps
cfg.norm_eps; x a layer's input:

  x_0       E[token]
  layer l   h = x + Attn_l(N1(x));  y = h + FFN_l(N2(h))
  head      RMSNorm, then the untied head

  Attn(u)   by kind: a "full_attention" layer has cfg.n_kv_heads K/V heads,
            rotates by cfg.rope_theta and reads every j <= i; a
            "sliding_attention" layer has cfg.window_kv_heads, rotates by
            cfg.rope_local_theta, reads j <= i with i - j < cfg.attn_window
            and (cfg.window_sink) has a learned logit s_h a query head.
            Both: q = u wq (H heads of Dk = cfg.head_dim), k = u wk (KV
            heads of Dk), v = cfg.attn_value_scale x (u wv) (KV heads of
            Dv = cfg.value_dim); the half-rotation (x1, x2) -> (x1 cos - x2
            sin, x2 cos + x1 sin) over lanes 0 .. cfg.rotary_dim - 1 of
            every query and key head, the other lanes passed through
            (ops/rope.apply_rope); scores at Dk^-0.5; p_ij = exp(s_ij) /
            (sum_j' exp(s_ij') + exp(s_h)) on a layer with a sink (it takes
            probability and gives no value), the plain softmax elsewhere;
            Attn = concat_h(sum_j p_ij v_j) wo. No bias, no gate, no
            per-head norm.
  FFN       the first cfg.first_k_dense layers: SwiGLU of cfg.ffn_dim; the
            others `stack.moe_ffn`: the experts held here, no shared one.

The cache, dense or paged (engine/paged.init_pool): "k" [Lg, ., KVg, ., Dk]
and "v" [.., Dv] for the global layers, "kw" / "vw" with KVw heads for the
sliding ones; dense the second axis is the batch and the fourth the
sequence, paged they are a group's blocks and a block's positions, each
group with its own blocks and its own half of the launch's block table
(`attn_hook.group`), and a key row is cfg.key_row wide: the keys and the
queries are zero-padded to it on the way in (zero lanes add nothing to a
score).

Params pytree (Lg / Lw global / sliding layers, V the vocabulary rows held):
  embed [V, D]   head [V, D] (untied; a row a token)   final_norm [D]
  layers: norm1 norm2 [L, D]
    global: wq [Lg, D, H*Dk]  wk [Lg, D, KVg*Dk]  wv [Lg, D, KVg*Dv]
            wo [Lg, H*Dv, D]
    window: the same with KVw, and sink [Lw, H] float32
    dense, moe: `stack.ffn_shapes`, `stack.moe_shapes`

`init_params` is `stack.draw_params` by published index (tests/test_mimo.py).
The sink is a float32 normal x SINK_SCALE: zeros would make it a constant no
test can tell from a wrong sign; a trained checkpoint brings its own.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from . import stack
from ..config import ModelConfig
from ..ops.attention import attend, update_kv_cache, update_kv_cache_slots
from ..ops.rope import apply_rope, rope_cos_sin
from .stack import embed, unembed  # noqa: F401 - the family's ends

Params = dict
F32 = jnp.float32
SINK_SCALE = 0.5

# init_params' key of each drawn leaf: an index into split(key, 24)
# (cellbench/reference/window_sink_moe.py writes the same table down)
LEAF_KEYS = {
    "embed": 0, "head": 1,
    "global.wq": 2, "global.wk": 3, "global.wv": 4, "global.wo": 5,
    "window.wq": 6, "window.wk": 7, "window.wv": 8, "window.wo": 9,
    "window.sink": 10,
    "dense.w_gate": 11, "dense.w_up": 12, "dense.w_down": 13,
    "moe.w_router": 14, "moe.router_bias": 15,
    "moe.w_gate": 16, "moe.w_up": 17, "moe.w_down": 18,
}
GROUP_OF = {"full_attention": "global", "sliding_attention": "window"}
CACHE_LEAVES = {"global": ("k", "v"), "window": ("kw", "vw")}


def kind_layers(cfg: ModelConfig, group: str) -> tuple:
    """The stack's layers of `group`'s kind."""
    return tuple(i for i, kind in enumerate(cfg.layer_types)
                 if GROUP_OF[kind] == group)


def leaf_shapes(cfg: ModelConfig) -> dict:
    """{leaf path: (shape, init scale or None for ones)}, stacked leaves
    with their layer axis first; the banks with the experts HELD."""
    D, V, L, H = cfg.dim, cfg.vocab_size, cfg.n_layers, cfg.n_heads
    shapes = {
        "embed": ((V, D), 0.02), "head": ((V, D), D ** -0.5),
        "final_norm": ((D,), None),
        "norm1": ((L, D), None), "norm2": ((L, D), None),
    }
    for group in ("global", "window"):
        shapes.update(stack.attn_shapes(
            group, len(kind_layers(cfg, group)), D, H,
            cfg.group_kv_heads(group), cfg.head_dim, cfg.value_dim))
    if cfg.window_sink:
        shapes["window.sink"] = (
            (len(kind_layers(cfg, "window")), H), SINK_SCALE)
    return {**shapes,
            **stack.ffn_shapes("dense", cfg.first_k_dense, D, cfg.ffn_dim),
            **stack.moe_shapes(cfg, cfg.n_layers - cfg.first_k_dense)}


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): scaled normals, norm
    weights 1, the selection bias and the sinks in float32."""
    return stack.draw_params(cfg, key, leaf_shapes(cfg), LEAF_KEYS,
                             float32=("moe.router_bias", "window.sink"))


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
                  n_layers: Optional[int] = None):
    """Zeroed dense cache: K/V of every layer, by kind."""
    stack.whole_cache_only(cfg, n_layers)
    S, cache = max_seq or cfg.max_seq_len, {}
    for group, (kn, vn) in CACHE_LEAVES.items():
        rows = (len(kind_layers(cfg, group)), batch,
                cfg.group_kv_heads(group), S)
        cache[kn] = jnp.zeros(rows + (cfg.head_dim,), cfg.jnp_dtype)
        cache[vn] = jnp.zeros(rows + (cfg.value_dim,), cfg.jnp_dtype)
    return cache


def dense_attn_hook(cfg, q, k, v, cache_k, cache_v, pos, mask, update_gate,
                    valid_start=None, window_flag=None, sink=None):
    """Cache write and attention over the dense cache (the whole-sequence
    forward of the tests; serving reads the paged pool): the XLA einsum
    whatever cfg.attn_impl says, since the chunk flash kernel has one width
    for keys and values and no sink."""
    del valid_start, window_flag
    write = update_kv_cache_slots if pos.ndim == 1 else update_kv_cache
    new_k, new_v = write(cache_k, cache_v, k, v, pos, gate=update_gate)
    return attend(q, new_k, new_v, mask, scale=cfg.query_scale,
                  sink=sink), new_k, new_v


def attention(cfg: ModelConfig, lp: Params, h, cache_k, cache_v, pos, rope,
              mask, hook, layer):
    """The attention operator on normed h [B, T, D] (parameter dtype);
    returns (float32 [B, T, D], new cache_k, new cache_v). cfg: the layer's
    own view (its kind's K/V heads, its window or none). rope: (cos, sin)
    of the kind's base over cfg.rotary_dim lanes. cache_k / v (the layer's
    group's leaves) and layer: `stack.cached`'s."""
    B, T, _ = h.shape
    H, KV, Dk, Dv = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.value_dim
    q, k, v = stack.head_products(h, lp, H, KV, Dk, Dv)
    if cfg.attn_value_scale != 1.0:
        v = (v.astype(F32) * cfg.attn_value_scale).astype(v.dtype)
    q, k = apply_rope(q, k, *rope)
    pad = cache_k.shape[-1] - Dk  # a pool's key row: whole lane tiles
    if pad:
        q, k = (jnp.pad(a, ((0, 0),) * 3 + ((0, pad),)) for a in (q, k))
    attn, new_k, new_v = hook(
        cfg, q, k, v, cache_k, cache_v, pos, mask, None, None, None,
        *(() if layer is None else (layer,)),
        **({"sink": lp["sink"]} if "sink" in lp else {}),
    )
    return jnp.dot(attn.reshape(B, T, H * Dv), lp["wo"],
                   preferred_element_type=F32), new_k, new_v


def _prepare(cfg: ModelConfig, layers: Params, x, cache, pos, hook,
             attn_seq_len):
    """What the layers share, once a forward: each kind's view of cfg, mask,
    rotary tables (its own base) and hook (under a pool of two groups its
    half of the launch's block table)."""
    paged = getattr(hook, "paged", False)
    two = "kw" in cache  # (a stack of one kind keeps "k" / "v" alone)
    S = (attn_seq_len // (2 if two else 1) if attn_seq_len is not None
         else cache["k"].shape[3])
    positions, masks = stack.positions_and_masks(
        pos, x.shape[1], S, (None, cfg.attn_window))
    with jax.named_scope("attn"):  # the rotary tables, once a kind a forward
        ropes = {
            group: rope_cos_sin(positions, cfg.rotary_dim or cfg.head_dim,
                                cfg.group_rope_theta(group))
            for group in CACHE_LEAVES}
    return SimpleNamespace(
        pos=pos, paged=paged, two=two, ropes=ropes,
        masks=dict(zip(CACHE_LEAVES, masks)),
        hooks=stack.group_hooks(hook, two and paged),
        views={"global": cfg.replace(attn_window=None, rope_local_theta=None),
               "window": cfg.replace(
                   n_kv_heads=cfg.group_kv_heads("window"))})


def _attn(group, cfg, c, lp, h, new, ia):
    return stack.cached(
        new, CACHE_LEAVES[group if c.two else "global"], ia, c.paged,
        lambda ck, cv, layer: attention(
            c.views[group], lp, h, ck, cv, c.pos, c.ropes[group],
            c.masks[group], c.hooks[group], layer))


forward_layers = functools.partial(
    stack.forward_layers, norms=("norm1", "norm2"), prepare=_prepare,
    routed=True, dense_hook=dense_attn_hook,
    kinds={kind: ("attn", group, functools.partial(_attn, group))
           for kind, group in GROUP_OF.items()})
forward = functools.partial(stack.forward, forward_layers)
