"""Sliding-window layers with a learned sink beside global layers, the two
kinds with their own K/V head counts, keys wider than values, over routed
experts with no shared one (XiaomiMiMo/MiMo-V2.5, model_type mimo_v2) in
pure JAX, as ONE chip's share of an expert-parallel deployment where the
configuration says so.

Layers of two kinds alternate in one stack (cfg.layer_types), so the layers
are not scanned: a Python loop over the pattern, each layer reading its own
row of its KIND's stacked leaves (the kinds' K/V projections differ in
shape). RMSNorm with a weight, eps cfg.norm_eps; x a layer's input:

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
            others: models/afmoe.moe_ffn, which is models/experts.route (a
            float32 sigmoid over ALL cfg.n_experts, the n_experts_per_tok
            largest of s + router_bias chosen, weights s / (sum of the
            chosen s + cfg.router_norm_eps)) and routed_ffn over the
            experts HELD here (cfg.expert_lo .. + cfg.experts_held); no
            shared expert.

The residual stream, every sublayer's output and the router's scores are
float32; matrix products take the parameter dtype in and float32 out.

The cache, dense or paged (engine/paged.init_pool): "k" [Lg, ., KVg, ., Dk]
and "v" [.., Dv] for the global layers, "kw" / "vw" with KVw heads for the
sliding ones; dense the second axis is the batch and the fourth the
sequence, paged they are a group's blocks and a block's positions, each
group with its own blocks and its own half of the launch's block table
(`attn_hook.group`), and a key row is cfg.key_row wide: the keys and the
queries are zero-padded to it on the way in (zero lanes add nothing to a
score).

Params pytree (Lg / Lw global / sliding layers, Ld / Lm dense / expert
layers, E the router's width, Eh experts held, V the vocabulary rows held):
  embed [V, D]   head [V, D] (untied; a row a token)   final_norm [D]
  layers: norm1 norm2 [L, D]
    global: wq [Lg, D, H*Dk]  wk [Lg, D, KVg*Dk]  wv [Lg, D, KVg*Dv]
            wo [Lg, H*Dv, D]
    window: the same with KVw, and sink [Lw, H] float32
    dense:  w_gate w_up [Ld, D, F]  w_down [Ld, F, D]
    moe:    w_router [Lm, D, E]  router_bias [Lm, E] float32
            w_gate w_up [Lm, Eh, D, Fm]  w_down [Lm, Eh, Fm, D]

`init_params` is models/afmoe.draw_params: an expert's matrices and a
vocabulary row from keys folded from their PUBLISHED index, so the shares
of one seed are shares of one model (tests/test_mimo.py). The sink is a
float32 normal x SINK_SCALE: zeros would make it a constant no test can
tell from a wrong sign; a trained checkpoint brings its own. The selection
bias is a float32 normal x ROUTER_BIAS_SCALE, the other routed
families' (models/mla_moe.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import (attend, causal_mask, slot_causal_mask,
                             update_kv_cache, update_kv_cache_slots)
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_cos_sin
from .afmoe import add_routed, draw_params, group_hooks, moe_ffn
from .experts import BANKS
from .llama import pin_products
from .mla_moe import ROUTER_BIAS_SCALE, swiglu

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
    Dk, Dv = cfg.head_dim, cfg.value_dim
    E, Eh, Fm, F = cfg.n_experts, cfg.experts_held, cfg.moe_ffn_dim, cfg.ffn_dim
    Ld, Lm = cfg.first_k_dense, cfg.n_layers - cfg.first_k_dense
    s = D ** -0.5
    shapes = {
        "embed": ((V, D), 0.02), "head": ((V, D), s), "final_norm": ((D,), None),
        "norm1": ((L, D), None), "norm2": ((L, D), None),
    }
    for group in ("global", "window"):
        n, KV = len(kind_layers(cfg, group)), cfg.group_kv_heads(group)
        shapes.update({
            f"{group}.wq": ((n, D, H * Dk), s),
            f"{group}.wk": ((n, D, KV * Dk), s),
            f"{group}.wv": ((n, D, KV * Dv), s),
            f"{group}.wo": ((n, H * Dv, D), (H * Dv) ** -0.5),
        })
    if cfg.window_sink:
        shapes["window.sink"] = (
            (len(kind_layers(cfg, "window")), H), SINK_SCALE)
    shapes.update({
        "dense.w_gate": ((Ld, D, F), s), "dense.w_up": ((Ld, D, F), s),
        "dense.w_down": ((Ld, F, D), F ** -0.5),
        "moe.w_router": ((Lm, D, E), s),
        "moe.router_bias": ((Lm, E), ROUTER_BIAS_SCALE),
        "moe.w_gate": ((Lm, Eh, D, Fm), s), "moe.w_up": ((Lm, Eh, D, Fm), s),
        "moe.w_down": ((Lm, Eh, Fm, D), Fm ** -0.5),
    })
    return shapes


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): scaled normals, norm
    weights 1, the selection bias and the sinks in float32."""
    if cfg.tie_embeddings:
        raise ValueError(f"{cfg.name}: the mimo_v2 family's head is untied")
    return draw_params(cfg, key, leaf_shapes(cfg), LEAF_KEYS,
                       float32=("moe.router_bias", "window.sink"))


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
                  n_layers: Optional[int] = None):
    """Zeroed dense cache: K/V of every layer, by kind."""
    if n_layers is not None and n_layers != cfg.n_layers:
        raise ValueError("a mimo_v2 cache is not cut by layers (no pp)")
    S, cache = max_seq or cfg.max_seq_len, {}
    for group, (kn, vn) in CACHE_LEAVES.items():
        rows = (len(kind_layers(cfg, group)), batch,
                cfg.group_kv_heads(group), S)
        cache[kn] = jnp.zeros(rows + (cfg.head_dim,), cfg.jnp_dtype)
        cache[vn] = jnp.zeros(rows + (cfg.value_dim,), cfg.jnp_dtype)
    return cache


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params: Params, tokens, pos=0):
    """[B, T] -> [B, T, D] float32."""
    del pos
    return params["embed"][tokens].astype(F32)


@jax.named_scope("head")
def unembed(cfg: ModelConfig, params: Params, x):
    """The last RMSNorm and the untied head: float32 logits over the held
    vocabulary rows."""
    h = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.jnp_dtype)
    return jax.lax.dot_general(
        h, params["head"], (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=F32,
    )


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
    of the kind's base over cfg.rotary_dim lanes. cache_k / v: the layer's
    slices of the dense cache (layer None), or under a paged hook the
    layer's group's pool leaves and `layer`, the layer's index in them."""
    B, T, _ = h.shape
    H, KV, Dk, Dv = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.value_dim
    # (`pin_products`: models/afmoe.attention says why)
    q, k, v = pin_products(h @ lp["wq"], h @ lp["wk"], h @ lp["wv"])
    q = q.reshape(B, T, H, Dk)
    k = k.reshape(B, T, KV, Dk)
    v = v.reshape(B, T, KV, Dv)
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


def forward_layers(cfg: ModelConfig, layers: Params, x, cache, pos,
                   update_gate=None, tp_axis=None, attn_hook=None,
                   valid_start=None, ep_axis=None, attn_seq_len=None):
    """Every layer over a chunk x [B, T, D] (float32 residual). cache: the
    dense cache (`init_kv_cache`) or, under a paged hook (`attn_hook.paged`,
    engine/paged.py), the pool, whose "routed" leaf the expert layers add
    to (models/afmoe.add_routed). pos: a scalar, or one position a row (the
    flat token layout). Returns (x, new cache)."""
    if tp_axis is not None or ep_axis is not None or update_gate is not None:
        raise ValueError("the mimo_v2 family is not sharded over pp, tp or ep")
    if valid_start is not None:
        raise ValueError("the mimo_v2 family takes no left-padded rows")
    T = x.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    paged = getattr(attn_hook, "paged", False)
    two = "kw" in cache  # (a stack of one kind keeps "k" / "v" alone)
    W = cfg.attn_window
    views = {
        "global": cfg.replace(attn_window=None, rope_local_theta=None),
        "window": cfg.replace(n_kv_heads=cfg.group_kv_heads("window")),
    }
    S = (attn_seq_len // (2 if two else 1) if attn_seq_len is not None
         else cache["k"].shape[3])
    if pos.ndim == 1:
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        masks = {"global": slot_causal_mask(pos, T, S),
                 "window": slot_causal_mask(pos, T, S, window=W)}
    else:
        positions = pos + jnp.arange(T, dtype=jnp.int32)
        masks = {"global": causal_mask(pos, T, S),
                 "window": causal_mask(pos, T, S, window=W)}
    with jax.named_scope("attn"):  # the rotary tables, once a kind a forward
        ropes = {
            group: rope_cos_sin(positions, cfg.rotary_dim or cfg.head_dim,
                                cfg.group_rope_theta(group))
            for group in ("global", "window")}
    hooks = group_hooks(attn_hook or dense_attn_hook, two and paged)
    live = getattr(attn_hook, "live", None)
    if live is not None and T > 1:
        live = jnp.repeat(live, T)
    dt = cfg.jnp_dtype
    banks = {name: layers["moe"][name] for name in BANKS}  # never sliced

    def row(kind, i):  # layer i's leaves of its kind's small stack
        return {name: leaf[i] for name, leaf in layers[kind].items()
                if not (kind == "moe" and name in BANKS)}

    new = dict(cache)
    sizes, away = [], []
    at = {"global": 0, "window": 0}  # a layer's index in its kind's leaves
    for li, kind in enumerate(cfg.layer_types):
        group = GROUP_OF[kind]
        routed = li >= cfg.first_k_dense
        with jax.named_scope("attn"):
            h = rms_norm(x, layers["norm1"][li], cfg.norm_eps).astype(dt)
            kn, vn = CACHE_LEAVES[group if two else "global"]
            ia = at[group]
            at[group] += 1
            ck, cv = (new[kn], new[vn]) if paged else (new[kn][ia], new[vn][ia])
            out, ck, cv = attention(
                views[group], row(group, ia), h, ck, cv, pos, ropes[group],
                masks[group], hooks[group], ia if paged else None,
            )
            new[kn] = ck if paged else new[kn].at[ia].set(ck)
            new[vn] = cv if paged else new[vn].at[ia].set(cv)
        with jax.named_scope("moe_route" if routed else "ffn"):
            x = x + out
            h = rms_norm(x, layers["norm2"][li], cfg.norm_eps).astype(dt)
        if routed:
            im = li - cfg.first_k_dense
            out, counts, elsewhere = moe_ffn(cfg, row("moe", im), banks, im,
                                             h, live)
            sizes.append(counts)
            away.append(elsewhere)
        else:
            with jax.named_scope("ffn"):
                lp = row("dense", li)
                out = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        with jax.named_scope("attn" if li + 1 < cfg.n_layers else "head"):
            x = x + out
    return x, add_routed(cache, new, sizes, away)


def forward(cfg: ModelConfig, params: Params, tokens, cache, pos):
    """Whole-model chunk forward: tokens [B, T] at offset pos -> (float32
    logits [B, T, V], new cache)."""
    x = embed(cfg, params, tokens)
    x, cache = forward_layers(cfg, params["layers"], x, cache, pos)
    return unembed(cfg, params, x), cache
