"""Gated grouped-query attention, sliding-window layers beside global ones,
over routed experts beside a shared one (arcee-ai/Trinity-Large-Preview,
model_type afmoe) in pure JAX, as ONE chip's share of an expert-parallel
deployment where the configuration says so.

The stack of two kinds of layer (cfg.layer_types) is models/stack.py's loop,
as are the gated attention, the FFN and the draw; here the family's leaves
and their binding. RMSNorm with a weight, eps cfg.norm_eps, everywhere; x a
layer's input, D = cfg.dim:

  x_0       E[token] * sqrt(D)              (cfg.embed_scale)
  layer l   h = x + N2(Attn_l(N1(x)));  y = h + N4(FFN_l(N3(h)))
  head      RMSNorm, then the untied head

  Attn(u)   q = u wq (H heads of Dh), k = u wk, v = u wv (KV heads),
            g = u wg (H * Dh numbers); a per-head RMSNorm with a weight on
            every query and key head; on a "sliding_attention" layer RoPE
            (half-rotation, cfg.rope_theta) on q and k and a query at i
            attends j <= i with i - j < cfg.attn_window; on a
            "full_attention" layer NO position encoding and every j <= i;
            softmax at Dh^-0.5; Attn = (sigmoid(g) * heads) wo. No bias.
  FFN       the first cfg.first_k_dense layers: SwiGLU of cfg.ffn_dim; the
            others `stack.moe_ffn`: the experts held here and the shared one.

The cache: dense, "k" / "v" [L, B, KV, S, Dh] for every layer. Paged
(engine/paged.init_pool), with both kinds of layer in the stack
(cfg.kv_groups), the pool keeps "k" / "v" [Lg, Ng, KV, bs, Dh] for the
full-attention layers and "kw" / "vw" [Lw, Nw, KV, bs, Dh] for the sliding
ones, each group with its own blocks and its own half of the launch's block
table (`attn_hook.group`); a stack of one kind keeps "k" / "v" alone.

Params pytree (L layers, V the vocabulary rows held):
  embed [V, D]   head [V, D] (untied; a row a token)   final_norm [D]
  layers: norm1 norm2 norm3 norm4 [L, D]
    attn:  wq wg [L, D, H*Dh]  wk wv [L, D, KV*Dh]  wo [L, H*Dh, D]
           q_norm k_norm [L, Dh]
    dense, moe: `stack.ffn_shapes`, `stack.moe_shapes` with a shared expert

`init_params` is `stack.draw_params` by published index: the eight shares'
routed parts add up to the uncut layer's (tests/test_afmoe.py).
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from . import stack
from ..config import ModelConfig
from ..ops.rope import rope_cos_sin
from .stack import embed, unembed  # noqa: F401 - the family's ends

Params = dict

# init_params' key of each drawn leaf: an index into split(key, 24)
# (cellbench/reference/windowed_gated_moe.py writes the same table down)
LEAF_KEYS = {
    "embed": 0, "head": 1,
    "attn.wq": 2, "attn.wk": 3, "attn.wv": 4, "attn.wg": 5, "attn.wo": 6,
    "dense.w_gate": 7, "dense.w_up": 8, "dense.w_down": 9,
    "moe.w_router": 10, "moe.router_bias": 11,
    "moe.w_gate": 12, "moe.w_up": 13, "moe.w_down": 14,
    "moe.ws_gate": 15, "moe.ws_up": 16, "moe.ws_down": 17,
}


def stack_depths(cfg: ModelConfig) -> dict:
    return {"dense": cfg.first_k_dense,
            "moe": cfg.n_layers - cfg.first_k_dense}


def leaf_shapes(cfg: ModelConfig) -> dict:
    """{leaf path: (shape, init scale or None for ones)}, stacked leaves
    with their layer axis first; the banks with the experts HELD."""
    D, V, L = cfg.dim, cfg.vocab_size, cfg.n_layers
    n = stack_depths(cfg)
    s = D ** -0.5
    return {
        "embed": ((V, D), 0.02), "head": ((V, D), s), "final_norm": ((D,), None),
        "norm1": ((L, D), None), "norm2": ((L, D), None),
        "norm3": ((L, D), None), "norm4": ((L, D), None),
        **stack.attn_shapes("attn", L, D, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim, gate=True, qk_norm=True),
        **stack.ffn_shapes("dense", n["dense"], D, cfg.ffn_dim),
        **stack.moe_shapes(cfg, n["moe"], shared=True),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): `stack.draw_params`
    by published index, the selection bias in float32; the head is untied."""
    return stack.draw_params(cfg, key, leaf_shapes(cfg), LEAF_KEYS,
                             float32=("moe.router_bias",))


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
                  n_layers: Optional[int] = None):
    """Zeroed dense cache: K/V of every layer."""
    stack.whole_cache_only(cfg, n_layers)
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_seq or cfg.max_seq_len,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.jnp_dtype),
            "v": jnp.zeros(shape, cfg.jnp_dtype)}


def _prepare(cfg: ModelConfig, layers: Params, x, cache, pos, hook,
             attn_seq_len):
    """What the layers share, once a forward: each kind's view of cfg, mask
    and rotary tables (a global layer takes no position encoding), and under
    a pool of two groups (cfg.kv_groups) each group's half of the launch's
    block table, its pool leaves and a layer's index in them."""
    paged = getattr(hook, "paged", False)
    groups = cfg.kv_groups if paged else ("global",)
    two = len(groups) == 2
    W = cfg.attn_window
    S = (attn_seq_len // len(groups) if attn_seq_len is not None
         else cache["k"].shape[3])
    positions, (full, slide) = stack.positions_and_masks(
        pos, x.shape[1], S, (None, W))
    with jax.named_scope("attn"):  # the rotary tables, once a forward
        rope = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    sliding = [kind == "sliding_attention" and W is not None
               for kind in cfg.layer_types]
    group = ["window" if s and two else "global" for s in sliding]
    return SimpleNamespace(
        pos=pos, paged=paged, sliding=sliding, group=group,
        hooks=stack.group_hooks(hook, two),
        view={False: cfg.replace(attn_window=None) if W is not None else cfg,
              True: cfg},
        mask={False: full, True: slide}, rope={False: None, True: rope},
        leaves={"global": ("k", "v"),
                "window": ("kw", "vw") if two else ("k", "v")},
        # a layer's index in its group's leaves (one group: in the stack)
        at=[group[:li].count(g) if two else li for li, g in enumerate(group)])


def _attn(cfg, c, lp, h, new, li):
    s, g = c.sliding[li], c.group[li]
    return stack.cached(
        new, c.leaves[g], c.at[li], c.paged,
        lambda ck, cv, layer: stack.gated_attention(
            c.view[s], lp, h, ck, cv, c.pos, c.rope[s], c.mask[s], c.hooks[g],
            layer))


forward_layers = functools.partial(
    stack.forward_layers, norms=("norm1", "norm3"),
    post_norms=("norm2", "norm4"), prepare=_prepare, routed=True,
    kinds={"sliding_attention": ("attn", "attn", _attn),
           "full_attention": ("attn", "attn", _attn)})
forward = functools.partial(stack.forward, forward_layers)
