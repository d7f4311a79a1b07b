"""Latent-attention, routed-expert decoder (the DeepSeek-V3 block as
kakaocorp/kanana-2-30b-a3b-instruct-2601 publishes it) in pure JAX.

Two kinds of layer in one model, so two stacks, each scanned:

  layers["dense"]  first_k_dense leading layers: attention + SwiGLU (ffn_dim)
  layers["moe"]    the rest: attention + routed experts + the shared expert

Attention (every layer; H heads, r = kv_lora_rank, dn / dr = nope / rope
query-key numbers a head, dv = value numbers a head; pre-norm, no biases):

  q = h wq                      [H, dn + dr]; q_r rotated (interleaved pairs)
  [c | k_r] = h w_kva           c <- RMSNorm(c) * kv_norm; k_r rotated, ONE
                                key shared by all heads
  [k_n | v]_h = w_kvb,h c       never formed here: the cache holds the row
                                [c | k_r] (r + dr numbers, zero-padded to
                                whole 128-lane tiles) and attention reads it
                                ABSORBED: q~_h = w_kvb,h^K^T q_n,h, scores
                                (q~_h . c + q_r,h . k_r) * (dn + dr)^-0.5,
                                o~_h = sum p c, o_h = w_kvb,h^V o~_h
  out = concat_h(o_h) wo

The hook seam is models/llama's with the latent contract: the hook gets
(q_abs [B,T,H,R], row [B,T,1,R], None, cache, None, pos, mask, ...) and
returns (o~ [B,T,H,r], new cache, None). engine/paged's hooks serve it over
the latent pool; `latent_attn_hook` below is the dense-cache one.

Expert layers: scores s = sigmoid(h w_router) in float32; the
n_experts_per_tok largest of s + router_bias are chosen (the bias chooses,
it does not weigh); weights s_i / sum of the chosen s, times
routed_scaling. `route` / `routed_ffn` and the grouped product are
models/experts.py's (shared with the llama family's routed layer); the
banks stay outside the scan's xs. The shared expert is one SwiGLU of width
n_shared_experts * moe_ffn_dim on every token.

The residual stream, every sublayer's output and the router's scores are
float32; matrix products take the parameter dtype in and float32 out.

Params pytree (Ld / Lm layers a stack, D dim, E experts, F moe_ffn_dim,
Fs = n_shared_experts * F, V vocab):
  embed [V, D]   final_norm [D]   lm_head [D, V]
  layers.dense / layers.moe, both:
    attn_norm mlp_norm [L, D]  wq [L, D, H*(dn+dr)]  w_kva [L, D, r+dr]
    kv_norm [L, r]  w_kvb [L, r, H*(dn+dv)]  wo [L, H*dv, D]
  layers.dense: w_gate w_up [Ld, D, ffn_dim]  w_down [Ld, ffn_dim, D]
  layers.moe:   w_router [Lm, D, E]  router_bias [Lm, E] float32
                w_gate w_up [Lm, E, D, F]  w_down [Lm, E, F, D]
                ws_gate ws_up [Lm, D, Fs]  ws_down [Lm, Fs, D]
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import causal_mask, ragged_causal_mask, slot_causal_mask
from ..ops.norms import rms_norm
from ..ops.rope import rope_interleaved
from .experts import (  # noqa: F401 - the family's routed half, re-exported
    BANKS, normal_slices, grouped_matmul, route, routed_expert_matmul,
    routed_ffn,
)
from .llama import pin_products, scan_layers  # one scan, dense cache or pool
from .stack import ROUTER_BIAS_SCALE, swiglu

Params = dict
F32 = jnp.float32

# init_params' key of each leaf: an index into split(key, 24), one table for
# both stacks (cellbench/reference/mla_moe.py writes the same table down)
LEAF_KEYS = {
    "embed": 0, "lm_head": 1,
    "dense.wq": 2, "dense.w_kva": 3, "dense.w_kvb": 4, "dense.wo": 5,
    "dense.w_gate": 6, "dense.w_up": 7, "dense.w_down": 8,
    "moe.wq": 9, "moe.w_kva": 10, "moe.w_kvb": 11, "moe.wo": 12,
    "moe.w_router": 13, "moe.router_bias": 14,
    "moe.w_gate": 15, "moe.w_up": 16, "moe.w_down": 17,
    "moe.ws_gate": 18, "moe.ws_up": 19, "moe.ws_down": 20,
}


def stack_depths(cfg: ModelConfig) -> tuple:
    """(dense layers, expert layers)."""
    return cfg.first_k_dense, cfg.n_layers - cfg.first_k_dense


def leaf_shapes(cfg: ModelConfig) -> dict:
    """{leaf path: (shape, init scale or None for ones)}, stacked leaves
    with their layer axis first."""
    D, H, V = cfg.dim, cfg.n_heads, cfg.vocab_size
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    E, F, Fd = cfg.n_experts, cfg.moe_ffn_dim, cfg.ffn_dim
    Fs = cfg.n_shared_experts * F
    Ld, Lm = stack_depths(cfg)
    s = D ** -0.5
    out = {"embed": ((V, D), 0.02), "lm_head": ((D, V), s),
           "final_norm": ((D,), None)}
    for stack, L in (("dense", Ld), ("moe", Lm)):
        out.update({
            f"{stack}.attn_norm": ((L, D), None),
            f"{stack}.mlp_norm": ((L, D), None),
            f"{stack}.kv_norm": ((L, r), None),
            f"{stack}.wq": ((L, D, H * (dn + dr)), s),
            f"{stack}.w_kva": ((L, D, r + dr), s),
            f"{stack}.w_kvb": ((L, r, H * (dn + dv)), r ** -0.5),
            f"{stack}.wo": ((L, H * dv, D), (H * dv) ** -0.5),
        })
    out.update({
        "dense.w_gate": ((Ld, D, Fd), s), "dense.w_up": ((Ld, D, Fd), s),
        "dense.w_down": ((Ld, Fd, D), Fd ** -0.5),
        "moe.w_router": ((Lm, D, E), s),
        "moe.router_bias": ((Lm, E), ROUTER_BIAS_SCALE),
        "moe.w_gate": ((Lm, E, D, F), s), "moe.w_up": ((Lm, E, D, F), s),
        "moe.w_down": ((Lm, E, F, D), F ** -0.5),
        "moe.ws_gate": ((Lm, D, Fs), s), "moe.ws_up": ((Lm, D, Fs), s),
        "moe.ws_down": ((Lm, Fs, D), Fs ** -0.5),
    })
    return out


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): scaled normals,
    norm weights 1, the selection bias normal * ROUTER_BIAS_SCALE in
    float32. A stack with no layers (first_k_dense 0) keeps empty leaves."""
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 24)
    params: Params = {"layers": {"dense": {}, "moe": {}}}
    for path, (shape, scale) in leaf_shapes(cfg).items():
        if scale is None:
            leaf = jnp.ones(shape, dt)
        elif 0 in shape:
            leaf = jnp.zeros(shape, dt)
        else:
            # the two vocabulary tables are drawn as 8 slices of rows
            cut = 8 if path in ("embed", "lm_head") and shape[0] % 8 == 0 \
                else None
            leaf = normal_slices(
                ks[LEAF_KEYS[path]], scale=float(scale),
                shape=(cut, shape[0] // cut) + shape[1:] if cut else shape,
                dtype=F32 if path == "moe.router_bias" else dt,
            ).reshape(shape)
        stack, _, name = path.rpartition(".")
        (params["layers"][stack] if stack else params)[name] = leaf
    return params


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
                  n_layers: Optional[int] = None):
    """Zeroed dense latent cache, one leaf a stack: [L, B, 1, S, R] rows
    [c | k_r | pad] (the per-head caches' layout with one shared head)."""
    if n_layers is not None and n_layers != cfg.n_layers:
        raise ValueError("a latent cache is not cut by layers (no pp)")
    S = max_seq or cfg.max_seq_len
    return {
        stack: jnp.zeros((L, batch, 1, S, cfg.latent_row), cfg.jnp_dtype)
        for stack, L in zip(("dense", "moe"), stack_depths(cfg))
    }


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params: Params, tokens, pos=0):
    """[B, T] -> [B, T, D], float32: the residual stream's dtype."""
    del pos
    return params["embed"][tokens].astype(F32)


@jax.named_scope("head")
def unembed(cfg: ModelConfig, params: Params, x):
    """Final RMSNorm and the (untied) output head: float32 logits."""
    h = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.jnp_dtype)
    return jnp.dot(h, params["lm_head"], preferred_element_type=F32)


# -- attention ----------------------------------------------------------------


def latent_attend(cfg: ModelConfig, q_abs, rows, mask):
    """Plain XLA attention in absorbed form: q_abs [B, T, H, R] against
    rows [B, S, R] (one row a position, shared by the heads) under mask
    [B | 1, T, S] -> [B, T, H, r] = sum p c. Float32 scores and softmax."""
    r = cfg.kv_lora_rank
    s = jnp.einsum("bthr,bsr->bhts", q_abs, rows, preferred_element_type=F32)
    s = jnp.where(mask[:, None], s * cfg.query_scale, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None], p, 0.0)  # a row that attends nothing
    out = jnp.einsum("bhts,bsr->bthr", p.astype(rows.dtype), rows[..., :r],
                     preferred_element_type=F32)
    return out.astype(q_abs.dtype)


def latent_attn_hook(cfg, q, k, v, cache_k, cache_v, pos, mask, update_gate,
                     valid_start=None, window_flag=None):
    """The dense-cache hook (whole forward, solo decode): write the chunk's
    rows at `pos` (a scalar, or one position a row), attend the cache."""
    del v, cache_v, valid_start, window_flag
    rows = k[:, :, 0]  # [B, T, R]
    if update_gate is not None:
        raise ValueError("a latent cache has no gated write (no pp)")
    if pos.ndim == 1:
        new = jax.vmap(
            lambda c, rw, p: jax.lax.dynamic_update_slice(c, rw, (p, 0))
        )(cache_k[:, 0], rows, pos)
    else:
        new = jax.lax.dynamic_update_slice(cache_k[:, 0], rows, (0, pos, 0))
    mask = mask if mask.ndim == 3 else mask[None]
    return latent_attend(cfg, q, new, mask), new[:, None], None


@jax.named_scope("attn")
def attention(cfg: ModelConfig, lp: Params, x, cache, pos, positions, mask,
              update_gate, hook, layer=None):
    """The attention sublayer on the float32 residual x [B, T, D]; returns
    (its float32 output [B, T, D], the new cache). cache: the layer's slice
    of the dense cache, or under a paged hook the stack's whole pool leaf
    with `layer`, the layer's index in it.

    `lp`'s leaves are the layer scan's slices of the stacked parameters. A
    reshape straight after a scanned weight's product is moved onto the
    weight, and the slice then cannot fuse into the dot: the query's product
    passes `llama.pin_products` before its head split (its docstring).
    `w_kvb` below is reshaped and sliced per head by this code itself (the
    absorbed form's batched dots want the head axis major): its relayout a
    layer-step is another cause and stays (PERF.md section 7)."""
    B, T, _ = x.shape
    dt = cfg.jnp_dtype
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    pad = cfg.latent_row - cfg.latent_dim
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps).astype(dt)
    (q,) = pin_products(h @ lp["wq"])
    q = q.reshape(B, T, H, dn + dr)
    q_r = rope_interleaved(q[..., dn:], positions[..., None], cfg.rope_theta)
    kva = h @ lp["w_kva"]
    c = rms_norm(kva[..., :r], lp["kv_norm"], cfg.norm_eps)
    k_r = rope_interleaved(kva[..., r:], positions, cfg.rope_theta)
    row = jnp.concatenate(
        [c, k_r, jnp.zeros((B, T, pad), dt)], axis=-1
    )[:, :, None]  # [B, T, 1, R]
    w_kvb = lp["w_kvb"].reshape(r, H, dn + dv)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bthn,rhn->bthr", q[..., :dn], w_kvb[..., :dn])
        q_abs = jnp.concatenate(
            [q_lat, q_r, jnp.zeros((B, T, H, pad), dt)], axis=-1
        )
    o_lat, new_cache, _ = hook(
        cfg, q_abs, row, None, cache, None, pos, mask, update_gate, None, None,
        *(() if layer is None else (layer,)),
    )
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("bthr,rhv->bthv", o_lat, w_kvb[..., dn:])
    out = jnp.dot(o.reshape(B, T, H * dv), lp["wo"], preferred_element_type=F32)
    return out, new_cache


# -- feed-forward -------------------------------------------------------------


def moe_ffn(cfg: ModelConfig, lp: Params, banks: Params, layer, h, live=None):
    """Routed experts + the shared expert on normed h [B, T, D]: (float32
    [B, T, D], routed tokens an expert [E]). Every expert is held here; a
    share under `ep` is `routed_ffn(expert_lo=...)`, its parts summed and
    the shared expert added once (no mesh is wired for the family yet)."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    with jax.named_scope("moe_route"):
        chosen, weights = route(cfg, flat, lp["w_router"], lp["router_bias"])
    out, sizes = routed_ffn(cfg, banks, layer, flat, chosen, weights, live=live)
    with jax.named_scope("moe_shared"):
        out = out + swiglu(flat, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out.reshape(B, T, D), sizes


# -- the two stacks -----------------------------------------------------------


def forward_layers(cfg: ModelConfig, layers: Params, x, cache, pos,
                   update_gate=None, tp_axis=None, attn_hook=None,
                   valid_start=None, ep_axis=None, attn_seq_len=None):
    """Both stacks over a chunk x [B, T, D] (float32 residual). cache: one
    leaf a stack ("dense", "moe": [L, ...] latent rows, dense or paged);
    with a "routed" leaf [2, Lm, E] int32 the expert layers add to it what
    they routed: [0] tokens an expert got, [1] steps in which it got any.
    pos: a scalar, or one position a row (the flat token layout).
    Under a paged hook (`attn_hook.paged`, engine/paged.py) each stack's
    pool leaf rides its scan as a carry, whole, and the hook gets the
    layer's index: see models/llama.forward_layers. Returns (x, new cache)."""
    if tp_axis is not None or ep_axis is not None:
        raise ValueError("the mla_moe family is not sharded over tp or ep")
    T = x.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    S = attn_seq_len if attn_seq_len is not None else cache["moe"].shape[3]
    if pos.ndim == 1:
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        mask = slot_causal_mask(pos, T, S)
    else:
        positions = jnp.broadcast_to(
            pos + jnp.arange(T, dtype=jnp.int32), x.shape[:2]
        )
        mask = (causal_mask(pos, T, S) if valid_start is None
                else ragged_causal_mask(pos, T, S, valid_start))
    hook = attn_hook or latent_attn_hook
    # rows whose output nothing reads reach no expert (engine/paged's hooks
    # say which: launch padding, freed slots)
    live = getattr(attn_hook, "live", None)
    if live is not None:
        live = jnp.repeat(live, T) if T > 1 else live
    dt = cfg.jnp_dtype

    paged = getattr(attn_hook, "paged", False)

    # the step's scopes (utils/tracing.STEP_SCOPES): the residual add and
    # the norm between two blocks belong to the block they feed (`feeds`;
    # in front of the routed experts that is the router)
    def attn_part(xc, lp, ck, layer, feeds):
        out, ck = attention(cfg, lp, xc, ck, pos, positions, mask,
                            update_gate, hook, layer if paged else None)
        with jax.named_scope(feeds):
            xc = xc + out
            h = rms_norm(xc, lp["mlp_norm"], cfg.norm_eps).astype(dt)
        return xc, h, ck

    def dense_layer(xc, lp, ck, layer):
        xc, h, ck = attn_part(xc, lp, ck, layer, "ffn")
        with jax.named_scope("ffn"):
            out = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        with jax.named_scope("attn"):  # the next layer's
            return xc + out, ck, None

    moe = layers["moe"]
    banks = {name: moe[name] for name in BANKS}  # closed over, never sliced

    def moe_layer(xc, lp, ck, layer):
        xc, h, ck = attn_part(xc, lp, ck, layer, "moe_route")
        out, sizes = moe_ffn(cfg, lp, banks, layer, h, live)
        with jax.named_scope("attn"):  # the next layer's (the head's, after the last)
            return xc + out, ck, sizes

    new = dict(cache)
    if stack_depths(cfg)[0]:
        x, new["dense"], _ = scan_layers(
            dense_layer, x, layers["dense"], cache["dense"], paged=paged
        )
    small = {name: leaf for name, leaf in moe.items() if name not in BANKS}
    x, new["moe"], sizes = scan_layers(
        moe_layer, x, small, cache["moe"], paged=paged
    )
    if "routed" in cache:
        new["routed"] = cache["routed"] + jnp.stack(
            [sizes, (sizes > 0).astype(jnp.int32)]
        )
    return x, new


def forward(cfg: ModelConfig, params: Params, tokens, cache, pos):
    """Whole-model chunk forward: tokens [B, T] at offset pos -> (float32
    logits [B, T, V], new cache)."""
    x = embed(cfg, params, tokens)
    x, cache = forward_layers(cfg, params["layers"], x, cache, pos)
    return unembed(cfg, params, x), cache
