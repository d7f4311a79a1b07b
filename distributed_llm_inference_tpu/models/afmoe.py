"""Gated grouped-query attention, sliding-window layers beside global ones,
over routed experts beside a shared one (arcee-ai/Trinity-Large-Preview,
model_type afmoe) in pure JAX, as ONE chip's share of an expert-parallel
deployment where the configuration says so.

Layers of two kinds alternate in one stack (cfg.layer_types), so the layers
are not scanned: a Python loop over the pattern, each layer reading its own
row of the stacked leaves (models/lfm2.py's form). RMSNorm with a weight,
eps cfg.norm_eps, everywhere; x a layer's input, D = cfg.dim:

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
            others: s = sigmoid(h w_router) in float32 over ALL
            cfg.n_experts, the n_experts_per_tok largest of s + router_bias
            chosen, weights s / (sum of the chosen s + cfg.router_norm_eps)
            x routed_scaling (models/experts.route); the pairs whose expert
            is held here (cfg.expert_lo .. + cfg.experts_held) computed,
            the others left out (models/experts.routed_ffn: no code stands
            in for the chips that hold them), plus the shared expert, whole.

The residual stream, every sublayer's output and the router's scores are
float32; matrix products take the parameter dtype in and float32 out.

The cache: dense, "k" / "v" [L, B, KV, S, Dh] for every layer. Paged
(engine/paged.init_pool), with both kinds of layer in the stack
(cfg.kv_groups), the pool keeps "k" / "v" [Lg, Ng, KV, bs, Dh] for the
full-attention layers and "kw" / "vw" [Lw, Nw, KV, bs, Dh] for the sliding
ones, each group with its own blocks and its own half of the launch's block
table (`attn_hook.group`); a stack of one kind keeps "k" / "v" alone.

Params pytree (L layers, Ld / Lm dense / expert layers, E the router's
width, Eh experts held, F ffn_dim, Fm moe_ffn_dim, Fs the shared expert's
width, V the vocabulary rows held):
  embed [V, D]   head [V, D] (untied; a row a token)   final_norm [D]
  layers: norm1 norm2 norm3 norm4 [L, D]
    attn:  wq wg [L, D, H*Dh]  wk wv [L, D, KV*Dh]  wo [L, H*Dh, D]
           q_norm k_norm [L, Dh]
    dense: w_gate w_up [Ld, D, F]  w_down [Ld, F, D]
    moe:   w_router [Lm, D, E]  router_bias [Lm, E] float32
           w_gate w_up [Lm, Eh, D, Fm]  w_down [Lm, Eh, Fm, D]
           ws_gate ws_up [Lm, D, Fs]  ws_down [Lm, Fs, D]

`init_params` draws an expert's matrices and a vocabulary row from keys
folded from the expert's PUBLISHED index (layer x n_experts + expert) and
the row's index, never from how many are held: the shares of one seed are
shares of one model, and the eight shares' routed parts add up to the uncut
layer's (tests/test_afmoe.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import causal_mask, slot_causal_mask
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_cos_sin
from .experts import BANKS, _normal_slices, route, routed_ffn
from .llama import default_attn_hook, pin_products
from .mla_moe import ROUTER_BIAS_SCALE, swiglu

Params = dict
F32 = jnp.float32

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
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, Eh, Fm, F = cfg.n_experts, cfg.experts_held, cfg.moe_ffn_dim, cfg.ffn_dim
    Fs = max(cfg.n_shared_experts, 1) * Fm
    n = stack_depths(cfg)
    Ld, Lm = n["dense"], n["moe"]
    s = D ** -0.5
    return {
        "embed": ((V, D), 0.02), "head": ((V, D), s), "final_norm": ((D,), None),
        "norm1": ((L, D), None), "norm2": ((L, D), None),
        "norm3": ((L, D), None), "norm4": ((L, D), None),
        "attn.wq": ((L, D, H * Dh), s), "attn.wg": ((L, D, H * Dh), s),
        "attn.wk": ((L, D, KV * Dh), s), "attn.wv": ((L, D, KV * Dh), s),
        "attn.wo": ((L, H * Dh, D), (H * Dh) ** -0.5),
        "attn.q_norm": ((L, Dh), None), "attn.k_norm": ((L, Dh), None),
        "dense.w_gate": ((Ld, D, F), s), "dense.w_up": ((Ld, D, F), s),
        "dense.w_down": ((Ld, F, D), F ** -0.5),
        "moe.w_router": ((Lm, D, E), s),
        "moe.router_bias": ((Lm, E), ROUTER_BIAS_SCALE),
        "moe.w_gate": ((Lm, Eh, D, Fm), s), "moe.w_up": ((Lm, Eh, D, Fm), s),
        "moe.w_down": ((Lm, Eh, Fm, D), Fm ** -0.5),
        "moe.ws_gate": ((Lm, D, Fs), s), "moe.ws_up": ((Lm, D, Fs), s),
        "moe.ws_down": ((Lm, Fs, D), Fs ** -0.5),
    }


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _normal_keyed(key, ids, *, shape, scale, dtype):
    """[len(ids)] + shape: slice i is normal(fold_in(key, ids[i]), shape) *
    scale in float32, rounded to `dtype`: a slice's values follow its id
    alone, whatever else is drawn beside it."""
    def draw(i):
        k = jax.random.fold_in(key, i)
        return (jax.random.normal(k, shape, F32) * scale).astype(dtype)

    return jax.lax.map(draw, ids, batch_size=min(int(ids.shape[0]), 256)
                       if len(shape) == 1 else None)


def draw_params(cfg: ModelConfig, key: jax.Array, shapes: dict,
                leaf_keys: dict, float32: tuple = (),
                n_keys: int = 24) -> Params:
    """The seeded tree of `shapes` ({leaf path: (shape, scale or None for
    ones)}; "kind.name" a leaf of layers[kind], a bare name the tree's own
    where it is embed / head / final_norm, else layers'): the expert banks
    ("moe." + BANKS) and the two vocabulary tables by published index
    (`_normal_keyed`, the module docstring), the held ones alone; the other
    leaves slice by slice (models/experts._normal_slices), key
    split(key, n_keys)[leaf_keys[path]] each, the `float32` paths in float32.
    One draw for the families that hold a share (config.HOLDS_EXPERT_SHARE)."""
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, n_keys)
    layers: Params = {}
    params: Params = {"layers": layers}
    E, Eh = cfg.n_experts, cfg.experts_held
    Lm = cfg.n_layers - cfg.first_k_dense
    held = (jnp.arange(Lm, dtype=jnp.int32)[:, None] * E + cfg.expert_lo
            + jnp.arange(Eh, dtype=jnp.int32)[None, :]).reshape(-1)
    for path, (shape, scale) in shapes.items():
        kind, _, name = path.rpartition(".")
        bank = kind == "moe" and name in BANKS
        if scale is None:
            leaf = jnp.ones(shape, dt)
        elif 0 in shape:
            leaf = jnp.zeros(shape, dt)
        elif bank or path in ("embed", "head"):
            leaf = _normal_keyed(
                ks[leaf_keys[path]],
                held if bank else jnp.arange(shape[0], dtype=jnp.int32),
                shape=shape[2:] if bank else shape[1:], scale=float(scale),
                dtype=dt,
            ).reshape(shape)
        else:
            leaf = _normal_slices(
                ks[leaf_keys[path]], scale=float(scale), shape=shape,
                dtype=F32 if path in float32 else dt,
            )
        if kind:
            layers.setdefault(kind, {})[name] = leaf
        elif name in ("embed", "head", "final_norm"):
            params[name] = leaf
        else:
            layers[name] = leaf
    return params


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): scaled normals, norm
    weights 1, the selection bias normal * ROUTER_BIAS_SCALE in float32
    (`draw_params`)."""
    if cfg.tie_embeddings:
        raise ValueError(f"{cfg.name}: the afmoe family's head is untied")
    return draw_params(cfg, key, leaf_shapes(cfg), LEAF_KEYS,
                       float32=("moe.router_bias",))


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
                  n_layers: Optional[int] = None):
    """Zeroed dense cache: K/V of every layer."""
    if n_layers is not None and n_layers != cfg.n_layers:
        raise ValueError("an afmoe cache is not cut by layers (no pp)")
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_seq or cfg.max_seq_len,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.jnp_dtype),
            "v": jnp.zeros(shape, cfg.jnp_dtype)}


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params: Params, tokens, pos=0):
    """[B, T] -> [B, T, D] float32, times sqrt(D) under cfg.embed_scale."""
    del pos
    x = params["embed"][tokens].astype(F32)
    return x * (cfg.dim ** 0.5) if cfg.embed_scale else x


@jax.named_scope("head")
def unembed(cfg: ModelConfig, params: Params, x):
    """The last RMSNorm and the untied head: float32 logits over the held
    vocabulary rows."""
    h = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.jnp_dtype)
    return jax.lax.dot_general(
        h, params["head"], (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=F32,
    )


def attention(cfg: ModelConfig, lp: Params, h, cache_k, cache_v, pos, rope,
              mask, hook, layer):
    """The gated attention operator on normed h [B, T, D] (parameter
    dtype); returns (float32 [B, T, D], new cache_k, new cache_v). cfg: the
    layer's own view (its window, or none). rope: (cos, sin) on a sliding
    layer, None on a global one. cache_k / v: the layer's slices of the
    dense cache (layer None), or under a paged hook the layer's group's
    pool leaves and `layer`, the layer's index in them."""
    B, T, _ = h.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # (the products pass `pin_products` before their head split: a reshape
    # straight after a stacked weight's product is moved onto the weight,
    # and the compiler then relays the whole stack out once a launch)
    q, k, v = pin_products(h @ lp["wq"], h @ lp["wk"], h @ lp["wv"])
    q = q.reshape(B, T, H, Dh)
    k = k.reshape(B, T, KV, Dh)
    v = v.reshape(B, T, KV, Dh)
    gate = jax.nn.sigmoid(jnp.dot(h, lp["wg"], preferred_element_type=F32))
    if "q_norm" in lp:  # (models/solar_open2.py's gated layer has none)
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if rope is not None:
        q, k = apply_rope(q, k, *rope)
    attn, new_k, new_v = hook(
        cfg, q, k, v, cache_k, cache_v, pos, mask, None, None, None,
        *(() if layer is None else (layer,)),
    )
    gated = (gate * attn.reshape(B, T, H * Dh).astype(F32)).astype(h.dtype)
    return jnp.dot(gated, lp["wo"], preferred_element_type=F32), new_k, new_v


def moe_ffn(cfg: ModelConfig, lp: Params, banks: Params, layer: int, h,
            live=None):
    """The held experts' part and the shared expert (where the layer has
    one: its leaves ws_*) on normed h [B, T, D]: (float32 [B, T, D], tokens
    each held expert got [Eh], the live pairs that went to experts held
    elsewhere)."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    with jax.named_scope("moe_route"):
        chosen, weights = route(cfg, flat, lp["w_router"], lp["router_bias"])
    out, sizes = routed_ffn(cfg, banks, layer, flat, chosen, weights,
                            live=live, expert_lo=cfg.expert_lo)
    with jax.named_scope("moe_route"):
        pairs = chosen.shape[0] * chosen.shape[1] if live is None else \
            jnp.sum(live.astype(jnp.int32)) * chosen.shape[1]
        elsewhere = pairs - jnp.sum(sizes)
    if "ws_gate" in lp:
        with jax.named_scope("moe_shared"):
            out = out + swiglu(flat, lp["ws_gate"], lp["ws_up"],
                               lp["ws_down"])
    return out.reshape(B, T, D), sizes, elsewhere


def group_hooks(hook, two: bool) -> dict:
    """{group: its attention hook}: under a pool of two groups each group's
    half of the launch's block table (`hook.group`); else the one hook for
    both kinds."""
    if two:
        return {"global": hook.group(0, 2), "window": hook.group(1, 2)}
    return {"global": hook, "window": hook}


def add_routed(cache: dict, new: dict, sizes: list, away: list) -> dict:
    """`new` with the launch's routed counts added to the pool's "routed"
    leaf, where `cache` has one ([2, Lm, Eh (+ 1 under a share)]: pairs each
    held expert got and whether it got any, a layer; a share's last column
    the pairs that went elsewhere)."""
    if "routed" not in cache:
        return new
    sizes = jnp.stack(sizes)
    counts = jnp.stack([sizes, (sizes > 0).astype(jnp.int32)])
    if cache["routed"].shape[2] > sizes.shape[1]:  # a share
        away = jnp.stack(away).astype(jnp.int32)[:, None]
        counts = jnp.concatenate(
            [counts, jnp.stack([away, jnp.zeros_like(away)])], axis=2)
    new["routed"] = cache["routed"] + counts
    return new


def forward_layers(cfg: ModelConfig, layers: Params, x, cache, pos,
                   update_gate=None, tp_axis=None, attn_hook=None,
                   valid_start=None, ep_axis=None, attn_seq_len=None):
    """Every layer over a chunk x [B, T, D] (float32 residual). cache: the
    dense cache (`init_kv_cache`) or, under a paged hook (`attn_hook.paged`,
    engine/paged.py), the pool; with a "routed" leaf [2, Lm, Eh (+ 1 under
    a share)] int32 the expert layers add to it what they routed
    (models/mla_moe.forward_layers' contract; a share's last column counts
    the pairs that went elsewhere). pos: a scalar, or one position a row
    (the flat token layout). Returns (x, new cache)."""
    if tp_axis is not None or ep_axis is not None or update_gate is not None:
        raise ValueError("the afmoe family is not sharded over pp, tp or ep")
    if valid_start is not None:
        raise ValueError("the afmoe family takes no left-padded rows")
    T = x.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    paged = getattr(attn_hook, "paged", False)
    groups = cfg.kv_groups if paged else ("global",)
    two = len(groups) == 2
    W = cfg.attn_window
    full_cfg = cfg.replace(attn_window=None) if W is not None else cfg
    S = (attn_seq_len // len(groups) if attn_seq_len is not None
         else cache["k"].shape[3])
    if pos.ndim == 1:
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        masks = {None: slot_causal_mask(pos, T, S),
                 W: slot_causal_mask(pos, T, S, window=W)}
    else:
        positions = pos + jnp.arange(T, dtype=jnp.int32)
        masks = {None: causal_mask(pos, T, S),
                 W: causal_mask(pos, T, S, window=W)}
    with jax.named_scope("attn"):  # the rotary tables, once a forward
        rope = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    # each group's half of the launch's block table, and its pool leaves
    hooks = group_hooks(attn_hook or default_attn_hook, two)
    leaves = {"global": ("k", "v"), "window": ("kw", "vw") if two else ("k", "v")}
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
    at = {"global": 0, "window": 0}  # a layer's index in its group's leaves
    for li, kind in enumerate(cfg.layer_types):
        sliding = kind == "sliding_attention" and W is not None
        group = "window" if sliding and two else "global"
        routed = li >= cfg.first_k_dense
        with jax.named_scope("attn"):
            h = rms_norm(x, layers["norm1"][li], cfg.norm_eps).astype(dt)
            kn, vn = leaves[group]
            ia = at[group] if two else li
            at[group] += 1
            ck, cv = (new[kn], new[vn]) if paged else (new[kn][ia], new[vn][ia])
            out, ck, cv = attention(
                cfg if sliding else full_cfg, row("attn", li), h, ck, cv, pos,
                rope if sliding else None, masks[W if sliding else None],
                hooks[group], ia if paged else None,
            )
            new[kn] = ck if paged else new[kn].at[ia].set(ck)
            new[vn] = cv if paged else new[vn].at[ia].set(cv)
            out = rms_norm(out, layers["norm2"][li], cfg.norm_eps)
        with jax.named_scope("moe_route" if routed else "ffn"):
            x = x + out
            h = rms_norm(x, layers["norm3"][li], cfg.norm_eps).astype(dt)
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
        with jax.named_scope("moe_combine" if routed else "ffn"):
            out = rms_norm(out, layers["norm4"][li], cfg.norm_eps)
        with jax.named_scope("attn" if li + 1 < cfg.n_layers else "head"):
            x = x + out
    return x, add_routed(cache, new, sizes, away)


def forward(cfg: ModelConfig, params: Params, tokens, cache, pos):
    """Whole-model chunk forward: tokens [B, T] at offset pos -> (float32
    logits [B, T, V], new cache)."""
    x = embed(cfg, params, tokens)
    x, cache = forward_layers(cfg, params["layers"], x, cache, pos)
    return unembed(cfg, params, x), cache
