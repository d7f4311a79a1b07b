"""Gated short convolutions beside grouped-query attention over routed
experts (LiquidAI/LFM2-24B-A2B, model_type lfm2_moe) in pure JAX.

Layers of two kinds alternate in one stack (cfg.layer_types), so the layers
are not scanned: the stack is a Python loop over the pattern, each layer
reading its own row of the stacked leaves of its kind by a static index.
RMSNorm eps cfg.norm_eps everywhere; x a layer's input, D = cfg.dim:

  layer l   h = x + Op_l(RMSNorm_op(x));  y = h + FFN_l(RMSNorm_ffn(h))
  head      RMSNorm (the family's `embedding_norm`), then the tied table

  Op, "conv" (K = cfg.conv_kernel taps, no bias, no position encoding):
            [B | C | X] = u w_in            (D -> 3D, split in that order)
            z = B * X                       (the gated input)
            c_t = sum_j w_conv[j] * z_{t-(K-1)+j}   depthwise, causal; z of
                                            positions before the row's first
                                            token is 0
            Op = (C * c) w_out
            STATE a row and layer: z_{t-(K-1)} .. z_{t-1}
  Op, "full_attention": GQA (H query heads, KV key/value heads of Dh), a
            per-head RMSNorm with a weight on every query and key head
            before the rotation, RoPE (half-rotation), causal softmax at
            Dh^-0.5, no bias: the llama family's pieces.
  FFN       the first cfg.first_k_dense layers: SwiGLU of cfg.ffn_dim; the
            others: s = sigmoid(h w_router) in float32, the
            n_experts_per_tok largest of s + router_bias chosen, weights
            s / (sum of the chosen s + cfg.router_norm_eps) x
            routed_scaling, each token through its experts alone
            (models/experts.py). No shared expert.

The residual stream, every sublayer's output and the router's scores are
float32; matrix products take the parameter dtype in and float32 out; z is
rounded to the parameter dtype where it is made, so what a later launch
reads back from the state is what a neighbour in the same launch reads.

The cache has two kinds of leaf. "k" / "v" hold the ATTENTION layers alone
(their index among the attention layers is the leaf's layer axis): dense
[La, B, KV, S, Dh], or the paged pool [La, N, KV / pack, bs, pack x Dh]
with cfg.kv_pack heads side by side on the 128 lanes (`pack_heads`), so
the paged kernels write head dim 64 in place. "conv" is the convolution
layers' state: dense [Lc, B, K-1, D]; paged [Lc, slots, K-1, D] beside
"tail" [Lc, N, K-1, D], one state a pool block (the last K-1 gated inputs
of the block), which a prefix hit restores the slot's state from. The
paged hooks say how a launch's flat tokens fall into rows
(`attn_hook.rows`, engine/paged.StateRows).

Params pytree (L layers, Lc / La conv / attention layers, Ld / Lm dense /
expert layers, E experts, F ffn_dim, Fm moe_ffn_dim, V vocab):
  embed [V, D] (also the head)   final_norm [D]
  layers: op_norm ffn_norm [L, D]
    conv:  w_in [Lc, D, 3D]  w_conv [Lc, K, D]  w_out [Lc, D, D]
    attn:  wq [La, D, H*Dh]  wk wv [La, D, KV*Dh]  wo [La, H*Dh, D]
           q_norm k_norm [La, Dh]
    dense: w_gate w_up [Ld, D, F]  w_down [Ld, F, D]
    moe:   w_router [Lm, D, E]  router_bias [Lm, E] float32
           w_gate w_up [Lm, E, D, Fm]  w_down [Lm, E, Fm, D]
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import causal_mask, slot_causal_mask
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_cos_sin
from .experts import BANKS, _normal_slices, route, routed_ffn
from .llama import default_attn_hook
from .mla_moe import ROUTER_BIAS_SCALE, swiglu

Params = dict
F32 = jnp.float32

# init_params' key of each drawn leaf: an index into split(key, 24)
# (cellbench/reference/conv_hybrid_moe.py writes the same table down)
LEAF_KEYS = {
    "embed": 0,
    "conv.w_in": 1, "conv.w_conv": 2, "conv.w_out": 3,
    "attn.wq": 4, "attn.wk": 5, "attn.wv": 6, "attn.wo": 7,
    "dense.w_gate": 8, "dense.w_up": 9, "dense.w_down": 10,
    "moe.w_router": 11, "moe.router_bias": 12,
    "moe.w_gate": 13, "moe.w_up": 14, "moe.w_down": 15,
}


def stack_depths(cfg: ModelConfig) -> dict:
    """Layers of each kind: conv + attn = dense + moe = cfg.n_layers."""
    return {"conv": len(cfg.conv_layers), "attn": len(cfg.attn_layers),
            "dense": cfg.first_k_dense,
            "moe": cfg.n_layers - cfg.first_k_dense}


def leaf_shapes(cfg: ModelConfig) -> dict:
    """{leaf path: (shape, init scale or None for ones)}, stacked leaves
    with their layer axis first."""
    D, V, K = cfg.dim, cfg.vocab_size, cfg.conv_kernel
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, Fm, F = cfg.n_experts, cfg.moe_ffn_dim, cfg.ffn_dim
    n = stack_depths(cfg)
    Lc, La, Ld, Lm = n["conv"], n["attn"], n["dense"], n["moe"]
    s = D ** -0.5
    return {
        "embed": ((V, D), 0.02), "final_norm": ((D,), None),
        "op_norm": ((cfg.n_layers, D), None),
        "ffn_norm": ((cfg.n_layers, D), None),
        "conv.w_in": ((Lc, D, 3 * D), s),
        "conv.w_conv": ((Lc, K, D), K ** -0.5),
        "conv.w_out": ((Lc, D, D), s),
        "attn.wq": ((La, D, H * Dh), s), "attn.wk": ((La, D, KV * Dh), s),
        "attn.wv": ((La, D, KV * Dh), s),
        "attn.wo": ((La, H * Dh, D), (H * Dh) ** -0.5),
        "attn.q_norm": ((La, Dh), None), "attn.k_norm": ((La, Dh), None),
        "dense.w_gate": ((Ld, D, F), s), "dense.w_up": ((Ld, D, F), s),
        "dense.w_down": ((Ld, F, D), F ** -0.5),
        "moe.w_router": ((Lm, D, E), s),
        "moe.router_bias": ((Lm, E), ROUTER_BIAS_SCALE),
        "moe.w_gate": ((Lm, E, D, Fm), s), "moe.w_up": ((Lm, E, D, Fm), s),
        "moe.w_down": ((Lm, E, Fm, D), Fm ** -0.5),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): scaled normals drawn
    slice by slice (models/experts._normal_slices), norm weights 1, the
    selection bias normal * ROUTER_BIAS_SCALE in float32. The embedding is
    the head too (tied). A kind with no layer keeps empty leaves."""
    if not cfg.tie_embeddings:
        raise ValueError(f"{cfg.name}: the lfm2 family ties its embeddings")
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 24)
    layers: Params = {"conv": {}, "attn": {}, "dense": {}, "moe": {}}
    params: Params = {"layers": layers}
    for path, (shape, scale) in leaf_shapes(cfg).items():
        if scale is None:
            leaf = jnp.ones(shape, dt)
        elif 0 in shape:
            leaf = jnp.zeros(shape, dt)
        else:
            # the vocabulary table is drawn as 8 slices of rows
            cut = 8 if path == "embed" and shape[0] % 8 == 0 else None
            leaf = _normal_slices(
                ks[LEAF_KEYS[path]], scale=float(scale),
                shape=(cut, shape[0] // cut) + shape[1:] if cut else shape,
                dtype=F32 if path == "moe.router_bias" else dt,
            ).reshape(shape)
        kind, _, name = path.rpartition(".")
        if kind:
            layers[kind][name] = leaf
        elif name in ("embed", "final_norm"):
            params[name] = leaf
        else:
            layers[name] = leaf
    return params


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
                  n_layers: Optional[int] = None):
    """Zeroed dense cache: K/V of the attention layers alone and the
    convolution layers' state (module docstring)."""
    if n_layers is not None and n_layers != cfg.n_layers:
        raise ValueError("an lfm2 cache is not cut by layers (no pp)")
    S = max_seq or cfg.max_seq_len
    n = stack_depths(cfg)
    kv = (n["attn"], batch, cfg.n_kv_heads, S, cfg.head_dim)
    dt = cfg.jnp_dtype
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "conv": jnp.zeros((n["conv"], batch, cfg.conv_kernel - 1, cfg.dim), dt),
    }


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params: Params, tokens, pos=0):
    """[B, T] -> [B, T, D], float32: the residual stream's dtype."""
    del pos
    return params["embed"][tokens].astype(F32)


@jax.named_scope("head")
def unembed(cfg: ModelConfig, params: Params, x):
    """The last RMSNorm and the tied table: float32 logits."""
    h = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.jnp_dtype)
    return jax.lax.dot_general(
        h, params["embed"], (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=F32,
    )


# -- attention ----------------------------------------------------------------


def pack_heads(q, k, v, pack: int):
    """The paged pool's row of `pack` K/V heads side by side
    (engine/paged.init_pool): k / v [B, T, KV, Dh] -> [B, T, KV / pack,
    pack x Dh], and each query head zero-extended to that row on its own
    K/V head's part, so a score over the row is the head's own (the zero
    lanes add nothing). The kernels then see KV / pack heads of pack x Dh
    with `pack` times the group: head dim 64 on whole 128-lane tiles."""
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    part = (jnp.arange(H) // (H // KV)) % pack  # a query head's part of the row
    q = jnp.concatenate(
        [jnp.where((part == i)[:, None], q, jnp.zeros_like(q))
         for i in range(pack)], axis=-1,
    )
    wide = k.shape[:2] + (KV // pack, pack * Dh)
    return q, k.reshape(wide), v.reshape(wide), part


def unpack_heads(out, part, pack: int):
    """The kernels' output [B, T, H, pack x Dh] cut to each query head's own
    part of the value row."""
    Dh = out.shape[-1] // pack
    pieces = out.reshape(out.shape[:-1] + (pack, Dh))
    return jnp.take_along_axis(
        pieces, part[None, None, :, None, None], axis=3
    )[..., 0, :]


def attention(cfg: ModelConfig, lp: Params, h, cache_k, cache_v, pos, cos,
              sin, mask, hook, layer):
    """The attention operator on normed h [B, T, D] (parameter dtype);
    returns (float32 [B, T, D], new cache_k, new cache_v). cache_k / v: the
    layer's slices of the dense cache (layer None), or under a paged hook
    the whole pool leaves and `layer`, the layer's index in them."""
    B, T, _ = h.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ lp["wq"]).reshape(B, T, H, Dh)
    k = (h @ lp["wk"]).reshape(B, T, KV, Dh)
    v = (h @ lp["wv"]).reshape(B, T, KV, Dh)
    q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
    k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q, k = apply_rope(q, k, cos, sin)
    pack = cfg.kv_pack if layer is not None else 1
    if pack > 1:
        q, k, v, part = pack_heads(q, k, v, pack)
    attn, new_k, new_v = hook(
        cfg, q, k, v, cache_k, cache_v, pos, mask, None, None, None,
        *(() if layer is None else (layer,)),
    )
    if pack > 1:
        attn = unpack_heads(attn, part, pack)
    out = jnp.dot(attn.reshape(B, T, H * Dh), lp["wo"],
                  preferred_element_type=F32)
    return out, new_k, new_v


# -- the gated short convolution ----------------------------------------------


def _taps(cfg: ModelConfig, lp: Params, gate_c, window):
    """c_t = sum_j w_conv[j] * window[j] in float32, gated and projected:
    (C * c) w_out. window: the K gated inputs z_{t-K+1} .. z_t, oldest
    first, each shaped like gate_c."""
    w = lp["w_conv"].astype(F32)
    c = sum(w[j] * z.astype(F32) for j, z in enumerate(window))
    y = (gate_c.astype(F32) * c).astype(cfg.jnp_dtype)
    return jnp.dot(y, lp["w_out"], preferred_element_type=F32)


def _gates(cfg: ModelConfig, lp: Params, h):
    """(z = B * X rounded to the parameter dtype, C) of normed h [..., D]."""
    D = cfg.dim
    bcx = jnp.dot(h, lp["w_in"], preferred_element_type=F32)
    z = (bcx[..., :D] * bcx[..., 2 * D:]).astype(cfg.jnp_dtype)
    return z, bcx[..., D:2 * D]


def _same_row(tok_row, j: int):
    """[W] bool: flat token w - j exists and is of token w's row."""
    W = tok_row.shape[0]
    before = jnp.pad(tok_row, (j, 0), constant_values=-2)[:W]
    return before == tok_row


def conv_mix_rows(cfg: ModelConfig, lp: Params, h, state, tail, layer, rows,
                  pos, bs: int):
    """The convolution operator over a paged launch's FLAT tokens: normed h
    [W, 1, D], one token a batch row at position pos [W]; rows
    (engine/paged.StateRows) says which fleet row each token belongs to
    (-1: launch padding, a dead row) and which rows start a new tenant.
    state [Lc, R, K-1, D] / tail [Lc, N, K-1, D] are the pool's leaves,
    `layer` this layer's (static) index in them, bs the pool's block size.

    A row's tokens lie side by side on the flat axis, so token w's j-th
    predecessor is flat token w - j where that is the same row's, and
    otherwise comes from the row's state: the slot's live state, or for a
    row that starts a tenant zeros (a cold start) or the tail of the block
    below its first position (a prefix hit). The row boundary decides,
    never the flat index. A row's last token leaves the new live state;
    a token that fills its block's last position leaves the block's tail.
    Returns (float32 [W, 1, D], state, tail)."""
    W, K = h.shape[0], cfg.conv_kernel
    tok_row, table = rows.tok_row, rows.table
    R = table.shape[0]
    z, gate_c = _gates(cfg, lp, h[:, 0])  # [W, D]
    live = tok_row >= 0
    rix = jnp.maximum(tok_row, 0)

    def back(a, j):  # a[w - j], zeros before the axis' start
        return jnp.pad(a, [(j, 0)] + [(0, 0)] * (a.ndim - 1))[:W]

    # same[j - 1][w]: flat token w - j is the same row's (side by side, so
    # same[j] implies same[j - 1]); dist: predecessors in this launch
    same = [live & _same_row(tok_row, j) for j in range(1, K)]
    dist = sum(s.astype(jnp.int32) for s in same)
    # the state each row starts this launch from
    below = table[jnp.arange(R), jnp.maximum(rows.start - 1, 0) // bs]
    first = jnp.where(
        (rows.start > 0)[:, None, None], tail[layer, below], 0
    ).astype(z.dtype)
    start = jnp.where(rows.fresh[:, None, None], first,
                      state[layer].astype(z.dtype))  # [R, K-1, D]
    # prev[j - 1][w] = z_{t-j} of token w
    prev = [
        jnp.where(same[j - 1][:, None], back(z, j),
                  start[rix, jnp.clip(K - 1 - j + dist, 0, K - 2)])
        for j in range(1, K)
    ]
    out = _taps(cfg, lp, gate_c, prev[::-1] + [z])

    def history(at):  # the K-1 gated inputs up to and with token `at`
        return jnp.stack([p[at] for p in prev[:K - 2][::-1]] + [z[at]],
                         axis=1)

    flat = jnp.arange(W, dtype=jnp.int32)
    last = jnp.full((R,), -1, jnp.int32).at[rix].max(
        jnp.where(live, flat, -1))
    new = jnp.where((last >= 0)[:, None, None],
                    history(jnp.maximum(last, 0)), state[layer])
    state = state.at[layer].set(new.astype(state.dtype))
    # tokens that fill a block's last position: at most W // bs + R of them
    ends = live & (pos % bs == bs - 1)
    (at,) = jnp.nonzero(ends, size=min(W, W // bs + R), fill_value=W)
    ok = at < W
    at = jnp.minimum(at, W - 1)
    blk = jnp.where(ok, table[rix[at], jnp.minimum(pos[at] // bs,
                                                   table.shape[1] - 1)],
                    tail.shape[1])  # out of range: dropped
    tail = tail.at[layer, blk].set(history(at).astype(tail.dtype),
                                   mode="drop")
    return out[:, None], state, tail


def conv_mix(cfg: ModelConfig, lp: Params, h, state):
    """The operator over whole rows of the dense cache: normed h [B, T, D],
    state [B, K-1, D] the rows' last gated inputs. The rows go side by side
    on `conv_mix_rows`' flat axis, each carrying on from its state, in one
    block that never ends (no tail is written): ONE implementation of the
    state and the taps. Returns (float32 [B, T, D], the new state)."""
    B, T, D = h.shape
    rows = SimpleNamespace(
        tok_row=jnp.repeat(jnp.arange(B, dtype=jnp.int32), T),
        table=jnp.zeros((B, 1), jnp.int32), fresh=jnp.zeros((B,), bool),
        start=jnp.zeros((B,), jnp.int32),
    )
    out, new, _ = conv_mix_rows(
        cfg, lp, h.reshape(B * T, 1, D), state[None],
        jnp.zeros((1, 1) + state.shape[1:], state.dtype), 0, rows,
        jnp.zeros((B * T,), jnp.int32), 1 << 30,
    )
    return out.reshape(B, T, D), new[0]


# -- feed-forward -------------------------------------------------------------


def moe_ffn(cfg: ModelConfig, lp: Params, banks: Params, layer: int, h,
            live=None):
    """Routed experts on normed h [B, T, D]: (float32 [B, T, D], routed
    tokens an expert [E]). Every expert is held here."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    with jax.named_scope("moe_route"):
        chosen, weights = route(cfg, flat, lp["w_router"], lp["router_bias"])
    out, sizes = routed_ffn(cfg, banks, layer, flat, chosen, weights,
                            live=live)
    return out.reshape(B, T, D), sizes


# -- the stack ----------------------------------------------------------------


def forward_layers(cfg: ModelConfig, layers: Params, x, cache, pos,
                   update_gate=None, tp_axis=None, attn_hook=None,
                   valid_start=None, ep_axis=None, attn_seq_len=None):
    """Every layer over a chunk x [B, T, D] (float32 residual). cache: the
    dense cache (`init_kv_cache`) or, under a paged hook (`attn_hook.paged`,
    engine/paged.py), the pool with its "conv" / "tail" leaves; with a
    "routed" leaf [2, Lm, E] int32 the expert layers add to it what they
    routed (models/mla_moe.forward_layers' contract). pos: a scalar, or one
    position a row (the flat token layout). Returns (x, new cache)."""
    if tp_axis is not None or ep_axis is not None or update_gate is not None:
        raise ValueError("the lfm2 family is not sharded over pp, tp or ep")
    if valid_start is not None:
        raise ValueError(
            "the lfm2 family takes no left-padded rows: a pad token would "
            "enter a convolution layer's state"
        )
    T = x.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    paged = getattr(attn_hook, "paged", False)
    S = attn_seq_len if attn_seq_len is not None else cache["k"].shape[3]
    if pos.ndim == 1:
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        mask = slot_causal_mask(pos, T, S)
    else:
        positions = pos + jnp.arange(T, dtype=jnp.int32)
        mask = causal_mask(pos, T, S)
    with jax.named_scope("attn"):  # the rotary tables, once a forward
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    hook = attn_hook or default_attn_hook
    rows = attn_hook.rows() if paged else None
    # rows whose output nothing reads reach no expert (engine/paged's hooks
    # say which: launch padding, freed slots)
    live = getattr(attn_hook, "live", None)
    if live is not None and T > 1:
        live = jnp.repeat(live, T)
    dt = cfg.jnp_dtype
    banks = {name: layers["moe"][name] for name in BANKS}  # never sliced

    def row(kind, i):  # layer i's leaves of its kind's small stack
        return {name: leaf[i] for name, leaf in layers[kind].items()
                if not (kind == "moe" and name in BANKS)}

    new = dict(cache)
    sizes = []
    ic = ia = 0
    # the step's scopes (utils/tracing.STEP_SCOPES): an operator with the
    # norm in front of it; the residual add belongs to the block it feeds
    scope = {"conv": "conv_mix", "full_attention": "attn"}
    for li, kind in enumerate(cfg.layer_types):
        routed = li >= cfg.first_k_dense
        with jax.named_scope(scope[kind]):
            h = rms_norm(x, layers["op_norm"][li], cfg.norm_eps).astype(dt)
            if kind == "conv":
                lp = row("conv", ic)
                if paged:
                    out, new["conv"], new["tail"] = conv_mix_rows(
                        cfg, lp, h, new["conv"], new["tail"], ic, rows, pos,
                        cache["k"].shape[3],
                    )
                else:
                    out, state = conv_mix(cfg, lp, h, new["conv"][ic])
                    new["conv"] = new["conv"].at[ic].set(state)
                ic += 1
            else:
                # a paged hook takes the pool's leaves whole and the layer's
                # index in them; the dense cache is cut and put back here
                ck, cv = (new["k"], new["v"]) if paged else \
                    (new["k"][ia], new["v"][ia])
                out, ck, cv = attention(
                    cfg, row("attn", ia), h, ck, cv, pos, cos, sin, mask, hook,
                    ia if paged else None,
                )
                new["k"] = ck if paged else new["k"].at[ia].set(ck)
                new["v"] = cv if paged else new["v"].at[ia].set(cv)
                ia += 1
        with jax.named_scope("moe_route" if routed else "ffn"):
            x = x + out
            h = rms_norm(x, layers["ffn_norm"][li], cfg.norm_eps).astype(dt)
        if routed:
            im = li - cfg.first_k_dense
            out, counts = moe_ffn(cfg, row("moe", im), banks, im, h, live)
            sizes.append(counts)
        else:
            lp = row("dense", li)
            with jax.named_scope("ffn"):
                out = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        after = cfg.layer_types[li + 1:li + 2]
        with jax.named_scope(scope[after[0]] if after else "head"):
            x = x + out
    if "routed" in cache:
        sizes = jnp.stack(sizes)
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
