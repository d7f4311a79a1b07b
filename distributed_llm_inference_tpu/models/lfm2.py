"""Gated short convolutions beside grouped-query attention over routed
experts (LiquidAI/LFM2-24B-A2B, model_type lfm2_moe) in pure JAX.

The stack of two kinds of layer (cfg.layer_types) is models/stack.py's loop;
here its leaves, its two mixers and their binding. RMSNorm eps cfg.norm_eps
everywhere; x a layer's input, D = cfg.dim:

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
            others `stack.moe_ffn`: every expert held, no shared one.

z is rounded to the parameter dtype where it is made, so what a later launch
reads back from the state is what a neighbour in the same launch reads.

The cache has two kinds of leaf. "k" / "v" hold the ATTENTION layers alone
(their index among the attention layers is the leaf's layer axis): dense
[La, B, KV, S, Dh], or the paged pool [La, N, KV / pack, bs, pack x Dh]
with cfg.kv_pack heads side by side on the 128 lanes (`stack.pack_heads`), so
the paged kernels write head dim 64 in place. "conv" is the convolution
layers' state: dense [Lc, B, K-1, D]; paged [Lc, slots, K-1, D] beside
"tail" [Lc, N, K-1, D], one state a pool block (the last K-1 gated inputs
of the block), which a prefix hit restores the slot's state from. The
paged hooks say how a launch's flat tokens fall into rows
(`attn_hook.rows`, engine/paged.StateRows).

Params pytree (L layers, Lc / La conv / attention layers, V vocab):
  embed [V, D] (also the head)   final_norm [D]
  layers: op_norm ffn_norm [L, D]
    conv:  w_in [Lc, D, 3D]  w_conv [Lc, K, D]  w_out [Lc, D, D]
    attn:  wq [La, D, H*Dh]  wk wv [La, D, KV*Dh]  wo [La, H*Dh, D]
           q_norm k_norm [La, Dh]
    dense, moe: `stack.ffn_shapes`, `stack.moe_shapes`
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from . import stack
from ..config import ModelConfig
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_cos_sin
from .stack import embed, unembed  # noqa: F401 - the family's ends

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
    n = stack_depths(cfg)
    Lc, La = n["conv"], n["attn"]
    s = D ** -0.5
    return {
        "embed": ((V, D), 0.02), "final_norm": ((D,), None),
        "op_norm": ((cfg.n_layers, D), None),
        "ffn_norm": ((cfg.n_layers, D), None),
        "conv.w_in": ((Lc, D, 3 * D), s),
        "conv.w_conv": ((Lc, K, D), K ** -0.5),
        "conv.w_out": ((Lc, D, D), s),
        **stack.attn_shapes("attn", La, D, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim, qk_norm=True),
        **stack.ffn_shapes("dense", n["dense"], D, cfg.ffn_dim),
        **stack.moe_shapes(cfg, n["moe"]),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): `stack.draw_params`,
    every expert held, the selection bias in float32. The embedding is the
    head too (tied)."""
    return stack.draw_params(cfg, key, leaf_shapes(cfg), LEAF_KEYS,
                             float32=("moe.router_bias",), by_index=False)


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
                  n_layers: Optional[int] = None):
    """Zeroed dense cache: K/V of the attention layers alone and the
    convolution layers' state (module docstring)."""
    stack.whole_cache_only(cfg, n_layers)
    S = max_seq or cfg.max_seq_len
    n = stack_depths(cfg)
    kv = (n["attn"], batch, cfg.n_kv_heads, S, cfg.head_dim)
    dt = cfg.jnp_dtype
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "conv": jnp.zeros((n["conv"], batch, cfg.conv_kernel - 1, cfg.dim), dt),
    }


# -- attention ----------------------------------------------------------------


def attention(cfg: ModelConfig, lp: Params, h, cache_k, cache_v, pos, cos,
              sin, mask, hook, layer):
    """The attention operator on normed h [B, T, D] (parameter dtype);
    returns (float32 [B, T, D], new cache_k, new cache_v). cache_k / v and
    layer: `stack.cached`'s. (The products are split into heads unpinned,
    against models/stack.py's rule 1: the cell was measured so, and pinning
    them is a `perf_opt` change.)"""
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
        q, k, v, part = stack.pack_heads(q, k, v, pack)
    attn, new_k, new_v = hook(
        cfg, q, k, v, cache_k, cache_v, pos, mask, None, None, None,
        *(() if layer is None else (layer,)),
    )
    if pack > 1:
        attn = stack.unpack_heads(attn, part, pack)
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


# -- the stack ----------------------------------------------------------------


def _prepare(cfg: ModelConfig, layers: Params, x, cache, pos, hook,
             attn_seq_len):
    """What the two mixers share, once a forward: the mask and the rotary
    tables, the hook, and under a paged hook how the launch's flat tokens
    fall into rows (engine/paged.StateRows) and the pool's block size."""
    paged = getattr(hook, "paged", False)
    S = attn_seq_len if attn_seq_len is not None else cache["k"].shape[3]
    positions, (mask,) = stack.positions_and_masks(pos, x.shape[1], S)
    with jax.named_scope("attn"):
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    return SimpleNamespace(
        pos=pos, mask=mask, cos=cos, sin=sin, hook=hook, paged=paged,
        rows=hook.rows() if paged else None, bs=cache["k"].shape[3])


def _conv(cfg, c, lp, h, new, ic):
    if c.paged:
        out, new["conv"], new["tail"] = conv_mix_rows(
            cfg, lp, h, new["conv"], new["tail"], ic, c.rows, c.pos, c.bs)
    else:
        out, state = conv_mix(cfg, lp, h, new["conv"][ic])
        new["conv"] = new["conv"].at[ic].set(state)
    return out, new


def _attn(cfg, c, lp, h, new, ia):
    return stack.cached(
        new, ("k", "v"), ia, c.paged, lambda ck, cv, layer: attention(
            cfg, lp, h, ck, cv, c.pos, c.cos, c.sin, c.mask, c.hook, layer))


forward_layers = functools.partial(
    stack.forward_layers, norms=("op_norm", "ffn_norm"), prepare=_prepare,
    routed=True,
    kinds={"conv": ("conv_mix", "conv", _conv),
           "full_attention": ("attn", "attn", _attn)})
forward = functools.partial(stack.forward, forward_layers)
