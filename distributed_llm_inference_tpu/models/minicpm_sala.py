"""Sparse attention beside decayed linear attention (openbmb/MiniCPM-SALA,
model_type minicpm_sala) in pure JAX.

The stack of two kinds of layer (cfg.layer_types) is models/stack.py's loop,
as is the tuple-a-layer draw; here the family's leaves, its selection, its
two mixers and their binding. RMSNorm eps cfg.norm_eps everywhere; x a
layer's input, D = cfg.dim, r = cfg.residual_multiplier (the family's
scale_depth / sqrt(the PUBLISHED depth)):

  embed     table[token] x cfg.embed_multiplier (scale_emb)
  layer l   h = x + r Mixer_l(RMSNorm_op(x));  y = h + r SwiGLU(RMSNorm_ffn(h))
  head      RMSNorm, the untied table, / cfg.logits_divider

  Mixer, "lightning-attn" (Hl = cfg.linear_heads heads of Dh):
            q, k, v = u wq, u wk, u wv; RMSNorm with a weight on every q and
            k head; RoPE (half-rotation) on q, k; q x Dh^-0.5; per head
            S_t = a_h S_{t-1} + k_t^T v_t (S [Dh, Dh] FLOAT32), o_t = q_t S_t
            (ops/linear_attention.py); Mixer = (RMSNorm(o) * sigmoid(u wg)) wo,
            the output norm over the heads side by side.
            STATE a row and layer: S, [Hl, Dh, Dh] float32.
  Mixer, "minicpm4" (H query heads, KV key/value heads of Dh, NO rotary):
            the same per-head RMSNorm on q and k; a query at position t
            (n = t + 1 positions visible) reads every position where
            n < cfg.sparse_dense_len, and otherwise the cfg.sparse_topk
            blocks of cfg.sparse_block tokens that the selection chooses
            for its KV head; causal softmax at Dh^-0.5 over what it reads;
            Mixer = (o * sigmoid(u wg)) wo.
            CACHE: K/V, and the compressed keys the selection scores
            against: c = mean of cfg.sparse_kernel consecutive keys, one
            every cfg.sparse_stride tokens, a key head each.

The selection (ops/sparse_select.select_blocks: one Pallas program a query
tile, over the row's compressed keys where the pool holds them, through the
block table), per query and KV head: p^h = softmax over
the compressed keys whose tokens all lie at or before t of q^h . c / sqrt(Dh);
r = the sum of p^h over the KV head's query heads; a block's score is the
largest r among the compressed keys whose tokens overlap it; the first
cfg.sparse_init_blocks blocks and the blocks that hold the last
cfg.sparse_window positions score +inf; the sparse_topk highest are read,
ties to the lower block.

The family is served from the paged pool alone (engine/paged.py), with
cfg.sparse_block tokens a pool block, so that a page of the paged kernels'
walk is one block of the selection. The pool's leaves:
  "k" / "v"  [Ls, N, KV, bs, Dh]      the sparse layers' K/V
  "ck"       a leaf a sparse layer, [N, rows, Dh]: a block's compressed keys
             a whole tile of the pool's dtype (16 rows of
             bfloat16, the KV x bs / stride = 8 keys and 8 rows of zeros),
             so that the scoring's kernel (ops/sparse_select.py, the leaf's
             one reader) copies a block's keys as it copies a K/V page. The
             compressed key whose LAST token is position e (e % stride ==
             stride - 1) sits with the block that holds e, KV head kv's at
             row kv x bs / stride + (e % bs) // stride: every token it
             covers lies at or before its block's end, so a block shared by
             the prefix index brings its compressed keys with it, and the
             launch that writes e (`compressed_keys`) has all of them (this
             launch's keys, or the pool's)
  "lin"      a leaf a linear layer, [slots, Hl, Dh, Dh] float32: a slot's
             live state
  "snap"     a leaf a linear layer, [snapshots, Hl, Dh, Dh] float32: states
             kept at block boundaries, which a prefix hit starts a row from
             (engine/paged.StateRows.restore / .take)
A leaf a layer (a tuple of them), and not one stacked over the layers as K/V
are for the kernels: a step replaces a layer's state whole, and an update in
place of one layer of a stacked leaf made the decode loop copy the leaf.

Params pytree (L layers, Ls / Ll sparse / linear layers, F ffn_dim, V vocab):
  embed [V, D]   lm_head [V, D]   final_norm [D]
  layers: op_norm ffn_norm [L, D]
    sparse: w_in [Ls, D, (2 H + 2 KV) Dh] = [wq | wg | wk | wv]
            wo [Ls, H*Dh, D]  q_norm k_norm [Ls, Dh]
    linear: w_in [Ll, D, 4 Hl*Dh] = [wq | wk | wv | wg]  wo [Ll, Hl*Dh, D]
            q_norm k_norm [Ll, Dh]  o_norm [Ll, Hl*Dh]
    ffn:    w_gate w_up [L, D, F]  w_down [L, F, D]
each leaf of a kind a TUPLE of its layers' arrays (the shapes above without
their first axis): the stack is not scanned, and a layer's slice of a
stacked matrix inside the decode chunk's loop cost a copy of the stack. A
mixer's input projections are drawn one by one (`leaf_shapes`, LEAF_KEYS:
wq, wk, wv, wg) and held side by side as ONE matrix `w_in`: one product a
mixer, and the decode chunk relaid each [D, D] projection out once a launch
where it leaves a [D, 4 D] one alone.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from . import stack
from ..config import ModelConfig
from ..ops import sparse_select
from ..ops.flash_attention import resolve_interpret
from ..ops.linear_attention import linear_attend_rows
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_cos_sin
from .stack import embed, unembed  # noqa: F401 - the family's ends

Params = dict
F32 = jnp.float32

# init_params' key of each drawn leaf: an index into split(key, 24)
# (cellbench/reference/sparse_linear_hybrid.py writes the same table down)
LEAF_KEYS = {
    "embed": 0, "lm_head": 1,
    "sparse.wq": 2, "sparse.wk": 3, "sparse.wv": 4, "sparse.wo": 5,
    "sparse.wg": 6,
    "linear.wq": 7, "linear.wk": 8, "linear.wv": 9, "linear.wo": 10,
    "linear.wg": 11,
    "ffn.w_gate": 12, "ffn.w_up": 13, "ffn.w_down": 14,
}


# a mixer's input projections in the order `w_in` holds them
W_IN = {"sparse": ("wq", "wg", "wk", "wv"), "linear": ("wq", "wk", "wv", "wg")}


def stack_depths(cfg: ModelConfig) -> dict:
    return {"sparse": len(cfg.attn_layers), "linear": len(cfg.linear_layers)}


def leaf_shapes(cfg: ModelConfig) -> dict:
    """{leaf path: (shape, init scale or None for ones)}, stacked leaves
    with their layer axis first."""
    D, V, L = cfg.dim, cfg.vocab_size, cfg.n_layers
    Dh, Hl = cfg.head_dim, cfg.linear_heads
    n = stack_depths(cfg)
    Ls, Ll = n["sparse"], n["linear"]
    s = D ** -0.5
    return {
        "embed": ((V, D), 0.02), "lm_head": ((V, D), s),
        "final_norm": ((D,), None),
        "op_norm": ((L, D), None), "ffn_norm": ((L, D), None),
        **stack.attn_shapes("sparse", Ls, D, cfg.n_heads, cfg.n_kv_heads, Dh,
                            gate=True, qk_norm=True),
        **stack.attn_shapes("linear", Ll, D, Hl, Hl, Dh, gate=True,
                            qk_norm=True),
        "linear.o_norm": ((Ll, Hl * Dh), None),
        **stack.ffn_shapes("ffn", L, D, cfg.ffn_dim),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks):
    `stack.draw_layer_tuples`; the head is untied."""
    return stack.draw_layer_tuples(cfg, key, leaf_shapes(cfg), LEAF_KEYS, W_IN)


# -- the selection ------------------------------------------------------------


def tile_meta(cfg: ModelConfig, rows, pos, tq: int):
    """The scoring kernel's scalars, once a forward: [G, 4] int32 a query
    tile of tq flat tokens, (fleet row, the tile's first position, its live
    queries, the blocks that hold the row up to its LAST position in this
    launch: the compressed keys a query of the launch may score)."""
    tok_row, (R, MB) = rows.tok_row, rows.table.shape
    live = tok_row >= 0
    rix = jnp.maximum(tok_row, 0)
    last = jnp.full((R,), -1, jnp.int32).at[rix].max(jnp.where(live, pos, -1))
    tile_live = live.reshape(-1, tq)
    row = jnp.maximum(jnp.max(tok_row.reshape(-1, tq), axis=1), 0)
    far = jnp.iinfo(jnp.int32).max
    start = jnp.min(jnp.where(tile_live, pos.reshape(-1, tq), far), axis=1)
    n = jnp.sum(tile_live, axis=1, dtype=jnp.int32)
    return jnp.stack([row, jnp.where(n > 0, start, 0), n,
                      jnp.minimum(last[row] // cfg.sparse_block + 1, MB)],
                     axis=1)


def page_lists(chosen, width: int):
    """The paged kernels' operand of a selected read: chosen
    [G, tq, KV, MB] bool -> (plist [G, KV, width] int32: the logical pages
    some query of the tile chose, ascending, count [G, KV] how many, and
    chosen_at [G * tq, KV, width] bool: the query chose list entry l).
    width: a static bound on a tile's union, a multiple of 128."""
    G, tq, KV, MB = chosen.shape
    union = jnp.any(chosen, axis=1)  # [G, KV, MB]
    count = jnp.minimum(jnp.sum(union, axis=-1), width).astype(jnp.int32)
    # list entry l is the chosen page of rank l (ascending): its one-hot row
    # over the pages, with no sort and no gather (each took milliseconds of
    # a mixed step on the chip)
    rank = jnp.cumsum(union, axis=-1, dtype=jnp.int32) - 1
    entry = union[:, :, None, :] & (
        rank[:, :, None, :] == jnp.arange(width, dtype=jnp.int32)[:, None]
    )  # [G, KV, width, MB]
    plist = jnp.sum(jnp.where(entry, jnp.arange(MB, dtype=jnp.int32), 0),
                    axis=-1)
    chosen_at = jnp.einsum("gtkb,gklb->gtkl", chosen.astype(jnp.bfloat16),
                           entry.astype(jnp.bfloat16),
                           preferred_element_type=F32) > 0.5
    return plist, count, chosen_at.reshape(G * tq, KV, width)


def list_width(cfg: ModelConfig, tq: int, MB: int) -> int:
    """The bound `page_lists` is given: a tile of tq queries reads at most
    tq x sparse_topk pages past the dense length and every page below it."""
    most = max(tq * cfg.sparse_topk,
               -(-cfg.sparse_dense_len // cfg.sparse_block))
    return -(-min(most, MB) // 128) * 128


@jax.named_scope("sparse_select")
def compressed_keys(cfg: ModelConfig, k, pool_k, pool_ck, layer: int, rows,
                    pos):
    """Write the compressed keys that END at this launch's tokens. k
    [W, KV, Dh]: the launch's new keys (normed); pool_k [Ls, N, KV, bs, Dh]
    does not hold them yet (`layer` this layer's index in it), pool_ck
    [N, rows, Dh] this layer's leaf (module docstring). A key window's
    tokens are this launch's (side by side on the flat axis where they are
    the same row's) or older ones of the row, read from the pool's current
    and previous block. Returns the leaf."""
    W, KV, Dh = k.shape
    bs, stride, kernel = cfg.sparse_block, cfg.sparse_stride, cfg.sparse_kernel
    tok_row, table = rows.tok_row, rows.table
    MB = table.shape[1]
    live = tok_row >= 0
    rix = jnp.maximum(tok_row, 0)
    lblk = jnp.minimum(pos // bs, MB - 1)
    cur = table[rix, lblk]
    prev = table[rix, jnp.maximum(lblk - 1, 0)]
    ends = live & (pos % stride == stride - 1) & (pos >= kernel - 1)
    # the two blocks' keys, oldest first: [W, 2 bs, KV, Dh]
    old = jnp.concatenate(
        [pool_k[layer, prev], pool_k[layer, cur]], axis=2
    ).transpose(0, 2, 1, 3).astype(F32)
    back = jnp.arange(kernel, dtype=jnp.int32)
    # the token j back: this launch's where it is the same row's (a row's
    # tokens lie side by side on the flat axis), else the pool's
    at = (bs + pos % bs)[:, None] - back[None, :]  # in `old`: >= 0
    was = jnp.take_along_axis(old, at[:, :, None, None], axis=1)
    flat = jnp.arange(W, dtype=jnp.int32)[:, None] - back[None, :]
    same = live[:, None] & (flat >= 0) & (
        tok_row[jnp.maximum(flat, 0)] == tok_row[:, None])
    now = k.astype(F32)[jnp.maximum(flat, 0)]  # [W, kernel, KV, Dh]
    total = jnp.sum(jnp.where(same[:, :, None, None], now, was), axis=1)
    mean = total / kernel
    slots, N, RB = bs // stride, *pool_ck.shape[:2]
    # the leaf's rows side by side: KV head kv's key at row kv x slots + slot
    # of its block (out of range: dropped)
    at = jnp.where(ends, cur * RB + (pos % bs) // stride, N * RB)[:, None] + (
        jnp.arange(KV, dtype=jnp.int32) * slots)[None, :]
    return pool_ck.reshape(N * RB, Dh).at[at].set(
        mean.astype(pool_ck.dtype), mode="drop").reshape(N, RB, Dh)


# -- the mixers ---------------------------------------------------------------


def _project(cfg, lp, h, kind: str, heads_q: int, heads_k: int):
    """(q, k, v normed where the family norms them, each [W, 1, heads, Dh],
    and the gate's logits [W, heads_q x Dh] float32) of normed h [W, D]: one
    product with `w_in`, cut in W_IN's order."""
    W, Dh = h.shape[0], cfg.head_dim
    width = {"wq": heads_q, "wg": heads_q, "wk": heads_k, "wv": heads_k}
    cuts, at = {}, 0
    out = stack.project(lp, h)
    for name in W_IN[kind]:
        cuts[name] = out[:, at:at + width[name] * Dh]
        at += width[name] * Dh
    dt = cfg.jnp_dtype
    q = cuts["wq"].astype(dt).reshape(W, 1, heads_q, Dh)
    k = cuts["wk"].astype(dt).reshape(W, 1, heads_k, Dh)
    v = cuts["wv"].astype(dt).reshape(W, 1, heads_k, Dh)
    q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
    k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    return q, k, v, cuts["wg"]


def sparse_attention(cfg: ModelConfig, c, lp: Params, h, pool, layer: int):
    """The "minicpm4" mixer over a paged launch's flat tokens: normed h
    [W, 1, D] at positions c.pos [W] (c: `_prepare`'s; c.tiles `tile_meta`
    of the launch). Returns (float32 [W, 1, D], pool)."""
    W, rows, pos, tq = h.shape[0], c.rows, c.pos, c.tile
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v, gate = _project(cfg, lp, h[:, 0], "sparse", H, KV)
    MB = rows.table.shape[1]
    with jax.named_scope("sparse_select"):
        G = W // tq
        ck_pool = compressed_keys(cfg, k[:, 0], pool["k"],
                                  pool["ck"][layer], layer, rows, pos)
        _, chosen = sparse_select.select_blocks(
            q[:, 0].reshape(G, tq, KV, H // KV, Dh), ck_pool, rows.table,
            c.tiles, block=cfg.sparse_block, stride=cfg.sparse_stride,
            kernel=cfg.sparse_kernel, topk=cfg.sparse_topk,
            window=cfg.sparse_window, init=cfg.sparse_init_blocks,
            dense_len=cfg.sparse_dense_len,
            interpret=resolve_interpret(None))
        chosen &= (rows.tok_row >= 0).reshape(G, tq, 1, 1)
        pages = page_lists(chosen, list_width(cfg, tq, MB))
    attn, new_k, new_v = c.hook(
        cfg, q, k, v, pool["k"], pool["v"], pos, None, None, None, None,
        layer, pages=pages,
    )
    y = (attn.reshape(W, H * Dh).astype(F32)
         * jax.nn.sigmoid(gate)).astype(cfg.jnp_dtype)
    out = jnp.dot(y, lp["wo"], preferred_element_type=F32)
    return out[:, None], {**pool, "k": new_k, "v": new_v,
                          "ck": stack.put(pool["ck"], layer, ck_pool)}


def linear_attention(cfg: ModelConfig, c, lp: Params, h, pool, layer: int):
    """The "lightning-attn" mixer over a paged launch's flat tokens (normed
    h [W, 1, D]; c: `_prepare`'s). A row that starts a tenant (rows.fresh) starts from zeros,
    or from snapshot rows.restore after a prefix hit, never from what the
    slot's previous tenant left; a row with rows.take >= 0 leaves its state
    after this launch in that snapshot. Returns (float32 [W, 1, D], pool)."""
    W, rows = h.shape[0], c.rows
    Hl, Dh = cfg.linear_heads, cfg.head_dim
    q, k, v, gate = _project(cfg, lp, h[:, 0], "linear", Hl, Hl)
    q, k = apply_rope(q, k, c.cos, c.sin)
    q = (q.astype(F32) * Dh ** -0.5).astype(cfg.jnp_dtype)
    lin, snap = pool["lin"][layer], pool["snap"][layer]

    start = lin
    if rows.restore is not None:  # a mixed launch: rows may start tenants
        def restored():
            held = snap[jnp.clip(rows.restore, 0, snap.shape[0] - 1)]
            first = jnp.where((rows.restore >= 0)[:, None, None, None], held,
                              0.0)
            return jnp.where(rows.fresh[:, None, None, None], first, lin)

        start = jax.lax.cond(jnp.any(rows.fresh), restored, lambda: lin)
    # (a decode step is the same call: one token a row, a tile each)
    o, lin = linear_attend_rows(q[:, 0], k[:, 0], v[:, 0], start,
                                rows.tok_row, c.tile)
    if rows.take is not None:
        at = jnp.where(rows.take >= 0, rows.take, snap.shape[0])  # dropped
        snap = jax.lax.cond(
            jnp.any(rows.take >= 0),
            lambda: snap.at[at].set(lin, mode="drop"), lambda: snap)
    o = rms_norm(o.reshape(W, Hl * Dh), lp["o_norm"], cfg.norm_eps)
    y = (o.astype(F32) * jax.nn.sigmoid(gate)).astype(cfg.jnp_dtype)
    out = jnp.dot(y, lp["wo"], preferred_element_type=F32)
    return out[:, None], {**pool, "lin": stack.put(pool["lin"], layer, lin),
                          "snap": stack.put(pool["snap"], layer, snap)}


# -- the stack ----------------------------------------------------------------


def _prepare(cfg: ModelConfig, layers: Params, x, cache, pos, hook,
             attn_seq_len):
    """Once a forward: how the launch's flat tokens fall into fleet rows
    (engine/paged.StateRows), the launch's tile, the linear layers' rotary
    tables and the selection's scalars."""
    rows, tq = hook.rows(), hook.tile
    with jax.named_scope("linear_attn"):
        cos, sin = rope_cos_sin(pos[:, None], cfg.head_dim, cfg.rope_theta)
    with jax.named_scope("attn"), jax.named_scope("sparse_select"):
        tiles = tile_meta(cfg, rows, pos, tq)
    return SimpleNamespace(pos=pos, hook=hook, rows=rows, tile=tq, cos=cos,
                           sin=sin, tiles=tiles)


forward_layers = functools.partial(
    stack.forward_layers, norms=("op_norm", "ffn_norm"), prepare=_prepare,
    dense=("ffn", stack.rows_ffn), paged_only=True,
    kinds={"minicpm4": ("attn", "sparse", sparse_attention),
           "lightning-attn": ("linear_attn", "linear", linear_attention)})
init_kv_cache = forward = stack.paged_pool_only
