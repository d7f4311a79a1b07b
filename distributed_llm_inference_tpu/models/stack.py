"""What the unrolled families share: one layer loop over cfg.layer_types, the
parts more than one of them uses, the seeded draws and the whole-model ends.

A family whose layers are of several KINDS in one stack (models/lfm2.py,
afmoe.py, minicpm_sala.py, granite_hybrid.py, mimo_v2.py, solar_open2.py)
cannot scan them: `forward_layers` is a Python loop over the pattern, each
layer reading its own row of its kind's small stack of leaves by a static
index. A family module is its leaves (`LEAF_KEYS`, `leaf_shapes`,
`stack_depths`, `init_params`), its mixers and a binding,
`functools.partial(stack.forward_layers, kinds=..., norms=..., ...)`, that
hands the loop data and callables, never its name: where two families
differ, the difference is an argument. It imports from here, experts.py,
ops/ and config.py, never from a sibling. The residual stream, every
sublayer's output and a router's scores are float32; matrix products take
the parameter dtype in and float32 out.

Three rules of an unrolled stack, each paid for on the chip:
 1. A product whose result is reshaped or sliced at once is PINNED first
    (models/llama.pin_products says what the compiler does otherwise; PR 48
    fell into it twice): a mixer calls `head_products` (before a head split)
    or `project` (one `w_in` product, cut after), not `h @ w` + reshape.
    (lfm2's attention predates the rule and is kept as it was measured.)
 2. A Pallas kernel called from here has a `jax.jit` of its own (ops/*:
    `ssm_scan`, `linear_scan`, `delta_state`, `delta_step`, `select_blocks`):
    the loop calls a mixer once a LAYER, and an unjitted kernel entry is
    traced once a call at every start (`setup_s` is an end-to-end metric).
 3. No kernel body under a Python loop: `lax.fori_loop(..., unroll=True)`
    traces it once and Mosaic unrolls it (PR 52). The one Python loop is the
    layer loop below, whose body is a layer.
"""

from __future__ import annotations

import functools
import math
from types import SimpleNamespace
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import causal_mask, slot_causal_mask
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope
from .experts import BANKS, normal_slices, route, routed_ffn
from .llama import default_attn_hook, pin_products

Params = dict
F32 = jnp.float32

# the scale a seeded draw gives the selection bias (a trained checkpoint
# brings its own): sigmoid scores of unit-variance logits lie a few
# hundredths apart near the k-th place, so this changes some choices
ROUTER_BIAS_SCALE = 0.05
# Mamba-2's own initialisation: a = -A uniform on A_INIT, dt log-uniform on
# DT_INIT (dt_bias its inverse softplus)
A_INIT = (1.0, 16.0)
DT_INIT = (0.001, 0.1)
TOP_LEAVES = ("embed", "head", "lm_head", "final_norm")  # the tree's own


# -- the seeded draws ---------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


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


def place_leaf(params: Params, path: str, leaf) -> None:
    """`leaf` into the tree at "kind.name" (layers[kind]), a bare name of
    TOP_LEAVES (the tree's own) or any other bare name (layers')."""
    kind, _, name = path.rpartition(".")
    layers = params.setdefault("layers", {})
    if kind:
        layers.setdefault(kind, {})[name] = leaf
    elif name in TOP_LEAVES:
        params[name] = leaf
    else:
        layers[name] = leaf


def _check_tied(cfg: ModelConfig, shapes: dict) -> None:
    if cfg.tie_embeddings != (not {"head", "lm_head"} & set(shapes)):
        raise ValueError(f"{cfg.name}: tie_embeddings={cfg.tie_embeddings} "
                         f"is not this family's tree (a head leaf, or none)")


def vocab_slices(key, shape, scale, dtype):
    """A vocabulary table drawn as 8 slices of rows where 8 divides it."""
    cut = 8 if shape[0] % 8 == 0 else 1
    return normal_slices(
        key, scale=float(scale), shape=(cut, shape[0] // cut) + shape[1:],
        dtype=dtype).reshape(shape)


def draw_params(cfg: ModelConfig, key: jax.Array, shapes: dict,
                leaf_keys: dict, float32: tuple = (), n_keys: int = 24,
                by_index: bool = True) -> Params:
    """The seeded tree of `shapes` ({leaf path: (shape, scale or None for
    ones)}: `place_leaf`'s paths), stacked leaves: every leaf slice by slice
    (models/experts.normal_slices), key split(key, n_keys)[leaf_keys[path]]
    each, the `float32` paths in float32, norm weights 1, a kind with no
    layer empty leaves. by_index, for the families that hold a share
    (config.HOLDS_EXPERT_SHARE): the expert banks ("moe." + BANKS) and the
    two vocabulary tables by PUBLISHED index (`_normal_keyed`: layer x
    n_experts + expert, the row's index), the held ones alone, so the shares
    of one seed are shares of one model. Otherwise the embedding as 8 slices
    of rows where 8 divides the vocabulary."""
    _check_tied(cfg, shapes)
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, n_keys)
    params: Params = {"layers": {}}
    Lm = cfg.n_layers - cfg.first_k_dense
    # the held experts' published indices, a routed layer after the other
    held = (jnp.arange(Lm, dtype=jnp.int32)[:, None] * cfg.n_experts
            + cfg.expert_lo + jnp.arange(cfg.experts_held, dtype=jnp.int32)
            [None, :]).reshape(-1) if by_index else None
    for path, (shape, scale) in shapes.items():
        kind, _, name = path.rpartition(".")
        bank = kind == "moe" and name in BANKS
        if scale is None:
            leaf = jnp.ones(shape, dt)
        elif 0 in shape:
            leaf = jnp.zeros(shape, dt)
        elif by_index and (bank or path in ("embed", "head")):
            leaf = _normal_keyed(
                ks[leaf_keys[path]],
                held if bank else jnp.arange(shape[0], dtype=jnp.int32),
                shape=shape[2:] if bank else shape[1:], scale=float(scale),
                dtype=dt,
            ).reshape(shape)
        elif path == "embed" and shape[0] % 8 == 0:
            leaf = vocab_slices(ks[leaf_keys[path]], shape, scale, dt)
        else:
            leaf = normal_slices(
                ks[leaf_keys[path]], scale=float(scale), shape=shape,
                dtype=F32 if path in float32 else dt,
            )
        place_leaf(params, path, leaf)
    return params


def draw_layer_tuples(cfg: ModelConfig, key: jax.Array, shapes: dict,
                      leaf_keys: dict, w_in: dict) -> Params:
    """The seeded tree of `shapes` with each leaf of a kind a TUPLE of its
    layers' arrays: a stacked shape [n, ...] is n arrays, array i a scaled
    normal from split(split(key, 24)[leaf_keys[path]], n)[i] in float32
    rounded to the dtype (the slices `normal_slices` draws as one leaf);
    norm weights 1; a vocabulary table by `vocab_slices`. Then `w_in`'s
    ({kind: names}) projections side by side as the kind's ONE matrix
    "w_in": one product a mixer."""
    _check_tied(cfg, shapes)
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 24)
    params: Params = {"layers": {}}
    for path, (shape, scale) in shapes.items():
        kind = path.rpartition(".")[0]
        if scale is None:
            leaf = jnp.ones(shape, dt)
            if kind:
                leaf = tuple(leaf)
        elif kind:
            keys = jax.random.split(ks[leaf_keys[path]], shape[0])
            leaf = tuple(_normal(keys[i], shape[1:], float(scale), dt)
                         for i in range(shape[0]))
        else:
            leaf = vocab_slices(ks[leaf_keys[path]], shape, scale, dt)
        place_leaf(params, path, leaf)
    for kind, order in w_in.items():
        leaves = params["layers"][kind]
        drawn = [leaves.pop(name) for name in order]
        leaves["w_in"] = tuple(
            jnp.concatenate(parts, axis=1) for parts in zip(*drawn))
    return params


def attn_shapes(stack: str, n: int, D: int, H: int, KV: int, Dk: int,
                Dv: Optional[int] = None, gate: bool = False,
                qk_norm: bool = False) -> dict:
    """An attention's leaves, n layers of them under layers[stack]: wq of H
    heads, wk / wv of KV (keys Dk wide, values Dv: Dk where not given), wo;
    wg under `gate`; q_norm / k_norm [n, Dk] under `qk_norm`."""
    Dv, s = Dv or Dk, D ** -0.5
    shapes = {f"{stack}.wq": ((n, D, H * Dk), s),
              f"{stack}.wk": ((n, D, KV * Dk), s),
              f"{stack}.wv": ((n, D, KV * Dv), s),
              f"{stack}.wo": ((n, H * Dv, D), (H * Dv) ** -0.5)}
    if gate:
        shapes[f"{stack}.wg"] = ((n, D, H * Dv), s)
    if qk_norm:
        shapes.update({f"{stack}.q_norm": ((n, Dk), None),
                       f"{stack}.k_norm": ((n, Dk), None)})
    return shapes


def ffn_shapes(stack: str, L: int, D: int, F: int) -> dict:
    """A SwiGLU's three leaves, L layers of them under layers[stack]."""
    return {f"{stack}.w_gate": ((L, D, F), D ** -0.5),
            f"{stack}.w_up": ((L, D, F), D ** -0.5),
            f"{stack}.w_down": ((L, F, D), F ** -0.5)}


def moe_shapes(cfg: ModelConfig, Lm: int, shared: bool = False) -> dict:
    """`moe_ffn`'s leaves of Lm routed layers: the router over ALL
    cfg.n_experts with its float32 selection bias, the banks of the experts
    HELD, and where `shared` the shared expert (ws_*)."""
    D, E, Eh, Fm = cfg.dim, cfg.n_experts, cfg.experts_held, cfg.moe_ffn_dim
    shapes = {"moe.w_router": ((Lm, D, E), D ** -0.5),
              "moe.router_bias": ((Lm, E), ROUTER_BIAS_SCALE),
              "moe.w_gate": ((Lm, Eh, D, Fm), D ** -0.5),
              "moe.w_up": ((Lm, Eh, D, Fm), D ** -0.5),
              "moe.w_down": ((Lm, Eh, Fm, D), Fm ** -0.5)}
    if shared:
        Fs = max(cfg.n_shared_experts, 1) * Fm
        shapes.update({"moe.ws_gate": ((Lm, D, Fs), D ** -0.5),
                       "moe.ws_up": ((Lm, D, Fs), D ** -0.5),
                       "moe.ws_down": ((Lm, Fs, D), Fs ** -0.5)})
    return shapes


def scan_constants(key_a, key_dt, H: int):
    """(a_log, dt_bias) [H] float32 of one layer: a uniform on A_INIT, dt
    log-uniform on DT_INIT and dt_bias = dt + log(-expm1(-dt)), its inverse
    softplus."""
    a = jax.random.uniform(key_a, (H,), F32, *A_INIT)
    lo, hi = math.log(DT_INIT[0]), math.log(DT_INIT[1])
    dt = jnp.exp(jax.random.uniform(key_dt, (H,), F32, lo, hi))
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


# -- the whole-model ends -----------------------------------------------------


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params: Params, tokens, pos=0):
    """[B, T] -> [B, T, D], float32 (the residual stream's dtype): times
    sqrt(D) under cfg.embed_scale, times cfg.embed_multiplier where set."""
    del pos
    x = params["embed"][tokens].astype(F32)
    if cfg.embed_scale:
        x = x * (cfg.dim ** 0.5)
    return x if cfg.embed_multiplier is None else x * cfg.embed_multiplier


@jax.named_scope("head")
def unembed(cfg: ModelConfig, params: Params, x):
    """The last RMSNorm and the head the tree holds ("head" / "lm_head",
    else the tied table), over cfg.logits_divider where set: float32 logits
    over the vocabulary rows held."""
    table = next(params[n] for n in ("head", "lm_head", "embed") if n in params)
    h = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.jnp_dtype)
    logits = jax.lax.dot_general(
        h, table, (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=F32,
    )
    return logits if cfg.logits_divider is None else logits / cfg.logits_divider


def forward(forward_layers: Callable, cfg: ModelConfig, params: Params,
            tokens, cache, pos):
    """Whole-model chunk forward over a dense cache: tokens [B, T] at offset
    pos -> (float32 logits [B, T, V], new cache)."""
    x = embed(cfg, params, tokens)
    x, cache = forward_layers(cfg, params["layers"], x, cache, pos)
    return unembed(cfg, params, x), cache


def whole_cache_only(cfg: ModelConfig, n_layers: Optional[int]) -> None:
    if n_layers is not None and n_layers != cfg.n_layers:
        raise ValueError(f"{cfg.name}: an unrolled stack's cache is not cut "
                         f"by layers (no pp)")


def paged_pool_only(cfg: ModelConfig, *args, **kwargs):
    """`init_kv_cache` and `forward` of a family the paged pool alone
    serves."""
    raise ValueError(
        f"{cfg.name}: served from the paged pool by the continuous engine "
        f"only (--continuous N --kv-pool-blocks M, engine/paged.py): there "
        f"is no dense cache of its layers' states and no dense-cache forward")


# -- parts of a mixer ---------------------------------------------------------


def put(leaves: tuple, i: int, leaf) -> tuple:
    """A leaf-a-layer tuple with layer i's leaf replaced."""
    return leaves[:i] + (leaf,) + leaves[i + 1:]


def cached(new: dict, names: tuple, i: int, paged: bool, attend: Callable):
    """An attention mixer over layer i of the cache leaves new[names] (K's,
    V's): attend(cache_k, cache_v, layer) -> (out, cache_k, cache_v). A
    paged hook takes the pool's leaves whole and the layer's index in them;
    the dense cache is cut here and put back (layer None). Returns (out,
    new)."""
    kn, vn = names
    ck, cv = (new[kn], new[vn]) if paged else (new[kn][i], new[vn][i])
    out, ck, cv = attend(ck, cv, i if paged else None)
    new[kn] = ck if paged else new[kn].at[i].set(ck)
    new[vn] = cv if paged else new[vn].at[i].set(cv)
    return out, new


def positions_and_masks(pos, T: int, S: int, windows: tuple = (None,)):
    """(the positions of a chunk's T tokens at pos, a scalar or one a row;
    the causal mask over S cache places under each of `windows`)."""
    if pos.ndim == 1:
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        return positions, [slot_causal_mask(pos, T, S, window=w)
                           for w in windows]
    positions = pos + jnp.arange(T, dtype=jnp.int32)
    return positions, [causal_mask(pos, T, S, window=w) for w in windows]


def head_products(h, lp: Params, H: int, KV: int, Dk: int, Dv: int):
    """(q [B, T, H, Dk], k [B, T, KV, Dk], v [B, T, KV, Dv]) of normed h:
    the three products pinned BEFORE their head split (rule 1)."""
    B, T, _ = h.shape
    q, k, v = pin_products(h @ lp["wq"], h @ lp["wk"], h @ lp["wv"])
    return (q.reshape(B, T, H, Dk), k.reshape(B, T, KV, Dk),
            v.reshape(B, T, KV, Dv))


def project(lp: Params, h):
    """h [W, D] through the mixer's one matrix `w_in`, float32 out, handed
    on as it is (rule 1: a slice straight after the product is moved through
    the dot onto the weight, and each part's product then reads the whole
    matrix again)."""
    return jax.lax.optimization_barrier(
        jnp.dot(h, lp["w_in"], preferred_element_type=F32))


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


def group_hooks(hook, two: bool) -> dict:
    """{group: its attention hook}: under a pool of two groups each group's
    half of the launch's block table (`hook.group`); else the one hook for
    both kinds."""
    if two:
        return {"global": hook.group(0, 2), "window": hook.group(1, 2)}
    return {"global": hook, "window": hook}


def gated_attention(cfg: ModelConfig, lp: Params, h, cache_k, cache_v, pos,
                    rope, mask, hook, layer):
    """Gated grouped-query attention on normed h [B, T, D] (parameter
    dtype): (sigmoid(h wg) * heads) wo, a per-head RMSNorm on q and k where
    the layer has the weights; returns (float32 [B, T, D], new cache_k, new
    cache_v). cfg: the layer's own view (its window, or none). rope: (cos,
    sin), or None for no position encoding. cache_k / v and layer:
    `cached`'s."""
    B, T, _ = h.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = head_products(h, lp, H, KV, Dh, Dh)
    gate = jax.nn.sigmoid(jnp.dot(h, lp["wg"], preferred_element_type=F32))
    if "q_norm" in lp:
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


def prepare_rows(cfg: ModelConfig, layers: Params, x, cache, pos, hook,
                 attn_seq_len):
    """`prepare` of a paged-only family whose attention takes no position
    encoding: the gather path's mask over a row's logical positions, how the
    launch's flat tokens fall into fleet rows (engine/paged.StateRows) and
    the launch's tile."""
    return SimpleNamespace(
        pos=pos, mask=slot_causal_mask(pos, 1, attn_seq_len), hook=hook,
        rows=hook.rows(), tile=hook.tile)


def restore_states(pool: dict, layer: int, rows):
    """A recurrent layer's float32 matrix states as a launch starts: (the
    "lin" leaf, with a prefix hit's rows started from their snapshots; zero
    [R] bool or None: the rows that start cold, which the scan itself reads
    as zeros; the slots' indices). A decode chunk (rows.restore None) starts
    no tenant."""
    lin = pool["lin"][layer]
    slot = jnp.arange(lin.shape[0], dtype=jnp.int32)
    if rows.restore is None:
        return lin, None, slot
    zero = rows.fresh & (rows.restore < 0)
    return move_rows(lin, pool["snap"][layer], rows.fresh & (rows.restore >= 0),
                     slot, rows.restore), zero, slot


def keep_states(pool: dict, layer: int, rows, slot, conv, lin) -> dict:
    """The pool with a recurrent layer's new states `conv` and `lin`, and,
    where a row has rows.take >= 0, BOTH kept in that snapshot: one snapshot
    index names the two states of every layer."""
    csnap, snap = pool["csnap"][layer], pool["snap"][layer]
    if rows.take is not None:
        snap = move_rows(snap, lin, rows.take >= 0, rows.take, slot)
        at = jnp.where(rows.take >= 0, rows.take, csnap.shape[0])  # dropped
        csnap = jax.lax.cond(
            jnp.any(rows.take >= 0),
            lambda: csnap.at[at].set(conv, mode="drop"), lambda: csnap)
    return {**pool, "conv": put(pool["conv"], layer, conv),
            "lin": put(pool["lin"], layer, lin),
            "csnap": put(pool["csnap"], layer, csnap),
            "snap": put(pool["snap"], layer, snap)}


def starts(rows, live_leaf, snap_leaf):
    """The convolution state each row starts the launch from: the slot's
    live one, or for a row that starts a tenant (rows.fresh) zeros, or
    snapshot rows.restore after a prefix hit: never what the slot's previous
    tenant left. (A pass over the leaf: 1.7 MB a layer at 64 slots.)"""
    if rows.restore is None:  # a decode chunk starts no tenant
        return live_leaf

    def restored():
        held = snap_leaf[jnp.clip(rows.restore, 0, snap_leaf.shape[0] - 1)]
        first = jnp.where((rows.restore >= 0)[:, None, None], held,
                          jnp.zeros_like(held))
        return jnp.where(rows.fresh[:, None, None], first, live_leaf)

    return jax.lax.cond(jnp.any(rows.fresh), restored, lambda: live_leaf)


def move_rows(dst, src, want, dst_at, src_at):
    """dst with dst[dst_at[r]] = src[src_at[r]] for every row r where want
    [R] holds, a state at a time and in place: the rows that move cost their
    own bytes, and a launch in which none does costs nothing (a matrix state
    is 2 MB a layer: a `where` or a scatter over the leaf would move all 64
    slots' for one row's sake)."""
    order = jnp.argsort(~want, stable=True)

    def move(i, dst):
        r = order[i]
        return jax.lax.dynamic_update_index_in_dim(
            dst, jax.lax.dynamic_index_in_dim(src, src_at[r], 0), dst_at[r], 0)

    return jax.lax.fori_loop(0, jnp.sum(want.astype(jnp.int32)), move, dst)


# -- feed-forward -------------------------------------------------------------


def swiglu(h, w_gate, w_up, w_down):
    """SwiGLU on h [..., D] in the parameter dtype; float32 out."""
    gate = jax.nn.silu(jnp.dot(h, w_gate, preferred_element_type=F32))
    up = jnp.dot(h, w_up, preferred_element_type=F32)
    return jnp.dot((gate * up).astype(h.dtype), w_down,
                   preferred_element_type=F32)


def dense_ffn(lp: Params, h):
    return swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


def rows_ffn(lp: Params, h):
    """`dense_ffn` of a paged launch's one-token rows h [W, 1, D], as rows x
    D (a batch of one-token rows made the last product a multiply-and-reduce
    at half the bandwidth in the decode chunk) and handed on as it is (fused
    with the residual add and the next norm's sum of squares the same
    product took twice its time: my chip run, PR 48)."""
    return jax.lax.optimization_barrier(dense_ffn(lp, h[:, 0]))[:, None]


def moe_ffn(cfg: ModelConfig, lp: Params, banks: Params, layer: int, h,
            live=None, count_away: bool = True):
    """The routed FFN on normed h [B, T, D]: s = sigmoid(h w_router) in
    float32 over ALL cfg.n_experts, the n_experts_per_tok largest of s +
    router_bias chosen, weights s / (sum of the chosen s +
    cfg.router_norm_eps) x routed_scaling (models/experts.route); the pairs
    whose expert is held here (cfg.expert_lo .. + cfg.experts_held: all of
    them where the configuration is no share) computed, the others left out
    (models/experts.routed_ffn: no code stands in for the chips that hold
    them); plus the shared expert, whole, where the layer has one (its
    leaves ws_*). Returns (float32 [B, T, D], tokens each held expert got
    [Eh], the live pairs that went to experts held elsewhere: None where
    nobody counts them, a loop's body keeps what its caller drops)."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    with jax.named_scope("moe_route"):
        chosen, weights = route(cfg, flat, lp["w_router"], lp["router_bias"])
    out, sizes = routed_ffn(cfg, banks, layer, flat, chosen, weights,
                            live=live, expert_lo=cfg.expert_lo)
    elsewhere = None
    if count_away:
        with jax.named_scope("moe_route"):
            pairs = chosen.shape[0] * chosen.shape[1] if live is None else \
                jnp.sum(live.astype(jnp.int32)) * chosen.shape[1]
            elsewhere = pairs - jnp.sum(sizes)
    if "ws_gate" in lp:
        with jax.named_scope("moe_shared"):
            out = out + swiglu(flat, lp["ws_gate"], lp["ws_up"],
                               lp["ws_down"])
    return out.reshape(B, T, D), sizes, elsewhere


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


# -- the stack ----------------------------------------------------------------


def forward_layers(cfg: ModelConfig, layers: Params, x, cache, pos,
                   update_gate=None, tp_axis=None, attn_hook=None,
                   valid_start=None, ep_axis=None, attn_seq_len=None, *,
                   kinds: dict, norms: tuple, prepare: Callable,
                   post_norms: tuple = (None, None), routed: bool = False,
                   dense: tuple = ("dense", dense_ffn), paged_only: bool = False,
                   dense_hook: Callable = default_attn_hook,
                   closing: Optional[str] = None):
    """Every layer over a chunk x [B, T, D] (float32 residual): x += r
    Mixer(N(x)); x += r FFN(N(x)) a layer, r cfg.residual_multiplier where
    set. cache: the family's dense cache or, under a paged hook
    (`attn_hook.paged`, engine/paged.py), the pool; with a "routed" leaf the
    expert layers add to it what they routed (`add_routed`). pos: a scalar,
    or one position a row (the flat token layout). Returns (x, new cache).

    The family's binding (keyword only):
      kinds     {layer kind: (scope, small-stack name, mixer)}; mixer(cfg,
                ctx, lp, h, new, index) -> (float32 out, new): lp the
                layer's row of layers[small stack], index its place there
      norms, post_norms  the leaves [L, D] normalising (the mixer's, the
                FFN's) input, and their output (None: not normalised)
      prepare   (cfg, layers, x, cache, pos, hook, attn_seq_len) -> ctx: what
                the mixers share, made once a forward (masks, rotary tables
                by kind, the grouped hooks, the state rows)
      routed    layers from cfg.first_k_dense on are `moe_ffn` over
                layers["moe"] (the banks never sliced); the others, or all,
      dense     (small-stack name, ffn(lp, h)): `dense_ffn` or `rows_ffn`
      paged_only  no dense cache: anything but a paged hook is refused
      dense_hook  the attention hook where the caller gives none
      closing   the scope of a layer's closing residual add; None: the NEXT
                block's (utils/tracing.STEP_SCOPES: a scope is an operator
                with the norm in front of it, and a residual add belongs to
                the block it feeds), "head" after the last
    """
    if tp_axis is not None or ep_axis is not None or update_gate is not None:
        raise ValueError(f"{cfg.name}: an unrolled stack is not sharded over "
                         f"pp, tp or ep")
    if valid_start is not None:
        raise ValueError(
            f"{cfg.name}: an unrolled stack takes no left-padded rows (a pad "
            f"token would enter a recurrent layer's state)")
    if paged_only and not getattr(attn_hook, "paged", False):
        raise ValueError(
            f"{cfg.name}: served from the paged pool only: flat tokens under "
            f"a paged hook")
    T = x.shape[1]
    assert T == 1 or not paged_only, \
        "the paged launches carry one token a batch row"
    pos = jnp.asarray(pos, jnp.int32)
    ctx = prepare(cfg, layers, x, cache, pos, attn_hook or dense_hook,
                  attn_seq_len)
    # rows whose output nothing reads reach no expert (engine/paged's hooks
    # say which: launch padding, freed slots)
    live = getattr(attn_hook, "live", None)
    if live is not None and T > 1:
        live = jnp.repeat(live, T)
    dt, r = cfg.jnp_dtype, cfg.residual_multiplier
    banks = {name: layers["moe"][name] for name in BANKS} if routed else {}
    # (a share's "routed" leaf has a column for the pairs that went elsewhere)
    share = routed and "routed" in cache and \
        cache["routed"].shape[2] > banks["w_gate"].shape[1]

    def row(stack, i):  # layer i's leaves of its small stack
        return {name: leaf[i] for name, leaf in layers[stack].items()
                if not (stack == "moe" and name in BANKS)}

    def normed(leaf, li, a):
        return rms_norm(a, layers[leaf][li], cfg.norm_eps)

    new = dict(cache)
    sizes, away = [], []
    at = dict.fromkeys((stack for _, stack, _ in kinds.values()), 0)
    for li, kind in enumerate(cfg.layer_types):
        scope, stack, mixer = kinds[kind]
        moe = routed and li >= cfg.first_k_dense
        with jax.named_scope(scope):
            h = normed(norms[0], li, x).astype(dt)
            out, new = mixer(cfg, ctx, row(stack, at[stack]), h, new, at[stack])
            at[stack] += 1
            if post_norms[0]:
                out = normed(post_norms[0], li, out)
        with jax.named_scope("moe_route" if moe else "ffn"):
            x = x + (out if r is None else r * out)
            h = normed(norms[1], li, x).astype(dt)
        if moe:
            im = li - cfg.first_k_dense
            out, counts, elsewhere = moe_ffn(cfg, row("moe", im), banks, im,
                                             h, live, share)
            sizes.append(counts)
            away.append(elsewhere)
        else:
            with jax.named_scope("ffn"):
                out = dense[1](row(dense[0], li), h)
        if post_norms[1]:
            with jax.named_scope("moe_combine" if moe else "ffn"):
                out = normed(post_norms[1], li, out)
        after = cfg.layer_types[li + 1:li + 2]
        with jax.named_scope(closing or (kinds[after[0]][0] if after
                                         else "head")):
            x = x + (out if r is None else r * out)
    return x, add_routed(cache, new, sizes, away) if sizes else new
