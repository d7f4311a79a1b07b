"""Mamba-2 state-space layers beside attention without a position encoding
(ibm-granite/granite-4.0-h, model_type granitemoehybrid, dense: no routed
expert) in pure JAX.

Layers of two kinds alternate in one stack (cfg.layer_types), so the layers
are not scanned: a Python loop over the pattern, each layer reading its own
leaves. RMSNorm eps cfg.norm_eps with a weight everywhere; x a layer's
input, D = cfg.dim, r = cfg.residual_multiplier:

  embed     table[token] x cfg.embed_multiplier
  layer l   h = x + r Mixer_l(RMSNorm_op(x));  y = h + r SwiGLU(RMSNorm_ffn(h))
  head      RMSNorm, the tied table, / cfg.logits_divider

  Mixer, "mamba" (H = cfg.ssm_heads heads of P = cfg.ssm_head_dim, d_inner
            = H P, N = cfg.ssm_state, ONE group of B and C, K =
            cfg.conv_kernel taps):
            [z | xBC | dt] = u w_in          (d_inner | d_inner + 2 N | H)
            xBC_t <- silu(conv_b + sum_j conv_w[j] * xBC_{t-(K-1)+j})
                                             depthwise, causal; inputs
                                             before the row's first token 0
            [x | B | C] = xBC                (d_inner | N | N)
            dt_t = softplus(dt_t + dt_bias), A = -exp(a_log)   (float32)
            S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t +
            d_h x_t per head (ops/ssm_scan.py; S [P, N] FLOAT32)
            Mixer = RMSNorm(y * silu(z)) wo  (the gate first, one norm over
                                             all d_inner numbers)
            STATE a row and layer: the convolution's last K - 1 inputs
            [K - 1, d_inner + 2 N] (the parameter dtype: xBC is rounded to
            it where it is made, so what a later launch reads back from the
            state is what a neighbour in the same launch reads) AND S.
  Mixer, "attention" (cfg.n_heads query heads, cfg.n_kv_heads key/value
            heads of Dh): q, k, v = u w_in; NO rotary and no other position
            signal; causal softmax at cfg.attn_scale_override (a direct
            multiplier, not Dh^-0.5); Mixer = o wo. No bias, no qk-norm.

The family is served from the paged pool alone (engine/paged.py). The
pool's leaves:
  "k" / "v"  [La, N, KV / pack, bs, pack x Dh]: the attention layers' K/V,
             cfg.kv_pack heads side by side on a 128-lane row
             (models/lfm2.pack_heads)
  "conv"     a leaf a mamba layer, [slots, K - 1, d_inner + 2 N]: a slot's
             live convolution state
  "lin"      a leaf a mamba layer, [slots, H / pack, N, pack x P] float32:
             a slot's live matrix state (ops/ssm_scan.state_shape)
  "csnap" / "snap"  a leaf a mamba layer each, [snapshots, ...]: BOTH states
             kept at block boundaries, which a prefix hit starts a row from
             (engine/paged.StateRows.restore / .take): one snapshot index
             names the two states of every layer
A leaf a layer (a tuple of them), as models/minicpm_sala.py says.

Params pytree (L layers, Lm / La mamba / attention layers, F ffn_dim):
  embed [V, D] (also the head)   final_norm [D]
  layers: op_norm ffn_norm [L, D]
    mamba: w_in [D, 2 d_inner + 2 N + H] = [wz | wx | wdt]
           conv_w [K, d_inner + 2 N]  conv_b [d_inner + 2 N]
           dt_bias a_log d [H] float32  norm [d_inner]  wo [d_inner, D]
    attn:  w_in [D, (Hq + 2 KV) Dh] = [wq | wk | wv]  wo [Hq Dh, D]
    ffn:   w_gate w_up [D, F]  w_down [F, D]
each leaf of a kind a TUPLE of its layers' arrays. A mixer's input
projections are drawn one by one (`leaf_shapes`, LEAF_KEYS) and held side by
side as ONE matrix `w_in`: one product a mixer.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import slot_causal_mask
from ..ops.norms import rms_norm
from ..ops.ssm_scan import causal_conv_rows, ssm_scan_rows
from .experts import _normal_slices
from .lfm2 import pack_heads, unpack_heads
from .mla_moe import swiglu

Params = dict
F32 = jnp.float32

# init_params' key of each drawn leaf: an index into split(key, 24)
# (cellbench/reference/ssm_hybrid.py writes the same table down)
LEAF_KEYS = {
    "embed": 0,
    "mamba.wz": 1, "mamba.wx": 2, "mamba.wdt": 3, "mamba.conv_w": 4,
    "mamba.conv_b": 5, "mamba.wo": 6, "mamba.a": 7, "mamba.dt": 8,
    "attn.wq": 9, "attn.wk": 10, "attn.wv": 11, "attn.wo": 12,
    "ffn.w_gate": 13, "ffn.w_up": 14, "ffn.w_down": 15,
}
# a mixer's input projections in the order `w_in` holds them
W_IN = {"mamba": ("wz", "wx", "wdt"), "attn": ("wq", "wk", "wv")}
# Mamba-2's own initialisation: a = -A uniform on A_INIT, dt log-uniform on
# DT_INIT (dt_bias its inverse softplus), D = 1
A_INIT = (1.0, 16.0)
DT_INIT = (0.001, 0.1)


def stack_depths(cfg: ModelConfig) -> dict:
    return {"mamba": len(cfg.linear_layers), "attn": len(cfg.attn_layers)}


def leaf_shapes(cfg: ModelConfig) -> dict:
    """{leaf path: (shape, init scale or None for ones)}, stacked leaves
    with their layer axis first."""
    D, V, F, L = cfg.dim, cfg.vocab_size, cfg.ffn_dim, cfg.n_layers
    H, KV, Dh, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.conv_kernel
    Hm, Di, C = cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim, cfg.conv_channels
    n = stack_depths(cfg)
    Lm, La = n["mamba"], n["attn"]
    s = D ** -0.5
    return {
        # (0.02 AFTER the multiplier, as the other families' tables: a tied
        # table 12 times as large would outweigh all 80 residual branches in
        # the last hidden state, every logit row would peak at the token that
        # came in, and greedy decoding would repeat it whatever the layers
        # computed: a check that compares choices would see nothing)
        "embed": ((V, D), 0.02 / (cfg.embed_multiplier or 1.0)),
        "final_norm": ((D,), None),
        "op_norm": ((L, D), None), "ffn_norm": ((L, D), None),
        "mamba.wz": ((Lm, D, Di), s), "mamba.wx": ((Lm, D, C), s),
        "mamba.wdt": ((Lm, D, Hm), s),
        "mamba.conv_w": ((Lm, K, C), K ** -0.5),
        "mamba.conv_b": ((Lm, C), 0.02 if cfg.conv_bias else 0.0),
        "mamba.wo": ((Lm, Di, D), Di ** -0.5),
        "mamba.norm": ((Lm, Di), None),
        "attn.wq": ((La, D, H * Dh), s), "attn.wk": ((La, D, KV * Dh), s),
        "attn.wv": ((La, D, KV * Dh), s),
        "attn.wo": ((La, H * Dh, D), (H * Dh) ** -0.5),
        "ffn.w_gate": ((L, D, F), s), "ffn.w_up": ((L, D, F), s),
        "ffn.w_down": ((L, F, D), F ** -0.5),
    }


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def scan_constants(key_a, key_dt, H: int):
    """(a_log, dt_bias) [H] float32 of one layer: a uniform on A_INIT, dt
    log-uniform on DT_INIT and dt_bias = dt + log(-expm1(-dt)), its inverse
    softplus."""
    a = jax.random.uniform(key_a, (H,), F32, *A_INIT)
    lo, hi = math.log(DT_INIT[0]), math.log(DT_INIT[1])
    dt = jnp.exp(jax.random.uniform(key_dt, (H,), F32, lo, hi))
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): a stacked shape
    [n, ...] of `leaf_shapes` is n arrays, array i a scaled normal drawn from
    split(key, n)[i] in float32 and rounded to the dtype; norm weights and d
    1; a_log and dt_bias by `scan_constants` from split(key, n)[i] of their
    own keys, in float32; the vocabulary table 8 slices of rows where the
    vocabulary divides."""
    if not cfg.tie_embeddings:
        raise ValueError(f"{cfg.name}: the granite_hybrid family ties its "
                         f"embeddings")
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 24)
    layers: Params = {"mamba": {}, "attn": {}, "ffn": {}}
    params: Params = {"layers": layers}
    for path, (shape, scale) in leaf_shapes(cfg).items():
        kind, _, name = path.rpartition(".")
        if scale is None:
            leaf = jnp.ones(shape, dt)
            if kind:
                leaf = tuple(leaf)
        elif kind:
            keys = jax.random.split(ks[LEAF_KEYS[path]], shape[0])
            leaf = tuple(_normal(keys[i], shape[1:], float(scale), dt)
                         for i in range(shape[0]))
        else:
            cut = 8 if shape[0] % 8 == 0 else 1
            leaf = _normal_slices(
                ks[LEAF_KEYS[path]], scale=float(scale),
                shape=(cut, shape[0] // cut) + shape[1:], dtype=dt,
            ).reshape(shape)
        if kind:
            layers[kind][name] = leaf
        elif name in ("embed", "final_norm"):
            params[name] = leaf
        else:
            layers[name] = leaf
    Lm, Hm = stack_depths(cfg)["mamba"], cfg.ssm_heads
    consts = [scan_constants(ka, kd, Hm) for ka, kd in zip(
        jax.random.split(ks[LEAF_KEYS["mamba.a"]], Lm),
        jax.random.split(ks[LEAF_KEYS["mamba.dt"]], Lm))]
    layers["mamba"]["a_log"] = tuple(a for a, _ in consts)
    layers["mamba"]["dt_bias"] = tuple(b for _, b in consts)
    layers["mamba"]["d"] = tuple(jnp.ones((Hm,), F32) for _ in range(Lm))
    for kind, order in W_IN.items():  # the input projections side by side
        drawn = [layers[kind].pop(name) for name in order]
        layers[kind]["w_in"] = tuple(
            jnp.concatenate(parts, axis=1) for parts in zip(*drawn))
    return params


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
                  n_layers: Optional[int] = None):
    raise ValueError(
        f"{cfg.name}: the granite_hybrid family is served from the paged "
        f"pool by the continuous engine only (--continuous N "
        f"--kv-pool-blocks M): there is no dense cache of convolution and "
        f"matrix states"
    )


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params: Params, tokens, pos=0):
    """[B, T] -> [B, T, D], float32: the residual stream's dtype."""
    del pos
    return params["embed"][tokens].astype(F32) * (cfg.embed_multiplier or 1.0)


@jax.named_scope("head")
def unembed(cfg: ModelConfig, params: Params, x):
    """The last RMSNorm and the tied table: float32 logits."""
    h = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.jnp_dtype)
    logits = jax.lax.dot_general(
        h, params["embed"], (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=F32,
    )
    return logits / (cfg.logits_divider or 1.0)


# -- the mixers ---------------------------------------------------------------


def _put(leaves: tuple, i: int, leaf) -> tuple:
    return leaves[:i] + (leaf,) + leaves[i + 1:]


def _project(lp, h):
    """h [W, D] through `w_in`, float32 out, handed on as it is (a slice
    straight after the product is moved through the dot onto the weight, and
    each part's product then reads the whole matrix again:
    models/llama.pin_products)."""
    return jax.lax.optimization_barrier(
        jnp.dot(h, lp["w_in"], preferred_element_type=F32))


def _starts(rows, live_leaf, snap_leaf):
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


def _move_rows(dst, src, want, dst_at, src_at):
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


def mamba_mixer(cfg: ModelConfig, lp: Params, h, pool, layer: int, rows,
                tq: int):
    """The "mamba" mixer over a paged launch's flat tokens (normed h
    [W, 1, D]); `layer` the layer's index among the mamba layers. A row with
    rows.take >= 0 leaves BOTH its states after this launch in that
    snapshot. Returns (float32 [W, 1, D], pool)."""
    W = h.shape[0]
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    Di, dt_ = H * P, cfg.jnp_dtype
    out = _project(lp, h[:, 0])
    z, xbc, dt = out[:, :Di], out[:, Di:Di + cfg.conv_channels], \
        out[:, Di + cfg.conv_channels:]
    conv, lin = pool["conv"][layer], pool["lin"][layer]
    csnap, snap = pool["csnap"][layer], pool["snap"][layer]
    slot = jnp.arange(lin.shape[0], dtype=jnp.int32)
    zero = None
    if rows.restore is not None:  # a mixed launch: rows may start tenants
        # a cold start is the scan's own (its row's block read as zeros); a
        # prefix hit's row starts from its snapshot
        zero = rows.fresh & (rows.restore < 0)
        lin = _move_rows(lin, snap, rows.fresh & (rows.restore >= 0), slot,
                         rows.restore)
    xbc, conv = causal_conv_rows(
        xbc.astype(dt_), lp["conv_w"], lp["conv_b"] if cfg.conv_bias else None,
        _starts(rows, conv, csnap), rows.tok_row)
    xbc = jax.nn.silu(xbc)
    x, B, C = xbc[:, :Di], xbc[:, Di:Di + N], xbc[:, Di + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"][None, :])
    # (a decode step is the same call: one token a row, a tile each)
    y, lin = ssm_scan_rows(
        x.reshape(W, H, P), dt, -jnp.exp(lp["a_log"]), B, C, lin,
        rows.tok_row, tq, zero=zero)
    if rows.take is not None:  # both states after the launch, by snapshot
        snap = _move_rows(snap, lin, rows.take >= 0, rows.take, slot)
        at = jnp.where(rows.take >= 0, rows.take, csnap.shape[0])  # dropped
        csnap = jax.lax.cond(
            jnp.any(rows.take >= 0),
            lambda: csnap.at[at].set(conv, mode="drop"), lambda: csnap)
    y = y + lp["d"][None, :, None] * x.reshape(W, H, P)
    y = y.reshape(W, Di) * jax.nn.silu(z)
    y = rms_norm(y, lp["norm"], cfg.norm_eps).astype(dt_)
    out = jnp.dot(y, lp["wo"], preferred_element_type=F32)
    return out[:, None], {
        **pool, "conv": _put(pool["conv"], layer, conv),
        "lin": _put(pool["lin"], layer, lin),
        "csnap": _put(pool["csnap"], layer, csnap),
        "snap": _put(pool["snap"], layer, snap),
    }


def attention(cfg: ModelConfig, lp: Params, h, pool, layer: int, hook, pos,
              mask):
    """The "attention" mixer over a paged launch's flat tokens (normed h
    [W, 1, D]): no rotary; `layer` the layer's index in the pool's K/V;
    mask: the gather path's, over a row's logical positions. Returns
    (float32 [W, 1, D], pool)."""
    W = h.shape[0]
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = _project(lp, h[:, 0]).astype(cfg.jnp_dtype)
    q = out[:, :H * Dh].reshape(W, 1, H, Dh)
    k = out[:, H * Dh:(H + KV) * Dh].reshape(W, 1, KV, Dh)
    v = out[:, (H + KV) * Dh:].reshape(W, 1, KV, Dh)
    pack = cfg.kv_pack
    if pack > 1:
        q, k, v, part = pack_heads(q, k, v, pack)
    attn, new_k, new_v = hook(cfg, q, k, v, pool["k"], pool["v"], pos, mask,
                              None, None, None, layer)
    if pack > 1:
        attn = unpack_heads(attn, part, pack)
    out = jnp.dot(attn.reshape(W, H * Dh), lp["wo"],
                  preferred_element_type=F32)
    return out[:, None], {**pool, "k": new_k, "v": new_v}


# -- the stack ----------------------------------------------------------------


def forward_layers(cfg: ModelConfig, layers: Params, x, cache, pos,
                   update_gate=None, tp_axis=None, attn_hook=None,
                   valid_start=None, ep_axis=None, attn_seq_len=None):
    """Every layer over a paged launch's flat tokens x [W, 1, D] (float32
    residual) at positions pos [W]; cache the pool (module docstring);
    attn_hook a paged hook (engine/paged.py) whose `rows()` says how the
    tokens fall into fleet rows. Returns (x, the pool)."""
    if tp_axis is not None or ep_axis is not None or update_gate is not None:
        raise ValueError("the granite_hybrid family is not sharded over pp, "
                         "tp or ep")
    if valid_start is not None or not getattr(attn_hook, "paged", False):
        raise ValueError(
            "the granite_hybrid family is served from the paged pool only: "
            "flat tokens under a paged hook, no left-padded rows")
    assert x.shape[1] == 1, "the paged launches carry one token a batch row"
    pos = jnp.asarray(pos, jnp.int32)
    mask = slot_causal_mask(pos, 1, attn_seq_len)
    rows = attn_hook.rows()
    tq = attn_hook.tile
    dt = cfg.jnp_dtype
    r = cfg.residual_multiplier or 1.0

    def row(kind, i):
        return {name: leaf[i] for name, leaf in layers[kind].items()}

    new = dict(cache)
    scope = {"mamba": "ssm_mix", "attention": "attn"}
    im = ia = 0
    for li, kind in enumerate(cfg.layer_types):
        with jax.named_scope(scope[kind]):
            h = rms_norm(x, layers["op_norm"][li], cfg.norm_eps).astype(dt)
            if kind == "attention":
                out, new = attention(cfg, row("attn", ia), h, new, ia,
                                     attn_hook, pos, mask)
                ia += 1
            else:
                out, new = mamba_mixer(cfg, row("mamba", im), h, new, im,
                                       rows, tq)
                im += 1
        with jax.named_scope("ffn"):
            x = x + r * out
            h = rms_norm(x, layers["ffn_norm"][li], cfg.norm_eps).astype(dt)
            lp = row("ffn", li)
            # (rows x D, and handed on as it is: models/minicpm_sala.py)
            out = jax.lax.optimization_barrier(
                swiglu(h[:, 0], lp["w_gate"], lp["w_up"], lp["w_down"])
            )[:, None]
        after = cfg.layer_types[li + 1:li + 2]
        with jax.named_scope(scope[after[0]] if after else "head"):
            x = x + r * out
    return x, new


def forward(cfg: ModelConfig, params: Params, tokens, cache, pos):
    raise ValueError(
        f"{cfg.name}: the granite_hybrid family has no dense-cache forward; "
        f"it is served from the paged pool (engine/paged.py)")
