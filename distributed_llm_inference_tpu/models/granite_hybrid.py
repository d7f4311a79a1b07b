"""Mamba-2 state-space layers beside attention without a position encoding
(ibm-granite/granite-4.0-h, model_type granitemoehybrid, dense: no routed
expert) in pure JAX.

The stack of two kinds of layer (cfg.layer_types) is models/stack.py's loop,
as is the tuple-a-layer draw; here the family's leaves, its two mixers and
their binding. RMSNorm eps cfg.norm_eps with a weight everywhere; x a layer's
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
             (models/stack.pack_heads)
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

import jax
import jax.numpy as jnp

from . import stack
from ..config import ModelConfig
from ..ops.norms import rms_norm
from ..ops.ssm_scan import causal_conv_rows, ssm_scan_rows
from .stack import embed, unembed  # noqa: F401 - the family's ends

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


def stack_depths(cfg: ModelConfig) -> dict:
    return {"mamba": len(cfg.linear_layers), "attn": len(cfg.attn_layers)}


def leaf_shapes(cfg: ModelConfig) -> dict:
    """{leaf path: (shape, init scale or None for ones)}, stacked leaves
    with their layer axis first."""
    D, V, L = cfg.dim, cfg.vocab_size, cfg.n_layers
    K, Hm, C = cfg.conv_kernel, cfg.ssm_heads, cfg.conv_channels
    Di = Hm * cfg.ssm_head_dim
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
        **stack.attn_shapes("attn", La, D, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim),
        **stack.ffn_shapes("ffn", L, D, cfg.ffn_dim),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): `stack.
    draw_layer_tuples`; a_log and dt_bias as Mamba-2 draws them
    (`stack.scan_constants`) from split(key, n)[i] of their own keys, in
    float32; d = 1."""
    params = stack.draw_layer_tuples(cfg, key, leaf_shapes(cfg), LEAF_KEYS,
                                     W_IN)
    mamba = params["layers"]["mamba"]
    ks = jax.random.split(key, 24)
    Lm, Hm = stack_depths(cfg)["mamba"], cfg.ssm_heads
    consts = [stack.scan_constants(ka, kd, Hm) for ka, kd in zip(
        jax.random.split(ks[LEAF_KEYS["mamba.a"]], Lm),
        jax.random.split(ks[LEAF_KEYS["mamba.dt"]], Lm))]
    mamba["a_log"] = tuple(a for a, _ in consts)
    mamba["dt_bias"] = tuple(b for _, b in consts)
    mamba["d"] = tuple(jnp.ones((Hm,), F32) for _ in range(Lm))
    return params


# -- the mixers ---------------------------------------------------------------


def mamba_mixer(cfg: ModelConfig, c, lp: Params, h, pool, layer: int):
    """The "mamba" mixer over a paged launch's flat tokens (normed h
    [W, 1, D]; c: `stack.prepare_rows`); `layer` the layer's index among the
    mamba layers. Returns (float32 [W, 1, D], pool)."""
    W, rows = h.shape[0], c.rows
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    Di, dt_ = H * P, cfg.jnp_dtype
    out = stack.project(lp, h[:, 0])
    z, xbc, dt = out[:, :Di], out[:, Di:Di + cfg.conv_channels], \
        out[:, Di + cfg.conv_channels:]
    lin, zero, slot = stack.restore_states(pool, layer, rows)
    xbc, conv = causal_conv_rows(
        xbc.astype(dt_), lp["conv_w"], lp["conv_b"] if cfg.conv_bias else None,
        stack.starts(rows, pool["conv"][layer], pool["csnap"][layer]),
        rows.tok_row)
    xbc = jax.nn.silu(xbc)
    x, B, C = xbc[:, :Di], xbc[:, Di:Di + N], xbc[:, Di + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"][None, :])
    # (a decode step is the same call: one token a row, a tile each)
    y, lin = ssm_scan_rows(
        x.reshape(W, H, P), dt, -jnp.exp(lp["a_log"]), B, C, lin,
        rows.tok_row, c.tile, zero=zero)
    pool = stack.keep_states(pool, layer, rows, slot, conv, lin)
    y = y + lp["d"][None, :, None] * x.reshape(W, H, P)
    y = y.reshape(W, Di) * jax.nn.silu(z)
    y = rms_norm(y, lp["norm"], cfg.norm_eps).astype(dt_)
    out = jnp.dot(y, lp["wo"], preferred_element_type=F32)
    return out[:, None], pool


def attention(cfg: ModelConfig, c, lp: Params, h, pool, layer: int):
    """The "attention" mixer over a paged launch's flat tokens (normed h
    [W, 1, D]): no rotary; `layer` the layer's index in the pool's K/V.
    Returns (float32 [W, 1, D], pool)."""
    W = h.shape[0]
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = stack.project(lp, h[:, 0]).astype(cfg.jnp_dtype)
    q = out[:, :H * Dh].reshape(W, 1, H, Dh)
    k = out[:, H * Dh:(H + KV) * Dh].reshape(W, 1, KV, Dh)
    v = out[:, (H + KV) * Dh:].reshape(W, 1, KV, Dh)
    pack = cfg.kv_pack
    if pack > 1:
        q, k, v, part = stack.pack_heads(q, k, v, pack)
    attn, new_k, new_v = c.hook(cfg, q, k, v, pool["k"], pool["v"], c.pos,
                                c.mask, None, None, None, layer)
    if pack > 1:
        attn = stack.unpack_heads(attn, part, pack)
    out = jnp.dot(attn.reshape(W, H * Dh), lp["wo"],
                  preferred_element_type=F32)
    return out[:, None], {**pool, "k": new_k, "v": new_v}


# -- the stack ----------------------------------------------------------------


forward_layers = functools.partial(
    stack.forward_layers, norms=("op_norm", "ffn_norm"),
    prepare=stack.prepare_rows, dense=("ffn", stack.rows_ffn), paged_only=True,
    kinds={"mamba": ("ssm_mix", "mamba", mamba_mixer),
           "attention": ("attn", "attn", attention)})
init_kv_cache = forward = stack.paged_pool_only
