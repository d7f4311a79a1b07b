"""Gated delta-rule layers (Kimi Delta Attention) beside gated attention
without a position encoding, every layer over routed experts beside a shared
one (upstage/Solar-Open2, model_type solar_open2) in pure JAX, as ONE chip's
share of an expert-parallel deployment where the configuration says so.

The stack of two kinds of layer (cfg.layer_types) is models/stack.py's loop,
as are the gated attention, the FFN and the draw; here the family's leaves,
its `kda_mixer` and their binding. RMSNorm with a weight, eps cfg.norm_eps;
x a layer's input, D = cfg.dim:

  x_0       E[token]
  layer l   h = x + Mixer_l(N1(x));  y = h + FFN_l(N2(h))
  head      RMSNorm, then the untied head

  Mixer, "kda" (H = cfg.linear_heads heads, keys and values Dh =
            cfg.head_dim wide, K = cfg.conv_kernel taps, r = Dh the width of
            the two low-rank pairs):
            [q | k | v | f | g | b] = u w_in    (3 x H Dh | r | r | H)
            [q | k | v]_t <- silu(sum_j conv_w[j] * [q | k | v]_{t-(K-1)+j})
                                             depthwise, causal, no bias;
                                             inputs before the row's first
                                             token 0
            q, k <- q / |q|, k / |k| a head (eps L2_EPS under the root)
            g_t = -exp(a_log_h) softplus(f_t wf_up + dt_bias)   [H, Dh] <= 0
            beta_t = (2 under cfg.delta_neg_eigval) sigmoid(b_t)      [H]
            S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
                  + beta_t k_t v_t^T,  o_t = S_t^T q_t Dh^-0.5 per head
                                             (ops/delta_rule.py; S FLOAT32)
            Mixer = (RMSNorm_head(o) * sigmoid(g_t wg_up)) wo
            STATE a row and layer: the convolutions' last K - 1 inputs
            [K - 1, 3 H Dh] (the parameter dtype: the projections are
            rounded to it where they are made, so what a later launch reads
            back is what a neighbour in the same launch reads) AND S.
  Mixer, "full_attention": models/stack.gated_attention without per-head
            norms: q = u wq (cfg.n_heads heads of Dh), k, v (cfg.n_kv_heads),
            NO position encoding, causal softmax at Dh^-0.5,
            (sigmoid(u wg) * heads) wo.
  FFN       `stack.moe_ffn` on every layer: the experts held here and the
            shared one.

The family is served from the paged pool alone (engine/paged.py), whose
leaves are granite_hybrid's by what the "kda" kind keeps
(config.STATE_OF_KIND): "k" / "v" of the attention layers, and a leaf a kda
layer each of "conv" [slots, K - 1, 3 H Dh], "lin" [slots, H, Dh, Dh]
float32 (a head's state transposed: ops/delta_rule.py), "csnap" / "snap" the
same two by snapshot, and the "routed" counts (models/stack.add_routed).

Params pytree (Lk / La kda / attention layers, V the vocabulary rows held):
  embed [V, D]   head [V, D] (untied)   final_norm [D]
  layers: norm1 norm2 [L, D]
    kda:  w_in [Lk, D, 3 H Dh + 2 r + H] = [wq | wk | wv | wf_down |
          wg_down | w_beta]   conv_w [Lk, K, 3 H Dh]
          wf_up wg_up [Lk, r, H Dh]   a_log [Lk, H] dt_bias [Lk, H Dh] float32
          o_norm [Lk, Dh]   wo [Lk, H Dh, D]
    attn: wq wg [La, D, Hq Dh]  wk wv [La, D, KV Dh]  wo [La, Hq Dh, D]
    moe:  `stack.moe_shapes` with a shared expert, every layer

`init_params` is `stack.draw_params` (by published index) over 32 keys; a_log
and dt_bias as Mamba-2 draws them (`stack.scan_constants`), a layer a key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import stack
from ..config import ModelConfig
from ..ops.delta_rule import delta_rule_rows, delta_rule_step
from ..ops.norms import rms_norm
from ..ops.ssm_scan import causal_conv_rows
from .stack import embed, unembed  # noqa: F401 - the family's ends

Params = dict
F32 = jnp.float32
L2_EPS = 1e-6
N_KEYS = 32

# init_params' key of each drawn leaf: an index into split(key, N_KEYS)
# (cellbench/reference/delta_hybrid_moe.py writes the same table down)
LEAF_KEYS = {
    "embed": 0, "head": 1,
    "kda.wq": 2, "kda.wk": 3, "kda.wv": 4, "kda.wf_down": 5, "kda.wf_up": 6,
    "kda.wg_down": 7, "kda.wg_up": 8, "kda.w_beta": 9, "kda.conv_w": 10,
    "kda.wo": 11, "kda.a": 12, "kda.dt": 13,
    "attn.wq": 14, "attn.wk": 15, "attn.wv": 16, "attn.wg": 17, "attn.wo": 18,
    "moe.w_router": 19, "moe.router_bias": 20,
    "moe.w_gate": 21, "moe.w_up": 22, "moe.w_down": 23,
    "moe.ws_gate": 24, "moe.ws_up": 25, "moe.ws_down": 26,
}
# a kda mixer's input projections in the order `w_in` holds them
W_IN = ("wq", "wk", "wv", "wf_down", "wg_down", "w_beta")


def stack_depths(cfg: ModelConfig) -> dict:
    return {"kda": len(cfg.linear_layers), "attn": len(cfg.attn_layers)}


def leaf_shapes(cfg: ModelConfig) -> dict:
    """{leaf path: (shape, init scale or None for ones)}, stacked leaves
    with their layer axis first; the banks with the experts HELD."""
    D, V, L = cfg.dim, cfg.vocab_size, cfg.n_layers
    Dh, K = cfg.head_dim, cfg.conv_kernel
    Hd, r = cfg.linear_heads * Dh, Dh
    n = stack_depths(cfg)
    Lk, La = n["kda"], n["attn"]
    s = D ** -0.5
    return {
        "embed": ((V, D), 0.02), "head": ((V, D), s), "final_norm": ((D,), None),
        "norm1": ((L, D), None), "norm2": ((L, D), None),
        "kda.wq": ((Lk, D, Hd), s), "kda.wk": ((Lk, D, Hd), s),
        "kda.wv": ((Lk, D, Hd), s),
        "kda.wf_down": ((Lk, D, r), s), "kda.wf_up": ((Lk, r, Hd), r ** -0.5),
        "kda.wg_down": ((Lk, D, r), s), "kda.wg_up": ((Lk, r, Hd), r ** -0.5),
        "kda.w_beta": ((Lk, D, cfg.linear_heads), s),
        "kda.conv_w": ((Lk, K, 3 * Hd), K ** -0.5),
        "kda.o_norm": ((Lk, Dh), None),
        "kda.wo": ((Lk, Hd, D), Hd ** -0.5),
        **stack.attn_shapes("attn", La, D, cfg.n_heads, cfg.n_kv_heads, Dh,
                            gate=True),
        **stack.moe_shapes(cfg, L, shared=bool(cfg.n_shared_experts)),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): scaled normals, norm
    weights 1, the selection bias, a_log and dt_bias in float32."""
    params = stack.draw_params(cfg, key, leaf_shapes(cfg), LEAF_KEYS,
                               float32=("moe.router_bias",), n_keys=N_KEYS)
    kda = params["layers"]["kda"]
    ks = jax.random.split(key, N_KEYS)
    Lk, H, Hd = stack_depths(cfg)["kda"], cfg.linear_heads, \
        cfg.linear_heads * cfg.head_dim
    consts = [(stack.scan_constants(ka, kd, H)[0],
               stack.scan_constants(ka, kd, Hd)[1])
              for ka, kd in zip(
                  jax.random.split(ks[LEAF_KEYS["kda.a"]], Lk),
                  jax.random.split(ks[LEAF_KEYS["kda.dt"]], Lk))]
    kda["a_log"] = jnp.stack([a for a, _ in consts])
    kda["dt_bias"] = jnp.stack([b for _, b in consts])
    kda["w_in"] = jnp.concatenate([kda.pop(name) for name in W_IN], axis=2)
    return params


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_mixer(cfg: ModelConfig, c, lp: Params, h, pool, layer: int):
    """The "kda" mixer over a paged launch's flat tokens (normed h
    [W, 1, D]; c: `stack.prepare_rows`); `layer` the layer's index among the
    kda layers. The delta rule's form follows the launch's shape (below): the
    decode program's one token a row is the recurrence itself, any other
    launch the chunked form; both carry the one `lin` leaf.
    Returns (float32 [W, 1, D], pool)."""
    W, rows, tq = h.shape[0], c.rows, c.tile
    H, Dh, dt_ = cfg.linear_heads, cfg.head_dim, cfg.jnp_dtype
    Hd = H * Dh
    out = stack.project(lp, h[:, 0])
    qkv, f, g, b = (out[:, :3 * Hd], out[:, 3 * Hd:3 * Hd + Dh],
                    out[:, 3 * Hd + Dh:3 * Hd + 2 * Dh],
                    out[:, 3 * Hd + 2 * Dh:])
    lin, zero, slot = stack.restore_states(pool, layer, rows)
    with jax.named_scope("delta_conv"):
        qkv, conv = causal_conv_rows(
            qkv.astype(dt_), lp["conv_w"], None,
            stack.starts(rows, pool["conv"][layer], pool["csnap"][layer]),
            rows.tok_row)
        qkv = jax.nn.silu(qkv).reshape(W, 3, H, Dh)
    q, k, v = _unit(qkv[:, 0]) * Dh ** -0.5, _unit(qkv[:, 1]), qkv[:, 2]
    decay = jax.nn.softplus(
        jnp.dot(f.astype(dt_), lp["wf_up"], preferred_element_type=F32)
        + lp["dt_bias"][None, :]).reshape(W, H, Dh)
    decay = -jnp.exp(lp["a_log"])[None, :, None] * decay
    beta = (2.0 if cfg.delta_neg_eigval else 1.0) * jax.nn.sigmoid(b)
    if (rows.restore is None and rows.take is None and tq == 1
            and W == lin.shape[0]):
        # the decode program's launch (engine/paged.make_paged_hook: flat
        # place i is fleet row i's one token, no tenant starts, no snapshot
        # is kept): the recurrence itself
        o, lin = delta_rule_step(q, k, v, decay, beta, lin, rows.tok_row,
                                 impl=cfg.attn_impl)
    else:
        o, lin = delta_rule_rows(q, k, v, decay, beta, lin, rows.tok_row, tq,
                                 zero=zero, impl=cfg.attn_impl)
    pool = stack.keep_states(pool, layer, rows, slot, conv, lin)
    gate = jax.nn.sigmoid(
        jnp.dot(g.astype(dt_), lp["wg_up"], preferred_element_type=F32))
    y = rms_norm(o, lp["o_norm"], cfg.norm_eps).reshape(W, Hd) * gate
    out = jnp.dot(y.astype(dt_), lp["wo"], preferred_element_type=F32)
    return out[:, None], pool


def _attn(cfg, c, lp, h, new, ia):
    return stack.cached(
        new, ("k", "v"), ia, True, lambda ck, cv, layer: stack.gated_attention(
            cfg, lp, h, ck, cv, c.pos, None, c.mask, c.hook, layer))


# (the closing add under "moe_combine", as the cell was first traced, not
# the next block's scope: the per-layer metrics read device time by scope)
forward_layers = functools.partial(
    stack.forward_layers, norms=("norm1", "norm2"),
    prepare=stack.prepare_rows, routed=True, paged_only=True,
    closing="moe_combine",
    kinds={"kda": ("delta_mix", "kda", kda_mixer),
           "full_attention": ("attn", "attn", _attn)})
init_kv_cache = forward = stack.paged_pool_only
