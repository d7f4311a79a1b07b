"""Gated delta-rule layers (Kimi Delta Attention) beside gated attention
without a position encoding, every layer over routed experts beside a shared
one (upstage/Solar-Open2, model_type solar_open2) in pure JAX, as ONE chip's
share of an expert-parallel deployment where the configuration says so.

Layers of two kinds alternate in one stack (cfg.layer_types), so the layers
are not scanned: a Python loop over the pattern, each layer reading its own
row of its KIND's stacked leaves. RMSNorm with a weight, eps cfg.norm_eps; x
a layer's input, D = cfg.dim:

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
  Mixer, "full_attention": models/afmoe.attention without its per-head
            norms: q = u wq (cfg.n_heads heads of Dh), k, v (cfg.n_kv_heads),
            NO position encoding, causal softmax at Dh^-0.5,
            (sigmoid(u wg) * heads) wo.
  FFN       models/afmoe.moe_ffn on every layer: models/experts.route over
            ALL cfg.n_experts, routed_ffn over the experts HELD here
            (cfg.expert_lo .. + cfg.experts_held), plus the shared expert.

The family is served from the paged pool alone (engine/paged.py), whose
leaves are granite_hybrid's by what the "kda" kind keeps
(config.STATE_OF_KIND): "k" / "v" of the attention layers, and a leaf a kda
layer each of "conv" [slots, K - 1, 3 H Dh], "lin" [slots, H, Dh, Dh]
float32 (a head's state transposed: ops/delta_rule.py), "csnap" / "snap" the
same two by snapshot, and the "routed" counts (models/afmoe.add_routed).

Params pytree (Lk / La kda / attention layers, E the router's width, Eh
experts held, Fm moe_ffn_dim, V the vocabulary rows held):
  embed [V, D]   head [V, D] (untied)   final_norm [D]
  layers: norm1 norm2 [L, D]
    kda:  w_in [Lk, D, 3 H Dh + 2 r + H] = [wq | wk | wv | wf_down |
          wg_down | w_beta]   conv_w [Lk, K, 3 H Dh]
          wf_up wg_up [Lk, r, H Dh]   a_log [Lk, H] dt_bias [Lk, H Dh] float32
          o_norm [Lk, Dh]   wo [Lk, H Dh, D]
    attn: wq wg [La, D, Hq Dh]  wk wv [La, D, KV Dh]  wo [La, Hq Dh, D]
    moe:  models/afmoe.py's, every layer

`init_params` is models/afmoe.draw_params (an expert's matrices and a
vocabulary row from keys folded from their PUBLISHED index, so the shares of
one seed are shares of one model) over 32 keys; a_log and dt_bias as Mamba-2
draws them (models/granite_hybrid.scan_constants), a layer a key.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import slot_causal_mask
from ..ops.delta_rule import delta_rule_rows, delta_rule_step
from ..ops.norms import rms_norm
from ..ops.ssm_scan import causal_conv_rows
from .afmoe import add_routed, attention, draw_params, moe_ffn
from .experts import BANKS
from .granite_hybrid import _move_rows, _put, _starts, scan_constants
from .mla_moe import ROUTER_BIAS_SCALE

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
    H, KV, Dh, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.conv_kernel
    Hd, r = cfg.linear_heads * Dh, Dh
    E, Eh, Fm = cfg.n_experts, cfg.experts_held, cfg.moe_ffn_dim
    Fs = max(cfg.n_shared_experts, 1) * Fm
    n = stack_depths(cfg)
    Lk, La = n["kda"], n["attn"]
    s = D ** -0.5
    shapes = {
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
        "attn.wq": ((La, D, H * Dh), s), "attn.wg": ((La, D, H * Dh), s),
        "attn.wk": ((La, D, KV * Dh), s), "attn.wv": ((La, D, KV * Dh), s),
        "attn.wo": ((La, H * Dh, D), (H * Dh) ** -0.5),
        "moe.w_router": ((L, D, E), s),
        "moe.router_bias": ((L, E), ROUTER_BIAS_SCALE),
        "moe.w_gate": ((L, Eh, D, Fm), s), "moe.w_up": ((L, Eh, D, Fm), s),
        "moe.w_down": ((L, Eh, Fm, D), Fm ** -0.5),
    }
    if cfg.n_shared_experts:
        shapes.update({
            "moe.ws_gate": ((L, D, Fs), s), "moe.ws_up": ((L, D, Fs), s),
            "moe.ws_down": ((L, Fs, D), Fs ** -0.5)})
    return shapes


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded random parameters (tests and benchmarks): scaled normals, norm
    weights 1, the selection bias, a_log and dt_bias in float32."""
    if cfg.tie_embeddings:
        raise ValueError(f"{cfg.name}: the solar_open2 family's head is untied")
    params = draw_params(cfg, key, leaf_shapes(cfg), LEAF_KEYS,
                         float32=("moe.router_bias",), n_keys=N_KEYS)
    kda = params["layers"]["kda"]
    ks = jax.random.split(key, N_KEYS)
    Lk, H, Hd = stack_depths(cfg)["kda"], cfg.linear_heads, \
        cfg.linear_heads * cfg.head_dim
    consts = [(scan_constants(ka, kd, H)[0], scan_constants(ka, kd, Hd)[1])
              for ka, kd in zip(
                  jax.random.split(ks[LEAF_KEYS["kda.a"]], Lk),
                  jax.random.split(ks[LEAF_KEYS["kda.dt"]], Lk))]
    kda["a_log"] = jnp.stack([a for a, _ in consts])
    kda["dt_bias"] = jnp.stack([b for _, b in consts])
    kda["w_in"] = jnp.concatenate([kda.pop(name) for name in W_IN], axis=2)
    return params


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
                  n_layers: Optional[int] = None):
    raise ValueError(
        f"{cfg.name}: the solar_open2 family is served from the paged pool "
        f"by the continuous engine only (--continuous N --kv-pool-blocks M): "
        f"there is no dense cache of convolution and matrix states"
    )


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params: Params, tokens, pos=0):
    """[B, T] -> [B, T, D] float32."""
    del pos
    return params["embed"][tokens].astype(F32)


@jax.named_scope("head")
def unembed(cfg: ModelConfig, params: Params, x):
    """The last RMSNorm and the untied head: float32 logits over the held
    vocabulary rows."""
    h = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.jnp_dtype)
    return jax.lax.dot_general(
        h, params["head"], (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=F32,
    )


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_mixer(cfg: ModelConfig, lp: Params, h, pool, layer: int, rows,
              tq: int):
    """The "kda" mixer over a paged launch's flat tokens (normed h
    [W, 1, D]); `layer` the layer's index among the kda layers. A row with
    rows.take >= 0 leaves BOTH its states after this launch in that
    snapshot. The delta rule's form follows the launch's shape (below): the
    decode program's one token a row is the recurrence itself, any other
    launch the chunked form; both carry the one `lin` leaf.
    Returns (float32 [W, 1, D], pool)."""
    W = h.shape[0]
    H, Dh, dt_ = cfg.linear_heads, cfg.head_dim, cfg.jnp_dtype
    Hd = H * Dh
    # (handed on as it is: models/granite_hybrid._project says why)
    out = jax.lax.optimization_barrier(
        jnp.dot(h[:, 0], lp["w_in"], preferred_element_type=F32))
    qkv, f, g, b = (out[:, :3 * Hd], out[:, 3 * Hd:3 * Hd + Dh],
                    out[:, 3 * Hd + Dh:3 * Hd + 2 * Dh],
                    out[:, 3 * Hd + 2 * Dh:])
    conv, lin = pool["conv"][layer], pool["lin"][layer]
    csnap, snap = pool["csnap"][layer], pool["snap"][layer]
    slot = jnp.arange(lin.shape[0], dtype=jnp.int32)
    zero = None
    if rows.restore is not None:  # a mixed launch: rows may start tenants
        # a cold start is the delta rule's own (its row's block read as
        # zeros); a prefix hit's row starts from its snapshot
        zero = rows.fresh & (rows.restore < 0)
        lin = _move_rows(lin, snap, rows.fresh & (rows.restore >= 0), slot,
                         rows.restore)
    with jax.named_scope("delta_conv"):
        qkv, conv = causal_conv_rows(
            qkv.astype(dt_), lp["conv_w"], None, _starts(rows, conv, csnap),
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
    if rows.take is not None:  # both states after the launch, by snapshot
        snap = _move_rows(snap, lin, rows.take >= 0, rows.take, slot)
        at = jnp.where(rows.take >= 0, rows.take, csnap.shape[0])  # dropped
        csnap = jax.lax.cond(
            jnp.any(rows.take >= 0),
            lambda: csnap.at[at].set(conv, mode="drop"), lambda: csnap)
    gate = jax.nn.sigmoid(
        jnp.dot(g.astype(dt_), lp["wg_up"], preferred_element_type=F32))
    y = rms_norm(o, lp["o_norm"], cfg.norm_eps).reshape(W, Hd) * gate
    out = jnp.dot(y.astype(dt_), lp["wo"], preferred_element_type=F32)
    return out[:, None], {
        **pool, "conv": _put(pool["conv"], layer, conv),
        "lin": _put(pool["lin"], layer, lin),
        "csnap": _put(pool["csnap"], layer, csnap),
        "snap": _put(pool["snap"], layer, snap),
    }


def forward_layers(cfg: ModelConfig, layers: Params, x, cache, pos,
                   update_gate=None, tp_axis=None, attn_hook=None,
                   valid_start=None, ep_axis=None, attn_seq_len=None):
    """Every layer over a paged launch's flat tokens x [W, 1, D] (float32
    residual) at positions pos [W]; cache the pool (module docstring), whose
    "routed" leaf the expert layers add to; attn_hook a paged hook
    (engine/paged.py) whose `rows()` says how the tokens fall into fleet
    rows. Returns (x, the pool)."""
    if tp_axis is not None or ep_axis is not None or update_gate is not None:
        raise ValueError("the solar_open2 family is not sharded over pp, tp "
                         "or ep")
    if valid_start is not None or not getattr(attn_hook, "paged", False):
        raise ValueError(
            "the solar_open2 family is served from the paged pool only: "
            "flat tokens under a paged hook, no left-padded rows")
    assert x.shape[1] == 1, "the paged launches carry one token a batch row"
    pos = jnp.asarray(pos, jnp.int32)
    mask = slot_causal_mask(pos, 1, attn_seq_len)
    rows = attn_hook.rows()
    tq = attn_hook.tile
    live = getattr(attn_hook, "live", None)
    dt = cfg.jnp_dtype
    banks = {name: layers["moe"][name] for name in BANKS}  # never sliced

    def row(kind, i):  # layer i's leaves of its kind's small stack
        return {name: leaf[i] for name, leaf in layers[kind].items()
                if not (kind == "moe" and name in BANKS)}

    new = dict(cache)
    sizes, away = [], []
    scope = {"kda": "delta_mix", "full_attention": "attn"}
    ik = ia = 0
    for li, kind in enumerate(cfg.layer_types):
        with jax.named_scope(scope[kind]):
            h = rms_norm(x, layers["norm1"][li], cfg.norm_eps).astype(dt)
            if kind == "kda":
                out, new = kda_mixer(cfg, row("kda", ik), h, new, ik, rows, tq)
                ik += 1
            else:
                out, new["k"], new["v"] = attention(
                    cfg, row("attn", ia), h, new["k"], new["v"], pos, None,
                    mask, attn_hook, ia)
                ia += 1
        with jax.named_scope("moe_route"):
            x = x + out
            h = rms_norm(x, layers["norm2"][li], cfg.norm_eps).astype(dt)
        out, counts, elsewhere = moe_ffn(cfg, row("moe", li), banks, li, h,
                                         live)
        sizes.append(counts)
        away.append(elsewhere)
        with jax.named_scope("moe_combine"):
            x = x + out
    return x, add_routed(cache, new, sizes, away)


def forward(cfg: ModelConfig, params: Params, tokens, cache, pos):
    raise ValueError(
        f"{cfg.name}: the solar_open2 family has no dense-cache forward; it "
        f"is served from the paged pool (engine/paged.py)")
