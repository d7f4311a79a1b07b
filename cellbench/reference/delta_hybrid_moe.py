"""Plain reference of the delta-rule / attention hybrid over routed experts
(upstage/Solar-Open2, model_type solar_open2) in straightforward float32
`jax.numpy` under `jax.default_matmul_precision("highest")`: a whole
sequence at a time, no kernels, no cache, no chunking of the recurrence, no
carried state between calls (the delta-rule layers scan the sequence itself,
token by token), no batching, nothing imported from the program.

RMSNorm eps `rms_norm_eps` with a weight everywhere; x a layer's input
[T, d]:

  embedding   table[token]
  layer l     h = x + Mixer_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
  head        RMSNorm, the untied head

  Mixer of a KDA layer (every layer not in `gqa_layers`; Kimi Delta
  Attention, arXiv:2510.26692: H = linear_attn_config.num_heads heads, keys
  and values Dh = linear_attn_config.head_dim wide, K =
  short_conv_kernel_size taps, r = Dh the low-rank pairs' width):
              q, k, v = conv(u W_q), conv(u W_k), conv(u W_v), each
              silu(sum_j w[j] * x_{t-(K-1)+j}): depthwise, causal, no bias,
              inputs before the first token 0: K shifted sums;
              q, k <- x / sqrt(sum x^2 + 1e-6) a head;
              g_t = -exp(A_log_h) softplus(u W_f_down W_f_up + dt_bias)
              [H, Dh]: the log of the token's decay a key CHANNEL;
              beta_t = 2 sigmoid(u W_beta) a head under kda_allow_neg_eigval
              (sigmoid alone without);
              per head, token by token, S [Dh, Dh] (keys x values):
              S <- Diag(exp g_t) S;  S <- S + beta_t k_t (v_t - k_t^T S)^T
              (which is S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} +
              beta_t k_t v_t^T);  o_t = S^T q_t Dh^-0.5;
              Mixer = (RMSNorm_head(o_t) * sigmoid(u W_g_down W_g_up)) W_o.
  Mixer of a GQA layer (`gqa_layers`; H_q query heads, KV key/value heads
  of head_dim): q, k, v = u W_q, u W_k, u W_v; NO rotary and no other
  position signal (use_rope false); causal softmax of q k^T head_dim^-0.5;
  Mixer = (attn * sigmoid(u W_gate)) W_o (use_gqa_gate: elementwise over
  H_q head_dim numbers). No bias, no qk-norm.
  FFN of every layer (first_k_dense_replace 0): s = sigmoid(h W_router) over
  ALL the router's experts, the num_experts_per_tok largest of s + bias
  chosen, their s renormalised to sum 1 (norm_topk_prob) x
  routed_scaling_factor; SwiGLU experts of moe_intermediate_size; plus one
  shared SwiGLU expert of n_shared_experts x moe_intermediate_size for every
  token.

One chip's share (`expert_share` in the configuration's file): the router is
`router_width` wide, and the experts held are published experts `expert_lo`
.. `expert_lo` + n_routed_experts - 1. What the router sends to the others
is left out, here as in the program: the partial result goes on to the next
layer. Nothing stands in for the other chips. The vocabulary is the file's
`vocab_size` rows of the published table, from row 0.

The delta rule is the literal recurrence (`lax.scan` over t), the sequence
computed in blocks of Q_BLOCK tokens with the state handed from block to
block, so that the check's rows fit the chip after the server has gone.

The weights are random: `make_params` writes down the program's documented
initialisation (models/solar_open2.py: 32 keys split from PRNGKey(seed), the
table LEAF_KEY below). An expert's matrix is normal(fold_in(leaf key, layer x
router_width + published expert)) and a vocabulary row normal(fold_in(leaf
key, row)): by published index, so every share of one seed is a share of one
model. A mixer's leaf is stacked over the layers of its KIND, slice i (the
kind's i-th layer) drawn from split(key, n)[i]; the routers, the biases and
the shared experts over all layers. All in float32, scaled (fan_in^-0.5; the
taps K^-0.5; the embedding 0.02), rounded to the served dtype; norm weights
1; the selection bias a float32 normal x `init.router_bias_scale`; A_log =
log(a), a uniform on [1, 16] a head, dt_bias the inverse softplus of a dt
log-uniform on [0.001, 0.1] a channel, float32, a key a layer from split of
their own keys (Mamba-2's constants).

A parameter is `params[name][layer]`: a list per name over ALL layers (None
where the layer has no such leaf), so that a wrapper (tools/control.py) can
hand back any layer's matrix changed. Of this model's matrices that wrapper
rounds wq, wk, wv, wo (both kinds of mixer), w_gate / w_up / w_down (the
expert banks [E, in, out]) and lm_head; the low-rank pairs, w_beta, the
attention gate, the taps, the routers, the shared experts and the norms stay
as they are there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # tokens a block of the recurrence and of attention's queries
L2_EPS = 1e-6

LEAF_KEY = {
    "embed": 0, "head": 1,
    "kda.wq": 2, "kda.wk": 3, "kda.wv": 4, "kda.wf_down": 5, "kda.wf_up": 6,
    "kda.wg_down": 7, "kda.wg_up": 8, "kda.w_beta": 9, "kda.conv_w": 10,
    "kda.wo": 11, "kda.a": 12, "kda.dt": 13,
    "attn.wq": 14, "attn.wk": 15, "attn.wv": 16, "attn.wg": 17, "attn.wo": 18,
    "w_router": 19, "router_bias": 20,
    "moe.w_gate": 21, "moe.w_up": 22, "moe.w_down": 23,
    "ws_gate": 24, "ws_up": 25, "ws_down": 26,
}


def sizes(config: dict) -> dict:
    """The sizes as the configuration file publishes them (HF key names)."""
    L = config["num_hidden_layers"]
    gqa = set(config["gqa_layers"])
    kinds = ["attn" if l in gqa else "kda" for l in range(L)]
    if config.get("first_k_dense_replace", 0) or config.get("use_rope", False):
        raise ValueError("every layer routes and none rotates: not written here")
    if config.get("kda_use_full_proj", False) or not config.get("use_gqa_gate", True):
        raise ValueError("low-rank decay / gate pairs and a gated GQA layer are what is written here")
    lin = config["linear_attn_config"]
    share = config.get("expert_share") or {}
    Eh = config["n_routed_experts"]
    return dict(
        L=L, kinds=kinds, D=config["hidden_size"],
        H=config["num_attention_heads"], KV=config["num_key_value_heads"],
        Dh=config["head_dim"], Hl=lin["num_heads"], Dl=lin["head_dim"],
        K=lin["short_conv_kernel_size"],
        beta=2.0 if config.get("kda_allow_neg_eigval") else 1.0,
        Fm=config["moe_intermediate_size"],
        Fs=config["n_shared_experts"] * config["moe_intermediate_size"],
        Eh=Eh, E=share.get("router_width", Eh), lo=share.get("expert_lo", 0),
        k=config["num_experts_per_tok"], V=config["vocab_size"],
        eps=float(config["rms_norm_eps"]),
        renorm=bool(config.get("norm_topk_prob", True)),
        scaling=float(config.get("routed_scaling_factor") or 1.0),
        norm_eps=float(config["init"]["router_norm_eps"]),
        bias_scale=float(config["init"]["router_bias_scale"]),
    )


def make_params(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The seeded random tree: {name: [layer 0's leaf, layer 1's, ...]}
    (None where a layer has no such leaf), and embed, lm_head, final_norm."""
    s = sizes(config)
    L, D, H, KV, Dh = (s[n] for n in ("L", "D", "H", "KV", "Dh"))
    Hd, r, K = s["Hl"] * s["Dl"], s["Dl"], s["K"]
    E, Eh, lo, Fm, Fs, V = (s[n] for n in ("E", "Eh", "lo", "Fm", "Fs", "V"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 32)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(k, shape, scale, dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    @functools.partial(jax.jit, static_argnums=(2, 3, 4))
    def keyed(k, ids, shape, scale, dt):  # slice i from fold_in(k, ids[i])
        return jax.lax.map(lambda i: normal(jax.random.fold_in(k, i), shape, scale, dt), ids)

    sc = D ** -0.5
    kda = [l for l in range(L) if s["kinds"][l] == "kda"]
    attn = [l for l in range(L) if s["kinds"][l] == "attn"]
    every = list(range(L))
    # leaf key -> (its name in `params`, the layers that have it, a layer's
    # shape, scale, dtype); wq, wk, wv, wo name both kinds' matrices
    sliced = {
        "kda.wq": ("wq", kda, (D, Hd), sc, dtype), "kda.wk": ("wk", kda, (D, Hd), sc, dtype),
        "kda.wv": ("wv", kda, (D, Hd), sc, dtype),
        "kda.wf_down": ("wf_down", kda, (D, r), sc, dtype),
        "kda.wf_up": ("wf_up", kda, (r, Hd), r ** -0.5, dtype),
        "kda.wg_down": ("wg_down", kda, (D, r), sc, dtype),
        "kda.wg_up": ("wg_up", kda, (r, Hd), r ** -0.5, dtype),
        "kda.w_beta": ("w_beta", kda, (D, s["Hl"]), sc, dtype),
        "kda.conv_w": ("conv_w", kda, (K, 3 * Hd), K ** -0.5, dtype),
        "kda.wo": ("wo", kda, (Hd, D), Hd ** -0.5, dtype),
        "attn.wq": ("wq", attn, (D, H * Dh), sc, dtype),
        "attn.wk": ("wk", attn, (D, KV * Dh), sc, dtype),
        "attn.wv": ("wv", attn, (D, KV * Dh), sc, dtype),
        "attn.wg": ("wg", attn, (D, H * Dh), sc, dtype),
        "attn.wo": ("wo", attn, (H * Dh, D), (H * Dh) ** -0.5, dtype),
        "w_router": ("w_router", every, (D, E), sc, dtype),
        "router_bias": ("router_bias", every, (E,), s["bias_scale"], jnp.float32),
        "ws_gate": ("ws_gate", every, (D, Fs), sc, dtype),
        "ws_up": ("ws_up", every, (D, Fs), sc, dtype),
        "ws_down": ("ws_down", every, (Fs, D), Fs ** -0.5, dtype),
    }
    params: dict = {}
    for leaf, (name, layers, shape, scale, dt) in sliced.items():
        params.setdefault(name, [None] * L)
        keys = jax.random.split(ks[LEAF_KEY[leaf]], len(layers))
        for i, l in enumerate(layers):
            params[name][l] = normal(keys[i], shape, float(scale), dt)
    for name, shape, scale in (("w_gate", (D, Fm), sc), ("w_up", (D, Fm), sc),
                               ("w_down", (Fm, D), Fm ** -0.5)):
        params[name] = [  # published experts lo .. lo + Eh - 1 of layer l
            keyed(ks[LEAF_KEY["moe." + name]], l * E + lo + jnp.arange(Eh, dtype=jnp.int32),
                  shape, float(scale), dtype) for l in range(L)]
    lo_dt, hi_dt = math.log(0.001), math.log(0.1)
    keys_a = jax.random.split(ks[LEAF_KEY["kda.a"]], len(kda))
    keys_dt = jax.random.split(ks[LEAF_KEY["kda.dt"]], len(kda))
    for name in ("a_log", "dt_bias", "o_norm"):
        params[name] = [None] * L
    for i, l in enumerate(kda):
        a = jax.random.uniform(keys_a[i], (s["Hl"],), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(keys_dt[i], (Hd,), jnp.float32, lo_dt, hi_dt))
        params["a_log"][l] = jnp.log(a)
        params["dt_bias"][l] = dt + jnp.log(-jnp.expm1(-dt))
        params["o_norm"][l] = jnp.ones((s["Dl"],), dtype)
    for name in ("norm1", "norm2"):
        params[name] = [jnp.ones((D,), dtype)] * L
    rows = jnp.arange(V, dtype=jnp.int32)
    params["embed"] = keyed(ks[LEAF_KEY["embed"]], rows, (D,), 0.02, dtype)
    params["lm_head"] = keyed(ks[LEAF_KEY["head"]], rows, (D,), float(sc), dtype).T
    params["final_norm"] = jnp.ones((D,), dtype)
    return params


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(a):
    return a.astype(jnp.float32)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def conv(x, w):
    """The causal depthwise convolution of x [T, C] with taps w [K, C]
    (w[K - 1] the token's own): K shifted sums."""
    T, K = x.shape[0], w.shape[0]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(w[j] * padded[j:j + T] for j in range(K))


def delta_block(S, q, k, v, g, beta):
    """The recurrence over one block of tokens from state S [H, Dk, Dv]: q,
    k, g [T, H, Dk], v [T, H, Dv], beta [T, H]. Returns (S after, o
    [T, H, Dv])."""
    def step(S, t):
        qt, kt, vt, gt, bt = t
        S = jnp.exp(gt)[:, :, None] * S
        err = vt - jnp.einsum("hd,hdv->hv", kt, S)
        S = S + bt[:, None, None] * kt[:, :, None] * err[:, None, :]
        return S, jnp.einsum("hd,hdv->hv", qt, S)

    return jax.lax.scan(step, S, (q, k, v, g, beta))


def kda_op(u, lp, *, H, Dh, beta_scale, eps):
    """The KDA mixer on a whole sequence u [T, d] (normed)."""
    T, Hd = u.shape[0], H * Dh
    taps = _f32(lp["conv_w"])
    q, k, v = (jax.nn.silu(conv(u @ _f32(lp[name]), taps[:, i * Hd:(i + 1) * Hd]))
               .reshape(T, H, Dh) for i, name in enumerate(("wq", "wk", "wv")))
    q, k = _unit(q) * Dh ** -0.5, _unit(k)
    g = jax.nn.softplus(u @ _f32(lp["wf_down"]) @ _f32(lp["wf_up"]) + lp["dt_bias"][None, :])
    g = -jnp.exp(lp["a_log"])[None, :, None] * g.reshape(T, H, Dh)
    beta = beta_scale * jax.nn.sigmoid(u @ _f32(lp["w_beta"]))
    S = jnp.zeros((H, Dh, Dh), jnp.float32)
    os_ = []
    for at in range(0, T, Q_BLOCK):  # the state handed from block to block
        cut = slice(at, min(at + Q_BLOCK, T))
        S, o = delta_block(S, q[cut], k[cut], v[cut], g[cut], beta[cut])
        os_.append(o)
    o = _rms(jnp.concatenate(os_), _f32(lp["o_norm"]), eps).reshape(T, Hd)
    gate = jax.nn.sigmoid(u @ _f32(lp["wg_down"]) @ _f32(lp["wg_up"]))
    return (o * gate) @ _f32(lp["wo"])


def attention_op(u, lp, *, H, KV, Dh):
    """Causal grouped-query attention without a position encoding on a
    whole sequence u [T, d] (normed), its heads gated elementwise."""
    T = u.shape[0]
    q = (u @ _f32(lp["wq"])).reshape(T, KV, H // KV, Dh)
    k = (u @ _f32(lp["wk"])).reshape(T, KV, Dh)
    v = (u @ _f32(lp["wv"])).reshape(T, KV, Dh)
    pos = jnp.arange(T, dtype=jnp.int32)

    def attend_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        t = i * Q_BLOCK + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * Dh ** -0.5
        scores = jnp.where((pos[None, :] <= t[:, None])[None, None], scores, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(attend_block, jnp.arange(T // Q_BLOCK)).reshape(T, H * Dh)
    return (o * jax.nn.sigmoid(u @ _f32(lp["wg"]))) @ _f32(lp["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def expert_weights(h, w_router, bias, *, k, renorm, scaling, norm_eps):
    """[T, E] float32: each token's weight on each of the router's experts,
    zero where the expert was not chosen."""
    s = jax.nn.sigmoid(h @ _f32(w_router))
    _, chosen = jax.lax.top_k(s + _f32(bias), k)
    picked = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32), axis=-2)
    w = s * picked
    if renorm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return w * scaling


def moe_ffn(h, lp, *, lo, **router):
    """The held experts' part (published experts lo .. lo + E_held - 1) and
    the shared expert, which every token takes."""
    w = expert_weights(h, lp["w_router"], lp["router_bias"], **router)
    held = jax.lax.dynamic_slice_in_dim(w, lo, lp["w_gate"].shape[0], axis=1)

    def one_expert(acc, e):  # every token through expert e, weighed (0: not chosen)
        wg, wu, wd, we = e
        return acc + _swiglu(h, wg, wu, wd) * we[:, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    return out + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def layer(x, lp, *, op, ffn, eps):
    x = x + op(_rms(x, _f32(lp["norm1"]), eps), lp)
    return x + ffn(_rms(x, _f32(lp["norm2"]), eps), lp)


# the leaves a layer asks `params` for, by its mixer
OP_LEAVES = {
    "kda": ("wq", "wk", "wv", "wf_down", "wf_up", "wg_down", "wg_up", "w_beta",
            "conv_w", "a_log", "dt_bias", "o_norm", "wo"),
    "attn": ("wq", "wk", "wv", "wg", "wo"),
}
FFN_LEAVES = ("norm1", "norm2", "w_router", "router_bias", "w_gate", "w_up",
              "w_down", "ws_gate", "ws_up", "ws_down")


def forward(config: dict, params: dict, ids):
    """Run one sequence of token ids through every layer. Returns the final
    hidden states [T_padded, D] before the last norm (pad at the end:
    causality keeps real tokens from seeing it). `params[name][l]` is all
    that is asked of a leaf."""
    s = sizes(config)
    n = len(ids)
    T = -(-n // Q_BLOCK) * Q_BLOCK
    toks = jnp.zeros((T,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32))
    ops = {
        "kda": functools.partial(kda_op, H=s["Hl"], Dh=s["Dl"], beta_scale=s["beta"],
                                 eps=s["eps"]),
        "attn": functools.partial(attention_op, H=s["H"], KV=s["KV"], Dh=s["Dh"]),
    }
    ffn = functools.partial(moe_ffn, lo=s["lo"], k=s["k"], renorm=s["renorm"],
                            scaling=s["scaling"], norm_eps=s["norm_eps"])
    run = {kind: jax.jit(functools.partial(layer, op=op, ffn=ffn, eps=s["eps"]))
           for kind, op in ops.items()}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(jnp.float32)
        for l, kind in enumerate(s["kinds"]):
            names = FFN_LEAVES + OP_LEAVES[kind]
            x = run[kind](x, {name: params[name][l] for name in names})
    return x


def logits(config: dict, params: dict, x):
    """The last norm and the head on hidden states x [n, D] -> [n, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), s["eps"])
        return h @ params["lm_head"].astype(jnp.float32)
