"""Plain reference of gated grouped-query attention with sliding-window and
global layers over routed experts beside a shared one
(arcee-ai/Trinity-Large-Preview, model_type afmoe) in straightforward
float32 `jax.numpy` under `jax.default_matmul_precision("highest")`: a whole
sequence at a time, no kernels, no cache, no batching, the full [T, T] mask
of each layer kind computed in blocks of queries so that it fits, nothing
imported from the program.

RMSNorm with a weight, eps `rms_norm_eps`, everywhere; d = hidden_size; x a
layer's input [T, d]:

  x_0 = E[token] * sqrt(d)                       (mup_enabled)
  layer l     h = x + N2(Attn_l(N1(x)));  y = h + N4(FFN_l(N3(h)))
  after the last layer one more RMSNorm, then the untied head.

  Attn(u)     q = u W_q (H heads of head_dim), k = u W_k, v = u W_v (KV
              heads), g = u W_g (H x head_dim numbers); RMSNorm with a
              weight over each query and key head; on a `sliding_attention`
              layer RoPE theta `rope_theta` over the two HALVES of a head
              (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin) on q and k,
              and the query at i attends j <= i with i - j < sliding_window;
              on a `full_attention` layer no position encoding at all and
              every j <= i; softmax at head_dim^-0.5;
              Attn = (sigmoid(g) * heads) W_o. No bias.
  FFN of the first num_dense_layers layers: W_2 (silu(W_1 h) * W_3 h),
              width intermediate_size
  FFN of the others: s = sigmoid(h W_r) over ALL the router's experts; the
              num_experts_per_tok largest of s + b chosen (b: the expert
              bias, for the choice only); weights s_e / (sum of the chosen
              s + router_norm_eps) (route_norm) x route_scale; every token
              through every HELD expert under a dense mask of those weights
              (zero where not chosen); plus the shared expert, whole.

One chip's share (`expert_share` in the configuration's file): the router
is `router_width` wide, and the experts held are published experts
`expert_lo` .. `expert_lo` + num_experts - 1. What the router sends to the
others is left out, here as in the program: the partial result goes on to
the next layer. Nothing stands in for the other chips. The vocabulary is
the file's `vocab_size` rows of the published table, from row 0.

The weights are random: `make_params` writes down the program's documented
initialisation (models/afmoe.py: 24 keys split from PRNGKey(seed), the
table LEAF_KEY below). An expert's matrix is normal(fold_in(leaf key, layer
x router_width + published expert)) and a vocabulary row normal(fold_in(leaf
key, row)): by published index, so every share of one seed is a share of
one model. Any other stacked leaf [n, ...] is n slices, slice i drawn from
split(key, n)[i]. All in float32, scaled, rounded to the served dtype; norm
weights 1; the expert bias a float32 normal x `init.router_bias_scale`.

A parameter is `params[name][layer]`: a list per name over ALL layers (None
where the layer has no such leaf), so that a wrapper (tools/control.py) can
hand back any layer's matrix changed. Of this model's matrices that wrapper
rounds wq, wk, wv, wo, w_gate / w_up / w_down (the dense layer's, the expert
banks [E, in, out]) and lm_head; the gate projection wg, the shared expert
ws_*, the routers and the norms stay as they are there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # queries per attention block (bounds the score matrix)

LEAF_KEY = {
    "embed": 0, "head": 1,
    "wq": 2, "wk": 3, "wv": 4, "wg": 5, "wo": 6,
    "dense.w_gate": 7, "dense.w_up": 8, "dense.w_down": 9,
    "w_router": 10, "router_bias": 11,
    "moe.w_gate": 12, "moe.w_up": 13, "moe.w_down": 14,
    "ws_gate": 15, "ws_up": 16, "ws_down": 17,
}


def sizes(config: dict) -> dict:
    """The sizes as the configuration file publishes them (HF key names)."""
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    share = config.get("expert_share") or {}
    Eh = config["num_experts"]
    return dict(
        L=len(kinds), kinds=kinds, Ld=config["num_dense_layers"],
        D=config["hidden_size"], H=config["num_attention_heads"],
        KV=config["num_key_value_heads"], Dh=config["head_dim"],
        F=config["intermediate_size"], Fm=config["moe_intermediate_size"],
        Fs=config.get("num_shared_experts", 1) * config["moe_intermediate_size"],
        Eh=Eh, E=share.get("router_width", Eh), lo=share.get("expert_lo", 0),
        k=config["num_experts_per_tok"], V=config["vocab_size"],
        window=config["sliding_window"], theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        renorm=bool(config.get("route_norm", True)),
        scaling=float(config.get("route_scale", 1.0)),
        norm_eps=float(config["init"]["router_norm_eps"]),
        bias_scale=float(config["init"]["router_bias_scale"]),
        scale_embed=bool(config.get("mup_enabled", False)),
    )


def make_params(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The seeded random tree: {name: [layer 0's leaf, layer 1's, ...]}
    (None where a layer has no such leaf), and embed, lm_head, final_norm."""
    s = sizes(config)
    L, Ld, D, H, KV, Dh = (s[n] for n in ("L", "Ld", "D", "H", "KV", "Dh"))
    E, Eh, lo, F, Fm, Fs, V = (s[n] for n in ("E", "Eh", "lo", "F", "Fm", "Fs", "V"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 24)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(k, shape, scale, dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    @functools.partial(jax.jit, static_argnums=(2, 3, 4))
    def keyed(k, ids, shape, scale, dt):  # slice i from fold_in(k, ids[i])
        return jax.lax.map(lambda i: normal(jax.random.fold_in(k, i), shape, scale, dt), ids)

    sc = D ** -0.5
    every, dense, moe = list(range(L)), list(range(Ld)), list(range(Ld, L))
    sliced = {  # name -> (leaf key, the layers that have it, a layer's shape, scale)
        "wq": ("wq", every, (D, H * Dh), sc), "wg": ("wg", every, (D, H * Dh), sc),
        "wk": ("wk", every, (D, KV * Dh), sc), "wv": ("wv", every, (D, KV * Dh), sc),
        "wo": ("wo", every, (H * Dh, D), (H * Dh) ** -0.5),
        "w_router": ("w_router", moe, (D, E), sc),
        "router_bias": ("router_bias", moe, (E,), s["bias_scale"]),
        "ws_gate": ("ws_gate", moe, (D, Fs), sc), "ws_up": ("ws_up", moe, (D, Fs), sc),
        "ws_down": ("ws_down", moe, (Fs, D), Fs ** -0.5),
    }
    params: dict = {}
    for name, (leaf, layers, shape, scale) in sliced.items():
        params[name] = [None] * L
        if not layers:
            continue
        keys = jax.random.split(ks[LEAF_KEY[leaf]], len(layers))
        dt = jnp.float32 if name == "router_bias" else dtype
        for i, l in enumerate(layers):
            params[name][l] = normal(keys[i], shape, float(scale), dt)
    # the dense layers' SwiGLU and the expert banks share their names
    for name, shape_d, scale_d, shape_m, scale_m in (
        ("w_gate", (D, F), sc, (D, Fm), sc), ("w_up", (D, F), sc, (D, Fm), sc),
        ("w_down", (F, D), F ** -0.5, (Fm, D), Fm ** -0.5),
    ):
        params[name] = [None] * L
        if dense:
            keys = jax.random.split(ks[LEAF_KEY["dense." + name]], len(dense))
            for i, l in enumerate(dense):
                params[name][l] = normal(keys[i], shape_d, float(scale_d), dtype)
        for i, l in enumerate(moe):  # published experts lo .. lo + Eh - 1 of layer i
            ids = i * E + lo + jnp.arange(Eh, dtype=jnp.int32)
            params[name][l] = keyed(ks[LEAF_KEY["moe." + name]], ids, shape_m,
                                    float(scale_m), dtype)
    for name in ("norm1", "norm2", "norm3", "norm4"):
        params[name] = [jnp.ones((D,), dtype)] * L
    for name in ("q_norm", "k_norm"):
        params[name] = [jnp.ones((Dh,), dtype)] * L
    rows = jnp.arange(V, dtype=jnp.int32)
    params["embed"] = keyed(ks[LEAF_KEY["embed"]], rows, (D,), 0.02, dtype)
    params["lm_head"] = keyed(ks[LEAF_KEY["head"]], rows, (D,), float(sc), dtype).T
    params["final_norm"] = jnp.ones((D,), dtype)
    return params


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(a):
    return a.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [T, heads, d] rotated over its two halves by positions x
    theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def attention_op(u, lp, *, H, KV, Dh, theta, eps, window):
    """Gated grouped-query attention on a whole sequence u [T, d] (normed).
    window: the layer's sliding window, or None for a global layer, which
    also takes no position encoding."""
    T = u.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    q = _rms((u @ _f32(lp["wq"])).reshape(T, H, Dh), _f32(lp["q_norm"]), eps)
    k = _rms((u @ _f32(lp["wk"])).reshape(T, KV, Dh), _f32(lp["k_norm"]), eps)
    v = (u @ _f32(lp["wv"])).reshape(T, KV, Dh)
    gate = jax.nn.sigmoid(u @ _f32(lp["wg"]))
    if window is not None:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(T, KV, H // KV, Dh)  # query head h reads K/V head h // group

    def attend_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * (Dh ** -0.5)
        seen = pos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= qpos[:, None] - pos[None, :] < window
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v)

    attn = jax.lax.map(attend_block, jnp.arange(T // Q_BLOCK)).reshape(T, H * Dh)
    return (gate * attn) @ _f32(lp["wo"])


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


def routed_part(h, lp, *, lo, **router):
    """The held experts' part: published experts lo .. lo + E_held - 1."""
    w = expert_weights(h, lp["w_router"], lp["router_bias"], **router)
    held = jax.lax.dynamic_slice_in_dim(w, lo, lp["w_gate"].shape[0], axis=1)

    def one_expert(acc, e):  # every token through expert e, weighed (0: not chosen)
        wg, wu, wd, we = e
        return acc + _swiglu(h, wg, wu, wd) * we[:, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    return out


def routed_ffn(h, lp, **router):
    return routed_part(h, lp, **router) + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def layer(x, lp, *, op, ffn, eps):
    x = x + _rms(op(_rms(x, _f32(lp["norm1"]), eps), lp), _f32(lp["norm2"]), eps)
    return x + _rms(ffn(_rms(x, _f32(lp["norm3"]), eps), lp), _f32(lp["norm4"]), eps)


# the leaves a layer asks `params` for
ATTN_LEAVES = ("norm1", "norm2", "norm3", "norm4", "wq", "wk", "wv", "wg", "wo",
               "q_norm", "k_norm")
FFN_LEAVES = {"dense": ("w_gate", "w_up", "w_down"),
              "moe": ("w_router", "router_bias", "w_gate", "w_up", "w_down",
                      "ws_gate", "ws_up", "ws_down")}


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
        kind: functools.partial(
            attention_op, H=s["H"], KV=s["KV"], Dh=s["Dh"], theta=s["theta"],
            eps=s["eps"], window=s["window"] if kind == "sliding_attention" else None)
        for kind in ("sliding_attention", "full_attention")
    }
    ffns = {
        "dense": lambda h, lp: _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]),
        "moe": functools.partial(routed_ffn, lo=s["lo"], k=s["k"], renorm=s["renorm"],
                                 scaling=s["scaling"], norm_eps=s["norm_eps"]),
    }
    run = {
        (o, f): jax.jit(functools.partial(layer, op=ops[o], ffn=ffns[f], eps=s["eps"]))
        for o in ops for f in ffns
    }
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(jnp.float32)
        if s["scale_embed"]:
            x = x * (s["D"] ** 0.5)
        for l, kind in enumerate(s["kinds"]):
            ffn = "dense" if l < s["Ld"] else "moe"
            names = ATTN_LEAVES + FFN_LEAVES[ffn]
            x = run[kind, ffn](x, {name: params[name][l] for name in names})
    return x


def logits(config: dict, params: dict, x):
    """The last norm and the head on hidden states x [n, D] -> [n, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), s["eps"])
        return h @ params["lm_head"].astype(jnp.float32)
