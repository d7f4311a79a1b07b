"""Plain reference of sliding-window layers with a learned sink beside global
layers, the two kinds with their own K/V head counts and rotation bases and
keys wider than values, over routed experts with no shared one
(XiaomiMiMo/MiMo-V2.5, model_type mimo_v2) in straightforward float32
`jax.numpy` under `jax.default_matmul_precision("highest")`: a whole sequence
at a time, no kernels, no cache, no batching, the full [T, T] mask of each
layer kind computed in blocks of queries so that it fits, nothing imported
from the program.

RMSNorm with a weight, eps `layernorm_epsilon`; x a layer's input [T, d]:

  x_0 = E[token]
  layer l     h = x + Attn_l(N1(x));  y = h + FFN_l(N2(h))
  after the last layer one more RMSNorm, then the untied head.

  Attn(u)     by `hybrid_layer_pattern[l]` (0 global, 1 window): KV =
              num_key_value_heads / swa_num_key_value_heads K/V heads, base
              rope_theta / swa_rope_theta; q = u W_q (H heads of head_dim),
              k = u W_k (KV heads of head_dim), v = attention_value_scale x
              (u W_v) (KV heads of v_head_dim). The rotation: on lanes 0 ..
              R-1 of every query and key head, R = partial_rotary_factor x
              head_dim rounded down to an even number (0.334 x 192 -> 64),
              over their two HALVES (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1
              sin) with inverse frequencies base^(-2i/R); lanes R .. pass
              through. Scores q_i . k_j / sqrt(head_dim) over j <= i, on a
              window layer only i - j < sliding_window (the query itself
              counted). Softmax: on a window layer (add_swa_attention_sink_bias)
              p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(sink_h)), a learned
              logit a query head that takes probability and gives no value;
              the plain softmax on a global one. A query head h reads K/V
              head h // (H / KV). Attn = concat_h(sum_j p_ij v_j) W_o. No
              bias, no gate, no per-head norm.
  FFN of the layers with moe_layer_freq 0: W_2 (silu(W_1 h) * W_3 h), width
              intermediate_size
  FFN of the others: s = sigmoid(h W_r) over ALL the router's experts; the
              num_experts_per_tok largest of s + b chosen (b: the selection
              bias of noaux_tc, for the choice only); weights s_e / (sum of
              the chosen s + router_norm_eps) (norm_topk_prob), no scaling
              factor; every token through every HELD expert under a dense
              mask of those weights (zero where not chosen). No shared expert.

One chip's share (`expert_share` in the configuration's file): the router is
`router_width` wide, and the experts held are published experts `expert_lo`
.. `expert_lo` + n_routed_experts - 1. What the router sends to the others
is left out, here as in the program: the partial result goes on to the next
layer. Nothing stands in for the other chips. The vocabulary is the file's
`vocab_size` rows of the published table, from row 0.

The weights are random: `make_params` writes down the program's documented
initialisation (models/mimo_v2.py: 24 keys split from PRNGKey(seed), the
table LEAF_KEY below). An expert's matrix is normal(fold_in(leaf key, layer x
router_width + published expert)) and a vocabulary row normal(fold_in(leaf
key, row)): by published index, so every share of one seed is a share of one
model. An attention leaf is stacked over the layers of its KIND, slice i (the
kind's i-th layer) drawn from split(key, n)[i]; so are the dense layers', the
routers' and the biases' over their layers. All in float32, scaled, rounded
to the served dtype; norm weights 1; the selection bias a float32 normal x
`init.router_bias_scale`, the sink a float32 normal x `init.sink_scale`.

A parameter is `params[name][layer]`: a list per name over ALL layers (None
where the layer has no such leaf), so that a wrapper (tools/control.py) can
hand back any layer's matrix changed. Of this model's matrices that wrapper
rounds wq, wk, wv, wo, w_gate / w_up / w_down (the dense layer's, the expert
banks [E, in, out]) and lm_head; the routers, the sinks and the norms stay as
they are there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # queries per attention block (bounds the score matrix)

LEAF_KEY = {
    "embed": 0, "head": 1,
    "global.wq": 2, "global.wk": 3, "global.wv": 4, "global.wo": 5,
    "window.wq": 6, "window.wk": 7, "window.wv": 8, "window.wo": 9,
    "window.sink": 10,
    "dense.w_gate": 11, "dense.w_up": 12, "dense.w_down": 13,
    "w_router": 14, "router_bias": 15,
    "moe.w_gate": 16, "moe.w_up": 17, "moe.w_down": 18,
}
KINDS = {0: "global", 1: "window"}


def sizes(config: dict) -> dict:
    """The sizes as the configuration file publishes them (HF key names)."""
    kinds = [KINDS[int(k)] for k in config["hybrid_layer_pattern"]]
    routed = [bool(f) for f in config["moe_layer_freq"]]
    if not len(kinds) == len(routed) == config["num_hidden_layers"]:
        raise ValueError("the layer patterns do not name num_hidden_layers layers")
    share = config.get("expert_share") or {}
    Eh, Dk = config["n_routed_experts"], config["head_dim"]
    if (config["swa_head_dim"], config["swa_v_head_dim"], config["swa_num_attention_heads"]) \
            != (Dk, config["v_head_dim"], config["num_attention_heads"]):
        raise ValueError("the two kinds' head widths and query heads differ: not written here")
    return dict(
        L=len(kinds), kinds=kinds, routed=routed, D=config["hidden_size"],
        H=config["num_attention_heads"], Dk=Dk, Dv=config["v_head_dim"],
        KV={"global": config["num_key_value_heads"],
            "window": config["swa_num_key_value_heads"]},
        theta={"global": float(config["rope_theta"]),
               "window": float(config["swa_rope_theta"])},
        sink={"global": bool(config["add_full_attention_sink_bias"]),
              "window": bool(config["add_swa_attention_sink_bias"])},
        R=int(config["partial_rotary_factor"] * Dk) // 2 * 2,
        value_scale=float(config["attention_value_scale"]),
        window=config["sliding_window"],
        F=config["intermediate_size"], Fm=config["moe_intermediate_size"],
        Eh=Eh, E=share.get("router_width", Eh), lo=share.get("expert_lo", 0),
        k=config["num_experts_per_tok"], V=config["vocab_size"],
        eps=float(config["layernorm_epsilon"]),
        renorm=bool(config.get("norm_topk_prob", True)),
        scaling=float(config.get("routed_scaling_factor") or 1.0),
        norm_eps=float(config["init"]["router_norm_eps"]),
        bias_scale=float(config["init"]["router_bias_scale"]),
        sink_scale=float(config["init"]["sink_scale"]),
    )


def make_params(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The seeded random tree: {name: [layer 0's leaf, layer 1's, ...]}
    (None where a layer has no such leaf), and embed, lm_head, final_norm."""
    s = sizes(config)
    L, D, H, Dk, Dv = (s[n] for n in ("L", "D", "H", "Dk", "Dv"))
    E, Eh, lo, F, Fm, V = (s[n] for n in ("E", "Eh", "lo", "F", "Fm", "V"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 24)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(k, shape, scale, dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    @functools.partial(jax.jit, static_argnums=(2, 3, 4))
    def keyed(k, ids, shape, scale, dt):  # slice i from fold_in(k, ids[i])
        return jax.lax.map(lambda i: normal(jax.random.fold_in(k, i), shape, scale, dt), ids)

    sc = D ** -0.5
    dense = [l for l in range(L) if not s["routed"][l]]
    moe = [l for l in range(L) if s["routed"][l]]
    if dense != list(range(len(dense))):
        raise ValueError("dense layers lead the stack (the program's first_k_dense)")
    # name -> (leaf key, the layers that have it, a layer's shape, scale, dtype)
    sliced = {
        "w_router": ("w_router", moe, (D, E), sc, dtype),
        "router_bias": ("router_bias", moe, (E,), s["bias_scale"], jnp.float32),
    }
    params: dict = {name: [None] * L for name in ("wq", "wk", "wv", "wo", "sink")}
    for kind in ("global", "window"):
        layers, KV = [l for l in range(L) if s["kinds"][l] == kind], s["KV"][kind]
        leaves = {"wq": ((D, H * Dk), sc, dtype), "wk": ((D, KV * Dk), sc, dtype),
                  "wv": ((D, KV * Dv), sc, dtype), "wo": ((H * Dv, D), (H * Dv) ** -0.5, dtype)}
        if s["sink"][kind]:
            leaves["sink"] = ((H,), s["sink_scale"], jnp.float32)
        for name, (shape, scale, dt) in leaves.items():
            if layers:
                keys = jax.random.split(ks[LEAF_KEY[f"{kind}.{name}"]], len(layers))
            for i, l in enumerate(layers):
                params[name][l] = normal(keys[i], shape, float(scale), dt)
    for name, (leaf, layers, shape, scale, dt) in sliced.items():
        params[name] = [None] * L
        if layers:
            keys = jax.random.split(ks[LEAF_KEY[leaf]], len(layers))
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


def _rope(x, positions, theta, R):
    """x [T, heads, d]: lanes 0 .. R-1 rotated over their two halves by
    positions x theta^(-2i/R), lanes R .. d-1 as they are."""
    inv = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    ang = positions.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    x1, x2 = x[..., : R // 2], x[..., R // 2: R]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang), x[..., R:]], axis=-1)


def attention_op(u, lp, *, H, KV, Dk, Dv, R, theta, value_scale, window, sink):
    """Grouped-query attention on a whole sequence u [T, d] (normed). window:
    the layer's sliding window, or None for a global layer; sink: whether the
    layer's softmax has the learned logit lp["sink"] [H] in its denominator."""
    T = u.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    q = _rope((u @ _f32(lp["wq"])).reshape(T, H, Dk), pos, theta, R)
    k = _rope((u @ _f32(lp["wk"])).reshape(T, KV, Dk), pos, theta, R)
    v = value_scale * (u @ _f32(lp["wv"])).reshape(T, KV, Dv)
    q = q.reshape(T, KV, H // KV, Dk)  # query head h reads K/V head h // group

    def attend_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * (Dk ** -0.5)
        seen = pos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= qpos[:, None] - pos[None, :] < window
        e = jnp.where(seen[None, None], scores, -jnp.inf)
        top = jnp.max(e, axis=-1, keepdims=True)
        if sink:
            s_h = _f32(lp["sink"]).reshape(KV, H // KV, 1, 1)
            top = jnp.maximum(top, s_h)
        e = jnp.exp(e - top)
        total = jnp.sum(e, axis=-1, keepdims=True)
        if sink:  # the sink takes probability and gives no value
            total = total + jnp.exp(s_h - top)
        return jnp.einsum("kgqs,skd->qkgd", e / total, v)

    attn = jax.lax.map(attend_block, jnp.arange(T // Q_BLOCK)).reshape(T, H * Dv)
    return attn @ _f32(lp["wo"])


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


def routed_ffn(h, lp, *, lo, **router):
    """The held experts' part: published experts lo .. lo + E_held - 1."""
    w = expert_weights(h, lp["w_router"], lp["router_bias"], **router)
    held = jax.lax.dynamic_slice_in_dim(w, lo, lp["w_gate"].shape[0], axis=1)

    def one_expert(acc, e):  # every token through expert e, weighed (0: not chosen)
        wg, wu, wd, we = e
        return acc + _swiglu(h, wg, wu, wd) * we[:, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    return out


def layer(x, lp, *, op, ffn, eps):
    x = x + op(_rms(x, _f32(lp["norm1"]), eps), lp)
    return x + ffn(_rms(x, _f32(lp["norm2"]), eps), lp)


# the leaves a layer asks `params` for
ATTN_LEAVES = ("norm1", "norm2", "wq", "wk", "wv", "wo")
FFN_LEAVES = {"dense": ("w_gate", "w_up", "w_down"),
              "moe": ("w_router", "router_bias", "w_gate", "w_up", "w_down")}


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
            attention_op, H=s["H"], KV=s["KV"][kind], Dk=s["Dk"], Dv=s["Dv"], R=s["R"],
            theta=s["theta"][kind], value_scale=s["value_scale"],
            window=s["window"] if kind == "window" else None, sink=s["sink"][kind])
        for kind in ("global", "window")
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
        for l, kind in enumerate(s["kinds"]):
            ffn = "moe" if s["routed"][l] else "dense"
            names = ATTN_LEAVES + FFN_LEAVES[ffn] + (("sink",) if s["sink"][kind] else ())
            x = run[kind, ffn](x, {name: params[name][l] for name in names})
    return x


def logits(config: dict, params: dict, x):
    """The last norm and the head on hidden states x [n, D] -> [n, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), s["eps"])
        return h @ params["lm_head"].astype(jnp.float32)
