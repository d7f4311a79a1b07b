"""Plain reference of the gated-short-convolution / attention hybrid over
routed experts (LiquidAI/LFM2-24B-A2B, model_type lfm2_moe) in
straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: a whole sequence at a time, no
kernels, no cache, no recurrent state (the convolution reads the sequence
itself, shifted), no batching, nothing imported from the program.

RMSNorm eps `norm_eps` everywhere; x a layer's input [T, d]:

  layer l     h = x + Op_l(RMSNorm_op(x));  y = h + FFN_l(RMSNorm_ffn(h))
  after the last layer one more RMSNorm (the family's `embedding_norm`),
  then the head, which is the embedding table (tied).

  Op of a `conv` layer (L = conv_L_cache taps, no bias, no positions):
              [B | C | X] = u W_in (d -> 3d, split in that order); z = B * X;
              c_t = sum_{j<L} w[j] * z_{t-(L-1)+j} (depthwise, causal, one
              weight vector of d numbers a tap; z before the first token is
              0); Op = (C * c) W_out
  Op of a `full_attention` layer: GQA, H query heads and KV key/value heads
              of head_dim; RMSNorm with a weight over each query and key
              head before the rotation; RoPE theta as published, over the
              two HALVES of a head (x1, x2) -> (x1 cos - x2 sin, x2 cos +
              x1 sin); causal softmax at head_dim^-0.5; no bias
  FFN of the first num_dense_layers layers: W_2 (silu(W_1 h) * W_3 h),
              width intermediate_size
  FFN of the others: s = sigmoid(h W_r); the num_experts_per_tok largest of
              s + b are chosen (b: the expert bias, for the choice only);
              weights s_e / (sum of the chosen s + router_norm_eps) (under
              norm_topk_prob) x routed_scaling_factor; every token goes
              through EVERY expert under a dense mask of those weights (zero
              where not chosen). No shared expert.

Departures from the published model: none in the layers. The weights are
random: `make_params` writes down the program's documented initialisation
(models/lfm2.py: 24 keys split from PRNGKey(seed), the table LEAF_KEY below;
a stacked leaf [n, ...] is n slices, slice i drawn from split(key, n)[i] in
float32, scaled, and rounded to the served dtype, where n counts the layers
of the leaf's KIND in stack order; the vocabulary table is 8 such slices of
rows; norm weights 1; the expert bias a float32 normal x
`init.router_bias_scale`). The tree is held in the served dtype and a layer
is upcast when it is used.

A parameter is `params[name][layer]`: a list per name over ALL layers (None
where the layer has no such leaf), so that a wrapper (tools/control.py) can
hand back any layer's matrix changed; `forward` asks a layer only for the
leaves of its own kind. `lm_head` is the embedding table transposed, a
leaf of its own for that wrapper's sake. Every operator's matrices go by
the attention operator's names, so that the wrapper's 8-bit control rounds
the convolution operator's too: its output projection W_out is `wo` (the
program's w_out), and the three column blocks of its in-projection W_in
(the program's ONE leaf w_in, drawn whole and cut here) are `wk` (B, which
gates what enters the memory, as a key does), `wq` (C, which gates what is
read out, as a query does) and `wv` (X, what is mixed, as a value is);
u W_in cut in three is u times each block. So of this model's matrices
tools/control.py quantizes every operator's wq, wk, wv and wo, w_gate,
w_up, w_down (the dense layer's, the expert banks [E, in, out]) and
lm_head; the taps, the routers and the norms stay as they are there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # queries per attention block (bounds the score matrix)

LEAF_KEY = {
    "embed": 0,
    "conv.w_in": 1, "conv.w_conv": 2, "conv.w_out": 3,
    "attn.wq": 4, "attn.wk": 5, "attn.wv": 6, "attn.wo": 7,
    "dense.w_gate": 8, "dense.w_up": 9, "dense.w_down": 10,
    "moe.w_router": 11, "moe.router_bias": 12,
    "moe.w_gate": 13, "moe.w_up": 14, "moe.w_down": 15,
}


def sizes(config: dict) -> dict:
    """The sizes as the configuration file publishes them (HF key names)."""
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    H = config["num_attention_heads"]
    return dict(
        L=len(kinds), kinds=kinds, Ld=config["num_dense_layers"],
        D=config["hidden_size"], H=H, KV=config["num_key_value_heads"],
        Dh=config.get("head_dim") or config["hidden_size"] // H,
        K=config["conv_L_cache"], F=config["intermediate_size"],
        Fm=config["moe_intermediate_size"], E=config["num_experts"],
        k=config["num_experts_per_tok"], V=config["vocab_size"],
        theta=float(config["rope_parameters"]["rope_theta"]),
        eps=float(config["norm_eps"]),
        renorm=bool(config.get("norm_topk_prob", True)),
        scaling=float(config.get("routed_scaling_factor", 1.0)),
        norm_eps=float(config["init"]["router_norm_eps"]),
        bias_scale=float(config["init"]["router_bias_scale"]),
    )


def make_params(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The seeded random tree: {name: [layer 0's leaf, layer 1's, ...]}
    (None where a layer has no such leaf), and embed, lm_head, final_norm."""
    s = sizes(config)
    L, Ld, D, H, KV, Dh, K = (s[n] for n in ("L", "Ld", "D", "H", "KV", "Dh", "K"))
    E, F, Fm, V = s["E"], s["F"], s["Fm"], s["V"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 24)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(k, shape, scale, dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    sc = D ** -0.5
    conv = [l for l in range(L) if s["kinds"][l] == "conv"]
    attn = [l for l in range(L) if s["kinds"][l] == "full_attention"]
    dense, moe = list(range(Ld)), list(range(Ld, L))
    shapes = {  # kind.name -> (the layers that have it, a layer's shape, scale)
        "conv.w_in": (conv, (D, 3 * D), sc),
        "conv.w_conv": (conv, (K, D), K ** -0.5),
        "conv.w_out": (conv, (D, D), sc),
        "attn.wq": (attn, (D, H * Dh), sc), "attn.wk": (attn, (D, KV * Dh), sc),
        "attn.wv": (attn, (D, KV * Dh), sc),
        "attn.wo": (attn, (H * Dh, D), (H * Dh) ** -0.5),
        "dense.w_gate": (dense, (D, F), sc), "dense.w_up": (dense, (D, F), sc),
        "dense.w_down": (dense, (F, D), F ** -0.5),
        "moe.w_router": (moe, (D, E), sc),
        "moe.router_bias": (moe, (E,), s["bias_scale"]),
        "moe.w_gate": (moe, (E, D, Fm), sc), "moe.w_up": (moe, (E, D, Fm), sc),
        "moe.w_down": (moe, (E, Fm, D), Fm ** -0.5),
    }
    params: dict = {}
    for path, (layers, shape, scale) in shapes.items():
        # conv.w_in is cut into its blocks B | C | X (the module docstring)
        names = {"conv.w_out": ["wo"], "conv.w_in": ["wk", "wq", "wv"]}.get(
            path, [path.split(".")[1]])
        for name in names:
            params.setdefault(name, [None] * L)
        if not layers:
            continue
        keys = jax.random.split(ks[LEAF_KEY[path]], len(layers))
        dt = jnp.float32 if names == ["router_bias"] else dtype
        for i, l in enumerate(layers):
            leaf = normal(keys[i], shape, float(scale), dt)
            blocks = jnp.split(leaf, len(names), axis=-1) if len(names) > 1 else [leaf]
            for name, block in zip(names, blocks):
                params[name][l] = block
    params["op_norm"] = [jnp.ones((D,), dtype)] * L
    params["ffn_norm"] = [jnp.ones((D,), dtype)] * L
    for name in ("q_norm", "k_norm"):
        params[name] = [jnp.ones((Dh,), dtype) if l in attn else None for l in range(L)]
    n = 8 if V % 8 == 0 else 1  # the vocabulary table: 8 slices of rows
    keys = jax.random.split(ks[LEAF_KEY["embed"]], n)
    params["embed"] = jnp.concatenate(
        [normal(keys[i], (V // n, D), 0.02, dtype) for i in range(n)]
    )
    params["lm_head"] = params["embed"].T  # tied
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


def conv_op(u, lp, *, K):
    """The gated short convolution on a whole sequence u [T, d] (normed)."""
    T, d = u.shape
    b, c_gate, x = u @ _f32(lp["wk"]), u @ _f32(lp["wq"]), u @ _f32(lp["wv"])
    z = jnp.concatenate([jnp.zeros((K - 1, d), jnp.float32), b * x])
    w = _f32(lp["w_conv"])
    c = sum(w[j] * z[j:j + T] for j in range(K))
    return (c_gate * c) @ _f32(lp["wo"])


def attention_op(u, lp, *, H, KV, Dh, theta, eps):
    """Grouped-query attention on a whole sequence u [T, d] (normed)."""
    T = u.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    q = _rms((u @ _f32(lp["wq"])).reshape(T, H, Dh), _f32(lp["q_norm"]), eps)
    k = _rms((u @ _f32(lp["wk"])).reshape(T, KV, Dh), _f32(lp["k_norm"]), eps)
    v = (u @ _f32(lp["wv"])).reshape(T, KV, Dh)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(T, KV, H // KV, Dh)  # query head h reads K/V head h // group

    def attend_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * (Dh ** -0.5)
        scores = jnp.where((pos[None, :] <= qpos[:, None])[None, None], scores, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v)

    attn = jax.lax.map(attend_block, jnp.arange(T // Q_BLOCK)).reshape(T, H * Dh)
    return attn @ _f32(lp["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def expert_weights(h, w_router, bias, *, k, renorm, scaling, norm_eps):
    """[T, E] float32: each token's weight on each expert, zero where the
    expert was not chosen."""
    s = jax.nn.sigmoid(h @ _f32(w_router))
    _, chosen = jax.lax.top_k(s + _f32(bias), k)
    picked = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32), axis=-2)
    w = s * picked
    if renorm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return w * scaling


def routed_ffn(h, lp, **router):
    w = expert_weights(h, lp["w_router"], lp["router_bias"], **router)

    def one_expert(acc, e):  # every token through expert e, weighed (0: not chosen)
        wg, wu, wd, we = e
        return acc + _swiglu(h, wg, wu, wd) * we[:, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], w.T))
    return out


def layer(x, lp, *, op, ffn, eps):
    x = x + op(_rms(x, _f32(lp["op_norm"]), eps), lp)
    return x + ffn(_rms(x, _f32(lp["ffn_norm"]), eps), lp)


# the leaves a layer asks `params` for, by its operator and its FFN
OP_LEAVES = {"conv": ("wk", "wq", "wv", "w_conv", "wo"),
             "full_attention": ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}
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
        "conv": functools.partial(conv_op, K=s["K"]),
        "full_attention": functools.partial(
            attention_op, H=s["H"], KV=s["KV"], Dh=s["Dh"], theta=s["theta"], eps=s["eps"]),
    }
    ffns = {
        "dense": lambda h, lp: _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]),
        "moe": functools.partial(routed_ffn, k=s["k"], renorm=s["renorm"],
                                 scaling=s["scaling"], norm_eps=s["norm_eps"]),
    }
    run = {
        (o, f): jax.jit(functools.partial(layer, op=ops[o], ffn=ffns[f], eps=s["eps"]))
        for o in ops for f in ffns
    }
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(jnp.float32)
        for l, kind in enumerate(s["kinds"]):
            ffn = "dense" if l < s["Ld"] else "moe"
            names = ("op_norm", "ffn_norm") + OP_LEAVES[kind] + FFN_LEAVES[ffn]
            x = run[kind, ffn](x, {name: params[name][l] for name in names})
    return x


def logits(config: dict, params: dict, x):
    """The last norm and the (tied) head on hidden states x [n, D] -> [n, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), s["eps"])
        return h @ params["lm_head"].astype(jnp.float32)
