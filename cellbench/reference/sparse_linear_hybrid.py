"""Plain reference of the sparse-attention / linear-attention hybrid
(openbmb/MiniCPM-SALA, model_type minicpm_sala) in straightforward float32
`jax.numpy` under `jax.default_matmul_precision("highest")`: a whole sequence
at a time, no kernels, no cache, no compressed-key store, no carried state
between calls (the linear layers scan the sequence itself, token by token),
no batching, nothing imported from the program.

RMSNorm eps `rms_norm_eps` everywhere; x a layer's input [T, d]; r =
scale_depth / sqrt(the PUBLISHED num_hidden_layers):

  embedding   table[token] x scale_emb
  layer l     h = x + r Mixer_l(RMSNorm(x));  y = h + r W_2 (silu(W_1 h') * W_3 h'),
              h' = RMSNorm(h)
  head        RMSNorm, the untied head, / (hidden_size / dim_model_base)

  Mixer of a `lightning-attn` layer (lightning_nh heads of lightning_head_dim):
              q, k, v = u W_q, u W_k, u W_v; RMSNorm with a weight over each
              q and k head; RoPE theta as published over the two HALVES of a
              head; q x head_dim^-0.5; per head, token by token,
              S_t = a_h S_{t-1} + k_t^T v_t, o_t = q_t S_t, a_h = exp(-s_h),
              s_h = 2^(-8 h / heads), h = 1 .. heads;
              Mixer = (RMSNorm(o) * sigmoid(u W_g)) W_o, the output norm over
              the heads side by side.
  Mixer of a `minicpm4` layer (H query heads, KV key/value heads, NO rotary):
              the same per-head RMSNorm on q and k; compressed keys
              c_j = mean(k[stride j : stride j + kernel]) a KV head, defined
              once token stride j + kernel - 1 exists. The query at position
              t sees n = t + 1 positions:
                n < dense_len: plain causal attention over all of them;
                else, per KV head: p^h = softmax_j(q^h . c_j / sqrt(head_dim))
                over the j with stride j + kernel - 1 <= t, for each of the
                head's query heads; r_j = the sum of p^h_j over them; a block
                b of `block` tokens scores max r_j over the j whose tokens
                overlap it; blocks 0 .. init_blocks - 1 and the blocks that
                hold positions t - window + 1 .. t score +inf; the `topk`
                highest are read (ties: the lower block), and attention is
                the causal softmax at head_dim^-0.5 over the positions <= t
                of those blocks.
              Mixer = (o * sigmoid(u W_g)) W_o.

Departures from the published model, each also under the configuration
file's `assumed`: the selection's constants are the family's published
`sparse_config` (MiniCPM4; config.json leaves them out); the forced blocks
count inside the topk; the selection's softmax is the exact one where the
family's kernel may use a coarser normaliser; the tie rule; the slopes s_h
(the Lightning Attention family's fixed ones); the gates are hidden_size
wide; the output norm has one weight over the heads side by side. The
weights are random: `make_params` writes down the program's documented
initialisation (models/minicpm_sala.py: 24 keys split from PRNGKey(seed),
the table LEAF_KEY below; a stacked leaf [n, ...] is n slices, slice i drawn
from split(key, n)[i] in float32, scaled, and rounded to the served dtype,
where n counts the layers of the leaf's KIND in stack order (the FFN's: all
layers); the two vocabulary tables are 8 such slices of rows each; norm
weights 1). The tree is held in the served dtype and a layer is upcast when
it is used.

A parameter is `params[name][layer]`: a list per name over ALL layers (None
where the layer has no such leaf), so that a wrapper (tools/control.py) can
hand back any layer's matrix changed. Both mixers' matrices go by the same
names (wq, wk, wv, wo, and wg for the gate), so the wrapper's 8-bit control
rounds every mixer's wq, wk, wv, wo, the FFN's w_gate, w_up, w_down and
lm_head; wg and the norms stay as they are there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # queries per attention block (bounds the score matrices)

LEAF_KEY = {
    "embed": 0, "lm_head": 1,
    "sparse.wq": 2, "sparse.wk": 3, "sparse.wv": 4, "sparse.wo": 5,
    "sparse.wg": 6,
    "linear.wq": 7, "linear.wk": 8, "linear.wv": 9, "linear.wo": 10,
    "linear.wg": 11,
    "ffn.w_gate": 12, "ffn.w_up": 13, "ffn.w_down": 14,
}


def sizes(config: dict) -> dict:
    """The sizes as the configuration file publishes them (HF key names);
    the selection's constants from its `sparse_config` (assumed)."""
    kinds = list(config["mixer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("mixer_types does not name num_hidden_layers layers")
    sp = config["sparse_config"]
    depth = config.get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    return dict(
        L=len(kinds), kinds=kinds, D=config["hidden_size"],
        H=config["num_attention_heads"], KV=config["num_key_value_heads"],
        Dh=config["head_dim"], Hl=config["lightning_nh"],
        F=config["intermediate_size"], V=config["vocab_size"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        emb=float(config["scale_emb"]),
        res=float(config["scale_depth"]) / depth ** 0.5,
        div=config["hidden_size"] / config["dim_model_base"],
        kernel=sp["kernel_size"], stride=sp["kernel_stride"],
        block=sp["block_size"], topk=sp["topk"], window=sp["window_size"],
        init=sp["init_blocks"], dense_len=sp["dense_len"],
    )


def make_params(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The seeded random tree: {name: [layer 0's leaf, layer 1's, ...]}
    (None where a layer has no such leaf), and embed, lm_head, final_norm."""
    s = sizes(config)
    L, D, H, KV, Dh, Hl, F, V = (
        s[n] for n in ("L", "D", "H", "KV", "Dh", "Hl", "F", "V"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 24)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(k, shape, scale, dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    sc = D ** -0.5
    sparse = [l for l in range(L) if s["kinds"][l] == "minicpm4"]
    linear = [l for l in range(L) if s["kinds"][l] == "lightning-attn"]
    every = list(range(L))
    shapes = {  # kind.name -> (the layers that have it, a layer's shape, scale)
        "sparse.wq": (sparse, (D, H * Dh), sc),
        "sparse.wk": (sparse, (D, KV * Dh), sc),
        "sparse.wv": (sparse, (D, KV * Dh), sc),
        "sparse.wo": (sparse, (H * Dh, D), (H * Dh) ** -0.5),
        "sparse.wg": (sparse, (D, H * Dh), sc),
        "linear.wq": (linear, (D, Hl * Dh), sc),
        "linear.wk": (linear, (D, Hl * Dh), sc),
        "linear.wv": (linear, (D, Hl * Dh), sc),
        "linear.wo": (linear, (Hl * Dh, D), (Hl * Dh) ** -0.5),
        "linear.wg": (linear, (D, Hl * Dh), sc),
        "ffn.w_gate": (every, (D, F), sc), "ffn.w_up": (every, (D, F), sc),
        "ffn.w_down": (every, (F, D), F ** -0.5),
    }
    params: dict = {}
    for path, (layers, shape, scale) in shapes.items():
        name = path.split(".")[1]
        params.setdefault(name, [None] * L)
        if not layers:
            continue
        keys = jax.random.split(ks[LEAF_KEY[path]], len(layers))
        for i, l in enumerate(layers):
            params[name][l] = normal(keys[i], shape, float(scale), dtype)
    params["op_norm"] = [jnp.ones((D,), dtype)] * L
    params["ffn_norm"] = [jnp.ones((D,), dtype)] * L
    for name in ("q_norm", "k_norm"):
        params[name] = [jnp.ones((Dh,), dtype)] * L
    params["o_norm"] = [jnp.ones((Hl * Dh,), dtype) if l in linear else None
                        for l in range(L)]
    n = 8 if V % 8 == 0 else 1  # a vocabulary table: 8 slices of rows
    for name, scale in (("embed", 0.02), ("lm_head", sc)):
        keys = jax.random.split(ks[LEAF_KEY[name]], n)
        params[name] = jnp.concatenate(
            [normal(keys[i], (V // n, D), scale, dtype) for i in range(n)])
    params["lm_head"] = params["lm_head"].T  # [D, V]
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


def linear_op(u, lp, *, Hl, Dh, theta, eps):
    """Decayed linear attention on a whole sequence u [T, d] (normed): the
    literal recurrence, a token a step."""
    T = u.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    q = _rms((u @ _f32(lp["wq"])).reshape(T, Hl, Dh), _f32(lp["q_norm"]), eps)
    k = _rms((u @ _f32(lp["wk"])).reshape(T, Hl, Dh), _f32(lp["k_norm"]), eps)
    v = (u @ _f32(lp["wv"])).reshape(T, Hl, Dh)
    q, k = _rope(q, pos, theta) * Dh ** -0.5, _rope(k, pos, theta)
    slope = jnp.exp2(-8.0 * jnp.arange(1, Hl + 1, dtype=jnp.float32) / Hl)
    decay = jnp.exp(-slope)[:, None, None]

    def step(S, qkv):
        qt, kt, vt = qkv  # [Hl, Dh] each
        S = decay * S + kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hd,hde->he", qt, S)

    _, o = jax.lax.scan(step, jnp.zeros((Hl, Dh, Dh), jnp.float32), (q, k, v))
    o = _rms(o.reshape(T, Hl * Dh), _f32(lp["o_norm"]), eps)
    return (o * jax.nn.sigmoid(u @ _f32(lp["wg"]))) @ _f32(lp["wo"])


def chosen_blocks(q, c, t, *, KV, Dh, kernel, stride, block, topk, window,
                  init, dense_len, n_blocks):
    """[Q, KV, n_blocks] bool: the blocks each query of q [Q, KV, group, Dh]
    at positions t [Q] reads, against the compressed keys c [J, KV, Dh]."""
    J = c.shape[0]
    j = jnp.arange(J, dtype=jnp.int32)
    b = jnp.arange(n_blocks, dtype=jnp.int32)
    valid = (stride * j + kernel - 1)[None, :] <= t[:, None]  # [Q, J]
    s = jnp.einsum("qkgd,jkd->qkgj", q, c) * Dh ** -0.5
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jnp.where(valid[:, None, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    p = jnp.where(jnp.any(valid, axis=-1)[:, None, None, None], p, 0.0)
    r = jnp.sum(p, axis=2)  # [Q, KV, J]
    r = jnp.where(valid[:, None, :], r, -jnp.inf)
    overlap = ((stride * j)[None, :] <= (block * b + block - 1)[:, None]) & (
        (stride * j + kernel - 1)[None, :] >= (block * b)[:, None])  # [B, J]
    score = jnp.max(
        jnp.where(overlap[None, None], r[:, :, None, :], -jnp.inf), axis=-1)
    own = t // block
    forced = (b[None, :] < init) | (
        b[None, :] >= (jnp.maximum(t - (window - 1), 0) // block)[:, None])
    visible = b[None, :] <= own[:, None]
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where(visible[:, None, :], score, -jnp.inf)
    _, idx = jax.lax.top_k(score, min(topk, n_blocks))  # ties: lower block
    picked = jnp.any(idx[..., None] == b, axis=-2)
    dense = (t + 1 < dense_len)[:, None, None]
    return jnp.where(dense, True, picked) & visible[:, None, :]


def sparse_op(u, lp, *, H, KV, Dh, eps, **sel):
    """InfLLM-v2 attention on a whole sequence u [T, d] (normed)."""
    T = u.shape[0]
    kernel, stride, block = sel["kernel"], sel["stride"], sel["block"]
    pos = jnp.arange(T, dtype=jnp.int32)
    q = _rms((u @ _f32(lp["wq"])).reshape(T, H, Dh), _f32(lp["q_norm"]), eps)
    k = _rms((u @ _f32(lp["wk"])).reshape(T, KV, Dh), _f32(lp["k_norm"]), eps)
    v = (u @ _f32(lp["wv"])).reshape(T, KV, Dh)
    q = q.reshape(T, KV, H // KV, Dh)  # query head h reads K/V head h // group
    # c_j = mean(k[stride j : stride j + kernel]): the mean of kernel / stride
    # consecutive strides' means (T is a multiple of the stride)
    m = jnp.mean(k.reshape(T // stride, stride, KV, Dh), axis=1)
    J = T // stride - kernel // stride + 1
    c = sum(m[i:i + J] for i in range(kernel // stride)) / (kernel // stride)
    n_blocks = -(-T // block)

    def attend_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        t = i * Q_BLOCK + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        reads = chosen_blocks(qb, c, t, KV=KV, Dh=Dh, n_blocks=n_blocks, **sel)
        mask = jnp.repeat(reads, block, axis=-1)[:, :, :T] & (
            pos[None, None, :] <= t[:, None, None])  # [Q, KV, T]
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * (Dh ** -0.5)
        scores = jnp.where(mask.transpose(1, 0, 2)[:, None], scores, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(attend_block, jnp.arange(T // Q_BLOCK)).reshape(T, H * Dh)
    return (o * jax.nn.sigmoid(u @ _f32(lp["wg"]))) @ _f32(lp["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def layer(x, lp, *, op, eps, res):
    x = x + res * op(_rms(x, _f32(lp["op_norm"]), eps), lp)
    h = _rms(x, _f32(lp["ffn_norm"]), eps)
    return x + res * _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


# the leaves a layer asks `params` for, by its mixer
OP_LEAVES = {
    "minicpm4": ("wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm"),
    "lightning-attn": ("wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm",
                       "o_norm"),
}
FFN_LEAVES = ("op_norm", "ffn_norm", "w_gate", "w_up", "w_down")


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
        "lightning-attn": functools.partial(
            linear_op, Hl=s["Hl"], Dh=s["Dh"], theta=s["theta"], eps=s["eps"]),
        "minicpm4": functools.partial(
            sparse_op, H=s["H"], KV=s["KV"], Dh=s["Dh"], eps=s["eps"],
            kernel=s["kernel"], stride=s["stride"], block=s["block"],
            topk=s["topk"], window=s["window"], init=s["init"],
            dense_len=s["dense_len"]),
    }
    run = {kind: jax.jit(functools.partial(layer, op=op, eps=s["eps"],
                                           res=s["res"]))
           for kind, op in ops.items()}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(jnp.float32) * s["emb"]
        for l, kind in enumerate(s["kinds"]):
            names = FFN_LEAVES + OP_LEAVES[kind]
            x = run[kind](x, {name: params[name][l] for name in names})
    return x


def logits(config: dict, params: dict, x):
    """The last norm and the untied head on hidden states x [n, D] -> [n, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), s["eps"])
        return h @ params["lm_head"].astype(jnp.float32) / s["div"]
