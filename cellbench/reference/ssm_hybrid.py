"""Plain reference of the state-space / attention hybrid
(ibm-granite/granite-4.0-h, model_type granitemoehybrid, dense) in
straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: a whole sequence at a time, no
kernels, no cache, no carried state between calls (the state-space layers
scan the sequence itself, token by token), no batching, nothing imported
from the program.

RMSNorm eps `rms_norm_eps` with a weight everywhere; x a layer's input
[T, d]; r = residual_multiplier:

  embedding   table[token] x embedding_multiplier
  layer l     h = x + r Mixer_l(RMSNorm(x));  y = h + r W_2 (silu(W_1 h') * W_3 h'),
              h' = RMSNorm(h)   ([W_1 | W_3] is the published
              shared_mlp.input_linear, W_2 its output_linear)
  head        RMSNorm, the tied table, / logits_scaling

  Mixer of a `mamba` layer (Mamba-2 / SSD: H = mamba_n_heads heads of P =
  mamba_d_head, d_inner = H P = mamba_expand x hidden_size, N =
  mamba_d_state, ONE group, K = mamba_d_conv taps):
              [z | xBC | dt] = u W_in, widths d_inner | d_inner + 2 N | H;
              xBC_t <- silu(b + sum_j w[j] * xBC_{t-(K-1)+j}): depthwise,
              causal, inputs before the first token 0: K shifted sums;
              [x | B | C] = xBC, widths d_inner | N | N;
              dt_t = softplus(dt_t + dt_bias) a head (no clamp:
              time_step_limit (0, inf)); A = -exp(A_log) a head;
              per head, token by token, S [P, N]:
              S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
              y_t = S_t C_t + D x_t  (B and C the same for every head);
              Mixer = RMSNorm(y * silu(z)) W_out: the gate first, then one
              norm over all d_inner numbers.
  Mixer of an `attention` layer (H_q query heads, KV key/value heads of
  head_dim = hidden_size / H_q): q, k, v = u W_q, u W_k, u W_v; NO rotary
  and no other position signal (position_embedding_type nope); causal
  softmax of q k^T x attention_multiplier (a direct multiplier, not
  head_dim^-0.5); Mixer = o W_o. No bias, no qk-norm.

The scan is the literal recurrence (`lax.scan` over t), the sequence
computed in blocks of Q_BLOCK tokens with the state handed from block to
block, so that the check's rows fit the chip after the server has gone.

Departures from the published model, each also under the configuration
file's `assumed`: the matrix state is float32 (the family's code computes
the scan in float32). The weights are random: `make_params` writes down the
program's documented initialisation (models/granite_hybrid.py: 24 keys
split from PRNGKey(seed), the table LEAF_KEY below; a stacked leaf [n, ...]
is n slices, slice i drawn from split(key, n)[i] in float32, scaled, and
rounded to the served dtype, where n counts the layers of the leaf's KIND in
stack order (the FFN's: all layers); the vocabulary table is 8 such slices
of rows at scale 0.02 / embedding_multiplier, so that the embedding's OUTPUT
has the other families' 0.02 and the tied head's logits do not peak at the
token that came in; norm weights and D 1; Mamba-2's own constants: A_log =
log(a), a uniform on [1, 16], dt_bias the inverse softplus of a dt
log-uniform on [0.001, 0.1], float32, a key a layer from split of their own
keys). The tree is held in the served dtype and a layer is upcast when it is
used.

A parameter is `params[name][layer]`: a list per name over ALL layers (None
where the layer has no such leaf), so that a wrapper (tools/control.py) can
hand back any layer's matrix changed. The attention layers' matrices go by
wq, wk, wv, wo and a mamba layer's output projection by wo too, so the
wrapper's 8-bit control rounds those, the FFN's w_gate, w_up, w_down and
lm_head (the tied table, held here a second time as [d, V]); a mamba
layer's w_in, its taps and its vectors stay as they are there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # tokens a block of the scan and of attention's queries

LEAF_KEY = {
    "embed": 0,
    "mamba.wz": 1, "mamba.wx": 2, "mamba.wdt": 3, "mamba.conv_w": 4,
    "mamba.conv_b": 5, "mamba.wo": 6, "mamba.a": 7, "mamba.dt": 8,
    "attn.wq": 9, "attn.wk": 10, "attn.wv": 11, "attn.wo": 12,
    "ffn.w_gate": 13, "ffn.w_up": 14, "ffn.w_down": 15,
}


def sizes(config: dict) -> dict:
    """The sizes as the configuration file publishes them (HF key names)."""
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    if config["mamba_n_groups"] != 1:
        raise ValueError("one group of B and C is what this reference scans")
    D, Hq = config["hidden_size"], config["num_attention_heads"]
    Hm, P = config["mamba_n_heads"], config["mamba_d_head"]
    if Hm * P != config["mamba_expand"] * D:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x "
                         "hidden_size")
    return dict(
        L=len(kinds), kinds=kinds, D=D, H=Hq,
        KV=config["num_key_value_heads"], Dh=config.get("head_dim") or D // Hq,
        Hm=Hm, P=P, N=config["mamba_d_state"], K=config["mamba_d_conv"],
        conv_bias=bool(config["mamba_conv_bias"]),
        F=config["shared_intermediate_size"], V=config["vocab_size"],
        eps=float(config["rms_norm_eps"]),
        emb=float(config["embedding_multiplier"]),
        res=float(config["residual_multiplier"]),
        att=float(config["attention_multiplier"]),
        div=float(config["logits_scaling"]),
    )


def make_params(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The seeded random tree: {name: [layer 0's leaf, layer 1's, ...]}
    (None where a layer has no such leaf), and embed, lm_head, final_norm."""
    s = sizes(config)
    L, D, H, KV, Dh, F, V = (s[n] for n in ("L", "D", "H", "KV", "Dh", "F", "V"))
    Hm, Di, K = s["Hm"], s["Hm"] * s["P"], s["K"]
    C = Di + 2 * s["N"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 24)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(k, shape, scale, dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    sc = D ** -0.5
    mamba = [l for l in range(L) if s["kinds"][l] == "mamba"]
    attn = [l for l in range(L) if s["kinds"][l] == "attention"]
    every = list(range(L))
    shapes = {  # kind.name -> (the layers that have it, a layer's shape, scale)
        "mamba.wz": (mamba, (D, Di), sc), "mamba.wx": (mamba, (D, C), sc),
        "mamba.wdt": (mamba, (D, Hm), sc),
        "mamba.conv_w": (mamba, (K, C), K ** -0.5),
        "mamba.conv_b": (mamba, (C,), 0.02 if s["conv_bias"] else 0.0),
        "mamba.wo": (mamba, (Di, D), Di ** -0.5),
        "attn.wq": (attn, (D, H * Dh), sc), "attn.wk": (attn, (D, KV * Dh), sc),
        "attn.wv": (attn, (D, KV * Dh), sc),
        "attn.wo": (attn, (H * Dh, D), (H * Dh) ** -0.5),
        "ffn.w_gate": (every, (D, F), sc), "ffn.w_up": (every, (D, F), sc),
        "ffn.w_down": (every, (F, D), F ** -0.5),
    }
    params: dict = {}
    for path, (layers, shape, scale) in shapes.items():
        name = path.split(".")[1]
        params.setdefault(name, [None] * L)
        keys = jax.random.split(ks[LEAF_KEY[path]], len(layers))
        for i, l in enumerate(layers):
            params[name][l] = normal(keys[i], shape, float(scale), dtype)
    # [z | xBC | dt] as one matrix, as the published in_proj holds them
    params["w_in"] = [
        None if params["wz"][l] is None else jnp.concatenate(
            [params[n][l] for n in ("wz", "wx", "wdt")], axis=1)
        for l in range(L)]
    for n in ("wz", "wx", "wdt"):
        del params[n]
    lo, hi = math.log(0.001), math.log(0.1)
    keys_a = jax.random.split(ks[LEAF_KEY["mamba.a"]], len(mamba))
    keys_dt = jax.random.split(ks[LEAF_KEY["mamba.dt"]], len(mamba))
    for name in ("a_log", "dt_bias", "d", "norm"):
        params[name] = [None] * L
    for i, l in enumerate(mamba):
        a = jax.random.uniform(keys_a[i], (Hm,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(keys_dt[i], (Hm,), jnp.float32, lo, hi))
        params["a_log"][l] = jnp.log(a)
        params["dt_bias"][l] = dt + jnp.log(-jnp.expm1(-dt))
        params["d"][l] = jnp.ones((Hm,), jnp.float32)
        params["norm"][l] = jnp.ones((Di,), dtype)
    params["op_norm"] = [jnp.ones((D,), dtype)] * L
    params["ffn_norm"] = [jnp.ones((D,), dtype)] * L
    n = 8 if V % 8 == 0 else 1  # the vocabulary table: 8 slices of rows
    keys = jax.random.split(ks[LEAF_KEY["embed"]], n)
    params["embed"] = jnp.concatenate(
        [normal(keys[i], (V // n, D), 0.02 / s["emb"], dtype) for i in range(n)])
    params["lm_head"] = params["embed"].T  # tied: [D, V]
    params["final_norm"] = jnp.ones((D,), dtype)
    return params


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(a):
    return a.astype(jnp.float32)


def conv(xbc, w, b):
    """The causal depthwise convolution of xbc [T, C] with taps w [K, C]
    (w[K - 1] the token's own) and bias b [C]: K shifted sums."""
    T, K = xbc.shape[0], w.shape[0]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    return b + sum(w[j] * padded[j:j + T] for j in range(K))


def scan_block(S, x, dt, A, B, C):
    """The recurrence over one block of tokens from state S [H, P, N]: x
    [T, H, P], dt [T, H], A [H], B, C [T, N]. Returns (S after, y [T, H, P])."""
    def step(S, t):
        xt, dtt, Bt, Ct = t
        S = jnp.exp(dtt * A)[:, None, None] * S + (
            (dtt[:, None] * xt)[:, :, None] * Bt[None, None, :])
        return S, jnp.einsum("hpn,n->hp", S, Ct)

    return jax.lax.scan(step, S, (x, dt, B, C))


def mamba_op(u, lp, *, Hm, P, N, eps):
    """The Mamba-2 mixer on a whole sequence u [T, d] (normed)."""
    T, Di = u.shape[0], Hm * P
    out = u @ _f32(lp["w_in"])
    z, xbc, dt = out[:, :Di], out[:, Di:2 * Di + 2 * N], out[:, 2 * Di + 2 * N:]
    xbc = jax.nn.silu(conv(xbc, _f32(lp["conv_w"]), _f32(lp["conv_b"])))
    x, B, C = xbc[:, :Di], xbc[:, Di:Di + N], xbc[:, Di + N:]
    x = x.reshape(T, Hm, P)
    dt = jax.nn.softplus(dt + lp["dt_bias"][None, :])
    A = -jnp.exp(lp["a_log"])
    S = jnp.zeros((Hm, P, N), jnp.float32)
    ys = []
    for at in range(0, T, Q_BLOCK):  # the state handed from block to block
        cut = slice(at, min(at + Q_BLOCK, T))
        S, y = scan_block(S, x[cut], dt[cut], A, B[cut], C[cut])
        ys.append(y)
    y = jnp.concatenate(ys) + lp["d"][None, :, None] * x
    y = _rms(y.reshape(T, Di) * jax.nn.silu(z), _f32(lp["norm"]), eps)
    return y @ _f32(lp["wo"])


def attention_op(u, lp, *, H, KV, Dh, att):
    """Causal grouped-query attention without a position encoding on a
    whole sequence u [T, d] (normed), at the direct multiplier `att`."""
    T = u.shape[0]
    q = (u @ _f32(lp["wq"])).reshape(T, KV, H // KV, Dh)
    k = (u @ _f32(lp["wk"])).reshape(T, KV, Dh)
    v = (u @ _f32(lp["wv"])).reshape(T, KV, Dh)
    pos = jnp.arange(T, dtype=jnp.int32)

    def attend_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        t = i * Q_BLOCK + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * att
        scores = jnp.where((pos[None, :] <= t[:, None])[None, None], scores,
                           -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(attend_block, jnp.arange(T // Q_BLOCK)).reshape(T, H * Dh)
    return o @ _f32(lp["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def layer(x, lp, *, op, eps, res):
    x = x + res * op(_rms(x, _f32(lp["op_norm"]), eps), lp)
    h = _rms(x, _f32(lp["ffn_norm"]), eps)
    return x + res * _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


# the leaves a layer asks `params` for, by its mixer
OP_LEAVES = {
    "mamba": ("w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d", "norm",
              "wo"),
    "attention": ("wq", "wk", "wv", "wo"),
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
        "mamba": functools.partial(mamba_op, Hm=s["Hm"], P=s["P"], N=s["N"],
                                   eps=s["eps"]),
        "attention": functools.partial(attention_op, H=s["H"], KV=s["KV"],
                                       Dh=s["Dh"], att=s["att"]),
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
    """The last norm and the tied table on hidden states x [n, D] -> [n, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), s["eps"])
        return h @ params["lm_head"].astype(jnp.float32) / s["div"]
