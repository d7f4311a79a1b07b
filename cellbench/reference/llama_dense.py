"""Plain reference of the dense llama-family decoder block (Mistral-7B,
Phi-3-mini: pre-norm RMSNorm; OLMo-2: the sublayer reads the residual
stream raw and its OUTPUT is normed before it is added, with an RMSNorm
over the whole q and k projections before the rotation; all: rotary
embedding in the rotate-half form, grouped-query or multi-head attention
with an optional sliding window, SwiGLU), in straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no
batching, and nothing imported from the program.

It takes its inputs from the seed: `make_params` writes down the program's
documented random initialisation (ten keys split from PRNGKey(seed);
scaled normals drawn in float32 and rounded to the served dtype) and makes
the same tree itself. The tree is held in bfloat16, as it is served, and a
layer is upcast to float32 when it is used: 15 GB of float32 weights do not
fit beside anything on a 16 GB chip. Rounding the weights to bfloat16 is
the configuration's stated precision, not an error of the program; what
the comparison sees is the program's bfloat16 activations and kernels
against float32 ones.

Departures from the published models: none in the block. Phi-3 stores q, k,
v and gate, up as fused matrices; with random weights the split form is the
same function.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # queries per attention block (bounds the score matrix)


def sizes(config: dict) -> dict:
    """The sizes as the configuration file publishes them (HF key names)."""
    D = config["hidden_size"]
    H = config["num_attention_heads"]
    return dict(
        L=config["num_hidden_layers"], D=D, H=H, KV=config["num_key_value_heads"],
        Dh=config.get("head_dim", D // H), F=config["intermediate_size"],
        V=config["vocab_size"], window=config.get("sliding_window"),
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        # the block's variant, stated in the configuration's file:
        # "pre" (Llama/Mistral/Phi-3) or "post" (OLMo-2); qk_norm None or "proj"
        norms=config.get("norm_placement", "pre"), qk_norm=config.get("qk_norm"),
    )


def make_params(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The seeded random tree, leaf by leaf inside one jitted call each (the
    float32 draw of a stacked leaf is fused into its cast and never
    stored)."""
    s = sizes(config)
    L, D, H, KV, Dh, F, V = (s[k] for k in ("L", "D", "H", "KV", "Dh", "F", "V"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    sc = D ** -0.5
    ones = {"attn_norm": (L, D), "mlp_norm": (L, D)}  # every norm weight is 1
    if s["qk_norm"] == "proj":
        ones.update(q_norm=(L, H * Dh), k_norm=(L, KV * Dh))
    return {
        **{name: jnp.ones(shape, dtype) for name, shape in ones.items()},
        "embed": normal(ks[0], (V, D), 0.02),
        "wq": normal(ks[1], (L, D, H * Dh), sc),
        "wk": normal(ks[2], (L, D, KV * Dh), sc),
        "wv": normal(ks[3], (L, D, KV * Dh), sc),
        "wo": normal(ks[4], (L, H * Dh, D), sc),
        "w_gate": normal(ks[5], (L, D, F), sc),
        "w_up": normal(ks[6], (L, D, F), sc),
        "w_down": normal(ks[7], (L, F, D), F ** -0.5),
        "lm_head": normal(ks[8], (D, V), sc),
        "final_norm": jnp.ones((D,), dtype),
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [T, heads, Dh]; rotate-half convention (HF LlamaRotaryEmbedding)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def layer(x, lp, *, H, KV, Dh, window, theta, eps, norms="pre", qk_norm=None):
    """One block on a whole sequence x [T, D] (T a multiple of Q_BLOCK, pad
    at the end: causality keeps real tokens from seeing it)."""
    f32 = lambda a: a.astype(jnp.float32)
    T = x.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    pre = norms == "pre"
    h = _rms(x, f32(lp["attn_norm"]), eps) if pre else x
    q, k, v = h @ f32(lp["wq"]), h @ f32(lp["wk"]), h @ f32(lp["wv"])
    if qk_norm == "proj":  # over the whole projection, before the head split
        q, k = _rms(q, f32(lp["q_norm"]), eps), _rms(k, f32(lp["k_norm"]), eps)
    q, k, v = q.reshape(T, H, Dh), k.reshape(T, KV, Dh), v.reshape(T, KV, Dh)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    kr = jnp.repeat(k, H // KV, axis=1)  # [T, H, Dh]: each query head's kv head
    vr = jnp.repeat(v, H // KV, axis=1)

    def attend_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        scores = jnp.einsum("qhd,khd->hqk", qb, kr) * (Dh ** -0.5)
        ok = pos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= pos[None, :] > qpos[:, None] - window
        scores = jnp.where(ok[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vr)

    attn = jax.lax.map(attend_block, jnp.arange(T // Q_BLOCK)).reshape(T, H * Dh)
    out = attn @ f32(lp["wo"])
    x = x + (out if pre else _rms(out, f32(lp["attn_norm"]), eps))
    h = _rms(x, f32(lp["mlp_norm"]), eps) if pre else x
    out = (jax.nn.silu(h @ f32(lp["w_gate"])) * (h @ f32(lp["w_up"]))) @ f32(lp["w_down"])
    return x + (out if pre else _rms(out, f32(lp["mlp_norm"]), eps))


LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "attn_norm",
                "mlp_norm", "q_norm", "k_norm")


def forward(config: dict, params: dict, ids):
    """Run one sequence of token ids through every layer. Returns the final
    hidden states [T_padded, D] before the last norm. `params[name][l]` is
    all that is asked of a stacked leaf."""
    s = sizes(config)
    n = len(ids)
    T = -(-n // Q_BLOCK) * Q_BLOCK
    toks = jnp.zeros((T,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32))
    step = jax.jit(functools.partial(
        layer, H=s["H"], KV=s["KV"], Dh=s["Dh"], window=s["window"],
        theta=s["theta"], eps=s["eps"], norms=s["norms"], qk_norm=s["qk_norm"],
    ))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(jnp.float32)
        for l in range(s["L"]):
            x = step(x, {name: params[name][l] for name in LAYER_LEAVES if name in params})
    return x


def logits(config: dict, params: dict, x):
    """Final norm and output head on hidden states x [n, D] -> [n, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), s["eps"])
        return h @ params["lm_head"].astype(jnp.float32)
