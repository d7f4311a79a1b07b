"""Plain reference of the latent-attention, routed-expert decoder block
(DeepSeek-V3's, as kakaocorp/kanana-2-30b-a3b-instruct-2601 publishes it)
in straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no
batching, nothing imported from the program, and attention in the PLAIN
form (keys and values of every head formed from the latent), where the
program reads its cache absorbed.

The block (d hidden, H heads, r = kv_lora_rank, dn / dr = nope / rope
numbers of a query or key head, dv of a value head; pre-norm RMSNorm, no
biases, untied head):

  attention   q = h Wq -> [H, dn + dr]; [c | k_r] = h Wkva, c <- RMSNorm(c) g;
              [k_n | v]_h = Wkvb,h c; q_r and the ONE k_r all heads share are
              rotated over interleaved pairs (2i, 2i+1), theta as published;
              scores (q_n . k_n + q_r . k_r) (dn + dr)^-0.5, causal softmax,
              out = concat_h(p v) Wo
  layer < first_k_dense_replace:  SwiGLU of width intermediate_size
  other layers:  s = sigmoid(h Wr); the num_experts_per_tok largest of s + b
              are chosen (b: the selection bias; n_group = topk_group = 1, so
              no group limit); weights s_i / sum of the chosen s (under
              norm_topk_prob) x routed_scaling_factor; every token goes
              through EVERY expert under a dense mask of those weights
              (zero where not chosen); plus the shared expert, one SwiGLU of
              width n_shared_experts x moe_intermediate_size.

Departures from the published model: none in the block. The weights are
random: `make_params` writes down the program's documented initialisation
(models/mla_moe.py: 24 keys split from PRNGKey(seed), the table LEAF_KEY
below; a stacked leaf [n, ...] is n slices, slice i drawn from
split(key, n)[i] in float32, scaled, and rounded to the served dtype; the
two vocabulary tables are 8 such slices of rows; norm weights 1; the
selection bias a float32 normal x `init.router_bias_scale`). The tree is
held in the served dtype and a layer is upcast when it is used.

A parameter is `params[name][layer]`: a list per name, so that a wrapper
(tools/control.py) can hand back any layer's matrix changed. The names
tools/control.py quantizes are wq, wo, w_gate, w_up, w_down (layer 0: the
dense matrices, further layers: the expert banks [E, in, out]) and
lm_head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # queries per attention block (bounds the score matrix)

LEAF_KEY = {
    "embed": 0, "lm_head": 1,
    "dense.wq": 2, "dense.w_kva": 3, "dense.w_kvb": 4, "dense.wo": 5,
    "dense.w_gate": 6, "dense.w_up": 7, "dense.w_down": 8,
    "moe.wq": 9, "moe.w_kva": 10, "moe.w_kvb": 11, "moe.wo": 12,
    "moe.w_router": 13, "moe.router_bias": 14,
    "moe.w_gate": 15, "moe.w_up": 16, "moe.w_down": 17,
    "moe.ws_gate": 18, "moe.ws_up": 19, "moe.ws_down": 20,
}


def sizes(config: dict) -> dict:
    """The sizes as the configuration file publishes them (HF key names)."""
    F = config["moe_intermediate_size"]
    return dict(
        L=config["num_hidden_layers"], Ld=config["first_k_dense_replace"],
        D=config["hidden_size"], H=config["num_attention_heads"],
        r=config["kv_lora_rank"], dn=config["qk_nope_head_dim"],
        dr=config["qk_rope_head_dim"], dv=config["v_head_dim"],
        Fd=config["intermediate_size"], F=F, Fs=config["n_shared_experts"] * F,
        E=config["n_routed_experts"], k=config["num_experts_per_tok"],
        V=config["vocab_size"], theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        renorm=bool(config.get("norm_topk_prob", True)),
        scaling=float(config.get("routed_scaling_factor", 1.0)),
        bias_scale=float(config["init"]["router_bias_scale"]),
    )


def make_params(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The seeded random tree: {name: [layer 0's leaf, layer 1's, ...]}
    (None where a layer has no such leaf), and embed, lm_head, final_norm."""
    s = sizes(config)
    L, Ld, D, H, r = s["L"], s["Ld"], s["D"], s["H"], s["r"]
    dn, dr, dv, E, F, Fs, Fd, V = (s[n] for n in ("dn", "dr", "dv", "E", "F", "Fs", "Fd", "V"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 24)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(k, shape, scale, dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    sc = D ** -0.5
    shapes = {  # name -> (dense stack's slice, expert stack's slice), each (shape, scale)
        "wq": (((D, H * (dn + dr)), sc),) * 2,
        "w_kva": (((D, r + dr), sc),) * 2,
        "w_kvb": (((r, H * (dn + dv)), r ** -0.5),) * 2,
        "wo": (((H * dv, D), (H * dv) ** -0.5),) * 2,
        "w_gate": (((D, Fd), sc), ((E, D, F), sc)),
        "w_up": (((D, Fd), sc), ((E, D, F), sc)),
        "w_down": (((Fd, D), Fd ** -0.5), ((E, F, D), F ** -0.5)),
        "w_router": (None, ((D, E), sc)),
        "router_bias": (None, ((E,), s["bias_scale"])),
        "ws_gate": (None, ((D, Fs), sc)), "ws_up": (None, ((D, Fs), sc)),
        "ws_down": (None, ((Fs, D), Fs ** -0.5)),
    }
    params = {}
    for name, per_stack in shapes.items():
        leaves = []
        for stack, n, spec in (("dense", Ld, per_stack[0]), ("moe", L - Ld, per_stack[1])):
            if spec is None or n == 0:
                leaves += [None] * n
                continue
            keys = jax.random.split(ks[LEAF_KEY[f"{stack}.{name}"]], n)
            dt = jnp.float32 if name == "router_bias" else dtype
            leaves += [normal(keys[i], spec[0], float(spec[1]), dt) for i in range(n)]
        params[name] = leaves
    for name, width in (("attn_norm", D), ("mlp_norm", D), ("kv_norm", r)):
        params[name] = [jnp.ones((width,), dtype)] * L

    def table(name, shape, scale):  # 8 slices of rows
        n = 8 if shape[0] % 8 == 0 else 1
        keys = jax.random.split(ks[LEAF_KEY[name]], n)
        return jnp.concatenate([
            normal(keys[i], (shape[0] // n,) + shape[1:], scale, dtype) for i in range(n)
        ])

    params["embed"] = table("embed", (V, D), 0.02)
    params["lm_head"] = table("lm_head", (D, V), sc)
    params["final_norm"] = jnp.ones((D,), dtype)
    return params


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [T, ..., d] rotated over interleaved pairs (2i, 2i+1) by
    positions x theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _f32(a):
    return a.astype(jnp.float32)


def attention(x, lp, *, H, r, dn, dr, dv, theta, eps):
    """The attention sublayer's output on a whole sequence x [T, D]."""
    T = x.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    h = _rms(x, _f32(lp["attn_norm"]), eps)
    q = (h @ _f32(lp["wq"])).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, theta)], axis=-1)
    kva = h @ _f32(lp["w_kva"])
    c = _rms(kva[:, :r], _f32(lp["kv_norm"]), eps)
    k_r = _rope(kva[:, r:], pos, theta)  # [T, dr]: one key for all heads
    kv = (c @ _f32(lp["w_kvb"])).reshape(T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (T, H, dr))], axis=-1)
    v = kv[..., dn:]

    def attend_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * ((dn + dr) ** -0.5)
        scores = jnp.where((pos[None, :] <= qpos[:, None])[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    attn = jax.lax.map(attend_block, jnp.arange(T // Q_BLOCK)).reshape(T, H * dv)
    return attn @ _f32(lp["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def dense_layer(x, lp, *, eps, **attn):
    x = x + attention(x, lp, eps=eps, **attn)
    h = _rms(x, _f32(lp["mlp_norm"]), eps)
    return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


def expert_weights(h, w_router, bias, *, k, renorm, scaling):
    """[T, E] float32: each token's weight on each expert, zero where the
    expert was not chosen."""
    s = jax.nn.sigmoid(h @ _f32(w_router))
    _, chosen = jax.lax.top_k(s + _f32(bias), k)
    picked = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32), axis=-2)
    w = s * picked
    if renorm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * scaling


def moe_layer(x, lp, *, eps, k, renorm, scaling, **attn):
    x = x + attention(x, lp, eps=eps, **attn)
    h = _rms(x, _f32(lp["mlp_norm"]), eps)
    w = expert_weights(h, lp["w_router"], lp["router_bias"], k=k, renorm=renorm,
                       scaling=scaling)

    def one_expert(acc, e):  # every token through expert e, weighed (0: not chosen)
        wg, wu, wd, we = e
        return acc + _swiglu(h, wg, wu, wd) * we[:, None], None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                             (lp["w_gate"], lp["w_up"], lp["w_down"], w.T))
    return x + routed + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def forward(config: dict, params: dict, ids):
    """Run one sequence of token ids through every layer. Returns the final
    hidden states [T_padded, D] before the last norm (pad at the end:
    causality keeps real tokens from seeing it). `params[name][l]` is all
    that is asked of a leaf."""
    s = sizes(config)
    n = len(ids)
    T = -(-n // Q_BLOCK) * Q_BLOCK
    toks = jnp.zeros((T,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32))
    attn = dict(H=s["H"], r=s["r"], dn=s["dn"], dr=s["dr"], dv=s["dv"],
                theta=s["theta"], eps=s["eps"])
    dense = jax.jit(functools.partial(dense_layer, **attn))
    moe = jax.jit(functools.partial(moe_layer, k=s["k"], renorm=s["renorm"],
                                    scaling=s["scaling"], **attn))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(jnp.float32)
        for l in range(s["L"]):
            lp = {}
            for name, leaves in params.items():
                if not isinstance(leaves, jax.Array):  # one leaf a layer
                    leaf = leaves[l]
                    if leaf is not None:
                        lp[name] = leaf
            x = (dense if l < s["Ld"] else moe)(x, lp)
    return x


def logits(config: dict, params: dict, x):
    """Final norm and output head on hidden states x [n, D] -> [n, V]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), s["eps"])
        return h @ params["lm_head"].astype(jnp.float32)
