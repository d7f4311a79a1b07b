"""Plain reference of SDAR-30B-A3B-Chat (JetLM, `sdar_moe`): the Qwen3-MoE
decoder layer generating by diffusion over blocks, in straightforward
float32 `jax.numpy` under `jax.default_matmul_precision("highest")`: no
kernels, no cache, no batching, nothing imported from the program, and
every token through EVERY expert under a dense mask of the weights.

The layer (d hidden, H query heads, KV key-value heads of Dh numbers;
pre-norm RMSNorm, no biases, untied head):

  attention   h = RMSNorm(x) g1; q = h Wq -> [H, Dh], k = h Wk, v = h Wv ->
              [KV, Dh]; q and k each RMSNorm over the Dh numbers of a head
              with its own weight, then rotated in the rotate-half form at
              the token's absolute position; scores q . k / sqrt(Dh), query
              head i reads key-value head i // (H / KV); position t sees s
              iff s < (t // B + 1) * B (B the block length); x += concat(p v) Wo
  experts     h2 = RMSNorm(x) g2; p = softmax(h2 Wr) over all experts; the
              num_experts_per_tok largest are chosen, weights p_i / sum of
              the chosen p (norm_topk_prob); x += sum_i w_i SwiGLU_i(h2)

Generation (the SDAR repository's `block_diffusion_generate` under its
`sequential` remasking with a static count): the sequence is cut into
blocks of B at absolute positions; earlier blocks are clean and their keys
and values are those of the clean block under the mask above; the open
block holds what is revealed so far followed by mask tokens; a forward of
the open block reveals the leftmost B / denoise_steps masked positions,
each from the logits AT its own position with the mask id's logit at -inf.

So the state in which token t was chosen follows from t alone: in t's
block the positions before t's reveal group are clean and the rest are
mask tokens. `forward` runs, for every block, the clean block and each of
its denoise states (one batch, earlier blocks' keys and values taken from
the clean ones), and returns in row t - 1 the final hidden state AT
position t of the state that reveals t, which is where
`harness/ref_child.generated_logits` reads the prediction of token t. A
prompt whose length is not a multiple of B leaves its remainder clean at
the head of the first generated block and moves that block's reveal
groups: `forward(..., n_prompt=)` (the harness passes no prompt length, so
the cell's check sequences have prompts that are multiples of B; the
repo's tests call this with the other remainders).

Not in the published config.json, so read from the configuration file's
`diffusion` group: block_length, mask_token_id, denoise_steps. The weights
are random: `make_params` writes down the program's documented
initialisation (models/llama._init_routed: 16 keys split from
PRNGKey(seed), the table LEAF_KEY below; a stacked leaf [n, ...] is n
slices, slice i drawn from split(key, n)[i] in float32, scaled, rounded to
the served dtype; the two vocabulary tables are 8 such slices of rows; norm
weights 1). A parameter is `params[name][layer]`, a list per name, so that
tools/control.py can hand back any layer's matrix changed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCKS_A_PASS = 64  # blocks whose states attend in one pass (bounds the scores)

LEAF_KEY = {
    "embed": 0, "lm_head": 1, "wq": 2, "wk": 3, "wv": 4, "wo": 5,
    "w_router": 6, "w_gate": 7, "w_up": 8, "w_down": 9,
}
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_router", "w_gate", "w_up", "w_down",
                "attn_norm", "mlp_norm", "q_norm", "k_norm")


def sizes(config: dict) -> dict:
    """The sizes as the configuration file publishes them (HF key names),
    and the three that config.json does not give."""
    d = config["diffusion"]
    return dict(
        L=config["num_hidden_layers"], D=config["hidden_size"],
        H=config["num_attention_heads"], KV=config["num_key_value_heads"],
        Dh=config["head_dim"], F=config["moe_intermediate_size"],
        E=config["num_experts"], k=config["num_experts_per_tok"],
        V=config["vocab_size"], theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]), renorm=bool(config["norm_topk_prob"]),
        B=int(d["block_length"]), mask=int(d["mask_token_id"]),
        steps=int(d["denoise_steps"]),
    )


def make_params(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The seeded random tree: {name: [layer 0's leaf, layer 1's, ...]},
    and embed, lm_head, final_norm."""
    s = sizes(config)
    L, D, H, KV, Dh, E, F, V = (s[n] for n in ("L", "D", "H", "KV", "Dh", "E", "F", "V"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 16)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    sc = D ** -0.5
    shapes = {
        "wq": ((D, H * Dh), sc), "wk": ((D, KV * Dh), sc), "wv": ((D, KV * Dh), sc),
        "wo": ((H * Dh, D), sc), "w_router": ((D, E), sc), "w_gate": ((E, D, F), sc),
        "w_up": ((E, D, F), sc), "w_down": ((E, F, D), F ** -0.5),
    }
    params = {}
    for name, (shape, scale) in shapes.items():
        keys = jax.random.split(ks[LEAF_KEY[name]], L)
        params[name] = [normal(keys[i], shape, float(scale)) for i in range(L)]
    for name, width in (("attn_norm", D), ("mlp_norm", D), ("q_norm", Dh), ("k_norm", Dh)):
        params[name] = [jnp.ones((width,), dtype)] * L

    def table(name, shape, scale):  # 8 slices of rows
        n = 8 if shape[0] % 8 == 0 else 1
        keys = jax.random.split(ks[LEAF_KEY[name]], n)
        return jnp.concatenate([
            normal(keys[i], (shape[0] // n,) + shape[1:], scale) for i in range(n)
        ])

    params["embed"] = table("embed", (V, D), 0.02)
    params["lm_head"] = table("lm_head", (D, V), sc)
    params["final_norm"] = jnp.ones((D,), dtype)
    return params


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [..., heads, Dh] at positions [...]; rotate-half convention."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def _f32(a):
    return a.astype(jnp.float32)


def attention(x, lp, *, H, KV, Dh, B, theta, eps):
    """The attention sublayer's output on x [nb, S, B, D]: state s of block
    b, whose last state (S - 1) is the clean block. Every state of block b
    attends the CLEAN keys and values of blocks before b and its own B."""
    nb, S = x.shape[:2]
    pos = (jnp.arange(nb, dtype=jnp.int32)[:, None] * B
           + jnp.arange(B, dtype=jnp.int32)[None, :])  # [nb, B]
    h = _rms(x, _f32(lp["attn_norm"]), eps)
    q = (h @ _f32(lp["wq"])).reshape(nb, S, B, H, Dh)
    k = (h @ _f32(lp["wk"])).reshape(nb, S, B, KV, Dh)
    v = (h @ _f32(lp["wv"])).reshape(nb, S, B, KV, Dh)
    q = _rms(q, _f32(lp["q_norm"]), eps)
    k = _rms(k, _f32(lp["k_norm"]), eps)
    p = jnp.broadcast_to(pos[:, None, :], (nb, S, B))
    q, k = _rope(q, p, theta), _rope(k, p, theta)
    k = jnp.repeat(k, H // KV, axis=3)  # each query head's key-value head
    v = jnp.repeat(v, H // KV, axis=3)
    k_clean = k[:, -1].reshape(nb * B, H, Dh)
    v_clean = v[:, -1].reshape(nb * B, H, Dh)
    scale = Dh ** -0.5

    def attend(i):  # BLOCKS_A_PASS blocks, all their states
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * BLOCKS_A_PASS, BLOCKS_A_PASS, 0)
        qb, kb, vb = cut(q), cut(k), cut(v)
        first = (i * BLOCKS_A_PASS + jnp.arange(BLOCKS_A_PASS, dtype=jnp.int32)) * B
        back = jnp.einsum("nsqhd,khd->nshqk", qb, k_clean) * scale
        seen = jnp.arange(nb * B, dtype=jnp.int32)[None, :] < first[:, None]  # [n, T]
        back = jnp.where(seen[:, None, None, None, :], back, -jnp.inf)
        own = jnp.einsum("nsqhd,nskhd->nshqk", qb, kb) * scale
        w = jax.nn.softmax(jnp.concatenate([back, own], axis=-1), axis=-1)
        out = jnp.einsum("nshqk,khd->nsqhd", w[..., : nb * B], v_clean)
        return out + jnp.einsum("nshqk,nskhd->nsqhd", w[..., nb * B:], vb)

    attn = jax.lax.map(attend, jnp.arange(nb // BLOCKS_A_PASS))
    return attn.reshape(nb, S, B, H * Dh) @ _f32(lp["wo"])


def expert_weights(h, w_router, *, k, renorm):
    """[..., E] float32: each token's weight on each expert, zero where the
    expert was not chosen."""
    p = jax.nn.softmax(h @ _f32(w_router), axis=-1)
    _, chosen = jax.lax.top_k(p, k)
    picked = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=jnp.float32), axis=-2)
    w = p * picked
    return w / jnp.sum(w, axis=-1, keepdims=True) if renorm else w


def layer(x, lp, *, eps, k, renorm, **attn):
    x = x + attention(x, lp, eps=eps, **attn)
    h = _rms(x, _f32(lp["mlp_norm"]), eps)
    w = expert_weights(h, lp["w_router"], k=k, renorm=renorm)

    def one_expert(acc, e):  # every token through expert e, weighed (0: not chosen)
        wg, wu, wd, we = e
        y = (jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))) @ _f32(wd)
        return acc + y * we[..., None], None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lp["w_gate"], lp["w_up"], lp["w_down"], jnp.moveaxis(w, -1, 0)))
    return x + routed


def reveal_groups(s: dict, head: int = 0) -> list:
    """For a block whose first `head` positions are clean from the start
    (a prompt's remainder; 0 for every other block): the clean count of
    each denoise state, in order. A forward reveals B / denoise_steps
    masked positions, leftmost first."""
    count = s["B"] // s["steps"]
    return list(range(head, s["B"], count))


def forward(config: dict, params: dict, ids, n_prompt=None):
    """Run one sequence's denoise states through every layer. Returns
    [T_padded, D]: row t - 1 holds the final hidden state (before the last
    norm) AT position t, in the state of t's block that reveals t. With
    `n_prompt` the block the prompt ends in keeps the prompt's remainder
    clean in every state. `params[name][l]` is all that is asked of a
    leaf."""
    s = sizes(config)
    B, n = s["B"], len(ids)
    S = s["steps"] + 1  # a block's denoise states, then the clean block
    nb = -(-(-(-n // B)) // BLOCKS_A_PASS) * BLOCKS_A_PASS
    toks = jnp.full((nb * B,), s["mask"], jnp.int32).at[:n].set(
        jnp.asarray(ids, jnp.int32)).reshape(nb, B)
    head = jnp.zeros((nb,), jnp.int32)
    if n_prompt is not None and n_prompt % B:
        head = head.at[n_prompt // B].set(n_prompt % B)
    count = B // s["steps"]
    offset = jnp.arange(B, dtype=jnp.int32)
    # state g of a block: its first head + g * count positions are clean
    clean = head[:, None] + jnp.arange(s["steps"], dtype=jnp.int32)[None, :] * count
    clean = jnp.concatenate([clean, jnp.full((nb, 1), B, jnp.int32)], axis=1)  # [nb, S]
    state = jnp.where(offset[None, None, :] < clean[:, :, None], toks[:, None, :], s["mask"])
    step = jax.jit(functools.partial(
        layer, H=s["H"], KV=s["KV"], Dh=s["Dh"], B=B, theta=s["theta"], eps=s["eps"],
        k=s["k"], renorm=s["renorm"]))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][state].astype(jnp.float32)  # [nb, S, B, D]
        for l in range(s["L"]):
            x = step(x, {name: params[name][l] for name in LAYER_LEAVES})
    # position (b, o) is revealed by state (o - head) // count of its block
    group = jnp.clip((offset[None, :] - head[:, None]) // count, 0, s["steps"] - 1)
    at = jnp.take_along_axis(x, group[:, None, :, None], axis=1)[:, 0]  # [nb, B, D]
    return at.reshape(nb * B, -1)[1:]


def logits(config: dict, params: dict, x):
    """Final norm and output head on hidden states x [n, D] -> [n, V]. The
    mask token is never an output: its logit is put at the row's lowest, so
    that no choice lands on it (the program puts it at -inf; a finite value
    here keeps the spread of a row, which the harness divides by,
    finite)."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), s["eps"])
        out = h @ params["lm_head"].astype(jnp.float32)
    return out.at[:, s["mask"]].set(jnp.min(out, axis=-1))
