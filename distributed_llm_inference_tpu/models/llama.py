"""Llama-family decoder (TinyLlama / Llama-2 / Llama-3) in pure JAX.

TPU-first redesign of the compute the reference spreads across three
processes: the orchestrator's embed/norm/lm_head
(/root/reference/orchestration.py:45-47,111,140-141) and the workers'
decoder-layer slices (/root/reference/Worker1.py:68-70,82-177) become one
functional model over a parameter pytree whose per-layer tensors are
*stacked on a leading layer axis*. That layout gives us:

  * `lax.scan` over layers (one compiled layer body, no Python loop),
  * clean pipeline partitioning — a stage's params are a contiguous slice
    of the layer axis, shardable with `NamedSharding` over the `pp` mesh
    axis (replacing the reference's LAYER_START/LAYER_END module constants,
    Worker1.py:27-28),
  * a KV cache with the same stacked layout, threaded through the scan.

Params pytree (L = n_layers, D = dim, H/KV heads, Dh = head_dim, F = ffn_dim,
V = vocab):
  embed       [V, D]
  layers:
    attn_norm [L, D]      mlp_norm [L, D]
    wq [L, D, H*Dh]  wk [L, D, KV*Dh]  wv [L, D, KV*Dh]  wo [L, H*Dh, D]
    w_gate [L, D, F]  w_up [L, D, F]  w_down [L, F, D]
  final_norm  [D]
  lm_head     [D, V]   (absent when tie_embeddings)
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import (
    attend,
    causal_mask,
    ragged_causal_mask,
    slot_causal_mask,
    update_kv_cache,
    update_kv_cache_slots,
)
from ..ops.flash_attention import flash_attend
from ..ops.kv_quant import KVQuant
from ..ops.kv_quant import dequantize as kv_dequantize
from ..ops.kv_quant import init_quant_cache
from ..ops.kv_quant import update_cache as kv_update
from ..ops.kv_quant import update_cache_slots as kv_update_slots
from ..ops.norms import rms_norm
from ..ops.quant import expert_einsum as eem
from ..ops.quant import matmul as mm
from ..ops.rope import apply_rope, rope_cos_sin
from .experts import BANKS, normal_slices, route, routed_ffn

Params = dict
KVCache = dict  # {"k": [L, B, KV, S, Dh], "v": [L, B, KV, S, Dh]}

# _init_routed's key of each drawn leaf: an index into split(key, 16)
# (cellbench/reference/block_diffusion_moe.py writes the same table down)
ROUTED_LEAF_KEYS = {
    "embed": 0, "lm_head": 1, "wq": 2, "wk": 3, "wv": 4, "wo": 5,
    "w_router": 6, "w_gate": 7, "w_up": 8, "w_down": 9,
}


def _init_routed(cfg: ModelConfig, key: jax.Array) -> Params:
    """init_params of a routed-expert configuration (cfg.moe_ffn_dim > 0):
    every drawn leaf slice by slice (models/experts.normal_slices: slice i
    of a stacked leaf [L, ...] is normal(split(key, L)[i]) * scale in
    float32, rounded to the dtype; the two vocabulary tables 8 slices of
    rows), because the float32 draw of a whole expert bank (5.6 GB at 7 x
    128 x 2048 x 768) does not fit beside the leaves already drawn. Norm
    weights 1; no biases; per-head qk-norm weights where the family has
    them."""
    dt = cfg.jnp_dtype
    L, D, V = cfg.n_layers, cfg.dim, cfg.vocab_size
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, F = cfg.n_experts, cfg.moe_ffn_dim
    if not (cfg.pre_norms and not cfg.post_norms and not cfg.tie_embeddings
            and not cfg.attn_qkv_bias and cfg.attn_window is None):
        raise ValueError(
            f"{cfg.name}: the routed llama layer is the plain pre-norm block"
        )
    ks = jax.random.split(key, 16)
    s = D ** -0.5
    shapes = {
        "wq": ((L, D, H * Dh), s), "wk": ((L, D, KV * Dh), s),
        "wv": ((L, D, KV * Dh), s), "wo": ((L, H * Dh, D), s),
        "w_router": ((L, D, E), s), "w_gate": ((L, E, D, F), s),
        "w_up": ((L, E, D, F), s), "w_down": ((L, E, F, D), F ** -0.5),
    }
    layers = {
        name: normal_slices(ks[ROUTED_LEAF_KEYS[name]], shape=shape,
                             scale=float(scale), dtype=dt)
        for name, (shape, scale) in shapes.items()
    }
    layers["attn_norm"] = jnp.ones((L, D), dt)
    layers["mlp_norm"] = jnp.ones((L, D), dt)
    if cfg.use_qk_norm:
        if cfg.qk_norm_dim != "head":
            raise ValueError(f"{cfg.name}: routed layers take per-head qk-norm")
        layers["q_norm"] = jnp.ones((L, Dh), dt)
        layers["k_norm"] = jnp.ones((L, Dh), dt)

    def table(name, shape, scale):
        cut = 8 if shape[0] % 8 == 0 else 1
        return normal_slices(
            ks[ROUTED_LEAF_KEYS[name]], scale=float(scale), dtype=dt,
            shape=(cut, shape[0] // cut) + shape[1:],
        ).reshape(shape)

    return {
        "embed": table("embed", (V, D), 0.02), "layers": layers,
        "final_norm": jnp.ones((D,), dt),
        "lm_head": table("lm_head", (D, V), s),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random-init params (for tests/benchmarks; real weights come from
    models/convert.py). Scaled-normal init, dtype = cfg.dtype."""
    if cfg.moe_ffn_dim:
        return _init_routed(cfg, key)
    dt = cfg.jnp_dtype
    L, D, F, V = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 10)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    s = D ** -0.5
    # unit-offset norms (Gemma) multiply by (1 + w): neutral init is 0
    norm_init = jnp.zeros if cfg.norm_unit_offset else jnp.ones
    params = {
        "embed": normal(ks[0], (V, D), 0.02),
        "layers": {
            "wq": normal(ks[1], (L, D, H * Dh), s),
            "wk": normal(ks[2], (L, D, KV * Dh), s),
            "wv": normal(ks[3], (L, D, KV * Dh), s),
            "wo": normal(ks[4], (L, H * Dh, D), s),
        },
        "final_norm": norm_init((D,), dt),
    }
    if cfg.pre_norms:
        params["layers"]["attn_norm"] = norm_init((L, D), dt)
        params["layers"]["mlp_norm"] = norm_init((L, D), dt)
    if cfg.post_norms:  # Gemma-2 sandwich norms (and OLMo-2's only norms)
        params["layers"]["attn_post_norm"] = norm_init((L, D), dt)
        params["layers"]["mlp_post_norm"] = norm_init((L, D), dt)
    wf = make_window_flags(cfg)
    if wf is not None:
        params["layers"]["window_flag"] = wf
    if cfg.n_experts:  # Mixtral-style MoE FFN: expert bank + router
        E = cfg.n_experts
        params["layers"].update(
            w_router=normal(ks[9], (L, D, E), s),
            w_gate=normal(ks[5], (L, E, D, F), s),
            w_up=normal(ks[6], (L, E, D, F), s),
            w_down=normal(ks[7], (L, E, F, D), F ** -0.5),
        )
    else:
        params["layers"].update(
            w_gate=normal(ks[5], (L, D, F), s),
            w_up=normal(ks[6], (L, D, F), s),
            w_down=normal(ks[7], (L, F, D), F ** -0.5),
        )
    if cfg.attn_qkv_bias:  # Qwen2-style
        params["layers"]["bq"] = jnp.zeros((L, H * Dh), dt)
        params["layers"]["bk"] = jnp.zeros((L, KV * Dh), dt)
        params["layers"]["bv"] = jnp.zeros((L, KV * Dh), dt)
    if cfg.use_qk_norm:
        # Qwen3/Gemma-3: per-head [Dh]; OLMo-2 ("proj"): whole projection
        if cfg.qk_norm_dim == "proj":
            params["layers"]["q_norm"] = norm_init((L, H * Dh), dt)
            params["layers"]["k_norm"] = norm_init((L, KV * Dh), dt)
        else:
            params["layers"]["q_norm"] = norm_init((L, Dh), dt)
            params["layers"]["k_norm"] = norm_init((L, Dh), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(ks[8], (D, V), s)
    return params


def make_window_flags(cfg: ModelConfig) -> Optional[jnp.ndarray]:
    """[L] per-layer sliding-window flag for mixed attention patterns
    (Gemma-2: even-indexed layers slide, HF `not bool(layer_idx % 2)`;
    Gemma-3: an explicit layer_types list — 5 sliding : 1 full), or None
    when the pattern is uniform. Single source of truth for init_params
    AND the converter — the stacked flag travels with a pipeline stage's
    layer slice."""
    if cfg.attn_window is None:
        return None
    if cfg.attn_window_layer_types is not None:
        return jnp.asarray(cfg.attn_window_layer_types, jnp.float32)
    if cfg.attn_window_pattern != "even":
        return None
    L = cfg.n_layers
    return (jnp.arange(L, dtype=jnp.int32) % 2 == 0).astype(jnp.float32)


def kernel_window(cfg: ModelConfig, window_flag):
    """Resolve this layer's window for the Pallas kernels: (static,
    traced) where exactly one is live. Uniform configs keep the STATIC
    cfg.attn_window; mixed patterns (window_flag is the layer's scalar
    from the stacked make_window_flags leaf, only present for them)
    yield a TRACED width — this layer's cfg.attn_window when flagged,
    -1 (= full causal, the kernels' <= 0 sentinel) otherwise. The single
    source of the flag -> width encoding for BOTH kernel hooks
    (default_attn_hook's chunk flash and engine/paged's fused decode)."""
    if window_flag is None:
        return cfg.attn_window, None
    return None, jnp.where(
        window_flag > 0, jnp.int32(cfg.attn_window), jnp.int32(-1)
    )


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_seq: Optional[int] = None, n_layers: Optional[int] = None
) -> KVCache:
    """Zeroed static-shape KV cache, stacked on the layer axis (shardable
    over `pp` exactly like the layer params)."""
    S = max_seq or cfg.max_seq_len
    L = n_layers if n_layers is not None else cfg.n_layers
    if cfg.kv_quant == "int8":
        # int8 data + per-(token, head) fp32 scales (ops/kv_quant.py);
        # same {"k", "v"} dict shape, leaves are KVQuant pytrees
        return init_quant_cache(L, batch, cfg.n_kv_heads, S, cfg.head_dim)
    shape = (L, batch, cfg.n_kv_heads, S, cfg.head_dim)
    dt = cfg.jnp_dtype
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def default_attn_hook(cfg, q, k, v, cache_k, cache_v, pos, mask, update_gate,
                      valid_start=None, window_flag=None):
    """Cache write + attention for the dense (whole-cache-per-device) case.

    The hook seam lets SPMD backends swap the attention/cache strategy per
    topology without forking the block: parallel/context.py substitutes
    ring attention (prefill) and context-parallel merge (decode) here.
    Returns (attn [B,T,H,Dh], cache_k, cache_v).

    window_flag: this layer's scalar from the stacked per-layer window
    pattern (Gemma-2/3 alternating layers; None for uniform configs). The
    XLA paths ignore it — their mask was already selected per layer in
    decoder_layer — but the flash kernel derives its traced per-layer
    window width from it (flash_attend's window_dyn scalar-prefetch
    operand).

    pos may be a PER-ROW [B] vector (continuous batching: each slot at its
    own position) — the cache write becomes a vmapped per-row update and
    attention uses the XLA path.

    attn_impl="pallas" applies to T>1 chunks only (prefill / chunked
    ingest / speculative verify — the compute-bound phases); every T=1
    decode step keeps the XLA einsum (see the inline notes).

    An int8 cache (ops/kv_quant.KVQuant leaves, cfg.kv_quant="int8")
    dispatches on the leaf type: quantize-on-write, dequantize into the
    attention matmuls on read. The fleet/solo split is the same.
    """
    # mixed per-layer window patterns (window_flag only exists for them):
    # the kernel's width becomes a TRACED per-layer scalar via the shared
    # kernel_window encoding, so one compiled kernel serves the whole scan
    def _flash(q_, nk, nv):
        w, wd = kernel_window(cfg, window_flag)
        return flash_attend(
            q_, nk, nv, pos, valid_start, wd, window=w,
            scale=cfg.query_scale, softcap=cfg.attn_softcap,
        )

    if isinstance(cache_k, KVQuant):
        upd = kv_update_slots if pos.ndim == 1 else kv_update
        new_k = upd(cache_k, k, pos, gate=update_gate)
        new_v = upd(cache_v, v, pos, gate=update_gate)
        if cfg.attn_impl == "pallas" and pos.ndim == 0 and q.shape[1] > 1:
            # same T>1-chunks-only gate as the raw-dtype path below; the
            # kernel dequantizes in its tile prologue, so the int8 cache
            # streams HALF the bytes the XLA dequant-then-attend path
            # materializes
            attn = _flash(q, new_k, new_v)
        else:
            attn = attend(
                q, kv_dequantize(new_k), kv_dequantize(new_v), mask,
                scale=cfg.query_scale, softcap=cfg.attn_softcap,
            )
        return attn, new_k, new_v
    if pos.ndim == 1:
        new_k, new_v = update_kv_cache_slots(
            cache_k, cache_v, k, v, pos, gate=update_gate
        )
        # the dense fleet's T=1 decode: the XLA einsum whatever
        # attn_impl says (the Pallas decode kernels read the paged pool)
        attn = attend(
            q, new_k, new_v, mask,
            scale=cfg.query_scale, softcap=cfg.attn_softcap,
        )
        return attn, new_k, new_v
    new_k, new_v = update_kv_cache(cache_k, cache_v, k, v, pos, gate=update_gate)
    if cfg.attn_impl == "pallas" and q.shape[1] > 1 \
            and not cfg.diffusion_block:  # (the flash kernel is causal)
        # Flash kernel for the COMPUTE-bound chunks only (prefill,
        # chunked ingest, speculative verify). A T=1 step has no flops
        # to hide a kernel launch under, so solo decode always takes the
        # XLA einsum (neither side measured on the serving path: the
        # benchmark's cells decode through the paged kernels).
        attn = _flash(q, new_k, new_v)
    else:
        attn = attend(
            q, new_k, new_v, mask,
            scale=cfg.query_scale, softcap=cfg.attn_softcap,
        )
    return attn, new_k, new_v


def moe_ffn(
    cfg: ModelConfig,
    lp: Params,
    h: jnp.ndarray,
    ep_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Mixtral-style sparse MoE FFN on a (normed) chunk h [B, T, D].

    HF MixtralSparseMoeBlock semantics (the behavioral spec): fp32 softmax
    over the router logits, top-k, renormalize the selected weights, sum
    the selected experts' SwiGLU outputs. Computed as all-local-experts +
    masked weighted sum: for small decode batches that is the standard
    inference pattern — under an `ep` mesh axis every device computes its
    1/ep slice of the expert bank for ALL tokens and one psum combines, so
    per-device FLOPs stay ~constant while parameters scale with E.

    lp holds this layer's (possibly ep-sharded) expert slice:
    w_router [D, E] (replicated), w_gate/w_up [E_loc, D, F],
    w_down [E_loc, F, D].
    """
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    logits = (h @ lp["w_router"]).astype(jnp.float32)  # [B, T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    if cfg.moe_renormalize:  # Qwen3-MoE: only with norm_topk_prob
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    weights = jnp.sum(
        jax.nn.one_hot(topi, E, dtype=jnp.float32) * topw[..., None], axis=-2
    )  # [B, T, E]: renormalized weight per expert, 0 for unselected
    weights = weights.astype(h.dtype)
    E_loc = lp["w_gate"].shape[0]
    if ep_axis is not None:
        lo = jax.lax.axis_index(ep_axis) * E_loc
        weights = jax.lax.dynamic_slice_in_dim(weights, lo, E_loc, axis=-1)
    # eem: dense array or int8 QTensor expert bank (ops/quant.expert_einsum)
    gate = jax.nn.silu(
        eem("btd,edf->btef", h, lp["w_gate"]).astype(jnp.float32)
    ).astype(h.dtype)
    up = eem("btd,edf->btef", h, lp["w_up"])
    down = eem("btef,efd->bted", gate * up, lp["w_down"])
    out = jnp.einsum("bted,bte->btd", down, weights)
    if ep_axis is not None:
        out = jax.lax.psum(out, ep_axis)
    return out


def decoder_layer(
    cfg: ModelConfig,
    lp: Params,
    x: jnp.ndarray,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    pos: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    mask: jnp.ndarray,
    update_gate: Optional[jnp.ndarray] = None,
    tp_axis: Optional[str] = None,
    attn_hook=None,
    valid_start: Optional[jnp.ndarray] = None,
    ep_axis: Optional[str] = None,
    lora_pages: Optional[jnp.ndarray] = None,
    layer: Optional[jnp.ndarray] = None,
    routed: Optional[tuple] = None,
):
    """One pre-norm decoder block on a chunk x [B,T,D] at offset `pos`.

    lp: this layer's params (no leading L axis). Returns (x, cache_k, cache_v).
    routed: None, or (banks, layer index, live) of a routed-expert
    configuration (cfg.moe_ffn_dim > 0: `routed_mlp`); the return then
    carries a fourth value, the tokens each expert got [E].
    layer: None, and cache_k/v are this layer's slices of the cache; or,
    under a paged hook (forward_layers), the traced index of this layer in
    the STACKED pool leaves that cache_k/v then are, handed on to the hook.
    update_gate: optional traced bool — when False the cache write is
    discarded (needed by the pipeline runtime, where a stage executes
    speculatively on microsteps when it holds no valid microbatch).
    attn_hook: optional override of `default_attn_hook` (same signature) —
    the context-parallel backend injects ring / merged attention here.

    Tensor parallelism (Megatron-style): under `shard_map` with a `tp` mesh
    axis, lp holds the HEAD-SLICED shard (wq/wk/wv column-sharded over
    heads, wo row-sharded; w_gate/w_up column-, w_down row-sharded) and
    `tp_axis` names the axis — head counts are derived from the local param
    shapes, and the two row-sharded projections psum their partial outputs
    before the residual add, keeping activations replicated over tp.

    lora_pages: optional [B] int32 adapter-pool page ids (engine/
    adapters.AdapterPool), TRACED — one compiled program serves any
    adapter mix. When lp carries paged lora_{leaf}_{a,b} leaves, every
    projection adds its per-row low-rank delta (x @ a[page]) @ b[page]
    via a traced gather + batched matmul. Page 0 is the reserved base
    page: its rows SELECT the undisturbed base product (jnp.where, not
    +0.0 — IEEE -0.0 + 0.0 would break bit-identity with the no-adapter
    program). Deltas apply BEFORE the tp psums: a/b shard so the partial
    products sum correctly by linearity (parallel/partition.py).

    Under the layer scan `lp`'s leaves are the scan's slices of the stacked
    parameters, and every projection's dot reads its slice in place. A
    reshape straight after a scanned weight's product would be moved onto
    the weight, and the slice then cannot fuse into the dot: q, k and v
    pass `pin_products` before their head split (its docstring).
    """
    B, T, D = x.shape
    Dh = cfg.head_dim  # invariant under tp (heads shard, head_dim doesn't)
    H = lp["wq"].shape[-1] // Dh
    KV = lp["wk"].shape[-1] // Dh
    uo = cfg.norm_unit_offset

    if isinstance(mask, tuple):
        # Gemma-2 alternating attention: (full, windowed) masks built once
        # per chunk; this layer's stacked window_flag picks its own
        mask_full, mask_win = mask
        mask = jnp.where(lp["window_flag"] > 0, mask_win, mask_full)

    # OLMo-2 (pre_norms=False): the sublayer reads x raw, its OUTPUT is
    # normed before the residual (post_norms carries those weights)
    def lmm(hh, leaf):
        # mm: plain array or int8 QTensor (ops/quant.py) transparently;
        # paged LoRA delta rides on top when the leaves are installed
        out = mm(hh, lp[leaf])
        a = lp.get(f"lora_{leaf}_a")
        if lora_pages is None or a is None:
            return out
        b = lp[f"lora_{leaf}_b"]
        u = jnp.einsum("bti,bir->btr", hh, a[lora_pages])
        d = jnp.einsum("btr,bro->bto", u, b[lora_pages])
        return jnp.where(
            (lora_pages > 0)[:, None, None], out + d.astype(out.dtype), out
        )

    # the step's scopes (utils/tracing.STEP_SCOPES): a block runs from its
    # input norm to its output projection, and the residual add between two
    # blocks belongs to the one it feeds
    with jax.named_scope("attn"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps, unit_offset=uo) \
            if cfg.pre_norms else x
        q, k, v = pin_products(lmm(h, "wq"), lmm(h, "wk"), lmm(h, "wv"))
        if cfg.attn_qkv_bias:  # Qwen2-style (biases tp-shard with their columns)
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        if cfg.use_qk_norm and cfg.qk_norm_dim == "proj":
            # OLMo-2: RMSNorm over the WHOLE projection before the head split
            # (weights [H*Dh] / [KV*Dh]; tp-sharded with their columns)
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps, unit_offset=uo)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps, unit_offset=uo)
        q = q.reshape(B, T, H, Dh)
        k = k.reshape(B, T, KV, Dh)
        v = v.reshape(B, T, KV, Dh)
        if cfg.use_qk_norm and cfg.qk_norm_dim == "head":
            # Qwen3/Gemma-3: per-head RMSNorm over head_dim on q and k,
            # BEFORE RoPE (HF Qwen3Attention / Gemma3Attention); weights [Dh]
            # broadcast over the head axis, invariant under tp. Gemma-3's
            # norm is the unit-offset (1 + w) flavor like its other norms.
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps, unit_offset=uo)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps, unit_offset=uo)
        if isinstance(cos, tuple):
            # Gemma-3 dual RoPE: sliding layers use the local table
            cos_full, cos_local = cos
            sin_full, sin_local = sin
            cos = jnp.where(lp["window_flag"] > 0, cos_local, cos_full)
            sin = jnp.where(lp["window_flag"] > 0, sin_local, sin_full)
        q, k = apply_rope(q, k, cos, sin)

        hook = attn_hook or default_attn_hook
        attn, new_k, new_v = hook(
            cfg, q, k, v, cache_k, cache_v, pos, mask, update_gate, valid_start,
            lp.get("window_flag"), *(() if layer is None else (layer,)),
        )
        attn_out = lmm(attn.reshape(B, T, H * Dh), "wo")
        if tp_axis is not None:
            attn_out = jax.lax.psum(attn_out, tp_axis)
        if cfg.post_norms:  # Gemma-2: norm the branch output before the residual
            attn_out = rms_norm(attn_out, lp["attn_post_norm"], cfg.norm_eps, unit_offset=uo)
        if cfg.residual_multiplier is not None:  # Granite
            attn_out = attn_out * jnp.asarray(cfg.residual_multiplier, attn_out.dtype)
    # (routed experts carry their own moe_* scopes: the norm in front of
    # them is the router's, what follows them the combine's)
    ffn_in, ffn_out = ("ffn", "ffn") if routed is None else \
        ("moe_route", "moe_combine")
    with jax.named_scope(ffn_in):
        x = x + attn_out
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps, unit_offset=uo) \
            if cfg.pre_norms else x
    sizes = None
    if routed is not None:
        mlp_out, sizes = routed_mlp(cfg, lp, h, *routed)
    else:
        with jax.named_scope("ffn"):
            if cfg.n_experts:
                mlp_out = moe_ffn(cfg, lp, h, ep_axis)  # psums over ep internally
            else:
                act = jax.nn.silu if cfg.act == "silu" else _gelu_tanh
                gate = act(lmm(h, "w_gate").astype(jnp.float32)).astype(h.dtype)
                mlp_out = lmm(gate * lmm(h, "w_up"), "w_down")
                if tp_axis is not None:
                    mlp_out = jax.lax.psum(mlp_out, tp_axis)
    with jax.named_scope(ffn_out):
        if cfg.post_norms:
            mlp_out = rms_norm(mlp_out, lp["mlp_post_norm"], cfg.norm_eps, unit_offset=uo)
        if cfg.residual_multiplier is not None:  # Granite
            mlp_out = mlp_out * jnp.asarray(cfg.residual_multiplier, mlp_out.dtype)
    with jax.named_scope("attn"):  # the next layer's (the head's, after the last)
        x = x + mlp_out
    if routed is not None:
        return x, new_k, new_v, sizes
    return x, new_k, new_v


def routed_mlp(cfg: ModelConfig, lp: Params, h, banks: Params, layer, live):
    """The routed-expert FFN of a llama-family layer (cfg.moe_ffn_dim > 0:
    SDAR-30B-A3B, the published Qwen3-MoE layer) on normed h [B, T, D]:
    float32 softmax over all experts, the n_experts_per_tok largest, their
    weights renormalized under moe_renormalize, each token through its
    experts alone (models/experts.routed_ffn over the STACKED banks, which
    ride outside the layer scan). `moe_ffn` above is the same layer with
    every expert on every token. Returns (out [B, T, D] in h's dtype,
    tokens an expert got [E])."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    with jax.named_scope("moe_route"):
        chosen, weights = route(cfg, flat, lp["w_router"])
    out, sizes = routed_ffn(cfg, banks, layer, flat, chosen, weights,
                            live=live)
    return out.reshape(B, T, D).astype(h.dtype), sizes


def _gelu_tanh(x):
    """gelu_pytorch_tanh (Gemma's hidden activation)."""
    return jax.nn.gelu(x, approximate=True)


def forward_layers(
    cfg: ModelConfig,
    layers: Params,
    x: jnp.ndarray,
    cache: KVCache,
    pos: jnp.ndarray,
    update_gate: Optional[jnp.ndarray] = None,
    tp_axis: Optional[str] = None,
    attn_hook=None,
    valid_start: Optional[jnp.ndarray] = None,
    ep_axis: Optional[str] = None,
    attn_seq_len: Optional[int] = None,
    lora_pages: Optional[jnp.ndarray] = None,
):
    """Scan the stacked layer params over a chunk. Works for any contiguous
    slice of layers (full model or one pipeline stage's slice).

    x: [B, T, D]; cache k/v: [L_slice, B, KV, S, Dh]; pos: scalar int32 OR
    a per-row [B] int32 vector (continuous batching — each slot row at its
    own sequence position; RoPE tables and the causal mask go per-row).
    Returns (x, new_cache). attn_hook: see decoder_layer.
    valid_start: optional [B] int32 — first REAL slot per row for ragged
    left-padded batches (slots before it are pad and never attended).
    attn_seq_len: mask sequence length override — the paged-KV hook
    (engine/paged.py) attends a GATHERED [B, KV, n_blocks*bs, Dh] view
    whose logical length is not the cache leaf's seq axis (that axis is
    the block size there), so masks must be built to the logical length.

    A paged hook (`attn_hook.paged`, engine/paged.py) changes what the
    scan does with the cache: the pool [L_slice, n_blocks, KV, bs, Dh] is
    not scanned over but CARRIED, stacked, beside x, and each layer's hook
    gets the whole leaves and the layer's index. As xs / ys the scan cut
    every layer's slice out of the pool and stacked the slices it got back
    into a second pool, moving the pool several times a step to write a
    few tokens; as a carry the hook's scatter updates it in place. The
    dense cache keeps the xs / ys form: its hook rewrites the layer's
    whole slice anyway.
    """
    T = x.shape[1]
    S = attn_seq_len if attn_seq_len is not None else cache["k"].shape[3]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B, T]
    else:
        positions = pos + jnp.arange(T, dtype=jnp.int32)
    with jax.named_scope("attn"):  # the rotary tables, once a forward
        cos, sin = rope_cos_sin(
            positions, cfg.head_dim, cfg.rope_theta,
            scaling=cfg.rope_scaling,
            scaling_factor=cfg.rope_scaling_factor,
            low_freq_factor=cfg.rope_low_freq_factor,
            high_freq_factor=cfg.rope_high_freq_factor,
            original_max_len=cfg.rope_original_max_len,
        )
    if cfg.rope_local_theta is not None:
        # Gemma-3: sliding layers rotate with their own UNSCALED local
        # theta; both tables built once, each layer selects by its
        # window_flag (decoder_layer)
        cos_l, sin_l = rope_cos_sin(
            positions, cfg.head_dim, cfg.rope_local_theta
        )
        cos, sin = (cos, cos_l), (sin, sin_l)

    def make_mask(window):
        B_ = cfg.diffusion_block
        if pos.ndim == 1:
            return slot_causal_mask(pos, T, S, window, B_)
        if valid_start is None:
            return causal_mask(pos, T, S, window, B_)
        return ragged_causal_mask(pos, T, S, valid_start, window, B_)

    mixed_pattern = cfg.attn_window is not None and (
        cfg.attn_window_pattern == "even"
        or cfg.attn_window_layer_types is not None
    )
    if mixed_pattern:
        # Gemma-2/3 mixed attention: both masks built once; each layer
        # selects by its stacked window_flag (decoder_layer)
        mask = (make_mask(None), make_mask(cfg.attn_window))
    else:
        mask = make_mask(cfg.attn_window)

    paged = getattr(attn_hook, "paged", False)

    if cfg.moe_ffn_dim:
        return _forward_routed(cfg, layers, x, cache, pos, cos, sin, mask,
                               update_gate, tp_axis, attn_hook, valid_start,
                               ep_axis, lora_pages)

    def layer_step(xc, lp, kv, layer):
        xc, ck, cv = decoder_layer(
            cfg, lp, xc, *kv, pos, cos, sin, mask, update_gate, tp_axis,
            attn_hook, valid_start, ep_axis, lora_pages,
            layer if paged else None,
        )
        return xc, (ck, cv), None

    x, (new_k, new_v), _ = scan_layers(
        layer_step, x, layers, (cache["k"], cache["v"]), paged=paged
    )
    return x, {"k": new_k, "v": new_v}


def _forward_routed(cfg, layers, x, cache, pos, cos, sin, mask, update_gate,
                    tp_axis, attn_hook, valid_start, ep_axis, lora_pages):
    """forward_layers' scan for a routed-expert configuration: the expert
    banks are closed over, never sliced by the scan (models/experts.py), and
    each layer hands back the tokens its experts got. With a "routed" leaf
    [2, L, E] int32 in the cache (the paged pool's, engine/paged.init_pool)
    the layers add what they routed to it: [0] tokens an expert got, [1]
    steps in which it got any (models/mla_moe.forward_layers' contract)."""
    if tp_axis is not None or ep_axis is not None or lora_pages is not None:
        raise ValueError(
            f"{cfg.name}: routed experts are not sharded over tp or ep and "
            f"take no runtime adapter"
        )
    T = x.shape[1]
    paged = getattr(attn_hook, "paged", False)
    # rows whose output nothing reads reach no expert (engine/paged's hooks
    # say which: launch padding, freed slots)
    live = getattr(attn_hook, "live", None)
    if live is not None and T > 1:
        live = jnp.repeat(live, T)
    banks = {name: layers[name] for name in BANKS}
    small = {name: leaf for name, leaf in layers.items() if name not in BANKS}

    def layer_step(xc, lp, kv, layer):
        xc, ck, cv, sizes = decoder_layer(
            cfg, lp, xc, *kv, pos, cos, sin, mask, update_gate, None,
            attn_hook, valid_start, None, None, layer if paged else None,
            routed=(banks, layer, live),
        )
        return xc, (ck, cv), sizes

    x, (new_k, new_v), sizes = scan_layers(
        layer_step, x, small, (cache["k"], cache["v"]), paged=paged
    )
    new = {**cache, "k": new_k, "v": new_v}
    if "routed" in cache:
        new["routed"] = cache["routed"] + jnp.stack(
            [sizes, (sizes > 0).astype(jnp.int32)]
        )
    return x, new


def pin_products(*products):
    """The products of a scanned layer's weights, handed on as they are:
    whatever reshapes them next stays behind them (ISSUE 39).

    The trap this closes: a reshape straight after `h @ w` (the head split)
    is moved by the TPU compiler THROUGH the dot onto the weight. Inside
    the layer scan the weight is the scan's dynamic slice of the stacked
    leaf, and with a bitcast between that slice and the dot the slice can
    no longer be an operand fused into the dot: it becomes a loop fusion of
    its own that copies one layer's weights out a layer-step (the dot then
    reads the copy), and layout assignment relays the WHOLE stack out once
    a launch. `wo` / `w_gate` / `w_up` / `w_down` never met it (nothing
    reshapes their products), nor OLMo-2's q and k (a whole-projection norm
    stands in between). An einsum onto a `[L, D, H, Dh]` view of the leaf
    fuses the slice and keeps the stack's relayout; the barrier keeps
    neither, and is the same `dot_general`s, bit for bit.
    tests/test_chip_compile.py holds the cells' programs to it."""
    return jax.lax.optimization_barrier(products)


def scan_layers(layer_step, x, layers, cache, *, paged: bool):
    """The layer scan of every family (llama here, gpt2, mla_moe's stacks):
    `layer_step(x, lp, cache, layer) -> (x, cache, ys)` over the stacked
    parameters `layers`, `cache` any pytree of leaves stacked [L, ...].
    Dense cache: `layer_step` gets the layer's slices, which are scanned
    over and stacked again. paged: the stacked leaves ride the carry whole
    and `layer` says which layer to touch (forward_layers' docstring).
    Returns (x, cache, the stacked ys).

    `lp` is the scan's dynamic slice of each stacked leaf, and a dot reads
    it in place only while nothing stands between the slice and the dot: a
    `layer_step` that reshapes a weight's product at once (a head split)
    passes the product through `pin_products` first."""
    index = jnp.arange(jax.tree.leaves(cache)[0].shape[0], dtype=jnp.int32)
    if paged:
        def body(carry, xs):
            (xc, cache), (lp, layer) = carry, xs
            xc, cache, ys = layer_step(xc, lp, cache, layer)
            return (xc, cache), ys

        (x, cache), ys = jax.lax.scan(body, (x, cache), (layers, index))
        return x, cache, ys

    def body(xc, xs):
        lp, cache, layer = xs
        xc, cache, ys = layer_step(xc, lp, cache, layer)
        return xc, (cache, ys)

    x, (cache, ys) = jax.lax.scan(body, x, (layers, cache, index))
    return x, cache, ys


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params: Params, tokens: jnp.ndarray, pos=0) -> jnp.ndarray:
    """Token embedding lookup: [B, T] -> [B, T, D]
    (reference orchestration.py:111). `pos` is accepted for interface parity
    with gpt2.embed (learned positions); RoPE models ignore it here.
    Gemma scales by sqrt(dim) in the activation dtype (HF normalizer)."""
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.dim ** 0.5, x.dtype)
    if cfg.embed_multiplier is not None:  # Granite
        x = x * jnp.asarray(cfg.embed_multiplier, x.dtype)
    return x


@jax.named_scope("head")
def unembed(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Final RMSNorm + LM head: [B, T, D] -> [B, T, V] logits
    (reference orchestration.py:140-141). Gemma-2 softcaps the final
    logits: cap * tanh(logits / cap)."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                 unit_offset=cfg.norm_unit_offset)
    if cfg.tie_embeddings:
        logits = (x @ params["embed"].T).astype(jnp.float32)
    else:
        logits = mm(x, params["lm_head"]).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    if cfg.logits_divider is not None:  # Granite logits_scaling
        logits = logits / cfg.logits_divider
    return logits


def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,
    cache: KVCache,
    pos: jnp.ndarray,
):
    """Full-model chunk forward: tokens [B,T] at offset pos -> (logits
    [B,T,V] fp32, new_cache). One call == prefill; T=1 call == decode step."""
    x = embed(cfg, params, tokens)
    x, cache = forward_layers(cfg, params["layers"], x, cache, pos)
    return unembed(cfg, params, x), cache
