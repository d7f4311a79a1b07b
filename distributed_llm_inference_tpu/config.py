"""Model / engine / mesh configuration.

Replaces the reference's hand-edited module constants (MODEL_NAME / LAYER_START /
LAYER_END / WORKER_*_URL, /root/reference/Worker1.py:26-31,
/root/reference/orchestration.py:20-24) with dataclass configs: the layer ranges
per pipeline stage are *computed* from (n_layers, pp_stages) instead of pasted by
hand, and the mesh shape replaces the manual URL wiring.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


def _mamba_state(cfg) -> tuple:
    from .ops.ssm_scan import state_shape  # (owns the packed layout)

    return state_shape(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)


# what a layer of each recurrent kind keeps for a row, as the pool's leaves
# hold it (engine/paged.init_pool): (channels of its convolution state, the
# last conv_kernel - 1 inputs; its float32 matrix state's shape), each a
# function of the ModelConfig, None where the kind keeps none. A new kind is
# a row here: the KEEPS_* sets below are read off it.
STATE_OF_KIND = {
    # gated short convolutions (models/lfm2.py): the model's width
    "conv": (lambda c: c.dim, None),
    # a state-space mixer (models/granite_hybrid.py): [x | B | C], and
    # [H / pack, N, pack x P]
    "mamba": (lambda c: (c.ssm_heads * c.ssm_head_dim
                         + 2 * c.ssm_groups * c.ssm_state), _mamba_state),
    # decayed linear attention (models/minicpm_sala.py): [heads, Dh, Dh]
    "lightning-attn": (
        None, lambda c: (c.linear_heads, c.head_dim, c.head_dim)),
    # the gated delta rule (models/solar_open2.py): the convolutions of q, k
    # and v side by side, and [heads, Dv, Dk] (ops/delta_rule.py: transposed)
    "kda": (lambda c: 3 * c.linear_heads * c.head_dim,
            lambda c: (c.linear_heads, c.head_dim, c.head_dim)),
}
# the layer kinds (ModelConfig.layer_types) by what a layer of the kind KEEPS
# for a row, whatever the arch: the pool's leaves, the prefix index's
# snapshots and the start-up refusals are keyed on these, not on arch names
KEEPS_CONV_STATE = frozenset(
    kind for kind, (conv, _) in STATE_OF_KIND.items() if conv)
KEEPS_MATRIX_STATE = frozenset(
    kind for kind, (_, state) in STATE_OF_KIND.items() if state)
KEEPS_KV = frozenset({"full_attention", "minicpm4", "attention"})
# K/V of the last attn_window positions alone: in a stack that also has a
# kind that keeps its whole context, the pool holds such layers in a group
# of their own whose rows give blocks back (`kv_groups`)
KEEPS_WINDOW_KV = frozenset({"sliding_attention"})
SELECTS_BLOCKS = frozenset({"minicpm4"})
# the families whose routed layer is told which experts it holds
# (cfg.expert_lo) and whose seeded draw follows an expert's PUBLISHED index,
# so that a configuration can be one chip's share of an expert-parallel layer
HOLDS_EXPERT_SHARE = frozenset({"afmoe", "mimo_v2", "solar_open2"})
# the layers whose matrix state is folded by the delta rule, a chunk at a
# time (ops/delta_rule.py: the launch records count their chunks)
FOLDS_BY_DELTA_RULE = frozenset({"kda"})


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for a decoder-only causal LM.

    Covers the Llama family (RMSNorm + RoPE + GQA + SwiGLU: TinyLlama,
    Llama-2-7B/13B, Llama-3-8B) and the GPT-2 family (LayerNorm + learned
    positions + MHA + gelu_new, tied embeddings).
    """

    name: str = "tinyllama-1.1b"
    # "llama" | "gpt2" | "mla_moe" | "lfm2" | "afmoe" | "minicpm_sala"
    # | "granite_hybrid" | "mimo_v2" | "solar_open2"
    arch: str = "llama"
    vocab_size: int = 32000
    dim: int = 2048
    n_layers: int = 22
    n_heads: int = 32
    n_kv_heads: int = 4  # GQA; == n_heads for MHA
    ffn_dim: int = 5632
    max_seq_len: int = 2048
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # RoPE frequency scaling (Llama-3.1/3.2-style "llama3" rope_scaling):
    # HF applies it to the inverse frequencies unconditionally — including
    # positions below the original context — so checkpoints trained with it
    # produce wrong logits at EVERY position unless it is reproduced.
    # None = plain RoPE.
    rope_scaling: Optional[str] = None  # None | "llama3" | "linear"
    rope_scaling_factor: float = 8.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_len: int = 8192
    # Gemma-3 dual RoPE: sliding-window layers use their own (local)
    # theta with NO scaling; full-attention layers use rope_theta (+ any
    # rope_scaling). None = one table for every layer.
    rope_local_theta: Optional[float] = None
    # Sliding-window attention (Mistral-style): a query attends only the
    # last `attn_window` positions. None = full causal.
    attn_window: Optional[int] = None
    # Which layers use the sliding window: "all" (Mistral) or "even"
    # (Gemma-2: even-indexed layers slide, odd attend fully — the stacked
    # layer params carry a per-layer window_flag so pipeline stages keep
    # their own slice's pattern).
    attn_window_pattern: str = "all"
    # Explicit per-layer pattern (Gemma-3's 5 sliding : 1 full): tuple of
    # n_layers ints, 1 = sliding-window layer, 0 = full attention.
    # Overrides attn_window_pattern when set.
    attn_window_layer_types: Optional[tuple] = None
    # Gemma-family knobs (all default off => plain Llama semantics):
    # explicit head_dim (Gemma-7B: 16 heads x 256 != dim 3072)
    head_dim_override: Optional[int] = None
    # RMSNorm multiplies by (1 + weight) (HF GemmaRMSNorm)
    norm_unit_offset: bool = False
    # MLP activation on the gate projection
    act: str = "silu"  # "silu" | "gelu_tanh"
    # scale embeddings by sqrt(dim) after lookup (GemmaModel normalizer)
    embed_scale: bool = False
    # Granite scalar multipliers (all None = off): embeddings scale by
    # embed_multiplier; every sublayer output scales by residual_multiplier
    # before its residual add; attention scores use attn_scale_override as
    # a DIRECT multiplier (not a head_dim power); logits divide by
    # logits_divider.
    embed_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    attn_scale_override: Optional[float] = None
    logits_divider: Optional[float] = None
    # Gemma-2 sandwich norms: post-attention and post-FFN RMSNorms applied
    # to each branch output before its residual add
    post_norms: bool = False
    # Gemma-2 logit softcapping: x -> cap * tanh(x / cap)
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    # Gemma-2 query_pre_attn_scalar: attention scores scale by its -0.5
    # power instead of head_dim**-0.5 (None = head_dim**-0.5)
    query_scale_override: Optional[float] = None
    # Biases on the q/k/v projections (Qwen2-style; llama family only —
    # gpt2 always has full biases).
    attn_qkv_bias: bool = False
    # Qwen3: per-head RMSNorm on q and k (weight [head_dim]) before RoPE
    use_qk_norm: bool = False
    # qk-norm granularity: "head" (weight [head_dim], Qwen3/Gemma-3) or
    # "proj" (weight [H*Dh] / [KV*Dh] over the whole projection, OLMo-2)
    qk_norm_dim: str = "head"
    # OLMo-2: NO pre-sublayer norms — the residual adds norm(sublayer(x))
    # (post_norms carries the norms; pre_norms=False skips the input ones)
    pre_norms: bool = True
    # MoE router: renormalize the top-k probabilities to sum 1 (Mixtral
    # always does; Qwen3-MoE gates it on norm_topk_prob)
    moe_renormalize: bool = True
    # Sparse mixture-of-experts FFN (Mixtral-style): n_experts == 0 means a
    # dense SwiGLU MLP; > 0 replaces it with a top-k routed expert bank
    # (models/llama.moe_ffn). Expert weights stack an E axis and shard
    # over the `ep` mesh axis.
    n_experts: int = 0
    n_experts_per_tok: int = 2
    tie_embeddings: bool = False
    # Latent attention + routed experts (arch "mla_moe", models/mla_moe.py:
    # the DeepSeek-V3 block as kanana-2-30b-a3b publishes it). The cache
    # holds one row [c | k_r] of kv_lora_rank + qk_rope_head_dim numbers
    # a token and layer; attention reads it in absorbed form. head_dim is
    # the published one (the rope part); n_kv_heads the published count,
    # which the latent cache does not use. ffn_dim is the width of the
    # first_k_dense leading dense layers.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # n_experts routed experts of width moe_ffn_dim, n_experts_per_tok a
    # token, chosen by sigmoid(score) + selection bias and weighed by the
    # scores alone (renormalized under moe_renormalize, times
    # routed_scaling); n_shared_experts fused into one SwiGLU of width
    # n_shared_experts * moe_ffn_dim that every token takes.
    # A llama-family model with moe_ffn_dim > 0 takes the same routed path
    # (models/experts.py) with softmax scores and no bias: `router_score`.
    moe_ffn_dim: int = 0
    n_shared_experts: int = 0
    first_k_dense: int = 0
    routed_scaling: float = 1.0
    # How routed experts are scored (models/experts.route): "sigmoid" (the
    # n_experts_per_tok largest of score + selection bias are chosen, the
    # scores alone weigh) or "softmax" (no bias). None takes the family's:
    # sigmoid for mla_moe and lfm2, softmax for the llama family.
    router_score: Optional[str] = None
    # added to the sum of the chosen scores before they are renormalized
    # (lfm2's modelling code: 1e-6; the other routed families add nothing)
    router_norm_eps: float = 0.0
    # One chip's share of an expert-parallel deployment (HOLDS_EXPERT_SHARE:
    # arch "afmoe", "mimo_v2"): the
    # router stays n_experts wide and this chip holds published experts
    # expert_lo .. expert_lo + n_experts_held - 1 (0: all of them). A pair
    # routed to an expert held elsewhere is left out, in the program and in
    # the reference alike (models/experts.routed_ffn); nothing stands in for
    # the other chips or their exchange.
    expert_lo: int = 0
    n_experts_held: int = 0
    # Gated short convolutions beside attention (arch "lfm2",
    # models/lfm2.py: LFM2-24B-A2B). layer_types names each layer's
    # operator, "conv" or "full_attention"; a conv layer keeps the last
    # conv_kernel - 1 rows of its gated input as recurrent state, a row
    # and layer (engine/paged.py: a live leaf a slot, a tail a block), and
    # only the attention layers own K/V. The first first_k_dense layers
    # carry a dense SwiGLU of ffn_dim, the others routed experts.
    # Arch "afmoe" (models/afmoe.py: Trinity) names each layer
    # "sliding_attention" (reads the last attn_window positions, takes RoPE)
    # or "full_attention" (reads everything, takes no position encoding);
    # every layer owns K/V, and the paged pool keeps the two kinds in two
    # groups of blocks (engine/paged.py: `kv_groups`).
    # Arch "minicpm_sala" (models/minicpm_sala.py: MiniCPM-SALA) names each
    # layer "minicpm4" (GQA without rotary whose queries past sparse_dense_len
    # positions read only the sparse_topk blocks of sparse_block tokens that a
    # score over mean-pooled keys selects; owns K/V and the compressed keys)
    # or "lightning-attn" (decayed linear attention over linear_heads heads:
    # a float32 matrix state a row and layer, no K/V).
    # Arch "granite_hybrid" (models/granite_hybrid.py: granite-4.0-h) names
    # each layer "mamba" (a Mamba-2 state-space mixer: a causal depthwise
    # convolution of conv_kernel taps, with a bias under conv_bias, over
    # [x | B | C], then a scan over ssm_heads heads of ssm_head_dim whose
    # decay every token sets for itself; keeps the convolution's last
    # conv_kernel - 1 inputs AND a float32 matrix state of ssm_heads x
    # ssm_head_dim x ssm_state numbers a row, no K/V) or "attention" (GQA
    # with no position encoding at attn_scale_override; owns K/V).
    # Arch "mimo_v2" (models/mimo_v2.py: MiMo-V2.5) names its layers as
    # "afmoe" does, and the two kinds differ in more than the window: a
    # sliding layer has window_kv_heads K/V heads (0: n_kv_heads, as a global
    # one), rotates by rope_local_theta (a global one by rope_theta) and,
    # under window_sink, adds one learned logit a query head to its softmax's
    # denominator (it takes probability and gives no value). Both kinds: keys
    # and queries head_dim wide and values v_head_dim (0: head_dim), the
    # rotation on the first rotary_dim lanes of a head (0: all of them), the
    # values times attn_value_scale.
    # Arch "solar_open2" (models/solar_open2.py: Solar-Open2) names each layer
    # "kda" (Kimi Delta Attention: linear_heads heads of head_dim whose q, k
    # and v each pass a causal depthwise convolution of conv_kernel taps, a
    # float32 matrix state folded by the gated delta rule with a decay a key
    # channel and, under delta_neg_eigval, beta in (0, 2); keeps the three
    # convolutions' last inputs AND the matrix state a row, no K/V) or
    # "full_attention" (gated GQA with no position encoding; owns K/V), every
    # layer over routed experts beside a shared one.
    layer_types: Optional[tuple] = None
    window_kv_heads: int = 0
    window_sink: bool = False
    rotary_dim: int = 0
    attn_value_scale: float = 1.0
    conv_kernel: int = 0
    linear_heads: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    conv_bias: bool = False
    delta_neg_eigval: bool = False
    # the selection's constants (the family's published sparse_config): a
    # compressed key is the mean of sparse_kernel keys, one every
    # sparse_stride tokens; block 0.. sparse_init_blocks - 1 and the blocks
    # of the last sparse_window positions are always read
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_window: int = 2048
    sparse_init_blocks: int = 1
    sparse_dense_len: int = 8192
    # Generation by block diffusion (SDAR): 0 = autoregressive. > 0: the
    # sequence is cut into blocks of this many tokens at absolute positions;
    # a token attends every position up to the END of its own block; a
    # forward carries a row's whole open block (mask_token_id where not yet
    # revealed), reads each masked position's token from the logits AT that
    # position, and the block's K/V counts only once no mask is left
    # (engine/paged.diffusion_step). Llama family, paged fleet only.
    diffusion_block: int = 0
    mask_token_id: Optional[int] = None
    # GPT-2 only: learned absolute position embeddings.
    use_learned_pos: bool = False
    dtype: str = "float32"  # parameter / activation dtype: "float32" | "bfloat16"
    # Three quantization knobs, one per byte stream (the first two live
    # here; the third is a TRANSPORT property, so it lives on
    # EngineConfig.pp_wire_quant beside the other engine-level levers):
    #   quant         — weight HBM bytes (the batch-1 decode bound)
    #   kv_quant      — KV-cache HBM bytes (the context/slot-count bound)
    #   pp_wire_quant — inter-stage ICI bytes (the deep-pipeline bound)
    # Weight-only quantization of the matmul weights (ops/quant.py):
    # None | "int8" | "int4". int8 halves decode's HBM bytes/token;
    # int4 halves them again (packed nibbles, group-wise scales).
    # Neither is measured on the serving path. Both families; works on the single device AND
    # the SPMD mesh backends (quantized leaves shard like their weights).
    quant: Optional[str] = None
    # KV-CACHE quantization (ops/kv_quant.py): "int8" stores K/V as int8
    # with per-(token, head) fp32 scales — half the cache HBM, 2x the
    # slots/context at the same budget. Both families via the shared
    # attn_hook seam, on EVERY topology — single device, pp/tp/dp
    # pipeline meshes, the 1F1B schedule (per-leaf cache specs +
    # tree-aware row slicing), and sp rings (the ring/cp hooks quantize
    # on write and rotate int8 chunks + scales over ICI). Composes with
    # the prefix KV cache (snapshots carry the scales), the paged block
    # pool (int8 blocks + scale blocks), warm recovery (shadowed KVQuant
    # leaves), and attn_impl="pallas" (the flash/paged kernels
    # dequantize int8 tiles/blocks in their prologues).
    kv_quant: Optional[str] = None
    # Attention implementation: "xla" (einsum + full mask, fused by XLA) or
    # "pallas" (flash kernel, ops/flash_attention.py; interpret-mode on CPU).
    attn_impl: str = "xla"
    eos_token_id: int = 2
    bos_token_id: int = 1
    pad_token_id: int = 0
    # Additional stop tokens beyond eos_token_id (e.g. Gemma-it's
    # <end_of_turn> id 107 — instruct checkpoints end their turn with it
    # and rarely emit <eos> mid-chat). Every decode loop stops on any of
    # them; the comparison unrolls statically (the tuple is tiny).
    stop_token_ids: tuple = ()
    # Chat prompt template (engine/chat.py): None derives from arch
    # (llama -> "tinyllama" Zephyr format, gpt2 -> passthrough);
    # "gemma" = <start_of_turn> turns.
    chat_template: Optional[str] = None

    def __post_init__(self):
        if self.router_score is None:
            object.__setattr__(
                self, "router_score",
                "sigmoid" if self.arch in ("mla_moe", "lfm2", "afmoe",
                                           "mimo_v2", "solar_open2")
                else "softmax",
            )
        if self.router_score not in ("sigmoid", "softmax"):
            raise ValueError(
                f"router_score must be 'sigmoid' or 'softmax', got "
                f"{self.router_score!r}"
            )
        if self.layer_types is not None:  # (a JSON override brings a list)
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.attn_impl not in ("xla", "pallas"):
            raise ValueError(f"attn_impl must be 'xla' or 'pallas', got {self.attn_impl!r}")
        if self.act not in ("silu", "gelu_tanh"):
            raise ValueError(f"act must be 'silu' or 'gelu_tanh', got {self.act!r}")
        # "hf": render chat through the serving tokenizer's own jinja
        # template (requires an HF tokenizer with one; the engine checks)
        if self.chat_template not in (None, "tinyllama", "gemma", "phi3",
                                      "none", "hf"):
            raise ValueError(
                f"chat_template must be None, 'tinyllama', 'gemma', 'phi3', "
                f"'none', or 'hf', got {self.chat_template!r}"
            )
        if self.qk_norm_dim not in ("head", "proj"):
            raise ValueError(
                f"qk_norm_dim must be 'head' or 'proj', got "
                f"{self.qk_norm_dim!r}"
            )
        if not self.pre_norms and not self.post_norms:
            raise ValueError(
                "pre_norms=False needs post_norms=True (a block with no "
                "norms at all matches no supported architecture)"
            )
        if self.attn_window_pattern not in ("all", "even"):
            raise ValueError(
                f"attn_window_pattern must be 'all' or 'even', got "
                f"{self.attn_window_pattern!r}"
            )
        # attn_impl='pallas' is legal for every attention variant now:
        # BOTH kernels (the chunk flash kernel, ops/flash_attention.py,
        # and the paged decode kernel, ops/paged_attention.py) take
        # softcap and scale overrides as static params and per-layer
        # window patterns as a traced scalar-prefetch width.
        if self.quant not in (None, "int8", "int4"):
            raise ValueError(
                f"quant must be None, 'int8', or 'int4', got {self.quant!r}"
            )
        if self.kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be None or 'int8', got {self.kv_quant!r}"
            )
        # kv_quant rides the shared attn_hook seam (models/llama.
        # default_attn_hook), which BOTH families route through now —
        # gpt2's block adopted the hook in round 5, so the int8 cache
        # (and the paged pool) apply to it unchanged.
        if self.rope_scaling not in (None, "llama3", "linear"):
            raise ValueError(
                f"rope_scaling must be None, 'llama3', or 'linear', got "
                f"{self.rope_scaling!r}"
            )
        if self.attn_window_layer_types is not None:
            if len(self.attn_window_layer_types) != self.n_layers:
                raise ValueError(
                    f"attn_window_layer_types has "
                    f"{len(self.attn_window_layer_types)} entries for "
                    f"{self.n_layers} layers"
                )
            if self.attn_window is None:
                raise ValueError(
                    "attn_window_layer_types needs attn_window set"
                )
        if self.rope_local_theta is not None and (
            self.attn_window is None
            or (self.attn_window_pattern == "all"
                and self.attn_window_layer_types is None
                and not set(self.layer_types or ()) & KEEPS_WINDOW_KV)
        ):
            raise ValueError(
                "rope_local_theta needs a per-layer window pattern "
                "(attn_window_layer_types or attn_window_pattern='even') — "
                "with one table per layer kind there must be two kinds"
            )
        if self.arch == "gpt2" and self.n_kv_heads != self.n_heads:
            raise ValueError(
                f"gpt2 is MHA: n_kv_heads ({self.n_kv_heads}) must equal "
                f"n_heads ({self.n_heads})"
            )
        if self.n_heads % self.n_kv_heads != 0:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be divisible by n_kv_heads "
                f"({self.n_kv_heads})"
            )
        if self.arch == "mla_moe":
            if min(self.kv_lora_rank, self.qk_nope_head_dim,
                   self.qk_rope_head_dim, self.v_head_dim) < 1:
                raise ValueError(
                    "arch 'mla_moe' needs kv_lora_rank, qk_nope_head_dim, "
                    "qk_rope_head_dim and v_head_dim"
                )
            if not (self.n_experts and self.moe_ffn_dim
                    and 0 <= self.first_k_dense < self.n_layers):
                raise ValueError(
                    "arch 'mla_moe' needs n_experts, moe_ffn_dim and "
                    "first_k_dense < n_layers (an expert stack)"
                )
        if self.arch == "lfm2":
            kinds = self.layer_types or ()
            if (len(kinds) != self.n_layers or "full_attention" not in kinds
                    or set(kinds) - {"conv", "full_attention"}):
                raise ValueError(
                    f"arch 'lfm2' needs layer_types: n_layers "
                    f"({self.n_layers}) entries of 'conv' / "
                    f"'full_attention', at least one of them attention; "
                    f"got {kinds!r}"
                )
            if self.conv_kernel < 2:
                raise ValueError("arch 'lfm2' needs conv_kernel >= 2")
            if not (self.n_experts and self.moe_ffn_dim
                    and 0 <= self.first_k_dense < self.n_layers):
                raise ValueError(
                    "arch 'lfm2' needs n_experts, moe_ffn_dim and "
                    "first_k_dense < n_layers (an expert stack)"
                )
        elif self.arch in ("afmoe", "mimo_v2"):
            kinds = self.layer_types or ()
            if (len(kinds) != self.n_layers
                    or set(kinds) - {"sliding_attention", "full_attention"}):
                raise ValueError(
                    f"arch {self.arch!r} needs layer_types: n_layers "
                    f"({self.n_layers}) entries of 'sliding_attention' / "
                    f"'full_attention'; got {kinds!r}"
                )
            if not (self.n_experts and self.moe_ffn_dim
                    and 0 <= self.first_k_dense < self.n_layers):
                raise ValueError(
                    f"arch {self.arch!r} needs n_experts, moe_ffn_dim and "
                    f"first_k_dense < n_layers (an expert stack)"
                )
            if self.conv_kernel:
                raise ValueError("conv_kernel is arch 'lfm2' only")
            if self.arch == "mimo_v2" and "full_attention" not in kinds:
                # (a pool of one group takes the global kind's K/V heads)
                raise ValueError(
                    "arch 'mimo_v2' needs at least one 'full_attention' "
                    "layer")
        elif self.arch == "minicpm_sala":
            kinds = self.layer_types or ()
            # (at least one of EACH: the family's pool, host position model
            # and start-up refusals are keyed on `linear_layers`)
            if (len(kinds) != self.n_layers
                    or set(kinds) != {"minicpm4", "lightning-attn"}):
                raise ValueError(
                    f"arch 'minicpm_sala' needs layer_types: n_layers "
                    f"({self.n_layers}) entries of 'minicpm4' / "
                    f"'lightning-attn', at least one of each; "
                    f"got {kinds!r}"
                )
            if self.linear_heads < 1:
                raise ValueError("arch 'minicpm_sala' needs linear_heads")
            k, st, blk = (self.sparse_kernel, self.sparse_stride,
                          self.sparse_block)
            if not (0 < st <= k <= blk and k % st == 0 and blk % st == 0
                    and self.sparse_window % blk == 0
                    and self.sparse_topk >= self.sparse_init_blocks
                    + self.sparse_window // blk + 1):
                raise ValueError(
                    f"arch 'minicpm_sala': the selection needs stride | "
                    f"kernel <= block, stride | block | window and topk "
                    f"above the forced blocks; got kernel {k}, stride {st}, "
                    f"block {blk}, window {self.sparse_window}, topk "
                    f"{self.sparse_topk}")
            if self.conv_kernel:
                raise ValueError("conv_kernel is arch 'lfm2' only")
        elif self.arch == "granite_hybrid":
            kinds = self.layer_types or ()
            if (len(kinds) != self.n_layers
                    or set(kinds) != {"mamba", "attention"}):
                raise ValueError(
                    f"arch 'granite_hybrid' needs layer_types: n_layers "
                    f"({self.n_layers}) entries of 'mamba' / 'attention', "
                    f"at least one of each; got {kinds!r}")
            if min(self.ssm_heads, self.ssm_head_dim, self.ssm_state) < 1 \
                    or self.conv_kernel < 2:
                raise ValueError(
                    "arch 'granite_hybrid' needs ssm_heads, ssm_head_dim, "
                    "ssm_state and conv_kernel >= 2")
            if self.ssm_groups != 1:
                raise ValueError(
                    f"arch 'granite_hybrid': the scan carries ONE group of "
                    f"B and C for all heads (ops/ssm_scan.py); got "
                    f"ssm_groups {self.ssm_groups}")
        elif self.arch == "solar_open2":
            kinds = self.layer_types or ()
            if (len(kinds) != self.n_layers
                    or set(kinds) != {"kda", "full_attention"}):
                raise ValueError(
                    f"arch 'solar_open2' needs layer_types: n_layers "
                    f"({self.n_layers}) entries of 'kda' / 'full_attention', "
                    f"at least one of each; got {kinds!r}")
            if self.linear_heads < 1 or self.conv_kernel < 2:
                raise ValueError(
                    "arch 'solar_open2' needs linear_heads and conv_kernel "
                    ">= 2")
            if not (self.n_experts and self.moe_ffn_dim
                    and self.first_k_dense == 0):
                raise ValueError(
                    "arch 'solar_open2' needs n_experts, moe_ffn_dim and "
                    "first_k_dense 0 (every layer routes)")
        elif self.layer_types is not None or self.conv_kernel:
            raise ValueError(
                "layer_types is arch 'lfm2' / 'afmoe' / 'mimo_v2' / "
                "'minicpm_sala' / 'granite_hybrid' / 'solar_open2' only, "
                "conv_kernel arch 'lfm2' / 'granite_hybrid' / 'solar_open2' "
                "only")
        if self.arch != "mimo_v2" and (
                self.window_kv_heads or self.window_sink or self.rotary_dim
                or self.attn_value_scale != 1.0):
            raise ValueError(
                "window_kv_heads, window_sink, rotary_dim and "
                "attn_value_scale are arch 'mimo_v2' only")
        if self.window_kv_heads and self.n_heads % self.window_kv_heads:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be divisible by "
                f"window_kv_heads ({self.window_kv_heads})")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(
                f"rotary_dim ({self.rotary_dim}) must be even and at most "
                f"head_dim ({self.head_dim})")
        if self.linear_heads and self.arch not in ("minicpm_sala",
                                                   "solar_open2"):
            raise ValueError(
                "linear_heads is arch 'minicpm_sala' / 'solar_open2' only")
        if self.delta_neg_eigval and self.arch != "solar_open2":
            raise ValueError("delta_neg_eigval is arch 'solar_open2' only")
        if self.arch != "granite_hybrid" and (
                self.ssm_heads or self.ssm_head_dim or self.ssm_state
                or self.conv_bias):
            raise ValueError("ssm_heads, ssm_head_dim, ssm_state and "
                             "conv_bias are arch 'granite_hybrid' only")
        if self.expert_lo or self.n_experts_held:
            if self.arch not in HOLDS_EXPERT_SHARE:
                raise ValueError(
                    "an expert share (expert_lo, n_experts_held) is for the "
                    "families whose routed layer is told what it holds: "
                    + " / ".join(sorted(HOLDS_EXPERT_SHARE)))
            if not (0 <= self.expert_lo
                    and self.expert_lo + self.experts_held <= self.n_experts):
                raise ValueError(
                    f"experts {self.expert_lo} .. {self.expert_lo} + "
                    f"{self.experts_held} are not all of the router's "
                    f"{self.n_experts}")
        if self.diffusion_block:
            if self.arch != "llama" or self.mask_token_id is None:
                raise ValueError(
                    "diffusion_block > 0 needs the llama family and a "
                    "mask_token_id"
                )
            if not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError("mask_token_id is outside the vocabulary")
        if self.moe_ffn_dim and not self.n_experts:
            raise ValueError("moe_ffn_dim > 0 needs n_experts > 0")
        if self.n_experts:
            if self.arch not in ("llama", "mla_moe", "lfm2", "afmoe",
                                 "mimo_v2", "solar_open2"):
                raise ValueError("MoE (n_experts > 0) is llama-family only")
            if not 1 <= self.n_experts_per_tok <= self.n_experts:
                raise ValueError(
                    f"n_experts_per_tok ({self.n_experts_per_tok}) must be in "
                    f"[1, n_experts={self.n_experts}]"
                )

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.dim // self.n_heads

    def _layers_of(self, kinds) -> tuple:
        return tuple(i for i, kind in enumerate(self.layer_types or ())
                     if kind in kinds)

    @property
    def conv_layers(self) -> tuple:
        """The layers that KEEP a convolution state a row, the last
        conv_kernel - 1 inputs of a causal convolution (their index in the
        stack): gated short convolutions, state-space mixers."""
        return self._layers_of(KEEPS_CONV_STATE)

    @property
    def linear_layers(self) -> tuple:
        """The layers that KEEP a float32 matrix state a row (their index in
        the stack): decayed linear attention, state-space mixers. Such a
        state is too large to keep one a pool block: `state_tails`."""
        return self._layers_of(KEEPS_MATRIX_STATE)

    @property
    def sparse_layers(self) -> tuple:
        """The attention layers whose queries read a SELECTED set of blocks
        and keep compressed keys for the selection beside their K/V."""
        return self._layers_of(SELECTS_BLOCKS)

    @property
    def recurrent(self) -> bool:
        """The model keeps a state a row beside (or in place of) K/V."""
        return bool(self.conv_layers or self.linear_layers)

    @property
    def state_tails(self) -> bool:
        """The pool keeps a row's state at the END of every block (a tail a
        block, which a prefix hit restores): a convolution state alone is
        small enough. A model with a matrix state keeps a few snapshots of
        ALL its states instead (engine/block_prefix.py)."""
        return bool(self.conv_layers) and not self.linear_layers

    def _kept(self, which: int):
        """What the stack's recurrent kind keeps (STATE_OF_KIND's column
        `which`), or None where no kind of the stack keeps one."""
        for kind in dict.fromkeys(self.layer_types or ()):
            kept = STATE_OF_KIND.get(kind, (None, None))[which]
            if kept is not None:
                return kept(self)
        return None

    @property
    def conv_channels(self) -> int:
        """Channels of a kept convolution state (its last conv_kernel - 1
        inputs), by the kind that keeps it (STATE_OF_KIND)."""
        return self._kept(0) or self.dim

    @property
    def matrix_state_shape(self) -> tuple:
        """A row's float32 matrix state in one layer, as the pool's leaf
        holds it, by the kind that keeps it (STATE_OF_KIND)."""
        return self._kept(1) or (
            self.linear_heads, self.head_dim, self.head_dim)

    @property
    def delta_layers(self) -> tuple:
        """The layers whose matrix state the delta rule folds chunk by chunk
        (their index in the stack)."""
        return self._layers_of(FOLDS_BY_DELTA_RULE)

    @property
    def attn_layers(self) -> tuple:
        """The layers that own K/V: every layer, or those of a kind that
        keeps K/V where layer_types tells kinds that do from kinds that do
        not."""
        if self.layer_types is None:
            return tuple(range(self.n_layers))
        return self._layers_of(KEEPS_KV | KEEPS_WINDOW_KV)

    @property
    def experts_held(self) -> int:
        """Routed experts whose banks live here (n_experts: no share)."""
        return self.n_experts_held or self.n_experts

    @property
    def kv_groups(self) -> tuple:
        """The paged pool's groups of K/V layers, each with its own blocks
        and block table (engine/paged.py): ("global",) for a model whose
        layers all keep their whole context (a uniform window is masked,
        never given back), ("global", "window") for a stack that has layers
        of a kind that keeps its whole context (KEEPS_KV) AND of a kind
        that keeps its last attn_window positions (KEEPS_WINDOW_KV), where
        a window layer gives back the blocks it can no longer read."""
        if (self.attn_window and self._layers_of(KEEPS_KV)
                and self._layers_of(KEEPS_WINDOW_KV)):
            return ("global", "window")
        return ("global",)

    def group_layers(self, group: str) -> tuple:
        """The layers (indices in the stack) whose K/V the pool's `group`
        holds: with two groups the full-attention layers / the sliding
        ones, else every layer that owns K/V."""
        if len(self.kv_groups) == 1:
            return self.attn_layers
        return self._layers_of(
            KEEPS_KV if group == "global" else KEEPS_WINDOW_KV)

    @property
    def kinds_of_attention(self) -> bool:
        """layer_types tells window layers from global ones and nothing
        else: every layer owns K/V and none keeps a state (the pool's
        leaves by group: engine/paged.init_pool)."""
        return (self.layer_types is not None and not self.conv_kernel
                and not set(self.layer_types)
                - {"sliding_attention", "full_attention"})

    def group_kv_heads(self, group: str) -> int:
        """K/V heads of the layers in the pool's `group`."""
        if group == "window" and self.window_kv_heads:
            return self.window_kv_heads
        return self.n_kv_heads

    def group_rope_theta(self, group: str) -> float:
        """The rotation's base on the layers of `group`."""
        if group == "window" and self.rope_local_theta is not None:
            return self.rope_local_theta
        return self.rope_theta

    @property
    def value_dim(self) -> int:
        """Numbers of a value head (a per-head K/V cache; keys and queries
        are head_dim wide)."""
        return self.head_dim if self.latent_dim else (
            self.v_head_dim or self.head_dim)

    @property
    def key_row(self) -> int:
        """A key head as the paged pool stores it: a head wider than one
        128-lane tile on whole tiles, zero pad (192 -> 256: the device
        tiles the minor dimension by 128 lanes whatever the array says, so
        the pad costs no byte the unpadded leaf would not, and the kernels
        then copy and write whole tiles, ops/paged_attention.
        writes_in_place); a narrower one as it is."""
        Dh = self.head_dim
        return -(-Dh // 128) * 128 if Dh > 128 else Dh

    @property
    def kv_pack(self) -> int:
        """K/V heads the paged pool stores side by side on one 128-lane
        row (head dim 64: 2), so that the paged kernels write in place
        (ops/paged_attention.writes_in_place); 1: a head a row. The
        families that keep a state a row beside K/V pack (their attention
        goes through models/stack.pack_heads), but for a selected read,
        whose scoring reads a key head a row."""
        if not self.recurrent or self.sparse_layers or 128 % self.head_dim:
            return 1
        pack = 128 // self.head_dim
        return pack if self.n_kv_heads % pack == 0 else 1

    @property
    def latent_dim(self) -> int:
        """Numbers of one latent cache row [c | k_r] (0: a per-head K/V
        cache)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The row as the pool stores it: whole 128-lane tiles, zero pad
        (a manual DMA cannot cut a tile, ops/paged_attention._lanes)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def all_stop_ids(self) -> tuple:
        """eos + extra stop tokens, for host-side stop checks."""
        return (self.eos_token_id,) + tuple(self.stop_token_ids)

    @property
    def query_scale(self) -> float:
        """Attention score scale (Gemma-2 overrides head_dim**-0.5 with
        query_pre_attn_scalar**-0.5; Granite's attention_multiplier is a
        direct multiplier)."""
        if self.attn_scale_override is not None:
            return float(self.attn_scale_override)
        base = self.query_scale_override or (
            self.qk_nope_head_dim + self.qk_rope_head_dim
            if self.arch == "mla_moe" else self.head_dim
        )
        return float(base) ** -0.5

    @property
    def jnp_dtype(self):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[self.dtype]

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Shape of the device mesh. Axes: data, pipeline, sequence, tensor.

    The reference's topology (orchestrator + 2 HTTP workers) maps to
    pp_stages=2; here any (dp, pp, sp, tp) factorization of the available
    devices is valid as long as pp <= n_layers (uneven splits are padded
    with zero no-op layers), n_kv_heads % tp == 0,
    and (for sp > 1) the prefill bucket % sp == 0. sp is the long-context
    axis: ring-attention prefill + context-parallel KV-cache decode
    (parallel/ring.py, parallel/context.py).
    """

    dp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    # expert parallelism: shards the MoE expert bank (ModelConfig.n_experts
    # % ep == 0); every device computes its local experts for all tokens
    # and a psum combines — the small-batch inference EP pattern.
    ep: int = 1

    @property
    def n_devices(self) -> int:
        return self.dp * self.pp * self.sp * self.tp * self.ep

    @property
    def is_trivial(self) -> bool:
        """True when every axis is 1 — the single-device topology.
        Backend selection (runtime.create_backend) keys off this instead
        of re-enumerating the axes, so a new axis cannot drift past it."""
        return self.n_devices == 1


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Per-request sampling parameters.

    Defaults mirror the reference's /generate route
    (/root/reference/orchestration.py:339-354): temperature 0.7,
    top_k 50, top_p 0.9, max_tokens default 20.
    """

    temperature: float = 0.7
    top_k: int = 50
    top_p: float = 0.9
    max_new_tokens: int = 20
    greedy: bool = False
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Decode-engine settings."""

    max_seq_len: int = 2048
    # Prompt-length buckets for prefill compilation (TTFT: avoids recompiling
    # per prompt length; prompts are right-padded up to the bucket).
    prefill_buckets: tuple = (64, 128, 256, 512, 1024, 2048)
    # Per-request wall-clock deadline in seconds (None = unlimited). The
    # reference enforces 30s per stage hop (orchestration.py:118,131);
    # here a whole request that exceeds the deadline gets a timeout error
    # envelope and the engine keeps serving (the wedged device call is
    # abandoned to a daemon thread; the engine lock frees when it dies).
    request_deadline_s: Optional[float] = None
    # Prefix KV cache (engine/prefix.py): number of chunk-aligned prompt-
    # prefix snapshots kept on device (0 = disabled). Requests whose
    # prompt starts with a stored prefix splice its KV back and prefill
    # only the tail — TTFT scales with the new tokens, not the prompt.
    prefix_cache_entries: int = 0
    # Snapshot alignment: prefixes are stored at multiples of this length.
    prefix_chunk: int = 64
    # Grammar-constraint compiled-artifact LRU (constrain/): how many
    # distinct constraints keep their (mask, transition) tables — host
    # numpy + warm device copies — cached per engine. A resident artifact
    # costs ~num_states x vocab x 5 bytes; eviction only costs a
    # recompile (host-side, milliseconds-to-seconds), never correctness.
    constraint_cache_entries: int = 16
    # State-row capacity of the continuous fleet's COMBINED constraint
    # table (constrain/fleet.py): constraints whose DFA cannot ever fit
    # run on the solo engine instead; admission backpressures while the
    # resident set transiently fills. Memory: 2 tables x capacity x vocab
    # (bool + int32).
    constraint_fleet_states: int = 1024
    # Flat-token launch width of a paged fleet's ragged ingest programs
    # (engine/paged.py + the ops/paged_attention ragged kernel: admission
    # prefills straight into the pool, no scratch cache, no prefill-bucket
    # ladder, prefix reuse at EXACT chunk depth): one compiled
    # (extend, prefill) program pair per width serves every tail length
    # (longer tails loop whole-width launches; the final launch pads with
    # dead tiles the kernel's DMA skips). Rounded up to a multiple of the
    # query tile (8).
    ragged_width: int = 64
    # States a paged fleet keeps beside its pool for the prefix index to
    # restore (a model whose recurrent state is too large to keep one a
    # block: models/minicpm_sala.py, engine/block_prefix.py). 0: two a slot.
    state_snapshots: int = 0
    # SLO-aware chunked-prefill scheduler (engine/scheduler.py): ragged
    # paged fleets stop prefilling an admission whole before it joins the
    # decode fleet — each scheduler step assembles ONE mixed ragged launch
    # of every active DECODE row plus PREFILL chunks of pending
    # admissions, sliced to the per-step flat-token budget below, so a
    # long prompt never stalls the decoding requests' TPOT. False (or a
    # non-ragged fleet) falls back to admit-then-prefill-whole.
    chunked_prefill: bool = True
    # Per-step flat-token budget of the mixed launch. None (the default)
    # derives it from what the model streams a step
    # (engine/scheduler.step_width, the one place a width is decided):
    # 128 for a dense model, whose launch stops being weight-bound at
    # 240 flat tokens on a v5e, so a wider one would only add arithmetic
    # to every decode row's step; 512 for a model whose routed layers
    # stream four or more experts for each one a token computes, where a
    # step costs the bytes of the whole bank whatever it carries and a
    # document's prefill is one pass over the bank a chunk; where a full
    # fleet's decode tiles would take a third or more of that launch, the
    # fleet's tiles go on top of it and the model computes the live tokens
    # packed on the budget's axis (engine/scheduler.live_width). An explicit
    # value is obeyed as it is. Either is rounded up to a whole number of query
    # tiles, and to at least one prefill tile above the decode fleet —
    # every active slot's decode row is reserved ahead of any prefill
    # chunk, so decode can never be starved by prefill and at least one
    # pending prefill always progresses.
    step_token_budget: Optional[int] = None
    # SLO classes: (name, ttft_target_s, tpot_target_s, weight,
    # sheddable). The scheduler apportions the per-step prefill budget
    # across classes by weight x urgency (urgency = queue head wait over
    # the class TTFT target, fed back from the request timing samples),
    # and admission sheds a sheddable class's request with a 429 when its
    # class-local queue drain estimate already overruns the TTFT target
    # (Retry-After derived from THAT class's drain estimate, never the
    # global queue depth). Non-sheddable classes only queue.
    slo_classes: tuple = (
        ("interactive", 0.5, 0.1, 4.0, True),
        ("standard", 2.0, 0.5, 2.0, True),
        ("batch", 30.0, 2.0, 1.0, False),
    )
    # Class assigned when a request carries no slo_class field.
    slo_default_class: str = "standard"
    # Warm-state recovery (engine/shadow.py): host-side crash-consistent
    # shadowing of filled paged-KV blocks, so supervisor restarts
    # re-prefill only each salvaged request's partial tail block and a
    # graceful drain can persist the block-prefix cache for a warm
    # rolling restart (--restore-dir). Paged fleets with a block-prefix
    # index only (prefix_cache_entries > 0 — restore re-enters through
    # the ordinary block-prefix hit machinery); the dense fleet has no
    # immutable-block contract to shadow.
    kv_shadow: bool = True
    # Host-RAM bound of the shadow store, in blocks (LRU with cascade
    # eviction, like the block-prefix index). 0 = auto: twice the pool,
    # so a full pool's worth of warm chains survives one generation of
    # churn.
    kv_shadow_blocks: int = 0
    # Cross-replica KV fabric (serving/kv_fabric.py): serve this
    # replica's shadowed KV chains by chunk digest on GET /kv/{digest},
    # and honor the router's X-KV-Transfer-* handoff hints by pulling a
    # missing prefix from the resident peer (scattered through the
    # pre-warmed restore program) instead of re-prefilling it. Needs the
    # same stack as kv_shadow (paged fleet + block-prefix index); False
    # keeps the shadow purely local (crash recovery only).
    kv_fabric: bool = True
    # Hard deadline on one fabric fetch, end to end: a dead or wedged
    # peer costs at most this long, then admission degrades to a local
    # cold prefill (the fallback ladder never errors).
    kv_fabric_timeout_s: float = 5.0
    # Disk tier of the KV cache hierarchy (ARCHITECTURE.md "Tiered KV"):
    # a directory of persisted parent-chained chunk files
    # (chunk_<digest>.npz) that LRU-evicted host-shadow entries DEMOTE
    # into instead of dropping, and every shadow read surface
    # (block-prefix restore planning, warm recovery, preemption swap,
    # the fabric) PROMOTES hits back out of — bounding the replica's
    # logical prefix cache by disk, not HBM. None (the default)
    # disables tier 2: eviction drops, as before.
    kv_disk_dir: Optional[str] = None
    # Disk-tier bound, in blocks (chunk files; LRU with the same
    # cascade discipline as the host tier). 0 = auto: 8x the host
    # tier, so the logical cache is an order of magnitude deeper than
    # host DRAM before files churn.
    kv_disk_blocks: int = 0
    # Streamed fabric transfer: pull peer chains chunk-at-a-time
    # (GET /kv/{digest}?stream=1 — length-prefixed single-block frames,
    # per-chunk digest recheck) so the importing replica overlaps the
    # network pull with its device scatters instead of buffering the
    # whole manifest first. False pins the PR-11 whole-manifest pull
    # (also the automatic fallback against pre-stream peers).
    kv_fabric_stream: bool = True
    # Cap on the digests /health advertises for router residency
    # bootstrap (MRU-first, host tier before disk): the disk tier makes
    # the full resident set unbounded, and bootstrap payloads must stay
    # O(1) however deep it grows.
    kv_health_digests: int = 64
    # Replica specialization class for prefill/decode disaggregation
    # ("prefill" | "decode" | "mixed"): the router sends fresh
    # long-prompt work to prefill-class replicas and hands the finished
    # prefix (by digest, via the fabric) to a decode-class replica for
    # the token loop. Engine-side this only labels the fabric metrics
    # and /health — specialization is routing policy, not a different
    # engine.
    replica_class: str = "mixed"
    # Speculative decoding on the ragged paged fleet (engine/continuous.py
    # + engine/paged.py spec programs): eligible greedy decode slots
    # submit a [current + K-token draft] VERIFY row instead of a 1-token
    # decode row inside the mixed scheduler launch — the ragged kernel
    # already serves arbitrary-length rows, so verifying K drafts costs
    # ~one decode step of weight streaming and accepts up to K+1 tokens.
    # Accept/reject is fully traced (match-prefix + correction token on
    # device, packed into the existing fetch — zero host syncs, one
    # compiled program for every accept pattern). Greedy acceptance is
    # bit-identical to plain decode. Decode/verify rows read their
    # q_start / per-token positions from the device-resident slot state
    # (engine/paged.DeviceMeta + apply_device_meta), so a slot with an
    # unfetched verify row is never frozen: verify rows launch back to
    # back under lag pipelining, and the scheduler sizes each slot's
    # next draft from its acceptance-rate EWMA
    # (TokenBudgetScheduler.spec_slot_k). spec_draft_len = drafted
    # tokens per verify row (0 disables the machinery entirely).
    spec_draft_len: int = 4
    # Block-diffusion models (ModelConfig.diffusion_block > 0): the
    # denoising forwards that reveal a block when a request names none
    # ("denoise_steps"); each forward reveals block / denoise_steps
    # masked positions, leftmost first, and one more forward commits the
    # clean block. 0 = the model's block length.
    denoise_steps: int = 0
    # Fleet-wide self-speculation: True speculates for EVERY eligible
    # greedy slot; False speculates only for requests that ask
    # ("speculative": true on /generate). Either way the scheduler
    # throttles drafting to 0 under decode TPOT pressure (speculation
    # accelerates idle fleets and self-disables under load), and a slot
    # whose history has no draft to offer submits a plain decode row —
    # non-repetitive streams pay nothing.
    spec_decode: bool = False
    # Draft-model speculation for the fleet (the decode_draft_speculative
    # flavor): registry name of a small same-tokenizer model whose greedy
    # chain proposes the drafts (device-side, batched over the fleet,
    # sharing the SAME block tables over its own pool leaves) instead of
    # n-gram lookup. A draft already attached via engine.set_draft()
    # takes precedence over loading this name. None = n-gram drafts.
    spec_draft_model: Optional[str] = None
    # SLO-aware KV preemption (engine/continuous.py _preempt_for): when a
    # paged admission still cannot get blocks after the evict-
    # unreferenced-chains retry, the scheduler preempts the lowest-SLO-
    # weight / youngest DECODING request instead of stalling the queue:
    #   "swap"      — push the victim's filled blocks to the host shadow
    #                 (synchronous flush through engine/shadow.py) before
    #                 releasing them, so the resume re-admission restores
    #                 the chain in one scatter and re-prefills only the
    #                 tail; a backlogged copier falls back to
    #                 drop-and-recompute (bit-identical either way);
    #   "recompute" — always drop the KV and re-prefill from the salvage
    #                 record (prompt + fetched tokens) on resume;
    #   "off"       — never preempt (pool exhaustion waits for a release,
    #                 the pre-preemption behavior).
    preempt_policy: str = "swap"
    # Livelock guard: a request preempted this many times becomes immune
    # (it keeps its blocks until completion; admission waits instead).
    max_preemptions_per_req: int = 2
    # Quantized inter-stage transfers (ops/wire_quant.py): "int8"
    # quantizes the [B, T, D] activation immediately before EVERY
    # inter-stage hand-off on an SPMD mesh and dequantizes on landing —
    # the gated microstep ring's ppermute, the 1F1B schedule's two
    # ppermute sites, the sp ring/ulysses chunk hops, and the masked
    # psum broadcasts of the final-stage [B, 1, D] window (int8 data +
    # fp32 per-token-row scales on the wire, EQuARX-style) — cutting the
    # ICI bytes that bound deeper pipelines ~4x at fp32 (~2x at bf16).
    # None (the default) is bit-identical to the unquantized wire on
    # every topology; "int8" is toleranced (greedy token-match-rate
    # gated in tests). The `wire-dtype` HLO rules machine-check that the
    # lowered collective-permutes really carry si8 when this is on.
    pp_wire_quant: Optional[str] = None
    # Paged LoRA adapter serving (engine/adapters.py): number of HBM
    # adapter pages the resident base model carries (0 disables the
    # subsystem entirely — no lora_* leaves are installed and the paged
    # programs trace without the pages operand, lowering byte-identically
    # to the pre-adapter build). Each page holds one adapter's stacked
    # A/B factors for every supported projection at `adapter_rank`; page
    # 0 is the all-zero BASE page (never written, never evicted), so
    # adapter id 0 is the base model by construction. Pages are
    # refcounted and LRU-evicted exactly like KV blocks (BlockAllocator
    # discipline): admission acquires, completion releases, eviction only
    # ever takes refcount-0 residents.
    adapter_slots: int = 0
    # Uniform rank budget of every adapter page: registered adapters of
    # LOWER rank are zero-padded to it (exact — padding contributes
    # nothing to the delta); higher rank is rejected at registration.
    adapter_rank: int = 8
    # Per-tenant prefill-budget weights, ((tenant, weight), ...): within
    # each SLO class's tile grant the chunked-prefill scheduler splits
    # across tenants by these weights (FIFO within a tenant). Unlisted
    # tenants weigh 1.0; empty = every tenant equal.
    tenant_weights: tuple = ()
    # Tenant admission quota: one tenant's queued share of the bounded
    # request queue may not exceed this fraction (beyond a small absolute
    # floor) — the over-quota tenant sheds with 429 + Retry-After before
    # other tenants starve. 1.0 disables the quota.
    tenant_max_queue_share: float = 0.5
    # Launch-level device-time attribution (utils/tracing.py +
    # serving/trace_store.py): fraction of traces whose requests get
    # per-launch dispatch→packed-fetch spans recorded host-side (launch
    # seq keyed — lag-pipelined launches attribute correctly with ZERO
    # extra device syncs; `analysis --hlo` stays clean because nothing
    # here touches compiled code). The decision is a deterministic
    # function of the trace id (tracing.sample_decision), so all
    # replicas agree per trace. 0 (the default) keeps the hot path
    # allocation-free: no profiling structure is ever created.
    trace_sample_rate: float = 0.0

    def __post_init__(self):
        if not (0.0 <= self.trace_sample_rate <= 1.0):
            raise ValueError(
                f"trace_sample_rate must be in [0, 1], got "
                f"{self.trace_sample_rate}"
            )
        if self.pp_wire_quant not in (None, "int8"):
            raise ValueError(
                f"pp_wire_quant must be None or 'int8', got "
                f"{self.pp_wire_quant!r}"
            )
        if self.kv_disk_blocks < 0:
            raise ValueError(
                f"kv_disk_blocks must be >= 0, got {self.kv_disk_blocks}"
            )
        if self.kv_health_digests < 1:
            raise ValueError(
                f"kv_health_digests must be >= 1, got "
                f"{self.kv_health_digests}"
            )
        if self.adapter_slots < 0:
            raise ValueError(
                f"adapter_slots must be >= 0, got {self.adapter_slots}"
            )
        if self.adapter_slots and self.adapter_rank < 1:
            raise ValueError(
                f"adapter_rank must be >= 1, got {self.adapter_rank}"
            )
        if not (0.0 < self.tenant_max_queue_share <= 1.0):
            raise ValueError(
                f"tenant_max_queue_share must be in (0, 1], got "
                f"{self.tenant_max_queue_share}"
            )
        for entry in self.tenant_weights:
            name, w = entry
            if not name or float(w) <= 0:
                raise ValueError(
                    f"tenant_weights entries need a name and a positive "
                    f"weight, got {entry!r}"
                )


def resolve_attn_impl(cfg: "ModelConfig", requested: Optional[str]) -> "ModelConfig":
    """Apply an --attn-impl request to a model config.

    "xla" / "pallas": explicit. "auto": pick the Pallas flash kernel
    (ops/flash_attention.py) when the session is actually on a TPU
    backend — the chunk kernel covers every attention variant now
    (softcap, scale overrides, per-layer window patterns), so legality no
    longer constrains the choice; on CPU the kernel runs in interpret
    mode, orders of magnitude slower than the XLA path, so auto never
    selects it there. None: keep the config's own setting.
    """
    if requested is None:
        return cfg
    if requested in ("xla", "pallas"):
        return cfg.replace(attn_impl=requested)
    if requested != "auto":
        raise ValueError(
            f"attn_impl request must be 'auto', 'xla', or 'pallas'; got "
            f"{requested!r}"
        )
    import jax

    if jax.default_backend() != "tpu":
        return cfg.replace(attn_impl="xla")
    # no legality guard needed: __post_init__ accepts pallas for every
    # attention variant (both kernels take softcap/scale overrides and
    # per-layer windows), so replace() cannot raise here
    return cfg.replace(attn_impl="pallas")


def stage_layer_range(n_layers: int, pp: int, stage: int) -> tuple[int, int]:
    """Contiguous layer range [start, end) owned by `stage`.

    The reference hardcodes 0-11 / 11-22 for TinyLlama's 22 layers
    (/root/reference/Worker1.py:27-28, Worker2.py:26-27); we compute a
    balanced split for ANY pp <= n_layers: the first n_layers % pp stages
    own one extra layer (22/4 -> 6,6,5,5). Stages whose share is short of
    ceil(n_layers/pp) are padded with zero no-op layers at shard time
    (parallel/partition.pad_stacked_layers) so the stacked layer axis still
    shards evenly over the pp mesh axis.
    """
    if not 1 <= pp <= n_layers:
        raise ValueError(f"pp={pp} must be in [1, n_layers={n_layers}]")
    if not 0 <= stage < pp:
        raise ValueError(f"stage={stage} out of range for pp={pp}")
    base, rem = divmod(n_layers, pp)
    start = stage * base + min(stage, rem)
    return start, start + base + (1 if stage < rem else 0)
