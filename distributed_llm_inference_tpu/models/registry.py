"""Model registry: named presets for the BASELINE.json configs.

Replaces the reference's single hardcoded MODEL_NAME
(/root/reference/orchestration.py:20). Architecture hyperparameters are
pinned here so the framework runs fully offline (random-init or converted
weights); when a HF checkpoint is available, models/convert.py produces the
params and the converted config overrides these.
"""

from __future__ import annotations

from ..config import ModelConfig

_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_model_config(name: str, **overrides) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model '{name}'; known: {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]
    return cfg.replace(**overrides) if overrides else cfg


def list_models() -> list[str]:
    return sorted(_REGISTRY)


# --- Llama family ----------------------------------------------------------
register(ModelConfig(
    name="tinyllama-1.1b", arch="llama", vocab_size=32000, dim=2048,
    n_layers=22, n_heads=32, n_kv_heads=4, ffn_dim=5632, max_seq_len=2048,
    rope_theta=10000.0, eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="llama2-7b", arch="llama", vocab_size=32000, dim=4096,
    n_layers=32, n_heads=32, n_kv_heads=32, ffn_dim=11008, max_seq_len=4096,
    rope_theta=10000.0, eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="llama2-13b", arch="llama", vocab_size=32000, dim=5120,
    n_layers=40, n_heads=40, n_kv_heads=40, ffn_dim=13824, max_seq_len=4096,
    rope_theta=10000.0, eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="llama3-8b", arch="llama", vocab_size=128256, dim=4096,
    n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14336, max_seq_len=8192,
    rope_theta=500000.0, eos_token_id=128001, bos_token_id=128000,
))
# Llama-3.1/3.2: "llama3" rope_scaling stretches the 8192-token training
# context to the checkpoints' 131072 max positions; the engine's
# EngineConfig.max_seq_len still bounds the actual KV-cache allocation.
register(ModelConfig(
    name="llama3.1-8b", arch="llama", vocab_size=128256, dim=4096,
    n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14336, max_seq_len=131072,
    rope_theta=500000.0, rope_scaling="llama3", rope_scaling_factor=8.0,
    eos_token_id=128001, bos_token_id=128000,
))
# Llama-3.1-70B: the BASELINE-class large config for pp=8/tp meshes.
# Llama-3.3-70B is the identical architecture with newer instruct data —
# derived by replace(name=...) so the equivalence holds by construction.
_l31_70b = register(ModelConfig(
    name="llama3.1-70b", arch="llama", vocab_size=128256, dim=8192,
    n_layers=80, n_heads=64, n_kv_heads=8, ffn_dim=28672, max_seq_len=131072,
    rope_theta=500000.0, rope_scaling="llama3", rope_scaling_factor=8.0,
    eos_token_id=128001, bos_token_id=128000,
))
register(_l31_70b.replace(name="llama3.3-70b"))
register(ModelConfig(
    name="llama3.2-1b", arch="llama", vocab_size=128256, dim=2048,
    n_layers=16, n_heads=32, n_kv_heads=8, ffn_dim=8192, max_seq_len=131072,
    rope_theta=500000.0, rope_scaling="llama3", rope_scaling_factor=32.0,
    tie_embeddings=True, eos_token_id=128001, bos_token_id=128000,
))
register(ModelConfig(
    name="llama3.2-3b", arch="llama", vocab_size=128256, dim=3072,
    n_layers=28, n_heads=24, n_kv_heads=8, ffn_dim=8192, max_seq_len=131072,
    rope_theta=500000.0, rope_scaling="llama3", rope_scaling_factor=32.0,
    tie_embeddings=True, eos_token_id=128001, bos_token_id=128000,
))

# --- Mistral family (llama arch + sliding-window attention) ---------------
register(ModelConfig(
    name="mistral-7b", arch="llama", vocab_size=32000, dim=4096,
    n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14336, max_seq_len=8192,
    rope_theta=10000.0, attn_window=4096, eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="mistral-7b-v0.2", arch="llama", vocab_size=32000, dim=4096,
    n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14336, max_seq_len=32768,
    rope_theta=1000000.0, eos_token_id=2, bos_token_id=1,
))

# --- Mixtral family (llama arch + sparse MoE FFN) -------------------------
register(ModelConfig(
    name="mixtral-8x7b", arch="llama", vocab_size=32000, dim=4096,
    n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14336, max_seq_len=32768,
    rope_theta=1000000.0, n_experts=8, n_experts_per_tok=2,
    eos_token_id=2, bos_token_id=1,
))

# --- Kanana-2 (arch "mla_moe": the DeepSeek-V3 block — latent attention
# without a low-rank query, sigmoid router with a selection bias, shared
# experts, one leading dense layer; kakaocorp/kanana-2-30b-a3b-instruct-2601
# config.json). head_dim is the published one (= qk_rope_head_dim). The
# published config names no special tokens: Llama-3's ids are assumed.
register(ModelConfig(
    name="kanana-2-30b-a3b", arch="mla_moe", vocab_size=128256, dim=2048,
    n_layers=48, n_heads=32, n_kv_heads=32, ffn_dim=6144, max_seq_len=32768,
    norm_eps=1e-6, rope_theta=1000000.0, head_dim_override=64,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, n_experts=128, n_experts_per_tok=6, moe_ffn_dim=768,
    n_shared_experts=2, first_k_dense=1, routed_scaling=2.448,
    moe_renormalize=True,
    eos_token_id=128001, bos_token_id=128000, pad_token_id=128001,
))

# --- Qwen2 family (llama arch + q/k/v projection biases) ------------------
_qwen2_7b = register(ModelConfig(
    name="qwen2-7b", arch="llama", vocab_size=152064, dim=3584,
    n_layers=28, n_heads=28, n_kv_heads=4, ffn_dim=18944, max_seq_len=32768,
    norm_eps=1e-6, rope_theta=1000000.0, attn_qkv_bias=True,
    eos_token_id=151645, bos_token_id=151643, pad_token_id=151643,
))
# Qwen2.5-7B: the Qwen2-7B architecture unchanged (same dims, GQA,
# qkv-bias, 1e6 theta) with refreshed training — derived, not retyped.
register(_qwen2_7b.replace(name="qwen2.5-7b"))
register(ModelConfig(
    name="qwen2-0.5b", arch="llama", vocab_size=151936, dim=896,
    n_layers=24, n_heads=14, n_kv_heads=2, ffn_dim=4864, max_seq_len=32768,
    norm_eps=1e-6, rope_theta=1000000.0, attn_qkv_bias=True,
    tie_embeddings=True,
    eos_token_id=151645, bos_token_id=151643, pad_token_id=151643,
))

# --- Qwen3 (llama arch + per-head q/k RMSNorm, explicit head_dim, no
# qkv biases) — HF transformers models/qwen3 ---
register(ModelConfig(
    name="qwen3-0.6b", arch="llama", vocab_size=151936, dim=1024,
    n_layers=28, n_heads=16, n_kv_heads=8, ffn_dim=3072, max_seq_len=40960,
    norm_eps=1e-6, rope_theta=1000000.0, head_dim_override=128,
    use_qk_norm=True, tie_embeddings=True,
    eos_token_id=151645, bos_token_id=151643, pad_token_id=151643,
))
register(ModelConfig(
    name="qwen3-30b-a3b", arch="llama", vocab_size=151936, dim=2048,
    n_layers=48, n_heads=32, n_kv_heads=4, ffn_dim=768, max_seq_len=40960,
    norm_eps=1e-6, rope_theta=1000000.0, head_dim_override=128,
    use_qk_norm=True, n_experts=128, n_experts_per_tok=8,
    moe_renormalize=True,
    eos_token_id=151645, bos_token_id=151643, pad_token_id=151643,
))
# --- SDAR (block diffusion over the Qwen3-MoE layer; JetLM/SDAR-30B-A3B-Chat
# config.json, model_type sdar_moe). qwen3-30b-a3b's widths, but: the experts
# are ROUTED (moe_ffn_dim > 0: models/experts.py, each token through its 8
# experts alone), and generation is by diffusion over blocks of 4 (engine/
# paged.diffusion_step). ffn_dim is the published intermediate_size, which
# no layer uses (mlp_only_layers is empty). Not in config.json and so
# assumed: the block length 4 and the mask token id 151669 (the SDAR
# repository's), per-head qk-norm (the family's modeling code), Qwen's
# special tokens.
register(ModelConfig(
    name="sdar-30b-a3b-chat", arch="llama", vocab_size=151936, dim=2048,
    n_layers=48, n_heads=32, n_kv_heads=4, ffn_dim=6144, max_seq_len=32768,
    norm_eps=1e-6, rope_theta=1000000.0, head_dim_override=128,
    use_qk_norm=True, n_experts=128, n_experts_per_tok=8, moe_ffn_dim=768,
    moe_renormalize=True, diffusion_block=4, mask_token_id=151669,
    eos_token_id=151645, bos_token_id=151643, pad_token_id=151643,
))
# --- LFM2 (gated short convolutions beside GQA over routed experts;
# LiquidAI/LFM2-24B-A2B config.json, model_type lfm2_moe: models/lfm2.py).
# layer_types: conv conv, then (full_attention conv conv conv) nine times,
# then full_attention conv: 30 convolution layers, 10 that own K/V. ffn_dim
# is the width of the num_dense_layers = 2 leading dense layers. Not in
# config.json and so assumed: the split order B | C | X and the gates'
# places, the 1e-6 in the router's normalisation, per-head qk-norm and the
# half-rotation (the family's modelling code), tied embeddings and the
# special tokens (the family's published configurations).
LFM2_24B_LAYER_TYPES = (
    ("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 9
    + ("full_attention", "conv")
)
register(ModelConfig(
    name="lfm2-24b-a2b", arch="lfm2", vocab_size=65536, dim=2048,
    n_layers=40, n_heads=32, n_kv_heads=8, ffn_dim=11776, max_seq_len=128000,
    norm_eps=1e-5, rope_theta=1000000.0, use_qk_norm=True,
    tie_embeddings=True, layer_types=LFM2_24B_LAYER_TYPES, conv_kernel=3,
    n_experts=64, n_experts_per_tok=4, moe_ffn_dim=1536, first_k_dense=2,
    moe_renormalize=True, routed_scaling=1.0, router_norm_eps=1e-6,
    eos_token_id=7, bos_token_id=1, pad_token_id=0,
))
# --- Trinity (gated GQA, three sliding-window layers to one global, 256
# routed experts beside a shared one; arcee-ai/Trinity-Large-Preview
# config.json, model_type afmoe: models/afmoe.py). Sliding layers take RoPE
# and read the last 4,096 positions, global layers take no position
# encoding; the first num_dense_layers = 6 layers carry a dense SwiGLU of
# ffn_dim. Not in config.json and so assumed (the family's modelling code):
# mup_enabled = the sqrt(dim) on the embedding and nothing else at
# inference, the four norms' places, the gate on the concatenated heads in
# front of W_o, the window's convention (i - j < 4096), the 1e-20 in the
# router's normalisation, per-head qk-norm before the half-rotation.
register(ModelConfig(
    name="trinity-large-preview", arch="afmoe", vocab_size=200192, dim=3072,
    n_layers=60, n_heads=48, n_kv_heads=8, ffn_dim=12288,
    max_seq_len=262144, norm_eps=1e-5, rope_theta=10000.0,
    head_dim_override=128, use_qk_norm=True, post_norms=True,
    embed_scale=True, attn_window=4096,
    layer_types=tuple(
        "full_attention" if i % 4 == 3 else "sliding_attention"
        for i in range(60)),
    n_experts=256, n_experts_per_tok=4, moe_ffn_dim=3072, n_shared_experts=1,
    first_k_dense=6, moe_renormalize=True, routed_scaling=2.448,
    router_norm_eps=1e-20, eos_token_id=2, bos_token_id=1, pad_token_id=0,
))
# --- MiMo-V2.5 (five 128-token sliding-window layers with a learned sink to
# each global layer, 256 sigmoid-routed experts and no shared one;
# XiaomiMiMo/MiMo-V2.5 config.json, model_type mimo_v2: models/mimo_v2.py).
# hybrid_layer_pattern: layer 0 global, 1-4 window, 5 global, then (window
# x 5, global) seven times; layer 0 dense, the others routed. Global layers:
# 4 K/V heads, rope_theta 1e7; window layers: 8 K/V heads, swa_rope_theta
# 1e4, a sink logit a query head; both: keys 192 wide and values 128, the
# rotation on 0.334 x 192 -> 64 lanes, values x 0.707. Not in config.json and
# so assumed: the value scale's place (on v, before the sum), the window's
# convention (i - j < 128), which 64 lanes rotate and in which form (the
# first, half-rotation), no per-head q/k norm, the 1e-20 in the router's
# normalisation; the vision and audio towers and the MTP layers are not here.
MIMO_V25_LAYER_TYPES = tuple(
    "full_attention" if i == 0 or i % 6 == 5 else "sliding_attention"
    for i in range(48))
register(ModelConfig(
    name="mimo-v2.5", arch="mimo_v2", vocab_size=152576, dim=4096,
    n_layers=48, n_heads=64, n_kv_heads=4, window_kv_heads=8, ffn_dim=16384,
    max_seq_len=1048576, norm_eps=1e-5, rope_theta=1e7,
    rope_local_theta=1e4, head_dim_override=192, v_head_dim=128,
    rotary_dim=64, attn_value_scale=0.707, attn_window=128, window_sink=True,
    layer_types=MIMO_V25_LAYER_TYPES,
    n_experts=256, n_experts_per_tok=8, moe_ffn_dim=2048, first_k_dense=1,
    moe_renormalize=True, routed_scaling=1.0, router_norm_eps=1e-20,
    eos_token_id=2, bos_token_id=1, pad_token_id=0,
))
# --- MiniCPM-SALA (sparse attention beside decayed linear attention;
# openbmb/MiniCPM-SALA config.json, model_type minicpm_sala:
# models/minicpm_sala.py). mixer_types as published: 8 "minicpm4" layers
# (InfLLM-v2 selection, GQA 32/2, no rotary, an output gate) among 24
# "lightning-attn" ones (32 heads of 128, rotary, qk-norm, output norm and
# gate). muP: scale_emb 12 on the embedding, scale_depth 1.4 / sqrt(32) on
# every residual branch, logits over hidden_size / dim_model_base = 16. Not
# in config.json and so assumed: the selection's constants (the family's
# published sparse_config, MiniCPM4: kernel 32, stride 16, block 64, top-64,
# window 2048, 1 initial block, dense below 8192), the decay slopes
# (ops/linear_attention.decay_slopes), the gates' width and the output
# norm's (over the heads side by side), the special tokens.
MINICPM_SALA_MIXERS = tuple(
    "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
    for i in range(32))
register(ModelConfig(
    name="minicpm-sala", arch="minicpm_sala", vocab_size=73448, dim=4096,
    n_layers=32, n_heads=32, n_kv_heads=2, ffn_dim=16384,
    max_seq_len=524288, norm_eps=1e-6, rope_theta=10000.0,
    head_dim_override=128, use_qk_norm=True, layer_types=MINICPM_SALA_MIXERS,
    linear_heads=32, embed_multiplier=12.0,
    residual_multiplier=1.4 / 32 ** 0.5, logits_divider=16.0,
    eos_token_id=2, bos_token_id=1, pad_token_id=0,
))
# --- granite-4.0-h-micro (Mamba-2 state-space layers beside attention
# without a position encoding; ibm-granite/granite-4.0-h-micro config.json,
# model_type granitemoehybrid, 3B dense: num_local_experts 0, every layer's
# FFN the "shared" SwiGLU of 8,192: models/granite_hybrid.py). layer_types as
# published: attention at layers 5, 15, 25 and 35 of 40, Mamba-2 elsewhere
# (64 heads of 64, state 128, one group, 4 taps with a bias, expand 2).
# position_embedding_type "nope"; Granite's four scalars. Not in config.json
# and so assumed: the special tokens.
GRANITE_H_MICRO_LAYERS = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))
register(ModelConfig(
    name="granite-4.0-h-micro", arch="granite_hybrid", vocab_size=100352,
    dim=2048, n_layers=40, n_heads=32, n_kv_heads=8, ffn_dim=8192,
    max_seq_len=131072, norm_eps=1e-5, head_dim_override=64,
    layer_types=GRANITE_H_MICRO_LAYERS, conv_kernel=4, conv_bias=True,
    ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
    embed_multiplier=12.0, residual_multiplier=0.22,
    attn_scale_override=0.015625, logits_divider=8.0, tie_embeddings=True,
    eos_token_id=2, bos_token_id=1, pad_token_id=0,
))
# --- Solar-Open2-250B (gated delta-rule layers 3:1 beside gated attention
# without a position encoding, every layer over 320 routed experts and a
# shared one; upstage/Solar-Open2-250B config.json, model_type solar_open2,
# 250B-A15B: models/solar_open2.py). gqa_layers 0, 4, ..., 44 as published;
# linear_attn_config: 64 heads of 128, 4 taps, kda_use_full_proj false,
# kda_allow_neg_eigval true. use_rope false. Not in config.json and so
# assumed (cellbench/configs/solar-open2-ep8-4l.json lists each): the
# low-rank width of the decay and gate projections (head_dim), A_log and
# dt_bias drawn as Mamba-2's, the values' head width, the float32 state, the
# gate's granularity, no q/k norm, the sigmoid router with a selection bias,
# the pre-norm order, the special tokens.
SOLAR_OPEN2_LAYERS = tuple(
    "full_attention" if i % 4 == 0 else "kda" for i in range(48))
register(ModelConfig(
    name="solar-open2-250b", arch="solar_open2", vocab_size=196608, dim=4096,
    n_layers=48, n_heads=64, n_kv_heads=8, ffn_dim=10240,
    max_seq_len=1048576, norm_eps=1e-5, head_dim_override=128,
    layer_types=SOLAR_OPEN2_LAYERS, linear_heads=64, conv_kernel=4,
    delta_neg_eigval=True,
    n_experts=320, n_experts_per_tok=8, moe_ffn_dim=1280, n_shared_experts=1,
    first_k_dense=0, moe_renormalize=True, routed_scaling=1.0,
    eos_token_id=2, bos_token_id=1, pad_token_id=0,
))
register(ModelConfig(
    name="qwen3-8b", arch="llama", vocab_size=151936, dim=4096,
    n_layers=36, n_heads=32, n_kv_heads=8, ffn_dim=12288, max_seq_len=40960,
    norm_eps=1e-6, rope_theta=1000000.0, head_dim_override=128,
    use_qk_norm=True,
    eos_token_id=151645, bos_token_id=151643, pad_token_id=151643,
))

# --- OLMo-2 (post-norm residuals, whole-projection qk-norm) ---
register(ModelConfig(
    name="olmo2-7b", arch="llama", vocab_size=100352, dim=4096,
    n_layers=32, n_heads=32, n_kv_heads=32, ffn_dim=11008,
    max_seq_len=4096, norm_eps=1e-6, rope_theta=500000.0,
    pre_norms=False, post_norms=True, use_qk_norm=True, qk_norm_dim="proj",
    eos_token_id=100257, bos_token_id=100257, pad_token_id=100277,
))

# --- Gemma-3 (gemma-2 bones minus softcaps, plus unit-offset qk-norm,
# 5-sliding:1-full layer pattern, dual local/global RoPE) ---
register(ModelConfig(
    name="gemma3-1b", arch="llama", vocab_size=262144, dim=1152,
    n_layers=26, n_heads=4, n_kv_heads=1, ffn_dim=6912, max_seq_len=32768,
    norm_eps=1e-6, rope_theta=1000000.0, rope_local_theta=10000.0,
    head_dim_override=256, norm_unit_offset=True, act="gelu_tanh",
    embed_scale=True, post_norms=True, use_qk_norm=True,
    query_scale_override=256.0, attn_window=512,
    attn_window_layer_types=tuple(
        1 if (i % 6) != 5 else 0 for i in range(26)
    ),
    tie_embeddings=True, chat_template="gemma",
    eos_token_id=1, stop_token_ids=(106,),  # <end_of_turn>
    bos_token_id=2, pad_token_id=0,
))

# --- Gemma family (llama arch + unit-offset norms / GeGLU / embed scale) --
register(ModelConfig(
    name="gemma-2b", arch="llama", vocab_size=256000, dim=2048,
    n_layers=18, n_heads=8, n_kv_heads=1, ffn_dim=16384, max_seq_len=8192,
    norm_eps=1e-6, rope_theta=10000.0, head_dim_override=256,
    norm_unit_offset=True, act="gelu_tanh", embed_scale=True,
    tie_embeddings=True, chat_template="gemma",
    eos_token_id=1, stop_token_ids=(107,),  # <end_of_turn> (gemma-it)
    bos_token_id=2, pad_token_id=0,
))
register(ModelConfig(
    name="gemma-7b", arch="llama", vocab_size=256000, dim=3072,
    n_layers=28, n_heads=16, n_kv_heads=16, ffn_dim=24576, max_seq_len=8192,
    norm_eps=1e-6, rope_theta=10000.0, head_dim_override=256,
    norm_unit_offset=True, act="gelu_tanh", embed_scale=True,
    tie_embeddings=True, chat_template="gemma",
    eos_token_id=1, stop_token_ids=(107,),  # <end_of_turn> (gemma-it)
    bos_token_id=2, pad_token_id=0,
))
# Gemma-2: sandwich norms, logit softcaps, alternating sliding window
register(ModelConfig(
    name="gemma2-2b", arch="llama", vocab_size=256000, dim=2304,
    n_layers=26, n_heads=8, n_kv_heads=4, ffn_dim=9216, max_seq_len=8192,
    norm_eps=1e-6, rope_theta=10000.0, head_dim_override=256,
    norm_unit_offset=True, act="gelu_tanh", embed_scale=True,
    post_norms=True, attn_softcap=50.0, final_softcap=30.0,
    query_scale_override=256.0, attn_window=4096, attn_window_pattern="even",
    tie_embeddings=True, chat_template="gemma",
    eos_token_id=1, stop_token_ids=(107,),  # <end_of_turn> (gemma-it)
    bos_token_id=2, pad_token_id=0,
))
register(ModelConfig(
    name="gemma2-9b", arch="llama", vocab_size=256000, dim=3584,
    n_layers=42, n_heads=16, n_kv_heads=8, ffn_dim=14336, max_seq_len=8192,
    norm_eps=1e-6, rope_theta=10000.0, head_dim_override=256,
    norm_unit_offset=True, act="gelu_tanh", embed_scale=True,
    post_norms=True, attn_softcap=50.0, final_softcap=30.0,
    query_scale_override=256.0, attn_window=4096, attn_window_pattern="even",
    tie_embeddings=True, chat_template="gemma",
    eos_token_id=1, stop_token_ids=(107,),  # <end_of_turn> (gemma-it)
    bos_token_id=2, pad_token_id=0,
))

# --- Phi-3 family (llama arch; HF fuses qkv / gate_up, split at convert) --
register(ModelConfig(
    name="phi3-mini-4k", arch="llama", vocab_size=32064, dim=3072,
    n_layers=32, n_heads=32, n_kv_heads=32, ffn_dim=8192, max_seq_len=4096,
    norm_eps=1e-5, rope_theta=10000.0, attn_window=2047,
    chat_template="phi3",
    eos_token_id=32000, stop_token_ids=(32007,),  # <|endoftext|>, <|end|>
    bos_token_id=1, pad_token_id=32000,
))

# --- GPT-2 family ----------------------------------------------------------
register(ModelConfig(
    name="gpt2-small", arch="gpt2", vocab_size=50257, dim=768,
    n_layers=12, n_heads=12, n_kv_heads=12, ffn_dim=3072, max_seq_len=1024,
    norm_eps=1e-5, tie_embeddings=True, use_learned_pos=True,
    eos_token_id=50256, bos_token_id=50256, pad_token_id=50256,
))
register(ModelConfig(
    name="gpt2-medium", arch="gpt2", vocab_size=50257, dim=1024,
    n_layers=24, n_heads=16, n_kv_heads=16, ffn_dim=4096, max_seq_len=1024,
    norm_eps=1e-5, tie_embeddings=True, use_learned_pos=True,
    eos_token_id=50256, bos_token_id=50256, pad_token_id=50256,
))

# --- tiny test configs (CI-sized) -----------------------------------------
register(ModelConfig(
    name="test-llama-tiny", arch="llama", vocab_size=256, dim=64,
    n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=128, max_seq_len=128,
    eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="test-qwen3-tiny", arch="llama", vocab_size=256, dim=64,
    n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=128, max_seq_len=128,
    norm_eps=1e-6, head_dim_override=24, use_qk_norm=True,
    tie_embeddings=True, eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="test-olmo2-tiny", arch="llama", vocab_size=256, dim=64,
    n_layers=4, n_heads=4, n_kv_heads=4, ffn_dim=128, max_seq_len=128,
    norm_eps=1e-6, rope_theta=500000.0,
    pre_norms=False, post_norms=True, use_qk_norm=True, qk_norm_dim="proj",
    eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="test-gemma3-tiny", arch="llama", vocab_size=256, dim=64,
    n_layers=6, n_heads=4, n_kv_heads=2, ffn_dim=128, max_seq_len=128,
    norm_eps=1e-6, rope_theta=1000000.0, rope_local_theta=10000.0,
    head_dim_override=24, norm_unit_offset=True, act="gelu_tanh",
    embed_scale=True, post_norms=True, use_qk_norm=True,
    query_scale_override=24.0, attn_window=32,
    attn_window_layer_types=(1, 1, 1, 1, 1, 0),
    tie_embeddings=True, chat_template="gemma",
    eos_token_id=1, bos_token_id=2, pad_token_id=0,
))
register(ModelConfig(
    name="test-moe-tiny", arch="llama", vocab_size=256, dim=64,
    n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=96, max_seq_len=128,
    n_experts=4, n_experts_per_tok=2,
    eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="test-mla-moe-tiny", arch="mla_moe", vocab_size=256, dim=64,
    n_layers=3, n_heads=4, n_kv_heads=4, ffn_dim=96, max_seq_len=128,
    norm_eps=1e-6, rope_theta=1000000.0, head_dim_override=8,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_experts=16, n_experts_per_tok=3, moe_ffn_dim=32, n_shared_experts=2,
    first_k_dense=1, routed_scaling=2.448,
    eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="test-sdar-tiny", arch="llama", vocab_size=256, dim=64,
    n_layers=3, n_heads=4, n_kv_heads=2, ffn_dim=96, max_seq_len=128,
    norm_eps=1e-6, rope_theta=1000000.0, head_dim_override=16,
    use_qk_norm=True, n_experts=16, n_experts_per_tok=3, moe_ffn_dim=32,
    moe_renormalize=True, diffusion_block=4, mask_token_id=255,
    eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="test-lfm2-tiny", arch="lfm2", vocab_size=256, dim=64,
    n_layers=6, n_heads=4, n_kv_heads=2, ffn_dim=96, max_seq_len=256,
    norm_eps=1e-5, rope_theta=1000000.0, head_dim_override=64,
    use_qk_norm=True, tie_embeddings=True, conv_kernel=3,
    layer_types=("conv", "full_attention", "conv", "conv", "full_attention",
                 "conv"),
    n_experts=8, n_experts_per_tok=2, moe_ffn_dim=32, first_k_dense=1,
    moe_renormalize=True, router_norm_eps=1e-6,
    eos_token_id=2, bos_token_id=1,
))
# (the selection at toy constants: a compressed key of 4 keys every 2
# tokens, blocks of 8, 4 of them read past 24 visible positions, the
# last 8 positions' and the first block forced)
register(ModelConfig(
    name="test-sala-tiny", arch="minicpm_sala", vocab_size=256, dim=64,
    n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=96, max_seq_len=256,
    norm_eps=1e-6, rope_theta=10000.0, head_dim_override=16,
    use_qk_norm=True, linear_heads=4,
    layer_types=("minicpm4", "lightning-attn", "lightning-attn",
                 "minicpm4"),
    sparse_kernel=4, sparse_stride=2, sparse_block=8, sparse_topk=4,
    sparse_window=8, sparse_init_blocks=1, sparse_dense_len=24,
    embed_multiplier=12.0, residual_multiplier=1.4 / 32 ** 0.5,
    logits_divider=4.0, eos_token_id=2, bos_token_id=1,
))
# (state-space layers at toy sizes: 4 heads of 16 over a state of 8, so a
# row's matrix state is two packed rows of [8, 128]; attention 4/2 at head
# dim 64, packed two a pool row as the published model's)
register(ModelConfig(
    name="test-granite-tiny", arch="granite_hybrid", vocab_size=256, dim=64,
    n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=96, max_seq_len=256,
    norm_eps=1e-5, head_dim_override=64,
    layer_types=("mamba", "attention", "mamba", "mamba"),
    conv_kernel=4, conv_bias=True, ssm_heads=16, ssm_head_dim=16,
    ssm_state=8, embed_multiplier=12.0, residual_multiplier=0.22,
    attn_scale_override=0.015625, logits_divider=8.0, tie_embeddings=True,
    eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="test-trinity-tiny", arch="afmoe", vocab_size=256, dim=64,
    n_layers=5, n_heads=4, n_kv_heads=2, ffn_dim=96, max_seq_len=256,
    norm_eps=1e-5, rope_theta=10000.0, head_dim_override=16,
    use_qk_norm=True, post_norms=True, embed_scale=True, attn_window=8,
    layer_types=("sliding_attention", "sliding_attention", "full_attention",
                 "sliding_attention", "sliding_attention"),
    n_experts=8, n_experts_per_tok=2, moe_ffn_dim=32, n_shared_experts=1,
    first_k_dense=1, moe_renormalize=True, routed_scaling=2.448,
    router_norm_eps=1e-20, eos_token_id=2, bos_token_id=1,
))
# (the published head widths, keys 192 on a 256-lane pool row and values
# 128, so that the paged kernels' in-place write runs at float32 blocks of 8;
# two window layers to a global one, K/V heads 2 and 1, a window of two
# blocks of 8)
register(ModelConfig(
    name="test-mimo-tiny", arch="mimo_v2", vocab_size=256, dim=64,
    n_layers=4, n_heads=4, n_kv_heads=1, window_kv_heads=2, ffn_dim=96,
    max_seq_len=256, norm_eps=1e-5, rope_theta=1e7, rope_local_theta=1e4,
    head_dim_override=192, v_head_dim=128, rotary_dim=64,
    attn_value_scale=0.707, attn_window=16, window_sink=True,
    layer_types=("full_attention", "sliding_attention", "sliding_attention",
                 "full_attention"),
    n_experts=8, n_experts_per_tok=2, moe_ffn_dim=32, first_k_dense=1,
    moe_renormalize=True, routed_scaling=1.0, router_norm_eps=1e-20,
    eos_token_id=2, bos_token_id=1,
))
# (the published head width, 128 for keys and values of both kinds of layer,
# so that float32 blocks of 8 take the paged kernels' in-place write; one
# whole period, gated attention first)
register(ModelConfig(
    name="test-solar-tiny", arch="solar_open2", vocab_size=256, dim=64,
    n_layers=4, n_heads=2, n_kv_heads=1, ffn_dim=96, max_seq_len=256,
    norm_eps=1e-5, head_dim_override=128,
    layer_types=("full_attention", "kda", "kda", "kda"), linear_heads=2,
    conv_kernel=4, delta_neg_eigval=True,
    n_experts=8, n_experts_per_tok=2, moe_ffn_dim=32, n_shared_experts=1,
    first_k_dense=0, moe_renormalize=True, routed_scaling=1.0,
    eos_token_id=2, bos_token_id=1,
))
register(ModelConfig(
    name="test-gemma2-tiny", arch="llama", vocab_size=256, dim=64,
    n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=128, max_seq_len=128,
    norm_eps=1e-6, head_dim_override=24, norm_unit_offset=True,
    act="gelu_tanh", embed_scale=True, post_norms=True,
    attn_softcap=50.0, final_softcap=30.0, query_scale_override=24.0,
    attn_window=32, attn_window_pattern="even", tie_embeddings=True,
    chat_template="gemma", eos_token_id=1, bos_token_id=2, pad_token_id=0,
))
register(ModelConfig(
    name="test-gpt2-tiny", arch="gpt2", vocab_size=256, dim=64,
    n_layers=4, n_heads=4, n_kv_heads=4, ffn_dim=256, max_seq_len=128,
    tie_embeddings=True, use_learned_pos=True,
    eos_token_id=250, bos_token_id=250, pad_token_id=250,
))
