"""Rotary position embeddings (Llama semantics).

The reference burns ~25 lines on transformers-version compat fallbacks just to
get cos/sin tables out of HF (/root/reference/Worker1.py:98-120) and rebuilds
position ids 0..seq-1 on every call (/root/reference/Worker1.py:93-94). Here
RoPE is a pure function of (positions, head_dim, theta) with pinned HF
"rotate_half" semantics: inv_freq over even indices, angles tiled twice, and
rotation by concat(-x2, x1) — matching transformers' LlamaRotaryEmbedding so
converter parity tests hold exactly.
"""

from __future__ import annotations

import jax.numpy as jnp


def llama3_scaled_inv_freq(
    inv_freq: jnp.ndarray,
    factor: float,
    low_freq_factor: float,
    high_freq_factor: float,
    original_max_len: int,
) -> jnp.ndarray:
    """Llama-3.1/3.2 "llama3" rope_scaling applied to the inverse frequencies.

    Matches transformers' `_compute_llama3_parameters`: wavelengths longer
    than original_max_len/low_freq_factor are slowed by `factor`, wavelengths
    shorter than original_max_len/high_freq_factor are kept, and the band in
    between interpolates smoothly. HF applies this unconditionally (not only
    past the original context), so parity requires it at every position.
    """
    wavelen = 2.0 * jnp.pi / inv_freq
    low_freq_wavelen = original_max_len / low_freq_factor
    high_freq_wavelen = original_max_len / high_freq_factor
    smooth = (original_max_len / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor
    )
    smoothed = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    scaled = jnp.where(wavelen > low_freq_wavelen, inv_freq / factor, smoothed)
    return jnp.where(wavelen < high_freq_wavelen, inv_freq, scaled)


def rope_cos_sin(
    positions: jnp.ndarray,
    head_dim: int,
    theta: float = 10000.0,
    *,
    scaling: str | None = None,
    scaling_factor: float = 8.0,
    low_freq_factor: float = 1.0,
    high_freq_factor: float = 4.0,
    original_max_len: int = 8192,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions.

    positions: [...] int array. Returns (cos, sin), each [..., head_dim],
    computed in float32 (HF computes RoPE tables in fp32 even for bf16 models).
    scaling="llama3" reproduces Llama-3.1/3.2 frequency scaling.
    """
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if scaling == "llama3":
        inv_freq = llama3_scaled_inv_freq(
            inv_freq, scaling_factor, low_freq_factor, high_freq_factor,
            original_max_len,
        )
    elif scaling == "linear":
        # HF "linear" rope_scaling (Gemma-3 global layers): every
        # frequency divides by the factor at every position
        inv_freq = inv_freq / scaling_factor
    elif scaling is not None:
        raise ValueError(f"unsupported rope scaling {scaling!r}")
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., head_dim/2]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [..., head_dim]
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(
    q: jnp.ndarray,
    k: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply rotary embedding to q [B,T,H,Dh] and k [B,T,KV,Dh].

    cos/sin: [T, R] or [B, T, R]; broadcast over the head axis. R == Dh
    rotates the whole head; R < Dh (a partial rotary factor: tables made
    for R) rotates lanes 0 .. R-1 of every head among themselves, in the
    same half-rotation form, and passes lanes R .. Dh-1 through.
    """
    R = cos.shape[-1]
    if R < q.shape[-1]:
        q_rot, k_rot = apply_rope(q[..., :R], k[..., :R], cos, sin)
        return (jnp.concatenate([q_rot, q[..., R:]], axis=-1),
                jnp.concatenate([k_rot, k[..., R:]], axis=-1))
    if cos.ndim == 2:  # [T, Dh] -> [1, T, 1, Dh]
        cos_b = cos[None, :, None, :]
        sin_b = sin[None, :, None, :]
    else:  # [B, T, Dh] -> [B, T, 1, Dh]
        cos_b = cos[:, :, None, :]
        sin_b = sin[:, :, None, :]
    orig = q.dtype
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    q_out = qf * cos_b + _rotate_half(qf) * sin_b
    k_out = kf * cos_b + _rotate_half(kf) * sin_b
    return q_out.astype(orig), k_out.astype(orig)


def rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                     theta: float) -> jnp.ndarray:
    """Rotary embedding over INTERLEAVED pairs (DeepSeek-V3 / Kanana-2
    `rope_interleave`): numbers (2i, 2i+1) of the last axis turn together
    by positions * theta**(-2i/d). x [..., d]; positions broadcast against
    x's leading axes. Float32 inside, x's dtype out."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
