"""Routed experts, one copy for the families that have them
(models/mla_moe.py: sigmoid scores with a selection bias beside a shared
expert; models/lfm2.py: the same router, no shared expert; models/llama.py
with cfg.moe_ffn_dim > 0: softmax scores, the published SDAR / Qwen3-MoE
layer).

`route` chooses n_experts_per_tok experts a token and weighs them;
`routed_ffn` is told which experts it holds (the banks' leading axis, from
`expert_lo`), and computes its own experts' part: token-expert pairs sorted
by expert, one grouped matrix product per projection over the tokens each
expert got (operations follow tokens x n_experts_per_tok, not x
n_experts), combined by weight. The grouped product's operand is the
STACKED bank [L, E, ...] seen as L x E groups of which only this layer's
are non-empty: a scan that sliced the bank per layer would copy it (1.2 GB
at 128 experts of 2048 x 768) every step, so the banks stay outside the
scan's xs (`BANKS`). `normal_slices` draws such a leaf slice by slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm

from ..config import ModelConfig
from ..ops.flash_attention import resolve_interpret

Params = dict
F32 = jnp.float32

BANKS = ("w_gate", "w_up", "w_down")  # the routed experts' stacked banks


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def normal_slices(key, *, shape, scale, dtype):
    """A leaf [n, ...] drawn slice by slice: slice i is
    normal(split(key, n)[i], shape[1:]) * scale, float32 rounded to
    `dtype`; the loop writes each slice into the output, so the float32
    draw of the whole leaf (2.4 GB for an expert bank) never exists."""
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape[1:], F32) * scale).astype(dtype),
        keys,
    )


def route(cfg: ModelConfig, h, w_router, router_bias=None):
    """(chosen experts [N, k] int32, their weights [N, k] float32) for the
    normed tokens h [N, D]. The scores are float32 whatever the model's
    dtype: a near-tie decides which expert computes. cfg.router_score:
    "sigmoid" (mla_moe, lfm2: the n_experts_per_tok largest of score +
    router_bias are chosen, the scores alone weigh) or "softmax" (the
    llama family: the largest probabilities over all experts, no bias).
    Under moe_renormalize the chosen scores are divided by their sum (+
    cfg.router_norm_eps, where the family's code adds one)."""
    logits = jnp.dot(h.astype(F32), w_router.astype(F32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "sigmoid":
        s = jax.nn.sigmoid(logits)
        pick = s + router_bias.astype(F32)
    else:
        s = pick = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(pick, cfg.n_experts_per_tok)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.moe_renormalize:
        total = jnp.sum(w, axis=-1, keepdims=True)
        if cfg.router_norm_eps:  # (lfm2's; the others divide by the sum)
            total = total + cfg.router_norm_eps
        w = w / total
    return chosen.astype(jnp.int32), w * cfg.routed_scaling


def _group_tiling(m: int, k: int, n: int, itemsize: int) -> tuple:
    """(tm, tk, tn) of the grouped product: a whole expert matrix a tile
    where it stays near 3 MiB (two buffers of it inside the scoped VMEM),
    so a touched expert costs one grid step a 128-pair tile."""
    tm = min(m, 128)
    tk, tn = k, n
    while tk * tn * itemsize > 13 * 2**18 and tk % 256 == 0:
        tk //= 2
    # (an inner width that stops halving above the limit, 1,280 -> 640: the
    # columns take the rest, or two buffers of the tile beside the float32
    # result's pass the scoped VMEM)
    while tk * tn * itemsize > 13 * 2**18 and tn % 256 == 0:
        tn //= 2
    return tm, tk, tn


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def routed_expert_matmul(x, groups_rhs, groups, *, tiling, interpret):
    """JAX's grouped matrix product kernel (megablox gmm) under a name of
    this program's, which the device trace then carries: the benchmark
    tells the expert kernels by it (tests/test_chip_compile.py)."""
    if x.dtype == F32:
        return _gmm.__wrapped__(x, groups_rhs, groups, F32, tiling,
                                interpret=interpret)
    # bfloat16 products are exact in float32 whatever the ambient matmul
    # precision asks, and Mosaic refuses "highest" on bfloat16 operands
    with jax.default_matmul_precision("default"):
        return _gmm.__wrapped__(x, groups_rhs, groups, F32, tiling,
                                interpret=interpret)


def grouped_matmul(x, bank, sizes, layer):
    """x [M, K] sorted by expert against this layer's experts of the
    STACKED bank [L, E, K, N]; sizes [E] int32 rows an expert. Rows past
    sum(sizes) are not computed (callers mask them). Float32 out."""
    L, E, K, N = bank.shape
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((L * E,), jnp.int32), sizes, (layer * E,)
    )
    return routed_expert_matmul(
        x, bank.reshape(L * E, K, N), groups,
        tiling=_group_tiling(x.shape[0], K, N, bank.dtype.itemsize),
        interpret=resolve_interpret(None),
    )


def routed_ffn(cfg: ModelConfig, banks: Params, layer, h, chosen, weights,
               live=None, expert_lo=0):
    """The held experts' part of the routed layer for normed tokens h
    [N, D]: (float32 [N, D], tokens each held expert got [E_held] int32).

    banks: w_gate / w_up [L, E_held, D, F], w_down [L, E_held, F, D], the
    stacked banks of the experts held here, published experts expert_lo ..
    expert_lo + E_held - 1; `layer` (traced) picks the stack's layer.
    chosen / weights: `route`'s, over all published experts; a pair whose
    expert is held elsewhere, or whose token is not live ([N] bool: launch
    padding, a freed slot), reaches no expert and adds nothing."""
    N, D = h.shape
    k = cfg.n_experts_per_tok
    E = banks["w_gate"].shape[1]
    with jax.named_scope("moe_dispatch"):
        local = chosen - expert_lo
        held = (local >= 0) & (local < E)
        if live is not None:
            held &= live[:, None]
        M0 = N * k
        unit = 128 if M0 >= 128 else 16  # whole tiles of the grouped product
        M = -(-M0 // unit) * unit
        ids = jnp.full((M,), E, jnp.int32).at[:M0].set(
            jnp.where(held, local, E).reshape(M0)
        )  # E: sorts last, reaches no expert
        order = jnp.argsort(ids, stable=True)
        sizes = jnp.zeros((E + 1,), jnp.int32).at[ids].add(1)[:E]
        xs = h[jnp.minimum(order // k, N - 1)]  # [M, D], sorted by expert
    with jax.named_scope("moe_experts"):
        gate = grouped_matmul(xs, banks["w_gate"], sizes, layer)
        up = grouped_matmul(xs, banks["w_up"], sizes, layer)
        act = (jax.nn.silu(gate) * up).astype(h.dtype)
        ys = grouped_matmul(act, banks["w_down"], sizes, layer)
    with jax.named_scope("moe_combine"):
        computed = jnp.arange(M) < jnp.sum(sizes)
        ys = jnp.where(computed[:, None], ys, 0.0)
        pairs = ys[jnp.argsort(order)[:M0]].reshape(N, k, D)
        w = jnp.where(held, weights, 0.0)
        out = jnp.einsum("nkd,nk->nd", pairs, w)
    return out, sizes
