"""Pallas TPU flash attention over the static-shape KV cache.

Drop-in replacement for `ops.attention.attend` (the XLA einsum path): same
GQA semantics, same [B,KV,S,Dh] cache layout, causal by absolute position.
One kernel covers both phases:

  * prefill — query chunk of length T at offset `pos`,
  * decode  — T=1 query at offset `pos`,

with an online-softmax (flash) loop over KV tiles, so the full [T,S] score
matrix is never materialized. The reference has no analogue — its
attention is HF eager attention recomputed over the whole sequence with no
cache at all (/root/reference/Worker1.py:125-154); this kernel is the
TPU-native hot path that makes decode O(prefix) per token.

Kernel layout decisions (see /opt/skills/guides/pallas_guide.md):
  * grid = (B, KV-heads, T-tiles, KV-tiles) under a
    `PrefetchScalarGridSpec`: `pos` is a scalar-prefetch argument, so the
    K/V BlockSpec index maps can CLAMP the KV-tile index to the live
    prefix — tiles past ceil((pos+T)/block_k) map to the same block as
    their predecessor, Pallas skips the redundant DMA, and HBM traffic is
    one pass over the live prefix, not max_seq. VMEM holds one
    [block_k, Dh] tile per operand, so max_seq is unbounded by VMEM.
  * GQA is folded into the query-row dimension: a tile holds
    block_t x group rows (row r = t*group + g), so one kernel serves MHA
    (group=1) and GQA alike and the MXU sees tall skinny matmuls instead
    of per-head vector products.
  * (m, l, acc) live in VMEM scratch, which persists across the
    sequentially-iterated KV-tile grid dimension (standard Pallas flash
    pattern); the output block is written once, on the last KV tile.
  * scores/accumulator in fp32 (preferred_element_type), output cast back
    to the query dtype.

On non-TPU backends the kernel runs in interpret mode, which is what the
CPU test suite exercises; numerics match `attend` to fp32 tolerance.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)  # mask fill; avoids inf-inf NaNs


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's interpret mode: an explicit argument wins, then
    the DLI_PALLAS_INTERPRET env switch ("1"/"0" — tests/conftest.py pins
    it to 1 so tier-1 exercises every Pallas kernel bit-for-bit on CPU),
    then the backend default (interpret anywhere but a real TPU). ONE
    resolver for all kernels (flash / paged / ragged), so the test-suite
    switch cannot miss one."""
    if interpret is not None:
        return interpret
    env = os.environ.get("DLI_PALLAS_INTERPRET", "")
    if env != "":
        return env not in ("0", "false", "no")
    return jax.default_backend() != "tpu"


def scale_column(s_ref, kv, n: int):
    """This kv head's dequant scales as an [n, 1] column, from a scale
    block [1, KV, n] that spans every kv head (int8 KV, ops/kv_quant).

    The TPU lowering takes a block whose last two dims are the array's own
    (KV) or 8/128-aligned, so the scales ride blocked whole over KV with
    the tokens on lanes, and the kernel picks its head's row. The row
    becomes a column exactly — the diagonal of its sublane broadcast,
    summed over lanes, adds only zeros — so `tile * column` is
    bit-identical to `tile * scales[:, None]`, with no copy or padded
    relayout of the scale array in HBM."""
    row = s_ref[0, pl.ds(kv, 1), :]  # [1, n]
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(r == c, row, 0.0), axis=1, keepdims=True)


def _needed_tiles(pos, qi, *, T: int, block_t: int, block_k: int):
    """KV tiles live for query tile qi: keys up to its last valid query
    position pos + min((qi+1)*block_t, T) - 1."""
    t_hi = jnp.minimum((qi + 1) * block_t, T)
    return pl.cdiv(pos + t_hi, block_k)


def _first_tile(pos, qi, *, block_t: int, block_k: int, win):
    """First KV tile any query in tile qi can see: with sliding-window
    attention the tile's EARLIEST query (pos + qi*block_t) bounds it at
    q_pos - win + 1; full causal starts at 0. `win` is a TRACED scalar
    (the 3rd scalar-prefetch operand): <= 0 means full causal — per-layer
    window patterns (Gemma-2/3) feed a per-layer value from the scan, so
    ONE compiled kernel serves windowed and full layers."""
    lo = pos + qi * block_t - win + 1
    return jnp.where(win > 0, jnp.maximum(lo, 0) // block_k, 0)


def _flash_kernel(
    pos_ref,  # scalar-prefetch [1] int32
    vs_ref,  # scalar-prefetch [B] int32: per-row first valid slot
    win_ref,  # scalar-prefetch [1] int32: sliding window (<= 0 = full)
    q_ref,  # [1, block_t, 1, group, Dh] VMEM
    k_ref,  # [1, 1, block_k, Dh] VMEM
    v_ref,  # [1, 1, block_k, Dh] VMEM
    *rest,  # quant: (ks_ref, vs_scale_ref, o_ref, scratch...) else (o_ref, ...)
    T: int,
    S: int,
    block_t: int,
    block_k: int,
    group: int,
    scale: float,
    softcap: float | None,
    quant: bool = False,
):
    if quant:
        # int8 cache (ops/kv_quant): per-(token, head) fp32 scales ride
        # as two extra [1, KV, block_k] operands (scale_column picks this
        # head's); dequant happens in the tile prologue below — the kernel
        # streams HALF the cache bytes from HBM and the MXU still sees
        # fp32 tiles.
        ks_ref, vscale_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vscale_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    pos = pos_ref[0]
    valid_from = vs_ref[pl.program_id(0)]
    win = win_ref[0]
    kv = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    n_j = pl.num_programs(3)
    rows = block_t * group
    Dh = q_ref.shape[-1]

    needed = _needed_tiles(pos, qi, T=T, block_t=block_t, block_k=block_k)
    first_live = _first_tile(pos, qi, block_t=block_t, block_k=block_k, win=win)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full((rows, 1), _NEG, jnp.float32)
        l_ref[:] = jnp.zeros((rows, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((rows, Dh), jnp.float32)

    @pl.when((j >= first_live) & (j < needed))
    def _():
        q = q_ref[0].reshape(rows, Dh).astype(jnp.float32) * scale
        # Row r of the tile is query (t_local = r // group, head g = r % group);
        # its absolute position is pos + qi*block_t + t_local.
        r_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
        t_global = qi * block_t + r_ids // group
        q_pos = pos + t_global
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)

        ks = k_ref[0, 0].astype(jnp.float32)  # [block_k, Dh]
        if quant:
            ks = ks * scale_column(ks_ref, kv, block_k)  # dequant prologue
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, block_k]
        if softcap is not None:  # Gemma-2 logit capping, pre-mask (HF order)
            s = softcap * jnp.tanh(s / softcap)
        kv_pos = j * block_k + col_ids
        mask = (t_global < T) & (kv_pos <= q_pos) & (kv_pos < S)
        mask &= kv_pos >= valid_from  # left-pad slots (ragged batches)
        # sliding-window attention (win <= 0 = full causal; per-layer
        # patterns pass this layer's width)
        mask &= (win <= 0) | (kv_pos > q_pos - win)
        s = jnp.where(mask, s, _NEG)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)  # first tile: exp(_NEG - _NEG) == 1
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        vs = v_ref[0, 0].astype(jnp.float32)
        if quant:
            vs = vs * scale_column(vscale_ref, kv, block_k)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == n_j - 1)
    def _():
        l = l_ref[:]
        l = jnp.where(l == 0.0, 1.0, l)  # padding rows (t >= T) are all-masked
        o_ref[0] = (acc_ref[:] / l).reshape(block_t, 1, group, Dh).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_k", "interpret", "window", "scale",
                     "softcap"),
)
def flash_attend(
    q: jnp.ndarray,
    cache_k,
    cache_v,
    pos: jnp.ndarray,
    valid_start: jnp.ndarray | None = None,
    window_dyn: jnp.ndarray | None = None,
    *,
    block_t: int = 0,
    block_k: int = 0,
    interpret: bool | None = None,
    window: int | None = None,
    scale: float | None = None,
    softcap: float | None = None,
) -> jnp.ndarray:
    """Causal GQA flash attention over the (already updated) cache.

    q [B,T,H,Dh], cache_k/v [B,KV,S,Dh] — or ops/kv_quant.KVQuant leaves
    (int8 data + per-(token, head) fp32 scales [B,KV,S]), dequantized in
    the kernel's tile prologue so the int8 cache streams half the HBM
    bytes. pos scalar int32 (chunk offset).
    valid_start: optional [B] int32 — first real slot per row (ragged
    LEFT-padded batches; earlier slots are never attended). window:
    static sliding-window width (None = full causal); window_dyn: TRACED
    scalar override (<= 0 = full causal) — the window rides the kernel as
    a scalar-prefetch operand, so per-layer patterns (Gemma-2/3
    alternating layers) feed each scan step's width through ONE compiled
    kernel. scale: score scale override (Gemma query scaling, Granite
    attention_multiplier; None = head_dim**-0.5). softcap: Gemma-2 logit
    capping. Returns [B,T,H,Dh] in q.dtype. Same contract as
    `attention.attend` with the mask derived from `pos` (and
    `valid_start`/window) instead of passed in.
    """
    from .kv_quant import KVQuant

    quant = isinstance(cache_k, KVQuant)
    if quant:
        cache_k, k_scale = cache_k.q, cache_k.s
        cache_v, v_scale = cache_v.q, cache_v.s
    B, T, H, Dh = q.shape
    KV, S = cache_k.shape[1], cache_k.shape[2]
    group = H // KV

    interpret = resolve_interpret(interpret)
    if block_t <= 0:
        # ~<=1024 query rows per tile keeps q + fp32 acc well inside VMEM.
        block_t = max(1, min(T, 1024 // group))
    if block_k <= 0:
        block_k = min(S, 256)

    # Heads of one KV group are contiguous in H (h = kv*group + g), so a
    # [*, block_t, 1, group, Dh] block at KV-index kv covers exactly that
    # group's queries.
    q5 = q.reshape(B, T, KV, group, Dh)
    pos_arr = jnp.reshape(pos.astype(jnp.int32), (1,))
    if valid_start is None:
        valid_start = jnp.zeros((B,), jnp.int32)
    valid_start = valid_start.astype(jnp.int32)
    if window_dyn is None:
        win_arr = jnp.full((1,), window if window is not None else -1, jnp.int32)
    else:
        win_arr = jnp.reshape(window_dyn.astype(jnp.int32), (1,))

    nt = _needed_tiles  # close over static tile params in the index maps

    def kv_index(b, kv, qi, j, pos_ref, vs_ref, win_ref):
        # Clamp dead tiles (past the causal frontier, or — with a sliding
        # window — before the window) to the nearest live one: the block
        # index repeats, so Pallas skips the DMA and dead grid steps cost
        # nothing. The kernel's pl.when gate skips their compute too.
        needed = nt(pos_ref[0], qi, T=T, block_t=block_t, block_k=block_k)
        first = _first_tile(
            pos_ref[0], qi, block_t=block_t, block_k=block_k, win=win_ref[0]
        )
        return (b, kv, jnp.clip(j, first, needed - 1), 0)

    def scale_index(b, kv, qi, j, pos_ref, vs_ref, win_ref):
        # the quant-scale operands [B, KV, S]: same clamped tile walk,
        # every kv head in the block (see scale_column)
        b, _, tile, _ = kv_index(b, kv, qi, j, pos_ref, vs_ref, win_ref)
        return (b, 0, tile)

    kernel = functools.partial(
        _flash_kernel,
        T=T,
        S=S,
        block_t=block_t,
        block_k=block_k,
        group=group,
        scale=scale if scale is not None else Dh**-0.5,
        softcap=softcap,
        quant=quant,
    )
    rows = block_t * group
    in_specs = [
        pl.BlockSpec(
            (1, block_t, 1, group, Dh),
            lambda b, kv, qi, j, pos_ref, vs_ref, win_ref: (b, qi, kv, 0, 0),
        ),
        pl.BlockSpec((1, 1, block_k, Dh), kv_index),
        pl.BlockSpec((1, 1, block_k, Dh), kv_index),
    ]
    operands = [q5, cache_k, cache_v]
    if quant:
        # scale rows [B, KV, S] tile with the SAME clamped kv index map,
        # one [KV, block_k] strip per tile
        in_specs += [
            pl.BlockSpec((1, KV, block_k), scale_index),
            pl.BlockSpec((1, KV, block_k), scale_index),
        ]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, pl.cdiv(T, block_t), pl.cdiv(S, block_k)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, block_t, 1, group, Dh),
            lambda b, kv, qi, j, pos_ref, vs_ref, win_ref: (b, qi, kv, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, KV, group, Dh), q.dtype),
        interpret=interpret,
    )(pos_arr, valid_start, win_arr, *operands)
    return out.reshape(B, T, H, Dh)
