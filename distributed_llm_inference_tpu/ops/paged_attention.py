"""Pallas TPU paged-attention decode kernel over the block pool.

Fused replacement for the gather-then-attend path in `engine/paged.py`:
the XLA path materializes each slot's block table into a contiguous
[B, KV, MB*bs, Dh] view (one extra HBM write + read of the whole logical
window per layer per step) and then runs the masked einsum attention over
it. Here the kernel walks the block table directly — each grid step DMAs
ONE physical pool block [bs, Dh] into VMEM and folds it into an
online-softmax (flash) accumulator, so

  * HBM traffic is one read of the slot's LIVE blocks (dead tail blocks
    and — with a sliding window — dead head blocks repeat their
    neighbour's index, so Pallas skips the DMA entirely), with no
    contiguous-view materialization at all;
  * the pool is never reshaped/transposed: the kernel reads the same
    [N, KV, bs, Dh] layout the scatter writes.

Contract (matches `engine/paged.make_paged_hook`'s gather path):
  * decode only — T=1 queries at per-row positions `pos` [B];
  * mask is derived IN-KERNEL from `pos` and the window — static, or a
    TRACED per-layer width via the `window_dyn` scalar-prefetch operand
    (Gemma-2/3 alternating patterns): row b attends logical positions
    max(0, pos_b-win+1) .. pos_b inclusive. Score-scale overrides and
    Gemma-2 softcapping are static kernel params, so the full attention
    variant surface runs fused (round 5 — the kernel previously fell
    back to the gather path for these).
  * GQA is folded into the query-row dimension exactly like
    ops/flash_attention.py: the score matmul is [group, Dh] x [Dh, bs].

The reference has no analogue at any level — it has no KV cache at all
(/root/reference/Worker1.py:132-134); block-paged KV + this kernel are
north-star serving scope (vLLM-class HBM discipline, re-designed for
XLA's static shapes: the table is a plain traced input, admission never
recompiles).

On non-TPU backends the kernel runs in interpret mode (CPU test suite);
numerics match the gather path to fp32 tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret, scale_column

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)  # mask fill; avoids inf-inf NaNs


def _live_range(pos_b, *, bs: int, MB: int, win):
    """(first, needed) logical-block bounds for a row at position pos_b:
    blocks [first, needed) hold at least one attendable position. `win`
    is a TRACED scalar or a static int (None / <= 0 = full causal) —
    per-layer window patterns (Gemma-2/3) feed each scan step's width
    through one compiled kernel, same contract as
    ops/flash_attention._first_tile."""
    if win is None:
        win = -1
    needed = jnp.minimum(pl.cdiv(pos_b + 1, bs), MB)
    needed = jnp.maximum(needed, 1)  # pos < 0 never happens; keep clip sane
    first = jnp.where(
        win > 0,
        jnp.minimum(jnp.maximum(pos_b - win + 1, 0) // bs, needed - 1),
        0,
    )
    return first, needed


def _paged_kernel(
    table_ref,  # scalar-prefetch [B, MB] int32
    pos_ref,  # scalar-prefetch [B] int32
    win_ref,  # scalar-prefetch [1] int32: sliding window (<= 0 = full)
    q_ref,  # [1, 1, 1, group, Dh] VMEM
    k_ref,  # [1, 1, bs, Dh] VMEM (one physical pool block)
    v_ref,  # [1, 1, bs, Dh] VMEM
    *rest,  # quant: (ks_ref, vscale_ref, o_ref, scratch...) else (o_ref, ...)
    bs: int,
    MB: int,
    group: int,
    scale: float,
    softcap: float | None,
    quant: bool = False,
):
    del table_ref  # physical placement is the index maps' concern
    if quant:
        # int8 pool (ops/kv_quant): per-(token, head) fp32 scales ride as
        # two extra [1, KV, bs] operands walking the same table
        # (scale_column picks this head's); dequant in the block prologue —
        # the table walk streams the int8 bytes, the MXU sees fp32
        ks_ref, vscale_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vscale_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    kv = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    pos_b = pos_ref[b]
    win = win_ref[0]
    Dh = q_ref.shape[-1]
    first, needed = _live_range(pos_b, bs=bs, MB=MB, win=win)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full((group, 1), _NEG, jnp.float32)
        l_ref[:] = jnp.zeros((group, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((group, Dh), jnp.float32)

    @pl.when((j >= first) & (j < needed))
    def _():
        q = q_ref[0, 0, 0].astype(jnp.float32) * scale  # [group, Dh]
        ks = k_ref[0, 0].astype(jnp.float32)  # [bs, Dh]
        vs = v_ref[0, 0].astype(jnp.float32)
        if quant:
            ks = ks * scale_column(ks_ref, kv, bs)
            vs = vs * scale_column(vscale_ref, kv, bs)
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [group, bs]
        if softcap is not None:  # Gemma-2 logit capping, pre-mask (HF order)
            s = softcap * jnp.tanh(s / softcap)
        kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
        mask = kv_pos <= pos_b
        mask &= (win <= 0) | (kv_pos > pos_b - win)
        s = jnp.where(mask, s, _NEG)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)  # first block: exp(_NEG - _NEG) == 1
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == n_j - 1)
    def _():
        l = l_ref[:]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked row (never in serving)
        o_ref[0, 0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("interpret", "window", "scale", "softcap")
)
def paged_flash_attend(
    q: jnp.ndarray,
    pool_k,
    pool_v,
    table: jnp.ndarray,
    pos: jnp.ndarray,
    window_dyn: jnp.ndarray | None = None,
    *,
    window: int | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Paged GQA decode attention over the (already updated) block pool.

    q [B,1,H,Dh]; pool_k/v [N,KV,bs,Dh] (one layer's pool slice) — or
    ops/kv_quant.KVQuant leaves (int8 blocks + per-(token, head) fp32
    scales [N,KV,bs]), dequantized in the block prologue so the table
    walk streams HALF the bytes per live block; table [B,MB] int32
    physical block ids; pos [B] int32 per-row positions.
    window: static sliding-window width (None = full causal);
    window_dyn: TRACED scalar override (<= 0 = full) riding as a
    scalar-prefetch operand — per-layer patterns (Gemma-2/3) feed each
    scan step's width through ONE compiled kernel. scale: score-scale
    override (None = head_dim**-0.5); softcap: Gemma-2 logit capping.
    Returns [B,1,H,Dh] in q.dtype — same contract as the gather path in
    engine/paged.make_paged_hook with the mask derived from pos/window.
    """
    from .kv_quant import KVQuant

    quant = isinstance(pool_k, KVQuant)
    if quant:
        pool_k, k_scale = pool_k.q, pool_k.s
        pool_v, v_scale = pool_v.q, pool_v.s
    B, T, H, Dh = q.shape
    assert T == 1, "paged kernel serves decode steps (T=1) only"
    KV, bs = pool_k.shape[1], pool_k.shape[2]
    MB = table.shape[1]
    group = H // KV

    interpret = resolve_interpret(interpret)

    q5 = q.reshape(B, 1, KV, group, Dh)
    table = table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    if window_dyn is None:
        win_arr = jnp.full((1,), window if window is not None else -1, jnp.int32)
    else:
        win_arr = jnp.reshape(window_dyn.astype(jnp.int32), (1,))

    def kv_index(b, kv, j, table_ref, pos_ref, win_ref):
        # Clamp dead logical blocks (past the causal frontier, or before
        # a sliding window) to the nearest live one: the PHYSICAL index
        # then repeats across consecutive dead steps, Pallas skips the
        # DMA, and the kernel's pl.when gate skips their compute.
        first, needed = _live_range(
            pos_ref[b], bs=bs, MB=MB, win=win_ref[0]
        )
        return (table_ref[b, jnp.clip(j, first, needed - 1)], kv, 0, 0)

    def scale_index(b, kv, j, table_ref, pos_ref, win_ref):
        # the quant-scale operands [N, KV, bs]: same table walk, every kv
        # head in the block (ops/flash_attention.scale_column)
        return (kv_index(b, kv, j, table_ref, pos_ref, win_ref)[0], 0, 0)

    kernel = functools.partial(
        _paged_kernel,
        bs=bs,
        MB=MB,
        group=group,
        scale=scale if scale is not None else Dh**-0.5,
        softcap=softcap,
        quant=quant,
    )
    in_specs = [
        pl.BlockSpec(
            (1, 1, 1, group, Dh),
            lambda b, kv, j, table_ref, pos_ref, win_ref: (b, 0, kv, 0, 0),
        ),
        pl.BlockSpec((1, 1, bs, Dh), kv_index),
        pl.BlockSpec((1, 1, bs, Dh), kv_index),
    ]
    operands = [q5, pool_k, pool_v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, KV, bs), scale_index),
            pl.BlockSpec((1, KV, bs), scale_index),
        ]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, 1, group, Dh),
            lambda b, kv, j, table_ref, pos_ref, win_ref: (b, 0, kv, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, KV, group, Dh), q.dtype),
        interpret=interpret,
    )(table, pos, win_arr, *operands)
    return out.reshape(B, 1, H, Dh)


def _slots_kernel(
    pos_ref,  # scalar-prefetch [B] int32
    q_ref,  # [1, 1, KV, group, Dh] VMEM
    k_ref,  # [1, KV, bk, Dh] VMEM (all kv heads, one seq tile)
    v_ref,  # [1, KV, bk, Dh] VMEM
    o_ref,  # [1, 1, KV, group, Dh] VMEM
    m_ref,  # scratch [H, 1] fp32
    l_ref,  # scratch [H, 1] fp32
    acc_ref,  # scratch [H, Dh] fp32
    *,
    bk: int,
    KV: int,
    group: int,
    S: int,
    scale: float,
    window: int | None,
):
    """One (batch row, seq tile) step: ALL kv heads in one MXU matmul.

    The per-(b, kv) variant (`_paged_kernel`) issues KV x S/bk programs of
    [group, bk] work each; this tile folds every kv head — scores are one
    [H, KV*bk] matmul (rows = all query heads, columns = every kv head's
    tile) and a block-diagonal mask kills the cross-head terms: 4x the
    multiplies on paper, but they ride an MXU that was idling, and the
    program count drops by KV x.

    Measured on v5e (TinyLlama, 8 x 8192 fleet cache at pos 1024):
    ~1.08 ms/call vs the XLA einsum's ~1.00 ms at the attention level
    (bench.py's fleet leg re-measures both every round), and 382 vs 395
    tok/s inside the full end-to-end fleet decode step — the live-prefix
    DMA savings do not yet overcome Mosaic pipelining overhead against
    XLA's fused masked einsum. That is why the serving hook never
    selects this kernel: decode stays on the XLA path regardless of
    attn_impl, and this kernel is the baseline future work (splash-style
    multi-tile pipelining) has to beat.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)
    pos_b = pos_ref[b]
    Dh = q_ref.shape[-1]
    H = KV * group
    C = KV * bk
    first, needed = _live_range(pos_b, bs=bk, MB=n_j, win=window)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full((H, 1), _NEG, jnp.float32)
        l_ref[:] = jnp.zeros((H, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((H, Dh), jnp.float32)

    @pl.when((j >= first) & (j < needed))
    def _():
        q = q_ref[0, 0].reshape(H, Dh).astype(jnp.float32) * scale
        ks = k_ref[0].reshape(C, Dh).astype(jnp.float32)
        vs = v_ref[0].reshape(C, Dh).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [H, C]
        row = jax.lax.broadcasted_iota(jnp.int32, (H, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (H, C), 1)
        kv_pos = j * bk + col % bk
        # block-diagonal: row h (kv head h // group) only sees columns of
        # its own kv head's tile (col // bk)
        mask = (row // group == col // bk) & (kv_pos <= pos_b)
        if S % bk != 0:
            mask &= kv_pos < S
            vs = jnp.where(
                j * bk + jax.lax.broadcasted_iota(jnp.int32, (C, Dh), 0) % bk
                < S,
                vs, 0.0,
            )  # BlockSpec pad garbage would ride 0 * NaN into acc
        if window is not None:
            mask &= kv_pos > pos_b - window
        s = jnp.where(mask, s, _NEG)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == n_j - 1)
    def _():
        l = l_ref[:]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (
            (acc_ref[:] / l).reshape(KV, group, Dh).astype(o_ref.dtype)
        )


@functools.partial(
    jax.jit, static_argnames=("block_k", "interpret", "window")
)
def flash_attend_slots(
    q: jnp.ndarray,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    block_k: int = 0,
    window: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-row-position flash decode over the DENSE slot-fleet cache.

    The same online-softmax walk as `paged_flash_attend` with the identity
    layout: the fleet cache is [B, KV, S, Dh] and row b's live prefix is
    positions 0..pos[b] (ops/attention.slot_causal_mask semantics, the
    continuous fleet's decode mask). Tiles past each row's causal frontier
    — or, with a sliding window, before it — clamp to the nearest live
    tile, so Pallas skips their DMA: HBM traffic per step is each row's
    LIVE prefix, where the XLA path reads all B*S slots of the fleet
    cache regardless of occupancy. ops/flash_attention.flash_attend is
    the shared-scalar-position counterpart (its grid offsets assume one
    frontier for the whole batch; this kernel's are per-row).

    Not reachable from the serving hook: see `_slots_kernel` — on v5e
    at serving sizes the XLA einsum still edges it out end to end;
    bench.py's fleet leg tracks the attention-level gap each round.

    q [B,1,H,Dh] (decode, T=1); cache_k/v [B,KV,S,Dh]; pos [B] int32.
    Returns [B,1,H,Dh] in q.dtype.
    """

    B, T, H, Dh = q.shape
    assert T == 1, "slots kernel serves decode steps (T=1) only"
    KV, S = cache_k.shape[1], cache_k.shape[2]
    group = H // KV

    interpret = resolve_interpret(interpret)
    if block_k <= 0:
        block_k = min(S, 512)
    MB = pl.cdiv(S, block_k)

    q5 = q.reshape(B, 1, KV, group, Dh)
    pos = pos.astype(jnp.int32)

    def kv_index(b, j, pos_ref):
        first, needed = _live_range(pos_ref[b], bs=block_k, MB=MB, win=window)
        return (b, 0, jnp.clip(j, first, needed - 1), 0)

    kernel = functools.partial(
        _slots_kernel,
        bk=block_k,
        KV=KV,
        group=group,
        S=S,
        scale=Dh**-0.5,
        window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec(
                (1, 1, KV, group, Dh), lambda b, j, pos_ref: (b, 0, 0, 0, 0)
            ),
            pl.BlockSpec((1, KV, block_k, Dh), kv_index),
            pl.BlockSpec((1, KV, block_k, Dh), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, KV, group, Dh), lambda b, j, pos_ref: (b, 0, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, KV, group, Dh), q.dtype),
        interpret=interpret,
    )(pos, q5, cache_k, cache_v)
    return out.reshape(B, 1, H, Dh)


# -- ragged paged attention: mixed prefill + decode rows, one launch ----------
#
# The decode kernel above serves exactly one query per row; prefill still
# climbs a bucket ladder of chunked fills over a contiguous scratch cache
# that is then scattered into the pool. This kernel collapses both phases
# into ONE grid: the flat query axis holds every row's tokens back to back
# (a prefill row contributes its chunk, a decode row contributes one
# token), a per-tile metadata array carries (row, start, length, kind),
# and the KV walk reads each tile's placement straight from the block
# table. Dead tiles (launch padding, or KV blocks past a tile's causal
# frontier) repeat their neighbour's physical index, so Pallas skips the
# DMA — padding costs control flow, not HBM bandwidth. The TPU "Ragged
# Paged Attention" kernel (PAPERS.md) is the design source; the flash
# accumulation discipline is shared with ops/flash_attention.py.

RAGGED_PREFILL = 0  # metadata `kind`: a prompt-chunk row (length >= 1)
RAGGED_DECODE = 1  # metadata `kind`: a single-token decode row


def _ragged_live_range(q_start, q_len, *, bs: int, MB: int, win):
    """(first, needed) logical-block bounds for a query tile starting at
    absolute position q_start with q_len valid queries. Dead tiles
    (q_len == 0 launch padding) evaluate with an effective length of 1 so
    their range — and therefore their clamped physical index — equals
    their predecessor's, which is what lets Pallas skip the DMA
    entirely (the builder copies the predecessor's row/start into pad
    tiles). `win` is a TRACED scalar (<= 0 = full causal)."""
    last = q_start + jnp.maximum(q_len, 1) - 1
    needed = jnp.clip(pl.cdiv(last + 1, bs), 1, MB)
    first = jnp.where(
        win > 0,
        jnp.minimum(jnp.maximum(q_start - win + 1, 0) // bs, needed - 1),
        0,
    )
    return first, needed


def _ragged_kernel(
    meta_ref,  # scalar-prefetch [G, 4] int32: (row, q_start, q_len, kind)
    table_ref,  # scalar-prefetch [R, MB] int32
    win_ref,  # scalar-prefetch [1] int32: sliding window (<= 0 = full)
    q_ref,  # [1, tq, 1, group, Dh] VMEM (one query tile, one kv head)
    k_ref,  # [1, 1, bs, Dh] VMEM (one physical pool block)
    v_ref,  # [1, 1, bs, Dh] VMEM
    *rest,  # quant: (ks_ref, vscale_ref, o_ref, scratch...) else (o_ref, ...)
    bs: int,
    MB: int,
    tq: int,
    group: int,
    scale: float,
    softcap: float | None,
    quant: bool = False,
):
    del table_ref  # physical placement is the index maps' concern
    if quant:
        ks_ref, vscale_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vscale_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    g = pl.program_id(0)
    kv = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    q_start = meta_ref[g, 1]
    q_len = meta_ref[g, 2]  # 0 = dead (launch-padding) tile
    win = win_ref[0]
    rows = tq * group
    Dh = q_ref.shape[-1]
    first, needed = _ragged_live_range(q_start, q_len, bs=bs, MB=MB, win=win)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full((rows, 1), _NEG, jnp.float32)
        l_ref[:] = jnp.zeros((rows, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((rows, Dh), jnp.float32)

    @pl.when((q_len > 0) & (j >= first) & (j < needed))
    def _():
        # Row r of the tile is (local query t = r // group, head g = r %
        # group); its absolute position is q_start + t — the SAME GQA
        # row-folding as the decode kernel, with tq queries per tile
        # instead of one.
        q = q_ref[0].reshape(rows, Dh).astype(jnp.float32) * scale
        ks = k_ref[0, 0].astype(jnp.float32)  # [bs, Dh]
        vs = v_ref[0, 0].astype(jnp.float32)
        if quant:
            ks = ks * scale_column(ks_ref, kv, bs)
            vs = vs * scale_column(vscale_ref, kv, bs)
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, bs]
        if softcap is not None:  # Gemma-2 logit capping, pre-mask (HF order)
            s = softcap * jnp.tanh(s / softcap)
        t_local = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) // group
        q_pos = q_start + t_local
        kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        mask = (t_local < q_len) & (kv_pos <= q_pos)
        mask &= (win <= 0) | (kv_pos > q_pos - win)
        s = jnp.where(mask, s, _NEG)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)  # first block: exp(_NEG - _NEG) == 1
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == n_j - 1)
    def _():
        l = l_ref[:]
        l = jnp.where(l == 0.0, 1.0, l)  # padding rows are fully masked
        o_ref[0] = (
            (acc_ref[:] / l).reshape(tq, 1, group, Dh).astype(o_ref.dtype)
        )


@functools.partial(
    jax.jit, static_argnames=("interpret", "window", "scale", "softcap")
)
def ragged_paged_attend(
    q: jnp.ndarray,
    pool_k,
    pool_v,
    table: jnp.ndarray,
    meta: jnp.ndarray,
    window_dyn: jnp.ndarray | None = None,
    *,
    window: int | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Mixed prefill + decode GQA attention over the (already updated)
    block pool — one launch for rows of ARBITRARY per-row length.

    q [W, H, Dh]: the flat query-token axis — every row's tokens laid out
    back to back at query-tile granularity (tq = W // meta.shape[0]); a
    prefill row contributes its chunk, a decode row one token.
    pool_k/v [N, KV, bs, Dh] (one layer's pool slice) — or
    ops/kv_quant.KVQuant leaves (int8 blocks + per-(token, head) fp32
    scales), dequantized in the block prologue.
    table [R, MB] int32 physical block ids, one row per fleet row.
    meta [G, 4] int32 per-tile metadata (row, q_start, q_len, kind), the
    host-built launch plan (engine/paged.build_ragged_meta): q_start is
    the tile's first ABSOLUTE position, q_len its valid queries (0 =
    launch-padding tile — its row/q_start repeat the predecessor's so the
    clamped KV index repeats and Pallas skips the DMA), kind is
    RAGGED_PREFILL / RAGGED_DECODE (launch accounting; the math is
    uniform — a decode row is simply q_len == 1 at its own position).
    window / window_dyn / scale / softcap: as `paged_flash_attend`.
    Returns [W, H, Dh] in q.dtype: each query token's attention output
    over its row's KV prefix (positions 0..q_pos through the block
    table), which is exactly the bucketed scratch prefill's per-token
    contract — so one compiled program replaces the whole bucket ladder.
    """
    from .kv_quant import KVQuant

    quant = isinstance(pool_k, KVQuant)
    if quant:
        pool_k, k_scale = pool_k.q, pool_k.s
        pool_v, v_scale = pool_v.q, pool_v.s
    W, H, Dh = q.shape
    G = meta.shape[0]
    tq = W // G
    assert tq * G == W, "flat query axis must be a whole number of tiles"
    KV, bs = pool_k.shape[1], pool_k.shape[2]
    MB = table.shape[1]
    group = H // KV

    interpret = resolve_interpret(interpret)

    q5 = q.reshape(G, tq, KV, group, Dh)
    table = table.astype(jnp.int32)
    meta = meta.astype(jnp.int32)
    if window_dyn is None:
        win_arr = jnp.full((1,), window if window is not None else -1, jnp.int32)
    else:
        win_arr = jnp.reshape(window_dyn.astype(jnp.int32), (1,))

    def kv_index(g, kv, j, meta_ref, table_ref, win_ref):
        # Clamp dead logical blocks to the tile's live range; pad tiles
        # (q_len == 0) share their predecessor's (row, q_start), so their
        # whole walk repeats the previous tile's physical indices and
        # Pallas skips every DMA. The kernel's pl.when gate skips the
        # compute either way.
        first, needed = _ragged_live_range(
            meta_ref[g, 1], meta_ref[g, 2], bs=bs, MB=MB, win=win_ref[0]
        )
        row = jnp.maximum(meta_ref[g, 0], 0)
        return (table_ref[row, jnp.clip(j, first, needed - 1)], kv, 0, 0)

    def scale_index(g, kv, j, meta_ref, table_ref, win_ref):
        # the quant-scale operands [N, KV, bs]: same table walk, every kv
        # head in the block (ops/flash_attention.scale_column)
        return (kv_index(g, kv, j, meta_ref, table_ref, win_ref)[0], 0, 0)

    kernel = functools.partial(
        _ragged_kernel,
        bs=bs,
        MB=MB,
        tq=tq,
        group=group,
        scale=scale if scale is not None else Dh**-0.5,
        softcap=softcap,
        quant=quant,
    )
    rows = tq * group
    in_specs = [
        pl.BlockSpec(
            (1, tq, 1, group, Dh),
            lambda g, kv, j, meta_ref, table_ref, win_ref: (g, 0, kv, 0, 0),
        ),
        pl.BlockSpec((1, 1, bs, Dh), kv_index),
        pl.BlockSpec((1, 1, bs, Dh), kv_index),
    ]
    operands = [q5, pool_k, pool_v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, KV, bs), scale_index),
            pl.BlockSpec((1, KV, bs), scale_index),
        ]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G, KV, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, tq, 1, group, Dh),
            lambda g, kv, j, meta_ref, table_ref, win_ref: (g, 0, kv, 0, 0),
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
        out_shape=jax.ShapeDtypeStruct((G, tq, KV, group, Dh), q.dtype),
        interpret=interpret,
    )(meta, table, win_arr, *operands)
    return out.reshape(W, H, Dh)
