"""Pallas TPU paged attention over the block pool: the decode kernel
(`paged_flash_attend`) and the mixed prefill + decode kernel
(`ragged_paged_attend`).

The two paged kernels are ONE kernel body under two wrappers. A program
of the grid is one row's work: a decode slot's single query, or one query
tile of the mixed launch's flat token axis (times a number of KV-head
groups where the working set of all heads would not fit the VMEM
budget). The pool stays in HBM ([N, KV, bs, Dh] a layer); the program
walks its row's block table itself:

  * `first, needed` bound the row's LIVE logical blocks: the causal
    frontier above, the sliding window below (static, or a traced
    per-layer width riding as a scalar-prefetch operand — Gemma-2/3);
  * a loop step covers a COMPUTE BLOCK of P consecutive logical blocks
    (pages) of the row, `first + i * P ..`: `lax.fori_loop(0,
    ceil((needed - first) / P), ...)`. Page `table[row, j]` — the slab of
    all the group's KV heads, contiguous in that layout — is one copy of
    its own (the table scatters the pages) into its P-th of one half of a
    two-slot VMEM buffer [2, KVg, P x bs, Dh]; a step starts the NEXT
    compute block's live pages before it waits for its own (scalar loops
    over the live pages: a step's code does not grow with P). Only live
    pages are copied: a page at or past `needed` starts no DMA, so the
    walk reads from HBM what a walk of one page a step reads. An int8
    pool's scale slabs (ops/kv_quant) walk the same loop, at P = 1;
  * the compute block's P x bs positions of every head fold into the
    online-softmax accumulators in ONE batched matmul pair, one max / exp
    / sum and one rescale: K and V raised to float32, float32 scores,
    softcap before the mask, float32 accumulators, output in q.dtype. The
    step's fixed cost (the waits, the chain score -> max -> exp -> sum ->
    value product -> rescale, each stage waiting for the one before) is
    paid once per P pages; (KVg, P) come from the shapes alone
    (`_walk_shape`), P = 1 being the same body;
  * a row that holds nothing (a launch-padding tile, a decode slot whose
    `active` flag is false) copies nothing and loops zero times; its
    output is zeros, which the caller discards;
  * a SELECTED read (`pages`: models/minicpm_sala.py) walks a LIST of the
    row's pages a KV head in place of the range. Its pages are 32 KB, so
    its step is its own (`_walk_kernel`, `fold_listed`): what such a page
    costs is a descriptor and the fold's vector work, not bytes.

The step programs hand the kernels the pool WHOLE, stacked over its layers
([L, N, KV, bs, Dh], a donated loop carry) with the layer to read and the
launch's new K/V rows, and the kernel writes those rows itself: the block
a new token falls in is in VMEM for the walk anyway, so the program
patches the rows into its copy and sends the touched sublane tiles back
through the pool's aliased output (`input_output_aliases`). XLA then sees
one buffer that only this custom call touches: no scatter whose preferred
layout differs from the kernel's operand layout, hence no copy of the pool
or of a layer's slice anywhere in a step (PERF.md, PR 29). Shapes that are
not whole tiles keep the older form: XLA scatters into one layer's slice
and the kernel reads that slice (`writes_in_place`).

So the device's work follows the rows' live blocks, not slots x KV heads
x table width. What the walk covers is counted on the host by
engine/continuous._kv_walk (the launch record's `kv_grid_tokens`, and
with `walk_pages_per_step` its `kv_walk_steps`), which repeats
`_ragged_live_range`'s arithmetic in numpy;
tests/test_launch_record.py holds the two together. Times on the chip are
in PERF.md (section 6, PR 25) and the ledger, not here.

Contract (matches `engine/paged.make_paged_hook`'s gather path): the mask
is derived IN-KERNEL from the positions and the window: a query at
position p attends logical positions max(0, p - win + 1) .. p. Score
scale and Gemma-2 softcapping are static kernel parameters. GQA is folded
into the query-row dimension exactly like ops/flash_attention.py: the
score matmul of one KV head is [queries x group, Dh] x [Dh, bs].

The values may be narrower than the keys (pool_v [.., Dv], pool_k
[.., Dk]: models/mimo_v2.py keeps keys of 192 numbers on 256 lanes and
values of 128): each leaf's slabs, new rows and buffers take the leaf's own
width, and the output is Dv wide. `sink` [H] float32 is a learned logit a
query head that joins the softmax's denominator and brings no value: the
online softmax's running maximum starts at the head's sink logit, its
denominator at 1 and its accumulator at 0, which IS the softmax over the
scores and the sink with the sink's value left out; it costs no column.
Absent, the call is what it was before the sink existed.

On non-TPU backends the kernels run in interpret mode (the CPU test
suite); numerics match the gather path to fp32 tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)  # mask fill; avoids inf-inf NaNs


# -- the paged walk: decode rows and mixed query tiles, one kernel body -------
#
# The flat query axis of a mixed launch holds every row's tokens back to
# back at query-tile granularity (a prefill row contributes its chunk, a
# decode row one token); a per-tile metadata array carries (row, start,
# length, kind). A decode launch is the same thing with one query per
# tile. The TPU "Ragged Paged Attention" kernel (PAPERS.md) is the design
# source; the flash accumulation discipline is shared with
# ops/flash_attention.py.

RAGGED_PREFILL = 0  # metadata `kind`: a prompt-chunk row (length >= 1)
RAGGED_DECODE = 1  # metadata `kind`: a single-token decode row

# VMEM one program's working set may take (_walk_shape counts it and sizes
# the KV-head group and the compute block by it): inside the default scoped
# limit, 16 MiB on v5e, with room for the query and output blocks' second
# buffers.
_WALK_VMEM_BYTES = 12 * 2**20


def _ragged_live_range(q_start, q_len, *, bs: int, MB: int, win):
    """(first, needed) logical-block bounds for a query tile starting at
    absolute position q_start with q_len valid queries: blocks
    [first, needed) hold at least one position some query of the tile
    attends. A tile that holds nothing (q_len == 0) evaluates with an
    effective length of 1 to keep the clips sane; the kernel walks
    nothing for it. `win` is a TRACED scalar (<= 0 = full causal):
    per-layer window patterns (Gemma-2/3) feed each scan step's width
    through one compiled kernel, same contract as
    ops/flash_attention._first_tile."""
    last = q_start + jnp.maximum(q_len, 1) - 1
    needed = jnp.clip(pl.cdiv(last + 1, bs), 1, MB)
    first = jnp.where(
        win > 0,
        jnp.minimum(jnp.maximum(q_start - win + 1, 0) // bs, needed - 1),
        0,
    )
    return first, needed


# What one loop step folds at most: pages (a step's copies, waits and dead-
# page tests are unrolled, P of each: pages of 16 tokens would make 64),
# positions (past this the score tiles outgrow the slabs), and bytes of K/V
# slabs in the pool's dtype. A step's
# fixed cost is 0.3-0.5 us; a step that carries 1-1.5 MB copies for 1.3-1.9 us
# at 819 GB/s, and a larger one only adds to what the last step folds for
# nothing (its dead pages: half a compute block on average) and to the
# temporaries (PERF.md section 6, PR 45: lfm2's decode row 56 us at 1 MB a
# step, 62 at 2 MB; sdar's mixed launch 116 / 133; mistral's 231 / 244).
_WALK_STEP_PAGES = 8
_WALK_STEP_TOKENS = 1024
_WALK_STEP_BYTES = 3 * 2**19
# A LISTED step (the selected read, `pages > 0` below) folds up to 32 pages
# and 2,048 positions: its pages are one KV head's (32 KB at 64 tokens of 128
# numbers, where the range walk's are 0.5-2 MB), its copies are straight-line
# code with one wait a leaf whatever P is, and its masks do not grow with P,
# so what a step pays beside its pages (0.6 us: the waits, the running max /
# sum / accumulator's rescale on 128 score rows, the branches of the in-place
# write) is halved with every doubling (PERF.md section 6, PR 52, the same
# kernel at sala-docs-xlong's shapes: 0.150 us a page of a prompt tile's union
# at 8 pages a step, 0.103 at 16, 0.093 at 32; a decode row's 0.110 / 0.088 /
# 0.075). At 32 pages of 64 tokens the working set is 9.4 of the 12 MiB.
_LIST_STEP_PAGES = 32
_LIST_STEP_TOKENS = 2048


def _walk_shape(KV: int, bs: int, Dh: int, itemsize: int, quant: bool,
                rows: int, MB: int, latent: bool = False,
                listed: bool = False) -> tuple[int, int]:
    """(KVg, P) of one program's walk, from the shapes and the stated
    count alone. KVg, the KV heads a program folds at a time: the largest
    divisor of KV whose working set at one page a step stays inside
    _WALK_VMEM_BYTES as VMEM tiles it (sublanes 32 / itemsize, 128 lanes).
    Then P, the pages a loop step folds (the compute block): the largest
    power of two, at most MB and _WALK_STEP_PAGES pages, _WALK_STEP_TOKENS
    positions and _WALK_STEP_BYTES of the KVg heads' K and V slabs (of K alone, which
    is all the latent form copies, where `latent`), at which the working
    set still fits. Per head: the K and V slabs of P pages, each held
    twice in the pool's dtype and once in float32, and per query row the
    float32 query, accumulator, running max and sum, and the compute
    block's scores and probabilities. An int8 pool walks a page a step:
    its scale slabs hold the tokens on lanes, and blocks under 128 tokens
    would not lie side by side there. listed: the selected read's walk of a
    page LIST: a program is ONE KV head (each chose its own pages), a step
    folds up to _LIST_STEP_PAGES of them and _LIST_STEP_TOKENS positions,
    and the working set holds the table that spreads a query's choices over
    a step's columns (`_walk_kernel`: 128 entries x 128 / P steps x P x bs
    columns)."""
    if listed:
        KV = 1

    def up(n, m):
        return -(-n // m) * m

    def head(P):
        lanes, toks = up(Dh, 128), up(P * bs, 128)
        n = 4 * up(P * bs, 32 // itemsize) * lanes * itemsize
        n += 2 * up(P * bs, 8) * lanes * 4
        n += up(rows, 8) * 4 * (2 * lanes + 2 * 128 + 3 * toks)
        if quant:  # a [heads, bs] float32 scale slab beside each int8 slab
            n += 4 * toks * 4
        if listed:
            n += 128 * (128 // P) * toks * 2
        return n

    fit = max(1, _WALK_VMEM_BYTES // head(1))
    # a [heads, bs] scale slab is cut from [KV, bs] along float32 sublanes
    step = 8 if quant else 1
    KVg = max(
        (d for d in range(1, min(KV, fit) + 1)
         if KV % d == 0 and (d % step == 0 or d == KV)),
        default=KV,
    )
    page = KVg * bs * up(Dh, 128) * itemsize * (1 if latent else 2)
    P = 1
    most, tokens = ((_LIST_STEP_PAGES, _LIST_STEP_TOKENS) if listed else
                    (_WALK_STEP_PAGES, _WALK_STEP_TOKENS))
    while (not quant and 2 * P <= min(MB, most)
           and 2 * P * bs <= tokens
           and 2 * P * page <= _WALK_STEP_BYTES
           and KVg * head(2 * P) <= _WALK_VMEM_BYTES):
        P *= 2
    return KVg, P


def walk_pages_per_step(leaf, n_heads: int, tq: int, MB: int,
                        latent: bool = False, listed: bool = False) -> int:
    """P of the walk over pool leaf `leaf` ([..., KV, bs, Dh], or
    ops/kv_quant's int8 pair; `latent`: a pool of latent rows, no V) for
    tiles of tq queries of n_heads heads under a table MB pages wide: what
    `_paged_walk` gives its kernel, for the host's count of loop steps
    (engine/continuous: `kv_walk_steps`). listed: the selected read's walk,
    a program a KV head."""
    from .kv_quant import KVQuant

    quant = isinstance(leaf, KVQuant)
    a = leaf.q if quant else leaf
    KV, bs, Dh = a.shape[-3:]
    return _walk_shape(KV, bs, Dh + -Dh % 128, a.dtype.itemsize, quant,
                       tq * (n_heads // KV), MB, latent, listed)[1]


def _walk_kernel(
    meta_ref,  # scalar-prefetch [G, 4] int32: (row, q_start, q_len, kind)
    table_ref,  # scalar-prefetch [R, MB] int32
    win_ref,  # scalar-prefetch [1] int32: sliding window (<= 0 = full);
    # [2] with `write`: and the layer of the stacked pool
    *rest,  # ([plist_ref, count_ref: `pages`' scalar-prefetch operands,]
    # q_ref [1, tq, KVg, group, Dh] VMEM: one query tile, one head group,
    # [sel_ref: `pages` and tq > 1,] [new K, V rows,] the pool leaves in
    # HBM, o_ref, [the pool's aliased outputs,] scratch...)
    bs: int,
    MB: int,
    tq: int,
    KVg: int,
    P: int,
    group: int,
    scale: float,
    softcap: float | None,
    quant: bool,
    latent: int = 0,
    write: bool = False,
    block: int = 0,
    pages: int = 0,
    sink: bool = False,
):
    """One program: query tile g (tq queries of one row; a decode slot is
    a tile of one) against head group hg's KVg KV heads. The walk over
    the row's live pages is the fori_loop below, P pages (a compute block)
    a step; every head of the compute block's slabs folds in one batched
    matmul pair. Row r of a head's score tile is (local query t = r //
    group, query head r % group of the KV head), its absolute position
    q_start + t; column c is position (first + i * P) * bs + c of the row.

    Compute block i holds logical pages first + i * P .. + P - 1. Its
    pages at or past `needed` (the last step's tail) are DEAD: they start
    no copy, their positions lie past every query's frontier and are
    masked like any other, and their rows of the VALUE operand (V; the K
    rows in latent form) are ZEROED in VMEM before the fold, because
    uninitialised VMEM may hold NaN and 0 x NaN is NaN. Dead K rows only
    reach scores the mask replaces.

    The pool leaves are one layer's slices, k_hbm / v_hbm [N, KV, bs, Dh]
    (an int8 pool: and ks_hbm / vs_hbm [N, KV, bs]; P = 1 there), already
    holding the launch's tokens; or, with `write`, the STACKED pool
    [L, N, KV, bs, Dh] that does not hold them yet: the tile's q_len
    tokens, positions q_start .. q_start + q_len - 1 of the row, arrive as
    new_refs [1, tq, KVg, 1, Dh] and this program puts them where the
    table says. A page they fall in (one, or the next too where the tile
    straddles a page edge, inside a compute block or across two) is
    patched in VMEM once its copy has landed, and its touched sublane
    tiles go back to HBM while the compute block folds; the step waits
    for them before its buffer half is filled again. The grid runs in
    order on the one core, so a later tile of the row reads what an
    earlier one wrote; the pool is read and written through its aliased
    OUTPUT refs (in interpret mode the inputs are copies).

    latent > 0 is the latent (MLA, absorbed) form: the pool holds one row
    [c | k_r | pad] a token and there is no V pool; scores run over the
    whole row, values are its first `latent` numbers, so one DMA serves
    both, and the output is `latent` wide.

    block > 0 is the block-diffusion mask (cfg.diffusion_block): a query
    attends every position up to the END of its own block of `block`
    tokens (ops/attention.block_frontier). The walk's bounds stay the
    causal ones: the engine cuts every tile at a multiple of `block`, so a
    tile's last block ends with the tile, inside the rows `patch` puts
    into the VMEM copy before the fold.

    pages > 0 is the SELECTED read (models/minicpm_sala.py): the program
    walks a LIST of the row's logical pages in place of the range
    first .. needed: plist_ref [G, KV, pages] int32, ascending, the first
    count_ref [G, KV] of them live, one list a tile and KV head (KVg is 1:
    each KV head chose its own pages). Compute block i holds list entries
    i * P .. + P - 1; an entry's positions are its logical page's. A tile
    of several queries walks the union of their choices and sel_ref
    [1, 1, pages / 128, rows, 128] (1.0 where score row r's query chose
    list entry 128 * a + b) masks per query what it did not choose; a tile
    of one query chose its whole list. The tile's own pages (where its new
    rows fall) are the list's last entries: every query's forced window
    holds them. A list has no window beside it (the list is the window).

    A listed page is one KV head's K and V, 2 x 16 KB at 64 tokens of 128
    numbers, and what it costs is not its bytes (PERF.md section 6, PR 52;
    the kernels alone at sala-docs-xlong's shapes): a copy's descriptor
    holds the core some 20 ns whatever it moves (half a page a descriptor
    took the time of a whole one, K alone two thirds of K and V), and the
    fold of 128 score rows is vector work a step (0.55 us of a step of 8
    pages with no product in it) that no copy hides. So the listed step
    (`fold_listed`) is built to pay each once: its copies are straight-line
    code, all P of them whatever is live (a dead entry copies the last live
    page again, masked) with ONE wait a leaf for a half's bytes, the next
    step's started in quarters between this step's phases and the last
    step the same code without them (no test of "is there a next step" in
    the loop); the positions of a step's columns are ONE row built from P
    scalars, compared against a column of frontiers; the 0 / 1 matrix that
    carries a score row's choices from list entries to columns is read from
    a table written once a call; a masked score is -inf, so no second
    select; and a step folds 32 pages (`_walk_shape`, listed). Read there: a
    page of a prompt tile's union of 192 cost 0.170 us and costs 0.093, a
    decode row's 0.129 and 0.075 with 16 rows live (0.21 / 0.16 with 2)."""
    n = 1 if latent else (4 if quant else 2)  # pool leaves
    plist_ref = count_ref = sel_ref = None
    if pages:
        plist_ref, count_ref, *rest = rest
    q_ref, *rest = rest
    sink_ref = None
    if sink:  # [KVg, rows, 1] float32: a score row's sink logit
        sink_ref, *rest = rest
    if pages and tq > 1:
        sel_ref, *rest = rest
    new_refs, rest = (rest[:n], rest[n:]) if write else ((), rest)
    srcs, o_ref, rest = rest[:n], rest[n], rest[n + 1:]
    if write:
        srcs, rest = rest[:n], rest[n:]
    m_ref, l_ref, acc_ref, sem, *bufs = rest
    spread_ref = bufs.pop() if sel_ref is not None else None
    pos_ref = bufs.pop() if pages else None
    wsem = bufs.pop() if write else None
    kbuf = bufs[0]
    vbuf = None if latent else bufs[1]
    if quant:
        # int8 pool (ops/kv_quant): per-(token, head) fp32 scales walk the
        # same loop as two more slabs, tokens on lanes
        ksbuf, vsbuf = bufs[2:]
    g = pl.program_id(0)
    hg = pl.program_id(1)
    row = jnp.maximum(meta_ref[g, 0], 0)
    q_start = meta_ref[g, 1]
    q_len = meta_ref[g, 2]  # 0 = the row holds nothing: walk nothing
    win = win_ref[0]
    layer = (win_ref[1],) if write else ()
    rows = tq * group
    Dh = q_ref.shape[-1]
    first, needed = _ragged_live_range(q_start, q_len, bs=bs, MB=MB, win=win)
    if pages:
        first, needed = 0, count_ref[g, hg]
    needed = jnp.where(q_len > 0, needed, first)

    def logical(j):
        # the row's logical page that walk index j stands for
        if pages:
            return plist_ref[g, hg, jnp.minimum(j, pages - 1)]
        return j

    if sink:  # the softmax already holds one term, exp(sink - sink) == 1
        m_ref[:] = sink_ref[:]
        l_ref[:] = jnp.ones(l_ref.shape, jnp.float32)
    else:
        m_ref[:] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    def page(buf, slot, p):
        # page p of buffer half `slot`: rows p * bs .. of every head (an
        # int8 pool's scale slabs are whole: P = 1 there)
        if P == 1:
            return buf.at[slot]
        return buf.at[slot, :, pl.ds(pl.multiple_of(p * bs, bs), bs)]

    def for_pages(lo, hi, body):
        # body(p) for pages lo <= p < hi of a compute block (traced bounds):
        # a scalar loop, so a step's code does not grow with P
        if P == 1:
            pl.when(lo < hi)(lambda: body(0))
        else:
            jax.lax.fori_loop(lo, hi, lambda p, c: (body(p), c)[1], 0)

    def copies(j, slot, p):
        # logical page j of the row -> page p of buffer half `slot`: the
        # head group's slab of the physical block, one contiguous run of
        # HBM per pool. A half's pages share its semaphore.
        blk = table_ref[row, logical(j)]
        return [
            pltpu.make_async_copy(
                src.at[(*layer, blk, pl.ds(hg * KVg, KVg))],
                page(buf, slot, p), sem.at[i, slot],
            )
            for i, (src, buf) in enumerate(zip(srcs, bufs))
        ]

    def start_block(j0, slot):
        """Starts the copies of compute block j0 .. j0 + P - 1's live
        pages into buffer half `slot`."""
        def start(p):
            for c in copies(j0 + p, slot, p):
                c.start()

        for_pages(0, jnp.clip(needed - j0, 0, P), start)

    # `write`: the sublane tiles of a page that go back to HBM. The new
    # rows of a page are at most tq consecutive ones, so a static span of
    # whole tiles holds them wherever they start; the tile's rows fall in
    # `touched` consecutive pages at most.
    sub = 32 // kbuf.dtype.itemsize  # rows of one (sublane, 128-lane) tile
    span = min(bs, sub * (pl.cdiv(tq, sub) + 1))
    touched = pl.cdiv(tq - 1, bs) + 1

    def rows_of(j, j0, start):
        # rows start .. start + span of page j, where the buffer half that
        # holds the compute block from j0 has them
        return pl.ds(pl.multiple_of((j - j0) * bs + start, sub), span)

    def put_back(j, j0, slot, start):
        blk = table_ref[row, logical(j)]
        return [
            pltpu.make_async_copy(
                buf.at[slot, :, rows_of(j, j0, start)],
                dst.at[(*layer, blk, pl.ds(hg * KVg, KVg),
                        pl.ds(start, span))],
                wsem.at[i],
            )
            for i, (dst, buf) in enumerate(zip(srcs, bufs))
        ]

    def patch(j, j0, slot):
        """The tile's new rows that fall in page j of the compute block
        from j0, into the page's VMEM copy: only the span that goes back
        is touched. Returns the span's first row in the page."""
        r0 = q_start - logical(j) * bs  # page row of the tile's first token
        start = 0
        if span < bs:
            start = pl.multiple_of(
                jnp.clip(r0, 0, bs - span) // sub * sub, sub
            )
        for new_ref, buf in zip(new_refs, bufs):
            ix = jax.lax.broadcasted_iota(
                jnp.int32, (span, buf.shape[3]), 0) + (start - r0)
            rows_ = (slot, slice(None), rows_of(j, j0, start))
            cur = buf[rows_].astype(jnp.float32)  # [KVg, span, Dh]
            for t in range(tq):
                new = new_ref[0, t].astype(jnp.float32)  # [KVg, 1, Dh]
                cur = jnp.where(((ix == t) & (t < q_len))[None], new, cur)
            buf[rows_] = cur.astype(buf.dtype)
        return start

    def start_listed(j0, slot, part=range(P)):
        """Starts the copies of list entries j0 + p, p in `part`, into
        buffer half `slot`, side by side (no loop, no branch, and no dead-
        page test: an entry at or past `needed` copies the last live page
        again, real rows of the row that are masked like any dead entry's).
        Over a step every page of a half is started, so the half is always
        whole and `wait_listed` is one wait a leaf. The copies are unrolled
        where the kernel is lowered, not by Python: a body traced P times a
        call site cost the server's start 10 s (PERF.md section 6, PR 52)."""
        def start(p, carry):
            for c in copies(jnp.minimum(j0 + p, needed - 1), slot, p):
                c.start()
            return carry

        if part:
            jax.lax.fori_loop(part.start, part.stop, start, 0, unroll=True)

    def wait_listed(slot):
        # a half's 2 P copies signal one semaphore a leaf, by their bytes:
        # one wait for the half's bytes
        for i, buf in enumerate(bufs):
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[i, slot]).wait()

    if pages:
        pl.when(needed > 0)(lambda: start_listed(0, 0))
    else:
        start_block(first, 0)

    # the tile's queries, KV heads first: [KVg, rows, Dh]
    q = q_ref[0]
    q = q[0] if tq == 1 else jnp.swapaxes(q, 0, 1)
    q = q.reshape(KVg, rows, Dh).astype(jnp.float32) * scale
    t_local = jax.lax.broadcasted_iota(jnp.int32, (rows, P * bs), 0) // group
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, P * bs), 1)
    q_pos = q_start + t_local
    if block:  # the last position of the query's own block
        q_end = (q_pos // block + 1) * block - 1
    else:
        q_end = q_pos
    heads = ((0,), (0,))  # dot_general batch dims: the slab's KV heads
    value_buf = kbuf if latent else vbuf

    def fold_block(i, carry):
        slot = i % 2
        j0 = first + i * P
        start_block(j0 + P, 1 - slot)
        live = jnp.minimum(needed - j0, P)  # >= 1: pages that land

        def wait(p):
            for c in copies(j0 + p, slot, p):
                c.wait()

        def zero(p):  # a dead page: nothing lands, the value rows read 0
            dead = page(value_buf, slot, p)
            dead[...] = jnp.zeros(dead.shape, dead.dtype)

        for_pages(0, live, wait)
        if P > 1:
            for_pages(live, P, zero)

        if write:
            # the pages of this compute block that hold new rows
            news = []
            for k in range(touched):
                at = q_start // bs + k  # the logical page
                j = at
                news.append((j, (j >= j0) & (j < jnp.minimum(j0 + P, needed))
                             & (at * bs < q_start + q_len)))
            for j, has_new in news:
                @pl.when(has_new)
                def _():
                    for c in put_back(j, j0, slot, patch(j, j0, slot)):
                        c.start()

        kv_pos = j0 * bs + col
        mask = (t_local < q_len) & (kv_pos <= q_end)
        mask &= (win <= 0) | (kv_pos > q_pos - win)
        ks = kbuf[slot].astype(jnp.float32)  # [KVg, P x bs, Dh]
        vs = ks[:, :, :latent] if latent else vbuf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, ks, (((2,), (2,)), heads), preferred_element_type=jnp.float32
        )  # [KVg, rows, P x bs]
        if quant:
            # a token's scale is common to its Dh products, so it scales
            # the score (and below the probability) with the tokens on
            # lanes as the slab holds them: q . (k * s) == (q . k) * s
            s = s * ksbuf[slot, :, pl.ds(0, bs)][:, None, :]
        if softcap is not None:  # Gemma-2 logit capping, pre-mask (HF order)
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, _NEG)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)  # first block: exp(_NEG - _NEG) == 1
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        if quant:
            p = p * vsbuf[slot, :, pl.ds(0, bs)][:, None, :]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, vs, (((2,), (1,)), heads), preferred_element_type=jnp.float32
        )
        if write:
            # before the half is filled again, and before a later program
            # of the row reads the page
            for j, has_new in news:
                @pl.when(has_new)
                def _():
                    for c in put_back(j, j0, slot, 0):
                        c.wait()

        return carry

    # -- the selected read's step (pages > 0) --------------------------------
    if pages:
        assert KVg == 1 and not (quant or latent)
        # the positions of a step's columns are built 128 lanes at a time (a
        # page of 128 tokens or more: a page at a time) from the pages' first
        # positions, which are scalars: lane c of a piece is column c of its
        # `per` pages
        w = P * bs
        if 128 % bs == 0 or bs % 128 == 0:
            w = min(w, max(bs, 128))
        per = w // bs
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        lane_page, lane_off = lane // bs, lane % bs
        # a score row's frontier, [rows, 1]; a row past q_len attends nothing
        t_row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        frontier = q_start + t_row
        if block:
            frontier = (frontier // block + 1) * block - 1
        frontier = jnp.where(t_row < q_len, frontier, -1)
        if sel_ref is not None:
            # spread_ref[a][e, c] = 1 where column c of a step belongs to
            # entry e of the 128-entry part the step lies in, the step the
            # part's a-th: the 0 / 1 matrix that carries a score row's
            # choices from entries to columns through one product. It does
            # not depend on the program: written once a call (the grid runs
            # in order on the one core, and scratch outlives a program)
            @pl.when((g == 0) & (hg == 0))
            def _():
                e = jax.lax.broadcasted_iota(jnp.int32, (128, P * bs), 0)
                c = jax.lax.broadcasted_iota(jnp.int32, (128, P * bs), 1)

                def spread(a, carry):
                    spread_ref[a] = (e == a * P + c // bs).astype(
                        jnp.float32).astype(spread_ref.dtype)
                    return carry

                jax.lax.fori_loop(0, 128 // P, spread, 0)

    def fold_listed(i, prefetch: bool):
        """Step i of a listed walk: list entries i * P .. + P - 1. With
        `prefetch` the next step's copies start here, unconditionally (the
        caller knows there is a next step): a quarter of them before this
        step's wait, so that the next step's first pages are under way
        early, and a quarter each between the fold's phases. What a copy of
        16 KB costs this program is its descriptor (some 20 ns in which the
        core issues nothing else), not its bytes: the quarters let the
        copies themselves run while the fold computes (PERF.md section 6,
        PR 52, calls 5-7)."""
        slot = i % 2
        j0 = i * P
        quarter = [range(k * P // 4, (k + 1) * P // 4) for k in range(4)]

        def start_next(k):
            if prefetch:
                start_listed(j0 + P, 1 - slot, quarter[k])

        start_next(0)
        wait_listed(slot)
        if write:
            # the tile's own pages end its list
            news = []
            for k in range(touched):
                at = q_start // bs + k  # the logical page
                j = needed - 1 - ((q_start + q_len - 1) // bs - at)
                news.append((j, (j >= j0) & (j < jnp.minimum(j0 + P, needed))
                             & (at * bs < q_start + q_len)))
            for j, has_new in news:
                @pl.when(has_new)
                def _():
                    for c in put_back(j, j0, slot, patch(j, j0, slot)):
                        c.start()

        def base(p):
            # the first position of list entry j0 + p; an entry at or past
            # `needed` lies past every frontier
            return jnp.where(j0 + p < needed, logical(j0 + p) * bs, MB * bs)

        def piece(a, carry):
            b = jnp.full((1, w), base(a * per), jnp.int32)
            for k in range(1, per):
                b = jnp.where(lane_page >= k, base(a * per + k), b)
            pos_ref[:, pl.ds(pl.multiple_of(a * w, w), w)] = b + lane_off
            return carry

        jax.lax.fori_loop(0, P * bs // w, piece, 0, unroll=True)
        mask = pos_ref[:] <= frontier  # [rows, P x bs]
        if sel_ref is not None:
            # (0 / 1 in bfloat16: exact at one pass, whatever precision the
            # process asks of its float32 products)
            mask &= jnp.dot(
                sel_ref[0, 0, j0 // 128], spread_ref[j0 % 128 // P],
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32) > 0.5
        start_next(1)
        ks = kbuf[slot].astype(jnp.float32)  # [1, P x bs, Dh]
        s = jax.lax.dot_general(
            q, ks, (((2,), (2,)), heads), preferred_element_type=jnp.float32
        )  # [1, rows, P x bs]
        start_next(2)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # the running max starts at _NEG and never falls, so a masked score
        # of -inf leaves exp(-inf - m) == 0 with no second select
        s = jnp.where(mask, s, -jnp.inf)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        start_next(3)
        vs = vbuf[slot].astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, vs, (((2,), (1,)), heads), preferred_element_type=jnp.float32
        )
        if write:
            for j, has_new in news:
                @pl.when(has_new)
                def _():
                    for c in put_back(j, j0, slot, 0):
                        c.wait()

    steps = pl.cdiv(needed - first, P)
    if pages:
        # every step but the last starts its successor's copies; the last is
        # the same code without them
        jax.lax.fori_loop(
            0, steps - 1, lambda i, c: (fold_listed(i, True), c)[1], 0)
        pl.when(steps > 0)(lambda: fold_listed(steps - 1, False))
    else:
        jax.lax.fori_loop(0, steps, fold_block, 0)

    l = l_ref[:]
    l = jnp.where(l == 0.0, 1.0, l)  # padding queries, rows not walked
    Dv = acc_ref.shape[-1]
    o = (acc_ref[:] / l).astype(o_ref.dtype).reshape(KVg, tq, group, Dv)
    o_ref[0] = o.reshape(1, KVg, group, Dv) if tq == 1 else o.swapaxes(0, 1)


def _lanes(a):
    """`a` with its minor dimension zero-padded to whole 128-lane tiles.
    A manual DMA cannot slice an HBM operand whose minor dimension is not
    (Mosaic: "Slice shape ... must be aligned to tiling (128)"), so a head
    dim under 128 costs a padded copy of the layer's pool slice per call,
    and 16- or 32-token blocks one of an int8 pool's scales. The head
    dims and block size the benchmark's cells run (128, 128) pad nothing.
    Zero lanes add nothing to a score and their output lanes are cut."""
    pad = -a.shape[-1] % 128
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)]) if pad else a


def writes_in_place(leaf) -> bool:
    """Whether the kernels can take this pool leaf ([..., KV, bs, Dh], of
    a layer or stacked) whole and write a launch's tokens into it
    themselves: a raw-dtype leaf (not ops/kv_quant's int8 pair) whose head
    dim is whole 128-lane tiles and whose blocks are whole sublane tiles,
    so a block's touched tiles go back to HBM by aligned DMAs. Every
    benchmark cell is (head dim 128 or a 640-number latent row, 128-token
    blocks). Otherwise (chip_smoke.py's TinyLlama, head dim 64; an int8
    pool) the caller scatters into one layer's slice and passes that: the
    padded copy `_lanes` makes is then one layer's, never the pool's."""
    from .kv_quant import KVQuant

    return (not isinstance(leaf, KVQuant) and leaf.shape[-1] % 128 == 0
            and leaf.shape[-2] % (32 // leaf.dtype.itemsize) == 0)


def _paged_walk(q, pool_k, pool_v, table, meta, window, window_dyn, *,
                scale, softcap, interpret, value_dim=None, write=None,
                block=0, pages=None, sink=None):
    """The pallas_call both wrappers share. q [G, tq, H, Dh]: G query
    tiles of tq queries; meta [G, 4]. pool_v None is the latent form.
    write None: the pool leaves are one layer's slices and hold the
    launch's tokens; returns the output, q's shape and dtype. write
    (layer, new_k, new_v): the leaves are the stacked pool, new_k / new_v
    [G, tq, KV, Dh] the launch's rows, which the kernel writes (see
    `_walk_kernel`); returns (output, pool_k, pool_v), the pool updated in
    place (donate it). pages (plist [G, KV, L], count [G, KV], chosen
    [G, tq, KV, L] bool or None): the selected read; L a multiple of 128.
    Absent, the call is what it was before the list existed. sink [H]: the
    module docstring's; the K leaf may be wider than the V leaf, and q is
    as wide as the K leaf."""
    from .kv_quant import KVQuant

    latent = pool_v is None
    quant = isinstance(pool_k, KVQuant)
    # the values' width before any pad to whole lane tiles
    pool_v_width = None if latent else (
        pool_v.q if quant else pool_v).shape[-1]
    leaves = [pool_k] if latent else [pool_k, pool_v]
    if quant:
        leaves = [pool_k.q, pool_v.q, _lanes(pool_k.s), _lanes(pool_v.s)]
    n = len(leaves)
    G, tq, H, Dh = q.shape
    KV, bs = leaves[0].shape[-3:-1]
    group = H // KV
    MB = table.shape[1]
    if window_dyn is None:
        scalars = jnp.full((1,), -1 if window is None else window, jnp.int32)
    else:
        scalars = jnp.reshape(window_dyn.astype(jnp.int32), (1,))
    news = []
    if write is not None:
        assert all(writes_in_place(a) for a in leaves), leaves
        layer, *news = write
        scalars = jnp.concatenate(
            [scalars, jnp.reshape(layer, (1,)).astype(jnp.int32)]
        )
        news = [a.reshape(G, tq, KV, 1, a.shape[-1]) for a in news[:n]]
    else:
        leaves[:2] = [_lanes(a) for a in leaves[:2]]
    q5 = _lanes(q.reshape(G, tq, KV, group, Dh))
    Dp = q5.shape[-1]
    Dv = value_dim if latent else leaves[1].shape[-1]
    rows = tq * group
    KVg, P = _walk_shape(KV, bs, Dp, leaves[0].dtype.itemsize, quant, rows,
                         MB, latent, listed=pages is not None)
    lists, sel, L = [], [], 0
    if pages is not None:
        assert not (latent or quant), "a selected read is of raw K/V pages"
        assert window is None and window_dyn is None and sink is None, (
            "a selected read's list is its window, and has no sink")
        plist, count, chosen = pages
        L = plist.shape[-1]
        assert L % 128 == 0, L
        lists = [plist.astype(jnp.int32), count.astype(jnp.int32)]
        if tq > 1:
            # [G, tq, KV, L] -> a score row's choices, 128 entries a part
            c = jnp.repeat(chosen.transpose(0, 2, 1, 3), group, axis=2)
            sel = [c.reshape(G, KV, rows, L // 128, 128)
                   .transpose(0, 1, 3, 2, 4).astype(jnp.bfloat16)]

    kernel = functools.partial(
        _walk_kernel, bs=bs, MB=MB, tq=tq, KVg=KVg, P=P, group=group,
        scale=scale if scale is not None else Dh**-0.5, softcap=softcap,
        quant=quant, latent=value_dim if latent else 0,
        write=write is not None, block=block, pages=L,
        sink=sink is not None,
    )
    sinks, sink_spec = [], []
    if sink is not None:
        # score row r of a KV head is (query r // group, head r % group)
        sinks = [jnp.tile(sink.astype(jnp.float32).reshape(KV, 1, group),
                          (1, tq, 1)).reshape(KV, rows, 1)]
        sink_spec = [pl.BlockSpec((KVg, rows, 1),
                                  lambda g, hg, *refs: (hg, 0, 0))]

    def tile(per_head, width):
        return pl.BlockSpec(
            (1, tq, KVg, per_head, width),
            lambda g, hg, *refs: (g, 0, hg, 0, 0),
        )

    scratch = [
        pltpu.VMEM((KVg, rows, 1), jnp.float32),
        pltpu.VMEM((KVg, rows, 1), jnp.float32),
        pltpu.VMEM((KVg, rows, Dv), jnp.float32),
        pltpu.SemaphoreType.DMA((n, 2)),
    ]
    scratch += [
        pltpu.VMEM((2, KVg, P * bs, a.shape[-1]), a.dtype)
        for a in leaves[:2]
    ]
    scratch += [
        pltpu.VMEM((2, KVg) + a.shape[2:], jnp.float32) for a in leaves[2:]
    ]
    in_hbm = [pl.BlockSpec(memory_space=pl.ANY)] * n
    out_specs = tile(group, Dv)
    out_shape = jax.ShapeDtypeStruct((G, tq, KV, group, Dv), q.dtype)
    aliases = {}
    if write is not None:
        scratch.append(pltpu.SemaphoreType.DMA((n,)))
        out_specs = [out_specs] + in_hbm
        out_shape = [out_shape] + [
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in leaves
        ]
        # operands: the prefetched scalars (3, and a page list's 2), q,
        # the list's choices, the new rows, the pool leaves
        at = 3 + len(lists) + 1 + len(sinks) + len(sel) + n
        aliases = {at + i: 1 + i for i in range(n)}
    if pages is not None:  # the positions of a step's columns
        scratch.append(pltpu.VMEM((1, P * bs), jnp.int32))
    if sel:  # the table that spreads a score row's choices over a step
        scratch.append(pltpu.VMEM((128 // P, 128, P * bs), jnp.bfloat16))
    sel_spec = [pl.BlockSpec(
        (1, 1, L // 128, rows, 128), lambda g, hg, *refs: (g, hg, 0, 0, 0),
    )] * len(sel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(lists),
        grid=(G, KV // KVg),
        in_specs=[tile(group, Dp)] + sink_spec + sel_spec
        + [tile(1, a.shape[-1]) for a in news] + in_hbm,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(meta.astype(jnp.int32), table.astype(jnp.int32), scalars, *lists, q5,
      *sinks, *sel, *news, *leaves)
    width = Dv if latent else pool_v_width
    if write is None:
        return out[..., :width].reshape(G, tq, H, width)
    out, *pool = out
    return out.reshape(G, tq, H, width), *pool, *[None] * (2 - n)


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "window", "scale", "softcap", "value_dim",
                     "block"),
)
def paged_flash_attend(
    q: jnp.ndarray,
    pool_k,
    pool_v,
    table: jnp.ndarray,
    pos: jnp.ndarray,
    window_dyn: jnp.ndarray | None = None,
    active: jnp.ndarray | None = None,
    write: tuple | None = None,
    pages: tuple | None = None,
    sink: jnp.ndarray | None = None,
    *,
    window: int | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    interpret: bool | None = None,
    value_dim: int | None = None,
    block: int = 0,
) -> jnp.ndarray:
    """Paged GQA decode attention over the block pool.

    q [B,1,H,Dh]; pool_k/v [N,KV,bs,Dh] (one layer's pool slice, already
    updated) — or ops/kv_quant.KVQuant leaves (int8 blocks + per-(token,
    head) fp32 scales [N,KV,bs]), dequantized in the block prologue; table
    [B,MB] int32 physical block ids; pos [B] int32 per-row positions.
    write (layer, new_k, new_v): pool_k/v are the STACKED pool
    [L,N,KV,bs,Dh] (`writes_in_place`), not yet updated; the kernel puts
    each live row's new token new_k/v [B,1,KV,Dh] at position pos[b] of
    its table in layer `layer` (a traced scalar) and attends it with the
    rest, and the call returns (output, pool_k, pool_v) with the pool
    updated in place. A row that is not live writes nothing.
    active: [B] bool, or None for every row live — a row whose flag is
    false is not walked (no DMA, no loop) and its output is zeros: a freed
    slot's position stays frozen at its last request's length, and only
    the caller knows nothing reads its row.
    window: static sliding-window width (None = full causal);
    window_dyn: TRACED scalar override (<= 0 = full) riding as a
    scalar-prefetch operand — per-layer patterns (Gemma-2/3) feed each
    scan step's width through ONE compiled kernel. scale: score-scale
    override (None = head_dim**-0.5); softcap: Gemma-2 logit capping.
    Returns [B,1,H,Dh] in q.dtype — same contract as the gather path in
    engine/paged.make_paged_hook with the mask derived from pos/window.
    pool_v None is the latent form: pool_k [N,1,bs,R] rows [c | k_r | 0],
    q [B,1,H,R] absorbed queries, the output [B,1,H,value_dim]; new_v and
    the returned pool_v are None.
    pages (plist [B, KV, L], count [B, KV]): the selected read
    (`_walk_kernel`): row b's KV head h reads the first count[b, h] logical
    pages of plist[b, h] (ascending; the page of pos[b] the last of them)
    in place of the range.
    sink [H] float32: a learned logit a query head in the softmax's
    denominator (the module docstring); pool_v may be narrower than pool_k,
    q is as wide as pool_k and the output as wide as pool_v.
    """
    B, T, H, Dh = q.shape
    assert T == 1, "paged kernel serves decode steps (T=1) only"
    live = jnp.ones((B,), jnp.int32) if active is None else active
    meta = jnp.stack(
        [jnp.arange(B, dtype=jnp.int32), pos.astype(jnp.int32),
         live.astype(jnp.int32), jnp.full((B,), RAGGED_DECODE, jnp.int32)],
        axis=1,
    )
    return _paged_walk(
        q, pool_k, pool_v, table, meta, window, window_dyn, scale=scale,
        softcap=softcap, interpret=resolve_interpret(interpret),
        value_dim=value_dim, write=write, block=block,
        pages=None if pages is None else (*pages, None), sink=sink,
    )


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "window", "scale", "softcap", "value_dim",
                     "block"),
)
def ragged_paged_attend(
    q: jnp.ndarray,
    pool_k,
    pool_v,
    table: jnp.ndarray,
    meta: jnp.ndarray,
    window_dyn: jnp.ndarray | None = None,
    write: tuple | None = None,
    pages: tuple | None = None,
    sink: jnp.ndarray | None = None,
    *,
    window: int | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    interpret: bool | None = None,
    value_dim: int | None = None,
    block: int = 0,
) -> jnp.ndarray:
    """Mixed prefill + decode GQA attention over the block pool — one
    launch for rows of ARBITRARY per-row length.

    q [W, H, Dh]: the flat query-token axis — every row's tokens laid out
    back to back at query-tile granularity (tq = W // meta.shape[0]); a
    prefill row contributes its chunk, a decode row one token.
    pool_k/v [N, KV, bs, Dh] (one layer's pool slice, already updated) —
    or ops/kv_quant.KVQuant leaves (int8 blocks + per-(token, head) fp32
    scales), dequantized in the block prologue. write (layer, new_k,
    new_v), as `paged_flash_attend`: the stacked pool, and the kernel
    writes each tile's q_len tokens new_k/v [W, KV, Dh] at positions
    q_start .. q_start + q_len - 1 of its row (launch padding, q_len 0,
    writes nothing); returns (output, pool_k, pool_v).
    table [R, MB] int32 physical block ids, one row per fleet row.
    meta [G, 4] int32 per-tile metadata (row, q_start, q_len, kind), the
    host-built launch plan (engine/paged.build_ragged_meta): q_start is
    the tile's first ABSOLUTE position, q_len its valid queries (0 =
    launch-padding tile: not walked, output zeros), kind is
    RAGGED_PREFILL / RAGGED_DECODE (launch accounting; the math is
    uniform — a decode row is simply q_len == 1 at its own position).
    window / window_dyn / scale / softcap: as `paged_flash_attend`.
    block: the block-diffusion mask (`_walk_kernel`); every tile then
    starts at a multiple of it.
    Returns [W, H, Dh] in q.dtype: each query token's attention output
    over its row's KV prefix (positions 0..q_pos through the block
    table), which is exactly the bucketed scratch prefill's per-token
    contract — so one compiled program replaces the whole bucket ladder.
    A prefill chunk's tiles each walk the row's prefix: the tile size is
    the scheduler's. pool_v None is the latent form, as in
    `paged_flash_attend`: the output is [W, H, value_dim].
    pages (plist [G, KV, L], count [G, KV], chosen [W, KV, L] bool): the
    selected read (`_walk_kernel`): tile g's KV head h walks the first
    count[g, h] logical pages of plist[g, h], the union of its queries'
    choices, and query w attends list entry l where chosen[w, h, l].
    """
    W, H, Dh = q.shape
    G = meta.shape[0]
    tq = W // G
    assert tq * G == W, "flat query axis must be a whole number of tiles"
    if pages is not None:
        plist, count, chosen = pages
        pages = (plist, count, chosen.reshape((G, tq) + chosen.shape[1:]))
    out = _paged_walk(
        q.reshape(G, tq, H, Dh), pool_k, pool_v, table, meta, window,
        window_dyn, scale=scale, softcap=softcap,
        interpret=resolve_interpret(interpret), value_dim=value_dim,
        write=write, block=block, pages=pages, sink=sink,
    )
    if write is None:
        return out.reshape(W, H, -1)
    return out[0].reshape(W, H, -1), *out[1:]

