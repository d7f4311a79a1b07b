"""A sparse layer's selection (models/minicpm_sala.py): which blocks each
query reads, one Pallas program a query tile over the row's compressed keys
where the pool holds them.

Per query at position p (n = p + 1 positions visible) and KV head, with c_j
the compressed key that ends at token e_j = j x stride + stride - 1 and
covers `kernel` tokens (valid: e_j <= p and e_j >= kernel - 1):

    p^h = softmax over the valid j of q^h . c_j / sqrt(Dh)    (float32)
    r_j = the sum of p^h_j over the KV head's query heads
    score of block b = the largest r_j among the keys that end in b, and
        those that end in b + 1's first (kernel - 1) // stride slots (they
        begin in b); -inf where none is valid.
    the first `init` blocks and the blocks that hold the last `window`
    positions score +inf; of the blocks up to the query's own the `topk`
    highest are read, equal scores to the lower block (`_choose`); every one
    of them where n < `dense_len`.

The leaf ("ck", engine/paged.init_pool): [N, rows, Dh] in the pool's dtype,
a pool block's compressed keys a whole tile: row kv x slots + s is KV head
kv's key that ends in the block's slot s (slots = block / stride keys a
block and head), the rows past KV x slots padding (zeros, never read as
keys). A block is then one contiguous copy that a manual DMA can make (a
quarter of a tile, as [N x slots, KV x Dh] held a block, it cannot).

The grid runs over the launch's query tiles in order. A tile of the row the
previous live tile had finds that row's keys in VMEM; otherwise the program
brings them in, block by block through the row's table, up to the row's
LAST position in this launch (the launch's own keys are in the pool: the
write comes first) and not the table's width, and lays them out by (KV
head, slot): `keys` [KV x slots, blocks, Dh]. (A block's rows are picked
apart by a product with a 0 / 1 matrix: one term a sum, so exact.) Then two
passes over the keys up to the tile's last query, a chunk of 128 blocks and
a slot at a time, the scores never leaving VMEM: the running max and sum of
every (query, head); then exp(s - max) / sum, summed over the heads, and
the block maxima; then the choice among them, on the scores where they lie.
Products are the leaf's dtype into float32, as
`jnp.einsum(..., preferred_element_type=float32)` was; everything after is
float32: a near-tie decides which block is read.

tests/test_sala_ops.py holds the kernel against the XLA form it replaced
and against the benchmark's reference."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)  # a max's start: no inf - inf

# blocks a step of the row's load copies and lays out (a store of the laid
# out keys is then whole tiles of 16 rows), and blocks a step of the scoring
# takes: the lanes of a score tile
_LOAD_BLOCKS = 16
_SCORE_BLOCKS = 128


def leaf_rows(n_kv_heads: int, slots: int, dtype) -> int:
    """Rows of a block in the leaf: its KV x slots keys, padded to whole
    tiles of the dtype (16 rows of bfloat16, 8 of float32)."""
    tile = 32 // jnp.dtype(dtype).itemsize
    return -(-n_kv_heads * slots // tile) * tile


def _select_kernel(meta_ref, table_ref, q_ref, ck_hbm, o_ref, c_ref, keys,
                   raw, held, sem, *, bs: int, stride: int, kernel: int,
                   tq: int, KV: int, group: int, scale: float, topk: int,
                   window: int, init: int, dense_len: int):
    """Query tile g = program_id(0). meta_ref [G, 4]: (row, the tile's first
    position, its live queries, the row's blocks up to its last position in
    the launch); q_ref [1, KV, group x tq, Dh], score row h x tq + t query
    t's head h of the KV head; o_ref (the block scores) and c_ref (1.0: the
    query reads the block) [1, KV, tq, MBp] float32."""
    g = pl.program_id(0)
    row, q_start, q_len, n_blocks = (meta_ref[g, i] for i in range(4))
    slots = bs // stride
    over = (kernel - 1) // stride
    RB, Dh = raw.shape[2:]
    CB, CC = _LOAD_BLOCKS, _SCORE_BLOCKS
    n_keys = KV * slots
    dt = raw.dtype
    # (stated, whatever precision the process asks of its float32 products:
    # a bfloat16 product is one pass, a float32 leaf's, in the tests, whole)
    exact = (jax.lax.Precision.HIGHEST if dt == F32
             else jax.lax.Precision.DEFAULT)

    @pl.when(g == 0)
    def _():
        held[0] = -1

    def copy(c, half, p):
        # block c x CB + p of the row (past its last: that one again, so a
        # step's copies are always CB and what they bring is finite)
        blk = table_ref[row, jnp.minimum(c * CB + p, n_blocks - 1)]
        return pltpu.make_async_copy(ck_hbm.at[blk], raw.at[half, p],
                                     sem.at[half])

    def for_copies(c, half, what):
        # (side by side, not a scalar loop: one by one a 30k row's copies
        # were 27 us of a decode step, so 20, and its waits 3 us more)
        jax.lax.fori_loop(
            0, CB, lambda p, carry: (what(copy(c, half, p)), carry)[1], 0,
            unroll=True)

    @pl.when((q_len > 0) & (held[0] != row))
    def _():
        held[0] = row
        n = pl.cdiv(n_blocks, CB)
        # laid-out row r x CB + b is row b x RB + r of the step's blocks
        i = jax.lax.broadcasted_iota(jnp.int32, (n_keys * CB, CB * RB), 0)
        k = jax.lax.broadcasted_iota(jnp.int32, (n_keys * CB, CB * RB), 1)
        pick = (k == (i % CB) * RB + i // CB).astype(dt)
        for_copies(0, 0, lambda c: c.start())

        def step(c, carry):
            half = c % 2

            @pl.when(c + 1 < n)
            def _():
                for_copies(c + 1, 1 - half, lambda c: c.start())

            for_copies(c, half, lambda c: c.wait())
            out = jnp.dot(pick, raw[half].reshape(CB * RB, Dh),
                          precision=exact,
                          preferred_element_type=F32).astype(dt)
            at = pl.ds(pl.multiple_of(c * CB, CB), CB)
            for r in range(n_keys):
                keys[r, at, :] = out[r * CB:(r + 1) * CB]
            return carry

        jax.lax.fori_loop(0, n, step, 0)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, F32)
    c_ref[...] = jnp.zeros(c_ref.shape, F32)

    @pl.when(q_len > 0)
    def _():
        rows = group * tq
        last = q_start + q_len - 1
        chunks = pl.cdiv(last // bs + 1, CC)
        # (a dead query of a tile that ends a chunk stands at the last live
        # one's position: nothing it reads lies past the row's keys)
        t = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % tq
        pos = jnp.minimum(q_start + t, last)  # [rows, 1]
        pos_q = jnp.minimum(
            q_start + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0), last)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, CC), 1)

        def valid(c, s, at):
            end = (c * CC + col) * bs + (s * stride + stride - 1)
            return (end <= at) & (end >= kernel - 1)

        def head(kv, carry):
            q = q_ref[0, kv]

            def scores(c, s):
                k = keys[kv * slots + s, pl.ds(pl.multiple_of(c * CC, CC), CC),
                         :]
                return jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), precision=exact,
                    preferred_element_type=F32) * scale  # [rows, CC]

            def fold(c, carry):
                m, l = carry
                for s in range(slots):
                    ok = valid(c, s, pos)
                    x = jnp.where(ok, scores(c, s), _NEG)
                    m_new = jnp.maximum(m, jnp.max(x, axis=1, keepdims=True))
                    e = jnp.where(ok, jnp.exp(x - m_new), 0.0)
                    l = l * jnp.exp(m - m_new) + jnp.sum(e, axis=1,
                                                         keepdims=True)
                    m = m_new
                return m, l

            m, l = jax.lax.fori_loop(
                0, chunks, fold,
                (jnp.full((rows, 1), _NEG, F32), jnp.zeros((rows, 1), F32)))
            inv = 1.0 / jnp.where(l > 0, l, 1.0)

            def block_scores(i, nxt):
                # chunks last to first: `nxt` [tq, 1] is what the first
                # block of the chunk after holds for this one's last
                c = chunks - 1 - i
                best = begun = jnp.full((tq, CC), -jnp.inf, F32)
                for s in range(slots):
                    p = jnp.where(valid(c, s, pos),
                                  jnp.exp(scores(c, s) - m), 0.0) * inv
                    if tq == 1:
                        r = jnp.sum(p, axis=0, keepdims=True)
                    else:
                        r = jnp.sum(p.reshape(group, tq, CC), axis=0)
                    r = jnp.where(valid(c, s, pos_q), r, -jnp.inf)
                    best = jnp.maximum(best, r)
                    if s < over:
                        begun = jnp.maximum(begun, r)
                if over:
                    after = jnp.where(col == CC - 1, nxt,
                                      pltpu.roll(begun, CC - 1, 1))
                    best = jnp.maximum(best, after)
                    nxt = begun[:, :1]
                o_ref[0, kv, :, pl.ds(pl.multiple_of(c * CC, CC), CC)] = best
                return nxt

            jax.lax.fori_loop(0, chunks, block_scores,
                              jnp.full((tq, 1), -jnp.inf, F32))
            return carry

        # (a loop, not KV copies of the body: a step program traces and lowers
        # the kernel at every start, compile cache or not)
        jax.lax.fori_loop(0, KV, head, 0)
        # (the KV heads together: the search below is 32 counts one after
        # the other, each as long for two heads' scores as for one's)
        c_ref[0] = _choose(
            o_ref[0], pos_q[None], bs=bs, topk=topk, window=window,
            init=init, dense_len=dense_len).astype(F32)


def _choose(score, pos, *, bs: int, topk: int, window: int, init: int,
            dense_len: int):
    """score [KV, tq, MBp] float32 (the block scores, -inf past the table),
    pos [1, tq, 1] -> [KV, tq, MBp] bool: the blocks each query reads. The
    first `init` blocks and those of the last `window` positions score +inf;
    of the blocks up to the query's own the `topk` highest are read, equal
    scores to the lower block; every one of them where fewer than
    `dense_len` positions are visible. The k-th largest is found bit by bit
    on the floats' order-preserving integer keys (32 counts of "how many are
    at least this"), and of its equals the first few by index: a prefix
    count, as a product with a 0 / 1 triangle (exact at one pass)."""
    KV, tq, MBp = score.shape
    C = _SCORE_BLOCKS
    blk = jax.lax.broadcasted_iota(jnp.int32, (1, 1, MBp), 2)
    visible = blk <= pos // bs
    forced = (blk < init) | (blk >= jnp.maximum(pos - (window - 1), 0) // bs)
    score = jnp.where(visible, jnp.where(forced, jnp.inf, score), -jnp.inf)
    # a float's bits as a signed key of the same order (-inf lowest)
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    low = jnp.int32(-2 ** 31)
    # (-inf keys past the query may fill the k where few blocks are visible:
    # `visible` cuts them again)
    k = float(topk)

    def count(mask):
        return jnp.sum(jnp.where(mask, 1.0, 0.0), axis=2, keepdims=True)

    def bit(i, t):
        # t: the k-th largest key's leading bits so far, as an unsigned
        # number's (the signed key with its top bit turned)
        cand = t | jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(count(key >= (cand ^ low)) >= k, cand, t)

    kth = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros((KV, tq, 1), jnp.int32)) ^ low
    above, equal = key > kth, key == kth
    room = k - count(above)
    # how many equals lie at or before each block: within a chunk of lanes by
    # the triangle (a chunk of every query a row of ONE product), the chunks
    # before added
    assert tq == 1 or tq % 8 == 0, tq  # a decode row's tile, a mixed launch's
    rows = max(tq, 8)  # (a tile of one query: its row eight times, a whole tile)
    ones = jnp.where(equal, 1.0, 0.0)
    parts = []
    for c in range(MBp // C):
        part = jnp.broadcast_to(ones[:, :, c * C:(c + 1) * C], (KV, rows, C))
        parts.append(part.reshape(KV * rows, C))
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    within = jnp.dot(jnp.concatenate(parts, axis=0),
                     jnp.where(i <= j, 1.0, 0.0),
                     precision=jax.lax.Precision.DEFAULT,
                     preferred_element_type=F32)
    before = jnp.zeros((KV, tq, 1), F32)
    first = []
    for c in range(MBp // C):
        part = within[c * KV * rows:(c + 1) * KV * rows].reshape(
            KV, rows, C)[:, :tq]
        first.append(part + before <= room)
        before = before + part[:, :, C - 1:]
    picked = above | (equal & jnp.concatenate(first, axis=2))
    # (the positions spread over the blocks as numbers: Mosaic selects and
    # broadcasts no booleans)
    dense = pos + 1 + jnp.zeros_like(blk) < dense_len
    return (dense | picked) & visible


@functools.partial(jax.jit, static_argnames=(
    "block", "stride", "kernel", "topk", "window", "init", "dense_len",
    "interpret"))
def select_blocks(q, pool_ck, table, meta, *, block: int, stride: int,
                  kernel: int, topk: int, window: int, init: int,
                  dense_len: int, interpret: bool):
    """q [G, tq, KV, group, Dh] (normed, not scaled): G tiles of tq queries,
    a tile one row's at consecutive positions; pool_ck [N, rows, Dh] the
    layer's leaf (module docstring), holding the launch's own keys; table
    [R, MB] int32; meta [G, 4] int32 a tile: (row, first position, live
    queries (0: nothing runs), the row's blocks up to its last position in
    this launch). Returns (the block scores [G, tq, KV, MB] float32: -inf
    where no valid key overlaps the block, and in every block of a tile
    that is not live; chosen [G, tq, KV, MB] bool: the blocks each query's
    KV head reads, none in a tile that is not live). Jitted, so that a
    stack's layers trace and lower ONE kernel a step program."""
    G, tq, KV, group, Dh = q.shape
    MB = table.shape[1]
    MBp = -(-MB // _SCORE_BLOCKS) * _SCORE_BLOCKS
    RB = pool_ck.shape[1]
    slots = block // stride
    # score rows head-major: a KV head's sum over its heads adds whole tiles
    q = q.astype(pool_ck.dtype).transpose(0, 2, 3, 1, 4).reshape(
        G, KV, group * tq, Dh)

    def of_tile():
        return pl.BlockSpec((1, KV, tq, MBp), lambda g, *refs: (g, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, KV, group * tq, Dh),
                         lambda g, *refs: (g, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[of_tile(), of_tile()],
        scratch_shapes=[
            pltpu.VMEM((KV * slots, MBp, Dh), pool_ck.dtype),
            pltpu.VMEM((2, _LOAD_BLOCKS, RB, Dh), pool_ck.dtype),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    score, chosen = pl.pallas_call(
        functools.partial(_select_kernel, bs=block, stride=stride,
                          kernel=kernel, tq=tq, KV=KV, group=group,
                          scale=Dh ** -0.5, topk=min(topk, MB), window=window,
                          init=init, dense_len=dense_len),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((G, KV, tq, MBp), F32)] * 2,
        # a tile finds its row's keys where the tile before left them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(meta.astype(jnp.int32), table.astype(jnp.int32), q, pool_ck)
    return (score[..., :MB].transpose(0, 2, 1, 3),
            chosen[..., :MB].transpose(0, 2, 1, 3) > 0.5)
