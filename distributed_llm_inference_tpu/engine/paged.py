"""Block-paged KV cache for continuous batching (vLLM-style, TPU-first).

The dense slot fleet (engine/continuous.py) pins `n_slots x slot_max_seq`
of KV in HBM for the server's lifetime — every slot pays for the worst
case even when typical requests use a fraction of the window. Here KV
lives in a shared pool of fixed-size blocks:

    pool k/v [L, n_blocks, KV, block_size, Dh]

and each slot's logical sequence is a *block table* — an int32 row mapping
logical block j to a physical pool block. Admission allocates exactly
ceil((prompt_len + max_tokens) / block_size) blocks from a host-side
REFCOUNTED free list; release decrefs them. Fleet memory is a function of
the POOL size (aggregate tokens actually in flight), not n_slots x
window, and the pool naturally backpressures: a request that cannot get
blocks waits in the queue until a running request completes (after the
block-prefix index has evicted what it can — engine/block_prefix.py).

Block-level prefix sharing rides the refcounts: full prompt blocks are
immutable once the launch that wrote them lands, so a prefix hit MAPS the
cached physical blocks into the new request's table (one more holder
each) and the tail's ragged launches attend them in place: the shared
head is never copied and never rewritten. Both decode paths run unchanged
over shared tables. See ARCHITECTURE.md "Block sharing" for the invariant
walk-through.

TPU/XLA design notes (why this shape, not a translation of vLLM's CUDA
paged attention):
  * Static shapes everywhere: every table is a fixed [B, max_blocks]
    int32 array (unused tail entries point at a reserved TRASH block);
    the decode program is compiled once per (n_slots, num_steps), exactly
    like the dense fleet.
  * The per-step attention has two paths. attn_impl="xla": GATHER the
    slot's blocks into a contiguous [B, KV, max_blocks*bs, Dh] view and
    run the stock masked attention — the gather reads the same bytes a
    dense cache read would, plus one materialization (~+2 x
    cache-bytes/step of HBM traffic vs dense while weight streaming
    still dominates at small batch). attn_impl="pallas": the paged
    kernels (ops/paged_attention.py) walk each live row's live blocks
    of the table with an online softmax — one DMA per block for all KV
    heads, no materialized view; a freed slot walks nothing.
  * Writes are per token: K/V lands at
    pool[layer, table[b, pos_b // bs], :, pos_b % bs] per slot row b, by
    the paged kernel itself where it can and by an XLA scatter elsewhere
    (_kernel_step). Distinct live slots never share a block, so the
    writes never collide (the shared trash block only ever receives
    writes from slots whose position has run past their budget — masked
    garbage, never attended; the same stale-region argument as the dense
    fleet's).
  * The step programs carry the pool through their loops (the layer
    scan, the decode chunk's step scan) as ONE donated buffer: a hook
    gets the stacked leaves and the layer's index (make_paged_hook),
    never a slice the scan cut out.

Paged mode serves BOTH families: the hook seam is shared
(models/llama.default_attn_hook; gpt2's block routes through it since
round 5). It runs on the single device AND on dp=1 pp/tp meshes: the pool
shards its layer axis over pp / kv heads over tp exactly like the dense
cache (parallel/partition.pool_spec), and ungated ring microsteps
redirect their block writes to
the trash block (parallel/pipeline._build_decode_slots_paged).

Reference contrast: /root/reference has no KV cache at all
(Worker1.py:132-134 — full-sequence recompute per token); this module is
north-star scope (serving HBM discipline), not parity scope.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import attend, block_frontier
from ..ops.kv_quant import KVQuant
from ..ops.kv_quant import dequantize as kv_dequantize
from ..ops.kv_quant import quantize_chunk
from ..ops.sparse_select import leaf_rows
from . import generate as G

TRASH_BLOCK = 0  # reserved pool block: write-only spill for table tails
# a grouped pool's K/V leaves, group by group (cfg.kv_groups' order)
GROUP_LEAVES = (("k", "v"), ("kw", "vw"))


def _routed_counts(cfg: ModelConfig):
    """The zeroed "routed" leaf of a family that may hold a share
    (models/stack.add_routed): the experts held here, with one more column
    under a share, the pairs routed elsewhere."""
    share = cfg.experts_held < cfg.n_experts
    return jnp.zeros(
        (2, cfg.n_layers - cfg.first_k_dense, cfg.experts_held + share),
        jnp.int32)


def init_pool(cfg: ModelConfig, n_blocks: int, block_size: int,
              n_layers: Optional[int] = None, n_slots: Optional[int] = None,
              n_snapshots: int = 0):
    """Zeroed block pool, stacked on the layer axis like the dense cache.
    Block 0 is the reserved trash block (never allocated to a slot).
    With cfg.kv_quant the pool leaves are KVQuant pytrees — int8 blocks
    plus per-(token, head) scales [L, N, KV, bs] — so BOTH HBM levers
    compose: the pool tracks in-flight tokens AND each token costs half
    the bytes. n_layers overrides the layer count (the pp mesh pads the
    layer axis to ceil(L/pp)*pp, matching the padded stacked layers).

    A latent-attention model (cfg.latent_dim > 0, models/mla_moe.py) has
    no K/V pair: its pool holds ONE row [c | k_r | zero pad] of
    cfg.latent_row numbers a token and layer, in the same block layout
    with one shared "head", one leaf a layer stack:
        "dense", "moe"  [L_stack, n_blocks, 1, block_size, latent_row]
    Allocator, block tables and prefix digests do not see the difference.
    The "routed" leaf [2, L_moe, E] int32 is not cache: the step programs
    zero it and the expert layers add what they routed, so the counts
    leave the device in the launch's one packed fetch (pack_routed).

    A model with recurrent layers (cfg.conv_layers, models/lfm2.py) keeps
    two kinds of cache in the one pool. "k" / "v" have the ATTENTION
    layers' depth alone, with cfg.kv_pack K/V heads side by side on a row
    ([La, N, KV / pack, bs, pack x Dh]: head dim 64 on whole 128-lane
    tiles, `ops/paged_attention.writes_in_place`). Beside them, for the
    Lc convolution layers: "conv" [Lc, n_slots, K-1, D], a SLOT's live
    state (the row's last K-1 gated inputs; a launch reads it at a row's
    first token and writes it at its last), and "tail" [Lc, N, K-1, D],
    the state at the END of each pool block, written by whichever launch
    fills the block's last position. The physical block id is the tail's
    key, so allocator, refcounts, eviction and the prefix index carry it
    unchanged, and a prefix hit at depth p0 (whole blocks) starts the
    slot's state from the tail of the last shared block: `StateRows`.

    A model whose layers keep a float32 matrix state (cfg.linear_layers)
    keeps a leaf a layer of them, "lin" a slot and "snap" a snapshot, the
    second a pool of its own that the prefix index gives out. One of sparse
    and linear attention layers (models/minicpm_sala.py) keeps three kinds:
    "k" / "v" of its SPARSE layers alone, with cfg.sparse_block tokens a block (a page of the
    kernels' walk is a block of the selection); beside them a third leaf
    of the same blocks, "ck" (a leaf a sparse layer, [N, rows, Dh]: a
    block's KV x bs / stride keys padded to whole tiles, which the
    scoring's kernel copies block by block), the compressed keys the
    selection scores against, each with the block that holds its last
    token, so allocator,
    refcounts, eviction and the prefix index carry it as they carry K/V;
    and for the Ll linear layers "lin" (a leaf a layer,
    [n_slots, Hl, Dh, Dh] FLOAT32), a slot's live matrix state, and "snap"
    ([n_snapshots, Hl, Dh, Dh] a layer), the snapshot pool: a state is
    2 MB a layer at 32 heads of 128, far too large to keep one a block as
    lfm2's tails are kept, so the prefix index decides which block
    boundaries have one (engine/block_prefix.py) and a hit is as deep as
    the deepest that has.

    A model of state-space and attention layers (models/granite_hybrid.py:
    its "mamba" layers are in cfg.conv_layers AND cfg.linear_layers) keeps
    "k" / "v" of its attention layers alone, heads packed cfg.kv_pack a
    row; and a leaf a mamba layer each of "conv" [n_slots, K-1, C] (the
    convolution's last inputs, the parameter dtype), "lin" [n_slots,
    H / pack, N, pack x P] FLOAT32 (cfg.matrix_state_shape), and "csnap" /
    "snap", the same two by snapshot: ONE snapshot index names both states
    of every layer at one block boundary, so a prefix hit restores the
    convolution state with the matrix state (no tail a block: at 36 layers
    a snapshot is 76 MB). A model of delta-rule and attention layers over
    routed experts (models/solar_open2.py) keeps the same leaves by what its
    "kda" kind keeps (config.STATE_OF_KIND: the three convolutions' inputs
    side by side, a [heads, Dv, Dk] state) and the "routed" counts beside
    them."""
    if cfg.linear_layers:
        if n_slots is None:
            raise ValueError(f"{cfg.name}: the pool holds a state a slot "
                             f"(pass n_slots)")
        dt, Dh = cfg.jnp_dtype, cfg.head_dim
        snaps = max(1, n_snapshots)

        def a_layer(layers: tuple, first: int, shape: tuple, dtype) -> tuple:
            # (a leaf a layer: models/minicpm_sala.py says why)
            return tuple(jnp.zeros((first,) + shape, dtype) for _ in layers)

        kv = (len(cfg.attn_layers), n_blocks, cfg.n_kv_heads // cfg.kv_pack,
              block_size, Dh * cfg.kv_pack)
        pool = {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)}
        if cfg.sparse_layers:
            if block_size != cfg.sparse_block:
                raise ValueError(
                    f"{cfg.name}: a pool block is one block of the "
                    f"selection (pass a block size of {cfg.sparse_block}, "
                    f"not {block_size})")
            ck = (leaf_rows(cfg.n_kv_heads, block_size // cfg.sparse_stride,
                            dt), Dh)
            pool["ck"] = a_layer(cfg.sparse_layers, n_blocks, ck, dt)
        if cfg.conv_layers:
            hist = (cfg.conv_kernel - 1, cfg.conv_channels)
            pool["conv"] = a_layer(cfg.conv_layers, n_slots, hist, dt)
            pool["csnap"] = a_layer(cfg.conv_layers, snaps, hist, dt)
        state = cfg.matrix_state_shape
        pool["lin"] = a_layer(cfg.linear_layers, n_slots, state, jnp.float32)
        pool["snap"] = a_layer(cfg.linear_layers, snaps, state, jnp.float32)
        if cfg.moe_ffn_dim:  # routed layers over the mixers: the same counts
            pool["routed"] = _routed_counts(cfg)
        return pool
    if cfg.kinds_of_attention:
        # K/V in groups by layer kind, each with its own blocks and block
        # table (the module docstring's "Groups"); n_blocks: one count a
        # group (`group_blocks`). A group's rows are its own kind's: its
        # K/V heads, keys cfg.key_row wide and values cfg.value_dim, so a
        # block of either group holds the same positions and not the same
        # bytes. The routed counts are of the experts held here, with one
        # more column under a share: pairs routed elsewhere.
        sizes = (n_blocks,) if isinstance(n_blocks, int) else tuple(n_blocks)
        pool = {}
        for group, (kn, vn), n in zip(cfg.kv_groups, GROUP_LEAVES, sizes):
            rows = (len(cfg.group_layers(group)), n,
                    cfg.group_kv_heads(group), block_size)
            pool[kn] = jnp.zeros(rows + (cfg.key_row,), cfg.jnp_dtype)
            pool[vn] = jnp.zeros(rows + (cfg.value_dim,), cfg.jnp_dtype)
        pool["routed"] = _routed_counts(cfg)
        return pool
    if cfg.state_tails:
        if n_slots is None:
            raise ValueError(f"{cfg.name}: the pool holds a state a slot "
                             f"(pass n_slots)")
        La, Lc = len(cfg.attn_layers), len(cfg.conv_layers)
        kv = (La, n_blocks, cfg.n_kv_heads // cfg.kv_pack, block_size,
              cfg.head_dim * cfg.kv_pack)
        hist, dt = (cfg.conv_kernel - 1, cfg.conv_channels), cfg.jnp_dtype
        return {
            "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
            "conv": jnp.zeros((Lc, n_slots) + hist, dt),
            "tail": jnp.zeros((Lc, n_blocks) + hist, dt),
            "routed": jnp.zeros(
                (2, cfg.n_layers - cfg.first_k_dense, cfg.n_experts),
                jnp.int32),
        }
    if cfg.latent_dim:
        from ..models.mla_moe import stack_depths

        Ld, Lm = stack_depths(cfg)
        row = (n_blocks, 1, block_size, cfg.latent_row)
        return {
            "dense": jnp.zeros((Ld,) + row, cfg.jnp_dtype),
            "moe": jnp.zeros((Lm,) + row, cfg.jnp_dtype),
            "routed": jnp.zeros((2, Lm, cfg.n_experts), jnp.int32),
        }
    shape = (
        n_layers or cfg.n_layers, n_blocks, cfg.n_kv_heads, block_size,
        cfg.head_dim,
    )
    if cfg.moe_ffn_dim:  # the llama family's routed layer: the same counts
        return {
            "k": jnp.zeros(shape, cfg.jnp_dtype),
            "v": jnp.zeros(shape, cfg.jnp_dtype),
            "routed": jnp.zeros((2, shape[0], cfg.n_experts), jnp.int32),
        }
    if cfg.kv_quant == "int8":
        sshape = shape[:-1]
        leaf = lambda: KVQuant(  # noqa: E731 - two identical leaves
            jnp.zeros(shape, jnp.int8), jnp.zeros(sshape, jnp.float32)
        )
        return {"k": leaf(), "v": leaf()}
    dt = cfg.jnp_dtype
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


class BlockAllocator:
    """Host-side REFCOUNTED free list over pool blocks 1..n_blocks-1 (0 is
    trash).

    Every allocated block carries a reference count: alloc() hands blocks
    out at refcount 1, incref() adds a holder (a request mapping a SHARED
    block into its table, or the block-prefix index caching a chain —
    engine/block_prefix.py), and decref() removes one — a block returns
    to the free list only when its LAST holder lets go. Pool-memory
    accounting therefore counts shared blocks once: free_blocks is the
    physical free list, however many tables map the resident blocks.

    Not thread-safe by itself — the continuous engine calls it only from
    its single worker thread (admission/release), matching the engine's
    single-owner design.

    registry (utils/metrics.MetricsRegistry, optional): pool-occupancy
    gauges (`dli_kv_pool_blocks_total` / `_free`), a shared-block gauge
    (`dli_kv_pool_shared_blocks` — blocks held by more than one
    referencer: live tables and/or the prefix index) and an exhaustion
    counter (`dli_kv_pool_exhausted_total` — alloc refusals, i.e. the
    admission backpressure events) for /metrics.
    """

    def __init__(self, n_blocks: int, registry=None):
        if n_blocks < 2:
            raise ValueError("pool needs >= 2 blocks (one is the trash block)")
        self.n_blocks = n_blocks
        self._free = list(range(1, n_blocks))
        self._ref: dict = {}  # block id -> holders (allocated blocks only)
        self._shared = 0  # blocks at refcount >= 2
        self._m_free = self._m_exhausted = self._m_shared = None
        if registry is not None:
            registry.gauge(
                "dli_kv_pool_blocks_total",
                "paged-KV pool size (excluding the trash block)",
            ).labels().set(n_blocks - 1)
            self._m_free = registry.gauge(
                "dli_kv_pool_blocks_free", "unallocated paged-KV blocks"
            ).labels()
            self._m_free.set(len(self._free))
            self._m_exhausted = registry.counter(
                "dli_kv_pool_exhausted_total",
                "admissions refused because the pool had too few blocks",
            ).labels()
            self._m_shared = registry.gauge(
                "dli_kv_pool_shared_blocks",
                "pool blocks held by more than one referencer "
                "(live block tables and/or the block-prefix index)",
            ).labels()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def outstanding(self) -> int:
        """Blocks currently held by anyone (leak accounting: after every
        holder releases — requests done, prefix index cleared — this must
        be 0, i.e. free_blocks == n_blocks - 1)."""
        return len(self._ref)

    def reset(self):
        """Forget every allocation and rebuild the full free list. The
        scheduler supervisor's DEFENSIVE path only: after a crash it
        releases every holder explicitly (the accounting is the leak
        regression the chaos suite pins) and calls this solely when the
        books still disagree, because a rebuilt pool must never start
        with phantom holders."""
        self._free = list(range(1, self.n_blocks))
        self._ref.clear()
        self._shared = 0
        if self._m_free is not None:
            self._m_free.set(len(self._free))
            self._m_shared.set(0)

    @property
    def shared_blocks(self) -> int:
        return self._shared

    def span_attrs(self) -> dict:
        """Pool occupancy as flat span/flight-event attributes (ISSUE
        17): the tracing span and flight-recorder payloads want a
        JSON-ready snapshot, not live gauge objects. Cheap — three ints
        already maintained by alloc/decref bookkeeping."""
        return {
            "pool_free": len(self._free),
            "pool_outstanding": len(self._ref),
            "pool_shared": self._shared,
        }

    def refcount(self, block: int) -> int:
        """Current holder count (0 = on the free list / never allocated)."""
        return self._ref.get(block, 0)

    def alloc(self, n: int) -> Optional[list]:
        """n blocks at refcount 1, or None (caller keeps the request
        queued — or evicts unreferenced cached chains and retries)."""
        if n > len(self._free):
            if self._m_exhausted is not None:
                self._m_exhausted.inc()
            return None
        out = self._free[:n]
        del self._free[:n]
        for b in out:
            self._ref[b] = 1
        if self._m_free is not None:
            self._m_free.set(len(self._free))
        return out

    def incref(self, ids: list):
        """Add a holder to each block (mapping a shared block into another
        request's table, or caching it in the block-prefix index)."""
        for b in ids:
            c = self._ref[b]  # KeyError on a free block = caller bug
            self._ref[b] = c + 1
            if c == 1:
                self._shared += 1
        if self._m_shared is not None:
            self._m_shared.set(self._shared)

    def decref(self, ids: list):
        """Drop one holder per block; blocks reaching zero return to the
        free list. Replaces unconditional free(): a completed request
        decrefs its whole table and shared blocks simply lose one mapper.
        """
        for b in ids:
            c = self._ref[b] - 1
            if c == 0:
                del self._ref[b]
                self._free.append(b)
            else:
                self._ref[b] = c
                if c == 1:
                    self._shared -= 1
        if self._m_free is not None:
            self._m_free.set(len(self._free))
            self._m_shared.set(self._shared)

    def free(self, ids: list):
        """Back-compat spelling of decref() — single-holder blocks behave
        exactly as the pre-refcount free list did."""
        self.decref(ids)


def blocks_needed(prompt_len: int, max_tokens: int, block_size: int) -> int:
    """Physical blocks a request occupies: prompt positions plus decode
    writes (the last emitted token's K/V is never written, but the frozen
    inactive row keeps re-writing at its final position — bound by
    prompt_len + max_tokens)."""
    return -(-(prompt_len + max_tokens) // block_size)


def window_row_budget(window: int, launch_tokens: int, block_size: int) -> int:
    """The most window-group blocks a row ever holds: the positions its
    widest launch's queries read and write, block-rounded at both ends."""
    return -(-(window + launch_tokens) // block_size) + 1


# the context a window group is sized for (`group_blocks`). The number only
# re-encodes "a quarter of the global group's blocks at a window of 4,096"
# (4,096 / 16,384), the one sizing a cell has run; no traffic backs it
WINDOW_GROUP_CONTEXT = 16384


def group_blocks(cfg: ModelConfig, n_blocks: int, row_budget: int,
                 n_slots: int, block_size: int) -> tuple:
    """The one rule that sizes a pool's groups from `kv_pool_blocks`: the
    global group gets n_blocks (the number's meaning for every model: the
    context tokens the pool holds, over the block size). The window group
    follows the window: of every WINDOW_GROUP_CONTEXT tokens the global
    group holds it holds one window (in whole blocks), so that every
    cached context that long or longer keeps its last window; never fewer
    than the slots' budgets (+ its own null block). At a window of 4,096
    that is a quarter of n_blocks (four windows to such a context); at a
    window of one block of 128 a 128th, where the slots' budgets decide.
    WINDOW_GROUP_CONTEXT is that quarter written as a context and nothing
    more: it was chosen so that the one accepted grouped configuration's
    counts stay what they were, and at a small window the rule's own term
    never decides (PERF.md section 7, question 27a)."""
    if len(cfg.kv_groups) == 1:
        return (n_blocks,)
    window = -(-cfg.attn_window // block_size)
    context = max(-(-WINDOW_GROUP_CONTEXT // block_size), window)
    return (n_blocks, max(-(-n_blocks * window // context),
                          n_slots * row_budget + 1))


class WindowBlocks:
    """Host side of a grouped pool's WINDOW group: its own free list
    (`alloc`, a BlockAllocator over the group's blocks; block 0 is the
    group's null block) and its own block tables [n_slots, max_blocks],
    indexed by LOGICAL block like the global group's, so the kernels take
    either table unchanged.

    A row holds global-group blocks for its whole context and window-group
    blocks only for the positions a later query of the row can still read.
    The host's position model decides at dispatch: before a launch that
    carries the row's queries [start, start + n) `ensure` gives it a block
    for every logical block they write; after the launch is dispatched
    `release_below` takes back every block wholly below last - (window -
    1), whose table entry then points at the null block. The kernels' live
    range starts at the window, so they never walk such an entry; the
    device runs launches in order, so a block let to another row is
    written only after the last launch that read it. A row never holds
    more than `row_budget` blocks: ceil((window + the widest launch) /
    block) + 1, whatever its prompt's length, which is what admission
    reserves (`admit`): free + evictable cached blocks never fall below
    what the admitted rows may still ask for, so `ensure` cannot fail.

    A block the prefix index also holds (engine/block_prefix.py: a shared
    depth's entry names one block of each group) stays resident after the
    row gives it back, until this free list runs dry: `index.evict_side`
    then takes cached blocks, least recently used document first, from its
    shallow end. Worker-thread only, like BlockAllocator."""

    def __init__(self, n_blocks: int, n_slots: int, max_blocks: int,
                 block_size: int, window: int, launch_tokens: int):
        import numpy as np

        self.alloc = BlockAllocator(n_blocks)
        self.block_size, self.window = int(block_size), int(window)
        self.row_budget = window_row_budget(window, launch_tokens, block_size)
        if n_blocks - 1 < self.row_budget:
            raise ValueError(
                f"the window group's {n_blocks} blocks cannot hold one row "
                f"({self.row_budget} blocks of {block_size} + the null "
                f"block); raise kv_pool_blocks")
        self.table = np.zeros((n_slots, max_blocks), np.int32)
        self._lo = np.zeros((n_slots,), np.int64)  # below: given back
        self._end = np.zeros((n_slots,), np.int64)  # logical blocks a row has
        self._budget = np.zeros((n_slots,), np.int64)
        self._held = np.zeros((n_slots,), np.int64)
        self.index = None  # the BlockPrefixIndex that caches this group too
        self.given = self.released = 0  # blocks, while rows went on

    def _evictable(self) -> int:
        return 0 if self.index is None else self.index.side_evictable()

    def reserved(self) -> int:
        """Blocks the admitted rows may still ask for."""
        short = self._budget - self._held
        return int(short[short > 0].sum())

    def admit(self, slot: int, need_blocks: int, first: int,
              shared: list) -> bool:
        """Let `slot` to a row of need_blocks logical blocks whose blocks
        [first, first + len(shared)) are mapped from the prefix index
        (`shared`: the cached blocks a hit reads, one more holder each).
        False, with nothing held, where the group cannot promise the row
        its budget."""
        self.release_row(slot)
        if shared:
            self.alloc.incref(shared)
        budget = min(self.row_budget, need_blocks)
        spare = self.alloc.free_blocks + self._evictable() - self.reserved()
        if spare < budget - len(shared):
            if shared:
                self.alloc.decref(shared)
            return False
        self.table[slot, first:first + len(shared)] = shared
        self._lo[slot], self._end[slot] = first, need_blocks
        self._budget[slot], self._held[slot] = budget, len(shared)
        return True

    def ensure(self, slot: int, start: int, n: int) -> bool:
        """A block for every logical block positions [start, start + n) of
        the row fall in (below the row's end); True if the table changed."""
        bs, row = self.block_size, self.table[slot]
        changed = False
        hi = min((start + n - 1) // bs + 1, int(self._end[slot]))
        for b in range(max(start // bs, int(self._lo[slot])), hi):
            if row[b]:
                continue
            if not self.alloc.free_blocks and self.index is not None:
                self.index.evict_side(8)
            got = self.alloc.alloc(1)
            if got is None:
                raise RuntimeError(
                    "window group exhausted under its own reservations")
            row[b] = got[0]
            self._held[slot] += 1
            self.given += 1
            changed = True
        return changed

    def _below(self, slot: int, last: int) -> tuple:
        # (lo, new_lo): the row's logical blocks [lo, new_lo) lie wholly
        # below the window of a query at `last`
        lo = int(self._lo[slot])
        return lo, max(lo, min((last - self.window + 1) // self.block_size,
                               int(self._end[slot])))

    def releasable(self, slot: int, last: int) -> int:
        """Blocks `release_below(slot, last)` would give back."""
        lo, new_lo = self._below(slot, last)
        return int((self.table[slot, lo:new_lo] != 0).sum())

    def release_below(self, slot: int, last: int) -> bool:
        """After a launch whose last query of the row stands at `last`:
        give back every block wholly below last - (window - 1)."""
        lo, new_lo = self._below(slot, last)
        if new_lo <= lo:
            return False
        row = self.table[slot]
        mine = [int(b) for b in row[lo:new_lo] if b]
        if mine:
            self.alloc.decref(mine)
            self._held[slot] -= len(mine)
            self.released += len(mine)
        row[lo:new_lo] = 0
        self._lo[slot] = new_lo
        return bool(mine)

    def release_row(self, slot: int):
        row = self.table[slot]
        mine = [int(b) for b in row if b]
        if mine:
            self.alloc.decref(mine)
        row[:] = 0
        self._lo[slot] = self._end[slot] = 0
        self._budget[slot] = self._held[slot] = 0

    def held(self, slot: int) -> list:
        """(logical block, physical block) of every block the row holds."""
        row = self.table[slot]
        return [(int(b), int(row[b])) for b in row.nonzero()[0]]

    def reset(self):
        self.alloc.reset()
        self.table[:] = 0
        for a in (self._lo, self._end, self._budget, self._held):
            a[:] = 0


def pool_block_size(pool) -> int:
    """Tokens a block of the pool holds (either layout)."""
    return (pool["k"] if "k" in pool else pool["moe"]).shape[3]


def refuse_unsupported_latent(cfg: ModelConfig, **asked):
    """The ONE start-up check of what a pool with a "routed" leaf does not
    carry: a latent pool (models/mla_moe.py) and the llama family's routed
    layer (cfg.moe_ffn_dim > 0: SDAR). Each caller passes what it knows
    (runtime.create_backend: quant, kv_quant, mesh, lora, adapter_slots;
    the continuous engine: kv_shadow, unchunked); a dense per-head K/V
    model passes through."""
    if not (cfg.latent_dim or cfg.moe_ffn_dim or cfg.linear_layers):
        return
    why = {
        "quant": "weight quantization: ops/quant knows no expert-bank or "
                 "latent-projection leaf",
        "kv_quant": "the int8 pool: a latent row has no per-head scale",
        "mesh": "pp / tp / ep / sp / dp meshes: two layer stacks and a "
                "latent pool are not partitioned (parallel/partition.py)",
        "lora": "LoRA merge: models/lora.py knows the llama leaves only",
        "adapter_slots": "runtime adapters: llama family only",
        "kv_shadow": "the host shadow store (and swap preemption, "
                     "/kv export): it copies K/V block pairs; pass "
                     "--no-kv-shadow",
        "unchunked": "the unchunked ragged admission: a latent model is "
                     "served by chunked prefill only (keep "
                     "chunked_prefill on)",
    }
    lead = "a latent-attention model is served on one device from a " \
           "latent pool"
    if not cfg.latent_dim:
        lead = "routed experts behind the llama family's layer are served " \
               "on one device from the paged pool"
        why.update({
            "quant": "weight quantization: ops/quant knows no expert bank",
            "kv_quant": "the int8 pool: the routed counts ride the pool",
            "mesh": "pp / tp / ep / sp / dp meshes: the expert banks ride "
                    "outside the layer scan and are not partitioned",
            "unchunked": "the unchunked ragged admission: served by "
                         "chunked prefill only (keep chunked_prefill on)",
        })
    if cfg.state_tails:
        lead = "a model with recurrent layers is served on one device " \
               "from the paged pool by chunked ragged prefill"
        why.update({
            "quant": "weight quantization: ops/quant knows no expert bank "
                     "or convolution leaf",
            "kv_quant": "the int8 pool: heads are stored in pairs and the "
                        "recurrent state has no scale",
            "mesh": "pp / tp / ep / sp / dp meshes: layers of two kinds, "
                    "the expert banks and a state a slot are not "
                    "partitioned (parallel/partition.py)",
            "kv_shadow": "the host shadow store (and swap preemption, /kv "
                         "export): it copies K/V block pairs and would "
                         "leave a block's state tail behind; pass "
                         "--no-kv-shadow",
            "unchunked": "the unchunked ragged admission: a row's state "
                         "rides the mixed launch's slot rows only",
            "spec": "speculative decoding: a rejected draft token would "
                    "already be in a convolution layer's state",
            "no_pool": "a dense slot fleet: there is no dense recurrent "
                       "fleet (pass --kv-pool-blocks)",
        })
    if cfg.sparse_layers:
        lead = "a model of sparse and linear attention layers is served " \
               "on one device from the paged pool by chunked ragged prefill"
        why.update({
            "quant": "weight quantization: ops/quant knows no leaf of "
                     "either mixer",
            "kv_quant": "the int8 pool: the selected read walks raw pages "
                        "and the compressed keys have no scale",
            "mesh": "pp / tp / ep / sp / dp meshes: layers of two kinds, "
                    "the compressed-key leaf, a matrix state a slot and "
                    "the snapshot pool are not partitioned "
                    "(parallel/partition.py)",
            "kv_shadow": "the host shadow store (and swap preemption, /kv "
                         "export, the KV fabric): it copies K/V block "
                         "pairs and would leave the compressed keys and "
                         "the state snapshots behind; pass --no-kv-shadow",
            "unchunked": "the unchunked ragged admission: a row's state "
                         "rides the mixed launch's slot rows only",
            "spec": "speculative decoding: a rejected draft token would "
                    "already be in a linear layer's state",
            "no_pool": "a dense slot fleet: there is no dense fleet of "
                       "compressed keys and matrix states (pass "
                       "--kv-pool-blocks)",
        })
    elif cfg.linear_layers:
        lead = "a model of state-space and attention layers is served on " \
               "one device from the paged pool by chunked ragged prefill"
        why.update({
            "quant": "weight quantization: ops/quant knows no leaf of the "
                     "state-space mixer",
            "kv_quant": "the int8 pool: heads are stored in pairs and the "
                        "recurrent states have no scale",
            "mesh": "pp / tp / ep / sp / dp meshes: layers of two kinds, "
                    "a convolution state and a matrix state a slot and "
                    "the snapshot pool are not partitioned "
                    "(parallel/partition.py)",
            "kv_shadow": "the host shadow store (and swap preemption, /kv "
                         "export, the KV fabric): it copies K/V block "
                         "pairs and would leave the state snapshots "
                         "behind; pass --no-kv-shadow",
            "unchunked": "the unchunked ragged admission: a row's states "
                         "ride the mixed launch's slot rows only",
            "spec": "speculative decoding: a rejected draft token would "
                    "already be in a state-space layer's states",
            "no_pool": "a dense slot fleet: there is no dense fleet of "
                       "convolution and matrix states (pass "
                       "--kv-pool-blocks)",
        })
    if len(cfg.kv_groups) > 1:
        lead = "a model with window and global layers is served on one " \
               "device from a pool grouped by layer kind, by chunked " \
               "ragged prefill"
        why.update({
            "kv_shadow": "the host shadow store (and swap preemption, /kv "
                         "export, the KV fabric): it copies one group's "
                         "block pairs and knows no second table; pass "
                         "--no-kv-shadow",
            "unchunked": "the unchunked ragged admission: window blocks "
                         "are given out and taken back launch by launch",
            "spec": "speculative decoding: the host's position model "
                    "decides which window blocks a row holds, and has to "
                    "be exact",
        })
    bad = [why[name] for name, value in asked.items() if value]
    if bad:
        raise ValueError(
            f"{cfg.name}: {lead}, which does not carry: " + "; ".join(bad)
        )


def _routed_reset(pool):
    """A step program's first act on a latent pool: zero what the last
    launch routed. A per-head pool passes through untouched."""
    if "routed" not in pool:
        return pool
    return {**pool, "routed": jnp.zeros_like(pool["routed"])}


def _pack_rows(packed, routed):
    pad = -routed.size % packed.shape[1]
    flat = jnp.pad(routed.reshape(-1), (0, pad))
    return jnp.concatenate([packed, flat.reshape(-1, packed.shape[1])], axis=0)


@jax.jit
def pack_routed(packed, routed):
    """The launch's packed int32 fetch [rows, B] with the routed counts
    [2, L_moe, E] appended as further rows (zero-padded to whole rows):
    they reach the host in the ONE blocking fetch, never a second."""
    return _pack_rows(packed, routed)


def unpack_routed(packed, shape):
    """Host side (numpy): (the launch's own rows, routed [2, L_moe, E])."""
    B = packed.shape[1]
    size = shape[0] * shape[1] * shape[2]
    rows = -(-size // B)
    return (packed[:-rows],
            packed[-rows:].reshape(-1)[:size].reshape(shape))


def _latent_rows(pool_c, layer, table):
    """A table's blocks of one layer of the stacked latent pool
    [L, N, 1, bs, R] as contiguous rows [..., MB * bs, R] (the gather
    path: a gather of the table's blocks, never a slice of the layer)."""
    g = pool_c[layer, table][..., 0, :, :]  # [..., MB, bs, R]
    return g.reshape(g.shape[:-3] + (-1, g.shape[-1]))


def _write_tokens(cache_k, cache_v, k, v, layer, blk, off):
    """XLA's form of both paged hooks' write: one token a row, k / v
    [B, 1, KV, Dh], scattered into the stacked pool leaves at
    (layer, blk[b], :, off[b], :). An int8 pool quantizes the token and
    scatters data and scale; a latent pool (v None) has the one leaf.
    Returns (cache_k, cache_v)."""

    def put(leaf, new):  # new [B, 1, KV(, Dh)]
        return leaf.at[layer, blk, :, off].set(new[:, 0])

    if v is None:
        return put(cache_k, k), None
    if isinstance(cache_k, KVQuant):
        (qk, sk), (qv, sv) = quantize_chunk(k), quantize_chunk(v)
        return (KVQuant(put(cache_k.q, qk), put(cache_k.s, sk)),
                KVQuant(put(cache_v.q, qv), put(cache_v.s, sv)))
    return put(cache_k, k), put(cache_v, v)


def _kernel_step(kernel, cache_k, cache_v, k, v, layer, blk, off,
                 update_gate):
    """The Pallas path of both paged hooks: write the step's tokens k / v
    [B, 1, KV, Dh] into layer `layer` of the stacked pool and attend.
    `kernel(pool_k, pool_v, write)` is the hook's call of its paged
    kernel. Returns (attn, cache_k, cache_v).

    Where the kernel can (ops/paged_attention.writes_in_place: every
    benchmark cell) it gets the pool whole and writes the tokens itself
    through the pool's aliased output, so the step holds no operation of
    the pool's or a layer's size. An XLA scatter in front of the kernel
    cannot do that: it wants the pool in another tiled layout than the
    kernel's operand, and the compiler then copies the pool between the
    two at every layer (PERF.md, PR 29). Elsewhere (a head dim that is not
    whole 128-lane tiles, an int8 pool, and the pp ring's gated writes,
    which land in the trash block) the layer's slice is cut out, XLA
    scatters into it, the kernel reads it, and it goes back: a layer's
    bytes a call, as before the pool was a carry."""
    from ..ops.paged_attention import writes_in_place

    if update_gate is None and writes_in_place(cache_k):
        return kernel(cache_k, cache_v, (layer, k, v))

    # (tree.map: an int8 leaf is two arrays, a latent pool's V is None)
    cut = functools.partial(
        jax.tree.map, lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0)
    )
    new_k, new_v = _write_tokens(cut(cache_k), cut(cache_v), k, v, 0, blk,
                                 off)
    first = functools.partial(jax.tree.map, lambda a: a[0])
    attn = kernel(first(new_k), first(new_v), None)
    back = functools.partial(
        jax.tree.map,
        lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0),
    )
    return attn, back(cache_k, new_k), back(cache_v, new_v)


def _gather_blocks_of(leaf, layer, ids):
    """The blocks `ids` [...] of one layer of a stacked pool leaf, in
    float32 if the leaf is int8: [..., KV, bs, Dh] (the gather path)."""
    if isinstance(leaf, KVQuant):
        return kv_dequantize(KVQuant(leaf.q[layer, ids], leaf.s[layer, ids]))
    return leaf[layer, ids]


def _chosen_positions(pages, MB: int, bs: int):
    """A selected read's page list (ops/paged_attention: plist [N, KV, L],
    count [N, KV] or None, chosen_at [N, KV, L] or None, a query each) as a
    mask over the row's logical positions [N, KV, MB x bs]: the gather
    path's form of the list."""
    plist, count, chosen_at = pages
    if chosen_at is None:
        chosen_at = jnp.arange(plist.shape[-1]) < count[..., None]
    N, KV, _ = plist.shape
    blocks = jnp.zeros((N, KV, MB + 1), bool).at[
        jnp.arange(N)[:, None, None], jnp.arange(KV)[None, :, None],
        jnp.where(chosen_at, plist, MB)].set(True)[..., :MB]
    return jnp.repeat(blocks, bs, axis=-1)


def _attend_chosen(cfg, q, keys, values, mask):
    """The gather path's attention under a mask a KV head: q [N, 1, H, Dh],
    keys / values [N, KV, S, Dh], mask [N, KV, 1, S]."""
    KV = keys.shape[1]
    group = q.shape[2] // KV
    return jnp.concatenate([
        attend(q[:, :, h * group:(h + 1) * group], keys[:, h:h + 1],
               values[:, h:h + 1], mask[:, h], scale=cfg.query_scale,
               softcap=cfg.attn_softcap)
        for h in range(KV)], axis=2)


def make_paged_hook(table: jnp.ndarray, active=None):
    """attn_hook for a decode step over a paged pool (llama family, gpt2,
    mla_moe).

    The paged contract (`hook.paged`): forward_layers does NOT unstack the
    pool. It carries the stacked leaves through its layer scan beside x and
    hands the hook the whole leaf, cache_k/v [L, N, KV, bs, Dh] (latent:
    [L_stack, N, 1, bs, R]; int8: KVQuant pairs), and `layer`, the traced
    index of the layer being run. The step's tokens go into that layer of
    the leaf in place (the kernel's own write, or XLA's scatter:
    _kernel_step, _write_tokens) and attention reads the layer's blocks out
    of the same buffer, so no operation of a step is as large as the pool;
    a scan that took the pool as xs and returned it as ys sliced every
    layer out and stacked a second pool (PERF.md, PR 29).

    table: [B, max_blocks] int32 physical block ids; per-row positions pos
    [B]; the chunk is always T=1 (decode).
    active: [B] bool slot liveness (SlotState.active), or None for every
    row live. The fused kernel walks no KV block for a row whose flag is
    false — a freed slot's position stays frozen at its last request's
    length — and returns zeros there; the row's logits are discarded by
    slot_step either way. The gather path ignores it.
    """

    def hook(cfg, q, k, v, cache_k, cache_v, pos, mask, update_gate,
             valid_start, window_flag=None, layer=None, pages=None,
             sink=None):
        del valid_start  # slots never left-pad
        # window_flag (mixed per-layer patterns): the XLA gather path
        # ignores it — decoder_layer resolved `mask` per layer already —
        # but the fused kernel derives its traced width from it below
        B, T, H, Dh = q.shape
        assert T == 1, "paged hook serves decode steps (T=1) only"
        bs = cache_k.shape[3]
        MB = table.shape[1]
        # Write: token K/V -> pool[layer, table[b, pos_b//bs], :, pos_b%bs].
        # The lblk clamp is the overrun guard: an inactive slot's frozen
        # row keeps forwarding its pad token and its pos can sit one past
        # the budget — the clamped write lands garbage in the slot's OWN
        # last block at a position only its own (masked, discarded) rows
        # ever attend. Same argument as the dense fleet's
        # dynamic_update_slice clamp (ops/attention.update_kv_cache_slots).
        lblk = jnp.minimum(pos // bs, MB - 1)  # [B]
        blk = jnp.take_along_axis(table, lblk[:, None], axis=1)[:, 0]  # [B]
        if update_gate is not None:
            # pp ring: a stage applies its layer shard EVERY microstep but
            # owns the live buffer on exactly one — ungated microsteps
            # redirect their scatter to the write-only TRASH block (table
            # tails only map logical positions past every slot's budget,
            # so trash content is never attended). Same slice-granularity
            # discard as the dense pipeline's gated cache writes.
            blk = jnp.where(update_gate, blk, TRASH_BLOCK)
        off = pos % bs
        if cfg.attn_impl == "pallas":
            # Fused Pallas paged attention (ops/paged_attention.py): each
            # live row walks its own live blocks of the table with an
            # online softmax — no contiguous-view materialization; int8
            # pools dequantize in the block prologue. Softcap and scale
            # overrides are static kernel params, and mixed per-layer
            # window patterns feed this layer's width through the
            # window_dyn scalar-prefetch operand (window_flag only exists
            # for mixed configs — models/llama.make_window_flags). A
            # latent pool (models/mla_moe.py; v None): k is the token's
            # one row, q the absorbed queries, the walk in latent form.
            from ..models.llama import kernel_window
            from ..ops.paged_attention import paged_flash_attend

            w, wd = (None, None) if v is None else kernel_window(
                cfg, window_flag)
            return _kernel_step(
                lambda pool_k, pool_v, write: paged_flash_attend(
                    q, pool_k, pool_v, table, pos, wd, active, write,
                    None if pages is None else pages[:2], sink,
                    window=w, scale=cfg.query_scale,
                    softcap=None if v is None else cfg.attn_softcap,
                    value_dim=cfg.kv_lora_rank if v is None else None,
                ),
                cache_k, cache_v, k, v, layer, blk, off, update_gate,
            )
        new_k, new_v = _write_tokens(cache_k, cache_v, k, v, layer, blk, off)
        if v is None:  # the gathered latent rows, the slow way
            from ..models.mla_moe import latent_attend

            attn = latent_attend(cfg, q, _latent_rows(new_k, layer, table),
                                 mask)
            return attn, new_k, None

        # Gather the whole table -> ONE contiguous per-slot view recipe
        # for both leaf types (int8 slabs dequantize through the dense
        # path's ops/kv_quant.dequantize; raw slabs gather as-is). Each
        # gathered slab is a [KV, bs, Dh] contiguous run of HBM; stale
        # content at logical positions > pos[b] (trash block included) is
        # masked by the slot causal mask, which forward_layers built to
        # the LOGICAL length MB*bs via attn_seq_len.
        KV_ = cache_k.shape[2]

        def gathered(leaf):
            g = _gather_blocks_of(leaf, layer, table)  # [B, MB, KV, bs, Dh]
            return g.transpose(0, 2, 1, 3, 4).reshape(B, KV_, MB * bs, -1)

        if pages is not None:  # a selected read: the mask is the list's
            kv_pos = jnp.arange(MB * bs, dtype=jnp.int32)
            causal = (kv_pos[None, :] <= pos[:, None])[:, None, None]
            attn = _attend_chosen(
                cfg, q, gathered(new_k), gathered(new_v),
                causal & _chosen_positions(pages, MB, bs)[:, :, None])
            return attn, new_k, new_v
        attn = attend(
            q, gathered(new_k), gathered(new_v), mask,
            scale=cfg.query_scale, softcap=cfg.attn_softcap, sink=sink,
        )
        return attn, new_k, new_v

    hook.paged = True  # forward_layers carries the stacked pool (above)
    hook.tile = 1  # queries a tile of the walk: a decode row's one
    hook.live = active  # rows routed experts compute for (models/mla_moe)
    hook.rows = functools.partial(_decode_rows, table, active)
    # a grouped pool's launch carries its groups' tables side by side
    hook.group = lambda g, n: make_paged_hook(_group_table(table, g, n),
                                              active)
    return hook


def _group_table(table, g: int, n: int):
    """Group g's block table of a launch table [R, n x MB] that carries n
    groups' side by side (models/afmoe.py; a uniform model's has one)."""
    MB = table.shape[1] // n
    return table[:, g * MB:(g + 1) * MB]


def _gather_blocks(shared_pool, table_row):
    """`table_row`'s pool blocks as one CONTIGUOUS batch-1 cache, leaf by
    leaf (the serving path reads the pool through its tables in place;
    this is the tests' tool for holding what a row's blocks hold against
    a dense cache)."""

    def g(pl):
        # pl [L, N, KV, bs(, Dh)] -> row blocks [L, MB, KV, bs(, Dh)] ->
        # contiguous batch-1 layout [L, 1, KV, MB*bs(, Dh)]; the int8
        # pool's scale leaves ride the same recipe one rank down
        blocks = pl[:, table_row]
        if pl.ndim == 5:
            L, MB, KV, bs, Dh = blocks.shape
            flat = blocks.transpose(0, 2, 1, 3, 4).reshape(L, KV, MB * bs, Dh)
        else:
            L, MB, KV, bs = blocks.shape
            flat = blocks.transpose(0, 2, 1, 3).reshape(L, KV, MB * bs)
        return flat[:, None]

    return jax.tree.map(g, shared_pool)


def _gather_shadow(shared_pool, block_ids):
    """Core of gather_shadow_blocks (un-jitted so the pp backend's
    shard_map body can trace it layer-locally — the gather reads whole
    blocks of the LOCAL layer shard, so it runs unchanged on a
    layer-sharded pool slice)."""

    def g(pl):
        return pl[:, block_ids].swapaxes(0, 1)

    return jax.tree.map(g, shared_pool)


def _restore_shadow(pool, blocks, block_ids):
    """Core of restore_shadow_blocks (un-jitted for the same shard_map
    reuse: the scatter is layer-local — each stage writes its own layer
    slice of every restored block)."""

    def s(pl, bl):
        return pl.at[:, block_ids].set(bl.swapaxes(0, 1))

    return jax.tree.map(s, pool, blocks)


@jax.jit
def gather_shadow_blocks(shared_pool, block_ids):
    """Read `block_ids`' pool blocks into a fresh stacked buffer for the
    warm-recovery shadow store (engine/shadow.py): each leaf comes back
    [N, L, KV, bs(, Dh)] — one row per requested block, whole layer
    axis. Dispatched by the scheduler worker right AFTER the launch that
    filled the blocks, so device execution order guarantees the gathered
    bytes are the blocks' final (immutable) content; the device->host
    transfer happens on the shadow copier thread, never here.

    shared_pool is a READ-ONLY view of live mapped blocks and must NOT
    be donated: live block tables keep reading these exact buffers
    (analysis/rules/donation.py enforces the inverse of its usual
    donate-your-cache rule for this parameter name). block_ids is
    a fixed-width operand (callers pad by repeating a real id) so one
    compiled program serves every capture batch.
    """
    return _gather_shadow(shared_pool, block_ids)


@functools.partial(jax.jit, donate_argnames=("pool",))
def restore_shadow_blocks(pool, blocks, block_ids):
    """Scatter host-restored shadow blocks back into a rebuilt pool in
    ONE launch — the exact inverse of gather_shadow_blocks. `blocks` is
    the pool-structured pytree of stacked per-block leaves
    [N, L, KV, bs(, Dh)]; block_ids [N] the freshly allocated physical
    destinations. The pool is donated (updated in place); restored
    blocks are complete by construction, so later tail prefills and
    decode writes only ever land at positions past them — the same
    immutability contract live blocks carry."""
    return _restore_shadow(pool, blocks, block_ids)


def _forward_step_paged(cfg, params, tokens, pool, table, pos, pages=None,
                        active=None):
    """One decode step through the stack over the paged pool (family-
    dispatched: gpt2 rides the same hook seam). pages: optional [B] i32
    adapter-pool page ids (0 = base) — traced, so adapter mixes never
    recompile. active: optional [B] bool — rows whose output nothing
    reads (make_paged_hook)."""
    from ..models import api as M

    bs = pool_block_size(pool)
    MB = table.shape[1]
    x = M.embed(cfg, params, tokens, pos)
    x, pool = M.forward_layers(
        cfg, params["layers"], x, pool, pos,
        attn_hook=make_paged_hook(table, active), attn_seq_len=MB * bs,
        lora_pages=pages,
    )
    logits = M.unembed(cfg, params, x[:, -1:, :])
    return logits[:, 0, :], pool


# -- generation by diffusion over blocks (cfg.diffusion_block > 0) -------------
#
# A row's sequence is cut into blocks of B tokens at absolute positions.
# Blocks below state.pos are CLEAN: every token is known. All but the last of
# them are COMMITTED: their K/V is in the pool, computed from clean tokens
# under the block mask (ops/attention.block_frontier), and never changes. The
# block at state.pos is OPEN: DiffState.open holds its B tokens,
# cfg.mask_token_id where nothing is revealed yet. The block just below it
# may be OWED its commit (DiffState.owe): its tokens (DiffState.owed) are
# known, but what the pool holds at its positions is what its last denoise
# forward wrote, computed while it still held masks.
#
# A forward carries, as query tokens of ONE row through the ragged seam (one
# tile at q_start, like a draft-and-verify row), the owed block at its own
# positions and then the open block (2B tokens from state.pos - B), or the
# open block alone (B tokens from state.pos) where nothing is owed: a row's
# first generated block, whose predecessors admission committed, and every
# forward of a block after its first. It writes their K/V and reads the
# logits AT the open block's positions. Under the block mask the owed
# block's tokens see nothing of the open block and the open block's see all
# of the owed one, so what lands in the pool for the owed block is what a
# forward of its clean tokens alone would write, and the open block reads
# it in the same pass: the commit costs no forward of its own.
#
#   * Every forward is a DENOISE forward: the `reveal` leftmost masked
#     positions of the open block take their token (the mask id's logit at
#     -inf). What it wrote for the open block is the K/V of a block still
#     holding masks: the row's next forward overwrites it before reading it,
#     no other row's table maps the block, and the prefix index only ever
#     registers blocks below a prompt's length, which admission committed.
#   * When it reveals the block's last mask the block is clean: its
#     generated tokens are emitted by THAT forward (not the prompt's
#     remainder at its head, `skip`; none past the budget or a stop token),
#     state.pos moves on by B, the clean block becomes the owed one and the
#     next block opens, all masks.
#   * A row that its budget or a stop token ends there owes nothing: ITS
#     LAST BLOCK IS NEVER COMMITTED. Nothing reads it: the row runs no
#     further forward, no other table maps the block, a preempted row
#     resumes from its delivered tokens as a prompt, and the prefix index
#     holds prompt blocks alone.
#
# The count a forward reveals is fixed per row at admission (B /
# denoise_steps), so the host's position model knows what every forward
# carries and the row's last forward without a fetch (engine/continuous).


class DiffState(NamedTuple):
    """Device-side per-slot state of block diffusion, beside SlotState
    (whose pos is the open block's first position, and whose token,
    presence and counts a diffusion row does not use). Also the shape of a
    completing prefill's arming operands beside MixedArm (`darm`: the
    prompt's remainder then masks, its length, the row's reveal count,
    nothing owed; rows with arm.on False are untouched)."""

    open: jnp.ndarray  # i32 [B, block]: the open block's tokens
    skip: jnp.ndarray  # i32 [B]: prompt tokens at the open block's head
    # (a prompt's last partial block; 0 from the second block on)
    reveal: jnp.ndarray  # i32 [B]: masked positions a forward reveals
    owed: jnp.ndarray  # i32 [B, block]: the clean block below state.pos
    owe: jnp.ndarray  # bool [B]: its K/V is not in the pool yet


def init_diffusion(cfg: ModelConfig, n_slots: int) -> DiffState:
    z = jnp.zeros((n_slots,), jnp.int32)
    blocks = jnp.full((n_slots, cfg.diffusion_block), cfg.mask_token_id,
                      jnp.int32)
    return DiffState(blocks, z, z + cfg.diffusion_block, blocks,
                     jnp.zeros((n_slots,), bool))


def block_row_tokens(diff: DiffState, rows, off):
    """The token of each flat position of a forward: `off` places from its
    row's state.pos, the owed block below 0 and the open block from 0."""
    Bd = diff.open.shape[1]
    return jnp.where(
        off < 0, diff.owed[rows, jnp.maximum(off + Bd, 0)],
        diff.open[rows, jnp.clip(off, 0, Bd - 1)],
    )


def open_block_rows(x, dec_idx, Bd: int):
    """The flat positions [B * block, ...] of every row's OPEN block, whose
    first stands at dec_idx [B]: the head runs over these alone."""
    at = jnp.maximum(dec_idx, 0)[:, None] + jnp.arange(Bd)[None, :]
    return x[at.reshape(-1)]


def block_row_layout(state: G.SlotState, diff: DiffState):
    """The flat axis of a forward that carries every live row, 2 x block
    positions a row (one query tile): the owed block from state.pos - block
    then the open one, or the open block from state.pos then launch padding
    (tok_row -1); a row that is not active carries nothing (q_len 0: not
    walked, not written, no expert). Returns (tokens, tok_row, tok_pos
    [B * 2 * block], meta [B, 4], the flat index [B] of each row's open
    block)."""
    S, Bd = diff.open.shape
    rows = jnp.arange(S, dtype=jnp.int32)
    below = jnp.where(diff.owe & state.active, Bd, 0)  # [S]
    q_len = jnp.where(state.active, below + Bd, 0)
    j = jnp.arange(2 * Bd, dtype=jnp.int32)[None, :]
    off = (j - below[:, None]).reshape(-1)
    rows_ix = jnp.repeat(rows, 2 * Bd)
    tok_row = jnp.where(j < q_len[:, None], rows[:, None], -1).reshape(-1)
    meta = jnp.stack(
        [rows, state.pos - below, q_len,
         jnp.full((S,), RAGGED_PREFILL, jnp.int32)], axis=1,
    )
    return (block_row_tokens(diff, rows_ix, off), tok_row,
            state.pos[rows_ix] + off, meta, rows * (2 * Bd) + below)


def _forward_blocks_paged(cfg, params, state: G.SlotState, diff: DiffState,
                          pool, table):
    """One forward of every live row over the paged pool
    (`block_row_layout`): float32 logits [B, block, V] at the OPEN block's
    positions, and the pool with the owed and open blocks' K/V written."""
    from ..models import api as M

    S, Bd = diff.open.shape
    toks, tok_row, tok_pos, meta, open_at = block_row_layout(state, diff)
    x = M.embed(cfg, params, toks[:, None], tok_pos)
    x, pool = M.forward_layers(
        cfg, params["layers"], x, pool, tok_pos,
        attn_hook=make_ragged_fill_hook(table, meta, tok_row),
        attn_seq_len=1,
    )
    logits = M.unembed(cfg, params, open_block_rows(x, open_at, Bd))
    return logits[:, 0, :].reshape(S, Bd, -1), pool


@jax.named_scope("sample")
def diffusion_step(cfg: ModelConfig, state: G.SlotState,
                   sparams: G.SlotParams, diff: DiffState, logits, key,
                   on=None):
    """ONE copy of a block-diffusion forward's bookkeeping (the decode
    chunk's loop and the mixed launch both call it). logits [B, block, V]:
    the model's output AT the open block's positions (no shift by one).
    on: rows that rode this forward (None: every row).

    The token of a masked position is chosen from its own logits with the
    mask id suppressed, by the row's sampling knobs (penalties do not
    apply: admission refuses them). A row reveals its `reveal` leftmost
    masked positions; where that leaves no mask the block is clean, is
    emitted and becomes the owed one (see the section comment). A row that
    rode carried what it owed. Returns (state, diff, emit [B, block],
    emit_ok [B, block])."""
    from ..ops.sampling import sample_token, suppress_token

    Bd = cfg.diffusion_block
    mask_id = jnp.int32(cfg.mask_token_id)
    pad = jnp.int32(cfg.pad_token_id)
    live = state.active if on is None else state.active & on
    masked = diff.open == mask_id  # [B, block]
    # the leftmost `reveal` masked positions take their token
    cand = sample_token(
        key, suppress_token(logits.astype(jnp.float32), cfg.mask_token_id),
        sparams.temperature[:, None, None], sparams.top_k[:, None, None],
        sparams.top_p[:, None, None], (sparams.greedy | ~live)[:, None],
        sparams.min_p[:, None, None],
    )
    rank = jnp.cumsum(masked.astype(jnp.int32), axis=1) - 1
    show = live[:, None] & masked & (rank < diff.reveal[:, None])
    opened = jnp.where(show, cand, diff.open)
    clean = live & ~jnp.any(opened == mask_id, axis=1)
    # a clean block: emit its generated tokens
    j = jnp.arange(Bd, dtype=jnp.int32)[None, :]
    gen = j >= diff.skip[:, None]
    order = j - diff.skip[:, None]  # a token's place among the generated
    stop = G.stop_mask(cfg, opened) & gen
    before = jnp.cumsum(stop.astype(jnp.int32), axis=1) == 0
    room = order < state.remaining[:, None]
    emit_ok = clean[:, None] & gen & before & room
    n_emit = jnp.sum(emit_ok.astype(jnp.int32), axis=1)
    # a stop token ends the row only where plain decoding would have
    # reached it: inside the budget
    saw_stop = clean & jnp.any(stop & room, axis=1)
    remaining = state.remaining - n_emit
    emit = jnp.where(emit_ok, opened, pad)
    state = state._replace(
        pos=state.pos + jnp.where(clean, Bd, 0),
        active=jnp.where(clean, ~saw_stop & (remaining > 0), state.active),
        remaining=remaining,
    )
    diff = DiffState(
        open=jnp.where(clean[:, None], mask_id, opened),
        skip=jnp.where(clean, 0, diff.skip),
        reveal=diff.reveal,
        owed=jnp.where(clean[:, None], opened, diff.owed),
        # a row that goes on owes its clean block; one that ended owes nothing
        owe=jnp.where(live, clean & state.active, diff.owe),
    )
    return state, diff, emit, emit_ok


def steps_while_active(step, state: G.SlotState, carry, key, *,
                       num_steps: int, emit_shape: tuple, pad):
    """A decode chunk's step loop, ONE copy for both bodies of
    decode_slots_paged: run `step(state, carry, key_i) -> (state, carry,
    emit, ok)` for i = 0, 1, ... while i < num_steps AND some row of the
    fleet is active. num_steps is the static upper bound (one program a
    configuration, as under the scan this replaced); the exit is what the
    program observes in its own state. Step i takes
    jax.random.split(key, num_steps)[i] whatever the trip count, and writes
    row i of emitted / emit_mask [num_steps, *emit_shape], which hold pad /
    False from the exit on. A step that runs is the step the scan ran; one
    that does not would have changed frozen rows' garbage alone (slot_step
    freezes an inactive row). Returns (emitted, emit_mask, state, carry,
    steps_run i32 [])."""
    subs = jax.random.split(key, num_steps)
    shape = (num_steps, *emit_shape)

    def live(c):
        return (c[0] < num_steps) & jnp.any(c[1].active)

    def body(c):
        i, state, carry, emitted, emit_mask = c
        state, carry, emit, ok = step(state, carry, subs[i])
        return (i + 1, state, carry, emitted.at[i].set(emit),
                emit_mask.at[i].set(ok))

    i, state, carry, emitted, emit_mask = jax.lax.while_loop(
        live, body,
        (jnp.int32(0), state, carry, jnp.full(shape, pad, jnp.int32),
         jnp.zeros(shape, bool)),
    )
    return emitted, emit_mask, state, carry, i


@functools.partial(
    jax.jit, static_argnames=("cfg", "num_steps"), donate_argnames=("pool",)
)
def decode_slots_paged(
    cfg: ModelConfig,
    params,
    state: G.SlotState,
    pool,
    table: jnp.ndarray,
    key,
    sparams: G.SlotParams,
    *,
    num_steps: int,
    pages=None,
    diff=None,
):
    """Paged twin of generate.decode_slots: advance every slot UP TO
    num_steps tokens over the block pool. Same slot_step, same
    emitted/emit_mask contract — only the cache strategy differs, so
    cross-mode token parity is structural. The table is a plain (traced)
    input: admission changes it without recompiling. pages: optional [B]
    i32 per-slot adapter pages (0 = base), traced like the table.

    The chunk ends when its last live row does (`steps_while_active`): no
    forward runs for a fleet with no active row, emitted / emit_mask hold
    pad / False from there on, and the count of steps that ran comes back
    LAST (steps_run, i32 []: num_steps where a row outlives the chunk, 0
    for a fleet that was dead at dispatch).

    A block-diffusion model (cfg.diffusion_block > 0, `diff` its
    DiffState) runs up to num_steps FORWARDS instead, each carrying every
    live row's whole open block (`_forward_blocks_paged`,
    `diffusion_step`): emitted / emit_mask are [num_steps * block, B] (a
    forward's block row by row) and the DiffState comes back before the
    count."""

    pool = _routed_reset(pool)
    B = state.active.shape[0]
    pad = jnp.int32(cfg.pad_token_id)
    if cfg.diffusion_block:
        def forward(state, carry, sub):
            diff, pool = carry
            logits, pool = _forward_blocks_paged(
                cfg, params, state, diff, pool, table
            )
            state, diff, emit, ok = diffusion_step(
                cfg, state, sparams, diff, logits, sub
            )
            return state, (diff, pool), emit.T, ok.T

        emitted, emit_mask, state, (diff, pool), steps_run = (
            steps_while_active(
                forward, state, (diff, pool), key, num_steps=num_steps,
                emit_shape=(cfg.diffusion_block, B), pad=pad,
            )
        )
        rows = (num_steps * cfg.diffusion_block, B)
        return (emitted.reshape(rows), emit_mask.reshape(rows), state, pool,
                diff, steps_run)

    def body(state, pool, sub):
        logits, pool = _forward_step_paged(
            cfg, params, state.token[:, None], pool, table, state.pos,
            pages=pages, active=state.active,
        )
        new, emit, can_emit = G.slot_step(cfg, state, sparams, logits, sub)
        return new, pool, emit, can_emit

    return steps_while_active(
        body, state, pool, key, num_steps=num_steps, emit_shape=(B,),
        pad=pad,
    )


# -- ragged ingest: prefill straight into the pool, no bucket ladder ----------
#
# The dense fleet prefills a request on a CONTIGUOUS batch-1 scratch cache
# (chunked through the prefill-bucket ladder) and splices the scratch row
# into its slot. A paged fleet has no scratch, no scatter into the pool
# and no gather of a shared head back out of it: the prompt tail is laid
# out on a FLAT token axis (each
# token is a batch row of one — forward_layers' slots mode, so RoPE and
# the learned-position families take per-token positions for free), each
# token's K/V scatters directly into its row's pool block, and attention
# runs over the pool through the ragged kernel
# (ops/paged_attention.ragged_paged_attend) — or its XLA gather twin on
# CPU — reading the mapped shared head IN PLACE. One compiled program per
# launch width covers ANY tail length (the last launch pads with dead
# tiles, which the kernel does not walk), so the block-prefix planner reuses at
# exact chunk depth instead of degrading to a bucket boundary.

RAGGED_PREFILL = 0  # launch-entry kind: a prompt chunk (length >= 1)
RAGGED_DECODE = 1  # launch-entry kind: one decode token at its own pos
# a prompt chunk that is its tenant's FIRST: the row's recurrent state does
# not come from the slot (models/lfm2.py; the kernels do not read the kind)
RAGGED_FIRST = 2


class StateRows(NamedTuple):
    """How a paged launch's flat tokens fall into fleet rows, for layers
    that carry a state a row (models/lfm2.conv_mix_rows): what a paged
    hook's `rows()` returns.

    A row that starts a tenant (`fresh`) takes nothing from its slot's live
    state, which may still be the previous tenant's (a slot is let again
    while that tenant's last launch is in flight): it starts from zeros at
    position 0, and from the tail of block table[row, (start - 1) // bs]
    after a prefix hit at depth `start` (whole blocks), which is the state
    a cold prefill would have reached there."""

    tok_row: jnp.ndarray  # i32 [W]: a flat token's fleet row; -1: launch
    # padding or a row nothing reads, which touches no state
    table: jnp.ndarray  # i32 [R, MB]: the rows' block tables
    fresh: jnp.ndarray  # bool [R]: the row starts a tenant in this launch
    start: jnp.ndarray  # i32 [R]: at this position (read where fresh)
    # a state too large to keep a block (models/minicpm_sala.py: "snap"): the
    # snapshot a fresh row starts from after a prefix hit (-1: zeros), and
    # the one the row's state after this launch is kept in (-1: none): the
    # mixed launch's `snaps` operand; None in a decode chunk, which starts no
    # tenant and keeps no snapshot
    restore: Optional[jnp.ndarray] = None  # i32 [R]
    take: Optional[jnp.ndarray] = None  # i32 [R]


def _decode_rows(table, active) -> StateRows:
    R = table.shape[0]
    rows = jnp.arange(R, dtype=jnp.int32)
    if active is not None:
        rows = jnp.where(active, rows, -1)
    return StateRows(rows, table, jnp.zeros((R,), bool),
                     jnp.zeros((R,), jnp.int32))


def _ragged_rows(table, meta, tok_row) -> StateRows:
    R = table.shape[0]
    first = (meta[:, 3] == RAGGED_FIRST) & (meta[:, 2] > 0)
    row = jnp.maximum(meta[:, 0], 0)
    far = jnp.iinfo(jnp.int32).max
    start = jnp.full((R,), far, jnp.int32).at[row].min(
        jnp.where(first, meta[:, 1], far))
    fresh = start < far
    return StateRows(tok_row, table, fresh, jnp.where(fresh, start, 0))


def build_ragged_meta(entries, *, width: int, tile: int):
    """HOST-side launch planner for the ragged ingest programs (strictly
    decode-unreachable — pinned in the test_analysis.py callgraph
    fixture, like utils/faults.py).

    entries: [(row, start, length, kind)] — each fleet row's contribution
    to this launch, in flat-token order; a decode row is (row, pos, 1,
    RAGGED_DECODE), a prefill chunk (row, chunk_start, chunk_len,
    RAGGED_PREFILL). Every entry starts on a query-tile boundary, so an
    entry's tokens occupy flat slots [offset, offset + length)
    contiguously (all its tiles but the last are full).

    Returns (meta [G, 4] int32, tok_row [W] int32, tok_pos [W] int32,
    offsets, stats): meta is the per-tile (row, q_start, q_len, kind)
    array the kernel prefetches; tok_row / tok_pos are the per-token row
    index (-1 = launch padding, scattered to the trash block) and
    absolute position; offsets[i] is entry i's flat token offset; stats
    counts tiles/pad_tiles/rows-by-kind for the dli_ragged_* metrics.
    Dead tiles copy their predecessor's (row, q_start) with q_len 0:
    the kernel walks nothing for them (ops/paged_attention._walk_kernel)
    and the row index stays a valid one.

    The plan's POSITIONAL half is only authoritative where the host
    position model is exact. For decode/verify rows in the mixed
    scheduler launch the serving path marks the tiles/slots with
    build_device_meta and the program substitutes state.pos on device
    (apply_device_meta) — the start values planned here become
    placeholders there, which is what lets verify rows launch
    back-to-back without waiting for their fetch (ISSUE 15).
    """
    import numpy as np

    if width % tile != 0:
        raise ValueError(f"ragged width {width} must be a multiple of the "
                         f"query tile {tile}")
    G = width // tile
    meta = np.zeros((G, 4), np.int32)
    tok_row = np.full((width,), -1, np.int32)
    tok_pos = np.zeros((width,), np.int32)
    offsets = []
    stats = {"tiles": G, "pad_tiles": 0, "prefill_rows": 0, "decode_rows": 0}
    g = 0
    for row, start, length, kind in entries:
        if length < 1:
            raise ValueError("ragged launch entries need length >= 1")
        need = -(-length // tile)
        if g + need > G:
            raise ValueError(
                f"launch overflow: {length} tokens need {need} tiles, "
                f"{G - g} left of {G}"
            )
        offsets.append(g * tile)
        stats["decode_rows" if kind == RAGGED_DECODE else "prefill_rows"] += 1
        for t in range(need):
            q_len = min(tile, length - t * tile)
            q_start = start + t * tile
            meta[g] = (row, q_start, q_len, kind)
            w = g * tile
            tok_row[w : w + q_len] = row
            tok_pos[w : w + q_len] = q_start + np.arange(q_len)
            g += 1
    # launch padding: dead tiles inherit the predecessor's placement (a
    # valid row); q_len 0 gates their walk off
    stats["pad_tiles"] = G - g
    while g < G:
        if g > 0:
            meta[g] = meta[g - 1]
            meta[g, 2] = 0
        g += 1
    return meta, tok_row, tok_pos, offsets, stats


def _ragged_attend_xla(cfg, q, cache_k, cache_v, layer, table, tok_row,
                       tok_pos, window_flag, sink=None):
    """XLA twin of the ragged kernel: per-token gather of the owning
    row's blocks of `layer` out of the stacked pool into a contiguous
    logical view, then the stock masked attention. This is the CPU /
    debug reference (the kernel's interpret mode is the bit-exactness
    oracle); on TPU the kernel path avoids materializing the W x MB*bs
    view entirely. q [W, 1, H, Dh]."""
    from ..models.llama import kernel_window

    W = q.shape[0]
    KV, bs = cache_k.shape[2], cache_k.shape[3]
    MB = table.shape[1]
    S = MB * bs
    w, wd = kernel_window(cfg, window_flag)

    def win_mask(mask, kv_pos, q_pos):
        if wd is not None:
            mask &= (wd <= 0) | (kv_pos > q_pos - wd)
        elif w is not None:
            mask &= kv_pos > q_pos - w
        return mask

    if table.shape[0] == 1:
        # Single fleet row (the admission launch shape): gather the row's
        # logical view ONCE and attend the whole flat token axis as one
        # [1, W, S] batch — the same attention shape the bucketed scratch
        # prefill runs, with none of its gather/scatter bookends.
        def gathered1(leaf):
            g = _gather_blocks_of(leaf, layer, table[0])  # [MB, KV, bs, Dh]
            return g.transpose(1, 0, 2, 3).reshape(1, KV, S, -1)

        kv_pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        q_pos = tok_pos[:, None]
        q_end = block_frontier(q_pos, cfg.diffusion_block)
        mask = (kv_pos <= q_end) & (tok_row >= 0)[:, None]  # [W, S]
        mask = win_mask(mask, kv_pos, q_pos)
        out = attend(
            q[:, 0][None], gathered1(cache_k), gathered1(cache_v),
            mask[None], scale=cfg.query_scale, softcap=cfg.attn_softcap,
            sink=sink,
        )  # [1, W, H, Dh]
        return out[0][:, None]

    rows = jnp.maximum(tok_row, 0)
    row_table = table[rows]  # [W, MB]

    def gathered(leaf):
        g = _gather_blocks_of(leaf, layer, row_table)  # [W, MB, KV, bs, Dh]
        return g.transpose(0, 2, 1, 3, 4).reshape(W, KV, S, -1)

    kv_pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]
    q_pos = tok_pos[:, None, None]
    q_end = block_frontier(q_pos, cfg.diffusion_block)
    mask = (kv_pos <= q_end) & (tok_row >= 0)[:, None, None]
    mask = win_mask(mask, kv_pos, q_pos)
    return attend(
        q, gathered(cache_k), gathered(cache_v), mask,
        scale=cfg.query_scale, softcap=cfg.attn_softcap, sink=sink,
    )


def _ragged_latent_xla(cfg, q, pool_c, layer, table, tok_row, tok_pos):
    """XLA twin of the ragged kernel's latent form: each flat token's
    absorbed queries q [W, 1, H, R] against its row's gathered latent rows
    under the causal mask of its own position; launch padding attends
    nothing. One fleet row gathers once, as _ragged_attend_xla does."""
    from ..models.mla_moe import latent_attend

    S = table.shape[1] * pool_c.shape[3]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    mask = (kv_pos[None, :] <= tok_pos[:, None]) & (tok_row >= 0)[:, None]
    if table.shape[0] == 1:
        rows = _latent_rows(pool_c, layer, table)  # [1, S, R]
        return latent_attend(cfg, q[:, 0][None], rows, mask[None])[0][:, None]
    rows = _latent_rows(
        pool_c, layer, table[jnp.maximum(tok_row, 0)]
    )  # [W, S, R]
    return latent_attend(cfg, q, rows, mask[:, None, :])


def live_tokens(tok_row, width: int):
    """A launch's live flat tokens (tok_row >= 0) side by side on an axis of
    `width`, in flat order, so that a row's tokens stay contiguous and in
    order: (at [width], the flat index each place of the axis reads: past
    the live tokens, launch padding; back [W], the place each live flat
    token went to: for padding some place, whose values nothing reads). The
    host plans no launch of more live tokens than `width`
    (engine/scheduler.live_width)."""
    live = tok_row >= 0
    at = jnp.argsort(~live, stable=True)[:width].astype(jnp.int32)
    back = jnp.clip(jnp.cumsum(live, dtype=jnp.int32) - 1, 0, width - 1)
    return at, back


def model_axis(live_width, tok_row, toks, pos):
    """(compact, own, own_toks, pos) of a mixed step: the axis the model runs
    on. The tile layout itself (compact None) unless a `live_width` under the
    launch's width is given: then the live tokens alone, side by side
    (`live_tokens`), with their rows, tokens and positions on that axis.
    `toks` itself keeps the layout spec.idx reads the drafts from."""
    if live_width is None or live_width >= tok_row.shape[0]:
        return None, tok_row, toks, pos
    at, back = live_tokens(tok_row, live_width)
    return (at, back), tok_row[at], toks[at], pos[at]


def _take_rows(x, index):
    """x[index] along the first axis, gathered as whole 2-D rows: the form
    the products on either side of the ragged hook write and read (gathered
    as [n, 1, H, Dh] the output projection read a relaid copy: 0.36 ms a
    layer more at mimo-v2.5's widths, PERF.md section 6, PR 56)."""
    return x.reshape(x.shape[0], -1)[index].reshape(index.shape + x.shape[1:])


def make_ragged_fill_hook(table, meta, tok_row, snaps=None, compact=None):
    """attn_hook for the ragged ingest programs: flat-token layout
    ([W, 1] chunks — each token is a batch row at its own position, the
    slots-mode contract), per-token K/V scatter into the owning row's
    pool block, attention over the pool via the ragged kernel
    (attn_impl="pallas") or its XLA gather twin. The paged contract of
    make_paged_hook: the stacked pool leaf and the layer's index.

    table [R, MB]: the launch's fleet rows' block tables; meta [G, 4]:
    the per-tile launch plan (build_ragged_meta); tok_row [W]: per-token
    owning row, -1 for launch padding — padding writes are redirected to
    the write-only TRASH block, exactly like ungated pp microsteps; snaps
    (restore [R], take [R]): StateRows' snapshot indices, for a fleet
    whose state has a snapshot pool.

    compact (`live_tokens` of tok_row; None: the model runs on the tile
    layout itself): the model runs on the launch's live tokens alone, side
    by side on a shorter axis that has no tiles (`hook.tile` 1). q / k / v
    and pos come in on that axis and go to the tile layout here, which the
    kernel and the pool's write want, and the attended rows come back: the
    one place that knows both layouts.
    """

    def tiles(cfg, q, k, v, cache_k, cache_v, pos, mask, update_gate,
              valid_start, window_flag=None, layer=None, pages=None,
              sink=None):
        del mask, valid_start  # mask derived from pos/tok_row in-kernel
        W, T = q.shape[0], q.shape[1]
        assert T == 1, "ragged fill runs the flat token layout (T=1 rows)"
        bs = cache_k.shape[3]
        MB = table.shape[1]
        # Write: token w's K/V -> pool[layer, table[row_w, pos_w // bs],
        # :, pos_w % bs]. Launch padding (row -1) — and, on the pp ring,
        # microsteps whose stage doesn't own the buffer (update_gate) —
        # redirect to the trash block: colliding trash writes are
        # write-only garbage at positions nothing ever attends.
        rows_ix = jnp.maximum(tok_row, 0)
        lblk = jnp.minimum(pos // bs, MB - 1)  # [W]
        blk = table[rows_ix, lblk]  # [W]
        live = tok_row >= 0
        if update_gate is not None:
            live = live & update_gate
        blk = jnp.where(live, blk, TRASH_BLOCK)
        off = pos % bs
        if cfg.attn_impl == "pallas":  # as in make_paged_hook
            from ..models.llama import kernel_window
            from ..ops.paged_attention import ragged_paged_attend

            w, wd = (None, None) if v is None else kernel_window(
                cfg, window_flag)

            def kernel(pool_k, pool_v, write):
                out = ragged_paged_attend(
                    q[:, 0], pool_k, pool_v, table, meta, wd, write, pages,
                    sink, window=w, scale=cfg.query_scale,
                    softcap=None if v is None else cfg.attn_softcap,
                    value_dim=cfg.kv_lora_rank if v is None else None,
                    block=cfg.diffusion_block,
                )
                if write is None:
                    return out[:, None]
                attn, *pool = out
                return attn[:, None], *pool

            return _kernel_step(kernel, cache_k, cache_v, k, v, layer, blk,
                                off, update_gate)
        new_k, new_v = _write_tokens(cache_k, cache_v, k, v, layer, blk, off)
        if v is None:
            attn = _ragged_latent_xla(cfg, q, new_k, layer, table, tok_row,
                                      pos)
        elif pages is not None:
            rows_tab = table[rows_ix]  # [W, MB]
            KV_, S = cache_k.shape[2], MB * bs

            def gathered(leaf):
                g = _gather_blocks_of(leaf, layer, rows_tab)
                return g.transpose(0, 2, 1, 3, 4).reshape(W, KV_, S, -1)

            kv_pos = jnp.arange(S, dtype=jnp.int32)
            causal = ((kv_pos[None, :] <= pos[:, None])
                      & (tok_row >= 0)[:, None])[:, None, None]
            attn = _attend_chosen(
                cfg, q, gathered(new_k), gathered(new_v),
                causal & _chosen_positions(
                    (jnp.repeat(pages[0], W // meta.shape[0], axis=0),
                     None, pages[2]), MB, bs)[:, :, None])
        else:
            attn = _ragged_attend_xla(
                cfg, q, new_k, new_v, layer, table, tok_row, pos,
                window_flag, sink,
            )
        return attn, new_k, new_v

    hook, own = tiles, tok_row  # own: a token's row on the model's axis
    if compact is not None:
        at, back = compact
        own = tok_row[at]

        def hook(cfg, q, k, v, cache_k, cache_v, pos, *rest, **kw):
            attn, new_k, new_v = tiles(
                cfg, _take_rows(q, back), _take_rows(k, back),
                None if v is None else _take_rows(v, back),
                cache_k, cache_v, pos[back], *rest, **kw)
            return _take_rows(attn, at), new_k, new_v

    hook.paged = True  # forward_layers carries the stacked pool
    hook.tile = 1 if compact is not None else (
        tok_row.shape[0] // meta.shape[0])
    hook.live = own >= 0  # launch padding reaches no routed expert
    rows = functools.partial(_ragged_rows, table, meta, own)
    hook.rows = rows if snaps is None else (
        lambda: rows()._replace(restore=snaps[0], take=snaps[1]))
    hook.group = lambda g, n: make_ragged_fill_hook(
        _group_table(table, g, n), meta, tok_row, compact=compact)
    return hook


def _token_pages(pages, tok_row):
    """Per-flat-token adapter pages from a per-row page vector: token w
    rides pages[tok_row[w]]; launch padding (row -1) rides the base page
    (0), whose delta is skipped anyway. None passes through — programs
    without a pages operand lower byte-identically to today's."""
    if pages is None:
        return None
    return jnp.where(
        tok_row >= 0, pages[jnp.maximum(tok_row, 0)], jnp.int32(0)
    )


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("pool",))
def extend_ragged_paged(cfg: ModelConfig, params, tokens, tok_row, tok_pos,
                        meta, pool, table, pages=None):
    """One full ragged launch with no sampling — the chunked-prefill
    extend() twin over the pool. tokens [W] int32 flat launch tokens;
    tok_row/tok_pos [W]; meta [G, 4]; table [R, MB]. The pool is donated
    (updated in place); the table is read-only. pages: optional [R] i32
    per-table-row adapter pages — each flat token reads its owning row's
    page; launch padding (row -1) rides the base page."""
    from ..models import api as M

    x = M.embed(cfg, params, tokens[:, None], tok_pos)
    _, pool = M.forward_layers(
        cfg, params["layers"], x, pool, tok_pos,
        attn_hook=make_ragged_fill_hook(table, meta, tok_row),
        attn_seq_len=1, lora_pages=_token_pages(pages, tok_row),
    )
    return pool


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("pool",))
def prefill_ragged_paged(cfg: ModelConfig, params, tokens, tok_row, tok_pos,
                         meta, pool, table, sample_at, key, sampling,
                         presence=None, bias=None, pages=None):
    """Final ragged launch: run the tail chunk, unembed ONE flat position
    (`sample_at` — the entry's last valid token, traced so every tail
    length shares this compiled program) and sample the first token.
    Returns (first [1], logits [1, V], pool) — the G.prefill contract the
    admission wave's stacked fetch expects."""
    from ..models import api as M
    from ..ops.sampling import sample_token

    x = M.embed(cfg, params, tokens[:, None], tok_pos)
    x, pool = M.forward_layers(
        cfg, params["layers"], x, pool, tok_pos,
        attn_hook=make_ragged_fill_hook(table, meta, tok_row),
        attn_seq_len=1, lora_pages=_token_pages(pages, tok_row),
    )
    last = jax.lax.dynamic_slice_in_dim(x, sample_at, 1, axis=0)  # [1, 1, D]
    logits = M.unembed(cfg, params, last)[:, 0, :]
    first = sample_token(key, logits, *sampling, presence=presence, bias=bias)
    return first, logits, pool


@functools.partial(jax.jit, static_argnames=("cfg",))
def arm_slot_only(cfg: ModelConfig, state: G.SlotState,
                  sparams: G.SlotParams, slot, *arm):
    """Arm a slot with NO cache movement — the ragged ingest already wrote
    the prompt's K/V into the pool blocks, so admission needs only the
    state-side half of the dense fleet's insert (the same shared
    generate.arm_slot, so the budget / EOS-on-first semantics cannot
    drift)."""
    state, sparams = G.arm_slot(cfg, state, sparams, jnp.int32(slot), *arm)
    return state, sparams


# -- mixed launch: all decode rows + prefill chunks in ONE program ------------
#
# The chunked-prefill scheduler (engine/scheduler.py) stops prefilling an
# admission whole before it joins the decode fleet: each scheduler step is
# ONE launch of this program, carrying every active slot's decode token
# plus budget-sliced PREFILL chunks of pending admissions on the same flat
# token axis. Decode tokens/positions are gathered FROM THE SLOT STATE on
# device (the host never fetches to plan the next step — lag pipelining
# and the zero-host-sync launch invariant both survive), decode sampling
# is the shared generate.slot_step (cross-mode token parity is
# structural), and an admission whose FINAL chunk rides this launch
# samples its first token and arms its slot entirely on device
# (vectorized generate.arm_slot semantics) — the host learns the first
# token from the same packed fetch that carries the decode chunk.


class MixedArm(NamedTuple):
    """Per-slot arming operands for prefill chunks COMPLETING in a mixed
    launch (all [B]-shaped; rows with on=False are untouched). The
    sampling knobs ride a stacked SlotParams so the armed slot's decode
    sampling state is set in the same pass."""

    on: jnp.ndarray  # bool [B]: slot completes its prefill this launch
    idx: jnp.ndarray  # i32 [B]: flat index of its last prompt token
    prompt_len: jnp.ndarray  # i32 [B]
    max_tokens: jnp.ndarray  # i32 [B]
    params: G.SlotParams  # [B]-shaped sampling knobs
    presence: jnp.ndarray  # bool [B, V]: prompt token sets (host-built)


def idle_mixed_arm(n_slots: int, vocab_size: int) -> MixedArm:
    """An all-off MixedArm (no admission completes this launch)."""
    z = jnp.zeros((n_slots,), jnp.int32)
    _, sp = G.init_slots(n_slots, 1)
    return MixedArm(
        jnp.zeros((n_slots,), bool), z, z, z,
        sp, jnp.zeros((n_slots, vocab_size), bool),
    )


class SpecPlan(NamedTuple):
    """Per-slot speculation operands for one mixed launch (draft-then-
    verify inside the existing program — ISSUE 13). A speculating slot's
    launch entry is a [current + K-token draft] VERIFY row: a short
    prefill-kind row over the block table whose first flat slot is
    dec_flag-substituted from device state (token AND position, like any
    decode row) and whose draft slots carry host-planned (n-gram) or
    draft-model tokens. Shapes are fixed by the fleet's max draft length,
    so ONE compiled program serves every accept pattern and every
    per-slot draft length — the host only moves int32 plan data.

    With device-derived launch metadata (ISSUE 15; DeviceMeta below),
    a verify row's positions come from the device-resident slot state,
    so the host submits verify rows EVERY step, back to back — the
    packed fetch only confirms emissions."""

    dec_on: jnp.ndarray  # bool [B]: slot has a PLAIN decode row this
    # launch — slot_step advances exactly these rows; verify rows
    # advance through spec_verify instead
    on: jnp.ndarray  # bool [B]: slot carries a verify row this launch
    idx: jnp.ndarray  # i32 [B, K+1]: flat launch indices of the row's
    # [current, draft...] slots (entries past the slot's own draft
    # length repeat the last valid index — duplicate gathers, never read)
    n_draft: jnp.ndarray  # i32 [B]: drafted tokens in the row (<= K)


def idle_spec_plan(n_slots: int, draft_len: int) -> SpecPlan:
    """An all-off SpecPlan with every slot marked as a plain decode row
    (the compiled shape for a fleet whose speculation is armed but idle
    this launch)."""
    return SpecPlan(
        jnp.ones((n_slots,), bool),
        jnp.zeros((n_slots,), bool),
        jnp.zeros((n_slots, draft_len + 1), jnp.int32),
        jnp.zeros((n_slots,), jnp.int32),
    )


class DeviceMeta(NamedTuple):
    """Device-derivation masks for one mixed launch (ISSUE 15): which
    tiles/flat slots of the host tile plan read their POSITIONS from the
    device-resident slot state instead of the host position model.

    The host still owns the STRUCTURAL half of the plan — which fleet
    row each tile serves, how many flat slots it spans, the launch
    width — because those are shapes/indices the program needs before
    dispatch. The POSITIONAL half (a decode/verify row's q_start and
    per-token write/RoPE positions) is data, and for decode and verify
    rows it is exactly `state.pos[row] (+ offset within the row)` — a
    value the device already holds post-previous-launch. Marking those
    tiles/slots here and substituting on device (apply_device_meta)
    means the host never needs the fetched result of launch N to plan
    launch N+1: verify rows ride lag pipelining like plain decode rows.
    All leaves are plain traced operands — one compiled program for
    every derivation pattern.
    """

    tile_on: jnp.ndarray  # bool [G]: tile's q_start = pos[row] + tile_off
    tile_off: jnp.ndarray  # i32 [G]: tile's offset within its row entry
    tok_on: jnp.ndarray  # bool [W]: slot's position = pos[row] + tok_off
    tok_off: jnp.ndarray  # i32 [W]: flat slot's offset within its entry


def build_device_meta(entries, offsets, n_dev: int, *, width: int,
                      tile: int):
    """HOST-side companion to build_ragged_meta (strictly decode-
    unreachable, same derivation): mark the first `n_dev` entries'
    tiles and flat slots for on-device position substitution. `entries`
    / `offsets` are the SAME lists build_ragged_meta consumed/returned —
    the walk here only recomputes each tile's offset within its entry.
    Launch-padding tiles inherit their predecessor's flags exactly like
    build_ragged_meta copies its (row, q_start): a pad tile behind a
    derived tile derives the SAME value (the kernel walks nothing for
    it either way: q_len 0).

    Returns numpy (tile_on [G] bool, tile_off [G] i32, tok_on [W] bool,
    tok_off [W] i32) — wrap in a DeviceMeta for the launch."""
    import numpy as np

    G = width // tile
    tile_on = np.zeros((G,), bool)
    tile_off = np.zeros((G,), np.int32)
    tok_on = np.zeros((width,), bool)
    tok_off = np.zeros((width,), np.int32)
    g = 0
    for i, ((row, start, length, kind), off) in enumerate(
        zip(entries, offsets)
    ):
        need = -(-length // tile)
        if i < n_dev:
            for t in range(need):
                tile_on[g + t] = True
                tile_off[g + t] = t * tile
            tok_on[off : off + length] = True
            tok_off[off : off + length] = np.arange(length, dtype=np.int32)
        g += need
    while g < G:
        if g > 0:
            tile_on[g] = tile_on[g - 1]
            tile_off[g] = tile_off[g - 1]
        g += 1
    return tile_on, tile_off, tok_on, tok_off


def build_block_meta(entries, offsets, owing, *, block: int, width: int,
                     tile: int):
    """build_device_meta for a block-diffusion launch (HOST side): the
    first len(owing) entries are decode rows, each its open block (`block`
    tokens from state.pos) or, where owing[i], the owed block in front of
    it (2 x block tokens from state.pos - block): those entries' offsets
    from state.pos start at -block. Returns build_device_meta's four
    arrays and, per decode row, the flat index of its OPEN block's first
    token (mixed_step_ragged's dec_idx)."""
    t_on, t_off, k_on, k_off = build_device_meta(
        entries, offsets, len(owing), width=width, tile=tile,
    )
    open_at = []
    for (_, _, n, _), off, owe in zip(entries, offsets, owing):
        below = block if owe else 0
        k_off[off : off + n] -= below
        t_off[off // tile : off // tile - (-n // tile)] -= below
        open_at.append(off + below)
    return t_on, t_off, k_on, k_off, open_at


def apply_device_meta(meta, tok_row, tok_pos, dev: DeviceMeta, pos):
    """TRACED half of the device-derived launch metadata: substitute
    `pos[row] + offset` into the marked tiles' q_start column and the
    marked flat slots' positions. Runs inside the mixed program BEFORE
    the kernel/hook sees either array, so the scalar-prefetch metadata
    the ragged kernel's index maps read — and the write/RoPE positions
    of the XLA twin — are exact device values with zero host syncs.
    Unmarked tiles/slots (prefill chunks, launch padding) keep the host
    plan verbatim."""
    rows = jnp.maximum(meta[:, 0], 0)
    q_dev = pos[rows].astype(jnp.int32) + dev.tile_off
    meta = meta.at[:, 1].set(jnp.where(dev.tile_on, q_dev, meta[:, 1]))
    rix = jnp.maximum(tok_row, 0)
    p_dev = pos[rix].astype(jnp.int32) + dev.tok_off
    tok_pos = jnp.where(dev.tok_on, p_dev, tok_pos)
    return meta, tok_pos


def spec_verify(cfg: ModelConfig, state: G.SlotState, window, draft,
                n_draft, live):
    """Traced accept/reject for the mixed launch's verify rows — the
    whole speculation decision stays on device (zero host syncs; the
    host learns the outcome from the packed fetch it already does).

    window [B, K+1] i32: greedy argmax at the verify row's flat
    positions (position j's argmax is the model's next token after
    consuming [current, draft[:j]]); draft [B, K] i32: the drafted
    tokens; n_draft [B]: drafts actually planned per row; live [B]:
    rows carrying a verify row AND still active on device.

    Emits the longest draft prefix matching the model's own argmax plus
    the model's correction token, replicating generate.slot_step's
    greedy semantics token for token so the STATE after a verify step is
    bit-identical to having decoded the same tokens one-by-one:
    break-before-append EOS (the EOS step still advances pos by one,
    like the plain step that sampled it), remaining-budget clamp
    (can_emit requires remaining > 0; budget exhaustion deactivates
    without the extra EOS-step position bump), pad token on
    deactivation. Rejected draft positions' K/V is overwritten before it
    can ever be attended or shadow-captured — the pool-rewind invariant
    (ARCHITECTURE.md "Speculative decoding").

    Returns (state', spec_emit [B, K+1], spec_mask [B, K+1], adv [B] —
    the per-row position advance the host position model resyncs from).
    """
    pad = jnp.int32(cfg.pad_token_id)
    K1 = window.shape[1]
    K = K1 - 1
    j = jnp.arange(K1, dtype=jnp.int32)[None, :]
    jk = jnp.arange(K, dtype=jnp.int32)[None, :]
    match = (draft == window[:, :K]) & (jk < n_draft[:, None])
    n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    valid = j <= n_acc[:, None]  # candidate emission stream: accepted
    # drafts + the correction token (all of them the model's own argmax)
    cum_eos = (
        jnp.cumsum(G.stop_mask(cfg, window).astype(jnp.int32), axis=1) > 0
    )
    emit_pre = valid & ~cum_eos  # break BEFORE appending a stop token
    n_pre = jnp.sum(emit_pre.astype(jnp.int32), axis=1)
    room = state.remaining
    n_emit = jnp.where(live, jnp.minimum(n_pre, room), 0)
    # the EOS "step" only happens when plain decode would have reached
    # it: budget exhaustion first means no EOS step (and no extra pos)
    saw_eos = live & jnp.any(valid & cum_eos, axis=1) & (n_pre < room)
    emit_ok = emit_pre & (j < n_emit[:, None]) & live[:, None]
    spec_emit = jnp.where(emit_ok, window, pad)
    adv = n_emit + saw_eos.astype(jnp.int32)
    last = jnp.take_along_axis(
        window, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
    )[:, 0]
    new_token = jnp.where(saw_eos | (n_emit <= 0), pad, last)
    new_rem = state.remaining - n_emit
    new_active = live & ~saw_eos & (new_rem > 0)
    # presence marks every token plain decode would have SAMPLED (the
    # emitted stream + the final EOS); counts only the emitted ones —
    # the exact slot_step bookkeeping, batched over the window. Inert
    # for eligible rows (speculation requires the penalties disabled),
    # kept exact so the state merge has one discipline.
    mark = emit_ok | (saw_eos[:, None] & (j == n_emit[:, None]))
    vocab = jnp.arange(state.presence.shape[-1], dtype=jnp.int32)
    onehot = window[:, :, None] == vocab[None, None, :]  # [B, K+1, V]
    pres_add = jnp.any(onehot & mark[:, :, None], axis=1)
    cnt_add = jnp.sum(
        onehot & emit_ok[:, :, None], axis=1
    ).astype(jnp.int32)
    state = G.SlotState(
        token=jnp.where(live, new_token, state.token),
        pos=state.pos + jnp.where(live, adv, 0),
        active=jnp.where(live, new_active, state.active),
        remaining=jnp.where(live, new_rem, state.remaining),
        presence=state.presence | pres_add,
        counts=state.counts + cnt_add,
    )
    return state, spec_emit, emit_ok, adv


@functools.partial(jax.jit, static_argnames=("cfg", "live_width"),
                   donate_argnames=("pool",))
def mixed_step_ragged(cfg: ModelConfig, params, tokens, tok_row, tok_pos,
                      dec_flag, meta, pool, table, state: G.SlotState,
                      sparams: G.SlotParams, key, dec_idx, arm: MixedArm,
                      spec: Optional[SpecPlan] = None, spec_toks=None,
                      dev: Optional[DeviceMeta] = None, pages=None,
                      diff: Optional[DiffState] = None,
                      darm: Optional[DiffState] = None, snaps=None,
                      live_width: Optional[int] = None):
    """One scheduler step: advance every active slot one decode token AND
    write the launch's prefill chunks into the pool, in one program.

    tokens/tok_pos [W]: host-planned flat launch (prefill chunk contents;
    decode positions hold placeholders). dec_flag [W]: True where the
    flat slot is a decode-row token — its token/position are REPLACED by
    the owning slot's device state (state.token / state.pos), so the host
    plans launches ahead of its fetches without ever syncing. meta [G,4] /
    tok_row [W]: the build_ragged_meta plan. With `dev` (DeviceMeta, the
    default serving mode) the decode/verify tiles' q_start and flat-slot
    positions are DERIVED ON DEVICE from state.pos (apply_device_meta) —
    the host plan carries placeholders there and the host never needs a
    fetch to plan the next launch, even for verify rows whose advance is
    data-dependent. Without `dev` (a fleet with spec_draft_len 0) the
    host position model must be exact (over-advance on rows that went
    inactive since the last fetch is masked garbage, the frozen-row
    argument).
    dec_idx [B]: flat index of each slot's decode token (0 for slots
    without one — their sampled garbage is gated by state.active exactly
    like idle rows in decode_slots_paged). arm: completing-prefill
    operands (MixedArm; all-off most steps).

    pages ([B] i32, optional): per-slot adapter-pool pages (engine/
    adapters) — every flat token (decode, verify, prefill chunk alike)
    computes with its owning slot's adapter delta; page 0 = base. A
    TRACED operand like the table, so one compiled program serves any
    adapter mix across launches.

    spec (SpecPlan, optional): draft-then-verify rows for eligible
    decode slots — each is a [current + draft] prefill-kind row whose
    first flat slot is dec_flag-substituted like any decode row, whose
    accept/reject runs fully traced (spec_verify), and whose emissions
    extend the packed fetch. spec_toks ([B, K] i32, optional): device-
    generated draft-model proposals scattered into the flat token axis
    (n-gram drafts arrive host-planned in `tokens` instead — either way
    zero extra host syncs).

    A block-diffusion model (cfg.diffusion_block > 0; `diff` its
    DiffState, `darm` the completing prefills' arming rows): a decode row is
    its open block, behind the owed block where the host's position model
    says the forward carries one (`build_block_meta`): an entry of block or
    2 x block tokens whose tokens come from diff.owed / diff.open and whose
    positions from state.pos (`dev` marks them, as it marks a verify row's;
    tok_off runs from -block where the owed block rides), dec_idx [B] the
    flat index of the OPEN block's first token, -1 for a slot without a row
    in this launch; `diffusion_step` takes slot_step's place and a
    completing prefill samples no first token (`diffusion_epilogue`).

    snaps ((restore, take), i32 [B] each; a model of linear attention
    layers): by slot, the snapshot a row that starts a tenant in this launch
    starts from (-1: zeros) and the one its state after the launch is kept
    in (-1: none): `StateRows.restore` / `.take`.

    live_width (static; engine/scheduler.live_width, given only where it is
    under the launch's width W): the model runs on the launch's live tokens,
    packed side by side on an axis that wide (`live_tokens`), and only the
    hook's kernel and pool write see the tile layout. The host plans no
    launch of more live tokens. None: the tile layout throughout, and the
    program is what it was before the axis existed.

    Returns (packed int32 — [5, B] plain, [5 + 2*(K+1) + 1, B] with
    spec: emitted / emit_mask / active / firsts / armed [/ spec_emit /
    spec_mask / position advance], ONE fetch per step — state, sparams,
    pool). Block diffusion: emitted and emit_mask are `block` rows each,
    and the DiffState comes back last."""
    from ..models import api as M

    pool = _routed_reset(pool)
    if dev is not None:
        meta, tok_pos = apply_device_meta(meta, tok_row, tok_pos, dev,
                                          state.pos)
    rows_ix = jnp.maximum(tok_row, 0)
    toks = jnp.where(dec_flag, state.token[rows_ix], tokens)
    if spec is not None and spec_toks is not None:
        # draft-model proposals: scatter each verify row's drafts into
        # its flat slots (rows without a verify row — and draft slots
        # past a row's own draft length — target an out-of-range index,
        # which the scatter drops)
        K = spec_toks.shape[1]
        jk = jnp.arange(K, dtype=jnp.int32)[None, :]
        want = spec.on[:, None] & (jk < spec.n_draft[:, None])
        tgt = jnp.where(want, spec.idx[:, 1:], jnp.int32(toks.shape[0]))
        toks = toks.at[tgt.reshape(-1)].set(
            spec_toks.reshape(-1), mode="drop"
        )
    pos = jnp.where(dec_flag, state.pos[rows_ix], tok_pos)
    if cfg.diffusion_block:
        # a decode row's tokens: its owed and open blocks. A row the device
        # holds ended (a stop token the host has not fetched yet) carries
        # nothing, as in the decode chunk: not walked, not written, no expert
        toks = jnp.where(
            dev.tok_on, block_row_tokens(diff, rows_ix, dev.tok_off), toks
        )
        tok_row = jnp.where(dev.tok_on & ~state.active[rows_ix], -1, tok_row)
        ended = dev.tile_on & ~state.active[jnp.maximum(meta[:, 0], 0)]
        meta = meta.at[:, 2].set(jnp.where(ended, 0, meta[:, 2]))
    compact, own, own_toks, pos = model_axis(live_width, tok_row, toks, pos)
    x = M.embed(cfg, params, own_toks[:, None], pos)
    if cfg.linear_layers and snaps is None:  # restores none, keeps none
        snaps = (jnp.full((table.shape[0],), -1, jnp.int32),) * 2
    x, pool = M.forward_layers(
        cfg, params["layers"], x, pool, pos,
        attn_hook=make_ragged_fill_hook(table, meta, tok_row, snaps, compact),
        attn_seq_len=1, lora_pages=_token_pages(pages, own),
    )
    if compact is not None:
        # dec_idx, arm.idx and spec.idx name flat tokens of the tile layout
        x = x[compact[1]]
    if cfg.diffusion_block:
        Bd = cfg.diffusion_block
        logits = M.unembed(cfg, params, open_block_rows(x, dec_idx, Bd))
        packed, state, sparams, diff = diffusion_epilogue(
            cfg, state, sparams, diff,
            logits[:, 0, :].reshape(dec_idx.shape[0], Bd, -1),
            key, dec_idx >= 0, arm, darm,
        )
        packed = _pack_rows(packed, pool["routed"])
        return packed, state, sparams, pool, diff
    # decode: gather each slot's flat position, one shared slot_step —
    # the same sampler/bookkeeping the whole-chunk decode programs run
    logits = M.unembed(cfg, params, x[dec_idx])[:, 0, :]  # [B, V]
    # completing prefills: sample each one's FIRST token off its last
    # prompt position with its own (stacked) sampling knobs, then arm the
    # slot in place — vectorized generate.arm_slot (budget / EOS-on-first
    # decided on device, same as insert_slot)
    pf_logits = M.unembed(cfg, params, x[arm.idx])[:, 0, :]  # [B, V]
    sp_logits = sp_draft = None
    if spec is not None:
        B, K1 = spec.idx.shape
        sel = x[spec.idx.reshape(-1)]  # [B*(K+1), 1, D]
        sp_logits = M.unembed(cfg, params, sel)[:, 0, :].reshape(B, K1, -1)
        sp_draft = toks[spec.idx[:, 1:]]  # [B, K] the verified drafts
    packed, state, sparams = mixed_epilogue(
        cfg, state, sparams, logits, pf_logits, key, arm,
        spec=spec, sp_logits=sp_logits, sp_draft=sp_draft,
    )
    if "routed" in pool:  # a latent pool's experts: same fetch, more rows
        packed = _pack_rows(packed, pool["routed"])
    return packed, state, sparams, pool


@functools.partial(
    jax.jit, static_argnames=("dcfg",), donate_argnames=("dpool",)
)
def mixed_fill_draft(dcfg: ModelConfig, dparams, tokens, tok_row, tok_pos,
                     dec_flag, meta, dpool, table, token, pos_state,
                     dev: Optional[DeviceMeta] = None):
    """Draft-pool twin of the mixed step's forward (no sampling): land
    this step's prefill chunks AND every decode row's current token in
    the DRAFT model's pool, with the same dec_flag substitution from the
    (replicated) slot state — so the draft chain's context tracks the
    canonical stream position by position. draft slots of verify rows
    carry placeholder zeros here; the propose chain rewrites exactly
    those positions before anything attends them (write-then-attend).
    `dev` rides the same apply_device_meta substitution as the target's
    mixed step, so the draft pool's positions track the device frontier
    under back-to-back verify rows too."""
    from ..models import api as M

    if dev is not None:
        meta, tok_pos = apply_device_meta(meta, tok_row, tok_pos, dev,
                                          pos_state)
    rows_ix = jnp.maximum(tok_row, 0)
    toks = jnp.where(dec_flag, token[rows_ix], tokens)
    pos = jnp.where(dec_flag, pos_state[rows_ix], tok_pos)
    x = M.embed(dcfg, dparams, toks[:, None], pos)
    _, dpool = M.forward_layers(
        dcfg, dparams["layers"], x, dpool, pos,
        attn_hook=make_ragged_fill_hook(table, meta, tok_row),
        attn_seq_len=1,
    )
    return dpool


@functools.partial(
    jax.jit, static_argnames=("dcfg", "draft_len"), donate_argnames=("dpool",)
)
def draft_propose_paged(dcfg: ModelConfig, dparams, token, pos, dpool,
                        table, *, draft_len: int):
    """Batched greedy draft chain over the fleet (the cfg-gated
    spec_draft_model flavor): `draft_len`+1 decode steps of the SMALL
    draft model from every slot's current (token, pos), over the draft
    model's own pool leaves indexed by the SAME block tables as the
    target pool — draft KV shares the target's allocation lifecycle for
    free. The +1 step writes the last proposal's K/V (draft_spec_loop's
    hole-free-full-accept discipline); its proposal is discarded.

    Rows not speculating this launch ride along: their chain writes
    their current token's K/V (canonical for the draft pool) plus
    proposal K/V beyond the frontier that later canonical writes
    overwrite — the same stale-region argument as the target pool, and
    in the draft pool even a violation could only degrade draft QUALITY
    (acceptance is verified against the target's own argmax).

    Returns (proposals [B, draft_len] i32, dpool)."""

    def body(carry, _):
        tok, p, dpool = carry
        logits, dpool = _forward_step_paged(
            dcfg, dparams, tok[:, None], dpool, table, p
        )
        nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(
            jnp.int32
        )
        return (nxt, p + 1, dpool), nxt

    (_, _, dpool), props = jax.lax.scan(
        body, (token, pos, dpool), None, length=draft_len + 1
    )
    return props[:draft_len].swapaxes(0, 1), dpool


@jax.named_scope("sample")
def diffusion_epilogue(cfg: ModelConfig, state: G.SlotState,
                       sparams: G.SlotParams, diff: DiffState, logits, key,
                       on, arm: MixedArm, darm: DiffState):
    """mixed_epilogue's twin for a block-diffusion model: `diffusion_step`
    advances the rows that rode the launch (`on`), and a completing
    prefill arms its slot with NO first token (a masked position's token
    comes from the logits AT that position, which only a later forward
    computes): its open block is the prompt's remainder followed by masks,
    its position the prompt's whole blocks, its budget max_tokens whole.
    Returns (packed [2 * block + 3, B]: emitted, emit_mask, active, a zero
    row where an autoregressive launch packs its first tokens, armed —
    state, sparams, diff)."""
    state, diff, emit, emit_ok = diffusion_step(
        cfg, state, sparams, diff, logits, key, on
    )
    a, a_col = arm.on, arm.on[:, None]
    state = state._replace(
        pos=jnp.where(a, arm.prompt_len, state.pos),
        active=jnp.where(a, arm.max_tokens > 0, state.active),
        remaining=jnp.where(a, arm.max_tokens, state.remaining),
    )
    diff = DiffState(*(
        jnp.where(a_col if new.ndim > 1 else a, new, old)
        for new, old in zip(darm, diff)
    ))
    sparams = G.SlotParams(*(
        jnp.where(a, new, old) for new, old in zip(arm.params, sparams)
    ))
    packed = jnp.concatenate([
        emit.T, emit_ok.astype(jnp.int32).T,
        state.active.astype(jnp.int32)[None],
        jnp.zeros_like(state.pos)[None], a.astype(jnp.int32)[None],
    ], axis=0)
    return packed, state, sparams, diff


@jax.named_scope("sample")
def mixed_epilogue(cfg: ModelConfig, state: G.SlotState,
                   sparams: G.SlotParams, logits, pf_logits, key,
                   arm: MixedArm, spec: Optional[SpecPlan] = None,
                   sp_logits=None, sp_draft=None):
    """Sampling/arming tail of the mixed step, ONE copy for the single-
    device program above and the pp shard_map twin (parallel/pipeline.
    _build_mixed_step_ragged — both hand replicated [B, V] logits in):
    slot_step advances the decoding rows, completing prefills sample
    their first token and arm via the vectorized arm_slot recipe. With a
    SpecPlan, slot_step's advance is gated to the rows that actually
    carried a plain decode row (spec.dec_on), verify rows advance
    through the traced spec_verify instead, and the packed fetch grows
    the spec emission block. Returns (packed, state, sparams)."""
    from ..ops.sampling import sample_token

    k_dec, k_arm = jax.random.split(key)
    prev = state
    state, emit, can_emit = G.slot_step(cfg, state, sparams, logits, k_dec)
    if spec is not None:
        # rows without a plain decode row this launch (verify rows)
        # must not advance through slot_step's garbage logits: freeze
        # them back to the pre-step state, then run the traced verify
        dec_col = spec.dec_on[:, None]
        state = G.SlotState(*(
            jnp.where(dec_col if n.ndim > 1 else spec.dec_on, n, o)
            for n, o in zip(state, prev)
        ))
        emit = jnp.where(spec.dec_on, emit, jnp.int32(cfg.pad_token_id))
        can_emit = can_emit & spec.dec_on
        # greedy argmax over the verify row's positions — the identical
        # argmax sample_token's all-greedy bypass computes (speculation
        # eligibility requires the penalties disabled, so the penalized
        # and raw logits coincide bitwise)
        window = jnp.argmax(
            sp_logits.astype(jnp.float32), axis=-1
        ).astype(jnp.int32)
        live = spec.on & prev.active
        state, spec_emit, spec_mask, spec_adv = spec_verify(
            cfg, state, window, sp_draft, spec.n_draft, live
        )
    firsts = sample_token(
        k_arm, pf_logits,
        arm.params.temperature[:, None], arm.params.top_k[:, None],
        arm.params.top_p[:, None], arm.params.greedy | ~arm.on,
        arm.params.min_p[:, None], arm.params.rep_penalty[:, None],
        arm.params.freq_penalty[:, None], arm.params.pres_penalty[:, None],
        presence=arm.presence,
    )
    budget = jnp.where(
        G.stop_mask(cfg, firsts), jnp.int32(0),
        jnp.maximum(arm.max_tokens - 1, 0),
    )
    vocab = jnp.arange(cfg.vocab_size, dtype=jnp.int32)
    first_onehot = vocab[None, :] == firsts[:, None]  # [B, V]
    on, on_col = arm.on, arm.on[:, None]
    state = G.SlotState(
        token=jnp.where(on, firsts, state.token),
        pos=jnp.where(on, arm.prompt_len, state.pos),
        active=jnp.where(on, budget > 0, state.active),
        remaining=jnp.where(on, budget, state.remaining),
        presence=jnp.where(on_col, arm.presence | first_onehot,
                           state.presence),
        counts=jnp.where(on_col, first_onehot.astype(jnp.int32),
                         state.counts),
    )
    sparams = G.SlotParams(*(
        jnp.where(on, new, old)
        for new, old in zip(arm.params, sparams)
    ))
    rows = [
        emit[None], can_emit.astype(jnp.int32)[None],
        state.active.astype(jnp.int32)[None], firsts[None],
        on.astype(jnp.int32)[None],
    ]
    if spec is not None:
        # verify-row results ride the SAME packed fetch: emissions,
        # their mask, and the per-row position advance the host position
        # model resyncs from — zero extra device->host round trips
        rows += [
            spec_emit.T, spec_mask.astype(jnp.int32).T, spec_adv[None],
        ]
    packed = jnp.concatenate(rows, axis=0)
    return packed, state, sparams
