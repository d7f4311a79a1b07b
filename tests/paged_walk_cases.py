"""Cases of the paged walk's COMPUTE BLOCKS (ops/paged_attention: a loop
step folds P pages), run by tests/test_paged.py and
tests/test_ragged_attention.py as cases of their parametrised walk tests.

A case's shapes give its P through `_walk_shape` itself (asserted, so a
change of the rule cannot leave a case at another P in silence): bf16 pools
of 2 KV heads x 128 in blocks of 1024 / P tokens under a table 3 P + 1 pages
wide, where the cap of 1,024 positions a step decides; a latent row pool;
pairs of 64-number heads side by side (models/stack.pack_heads). Contexts end
at P x k pages exactly, one position past, one page short; windows start
inside a compute block; a tile's new rows straddle a page edge inside a
compute block and across two; rows that hold nothing lie beside live ones.
`poison` runs the kernel in the TPU interpreter with uninitialised memory
as NaN: what a dead page's VMEM rows hold must not reach the output.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from distributed_llm_inference_tpu.config import resolve_attn_impl
from distributed_llm_inference_tpu.engine import paged as EP
from distributed_llm_inference_tpu.models.stack import pack_heads, unpack_heads
from distributed_llm_inference_tpu.models.registry import get_model_config
from distributed_llm_inference_tpu.ops.attention import attend
from distributed_llm_inference_tpu.ops.paged_attention import (
    _walk_shape, paged_flash_attend, ragged_paged_attend,
)

from dense_equal import cell_serving


def cell_pool(name):
    """(cfg, slots, table width in pages, the pool's shapes) of a benchmark
    configuration as its cells serve it: the registry entry with the file's
    overrides under `--attn-impl pallas`, the pool `init_pool` makes from
    its flags (a grouped pool's groups as engine/paged.group_blocks sizes
    them under the served launch), nothing allocated."""
    serving = cell_serving(name)
    flags = serving["flags"]
    slots, context, blocks, bs = (
        int(flags[flags.index(k) + 1]) for k in
        ("--continuous", "--continuous-max-seq", "--kv-pool-blocks",
         "--kv-block-size"))
    cfg = resolve_attn_impl(get_model_config(serving["base"]).replace(
        dtype="bfloat16", **serving["overrides"]), "pallas")
    if len(cfg.kv_groups) > 1:
        from distributed_llm_inference_tpu.engine.scheduler import live_width

        blocks = EP.group_blocks(cfg, blocks, EP.window_row_budget(
            cfg.attn_window, live_width(cfg, slots, 8), bs), slots, bs)
    pool = jax.eval_shape(lambda: EP.init_pool(cfg, blocks, bs, n_slots=slots))
    return cfg, slots, -(-context // bs), pool


H, KV, DH = 4, 2, 128
LATENT_ROW, LATENT = 256, 128
TQ = 8  # the mixed launch's query tile


def _geometry(P, form):
    """(bs, MB) of a case that walks P pages a step."""
    bs = 128 if form == "latent" else 1024 // P
    return bs, 3 * P + 1


def _pool(P, form, layers=None, seed=0):
    """(pool_k, pool_v, table, bs, MB): three rows' tables over a scattered
    pool (page 0 is the trash block, unmapped tails point at it)."""
    bs, MB = _geometry(P, form)
    rng = np.random.default_rng(seed)
    N = 3 * MB + 1
    kv, width = {"heads": (KV, DH), "packed64": (KV // 2, DH),
                 "latent": (1, LATENT_ROW)}[form]
    shape = (() if layers is None else (layers,)) + (N, kv, bs, width)
    pool_k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    pool_v = None if form == "latent" else jnp.asarray(
        rng.normal(size=shape), jnp.bfloat16)
    table = 1 + rng.permutation(N - 1)[: 3 * MB].reshape(3, MB)
    return pool_k, pool_v, jnp.asarray(table, jnp.int32), bs, MB


def _rows_view(leaf, table, row, width=None):
    """Row `row`'s logical view [1, KV, MB x bs, width] of a layer's leaf."""
    g = jnp.asarray(leaf, jnp.float32)[table[row]]  # [MB, KV, bs, Dh]
    MB, kv, bs, Dh = g.shape
    return g.transpose(1, 0, 2, 3).reshape(1, kv, MB * bs, Dh)[..., :width]


def _mask(positions, total, window, block):
    kv = np.arange(total)[None, :]
    p = np.asarray(positions)[:, None]
    end = (p // block + 1) * block - 1 if block else p
    mask = kv <= end
    if window:
        mask &= kv > p - window
    return jnp.asarray(mask)[None]


def _reference(form, q_rows, pool_k, pool_v, table, row, positions, window,
               block):
    """attend() over row `row`'s gathered view at `positions`; q_rows
    [n, H, Dh] as the model makes them (before pack_heads)."""
    total = table.shape[1] * pool_k.shape[-2]
    mask = _mask(positions, total, window, block)
    if form == "latent":  # scores over the whole row, values its head
        k = np.asarray(_rows_view(pool_k, table, row), np.float64)[0, 0]
        s = np.einsum("nhd,sd->nhs", np.asarray(q_rows, np.float64), k)
        s = np.where(np.asarray(mask)[0][:, None, :],
                     s * LATENT_ROW ** -0.5, -np.inf)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        return (p / p.sum(axis=-1, keepdims=True)) @ k[:, :LATENT]
    k, v = _rows_view(pool_k, table, row), _rows_view(pool_v, table, row)
    if form == "packed64":  # [1, 1, S, 128] -> two heads of 64
        k, v = (a.reshape(1, a.shape[2], 2, 64).transpose(0, 2, 1, 3)
                for a in (k, v))
    return attend(q_rows[None], k, v, mask)[0]


def _kernel_kwargs(form):
    if form == "latent":
        return dict(scale=LATENT_ROW ** -0.5, value_dim=LATENT)
    return dict(scale=64 ** -0.5) if form == "packed64" else {}


def _queries(form, n, seed):
    rng = np.random.default_rng(seed)
    width = {"heads": DH, "packed64": 64, "latent": LATENT_ROW}[form]
    return jnp.asarray(rng.normal(size=(n, H, width)), jnp.float32)


def _interpret(poison):
    return pltpu.InterpretParams(uninitialized_memory="nan") if poison else True


def _assert_p(P, form, rows, bs, MB):
    width = LATENT_ROW if form == "latent" else DH
    kv = {"heads": KV, "packed64": KV // 2, "latent": 1}[form]
    assert _walk_shape(kv, bs, width, 2, False, rows, MB,
                       form == "latent")[1] == P


# -- decode rows (paged_flash_attend) -----------------------------------------
# name: (P, form, window as (pages, positions) or None, poison). Every case
# holds four rows: a context of 2 P pages exactly, one position more, one
# page fewer, and a row that is not live.
DECODE_BLOCK_CASES = {
    f"blocks-P{P}": (P, "heads", None, False) for P in (1, 2, 4, 8)
}
DECODE_BLOCK_CASES.update({
    # the window's first page is P + 1 pages below the frontier, not at a
    # multiple of P: the walk's compute blocks start there
    f"blocks-window-P{P}": (P, "heads", (P + 1, 5), False)
    for P in (1, 2, 4, 8)
})
DECODE_BLOCK_CASES.update({
    "blocks-latent-P8": (8, "latent", None, False),
    "blocks-packed64-P4": (4, "packed64", None, False),
    "blocks-poison-P4": (4, "heads", None, True),
    "blocks-poison-latent-P8": (8, "latent", None, True),
})


def check_decode_block_case(name):
    P, form, window, poison = DECODE_BLOCK_CASES[name]
    pool_k, pool_v, table, bs, MB = _pool(P, form)
    group = H // (KV // 2 if form == "packed64" else pool_k.shape[1])
    _assert_p(P, form, group, bs, MB)
    window = window and window[0] * bs + window[1]
    pos = np.asarray([2 * P * bs - 1, 2 * P * bs, (2 * P - 1) * bs - 1, 7])
    active = np.asarray([True, True, True, False])
    table = jnp.concatenate([table, table[:1]])  # the dead slot's stale row
    q = _queries(form, 4, seed=1)
    kq, part = q[:, None], None
    if form == "packed64":
        kq, _, _, part = pack_heads(q[:, None], jnp.zeros((4, 1, KV, 64)),
                                    jnp.zeros((4, 1, KV, 64)), 2)
    got = paged_flash_attend(
        kq, pool_k, pool_v, table, jnp.asarray(pos, jnp.int32), None,
        jnp.asarray(active), window=window, interpret=_interpret(poison),
        **_kernel_kwargs(form),
    )
    if part is not None:
        got = unpack_heads(got, part, 2)
    got = np.asarray(got)[:, 0]
    assert np.all(np.isfinite(got)), name
    for b in range(3):
        want = _reference(form, q[b:b + 1], pool_k, pool_v, table, b,
                          pos[b:b + 1], window, 0)
        np.testing.assert_allclose(got[b:b + 1], np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"{name} {b}")
    assert np.all(got[3] == 0.0)


# -- query tiles that write their rows (ragged_paged_attend, write=...) -------
# name: (P, form, window, block, write, poison); the tiles are made from P
# and the block size by `_tiles`.
RAGGED_BLOCK_CASES = {
    f"blocks-write-P{P}": (P, "heads", None, 0, True, False)
    for P in (1, 2, 4, 8)
}
RAGGED_BLOCK_CASES.update({
    "blocks-write-window-P2": (2, "heads", (3, 5), 0, True, False),
    "blocks-write-block-mask-P4": (4, "heads", None, 4, True, False),
    "blocks-write-latent-P8": (8, "latent", None, 0, True, False),
    "blocks-poison-P2": (2, "heads", None, 0, False, True),
})


def _tiles(P, bs):
    """(row, q_start, q_len) a tile: new rows that straddle a page edge
    inside a compute block (P > 1) and across two; a context that ends with
    a compute block, one position past it, one page short of it; a tile that
    holds nothing between live ones; a later tile of a row whose earlier
    tile wrote (a row's tiles come in the order of their positions, as the
    engine lays them: the grid runs in order); a single decode token on a
    page's first row."""
    return [
        (0, bs - 4, 8),  # pages 0 | 1
        (0, bs + 4, 8),  # the same row goes on: reads what the tile wrote
        (1, P * bs - 4, 8),  # pages P - 1 | P: two compute blocks
        (1, P * bs + 4, 0),  # holds nothing
        (2, 2 * P * bs - 8, 8),  # ends with compute block 1
        (0, 2 * P * bs - 4, 8),  # ends one page into compute block 2
        (1, (3 * P - 1) * bs - 8, 5),  # one page short of 3 compute blocks
        (2, 2 * P * bs, 1),  # a decode token on a page's first row
    ]


def check_ragged_block_case(name):
    P, form, window, block, write, poison = RAGGED_BLOCK_CASES[name]
    layers, layer = (2, 1) if write else (None, None)
    pool_k, pool_v, table, bs, MB = _pool(P, form, layers)
    kv = pool_k.shape[-3]
    _assert_p(P, form, TQ * (H // kv), bs, MB)
    window = window and window[0] * bs + window[1]
    tiles = _tiles(P, bs)
    meta = jnp.asarray([(r, s, n, 0) for r, s, n in tiles], jnp.int32)
    W = len(tiles) * TQ
    q = _queries(form, W, seed=2)
    rng = np.random.default_rng(3)
    width = pool_k.shape[-1]
    new_k = jnp.asarray(rng.normal(size=(W, kv, width)), jnp.bfloat16)
    new_v = None if pool_v is None else jnp.asarray(
        rng.normal(size=(W, kv, width)), jnp.bfloat16)
    out = ragged_paged_attend(
        q, pool_k, pool_v, table, meta, None,
        (jnp.int32(layer), new_k, new_v) if write else None,
        window=window, block=block, interpret=_interpret(poison),
        **_kernel_kwargs(form),
    )
    want_k = np.array(pool_k)
    want_v = None if pool_v is None else np.array(pool_v)
    if write:
        out, got_k, got_v = out
        for g, (row, start, n) in enumerate(tiles):
            for t in range(n):
                at = (layer, int(table[row, (start + t) // bs]), slice(None),
                      (start + t) % bs)
                want_k[at] = np.asarray(new_k)[g * TQ + t]
                if want_v is not None:
                    want_v[at] = np.asarray(new_v)[g * TQ + t]
        np.testing.assert_array_equal(np.asarray(got_k), want_k)
        if want_v is not None:
            np.testing.assert_array_equal(np.asarray(got_v), want_v)
    out = np.asarray(out)
    assert np.all(np.isfinite(out)), name
    ref_k = want_k[layer] if write else want_k
    ref_v = want_v if want_v is None or not write else want_v[layer]
    for g, (row, start, n) in enumerate(tiles):
        got = out[g * TQ:(g + 1) * TQ]
        assert np.all(got[n:] == 0.0), (name, g)
        if n:
            want = _reference(form, q[g * TQ:g * TQ + n], ref_k, ref_v, table,
                              row, np.arange(start, start + n), window, block)
            np.testing.assert_allclose(got[:n], np.asarray(want), rtol=2e-5,
                                       atol=2e-5, err_msg=f"{name} {g}")


# -- the walk of a page LIST (the selected read: `_walk_kernel`, pages > 0) ----
# name: (heads H, bs, MB, tiles [(row, q_start, q_len)], older, want, poison).
# older(g, t, kv) -> the logical pages below the tile's own that query t of
# tile g chose for KV head kv; every query also reads the tile's pages up to
# its own position's (its forced window), so they end its list. want: what
# the case is there for, asserted on the lists it makes ("long": more pages
# than a step folds and not a multiple of P; "edge": the tile's two pages lie
# on either side of a compute block's edge; "disjoint": no older page is two
# queries'). P is the listed walk's own (`_walk_shape(..., listed=True)`).
def _mod(g, t, kv):
    return {0} | {1 + (5 * t + 3 * i + kv + 7 * g) % 34 for i in range(12)}


LISTED_CASES = {
    # 64-token pages, 32 a step: lists of 25-37 pages under a table of 40; a
    # tile that straddles pages 36 | 37, one of 5 queries, a decode tile, a
    # tile that holds nothing, a later tile of the first row
    "listed-64tok": (4, 64, 40, [(0, 37 * 64 - 4, 8), (1, 29 * 64 + 7, 5),
                                 (2, 33 * 64 + 1, 1), (1, 29 * 64 + 12, 0),
                                 (0, 37 * 64 + 4, 8)], _mod, "long", False),
    # 31 older pages, so the tile's pages 35 | 36 are entries 31 | 32 of 33
    "listed-edge": (4, 64, 40, [(0, 36 * 64 - 3, 8)],
                    lambda g, t, kv: {p for p in range(31) if p % 8 == t},
                    "edge", False),
    "listed-disjoint": (4, 64, 40, [(0, 31 * 64 + 9, 8), (2, 35 * 64, 8)],
                        lambda g, t, kv: {1 + 3 * t + i + kv for i in range(3)},
                        "disjoint", False),
    # the cell's tile: 16 query heads a KV head, 128 score rows
    "listed-rows128": (32, 64, 40, [(0, 37 * 64 - 4, 8), (1, 20 * 64 + 3, 1)],
                       _mod, "long", False),
    # 16-token pages: eight pages of a step lie side by side on 128 lanes
    "listed-16tok": (4, 16, 40, [(0, 37 * 16 - 4, 8), (1, 29 * 16 + 7, 5),
                                 (2, 33 * 16 + 1, 1)], _mod, "long", False),
    # a step's dead entries (the count is not a multiple of P) over
    # uninitialised memory that reads NaN
    "listed-poison": (4, 64, 40, [(0, 37 * 64 - 4, 8), (1, 29 * 64 + 7, 5)],
                      _mod, "long", True),
}


def _listed_pool(bs, MB, seed=0):
    rng = np.random.default_rng(seed)
    N = 3 * MB + 1
    shape = (2, N, KV, bs, DH)
    k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16) for _ in range(2))
    table = 1 + rng.permutation(N - 1)[:3 * MB].reshape(3, MB)
    return k, v, jnp.asarray(table, jnp.int32), rng


def _chosen(tiles, tq, bs, MB, older):
    """[G, tq, KV, MB] bool: each live query's older pages and the tile's
    pages up to its own."""
    chosen = np.zeros((len(tiles), tq, KV, MB), bool)
    for g, (_, start, n) in enumerate(tiles):
        for t in range(n):
            for kv in range(KV):
                mine = sorted(p for p in older(g, t, kv) if p < start // bs)
                chosen[g, t, kv, mine] = True
                chosen[g, t, kv, start // bs:(start + t) // bs + 1] = True
    return chosen


def _assert_listed(want, chosen, count, tiles, bs, P):
    count = np.asarray(count)
    if want == "long":
        assert np.any((count > P) & (count % P != 0)), count
    if want == "edge":
        _, start, n = tiles[0]
        assert start // bs != (start + n - 1) // bs
        assert np.all(count % P == 1) and np.all(count > P), count
    if want == "disjoint":
        older = chosen.copy()
        for g, (_, start, _) in enumerate(tiles):
            older[g, :, :, start // bs:] = False
        assert older.sum(axis=1).max() == 1


def _listed_reference(q, keys, vals, reads):
    """Plain attention of one query head over the positions `reads`."""
    s = keys[reads].astype(np.float64) @ np.asarray(q, np.float64) * DH ** -0.5
    p = np.exp(s - s.max())
    return (p / p.sum()) @ vals[reads].astype(np.float64)


def check_listed_case(name):
    """A mixed launch over page lists, the kernel writing the tiles' new rows
    in place (layer 1 of 2): every query against plain attention over the
    pages it chose up to its own position, the pool's written rows equal and
    every other row as it was."""
    from distributed_llm_inference_tpu.models.minicpm_sala import page_lists

    heads, bs, MB, tiles, older, want, poison = LISTED_CASES[name]
    group = heads // KV
    P = _walk_shape(KV, bs, DH, 2, False, TQ * group, MB, listed=True)[1]
    assert P == min(32, 2048 // bs), P
    pool_k, pool_v, table, rng = _listed_pool(bs, MB)
    chosen = _chosen(tiles, TQ, bs, MB, older)
    plist, count, at = page_lists(jnp.asarray(chosen), 128)
    _assert_listed(want, chosen, count, tiles, bs, P)
    W = len(tiles) * TQ
    q = jnp.asarray(rng.normal(size=(W, heads, DH)), jnp.float32)
    new_k, new_v = (jnp.asarray(rng.normal(size=(W, KV, DH)), jnp.bfloat16)
                    for _ in range(2))
    meta = jnp.asarray([(r, s, n, int(n == 1)) for r, s, n in tiles], jnp.int32)
    out, got_k, got_v = ragged_paged_attend(
        q, pool_k, pool_v, table, meta, None, (jnp.int32(1), new_k, new_v),
        (plist, count, at), interpret=_interpret(poison))
    want_k, want_v = np.array(pool_k), np.array(pool_v)
    for g, (row, start, n) in enumerate(tiles):
        for t in range(n):
            where = (1, int(table[row, (start + t) // bs]), slice(None),
                     (start + t) % bs)
            want_k[where] = np.asarray(new_k)[g * TQ + t]
            want_v[where] = np.asarray(new_v)[g * TQ + t]
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    out = np.asarray(out)
    assert np.all(np.isfinite(out)), name
    for g, (row, start, n) in enumerate(tiles):
        assert np.all(out[g * TQ + n:(g + 1) * TQ] == 0.0), (name, g)
        view = [np.asarray(a[1], np.float32)[np.asarray(table[row])]
                .transpose(1, 0, 2, 3).reshape(KV, MB * bs, DH)
                for a in (want_k, want_v)]
        for t in range(n):
            for h in range(heads):
                kv = h // group
                reads = np.repeat(chosen[g, t, kv], bs) & (
                    np.arange(MB * bs) <= start + t)
                np.testing.assert_allclose(
                    out[g * TQ + t, h],
                    _listed_reference(q[g * TQ + t, h], view[0][kv],
                                      view[1][kv], reads),
                    rtol=2e-5, atol=2e-5, err_msg=f"{name} {g} {t} {h}")


# decode rows under a list (paged_flash_attend, pages=...): name: (bs, MB,
# positions, live, pages a list holds besides the row's own, poison); the
# fourth row is a freed slot whose stale table row is the first row's
LISTED_DECODE_CASES = {
    "listed-decode-64tok": (64, 40, [37 * 64 + 5, 20 * 64, 39 * 64 + 63, 7],
                            [True, True, True, False], 35, False),
    "listed-decode-one-step": (64, 40, [31 * 64 + 5, 20 * 64, 64 + 3, 7],
                               [True, True, True, False], 15, False),
    "listed-decode-poison": (64, 40, [37 * 64 + 5, 20 * 64, 39 * 64 + 63, 7],
                             [True, False, True, False], 36, True),
}


def check_listed_decode_case(name):
    """Decode rows, a list a row and KV head (its own page the last), the new
    token written in place; a row that is not live is not walked."""
    from distributed_llm_inference_tpu.models.minicpm_sala import page_lists

    bs, MB, pos, live, extra, poison = LISTED_DECODE_CASES[name]
    P = _walk_shape(KV, bs, DH, 2, False, H // KV, MB, listed=True)[1]
    assert P == 32
    pool_k, pool_v, table, rng = _listed_pool(bs, MB, seed=4)
    table = jnp.concatenate([table, table[:1]])
    B = len(pos)
    chosen = np.zeros((B, 1, KV, MB), bool)
    for b in range(B):
        own = pos[b] // bs
        for kv in range(KV):
            chosen[b, 0, kv, rng.permutation(own)[:extra]] = True
            chosen[b, 0, kv, own] = True
    plist, count, _ = page_lists(jnp.asarray(chosen), 128)
    q = jnp.asarray(rng.normal(size=(B, 1, H, DH)), jnp.float32)
    new_k, new_v = (jnp.asarray(rng.normal(size=(B, 1, KV, DH)), jnp.bfloat16)
                    for _ in range(2))
    out, got_k, got_v = paged_flash_attend(
        q, pool_k, pool_v, table, jnp.asarray(pos, jnp.int32), None,
        jnp.asarray(live), (jnp.int32(0), new_k, new_v), (plist, count),
        interpret=_interpret(poison))
    want_k, want_v = np.array(pool_k), np.array(pool_v)
    for b in range(B):
        if live[b]:
            where = (0, int(table[b, pos[b] // bs]), slice(None), pos[b] % bs)
            want_k[where] = np.asarray(new_k)[b, 0]
            want_v[where] = np.asarray(new_v)[b, 0]
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    out = np.asarray(out)[:, 0]
    assert np.all(np.isfinite(out)), name
    for b in range(B):
        if not live[b]:
            assert np.all(out[b] == 0.0)
            continue
        view = [np.asarray(a[0], np.float32)[np.asarray(table[b])]
                .transpose(1, 0, 2, 3).reshape(KV, MB * bs, DH)
                for a in (want_k, want_v)]
        for h in range(H):
            kv = h // (H // KV)
            reads = np.repeat(chosen[b, 0, kv], bs) & (
                np.arange(MB * bs) <= pos[b])
            np.testing.assert_allclose(
                out[b, h], _listed_reference(q[b, 0, h], view[0][kv],
                                             view[1][kv], reads),
                rtol=2e-5, atol=2e-5, err_msg=f"{name} {b} {h}")
