"""Cases of the paged walk's COMPUTE BLOCKS (ops/paged_attention: a loop
step folds P pages), run by tests/test_paged.py and
tests/test_ragged_attention.py as cases of their parametrised walk tests.

A case's shapes give its P through `_walk_shape` itself (asserted, so a
change of the rule cannot leave a case at another P in silence): bf16 pools
of 2 KV heads x 128 in blocks of 1024 / P tokens under a table 3 P + 1 pages
wide, where the cap of 1,024 positions a step decides; a latent row pool;
pairs of 64-number heads side by side (models/lfm2.pack_heads). Contexts end
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
from distributed_llm_inference_tpu.models.lfm2 import pack_heads, unpack_heads
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
    its flags (a grouped pool's window group a quarter of the global one's
    blocks), nothing allocated."""
    serving = cell_serving(name)
    flags = serving["flags"]
    slots, context, blocks, bs = (
        int(flags[flags.index(k) + 1]) for k in
        ("--continuous", "--continuous-max-seq", "--kv-pool-blocks",
         "--kv-block-size"))
    cfg = resolve_attn_impl(get_model_config(serving["base"]).replace(
        dtype="bfloat16", **serving["overrides"]), "pallas")
    if len(cfg.kv_groups) > 1:
        blocks = (blocks, blocks // 4)
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
