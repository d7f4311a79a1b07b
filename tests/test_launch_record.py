"""The launch record and the worker's phase clock (ISSUE 24;
engine/continuous.py `_launch_record` / `_fetch`, utils/tracing.PhaseClock).

One record per launch, mixed step or pure-decode chunk, feeds the
counters, the profiler annotations, the flight `plan` event and the sampled
per-tenant span. These tests hold it to numbers a hand can compute: a fixed
request list is served one request at a time through a tiny chunked paged
fleet with a sliding window, so every prompt lands in chunks of the step
width and every answer runs to its budget (no stop token), and the sums
follow from prompt lengths, answer lengths and the window alone.
"""

import math
import os
import time

import jax
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.utils.metrics import MetricsRegistry
from distributed_llm_inference_tpu.utils.tracing import (
    LAUNCH_PHASES, LAUNCH_TIMINGS, WORKER_PHASES, PhaseClock,
)

WINDOW = 48
SLOTS, CHUNK_STEPS, LAG, BLOCK, MAX_SEQ = 3, 4, 2, 16, 256
# (prompt, max_tokens): one-chunk prompts, a three-chunk prompt that
# crosses the window inside its prefill, answers that end inside a chunk,
# on a chunk's edge and after one token. Distinct from their first byte
# on, so the block-prefix index finds nothing.
REQUESTS = [
    ("alpha beta gamma", 9),
    ("Z" + " lorem ipsum dolor sit amet" * 6, 13),
    ("q", 1),
    ("mid-sized prompt, forty-one tokens long!!", 30),
    ("7 seven", 5),
]


@pytest.fixture(scope="module")
def setup():
    cfg = get_model_config(
        "test-llama-tiny", dtype="float32", eos_token_id=-1, max_seq_len=512,
        attn_window=WINDOW,
    )
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _cont(cfg, params, **engine_cfg):
    ecfg = dict(prefix_cache_entries=0, chunked_prefill=True,
                step_token_budget=64, prefill_buckets=(64, 128, 256))
    ecfg.update(engine_cfg)
    eng = InferenceEngine(cfg, params=params, engine_cfg=EngineConfig(**ecfg))
    return ContinuousEngine(
        eng, n_slots=SLOTS, chunk_steps=CHUNK_STEPS, chunk_lag=LAG,
        slot_max_seq=MAX_SEQ, kv_pool_blocks=64, kv_block_size=BLOCK,
        restart_backoff_s=0.01,
    )


def _series(snap, name):
    return {
        tuple(sorted(s["labels"].items())): s
        for s in snap.get(name, {}).get("series", [])
    }


def _value(snap, name, **labels):
    s = _series(snap, name).get(tuple(sorted(labels.items())))
    return 0 if s is None else s["value"]


def _hist(snap, name, **labels):
    s = _series(snap, name)[tuple(sorted(labels.items()))]
    return s["sum"], s["count"]


def _serve(cfg, params, **engine_cfg):
    """The request list, one at a time, through a fresh fleet."""
    cont = _cont(cfg, params, **engine_cfg)
    t0 = time.perf_counter()
    try:
        results = [
            cont.submit(p, max_tokens=n, greedy=True, chat=False)
            for p, n in REQUESTS
        ]
    finally:
        cont.close()
        cont._thread.join(timeout=30)
    wall = time.perf_counter() - t0
    assert not cont._thread.is_alive()
    assert all(r["status"] == "success" for r in results), results
    return {
        "snap": cont.engine.metrics.snapshot(), "results": results,
        "wall_s": wall, "width": cont._sched_width, "cont": cont,
        "flight": cont.engine.flight.events(),
    }


@pytest.fixture(scope="module")
def runs(setup):
    return [_serve(*setup) for _ in range(2)]


def _clip(n):
    return min(n, WINDOW)


def _unfetched(run):
    """Launches close() found dispatched and not fetched: the steps still
    in flight are whole chunks, and one step for a mixed launch."""
    ahead = run["cont"]._steps_inflight
    return ahead // CHUNK_STEPS + ahead % CHUNK_STEPS


def _expected(run):
    """By hand: a prompt of P tokens lands in chunks of the step width W,
    each reading min(tokens so far, window) positions once; its first
    token comes out of the last chunk, and each of its A - 1 further
    tokens takes one decode step at positions P .. P + A - 2, a step at
    position n reading min(n + 1, window)."""
    W = run["width"]
    out = {"mixed": 0, "prefill_tokens": 0, "attended_mixed": 0,
           "attended_chunk": 0, "row_steps": 0}
    for (_, answer), r in zip(REQUESTS, run["results"]):
        P = r["prompt_tokens"]
        assert r["tokens_generated"] == answer, r  # ran to its budget
        chunks = math.ceil(P / W)
        out["mixed"] += chunks
        out["prefill_tokens"] += P
        out["attended_mixed"] += sum(
            _clip(min(P, (c + 1) * W)) for c in range(chunks)
        )
        out["attended_chunk"] += sum(
            _clip(n + 1) for n in range(P, P + answer - 1)
        )
        out["row_steps"] += answer - 1
    return out


# -- (a) attended KV positions, exactly, on two runs ---------------------------

@pytest.mark.parametrize("i", [0, 1])
def test_attended_kv_tokens_equal_the_hand_sum(runs, i):
    run = runs[i]
    want = _expected(run)
    snap = run["snap"]
    kv = "dli_attn_kv_tokens_total"
    assert _value(snap, kv, phase="mixed", state="attended") == want["attended_mixed"]
    assert _value(snap, kv, phase="chunk", state="attended") == want["attended_chunk"]
    # the window bit: the long prompt and the long answer both crossed it
    assert want["attended_mixed"] < sum(r["prompt_tokens"] for r in run["results"]) * 2
    assert any(r["prompt_tokens"] + n > WINDOW for r, (_, n) in zip(run["results"], REQUESTS))


def test_walked_kv_tokens_are_the_grid(runs):
    """tiles (mixed) or slots x steps (chunk), each the block table wide."""
    run = runs[0]
    snap = run["snap"]
    row = -(-MAX_SEQ // BLOCK) * BLOCK
    mixed = _value(snap, "dli_ragged_launches_total", phase="mixed")
    chunk = _value(snap, "dli_ragged_launches_total", phase="chunk")
    kv = "dli_attn_kv_tokens_total"
    assert _value(snap, kv, phase="mixed", state="walked") == mixed * (run["width"] // 8) * row
    assert _value(snap, kv, phase="chunk", state="walked") == chunk * SLOTS * CHUNK_STEPS * row
    for phase in ("mixed", "chunk"):
        assert 0 < _value(snap, kv, phase=phase, state="attended") < _value(
            snap, kv, phase=phase, state="walked")


# -- (a2) under the paged kernels the walk is counted, not the grid -------------

@pytest.fixture(scope="module")
def walk_run(setup):
    """The same list through the Pallas kernels (interpreted here): the
    block walk decides `kv_grid_tokens`, and inactive slots walk nothing."""
    cfg, params = setup
    return _serve(cfg.replace(attn_impl="pallas"), params)


def _walk(start, n=1):
    """By hand: the whole blocks between the first position the tile's
    first query attends and the last position its last query attends."""
    needed = min(-(-(start + n) // BLOCK), -(-MAX_SEQ // BLOCK))
    first = min(max(start - WINDOW + 1, 0) // BLOCK, needed - 1)
    return (needed - first) * BLOCK


def test_walked_kv_tokens_are_the_live_blocks(walk_run):
    """A prompt's chunk is walked once per 8-token query tile, a decode
    step once; a slot that holds no row, and a row whose budget ran out
    inside a chunk, are not walked at all."""
    run = walk_run
    W, snap = run["width"], run["snap"]
    mixed = chunk = 0
    for (_, answer), r in zip(REQUESTS, run["results"]):
        P = r["prompt_tokens"]
        assert r["tokens_generated"] == answer, r
        for c in range(math.ceil(P / W)):
            end = min(P, (c + 1) * W)
            mixed += sum(_walk(t, min(8, end - t)) for t in range(c * W, end, 8))
        chunk += sum(_walk(n) for n in range(P, P + answer - 1))
    kv = "dli_attn_kv_tokens_total"
    assert _value(snap, kv, phase="mixed", state="walked") == mixed
    assert _value(snap, kv, phase="chunk", state="walked") == chunk
    # the attended counts do not depend on which path reads the pool
    want = _expected(run)
    assert _value(snap, kv, phase="mixed", state="attended") == want["attended_mixed"]
    assert _value(snap, kv, phase="chunk", state="attended") == want["attended_chunk"]
    # a decode step walks at most the window rounded out to whole blocks
    assert want["attended_chunk"] <= chunk <= (
        want["attended_chunk"] + 2 * BLOCK * want["row_steps"])
    row = -(-MAX_SEQ // BLOCK) * BLOCK
    chunks = _value(snap, "dli_ragged_launches_total", phase="chunk")
    assert chunk < chunks * SLOTS * CHUNK_STEPS * row / 4  # the old grid


def test_launch_records_walk_no_less_than_they_attend(walk_run):
    """Per record: kv_tokens <= kv_grid_tokens, and no more than each live
    tile's window rounded out to whole blocks."""
    ev = [e for e in walk_run["flight"] if e["kind"] == "plan"]
    assert len(ev) == 7
    for e in ev:
        assert 0 < e["kv_tokens"] <= e["kv_grid_tokens"]
        assert e["kv_grid_tokens"] <= e["tiles_live"] * (WINDOW + 2 * BLOCK)
        assert e["kv_grid_tokens"] < e["tiles"] * MAX_SEQ


@pytest.mark.parametrize("window", [None, 1, 5, 48, 100, 4096])
@pytest.mark.parametrize("bs,mb", [(8, 4), (16, 16), (32, 3), (128, 50)])
def test_host_walk_is_the_kernels_live_range(bs, mb, window):
    """`_kv_walk` (numpy, the launch record) against `_ragged_live_range`
    (the kernels' loop bounds) on a sweep of positions and tile lengths,
    past the table's end included; a decode row is a tile of one query."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.ops.paged_attention import (
        _ragged_live_range,
    )

    host = types.SimpleNamespace(
        _kv_walks=True, kv_block_size=bs, _max_blocks=mb, _kv_window=window,
        _scratch_seq=bs * mb, _sparse=None,
    )
    pos = np.arange(0, bs * mb + bs + 3)
    win = jnp.int32(window if window is not None else -1)
    first, needed = _ragged_live_range(
        jnp.asarray(pos), jnp.int32(1), bs=bs, MB=mb, win=win
    )
    np.testing.assert_array_equal(
        ContinuousEngine._kv_walk(host, pos),
        (np.asarray(needed) - np.asarray(first)) * bs,
    )
    for n in (1, 3, 8):
        first, needed = _ragged_live_range(
            jnp.asarray(pos), jnp.int32(n), bs=bs, MB=mb, win=win
        )
        walked = ContinuousEngine._kv_walk(host, pos, n)
        np.testing.assert_array_equal(
            walked, (np.asarray(needed) - np.asarray(first)) * bs
        )
        span = pos + n if window is None else np.minimum(pos + n, window)
        inside = pos + n <= bs * mb  # the engine never launches past it
        assert np.all(walked[inside] >= span[inside])
        assert np.all(walked <= bs * mb)
    # a tile that holds no query (launch padding, a freed slot) walks
    # nothing; the gather path reads every row's whole table, live or not
    assert np.all(ContinuousEngine._kv_walk(host, pos, 0) == 0)
    host._kv_walks = False
    assert np.all(ContinuousEngine._kv_walk(host, pos, 0) == bs * mb)


# -- (a3) loop steps of the walk: pages over the pages a step folds -------------

def test_walk_steps_equal_the_hand_sum(walk_run, setup):
    """`kv_walk_steps` / `dli_attn_walk_steps_total`: every tile's pages
    over P, rounded up, P from the function the kernels take it from. The
    tiny fleet's pool (2 KV heads of 16 numbers, 16-token blocks, a table
    of 16) gives 8 pages a step in both programs, the most any shape gets,
    so with a 48-token window every live tile is one loop step."""
    from distributed_llm_inference_tpu.engine import paged as EP
    from distributed_llm_inference_tpu.ops.paged_attention import (
        walk_pages_per_step,
    )

    cfg, _ = setup
    leaf = jax.eval_shape(lambda: EP.init_pool(cfg, 64, BLOCK))["k"]
    P = {"chunk": walk_pages_per_step(leaf, cfg.n_heads, 1, MAX_SEQ // BLOCK),
         "mixed": walk_pages_per_step(leaf, cfg.n_heads, 8, MAX_SEQ // BLOCK)}
    assert P == {"chunk": 8, "mixed": 8}
    run = walk_run
    W, snap = run["width"], run["snap"]
    steps = {"mixed": 0, "chunk": 0}
    for (_, answer), r in zip(REQUESTS, run["results"]):
        n = r["prompt_tokens"]
        for c in range(math.ceil(n / W)):
            end = min(n, (c + 1) * W)
            steps["mixed"] += sum(
                -(-_walk(t, min(8, end - t)) // BLOCK // P["mixed"])
                for t in range(c * W, end, 8))
        steps["chunk"] += sum(-(-_walk(p) // BLOCK // P["chunk"])
                              for p in range(n, n + answer - 1))
    for phase in ("mixed", "chunk"):
        assert _value(snap, "dli_attn_walk_steps_total", phase=phase) \
            == steps[phase] > 0
    # the flight recorder's `plan` events are the mixed launches' records
    ev = [e for e in run["flight"] if e["kind"] == "plan"]
    assert sum(e["kv_walk_steps"] for e in ev) == steps["mixed"]
    for e in ev:  # pages a loop step: between 1 and P
        pages = e["kv_grid_tokens"] // BLOCK
        assert e["kv_walk_steps"] <= pages <= 8 * e["kv_walk_steps"]


def test_walk_steps_are_zero_under_the_gather_path(runs):
    snap = runs[0]["snap"]
    for phase in ("mixed", "chunk"):
        assert _value(snap, "dli_attn_walk_steps_total", phase=phase) == 0
    assert all(e["kv_walk_steps"] == 0 for e in runs[0]["flight"]
               if e["kind"] == "plan")


@pytest.mark.parametrize("P", [1, 2, 4, 8])
def test_host_walk_steps_round_the_pages_up(P):
    """`_kv_walk_steps` against the kernels' trip count
    ceil((needed - first) / P), on a sweep of positions under a window."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.ops.paged_attention import (
        _ragged_live_range,
    )

    bs, mb, window = 16, 24, 100
    host = types.SimpleNamespace(
        _kv_walks=True, kv_block_size=bs, _max_blocks=mb, _kv_window=window,
        _scratch_seq=bs * mb, _walk_pages={"chunk": (P,), "mixed": (1,)},
        _sparse=None,
    )
    host._kv_walk = types.MethodType(ContinuousEngine._kv_walk, host)
    pos = np.arange(0, bs * mb)
    for n in (0, 1, 8):
        first, needed = _ragged_live_range(
            jnp.asarray(pos), jnp.int32(n), bs=bs, MB=mb, win=jnp.int32(window))
        trips = -(-(np.asarray(needed) - np.asarray(first)) // P)
        walk = host._kv_walk(pos, n)
        got = ContinuousEngine._kv_walk_steps(host, "chunk", walk)
        np.testing.assert_array_equal(got, trips if n else 0 * trips)
        pages = ContinuousEngine._kv_walk_steps(host, "mixed", walk)
        np.testing.assert_array_equal(pages * bs, walk)
    host._kv_walks = False
    walk = host._kv_walk(pos)  # the gather path: every row's whole table
    assert np.all(ContinuousEngine._kv_walk_steps(host, "chunk", walk) == 0)


# (KV heads a program, pages a loop step) of the decode chunk's kernel and of
# the mixed launch's (query tiles of 8), at the benchmark's configurations as
# their cells serve them (cellbench/configs/<name>.json); PERF.md, PR 45
CELL_WALK_SHAPES = {
    "olmo2-7b-16l": ((32, 1), (32, 1)),  # 2 MB a page already: olmo2-chat, -batch
    "mistral-7b-16l": ((8, 2), (8, 2)),
    "kanana-2-30b-a3b-7l": ((1, 8), (1, 4)),  # 164 KB a page: the latent row
    "sdar-30b-a3b-7l": ((4, 4), (4, 4)),  # a row's forward is a tile of 8
    "lfm2-24b-a2b-9l": ((4, 4), (4, 4)),  # pairs of 64-number heads a row
    "trinity-large-ep8-5l": ((8, 2), (8, 2)),
    # a page LIST a KV head: a program is one head, 32 pages of 64 tokens a
    # step (32 KB a page: `_LIST_STEP_PAGES`, ISSUE 52; the seven rows above
    # are the range walk's and did not move)
    "minicpm-sala-9b-16l": ((1, 32), (1, 32)),
}


@pytest.mark.parametrize("name", sorted(CELL_WALK_SHAPES))
def test_walk_shape_at_the_cells_shapes(name):
    """`_walk_shape` pinned where the benchmark runs it, through the
    engine's own reading of its pool (`_walk_pages_of`): a change of the
    rule or of the stated VMEM count shows here as the P each cell gets."""
    import types

    from paged_walk_cases import cell_pool

    from distributed_llm_inference_tpu.ops.paged_attention import _walk_shape

    cfg, _, mb, pool = cell_pool(name)
    listed = bool(cfg.linear_layers)  # (the selected read: `_walk_kernel`)
    host = types.SimpleNamespace(cfg=cfg, _max_blocks=mb, cache=pool,
                                 _sparse=cfg if listed else None)
    kv, bs, dh = next(a for a in jax.tree.leaves(pool) if a.ndim == 5).shape[-3:]
    tiles = (2 * cfg.diffusion_block or 1, 8)  # the decode chunk's, a mixed launch's
    for tq, want in zip(tiles, CELL_WALK_SHAPES[name]):
        # (one number a group of the pool)
        assert ContinuousEngine._walk_pages_of(host, tq) == (
            want[1],) * len(cfg.kv_groups)
        assert _walk_shape(kv, bs, dh, 2, False, tq * (cfg.n_heads // kv), mb,
                           cfg.latent_dim > 0, listed) == want


# -- (a4) the compressed keys a selection's scoring reads (ISSUE 50) -------------

def test_ck_scored_is_the_rows_keys_up_to_their_lengths():
    """`ck_scored` of launches of known rows at `sala-docs-xlong`'s sizes,
    a sparse layer and KV head: a row's compressed keys once a step, up to
    the row's length (what ops/sparse_select.py copies), against what the
    gather it replaced read: every tile's (a decode chunk: every slot's)
    whole table of 66,048 / 16 = 4,128 keys, live or not, at any length."""
    import types

    import numpy as np

    from distributed_llm_inference_tpu.models.registry import get_model_config

    cfg = get_model_config("minicpm-sala")
    tally = types.SimpleNamespace(total=0)
    tally.inc = lambda n=1: setattr(tally, "total", tally.total + n)
    series = types.SimpleNamespace(labels=lambda **kw: types.SimpleNamespace(
        inc=lambda n=1: None))
    host = types.SimpleNamespace(
        _sparse=cfg, kv_block_size=64, n_slots=16, _m_ck_scored=tally,
        _m_kv_tokens=series, _m_sparse_rows=series, _m_lin_rows=series)
    host._kv_span = lambda *a: ContinuousEngine._kv_span(host, *a)
    # a mixed launch: two decode rows at 30,000 beside a 120-token chunk
    # that ends at 20,120 (17 tiles of 8)
    mixed = ContinuousEngine._sparse_fields(
        host, "mixed", [30001, 30001, 20120], 1)
    assert mixed["ck_scored"] == 2 * 1876 + 1258 == 5010
    assert 17 * 4128 / mixed["ck_scored"] > 14
    # a 16-step decode chunk with two rows live of 16 slots, from 30,000
    at = 30000 + np.arange(16)[None, :] + np.zeros((2, 1), int)
    chunk = ContinuousEngine._sparse_fields(host, "chunk", at + 1, 16)
    assert chunk["ck_scored"] == 2 * 16 * 1876  # (30,016 visible: still 1,876)
    assert 16 * 16 * 4128 / chunk["ck_scored"] > 17
    assert tally.total == mixed["ck_scored"] + chunk["ck_scored"]
    assert chunk["state_rows"] == 32 and mixed["sparse_rows"] == 3


# -- (b) both kinds of launch are counted; the old series keep their values ----

def test_chunk_launches_and_row_steps_are_counted(runs):
    run = runs[0]
    want, snap = _expected(run), run["snap"]
    chunk = _value(snap, "dli_ragged_launches_total", phase="chunk")
    # every decode step ran in some chunk (an answer of one token still
    # costs a chunk of dead rows: no row-steps)
    assert chunk * CHUNK_STEPS >= want["row_steps"] and chunk >= 5
    assert _value(snap, "dli_sched_decode_rows_total") == want["row_steps"]
    assert _value(snap, "dli_sched_step_tokens_total", kind="decode") == want["row_steps"]
    # one fetch per launch of either kind, as ever (close() leaves the
    # chunks the lag had dispatched ahead unfetched)
    _, fetches = _hist(snap, "dli_decode_step_seconds", engine="continuous")
    unfetched = _unfetched(run)
    assert 0 <= unfetched <= LAG
    assert fetches == chunk + want["mixed"] - unfetched


def test_a_chunks_steps_are_counted_as_run_or_cut(runs):
    """ISSUE 46: a chunk is dispatched with CHUNK_STEPS and ends on the device
    with its last live row. Served one at a time, a request's chunks run its
    answer - 1 decode steps between them and the exit saves the rest; an
    answer of one token dispatches a chunk of dead rows that runs nothing.
    The counter is kept at the fetch: what close() found unfetched (whole
    chunks of the steps still in flight) is in neither state."""
    for run in runs:
        want, snap = _expected(run), run["snap"]
        chunk = _value(snap, "dli_ragged_launches_total", phase="chunk")
        unfetched = run["cont"]._steps_inflight // CHUNK_STEPS
        ran = _value(snap, "dli_decode_chunk_steps_total", state="run")
        cut = _value(snap, "dli_decode_chunk_steps_total", state="cut")
        assert ran + cut == (chunk - unfetched) * CHUNK_STEPS
        assert 0 <= want["row_steps"] - ran <= unfetched * CHUNK_STEPS
        # (9, 13, 1, 30, 5) tokens: 8 + 12 + 0 + 29 + 4 live steps in 15
        # chunks of 4
        assert want["row_steps"] == 53 and chunk * CHUNK_STEPS == 60
        if not unfetched:
            assert (ran, cut) == (53, 7)


def test_old_series_read_what_the_parent_counted(runs):
    """The series the benchmark's readers name: the numbers the parent
    commit (a38196f) gave for this list on both of two runs, read there by
    hand (PR 24: 7 mixed launches, 233 prefill tokens, 7 chunks, 30
    fetches, 5 admissions), and the rule behind them."""
    for run in runs:
        want, snap = _expected(run), run["snap"]
        assert want["mixed"] == 7 and want["prefill_tokens"] == 233
        assert _value(snap, "dli_ragged_launches_total", phase="mixed") == 7
        assert _value(snap, "dli_sched_step_tokens_total", kind="prefill") == 233
        assert _value(snap, "dli_sched_prefill_chunks_total") == 7
        # 15 chunk + 7 mixed launches, each fetched once, less those close()
        # found dispatched ahead. How many that is depends on when close()
        # reaches a worker that is draining its lag, so the test takes it
        # from the run's own record. The chunks: ceil((answer - 1) / 4) an
        # answer, and one for the answer of one token (its row never
        # lives; the chunk after its arming launch is where the position
        # model sees that). Until PR 31 a slot was released by the fetch
        # alone, two chunks of dead rows later: 24 chunks, 31 fetches
        unfetched = _unfetched(run)
        chunks = sum(-(-(n - 1) // CHUNK_STEPS) or 1 for _, n in REQUESTS)
        assert chunks == 15
        assert _value(snap, "dli_ragged_launches_total", phase="chunk") == chunks
        assert 0 <= unfetched <= LAG
        assert _hist(snap, "dli_decode_step_seconds", engine="continuous")[1] == (
            chunks + 7 - unfetched)
        assert _hist(snap, "dli_admission_wait_seconds", queue="continuous")[1] == 5


# -- (c) the worker's phases sum to its wall time -------------------------------

def test_worker_phases_sum_to_wall_time(runs):
    run = runs[1]
    phases = {
        dict(k)["phase"]: s["value"]
        for k, s in _series(run["snap"], "dli_worker_phase_seconds_total").items()
    }
    assert set(phases) == set(WORKER_PHASES)
    # the thread started inside the engine's constructor, before t0, and
    # ended inside close(): the clock covers at least the served part
    assert sum(phases.values()) >= 0.99 * run["wall_s"] - 0.05
    clock = run["cont"]._clock
    assert clock._phase is None and clock._open is None  # stopped at close
    assert phases["fetch_wait"] > 0 and phases["dispatch"] > 0 and phases["plan"] > 0


def test_phase_clock_is_contiguous():
    m = MetricsRegistry()
    fam = m.counter("dli_worker_phase_seconds_total", "", ("phase",))
    clock = PhaseClock(fam, m.counter("dli_device_empty_seconds_total", "", ("phase",)))
    t0 = clock.mark("plan")
    time.sleep(0.01)
    clock.mark("dispatch", "launch.mixed", seq=1, kv_tokens=5)
    time.sleep(0.01)
    clock.mark("plan")
    t1 = clock.mark(None)
    total = sum(s["value"] for s in fam.snapshot()["series"])
    assert total == pytest.approx(t1 - t0, rel=0.01)
    assert clock.mark(None) >= t1  # stopped: nothing more is added
    assert sum(s["value"] for s in fam.snapshot()["series"]) == total


# -- (d) the two halves of a first token's wait ---------------------------------

def test_queue_wait_plus_prefill_is_the_admission_wait(runs):
    for run in runs:
        snap = run["snap"]
        whole, n = _hist(snap, "dli_admission_wait_seconds", queue="continuous")
        queue, nq = _hist(snap, "dli_queue_wait_seconds", queue="continuous")
        prefill, npf = _hist(snap, "dli_prefill_seconds", queue="continuous")
        assert n == nq == npf == len(REQUESTS)
        assert queue + prefill == pytest.approx(whole, abs=1e-3 * n)
        assert prefill > queue  # one request at a time: nothing waits for a slot


def test_a_waiting_head_is_counted_by_reason(setup):
    """Four requests at once for three slots: while all slots are taken the
    head waits for a slot, once per scheduler iteration."""
    import threading

    cont = _cont(*setup)
    try:
        ts = [
            threading.Thread(target=cont.submit, args=(f"{i} waits",),
                             kwargs=dict(max_tokens=24, greedy=True, chat=False))
            for i in range(SLOTS + 1)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        cont.close()
    snap = cont.engine.metrics.snapshot()
    assert _value(snap, "dli_admission_blocked_total", reason="slot") >= 1
    assert _value(snap, "dli_admission_blocked_total", reason="blocks") == 0
    queue, n = _hist(snap, "dli_queue_wait_seconds", queue="continuous")
    assert n == SLOTS + 1 and queue > 0


# -- (f) work dispatched ahead of a launch --------------------------------------

def test_steps_ahead_is_zero_first_and_bounded_by_the_lag(runs):
    run = runs[0]
    plans = [e for e in run["flight"] if e["kind"] == "plan"]
    assert plans and plans[0]["steps_ahead"] == 0 and plans[0]["seq"] == 1
    bound = (LAG + 1) * CHUNK_STEPS
    for phase in ("mixed", "chunk"):
        s = _series(run["snap"], "dli_launch_steps_ahead")[(("phase", phase),)]
        assert s["count"] == _value(run["snap"], "dli_ragged_launches_total", phase=phase)
        assert s["sum"] <= bound * s["count"]
        assert s["p99"] <= bound


# -- (g) the worker times the device it feeds (ISSUE 53) ------------------------

@pytest.fixture(scope="module")
def drained(setup):
    """The request list through a fresh fleet whose worker is left to fetch
    every launch it dispatched before the scrape (close() would find the
    lag's launches unfetched), then a second of an idle engine that a last
    request ends."""
    cont = _cont(*setup)
    try:
        for p, n in REQUESTS:
            cont.submit(p, max_tokens=n, greedy=True, chat=False)
        deadline = time.time() + 30
        while cont._steps_inflight and time.time() < deadline:
            time.sleep(0.005)
        assert cont._steps_inflight == 0
        time.sleep(0.05)  # the worker is back in wait_work
        served = cont.engine.metrics.snapshot()
        # an interval is counted when it closes: a request ends the wait
        time.sleep(1.0)
        cont.submit("7 seven", max_tokens=1, greedy=True, chat=False)
        idle = cont.engine.metrics.snapshot()
    finally:
        cont.close()
    return {"served": served, "idle": idle}


def test_every_fetched_launch_is_timed_or_says_why_not(drained):
    snap = drained["served"]
    for phase in LAUNCH_PHASES:
        by_state = {s: _value(snap, "dli_launch_timing_total", phase=phase, state=s)
                    for s in LAUNCH_TIMINGS}
        assert sum(by_state.values()) == _value(
            snap, "dli_ragged_launches_total", phase=phase) > 0, by_state
        # the loop's first launch met an empty queue (a later request's
        # first may find the one before's last chunks still unfetched)
        if phase == "mixed":
            assert by_state["queue_empty"] >= 1
    # timed chunks ran no more steps than all fetched chunks did, and seconds
    # are counted for timed launches alone
    steps = _value(snap, "dli_launch_device_steps_total", phase="chunk")
    assert steps <= _value(snap, "dli_decode_chunk_steps_total", state="run")
    for phase in LAUNCH_PHASES:
        timed = _value(snap, "dli_launch_timing_total", phase=phase, state="timed")
        seconds = _value(snap, "dli_launch_device_seconds_total", phase=phase)
        rows = _value(snap, "dli_decode_row_seconds_total", phase=phase)
        assert (seconds > 0) == (timed > 0) and rows <= seconds * SLOTS
        if phase == "mixed":  # a mixed launch is one step
            assert _value(snap, "dli_launch_device_steps_total", phase=phase) == timed


def test_the_families_are_on_an_idle_engines_metrics_from_zero(setup):
    cont = _cont(*setup)
    try:
        text = cont.engine.metrics.render()
    finally:
        cont.close()
    for phase in LAUNCH_PHASES:
        for name in ("dli_launch_device_seconds_total", "dli_launch_device_steps_total",
                     "dli_decode_row_seconds_total"):
            assert f'{name}{{phase="{phase}"}} 0' in text
        for state in LAUNCH_TIMINGS:
            assert f'dli_launch_timing_total{{phase="{phase}",state="{state}"}} 0' in text
    for phase in WORKER_PHASES:  # the loop's first instants are already counted
        assert f'dli_device_empty_seconds_total{{phase="{phase}"}} ' in text
    for state in ("live", "computed"):
        assert f'dli_mixed_tokens_total{{state="{state}"}} 0' in text


def test_an_idle_engines_empty_seconds_are_wait_work(drained):
    def empty(snap):
        return {dict(k)["phase"]: s["value"] for k, s in
                _series(snap, "dli_device_empty_seconds_total").items()}

    served, idle = empty(drained["served"]), empty(drained["idle"])
    assert set(idle) == set(WORKER_PHASES) and idle["fetch_wait"] == 0
    grew = {p: idle[p] - served[p] for p in idle}
    # the idle second is the traffic's; the last request's own launch adds
    # a little of the host's phases
    assert 0.9 <= grew["wait_work"] <= 1.3
    assert grew["wait_work"] >= 0.8 * sum(grew.values())
    # empty seconds are worker seconds: never more than the phase's own
    for p in WORKER_PHASES:
        assert idle[p] <= _value(drained["idle"], "dli_worker_phase_seconds_total",
                                 phase=p) + 1e-9
    # while it served, the queue also stood empty in the host's own phases
    # (the first launch, at the least, is planned and dispatched with
    # nothing queued)
    assert served["dispatch"] > 0 and served["plan"] > 0


def test_mixed_tokens_are_counted_live_and_computed(runs):
    """The record's `tokens_live` is the launch's decode, verify and prompt
    tokens and `tokens_computed` the axis the token-wise layers ran on
    (engine/scheduler.live_width: this dense fleet's whole width); the
    counter sums both over the mixed launches."""
    run = runs[0]
    ev = [e for e in run["flight"] if e["kind"] == "plan"]
    assert all(e["tokens_live"] == e["decode_rows"] + e["prefill_tokens"] for e in ev)
    assert all(e["tokens_computed"] == run["width"] for e in ev)
    assert run["cont"].stats()["scheduler"]["live_width"] == run["width"]
    want = _expected(run)
    snap = run["snap"]
    assert _value(snap, "dli_mixed_tokens_total", state="live") \
        == sum(e["tokens_live"] for e in ev) == want["prefill_tokens"]
    assert _value(snap, "dli_mixed_tokens_total", state="computed") \
        == run["width"] * want["mixed"]


def test_flight_plan_event_is_the_launch_record(runs):
    ev = [e for e in runs[0]["flight"] if e["kind"] == "plan"]
    assert len(ev) == 7  # one per mixed step that carried a prefill chunk
    for e in ev:
        assert e["phase"] == "mixed" and e["steps"] == 1 and e["prefill_chunks"] == 1
        assert 0 < e["kv_tokens"] <= e["kv_grid_tokens"]
        assert e["tiles_live"] <= e["tiles"] and "budget" in e
        assert e["queue_empty"] == int(e["steps_ahead"] == 0)
    assert sum(e["prefill_tokens"] for e in ev) == 233
    assert [e["seq"] for e in ev] == sorted(e["seq"] for e in ev)


# -- (e) the annotations, on the profiler's clock -------------------------------

def test_profiler_trace_holds_launch_fetch_and_phase_events(setup, tmp_path):
    from jax.profiler import ProfileData

    cont = _cont(*setup)
    try:
        cont.submit("warm the programs", max_tokens=6, greedy=True, chat=False)
        before = cont.engine.metrics.snapshot()
        jax.profiler.start_trace(str(tmp_path))
        try:
            for p, n in REQUESTS[:2]:
                cont.submit(p, max_tokens=n, greedy=True, chat=False)
            time.sleep(0.05)  # the lagged fetches drain
        finally:
            jax.profiler.stop_trace()
        after = cont.engine.metrics.snapshot()
    finally:
        cont.close()
    path = next(
        os.path.join(base, f) for base, _, fs in os.walk(tmp_path)
        for f in fs if f.endswith(".xplane.pb")
    )
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split(".")[0] in ("launch", "fetch", "phase", "begin"):
                    events.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    # a wait is preceded by an instant marker of its own name and stats
    # (the profiler keeps no interval still open when it stops)
    begun = [(n[len("begin."):], s, st) for n, s, _, st in events if n.startswith("begin.")]
    events = [e for e in events if not e[0].startswith("begin.")]
    waits = {(n, st.get("seq")) for n, _, _, st in events
             if n.startswith("fetch.") or n == "phase.wait_work"}
    assert begun and {(n, st.get("seq")) for n, _, st in begun} >= waits
    names = {n for n, *_ in events}
    assert {"launch.mixed", "launch.chunk", "fetch.mixed", "fetch.chunk"} <= names
    assert {"phase.plan", "phase.distribute", "phase.wait_work", "phase.admit",
            "phase.reap"} <= names
    launches = {st["seq"]: (n, s, e, st) for n, s, e, st in events if n.startswith("launch.")}
    fetches = {st["seq"]: (n, s, e) for n, s, e, st in events if n.startswith("fetch.")}
    kv = "dli_attn_kv_tokens_total"
    for phase in ("mixed", "chunk"):
        delta = (_value(after, kv, phase=phase, state="attended")
                 - _value(before, kv, phase=phase, state="attended"))
        got = sum(int(st["kv_tokens"]) for n, _, _, st in launches.values()
                  if n == f"launch.{phase}")
        assert got == delta > 0
    assert fetches
    for seq, (name, start, _) in fetches.items():
        if seq in launches:  # a launch from before the trace has none
            ln, _, l_end, st = launches[seq]
            assert name == "fetch." + ln.split(".", 1)[1] and start >= l_end
            assert int(st["steps"]) in (1, CHUNK_STEPS)
    # a chunk's record carries the position model's forecast, and the span
    # that follows its fetch what the device ran (ISSUE 46): no stop token
    # here, so the two agree, and steps stays what was dispatched
    ran = {int(st["seq"]): int(st["steps_run"]) for n, _, _, st in events
           if n == "phase.distribute" and "steps_run" in st}
    chunks = {seq: st for seq, (n, _, _, st) in launches.items() if n == "launch.chunk"}
    assert chunks and all("steps_live" not in st for seq, (n, _, _, st) in launches.items()
                          if n == "launch.mixed")
    for seq, st in chunks.items():
        assert int(st["steps"]) == CHUNK_STEPS and 0 <= int(st["steps_live"]) <= CHUNK_STEPS
        if seq in ran:
            assert ran[seq] == int(st["steps_live"])
    assert set(ran) & set(chunks) and set(ran) <= {seq for seq, *_ in fetches.items()}
    # ISSUE 53: a launch says whether it met an empty queue, a fetch whether
    # its result was ready, and the span after a fetch what the worker made
    # of the launch's device time (microseconds, 0 unless timed)
    assert all(int(st["queue_empty"]) == int(int(st["steps_ahead"]) == 0)
               for _, _, _, st in launches.values())
    assert {int(st["ready"]) for n, _, _, st in events if n.startswith("fetch.")} <= {0, 1}
    closed = {int(st["seq"]): st for n, _, _, st in events
              if n == "phase.distribute" and "seq" in st}
    assert set(closed) == set(fetches)
    for st in closed.values():
        assert int(st["timed"]) in (0, 1)
        assert (int(st["device_us"]) > 0) == bool(int(st["timed"]))
    # the worker's intervals are contiguous: each begins where one ended.
    # A hole the clock leaves shows at every iteration (one gap in seven),
    # so nine gaps in ten are held and not the longest: a loaded machine
    # takes the thread away between two spans for milliseconds now and then
    worker = sorted((s, e) for n, s, e, _ in events)
    gaps = sorted(b[0] - a[1] for a, b in zip(worker, worker[1:]))
    assert gaps[0] > -2e3  # ns: no overlap
    assert gaps[len(gaps) * 9 // 10] < 2e5  # ns: no hole
