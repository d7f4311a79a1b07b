"""SLO-aware chunked-prefill scheduler (engine/scheduler.py) tests.

The bar: chunked scheduling is a LAUNCH strategy, not a semantics change —
greedy output must be bit-identical to the whole-prefill admission flow,
decode must keep advancing while a long prompt lands chunk by chunk (the
TPOT guarantee the subsystem exists for), the per-step token budget must
be sliced deterministically (decode rows first, class-apportioned prefill,
starvation-free), SLO admission control must shed with class-local
Retry-After hints, and a crash mid-chunked-prefill must salvage with
bit-identical greedy output (PR-5 discipline, chunk-aligned progress).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine.continuous import (
    ContinuousEngine,
    _Request,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.scheduler import (
    SHED_GRACE,
    PrefillJob,
    SLOClass,
    TokenBudgetScheduler,
    live_width,
    parse_slo_classes,
    step_width,
)
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.utils import faults

from dense_equal import CELL_CONFIGS, cell_config

TILE = 8


# -- planner units (no engine, no device) ------------------------------------

class _FakeReq:
    def __init__(self, enqueued):
        self.enqueued = enqueued


def _job(cls, tail, enqueued=0.0, slot=0):
    job = PrefillJob(
        _FakeReq(enqueued), ids=list(range(tail)), p0=0, prompt_len=tail,
        max_tokens=4, slot=slot, sampling=(0.7, 50, 0.9, True, 0.0, 1.0,
                                           0.0, 0.0),
        presence_row=None, table_row=None, cls=cls,
    )
    return job


def _sched(width=64, n_slots=4, classes=None, default="standard"):
    if classes is None:
        classes = {
            "interactive": SLOClass("interactive", 0.5, 0.1, 4.0, True),
            "standard": SLOClass("standard", 2.0, 0.5, 2.0, True),
            "batch": SLOClass("batch", 30.0, 2.0, 1.0, False),
        }
    return TokenBudgetScheduler(classes, default, width, TILE, n_slots)


def test_width_clamps_to_fleet_plus_one_tile():
    s = _sched(width=8, n_slots=4)
    # 4 decode tiles + >= 1 prefill tile: 40 tokens minimum at tile 8
    assert s.width == (4 + 1) * TILE
    # and always whole tiles
    assert _sched(width=70, n_slots=2).width == 72


@pytest.mark.parametrize("model,slots,width", [
    # dense: the launch stops being weight-bound at 240 flat tokens; the
    # fleet's decode tiles on top where they would take a third of that
    ("olmo2-7b", 12, 96 + 128),
    ("mistral-7b", 16, 128 + 128),
    ("olmo2-7b", 5, 128),  # 40 of 128: under a third
    # routed through the grouped kernels, 16 / 21 / 16 experts streamed for
    # each one a token computes (llama family, mla_moe, lfm2)
    ("sdar-30b-a3b-chat", 32, 512),  # (a block-diffusion row fills its tile)
    ("kanana-2-30b-a3b", 8, 512),
    ("lfm2-24b-a2b", 16, 512),
    ("lfm2-24b-a2b", 80, 640 + 512),  # a fleet that would pad a routed width
    # the all-experts einsum computes every expert it streams for every
    # token: dense by its arithmetic whatever n_experts / n_experts_per_tok
    ("mixtral-8x7b", 8, 64 + 128),
    ("qwen3-30b-a3b", 8, 64 + 128),
    ("test-moe-tiny", 4, 128),
    # the CI presets of the three routed families: 5, 5 and 4 to one
    ("test-sdar-tiny", 4, 512),
    ("test-mla-moe-tiny", 3, 512),
    ("test-lfm2-tiny", 2, 512),
])
def test_step_width_is_derived_from_what_the_model_streams(model, slots,
                                                           width):
    cfg = get_model_config(model)
    assert step_width(cfg, slots, TILE) == width
    # read from the configuration's numbers, never from its name or family
    assert step_width(cfg.replace(name="x"), slots, TILE) == width
    # one routed expert in three is not a bank worth a wider launch
    if cfg.moe_ffn_dim:
        few = cfg.replace(n_experts=3 * cfg.n_experts_per_tok)
        assert step_width(few, 4, TILE) == 128


@pytest.mark.parametrize("model", ["test-llama-tiny", "test-lfm2-tiny"])
@pytest.mark.parametrize("budget,slots,width", [
    (16, 1, 16), (32, 3, 32), (64, 4, 64), (70, 2, 72), (8, 4, 40),
    (1024, 4, 1024),
])
def test_an_explicit_step_token_budget_is_obeyed(model, budget, slots, width):
    assert step_width(get_model_config(model), slots, TILE, budget) == width


@pytest.mark.parametrize("model,dtype,slots,width,live", [
    # 36 layers' float32 states, read and written: 151 MB a row beside
    # 6.38 GB of bfloat16 weights, so the states are the larger stream from
    # 43 rows on: there the live tokens are the fleet's and twice the dense
    # budget of prompt, under it the (clamped) budget as everywhere
    ("granite-4.0-h-micro", "bfloat16", 64, 64 * TILE + 128, 320),
    ("granite-4.0-h-micro", "bfloat16", 128, 128 * TILE + 128, 384),
    ("granite-4.0-h-micro", "bfloat16", 43, 43 * TILE + 128, 304),
    ("granite-4.0-h-micro", "bfloat16", 42, 42 * TILE + 128, 43 * TILE),
    ("granite-4.0-h-micro", "bfloat16", 16, 16 * TILE + 128, 136),
    # float32 weights are twice the stream: 64 rows' states do not outweigh them
    ("granite-4.0-h-micro", "float32", 64, 64 * TILE + 128, 65 * TILE),
    # a sparse model's selection reads the launch's tiles: the tile layout,
    # however many rows it keeps
    ("minicpm-sala", "bfloat16", 16, 136, 136),
    ("minicpm-sala", "bfloat16", 64, 65 * TILE, 65 * TILE),
    ("minicpm-sala", "bfloat16", 512, 513 * TILE, 513 * TILE),
    # no matrix state, however many rows: a convolution state is a few KB
    ("lfm2-24b-a2b", "bfloat16", 80, 640 + 512, 648),
    ("mistral-7b", "bfloat16", 64, 512 + 128, 65 * TILE),
])
def test_widths_where_a_fleet_pads_the_launch(model, dtype, slots, width, live):
    cfg = get_model_config(model).replace(dtype=dtype)
    assert (step_width(cfg, slots, TILE), live_width(cfg, slots, TILE)) == (
        width, live)
    # whole tiles, room for a full fleet's decode tokens and a tile of prompt
    assert live % TILE == 0 and live >= slots + TILE
    # an explicit budget is a width of full tiles: obeyed as it is
    assert step_width(cfg, slots, TILE, 1024) == max(1024, (slots + 1) * TILE)
    assert live_width(cfg, slots, TILE, 1024) == step_width(cfg, slots, TILE, 1024)


# -- the two widths of the benchmark's configurations (ISSUE 56) ---------------

# configuration -> (its slots, step_width, live_width): the launch in the
# kernel's tile layout, and the axis the model computes
BENCHMARK_WIDTHS = {
    # a full fleet's decode tiles would take a third or more of the launch
    # the budget alone gives: the tiles go on top, the budget stays the axis
    "mimo-v2.5-7l": (32, 768, 512),  # 256 of 512
    "olmo2-7b-16l": (12, 224, 128),  # 96 of 128
    "mistral-7b-16l": (16, 256, 136),  # 128 of 136
    # ... and where the states outweigh the weights, slots + 2 x 128 of it
    "granite-4.0-h-micro": (64, 640, 320),  # 512 of 520
    # under a third (64-128 of 512): the budget is the launch
    "kanana-2-30b-a3b-7l": (8, 512, 512),
    "lfm2-24b-a2b-9l": (16, 512, 512),
    "trinity-large-ep8-5l": (16, 512, 512),
    "solar-open2-ep8-4l": (16, 512, 512),
    # the selection reads the launch's tiles; a decode row's tile is its blocks
    "minicpm-sala-9b-16l": (16, 136, 136),
    "sdar-30b-a3b-7l": (32, 512, 512),
}


@pytest.mark.parametrize("config", sorted(BENCHMARK_WIDTHS))
def test_the_benchmarks_configurations_launch_and_compute_these_widths(config):
    slots, width, live = BENCHMARK_WIDTHS[config]
    cfg, served = cell_config(config)
    assert served == slots
    assert (step_width(cfg, slots, TILE), live_width(cfg, slots, TILE)) == (
        width, live)
    # the rule reads shapes: no name
    assert live_width(cfg.replace(name="x"), slots, TILE) == live
    # a newly covered configuration computes exactly the width it launched
    # before the tiles went on top (its clamped budget)
    budget = 512 if cfg.moe_ffn_dim else 128
    if live < width and not cfg.linear_layers:
        assert (width, live) == (slots * TILE + budget,
                                 max(budget, (slots + 1) * TILE))
    # an explicit budget is obeyed as it is, by both
    assert step_width(cfg, slots, TILE, 1024) == live_width(
        cfg, slots, TILE, 1024) == 1024


def test_every_benchmark_configuration_is_in_the_table():
    assert sorted(CELL_CONFIGS) == sorted(BENCHMARK_WIDTHS)


@pytest.mark.parametrize("decoding,prompt", [
    (31, 480),  # a full fleet: 512 - 31 = 481 live tokens' room, 60 whole tiles
    (16, 496), (1, 504),
    (0, 512),  # a cold start: every place of the axis, not the 768 tiles' worth
])
def test_plan_at_mimos_shapes_gives_a_decode_row_one_token(decoding, prompt):
    cfg, slots = cell_config("mimo-v2.5-7l")
    s0 = _sched()
    s = TokenBudgetScheduler(
        s0.classes, "standard", step_width(cfg, slots, TILE), TILE, slots,
        live_width=live_width(cfg, slots, TILE))
    assert (s.width, s.live_width) == (768, 512)
    cls = s.classes["standard"]
    jobs = [_job(cls, tail=2800, enqueued=1.0 + i, slot=decoding + i)
            for i in range(slots - decoding)]
    plan = s.plan(decoding, jobs, now=2.0, n_decode_tokens=decoding)
    assert sum(n for _, n in plan) == prompt == (512 - decoding) // TILE * TILE
    assert decoding + prompt <= s.live_width
    assert decoding + sum(-(-n // TILE) for _, n in plan) <= s.width // TILE
    # the tile layout alone (the parent's plan) left 264 beside 31 rows
    tiles = TokenBudgetScheduler(s0.classes, "standard", 512, TILE, slots)
    assert sum(n for _, n in tiles.plan(decoding, jobs, now=2.0)) == \
        512 - decoding * TILE


def _live_sched(slots=64, live=320):
    s = _sched(width=slots * TILE + 128, n_slots=slots)
    return TokenBudgetScheduler(s.classes, "standard", s.width, TILE, slots,
                                live_width=live)


@pytest.mark.parametrize("decoding,spec_k,callers", [
    (0, 0, 128),  # a cold start: every tile is free, 640 tokens would fit
    (20, 0, 44), (45, 0, 19), (46, 0, 18),  # the tiles bind from 46 rows up
    (63, 0, 1),  # a full fleet
    (24, 3, 40),  # verify rows: 1 + K live tokens a row
    (40, 6, 24),  # ... whose live tokens alone nearly fill the axis
])
def test_plan_never_plans_more_live_tokens_than_the_axis_holds(
        decoding, spec_k, callers):
    s = _live_sched()
    cls = s.classes["standard"]
    assert (s.width, s.live_width) == (640, 320)
    k = s.spec_draft_len(spec_k, decoding, 0, jobs_pending=True) if spec_k else 0
    assert k <= spec_k and (k > 0 or not spec_k or decoding * 2 > 312)
    tiles = decoding * -(-(1 + k) // TILE)
    tokens = decoding * (1 + k)
    jobs = [_job(cls, tail=64 + (7 * i) % 65, enqueued=1.0 + i, slot=decoding + i)
            for i in range(min(callers, 64 - decoding))]
    plan = s.plan(tiles, jobs, now=200.0, n_decode_tokens=tokens)
    prompt = sum(n for _, n in plan)
    assert tokens + prompt <= s.live_width
    assert tiles + sum(-(-n // TILE) for _, n in plan) <= s.width // TILE
    # no token of room is left idle beyond a tile, and the oldest job moves
    room = min(s.live_width - tokens, (s.width // TILE - tiles) * TILE)
    assert prompt > room - 2 * TILE or prompt == sum(len(j.ids) for j in jobs)
    assert plan[0][0] is jobs[0]


def test_plan_without_a_live_width_fills_every_tile_as_before():
    s = _sched(width=640, n_slots=64)
    assert s.live_width == s.width
    cls = s.classes["standard"]
    jobs = [_job(cls, tail=1000, enqueued=1.0 + i, slot=i) for i in range(10)]
    assert sum(n for _, n in s.plan(0, jobs, now=2.0)) == 640
    assert sum(n for _, n in s.plan(0, jobs, now=2.0, n_decode_tokens=0)) == 640
    assert s.spec_draft_len(7, 60, 3, jobs_pending=True) == 7


def test_budget_slicing_reserves_decode_rows():
    s = _sched(width=64, n_slots=4)  # 8 tiles
    cls = s.classes["standard"]
    jobs = [_job(cls, tail=200, enqueued=1.0)]
    # 3 decoding slots -> 5 tiles = 40 tokens of prefill budget
    plan = s.plan(3, jobs, now=1.0)
    assert plan == [(jobs[0], 40)]
    # full fleet decoding is impossible WITH a pending job (a job holds a
    # slot), but the planner still never over-fills the launch
    plan = s.plan(7, jobs, now=1.0)
    assert plan == [(jobs[0], 8)]


def test_final_chunk_is_partial_not_padded():
    s = _sched(width=64, n_slots=4)
    cls = s.classes["standard"]
    jobs = [_job(cls, tail=13, enqueued=1.0)]
    plan = s.plan(0, jobs, now=1.0)
    assert plan == [(jobs[0], 13)]  # the tail itself, not a tile multiple


def test_class_apportionment_follows_weight_and_urgency():
    s = _sched(width=272, n_slots=4)  # 34 tiles
    inter, batch = s.classes["interactive"], s.classes["batch"]
    ji = _job(inter, tail=400, enqueued=100.0, slot=0)
    jb = _job(batch, tail=400, enqueued=100.0, slot=1)
    plan = dict(
        (id(j), n) for j, n in s.plan(0, [jb, ji], now=100.2)
    )
    # same wait: interactive's weight 4 (and tighter TTFT target ->
    # higher urgency) must out-apportion batch's weight 1
    assert plan[id(ji)] > plan[id(jb)]
    # a batch job that has waited far past ITS OWN 30s target gains
    # urgency and claws budget back
    jb_old = _job(batch, tail=400, enqueued=0.0, slot=1)
    plan2 = dict(
        (id(j), n) for j, n in s.plan(0, [jb_old, ji], now=100.2)
    )
    assert plan2[id(jb_old)] > plan[id(jb)]


def test_starvation_freedom_all_jobs_complete():
    """Many jobs, tiny budget: every job finishes within a bounded number
    of planned steps — the oldest job always progresses."""
    s = _sched(width=48, n_slots=4)  # 6 tiles; 4 decoding -> 2 prefill
    inter, batch = s.classes["interactive"], s.classes["batch"]
    jobs = [
        _job(batch, tail=64, enqueued=0.0, slot=0),
        _job(inter, tail=64, enqueued=0.1, slot=1),
        _job(inter, tail=64, enqueued=0.2, slot=2),
    ]
    pending = list(jobs)
    steps = 0
    while pending and steps < 100:
        for job, n in s.plan(4 - len(pending), pending, now=1.0 + steps):
            job.done += n
        pending = [j for j in pending if j.remaining > 0]
        steps += 1
    assert not pending, [(j.cls.name, j.remaining) for j in pending]
    assert steps <= 30  # 192 tokens at >= 16/step, with slack


def test_decode_pressure_halves_prefill_budget():
    s = _sched(width=96, n_slots=4)  # 12 tiles
    cls = s.classes["standard"]
    jobs = [_job(cls, tail=400, enqueued=1.0)]
    full = s.plan(2, jobs, now=1.0)[0][1]
    # report TPOT over the standard class's target, with standard decoding
    s.observe("standard", ttft_s=0.1, tpot_s=cls.tpot_target_s * 3)
    throttled = s.plan(2, jobs, active_classes={"standard"}, now=1.0)[0][1]
    assert throttled == full // 2
    # pressure on a class with NO active decode rows must not throttle
    unrelated = s.plan(2, jobs, active_classes=set(), now=1.0)[0][1]
    assert unrelated == full


def test_admission_control_shed_and_class_retry_after():
    s = _sched()
    inter, batch = s.classes["interactive"], s.classes["batch"]
    # no observed data: never shed on a guess
    assert not s.should_shed(inter, class_depth=50)
    # feedback: ~0.4s per interactive request -> depth 10 drains in ~4s,
    # past SHED_GRACE x 0.5s target
    for _ in range(4):
        s.observe("interactive", ttft_s=0.4, tpot_s=0.05)
    assert s.should_shed(inter, class_depth=10)
    assert not s.should_shed(inter, class_depth=2)  # tiny backlog: noise
    assert s.drain_estimate_s(inter, 10) > SHED_GRACE * inter.ttft_target_s
    # non-sheddable classes only queue, however deep
    for _ in range(4):
        s.observe("batch", ttft_s=5.0, tpot_s=1.0)
    assert not s.should_shed(batch, class_depth=50)
    # Retry-After is CLASS-local: same global state, different hints
    assert s.retry_after_s(inter, 10) == 4  # 10 x 0.4s
    assert s.retry_after_s(batch, 2) == 10  # 2 x 5.0s
    assert s.retry_after_s(inter, 0) == 1  # floor


def test_parse_slo_classes_validation():
    classes = parse_slo_classes(EngineConfig())
    assert EngineConfig().slo_default_class in classes
    with pytest.raises(ValueError):
        parse_slo_classes(EngineConfig(slo_default_class="nope"))
    with pytest.raises(ValueError):
        parse_slo_classes(
            EngineConfig(slo_classes=(("bad", -1.0, 0.1, 1.0, True),))
        )


# -- engine level -------------------------------------------------------------

SERVE_CFG = dict(dtype="float32", eos_token_id=-1, max_seq_len=512)


@pytest.fixture(scope="module")
def setup():
    cfg = get_model_config("test-llama-tiny", **SERVE_CFG)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _cont(cfg, params, chunked, **kw):
    ecfg = dict(
        prefix_cache_entries=4, chunked_prefill=chunked,
        step_token_budget=64, prefill_buckets=(64, 128, 256),
    )
    ecfg.update(kw.pop("engine_cfg", {}))
    eng = InferenceEngine(cfg, params=params, engine_cfg=EngineConfig(**ecfg))
    args = dict(n_slots=4, chunk_steps=8, slot_max_seq=512,
                kv_pool_blocks=120, kv_block_size=16,
                restart_backoff_s=0.01)
    args.update(kw)
    return ContinuousEngine(eng, **args)


def test_chunked_greedy_identical_to_whole_prefill(setup):
    """The acceptance bar: mixed-launch chunked prefill serves the exact
    greedy token streams the whole-prefill admission flow serves — warm
    prefix reuse and a threaded mixed fleet included."""
    cfg, params = setup
    shared = " ".join(f"ctx{j}" for j in range(24))
    prompts = [
        "the quick brown fox jumps over the lazy dog",
        shared + " question one",
        shared + " question two",
        "short",
        "y " * 150,
    ]
    outs = {}
    for chunked in (False, True):
        cont = _cont(cfg, params, chunked)
        try:
            warm = [
                cont.submit(p, max_tokens=10, greedy=True, chat=False)
                for p in prompts
            ]
            wave = [None] * len(prompts)

            def run(i, c=cont, w=wave):
                w[i] = c.submit(prompts[i], max_tokens=10, greedy=True,
                                chat=False)

            ts = [
                threading.Thread(target=run, args=(i,))
                for i in range(len(prompts))
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            st = cont.stats()
        finally:
            cont.close()
        assert all(
            r["status"] == "success" for r in warm + wave
        ), (chunked, warm, wave)
        assert st.get("scheduler", {}).get("chunked_prefill", False) is chunked
        outs[chunked] = [r["response"] for r in warm + wave]
    assert outs[True] == outs[False]


def test_long_prompt_interleaves_with_decode(setup):
    """The tentpole behavior: a long prompt admitted while the fleet
    decodes lands as PREFILL CHUNKS interleaved with decode rows in the
    same launches — decode never stalls for the whole prefill."""
    cfg, params = setup
    cont = _cont(cfg, params, True, engine_cfg={"prefix_cache_entries": 0})
    eng = cont.engine
    try:
        cont.submit("warm", max_tokens=4, greedy=True, chat=False)
        outs = [None] * 3

        def decoder(i):
            outs[i] = cont.submit(
                f"short prompt {i}", max_tokens=250, greedy=True, chat=False
            )

        def longp():
            time.sleep(0.1)
            outs[2] = cont.submit(
                "y " * 150, max_tokens=6, greedy=True, chat=False
            )

        ts = [
            threading.Thread(target=decoder, args=(i,)) for i in range(2)
        ] + [threading.Thread(target=longp)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap = eng.metrics.snapshot()
    finally:
        cont.close()
    assert all(r and r["status"] == "success" for r in outs), outs

    def series(name):
        return {
            tuple(sorted(s["labels"].items())): s["value"]
            for s in snap.get(name, {}).get("series", [])
        }

    toks = series("dli_sched_step_tokens_total")
    # BOTH kinds rode scheduler launches: decode advanced during prefill
    assert toks.get((("kind", "decode"),), 0) > 0
    assert toks.get((("kind", "prefill"),), 0) > 0
    assert series("dli_sched_prefill_chunks_total").get((), 0) >= 4
    assert series("dli_sched_decode_rows_total").get((), 0) > 0
    launches = series("dli_ragged_launches_total")
    assert launches.get((("phase", "mixed"),), 0) > 0
    # the pool frees fully once the fleet drains (chunked scatter leaks
    # no blocks)
    assert cont._alloc.outstanding == 0
    assert cont._alloc.free_blocks == cont._alloc.n_blocks - 1


def test_streaming_through_chunked_path(setup):
    """stream() rides the chunked scheduler unchanged: deltas as chunks
    land, final envelope concatenates exactly."""
    cfg, params = setup
    cont = _cont(cfg, params, True)
    try:
        events = list(cont.stream(
            "stream me please", max_tokens=12, greedy=True, chat=False
        ))
    finally:
        cont.close()
    final = events[-1]
    assert final.get("done") and final["status"] == "success"
    joined = "".join(e.get("delta", "") for e in events[:-1])
    assert joined == final["response"]


@pytest.mark.parametrize("slots,asked,held", [(2, None, 64), (40, None, 80), (2, 3, 3)],
                         ids=["small-fleet", "large-fleet", "asked"])
def test_the_waiting_room_holds_a_fleets_worth_where_none_is_asked(
        setup, slots, asked, held):
    """A closed loop of twice as many callers as slots is never refused: the
    default queue is the larger of 64 and twice the slots."""
    cfg, params = setup
    cont = _cont(cfg, params, True, n_slots=slots, max_queue=asked)
    try:
        assert cont.max_queue == held
    finally:
        cont.close()


def test_slo_class_envelope_and_shed(setup):
    """slo_class flows end to end (resolved, echoed) and queue-full 429s
    carry a CLASS-derived Retry-After, not a global-depth one."""
    cfg, params = setup
    cont = _cont(cfg, params, True, max_queue=3, n_slots=2,
                 kv_pool_blocks=70)
    try:
        r = cont.submit("hello", max_tokens=4, greedy=True, chat=False,
                        slo_class="interactive")
        assert r["status"] == "success" and r["slo_class"] == "interactive"
        # unknown classes fall back to the default (the serving edge
        # 400s unknown names before they reach the engine)
        r = cont.submit("hello again", max_tokens=4, greedy=True,
                        chat=False, slo_class="not-a-class")
        assert r["slo_class"] == cont._sched.default_name
        # wedge the worker so the queue fills deterministically: pause by
        # holding the queue full of batch-class requests
        with cont._cv:
            for i in range(3):
                q = _Request(f"fill {i}", dict(max_tokens=4, greedy=True,
                                               chat=False))
                q.slo = "batch"
                cont._queue.append(q)
            cont._note_queue_locked()
        shed = cont._enqueue(_mk_req("shed me", slo="interactive"))
        assert shed is not None and shed["error_type"] == "overloaded"
        assert shed["slo_class"] == "interactive"
        # class-local estimate: 0 interactive requests queued ahead ->
        # floor hint, NOT the batch backlog's
        assert shed["retry_after_s"] == 1
        shed_b = cont._enqueue(_mk_req("shed batch", slo="batch"))
        assert shed_b is not None
        assert shed_b["retry_after_s"] >= shed["retry_after_s"]
        with cont._cv:
            cont._queue.clear()
            cont._note_queue_locked()
    finally:
        cont.close()


def _mk_req(prompt, slo=None):
    req = _Request(prompt, dict(max_tokens=4, greedy=True, chat=False))
    req.slo = slo
    return req


def test_slo_over_target_shed(setup):
    """A sheddable class whose drain estimate overruns its TTFT target is
    refused at enqueue with the class drain estimate as Retry-After."""
    cfg, params = setup
    cont = _cont(cfg, params, True, max_queue=64)
    try:
        # feedback: interactive requests observed at ~1s TTFT
        for _ in range(4):
            cont._sched.observe("interactive", ttft_s=1.0, tpot_s=0.05)
        with cont._cv:
            for i in range(6):
                q = _Request(f"fill {i}", dict(max_tokens=4, greedy=True,
                                               chat=False))
                q.slo = "interactive"
                cont._queue.append(q)
            cont._note_queue_locked()
        shed = cont._enqueue(_mk_req("over target", slo="interactive"))
        assert shed is not None and shed["error_type"] == "overloaded"
        assert "TTFT target" in shed["error"]
        assert shed["retry_after_s"] == 6  # 6 queued x 1.0s EWMA
        # batch is non-sheddable: same depth, still queues
        for _ in range(4):
            cont._sched.observe("batch", ttft_s=1.0, tpot_s=0.5)
        with cont._cv:
            for q in cont._queue:
                q.slo = "batch"
            cont._note_queue_locked()
        ok = cont._enqueue(_mk_req("bulk", slo="batch"))
        assert ok is None
        with cont._cv:
            cont._queue.clear()
            cont._note_queue_locked()
    finally:
        cont.close()


def test_slo_queue_depth_gauge(setup):
    cfg, params = setup
    cont = _cont(cfg, params, True)
    eng = cont.engine
    try:
        cont.submit("hello", max_tokens=4, greedy=True, chat=False,
                    slo_class="batch")
        snap = eng.metrics.snapshot()
    finally:
        cont.close()
    series = {
        tuple(sorted(s["labels"].items())): s["value"]
        for s in snap.get("dli_slo_queue_depth", {}).get("series", [])
    }
    # every configured class exposes a series (schema-stable scrape);
    # the anonymous tenant "" carries untagged traffic
    for name in ("interactive", "standard", "batch"):
        assert (("slo_class", name), ("tenant", "")) in series, series


# -- serving surface ----------------------------------------------------------

def _post(port, path, payload):
    import json
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_slo_class_http_surface():
    """slo_class rides /generate and the OpenAI routes: accepted + echoed
    for configured classes, 400 for unknown names on both surfaces."""
    from distributed_llm_inference_tpu.serving.server import InferenceServer

    cfg = get_model_config("test-llama-tiny")
    eng = InferenceEngine(
        cfg, engine_cfg=EngineConfig(prefill_buckets=(32, 64))
    )
    server = InferenceServer(eng, host="127.0.0.1", port=0)
    server.start()
    try:
        code, r = _post(server.port, "/generate", {
            "prompt": "hi there", "max_tokens": 4,
            "slo_class": "interactive",
        })
        assert code == 200 and r["slo_class"] == "interactive"
        code, r = _post(server.port, "/generate", {
            "prompt": "hi there", "max_tokens": 4, "slo_class": "nope",
        })
        assert code == 400 and "slo_class" in r["error"]
        code, r = _post(server.port, "/v1/completions", {
            "model": cfg.name, "prompt": "hi", "max_tokens": 4,
            "slo_class": "batch",
        })
        assert code == 200, r
        code, r = _post(server.port, "/v1/chat/completions", {
            "model": cfg.name, "max_tokens": 4, "slo_class": "nope",
            "messages": [{"role": "user", "content": "hi"}],
        })
        assert code == 400
        assert r["error"]["param"] == "slo_class"
    finally:
        server.shutdown()


# -- chaos leg: crash mid-chunked-prefill ------------------------------------

@pytest.fixture(autouse=True)
def _always_disarm():
    faults.disarm()
    yield
    faults.disarm()


@pytest.mark.chaos
def test_crash_mid_chunked_prefill_salvages_bit_identical(setup):
    """A scheduler crash while a long prompt is mid-chunked-prefill (some
    chunks already in the pool) salvages every in-flight request: the
    long prompt re-admits from its chunk-aligned progress record (zero —
    the rebuilt pool holds none of its chunks) and every greedy stream is
    bit-identical to a fault-free run."""
    cfg, params = setup
    long_prompt = "y " * 150
    prompts = ["the quick brown fox", long_prompt, "a lazy dog"]

    def serve(spec):
        faults.disarm()
        cont = _cont(cfg, params, True,
                     engine_cfg={"prefix_cache_entries": 0})
        try:
            if spec:
                faults.arm(spec)
            out = {}
            lock = threading.Lock()

            def run(i, p):
                time.sleep(0.05 * i)
                r = cont.submit(p, max_tokens=12, greedy=True, chat=False)
                with lock:
                    out[p] = r

            ts = [
                threading.Thread(target=run, args=(i, p))
                for i, p in enumerate(prompts)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
            restarts = cont.restarts_total
        finally:
            faults.disarm()
            cont.close()
        return out, restarts

    clean, _ = serve(None)
    assert all(r["status"] == "success" for r in clean.values()), clean
    # crash the SECOND prefill-chunk launch that carries the long prompt:
    # its first chunk already landed in the pool — a mid-prefill crash
    crashed, restarts = serve(
        [faults.FaultRule("prefill", "transient", on_call=2, match="y y y")]
    )
    assert restarts >= 1
    for p in prompts:
        assert crashed[p]["status"] == "success", crashed[p]
        assert crashed[p]["response"] == clean[p]["response"], p
    # NOTE: the long prompt re-admits with NO continuation tokens (its
    # chunk-aligned progress record resets with the rebuilt pool), so
    # the PR-5 `recovered` continuation flag deliberately stays off —
    # bit-identical output is the contract, asserted above


@pytest.mark.chaos
def test_crash_at_mixed_decode_launch_salvages(setup):
    """Same bar for a crash at the mixed launch itself (decode rows in
    flight): salvage + continuation prefill, greedy bit-identical."""
    cfg, params = setup
    prompts = ["the quick brown fox", "jumps over the moon"]

    def serve(spec):
        faults.disarm()
        cont = _cont(cfg, params, True,
                     engine_cfg={"prefix_cache_entries": 0})
        try:
            if spec:
                faults.arm(spec)
            return {
                p: cont.submit(p, max_tokens=10, greedy=True, chat=False)
                for p in prompts
            }, cont.restarts_total
        finally:
            faults.disarm()
            cont.close()

    clean, _ = serve(None)
    crashed, restarts = serve("decode_launch:transient:on=3")
    assert restarts >= 1
    for p in prompts:
        assert crashed[p]["status"] == "success", crashed[p]
        assert crashed[p]["response"] == clean[p]["response"], p


# -- release by the host position model (ISSUE 31) ---------------------------
# A row whose budget ends inside a launch already dispatched gives its slot
# (and its blocks) back at that dispatch; the fetch only finalizes it. The
# probe below reads the worker's own seams, on the worker thread.

K_STEPS = 8


def _probe(cont, monkeypatch=None):
    """Log launches, fetches, slot releases and job starts with the launch
    counter each saw; with `monkeypatch`, every kill_slot too."""
    log = {"launch": [], "fetch": [], "release": [], "start": [], "kill": []}
    if monkeypatch is not None:
        kill = G.kill_slot

        def kill_slot(state, slot):
            log["kill"].append(int(slot))
            return kill(state, slot)

        monkeypatch.setattr(G, "kill_slot", kill_slot)
    lr, ft = cont._launch_record, cont._fetch
    fr, sj = cont._free_slot_resources, cont._start_job

    def launch_record(*a, **k):
        rec = lr(*a, **k)
        log["launch"].append(dict(rec))
        return rec

    def fetch(dev, t_launch, rec):
        out = ft(dev, t_launch, rec)
        log["fetch"].append(rec["seq"])
        return out

    def free(req, by="fetch"):
        owned = req.slot is not None and cont._assignment[req.slot] is req
        fr(req, by=by)
        if owned:
            log["release"].append((by, req.slot, cont._launch_seq, req))

    def start_job(req, slot):
        log["start"].append((
            slot, cont._launch_seq, max(log["fetch"], default=0), req,
            cont._retiring[slot],
        ))
        return sj(req, slot)

    cont._launch_record, cont._fetch = launch_record, fetch
    cont._free_slot_resources, cont._start_job = free, start_job
    return log


def _serve_together(cont, items):
    """Enqueue every (prompt, kwargs) in list order while the worker
    cannot pop one (the queue's lock is re-entrant), then wait for all:
    which request waits behind which does not depend on the machine."""
    reqs = [
        _Request(p, dict(dict(max_tokens=21, greedy=True, chat=False), **kw))
        for p, kw in items
    ]
    with cont._cv:
        for r in reqs:
            assert cont._enqueue(r) is None
    for r in reqs:
        assert r.done.wait(timeout=300), r.prompt
    return {r.prompt: r.result for r in reqs}


def _serve_all(cont, prompts):
    return _serve_together(cont, [(p, {}) for p in prompts])


def _series(eng, name):
    return {
        tuple(sorted(s["labels"].items())): s
        for s in eng.metrics.snapshot().get(name, {}).get("series", [])
    }


def _released(eng, by):
    s = _series(eng, "dli_slot_release_total").get((("by", by),))
    return 0 if s is None else s["value"]


RELET_PROMPTS = [f"prompt number {i} says hello" for i in range(6)]


@pytest.mark.parametrize("shadow", [False, True], ids=["cold", "shadow"])
def test_budget_ended_slot_is_relet_before_its_last_fetch(setup, shadow):
    """Six requests for two slots, every row ending by its budget: the next
    tenant's first prefill chunk rides the launch right after the one in
    which the old row ended, while that launch (and, under lag 2, the one
    before it) is still unfetched. Released by the fetch, as until PR 31,
    it could start no earlier than two launches later. Tokens are the
    solo engine's."""
    cfg, params = setup
    cont = _cont(cfg, params, True, n_slots=2, chunk_steps=K_STEPS,
                 kv_shadow=shadow)
    log = _probe(cont)
    try:
        out = _serve_all(cont, RELET_PROMPTS)
        eng = cont.engine
        assert _released(eng, "model") == 6 and _released(eng, "fetch") == 0
        turn = _series(eng, "dli_slot_turnover_steps")[()]
    finally:
        cont.close()
    assert (cont._shadow is not None) is shadow
    phases = {r["seq"]: r for r in log["launch"]}
    relets = 0
    for slot, seq_at_start, fetched, req, retiring in log["start"]:
        before = [
            r for r in log["release"]
            if r[1] == slot and r[2] <= seq_at_start and r[3] is not req
        ]
        if not before:
            continue  # the slot's first tenant
        by, _, ended_in, old = before[-1]
        relets += 1
        assert by == "model" and retiring is old
        # no launch between the old row's last one and the re-let ...
        assert seq_at_start == ended_in
        # ... which is not fetched yet: the fetch-driven release needs it
        assert fetched < ended_in
        nxt = phases[ended_in + 1]
        assert nxt["phase"] == "mixed" and nxt["prefill_chunks"] >= 1
    assert relets == 4
    # observed where a request was waiting when the row ended: the four
    # re-lets, each a chunk's remainder at most
    assert turn["count"] == 4 and turn["sum"] <= 4 * (K_STEPS - 1)
    for p in RELET_PROMPTS:
        solo = cont.engine.generate(p, max_tokens=21, greedy=True, chat=False)
        assert out[p]["status"] == "success", out[p]
        assert out[p]["tokens_generated"] == 21
        assert out[p]["response"] == solo["response"], p
        assert out[p]["finish_reason"] == "length"


@pytest.mark.parametrize("how", ["eos", "stop", "eos_early"])
def test_row_that_ends_early_beside_its_budget_is_finalized_once(
        setup, how, monkeypatch):
    """One slot, a second request waiting. The first row ends by EOS, or
    by a stop sequence, inside the chunk in which its budget also ends: the
    model lets the slot again at that chunk's dispatch, and the fetch
    finalizes the retiring tenant once, with the tokens it had, while the
    new tenant decodes untouched. `eos_early` is the control: an EOS in the
    first chunk of a budget of 61 is the fetch's to find."""
    cfg, params = setup
    first, second = "a row that ends early", "the tenant after it"
    n = 61 if how == "eos_early" else 21
    ecfg = EngineConfig(prefill_buckets=(64, 128, 256))
    kw = {}
    if how == "stop":
        text = InferenceEngine(cfg, params=params, engine_cfg=ecfg).generate(
            first, max_tokens=n, greedy=True, chat=False)["response"]
        # a stop string whose first occurrence is at the text's end
        kw["stop"] = [next(
            text[i:] for i in range(len(text) - 1, 0, -1)
            if text.find(text[i:]) == i
        )]
    cont0 = _cont(cfg, params, True, n_slots=1, chunk_steps=K_STEPS)
    try:
        r = _Request(first, dict(max_tokens=n, greedy=True, chat=False))
        assert cont0._enqueue(r) is None
        r.done.wait(timeout=120)
        toks = [r.first_id] + list(r.tokens)
    finally:
        cont0.close()
    assert len(toks) == n
    if how != "stop":
        # generated index 18 is in the last chunk (1 + 8 + 8 + 4), index 5
        # in the first; the token must not occur earlier in the stream
        idx = 18 if how == "eos" else 5
        idx = next(i for i in range(idx, 0, -1) if toks[i] not in toks[:i])
        assert (idx > 16) if how == "eos" else (idx <= 8)
        cfg = cfg.replace(eos_token_id=int(toks[idx]))
    solo_eng = InferenceEngine(cfg, params=params, engine_cfg=ecfg)
    solo = {
        first: solo_eng.generate(first, max_tokens=n, greedy=True,
                                 chat=False, **kw),
        second: solo_eng.generate(second, max_tokens=21, greedy=True,
                                  chat=False),
    }
    cont = _cont(cfg, params, True, n_slots=1, chunk_steps=K_STEPS)
    log = _probe(cont, monkeypatch)
    finals = []
    push = cont._push_final

    def push_final(req):
        finals.append(req.prompt)
        push(req)

    cont._push_final = push_final
    try:
        out = _serve_together(
            cont, [(first, dict(kw, max_tokens=n)), (second, {})])
    finally:
        cont.close()
    assert sorted(finals) == sorted([first, second])  # once each
    for p in (first, second):
        assert out[p]["status"] == "success", out[p]
        assert out[p]["response"] == solo[p]["response"], (how, p)
        assert out[p]["tokens_generated"] == solo[p]["tokens_generated"]
    if how == "stop":
        assert out[first]["stopped"] is True
    else:
        assert out[first]["tokens_generated"] < n
    assert out[first]["finish_reason"] == "stop"
    by_first = [r[0] for r in log["release"] if r[3].prompt == first]
    assert by_first == (["fetch"] if how == "eos_early" else ["model"])
    assert not log["kill"]


@pytest.mark.parametrize("how", ["cancel", "deadline_ms", "deadline_s"])
def test_killing_a_retiring_tenant_never_kills_the_new_one(
        setup, how, monkeypatch):
    """The client of a retiring tenant goes away (or its deadline passes)
    after its slot was let again and before its last launch is fetched:
    it gets the envelope it always got, and the slot, which is the next
    tenant's by now, is never killed."""
    cfg, params = setup
    first, second = "the one that is cancelled", "the tenant after it"
    extra = {"request_deadline_s": 600.0} if how == "deadline_s" else {}
    cont = _cont(cfg, params, True, n_slots=1, chunk_steps=K_STEPS,
                 engine_cfg=extra)
    log = _probe(cont, monkeypatch)
    free = cont._free_slot_resources

    def free_then_fail(req, by="fetch"):
        free(req, by=by)
        if by == "model" and req.prompt == first:
            # what the client's disconnect, or the clock, would do just
            # now: the next fetch (an earlier chunk's) finds it
            if how == "cancel":
                req.cancelled = True
            elif how == "deadline_ms":
                req.deadline_at = time.time() - 1.0
            else:
                req.t_start -= 1000.0

    cont._free_slot_resources = free_then_fail
    try:
        out = _serve_all(cont, [first, second])
        assert cont._retiring == [None] and cont._assignment == [None]
    finally:
        cont.close()
    assert [r[0] for r in log["release"]] == ["model", "model"]
    assert not log["kill"]
    assert out[first]["status"] == "failed"
    assert out[first]["error_type"] == {
        "cancel": "cancelled", "deadline_ms": "deadline_exceeded",
        "deadline_s": "timeout",
    }[how]
    solo = cont.engine.generate(second, max_tokens=21, greedy=True,
                                chat=False)
    assert out[second]["status"] == "success", out[second]
    assert out[second]["tokens_generated"] == 21
    assert out[second]["response"] == solo["response"]


@pytest.mark.parametrize("shadow", [False, True], ids=["cold", "shadow"])
def test_pool_is_whole_after_model_releases_with_the_prefix_index_on(
        setup, shadow):
    """Blocks go back at the re-let (or, under a shadow store, when the
    retiring tenant is finalized); what the prefix index caches stays
    under its own references. After a drained run of prompts that share
    a head: free + cached is the pool, and evicting the index gives back
    every block the run began with."""
    cfg, params = setup
    head = " ".join(f"ctx{j}" for j in range(24))
    prompts = [f"{head} question {i}" for i in range(6)] + ["short", "x y z"]
    cont = _cont(cfg, params, True, n_slots=2, chunk_steps=K_STEPS,
                 kv_shadow=shadow, kv_pool_blocks=40)
    try:
        start = cont._alloc.free_blocks
        assert start == cont._alloc.n_blocks - 1  # less the trash block
        out = _serve_all(cont, prompts)
        assert all(r["status"] == "success" for r in out.values()), out
        assert any(r.get("prefix_cached_tokens") for r in out.values())
        assert _released(cont.engine, "model") == len(prompts)
        st = cont.stats()["paged"]
        assert st["cached_blocks"] > 0
        assert st["free_blocks"] + st["cached_blocks"] == start
        assert cont._alloc.outstanding == st["cached_blocks"]
        cont._bpx.evict(start)
        assert cont._alloc.free_blocks == start
        assert cont._alloc.outstanding == 0
    finally:
        cont.close()
    for p in prompts:
        solo = cont.engine.generate(p, max_tokens=21, greedy=True, chat=False)
        assert out[p]["response"] == solo["response"], p


@pytest.mark.chaos
@pytest.mark.parametrize("shadow", [False, True], ids=["cold", "shadow"])
def test_crash_between_the_relet_and_the_old_fetch_salvages_both(
        setup, shadow):
    """The fetch that follows an early re-let dies: the retiring tenant
    (no slot, its last launches unfetched) and the tenant that took its
    slot are both salvaged, bit-identical to a clean run; the re-let is
    the mutation the suspect set counts (the new tenant is struck, the
    old one, vindicated long ago, is not)."""
    cfg, params = setup
    first, second = "the one that retires", "the tenant after it"

    def serve(crash):
        faults.disarm()
        cont = _cont(cfg, params, True, n_slots=1, chunk_steps=K_STEPS,
                     kv_shadow=shadow,
                     engine_cfg={"prefix_cache_entries": 4 if shadow else 0})
        seen = {}
        start = cont._start_job

        def start_job(req, slot):
            job = start(req, slot)
            if crash and req.prompt == second and not seen:
                seen["reqs"] = (cont._retiring[slot], req)
                faults.arm([faults.FaultRule("fetch", "transient",
                                             on_call=1)])
            return job

        cont._start_job = start_job
        try:
            out = _serve_all(cont, [first, second])
            pool = cont.stats()["paged"]
            return out, cont.restarts_total, seen, pool
        finally:
            faults.disarm()
            cont.close()

    clean, restarts, _, _ = serve(False)
    assert restarts == 0
    crashed, restarts, seen, pool = serve(True)
    assert restarts == 1
    old, new = seen["reqs"]
    assert old is not None and old.prompt == first  # it was retiring
    assert (old.strikes, new.strikes) == (0, 1)
    for p in (first, second):
        assert crashed[p]["status"] == "success", crashed[p]
        assert crashed[p]["tokens_generated"] == 21
        assert crashed[p]["response"] == clean[p]["response"], p
    assert crashed[first].get("recovered") is True
    assert pool["free_blocks"] + pool["cached_blocks"] == pool["pool_blocks"] - 1


@pytest.mark.parametrize("fleet", ["whole_prefill", "speculating"])
def test_rows_the_model_does_not_bound_leave_by_the_fetch(setup, fleet):
    """The whole-prefill loop has no release by the model; a speculating
    fleet has it only for a slot with no verify row unfetched. Either way
    every slot release is counted once, and the tokens are the solo
    engine's."""
    cfg, params = setup
    spec = fleet == "speculating"
    cont = _cont(
        cfg, params, spec, n_slots=2, chunk_steps=K_STEPS,
        engine_cfg=dict(spec_decode=True, spec_draft_len=4) if spec else {},
    )
    prompts = ["ab ab ab ab ab ab ab ab", "the cat the cat the cat the",
               "one more prompt", "and a last one"]
    try:
        out = _serve_all(cont, prompts)
        eng = cont.engine
        by_model, by_fetch = _released(eng, "model"), _released(eng, "fetch")
        assert cont._retiring == [None, None]
    finally:
        cont.close()
    assert by_model + by_fetch == len(prompts)
    if not spec:
        assert by_model == 0
    for p in prompts:
        solo = cont.engine.generate(p, max_tokens=21, greedy=True, chat=False)
        assert out[p]["status"] == "success", out[p]
        assert out[p]["response"] == solo["response"], p


def test_snapshot_is_taken_on_the_host_before_the_transfer(monkeypatch):
    """A slot's table row is rewritten right after the launch that carries
    its last step is dispatched, and a host-to-device transfer may read
    its source after `jnp.array` has returned (on the CPU backend it does,
    under load: the rows that rode that launch then walked the trash
    block). `_snapshot` hands the device an array nothing else holds."""
    table = np.arange(12, dtype=np.int32).reshape(3, 4)
    handed = []
    real = jnp.asarray

    def asarray(a, *args, **kw):
        handed.append(a)
        return real(a, *args, **kw)

    monkeypatch.setattr(jnp, "asarray", asarray)
    dev = ContinuousEngine._snapshot(table)
    monkeypatch.undo()
    assert len(handed) == 1 and isinstance(handed[0], np.ndarray)
    assert not np.shares_memory(handed[0], table)
    table[:] = 0  # what _release_ended does to a row, a moment later
    assert np.asarray(dev).tolist() == np.arange(12).reshape(3, 4).tolist()
    # and under a busy device queue, many times over
    x = jnp.ones((256, 256))
    for _ in range(300):
        t = np.full((4, 32), 7, np.int32)
        x = x @ x / 256.0
        d = ContinuousEngine._snapshot(t)
        t[:] = 0
        assert int(np.asarray(d).sum()) == 7 * 128


# -- the derived launch width (engine/scheduler.step_width) --------------------

def _routed_fleet(budget):
    """test-mla-moe-tiny's fleet with every launch record kept."""
    from distributed_llm_inference_tpu import create_engine

    eng = create_engine(
        "test-mla-moe-tiny", seed=5, attn_impl="xla", dtype="float32",
        engine_cfg=EngineConfig(prefix_cache_entries=8, kv_shadow=False,
                                step_token_budget=budget))
    cont = ContinuousEngine(eng, n_slots=3, chunk_steps=4, slot_max_seq=128,
                            kv_pool_blocks=40, kv_block_size=16,
                            restart_backoff_s=0.01)
    records = []
    record = cont._launch_record
    cont._launch_record = lambda *a, **k: (
        records.append(record(*a, **k)) or records[-1])
    return cont, records


def _document_beside_a_decoding_row(cont, records):
    """A document asked cold; then, while another request decodes, asked
    again (a prefix hit: only the tail is prefilled, beside that row)."""
    doc = "a document of some length, asked about twice over " * 2
    ask = dict(greedy=True, chat=False)
    out = [cont.submit(doc + "?", max_tokens=9, **ask)]
    row, n0 = [], len(records)
    t = threading.Thread(target=lambda: row.append(
        cont.submit("a row that decodes", max_tokens=100, **ask)))
    t.start()
    deadline = time.time() + 120
    while True:  # the row's own prefill, then a launch that decodes it
        new = records[n0:]
        armed = [i for i, r in enumerate(new) if r["prefill_chunks"]]
        if armed and any(r["decode_rows"] for r in new[armed[0] + 1:]):
            break
        assert time.time() < deadline
        time.sleep(0.002)
    out.append(cont.submit(doc + "!", max_tokens=9, **ask))
    t.join(300)
    assert all(r["status"] == "success" for r in out + row), (out, row)
    return out + row


def test_the_derived_width_serves_the_tokens_a_16_token_budget_serves():
    """The width is a LAUNCH shape: a routed fleet at the derived 512 flat
    tokens gives the greedy tokens it gives at step_token_budget=16, through
    a prefix hit and beside a decoding row, and the two series count what
    the launch records say."""
    got = {}
    for budget in (16, None):
        cont, records = _routed_fleet(budget)
        try:
            res = _document_beside_a_decoding_row(cont, records)
            st = cont.stats()["scheduler"]
            snap = cont.engine.metrics.snapshot()
        finally:
            cont.close()
        assert res[1]["prefix_cached_tokens"] >= 4 * 16
        got[budget] = [r["response"] for r in res]
        mixed = [r for r in records if r["phase"] == "mixed"]
        width = 32 if budget else 512  # 16 clamps to 3 rows + a tile
        assert st["step_width"] == width
        assert all(r["tiles"] == width // TILE for r in mixed)
        # the hit's tail rode a launch that also carried the decoding row
        assert any(r["decode_rows"] and r["prefill_chunks"] for r in mixed)

        def series(name):
            return {tuple(sorted(s["labels"].items())): s["value"]
                    for s in snap[name]["series"]}

        assert series("dli_sched_step_width_tokens") == {(): width}
        # the record's tiles are the ones dli_ragged_tiles_total counts (a
        # chunked fleet launches no ingest program beside its mixed steps)
        tiles = series("dli_ragged_tiles_total")
        assert tiles[(("state", "live"),)] + tiles[(("state", "pad"),)] == sum(
            r["tiles"] for r in mixed)
        assert tiles[(("state", "live"),)] == sum(
            r["tiles_live"] for r in mixed) > 0
        if budget is None:  # a prompt's prefill is ONE launch, not four
            assert sum(r["prefill_chunks"] for r in mixed) == 3
    assert got[None] == got[16]


def test_the_engine_obeys_an_explicit_budget_and_derives_a_dense_one(setup):
    cfg, params = setup
    for budget, width in ((64, 64), (None, 128)):
        cont = _cont(cfg, params, True,
                     engine_cfg={"step_token_budget": budget})
        try:
            assert cont.stats()["scheduler"]["step_width"] == width
        finally:
            cont.close()
