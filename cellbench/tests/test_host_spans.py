"""Tests of what PR 24 adds to the yardstick, run on the CPU:

    python -m pytest cellbench/tests/test_host_spans.py -q

`harness/host_spans.py` and `tools/gaps.py` are checked against a recorded
fixture (the first 0.3 s of a traced `olmo2-chat` chip run of PR 24, cut by
`tools/cut_spans.py`, whose expected file is computed there by plain sorting
and scanning) and on a hand-made trace; each new per-layer reader on a
hand-made `Context`.
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))

from harness import host_spans, manifest, scrape, trace_reduce  # noqa: E402

FIXTURE = os.path.join(BENCH_DIR, "fixtures", "olmo2-chat.spans")
STEP_MODULES = {"mixed_step_ragged": 1, "decode_slots_paged": None}


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


# ---- a hand-made trace ------------------------------------------------------

def _xplane(tmp_path, device, spans):
    """device: [(line, name, start_us, dur_us)]; spans: [(name, start_us,
    dur_us, {stat: int | str})] -> the path of a .xplane.pb holding them
    (written by the fixture tool's own writer)."""
    import cut_spans
    from jax.profiler import ProfileData

    lines = {ln: [(n, s * 1000, d * 1000) for line, n, s, d in device if line == ln]
             for ln in ("XLA Modules", "XLA Ops")}
    text = cut_spans.xspace_text(
        "/device:TPU:0", lines, [(n, s * 1000, d * 1000, st) for n, s, d, st in spans], 0)
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace("\n".join(text)))
    return str(path)


def _hand_trace(tmp_path):
    """Three launches on the host (seq 7 chunk, 8 mixed, 9 mixed) and four
    step modules on the chip: the first module was dispatched before the
    trace began (no span), launch 9's ran after it ended (no module). Times
    in microseconds. Launch 7's module runs 1000-5000, launch 8's
    5100-6100; the chip is idle 0-1000 (inside the head, `fetch_wait`),
    5000-5100 (inside fetch.chunk seq 7), 6100-6400 (it begins inside
    fetch.mixed seq 8, which returns at 6110) and 6500-6700 (inside the
    fetch of seq 9, which outlasted the trace: only its marker is there)."""
    device = [
        ("XLA Modules", "jit_mixed_step_ragged(11)", 200, 700),   # before the trace: no span
        ("XLA Modules", "jit_decode_slots_paged(12)", 1000, 4000),
        ("XLA Modules", "jit_pack_chunk(13)", 5000, 1),
        ("XLA Modules", "jit_mixed_step_ragged(11)", 5100, 1000),
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 200, 700),
        ("XLA Ops", "%paged_flash_attend.2 = bf16[] custom-call()", 1000, 1500),
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 2500, 2500),
        ("XLA Ops", "%ragged_paged_attend.5 = bf16[] custom-call()", 5100, 400),
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 5500, 600),
        ("XLA Ops", "%fusion.9 = f32[] fusion()", 6400, 100),
        ("XLA Ops", "%fusion.9 = f32[] fusion()", 6700, 100),
    ]
    spans = [
        ("phase.distribute", 950, 30, {"prev": "fetch_wait"}),
        ("phase.plan", 980, 10, {"prev": "distribute"}),
        ("launch.chunk", 990, 20, {"prev": "plan", "seq": 7, "steps": 16, "kv_tokens": 4000,
                                   "kv_grid_tokens": 90000, "phase": "chunk"}),
        ("phase.plan", 1010, 10, {"prev": "dispatch"}),
        ("launch.mixed", 1020, 30, {"prev": "plan", "seq": 8, "steps": 1, "kv_tokens": 600,
                                    "kv_grid_tokens": 30000, "phase": "mixed"}),
        ("phase.plan", 1050, 10, {"prev": "dispatch"}),
        ("begin.fetch.chunk", 1060, 0, {"seq": 7}),
        ("fetch.chunk", 1060, 3990, {"prev": "plan", "seq": 7}),
        ("phase.distribute", 5050, 40, {"prev": "fetch_wait"}),
        ("launch.mixed", 5090, 20, {"prev": "plan", "seq": 9, "steps": 1, "kv_tokens": 700,
                                    "kv_grid_tokens": 30000, "phase": "mixed"}),
        ("begin.fetch.mixed", 5110, 0, {"seq": 8}),
        ("fetch.mixed", 5110, 1000, {"prev": "dispatch", "seq": 8}),
        ("phase.distribute", 6110, 200, {"prev": "fetch_wait"}),
        ("begin.fetch.mixed", 6310, 0, {"seq": 9}),
    ]
    return _xplane(tmp_path, device, spans)


def test_spans_are_read_with_their_stats_and_the_head_is_put_back(tmp_path):
    path = _hand_trace(tmp_path)
    spans = host_spans.read(path)
    assert [s[0] for s in spans][:3] == ["phase.distribute", "phase.plan", "launch.chunk"]
    assert spans[2][3]["seq"] == 7 and spans[2][3]["kv_tokens"] == 4000
    assert spans[2][1] == pytest.approx(990e-6) and spans[2][2] == pytest.approx(1010e-6)
    whole = host_spans.bounded(spans, 200e-6, 6800e-6)
    assert not any(s[0].startswith("begin.") for s in whole)
    assert whole[0][0] == "phase.fetch_wait" and whole[0][1:3] == (200e-6, spans[0][1])
    assert whole[-1][0] == "fetch.mixed" and whole[-1][3] == {"seq": 9, "tail": 1}
    assert whole[-1][1:3] == (pytest.approx(6310e-6), 6800e-6)
    assert host_spans.open_at(whole, 500e-6)[0] == "phase.fetch_wait"
    assert host_spans.open_at(whole, 5000e-6)[3]["seq"] == 7
    assert host_spans.open_at(whole, 7000e-6) is None and host_spans.open_at(whole, 100e-6) is None
    assert host_spans.covered(whole, 200e-6, 6800e-6) == pytest.approx((6800 - 200) * 1e-6)
    # a span recorded after the last marker: that wait ended inside the trace, no tail
    later = spans + [("phase.plan", 6320e-6, 6330e-6, {"prev": "distribute"})]
    assert host_spans.bounded(later, 200e-6, 6800e-6)[-1][0] == "phase.plan"
    assert host_spans.find(str(tmp_path)) == path and host_spans.find(str(tmp_path / "nope")) is None


def test_launches_join_their_modules_by_order_and_the_fetch(tmp_path):
    path = _hand_trace(tmp_path)
    chip = trace_reduce.read_planes(path)[0]
    joined = host_spans.join_launches(host_spans.read(path), chip["XLA Modules"], STEP_MODULES)
    assert [(st["seq"], round(s * 1e6), round(e * 1e6)) for st, s, e in joined] == [
        (7, 1000, 5000), (8, 5100, 6100)]
    # no spans (an older program): nothing joined, and the reader says None
    assert host_spans.join_launches([], chip["XLA Modules"], STEP_MODULES) == []


def test_gaps_names_the_span_open_when_each_gap_began(tmp_path):
    import gaps

    r = gaps.attribute(_hand_trace(tmp_path), min_us=50.0)
    assert [(round(g["start_ms"] * 1e3), round(g["us"]), g["span"]) for g in r["gaps"]] == [
        (700, 100, "phase.fetch_wait"),      # 900-1000, inside the head
        (4800, 100, "fetch.chunk"),          # 5000-5100
        (5900, 300, "fetch.mixed"),          # 6100-6400
        (6300, 200, "fetch.mixed"),          # 6500-6700, inside the tail
    ]
    assert [g["seq"] for g in r["gaps"][1:]] == [7, 8, 9]
    assert r["idle_owned_pct"] == pytest.approx(100.0)
    assert r["window_covered_pct"] == pytest.approx(100.0)


def test_attn_kv_roofline_counts_matched_launches_only(tmp_path):
    path = _hand_trace(tmp_path)

    class Ctx:
        trace_dir = str(tmp_path)
        config = {"hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
                  "num_hidden_layers": 3, "torch_dtype": "bfloat16",
                  "serving": {"trace": {"step_modules": STEP_MODULES,
                                        "attention_kernels": ["ragged_paged_attend",
                                                              "paged_flash_attend"]}}}
        peaks = {"hbm_bytes_per_s": 1e9}

    # launches 7 and 8: 4600 positions x 3 layers x 2 x 2 heads x 64 x 2 bytes
    # over the kernels' 1500 + 400 us inside their two modules
    least = 4600 * 3 * 2 * 2 * 64 * 2 / 1e9
    assert read("attn_kv_roofline", Ctx) == pytest.approx(100 * least / 1900e-6)
    Ctx.trace_dir = str(tmp_path / "nothing-here")
    assert read("attn_kv_roofline", Ctx) is None


# ---- the counter readers, on a hand-made Context ----------------------------

BEFORE = """
dli_worker_phase_seconds_total{phase="wait_work"} 1.0
dli_worker_phase_seconds_total{phase="fetch_wait"} 10.0
dli_worker_phase_seconds_total{phase="plan"} 0.5
dli_worker_phase_seconds_total{phase="dispatch"} 0.25
dli_worker_phase_seconds_total{phase="distribute"} 0.25
dli_ragged_launches_total{phase="mixed"} 10
dli_ragged_launches_total{phase="chunk"} 5
dli_ragged_launches_total{phase="prefill"} 3
dli_attn_kv_tokens_total{phase="mixed",state="attended"} 1000
dli_attn_kv_tokens_total{phase="mixed",state="walked"} 100000
dli_attn_kv_tokens_total{phase="chunk",state="attended"} 2000
dli_attn_kv_tokens_total{phase="chunk",state="walked"} 200000
dli_queue_wait_seconds_sum{queue="continuous"} 1.0
dli_queue_wait_seconds_count{queue="continuous"} 4
dli_prefill_seconds_sum{queue="continuous"} 6.0
dli_prefill_seconds_count{queue="continuous"} 4
dli_launch_steps_ahead_sum{phase="mixed"} 100
dli_launch_steps_ahead_count{phase="mixed"} 10
dli_launch_steps_ahead_sum{phase="chunk"} 999
dli_launch_steps_ahead_count{phase="chunk"} 5
"""
AFTER = """
dli_worker_phase_seconds_total{phase="wait_work"} 2.0
dli_worker_phase_seconds_total{phase="fetch_wait"} 55.0
dli_worker_phase_seconds_total{phase="plan"} 1.5
dli_worker_phase_seconds_total{phase="dispatch"} 0.75
dli_worker_phase_seconds_total{phase="distribute"} 1.25
dli_ragged_launches_total{phase="mixed"} 50
dli_ragged_launches_total{phase="chunk"} 45
dli_ragged_launches_total{phase="prefill"} 3
dli_attn_kv_tokens_total{phase="mixed",state="attended"} 4000
dli_attn_kv_tokens_total{phase="mixed",state="walked"} 160000
dli_attn_kv_tokens_total{phase="chunk",state="attended"} 11000
dli_attn_kv_tokens_total{phase="chunk",state="walked"} 380000
dli_queue_wait_seconds_sum{queue="continuous"} 3.0
dli_queue_wait_seconds_count{queue="continuous"} 14
dli_prefill_seconds_sum{queue="continuous"} 24.0
dli_prefill_seconds_count{queue="continuous"} 14
dli_launch_steps_ahead_sum{phase="mixed"} 900
dli_launch_steps_ahead_count{phase="mixed"} 50
dli_launch_steps_ahead_sum{phase="chunk"} 9999
dli_launch_steps_ahead_count{phase="chunk"} 45
"""


def test_counter_readers_on_a_hand_made_context():
    class Ctx:
        chunk_steps, window_s = 16, 50.0
        before, after = scrape.parse(BEFORE), scrape.parse(AFTER)

    steps = 40 + 16 * 40
    assert read("slot_wait_ms_mean", Ctx) == pytest.approx(200.0)
    assert read("prefill_ms_mean", Ctx) == pytest.approx(1800.0)
    assert read("steps_ahead_of_prefill_mean", Ctx) == pytest.approx(20.0)
    assert read("mixed_step_pct", Ctx) == pytest.approx(100 * 40 / steps)
    assert read("host_ms_per_step", Ctx) == pytest.approx(1e3 * (1.0 + 0.5 + 1.0) / steps)
    assert read("fetch_wait_pct", Ctx) == pytest.approx(90.0)
    assert read("attn_grid_live_pct", Ctx) == pytest.approx(100 * 12000 / 240000)


def test_readers_return_none_from_a_program_without_the_series():
    """The parent commit counts mixed launches only and has none of the new
    families: every new reader leaves its metric out, none raises."""
    class Ctx:
        chunk_steps, window_s, trace_dir = 16, 50.0, None
        before = scrape.parse('dli_ragged_launches_total{phase="mixed"} 10\n'
                              'dli_admission_wait_seconds_count{queue="continuous"} 3\n')
        after = scrape.parse('dli_ragged_launches_total{phase="mixed"} 41\n'
                             'dli_admission_wait_seconds_count{queue="continuous"} 9\n')
        config = {"serving": {"trace": {"step_modules": STEP_MODULES, "attention_kernels": []}}}

    for name in ("slot_wait_ms_mean", "prefill_ms_mean", "steps_ahead_of_prefill_mean",
                 "mixed_step_pct", "host_ms_per_step", "fetch_wait_pct", "attn_grid_live_pct",
                 "attn_kv_roofline"):
        assert read(name, Ctx) is None, name


def test_the_manifest_lists_the_new_metrics_last_and_nothing_else_changed():
    man = manifest.load_json(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    names = [m["name"] for m in man["per_layer"]]
    new = ["slot_wait_ms_mean", "prefill_ms_mean", "steps_ahead_of_prefill_mean",
           "mixed_step_pct", "host_ms_per_step", "fetch_wait_pct", "attn_grid_live_pct",
           "attn_kv_roofline"]
    assert names[-8:] == new and names[:14][-1] == "device_idle_pct"
    for m in man["per_layer"][-8:]:
        assert m["moves"] == "tpot_ms_p50" and set(m["workloads"]) <= {
            w["name"] for w in man["workloads"]}
        assert hasattr(manifest.load_module("layer_metrics", m["name"]), "read")


# ---- the recorded fixture ---------------------------------------------------

@pytest.mark.skipif(not os.path.isfile(FIXTURE + ".xplane.pb"), reason="no recorded fixture")
def test_host_spans_and_gaps_on_the_recorded_fixture():
    import gaps

    want = manifest.load_json(FIXTURE + ".expected.json")
    path = FIXTURE + ".xplane.pb"
    spans = host_spans.read(path)
    assert len(spans) == want["spans"] + want["markers"]
    spans = [s for s in spans if not s[0].startswith("begin.")]
    assert sorted({s[0] for s in spans}) == want["span_names"]
    first = want["first_span"]
    assert spans[0][0] == first["name"] and spans[0][1] == pytest.approx(first["start_s"], rel=1e-9)
    assert {k: spans[0][3][k] for k in first["stats"]} == first["stats"]
    chip = trace_reduce.read_planes(path)[0]
    joined = host_spans.join_launches(spans, chip["XLA Modules"], STEP_MODULES)
    got = {int(st["seq"]): (int(st["kv_tokens"]), s, e) for st, s, e in joined}
    assert want["joined"]
    for j in want["joined"]:
        kv, s, e = got[j["seq"]]
        assert kv == j["kv_tokens"]
        assert s == pytest.approx(j["module_start_s"], rel=1e-9)
        assert e == pytest.approx(j["module_end_s"], rel=1e-9)
    r = gaps.attribute(path, min_us=50.0)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["idle_s"] == pytest.approx(want["idle_s"], rel=1e-6)
    assert len(r["gaps"]) == len(want["gaps"])
    for g, w in zip(r["gaps"], want["gaps"]):
        assert g["us"] == pytest.approx(w["us"], rel=1e-6)
        assert (g["span"] if w["span"] else None) == w["span"]
    if want["idle_owned_pct"] is not None:
        assert r["idle_owned_pct"] == pytest.approx(want["idle_owned_pct"], rel=1e-6)
