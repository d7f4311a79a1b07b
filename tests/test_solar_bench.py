"""What ISSUE 57 adds to the benchmark (cellbench/), run on the CPU: the new
cell rehearsed end to end at a tiny size through `cellbench/run.py` (the
harness as it stands; the tiny manifest and its data live under tests/data),
the four new per-layer readers on a hand-made trace and on the recorded
`olmo2-chat` fixtures (a program that has none of what they read: nothing,
without raising), the rooflines' arithmetic, what the 8-bit control rounds of
this reference, the manifest's appended entries, and the configuration's file
against the catalog's row and against the registry.
"""

import json
import os
import statistics
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "cellbench")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "tools"))

from harness import host_spans, manifest, scrape, trace_reduce  # noqa: E402

TEST_MANIFEST = os.path.join(ROOT, "tests", "data", "BENCHMARK.solar.json")
CELL, CONFIG = "solar-docs-xlong", "solar-open2-ep8-4l"
NEW_METRICS = ["delta_mix_ms_per_step", "delta_scan_roofline",
               "delta_share_step_roofline", "delta_chunks_per_state_row"]
ACCEPTED = ["olmo2-chat", "mistral-docs", "olmo2-batch", "kanana-docs-long", "sdar-batch",
            "lfm2-docs-long", "trinity-docs-xlong", "sala-docs-xlong", "granite-batch",
            "mimo-reason-batch"]
# the accepted metrics whose readers read this cell for what they say: the
# open-loop group, the routed layer's, the share's, the scoped and the
# worker-timed ones
JOINED = ["gen_late_ms_max", "queue_wait_ms_mean", "ttft_ms_p50", "ttft_ms_p90",
          "prefix_hit_pct", "slot_wait_ms_mean", "prefill_ms_mean",
          "steps_ahead_of_prefill_mean", "mixed_step_pct", "host_ms_per_step",
          "fetch_wait_pct", "attn_grid_live_pct", "moe_ms_per_step", "moe_expert_roofline",
          "moe_experts_touched_pct", "moe_layer_ms_per_step", "moe_held_pair_pct",
          "scoped_device_pct", "attn_layer_ms_per_step",
          "head_sample_ms_per_step", "decode_step_ms_mean", "mixed_step_ms_mean",
          "launch_timed_pct", "decode_time_in_mixed_pct", "device_empty_wait_pct",
          "device_empty_host_pct"]
# their counts are other models'; every layer routes, so no dense `ffn` scope
# (as sdar-batch): a traced line would lack `ffn_ms_per_step`
NOT_JOINED = ["ffn_ms_per_step", "attn_kv_roofline", "step_weight_roofline", "hybrid_attn_kv_roofline",
              "window_attn_kv_roofline", "sparse_attn_kv_roofline", "linear_attn_ms_per_step",
              "linear_attn_roofline", "ssm_mix_ms_per_step", "ssm_scan_roofline",
              "ssm_step_roofline", "ssm_state_rows_mean", "routed_share_step_roofline",
              "sink_window_attn_kv_roofline", "mla_attn_roofline", "conv_mix_ms_per_step",
              "steps_per_s.batch", "kv_free_min_pct.batch", "ragged_attn_roofline.batch",
              "mixed_tokens_live_pct", "window_kv_held_pct"]
LIST_LESS = ["batch_rows_mean", "prefill_tok_pct", "step_device_ms_p50",
             "attn_kernel_ms_per_step", "device_idle_pct"]
# appended by later PRs for this cell alone (PR 58: the share of the state's
# row-steps that ride a decode chunk, which the one-token form serves)
LATER = ["delta_decode_chunk_row_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _config():
    return manifest.load_json(os.path.join(BENCH, "configs", f"{CONFIG}.json"))


def _tiny():
    return manifest.load_json(
        os.path.join(ROOT, "tests", "data", "solar", "configs", "tiny-solar.json"))


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


# ---- the cell, rehearsed -----------------------------------------------------

def test_the_new_cell_runs_every_phase_at_a_tiny_size_and_refuses_a_cpu(tmp_path):
    """Open-loop sessions of a document and two questions on 4 slots, as one
    share of two (experts 4-7 of 8): the second ask of a session restores its
    snapshot, the check's `repeat` restores `long`'s, and every delivered
    token of the check is the reference's top-1 (float32)."""
    from bench_rehearsal import light_manifest

    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         light_manifest(tmp_path, TEST_MANIFEST, CELL, 1.0),
         "--platform", "cpu", "--workload", CELL, "--seed", "4242424242",
         "--seconds", "14", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    out = p.stdout
    assert p.returncode != 0 and "the device is not a TPU" in out, out[-3000:] + p.stderr[-2000:]
    assert "server ready in" in out and "window:" in out and "reference child:" in out
    assert ", 0 failed" in out.split("window:")[1].splitlines()[0]
    hit = int(out.split("repeat prefix_cached_tokens=")[1].split()[0])
    assert hit == 64  # the long prompt's 70 tokens left snapshots at 56 and 64
    assert out.count("-> ok") == 3 and "FAIL" not in out and "NOT COMPARED" not in out
    assert not out.strip().splitlines()[-1].startswith("{")


# ---- the readers ---------------------------------------------------------------

SCOPES = {"vocabulary": ["attn", "delta_mix"], "programs": {
    "jit_mixed_step_ragged": {
        "%ragged_paged_attend.5": {"scope": ["attn"], "mixed": 0},
        "%fusion.3": {"scope": ["delta_mix", "delta_scan"], "mixed": 0},
        "%delta_state.7": {"scope": ["delta_mix", "delta_scan"], "mixed": 0},
        "%fusion.4": {"scope": ["delta_mix", "delta_conv"], "mixed": 0},
        "%fusion.6": {"scope": ["moe_experts"], "mixed": 0}},
    "jit_decode_slots_paged": {
        "%delta_state.1": {"scope": ["delta_mix", "delta_scan"], "mixed": 0},
        "%paged_flash_attend.2": {"scope": ["attn"], "mixed": 0},
        "%fusion.8": {"scope": ["delta_mix"], "mixed": 0},
        "%fusion.9": {"scope": ["moe_experts"], "mixed": 0}},
}}
CHUNK = {"phase": "chunk", "state_rows": 8, "delta_chunks": 8, "decode_rows": 2,
         "prefill_chunks": 0, "prefill_tokens": 0, "steps_live": 4, "row_steps": 8}
MIXED = {"phase": "mixed", "state_rows": 3, "delta_chunks": 9, "decode_rows": 2,
         "prefill_chunks": 1, "prefill_tokens": 448, "row_steps": 2, "tokens_live": 450}
AFTER = {7: {"moe_pairs": 30, "moe_experts_touched": 25, "steps_run": 4},
         8: {"moe_pairs": 1800, "moe_experts_touched": 160}}


def _hand_trace(tmp_path, fields=True, scopes=True, head=False):
    """A chunk launch (seq 7, 4 steps, 2 rows) and a mixed launch (seq 8: two
    decode rows and a 448-token chunk) with their modules, kernels and scoped
    operations; launch 9's module ran after the trace. Microseconds. fields
    False: a program that writes `kv_tokens` alone on a launch span; scopes
    False: and no map beside the trace; head True: before them a chunk
    dispatched ahead of the profiler, so an execution that no span names."""
    import cut_spans
    from jax.profiler import ProfileData

    device = {
        "XLA Modules": [("jit_decode_slots_paged(12)", 1000, 4000),
                        ("jit_mixed_step_ragged(11)", 5100, 1000)],
        "XLA Ops": [("%paged_flash_attend.2 = bf16[] custom-call()", 1000, 500),
                    ("%delta_state.1 = f32[] custom-call()", 1500, 800),
                    ("%fusion.8 = f32[] fusion()", 2300, 200),
                    ("%fusion.9 = f32[] fusion()", 2500, 2500),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 5100, 200),
                    ("%fusion.3 = f32[] fusion()", 5300, 100),
                    ("%delta_state.7 = f32[] custom-call()", 5400, 300),
                    ("%fusion.4 = f32[] fusion()", 5700, 100),
                    ("%fusion.6 = f32[] fusion()", 5800, 300)],
    }
    if head:
        device["XLA Modules"].insert(0, ("jit_decode_slots_paged(12)", 100, 800))
        device["XLA Ops"].insert(0, ("%delta_state.1 = f32[] custom-call()", 100, 700))
    own = lambda kw: kw if fields else {}  # noqa: E731
    spans = [
        ("launch.chunk", 990, 20, {"prev": "plan", "seq": 7, "steps": 4, "kv_tokens": 32000,
                                   **own(CHUNK)}),
        ("launch.mixed", 1020, 30, {"prev": "plan", "seq": 8, "steps": 1, "kv_tokens": 8000,
                                    **own(MIXED)}),
        ("fetch.chunk", 1060, 3990, {"prev": "plan", "seq": 7}),
        ("phase.distribute", 5060, 10, {"seq": 7, **own(AFTER[7])}),
        ("launch.mixed", 5090, 20, {"prev": "plan", "seq": 9, "steps": 1, "kv_tokens": 700}),
        ("fetch.mixed", 5110, 1000, {"prev": "dispatch", "seq": 8}),
        ("phase.distribute", 6120, 10, {"seq": 8, **own(AFTER[8])}),
    ]
    lines = {ln: [(n, s * 1000, d * 1000) for n, s, d in evs] for ln, evs in device.items()}
    text = cut_spans.xspace_text(
        "/device:TPU:0", lines, [(n, s * 1000, d * 1000, st) for n, s, d, st in spans], 0)
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(text)))
    if scopes:
        (tmp_path / "program_scopes.json").write_text(json.dumps(SCOPES))


def _traced(tmp_path, config):
    path = host_spans.find(str(tmp_path))
    counters = scrape.parse('dli_attn_kv_tokens_total{phase="mixed",state="attended"} 5\n')
    return Ctx(
        trace_dir=str(tmp_path), chunk_steps=4, peaks=PEAKS, config=config, window_s=8.0,
        trace=trace_reduce.reduce(path) if path else {"modules": {}, "ops": {}},
        before=counters, after=counters)


def test_the_scoped_readers_read_their_labels_at_any_depth(tmp_path):
    _hand_trace(tmp_path)
    ctx = _traced(tmp_path, _config())
    # 5 scheduler steps (a chunk of 4 and a mixed step); delta_mix: 800 + 200
    # us in the chunk, 100 + 300 + 100 in the mixed step
    assert read("delta_mix_ms_per_step", ctx) == pytest.approx(1.5 / 5)
    assert read("attn_layer_ms_per_step", ctx) == pytest.approx(0.7 / 5)
    # 8 + 9 chunks over 8 + 3 state rows
    assert read("delta_chunks_per_state_row", ctx) == pytest.approx(17 / 11)


def test_the_rooflines_count_useful_work_of_the_matched_launches(tmp_path):
    _hand_trace(tmp_path)
    config = _config()
    rule = manifest.load_module("roofline", "delta_rule")
    assert rule.sizes(config) == {"H": 64, "Dk": 128, "Dv": 128, "layers": 3, "item": 2}
    assert rule.state_bytes(config) == 64 * 128 * 128 * 4 == 2 ** 22
    assert rule.counts(CHUNK) == (8, 8) and rule.counts(MIXED) == (3, 450)
    token = (64 * 5 * 128 + 64) * 2
    nbytes = 3 * (11 * 2 * 2 ** 22 + 458 * token)
    flops = 3 * 458 * 64 * 7 * 128 * 128
    assert rule.work(config, MIXED) == (3 * (3 * 2 * 2 ** 22 + 450 * token),
                                        3 * 450 * 64 * 7 * 128 * 128)
    least, which = rule.bound(config, [CHUNK, MIXED], PEAKS)
    assert which == "bandwidth" and least == pytest.approx(nbytes / 819e9)
    assert flops / 197e12 < least
    # over the scope's 800 us in the chunk and 100 + 300 in the mixed step
    got = read("delta_scan_roofline", _traced(tmp_path, config))
    assert got == pytest.approx(100 * least / 1200e-6) and got < 100
    # ... and of the matched launches' executions alone
    _hand_trace(tmp_path / "head", head=True)
    ctx = _traced(tmp_path / "head", config)
    assert len(ctx.trace["modules"]["jit_decode_slots_paged"]) == 2
    assert read("delta_scan_roofline", ctx) == pytest.approx(got)
    # the whole step: what every token passes, by the published sizes
    step = manifest.load_module("roofline", "delta_share_step")
    s = step.sizes(config)
    D, Hd, r = 4096, 8192, 128
    kda = D * (3 * Hd + 2 * r + 64) + 2 * r * Hd + Hd * D + 4 * 3 * Hd
    gqa = D * (2 * 8192 + 2 * 1024) + 8192 * D
    every = 3 * kda + gqa + 4 * (D * 320 + 3 * D * 1280)
    assert s == {"every_token": every, "head": 24576 * D, "expert": 3 * D * 1280,
                 "item": 2, "kv_row": 2 * 8 * 128 * 2, "kv_flops": 64 * 4 * 128}
    # (the mixers, routers, shared experts and the head's slice: 0.69 G
    # parameters of the 3.31 G; the rest is the 160 held experts and the
    # embedding's rows, which are gathered)
    assert round((every + s["head"]) / 1e6) == 691
    chunk = dict(CHUNK, kv_tokens=32000)
    mixed = dict(MIXED, kv_tokens=8000)
    state = 3 * 2 * 2 ** 22
    want_chunk = (4 * (every + s["head"]) + 25 * s["expert"]) * 2 + 32000 * 4096 \
        + 8 * state + 3 * 8 * token
    assert step.counts(config, chunk, AFTER[7])[0] == want_chunk
    b, f = step.counts(config, mixed, AFTER[8])
    assert b == ((every + s["head"]) + 160 * s["expert"]) * 2 + 8000 * 4096 \
        + 3 * state + 3 * 450 * token
    assert f == 2 * (450 * every + 3 * s["head"] + 1800 * s["expert"]) \
        + 8000 * 64 * 4 * 128 + 3 * 450 * 64 * 7 * 128 * 128
    least = step.least_seconds(config, chunk, AFTER[7], PEAKS) \
        + step.least_seconds(config, mixed, AFTER[8], PEAKS)
    got = read("delta_share_step_roofline", _traced(tmp_path, config))
    assert got == pytest.approx(100 * least / 5000e-6)
    assert step.counts(config, mixed, {}) is None  # no routed counts on the span


def test_the_new_readers_give_nothing_for_a_program_without_what_they_read(tmp_path):
    """The parent commit (no scope map's labels, `kv_tokens` alone on a
    launch span), another family's configuration, a run without a trace, and
    the recorded olmo2-chat fixtures: the metric is left out, and nothing
    raises."""
    _hand_trace(tmp_path / "parent", fields=False, scopes=False)
    ctx = _traced(tmp_path / "parent", _config())
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name
    _hand_trace(tmp_path / "fields", fields=False)
    ctx = _traced(tmp_path / "fields", _config())
    for name in NEW_METRICS[1:]:
        assert read(name, ctx) is None, name
    _hand_trace(tmp_path / "other")
    for other in ("olmo2-7b-16l", "granite-4.0-h-micro", "mimo-v2.5-7l",
                  "trinity-large-ep8-5l"):
        config = manifest.load_json(os.path.join(BENCH, "configs", f"{other}.json"))
        ctx = _traced(tmp_path / "other", config)
        assert read("delta_scan_roofline", ctx) is None
        assert read("delta_share_step_roofline", ctx) is None
    ctx = _traced(tmp_path / "nothing-here", _config())
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name
    olmo2 = manifest.load_json(os.path.join(BENCH, "configs", "olmo2-7b-16l.json"))
    for cut in ("olmo2-chat.cut", "olmo2-chat.spans"):
        d = tmp_path / cut / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        os.symlink(os.path.join(BENCH, "fixtures", f"{cut}.xplane.pb"),
                   d / "host.xplane.pb")
        for config in (olmo2, _config()):
            ctx = _traced(tmp_path / cut, config)
            assert ctx.trace["modules"], cut
            for name in NEW_METRICS:
                assert read(name, ctx) is None, (cut, name)


@pytest.mark.parametrize("name", LIST_LESS + NEW_METRICS + [
    "mixed_step_pct", "scoped_device_pct", "attn_grid_live_pct",
    "attn_layer_ms_per_step", "moe_layer_ms_per_step"])
def test_a_reader_of_the_cell_reads_the_tiny_configuration(tmp_path, name):
    """Every reader the cell reports off a trace or a counter, on the tiny
    configuration's file: the keys it asks of a configuration are in a
    solar_open2 file."""
    _hand_trace(tmp_path)
    ctx = _traced(tmp_path, _tiny())
    extra = ('dli_sched_step_tokens_total{{kind="prefill"}} {}\n'
             'dli_sched_step_tokens_total{{kind="decode"}} {}\n'
             'dli_ragged_launches_total{{phase="mixed"}} {}\n'
             'dli_ragged_launches_total{{phase="chunk"}} {}\n'
             'dli_attn_kv_tokens_total{{phase="mixed",state="walked"}} {}\n'
             'dli_attn_kv_tokens_total{{phase="mixed",state="attended"}} {}\n'
             'dli_worker_phase_seconds_total{{phase="plan"}} {}\n')
    ctx.before = scrape.parse(extra.format(100, 100, 10, 2, 50, 5, 1.0))
    ctx.after = scrape.parse(extra.format(1200, 900, 50, 6, 9000, 4000, 2.0))
    ctx.end_to_end = {"out_tok_s": 22.0}
    got = read(name, ctx)
    assert got is not None and got >= 0, name


def test_the_control_rounds_the_matrices_it_names_of_this_reference():
    """tools/control.py quantizes by leaf name: both kinds of mixer's wq, wk,
    wv, wo, the expert banks w_gate / w_up / w_down and lm_head; the low-rank
    pairs, w_beta, the attention gate, the taps, the routers, the shared
    experts and the vectors stay."""
    import control
    import jax.numpy as jnp
    import numpy as np

    config = _tiny()
    ref = manifest.load_module("reference", config["reference"])
    params = ref.make_params(config, 7, jnp.float32)
    low = control.quantized(params, control.BITS)
    for name in control.MATRICES:
        assert isinstance(low[name], control.QuantizedLeaf), name
        for l in range(4):
            plain, rounded = np.asarray(params[name][l]), np.asarray(low[name][l])
            assert plain.shape == rounded.shape and 0 < np.abs(plain - rounded).max() < 0.08, (name, l)
    assert params["wq"][0].shape == (64, 256) and params["wq"][1].shape == (64, 256)
    assert params["wk"][0].shape == (64, 128) and params["wk"][1].shape == (64, 256)
    assert params["w_gate"][0].shape == (4, 64, 32)  # the held share's bank
    for name in ("wf_down", "wf_up", "wg_down", "wg_up", "w_beta", "wg", "conv_w",
                 "a_log", "dt_bias", "o_norm", "w_router", "router_bias", "ws_gate"):
        assert low[name] is params[name]
    assert np.abs(np.asarray(low["lm_head"]) - np.asarray(params["lm_head"])).max() > 0


def test_the_reference_draws_the_programs_weights():
    """The same table of keys, in float32 and bfloat16, for the tiny share
    (experts 4-7 of 8): every leaf of models/solar_open2.init_params equals
    the reference's, bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.models import solar_open2
    from harness import serve

    config = _tiny()
    ref = manifest.load_module("reference", config["reference"])
    # (the same table; the reference names the router's and the shared
    # expert's leaves bare)
    assert sorted(ref.LEAF_KEY.values()) == sorted(solar_open2.LEAF_KEYS.values())
    assert {k: v for k, v in ref.LEAF_KEY.items() if k in solar_open2.LEAF_KEYS} \
        == {k: v for k, v in solar_open2.LEAF_KEYS.items() if k in ref.LEAF_KEY}
    cfg0 = serve.register_config(config)
    assert (cfg0.expert_lo, cfg0.experts_held, cfg0.n_experts) == (4, 4, 8)
    for dtype in ("float32", "bfloat16"):
        cfg = cfg0.replace(dtype=dtype)
        ours = solar_open2.init_params(cfg, jax.random.PRNGKey(11))
        theirs = ref.make_params(config, 11, jnp.dtype(dtype))
        same = lambda a, b: np.testing.assert_array_equal(  # noqa: E731
            np.asarray(a, np.float32), np.asarray(b, np.float32))
        same(ours["embed"], theirs["embed"])
        same(ours["head"].T, theirs["lm_head"])
        ik = 0
        for l, kind in enumerate(cfg.layer_types):
            if kind == "kda":
                lp = ours["layers"]["kda"]
                same(lp["w_in"][ik], np.concatenate(
                    [np.asarray(theirs[n][l], np.float32) for n in solar_open2.W_IN], axis=1))
                for n in ("conv_w", "wf_up", "wg_up", "a_log", "dt_bias", "o_norm", "wo"):
                    same(lp[n][ik], theirs[n][l])
                ik += 1
            else:
                for n in ("wq", "wk", "wv", "wg", "wo"):
                    same(ours["layers"]["attn"][n][0], theirs[n][l])
            for n in ("w_router", "router_bias", "w_gate", "w_up", "w_down",
                      "ws_gate", "ws_up", "ws_down"):
                same(ours["layers"]["moe"][n][l], theirs[n][l])


# ---- the manifest and the configuration's file -------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_four_metrics():
    man = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # (the tenth configuration and the eleventh cell: later PRs append after them)
    assert man["configs"][9]["name"] == CONFIG and man["workloads"][10]["name"] == CELL
    solar = man["configs"][9]
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 4] == NEW_METRICS
    assert solar["reduced"] == ["num_hidden_layers", "gqa_layers", "n_routed_experts",
                                "vocab_size"]
    assert solar["source"] == \
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
    cells = {w["name"]: w for w in man["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG, "traffic": "docs-repeat-xlong",
                           "chips": 1}
    assert len(cells[CELL]["why"]) <= 200 and len(solar["why"]) <= 200
    assert all(w["chips"] == 1 for w in man["workloads"])
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "tpot_ms_p50"
        assert set(by_name[name]) == {"name", "unit", "better", "source", "layer", "moves",
                                      "workloads"}
    assert by_name["delta_scan_roofline"]["layer"] == by_name["ssm_scan_roofline"]["layer"]
    assert by_name["delta_mix_ms_per_step"]["layer"] == by_name["ssm_mix_ms_per_step"]["layer"]
    assert by_name["delta_share_step_roofline"]["layer"] == \
        by_name["routed_share_step_roofline"]["layer"]
    assert by_name["delta_chunks_per_state_row"]["layer"] == by_name["batch_rows_mean"]["layer"]
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name  # (appended at the end: the diff)
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"], name
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_p50", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == \
        set(LIST_LESS) | set(NEW_METRICS) | set(JOINED) | set(LATER)
    for name in LATER:
        assert names.index(name) > at + 3 and by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == by_name["delta_scan_roofline"]["layer"]
    for other in ACCEPTED:  # nothing an accepted cell reports has changed
        assert not set(NEW_METRICS + LATER) & {
            m["name"] for m in manifest.Cell(man, other).per_layer}
    # the traffic file trinity-docs-xlong and sala-docs-xlong run, unedited
    assert cell.traffic == manifest.Cell(man, "trinity-docs-xlong").traffic
    assert cell.traffic["check"]["long_tokens"] == 12400 and cell.traffic["begin_at"] == 1
    own = manifest.load_json(os.path.join(BENCH, "cells", f"{CELL}.json"))
    assert cell.load == {"loop": "open", "rate": own["load"]["rate"]}
    steps = [s[0] for s in own["sweep"]["steps"]]
    assert own["knee"] in steps and steps[0] == 0.1
    assert all(b == pytest.approx(a * 1.25, abs=1e-3) for a, b in zip(steps, steps[1:]))
    sets = own["steadiness"]["sets"]
    assert len(sets) >= 2
    for _, values, spread, _ in sets:
        q = statistics.quantiles(values, n=4)
        assert len(values) == 6
        assert spread == pytest.approx(100 * (q[2] - q[0]) / statistics.median(values), abs=6e-3)
    assert own["load"]["rate"] == sets[0][0] and sets[0][2] < 3.5 and sets[1][2] < 3.5
    assert 70 <= own["memory"]["after_warmup_pct"] <= 85
    manifest.load_module("reference", cell.config["reference"])


def test_the_configuration_keeps_every_published_number():
    config = _config()
    reduced = set(config["reduced"])
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
        assert config["source"] == row["source_url"]
        assert {k for k, v in row["config"].items()
                if config.get(k, "absent") != v} == reduced
        assert {k: config["published"][k] for k in reduced} == \
            {k: row["config"][k] for k in reduced}
    # no width is reduced: a hidden, intermediate, head, conv or top-k size
    assert not {k for k in reduced if k.endswith(("_size", "_dim", "_rank"))
                and k != "vocab_size"}
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None}
    assert (config["num_hidden_layers"], config["gqa_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, [0], 40, 24576)
    assert config["expert_share"] == {**config["expert_share"], "router_width": 320,
                                      "expert_lo": 0, "chips": 8}
    assert 320 // 8 == 40 and 196608 // 8 == 24576 and 48 // 4 == 12
    for key in ("assumed", "served", "deployment", "check_why", "reduced_why", "published"):
        assert config[key], key
    assert set(config["reduced_why"]) == reduced
    said = " ".join(config["assumed"])
    for what in ("low-rank width", "uniform on [1, 16]", "log-uniform on [0.001, 0.1]",
                 "inverse softplus", "float32", "2 sigmoid", "no q/k norm", "noaux_tc",
                 "pre-norm", "word-level", "bos 1, eos 2, pad 0", "bfloat16",
                 "PUBLISHED index"):
        assert what in said, what
    for what in ("one of 8 chips", "one pipeline stage of twelve", "an eighth",
                 "more than their share"):
        assert what in config["deployment"], what
    assert set(config["check"]) == {"mismatch", "mean", "worst"}
    flags = config["serving"]["flags"]
    for flag, value in (("--continuous", "16"), ("--continuous-max-seq", "66048"),
                        ("--kv-block-size", "128"), ("--kv-pool-blocks", "8192"),
                        ("--state-snapshots", "96"), ("--prefix-cache", "8"),
                        ("--attn-impl", "pallas"), ("--max-tokens-cap", "1024")):
        assert flags[flags.index(flag) + 1] == value, flag
    assert "--no-kv-shadow" in flags and "--warmup" in flags
    trinity = manifest.load_json(os.path.join(BENCH, "configs", "trinity-large-ep8-5l.json"))
    theirs = trinity["serving"]["flags"]
    for flag in ("--continuous", "--continuous-max-seq", "--kv-block-size"):
        assert flags[flags.index(flag) + 1] == theirs[theirs.index(flag) + 1], flag
    for reason in ("--continuous 16", "--kv-pool-blocks 8192", "context",
                   "--state-snapshots 96", "--kv-block-size 128", "bytes_in_use"):
        assert config["served"][reason], reason
    assert config["serving"]["trace"]["step_modules"] == {
        "mixed_step_ragged": 1, "decode_slots_paged": None}


def test_the_files_arithmetic_and_the_registrys_sizes():
    """The file's sizes are the registry's, and the bytes `reduced_why` and
    `served` state are the program's own leaves'."""
    import jax

    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.engine.scheduler import live_width, step_width
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models import solar_open2
    from harness import serve

    config = _config()
    cfg = serve.register_config(config)
    assert (cfg.arch, cfg.n_layers) == ("solar_open2", 4)
    assert list(cfg.layer_types) == ["full_attention", "kda", "kda", "kda"]
    assert solar_open2.stack_depths(cfg) == {"kda": 3, "attn": 1}
    lin = config["linear_attn_config"]
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size,
            cfg.norm_eps, cfg.max_seq_len, cfg.ffn_dim) == (
        config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], config["vocab_size"], config["rms_norm_eps"],
        config["max_position_embeddings"], config["intermediate_size"])
    assert (cfg.linear_heads, cfg.conv_kernel, cfg.delta_neg_eigval) == (
        lin["num_heads"], lin["short_conv_kernel_size"], config["kda_allow_neg_eigval"])
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_lo, cfg.n_experts_per_tok,
            cfg.moe_ffn_dim, cfg.n_shared_experts, cfg.first_k_dense, cfg.routed_scaling,
            cfg.moe_renormalize, cfg.router_score) == (
        config["expert_share"]["router_width"], config["n_routed_experts"], 0,
        config["num_experts_per_tok"], config["moe_intermediate_size"],
        config["n_shared_experts"], config["first_k_dense_replace"],
        config["routed_scaling_factor"], config["norm_topk_prob"], "sigmoid")
    assert not cfg.tie_embeddings and cfg.recurrent and not cfg.state_tails
    assert cfg.conv_layers == cfg.linear_layers == cfg.delta_layers == (1, 2, 3)
    bf16 = cfg.replace(dtype="bfloat16")
    shapes = jax.eval_shape(lambda: M.init_params(bf16, jax.random.PRNGKey(0)))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    D, Hd, r = 4096, 8192, 128
    expert = 3 * D * 1280
    kda = D * (3 * Hd + 2 * r + 64) + 2 * r * Hd + Hd * D + 4 * 3 * Hd + 64 + Hd + 128
    gqa = D * (2 * Hd + 2 * 1024) + Hd * D
    routed = D * 320 + 320 + 40 * expert + expert
    tables = 2 * 24576 * D
    assert count == 3 * (kda + routed) + gqa + routed + tables + 9 * D == 3_308_353_344
    assert nbytes == 6_616_758_784
    said = " ".join(config["reduced_why"].values())
    for number in ("15.73M", "137.73M", "109.05M", "646.19M", "783.92M", "755.24M",
                   "201.33M", "3,308.35M", "6,616,758,784", "6.62 GB", "10.07 GB"):
        assert number in said, number
    assert round(expert / 1e6, 2) == 15.73 and round(kda / 1e6, 2) == 137.73
    assert round(gqa / 1e6, 2) == 109.05 and round(routed / 1e6, 2) == 646.19
    assert round((kda + routed) / 1e6, 2) == 783.92 and round((gqa + routed) / 1e6, 2) == 755.24
    assert round(tables / 1e6, 2) == 201.33 and round(320 * expert * 2 / 1e9, 2) == 10.07
    flags = config["serving"]["flags"]
    slots, blocks, snaps = (int(flags[flags.index(f) + 1]) for f in (
        "--continuous", "--kv-pool-blocks", "--state-snapshots"))
    assert (step_width(bf16, slots, 8), live_width(bf16, slots, 8)) == (512, 512)
    pool = jax.eval_shape(lambda: P.init_pool(bf16, blocks, 128, n_slots=slots,
                                              n_snapshots=snaps))
    assert pool["k"].shape == (1, blocks, 8, 128, 128)
    assert len(pool["lin"]) == len(pool["snap"]) == len(pool["conv"]) == len(pool["csnap"]) == 3
    assert pool["lin"][0].shape == (slots, 64, 128, 128) and pool["lin"][0].dtype == "float32"
    assert pool["conv"][0].shape == (slots, 3, 24576) and pool["conv"][0].dtype == "bfloat16"
    token = (pool["k"].size + pool["v"].size) * 2 / (blocks * 128)
    assert token == 4096  # 1 layer x K and V x 8 heads x 128 x 2 B
    live = (sum(a.size for a in pool["lin"]) * 4 + sum(a.size for a in pool["conv"]) * 2) / slots
    assert round(live / 1e6, 2) == 13.03  # 12.58 MB of matrix states + 0.44 MB of inputs
    kept = sum(a.size for a in pool["snap"]) * 4 + sum(a.size for a in pool["csnap"]) * 2
    assert round(kept / 1e9, 2) == 1.25
    total = nbytes + sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    assert round(total / 1e9, 2) == 12.37 and 0.70 < total / 16.9e9 < 0.85
