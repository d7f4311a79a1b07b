"""What ISSUE 51 adds to the benchmark (cellbench/), run on the CPU: the new
cell rehearsed end to end at a tiny size through `cellbench/run.py` (the
harness as it stands; the tiny manifest and its data live under tests/data,
since nothing under cellbench/tests may change), the four new per-layer
readers on a hand-made trace and on the recorded `olmo2-chat` fixtures (a
program that has none of what they read: nothing, without raising), the
rooflines' arithmetic, what the 8-bit control rounds of this reference, the
manifest's appended entries, and the configuration's file against the
catalog's row and against the registry.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "cellbench")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "tools"))

from harness import host_spans, manifest, scrape, trace_reduce  # noqa: E402

TEST_MANIFEST = os.path.join(ROOT, "tests", "data", "BENCHMARK.granite.json")
CELL, CONFIG = "granite-batch", "granite-4.0-h-micro"
NEW_METRICS = ["ssm_mix_ms_per_step", "ssm_scan_roofline", "ssm_step_roofline",
               "ssm_state_rows_mean"]
ACCEPTED = ["olmo2-chat", "mistral-docs", "olmo2-batch", "kanana-docs-long", "sdar-batch",
            "lfm2-docs-long", "trinity-docs-xlong", "sala-docs-xlong"]
JOINED = ["mixed_step_pct", "host_ms_per_step", "fetch_wait_pct", "attn_grid_live_pct",
          "scoped_device_pct", "attn_layer_ms_per_step", "ffn_ms_per_step",
          "head_sample_ms_per_step"]
# their formulas read another stack's keys; the open-loop metrics; `.batch`
# metrics move out_tok_s, which keeps its one cell
# PR 53's six read the worker's own counters in every cell they list
WORKER_TIMED = ["decode_step_ms_mean", "mixed_step_ms_mean", "launch_timed_pct",
                "decode_time_in_mixed_pct", "device_empty_wait_pct", "device_empty_host_pct",
                # PR 54: the live share of the tokens a mixed step computes
                "mixed_tokens_live_pct"]
NOT_JOINED = ["step_weight_roofline", "attn_kv_roofline", "hybrid_attn_kv_roofline",
              "linear_attn_ms_per_step", "linear_attn_roofline", "conv_mix_ms_per_step",
              "gen_late_ms_max", "queue_wait_ms_mean", "ttft_ms_p50", "ttft_ms_p90",
              "prefix_hit_pct", "slot_wait_ms_mean", "prefill_ms_mean",
              "steps_ahead_of_prefill_mean", "steps_per_s.batch", "kv_free_min_pct.batch",
              "ragged_attn_roofline.batch"]
LIST_LESS = ["batch_rows_mean", "prefill_tok_pct", "step_device_ms_p50",
             "attn_kernel_ms_per_step", "device_idle_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _config():
    return manifest.load_json(os.path.join(BENCH, "configs", f"{CONFIG}.json"))


def _tiny():
    return manifest.load_json(
        os.path.join(ROOT, "tests", "data", "granite", "configs", "tiny-granite.json"))


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


# ---- the cell, rehearsed -----------------------------------------------------

def test_the_new_cell_runs_every_phase_at_a_tiny_size_and_refuses_a_cpu():
    """A closed loop of 8 callers on 4 slots: every slot is let again and
    again from zeros while its neighbours carry on, the check's `repeat`
    restores `long`'s snapshot, and every delivered token of the check is
    the reference's top-1 (float32)."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest", TEST_MANIFEST,
         "--platform", "cpu", "--workload", CELL, "--seed", "4242424242",
         "--seconds", "6", "--trace", "0"],
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

SCOPES = {"vocabulary": ["attn", "ssm_mix"], "programs": {
    "jit_mixed_step_ragged": {
        "%ragged_paged_attend.5": {"scope": ["attn"], "mixed": 0},
        "%fusion.3": {"scope": ["ssm_mix", "ssm_scan"], "mixed": 0},
        "%ssm_scan.7": {"scope": ["ssm_mix", "ssm_scan"], "mixed": 0},
        "%fusion.4": {"scope": ["ssm_mix"], "mixed": 0},
        "%fusion.6": {"scope": ["ffn"], "mixed": 0}},
    "jit_decode_slots_paged": {
        "%ssm_scan.1": {"scope": ["ssm_mix", "ssm_scan"], "mixed": 0},
        "%paged_flash_attend.2": {"scope": ["attn"], "mixed": 0},
        "%fusion.8": {"scope": ["ssm_mix"], "mixed": 0},
        "%fusion.9": {"scope": ["ffn"], "mixed": 0}},
}}
CHUNK = {"state_rows": 8, "state_fresh_rows": 0, "decode_rows": 2, "prefill_chunks": 0,
         "prefill_tokens": 0, "steps_live": 4}
MIXED = {"state_rows": 3, "state_fresh_rows": 1, "decode_rows": 2, "prefill_chunks": 1,
         "prefill_tokens": 100}


def _hand_trace(tmp_path, fields=True, scopes=True, head=False):
    """A chunk launch (seq 7, 4 steps, 2 rows) and a mixed launch (seq 8: two
    decode rows and a 100-token chunk) with their modules, kernels and
    scoped operations; launch 9's module ran after the trace. Microseconds.
    fields False: a program that writes `kv_tokens` alone on a launch span;
    scopes False: and no map beside the trace; head True: before them a chunk
    dispatched ahead of the profiler, so an execution that no span names."""
    import cut_spans
    from jax.profiler import ProfileData

    device = {
        "XLA Modules": [("jit_decode_slots_paged(12)", 1000, 4000),
                        ("jit_mixed_step_ragged(11)", 5100, 1000)],
        "XLA Ops": [("%paged_flash_attend.2 = bf16[] custom-call()", 1000, 500),
                    ("%ssm_scan.1 = f32[] custom-call()", 1500, 800),
                    ("%fusion.8 = f32[] fusion()", 2300, 200),
                    ("%fusion.9 = f32[] fusion()", 2500, 2500),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 5100, 200),
                    ("%fusion.3 = f32[] fusion()", 5300, 100),
                    ("%ssm_scan.7 = f32[] custom-call()", 5400, 300),
                    ("%fusion.4 = f32[] fusion()", 5700, 100),
                    ("%fusion.6 = f32[] fusion()", 5800, 300)],
    }
    if head:
        device["XLA Modules"].insert(0, ("jit_decode_slots_paged(12)", 100, 800))
        device["XLA Ops"].insert(0, ("%ssm_scan.1 = f32[] custom-call()", 100, 700))
    own = lambda kw: kw if fields else {}  # noqa: E731
    spans = [
        ("launch.chunk", 990, 20, {"prev": "plan", "seq": 7, "steps": 4, "kv_tokens": 32000,
                                   **own(CHUNK)}),
        ("launch.mixed", 1020, 30, {"prev": "plan", "seq": 8, "steps": 1, "kv_tokens": 8000,
                                    **own(MIXED)}),
        ("fetch.chunk", 1060, 3990, {"prev": "plan", "seq": 7}),
        ("launch.mixed", 5090, 20, {"prev": "plan", "seq": 9, "steps": 1, "kv_tokens": 700}),
        ("fetch.mixed", 5110, 1000, {"prev": "dispatch", "seq": 8}),
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
    # 5 scheduler steps (a chunk of 4 and a mixed step); ssm_mix: 800 + 200 us
    # in the chunk, 100 + 300 + 100 in the mixed step
    assert read("ssm_mix_ms_per_step", ctx) == pytest.approx(1.5 / 5)
    assert read("attn_layer_ms_per_step", ctx) == pytest.approx(0.7 / 5)
    # 8 row-steps over the chunk's 4 steps and 3 over the mixed step's one
    assert read("ssm_state_rows_mean", ctx) == pytest.approx(11 / 5)


def test_the_rooflines_count_useful_work_of_the_matched_launches(tmp_path):
    _hand_trace(tmp_path)
    config = _config()
    ssm = manifest.load_module("roofline", "ssm_scan")
    assert ssm.state_bytes(config) == 64 * 64 * 128 * 4 == 2 ** 21
    assert ssm.sizes(config) == {"H": 64, "P": 64, "N": 128, "Di": 4096, "K": 4,
                                 "C": 4352, "mamba": 36, "attention": 4, "item": 2}
    assert ssm.counts(CHUNK) == (8, 8, 8)
    assert ssm.counts(MIXED) == (3, 102, 2 + 100 * 101 / 2)
    nbytes = 36 * (11 * 2 * 2 ** 21 + 110 * (2 * 4096 + 256 + 64) * 2)
    flops = 36 * (110 * 64 * 4 * 64 * 128 + (8 + 5052) * (256 + 64 * 128))
    least, which = ssm.bound(config, [CHUNK, MIXED], PEAKS)
    assert which == "bandwidth" and least == pytest.approx(nbytes / 819e9)
    assert flops / 197e12 < least
    # over the scope's 800 us in the chunk and 100 + 300 in the mixed step
    got = read("ssm_scan_roofline", _traced(tmp_path, config))
    assert got == pytest.approx(100 * least / 1200e-6)
    # ... and of the matched launches' executions alone: a chunk that ran
    # under the trace with no span gives neither its rows nor its 700 us
    _hand_trace(tmp_path / "head", head=True)
    ctx = _traced(tmp_path / "head", config)
    assert len(ctx.trace["modules"]["jit_decode_slots_paged"]) == 2
    assert read("ssm_scan_roofline", ctx) == pytest.approx(got)
    assert read("ssm_step_roofline", ctx) is not None
    # the whole step: the weights by layer_types, once a step the device ran
    D, F, V = 2048, 8192, 100352
    mamba = D * 8512 + 5 * 4352 + 4096 + 4096 * D
    attention = D * (32 + 16) * 64 + 2048 * D
    ffn = 3 * D * F + 2 * D
    weights = 2 * (36 * (mamba + ffn) + 4 * (attention + ffn) + V * D + D) + 36 * 3 * 64 * 4
    assert ssm.step_weight_bytes(config) == weights and round(weights / 1e9, 2) == 6.38
    assert round((36 * (mamba + ffn) + 4 * (attention + ffn) + V * D + D) / 1e6) == 3191
    assert ssm.step_bytes(config, CHUNK) == 4 * weights + 8 * 2 * 36 * 2 ** 21
    assert ssm.step_bytes(config, MIXED) == weights + 3 * 2 * 36 * 2 ** 21
    # (not roofline/weights.py's dense formula: attention at all 40 layers)
    dense = manifest.load_module("roofline", "weights").step_weight_bytes(config)
    assert dense != weights
    got = read("ssm_step_roofline", _traced(tmp_path, config))
    assert got == pytest.approx(
        100 * (5 * weights + 11 * 2 * 36 * 2 ** 21) / 819e9 / 5000e-6)


@pytest.mark.parametrize("live,computed,want", [
    (25000, 32000, 78.125),  # 100 launches of 640 flat tokens computed on 320
    (25000, 64000, 39.0625),  # the same launches on the tile layout
    (0, 0, None),  # no mixed launch in the window
    (None, None, None),  # a program before PR 54: no counter, and no error
], ids=["packed", "tiles", "no-launch", "parent"])
def test_the_live_share_of_the_computed_tokens_is_the_counters_delta(live, computed, want):
    from harness import scrape

    def text(a, b):
        held = 'dli_ragged_launches_total{phase="mixed"} 3\n'
        if a is None:
            return held
        return held + (f'dli_mixed_tokens_total{{state="live"}} {a}\n'
                       f'dli_mixed_tokens_total{{state="computed"}} {b}\n')

    class Ctx:
        before = scrape.parse(text(None if live is None else 1000, 6400))
        after = scrape.parse(text(None if live is None else 1000 + live, 6400 + (computed or 0)))

    got = read("mixed_tokens_live_pct", Ctx)
    assert got is None if want is None else got == pytest.approx(want)


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
    for name in ("ssm_scan_roofline", "ssm_step_roofline", "ssm_state_rows_mean"):
        assert read(name, ctx) is None, name
    _hand_trace(tmp_path / "other")
    for other in ("olmo2-7b-16l", "lfm2-24b-a2b-9l", "minicpm-sala-9b-16l"):
        config = manifest.load_json(os.path.join(BENCH, "configs", f"{other}.json"))
        ctx = _traced(tmp_path / "other", config)
        assert read("ssm_scan_roofline", ctx) is None
        assert read("ssm_step_roofline", ctx) is None
    ctx = _traced(tmp_path / "nothing-here", _config())
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name
    olmo2 = manifest.load_json(os.path.join(BENCH, "configs", "olmo2-7b-16l.json"))
    for cut in ("olmo2-chat.cut", "olmo2-chat.spans"):
        # (a reader finds a profile under plugins/profile/<time>/ of a directory)
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
    n for n in JOINED if n in ("mixed_step_pct", "scoped_device_pct", "attn_grid_live_pct",
                               "attn_layer_ms_per_step", "ffn_ms_per_step")])
def test_a_reader_of_the_cell_reads_the_tiny_configuration(tmp_path, name):
    """Every reader the cell reports off a trace or a counter, on the tiny
    configuration's file: the keys it asks of a configuration are in a
    granitemoehybrid file."""
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
    """tools/control.py quantizes by leaf name: the attention layers' wq, wk,
    wv, every mixer's wo (a mamba layer's output projection goes by that
    name), the FFN's w_gate / w_up / w_down and lm_head, the tied table held
    a second time; a mamba layer's w_in, taps and vectors stay."""
    import control
    import jax.numpy as jnp
    import numpy as np

    config = _tiny()
    ref = manifest.load_module("reference", config["reference"])
    params = ref.make_params(config, 7, jnp.float32)
    low = control.quantized(params, control.BITS)
    kinds = config["layer_types"]
    for name in control.MATRICES:
        assert isinstance(low[name], control.QuantizedLeaf), name
        for l, kind in enumerate(kinds):
            if params[name][l] is None:
                assert kind == "mamba" and name in ("wq", "wk", "wv")
                continue
            plain, rounded = np.asarray(params[name][l]), np.asarray(low[name][l])
            assert plain.shape == rounded.shape and 0 < np.abs(plain - rounded).max() < 0.08, (name, l)
    assert params["wo"][0].shape == (256, 64) and params["wo"][1].shape == (256, 64)
    assert params["w_in"][0].shape == (64, 2 * 256 + 2 * 8 + 16) and params["w_in"][1] is None
    for name in ("w_in", "conv_w", "conv_b", "a_log", "dt_bias", "d", "norm"):
        assert low[name] is params[name]
    np.testing.assert_array_equal(np.asarray(params["lm_head"]), np.asarray(params["embed"]).T)
    assert np.abs(np.asarray(low["lm_head"]) - np.asarray(params["lm_head"])).max() > 0


def test_the_reference_draws_the_programs_weights():
    """The same table of keys: every leaf of models/granite_hybrid.init_params
    equals the reference's, bit for bit (float32 and bfloat16)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.models import granite_hybrid
    from distributed_llm_inference_tpu.models.registry import get_model_config

    config = _tiny()
    ref = manifest.load_module("reference", config["reference"])
    assert ref.LEAF_KEY == granite_hybrid.LEAF_KEYS
    for dtype in ("float32", "bfloat16"):
        cfg = get_model_config("test-granite-tiny", dtype=dtype)
        ours = granite_hybrid.init_params(cfg, jax.random.PRNGKey(11))
        theirs = ref.make_params(config, 11, jnp.dtype(dtype))
        np.testing.assert_array_equal(np.asarray(ours["embed"], np.float32),
                                      np.asarray(theirs["embed"], np.float32))
        im = ia = 0
        for l, kind in enumerate(cfg.layer_types):
            lp = ours["layers"]["mamba" if kind == "mamba" else "attn"]
            i = im if kind == "mamba" else ia
            if kind == "mamba":
                names = ("w_in", "conv_w", "conv_b", "a_log", "dt_bias", "d", "norm", "wo")
                pairs = [(lp[n][i], theirs[n][l]) for n in names]
                im += 1
            else:
                w = np.asarray(lp["w_in"][i], np.float32)
                cut = np.cumsum([theirs[n][l].shape[1] for n in ("wq", "wk")])
                pairs = list(zip(np.split(w, cut, axis=1),
                                 (theirs[n][l] for n in ("wq", "wk", "wv"))))
                pairs.append((lp["wo"][i], theirs["wo"][l]))
                ia += 1
            pairs += [(ours["layers"]["ffn"][n][l], theirs[n][l])
                      for n in ("w_gate", "w_up", "w_down")]
            for got, want in pairs:
                np.testing.assert_array_equal(np.asarray(got, np.float32),
                                              np.asarray(want, np.float32))


# ---- the manifest and the configuration's file -------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_four_metrics():
    man = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # (the eighth configuration and the ninth cell: later PRs append after them)
    assert man["configs"][7]["name"] == CONFIG and man["workloads"][8]["name"] == CELL
    granite = man["configs"][7]
    names = [m["name"] for m in man["per_layer"]]  # looked up: later PRs append after them
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 4] == NEW_METRICS
    assert granite["reduced"] == []
    assert granite["source"] == \
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    cells = {w["name"]: w for w in man["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG, "traffic": "batch-closed",
                           "chips": 1}
    assert len(cells[CELL]["why"]) <= 200 and len(granite["why"]) <= 200
    assert len(man["configs"]) >= 8 and len(man["workloads"]) >= 9
    assert all(w["chips"] == 1 for w in man["workloads"])
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "tpot_ms_p50"
        assert set(by_name[name]) == {"name", "unit", "better", "source", "layer", "moves",
                                      "workloads"}
    assert by_name["ssm_scan_roofline"]["layer"] == by_name["linear_attn_roofline"]["layer"]
    assert by_name["ssm_mix_ms_per_step"]["layer"] == by_name["conv_mix_ms_per_step"]["layer"]
    assert by_name["ssm_step_roofline"]["layer"] == by_name["step_weight_roofline"]["layer"]
    assert by_name["ssm_state_rows_mean"]["layer"] == by_name["batch_rows_mean"]["layer"]
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"], name
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_p50", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == \
        set(LIST_LESS) | set(NEW_METRICS) | set(JOINED) | {n for n in WORKER_TIMED if CELL in by_name[n]["workloads"]}
    for other in ACCEPTED:  # nothing an accepted cell reports has changed
        assert not set(NEW_METRICS) & {m["name"] for m in manifest.Cell(man, other).per_layer}
    # the traffic file olmo2-batch runs, unedited: two models read one mix
    assert cell.traffic == manifest.Cell(man, "olmo2-batch").traffic
    assert "check" not in cell.traffic  # (the harness's own sample)
    assert cell.load == {"loop": "closed", "clients": 128}
    flags = cell.config["serving"]["flags"]
    assert cell.load["clients"] == 2 * int(flags[flags.index("--continuous") + 1])
    own = manifest.load_json(os.path.join(BENCH, "cells", f"{CELL}.json"))
    # the sets as read at the derived width, each spread the arithmetic of its
    # values, the first the derived 640's own and under half the bound
    import statistics

    sets = own["steadiness"]["sets"]
    assert len(sets) >= 2 and sets[0][2] < 3.5
    for _, values, spread in sets:
        q = statistics.quantiles(values, n=4)
        assert len(values) == 6
        assert spread == pytest.approx(100 * (q[2] - q[0]) / statistics.median(values), abs=2e-3)
    assert own["memory"]["after_warmup_pct"] >= 65
    manifest.load_module("reference", cell.config["reference"])


def test_the_configuration_keeps_every_published_number():
    config = _config()
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
        assert config["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if config.get(k, "absent") != v} == set()
    assert config["reduced"] == [] and "nothing is cut" in config["reduced_why"]
    kinds = config["layer_types"]
    assert len(kinds) == config["num_hidden_layers"] == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [5, 15, 25, 35]
    assert set(kinds) == {"mamba", "attention"}
    for key in ("assumed", "served", "deployment", "check_why", "reduced_why"):
        assert config[key], key
    said = " ".join(config["assumed"])
    for what in ("float32", "uniform on [1, 16]", "log-uniform on [0.001, 0.1]",
                 "inverse softplus", "D = 1", "word-level", "bos 1, eos 2, pad 0",
                 "bfloat16"):
        assert what in said, what
    for what in ("one TPU v5e chip", "whole", "nothing is divided"):
        assert what in config["deployment"], what
    assert set(config["check"]) == {"mismatch", "mean", "worst"}
    flags = config["serving"]["flags"]
    for flag, value in (("--continuous", "64"), ("--continuous-max-seq", "2048"),
                        ("--kv-block-size", "64"), ("--kv-pool-blocks", "2048"),
                        ("--state-snapshots", "16"), ("--prefix-cache", "8"),
                        ("--attn-impl", "pallas"), ("--max-tokens-cap", "1024")):
        assert flags[flags.index(flag) + 1] == value, flag
    assert "--no-kv-shadow" in flags and "--warmup" in flags
    for reason in ("--continuous 64", "step width", "--kv-pool-blocks 2048", "context",
                   "--state-snapshots 16", "--kv-block-size 64", "bytes_in_use"):
        assert config["served"][reason], reason
    assert config["serving"]["trace"]["step_modules"] == {
        "mixed_step_ragged": 1, "decode_slots_paged": None}


def test_the_files_arithmetic_and_the_registrys_sizes():
    """The file's sizes are the registry's, and the bytes `reduced_why` and
    `served` state are the program's own leaves'."""
    import jax

    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.engine.scheduler import step_width
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models import granite_hybrid
    from harness import serve

    config = _config()
    cfg = serve.register_config(config)
    assert (cfg.arch, cfg.n_layers) == ("granite_hybrid", 40)
    assert list(cfg.layer_types) == config["layer_types"]
    assert granite_hybrid.stack_depths(cfg) == {"mamba": 36, "attn": 4}
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim, cfg.vocab_size,
            cfg.norm_eps, cfg.max_seq_len) == (
        config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
        64, config["shared_intermediate_size"], config["vocab_size"], config["rms_norm_eps"],
        config["max_position_embeddings"])
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.conv_kernel,
            cfg.conv_bias) == (
        config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"],
        config["mamba_n_groups"], config["mamba_d_conv"], config["mamba_conv_bias"])
    assert cfg.ssm_heads * cfg.ssm_head_dim == config["mamba_expand"] * cfg.dim
    assert (cfg.embed_multiplier, cfg.residual_multiplier, cfg.attn_scale_override,
            cfg.logits_divider) == (
        config["embedding_multiplier"], config["residual_multiplier"],
        config["attention_multiplier"], config["logits_scaling"])
    assert cfg.tie_embeddings and cfg.recurrent and not cfg.state_tails
    assert cfg.conv_layers == cfg.linear_layers and not cfg.sparse_layers
    shapes = jax.eval_shape(lambda: M.init_params(cfg.replace(dtype="bfloat16"),
                                                  jax.random.PRNGKey(0)))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    D, F, V = 2048, 8192, 100352
    mamba = D * 8512 + 5 * 4352 + 3 * 64 + 4096 + 4096 * D
    attention = D * 48 * 64 + 2048 * D
    ffn = 3 * D * F
    assert count == 36 * (mamba + ffn) + 4 * (attention + ffn) + V * D + 81 * D
    assert round((mamba + ffn) / 1e6, 2) == 76.18 and round((attention + ffn) / 1e6, 2) == 60.82
    assert round(count / 1e6) == 3191
    for said in ("76.18M", "60.82M", "205.52M", "3,191M", "6.38 GB"):
        assert said in config["reduced_why"], said
    flags = config["serving"]["flags"]
    slots, blocks, snaps = (int(flags[flags.index(f) + 1]) for f in (
        "--continuous", "--kv-pool-blocks", "--state-snapshots"))
    # the fleet's 64 tiles of 8 and the dense budget for prefill on top, as served
    assert step_width(cfg.replace(dtype="bfloat16"), slots, 8) == 640
    pool = jax.eval_shape(lambda: P.init_pool(cfg.replace(dtype="bfloat16"), blocks, 64,
                                              n_slots=slots, n_snapshots=snaps))
    assert pool["k"].shape == (4, blocks, 4, 64, 128)
    assert len(pool["lin"]) == len(pool["snap"]) == len(pool["conv"]) == len(pool["csnap"]) == 36
    assert pool["lin"][0].shape == (slots, 32, 128, 128) and pool["lin"][0].dtype == "float32"
    assert pool["conv"][0].shape == (slots, 3, 4352) and pool["conv"][0].dtype == "bfloat16"
    token = (pool["k"].size + pool["v"].size) * 2 / (blocks * 64)
    assert token == 8192  # 4 layers x K and V x 8 heads x 64 x 2 B
    live = (sum(a.size for a in pool["lin"]) * 4 + sum(a.size for a in pool["conv"]) * 2) / slots
    assert round(live / 1e6, 2) == 76.44  # 75.50 MB of matrix states + 0.94 MB of inputs
    kept = sum(a.size for a in pool["snap"]) * 4 + sum(a.size for a in pool["csnap"]) * 2
    assert round(kept / 1e9, 2) == 1.22
