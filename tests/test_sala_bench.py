"""What ISSUE 48 adds to the benchmark (cellbench/), run on the CPU: the new
cell rehearsed end to end at a tiny size through `cellbench/run.py` (the
harness as it stands; the tiny manifest and its data live under tests/data,
since nothing under cellbench/tests may change), the five new per-layer
readers on a hand-made trace and on the recorded `olmo2-chat` fixtures (a
program that has none of what they read: nothing, without raising), the
rooflines' arithmetic, what the 8-bit control rounds of this reference, the
manifest's appended entries, and the configuration's file against the
published one and against the registry.
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

TEST_MANIFEST = os.path.join(ROOT, "tests", "data", "BENCHMARK.sala.json")
CELL, CONFIG = "sala-docs-xlong", "minicpm-sala-9b-16l"
NEW_METRICS = ["linear_attn_ms_per_step", "sparse_select_ms_per_step",
               "sparse_attn_kv_roofline", "linear_attn_roofline", "sparse_selected_pct"]
ACCEPTED = ["olmo2-chat", "mistral-docs", "olmo2-batch", "kanana-docs-long", "sdar-batch",
            "lfm2-docs-long", "trinity-docs-xlong"]
JOINED = ["gen_late_ms_max", "queue_wait_ms_mean", "ttft_ms_p50", "ttft_ms_p90",
          "prefix_hit_pct", "slot_wait_ms_mean", "prefill_ms_mean",
          "steps_ahead_of_prefill_mean", "mixed_step_pct", "host_ms_per_step", "fetch_wait_pct",
          "scoped_device_pct", "attn_layer_ms_per_step", "ffn_ms_per_step",
          "head_sample_ms_per_step"]
# their bytes are a range's, or a formula that miscounts this stack; attended /
# walked is a bound here, not the kernels' grid (a tile walks its queries' union)
# PR 53's six read the worker's own counters in every cell they list
WORKER_TIMED = ["decode_step_ms_mean", "mixed_step_ms_mean", "launch_timed_pct",
                "decode_time_in_mixed_pct", "device_empty_wait_pct", "device_empty_host_pct"]
NOT_JOINED = ["attn_kv_roofline", "hybrid_attn_kv_roofline", "step_weight_roofline",
              "attn_grid_live_pct", "window_attn_kv_roofline", "conv_mix_ms_per_step"]
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
        os.path.join(ROOT, "tests", "data", "sala", "configs", "tiny-sala.json"))


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


# ---- the cell, rehearsed -----------------------------------------------------

def test_the_new_cell_runs_every_phase_at_a_tiny_size_and_refuses_a_cpu(tmp_path):
    """(at half the tiny cell's own rate: tests/bench_rehearsal.py says why)"""
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

SCOPES = {"vocabulary": ["attn", "linear_attn"], "programs": {
    "jit_mixed_step_ragged": {
        "%fusion.1": {"scope": ["attn", "sparse_select"], "mixed": 0},
        "%ragged_paged_attend.5": {"scope": ["attn"], "mixed": 0},
        "%fusion.3": {"scope": ["linear_attn", "linear_scan"], "mixed": 0},
        "%fusion.4": {"scope": ["linear_attn"], "mixed": 0},
        "%fusion.6": {"scope": ["ffn"], "mixed": 0}},
    "jit_decode_slots_paged": {
        "%fusion.1": {"scope": ["linear_attn", "linear_scan"], "mixed": 0},
        "%paged_flash_attend.2": {"scope": ["attn"], "mixed": 0},
        "%fusion.8": {"scope": ["attn", "sparse_select"], "mixed": 0},
        "%fusion.9": {"scope": ["ffn"], "mixed": 0}},
}}


def _hand_trace(tmp_path, fields=True, scopes=True):
    """A chunk launch (seq 7, 4 steps, 2 rows) and a mixed launch (seq 8: a
    decode row and a 100-token chunk) with their modules, kernels and
    scoped operations; launch 9's module ran after the trace. Microseconds.
    fields False: a program that writes `kv_tokens` alone on a launch span;
    scopes False: and no map beside the trace."""
    import cut_spans
    from jax.profiler import ProfileData

    device = {
        "XLA Modules": [("jit_decode_slots_paged(12)", 1000, 4000),
                        ("jit_mixed_step_ragged(11)", 5100, 1000)],
        "XLA Ops": [("%paged_flash_attend.2 = bf16[] custom-call()", 1000, 500),
                    ("%fusion.1 = f32[] fusion()", 1500, 800),
                    ("%fusion.8 = f32[] fusion()", 2300, 200),
                    ("%fusion.9 = f32[] fusion()", 2500, 2500),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 5100, 200),
                    ("%fusion.1 = f32[] fusion()", 5300, 100),
                    ("%fusion.3 = f32[] fusion()", 5400, 300),
                    ("%fusion.4 = f32[] fusion()", 5700, 100),
                    ("%fusion.6 = f32[] fusion()", 5800, 300)],
    }
    sala = lambda **kw: kw if fields else {}  # noqa: E731
    spans = [
        ("launch.chunk", 990, 20, {"prev": "plan", "seq": 7, "steps": 4, "kv_tokens": 32000,
                                   **sala(kv_tokens_visible=80000, state_rows=8, sparse_rows=8,
                                          decode_rows=2, prefill_chunks=0, prefill_tokens=0)}),
        ("launch.mixed", 1020, 30, {"prev": "plan", "seq": 8, "steps": 1, "kv_tokens": 8000,
                                    **sala(kv_tokens_visible=30000, state_rows=2, sparse_rows=2,
                                           decode_rows=1, prefill_chunks=1, prefill_tokens=100)}),
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


def _traced(tmp_path, config, counters=True):
    path = host_spans.find(str(tmp_path))
    kv = ('dli_attn_kv_tokens_total{{phase="mixed",state="visible"}} {}\n'
          'dli_attn_kv_tokens_total{{phase="chunk",state="visible"}} {}\n'
          'dli_attn_kv_tokens_total{{phase="mixed",state="selected"}} {}\n'
          'dli_attn_kv_tokens_total{{phase="chunk",state="selected"}} {}\n') if counters else ""
    other = 'dli_attn_kv_tokens_total{phase="mixed",state="attended"} 5\n'
    return Ctx(
        trace_dir=str(tmp_path), chunk_steps=4, peaks=PEAKS, config=config, window_s=8.0,
        trace=trace_reduce.reduce(path) if path else {"modules": {}, "ops": {}},
        before=scrape.parse(other + kv.format(100, 100, 50, 50)),
        after=scrape.parse(other + kv.format(30100, 80100, 8050, 32050)))


def test_the_scoped_readers_read_their_labels_at_any_depth(tmp_path):
    _hand_trace(tmp_path)
    ctx = _traced(tmp_path, _config())
    # 5 scheduler steps (a chunk of 4 and a mixed step); linear_attn: 800 us in
    # the chunk, 300 + 100 in the mixed step; sparse_select (under attn): 200 + 100
    assert read("linear_attn_ms_per_step", ctx) == pytest.approx(1.2 / 5)
    assert read("sparse_select_ms_per_step", ctx) == pytest.approx(0.3 / 5)
    # the accepted reader counts by the outermost label: the kernels' 700 us and the selection
    assert read("attn_layer_ms_per_step", ctx) == pytest.approx(1.0 / 5)
    assert read("sparse_selected_pct", ctx) == pytest.approx(100 * 40000 / 110000)


def test_the_rooflines_count_useful_work_of_the_matched_launches(tmp_path):
    _hand_trace(tmp_path)
    config = _config()
    sparse = manifest.load_module("roofline", "sparse_attention")
    assert sparse.sparse_layers(config) == 4
    # 4,096 bytes a position: 4 sparse layers x K and V x 2 heads x 128 x 2 B
    assert sparse.kv_bytes(config, 1) == 4096
    assert sparse.flops(config, 1) == 4 * 32 * 4 * 128
    # launches 7 and 8 matched: 40,000 selected positions over the kernels' 700 us
    tb, tc = 40000 * 4096 / 819e9, 40000 * 65536 / 197e12
    assert sparse.bound(config, 40000, PEAKS) == (pytest.approx(tb), "bandwidth")
    assert tb > tc
    got = read("sparse_attn_kv_roofline", _traced(tmp_path, config))
    assert got == pytest.approx(100 * tb / 700e-6)
    linear = manifest.load_module("roofline", "linear_attention")
    assert linear.linear_layers(config) == 12 and linear.state_bytes(config) == 2 ** 21
    chunk = {"state_rows": 8, "prefill_chunks": 0, "prefill_tokens": 0}
    mixed = {"state_rows": 2, "prefill_chunks": 1, "prefill_tokens": 100}
    assert linear.counts(chunk) == (8, 8, 8)
    assert linear.counts(mixed) == (2, 101, 1 + 100 * 101 / 2)
    nbytes = 12 * (10 * 2 * 2 ** 21 + 109 * 4 * 32 * 128 * 2)
    flops = 12 * 32 * (109 * 4 * 128 * 128 + (8 + 5051) * 4 * 128)
    least, which = linear.bound(config, [chunk, mixed], PEAKS)
    assert which == "bandwidth" and least == pytest.approx(nbytes / 819e9)
    assert flops / 197e12 < least
    # over the scan's 800 + 300 us in the traced step programs
    got = read("linear_attn_roofline", _traced(tmp_path, config))
    assert got == pytest.approx(100 * least / 1100e-6)
    assert 0 < got < 100


def test_the_new_readers_give_nothing_for_a_program_without_what_they_read(tmp_path):
    """The parent commit (no scope map's labels, no counter states, `kv_tokens`
    alone on a launch span), another family's configuration, a run without a
    trace, and the recorded olmo2-chat fixtures: the metric is left out, and
    nothing raises."""
    _hand_trace(tmp_path / "parent", fields=False, scopes=False)
    ctx = _traced(tmp_path / "parent", _config(), counters=False)
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name
    _hand_trace(tmp_path / "fields", fields=False)
    ctx = _traced(tmp_path / "fields", _config(), counters=False)
    for name in ("sparse_attn_kv_roofline", "linear_attn_roofline", "sparse_selected_pct"):
        assert read(name, ctx) is None, name
    _hand_trace(tmp_path / "other")
    for other in ("olmo2-7b-16l", "lfm2-24b-a2b-9l", "trinity-large-ep8-5l"):
        config = manifest.load_json(os.path.join(BENCH, "configs", f"{other}.json"))
        ctx = _traced(tmp_path / "other", config)
        assert read("sparse_attn_kv_roofline", ctx) is None
        assert read("linear_attn_roofline", ctx) is None
    ctx = _traced(tmp_path / "nothing-here", _config(), counters=False)
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
            ctx = _traced(tmp_path / cut, config, counters=False)
            assert ctx.trace["modules"], cut
            for name in NEW_METRICS:
                assert read(name, ctx) is None, (cut, name)


@pytest.mark.parametrize("name", LIST_LESS + NEW_METRICS + [
    n for n in JOINED if n in ("prefix_hit_pct", "scoped_device_pct",
                               "attn_layer_ms_per_step", "ffn_ms_per_step")])
def test_a_reader_of_the_cell_reads_the_tiny_configuration(tmp_path, name):
    """Every reader the cell reports off a trace or a counter, on the tiny
    configuration's file: the keys it asks of a configuration are in a
    minicpm_sala file."""
    _hand_trace(tmp_path)
    ctx = _traced(tmp_path, _tiny())
    extra = ('dli_sched_step_tokens_total{{kind="prefill"}} {}\n'
             'dli_ragged_launches_total{{phase="mixed"}} {}\n'
             'dli_ragged_launches_total{{phase="chunk"}} {}\n')
    ctx.before = {**ctx.before, **scrape.parse(extra.format(100, 10, 2))}
    ctx.after = {**ctx.after, **scrape.parse(extra.format(1200, 50, 6))}
    ctx.end_to_end = {"out_tok_s": 22.0}

    class Res:
        prompt_tokens, cached_tokens = 90, 64

    ctx.ok = [Res]
    got = read(name, ctx)
    assert got is not None and got >= 0, name


def test_the_control_rounds_both_mixers_matrices_of_this_reference():
    """tools/control.py quantizes by leaf name: every mixer's wq, wk, wv, wo,
    the FFN's w_gate / w_up / w_down and lm_head; the gates and norms stay."""
    import control
    import jax.numpy as jnp
    import numpy as np

    config = _tiny()
    ref = manifest.load_module("reference", config["reference"])
    params = ref.make_params(config, 7, jnp.float32)
    low = control.quantized(params, control.BITS)
    for name in control.MATRICES:
        assert isinstance(low[name], control.QuantizedLeaf), name
        for l in range(config["num_hidden_layers"]):
            plain, rounded = np.asarray(params[name][l]), np.asarray(low[name][l])
            assert plain.shape == rounded.shape and 0 < np.abs(plain - rounded).max() < 0.08, (name, l)
    assert params["wk"][0].shape == (64, 32) and params["wk"][1].shape == (64, 64)
    for name in ("wg", "q_norm", "o_norm"):
        assert low[name] is params[name]
    assert np.abs(np.asarray(low["lm_head"]) - np.asarray(params["lm_head"])).max() > 0


# ---- the manifest and the configuration's file -------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_five_metrics():
    man = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # (the seventh configuration and the eighth cell: later PRs append theirs)
    assert man["configs"][6]["name"] == CONFIG and man["workloads"][7]["name"] == CELL
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 5] == NEW_METRICS
    assert man["configs"][6]["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert man["configs"][6]["source"].endswith("openbmb/MiniCPM-SALA/blob/main/config.json")
    cells = {w["name"]: w for w in man["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG, "traffic": "docs-repeat-xlong",
                           "chips": 1}
    assert len(cells[CELL]["why"]) <= 200 and len(man["configs"][6]["why"]) <= 200
    assert len(man["configs"]) >= 7 and len(man["workloads"]) >= 8
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "tpot_ms_p50"
        assert set(by_name[name]) == {"name", "unit", "better", "source", "layer", "moves",
                                      "workloads"}
    assert by_name["sparse_attn_kv_roofline"]["layer"] == by_name["attn_kv_roofline"]["layer"]
    assert by_name["linear_attn_ms_per_step"]["layer"] == by_name["conv_mix_ms_per_step"]["layer"]
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
    # the traffic file trinity-docs-xlong runs, unedited: two models, one pinned trace
    assert cell.traffic == manifest.Cell(man, "trinity-docs-xlong").traffic
    assert cell.traffic["check"] == {"long_tokens": 12400, "repeat_extra_tokens": 200}
    assert cell.load["loop"] == "open" and cell.load["rate"] > 0
    own = manifest.load_json(os.path.join(BENCH, "cells", f"{CELL}.json"))
    swept = [step[0] for step in own["sweep"]["steps"]]
    assert any(r == pytest.approx(own["knee"], rel=0.01) for r in swept)
    # (ISSUE 48's fallback: 0.8 x the knee cannot print a median, so the cell
    # runs at a swept step, none above the knee)
    assert own["load"]["rate"] <= own["knee"] * 1.005
    assert any(r == pytest.approx(own["load"]["rate"], rel=0.01)
               for r in swept + [0.8 * own["knee"]])
    sets = [s for s in own["steadiness"]["sets"]
            if s[0] == pytest.approx(own["load"]["rate"], rel=0.01)]
    assert len(sets) >= 2 and all(len(s[1]) == 6 and s[2] <= 3.5 for s in sets)
    manifest.load_module("reference", cell.config["reference"])


def test_the_configuration_keeps_every_published_number_but_the_cuts():
    config = _config()
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == set(config["reduced"])
        for key in config["reduced"]:
            assert config["published"][key] == row["config"][key], key
    assert config["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 3 + (
        ["minicpm4"] + ["lightning-attn"] * 3) * 3
    assert config["num_hidden_layers"] == 16
    assert config["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "window_size": 2048, "init_blocks": 1, "dense_len": 8192}
    for key in ("assumed", "served", "deployment", "check_why", "reduced_why"):
        assert config[key], key
    assert set(config["reduced_why"]) == set(config["reduced"])
    said = " ".join(config["assumed"])
    for what in ("sparse_config", "arXiv:2506.07900", "count inside the 64", "lower block",
                 "2^(-8 h / 32)", "float32", "half-rotation", "sigmoid(g)", "scale_emb 12",
                 "1.4 / sqrt(32)", "dim_model_base", "bfloat16", "exact one"):
        assert what in said, what
    for what in ("two pipeline stages", "16 of 32 layers", "No layer is divided"):
        assert what in config["deployment"], what
    assert set(config["check"]) == {"mismatch", "mean", "worst"}
    flags = config["serving"]["flags"]
    for flag, value in (("--continuous", "16"), ("--continuous-max-seq", "66048"),
                        ("--kv-block-size", "64"), ("--prefix-cache", "8"),
                        ("--attn-impl", "pallas"), ("--max-tokens-cap", "1024")):
        assert flags[flags.index(flag) + 1] == value, flag
    assert "--no-kv-shadow" in flags
    blocks = flags[flags.index("--kv-pool-blocks") + 1]
    snaps = flags[flags.index("--state-snapshots") + 1]
    assert int(blocks) * 64 >= 4608 * 128 and int(snaps) >= 24
    for reason in ("--continuous 16", f"--kv-pool-blocks {blocks}", "context",
                   f"--state-snapshots {snaps}", "--kv-block-size 64"):
        assert config["served"][reason], reason


def test_reduced_whys_arithmetic_and_the_registrys_sizes():
    """The file's sizes are the registry's, and the bytes `reduced_why` and
    `served` state are the program's own leaves'."""
    import jax

    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models import minicpm_sala
    from distributed_llm_inference_tpu.models.registry import get_model_config
    from harness import serve

    config = _config()
    cfg = serve.register_config(config)
    pub = get_model_config("minicpm-sala")
    assert pub.n_layers == 32 and list(pub.layer_types) == config["published"]["mixer_types"]
    assert (len(pub.attn_layers), len(pub.linear_layers)) == (8, 24)
    assert (cfg.arch, cfg.n_layers) == ("minicpm_sala", 16)
    assert list(cfg.layer_types) == config["mixer_types"]
    assert minicpm_sala.stack_depths(cfg) == {"sparse": 4, "linear": 12}
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim, cfg.linear_heads,
            cfg.vocab_size, cfg.rope_theta, cfg.norm_eps) == (
        config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], config["intermediate_size"], config["lightning_nh"],
        config["vocab_size"], config["rope_theta"], config["rms_norm_eps"])
    sp = config["sparse_config"]
    assert (cfg.sparse_kernel, cfg.sparse_stride, cfg.sparse_block, cfg.sparse_topk,
            cfg.sparse_window, cfg.sparse_init_blocks, cfg.sparse_dense_len) == (
        sp["kernel_size"], sp["kernel_stride"], sp["block_size"], sp["topk"],
        sp["window_size"], sp["init_blocks"], sp["dense_len"])
    assert cfg.embed_multiplier == config["scale_emb"] == 12
    assert cfg.residual_multiplier == pytest.approx(
        config["scale_depth"] / config["mup_denominator"] ** 0.5)
    assert cfg.logits_divider == config["hidden_size"] / config["dim_model_base"] == 16
    assert not cfg.tie_embeddings and cfg.use_qk_norm and cfg.recurrent
    shapes = jax.eval_shape(lambda: M.init_params(cfg.replace(dtype="bfloat16"),
                                                  jax.random.PRNGKey(0)))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    D, F, V = 4096, 16384, 73448
    sparse = D * (3 * 4096 + 2 * 256) + 3 * D * F
    linear = 5 * D * 4096 + 3 * D * F
    norms = 2 * 16 * D + D + 16 * 2 * 128 + 12 * 4096
    assert count == 4 * sparse + 12 * linear + 2 * V * D + norms
    assert round(sparse / 1e6, 2) == 253.76 and round(linear / 1e6, 2) == 285.21
    why = config["reduced_why"]["num_hidden_layers"]
    for said in ("52.43M", "201.33M", "253.76M", "83.89M", "285.21M", "601.7M", "9,476.8M",
                 "18.95 GB", "4,437.6M", "5,039.3M", "10.08 GB"):
        assert said in why, said
    assert round((8 * sparse + 24 * linear + 2 * V * D) / 1e6, 1) == 9476.8
    assert 10.07e9 < 2 * count < 10.09e9
    flags = config["serving"]["flags"]
    slots, blocks, snaps = (int(flags[flags.index(f) + 1]) for f in (
        "--continuous", "--kv-pool-blocks", "--state-snapshots"))
    pool = jax.eval_shape(lambda: P.init_pool(cfg.replace(dtype="bfloat16"), blocks, 64,
                                              n_slots=slots, n_snapshots=snaps))
    assert pool["k"].shape == (4, blocks, 2, 64, 128)
    assert len(pool["ck"]) == 4 and pool["ck"][0].shape == (blocks, 16, 128)
    assert len(pool["lin"]) == len(pool["snap"]) == 12
    assert pool["lin"][0].shape == (slots, 32, 128, 128) and pool["lin"][0].dtype == "float32"
    assert pool["snap"][0].shape == (snaps, 32, 128, 128)
    token = (pool["k"].size + pool["v"].size) * 2 / (blocks * 64)
    keys = sum(a.size for a in pool["ck"]) * 2 / (blocks * 64)
    # bytes a token: K/V; the compressed keys' leaf, half of whose rows pad a
    # block's 8 keys to a whole tile (ISSUE 50): the configuration's file
    # counts the 128 bytes of keys alone
    assert (token, keys) == (4096, 256)
    state = sum(a.size for a in pool["lin"]) * 4 / slots
    assert round(state / 1e6, 1) == 25.2
    total = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    assert f"{(blocks * 64 * 4224) / 1e9:.2f} GB" in config["served"][f"--kv-pool-blocks {blocks}"]
    assert 0.80 * 16.9e9 < 2 * count + total < 0.88 * 16.9e9
