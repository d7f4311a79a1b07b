"""What ISSUE 40 adds to the benchmark (cellbench/), run on the CPU: the new
cell rehearsed end to end at a tiny size through `cellbench/run.py` (the
harness as it stands; the reference read through `harness/ref_child.py`
unchanged), the three new per-layer readers, every list-less reader and every
reader of a list the cell joined on the tiny configuration, what the new
readers give for a program or a configuration without what they read
(nothing, without raising), what the 8-bit control rounds of this reference,
the manifest's appended entries, and the configuration's file against the
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

from harness import manifest, scrape  # noqa: E402

TEST_MANIFEST = os.path.join(BENCH, "tests", "data", "BENCHMARK.trinity.json")
CELL, CONFIG = "trinity-docs-xlong", "trinity-large-ep8-5l"
NEW_METRICS = ["window_attn_kv_roofline", "window_kv_held_pct", "moe_held_pair_pct"]
ACCEPTED = ["olmo2-chat", "mistral-docs", "olmo2-batch", "kanana-docs-long", "sdar-batch",
            "lfm2-docs-long"]
JOINED = ["gen_late_ms_max", "queue_wait_ms_mean", "slot_wait_ms_mean", "ttft_ms_p50",
          "ttft_ms_p90", "prefix_hit_pct", "prefill_ms_mean", "steps_ahead_of_prefill_mean",
          "mixed_step_pct", "host_ms_per_step", "fetch_wait_pct", "attn_grid_live_pct",
          "moe_ms_per_step", "moe_expert_roofline", "moe_experts_touched_pct"]
SCOPES = ["moe_layer_ms_per_step", "scoped_device_pct", "attn_layer_ms_per_step",
          "ffn_ms_per_step", "head_sample_ms_per_step"]
LIST_LESS = ["batch_rows_mean", "prefill_tok_pct", "step_device_ms_p50",
             "attn_kernel_ms_per_step", "device_idle_pct"]
# PR 53's six read the worker's own counters in every cell they list
WORKER_TIMED = ["decode_step_ms_mean", "mixed_step_ms_mean", "launch_timed_pct",
                "decode_time_in_mixed_pct", "device_empty_wait_pct", "device_empty_host_pct"]
NOT_JOINED = ["step_weight_roofline", "attn_kv_roofline", "hybrid_attn_kv_roofline",
              "mla_attn_roofline", "conv_mix_ms_per_step"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
COUNTERS = (
    'dli_worker_phase_seconds_total{{phase="fetch_wait"}} {}\n'
    'dli_worker_phase_seconds_total{{phase="wait_work"}} {}\n'
    'dli_worker_phase_seconds_total{{phase="plan"}} {}\n'
    'dli_admission_wait_seconds_sum {}\ndli_admission_wait_seconds_count {}\n'
    'dli_queue_wait_seconds_sum {}\ndli_queue_wait_seconds_count {}\n'
    'dli_launch_steps_ahead_sum{{phase="mixed"}} {}\ndli_launch_steps_ahead_count{{phase="mixed"}} {}\n'
    'dli_attn_kv_tokens_total{{state="attended"}} {}\ndli_attn_kv_tokens_total{{state="walked"}} {}\n'
    'dli_moe_pairs_total{{where="held"}} {}\ndli_moe_pairs_total{{where="routed"}} {}\n'
)
GROUPS = ('dli_kv_group_blocks{{group="global",state="live"}} {}\n'
          'dli_kv_group_blocks{{group="global",state="cached"}} {}\n'
          'dli_kv_group_blocks{{group="global",state="free"}} 5\n'
          'dli_kv_group_blocks{{group="window",state="live"}} {}\n'
          'dli_kv_group_blocks{{group="window",state="cached"}} {}\n')


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _config():
    return manifest.load_json(os.path.join(BENCH, "configs", f"{CONFIG}.json"))


def _tiny():
    return manifest.load_json(os.path.join(BENCH, "tests", "data", "configs", "tiny-trinity.json"))


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


# ---- the cell, rehearsed -----------------------------------------------------

def test_the_new_cell_runs_every_phase_at_a_tiny_size_and_refuses_a_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest", TEST_MANIFEST,
         "--platform", "cpu", "--workload", CELL, "--seed", "4242424242",
         "--seconds", "8", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    out = p.stdout
    assert p.returncode != 0 and "the device is not a TPU" in out, out[-3000:] + p.stderr[-2000:]
    assert "server ready in" in out and "window:" in out and "reference child:" in out
    assert ", 0 failed" in out.split("window:")[1].splitlines()[0]
    hit = int(out.split("repeat prefix_cached_tokens=")[1].split()[0])
    # 17 blocks of 4: eight windows and a half deep, the row's window blocks
    # given back all along the way
    assert hit == 68
    assert out.count("-> ok") == 3 and "FAIL" not in out and "NOT COMPARED" not in out
    assert not out.strip().splitlines()[-1].startswith("{")


# ---- the readers ---------------------------------------------------------------

def _hand_trace(tmp_path, kinds=True):
    """test_lfm2_bench's hand-made trace, the launch spans carrying the counts
    per layer kind (kinds False: a program that writes `kv_tokens` alone)."""
    import cut_spans
    from jax.profiler import ProfileData

    def st(seq, steps, g, w):
        out = {"prev": "plan", "seq": seq, "steps": steps, "kv_tokens": g + 4 * w}
        if kinds:
            out.update(kv_tokens_global=g, kv_tokens_window=w)
        return out

    device = {
        "XLA Modules": [("jit_decode_slots_paged(12)", 1000, 4000),
                        ("jit_mixed_step_ragged(11)", 5100, 1000)],
        "XLA Ops": [("%paged_flash_attend.2 = bf16[] custom-call()", 1000, 500),
                    ("%routed_expert_matmul.4 = f32[] custom-call()", 1500, 1800),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 5100, 200),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 6400, 100)],
    }
    spans = [
        ("launch.chunk", 990, 20, st(7, 16, 64000, 16000)),
        ("launch.mixed", 1020, 30, st(8, 1, 9000, 4000)),
        ("fetch.chunk", 1060, 3990, {"prev": "plan", "seq": 7}),
        ("launch.mixed", 5090, 20, st(9, 1, 700, 700)),
        ("fetch.mixed", 5110, 1000, {"prev": "dispatch", "seq": 8}),
    ]
    lines = {ln: [(n, s * 1000, d * 1000) for n, s, d in evs] for ln, evs in device.items()}
    text = cut_spans.xspace_text(
        "/device:TPU:0", lines, [(n, s * 1000, d * 1000, a) for n, s, d, a in spans], 0)
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(text)))


def _traced(tmp_path, config):
    zeros, some = [0] * 13, [3.0, 4.0, 1.0, 0.6, 5, 0.2, 5, 80, 5, 9000, 30000, 125, 1000]
    return Ctx(
        trace_dir=str(tmp_path), chunk_steps=16, peaks=PEAKS, config=config, window_s=8.0,
        end_to_end={"out_tok_s": 22.0},
        trace={"modules": {"jit_decode_slots_paged": [4000e-6],
                           "jit_mixed_step_ragged": [1000e-6]},
               "ops": {"%paged_flash_attend.2": 500e-6, "%ragged_paged_attend.5": 300e-6,
                       "%routed_expert_matmul.4": 1800e-6},
               "busy_s": 0.005, "window_s": 0.008},
        scrapes=[scrape.parse(GROUPS.format(30, 70, 10, 15)),
                 scrape.parse(GROUPS.format(50, 150, 20, 20))],
        before=scrape.parse('dli_sched_step_tokens_total{kind="prefill"} 100\n'
                            'dli_ragged_launches_total{phase="mixed"} 10\n'
                            'dli_ragged_launches_total{phase="chunk"} 2\n'
                            'dli_moe_experts_touched_total{phase="mixed"} 10\n'
                            'dli_moe_expert_slots_total{phase="mixed"} 40\n'
                            + COUNTERS.format(*zeros)),
        after=scrape.parse('dli_sched_step_tokens_total{kind="prefill"} 1200\n'
                           'dli_ragged_launches_total{phase="mixed"} 50\n'
                           'dli_ragged_launches_total{phase="chunk"} 6\n'
                           'dli_moe_experts_touched_total{phase="mixed"} 30\n'
                           'dli_moe_expert_slots_total{phase="mixed"} 120\n'
                           + COUNTERS.format(*some)))


def test_the_attention_roofline_counts_each_kind_of_layer_by_its_own_positions(tmp_path):
    _hand_trace(tmp_path)
    config = _config()
    windowed = manifest.load_module("roofline", "windowed_attention")
    assert windowed.layers(config) == {"global": 1, "window": 4}
    # 4,096 bytes a position and layer: K and V x 8 heads x 128 x 2 B
    assert windowed.kv_bytes(config, 1) == 4096
    assert windowed.flops(config, 1) == 48 * 4 * 128
    # launches 7 and 8 matched: (64,000 + 9,000) x 1 + (16,000 + 4,000) x 4 positions x layers
    # over the kernels' 700 us in them
    positions = 73000 + 4 * 20000
    assert windowed.positions(config, {"kv_tokens_global": 9000, "kv_tokens_window": 4000}) == 25000
    least = positions * 4096 / 819e9
    assert windowed.bound(config, positions, PEAKS) == (pytest.approx(least), "bandwidth")
    got = read("window_attn_kv_roofline", _traced(tmp_path, config))
    assert got == pytest.approx(100 * least / 700e-6)
    # the published 60 layers: 15 global, 45 window
    whole = {**config, "layer_types": config["published"]["layer_types"]}
    assert windowed.layers(whole) == {"global": 15, "window": 45}


def test_the_pools_share_and_the_held_pairs_read_the_programs_counters(tmp_path):
    ctx = _traced(tmp_path, _config())
    # (10 + 15) / (30 + 70) and (20 + 20) / (50 + 150): the mean of the scrapes' shares
    assert read("window_kv_held_pct", ctx) == pytest.approx((25.0 + 20.0) / 2)
    assert read("moe_held_pair_pct", ctx) == pytest.approx(12.5)


def test_the_new_readers_give_nothing_for_a_program_without_what_they_read(tmp_path):
    """The parent commit (no counter, no gauge, `kv_tokens` alone on a launch
    span), a configuration of one kind of layer, a run without a trace: the
    metric is left out, and nothing raises."""
    _hand_trace(tmp_path, kinds=False)
    ctx = _traced(tmp_path, _config())
    ctx.before = ctx.after = scrape.parse(
        'dli_kv_pool_blocks_free 9\ndli_moe_expert_slots_total{phase="mixed"} 4\n')
    ctx.scrapes = [ctx.before, ctx.after]
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name
    dense = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "serving": {"trace": {
                 "step_modules": {"mixed_step_ragged": 1, "decode_slots_paged": None},
                 "attention_kernels": ["paged_flash_attend"]}}}
    assert read("window_attn_kv_roofline", _traced(tmp_path, dense)) is None
    lfm2 = manifest.load_json(os.path.join(BENCH, "configs", "lfm2-24b-a2b-9l.json"))
    assert read("window_attn_kv_roofline", _traced(tmp_path, lfm2)) is None
    ctx = _traced(tmp_path / "nothing-here", _config())
    ctx.before = ctx.after = {}
    ctx.scrapes = []
    ctx.trace = {"modules": {}, "ops": {}}
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name


@pytest.mark.parametrize("name", LIST_LESS + NEW_METRICS + [
    n for n in JOINED if n not in ("ttft_ms_p50", "ttft_ms_p90", "prefill_ms_mean",
                                   "moe_expert_roofline")])
def test_a_reader_of_the_cell_reads_the_tiny_configuration(tmp_path, name):
    """Every reader the cell reports, on the tiny configuration's file: the
    keys it asks of a configuration are in an afmoe file."""
    _hand_trace(tmp_path)
    ctx = _traced(tmp_path, _tiny())
    ctx.closed, ctx.late_ms = False, [0.4, 1.7]

    class Res:
        prompt_tokens, cached_tokens = 90, 68

    ctx.ok = [Res]
    got = read(name, ctx)
    assert got is not None and got >= 0, name


def test_the_control_rounds_the_attention_and_expert_matrices_of_this_reference():
    """tools/control.py quantizes by leaf name: wq, wk, wv, wo, the dense
    layer's and the expert banks' w_gate / w_up / w_down and lm_head; the gate
    projection, the shared expert, the routers and the norms stay."""
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
            if params[name][l] is None:
                continue
            plain, rounded = np.asarray(params[name][l]), np.asarray(low[name][l])
            assert plain.shape == rounded.shape and 0 < np.abs(plain - rounded).max() < 0.08, (name, l)
    assert params["w_gate"][0].shape == (64, 96) and params["w_gate"][1].shape == (4, 64, 32)
    for name in ("wg", "ws_gate", "w_router"):
        assert low[name] is params[name]
    assert np.abs(np.asarray(low["lm_head"]) - np.asarray(params["lm_head"])).max() > 0


# ---- the manifest and the configuration's file -------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_three_metrics():
    man = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    by_config = {c["name"]: c for c in man["configs"]}
    assert by_config[CONFIG]["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                            "layer_types", "num_experts", "vocab_size"]
    cells = {w["name"]: w for w in man["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG, "traffic": "docs-repeat-xlong",
                           "chips": 1}
    assert len(cells[CELL]["why"]) <= 200 and len(by_config[CONFIG]["why"]) <= 200
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW_METRICS:
        # (a later cell that runs a grouped pool and a share joined two of the
        # lists behind this one: ISSUE 55)
        assert by_name[name]["workloads"][0] == CELL and by_name[name]["moves"] == "tpot_ms_p50"
    assert by_name["window_attn_kv_roofline"]["workloads"] == [CELL]
    assert by_name["window_attn_kv_roofline"]["source"] == "device_trace"
    assert by_name["window_attn_kv_roofline"]["layer"] == "kernels"
    assert by_name["window_kv_held_pct"]["layer"] == "paged KV + prefix"
    assert by_name["moe_held_pair_pct"]["layer"] == "routed experts"
    for name in JOINED + SCOPES:
        assert CELL in by_name[name]["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"], name
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_p50", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == \
        set(LIST_LESS) | set(NEW_METRICS) | set(JOINED) | set(SCOPES) | {n for n in WORKER_TIMED if CELL in by_name[n]["workloads"]}
    for other in ACCEPTED:  # nothing an accepted cell reports has changed
        assert not set(NEW_METRICS) & {m["name"] for m in manifest.Cell(man, other).per_layer}
    # docs-repeat-long's trace at three times the length
    longer, long = cell.traffic, manifest.Cell(man, "kanana-docs-long").traffic
    assert longer["session"]["doc_tokens"] == {"dist": "lognormal", "median": 24576,
                                               "sigma": 0.5, "min": 8192, "max": 65536}
    assert longer["max_tokens"] == {"dist": "uniform", "min": 128, "max": 256}
    assert longer["check"] == {"long_tokens": 12400, "repeat_extra_tokens": 200}
    for key in ("generator", "load", "begin_at", "prompt_tokens", "sampling", "ramp_s"):
        assert longer[key] == long[key], key
    assert longer["session"]["turns"] == 4 and longer["session"]["think_s"] == long["session"]["think_s"]
    assert cell.load["loop"] == "open" and cell.load["rate"] > 0
    own = manifest.load_json(os.path.join(BENCH, "cells", f"{CELL}.json"))
    swept = [step[0] for step in own["sweep"]["steps"]]
    assert any(r == pytest.approx(own["knee"], rel=0.01) for r in swept)
    assert own["load"]["rate"] <= 0.8 * own["knee"] * 1.005
    manifest.load_module("reference", cell.config["reference"])


def test_the_configuration_keeps_every_published_number_but_the_cuts():
    config = _config()
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Large-Preview")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == set(config["reduced"])
        for key in config["reduced"]:
            assert config["published"][key] == row["config"][key], key
    assert config["layer_types"] == ["sliding_attention"] * 3 + ["full_attention",
                                                                 "sliding_attention"]
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert (config["num_experts"], config["expert_share"]["router_width"],
            config["expert_share"]["expert_lo"], config["vocab_size"]) == (32, 256, 0, 25024)
    for key in ("assumed", "served", "deployment", "check_why", "reduced_why"):
        assert config[key], key
    assert set(config["reduced_why"]) == set(config["reduced"])
    said = " ".join(config["assumed"])
    for what in ("mup_enabled", "four norms", "no position encoding", "half-rotation",
                 "sigmoid(g)", "i - j < 4096", "1e-20", "per-head RMSNorm", "expert bias",
                 "bfloat16", "PUBLISHED index"):
        assert what in said, what
    for what in ("8 chips", "data-parallel attention", "an eighth of the tokens", "5 layers"):
        assert what in config["deployment"], what
    assert set(config["check"]) == {"mismatch", "mean", "worst"}
    flags = config["serving"]["flags"]
    for flag, value in (("--continuous", "16"), ("--continuous-max-seq", "66048"),
                        ("--kv-block-size", "128"), ("--prefix-cache", "8"),
                        ("--attn-impl", "pallas"), ("--max-tokens-cap", "1024")):
        assert flags[flags.index(flag) + 1] == value, flag
    assert "--no-kv-shadow" in flags
    blocks = flags[flags.index("--kv-pool-blocks") + 1]
    for reason in ("--continuous 16", f"--kv-pool-blocks {blocks}", "context"):
        assert config["served"][reason], reason


def test_reduced_whys_arithmetic_and_the_registrys_sizes():
    """The file's sizes are the registry's, and the bytes `reduced_why` states
    are the program's own leaves'."""
    import jax

    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.models import afmoe
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models.registry import get_model_config
    from harness import serve

    config = _config()
    cfg = serve.register_config(config)
    pub = get_model_config("trinity-large-preview")
    assert (pub.n_layers, pub.first_k_dense, pub.n_experts, pub.vocab_size) == (60, 6, 256, 200192)
    assert list(pub.layer_types) == config["published"]["layer_types"]
    assert (cfg.arch, cfg.n_layers, cfg.first_k_dense) == ("afmoe", 5, 1)
    assert list(cfg.layer_types) == config["layer_types"]
    assert cfg.kv_groups == ("global", "window")
    assert (cfg.group_layers("global"), cfg.group_layers("window")) == ((3,), (0, 1, 2, 4))
    assert afmoe.stack_depths(cfg) == {"dense": 1, "moe": 4}
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_lo, cfg.n_experts_per_tok) == (
        config["expert_share"]["router_width"], config["num_experts"],
        config["expert_share"]["expert_lo"], config["num_experts_per_tok"])
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim, cfg.moe_ffn_dim) == (
        config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], config["intermediate_size"], config["moe_intermediate_size"])
    assert (cfg.attn_window, cfg.rope_theta, cfg.norm_eps, cfg.vocab_size) == (
        config["sliding_window"], config["rope_theta"], config["rms_norm_eps"],
        config["vocab_size"])
    assert cfg.router_score == "sigmoid" and not cfg.tie_embeddings and cfg.use_qk_norm
    assert cfg.embed_scale is config["mup_enabled"] and cfg.moe_renormalize is config["route_norm"]
    assert cfg.routed_scaling == config["route_scale"] == 2.448
    assert cfg.router_norm_eps == config["init"]["router_norm_eps"] == 1e-20
    shapes = jax.eval_shape(lambda: M.init_params(cfg.replace(dtype="bfloat16"),
                                                  jax.random.PRNGKey(0)))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    D, F, Fm, V = 3072, 12288, 3072, 25024
    attn, dense_ffn, expert = D * (6144 * 3 + 1024 * 2), 3 * D * F, 3 * D * Fm
    assert (attn, dense_ffn, expert) == (62914560, 113246208, 28311552)
    dense = attn + dense_ffn
    routed = attn + 33 * expert + D * 256
    norms = 4 * 5 * D + D + 5 * 2 * 128 + 4 * 256  # layer norms, the last, qk-norms, biases
    assert count == dense + 4 * routed + 2 * V * D + norms
    why = config["reduced_why"]["num_hidden_layers"]
    for said in ("62.91M", "113.25M", "28.31M", "905.97M", "0.79M", "176.2M", "998.0M",
                 "153.8M", "4,321.8M", "8.64 GB", "14.7 GB"):
        assert said in why, said
    assert round(dense / 1e6, 1) == 176.2 and round(routed / 1e6, 1) == 998.0
    assert round((dense + 4 * routed + 2 * V * D) / 1e6, 1) == 4321.8
    assert 8.64e9 < 2 * count < 8.65e9
    # the pool the flags ask for, as `served` states it: one number, two groups
    flags = config["serving"]["flags"]
    slots, blocks = (int(flags[flags.index(f) + 1]) for f in ("--continuous", "--kv-pool-blocks"))
    groups = P.group_blocks(cfg, blocks, 37, slots, 128)
    assert groups == (blocks, blocks // 4) == (4608, 1152)
    pool = jax.eval_shape(lambda: P.init_pool(cfg.replace(dtype="bfloat16"), groups, 128))
    assert pool["k"].shape == (1, 4608, 8, 128, 128) and pool["kw"].shape == (4, 1152, 8, 128, 128)
    # K and V of a block: 0.5 MiB in the global group (1 layer), 2 MiB in the window group (4)
    assert (pool["k"].size + pool["v"].size) * 2 == 4608 * 2**19
    assert (pool["kw"].size + pool["vw"].size) * 2 == 1152 * 2**21
    assert pool["routed"].shape == (2, 4, 33)  # the held experts, and the pairs routed elsewhere
    # a cached 24k document: 192 global blocks of 0.5 MiB + 33 window blocks of 2 MiB
    assert 192 * 0.5 + 33 * 2 == 162 and 192 * 2.5 == 480
