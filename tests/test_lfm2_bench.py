"""What ISSUE 34 adds to the benchmark (cellbench/), run on the CPU: the new
cell rehearsed end to end at a tiny size through `cellbench/run.py` (the
harness as it stands: the convolution / attention hybrid's reference read
through `harness/ref_child.py` unchanged), the new per-layer reader, every
list-less reader and every reader of a list the cell joined on the tiny
configuration, what the new reader gives for a program or a configuration
without what it reads (nothing, without raising), what the 8-bit control
rounds of this reference, the manifest's appended entries, and the
configuration's file against the published one and against the registry.
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

TEST_MANIFEST = os.path.join(BENCH, "tests", "data", "BENCHMARK.lfm2.json")
CELL, CONFIG = "lfm2-docs-long", "lfm2-24b-a2b-9l"
NEW_METRICS = ["hybrid_attn_kv_roofline"]
ACCEPTED = ["olmo2-chat", "mistral-docs", "olmo2-batch", "kanana-docs-long", "sdar-batch"]
# accepted lists this PR appended the cell to (data only)
JOINED = ["moe_ms_per_step", "moe_expert_roofline", "moe_experts_touched_pct", "prefix_hit_pct",
          "ttft_ms_p50", "ttft_ms_p90", "prefill_ms_mean"]
# ... and the scheduler's, the generator's and the kernels' grid's, which read this cell's counters as
# they read the dense cells' (the share of mixed steps is what decides its tpot_ms_p50)
JOINED_COUNTERS = ["mixed_step_pct", "host_ms_per_step", "fetch_wait_pct", "gen_late_ms_max",
                   "queue_wait_ms_mean", "slot_wait_ms_mean", "steps_ahead_of_prefill_mean",
                   "attn_grid_live_pct"]
# PR 53's six read the worker's own counters in every cell they list
WORKER_TIMED = ["decode_step_ms_mean", "mixed_step_ms_mean", "launch_timed_pct",
                "decode_time_in_mixed_pct", "device_empty_wait_pct", "device_empty_host_pct"]
LIST_LESS = ["batch_rows_mean", "prefill_tok_pct", "step_device_ms_p50",
             "attn_kernel_ms_per_step", "device_idle_pct"]
# ... and the program's scopes (PR 38): every one of the six lists this cell
SCOPES = ["scoped_device_pct", "attn_layer_ms_per_step", "ffn_ms_per_step", "moe_layer_ms_per_step",
          "conv_mix_ms_per_step", "head_sample_ms_per_step"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# a scrape of what JOINED_COUNTERS' readers read
COUNTERS = (
    'dli_worker_phase_seconds_total{{phase="fetch_wait"}} {}\n'
    'dli_worker_phase_seconds_total{{phase="wait_work"}} {}\n'
    'dli_worker_phase_seconds_total{{phase="plan"}} {}\n'
    'dli_admission_wait_seconds_sum {}\ndli_admission_wait_seconds_count {}\n'
    'dli_queue_wait_seconds_sum {}\ndli_queue_wait_seconds_count {}\n'
    'dli_launch_steps_ahead_sum{{phase="mixed"}} {}\ndli_launch_steps_ahead_count{{phase="mixed"}} {}\n'
    'dli_attn_kv_tokens_total{{state="attended"}} {}\ndli_attn_kv_tokens_total{{state="walked"}} {}\n'
)


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _config():
    return manifest.load_json(os.path.join(BENCH, "configs", f"{CONFIG}.json"))


def _tiny():
    return manifest.load_json(os.path.join(BENCH, "tests", "data", "configs", "tiny-lfm2.json"))


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


# ---- the cell, rehearsed -----------------------------------------------------

def test_the_new_cell_runs_every_phase_at_a_tiny_size_and_refuses_a_cpu(tmp_path):
    # (at a rate a machine shared with the suite's other workers still serves:
    # tests/bench_rehearsal.py says what the tiny cell's own 2 sessions/s did)
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
    assert hit == 64  # the repeat started from the tail of the document's fourth block
    assert out.count("-> ok") == 3 and "FAIL" not in out and "NOT COMPARED" not in out
    assert not out.strip().splitlines()[-1].startswith("{")


# ---- the readers ---------------------------------------------------------------

def _hand_trace(tmp_path):
    """A chunk launch (seq 7, 16 steps) and a mixed launch (seq 8) with their
    modules and kernels; launch 9's module ran after the trace. Microseconds."""
    import cut_spans
    from jax.profiler import ProfileData

    device = {
        "XLA Modules": [("jit_decode_slots_paged(12)", 1000, 4000),
                        ("jit_mixed_step_ragged(11)", 5100, 1000)],
        "XLA Ops": [("%paged_flash_attend.2 = bf16[] custom-call()", 1000, 500),
                    ("%routed_expert_matmul.4 = f32[] custom-call()", 1500, 1800),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 5100, 200),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 6400, 100)],
    }
    spans = [
        ("launch.chunk", 990, 20, {"prev": "plan", "seq": 7, "steps": 16, "kv_tokens": 64000}),
        ("launch.mixed", 1020, 30, {"prev": "plan", "seq": 8, "steps": 1, "kv_tokens": 9000}),
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


def _traced(tmp_path, config):
    return Ctx(
        trace_dir=str(tmp_path), chunk_steps=16, peaks=PEAKS, config=config, window_s=8.0,
        end_to_end={"out_tok_s": 22.0},
        trace={"modules": {"jit_decode_slots_paged": [4000e-6],
                           "jit_mixed_step_ragged": [1000e-6]},
               "ops": {"%paged_flash_attend.2": 500e-6, "%ragged_paged_attend.5": 300e-6},
               "busy_s": 0.005, "window_s": 0.008},
        before=scrape.parse('dli_sched_step_tokens_total{kind="prefill"} 100\n'
                            'dli_ragged_launches_total{phase="mixed"} 10\n'
                            'dli_ragged_launches_total{phase="chunk"} 2\n'),
        after=scrape.parse('dli_sched_step_tokens_total{kind="prefill"} 1200\n'
                           'dli_ragged_launches_total{phase="mixed"} 50\n'
                           'dli_ragged_launches_total{phase="chunk"} 6\n'))


def test_the_attention_roofline_counts_the_attention_layers_alone(tmp_path):
    _hand_trace(tmp_path)
    config = _config()
    hybrid = manifest.load_module("roofline", "hybrid_attention")
    assert hybrid.attention_layers(config) == 2 and hybrid.head_dim(config) == 64
    # 4,096 bytes a token: 2 attention layers x K and V x 8 heads x 64 x 2 B
    assert hybrid.kv_bytes(config, 1) == 4096
    assert hybrid.flops(config, 1) == 2 * 32 * 4 * 64
    # launches 7 and 8 matched: 73,000 positions over the kernels' 700 us in them
    least = 73000 * 4096 / 819e9
    assert hybrid.bound(config, 73000, PEAKS) == (pytest.approx(least), "bandwidth")
    got = read("hybrid_attn_kv_roofline", _traced(tmp_path, config))
    assert got == pytest.approx(100 * least / 700e-6) and got < 100
    # the published 40 layers hold K/V in 10
    whole = {**config, "layer_types": config["published"]["layer_types"]}
    assert hybrid.kv_bytes(whole, 1) == 5 * 4096
    # roofline/ragged_attention.py's factor is num_hidden_layers: 4.5 times too much here,
    # which is why the cell joins neither attn_kv_roofline nor ragged_attn_roofline.batch
    ragged = manifest.load_module("roofline", "ragged_attention")
    assert ragged.kv_bytes_per_step(config, [1]) == pytest.approx(4.5 * 4096)


def test_the_control_rounds_every_matrix_of_a_convolution_operator():
    """tools/control.py quantizes by leaf name: the reference hands it the
    convolution operator's in-projection as wk | wq | wv and its output
    projection as wo, cut from the program's own draw of w_in."""
    import control
    import jax.numpy as jnp
    import numpy as np

    config = _tiny()
    ref = manifest.load_module("reference", config["reference"])
    params = ref.make_params(config, 7, jnp.float32)
    low = control.quantized(params, control.BITS)
    conv = [l for l, kind in enumerate(config["layer_types"]) if kind == "conv"]
    attn = [l for l, kind in enumerate(config["layer_types"]) if kind == "full_attention"]
    assert conv and attn
    for name in ("wk", "wq", "wv", "wo"):
        assert isinstance(low[name], control.QuantizedLeaf), name
        for l in conv + attn:
            plain, rounded = np.asarray(params[name][l]), np.asarray(low[name][l])
            assert plain.shape == rounded.shape and 0 < np.abs(plain - rounded).max() < 0.05, (name, l)
    D = config["hidden_size"]
    assert all(params[name][conv[0]].shape == (D, D) for name in ("wk", "wq", "wv", "wo"))
    assert "w_in" not in params and "w_out" not in params
    # the three blocks are the program's one leaf, in the order B | C | X
    from distributed_llm_inference_tpu.models import api as M
    from harness import serve

    import jax

    own = M.init_params(serve.register_config(config).replace(dtype="float32"),
                        jax.random.PRNGKey(7))["layers"]["conv"]["w_in"]
    whole = np.concatenate([np.asarray(params[n][conv[0]]) for n in ("wk", "wq", "wv")], axis=-1)
    assert np.array_equal(whole, np.asarray(own[0]))


def test_the_new_readers_give_nothing_for_a_program_without_what_they_read(tmp_path):
    """The parent commit (no counter), a configuration whose layers are all
    attention layers, a window without a hit, a run without a trace: the
    metric is left out, and nothing raises."""
    _hand_trace(tmp_path)
    dense = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "serving": {"trace": {
                 "step_modules": {"mixed_step_ragged": 1, "decode_slots_paged": None},
                 "attention_kernels": ["paged_flash_attend"]}}}
    ctx = _traced(tmp_path, dense)
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name
    ctx = _traced(tmp_path / "nothing-here", _config())
    ctx.before = ctx.after = {}
    ctx.trace = {"modules": {}, "ops": {}}
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name


@pytest.mark.parametrize("name", LIST_LESS + NEW_METRICS + JOINED_COUNTERS + [
    "moe_ms_per_step", "moe_experts_touched_pct", "prefix_hit_pct"])
def test_a_reader_of_the_cell_reads_the_tiny_configuration(tmp_path, name):
    """Every reader the cell reports, on the tiny configuration's file: the
    keys it asks of a configuration are in an lfm2 file."""
    _hand_trace(tmp_path)
    ctx = _traced(tmp_path, _tiny())
    ctx.before.update(scrape.parse(
        'dli_moe_experts_touched_total{phase="mixed"} 10\n'
        'dli_moe_expert_slots_total{phase="mixed"} 40\n' + COUNTERS.format(*[0] * 11)))
    ctx.after.update(scrape.parse(
        'dli_moe_experts_touched_total{phase="mixed"} 30\n'
        'dli_moe_expert_slots_total{phase="mixed"} 120\n'
        + COUNTERS.format(3.0, 4.0, 1.0, 0.6, 5, 0.2, 5, 80, 5, 9000, 30000)))
    ctx.closed, ctx.late_ms = False, [0.4, 1.7]
    ctx.trace["ops"]["%routed_expert_matmul.4"] = 1800e-6

    class Res:
        prompt_tokens, cached_tokens = 90, 64

    ctx.ok = [Res]
    got = read(name, ctx)
    assert got is not None and got >= 0, name


# ---- the manifest and the configuration's file -------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_one_metric():
    man = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # looked up by name, not counted from the end: later PRs append after them (PR 40's cell)
    entry = {c["name"]: c for c in man["configs"]}[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types"]
    own = {w["name"]: w for w in man["workloads"]}[CELL]
    assert own == {**own, "config": CONFIG, "traffic": "docs-repeat-long", "chips": 1}
    assert len(own["why"]) <= 200
    by_name = {m["name"]: m for m in man["per_layer"]}
    # looked up by name, not counted from the end: later PRs append after it
    # (PR 38's six scope metrics)
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tpot_ms_p50"
    # a share that could only read 100 is no metric: the restore is held to exactness by the check's
    # `repeat` sequence, and counted by dli_prefix_state_tokens_total
    assert "state_restored_tok_pct" not in by_name
    assert by_name["hybrid_attn_kv_roofline"]["source"] == "device_trace"
    assert by_name["hybrid_attn_kv_roofline"]["layer"] == "kernels"
    for name in JOINED + JOINED_COUNTERS:
        assert by_name[name]["workloads"].count(CELL) == 1, name
    for name in ("attn_kv_roofline", "ragged_attn_roofline.batch", "mla_attn_roofline"):
        assert CELL not in by_name[name]["workloads"], name
    # the dense weight formula counts an 11,776-wide FFN at all 9 layers: 1.76 GB where a
    # decode step streams 1.2-2.4, so the share would pass 100%: the five accepted cells keep it
    assert by_name["step_weight_roofline"]["workloads"] == ACCEPTED
    weights = manifest.load_module("roofline", "weights")
    assert 1.7e9 < weights.step_weight_bytes(_config()) < 1.8e9
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_p50", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert reported == set(LIST_LESS) | set(NEW_METRICS) | set(JOINED) | set(JOINED_COUNTERS) \
        | set(SCOPES) | {n for n in WORKER_TIMED if CELL in by_name[n]["workloads"]}
    for other in ACCEPTED:
        assert "step_weight_roofline" in {m["name"] for m in manifest.Cell(man, other).per_layer}
    # the same trace as kanana-docs-long, at a rate of its own
    assert cell.traffic == manifest.Cell(man, "kanana-docs-long").traffic
    assert cell.load["loop"] == "open" and cell.load["rate"] > 0
    own = manifest.load_json(os.path.join(BENCH, "cells", f"{CELL}.json"))
    # the knee is a step of the file's sweep, and the rate 0.8 x the knee, as ISSUE 34 fixes it
    knee, rate = own["knee"], own["load"]["rate"]
    swept = [step[0] for step in own["sweep"]["steps"]]
    assert any(r == pytest.approx(knee, rel=0.01) for r in swept)
    assert rate == pytest.approx(0.8 * knee, rel=0.005)
    assert rate in [row[0] for row in own["steadiness"]["rates"]]
    manifest.load_module("reference", cell.config["reference"])


def test_the_configuration_keeps_every_published_number_but_the_depth():
    config = _config()
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == set(config["reduced"])
        for key in config["reduced"]:
            assert config["published"][key] == row["config"][key], key
    kinds = config["layer_types"]
    assert kinds == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (9, 1)
    assert config["published"]["layer_types"].count("full_attention") == 10
    for key in ("assumed", "served", "deployment", "check_why", "reduced_why"):
        assert config[key], key
    said = " ".join(config["assumed"])
    for what in ("B | C | X", "1e-6", "half-rotation", "per-head RMSNorm", "embedding_norm",
                 "tie_word_embeddings", "expert bias", "bfloat16"):
        assert what in said, what
    assert set(config["check"]) == {"mismatch", "mean", "worst"}
    flags = config["serving"]["flags"]
    for flag, value in (("--continuous-max-seq", "32768"), ("--kv-block-size", "128"),
                        ("--prefix-cache", "8"), ("--attn-impl", "pallas"),
                        ("--max-tokens-cap", "1024")):
        assert flags[flags.index(flag) + 1] == value, flag
    assert "--no-kv-shadow" in flags
    for reason in ("--continuous 16", "--kv-pool-blocks 3500", "context"):
        assert config["served"][reason], reason


def test_reduced_whys_arithmetic_and_the_registrys_sizes():
    """The file's sizes are the registry's, and the bytes `reduced_why` states
    are the program's own leaves'."""
    import jax

    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models import lfm2
    from harness import serve

    config = _config()
    cfg = serve.register_config(config)
    assert (cfg.arch, cfg.n_layers, cfg.first_k_dense) == ("lfm2", 9, 1)
    assert list(cfg.layer_types) == config["layer_types"]
    assert lfm2.stack_depths(cfg) == {"conv": 7, "attn": 2, "dense": 1, "moe": 8}
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.moe_ffn_dim, cfg.ffn_dim) == (
        config["num_experts"], config["num_experts_per_tok"], config["moe_intermediate_size"],
        config["intermediate_size"])
    assert (cfg.conv_kernel, cfg.head_dim, cfg.norm_eps, cfg.rope_theta) == (
        config["conv_L_cache"], config["head_dim"], config["norm_eps"],
        config["rope_parameters"]["rope_theta"])
    assert cfg.router_score == "sigmoid" and cfg.tie_embeddings and cfg.use_qk_norm
    assert cfg.router_norm_eps == config["init"]["router_norm_eps"] == 1e-6
    assert cfg.moe_renormalize is config["norm_topk_prob"]
    assert cfg.routed_scaling == config["routed_scaling_factor"]
    assert (cfg.eos_token_id, cfg.bos_token_id) == (config["eos_token_id"], config["bos_token_id"])
    shapes = jax.eval_shape(lambda: M.init_params(cfg.replace(dtype="bfloat16"),
                                                  jax.random.PRNGKey(0)))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    D, E, Fm, F, V = 2048, 64, 1536, 11776, 65536
    experts, conv, attn = E * 3 * D * Fm, D * 3 * D + D * D + 3 * D, 2 * D * D + 2 * D * 512
    assert (experts, conv, attn) == (603979776, 16783360, 10485760)
    routed = 8 * experts + 6 * conv + 2 * attn + 8 * D * E
    dense = conv + 3 * D * F
    norms = 2 * 9 * D + D + 2 * 2 * 64 + 8 * E  # layer norms, the last one, qk-norms, biases
    assert count == routed + dense + V * D + norms
    why = config["reduced_why"]["num_hidden_layers"]
    for said in ("603.98M", "16.78M", "10.49M", "89.1M", "4,954.6M", "134.2M", "5,178M",
                 "10.36 GB"):
        assert said in why, said
    assert round(routed / 1e6, 1) == 4954.6 and round(dense / 1e6, 1) == 89.1
    assert round(count / 1e6) == 5178 and 10.35e9 < 2 * count < 10.37e9
    # the pool the flags ask for, as `served` states it
    slots = int(config["serving"]["flags"][config["serving"]["flags"].index("--continuous") + 1])
    blocks = int(config["serving"]["flags"][config["serving"]["flags"].index("--kv-pool-blocks") + 1])
    pool = jax.eval_shape(lambda: P.init_pool(cfg.replace(dtype="bfloat16"), blocks, 128,
                                              n_slots=slots))
    kv = sum(pool[k].size * 2 for k in ("k", "v"))
    assert kv == blocks * 128 * 4096  # 4,096 B a token
    assert pool["tail"].size * 2 == 7 * blocks * 8192  # 8 KB a block and convolution layer
