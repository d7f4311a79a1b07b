"""What ISSUE 28 adds to the benchmark (cellbench/), run on the CPU: the new
cell rehearsed end to end at a tiny size through `cellbench/run.py`, the
four new per-layer readers on hand-made traces and scrapes, what they give
for a program that lacks what they read (nothing, without raising), and the
configuration's file against the published one.
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

TEST_MANIFEST = os.path.join(BENCH, "tests", "data", "BENCHMARK.mla.json")
CELL, CONFIG = "kanana-docs-long", "kanana-2-30b-a3b-7l"
NEW_METRICS = ["moe_ms_per_step", "moe_expert_roofline", "moe_experts_touched_pct",
               "mla_attn_roofline"]
STEP_MODULES = {"mixed_step_ragged": 1, "decode_slots_paged": None}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _config():
    return manifest.load_json(os.path.join(BENCH, "configs", f"{CONFIG}.json"))


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
    hit = int(out.split("repeat prefix_cached_tokens=")[1].split()[0])
    assert hit > 0  # the repeat hit the prefix index over latent blocks
    assert out.count("-> ok") == 3 and "FAIL" not in out and "NOT COMPARED" not in out
    assert not out.strip().splitlines()[-1].startswith("{")


# ---- the readers, on a hand-made trace ----------------------------------------

def _hand_trace(tmp_path):
    """A chunk launch (seq 7, 16 steps) and a mixed launch (seq 8) with
    their modules, kernels and the spans that follow their fetches; launch
    9's module ran after the trace. Microseconds."""
    import cut_spans
    from jax.profiler import ProfileData

    device = {
        "XLA Modules": [("jit_decode_slots_paged(12)", 1000, 4000),
                        ("jit_mixed_step_ragged(11)", 5100, 1000)],
        "XLA Ops": [("%paged_flash_attend.2 = bf16[] custom-call()", 1000, 500),
                    ("%routed_expert_matmul.4 = f32[] custom-call()", 1500, 1800),
                    ("%fusion.1 = f32[] fusion()", 3300, 1700),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 5100, 200),
                    ("%routed_expert_matmul.4 = f32[] custom-call()", 5300, 600),
                    ("%routed_expert_matmul.4 = f32[] custom-call()", 6400, 100)],
    }
    spans = [
        ("launch.chunk", 990, 20, {"prev": "plan", "seq": 7, "steps": 16, "kv_tokens": 64000}),
        ("launch.mixed", 1020, 30, {"prev": "plan", "seq": 8, "steps": 1, "kv_tokens": 9000}),
        ("fetch.chunk", 1060, 3990, {"prev": "plan", "seq": 7}),
        ("phase.distribute", 5050, 40, {"prev": "fetch_wait", "seq": 7, "moe_pairs": 2304,
                                        "moe_experts_touched": 1500,
                                        "moe_expert_slots": 12288}),
        ("launch.mixed", 5090, 20, {"prev": "plan", "seq": 9, "steps": 1, "kv_tokens": 700}),
        ("fetch.mixed", 5110, 1000, {"prev": "dispatch", "seq": 8}),
        ("phase.distribute", 6110, 200, {"prev": "fetch_wait", "seq": 8, "moe_pairs": 4608,
                                         "moe_experts_touched": 700,
                                         "moe_expert_slots": 768}),
    ]
    lines = {ln: [(n, s * 1000, d * 1000) for n, s, d in evs] for ln, evs in device.items()}
    text = cut_spans.xspace_text(
        "/device:TPU:0", lines, [(n, s * 1000, d * 1000, st) for n, s, d, st in spans], 0)
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(text)))


def _ctx(tmp_path, config):
    class Ctx:
        trace_dir, chunk_steps = str(tmp_path), 16
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
        trace = {"modules": {"jit_decode_slots_paged": [4000e-6],
                             "jit_mixed_step_ragged": [1000e-6]},
                 "ops": {"%routed_expert_matmul.4": 2500e-6, "%fusion.1": 1700e-6,
                         "%paged_flash_attend.2": 500e-6}}

    Ctx.config = config
    return Ctx


def test_expert_and_latent_rooflines_count_matched_launches_only(tmp_path):
    _hand_trace(tmp_path)
    config = _config()
    ctx = _ctx(tmp_path, config)
    D, F = config["hidden_size"], config["moe_intermediate_size"]
    expert = 3 * D * F * 2
    # launch 7: 1500 expert reads against 2304 pairs: the bytes bound it;
    # launch 8: 700 reads against 4608 pairs: still the bytes (3.0 MB an
    # expert a read is 11.5 us, a pair 0.05 us)
    least = (1500 + 700) * expert / 819e9
    assert read("moe_expert_roofline", ctx) == pytest.approx(100 * least / 2400e-6)
    # 73,000 positions x 7 layers x 576 numbers x 2 bytes over 700 us
    least = 73000 * 7 * 576 * 2 / 819e9
    assert read("mla_attn_roofline", ctx) == pytest.approx(100 * least / 700e-6)
    # the kernels' 2.5 ms over 17 steps
    assert read("moe_ms_per_step", ctx) == pytest.approx(2.5 / 17)
    moe = manifest.load_module("roofline", "moe_experts")
    assert moe.bound(config, 1, 100000, ctx.peaks)[1] == "compute"
    assert moe.bound(config, 36, 48, ctx.peaks) == (pytest.approx(36 * expert / 819e9),
                                                     "bandwidth")


def test_touched_share_is_a_delta_of_the_two_counters():
    class Ctx:
        before = scrape.parse('dli_moe_experts_touched_total{phase="mixed"} 100\n'
                              'dli_moe_experts_touched_total{phase="chunk"} 50\n'
                              'dli_moe_expert_slots_total{phase="mixed"} 768\n'
                              'dli_moe_expert_slots_total{phase="chunk"} 12288\n')
        after = scrape.parse('dli_moe_experts_touched_total{phase="mixed"} 800\n'
                             'dli_moe_experts_touched_total{phase="chunk"} 2350\n'
                             'dli_moe_expert_slots_total{phase="mixed"} 1536\n'
                             'dli_moe_expert_slots_total{phase="chunk"} 24576\n')

    assert read("moe_experts_touched_pct", Ctx) == pytest.approx(100 * 3000 / 13056)


def test_the_new_readers_give_nothing_for_a_program_without_what_they_read(tmp_path):
    """The parent commit (no expert, no latent pool, no counter), a dense
    configuration, a run without a trace: the metric is left out."""
    _hand_trace(tmp_path)
    dense = {"hidden_size": 64, "num_hidden_layers": 2, "serving": {"trace": {
        "step_modules": STEP_MODULES, "attention_kernels": ["paged_flash_attend"]}}}
    ctx = _ctx(tmp_path, dense)
    ctx.before = ctx.after = scrape.parse('dli_ragged_launches_total{phase="mixed"} 4\n')
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name
    ctx = _ctx(tmp_path / "nothing-here", _config())
    ctx.before = ctx.after = {}
    ctx.trace = {"modules": {}, "ops": {}}
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name


# ---- the manifest and the configuration's file -------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_four_metrics():
    man = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # (looked up by name: later PRs append after them, ISSUE 32)
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "docs-repeat-long", "chips": 1}
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 4] == NEW_METRICS
    for m in man["per_layer"][at:at + 4]:
        assert m["workloads"][0] == CELL and m["moves"] == "tpot_ms_p50"
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_p50", "setup_s"}
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    assert cell.load["loop"] == "open" and 0 < cell.load["rate"] < 2
    doc = cell.traffic["session"]["doc_tokens"]
    assert (doc["median"], doc["min"], doc["max"]) == (8192, 2048, 16384)
    assert cell.traffic["begin_at"] == 1 and cell.traffic["session"]["turns"] == 4
    manifest.load_module("reference", cell.config["reference"])


def test_the_configuration_keeps_every_published_number_but_the_depth():
    config = _config()
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == {"num_hidden_layers"} == set(config["reduced"])
        assert config["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]
    assert config["num_hidden_layers"] == 7 and config["first_k_dense_replace"] == 1
    for key in ("assumed", "served", "deployment", "check_why"):
        assert config[key], key
    assert set(config["check"]) == {"mismatch", "mean", "worst"}
    # the registry entry the server starts is this file's model
    from harness import serve

    cfg = serve.register_config(config)
    assert (cfg.arch, cfg.n_layers, cfg.n_experts, cfg.n_experts_per_tok) == (
        "mla_moe", 7, config["n_routed_experts"], config["num_experts_per_tok"])
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.moe_ffn_dim, cfg.n_shared_experts, cfg.first_k_dense) == tuple(
        config[k] for k in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                            "v_head_dim", "moe_intermediate_size", "n_shared_experts",
                            "first_k_dense_replace"))
    assert cfg.routed_scaling == config["routed_scaling_factor"]
    from distributed_llm_inference_tpu.models import mla_moe

    assert mla_moe.ROUTER_BIAS_SCALE == config["init"]["router_bias_scale"]
    assert (cfg.latent_dim, cfg.latent_row) == (576, 640)
