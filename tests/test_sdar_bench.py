"""What ISSUE 32 adds to the benchmark (cellbench/), run on the CPU: the new
cell rehearsed end to end at a tiny size through `cellbench/run.py` (the
harness as it stands: the block-diffusion reference read through
`harness/ref_child.py` unchanged), the two new per-layer readers on
hand-made scrapes and what they give for a program without the counters
(nothing, without raising), the manifest's appended entries, and the
configuration's file against the published one.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "cellbench")
sys.path.insert(0, BENCH)

from harness import manifest, scrape  # noqa: E402

TEST_MANIFEST = os.path.join(BENCH, "tests", "data", "BENCHMARK.sdar.json")
CELL, CONFIG = "sdar-batch", "sdar-30b-a3b-7l"
NEW_METRICS = ["denoise_forwards_per_token", "commit_forward_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _config():
    return manifest.load_json(os.path.join(BENCH, "configs", f"{CONFIG}.json"))


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_the_new_cell_runs_every_phase_at_a_tiny_size_and_refuses_a_cpu():
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
    assert hit > 0  # the repeat hit the prefix index over committed blocks
    # every check sequence delivered its whole budget, block by block
    assert "long 72+8, " in out and "decode0 20+40" in out and "repeat 92+8" in out
    assert out.count("-> ok") == 3 and "FAIL" not in out and "NOT COMPARED" not in out
    assert not out.strip().splitlines()[-1].startswith("{")


def test_the_two_readers_on_hand_made_scrapes():
    before = scrape.parse(
        'dli_diffusion_row_forwards_total{kind="denoise"} 100\n'
        'dli_diffusion_row_forwards_total{kind="commit"} 50\n'
        'dli_diffusion_tokens_total 200\n')
    after = scrape.parse(
        'dli_diffusion_row_forwards_total{kind="denoise"} 740\n'
        'dli_diffusion_row_forwards_total{kind="commit"} 370\n'
        'dli_diffusion_tokens_total 1480\n')
    ctx = Ctx(before=before, after=after)
    assert read("denoise_forwards_per_token", ctx) == 0.75  # 3 forwards a block of 4
    assert abs(read("commit_forward_pct", ctx) - 100 / 3) < 1e-9
    # a program without the counters (the parent), or one that committed
    # nothing in the window: nothing, and no exception
    for empty in (Ctx(before={}, after={}), Ctx(before=after, after=after)):
        for name in NEW_METRICS:
            assert read(name, empty) is None, name


def test_the_manifest_gained_one_configuration_one_cell_and_two_metrics():
    man = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # (looked up by name: later PRs append after them, ISSUE 34)
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "batch-closed-blocks", "chips": 1}
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 2] == NEW_METRICS
    for m in man["per_layer"][at:at + 2]:
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p50"
        assert m["source"] == "program_counter"
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in ("moe_ms_per_step", "moe_expert_roofline", "moe_experts_touched_pct"):
        assert by_name[name]["workloads"][:2] == ["kanana-docs-long", CELL]
    assert by_name["mla_attn_roofline"]["workloads"] == ["kanana-docs-long"]
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_p50", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= reported
    assert {"batch_rows_mean", "prefill_tok_pct", "step_device_ms_p50", "step_weight_roofline",
            "attn_kernel_ms_per_step", "device_idle_pct"} <= reported
    assert cell.load == {"loop": "closed", "clients": 64}
    assert cell.traffic["max_tokens"] == {"dist": "fixed", "value": 384}
    assert cell.traffic["request_fields"] == {"slo_class": "batch", "denoise_steps": 2}
    # every prompt and budget of the check is a multiple of the block length:
    # the harness hands the reference no prompt length
    from harness.check import DEFAULT_SAMPLE

    sample = {**DEFAULT_SAMPLE, **cell.traffic["check"]}
    block = cell.config["diffusion"]["block_length"]
    sizes = [sample["long_tokens"], sample["repeat_extra_tokens"], sample["prefill_max_tokens"]]
    sizes += [n for d in sample["decode"] for n in d.values()]
    assert all(n % block == 0 for n in sizes), sizes
    manifest.load_module("reference", cell.config["reference"])


def test_the_configuration_keeps_every_published_number_but_the_depth():
    config = _config()
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == {"num_hidden_layers"} == set(config["reduced"])
        assert config["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]
    assert config["num_hidden_layers"] == 7
    for key in ("assumed", "served", "deployment", "check_why", "reduced_why"):
        assert config[key], key
    said = " ".join(config["assumed"])
    for what in ("block length 4", "mask token id 151669", "sequential", "qk-norm", "-inf"):
        assert what in said, what
    assert set(config["check"]) == {"mismatch", "mean", "worst"}
    flags = config["serving"]["flags"]
    for flag, value in (("--continuous", "32"), ("--continuous-max-seq", "2048"),
                        ("--kv-block-size", "128"), ("--kv-pool-blocks", "512"),
                        ("--prefix-cache", "8"), ("--attn-impl", "pallas"),
                        ("--max-tokens-cap", "1024"), ("--denoise-steps", "2")):
        assert flags[flags.index(flag) + 1] == value, flag
    assert "--no-kv-shadow" in flags
    # the registry entry the server starts is this file's model
    from harness import serve

    cfg = serve.register_config(config)
    d = config["diffusion"]
    assert (cfg.arch, cfg.n_layers, cfg.n_experts, cfg.n_experts_per_tok, cfg.moe_ffn_dim) == (
        "llama", 7, config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"])
    assert (cfg.diffusion_block, cfg.mask_token_id) == (d["block_length"], d["mask_token_id"])
    assert str(d["denoise_steps"]) == flags[flags.index("--denoise-steps") + 1]
    assert cfg.use_qk_norm and cfg.qk_norm_dim == "head" and cfg.router_score == "softmax"
    assert cfg.moe_renormalize is config["norm_topk_prob"] and not cfg.tie_embeddings
