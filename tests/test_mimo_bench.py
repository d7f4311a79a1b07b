"""What ISSUE 55 adds to the benchmark (cellbench/), run on the CPU: the new
cell rehearsed end to end at a tiny size through `cellbench/run.py` (the
harness as it stands; the reference read through `harness/ref_child.py`
unchanged), the three new per-layer readers and the two roofline modules on
a hand-made trace, every list-less reader and every reader of a list the
cell joined on the tiny configuration, what the new readers give for a
program or a configuration without what they read (nothing, without
raising), what the 8-bit control rounds of this reference, the manifest's
appended entries, and the configuration's file against the published one and
against the registry.
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

TEST_MANIFEST = os.path.join(ROOT, "tests", "data", "BENCHMARK.mimo.json")
CELL, CONFIG = "mimo-reason-batch", "mimo-v2.5-7l"
NEW_METRICS = ["sink_window_attn_kv_roofline", "routed_share_step_roofline",
               "window_blocks_released_per_100_steps"]
ACCEPTED = ["olmo2-chat", "mistral-docs", "olmo2-batch", "kanana-docs-long", "sdar-batch",
            "lfm2-docs-long", "trinity-docs-xlong", "sala-docs-xlong", "granite-batch"]
JOINED = ["mixed_step_pct", "host_ms_per_step", "fetch_wait_pct", "scoped_device_pct",
          "attn_layer_ms_per_step", "ffn_ms_per_step", "head_sample_ms_per_step",
          "moe_ms_per_step", "moe_expert_roofline", "moe_experts_touched_pct",
          "moe_layer_ms_per_step", "moe_held_pair_pct", "window_kv_held_pct",
          "decode_step_ms_mean", "mixed_step_ms_mean", "launch_timed_pct",
          "decode_time_in_mixed_pct", "device_empty_wait_pct", "device_empty_host_pct",
          # ISSUE 55 left it to the builder: it means here what it means in the
          # cells it lists (PERF.md section 6, PR 55)
          "attn_grid_live_pct"]
LIST_LESS = ["batch_rows_mean", "prefill_tok_pct", "step_device_ms_p50",
             "attn_kernel_ms_per_step", "device_idle_pct"]
NOT_JOINED = ["step_weight_roofline", "attn_kv_roofline", "window_attn_kv_roofline",
              "hybrid_attn_kv_roofline", "ttft_ms_p50", "queue_wait_ms_mean", "prefix_hit_pct",
              "steps_per_s.batch", "kv_free_min_pct.batch", "ragged_attn_roofline.batch",
              "prefill_ms_mean",
              # means the same here, but cellbench/tests/test_mixed_tokens.py pins
              # its list to its two cells: a `benchmark` PR appends (PERF.md)
              "mixed_tokens_live_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _config():
    return manifest.load_json(os.path.join(BENCH, "configs", f"{CONFIG}.json"))


def _tiny():
    return manifest.load_json(
        os.path.join(ROOT, "tests", "data", "mimo", "configs", "tiny-mimo.json"))


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
    # 8 blocks of 8: four windows deep, the row's window blocks given back
    # all along the way
    assert hit == 64
    assert out.count("-> ok") == 3 and "FAIL" not in out and "NOT COMPARED" not in out
    assert not out.strip().splitlines()[-1].startswith("{")


# ---- the readers ---------------------------------------------------------------

def _hand_trace(tmp_path, kinds=True, routed=True):
    """test_trinity_bench's hand-made trace: two launches matched with their
    step modules, the launch spans carrying the counts per layer kind and the
    window group's turnover, the span after each fetch what it routed (kinds
    / routed False: a program that writes neither)."""
    import cut_spans
    from jax.profiler import ProfileData

    def st(seq, phase, steps, g, w, **more):
        out = {"prev": "plan", "seq": seq, "phase": phase, "steps": steps,
               "kv_tokens": 2 * g + 5 * w, **more}
        if kinds:
            out.update(kv_tokens_global=g, kv_tokens_window=w,
                       window_blocks_given=4, window_blocks_released=4)
        return out

    def after(seq, **more):
        return {"prev": "fetch_wait", "seq": seq, "timed": 1, **(more if routed else {})}

    device = {
        "XLA Modules": [("jit_decode_slots_paged(12)", 1000, 4000),
                        ("jit_mixed_step_ragged(11)", 5100, 1000)],
        "XLA Ops": [("%paged_flash_attend.2 = bf16[] custom-call()", 1000, 500),
                    ("%routed_expert_matmul.4 = f32[] custom-call()", 1500, 1800),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 5100, 200),
                    ("%ragged_paged_attend.5 = bf16[] custom-call()", 6400, 100)],
    }
    spans = [
        ("launch.chunk", 990, 20, st(7, "chunk", 16, 1600000, 65536, row_steps=512,
                                     decode_rows=32, steps_live=16)),
        ("launch.mixed", 1020, 30, st(8, "mixed", 1, 120000, 8000, row_steps=31,
                                      decode_rows=31, prefill_chunks=1, prefill_tokens=264,
                                      tokens_live=295)),
        ("fetch.chunk", 1060, 3990, {"prev": "plan", "seq": 7}),
        ("phase.distribute", 5051, 10, after(7, steps_run=16, moe_pairs=3072,
                                             moe_experts_touched=1900, moe_expert_slots=3072)),
        ("launch.mixed", 5090, 20, st(9, "mixed", 1, 700, 700, row_steps=1, tokens_live=1)),
        ("fetch.mixed", 5110, 1000, {"prev": "dispatch", "seq": 8}),
        ("phase.distribute", 6111, 10, after(8, moe_pairs=1770, moe_experts_touched=190,
                                             moe_expert_slots=192)),
    ]
    lines = {ln: [(n, s * 1000, d * 1000) for n, s, d in evs] for ln, evs in device.items()}
    text = cut_spans.xspace_text(
        "/device:TPU:0", lines, [(n, s * 1000, d * 1000, a) for n, s, d, a in spans], 0)
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(text)))


COUNTERS = (
    'dli_kv_window_blocks_released_total {}\ndli_kv_window_blocks_given_total {}\n'
    'dli_decode_chunk_steps_total{{state="run"}} {}\ndli_decode_chunk_steps_total{{state="cut"}} 0\n'
    'dli_ragged_launches_total{{phase="mixed"}} {}\ndli_ragged_launches_total{{phase="chunk"}} {}\n'
    'dli_launch_device_steps_total{{phase="chunk"}} {}\n'
    'dli_launch_device_seconds_total{{phase="chunk"}} {}\n'
    'dli_launch_device_steps_total{{phase="mixed"}} {}\n'
    'dli_launch_device_seconds_total{{phase="mixed"}} {}\n'
    'dli_launch_timing_total{{state="timed"}} {}\ndli_launch_timing_total{{state="queue_empty"}} 0\n'
    'dli_moe_pairs_total{{where="held"}} {}\ndli_moe_pairs_total{{where="routed"}} {}\n'
    'dli_moe_experts_touched_total{{phase="mixed"}} {}\ndli_moe_expert_slots_total{{phase="mixed"}} {}\n'
    'dli_worker_phase_seconds_total{{phase="fetch_wait"}} {}\n'
    'dli_worker_phase_seconds_total{{phase="wait_work"}} 0\n'
    'dli_worker_phase_seconds_total{{phase="plan"}} {}\n'
    'dli_device_empty_seconds_total{{phase="wait_work"}} 0.25\n'
    'dli_device_empty_seconds_total{{phase="plan"}} {}\n'
    'dli_decode_row_seconds_total{{phase="mixed"}} {}\n'
    'dli_decode_row_seconds_total{{phase="chunk"}} {}\n'
    'dli_sched_step_tokens_total{{kind="prefill"}} {}\ndli_sched_step_tokens_total{{kind="decode"}} {}\n'
    'dli_sched_decode_rows_total {}\n'
    'dli_attn_kv_tokens_total{{phase="chunk",state="attended"}} {}\n'
    'dli_attn_kv_tokens_total{{phase="chunk",state="walked"}} {}\n'
    'dli_mixed_tokens_total{{state="live"}} {}\ndli_mixed_tokens_total{{state="computed"}} {}\n'
)
GROUPS = ('dli_kv_group_blocks{{group="global",state="live"}} {}\n'
          'dli_kv_group_blocks{{group="global",state="cached"}} {}\n'
          'dli_kv_group_blocks{{group="window",state="live"}} {}\n'
          'dli_kv_group_blocks{{group="window",state="cached"}} {}\n')


def _traced(tmp_path, config):
    zeros = [0] * 26
    some = [1000, 1040, 3200, 800, 200, 3000, 36.0, 700, 21.0, 950, 1250, 10000,
            1900, 3072, 40.0, 4.0, 0.5, 30.0, 90.0, 70000, 100000, 100000,
            # (positions attended of those walked; live tokens of 800 launches of 512)
            6000000, 10000000, 230400, 409600]
    return Ctx(
        trace_dir=str(tmp_path), chunk_steps=16, peaks=PEAKS, config=config, window_s=50.0,
        end_to_end={"tpot_ms_p50": 13.0, "out_tok_s": 2400.0},
        trace={"modules": {"jit_decode_slots_paged": [4000e-6],
                           "jit_mixed_step_ragged": [1000e-6]},
               "ops": {"%paged_flash_attend.2": 500e-6, "%ragged_paged_attend.5": 300e-6,
                       "%routed_expert_matmul.4": 1800e-6},
               "busy_s": 0.005, "window_s": 0.008},
        scrapes=[scrape.parse(GROUPS.format(900, 100, 60, 4)),
                 scrape.parse(GROUPS.format(1100, 100, 70, 2))],
        before=scrape.parse(COUNTERS.format(*zeros)),
        after=scrape.parse(COUNTERS.format(*some)))


def test_the_attention_roofline_counts_each_kind_by_its_own_heads_and_useful_lanes(tmp_path):
    _hand_trace(tmp_path)
    config = _config()
    attention = manifest.load_module("roofline", "window_sink_attention")
    s = attention.sizes(config)
    assert s["kinds"] == {"global": (2, 4), "window": (5, 8)}
    assert (s["Dk"], s["Dv"], s["H"], s["item"]) == (192, 128, 64, 2)
    # a position of a global layer: 4 heads x 320 numbers x 2 B; of a window
    # layer: 8 heads
    one = attention.counts(config, {"kv_tokens_global": 1, "kv_tokens_window": 0})
    assert one == (2 * 2560, 2 * 64 * 2 * 320)
    assert attention.counts(config, {"kv_tokens_global": 0, "kv_tokens_window": 1})[0] == 5 * 5120
    assert attention.counts(config, {"kv_tokens": 9}) is None
    # launches 7 and 8 are matched with their modules; the kernels ran 700 us in them
    nbytes = (1600000 + 120000) * 2 * 2560 + (65536 + 8000) * 5 * 5120
    flops = ((1600000 + 120000) * 2 + (65536 + 8000) * 5) * 64 * 2 * 320
    assert nbytes / 819e9 > flops / 197e12
    got = read("sink_window_attn_kv_roofline", _traced(tmp_path, config))
    assert got == pytest.approx(100 * nbytes / 819e9 / 700e-6)
    # the published 48 layers: 9 global, 39 window
    whole = {**config, "hybrid_layer_pattern": config["published"]["hybrid_layer_pattern"]}
    assert {k: v[0] for k, v in attention.sizes(whole)["kinds"].items()} == \
        {"global": 9, "window": 39}


def test_the_whole_steps_share_counts_the_touched_experts_and_not_the_held(tmp_path):
    _hand_trace(tmp_path)
    config = _config()
    step = manifest.load_module("roofline", "routed_share_step")
    s = step.sizes(config)
    D = 4096
    att = 2 * (D * 13568 + 8192 * D) + 5 * (D * 14848 + 8192 * D)
    assert s["every_token"] == att + 3 * D * 16384 + 6 * D * 256
    assert (s["head"], s["expert"]) == (19072 * D, 3 * D * 2048)
    chunk = {"phase": "chunk", "steps": 16, "steps_live": 16, "row_steps": 512,
             "kv_tokens_global": 1600000, "kv_tokens_window": 65536}
    after = {"steps_run": 16, "moe_pairs": 3072, "moe_experts_touched": 1900}
    nbytes, flops = step.counts(config, chunk, after)
    kv = (1600000 * 2 * 2560 + 65536 * 5 * 5120)
    assert nbytes == (16 * (s["every_token"] + s["head"]) + 1900 * s["expert"]) * 2 + kv
    # 16 steps that touch 1,900 of the 16 x 6 x 32 = 3,072 expert slots: 10.3 ms
    # a step at the peak bandwidth (ISSUE 55 reckoned 10.7), where reading
    # every held expert would be 14.8
    t = step.least_seconds(config, chunk, after, PEAKS)
    assert t == pytest.approx(nbytes / 819e9) and 0.0100 < t / 16 < 0.0107
    every = step.least_seconds(config, chunk, {**after, "moe_experts_touched": 3072}, PEAKS)
    assert 0.0145 < every / 16 < 0.0151
    assert step.counts(config, chunk, {}) is None  # a program that routes nothing out
    mixed = {"phase": "mixed", "steps": 1, "row_steps": 31, "prefill_chunks": 1,
             "tokens_live": 295, "kv_tokens_global": 120000, "kv_tokens_window": 8000}
    m_after = {"moe_pairs": 1770, "moe_experts_touched": 190}
    m_bytes, m_flops = step.counts(config, mixed, m_after)
    assert m_flops > 2 * (295 * s["every_token"] + 32 * s["head"] + 1770 * s["expert"])
    got = read("routed_share_step_roofline", _traced(tmp_path, config))
    least = t + step.least_seconds(config, mixed, m_after, PEAKS)
    assert got == pytest.approx(100 * least / 5000e-6)


def test_the_turnover_reads_the_programs_counters(tmp_path):
    ctx = _traced(tmp_path, _config())
    # 1,000 blocks given back over 3,200 chunk steps and 800 mixed steps
    assert read("window_blocks_released_per_100_steps", ctx) == pytest.approx(25.0)
    assert read("window_kv_held_pct", ctx) == pytest.approx((6.4 + 6.0) / 2)
    assert read("moe_held_pair_pct", ctx) == pytest.approx(12.5)
    assert read("attn_grid_live_pct", ctx) == pytest.approx(60.0)
    assert read("mixed_tokens_live_pct", ctx) == pytest.approx(56.25)


def test_the_new_readers_give_nothing_for_a_program_without_what_they_read(tmp_path):
    """The parent commit (no turnover counter, `kv_tokens` alone on a launch
    span, nothing routed on the span after the fetch), a configuration of
    another family, a run without a trace: the metric is left out, and
    nothing raises."""
    _hand_trace(tmp_path, kinds=False, routed=False)
    ctx = _traced(tmp_path, _config())
    ctx.before = ctx.after = scrape.parse(
        'dli_kv_pool_blocks_free 9\ndli_moe_expert_slots_total{phase="mixed"} 4\n')
    ctx.scrapes = [ctx.before, ctx.after]
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name
    _hand_trace(tmp_path / "kinds-only", routed=False)
    assert read("routed_share_step_roofline", _traced(tmp_path / "kinds-only", _config())) is None
    _hand_trace(tmp_path / "whole")
    for other in ("trinity-large-ep8-5l", "lfm2-24b-a2b-9l", "olmo2-7b-16l"):
        config = manifest.load_json(os.path.join(BENCH, "configs", f"{other}.json"))
        for name in NEW_METRICS[:2]:
            assert read(name, _traced(tmp_path / "whole", config)) is None, (other, name)
    ctx = _traced(tmp_path / "nothing-here", _config())
    ctx.before = ctx.after = {}
    ctx.scrapes = []
    ctx.trace = {"modules": {}, "ops": {}}
    for name in NEW_METRICS:
        assert read(name, ctx) is None, name


# (the five readers of the program's scopes read a real trace's scope table:
# cellbench/tests/test_program_scopes.py holds them, and the chip run the cell)
SCOPED = ("scoped_device_pct", "attn_layer_ms_per_step", "ffn_ms_per_step",
          "head_sample_ms_per_step", "moe_layer_ms_per_step", "moe_expert_roofline")


@pytest.mark.parametrize("name", LIST_LESS + NEW_METRICS + [
    n for n in JOINED if n not in SCOPED])
def test_a_reader_of_the_cell_reads_the_tiny_configuration(tmp_path, name):
    """Every reader the cell reports, on the tiny configuration's file: the
    keys it asks of a configuration are in a mimo_v2 file."""
    _hand_trace(tmp_path)
    ctx = _traced(tmp_path, _tiny())
    ctx.closed, ctx.late_ms = True, [0.4, 1.7]

    class Res:
        prompt_tokens, cached_tokens = 90, 64

    ctx.ok = [Res]
    got = read(name, ctx)
    assert got is not None and got >= 0, name


def test_the_control_rounds_the_attention_and_expert_matrices_of_this_reference():
    """tools/control.py quantizes by leaf name: wq, wk, wv, wo (each kind's
    own shapes), the dense layer's and the expert banks' w_gate / w_up /
    w_down and lm_head; the routers, the sinks and the norms stay."""
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
    assert params["wk"][0].shape == (64, 192) and params["wk"][1].shape == (64, 384)
    assert params["wv"][0].shape == (64, 128) and params["wo"][1].shape == (512, 64)
    assert params["w_gate"][0].shape == (64, 96) and params["w_gate"][1].shape == (4, 64, 32)
    for name in ("sink", "w_router", "router_bias", "norm1"):
        assert low[name] is params[name]
    assert np.abs(np.asarray(low["lm_head"]) - np.asarray(params["lm_head"])).max() > 0


def test_the_jitted_control_rounds_as_the_plain_one_and_changes_nothing_else():
    """tools/control_jit.py: the same leaves by name, the rounding
    `control.fake_quant`'s own inside one compiled call (the eager result's
    8-bit levels, from a bfloat16 leaf as the chip holds it), and `main` is
    `control.main` with that one class in place; tools/control.py's own
    class is what it was once the module is left alone."""
    import control
    import control_jit
    import jax.numpy as jnp
    import numpy as np

    assert issubclass(control_jit.JitQuantizedLeaf, control.QuantizedLeaf)
    config = _tiny()
    ref = manifest.load_module("reference", config["reference"])
    params = ref.make_params(config, 7, jnp.bfloat16)
    plain = control.quantized(params, control.BITS)
    for name in control.MATRICES:
        jitted = control_jit.JitQuantizedLeaf(params[name], control.BITS)
        for l in range(config["num_hidden_layers"]):
            if params[name][l] is None:
                continue
            want, got = plain[name][l], jitted[l]
            assert got.dtype == jnp.float32 and got.shape == want.shape, (name, l)
            # the same 8-bit levels of the same scales (a compiled product may
            # differ in float32's last place, and a quotient within that of a
            # tie may take the other neighbour: fewer than one entry in 200)
            w = np.asarray(params[name][l].astype(jnp.float32))
            step = np.maximum(np.abs(w).max(axis=0, keepdims=True), 1e-12) / 127.0  # (fake_quant's)
            levels = [np.round(np.asarray(x) / step) for x in (want, got)]
            assert np.abs(np.asarray(got) / step - levels[1]).max() < 1e-3, (name, l)
            assert np.abs(levels[1]).max() <= 127 and np.abs(levels[0] - levels[1]).max() <= 1
            assert np.mean(levels[0] != levels[1]) < 0.005, (name, l)
            assert np.abs(np.asarray(got) - w).max() <= 0.501 * step.max(), (name, l)
    assert control.QuantizedLeaf is not control_jit.JitQuantizedLeaf
    seen = {}
    real = control.main
    try:
        control.main = lambda: seen.setdefault("leaf", control.QuantizedLeaf) and 0
        assert control_jit.main() == 0 and seen["leaf"] is control_jit.JitQuantizedLeaf
    finally:
        control.main = real
        control.QuantizedLeaf = control_jit.JitQuantizedLeaf.__mro__[1]
    assert isinstance(control.quantized(params, 8)["wq"], control.QuantizedLeaf)
    assert not isinstance(control.quantized(params, 8)["wq"], control_jit.JitQuantizedLeaf)


def test_the_reference_writes_down_the_programs_draw():
    """The same table of keys, and leaf for leaf the same values in both
    dtypes: the reference's weights are the program's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.models import mimo_v2
    from distributed_llm_inference_tpu.models.registry import get_model_config
    from mimo_util import ref_params

    ref = manifest.load_module("reference", "window_sink_moe")
    assert {k.replace("moe.w_router", "w_router").replace("moe.router_bias", "router_bias"): v
            for k, v in mimo_v2.LEAF_KEYS.items()} == ref.LEAF_KEY
    for dtype in ("float32", "bfloat16"):
        cfg = get_model_config("test-mimo-tiny").replace(dtype=dtype)
        ours = mimo_v2.init_params(cfg, jax.random.PRNGKey(11))
        theirs = ref_params(cfg, 11, jnp.dtype(dtype))
        at = {"global": 0, "window": 0}
        for l, kind in enumerate(cfg.layer_types):
            group = mimo_v2.GROUP_OF[kind]
            for name, leaf in ours["layers"][group].items():
                np.testing.assert_array_equal(theirs[name][l], leaf[at[group]])
            at[group] += 1
        for l in range(1, 4):
            for name, leaf in ours["layers"]["moe"].items():
                np.testing.assert_array_equal(theirs[name][l], leaf[l - 1])
        np.testing.assert_array_equal(theirs["w_down"][0], ours["layers"]["dense"]["w_down"][0])
        np.testing.assert_array_equal(theirs["embed"], ours["embed"])


# ---- the manifest and the configuration's file -------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_three_metrics():
    man = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # (the ninth configuration and the tenth cell: later PRs append after them)
    assert man["configs"][8]["name"] == CONFIG and man["workloads"][9]["name"] == CELL
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 3] == NEW_METRICS
    by_config = {c["name"]: c for c in man["configs"]}
    assert by_config[CONFIG]["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts",
        "vocab_size"]
    cells = {w["name"]: w for w in man["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG, "traffic": "reason-closed",
                           "chips": 1}
    assert len(cells[CELL]["why"]) <= 200 and len(by_config[CONFIG]["why"]) <= 200
    assert all(w["chips"] == 1 for w in man["workloads"])
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "tpot_ms_p50"
    assert by_name["sink_window_attn_kv_roofline"]["layer"] == "kernels"
    assert by_name["routed_share_step_roofline"]["layer"] == "model step"
    assert by_name["window_blocks_released_per_100_steps"] == {
        **by_name["window_blocks_released_per_100_steps"], "source": "program_counter",
        "layer": "paged KV + prefix"}
    for name in JOINED:
        assert CELL in by_name[name]["workloads"][-2:], name  # (last, until the next cell joins)
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"], name
    assert CELL not in next(m for m in man["end_to_end"] if m["name"] == "out_tok_s")["workloads"]
    cell = manifest.Cell(man, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_p50", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(LIST_LESS) | set(NEW_METRICS) | set(JOINED)
    for other in ACCEPTED:  # nothing an accepted cell reports has changed
        assert not set(NEW_METRICS) & {m["name"] for m in manifest.Cell(man, other).per_layer}
    traffic = cell.traffic
    # (ISSUE 55's mix, number for number)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.8,
                                        "min": 256, "max": 16384}
    assert traffic["max_tokens"] == {"dist": "uniform", "min": 1024, "max": 2048}
    assert traffic["sampling"] == {"greedy": True}
    assert traffic["request_fields"] == {"slo_class": "batch"}
    assert (traffic["ramp_s"], traffic["tail_s"], traffic["drain_s"], traffic["trace_s"]) == (30, 0, 5, 4)
    check = traffic["check"]
    assert (check["long_tokens"], check["repeat_extra_tokens"]) == (3000, 200)
    assert all(d["max_tokens"] >= 400 for d in check["decode"]) and len(check["decode"]) == 3
    assert cell.load == {"loop": "closed", "clients": 64}
    flags = cell.config["serving"]["flags"]
    assert cell.load["clients"] == 2 * int(flags[flags.index("--continuous") + 1])
    manifest.load_module("reference", cell.config["reference"])
    # the longest row the mix can offer fits the served context
    longest = traffic["prompt_tokens"]["max"] + traffic["max_tokens"]["max"]
    assert longest == int(flags[flags.index("--continuous-max-seq") + 1]) == 18432


def test_the_configuration_keeps_every_published_number_but_the_cuts():
    config = _config()
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == set(config["reduced"])
        for key in config["reduced"]:
            assert config["published"][key] == row["config"][key], key
    assert config["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert config["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert config["num_hidden_layers"] == 7
    assert (config["n_routed_experts"], config["expert_share"]["router_width"],
            config["expert_share"]["expert_lo"], config["expert_share"]["chips"],
            config["vocab_size"]) == (32, 256, 0, 8, 19072)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] and 19072 % 128 == 0
    # the floors: a whole period, six routed layers after the dense one, 32
    # experts, an eighth of the vocabulary
    assert config["hybrid_layer_pattern"][1:] == config["published"]["hybrid_layer_pattern"][6:12]
    for key in ("assumed", "served", "deployment", "check_why", "reduced_why"):
        assert config[key], key
    assert set(config["reduced_why"]) == set(config["reduced"])
    said = " ".join(config["assumed"])
    for what in ("before the weighted sum", "i - j < 128", "FIRST 64 lanes", "half-rotation",
                 "no per-head q/k norm", "fused_qkv", "normal x 0.5", "normal x 0.05",
                 "1e-20", "vision tower", "MTP", "special tokens", "word-level",
                 "bfloat16", "PUBLISHED index"):
        assert what in said, what
    for what in ("8 chips", "data-parallel attention", "B pairs a layer", "7 layers",
                 "attention sees more than its share"):
        assert what in config["deployment"], what
    assert set(config["check"]) == {"mismatch", "mean", "worst"}
    flags = config["serving"]["flags"]
    for flag, value in (("--continuous", "32"), ("--continuous-max-seq", "18432"),
                        ("--kv-block-size", "128"), ("--prefix-cache", "8"),
                        ("--attn-impl", "pallas"), ("--max-tokens-cap", "2048"),
                        ("--dtype", "bfloat16")):
        assert flags[flags.index(flag) + 1] == value, flag
    assert "--no-kv-shadow" in flags and "--warmup" in flags
    blocks = flags[flags.index("--kv-pool-blocks") + 1]
    for reason in ("--continuous 32", f"--kv-pool-blocks {blocks}", "context"):
        assert config["served"][reason], reason


def test_reduced_whys_arithmetic_and_the_registrys_sizes():
    """The file's sizes are the registry's, and the bytes `reduced_why` states
    are the program's own leaves'."""
    import jax

    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.engine.scheduler import live_width, step_width
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models.registry import get_model_config
    from harness import serve

    config = _config()
    cfg = serve.register_config(config)
    pub = get_model_config("mimo-v2.5")
    assert (pub.n_layers, pub.first_k_dense, pub.n_experts, pub.vocab_size) == (48, 1, 256, 152576)
    kinds = {0: "full_attention", 1: "sliding_attention"}
    assert list(pub.layer_types) == [kinds[k] for k in config["published"]["hybrid_layer_pattern"]]
    assert (cfg.arch, cfg.n_layers, cfg.first_k_dense) == ("mimo_v2", 7, 1)
    assert list(cfg.layer_types) == [kinds[k] for k in config["hybrid_layer_pattern"]]
    assert cfg.kv_groups == ("global", "window")
    assert (cfg.group_layers("global"), cfg.group_layers("window")) == ((0, 6), (1, 2, 3, 4, 5))
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_lo, cfg.n_experts_per_tok) == (
        config["expert_share"]["router_width"], config["n_routed_experts"],
        config["expert_share"]["expert_lo"], config["num_experts_per_tok"])
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.window_kv_heads, cfg.head_dim,
            cfg.value_dim, cfg.ffn_dim, cfg.moe_ffn_dim) == (
        config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
        config["swa_num_key_value_heads"], config["head_dim"], config["v_head_dim"],
        config["intermediate_size"], config["moe_intermediate_size"])
    assert (cfg.attn_window, cfg.rope_theta, cfg.rope_local_theta, cfg.norm_eps,
            cfg.vocab_size, cfg.attn_value_scale) == (
        config["sliding_window"], config["rope_theta"], config["swa_rope_theta"],
        config["layernorm_epsilon"], config["vocab_size"], config["attention_value_scale"])
    assert cfg.rotary_dim == int(config["partial_rotary_factor"] * config["head_dim"]) // 2 * 2 == 64
    assert cfg.window_sink is config["add_swa_attention_sink_bias"] is True
    assert cfg.router_score == config["scoring_func"] == "sigmoid" and not cfg.tie_embeddings
    assert cfg.moe_renormalize is config["norm_topk_prob"] and cfg.routed_scaling == 1.0
    assert cfg.router_norm_eps == config["init"]["router_norm_eps"] == 1e-20
    assert not cfg.n_shared_experts and config["n_shared_experts"] is None
    shapes = jax.eval_shape(lambda: M.init_params(cfg.replace(dtype="bfloat16"),
                                                  jax.random.PRNGKey(0)))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    D, F, Fm, V = 4096, 16384, 2048, 19072
    glob, wind = D * 13568 + 8192 * D, D * 14848 + 8192 * D
    dense_ffn, expert, router = 3 * D * F, 3 * D * Fm, D * 256
    assert (glob, wind, dense_ffn, expert) == (89128960, 94371840, 201326592, 25165824)
    layer0 = glob + dense_ffn
    routed_w, routed_g = wind + 32 * expert + router, glob + 32 * expert + router
    small = 2 * 7 * D + D + 5 * 64 + 6 * 256  # layer norms, the last, the sinks, the biases
    assert count == layer0 + 5 * routed_w + routed_g + 2 * V * D + small
    why = config["reduced_why"]["num_hidden_layers"]
    for said in ("89.13M", "94.37M", "201.33M", "25.17M", "805.31M", "1.05M", "290.46M",
                 "900.73M", "895.48M", "156.24M", "5,845.8M", "11.69 GB", "12.9 GB"):
        assert said in why, said
    assert round(layer0 / 1e6, 2) == 290.46 and round(routed_w / 1e6, 2) == 900.73
    assert round(routed_g / 1e6, 2) == 895.48 and round(2 * V * D / 1e6, 2) == 156.24
    assert round((layer0 + 5 * routed_w + routed_g + 2 * V * D) / 1e6, 1) == 5845.8
    assert 11.69e9 < 2 * count < 11.70e9 and round(256 * expert * 2 / 1e9, 1) == 12.9
    # the pool the flags ask for, as `served` states it: one number, two groups
    flags = config["serving"]["flags"]
    slots, blocks = (int(flags[flags.index(f) + 1]) for f in ("--continuous", "--kv-pool-blocks"))
    # (the fleet's 256 tile places on top of the 512 the model computes, which
    # is the most one row carries in a launch: engine/scheduler.live_width)
    assert (step_width(cfg, slots, 8), live_width(cfg, slots, 8)) == (768, 512)
    budget = P.window_row_budget(cfg.attn_window, 512, 128)
    groups = P.group_blocks(cfg, blocks, budget, slots, 128)
    assert (budget, groups) == (6, (2304, 193))
    pool = jax.eval_shape(lambda: P.init_pool(cfg.replace(dtype="bfloat16"), groups, 128))
    assert (pool["k"].size + pool["v"].size) * 2 == 2304 * 786432
    assert (pool["kw"].size + pool["vw"].size) * 2 == 193 * 3932160
    assert pool["routed"].shape == (2, 6, 33)  # the held experts, and the pairs routed elsewhere
    total = 2 * count + 2304 * 786432 + 193 * 3932160
    assert 14.25e9 < total < 14.28e9  # `served`: 14.26 GB of 16.9
