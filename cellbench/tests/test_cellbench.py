"""Tests of the benchmark's own yardstick, run on the CPU:

    python -m pytest cellbench/tests -q

They are not part of the repo's tier-1 suite (ROADMAP "Tier-1 verify" runs
`tests/`); a benchmark PR runs them by hand. The end-to-end ones start the
program's server with the tiny CI preset (interpreted kernels) and take
about a minute each.
"""

from __future__ import annotations

import json
import os
import random
import statistics
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from harness import manifest, scrape, stats, trace_reduce  # noqa: E402
from harness.check import parse_words  # noqa: E402
from harness.traffic_lib import Words, arrivals, stratified  # noqa: E402

TEST_MANIFEST = os.path.join(HERE, "data", "BENCHMARK.test.json")


def _traffic(name):
    return manifest.load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


# ---- generators -----------------------------------------------------------

def test_open_plan_is_deterministic_in_the_seed():
    gen = manifest.load_module("generators", "sessions")
    words = Words(32064, (32000, 32007))
    t = _traffic("chat-open")
    a = gen.plan_open(t, {"rate": 5.0}, 7, 30.0, words)
    b = gen.plan_open(t, {"rate": 5.0}, 7, 30.0, words)
    c = gen.plan_open(t, {"rate": 5.0}, 8, 30.0, words)
    assert [(s.due_s, s.turns[0].prompt, s.turns[0].max_tokens) for s in a] == \
        [(s.due_s, s.turns[0].prompt, s.turns[0].max_tokens) for s in b]
    assert [s.turns[0].prompt for s in a] != [s.turns[0].prompt for s in c]
    # every seed offers the same multiset of sizes and gaps, in another order
    assert sorted(s.turns[0].n_prompt for s in a) == sorted(s.turns[0].n_prompt for s in c)
    assert sorted(s.turns[0].max_tokens for s in a) == sorted(s.turns[0].max_tokens for s in c)
    assert len(a) == len(c) == 150
    # the parts of a run each hold rate x length arrivals, whatever the seed
    d = gen.plan_open(t, {"rate": 0.5}, 12345, [8.0, 50.0, 4.0], words)
    assert [sum(lo <= s.due_s < hi for s in d) for lo, hi in ((0, 8), (8, 58), (58, 62))] == [4, 25, 2]


def test_chat_open_hits_its_stated_distributions():
    gen = manifest.load_module("generators", "sessions")
    plan = gen.plan_open(_traffic("chat-open"), {"rate": 10.0}, 3, 100.0,
                         Words(32000, (2,)))
    prompts = [s.turns[0].n_prompt for s in plan]
    outs = [s.turns[0].max_tokens for s in plan]
    assert 32 <= min(prompts) and max(prompts) <= 1536
    assert 16 <= min(outs) and max(outs) <= 512
    assert abs(statistics.median(prompts) - 200) <= 4
    assert abs(statistics.median(outs) - 100) <= 2
    # log-normal sigma 0.8: the 84th percentile sits at median * e^0.8
    q84 = sorted(prompts)[int(0.8413 * len(prompts))]
    assert abs(q84 - 200 * 2.2255) / (200 * 2.2255) < 0.05
    gaps = [b.due_s - a.due_s for a, b in zip(plan, plan[1:])]
    assert abs(statistics.mean(gaps) - 0.1) < 0.005  # rate 10/s
    assert abs(statistics.pstdev(gaps) / statistics.mean(gaps) - 1.0) < 0.1  # exponential: CV 1
    for s in plan[:20]:  # a prompt of n words is n tokens, none of them special
        ws = s.turns[0].prompt.split()
        assert len(ws) == s.turns[0].n_prompt
        assert all(w[0] == "w" and 3 <= int(w[1:]) < 32000 and int(w[1:]) != 2 for w in ws)


def test_docs_repeat_sessions_share_their_document():
    gen = manifest.load_module("generators", "sessions")
    plan = gen.plan_open(_traffic("docs-repeat"), {"rate": 1.0}, 5, 40.0, Words(32000, (2,)))
    assert len(plan) == 40
    for s in plan:
        assert len(s.turns) == 4 and len(s.think_s) == 4
        doc = s.turns[1].shared_tokens
        assert 1024 <= doc <= 6144 and s.turns[0].shared_tokens == 0
        head = s.turns[0].prompt.split()[:doc]
        for t in s.turns:
            assert t.prompt.split()[:doc] == head
            assert 32 <= t.n_prompt - doc <= 64 and 32 <= t.max_tokens <= 96
        assert len({t.prompt for t in s.turns}) == 4  # a fresh question every turn
    docs = [s.turns[1].shared_tokens for s in plan]
    assert abs(statistics.median(docs) - 3072) < 200


def test_closed_plan_is_a_function_of_seed_client_and_index():
    gen = manifest.load_module("generators", "sessions")
    t = _traffic("batch-closed")
    words = Words(32064, (32000, 32007))
    a = gen.ClosedPlan(t, t["load"], 9, words)
    b = gen.ClosedPlan(t, t["load"], 9, words)
    assert a.clients == 48
    r = a.request(3, 5)
    assert r.prompt == b.request(3, 5).prompt and r.prompt != a.request(3, 6).prompt
    assert 64 <= r.n_prompt <= 128 and r.max_tokens == 384
    assert r.fields == {"slo_class": "batch"}


def test_a_seed_only_chooses_where_the_fixed_trace_begins():
    """What the mixes' `about` lines admit: nothing but the words is sampled.
    Two seeds that agree modulo the number of arrivals offer the same due
    times and sizes; any two seeds offer cyclic shifts of one sequence."""
    gen = manifest.load_module("generators", "sessions")
    t, words = _traffic("chat-open"), Words(32000, (2,))
    shape = lambda plan: [(round(s.due_s, 9), s.turns[0].n_prompt, s.turns[0].max_tokens)  # noqa: E731
                          for s in plan]
    a = gen.plan_open(t, {"rate": 0.5}, 3, [50.0], words)
    b = gen.plan_open(t, {"rate": 0.5}, 3 + 25 * 85899345, [50.0], words)
    c = gen.plan_open(t, {"rate": 0.5}, 4, [50.0], words)
    assert len(a) == 25 and shape(a) == shape(b) and shape(a) != shape(c)
    assert [s.turns[0].prompt for s in a] != [s.turns[0].prompt for s in b]  # the words differ
    sizes = lambda plan: [x[1:] for x in shape(plan)]  # noqa: E731
    assert sizes(a)[1:] + sizes(a)[:1] == sizes(c)


def test_begin_at_pins_the_trace_for_every_seed():
    """docs-repeat gives `begin_at`: every seed then replays the same due
    times, sizes and think times (those seed 2147481001 got while the seed
    still chose the place), and only the words differ."""
    gen = manifest.load_module("generators", "sessions")
    t, words = _traffic("docs-repeat"), Words(32000, (2,))
    assert t["begin_at"] == 1
    shape = lambda plan: [(round(s.due_s, 9), [round(x, 9) for x in s.think_s],  # noqa: E731
                           [(r.n_prompt, r.max_tokens, r.shared_tokens) for r in s.turns])
                          for s in plan]
    parts = [30.0, 50.0, 4.0]
    a = gen.plan_open(t, {"rate": 0.16}, 7, parts, words)
    b = gen.plan_open(t, {"rate": 0.16}, 2147491234, parts, words)
    assert shape(a) == shape(b)
    assert [s.turns[0].prompt for s in a] != [s.turns[0].prompt for s in b]
    assert [sum(lo <= s.due_s < hi for s in a) for lo, hi in ((0, 30), (30, 80), (80, 84))] == [5, 8, 1]
    free = {k: v for k, v in t.items() if k != "begin_at"}  # the seed chooses again
    assert shape(gen.plan_open(free, {"rate": 0.16}, 2147481001, parts, words)) == shape(a)
    assert shape(gen.plan_open(free, {"rate": 0.16}, 2147481002, parts, words)) != shape(a)


def test_stratified_is_the_same_multiset_for_every_seed():
    d = {"dist": "lognormal", "median": 100, "sigma": 0.7, "min": 16, "max": 512}
    a = stratified(d, 64, "mix", 1, integer=True)
    b = stratified(d, 64, "mix", 2, integer=True)
    assert a != b and sorted(a) == sorted(b)
    assert a[1:] + a[:1] == b  # the same cyclic order, begun one place on
    assert stratified(d, 64, "mix", 2147483650 + 64, integer=True) == stratified(
        d, 64, "mix", 2147483650, integer=True)
    ts = arrivals(4.0, 25.0, "mix", 1)
    assert len(ts) == 100 and 0 < ts[0] and ts[-1] < 25.0


# ---- percentile arithmetic ------------------------------------------------

def test_percentile_refuses_a_tail_the_sample_does_not_support():
    xs = list(range(199))
    with pytest.raises(ValueError, match="needs 200"):
        stats.percentile(xs, 95)
    assert stats.percentile(list(range(200)), 95) == pytest.approx(189.05)
    with pytest.raises(ValueError, match="needs 100"):
        stats.percentile(xs[:99], 90)
    with pytest.raises(ValueError, match="needs 20"):
        stats.percentile(xs[:19], 50)
    assert stats.percentile([1, 2, 3, 4], 50, enforce=False) == 2.5
    assert stats.min_samples(99) == 1000


def test_a_delivery_across_a_window_edge_counts_by_its_share():
    ev = [(1.0, 1), (2.0, 17), (3.0, 33), (3.5, 40)]  # first token, then chunks
    assert stats.tokens_in_window(ev, 0.0, 10.0) == 40
    assert stats.tokens_in_window(ev, 1.5, 2.5) == pytest.approx(8 + 8)
    assert stats.tokens_in_window(ev, 2.0, 3.0) == pytest.approx(16)
    assert stats.tokens_in_window(ev, 0.0, 1.0) == 0  # the first delivery counts at its instant
    assert stats.tokens_in_window(ev, 1.0, 1.5) == pytest.approx(1 + 8)
    # windows that tile the time tile the tokens
    parts = [stats.tokens_in_window(ev, a, b) for a, b in ((0, 1.3), (1.3, 2.9), (2.9, 4))]
    assert sum(parts) == pytest.approx(40)
    assert stats.tokens_in_window([], 0.0, 1.0) == 0


def test_interval_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert stats.interval_union(iv) == pytest.approx(3.0)
    assert stats.interval_gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]


def test_steps_count_mixed_launches_and_decode_chunks():
    class Ctx:
        chunk_steps, window_s, end_to_end = 16, 50.0, {"out_tok_s": 191.6}
        before = scrape.parse('dli_ragged_launches_total{phase="mixed"} 10\n'
                              'dli_decode_step_seconds_count{engine="continuous"} 20\n'
                              'dli_sched_step_tokens_total{kind="prefill"} 1000\n')
        after = scrape.parse('dli_ragged_launches_total{phase="mixed"} 41\n'
                             'dli_decode_step_seconds_count{engine="continuous"} 104\n'
                             'dli_sched_step_tokens_total{kind="prefill"} 3304\n')

    read = lambda name: manifest.load_module("layer_metrics", name).read(Ctx)  # noqa: E731
    assert read("steps_per_s.batch") == pytest.approx((31 + 16 * 53) / 50.0)
    assert read("batch_rows_mean") == pytest.approx(191.6 * 50 / (31 + 16 * 53))
    assert read("prefill_tok_pct") == pytest.approx(100 * 2304 / (2304 + 191.6 * 50))


def test_step_and_kernel_names_come_from_the_configuration():
    """Nothing in the harness knows a name of the program's: a configuration
    whose entry point runs other programs names them in its own file."""
    class Ctx:
        chunk_steps = 4
        config = {"serving": {"trace": {"step_modules": {"stage_step": 1, "decode_loop": None},
                                        "attention_kernels": ["my_attend"]}}}
        trace = {"modules": {"jit_stage_step": [0.010, 0.012], "jit_decode_loop": [0.040],
                             "jit_other": [9.0]},
                 "ops": {"%my_attend.3": 0.006, "%my_attend.7": 0.004, "%fusion.1": 1.0}}

    assert sorted(trace_reduce.step_durations(Ctx)) == pytest.approx([0.010] * 5 + [0.012])
    assert trace_reduce.kernel_seconds(Ctx, "attention_kernels") == pytest.approx(0.010)
    read = lambda name: manifest.load_module("layer_metrics", name).read(Ctx)  # noqa: E731
    assert read("step_device_ms_p50") == pytest.approx(10.0)
    assert read("attn_kernel_ms_per_step") == pytest.approx(1e3 * 0.010 / 6)
    Ctx.trace = {"modules": {"jit_other": [1.0]}, "ops": {"%fusion.1": 1.0}}
    assert read("step_device_ms_p50") is None and read("attn_kernel_ms_per_step") is None


def test_parse_words_reads_token_ids_or_refuses():
    assert parse_words("w5 w17  w100351") == [5, 17, 100351]
    assert parse_words("") == [] and parse_words("w5 hello") is None


def test_scrape_deltas():
    a = scrape.parse('# HELP x\nx_total{kind="prefill"} 10\nx_total{kind="decode"} 5\ny 2\n')
    b = scrape.parse('x_total{kind="prefill"} 40\nx_total{kind="decode"} 15\ny 3\n')
    assert scrape.delta(a, b, "x_total") == 40
    assert scrape.delta(a, b, "x_total", kind="prefill") == 30
    assert scrape.delta(a, b, "y") == 1


# ---- the trace reduction, on a recorded trace cut to a size a hand can check

def test_trace_reduce_on_the_recorded_fixture():
    fx = os.path.join(BENCH_DIR, "fixtures")
    want = manifest.load_json(os.path.join(fx, "olmo2-chat.cut.expected.json"))
    got = trace_reduce.reduce(os.path.join(fx, "olmo2-chat.cut.xplane.pb"))
    assert got["chips"] == want["chips"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for name, durs in want["modules"].items():
        assert got["modules"][name] == pytest.approx(durs, rel=1e-9)
    for name, total in want["ops"].items():
        assert got["ops"][name] == pytest.approx(total, rel=1e-9)
    idle = 1 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(want["idle_share"], rel=1e-9)
    assert len(got["breakdown"]["device_ops"]) <= 10
    assert all(k.startswith("unattributed") for k, _ in got["breakdown"]["idle_gaps"])


# ---- driven by data: a new cell is new files and one manifest entry --------

def test_a_new_cell_is_found_from_new_files_only(tmp_path):
    bench = tmp_path / "cellbench"
    for d in ("configs", "traffic", "cells", "generators", "layer_metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "newmodel.json").write_text(json.dumps(
        {"name": "newmodel", "vocab_size": 1000, "serving": {"flags": []}}))
    (bench / "traffic" / "newmix.json").write_text(json.dumps(
        {"generator": "newgen", "load": {"loop": "open", "rate": 1.0}}))
    (bench / "cells" / "new-cell.json").write_text(json.dumps({"load": {"rate": 2.5}}))
    (bench / "generators" / "newgen.py").write_text("def plan_open(*a):\n    return ['planned']\n")
    (bench / "layer_metrics" / "new_metric.py").write_text("def read(ctx):\n    return 42.0\n")
    man = {
        "paths": ["cellbench"],
        "configs": [{"name": "newmodel", "file": "cellbench/configs/newmodel.json"}],
        "workloads": [{"name": "new-cell", "config": "newmodel", "traffic": "newmix", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}, {"name": "other", "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "new_metric", "moves": "setup_s"},
                      {"name": "not_here", "moves": "other"}],
    }
    cell = manifest.Cell(man, "new-cell", root=str(tmp_path))
    assert cell.config["name"] == "newmodel" and cell.load == {"loop": "open", "rate": 2.5}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    gen = manifest.load_module("generators", cell.traffic["generator"], str(bench))
    assert gen.plan_open() == ["planned"]
    assert manifest.load_module("layer_metrics", "new_metric", str(bench)).read(None) == 42.0
    with pytest.raises(SystemExit, match="unknown workload"):
        manifest.Cell(man, "nope", root=str(tmp_path))


def test_the_manifest_names_files_that_exist():
    man = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    e2e = {m["name"] for m in man["end_to_end"]}
    for w in man["workloads"]:
        cell = manifest.Cell(man, w["name"])
        manifest.load_module("generators", cell.traffic["generator"])
        manifest.load_module("reference", cell.config["reference"])
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert set(cell.config["check"]) == {"mismatch", "mean", "worst"}
        assert set(cell.config["serving"]["trace"]) >= {"step_modules", "attention_kernels"}
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        assert hasattr(manifest.load_module("layer_metrics", m["name"]), "read")


# ---- end to end on the CPU -------------------------------------------------

def _run(*args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--manifest", TEST_MANIFEST,
         "--platform", "cpu", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.parametrize("cell", ["olmo2-chat", "olmo2-batch"])
def test_rehearsal_runs_every_phase_and_refuses_a_cpu(cell):
    p = _run("--workload", cell, "--seed", "4242424242", "--seconds", "8", "--trace", "0")
    out = p.stdout
    assert p.returncode != 0, out[-2000:]
    assert "the device is not a TPU" in out
    assert "server ready in" in out and "window:" in out and "check requests:" in out
    assert "reference child:" in out and "prefix_cached_tokens=" in out
    assert "compiles in the window (new cache entries)" in out  # 0 only on a warm cache
    assert out.count("-> ok") == 3 and "FAIL" not in out and "NOT COMPARED" not in out
    last = out.strip().splitlines()[-1]
    assert not last.startswith("{"), "a CPU run must print no result line"


def test_the_control_fails_the_check_at_a_size_a_test_can_hold():
    """The control of `correct`: the same server with its int8 weight path
    switched on must come out as not correct (at the tiny float32 preset the
    sound server chooses the reference's top-1 in every row, the int8 one
    misses it in a share of them)."""
    p = _run("--workload", "olmo2-chat", "--seed", "11", "--seconds", "8", "--trace", "0",
             "--check-only", "--server-flag=--quant", "--server-flag=int8")
    assert p.returncode != 0
    assert "FAIL" in p.stdout and "check-only: correct=False" in p.stdout
    sound = _run("--workload", "olmo2-chat", "--seed", "11", "--seconds", "8", "--trace", "0",
                 "--check-only")
    assert sound.returncode == 0 and "check-only: correct=True" in sound.stdout


def test_the_reference_with_8_bit_weights_fails_the_check():
    """The control as it was read on the chip (PERF.md section 6): the plain
    reference computed with 8-bit weights, put in the program's place by
    tools/control.py, must come out as not correct under the limits that the
    sound server passes. Tiny size, CPU."""
    seed = "13"
    sound = _run("--workload", "olmo2-chat", "--seed", seed, "--seconds", "8", "--trace", "0",
                 "--check-only")
    assert sound.returncode == 0 and "check-only: correct=True" in sound.stdout, sound.stdout[-2000:]
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "tools", "control.py"), "--manifest", TEST_MANIFEST,
         "--workload", "olmo2-chat", "--seed", seed],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "FAIL" in p.stdout and "control: correct=False" in p.stdout


@pytest.mark.parametrize("config", ["tiny-pp4", "tiny-router"])
def test_launcher_takes_a_four_chip_configuration(config):
    """A configuration's chips, flags and entry point are data: one server
    over four (virtual CPU) devices, and a router that spawns four replicas."""
    from harness import launcher, tokenizer
    from harness.load import Fleet
    from harness.traffic_lib import Request

    path = os.path.join(HERE, "data", "configs", f"{config}.json")
    cfg = manifest.load_json(path)
    tok = tokenizer.ensure(launcher.state_dir(), cfg["vocab_size"])
    with launcher.Server(path, cfg, 3, "cpu", tok, f"test.{config}") as srv:
        dev = srv.device()
        assert dev["platform"] == "cpu" and dev["count"] == 4
        words = Words(cfg["vocab_size"], (2,))
        ids = words.ids(random.Random(1), 12)
        res = Fleet("127.0.0.1", srv.port).send(
            Request(prompt=Words.text(ids), n_prompt=12, max_tokens=5), 0.0)
        assert res.ok and res.prompt_tokens == 12 and res.tokens >= 1, res.error
        assert isinstance(srv.memory(), list)
    assert srv.proc.poll() is not None  # nothing outlives the run
