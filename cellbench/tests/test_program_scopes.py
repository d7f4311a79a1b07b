"""Tests of what ISSUE 38 adds to the yardstick, run on the CPU:

    python -m pytest cellbench/tests/test_program_scopes.py -q

`harness/program_scopes.py`, the six per-layer readers built on it and
`tools/scopes.py` are checked on a hand-made trace in which two step modules
reuse `%fusion.1` under different labels, and against a recorded fixture: a
0.1 s cut of a traced `lfm2-docs-long` chip run of PR 38 with the
`program_scopes.json` the server wrote beside it (`fixtures/
lfm2-docs-long.scopes/`, cut by `tools/cut_scopes.py`, whose expected file
is computed there by plain sorting and scanning).
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))

from harness import host_spans, manifest, program_scopes, trace_reduce  # noqa: E402

FIXTURE = os.path.join(BENCH_DIR, "fixtures", "lfm2-docs-long.scopes")
STEP_MODULES = {"mixed_step_ragged": 1, "decode_slots_paged": None}
METRICS = ("scoped_device_pct", "attn_layer_ms_per_step", "ffn_ms_per_step",
           "moe_layer_ms_per_step", "conv_mix_ms_per_step", "head_sample_ms_per_step")


class Ctx:
    def __init__(self, trace_dir, chunk_steps=16, step_modules=STEP_MODULES):
        self.trace_dir, self.chunk_steps = str(trace_dir), chunk_steps
        self.config = {"serving": {"trace": {
            "step_modules": step_modules,
            "attention_kernels": ["ragged_paged_attend", "paged_flash_attend"],
            "expert_kernels": ["routed_expert_matmul"]}}}
        path = host_spans.find(self.trace_dir)
        self.trace = trace_reduce.reduce(path) if path else None


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


# ---- a hand-made trace ------------------------------------------------------

def _entry(*scope, mixed=0):
    return {"scope": list(scope), "mixed": mixed}


MAP = {"vocabulary": ["attn", "ffn"], "programs": {
    "jit_mixed_step_ragged": {
        "%fusion.1": _entry("attn"), "%fusion.2": _entry("head", mixed=2),
        "%ragged_paged_attend.5": _entry("attn"), "%copy.3": _entry(),
        "%fusion.7": _entry("moe_route"), "%while.4": _entry()},
    "jit_decode_slots_paged": {
        "%fusion.1": _entry("ffn"), "%fusion.2": _entry("sample"),
        "%paged_flash_attend.2": _entry("attn", "mla_absorb"),
        "%fusion.9": _entry("conv_mix"), "%while.8": _entry()},
}}


def _hand_trace(tmp_path, held=MAP, extra=()):
    """Times in microseconds. One mixed step 0-1000 and one decode chunk of
    4 steps 2000-6000; a small program between them that is no step
    program. `%fusion.1` is `attn` in the one and `ffn` in the other."""
    import cut_spans
    from jax.profiler import ProfileData

    device = [
        ("XLA Modules", "jit_mixed_step_ragged(11)", 0, 1000),
        ("XLA Modules", "jit_pack_chunk(13)", 1500, 10),
        ("XLA Modules", "jit_decode_slots_paged(12)", 2000, 4000),
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 0, 300),
        ("XLA Ops", "%ragged_paged_attend.5 = bf16[] custom-call()", 300, 200),
        ("XLA Ops", "%fusion.7 = f32[] fusion()", 500, 100),
        ("XLA Ops", "%copy.3 = f32[] copy()", 600, 100),
        ("XLA Ops", "%fusion.2 = f32[] fusion()", 700, 250),
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 1500, 10),  # not in a step program
        ("XLA Ops", "%while.8 = () while()", 2000, 4000),  # a container
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 2000, 2000),
        ("XLA Ops", "%paged_flash_attend.2 = bf16[] custom-call()", 4000, 1000),
        ("XLA Ops", "%fusion.9 = f32[] fusion()", 5000, 400),
        ("XLA Ops", "%fusion.2 = f32[] fusion()", 5400, 600),
        *extra,
    ]
    lines = {ln: [(n, s * 1000, d * 1000) for line, n, s, d in device if line == ln]
             for ln in ("XLA Modules", "XLA Ops")}
    text = cut_spans.xspace_text("/device:TPU:0", lines, [], 0)
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(text)))
    if held is not None:
        (tmp_path / program_scopes.FILE).write_text(json.dumps(held))
    return Ctx(tmp_path, chunk_steps=4)


def test_an_instruction_goes_to_its_own_modules_map(tmp_path):
    got = program_scopes.read(_hand_trace(tmp_path))
    mixed, chunk = (got["modules"][m] for m in ("jit_mixed_step_ragged", "jit_decode_slots_paged"))
    us = lambda d: {k: round(v * 1e6, 3) for k, v in d.items()}  # noqa: E731
    assert us(mixed["by_scope"]) == {"attn": 500.0, "moe_route": 100.0, "": 100.0, "head": 250.0}
    assert us(chunk["by_scope"]) == {"ffn": 2000.0, "attn/mla_absorb": 1000.0,
                                     "conv_mix": 400.0, "sample": 600.0}
    assert round(mixed["seconds"] * 1e6, 3) == 950.0  # the container is its children
    assert round(chunk["seconds"] * 1e6, 3) == 4000.0
    assert us(mixed["unlabelled"]) == {"%copy.3": 100.0}
    assert us(mixed["mixed"]) == {"%fusion.2": 250.0} and chunk["mixed"] == {}
    assert [round(x * 1e6) for x in mixed["executions"]] == [1000]
    assert got["unmapped_modules"] == [] and not mixed["unknown"]
    # trace_reduce's `ops` sums `%fusion.1` over both modules (and the
    # program between them): by name alone it cannot tell the two
    assert round(_hand_trace(tmp_path / "again").trace["ops"]["%fusion.1"] * 1e6) == 2310


def test_the_six_readers_on_the_hand_made_trace(tmp_path):
    ctx = _hand_trace(tmp_path)
    steps = 1 + 4
    want = {
        "scoped_device_pct": 100.0 * (950 - 100 + 4000) / (950 + 4000),
        "attn_layer_ms_per_step": (500 + 1000) / 1e3 / steps,
        "ffn_ms_per_step": 2000 / 1e3 / steps,
        "moe_layer_ms_per_step": 100 / 1e3 / steps,
        "conv_mix_ms_per_step": 400 / 1e3 / steps,
        "head_sample_ms_per_step": (250 + 600) / 1e3 / steps,
    }
    for name, value in want.items():
        assert read(name, ctx) == pytest.approx(value, rel=1e-9), name
    # the scope metrics sum to no more than the busy time a step
    assert sum(v for k, v in want.items() if k != "scoped_device_pct") \
        <= 1e3 * ctx.trace["busy_s"] / steps


def test_without_the_programs_file_every_reader_returns_none(tmp_path):
    ctx = _hand_trace(tmp_path, held=None)
    assert program_scopes.read(ctx) is None
    assert [read(name, ctx) for name in METRICS] == [None] * len(METRICS)


def test_a_label_no_traced_program_holds_reads_none_not_zero(tmp_path):
    held = json.loads(json.dumps(MAP))
    del held["programs"]["jit_decode_slots_paged"]["%fusion.9"]
    held["programs"]["jit_decode_slots_paged"]["%fusion.9"] = _entry("ffn")
    ctx = _hand_trace(tmp_path, held=held)
    assert read("conv_mix_ms_per_step", ctx) is None
    assert read("ffn_ms_per_step", ctx) == pytest.approx(2400 / 1e3 / 5)


def test_a_map_that_does_not_fit_its_trace_raises(tmp_path):
    held = json.loads(json.dumps(MAP))
    del held["programs"]["jit_decode_slots_paged"]["%paged_flash_attend.2"]
    with pytest.raises(SystemExit, match="does not fit its trace.*75.00%"):
        program_scopes.read(_hand_trace(tmp_path, held=held))
    # under a hundredth of a module's seconds may go unnamed
    tiny = [("XLA Ops", "%unknown.1 = f32[] copy()", 950, 5)]
    got = program_scopes.read(_hand_trace(tmp_path / "tiny", extra=tiny))
    assert list(got["modules"]["jit_mixed_step_ragged"]["unknown"]) == ["%unknown.1"]


def test_a_step_module_the_session_never_dispatched_counts_as_unscoped(tmp_path):
    held = {"vocabulary": [], "programs": {
        "jit_decode_slots_paged": MAP["programs"]["jit_decode_slots_paged"]}}
    ctx = _hand_trace(tmp_path, held=held)
    got = program_scopes.read(ctx)
    assert got["unmapped_modules"] == ["jit_mixed_step_ragged"]
    assert read("scoped_device_pct", ctx) == pytest.approx(100.0 * 4000 / 4950)
    assert read("attn_layer_ms_per_step", ctx) == pytest.approx(1000 / 1e3 / 5)


def test_the_tool_prints_the_table_a_perf_opt_issue_quotes(tmp_path, capsys):
    import scopes

    _hand_trace(tmp_path)
    r = scopes.table(str(tmp_path))
    chunk = r["programs"]["jit_decode_slots_paged"]
    assert chunk["executions"] == 1 and chunk["median_ms"] == pytest.approx(4.0)
    assert chunk["ms_by_scope"] == pytest.approx(
        {"ffn": 2.0, "attn/mla_absorb": 1.0, "sample": 0.6, "conv_mix": 0.4})
    mixed = r["programs"]["jit_mixed_step_ragged"]
    assert mixed["largest_unlabelled"] == [["%copy.3", pytest.approx(0.1)]]
    assert mixed["largest_mixed"] == [["%fusion.2", pytest.approx(0.25)]]
    sys.argv = ["scopes.py", str(tmp_path)]
    scopes.main()
    out = capsys.readouterr().out
    assert "jit_decode_slots_paged: 1 executions, median 4.000 ms" in out
    assert "largest under no scope" in out and "%copy.3" in out


# ---- the recorded fixture ----------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(FIXTURE, "expected.json")) as f:
        return Ctx(FIXTURE), json.load(f)


def test_the_readers_on_the_recorded_fixture(recorded):
    ctx, expected = recorded
    got = program_scopes.read(ctx)
    assert set(got["modules"]) == set(expected["modules"]) and not got["unmapped_modules"]
    for name, want in expected["modules"].items():
        mod = got["modules"][name]
        assert len(mod["executions"]) == want["executions"]
        assert mod["seconds"] == pytest.approx(want["seconds"], rel=1e-9)
        assert mod["by_scope"] == pytest.approx(want["by_scope"], rel=1e-9)
        assert sum(mod["mixed"].values()) == pytest.approx(want["mixed_s"], rel=1e-9)
        assert sum(mod["unknown"].values()) == pytest.approx(want["unknown_s"], abs=1e-12)
    assert len(trace_reduce.step_durations(ctx)) == expected["steps"]
    for name in METRICS:
        assert read(name, ctx) == pytest.approx(expected["metrics"][name], rel=1e-9), name
    # what the acceptance holds a traced run's line to
    assert read("moe_layer_ms_per_step", ctx) >= read("moe_ms_per_step", ctx)
    assert read("attn_layer_ms_per_step", ctx) >= read("attn_kernel_ms_per_step", ctx)
    assert sum(read(n, ctx) for n in METRICS[1:]) \
        <= 1e3 * ctx.trace["busy_s"] / expected["steps"]
    assert read("scoped_device_pct", ctx) >= 90.0


def test_the_tool_on_the_recorded_fixture(recorded):
    import scopes

    _, expected = recorded
    r = scopes.table(FIXTURE)
    for name, want in expected["modules"].items():
        p = r["programs"][name]
        assert p["executions"] == want["executions"]
        assert p["device_ms"] == pytest.approx(1e3 * want["seconds"] / want["executions"])
        assert p["unknown_ms"] == 0.0
