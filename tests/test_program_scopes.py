"""The device's half of a launch by the program's own scopes (ISSUE 38).

A device trace's events carry an instruction's name and none of the
`jax.named_scope` labels the step programs are written under
(utils/tracing.STEP_SCOPES). So a profiler session keeps the abstract
arguments of the step programs it dispatches (engine/continuous
`_step_program`), and its end compiles them again, maps instruction ->
scope (`scope_map`) and writes `program_scopes.json` beside the profile
(serving/server._Profiler.stop). Held here on the CPU at the registry's tiny
models; tests/cell_program_checks.py holds the labels at the cells' shapes
for the chip.
"""

import collections
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

import dense_equal
from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.serving.server import _Profiler
from distributed_llm_inference_tpu.utils import tracing

BLOCKS = {"embed", "attn", "head", "sample"}
ROUTED = {"moe_route", "moe_dispatch", "moe_experts", "moe_combine"}
# preset -> the labels its step programs must carry
FAMILIES = {
    "test-llama-tiny": BLOCKS | {"ffn"},
    "test-olmo2-tiny": BLOCKS | {"ffn"},
    "test-mla-moe-tiny": BLOCKS | ROUTED | {"ffn", "moe_shared", "mla_absorb"},
    "test-sdar-tiny": BLOCKS | ROUTED,
    "test-lfm2-tiny": BLOCKS | ROUTED | {"ffn", "conv_mix"},
    "test-sala-tiny": BLOCKS | {"ffn", "linear_attn", "linear_scan",
                                "sparse_select"},
    "test-granite-tiny": BLOCKS | {"ffn", "ssm_mix", "ssm_scan"},
    # (every layer routes: no dense `ffn`)
    "test-solar-tiny": BLOCKS | ROUTED | {"moe_shared", "delta_mix",
                                          "delta_conv", "delta_scan"},
}
# a label that only ever nests in another
NESTED = {"mla_absorb": "attn", "sparse_select": "attn",
          "linear_scan": "linear_attn", "ssm_scan": "ssm_mix",
          "delta_conv": "delta_mix", "delta_scan": "delta_mix"}


def test_the_vocabulary_names_every_scope_once():
    assert len(set(tracing.STEP_SCOPES)) == len(tracing.STEP_SCOPES)
    assert set().union(*FAMILIES.values()) == set(tracing.STEP_SCOPES)
    assert not set(tracing.STEP_SCOPES) & set(tracing.WORKER_PHASES)


@pytest.mark.parametrize("preset", sorted(FAMILIES))
def test_scope_map_finds_every_label_of_the_family_in_both_step_programs(preset):
    # (a pool block of the sparse family is one block of its selection)
    block = get_model_config(preset).sparse_block \
        if preset == "test-sala-tiny" else 16
    texts = dense_equal.programs(preset, 3, 24, 64, block_size=block, layers=0,
                                 described=False, snapshots=2).texts
    assert set(texts) == {"decode_slots_paged", "mixed_step_ragged"}
    for program, text in texts.items():
        (module, insts), = tracing.scope_map(text).items()
        assert module == f"jit_{program}"
        held = {label for v in insts.values() for label in v["scope"]}
        assert held == FAMILIES[preset], (program, held ^ FAMILIES[preset])
        # a name is one instruction (a trace's event names it and no more),
        # and every key is an instruction of the text
        names = re.findall(r"(?m)^\s+(?:ROOT )?(%[\w.\-]+) = ", text)
        assert len(names) == len(set(names))
        assert set(insts) <= set(names)
        for name, v in insts.items():
            assert set(v) == {"scope", "mixed"} and v["mixed"] >= 0, name
        for inner, outer in NESTED.items():  # nested: outermost first
            assert all(v["scope"][0] == outer for v in insts.values()
                       if inner in v["scope"]), inner


def test_scope_map_on_a_hand_made_module():
    text = '''HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %a = f32[8]{0} add(%p0, %p0), metadata={op_name="jit(step)/while/body/attn/add"}
  %b = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(step)/while/body/attn/mla_absorb/mul"}
  ROOT %c = f32[8]{0} negate(%b), metadata={op_name="jit(step)/while/body/ffn/neg"}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %d = f32[8]{0} add(%p0.1, %p0.1), metadata={op_name="jit(step)/head/head/add"}
  ROOT %e = f32[8]{0} tanh(%d), metadata={op_name="jit(step)/head/tanh"}
}

ENTRY %main.7 (x: f32[8]) -> (f32[8], f32[8]) {
  %x = f32[8]{0:T(256)} parameter(0)
  %fusion.1 = f32[8]{0:T(256)} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/ffn/neg" stack_frame_id=3}
  %fusion.2 = f32[8]{0:T(256)} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %kernel.3 = (f32[8]{0:T(256)}, f32[8]{0}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attn/jit(kernel)/pallas_call"}
  %copy.4 = f32[8]{0} copy(%fusion.2)
  ROOT %tuple.5 = (f32[8]{0}, f32[8]{0}) tuple(%copy.4, %fusion.2)
}
'''
    assert tracing.scope_map(text) == {"jit_step": {
        "%fusion.1": {"scope": ["ffn"], "mixed": 3},
        "%fusion.2": {"scope": ["head"], "mixed": 1},
        "%kernel.3": {"scope": ["attn"], "mixed": 0},
        "%copy.4": {"scope": [], "mixed": 0},
    }}
    # two modules in one text keep their own instructions
    two = tracing.scope_map(text + "\n" + text.replace("jit_step", "jit_other"))
    assert set(two) == {"jit_step", "jit_other"} and two["jit_step"] == two["jit_other"]


@pytest.fixture(scope="module")
def fleet():
    cfg = get_model_config("test-llama-tiny", dtype="float32", eos_token_id=-1)
    eng = InferenceEngine(
        cfg, params=M.init_params(cfg, jax.random.PRNGKey(0)),
        engine_cfg=EngineConfig(prefix_cache_entries=0, chunked_prefill=True),
    )
    cont = ContinuousEngine(
        eng, n_slots=3, chunk_steps=4, chunk_lag=2, slot_max_seq=128,
        kv_pool_blocks=40, kv_block_size=16, restart_backoff_s=0.01,
    )
    yield cont
    cont.close()


def test_a_launch_records_nothing_outside_a_session_and_both_programs_inside(
    fleet, monkeypatch
):
    lowered = collections.Counter()
    lower = fleet.backend.lower_step
    monkeypatch.setattr(
        fleet.backend, "lower_step",
        lambda name, *a: lowered.update([name]) or lower(name, *a), raising=False)
    fleet.submit("warm up both programs", max_tokens=10, greedy=True)
    assert fleet._step_calls is None and not lowered
    assert fleet.trace_step_programs(False) == {}
    fleet.trace_step_programs(True)
    assert fleet._step_calls == {}
    fleet.submit("alpha beta gamma delta", max_tokens=10, greedy=True)
    calls = fleet._step_calls
    assert set(calls) == {"decode_slots_paged", "mixed_step_ragged"}
    # shapes, not buffers: nothing of the donated pool is kept alive
    assert not [a for a in jax.tree.leaves(calls) if isinstance(a, jax.Array)]
    assert calls["decode_slots_paged"][1]["num_steps"] == 4
    lowerings = fleet.trace_step_programs(False)
    assert fleet._step_calls is None and not lowered  # nothing compiled yet
    fleet.submit("no session, no record", max_tokens=6, greedy=True)
    assert fleet._step_calls is None
    # lowered again from those shapes, each is the program a plain call
    # of the same shapes compiles, instruction for instruction by name: a
    # trace's event names are the map's keys
    from distributed_llm_inference_tpu.engine import paged as P

    def instructions(text):
        return [m.groups() for m in map(tracing._HLO_INSTRUCTION.match,
                                        text.splitlines()) if m]

    for name, again in lowerings.items():
        text = tracing.fresh_hlo_text(again())
        (module, insts), = tracing.scope_map(text).items()
        assert module == f"jit_{name}" and insts
        args, kwargs = calls[name]
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), fleet.backend.params)
        plain = getattr(P, name).lower(fleet.cfg, params, *args, **kwargs)
        # compiled as the map's own text is, past both caches: a plain
        # `.compile()` hands back the executable the fleet's first dispatch
        # made, and where a neighbour in this worker has switched the
        # persistent cache on (utils/compile_cache.enable sets it for the
        # process) that one may be a metadata-free hit of what another call
        # stack compiled, with a few instructions numbered otherwise: the
        # test then failed in the suite and passed alone (ISSUE 40)
        assert instructions(tracing.fresh_hlo_text(plain)) == instructions(text)
    assert lowered == {"decode_slots_paged": 1, "mixed_step_ragged": 1}


class _Session:
    """A stand-in for the continuous engine behind `_Profiler.programs`."""

    def __init__(self, lowerings):
        self.lowerings, self.calls = lowerings, []

    def trace_step_programs(self, on):
        self.calls.append(on)
        return {} if on else self.lowerings


def _stub_profiler(monkeypatch, events):
    def start(path):
        os.makedirs(path, exist_ok=True)
        events.append(("start", path))

    def stop():
        events.append(("stop", sorted(os.listdir(events[0][1]))))

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop)


def _labelled(label):
    def step(x, w):
        with jax.named_scope(label):
            return jnp.tanh(x @ w).sum()

    shapes = (jax.ShapeDtypeStruct((8, 16), jnp.float32),
              jax.ShapeDtypeStruct((16, 4), jnp.float32))
    return lambda: jax.jit(step).lower(*shapes)


def test_profiler_stop_writes_the_map_after_the_profile_and_never_fails_on_it(
    monkeypatch, tmp_path
):
    events = []
    _stub_profiler(monkeypatch, events)
    prof = _Profiler(str(tmp_path))
    prof.programs = _Session({"step": _labelled("attn")})
    assert prof.start("a")["status"] == "tracing"
    assert prof.programs.calls == [True]
    reply = prof.stop()
    assert reply["status"] == "stopped" and prof.programs.calls == [True, False]
    # the profiler had stopped, its directory still without the map
    assert events[1] == ("stop", [])
    assert reply["scopes"] == os.path.join(reply["trace_dir"], tracing.PROGRAM_SCOPES_FILE)
    with open(reply["scopes"]) as f:
        held = json.load(f)
    assert held["vocabulary"] == list(tracing.STEP_SCOPES)
    (module, insts), = held["programs"].items()
    assert module == "jit_step"
    assert {tuple(v["scope"]) for v in insts.values()} == {("attn",)}

    def refused():
        raise RuntimeError("the compiler refused")

    events.clear()
    prof.programs = _Session({"step": refused})
    prof.start("b")
    reply = prof.stop()
    assert reply["status"] == "stopped"
    assert reply["scopes"] == "error: the compiler refused"
    assert prof.start("c")["status"] == "tracing"  # the session did end
    prof.stop()
    # a server without a continuous engine says nothing of scopes
    prof.programs = None
    prof.start("d")
    assert "scopes" not in prof.stop()


def test_the_map_is_this_trees_after_another_tree_filled_the_compile_cache(tmp_path):
    """The persistent cache's key strips debug info: a program compiled
    once WITHOUT a label is handed back, under its old `op_name`s, to a
    tree that has since gained the label. `fresh_hlo_text` is not fooled."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
        old = _labelled("an_older_trees_label")().compile().as_text()
        assert "an_older_trees_label" in old and len(os.listdir(tmp_path)) == 1
        hit = _labelled("attn")().compile().as_text()
        # the trap itself (should a JAX release close it, this line fails
        # first and `fresh_hlo_text` can go)
        assert "an_older_trees_label" in hit and len(os.listdir(tmp_path)) == 1
        (insts,) = tracing.scope_map(tracing.fresh_hlo_text(_labelled("attn")())).values()
        assert {tuple(v["scope"]) for v in insts.values()} == {("attn",)}
        assert len(os.listdir(tmp_path)) == 2  # its own entry
        # and so is the file a session's end writes
        os.mkdir(tmp_path / "trace")
        path = tracing.write_program_scopes(
            str(tmp_path / "trace"), {"step": _labelled("attn")})
        with open(path) as f:
            (insts,) = json.load(f)["programs"].values()
        assert {tuple(v["scope"]) for v in insts.values()} == {("attn",)}
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
