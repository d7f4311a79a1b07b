"""Constrained decoding on the multi-device backends and the continuous
fleet: bit-exact greedy equivalence single-device vs the pp ring (and the
1F1B backend's plain-ring dispatch), every-path property coverage, and
mixed constrained/unconstrained slots coexisting mid-decode.

Fast-tier exclusion: pp-mesh + fleet compiles per variant; run the full
suite (plain `pytest`) to include it.
"""

import json
import re
import threading

import numpy as np
import pytest

import jax

from distributed_llm_inference_tpu import (
    EngineConfig, MeshConfig, create_engine, get_model_config,
)
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.models import api as M

pytestmark = pytest.mark.slow

SCHEMA = {
    "type": "object",
    "properties": {"name": {"type": "string"}, "age": {"type": "integer"}},
    "required": ["name", "age"],
}


@pytest.fixture(scope="module")
def pair():
    cfg = get_model_config("test-llama-tiny")
    params = M.init_params(cfg, jax.random.PRNGKey(5))
    ecfg = EngineConfig(prefill_buckets=(32, 64))
    sd = InferenceEngine(cfg, params=params, engine_cfg=ecfg)
    pp = create_engine(cfg, mesh_cfg=MeshConfig(pp=2), params=params,
                       engine_cfg=ecfg)
    return sd, pp


def test_pp_greedy_bit_exact(pair):
    """Acceptance: bit-exact greedy equivalence single-device vs the pp
    ring on the 8-virtual-device CPU mesh, for every constraint kind."""
    sd, pp = pair
    for spec in (
        {"regex": "(red|green|blue|[0-9]{1,3})"},
        {"choices": ["alpha", "beta"]},
        {"json_schema": SCHEMA},
    ):
        a = sd.generate("the answer is", max_tokens=80, greedy=True,
                        chat=False, constraint=spec)
        b = pp.generate("the answer is", max_tokens=80, greedy=True,
                        chat=False, constraint=spec)
        assert a["status"] == b["status"] == "success"
        assert a["response"] == b["response"], spec


def test_pp_sampled_satisfies_constraint(pair):
    _, pp = pair
    pat = r"[0-9]{2,4}"
    for seed in range(4):
        r = pp.generate("n:", max_tokens=30, chat=False, seed=seed,
                        temperature=1.8, top_k=0, top_p=1.0,
                        constraint={"regex": pat})
        assert re.fullmatch(pat, r["response"]), r["response"]


def test_pp_schema_parses(pair):
    _, pp = pair
    r = pp.generate("json:", max_tokens=120, greedy=True, chat=False,
                    constraint={"json_schema": SCHEMA})
    obj = json.loads(r["response"])
    assert isinstance(obj["name"], str) and isinstance(obj["age"], int)


def test_1f1b_routes_constraint_to_plain_ring(pair):
    sd, _ = pair
    cfg = get_model_config("test-llama-tiny")
    params = sd.backend.params
    mb = create_engine(cfg, mesh_cfg=MeshConfig(pp=2), microbatches=2,
                       params=params,
                       engine_cfg=EngineConfig(prefill_buckets=(32, 64)))
    assert mb.backend.name == "pipeline-1f1b"
    spec = {"regex": "(red|green|blue|[0-9]{1,3})"}
    a = sd.generate("the answer is", max_tokens=40, greedy=True, chat=False,
                    constraint=spec)
    b = mb.generate("the answer is", max_tokens=40, greedy=True, chat=False,
                    constraint=spec)
    assert a["response"] == b["response"]


# -- continuous fleet (single-device backend, no shard_map needed) -----------

@pytest.fixture(scope="module")
def solo_engine():
    cfg = get_model_config("test-llama-tiny")
    return InferenceEngine(cfg, engine_cfg=EngineConfig(prefill_buckets=(32, 64)))


def test_continuous_mixed_slots(solo_engine):
    """Constrained and unconstrained requests coexist mid-decode in one
    fleet; every constrained result satisfies its OWN constraint and the
    unconstrained result matches its solo greedy run."""
    solo_free = solo_engine.generate(
        "tell me something", max_tokens=10, greedy=True, chat=False
    )
    cont = ContinuousEngine(solo_engine, n_slots=2, chunk_steps=4,
                            max_queue=16)
    try:
        results = {}
        lock = threading.Lock()

        def run(name, prompt, **kw):
            r = cont.submit(prompt, **kw)
            with lock:
                results[name] = r

        jobs = [
            ("color", "pick a color:", dict(
                max_tokens=20, greedy=True, chat=False,
                constraint={"regex": "(red|green|blue)"})),
            ("free", "tell me something", dict(
                max_tokens=10, greedy=True, chat=False)),
            ("digits", "digits:", dict(
                max_tokens=20, greedy=True, chat=False,
                constraint={"regex": "[0-9]{2,3}x"})),
            ("json", "emit:", dict(
                max_tokens=140, greedy=True, chat=False,
                constraint={"json_schema": SCHEMA})),
            ("choice", "pick:", dict(
                max_tokens=20, temperature=1.5, top_k=0, top_p=1.0,
                chat=False, constraint={"choices": ["on", "off"]})),
        ]
        threads = [
            threading.Thread(target=run, args=(n, p), kwargs=kw)
            for n, p, kw in jobs
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert set(results) == {n for n, _, _ in jobs}
        for name, r in results.items():
            assert r["status"] == "success", (name, r)
        assert re.fullmatch("red|green|blue", results["color"]["response"])
        assert re.fullmatch("[0-9]{2,3}x", results["digits"]["response"])
        obj = json.loads(results["json"]["response"])
        assert isinstance(obj["age"], int)
        assert results["choice"]["response"] in ("on", "off")
        # the unconstrained tenant decoded EXACTLY its solo stream even
        # while constrained tenants shared the fleet
        assert results["free"]["response"] == solo_free["response"]
        assert results["free"].get("constrained") is None
        assert results["color"].get("constrained") is True
        # residency drained back to zero active
        st = cont.stats()
        assert st["constraints"]["active"] == 0
    finally:
        cont.close()


def test_continuous_constraint_reuse_and_release(solo_engine):
    """Same constraint admitted twice reuses the resident table rows
    (refcount), and release frees them for compaction."""
    cont = ContinuousEngine(solo_engine, n_slots=2, chunk_steps=4,
                            max_queue=16)
    try:
        spec = {"choices": ["yes", "no"]}
        for _ in range(2):
            r = cont.submit("q:", max_tokens=15, greedy=True, chat=False,
                            constraint=spec)
            assert r["response"] in ("yes", "no")
        st = cont.stats()["constraints"]
        assert st["resident"] == 1 and st["active"] == 0
    finally:
        cont.close()


def test_continuous_paged_falls_back_solo(solo_engine):
    """constraint x paged fleet: served via the solo fallback (correct,
    just not fleet-batched) — never a failure, never unvalidated output."""
    cont = ContinuousEngine(solo_engine, n_slots=2, chunk_steps=4,
                            max_queue=16, kv_pool_blocks=40, kv_block_size=16)
    try:
        r = cont.submit("pick:", max_tokens=20, greedy=True, chat=False,
                        constraint={"regex": "(red|green|blue)"})
        assert r["status"] == "success"
        assert re.fullmatch("red|green|blue", r["response"])
        # solo fallback: the envelope is the solo engine's, not the fleet's
        assert r.get("continuous") is None
    finally:
        cont.close()


def test_continuous_streaming_constrained(solo_engine):
    """A constrained streaming request: deltas concatenate to the exact
    final (constraint-satisfying) response."""
    cont = ContinuousEngine(solo_engine, n_slots=2, chunk_steps=4,
                            max_queue=16)
    try:
        deltas = []
        final = None
        for ev in cont.stream("pick a color:", max_tokens=20, greedy=True,
                              chat=False,
                              constraint={"regex": "(red|green|blue)"}):
            if ev.get("done"):
                final = ev
                break
            deltas.append(ev.get("delta", ""))
        assert final is not None and final["status"] == "success"
        assert "".join(deltas) == final["response"]
        assert re.fullmatch("red|green|blue", final["response"])
    finally:
        cont.close()


def test_fleet_table_overflow_routes_solo(solo_engine):
    """A constraint whose DFA can never fit the fleet table serves via the
    solo engine instead of deadlocking the queue."""
    cont = ContinuousEngine(solo_engine, n_slots=2, chunk_steps=4,
                            max_queue=16)
    # shrink the fleet table so the schema constraint cannot ever fit
    cont._ctable.max_states = 8
    try:
        r = cont.submit("emit:", max_tokens=140, greedy=True, chat=False,
                        constraint={"json_schema": SCHEMA})
        assert r["status"] == "success"
        assert isinstance(json.loads(r["response"])["age"], int)
        assert r.get("continuous") is None  # solo envelope
    finally:
        cont.close()
