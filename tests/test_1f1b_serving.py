"""1F1B through the front door (round-2 review #4): BASELINE config 5's
microbatched backend served by the ENGINE and the HTTP surface, not just
a backend caller. Greedy fleets must match the plain pipeline backend
token-for-token (the zero-bubble schedule changes the compute order, not
the math — equivalence-tested in tests/test_schedule.py at the backend
level; here through the serving stack).
"""

import json
import urllib.request

import pytest

import jax

from distributed_llm_inference_tpu import (
    EngineConfig, MeshConfig, create_engine, get_model_config,
)
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.serving.server import InferenceServer

# fast-tier exclusion: 1F1B mesh compiles; run the full suite (plain
# `pytest`) to include it
pytestmark = pytest.mark.slow


class _NumTok:
    def encode(self, text):
        return [int(t) % 250 + 3 for t in text.split()] or [3]

    def decode(self, toks, skip_special_tokens=True):
        return " ".join(str(int(t)) for t in toks)


@pytest.fixture(scope="module")
def engines():
    cfg = get_model_config("test-llama-tiny", eos_token_id=-1)
    params = M.init_params(cfg, jax.random.PRNGKey(9))
    ecfg = EngineConfig(prefill_buckets=(32,))
    plain = create_engine(
        cfg, mesh_cfg=MeshConfig(pp=2), params=params, tokenizer=_NumTok(),
        engine_cfg=ecfg,
    )
    f1b = create_engine(
        cfg, mesh_cfg=MeshConfig(pp=2), microbatches=2, params=params,
        tokenizer=_NumTok(), engine_cfg=ecfg,
    )
    return plain, f1b


PROMPTS = [f"{3 * i + 1} {7 * i + 2} {5 * i + 4}" for i in range(8)]


def test_backend_selected(engines):
    _, f1b = engines
    assert f1b.backend.name == "pipeline-1f1b"
    assert f1b.backend.batch_granularity == 2


def test_batch8_matches_plain_pipeline_greedy(engines):
    plain, f1b = engines
    a = plain.generate_batch(PROMPTS, max_tokens=6, greedy=True, chat=False)
    b = f1b.generate_batch(PROMPTS, max_tokens=6, greedy=True, chat=False)
    assert a["status"] == b["status"] == "success"
    for ra, rb in zip(a["results"], b["results"]):
        assert ra["response"] == rb["response"]
        assert ra["tokens_generated"] == rb["tokens_generated"]


def test_solo_serves_on_plain_ring(engines):
    """Solo requests dispatch to the inherited plain-ring batch-1
    programs (round-3 review #3) — bit-identical to the plain pipeline,
    full solo envelope."""
    plain, f1b = engines
    a = plain.generate("11 22 33", max_tokens=5, greedy=True, chat=False)
    b = f1b.generate("11 22 33", max_tokens=5, greedy=True, chat=False)
    assert b["status"] == "success"
    assert b["response"] == a["response"]
    assert b["backend"] == "pipeline-1f1b"
    for k in ("time_taken", "tokens_generated", "tokens_per_sec",
              "prompt_tokens"):
        assert k in b


def test_solo_full_surface_on_1f1b(engines):
    """Round-3 review #3's acceptance: logprobs / logit_bias / penalties
    SERVE on the 1F1B backend now (plain-ring dispatch), identical to
    the plain pipeline."""
    plain, f1b = engines
    kw = dict(max_tokens=4, greedy=True, chat=False)
    a = plain.generate("1 2", logprobs=True, **kw)
    b = f1b.generate("1 2", logprobs=True, **kw)
    assert b["status"] == "success"
    assert b["response"] == a["response"]
    assert b["token_logprobs"] == a["token_logprobs"]
    a = plain.generate("1 2", logit_bias={"17": 100.0}, **kw)
    b = f1b.generate("1 2", logit_bias={"17": 100.0}, **kw)
    assert b["response"] == a["response"]
    assert set(b["response"].split()) == {"17"}
    a = plain.generate("5 5 5", frequency_penalty=1.5, **kw)
    b = f1b.generate("5 5 5", frequency_penalty=1.5, **kw)
    assert b["status"] == "success"
    assert b["response"] == a["response"]


def test_odd_batch_pads_to_granularity(engines):
    """B=3 on M=2 pads the fleet to 4 rows; 3 results come back."""
    _, f1b = engines
    r = f1b.generate_batch(PROMPTS[:3], max_tokens=4, greedy=True, chat=False)
    assert r["status"] == "success"
    assert len(r["results"]) == 3


def test_http_batch8_on_1f1b(engines):
    """The VERDICT's acceptance check: an HTTP {"prompts": [8]} request
    served by pipeline-1f1b, identical to the plain pipeline."""
    plain, f1b = engines
    expected = plain.generate_batch(PROMPTS, max_tokens=5, greedy=True,
                                    chat=False)
    server = InferenceServer(f1b, host="127.0.0.1", port=0)
    server.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/generate",
            data=json.dumps({
                "prompts": PROMPTS, "max_tokens": 5, "greedy": True,
                "chat": False,
            }).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=300) as resp:
            r = json.loads(resp.read())
        assert r["status"] == "success"
        assert r["backend"] == "pipeline-1f1b"
        got = [row["response"] for row in r["results"]]
        want = [row["response"] for row in expected["results"]]
        assert got == want
    finally:
        server.shutdown()


def test_1f1b_warmup(engines):
    """--warmup on a 1F1B engine compiles BOTH the batch-1 plain-ring solo
    programs (solo requests dispatch there now) and the granularity-
    multiple fleet programs."""
    _, f1b = engines
    stats = f1b.warmup(decode_buckets=(16,), batch_buckets=(2,))
    assert stats["programs"] > 0
