"""Quantized inter-stage transfers (ops/wire_quant.py +
EngineConfig.pp_wire_quant).

Four layers of coverage:

  * WireQuant primitive units — round-trip contracts, per-row scale
    isolation (an outlier token cannot poison its neighbors), and the
    shared-implementation guarantee with the KV cache's quantize_chunk;
  * collective semantics WITHOUT a mesh — `jax.vmap(axis_name=...)`
    carries ppermute/psum, so the off-path bit-identity contract
    (`wire_ppermute(quant=False)` IS `lax.ppermute`, `masked_psum`
    IS the masked-psum idiom) and the on-path round-trip numerics are
    asserted bitwise even on jax builds with no shard_map;
  * the CPU proxy (proxy_stage_generate/_match) — the pp ring's wire
    numerics replayed on one device: quant-off bit-identity with the
    single-device greedy path, and the greedy token-match-rate GATE
    (teacher-forced, per-decision — asserted, not eyeballed);
  * real-mesh tests (shard_map-gated like all pp tests): quant-off
    bit-identity with today's outputs on pp / 1F1B / sp / sp x pp,
    quant-on equality with the proxy's numerics twin, sp's
    wire==kv-quant prefill equivalence, and the chaos leg (crash + warm
    recovery mid-decode with the wire on stays bit-identical — the
    tolerance envelope's floor).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_llm_inference_tpu import EngineConfig, MeshConfig, get_model_config
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.ops import kv_quant as KQ
from distributed_llm_inference_tpu.ops import wire_quant as WQ

# Greedy token-match-rate gate for the int8 wire on the tiny proxy
# config (4 layers, dim 64, RANDOM weights — near-flat logits, far
# harsher than any real checkpoint): teacher-forced per-decision
# agreement, calibrated on this config (observed S=2 mean 0.995 / min
# 0.958, S=4 mean 0.969 / min 0.875 over 8 prompts).
WIRE_MATCH_MEAN = 0.90
WIRE_MATCH_MIN = 0.80
_N_TOKENS = 20


# -- WireQuant primitive units ------------------------------------------------

def test_roundtrip_shape_dtype_contract():
    for dt in (jnp.float32, jnp.bfloat16):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 16), dt)
        w = WQ.wire_encode(x)
        assert w.q.shape == x.shape and w.q.dtype == jnp.int8
        assert w.s.shape == x.shape[:-1] and w.s.dtype == jnp.float32
        back = WQ.wire_decode(w, x.dtype)
        assert back.shape == x.shape and back.dtype == dt
        # symmetric int8: quantization error bounded by half a step/row
        # (measured pre-cast — the bf16 restore adds its own rounding)
        err = jnp.abs(
            WQ.wire_decode(w, jnp.float32) - x.astype(jnp.float32)
        )
        assert float(jnp.max(err - 0.5 * w.s[..., None])) <= 1e-6


def test_outlier_token_keeps_own_scale():
    """Per-row scales: blowing up one token's row must not change any
    OTHER row's reconstruction by a single bit."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 32))
    spiked = x.at[0, 2].multiply(1e4)
    base = WQ.wire_roundtrip(x)
    spk = WQ.wire_roundtrip(spiked)
    for t in (0, 1, 3):
        np.testing.assert_array_equal(
            np.asarray(base[0, t]), np.asarray(spk[0, t])
        )
    # and the outlier row still reconstructs to its own magnitude
    assert float(jnp.max(jnp.abs(spk[0, 2]))) > 1e3


def test_zero_rows_stay_zero():
    x = jnp.zeros((2, 3, 8))
    w = WQ.wire_encode(x)
    assert float(jnp.max(jnp.abs(WQ.wire_decode(w, x.dtype)))) == 0.0


def test_kv_quant_shares_wire_impl():
    """quantize_chunk IS quantize_rows — cache and wire quantization
    cannot drift (the one-implementation satellite)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 4, 16))
    q1, s1 = WQ.quantize_rows(x)
    q2, s2 = KQ.quantize_chunk(x)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_wirequant_is_pytree():
    w = WQ.wire_encode(jnp.ones((2, 4)))
    leaves = jax.tree.leaves(w)
    assert len(leaves) == 2
    w2 = jax.tree.map(lambda a: a, w)
    assert isinstance(w2, WQ.WireQuant)


def test_wire_bytes_formula():
    # f32 [1, 1, 64]: 256 bytes raw vs 64 int8 + 4 scale = 3.76x
    off = WQ.wire_bytes((1, 1, 64), 4, 1, quant=False)
    on = WQ.wire_bytes((1, 1, 64), 4, 1, quant=True)
    assert off == 256 and on == 68
    assert off / on >= 2.0
    assert WQ.wire_bytes((2, 3, 64), 4, 5, quant=False) == 2 * 3 * 64 * 4 * 5


# -- collective semantics under vmap (no shard_map needed) --------------------

_PERM4 = [(0, 1), (1, 2), (2, 3), (3, 0)]


def _ring(fn, x):
    return jax.vmap(fn, axis_name="r")(x)


def test_wire_ppermute_off_is_lax_ppermute():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 2, 8))
    a = _ring(lambda y: WQ.wire_ppermute(y, "r", _PERM4, quant=False), x)
    b = _ring(lambda y: jax.lax.ppermute(y, "r", _PERM4), x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_wire_ppermute_on_is_roundtrip_then_permute():
    """The receiving stage sees exactly wire_roundtrip(sender's buffer)
    — the property the CPU proxy (and the mesh-equals-proxy test)
    stand on."""
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 2, 8))
    a = _ring(lambda y: WQ.wire_ppermute(y, "r", _PERM4, quant=True), x)
    b = _ring(
        lambda y: jax.lax.ppermute(WQ.wire_roundtrip(y), "r", _PERM4), x
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_masked_psum_off_is_masked_psum():
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 1, 8))

    def off(y):
        sel = jax.lax.axis_index("r") == 0
        return WQ.masked_psum(y, sel, "r", quant=False)

    def ref(y):
        sel = jax.lax.axis_index("r") == 0
        return jax.lax.psum(jnp.where(sel, y, jnp.zeros((), y.dtype)), "r")

    np.testing.assert_array_equal(
        np.asarray(_ring(off, x)), np.asarray(_ring(ref, x))
    )


def test_masked_psum_on_broadcasts_owner_roundtrip():
    """Quantized masked broadcast: every participant lands exactly the
    owner's wire_roundtrip — one nonzero int8 contribution, no
    overflow, no cross-talk."""
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 1, 8))

    def on(y):
        sel = jax.lax.axis_index("r") == 0
        return WQ.masked_psum(y, sel, "r", quant=True)

    got = _ring(on, x)
    want = WQ.wire_roundtrip(x[0])
    for r in range(4):
        np.testing.assert_array_equal(np.asarray(got[r]), np.asarray(want))


# -- config validation + metrics ---------------------------------------------

def test_engine_config_validates_pp_wire_quant():
    with pytest.raises(ValueError, match="pp_wire_quant must be None or"):
        EngineConfig(pp_wire_quant="int4")
    with pytest.raises(ValueError, match="pp_wire_quant must be None or"):
        EngineConfig(pp_wire_quant="fp8")
    EngineConfig(pp_wire_quant="int8")
    EngineConfig(pp_wire_quant=None)


def test_error_shape_matches_kv_quant():
    """The satellite contract: unknown values reject with the same error
    shape as kv_quant's."""
    cfg = get_model_config("test-llama-tiny")
    with pytest.raises(ValueError, match="kv_quant must be None or 'int8'"):
        cfg.replace(kv_quant="int4")
    with pytest.raises(
        ValueError, match="pp_wire_quant must be None or 'int8'"
    ):
        EngineConfig(pp_wire_quant="int4")


def test_metrics_preregistered_and_gauge_off_on_single_device():
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine

    eng = InferenceEngine(
        get_model_config("test-llama-tiny"),
        engine_cfg=EngineConfig(prefill_buckets=(32,)),
    )
    assert eng.metrics.get("dli_pp_wire_bytes_total") is not None
    snap = eng.metrics.snapshot()
    series = snap["dli_pp_wire_quant"]["series"]
    assert len(series) == 1 and series[0]["value"] == 0.0


# -- the CPU proxy (runs everywhere) ------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("test-llama-tiny")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _proxy_prompt(seed, cfg, n=16):
    rng = np.random.default_rng(seed)
    return rng.integers(3, cfg.vocab_size, size=n).tolist()


def test_proxy_off_bit_identical_to_single_device(tiny):
    """quant=False stage-sliced proxy == the real single-device greedy
    path, token for token — so the proxy's quant-on delta isolates
    exactly the wire quantization."""
    cfg, params = tiny
    prompt = _proxy_prompt(0, cfg, 12)
    N = _N_TOKENS
    got = WQ.proxy_stage_generate(cfg, params, prompt, N, 4, quant=False)
    toks = jnp.asarray([prompt], jnp.int32)
    cache = M.init_kv_cache(cfg, 1, max_seq=64)
    sampling = G.default_sampling(greedy=True)
    first, _, cache = G.prefill(
        cfg, params, toks, jnp.int32(len(prompt)), cache,
        jax.random.PRNGKey(0), sampling,
    )
    out, _, _ = G.decode(
        cfg, params, first, cache, jnp.int32(len(prompt)), jnp.int32(N - 1),
        jax.random.PRNGKey(1), sampling, None, None, None, None, None,
        max_steps=N - 1,
    )
    ref = [int(first[0])] + [int(t) for t in np.asarray(out[0])[: N - 1]]
    assert got == ref


@pytest.mark.parametrize("stages", [2, 4])
def test_proxy_greedy_match_rate_gate(tiny, stages):
    """THE quality gate: teacher-forced greedy agreement of the
    wire-quantized forward, asserted against the documented tolerance
    (not eyeballed). Per-decision — one flip cannot cascade."""
    cfg, params = tiny
    rates = [
        WQ.proxy_stage_match(
            cfg, params, _proxy_prompt(seed, cfg), _N_TOKENS, stages
        )
        for seed in range(6)
    ]
    assert float(np.mean(rates)) >= WIRE_MATCH_MEAN, rates
    assert min(rates) >= WIRE_MATCH_MIN, rates


# -- real-mesh tests (shard_map-gated like all pp tests) ----------------------

def _pb(cfg, params, eight_devices, pp, **kw):
    from distributed_llm_inference_tpu.parallel.mesh import build_mesh
    from distributed_llm_inference_tpu.parallel.pipeline import PipelineBackend

    mesh = build_mesh(MeshConfig(dp=1, pp=pp, tp=1), eight_devices)
    return PipelineBackend(cfg, params, mesh, **kw)


def _greedy_seq(backend, prompt, n):
    toks = jnp.asarray([prompt], jnp.int32)
    sampling = G.default_sampling(greedy=True)
    cache = backend.init_cache(1, 64)
    first, _, cache = backend.prefill(
        toks, jnp.int32(len(prompt)), cache, jax.random.PRNGKey(0), sampling
    )
    out, _, _ = backend.decode(
        first, cache, jnp.int32(len(prompt)), jnp.int32(n - 1),
        jax.random.PRNGKey(1), sampling, max_steps=n - 1,
    )
    return [int(first[0])] + [int(t) for t in np.asarray(out[0])[: n - 1]]


def test_pp_wire_off_bit_identical(tiny, eight_devices):
    """pp_wire_quant=None is bit-identical to today's outputs (and both
    are bit-identical to the single device — the pre-existing pp
    invariant catches an off-path that accidentally quantizes)."""
    cfg, params = tiny
    prompt = _proxy_prompt(0, cfg, 12)
    base = _greedy_seq(_pb(cfg, params, eight_devices, 2), prompt, 12)
    off = _greedy_seq(
        _pb(cfg, params, eight_devices, 2, wire_quant=None), prompt, 12
    )
    assert off == base
    solo = WQ.proxy_stage_generate(cfg, params, prompt, 12, 2, quant=False)
    assert base == solo


def test_pp_wire_on_matches_proxy_numerics(tiny, eight_devices):
    """The numerics-twin contract: the pp=2 mesh with the int8 wire on
    emits EXACTLY the proxy's quantized sequence — every hand-off is one
    row-local wire_roundtrip, nothing else differs."""
    cfg, params = tiny
    pb = _pb(cfg, params, eight_devices, 2, wire_quant="int8")
    for seed in range(3):
        prompt = _proxy_prompt(seed, cfg, 12)
        mesh_seq = _greedy_seq(pb, prompt, 12)
        proxy_seq = WQ.proxy_stage_generate(
            cfg, params, prompt, 12, 2, quant=True
        )
        assert mesh_seq == proxy_seq, (seed, mesh_seq, proxy_seq)


def test_pp_wire_on_match_rate_gate(tiny, eight_devices):
    """Per-decision gate on the real mesh: the FIRST sampled token of
    each prefill is one independent decision (no cascade) — agreement
    with the exact single-device first token must clear the documented
    floor."""
    cfg, params = tiny
    pb = _pb(cfg, params, eight_devices, 4, wire_quant="int8")
    sampling = G.default_sampling(greedy=True)
    hits = total = 0
    for seed in range(8):
        prompt = _proxy_prompt(seed, cfg, 12)
        toks = jnp.asarray([prompt], jnp.int32)
        cache = M.init_kv_cache(cfg, 1, max_seq=64)
        ref, _, _ = G.prefill(
            cfg, params, toks, jnp.int32(len(prompt)), cache,
            jax.random.PRNGKey(0), sampling,
        )
        cache_p = pb.init_cache(1, 64)
        got, _, _ = pb.prefill(
            toks, jnp.int32(len(prompt)), cache_p, jax.random.PRNGKey(0),
            sampling,
        )
        hits += int(int(got[0]) == int(ref[0]))
        total += 1
    assert hits / total >= WIRE_MATCH_MIN, (hits, total)


@pytest.mark.slow
def test_1f1b_wire_off_and_on(tiny, eight_devices):
    """1F1B fleet decode: wire off is bit-identical to the default
    backend; wire on emits the proxy's quantized sequence per row (the
    1F1B schedule gives every token the same S hops + one broadcast as
    the plain ring)."""
    from distributed_llm_inference_tpu.parallel.mesh import build_mesh
    from distributed_llm_inference_tpu.parallel.schedule import (
        MicrobatchPipelineBackend,
    )

    cfg, params = tiny
    mesh = build_mesh(MeshConfig(dp=1, pp=2, tp=1), eight_devices)
    prompts = [_proxy_prompt(s, cfg, 12) for s in range(2)]
    toks = jnp.asarray(prompts, jnp.int32)
    sampling = G.default_sampling(greedy=True)

    def fleet_seq(backend, n=10):
        cache = backend.init_cache(2, 64)
        first, _, cache = backend.prefill(
            toks, jnp.int32(12), cache, jax.random.PRNGKey(0), sampling
        )
        out, _, _ = backend.decode(
            first, cache, jnp.int32(12), jnp.int32(n - 1),
            jax.random.PRNGKey(1), sampling, max_steps=n - 1,
        )
        return [
            [int(first[r])] + [int(t) for t in np.asarray(out[r])[: n - 1]]
            for r in range(2)
        ]

    base = fleet_seq(MicrobatchPipelineBackend(cfg, params, mesh))
    off = fleet_seq(
        MicrobatchPipelineBackend(cfg, params, mesh, wire_quant=None)
    )
    assert off == base
    on = fleet_seq(
        MicrobatchPipelineBackend(cfg, params, mesh, wire_quant="int8")
    )
    for r in range(2):
        proxy_seq = WQ.proxy_stage_generate(
            cfg, params, prompts[r], 10, 2, quant=True
        )
        assert on[r] == proxy_seq, (r, on[r], proxy_seq)


def test_sp_wire_off_bit_identical_and_on_equals_kv_quant_prefill(
    tiny, eight_devices
):
    """sp ring: wire off == today's outputs; wire ON attends exactly the
    quantized chunk round-trip — which is the SAME attention math the
    int8 KV cache performs — so the wire-on prefill's sampled token
    equals the kv_quant="int8" prefill's, bit for bit."""
    from distributed_llm_inference_tpu.parallel.context import (
        ContextParallelBackend,
    )
    from distributed_llm_inference_tpu.parallel.mesh import build_mesh

    cfg, params = tiny
    mesh = build_mesh(MeshConfig(dp=1, pp=1, sp=2, tp=1), eight_devices)
    prompt = _proxy_prompt(0, cfg, 16)  # bucket 16 % sp == 0
    toks = jnp.asarray([prompt], jnp.int32)
    sampling = G.default_sampling(greedy=True)

    def sp_first(backend):
        cache = backend.init_cache(1, 64)
        first, logits, _ = backend.prefill(
            toks, jnp.int32(16), cache, jax.random.PRNGKey(0), sampling
        )
        return int(first[0]), np.asarray(logits)

    base, logits_base = sp_first(ContextParallelBackend(cfg, params, mesh))
    off, logits_off = sp_first(
        ContextParallelBackend(cfg, params, mesh, wire_quant=None)
    )
    assert off == base
    np.testing.assert_array_equal(logits_off, logits_base)

    # isolate the chunk-hop recipe: the full wire ALSO quantizes the
    # final sampled-window broadcast, which kv_quant never does — with
    # that leg white-box disabled, the two attend byte-identical
    # quantized chunks and the prefill logits must match bit for bit
    pb_on = ContextParallelBackend(cfg, params, mesh, wire_quant="int8")
    pb_on._wire_bcast = False
    on, logits_on = sp_first(pb_on)
    kvq, logits_kvq = sp_first(
        ContextParallelBackend(cfg.replace(kv_quant="int8"), params, mesh)
    )
    assert on == kvq
    np.testing.assert_array_equal(logits_on, logits_kvq)

    # and the FULL wire (broadcast included) still samples a valid
    # token within a step of the kv-quant logits
    full, logits_full = sp_first(
        ContextParallelBackend(cfg, params, mesh, wire_quant="int8")
    )
    assert 0 <= full < cfg.vocab_size
    assert float(np.max(np.abs(logits_full - logits_kvq))) < 0.5


@pytest.mark.slow
def test_sp_pp_composition_wire(tiny, eight_devices):
    """sp x pp: off is bit-identical to the default composed backend;
    on serves greedy decode end to end (composition smoke + the
    per-decision first-token gate)."""
    from distributed_llm_inference_tpu.parallel.context import (
        ContextParallelBackend,
    )
    from distributed_llm_inference_tpu.parallel.mesh import build_mesh

    cfg, params = tiny
    mesh = build_mesh(MeshConfig(dp=1, pp=2, sp=2, tp=1), eight_devices)
    prompt = _proxy_prompt(0, cfg, 16)
    toks = jnp.asarray([prompt], jnp.int32)
    sampling = G.default_sampling(greedy=True)

    def run(backend, n=8):
        cache = backend.init_cache(1, 64)
        first, _, cache = backend.prefill(
            toks, jnp.int32(16), cache, jax.random.PRNGKey(0), sampling
        )
        out, n_gen, _ = backend.decode(
            first, cache, jnp.int32(16), jnp.int32(n - 1),
            jax.random.PRNGKey(1), sampling, max_steps=n - 1,
        )
        return [int(first[0])] + [int(t) for t in np.asarray(out[0])[: n - 1]]

    base = run(ContextParallelBackend(cfg, params, mesh))
    off = run(ContextParallelBackend(cfg, params, mesh, wire_quant=None))
    assert off == base
    on = run(ContextParallelBackend(cfg, params, mesh, wire_quant="int8"))
    assert len(on) == 8
    assert all(0 <= t < cfg.vocab_size for t in on)


@pytest.mark.slow
def test_pp_wire_chaos_crash_recovers_within_envelope(tiny, eight_devices):
    """The chaos leg: a mid-decode crash on a pp=2 paged fleet WITH the
    int8 wire on recovers warm and re-emits the fault-free wire-on
    output bit-identically — the recovery re-prefill's wire crossings
    are row-local, so the restored run cannot leave the envelope."""
    from distributed_llm_inference_tpu.engine.continuous import (
        ContinuousEngine,
    )
    from distributed_llm_inference_tpu.runtime import create_engine
    from distributed_llm_inference_tpu.utils import faults

    eng = create_engine(
        "test-llama-tiny", mesh_cfg=MeshConfig(pp=2),
        engine_cfg=EngineConfig(
            prefill_buckets=(32, 64), prefix_cache_entries=8,
            pp_wire_quant="int8",
        ),
    )
    assert eng.backend.wire_quant == "int8"
    prompt = "the quick brown fox jumps over the"
    ref = eng.generate(prompt, max_tokens=10, greedy=True, chat=False)
    cont = ContinuousEngine(
        eng, n_slots=2, chunk_steps=4, restart_backoff_s=0.01,
        kv_pool_blocks=48, kv_block_size=8,
    )
    try:
        r0 = cont.submit(prompt, max_tokens=10, greedy=True, chat=False)
        assert r0["response"] == ref["response"]
        assert cont._shadow is not None and cont._shadow.flush(10.0)
        faults.arm([
            faults.FaultRule("decode_launch", "transient", on_call=4)
        ])
        r1 = cont.submit(prompt, max_tokens=10, greedy=True, chat=False)
        faults.disarm()
        assert r1["status"] == "success", r1
        assert r1["response"] == ref["response"]
    finally:
        faults.disarm()
        cont.close()


def test_pp_wire_bytes_counter_accounts(tiny, eight_devices):
    """dli_pp_wire_bytes_total: attached through the engine seam, the
    backend counts static per-launch bytes on the microstep +
    broadcast families, and the quantized backend counts ~4x less."""
    from distributed_llm_inference_tpu.utils.metrics import MetricsRegistry

    cfg, params = tiny

    def bytes_for(wire):
        pb = _pb(cfg, params, eight_devices, 2, wire_quant=wire)
        reg = MetricsRegistry()
        reg.counter(
            "dli_pp_wire_bytes_total", "", ("path",)
        )
        pb.attach_wire_metrics(reg)
        _greedy_seq(pb, _proxy_prompt(0, cfg, 12), 8)
        snap = reg.snapshot()
        series = snap["dli_pp_wire_bytes_total"]["series"]
        return {
            tuple(s["labels"].items()): s["value"] for s in series
        }

    off = bytes_for(None)
    on = bytes_for("int8")
    assert any("microstep" in str(k) for k in off)
    assert any("broadcast" in str(k) for k in off)
    total_off = sum(off.values())
    total_on = sum(on.values())
    assert total_off / total_on >= 2.0
