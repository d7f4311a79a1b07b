"""Multi-tenant paged LoRA adapter serving (engine/adapters.py) tests.

The bar: many adapters off ONE resident base model without merging —
page 0 (the base page) is bit-identical to a build with no adapter
leaves at all; a single runtime adapter serves the same greedy stream
merge-at-load serves; a mixed-adapter fleet emits token-identical
output to each (prompt, adapter) served solo; the adapter mix never
grows the compiled-program set (the page ids are a traced operand);
the pool is strict refcount/LRU discipline (referenced pages are
untouchable, refcount-0 residents park instead of dropping); tenancy
is first-class (weighted prefill split, queue quota 429s, router
inflight quota); and a scheduler crash with adapters resident recovers
bit-identical with a clean page ledger.
"""

import json
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine.adapters import (
    AdapterPool,
    adapter_leaf_dims,
    attach_adapter_pool,
    install_adapter_leaves,
)
from distributed_llm_inference_tpu.engine.continuous import (
    ContinuousEngine,
    _Request,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.utils import faults

SERVE_CFG = dict(dtype="float32", eos_token_id=-1, max_seq_len=512)
RANK = 4
KW = dict(max_tokens=8, greedy=True, chat=False)


@pytest.fixture(scope="module")
def setup():
    cfg = get_model_config("test-llama-tiny", **SERVE_CFG)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _adapter_host(cfg, seed, rank=RANK, leaves=None):
    """Programmatic host adapter: {leaf: (a [L,in,r], b [L,r,out])}."""
    rng = np.random.default_rng(seed)
    dims = adapter_leaf_dims(cfg)
    if leaves is not None:
        dims = {k: dims[k] for k in leaves}
    return {
        leaf: (
            (rng.standard_normal((cfg.n_layers, d_in, rank))
             * 0.05).astype(np.float32),
            (rng.standard_normal((cfg.n_layers, rank, d_out))
             * 0.05).astype(np.float32),
        )
        for leaf, (d_in, d_out) in dims.items()
    }


def _cont(cfg, params, adapters=0, **kw):
    """Fleet builder; adapters=N attaches an N-page pool BEFORE the
    continuous engine is built (the create_engine wiring order)."""
    ecfg = dict(prefix_cache_entries=0, prefill_buckets=(64, 128, 256))
    ecfg.update(kw.pop("engine_cfg", {}))
    eng = InferenceEngine(cfg, params=params,
                          engine_cfg=EngineConfig(**ecfg))
    if adapters:
        attach_adapter_pool(eng, slots=adapters, rank=RANK)
    args = dict(n_slots=4, chunk_steps=8, slot_max_seq=512,
                kv_pool_blocks=120, kv_block_size=16,
                restart_backoff_s=0.01)
    args.update(kw)
    return ContinuousEngine(eng, **args)


# -- pool units (no device, no engine) ----------------------------------------

class _FakeBackend:
    """Records page writes; the pool never reads them back."""

    def __init__(self):
        self.writes = []

    def write_adapter_page(self, page, updates):
        self.writes.append((page, tuple(sorted(updates))))


def _pool(cfg, slots=2, **kw):
    return AdapterPool(cfg, _FakeBackend(), slots, RANK, **kw)


def test_pool_refcount_and_lru_eviction(setup):
    cfg, _ = setup
    pool = _pool(cfg, slots=2)
    for name, seed in (("a", 1), ("b", 2), ("c", 3)):
        pool.register(name, _adapter_host(cfg, seed))
    pa = pool.acquire("a")
    assert pa in (1, 2)
    assert pool.acquire("a") == pa  # second holder, same page, no write
    assert len(pool.backend.writes) == 1
    pb = pool.acquire("b")
    assert pb != pa
    # every page referenced: backpressure, NOT eviction
    assert pool.acquire("c") is None
    assert pool.free == 0
    # refcount 2 on a: one release keeps it referenced
    pool.release("a")
    assert pool.acquire("c") is None
    pool.release("a")  # refcount 0: parks in the LRU, still resident
    assert pool.free == 1
    pc = pool.acquire("c")  # evicts the LRU resident (a), reuses its page
    assert pc == pa
    st = pool.stats()
    assert st["evictions"] == 1 and st["swaps"] == 1 and st["loads"] == 3
    # b and c referenced again: a cannot come back until a release
    assert pool.acquire("a") is None
    pool.release("b")
    assert pool.acquire("a") == pb  # evicts b, the only refcount-0 page
    pool.release("a")
    pool.release("c")
    assert pool.free == pool.total and pool.referenced() == 0


def test_pool_acquire_unknown_adapter_raises(setup):
    cfg, _ = setup
    pool = _pool(cfg)
    with pytest.raises(KeyError):
        pool.acquire("never-registered")


def test_pool_over_release_clamps(setup):
    cfg, _ = setup
    pool = _pool(cfg)
    pool.register("a", _adapter_host(cfg, 1))
    page = pool.acquire("a")
    pool.release("a")
    pool.release("a")  # accounting bug surfaced in the log, then clamped
    assert pool.referenced() == 0
    assert pool.acquire("a") == page  # still serviceable, no re-write
    assert len(pool.backend.writes) == 1


def test_pool_reset_refs_parks_residents(setup):
    """Crash recovery: holders die with the fleet, page CONTENT survives
    (the leaves live in params) — residents park in the LRU and the
    recovered requests reload nothing."""
    cfg, _ = setup
    pool = _pool(cfg, slots=2)
    pool.register("a", _adapter_host(cfg, 1))
    pool.register("b", _adapter_host(cfg, 2))
    pa, pb = pool.acquire("a"), pool.acquire("b")
    pool.acquire("a")
    pool.reset_refs()
    assert pool.referenced() == 0 and pool.free == 2
    writes = len(pool.backend.writes)
    assert pool.acquire("a") == pa and pool.acquire("b") == pb
    assert len(pool.backend.writes) == writes  # zero reloads


def test_register_validation(setup):
    cfg, _ = setup
    pool = _pool(cfg)
    with pytest.raises(ValueError, match="non-empty"):
        pool.register("", _adapter_host(cfg, 1))
    with pytest.raises(ValueError, match="base model name"):
        pool.register(cfg.name, _adapter_host(cfg, 1))
    pool.register("a", _adapter_host(cfg, 1))
    with pytest.raises(ValueError, match="already registered"):
        pool.register("a", _adapter_host(cfg, 1))
    bad = dict(_adapter_host(cfg, 2), nope=_adapter_host(cfg, 2)["wq"])
    with pytest.raises(ValueError, match="no adapter leaves"):
        pool.register("b", bad)
    wrong = _adapter_host(cfg, 3)
    a, b = wrong["wq"]
    wrong["wq"] = (a[:, :, :-1], b)  # rank mismatch
    with pytest.raises(ValueError, match="do not match"):
        pool.register("c", wrong)


def test_register_rejects_the_merged_adapter(setup):
    """Satellite: the --lora merge-at-load adapter may not ALSO register
    as a runtime adapter — its delta is already in the dense weights, so
    serving it through a page would apply the delta twice."""
    cfg, _ = setup
    pool = _pool(cfg, merged_source="/tmp/some/adapter")
    with pytest.raises(ValueError, match="already merged"):
        pool.register("tuned", "/tmp/some/../some/adapter")
    # a DIFFERENT path is not the merged adapter: it proceeds into the
    # on-disk loader (and fails there on the fake path, not on the
    # collision check)
    with pytest.raises(Exception) as ei:
        pool.register("other", "/tmp/not/that/adapter")
    assert "already merged" not in str(ei.value)


def test_install_leaves_shapes_and_validation(setup):
    cfg, params = setup
    out = install_adapter_leaves(cfg, params, slots=2, rank=RANK)
    L, P = cfg.n_layers, 3
    for leaf, (d_in, d_out) in adapter_leaf_dims(cfg).items():
        a = out["layers"][f"lora_{leaf}_a"]
        b = out["layers"][f"lora_{leaf}_b"]
        assert a.shape == (L, P, d_in, RANK)
        assert b.shape == (L, P, RANK, d_out)
        assert not np.asarray(a).any() and not np.asarray(b).any()
    # the original params are untouched (fresh dicts on the way out)
    assert "lora_wq_a" not in params["layers"]
    with pytest.raises(ValueError, match="llama"):
        install_adapter_leaves(
            cfg.replace(arch="gpt2", n_kv_heads=cfg.n_heads), params,
            2, RANK,
        )
    with pytest.raises(ValueError, match="adapter_slots"):
        install_adapter_leaves(cfg, params, 0, RANK)
    with pytest.raises(ValueError, match="adapter_rank"):
        install_adapter_leaves(cfg, params, 2, 0)


# -- identity gates (the acceptance bar) --------------------------------------

PROMPTS = [
    "the quick brown fox jumps over the lazy dog",
    "pack my box with five dozen liquor jugs",
    "how vexingly quick daft zebras jump",
    "short",
]


@pytest.fixture(scope="module")
def fleet(setup):
    """One adapter-carrying fleet shared by the identity tests: 2 pages,
    adapters ad-a / ad-b registered."""
    cfg, params = setup
    cont = _cont(cfg, params, adapters=2)
    pool = cont.engine.adapters
    pool.register("ad-a", _adapter_host(cfg, 1))
    pool.register("ad-b", _adapter_host(cfg, 2))
    yield cont, pool
    cont.close()


def test_base_request_bit_identical_to_no_adapter_build(setup, fleet):
    """Adapter id 0 IS the base model: a request naming no adapter on the
    adapter-carrying fleet emits byte-identical greedy output to a build
    with no adapter leaves installed at all (the where-select contract —
    the delta is skipped, not added as zero)."""
    cfg, params = setup
    cont_a, _ = fleet
    plain = _cont(cfg, params)
    try:
        for p in PROMPTS[:2]:
            ra = cont_a.submit(p, **KW)
            rp = plain.submit(p, **KW)
            assert ra["status"] == rp["status"] == "success"
            assert ra["response"] == rp["response"]
    finally:
        plain.close()


def test_single_adapter_matches_merge_at_load(setup, fleet):
    """The runtime-page path and merge-at-load serve the same adapter the
    same way: greedy output through (x@a)@b on page p equals a build
    whose dense weights carry W + a@b baked in."""
    cfg, params = setup
    cont_a, _ = fleet
    host = _adapter_host(cfg, 1)  # ad-a's exact tensors
    layers = dict(params["layers"])
    for leaf, (a, b) in host.items():
        delta = np.einsum("lir,lro->lio", a, b)
        layers[leaf] = layers[leaf] + delta.astype(np.float32)
    merged = dict(params, layers=layers)
    cont_m = _cont(cfg, merged)
    try:
        for p in PROMPTS[:2]:
            rr = cont_a.submit(p, adapter="ad-a", **KW)
            rm = cont_m.submit(p, **KW)
            assert rr["status"] == rm["status"] == "success"
            assert rr["response"] == rm["response"]
    finally:
        cont_m.close()


def test_mixed_fleet_token_identical_to_solo(fleet):
    """The headline gate: every (prompt, adapter) pair served inside a
    threaded mixed-adapter fleet emits exactly the tokens it emits served
    alone — base rows included."""
    cont, pool = fleet
    jobs = [
        (p, ad)
        for p in PROMPTS
        for ad in (None, "ad-a", "ad-b")
    ]
    solo = {}
    for p, ad in jobs:
        extra = {"adapter": ad} if ad else {}
        r = cont.submit(p, **KW, **extra)
        assert r["status"] == "success", r
        solo[(p, ad)] = r["response"]

    mixed, lock = {}, threading.Lock()
    it = iter(jobs)

    def client():
        while True:
            with lock:
                j = next(it, None)
            if j is None:
                return
            p, ad = j
            extra = {"adapter": ad} if ad else {}
            r = cont.submit(p, **KW, **extra)
            with lock:
                mixed[(p, ad)] = r.get("response")

    threads = [threading.Thread(target=client) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert mixed == solo
    # post-drain pool hygiene: nothing holds a page, residents parked
    assert pool.referenced() == 0
    assert pool.free == pool.total


def test_adapter_mix_never_recompiles(fleet):
    """One compiled program serves ANY adapter mix: the page ids are a
    traced operand, so churning through different adapter combinations
    leaves the jit caches exactly where the warmup put them."""
    from distributed_llm_inference_tpu.engine import paged as EP

    cont, _ = fleet
    # warm every program shape with one mixed pass (the earlier tests in
    # this module already churned the fleet, but stay self-sufficient)
    for ad in (None, "ad-a", "ad-b"):
        extra = {"adapter": ad} if ad else {}
        cont.submit(PROMPTS[0], **KW, **extra)
    mixed_programs = EP.mixed_step_ragged._cache_size()
    ingest_programs = cont.engine.backend.ragged_program_count()
    jobs = [(p, ad) for p in PROMPTS[:3]
            for ad in ("ad-b", None, "ad-a")]
    lock = threading.Lock()
    it = iter(jobs)

    def client():
        while True:
            with lock:
                j = next(it, None)
            if j is None:
                return
            p, ad = j
            extra = {"adapter": ad} if ad else {}
            cont.submit(p, **KW, **extra)

    threads = [threading.Thread(target=client) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert EP.mixed_step_ragged._cache_size() == mixed_programs
    assert cont.engine.backend.ragged_program_count() == ingest_programs


def test_adapter_request_rejections(setup, fleet):
    cfg, params = setup
    cont, _ = fleet
    r = cont.submit(PROMPTS[0], adapter="nope", **KW)
    assert r["status"] == "failed"
    assert r["error_type"] == "invalid_request"
    assert "unknown adapter" in r["error"]
    # solo-engine contracts cannot ride an adapter page
    r = cont.submit(PROMPTS[0], adapter="ad-a", seed=7,
                    max_tokens=4, chat=False)
    assert r["status"] == "failed" and "solo" in r["error"]
    # a fleet with NO pool attached rejects adapter requests outright
    plain = _cont(cfg, params)
    try:
        r = plain.submit(PROMPTS[0], adapter="ad-a", **KW)
        assert r["status"] == "failed"
        assert "adapter pool" in r["error"]
    finally:
        plain.close()


# -- tenancy ------------------------------------------------------------------

def test_tenant_weighted_prefill_split():
    """Within one class's tile grant, tenants split by configured weight:
    a weight-3 tenant's job out-apportions a weight-1 tenant's equal-age
    job roughly 3:1, and a single-tenant class degenerates to FIFO."""
    from distributed_llm_inference_tpu.engine.scheduler import (
        PrefillJob,
        SLOClass,
        TokenBudgetScheduler,
    )

    class _Req:
        def __init__(self, tenant):
            self.enqueued = 0.0
            self.tenant = tenant

    def job(tenant, slot):
        return PrefillJob(
            _Req(tenant), ids=list(range(400)), p0=0, prompt_len=400,
            max_tokens=4, slot=slot,
            sampling=(0.7, 50, 0.9, True, 0.0, 1.0, 0.0, 0.0),
            presence_row=None, table_row=None, cls=cls,
        )

    classes = {"standard": SLOClass("standard", 2.0, 0.5, 2.0, True)}
    cls = classes["standard"]
    s = TokenBudgetScheduler(
        classes, "standard", 256, 8, 4,
        tenant_weights=(("heavy", 3.0), ("light", 1.0)),
    )
    jh, jl = job("heavy", 0), job("light", 1)
    plan = {id(j): n for j, n in s.plan(0, [jl, jh], now=1.0)}
    assert plan[id(jh)] > 2 * plan[id(jl)] > 0
    # same class, no tenants: pure FIFO — the first-arrived job gets at
    # least as much of the grant as the second
    j0, j1 = job(None, 0), job(None, 1)
    plan = {id(j): n for j, n in s.plan(0, [j0, j1], now=1.0)}
    assert plan[id(j0)] >= plan.get(id(j1), 0)


def test_tenant_queue_quota_sheds(setup):
    """One tenant's queued share of the bounded queue is capped: the
    over-quota tenant 429s (with its name in the envelope) while other
    tenants and anonymous traffic still queue."""
    cfg, params = setup
    cont = _cont(cfg, params, max_queue=8,
                 engine_cfg={"tenant_max_queue_share": 0.5})
    try:
        with cont._cv:
            for i in range(4):  # cap = max(4, int(8 * 0.5)) = 4
                q = _Request(f"fill {i}",
                             dict(max_tokens=4, greedy=True, chat=False))
                q.slo = "standard"
                q.tenant = "flood"
                cont._queue.append(q)
            cont._note_queue_locked()
        req = _Request("over", dict(max_tokens=4, greedy=True, chat=False))
        req.slo = None
        req.tenant = "flood"
        shed = cont._enqueue(req)
        assert shed is not None and shed["error_type"] == "overloaded"
        assert shed["tenant"] == "flood"
        assert "queue quota" in shed["error"]
        assert shed["retry_after_s"] >= 0
        # another tenant (and anonymous traffic) is untouched
        ok = _Request("fine", dict(max_tokens=4, greedy=True, chat=False))
        ok.slo = None
        ok.tenant = "other"
        assert cont._enqueue(ok) is None
        anon = _Request("anon", dict(max_tokens=4, greedy=True, chat=False))
        anon.slo = None
        assert cont._enqueue(anon) is None
        # the per-tenant shed counter carries the tenant label
        snap = cont.engine.metrics.snapshot()
        series = {
            s["labels"].get("tenant"): s["value"]
            for s in snap.get("dli_tenant_shed_total", {}).get("series", [])
        }
        assert series.get("flood") == 1
        with cont._cv:
            cont._queue.clear()
            cont._note_queue_locked()
    finally:
        cont.close()


def test_queue_depth_gauge_carries_tenant_label(setup):
    cfg, params = setup
    cont = _cont(cfg, params)
    try:
        cont.submit(PROMPTS[3], tenant="acme", **KW)
        snap = cont.engine.metrics.snapshot()
    finally:
        cont.close()
    series = {
        (s["labels"]["slo_class"], s["labels"]["tenant"])
        for s in snap.get("dli_slo_queue_depth", {}).get("series", [])
    }
    # the tenant ever seen keeps its series (reads 0 after drain), and
    # the anonymous series stays schema-stable alongside it
    assert ("standard", "acme") in series
    assert ("standard", "") in series


def test_router_tenant_inflight_quota():
    from distributed_llm_inference_tpu.serving.router import (
        Replica,
        Router,
    )

    router = Router([Replica("r1", "http://127.0.0.1:9")],
                    tenant_max_inflight_share=0.5)
    # the floor: a quiet router admits a few requests from anyone
    for _ in range(4):
        assert router.tenant_begin("acme")
    # 4 inflight, cap = max(4, int(4 * 0.5)) = 4: the 5th sheds
    assert not router.tenant_begin("acme")
    # other tenants and the anonymous bucket are unaffected
    assert router.tenant_begin("globex")
    assert router.tenant_begin(None)
    # anonymous load raises the total, so the cap loosens: 6 inflight
    # -> cap 4 still binds at 4... grow the pie past 8 and acme fits
    for _ in range(4):
        assert router.tenant_begin("")
    assert router.tenant_begin("acme")  # cap = int(10 * .5) = 5 now
    router.tenant_end("acme")
    snap = router.metrics.snapshot()
    series = {
        s["labels"].get("tenant"): s["value"]
        for s in snap.get("dli_tenant_shed_total", {}).get("series", [])
    }
    assert series.get("acme") == 1


def test_router_affinity_key_is_adapter_scoped():
    """The same prompt under two adapters must never share an affinity
    chain (adapter KV is conditioned on adapter weights); the OpenAI
    `model` field scopes identically."""
    from distributed_llm_inference_tpu.serving.router import _affinity_key

    base = _affinity_key({"prompt": "shared prefix text"})
    ka = _affinity_key({"prompt": "shared prefix text", "adapter": "ad-a"})
    kb = _affinity_key({"prompt": "shared prefix text", "adapter": "ad-b"})
    km = _affinity_key({"prompt": "shared prefix text", "model": "ad-a"})
    assert len({base, ka, kb}) == 3
    assert ka == km  # /generate adapter and OpenAI model key the same
    assert ka.endswith("shared prefix text")


# -- HTTP surface -------------------------------------------------------------

def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture(scope="module")
def served(setup):
    from distributed_llm_inference_tpu.serving.server import InferenceServer

    cfg, params = setup
    cont = _cont(cfg, params, adapters=2)
    cont.engine.adapters.register("ad-a", _adapter_host(cfg, 1))
    server = InferenceServer(cont.engine, host="127.0.0.1", port=0,
                             continuous=cont)
    server.start()
    yield server
    server.shutdown()


def test_models_route_lists_adapters(served):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{served.port}/v1/models", timeout=30
    ) as resp:
        models = json.loads(resp.read())
    ids = {m["id"]: m for m in models["data"]}
    assert "test-llama-tiny" in ids and "ad-a" in ids
    assert ids["ad-a"]["root"] == "test-llama-tiny"


def test_generate_adapter_resolution(served):
    status, body = _post(served.port, "/generate",
                         {"prompt": "hi there", "adapter": "ad-a",
                          "max_tokens": 4, "greedy": True, "chat": False})
    assert status == 200 and body["status"] == "success"
    status, body = _post(served.port, "/generate",
                         {"prompt": "hi", "adapter": "nope",
                          "max_tokens": 4})
    assert status == 400 and "unknown adapter" in body["error"]
    status, body = _post(served.port, "/generate",
                         {"prompt": "hi", "adapter": 7, "max_tokens": 4})
    assert status == 400
    # naming the base model is the base path, not an adapter lookup
    status, body = _post(served.port, "/generate",
                         {"prompt": "hi", "adapter": "test-llama-tiny",
                          "max_tokens": 4, "greedy": True, "chat": False})
    assert status == 200 and body["status"] == "success"


def test_openai_model_resolves_to_adapter(served):
    status, body = _post(
        served.port, "/v1/completions",
        {"model": "ad-a", "prompt": "hello", "max_tokens": 4},
    )
    assert status == 200 and body["model"] == "ad-a"
    status, body = _post(
        served.port, "/v1/completions",
        {"model": "not-registered", "prompt": "hello", "max_tokens": 4},
    )
    assert status == 400
    assert "neither the base model" in body["error"]["message"]
    # the base name keeps meaning the base
    status, body = _post(
        served.port, "/v1/completions",
        {"model": "test-llama-tiny", "prompt": "hello", "max_tokens": 4},
    )
    assert status == 200


def test_tenant_field_validation(served):
    status, body = _post(served.port, "/generate",
                         {"prompt": "hi", "tenant": 12, "max_tokens": 4})
    assert status == 400
    status, body = _post(
        served.port, "/v1/completions",
        {"model": "test-llama-tiny", "prompt": "hi", "tenant": 12,
         "max_tokens": 4},
    )
    assert status == 400
    status, body = _post(served.port, "/generate",
                         {"prompt": "hi", "tenant": "acme",
                          "max_tokens": 4, "greedy": True, "chat": False})
    assert status == 200 and body["status"] == "success"


def test_generate_adapter_without_pool_is_400(setup):
    from distributed_llm_inference_tpu.serving.server import InferenceServer

    cfg, params = setup
    cont = _cont(cfg, params)
    server = InferenceServer(cont.engine, host="127.0.0.1", port=0,
                             continuous=cont)
    server.start()
    try:
        status, body = _post(server.port, "/generate",
                             {"prompt": "hi", "adapter": "ad-a",
                              "max_tokens": 4})
        assert status == 400
        assert "adapter serving is not configured" in body["error"]
    finally:
        server.shutdown()


# -- chaos: crash with adapters resident --------------------------------------

@pytest.fixture(autouse=True)
def _always_disarm():
    faults.disarm()
    yield
    faults.disarm()


@pytest.mark.chaos
def test_crash_with_adapters_resident_recovers_bit_identical(setup):
    """A scheduler crash mid-decode with adapter pages referenced: the
    fleet rebuilds, page refcounts reset wholesale (reset_refs — content
    survives in params), every greedy stream re-emerges bit-identical,
    and after the drain the ledger is clean (referenced == 0,
    free == total)."""
    cfg, params = setup
    jobs = [(PROMPTS[0], None), (PROMPTS[1], "ad-a"), (PROMPTS[2], "ad-b")]

    def serve(spec):
        faults.disarm()
        cont = _cont(cfg, params, adapters=2)
        pool = cont.engine.adapters
        pool.register("ad-a", _adapter_host(cfg, 1))
        pool.register("ad-b", _adapter_host(cfg, 2))
        try:
            # warm the launch programs OUTSIDE the fault window
            cont.submit("warm", **KW)
            cont.submit("warm", adapter="ad-a", **KW)
            if spec:
                faults.arm(spec)
            out, lock = {}, threading.Lock()

            def client(j):
                p, ad = j
                extra = {"adapter": ad} if ad else {}
                r = cont.submit(p, **dict(KW, max_tokens=12), **extra)
                with lock:
                    out[j] = r

            threads = [threading.Thread(target=client, args=(j,))
                       for j in jobs]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            faults.disarm()
            return out, cont.restarts_total, pool.stats()
        finally:
            faults.disarm()
            cont.close()

    clean, restarts0, _ = serve(None)
    assert restarts0 == 0
    faulted, restarts, st = serve([
        faults.FaultRule("decode_launch", "transient", on_call=2),
    ])
    assert restarts >= 1
    for j in jobs:
        assert faulted[j]["status"] == "success", faulted[j]
        assert faulted[j]["response"] == clean[j]["response"]
    assert st["referenced"] == 0
    assert st["free"] == st["total"]


# -- pp twin ------------------------------------------------------------------

def test_pp_fleet_serves_adapters_identically(setup):
    """The pipeline backend's shard_map twin: the same adapter request on
    a pp=2 mesh emits the single-device fleet's exact greedy stream (the
    lora leaves shard through the ordinary partition specs and the page
    write runs per-stage)."""
    from distributed_llm_inference_tpu import MeshConfig, create_engine

    cfg, params = setup
    host = _adapter_host(cfg, 1)
    eng_pp = create_engine(
        cfg, params=params, mesh_cfg=MeshConfig(pp=2),
        engine_cfg=EngineConfig(
            prefix_cache_entries=0, prefill_buckets=(64, 128, 256),
            adapter_slots=2, adapter_rank=RANK,
        ),
    )
    eng_pp.adapters.register("ad-a", host)
    cont_pp = ContinuousEngine(
        eng_pp, n_slots=4, chunk_steps=8, slot_max_seq=512,
        kv_pool_blocks=120, kv_block_size=16, restart_backoff_s=0.01,
    )
    cont_sd = _cont(cfg, params, adapters=2)
    cont_sd.engine.adapters.register("ad-a", host)
    try:
        for p in PROMPTS[:2]:
            rp = cont_pp.submit(p, adapter="ad-a", **KW)
            rs = cont_sd.submit(p, adapter="ad-a", **KW)
            assert rp["status"] == rs["status"] == "success"
            assert rp["response"] == rs["response"]
    finally:
        cont_pp.close()
        cont_sd.close()
