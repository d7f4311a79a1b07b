"""Fleet-wide distributed tracing suite (ISSUE 17: utils/tracing.py,
serving/trace_store.py, and the traceparent propagation seams in
serving/{server,router,kv_fabric}.py + engine/continuous.py).

Three layers:

  * UNIT: W3C traceparent round trip, sampling determinism, TraceStore
    bounds/LRU/idempotent end, tree assembly (orphans degrade to a
    forest), Chrome trace-event (Perfetto) export schema, histogram
    exemplars, flight-recorder ring bounds.
  * IN-PROCESS ENGINE (chaos): the sampled launch-attribution path at
    rate 1.0 (launch spans parented under the request's inbound span,
    exemplar links to a stored trace), the ZERO-overhead contract at the
    default rate 0 (no span allocation on the hot path — asserted by
    making allocation impossible), and the crash leg: a fault-injected
    scheduler crash persists the flight ring next to --restore-dir.
  * REAL SUBPROCESS FLEET (chaos): 1 prefill + 1 decode replica behind
    an in-process router — one client-rooted request yields a SINGLE
    assembled trace tree spanning router dispatch, the prefill handoff,
    the decode replica's fabric pull, the serving peer's /kv span, and
    per-launch device-time attribution; span total ≈ end-to-end wall
    time; the JSON and Perfetto exports agree. The final leg kill -9s
    the decode replica so the failover hop appears as a router.retry
    span (it runs LAST: the fleet is spent afterwards).
"""

import json
import math
import os
import subprocess
import time
import urllib.error
import urllib.request

import pytest

from distributed_llm_inference_tpu.serving.trace_store import (
    TraceStore, assemble_tree, span_tree_total, to_chrome_trace,
)
from distributed_llm_inference_tpu.utils.tracing import (
    FlightRecorder, SpanContext, parse_traceparent, sample_decision,
)


# -- traceparent + sampling units ---------------------------------------------

def test_traceparent_round_trip():
    ctx = SpanContext.new_root()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    back = parse_traceparent(ctx.header())
    assert back is not None
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.sampled == ctx.sampled
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-beef-01",
    "00-" + "g" * 32 + "-" + "b" * 16 + "-01",   # non-hex trace id
    "99-" + "a" * 32 + "-" + "b" * 16 + "-01",   # unknown version
    "00-" + "0" * 32 + "-" + "b" * 16 + "-01",   # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span id
])
def test_traceparent_malformed_degrades_to_none(bad):
    assert parse_traceparent(bad) is None


def test_sample_decision_deterministic_and_bounded():
    ids = [SpanContext.new_root().trace_id for _ in range(64)]
    for tid in ids:
        assert sample_decision(tid, 0.0) is False
        assert sample_decision(tid, 1.0) is True
        # deterministic: same id, same verdict
        assert sample_decision(tid, 0.5) == sample_decision(tid, 0.5)
    frac = sum(sample_decision(t, 0.5) for t in ids) / len(ids)
    assert 0.05 < frac < 0.95  # keyed off the id, not constant


# -- span store ---------------------------------------------------------------

def test_span_store_pairing_tree_and_totals():
    store = TraceStore(service="unit")
    root = SpanContext.new_root()
    with store.span("parent", root) as sp:
        sub = root.child(sp["span_id"])
        with store.span("child", sub, attrs={"k": 1}):
            time.sleep(0.01)
    spans = store.get(root.trace_id)
    assert [s["name"] for s in spans] == ["child", "parent"]  # close order
    assert all(s["service"] == "unit" for s in spans)
    roots = assemble_tree(spans)
    assert len(roots) == 1 and roots[0]["name"] == "parent"
    assert roots[0]["children"][0]["name"] == "child"
    assert roots[0]["children"][0]["attrs"] == {"k": 1}
    total = span_tree_total(roots)
    assert total >= 0.01
    assert math.isclose(
        total, spans[1]["t1"] - spans[1]["t0"], rel_tol=1e-9
    )


def test_span_store_end_is_commit_once():
    store = TraceStore(service="unit")
    ctx = SpanContext.new_root()
    sp = store.start_span("once", ctx)
    store.end_span(sp, attrs={"a": 1})
    store.end_span(sp, attrs={"b": 2})  # defensive double-end: attrs only
    spans = store.get(ctx.trace_id)
    assert len(spans) == 1
    assert spans[0]["attrs"] == {"a": 1, "b": 2}


def test_span_store_exception_path_marks_error():
    store = TraceStore(service="unit")
    ctx = SpanContext.new_root()
    with pytest.raises(RuntimeError):
        with store.span("boom", ctx):
            raise RuntimeError("x")
    spans = store.get(ctx.trace_id)
    assert len(spans) == 1 and spans[0]["attrs"]["error"] is True
    assert spans[0]["t1"] is not None  # ended despite the raise


def test_span_store_lru_and_per_trace_bounds():
    store = TraceStore(service="unit", max_traces=4, max_spans_per_trace=8)
    ids = []
    for _ in range(6):
        ctx = SpanContext.new_root()
        ids.append(ctx.trace_id)
        store.add_span(ctx.trace_id, "s", 0.0, 1.0)
    kept = store.trace_ids()
    assert len(kept) == 4 and kept == ids[2:]  # LRU evicted the oldest
    # per-trace cap: extra spans drop (counted), trace survives
    busy = ids[-1]
    for i in range(20):
        store.add_span(busy, f"s{i}", 0.0, 1.0)
    assert len(store.get(busy)) == 8
    assert store.stats()["spans_dropped"] > 0
    # reading refreshes recency
    store.get(ids[2])
    store.add_span(SpanContext.new_root().trace_id, "s", 0.0, 1.0)
    assert ids[2] in store.trace_ids()


def test_assemble_tree_orphans_surface_as_forest():
    # parent span lives in a process that was never queried: the child
    # must surface as a root, not vanish
    tid = SpanContext.new_root().trace_id
    spans = [
        {"name": "a", "trace_id": tid, "span_id": "a" * 16,
         "parent_id": None, "t0": 1.0, "t1": 3.0, "attrs": {},
         "service": "s1"},
        {"name": "orphan", "trace_id": tid, "span_id": "b" * 16,
         "parent_id": "f" * 16, "t0": 1.5, "t1": 2.0, "attrs": {},
         "service": "s2"},
    ]
    roots = assemble_tree(spans)
    assert sorted(r["name"] for r in roots) == ["a", "orphan"]
    assert span_tree_total(roots) == 2.0  # max t1 - min t0 over roots


# -- Perfetto (Chrome trace-event) export -------------------------------------

def _validate_chrome(doc):
    """Minimal trace-event schema check: what Perfetto's JSON importer
    requires of every event we emit."""
    assert isinstance(doc["traceEvents"], list)
    names_by_pid = {}
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        elif ev["name"] == "process_name":
            names_by_pid[ev["pid"]] = ev["args"]["name"]
    # every complete event's pid has a declared process-name lane
    for ev in doc["traceEvents"]:
        if ev["ph"] == "X":
            assert ev["pid"] in names_by_pid
    return names_by_pid


def test_chrome_trace_schema_and_lanes():
    store = TraceStore(service="svc-a")
    ctx = SpanContext.new_root()
    with store.span("a", ctx):
        pass
    spans = store.get(ctx.trace_id)
    # a second service's span in the same trace -> its own pid lane
    spans.append({
        "name": "b", "trace_id": ctx.trace_id, "span_id": "c" * 16,
        "parent_id": spans[0]["span_id"], "t0": spans[0]["t0"],
        "t1": None, "attrs": {}, "service": "svc-b",  # unfinished
    })
    doc = to_chrome_trace(spans)
    json.dumps(doc)  # JSON-serializable end to end
    lanes = _validate_chrome(doc)
    assert sorted(lanes.values()) == ["svc-a", "svc-b"]
    unfinished = [
        e for e in doc["traceEvents"]
        if e["ph"] == "X" and e["args"].get("unfinished")
    ]
    assert len(unfinished) == 1 and unfinished[0]["dur"] == 0


# -- exemplars ----------------------------------------------------------------

def test_histogram_exemplars_keep_latest_traced_sample():
    from distributed_llm_inference_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("t_seconds", "t", buckets=(0.1, 1.0)).labels()
    h.observe(0.05)                       # untraced: no exemplar
    h.observe(0.06, trace_id="aaaa")
    h.observe(0.07, trace_id="bbbb")      # same bucket: latest wins
    h.observe(5.0, trace_id="cccc")       # +Inf bucket
    ex = h.exemplars()
    assert ex["0.1"]["trace_id"] == "bbbb"
    assert ex["+Inf"]["trace_id"] == "cccc"
    assert ex["0.1"]["value"] == 0.06 or ex["0.1"]["value"] == 0.07
    # surfaced in the JSON snapshot for /stats
    snap = reg.snapshot()["t_seconds"]["series"][0]
    assert snap["exemplars"]["+Inf"]["trace_id"] == "cccc"


# -- flight recorder ----------------------------------------------------------

def test_flight_recorder_ring_bounds_and_dump():
    fl = FlightRecorder(capacity=16)
    for i in range(100):
        fl.record("tick", i=i)
    dump = fl.dump()
    assert dump["capacity"] == 16
    assert dump["recorded_total"] == 100
    assert len(dump["events"]) == 16
    # the ring keeps the TAIL, in order, with monotone seq
    assert [e["i"] for e in dump["events"]] == list(range(84, 100))
    seqs = [e["seq"] for e in dump["events"]]
    assert seqs == sorted(seqs)
    json.dumps(dump)  # crash-report-safe verbatim
    assert fl.events(limit=3) == dump["events"][-3:]


# -- in-process engine legs ---------------------------------------------------

BS = 8
POOL = 48
PROMPT = "the quick brown fox jumps over the"


@pytest.fixture(scope="module")
def engine():
    from distributed_llm_inference_tpu import get_model_config
    from distributed_llm_inference_tpu.config import EngineConfig
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine

    cfg = get_model_config("test-llama-tiny")
    return InferenceEngine(
        cfg,
        engine_cfg=EngineConfig(
            prefill_buckets=(32, 64), prefix_cache_entries=8
        ),
    )


def _cont(engine, **kw):
    from distributed_llm_inference_tpu.engine.continuous import (
        ContinuousEngine,
    )

    kw.setdefault("n_slots", 2)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("restart_backoff_s", 0.01)
    kw.setdefault("kv_pool_blocks", POOL)
    kw.setdefault("kv_block_size", BS)
    return ContinuousEngine(engine, **kw)


def test_trace_sample_rate_validated():
    from distributed_llm_inference_tpu.config import EngineConfig

    with pytest.raises(ValueError):
        EngineConfig(trace_sample_rate=1.5)
    with pytest.raises(ValueError):
        EngineConfig(trace_sample_rate=-0.1)


@pytest.mark.chaos
def test_zero_overhead_at_rate_zero(engine, monkeypatch):
    """The sampling contract of the PER-TENANT export: at the default
    rate 0 the hot path must not store a single span or note a launch
    for a tenant — enforced by making span creation blow up for the
    duration, then serving a full request. (The launch record itself,
    its counters and profiler annotations are always on:
    tests/test_launch_record.py.)"""
    import distributed_llm_inference_tpu.engine.continuous as C

    def _bomb(*a, **k):
        raise AssertionError("span allocated on the rate-0 hot path")

    cont = _cont(engine)
    assert cont._trace_rate == 0.0
    try:
        monkeypatch.setattr(TraceStore, "start_span", _bomb)
        monkeypatch.setattr(TraceStore, "add_span", _bomb)
        monkeypatch.setattr(C.ContinuousEngine, "_prof_note_launch", _bomb)
        ctx = SpanContext.new_root()  # sampled inbound context, rate 0
        r = cont.submit(PROMPT, max_tokens=8, greedy=True, chat=False,
                        trace_ctx=ctx)
        assert r["status"] == "success", r
        assert not cont._launch_log
        assert engine.trace_store.get(ctx.trace_id) == []
    finally:
        cont.close()


@pytest.mark.chaos
def test_launch_attribution_and_exemplar_link_at_rate_one(engine):
    """rate 1.0: every launch a profiled request rode emits one
    launch.<phase> span parented under the request's inbound span,
    whose attrs are the launch record, and the latency histograms'
    exemplars link to the SAME stored trace."""
    import dataclasses

    old = engine.engine_cfg
    engine.engine_cfg = dataclasses.replace(old, trace_sample_rate=1.0)
    try:
        cont = _cont(engine)
        assert cont._trace_rate == 1.0
        ctx = SpanContext.new_root()
        try:
            r = cont.submit(PROMPT, max_tokens=8, greedy=True, chat=False,
                            trace_ctx=ctx)
        finally:
            cont.close()
        assert r["status"] == "success", r
        spans = engine.trace_store.get(ctx.trace_id)
        launches = [s for s in spans if s["name"].startswith("launch.")]
        assert launches, [s["name"] for s in spans]
        for sp in launches:
            assert sp["parent_id"] == ctx.span_id  # nests under inbound
            assert sp["t1"] >= sp["t0"]
            assert sp["attrs"].get("launch_to_fetch_s") is not None
            assert sp["name"] == "launch." + sp["attrs"]["phase"]
            assert sp["attrs"]["kv_tokens"] <= sp["attrs"]["kv_grid_tokens"]
        # exemplar -> this exact trace, which IS inspectable in the store
        ex = engine._m_duration.labels(engine="continuous").exemplars()
        assert any(e["trace_id"] == ctx.trace_id for e in ex.values())
        assert ctx.trace_id in engine.trace_store.trace_ids()
    finally:
        engine.engine_cfg = old


@pytest.mark.chaos
def test_crash_dump_persists_flight_ring(engine, tmp_path):
    """A fault-injected scheduler crash writes the full flight dump next
    to --restore-dir; the ring's live view shows the episode too."""
    from distributed_llm_inference_tpu.utils import faults

    cont = _cont(engine, kv_shadow=True, restore_dir=str(tmp_path))
    try:
        faults.arm([faults.FaultRule("prefill", "transient", on_call=1)])
        try:
            r = cont.submit(PROMPT, max_tokens=8, greedy=True, chat=False)
        finally:
            faults.disarm()
        assert r["status"] == "success", r  # supervisor recovered
    finally:
        cont.close()
    path = tmp_path / "flight_crash.json"
    assert path.exists()
    dump = json.loads(path.read_text())
    assert dump["recorded_total"] >= 1
    kinds = [e["kind"] for e in dump["events"]]
    assert "crash" in kinds
    assert dump["error"]
    # the live ring saw the same episode (plus the recovery)
    live = [e["kind"] for e in engine.flight.events()]
    assert "crash" in live and "restart" in live


# -- real subprocess fleet ----------------------------------------------------

FLEET_ARGS = [
    "--model", "test-llama-tiny", "--continuous", "2",
    "--continuous-chunk", "4", "--kv-pool-blocks", "48",
    "--kv-block-size", str(BS), "--prefix-cache", "8",
    "--max-tokens-cap", "64", "--trace-sample-rate", "1.0",
]
PROMPT_FLEET = "fresh traced disaggregated workload " * 3 + "alpha"


def _spawn_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("DLI_FAULTS", None)
    return env


@pytest.fixture(scope="module")
def fleet():
    """1 prefill- + 1 decode-class REAL engine server (sampling 1.0)
    behind an in-process router. probe_interval is long so the final
    kill -9 leg races the prober deterministically (the router still
    believes the corpse READY when it dispatches)."""
    from distributed_llm_inference_tpu.serving.router import (
        Router, RouterServer, spawn_replicas,
    )

    pre = spawn_replicas(1, FLEET_ARGS, env=_spawn_env(),
                         replica_class="prefill", name_prefix="p")[0]
    dec = spawn_replicas(1, FLEET_ARGS, env=_spawn_env(),
                         replica_class="decode", name_prefix="d")[0]
    router = Router(
        [pre, dec], eject_threshold=3, probe_interval_s=3.0,
        probe_timeout_s=2.0, request_timeout_s=120.0,
        handoff_min_bytes=64,
    )
    server = RouterServer(router, host="127.0.0.1", port=0)
    server.start()
    try:
        yield router, server, f"http://127.0.0.1:{server.port}", pre, dec
    finally:
        server.shutdown()
        for rep in (pre, dec):
            if rep.proc is not None:
                try:
                    rep.proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    rep.proc.kill()


def _get(base, path, timeout=15):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _trace_with_its_root(base, trace_id):
    """The router's view of a trace once `router.request` is in it: that
    span closes (and is recorded) AFTER the response is written, so a
    client that asks at once may be ahead of the handler's thread."""
    deadline = time.monotonic() + 10
    while True:
        code, tr, hdrs = _get(base, f"/debug/traces/{trace_id}")
        rooted = code == 200 and any(
            sp["name"] == "router.request" for sp in tr["spans"])
        if rooted or time.monotonic() > deadline:
            return code, tr, hdrs
        time.sleep(0.05)


def _get_text(base, path, timeout=15):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.read().decode()


def _post(base, payload, headers=None, timeout=180):
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


@pytest.mark.chaos
def test_fleet_round_trip_single_tree(fleet):
    """THE acceptance leg: one client-rooted request through router ->
    prefill handoff -> fabric PUSH (the prefill replica posts the finished
    chain to the decode peer, which promotes it — no pull round trip since
    PR 19) -> decode yields ONE assembled trace tree covering every hop,
    span total ≈ end-to-end wall, and both export formats agree."""
    router, _, base, pre, dec = fleet
    ctx = SpanContext.new_root()
    code, body, hdrs = _post(
        base,
        {"prompt": PROMPT_FLEET, "max_tokens": 8, "greedy": True,
         "chat": False},
        headers={"traceparent": ctx.header()},
    )
    assert code == 200 and body["status"] == "success", body
    assert hdrs.get("X-Trace-Id") == ctx.trace_id
    assert body["replica"] == "d0"          # token loop on the decode tier
    # the pushed chain landed before the decode admission: its blocks were
    # promoted from the shadow tier and the prompt served as a prefix hit
    assert body.get("kv_promoted_blocks", 0) > 0
    assert body.get("prefix_cached_tokens", 0) > 0

    code, tr, _ = _trace_with_its_root(base, ctx.trace_id)
    assert code == 200
    names = {(s["service"], s["name"]) for s in tr["spans"]}
    # every hop of the disaggregated request is present
    assert ("router", "router.request") in names
    assert ("router", "router.dispatch") in names
    assert ("router", "router.handoff_prefill") in names
    assert ("replica-prefill", "replica.request") in names
    assert ("replica-prefill", "fabric.push") in names
    assert ("replica-decode", "replica.request") in names
    assert any(s == "replica-decode" and n.startswith("launch.")
               for s, n in names)
    assert any(n.startswith("stage.") for _, n in names)
    # one single root: the router.request span
    assert len(tr["tree"]) == 1
    assert tr["tree"][0]["name"] == "router.request"
    # span total ≈ end-to-end wall time (the router folds its own hop
    # into timings.total_s, so the two measure the same interval)
    assert tr["total_s"] == pytest.approx(
        body["timings"]["total_s"], rel=0.25, abs=0.5
    )
    # Perfetto export: valid schema, one pid lane per fleet role
    code, chrome, _ = _get(
        base, f"/debug/traces/{ctx.trace_id}?format=chrome"
    )
    assert code == 200
    lanes = _validate_chrome(chrome)
    assert sorted(lanes.values()) == [
        "replica-decode", "replica-prefill", "router",
    ]
    # the replica-side view exists too (partial forest is fine)
    code, rep_tr, _ = _get(dec.url, f"/debug/traces/{ctx.trace_id}")
    assert code == 200 and rep_tr["spans"]
    # listing endpoints answer on both tiers
    code, listing, _ = _get(base, "/debug/traces")
    assert code == 200 and ctx.trace_id in listing["traces"]


@pytest.mark.chaos
def test_fleet_exemplar_links_to_fetchable_trace(fleet):
    """A decode-replica latency exemplar names a trace the router can
    actually assemble (metrics -> traces pivot)."""
    router, _, base, _, dec = fleet
    code, stats, _ = _get(dec.url, "/stats")
    assert code == 200
    ex = stats.get("exemplars", {}).get(
        "dli_request_duration_seconds", {}
    )
    tids = [e["trace_id"] for e in ex.values()]
    assert tids, "no exemplars on the decode replica"
    code, tr, _ = _get(base, f"/debug/traces/{tids[0]}")
    assert code == 200 and tr["spans"]


@pytest.mark.chaos
def test_fleet_flight_and_kv_headers(fleet):
    """/debug/flight aggregates the replicas' rings through the router;
    /kv answers echo X-Request-Id; /metrics serves dli_build_info on
    both tiers with the right replica_class label."""
    router, _, base, pre, dec = fleet
    code, fl, _ = _get(base, "/debug/flight")
    assert code == 200
    assert set(fl["replicas"]) == {"p0", "d0"}
    kinds = [e["kind"] for e in fl["replicas"]["d0"].get("events", [])]
    # the hand-off PUSHES (PR 19): the decode ring records the inbound
    # chain, not a fetch
    assert "admit" in kinds and "fabric_push_in" in kinds
    # fabric response header echo (miss path: echo must not depend on a hit)
    req = urllib.request.Request(
        pre.url + "/kv/" + "ab" * 8,
        headers={"X-Request-Id": "req-echo-check",
                 "traceparent": SpanContext.new_root().header()},
    )
    try:
        with urllib.request.urlopen(req, timeout=15) as r:
            got = dict(r.headers)
    except urllib.error.HTTPError as e:
        got = dict(e.headers)
    assert got.get("X-Request-Id") == "req-echo-check"
    # build-info gauge on every /metrics surface
    for url, cls in ((base, 'replica_class="router"'),
                     (pre.url, 'replica_class="prefill"'),
                     (dec.url, 'replica_class="decode"')):
        text = _get_text(url, "/metrics")
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("dli_build_info{")
        )
        assert cls in line and line.split()[-1] in ("1", "1.0")


@pytest.mark.chaos
def test_fleet_failover_hop_is_retry_span(fleet):
    """LAST leg (spends the fleet): kill -9 the decode replica, dispatch
    before the prober notices — the dead-replica attempt appears as a
    router.dispatch span with a connect_error outcome and the failover
    hop as a router.retry span, both in the same assembled tree."""
    router, _, base, pre, dec = fleet
    dec.proc.kill()
    dec.proc.wait(timeout=15)
    ctx = SpanContext.new_root()
    code, body, _ = _post(
        base,
        {"prompt": "failover traced probe", "max_tokens": 4,
         "greedy": True, "chat": False},
        headers={"traceparent": ctx.header()},
    )
    assert code == 200 and body["status"] == "success", body
    assert body["replica"] == "p0"  # availability beats specialization
    assert body.get("router_attempts", 1) > 1
    code, tr, _ = _trace_with_its_root(base, ctx.trace_id)
    assert code == 200
    by_name = {}
    for s in tr["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    assert "router.retry" in by_name
    retry = by_name["router.retry"][0]
    assert retry["attrs"]["replica"] == "p0"
    assert retry["attrs"]["attempt"] >= 2
    dead = [
        s for s in by_name.get("router.dispatch", [])
        if s["attrs"].get("outcome") == "connect_error"
    ]
    assert dead and dead[0]["attrs"]["replica"] == "d0"
    # both attempts nest under the one router.request root
    assert len(tr["tree"]) == 1
    root_id = tr["tree"][0]["span_id"]
    assert retry["parent_id"] == root_id
    assert dead[0]["parent_id"] == root_id
