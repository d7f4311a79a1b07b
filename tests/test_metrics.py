"""Observability tests (ISSUE 2): the metrics registry (utils/metrics.py),
Prometheus exposition round-trip via an in-test parser, /stats ≡ registry
consistency, per-request stage tracing (utils/tracing.py) on the solo and
continuous paths, and warmup-traffic exclusion."""

import json
import logging as pylog
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

from distributed_llm_inference_tpu import EngineConfig, create_engine
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.serving.queue import BatchingQueue
from distributed_llm_inference_tpu.serving.server import InferenceServer
from distributed_llm_inference_tpu.utils import logging as slog
from distributed_llm_inference_tpu.utils.metrics import (
    MetricsRegistry,
    percentile,
)
from distributed_llm_inference_tpu.utils.tracing import (
    Trace,
    sanitize_request_id,
)

# ---------------------------------------------------------------- registry


def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "reqs", ("engine",))
    c.labels(engine="solo").inc()
    c.labels(engine="solo").inc(2)
    c.labels(engine="batch").inc()
    assert c.labels(engine="solo").value == 3
    assert c.labels(engine="batch").value == 1
    with pytest.raises(ValueError):
        c.labels(engine="solo").inc(-1)
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    g = reg.gauge("t_depth")
    g.labels().set(5)
    g.labels().dec()
    assert g.labels().value == 4


def test_registration_is_idempotent_but_typed():
    reg = MetricsRegistry()
    fam = reg.counter("x_total")
    assert reg.counter("x_total") is fam
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total", labelnames=("a",))


def test_histogram_bucketing_and_window_percentiles():
    reg = MetricsRegistry()
    fam = reg.histogram("h_seconds", "h", buckets=(0.1, 1.0, 10.0))
    h = fam.labels()
    values = [0.05, 0.5, 5.0, 50.0]
    for v in values:
        h.observe(v)
    assert h.count == 4
    assert abs(h.sum - sum(values)) < 1e-9
    # non-cumulative internal counts: one observation per bucket (+Inf last)
    assert h._bucket_counts == [1, 1, 1, 1]
    # window percentiles match the shared nearest-rank formula exactly
    for q in (0.5, 0.9, 0.99):
        assert h.percentile(q) == percentile(values, q)


def test_thread_safety_under_contention():
    reg = MetricsRegistry()
    c = reg.counter("race_total").labels()
    h = reg.histogram("race_seconds").labels()

    def work():
        for _ in range(500):
            c.inc()
            h.observe(0.01)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 4000
    assert h.count == 4000


def test_label_cardinality_cap_collapses_to_other():
    reg = MetricsRegistry(max_series=4)
    c = reg.counter("cap_total", "capped", ("route",))
    for i in range(10):
        c.labels(route=f"r{i}").inc()
    series = reg.snapshot()["cap_total"]["series"]
    assert len(series) == 5  # 4 real + 1 overflow
    other = [s for s in series if s["labels"]["route"] == "_other_"]
    assert len(other) == 1 and other[0]["value"] == 6
    # no count lost to the cap
    assert sum(s["value"] for s in series) == 10


# ------------------------------------------- exposition format round-trip

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$"
)


def _parse_exposition(text: str) -> dict:
    """Tiny Prometheus text-format parser: family name ->
    {"type": ..., "samples": {(sample_name, labels_str): float}}."""
    families: dict = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split()
            families[name] = {"type": typ, "samples": {}}
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        fam = name
        for suffix in ("_bucket", "_sum", "_count"):
            if fam not in families and name.endswith(suffix):
                fam = name[: -len(suffix)]
        assert fam in families, f"sample {name!r} without a # TYPE line"
        v = float("inf") if value == "+Inf" else float(value)
        families[fam]["samples"][(name, labels)] = v
    return families


def test_exposition_roundtrip_unit():
    reg = MetricsRegistry()
    reg.counter("rt_total", "a counter", ("engine",)).labels(
        engine="solo"
    ).inc(7)
    h = reg.histogram("rt_seconds", "a hist", buckets=(0.1, 1.0)).labels()
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    fams = _parse_exposition(reg.render())
    assert fams["rt_total"]["type"] == "counter"
    assert fams["rt_total"]["samples"][("rt_total", 'engine="solo"')] == 7
    s = fams["rt_seconds"]["samples"]
    # cumulative buckets, +Inf == count, sum preserved
    assert s[("rt_seconds_bucket", 'le="0.1"')] == 1
    assert s[("rt_seconds_bucket", 'le="1"')] == 2
    assert s[("rt_seconds_bucket", 'le="+Inf"')] == 3
    assert s[("rt_seconds_count", "")] == 3
    assert abs(s[("rt_seconds_sum", "")] - 5.55) < 1e-9


def test_label_values_escaped():
    reg = MetricsRegistry()
    reg.counter("esc_total", "", ("route",)).labels(
        route='we"ird\npath\\x'
    ).inc()
    line = [
        ln for ln in reg.render().splitlines()
        if ln.startswith("esc_total{")
    ][0]
    assert '\\"' in line and "\\n" in line and "\\\\" in line
    assert "\n" not in line


# ------------------------------------------------------------------ trace


def test_trace_spans_ordered_and_sum_to_total():
    tr = Trace("rid-1")
    time.sleep(0.02)
    tr.checkpoint("prefill")
    time.sleep(0.01)
    tr.checkpoint("decode")
    tr.checkpoint("decode")  # repeat accumulates, no duplicate key
    t = tr.timings()
    keys = list(t)
    assert keys == ["prefill_s", "decode_s", "total_s"]
    assert all(v >= 0 for v in t.values())
    span_sum = sum(v for k, v in t.items() if k != "total_s")
    assert span_sum <= t["total_s"] + 1e-6
    assert t["total_s"] - span_sum < 0.05
    assert tr.request_id == "rid-1"


def test_request_id_sanitization():
    assert sanitize_request_id("ok-1.2:3_X") == "ok-1.2:3_X"
    assert sanitize_request_id("  padded  ") == "padded"
    assert sanitize_request_id("bad id") is None
    assert sanitize_request_id("x" * 200) is None
    assert sanitize_request_id(7) is None
    assert sanitize_request_id(None) is None


# ----------------------------------------------------- logging satellites


def test_configure_repeat_updates_level_installs_once():
    root = pylog.getLogger("distributed_llm_inference_tpu")
    old_level = root.level
    try:
        slog.configure(pylog.INFO)
        n_handlers = len(root.handlers)
        slog.configure(pylog.DEBUG)  # used to be silently ignored
        assert root.level == pylog.DEBUG
        assert len(root.handlers) == n_handlers
    finally:
        root.setLevel(old_level)


def test_request_id_attached_to_records():
    import io

    buf = io.StringIO()
    root = pylog.getLogger("distributed_llm_inference_tpu")
    handler = pylog.StreamHandler(buf)
    handler.setFormatter(slog._JsonFormatter())
    root.addHandler(handler)
    old_level = root.level
    root.setLevel(pylog.INFO)
    try:
        log = slog.get_logger("unit-rid")
        with slog.request_id_context("rid-77"):
            log.info("inside")
        log.info("outside")
        lines = [json.loads(l) for l in buf.getvalue().strip().splitlines()]
    finally:
        root.removeHandler(handler)
        root.setLevel(old_level)
    assert lines[0]["request_id"] == "rid-77"
    assert "request_id" not in lines[1]


# ------------------------------------------------- engine + serving paths


@pytest.fixture(scope="module")
def served():
    engine = create_engine(
        "test-llama-tiny",
        engine_cfg=EngineConfig(prefill_buckets=(64,)),
    )
    cont = ContinuousEngine(engine, n_slots=2, chunk_steps=4)
    server = InferenceServer(
        engine, host="127.0.0.1", port=0, continuous=cont
    )
    server.start()
    yield server
    server.shutdown()


def _post(server, path, body, headers=None, timeout=180):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read()), dict(r.headers)


def _assert_spans(timings: dict, required: tuple):
    keys = list(timings)
    assert keys[-1] == "total_s"
    for name in required:
        assert f"{name}_s" in timings, timings
    assert all(v >= 0 for v in timings.values())
    span_sum = sum(v for k, v in timings.items() if k != "total_s")
    total = timings["total_s"]
    assert span_sum <= total + 1e-6
    # spans must cover ≈ the end-to-end latency (contiguous checkpoints;
    # the residual is envelope assembly after the last checkpoint)
    assert total - span_sum < max(0.1, 0.25 * total), timings


def test_generate_continuous_request_id_and_timings(served):
    body, headers = _post(
        served, "/generate",
        {"prompt": "trace me", "max_tokens": 6, "chat": False},
        headers={"X-Request-Id": "corr-42"},
    )
    assert body["status"] == "success"
    assert body["request_id"] == "corr-42"
    assert headers.get("X-Request-Id") == "corr-42"
    _assert_spans(body["timings"], ("queue_wait", "admission", "decode"))


def test_generate_solo_timings(served):
    # the bare engine (the continuous front end is bypassed): solo spans
    r = served.engine.generate(
        "solo trace", max_tokens=5, greedy=True, chat=False,
        request_id="solo-1",
    )
    assert r["status"] == "success" and r["request_id"] == "solo-1"
    _assert_spans(
        r["timings"], ("queue_wait", "prefill", "decode", "detokenize")
    )


def test_bad_request_id_replaced(served):
    body, headers = _post(
        served, "/generate",
        {"prompt": "x", "max_tokens": 3, "chat": False},
        headers={"X-Request-Id": "bad id with spaces!"},
    )
    assert body["request_id"] != "bad id with spaces!"
    assert body["request_id"].startswith("req-")
    assert headers.get("X-Request-Id") == body["request_id"]


def test_metrics_route_exposition(served):
    # ensure some traffic exists on both views
    _post(served, "/generate", {"prompt": "m", "max_tokens": 3, "chat": False})
    with urllib.request.urlopen(
        f"http://127.0.0.1:{served.port}/metrics", timeout=10
    ) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    fams = _parse_exposition(text)
    # the acceptance bar: >= 10 distinct families spanning server, queue,
    # engines, prefix cache, and the constrain fleet table
    required = {
        "dli_http_requests_total",          # server
        "dli_queue_depth",                  # queue/admission
        "dli_admission_wait_seconds",
        "dli_ttft_seconds",                 # solo + continuous engines
        "dli_tpot_seconds",
        "dli_request_duration_seconds",
        "dli_requests_total",
        "dli_tokens_generated_total",
        "dli_slots_occupied",               # continuous fleet
        "dli_decode_step_seconds",
        "dli_decode_chunk_steps_total",     # a chunk's steps: run | cut
        "dli_preemptions_total",
        "dli_constraint_states_resident",   # constrain fleet
    }
    assert required <= set(fams), sorted(required - set(fams))
    assert len(fams) >= 10
    # histogram invariant everywhere: +Inf bucket == count per series
    for name, fam in fams.items():
        if fam["type"] != "histogram":
            continue
        for (sample, labels), v in fam["samples"].items():
            if sample.endswith("_bucket") and 'le="+Inf"' in labels:
                rest = ",".join(
                    p for p in labels.split(",") if not p.startswith('le=')
                )
                assert v == fam["samples"][(name + "_count", rest)]


def test_http_counter_counts_routes_and_statuses(served):
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{served.port}/nope", timeout=10
        )
    _post(served, "/generate", {"prompt": "c", "max_tokens": 3, "chat": False})
    fam = served.engine.metrics.get("dli_http_requests_total")
    assert fam.labels(route="other", method="GET", status="404").value >= 1
    assert fam.labels(route="/generate", method="POST", status="200").value >= 1


def test_chat_completions_carry_request_id_and_timings(served):
    body, headers = _post(
        served, "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 4},
        headers={"X-Request-Id": "oai-7"},
    )
    assert body["choices"][0]["message"]["content"] is not None
    assert body["request_id"] == "oai-7"
    assert headers.get("X-Request-Id") == "oai-7"
    _assert_spans(body["timings"], ("queue_wait", "decode"))


def test_stats_consistency_with_registry():
    engine = create_engine(
        "test-llama-tiny", engine_cfg=EngineConfig(prefill_buckets=(64,))
    )
    for i in range(5):
        r = engine.generate(
            f"consistency {i}", max_tokens=3, greedy=True, chat=False
        )
        assert r["status"] == "success"
    s = engine.stats()
    h = engine.metrics.get("dli_ttft_seconds").labels(engine="solo")
    assert s["window"] == 5 == h.count == s["samples_total"]
    assert s["ttft_p50_s"] == h.percentile(0.5)
    assert s["ttft_p90_s"] == h.percentile(0.9)
    assert s["ttft_p99_s"] == h.percentile(0.99)
    assert s["ttft_p99_s"] >= s["ttft_p50_s"]
    tok = engine.metrics.get("dli_tokens_generated_total")
    assert tok.labels(engine="solo").value == s["tokens_total"]
    assert (
        engine.metrics.get("dli_requests_total")
        .labels(engine="solo", model=engine.cfg.name).value == 5
    )


def test_warmup_traffic_excluded_from_both_views():
    engine = create_engine(
        "test-llama-tiny", engine_cfg=EngineConfig(prefill_buckets=(64,))
    )
    cont = ContinuousEngine(engine, n_slots=2, chunk_steps=4)
    try:
        assert cont.warmup()["ok"]
        h = engine.metrics.get("dli_ttft_seconds").labels(engine="continuous")
        assert h.count == 0  # /metrics view clean
        assert engine.stats()["window"] == 0  # /stats view clean
        assert (
            engine.metrics.get("dli_requests_total")
            .labels(engine="continuous", model=engine.cfg.name).value == 0
        )
        r = cont.submit("real", max_tokens=4, greedy=True, chat=False)
        assert r["status"] == "success"
        _assert_spans(r["timings"], ("queue_wait", "admission", "decode"))
        assert h.count == 1
        assert engine.stats()["window"] == 1
    finally:
        cont.close()


def test_bare_engine_exposes_full_catalog_schema():
    # a solo server with no queue/continuous/prefix still renders >= 10
    # families — the scrape schema is stable across server configs
    engine = create_engine(
        "test-llama-tiny", engine_cfg=EngineConfig(prefill_buckets=(64,))
    )
    fams = {f.name for f in engine.metrics.families()}
    assert len(fams) >= 10
    assert {
        "dli_ttft_seconds", "dli_queue_depth", "dli_slots_occupied",
        "dli_prefix_cache_hits_total", "dli_preemptions_total",
        # tiered-KV families pre-register on every engine, so the
        # scrape schema is stable whether or not a tier ever fills
        "dli_kv_tier_entries", "dli_kv_tier_bytes",
        "dli_kv_tier_promotions_total", "dli_kv_tier_demotions_total",
        "dli_kv_tier_disk_hits_total",
        # the decode chunk's exit (ISSUE 46): pre-registered like the
        # other launch-record families
        "dli_decode_chunk_steps_total",
    } <= fams
    chunk_steps = engine.metrics.get("dli_decode_chunk_steps_total")
    assert chunk_steps.type == "counter" and chunk_steps.labelnames == ("state",)


def test_queue_metrics_and_member_timings():
    engine = create_engine(
        "test-llama-tiny", engine_cfg=EngineConfig(prefill_buckets=(64,))
    )
    queue = BatchingQueue(engine, max_queue=4, max_batch=2, max_wait_ms=1.0)
    try:
        r = queue.submit(
            "through the queue", max_tokens=3, greedy=True, chat=False,
            request_id="q-1",
        )
        assert r["status"] == "success"
        assert r["request_id"] == "q-1"
        _assert_spans(r["timings"], ("queue_wait", "prefill", "decode"))
        m = engine.metrics
        assert m.get("dli_queue_depth").labels(queue="batching").value == 0
        assert (
            m.get("dli_admission_wait_seconds")
            .labels(queue="batching").count >= 1
        )
    finally:
        queue.close()
