"""HTTP serving surface (reference L4).

Same API shape as the reference's Flask app
(/root/reference/orchestration.py:231-356): `POST /generate` (prompt,
max_tokens default 20 clamped to a cap, temperature default 0.7; top_k=50 /
top_p=0.9 defaults), `GET /health`, `GET /workers`, `GET /` HTML status page
— but on the stdlib ThreadingHTTPServer (no Flask/ngrok dependency), and
`/workers` reports pipeline-stage health from the mesh instead of polling
remote Flask processes over HTTP (the stages live inside this process's
compiled program; there is no remote worker to poll — that is the point).

HTTP survives only at this serving edge; it never sits between stages.
"""

from __future__ import annotations

import argparse
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

from . import kv_fabric as kvf

__version__ = "tpu_pipeline_v1"

# Reference defaults: orchestration.py:339-347 (max_tokens default 20, cap
# 30) and 353-354 (top_k 50, top_p 0.9). The cap is configurable here.
DEFAULT_MAX_TOKENS = 20
DEFAULT_TEMPERATURE = 0.7
DEFAULT_TOP_K = 50
DEFAULT_TOP_P = 0.9


def _parse_bool(v, name: str) -> bool:
    """Strict JSON-ish bool: bool(\"false\") is True, which would silently
    invert the caller's intent — reject non-bool junk with a 400 instead."""
    if isinstance(v, bool):
        return v
    if isinstance(v, str):
        low = v.strip().lower()
        if low in ("true", "1", "yes"):
            return True
        if low in ("false", "0", "no"):
            return False
    raise ValueError(f"{name} must be a boolean, got {v!r}")


def _status_html(engine) -> str:
    h = engine.health()
    stages = engine.backend.health()
    rows = "".join(
        f"<tr><td>stage {s['stage']}</td><td>{', '.join(s['devices'])}</td>"
        f"<td>{s.get('layers', '-')}</td><td>{s['status']}</td></tr>"
        for s in stages
    )
    return f"""<html><head><title>distributed_llm_inference_tpu</title></head>
<body style="font-family: monospace; margin: 2em;">
<h1>distributed_llm_inference_tpu — orchestrator</h1>
<p>status: <b>{h['status']}</b> | model: <b>{h['model']}</b> |
backend: <b>{h['backend']}</b> | stages: <b>{h['n_stages']}</b> |
requests served: <b>{h['requests_served']}</b></p>
<table border="1" cellpadding="4">
<tr><th>stage</th><th>devices</th><th>layers</th><th>status</th></tr>
{rows}
</table>
<p>POST /generate {{"prompt": ..., "max_tokens": ..., "temperature": ...}}
| GET /health | GET /workers</p>
</body></html>"""


class _Profiler:
    """jax.profiler trace capture behind HTTP (SURVEY.md §5 tracing note:
    the reference's only 'profiling' is wall-clock prints,
    /root/reference/orchestration.py:82,201). Traces are viewable in
    TensorBoard / Perfetto.

    Clients name a subdirectory, not a path: traces always land under
    `base` — otherwise POST /profiler/start would be an arbitrary
    filesystem-write primitive for anyone who can reach the port."""

    def __init__(self, base: str = "/tmp/jax-traces"):
        self._lock = threading.Lock()
        self.base = base
        self.dir: Optional[str] = None
        # the continuous engine (make_handler sets it): a session keeps the
        # step programs it dispatched and, once the profile is on disk,
        # writes instruction -> scope of each beside it
        # (utils/tracing.write_program_scopes), since a device trace's
        # events carry an instruction's name and no scope
        self.programs = None

    def _resolve(self, name: str) -> str:
        import os

        name = name or "trace"
        if os.path.isabs(name) or ".." in name.split("/"):
            raise ValueError(f"trace_dir must be a relative subdir name, got {name!r}")
        out = os.path.normpath(os.path.join(self.base, name))
        if not (out + "/").startswith(os.path.normpath(self.base) + "/"):
            raise ValueError(f"trace_dir escapes base: {name!r}")
        return out

    def start(self, trace_dir: str) -> dict:
        import jax

        with self._lock:
            if self.dir is not None:
                return {"error": f"trace already running to {self.dir}"}
            try:
                resolved = self._resolve(trace_dir)
                jax.profiler.start_trace(resolved)
            except Exception as e:
                return {"error": f"profiler start failed: {e}"}
            self.dir = resolved
            if self.programs is not None:
                self.programs.trace_step_programs(True)
            return {"status": "tracing", "trace_dir": resolved}

    def stop(self) -> dict:
        import jax

        with self._lock:
            if self.dir is None:
                return {"error": "no trace running"}
            out = self.dir
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                # JAX may still be mid-trace: keep self.dir so state stays
                # truthful ('trace already running' on a retried /start)
                # and tell the caller how to recover
                return {
                    "error": f"profiler stop failed: {e}; trace state is "
                    "unknown — retry /profiler/stop or restart the server",
                    "trace_dir": out,
                }
            self.dir = None
            reply = {"status": "stopped", "trace_dir": out}
            if self.programs is not None:
                # the profile is complete; compiling the map may take a
                # minute a program the first time, and never fails the stop
                from ..utils.tracing import write_program_scopes

                t0 = time.monotonic()
                try:
                    reply["scopes"] = write_program_scopes(
                        out, self.programs.trace_step_programs(False)
                    )
                except Exception as e:  # noqa: BLE001 - reported, not raised
                    reply["scopes"] = f"error: {e}"
                reply["scopes_s"] = round(time.monotonic() - t0, 3)
            return reply


# the fixed route set for the http counter's `route` label: anything else
# collapses to "other" so an attacker probing random paths cannot grow the
# label cardinality (the registry's own series cap is the second fence)
_KNOWN_ROUTES = frozenset((
    "/", "/health", "/ready", "/workers", "/stats", "/metrics", "/v1/models",
    "/generate", "/v1/completions", "/v1/chat/completions",
    "/profiler/start", "/profiler/stop", "/debug/traces", "/debug/flight",
))

# Retry-After (seconds) sent with every drain/overload rejection — the
# client's bounded-retry backoff honors it (client.py)
RETRY_AFTER_S = 2


def _route_label(path: str) -> str:
    if path == "/kv" or path.startswith("/kv/"):
        return "/kv"  # one label for every digest (bounded cardinality)
    if path.startswith("/debug/traces"):
        return "/debug/traces"  # one label for every trace id
    return path if path in _KNOWN_ROUTES else "other"


def make_handler(engine, max_tokens_cap: int, profiler: Optional[_Profiler] = None,
                 queue=None, continuous=None, state=None,
                 wedge_unready_s: float = 10.0):
    from ..utils.logging import request_id_context
    from ..utils.tracing import (
        SpanContext,
        new_request_id,
        parse_traceparent,
        sanitize_request_id,
    )
    from . import openai_api as oai
    from .trace_store import assemble_tree, span_tree_total, to_chrome_trace

    profiler = profiler or _Profiler()
    profiler.programs = continuous
    if state is None:  # embedding callers without an InferenceServer
        state = _ServerState()
    started_at = int(time.time())
    # configured SLO classes (engine/scheduler.py): the serving edge
    # validates request slo_class fields against them (unknown -> 400)
    from ..engine.scheduler import parse_slo_classes

    slo_classes = parse_slo_classes(engine.engine_cfg)
    # runtime LoRA adapter pool (engine/adapters.py), if configured —
    # requests select a registered adapter by name (`adapter` on
    # /generate, `model` on the OpenAI routes); unknown names are 400s
    # at this edge, before admission
    adapters = getattr(engine, "adapters", None)
    # HTTP request/error counter by route + status — every response path
    # (JSON, HTML, SSE, NDJSON) passes through exactly one counting point
    http_requests = engine.metrics.counter(
        "dli_http_requests_total", "HTTP responses",
        ("route", "method", "status"),
    )
    # scoring requests bypass the queue/continuous ladder (they are not
    # generations), so they need their own backpressure: a small bound on
    # concurrent scorers — overflow sheds with 429 instead of piling
    # threads on the engine lock
    score_slots = threading.BoundedSemaphore(4)

    class Handler(BaseHTTPRequestHandler):
        # quiet default request logging; serving logs are structured
        def log_message(self, fmt, *args):
            pass

        _rid: Optional[str] = None  # set per POST; echoed as X-Request-Id
        # inbound (traceparent header) or freshly-rooted SpanContext; set
        # per POST, echoed as X-Trace-Id so callers can find their trace
        _trace_ctx: Optional[SpanContext] = None

        def _count(self, code: int):
            http_requests.labels(
                route=_route_label(self.path.split("?")[0].rstrip("/") or "/"),
                method=self.command, status=str(code),
            ).inc()

        def _send(self, code: int, payload: Any, content_type="application/json",
                  headers=None):
            body = (
                payload if isinstance(payload, bytes)
                else payload.encode() if isinstance(payload, str)
                else json.dumps(payload).encode()
            )
            self._count(code)
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            if self._rid:
                self.send_header("X-Request-Id", self._rid)
            if self._trace_ctx is not None:
                self.send_header("X-Trace-Id", self._trace_ctx.trace_id)
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _readiness(self) -> tuple:
            """(ready, reason): liveness is /health's job; THIS is the
            load-balancer signal — False while draining, while the
            continuous scheduler is restart-looping or dead, and while
            an abandoned deadline-overrun device call has been wedged
            past --wedge-unready (the router tier's probes eject the
            replica off this; /health keeps answering 200 so the
            process is not reaped — a wedge can still drain)."""
            if state.draining:
                return False, "draining"
            if wedge_unready_s and hasattr(engine, "max_wedged_age"):
                age = engine.max_wedged_age()
                if age is not None and age > wedge_unready_s:
                    return False, "wedged"
            if continuous is not None and not continuous.ready:
                return False, (
                    "scheduler_dead"
                    if continuous.stats()["supervisor"]["dead"]
                    else "scheduler_restarting"
                )
            return True, None

        def do_GET(self):
            # reset per-request correlation state: keep-alive connections
            # reuse this handler instance, and a prior POST's ids must not
            # leak into this response's headers
            self._rid = None
            self._trace_ctx = None
            path = self.path.split("?")[0].rstrip("/") or "/"
            if path == "/":
                self._send(200, _status_html(engine), content_type="text/html")
            elif path == "/health":
                h = engine.health()
                ready, why = self._readiness()
                # reference shape: status/role/model/version
                # (orchestration.py:297-304) + our backend detail.
                # LIVENESS stays 200 even while draining/restart-looping —
                # readiness is the separate /ready signal (and the `ready`
                # field here), so an LB can stop routing without the
                # process being reaped mid-drain.
                out = {
                    "status": h["status"],
                    "ready": ready,
                    **({"ready_reason": why} if why else {}),
                    "role": "orchestrator",
                    # disaggregation class (--replica-class): the router
                    # learns prefill/decode/mixed from here, so URL-joined
                    # replicas specialize without any spawn-time wiring
                    "replica_class": engine.engine_cfg.replica_class,
                    "model": h["model"],
                    "version": __version__,
                    "backend": h["backend"],
                    # the devices of THIS process, as JAX reports them — a
                    # server that came up on the wrong platform says so
                    "device": h["device"],
                    "n_stages": h["n_stages"],
                    "requests_served": h["requests_served"],
                    "stats": h["stats"],
                }
                if continuous is not None and continuous.fabric_serving:
                    # residency bootstrap: resident chain digests (MRU
                    # first, capped) so the router can steer fabric
                    # pulls at this replica without ever having routed
                    # traffic to it
                    out["kv"] = {
                        "fabric": True,
                        "block_size": continuous.kv_block_size,
                        # capped MRU-first (--kv-health-digests): the
                        # disk tier makes the full resident set
                        # unbounded, bootstrap payloads must stay O(1)
                        "resident_digests": continuous.fabric_digests(),
                    }
                self._send(200, out)
            elif path == "/ready":
                # load-balancer readiness probe: 200/503 is the whole
                # contract (k8s readinessProbe-friendly)
                ready, why = self._readiness()
                if ready:
                    self._send(200, {"ready": True})
                else:
                    self._send(
                        503, {"ready": False, "reason": why},
                        headers={"Retry-After": str(RETRY_AFTER_S)},
                    )
            elif path == "/workers":
                # reference shape: {"worker_1": "online", ...}
                # (orchestration.py:306-329); stages are in-process mesh
                # slices, so liveness == device presence. Single source:
                # engine.workers(), re-keyed to the reference's 1-based names.
                stages = list(engine.workers()["workers"].values())
                results = {
                    f"worker_{s['stage'] + 1}": s["status"] for s in stages
                }
                results["detail"] = stages
                self._send(200, results)
            elif path == "/stats":
                s = engine.stats()
                if continuous is not None:
                    s["continuous"] = continuous.stats()
                if queue is not None:
                    s["queue"] = {
                        "depth": queue.depth(),
                        "coalesced_batches": queue.coalesced_batches,
                    }
                self._send(200, s)
            elif path == "/metrics":
                # Prometheus text exposition over the SAME registry /stats
                # reads (utils/metrics.py); warmup traffic never reaches
                # _record_sample, so it is excluded from both views
                self._send(
                    200, engine.metrics.render(),
                    content_type="text/plain; version=0.0.4; charset=utf-8",
                )
            elif path == "/v1/models":
                self._send(
                    200, oai.models_response(
                        engine.cfg.name, started_at,
                        adapters=adapters.names() if adapters else (),
                    )
                )
            elif path == "/debug/flight":
                # live flight-recorder view: the SAME bounded ring the
                # continuous supervisor dumps into crash reports (and
                # persists next to --restore-dir on a crash)
                flight = getattr(engine, "flight", None)
                self._send(
                    200,
                    flight.dump() if flight is not None
                    else {"capacity": 0, "recorded_total": 0, "events": []},
                )
            elif path == "/debug/traces" or path.startswith("/debug/traces/"):
                # this process's span store: the bare route lists known
                # trace ids; /debug/traces/{id} returns that trace's spans
                # plus the locally-assembled tree (the router concatenates
                # the flat `spans` lists from every replica to build the
                # full cross-process view); ?format=chrome emits Chrome
                # trace-event JSON loadable in Perfetto
                store = getattr(engine, "trace_store", None)
                if store is None:
                    self._send(404, {"error": "no trace store"})
                    return
                trace_id = path[len("/debug/traces/"):] if path.startswith(
                    "/debug/traces/"
                ) else ""
                if not trace_id:
                    self._send(200, {
                        "traces": store.trace_ids(), "stats": store.stats(),
                    })
                elif "format=chrome" in self.path.partition("?")[2]:
                    self._send(200, to_chrome_trace(store.get(trace_id)))
                else:
                    spans = store.get(trace_id)
                    tree = assemble_tree(spans)
                    self._send(200, {
                        "trace_id": trace_id,
                        "service": store.service,
                        "spans": spans,
                        "tree": tree,
                        "total_s": round(span_tree_total(tree), 6),
                    })
            elif path.startswith("/kv/"):
                # the KV fabric's serving half (serving/kv_fabric.py):
                # the resident shadow chain ending at this chunk digest,
                # wire-encoded. A miss — unknown digest, LRU-evicted, or
                # fabric disabled — is a 404 the fetching peer treats as
                # "prefill locally", never an error. The fetching peer's
                # X-Request-Id is echoed back and its traceparent joins
                # this serve to the same trace as its fabric.pull span.
                self._rid = sanitize_request_id(
                    self.headers.get("X-Request-Id")
                )
                ctx = parse_traceparent(self.headers.get("traceparent"))
                self._trace_ctx = ctx
                digest = path[len("/kv/"):]
                t0 = time.time()
                want_stream = self.headers.get("X-KV-Stream") in (
                    "1", "true"
                )
                tier = (
                    continuous.fabric_digest_tier(digest)
                    if continuous is not None else None
                ) or "host"
                if want_stream and continuous is not None:
                    # streamed serve: length-prefixed one-block frames,
                    # encoded lazily (O(1) time-to-first-byte), each
                    # carrying its running parent-chained digest so the
                    # peer verifies chunk-at-a-time and overlaps its
                    # pool scatters with the wire
                    res = continuous.fabric_chain_stream(digest)
                    if ctx is not None:
                        engine.trace_store.add_span(
                            ctx.trace_id, "kv.serve", t0, time.time(),
                            parent_id=ctx.span_id,
                            attrs={
                                "digest": digest[:16],
                                "hit": res is not None,
                                "streamed": True, "tier": tier,
                            },
                        )
                    if res is None:
                        self._send(404, {
                            "error": f"no resident chain for digest "
                                     f"{digest[:64]!r}",
                        })
                        return
                    n_chunks, tier, frames = res
                    # manual write path (like the NDJSON stream): no
                    # Content-Length — frames land as they encode
                    self._count(200)
                    self.send_response(200)
                    self.send_header("Content-Type", kvf.STREAM_CONTENT_TYPE)
                    self.send_header(
                        "X-KV-Block-Size", str(continuous.kv_block_size)
                    )
                    self.send_header("X-KV-Chain-Len", str(n_chunks))
                    self.send_header("X-KV-Tier", tier)
                    self.send_header("Connection", "close")
                    self.end_headers()
                    try:
                        for frame in frames:
                            self.wfile.write(frame)
                        self.wfile.flush()
                    except (BrokenPipeError, ConnectionResetError, OSError):
                        pass  # peer gave up mid-pull: its problem only
                    return
                chain = (
                    continuous.fabric_chain(digest)
                    if continuous is not None else None
                )
                if ctx is not None:
                    engine.trace_store.add_span(
                        ctx.trace_id, "kv.serve", t0, time.time(),
                        parent_id=ctx.span_id,
                        attrs={
                            "digest": digest[:16],
                            "hit": chain is not None,
                            "streamed": False, "tier": tier,
                        },
                    )
                if chain is None:
                    self._send(404, {
                        "error": f"no resident chain for digest "
                                 f"{digest[:64]!r}",
                    })
                else:
                    self._send(
                        200, chain,
                        content_type="application/octet-stream",
                        headers={
                            "X-KV-Block-Size": str(continuous.kv_block_size),
                            "X-KV-Tier": tier,
                        },
                    )
            else:
                self._send(404, {"error": f"no route {path}"})

        def _deadline_ms(self, data: dict):
            """The request's end-to-end deadline budget in ms, or None.
            X-Request-Deadline-Ms (the router's remaining-budget relay)
            overrides the body's deadline_ms; both must be positive
            numbers (a non-positive header means the budget is already
            spent upstream — keep it, the engine fail-fasts it)."""
            hdr = self.headers.get("X-Request-Deadline-Ms")
            if hdr is not None:
                try:
                    return float(hdr)
                except (TypeError, ValueError):
                    pass  # junk header: fall back to the body field
            raw = data.get("deadline_ms")
            if raw is None:
                return None
            dl = float(raw)  # ValueError -> the route's 400 handler
            if dl <= 0:
                raise ValueError("deadline_ms must be > 0")
            return dl

        def _read_json(self):
            """Parse the request body; None (after a 400 reply) on bad JSON."""
            try:
                length = int(self.headers.get("Content-Length", 0))
                return json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError):
                self._send(400, {"error": "invalid JSON body"})
                return None

        def _kv_headers(self) -> tuple:
            """(kv_hint, prefill_only, kv_push_to) — the router's
            disaggregation headers. X-KV-Transfer-Peer +
            X-KV-Transfer-Digest name where this prompt's prefix chain
            is resident (the engine pulls it over the fabric at
            admission); X-KV-Prefill-Only marks phase 1 of a
            prefill->decode handoff (prefill + shadow-flush, one token,
            never streamed); X-KV-Push-To names the decode replica the
            router pre-picked, so phase 1 PUSHES the finished chain
            (POST /kv) before answering — phase 2's admission finds it
            resident with no pull round-trip. All no-ops without
            --continuous."""
            peer = self.headers.get("X-KV-Transfer-Peer")
            digest = self.headers.get("X-KV-Transfer-Digest")
            hint = (
                {"peer": peer, "digest": digest}
                if continuous is not None and peer and digest else None
            )
            prefill_only = (
                continuous is not None
                and self.headers.get("X-KV-Prefill-Only") in ("1", "true")
            )
            push_to = (
                self.headers.get("X-KV-Push-To")
                if continuous is not None and prefill_only else None
            )
            return hint, prefill_only, push_to

        # -- OpenAI-compatible surface (serving/openai_api.py) -----------

        def _run_single(self, prompt: str, kwargs: dict) -> dict:
            """One prompt through the same dispatch ladder as /generate:
            continuous fleet > bounded queue > bare engine. This is the
            replica's span-recording point: the whole dispatch runs under
            a `replica.request` span, the finished envelope's contiguous
            stage timings re-export as its child spans (uniform across
            all three ladder rungs), and the child context rides kwargs
            into the continuous engine so its launch-attribution spans
            nest under the same parent."""
            ctx = self._trace_ctx
            store = getattr(engine, "trace_store", None)
            if ctx is None or store is None:  # embedding callers
                return self._dispatch(prompt, kwargs)
            with store.span("replica.request", ctx, attrs={
                "request_id": kwargs.get("request_id"),
            }) as sp:
                kwargs["trace_ctx"] = ctx.child(sp["span_id"])
                result = self._dispatch(prompt, kwargs)
                sp["attrs"]["status"] = result.get("status")
                self._stage_spans(store, sp, result)
            return result

        def _stream_span(self, kwargs: dict):
            """Open the replica.request span for a STREAMED request and
            thread the child context into kwargs. The span outlives this
            frame by design — ownership transfers to the stream loop,
            whose finally calls end_span (the explicit-pair form the
            span-store docstring reserves for exactly this case)."""
            ctx = self._trace_ctx
            store = getattr(engine, "trace_store", None)
            if ctx is None or store is None:
                return None
            sp = store.start_span("replica.request", ctx, attrs={
                "request_id": kwargs.get("request_id"), "stream": True,
            })
            kwargs["trace_ctx"] = ctx.child(sp["span_id"])
            return sp

        def _dispatch(self, prompt: str, kwargs: dict) -> dict:
            if continuous is not None:
                return continuous.submit(prompt, **kwargs)
            if queue is not None:
                return queue.submit(prompt, **kwargs)
            kwargs.pop("trace_ctx", None)  # no bare-engine seam for it
            return engine.generate(prompt, **kwargs)

        @staticmethod
        def _stage_spans(store, parent: dict, result: dict):
            """Re-export the envelope's contiguous `timings` breakdown
            (utils/tracing.Trace: spans sum to ≈ total by construction)
            as child spans of `parent`, laid end to end from the request
            span's start — the per-stage view (queue_wait / admission /
            prefill / decode / detokenize) lands in the assembled fleet
            trace without a second engine-side recording hook."""
            timings = result.get("timings")
            if not isinstance(timings, dict):
                return
            t = parent["t0"]
            for key, dur in timings.items():
                if key == "total_s" or not key.endswith("_s"):
                    continue
                try:
                    dur = float(dur)
                except (TypeError, ValueError):
                    continue
                store.add_span(
                    parent["trace_id"], f"stage.{key[:-2]}", t, t + dur,
                    parent_id=parent["span_id"],
                )
                t += dur

        def _openai_stream(self, prompt: str, kwargs: dict, chat: bool):
            """SSE streaming: real per-chunk deltas on a --continuous
            server, single-chunk emulation otherwise (still valid SSE, so
            OpenAI-SDK streaming clients work against any server config)."""
            sp = None
            if continuous is not None:
                # real streaming records its request span here (the
                # non-continuous emulation goes through _run_single's)
                sp = self._stream_span(kwargs)
                events = continuous.stream(prompt, **kwargs)
            else:
                def _one_shot():
                    result = self._run_single(prompt, kwargs)
                    if result.get("status") == "success":
                        yield {"delta": result.get("response", "")}
                    yield {**result, "done": True}

                events = _one_shot()
            self._count(200)
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            if self._rid:
                self.send_header("X-Request-Id", self._rid)
            if self._trace_ctx is not None:
                self.send_header("X-Trace-Id", self._trace_ctx.trace_id)
            self.end_headers()
            try:
                for payload, _final in oai.stream_events(
                    events, engine.cfg.name, kwargs, chat=chat
                ):
                    self.wfile.write(payload)
                    self.wfile.flush()
            except OSError:
                # vanished SSE client (BrokenPipe/ConnectionReset and the
                # platform-specific OSError spellings): closing the event
                # generator routes into the engine's cancellation path —
                # continuous.stream's finally flips the cancel flag, the
                # worker kills the slot and frees its blocks at the next
                # launch boundary instead of decoding the dead client's
                # full max_new_tokens budget (regression-pinned in
                # tests/test_preemption.py)
                if hasattr(events, "close"):
                    events.close()  # cancel: frees the decode slot
            finally:
                if sp is not None:
                    engine.trace_store.end_span(sp)

        def _openai(self, path: str, data: dict):
            chat = path == "/v1/chat/completions"
            envelope = None  # the engine envelope carrying request_id/timings
            try:
                if chat:
                    prompt, kwargs, meta = oai.parse_chat(
                        data, engine.render_chat, max_tokens_cap,
                    )
                    prompts = [prompt]
                else:
                    prompts, kwargs, meta = oai.parse_completion(
                        data, max_tokens_cap
                    )
                if (
                    kwargs.get("slo_class") is not None
                    and kwargs["slo_class"] not in slo_classes
                ):
                    # same validation as /generate: an unknown class is a
                    # caller bug, never a silent fallback to the default
                    raise oai.OpenAIError(
                        f"unknown slo_class {kwargs['slo_class']!r}; "
                        f"configured: {sorted(slo_classes)}",
                        param="slo_class",
                    )
                req_model = data.get("model")
                if (
                    adapters is not None
                    and isinstance(req_model, str)
                    and req_model
                    and req_model != engine.cfg.name
                ):
                    # `model` resolves to a registered runtime adapter
                    # (the base model's own name keeps meaning the base).
                    # With a pool attached, an unknown model id is a
                    # caller bug — 400, never a silent base fallback.
                    # Without a pool, `model` stays informational, as
                    # before.
                    if not adapters.is_registered(req_model):
                        raise oai.OpenAIError(
                            f"model {req_model!r} is neither the base "
                            f"model {engine.cfg.name!r} nor a registered "
                            f"adapter; see GET /v1/models",
                            param="model",
                        )
                    kwargs["adapter"] = req_model
                hdr_dl = self.headers.get("X-Request-Deadline-Ms")
                if hdr_dl is not None:
                    # router relay of the REMAINING end-to-end budget:
                    # wins over the body's own deadline_ms
                    try:
                        kwargs["deadline_ms"] = float(hdr_dl)
                    except (TypeError, ValueError):
                        pass
                kwargs["request_id"] = self._rid
                kv_hint, prefill_only, kv_push_to = self._kv_headers()
                if kv_hint is not None:
                    kwargs["kv_hint"] = kv_hint
                if prefill_only:
                    # handoff phase 1 (see /generate): never streamed —
                    # the decode-class replica streams phase 2, so SSE
                    # clients see one transparent stream either way
                    kwargs["prefill_only"] = True
                    meta["stream"] = False
                    if kv_push_to:
                        kwargs["kv_push_to"] = kv_push_to
                if meta.get("echo_score"):
                    # echo + logprobs + max_tokens=0: teacher-forced
                    # scoring of the prompt itself (lm-eval pattern)
                    if not score_slots.acquire(blocking=False):
                        raise oai.OpenAIError(
                            "too many concurrent scoring requests",
                            status=429, err_type="overloaded_error",
                        )
                    try:
                        result = engine.score(
                            prompts[0], top_n=meta.get("score_top_n", 0)
                        )
                    finally:
                        score_slots.release()
                    if result.get("status") != "success":
                        raise oai.error_for_envelope(result)
                    self._send(200, oai.echo_score_response(
                        result, engine.cfg.name
                    ))
                    return
                if meta["stream"]:
                    if len(prompts) != 1:
                        raise oai.OpenAIError(
                            "streaming requires a single prompt", param="stream"
                        )
                    self._openai_stream(prompts[0], kwargs, chat=chat)
                    return
                n = meta.get("n", 1)
                if n > 1:
                    # n choices = one ragged fleet of the same prompt
                    # (categorical draws are independent per row)
                    prompts = prompts * n
                if len(prompts) == 1:
                    result = self._run_single(prompts[0], kwargs)
                    if result.get("status") != "success":
                        raise oai.error_for_envelope(result)
                    entries = [result]
                    envelope = result
                else:
                    if kwargs.get("logprobs"):
                        raise oai.OpenAIError(
                            "logprobs requires a single string prompt",
                            param="logprobs",
                        )
                    batch = (
                        queue.submit_batch(prompts, **kwargs)
                        if queue is not None
                        else engine.generate_batch(prompts, **kwargs)
                    )
                    if batch.get("status") != "success":
                        raise oai.error_for_envelope(batch)
                    entries = batch["results"]
                    envelope = batch
            except oai.OpenAIError as e:
                self._send(e.status, e.body)
                return
            except (TypeError, ValueError) as e:
                # defense in depth: any param-shape error that escaped the
                # parsers still answers 400, never a dropped connection
                self._send(400, oai.OpenAIError(f"bad parameter: {e}").body)
                return
            prompt_once = meta.get("n", 1) > 1
            build = oai.chat_response if chat else oai.completion_response
            # KV-fabric fields ride the OpenAI envelope as extension
            # keys (clients ignore unknown fields): the router learns
            # residency / scores handoffs identically on every route
            kv_extra = {
                k: envelope[k]
                for k in ("kv_digests", "kv_fabric_blocks",
                          "kv_promoted_blocks", "prefill_only",
                          "kv_pushed")
                if isinstance(envelope, dict) and k in envelope
            }
            self._send(
                200,
                # adapter-resolved requests echo the adapter id as the
                # model (vLLM convention): the client asked for that id
                # and /v1/models lists it
                build(entries, kwargs.get("adapter") or engine.cfg.name,
                      kwargs,
                      prompt_once=prompt_once,
                      request_id=envelope.get("request_id", self._rid),
                      timings=envelope.get("timings"),
                      kv_extra=kv_extra or None,
                      trace_id=(self._trace_ctx.trace_id
                                if self._trace_ctx is not None else None)),
            )

        def do_POST(self):
            path = self.path.split("?")[0].rstrip("/")
            # accept a client-supplied X-Request-Id (sanitized) for
            # cross-service correlation, else mint one; echoed on every
            # response header and in the JSON envelope
            self._rid = (
                sanitize_request_id(self.headers.get("X-Request-Id"))
                or new_request_id()
            )
            # join the caller's trace (router/client `traceparent`) or
            # root a fresh one; every log record inside the request then
            # carries both ids (utils/logging request_id_context)
            self._trace_ctx = (
                parse_traceparent(self.headers.get("traceparent"))
                or SpanContext.new_root()
            )
            with request_id_context(self._rid, self._trace_ctx.trace_id):
                self._do_POST(path)

        def _do_POST(self, path: str):
            if state.draining and path in (
                "/generate", "/v1/completions", "/v1/chat/completions"
            ):
                # graceful drain: admission closed at the edge (in-flight
                # work keeps finishing); Retry-After tells well-behaved
                # clients when to try the next replica
                self._send(
                    503,
                    {
                        "error": "Error: server draining",
                        "status": "failed", "error_type": "draining",
                    },
                    headers={"Retry-After": str(RETRY_AFTER_S)},
                )
                return
            if path in ("/v1/completions", "/v1/chat/completions"):
                data = self._read_json()
                if data is not None:
                    self._openai(path, data)
                return
            if path == "/profiler/start":
                data = self._read_json()
                if data is None:
                    return
                # default is a subdir NAME under the profiler base, not a path
                res = profiler.start(data.get("trace_dir", "trace"))
                self._send(400 if "error" in res else 200, res)
                return
            if path == "/profiler/stop":
                res = profiler.stop()
                self._send(400 if "error" in res else 200, res)
                return
            if path == "/kv":
                # the KV fabric's push half: a peer's proactive chain
                # push at the prefill->decode handoff. The payload is
                # validated against its OWN content key (the digest is
                # recomputed from its tokens) and landed in the host
                # shadow tier; a payload failing validation is a 400 the
                # pusher treats as "the pull fallback will cover it".
                if continuous is None or not continuous.fabric_serving:
                    self._send(404, {"error": "kv fabric not serving"})
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                except (TypeError, ValueError):
                    length = 0
                if length <= 0:
                    self._send(400, {"error": "empty /kv push"})
                    return
                body = self.rfile.read(length)
                res = continuous.fabric_accept_push(body)
                if res is None:
                    self._send(400, {"error": "push payload failed "
                                              "content-key validation"})
                else:
                    self._send(200, res)
                return
            if path != "/generate":
                self._send(404, {"error": f"no route {path}"})
                return
            data = self._read_json()
            if data is None:
                return
            prompt = data.get("prompt", "")
            prompts = data.get("prompts")
            if not prompt and not prompts:
                # reference: 400 "No prompt provided" (orchestration.py:343)
                self._send(400, {"error": "No prompt provided"})
                return
            try:
                max_tokens = min(int(data.get("max_tokens", DEFAULT_MAX_TOKENS)), max_tokens_cap)
                seed = data.get("seed")
                kwargs = dict(
                    request_id=self._rid,
                    max_tokens=max_tokens,
                    temperature=float(data.get("temperature", DEFAULT_TEMPERATURE)),
                    top_k=int(data.get("top_k", DEFAULT_TOP_K)),
                    top_p=float(data.get("top_p", DEFAULT_TOP_P)),
                    greedy=_parse_bool(data.get("greedy", False), "greedy"),
                    chat=_parse_bool(data.get("chat", True), "chat"),
                    seed=int(seed) if seed is not None else None,
                    # HF-parity extensions (0.0 / 1.0 = off)
                    min_p=float(data.get("min_p", 0.0)),
                    repetition_penalty=float(
                        data.get("repetition_penalty", 1.0)
                    ),
                    # OpenAI penalties over generated-token counts (0 = off)
                    frequency_penalty=float(
                        data.get("frequency_penalty", 0.0)
                    ),
                    presence_penalty=float(
                        data.get("presence_penalty", 0.0)
                    ),
                )
                raw_dl = self._deadline_ms(data)
                if raw_dl is not None:
                    # end-to-end deadline: expiry anywhere (queued,
                    # mid-prefill, mid-decode) returns a 504
                    # deadline_exceeded envelope and frees the request's
                    # blocks/slot at the next launch boundary. The header
                    # form (X-Request-Deadline-Ms, set by the router with
                    # the REMAINING budget) wins over the body field.
                    kwargs["deadline_ms"] = raw_dl
                raw_slo = data.get("slo_class")
                if raw_slo is not None:
                    # SLO class (engine/scheduler.py): admission priority,
                    # prefill-budget share, and shed policy on the
                    # continuous fleet; class-aware Retry-After on 429s.
                    # Unknown names are a caller bug -> 400.
                    if (
                        not isinstance(raw_slo, str)
                        or raw_slo not in slo_classes
                    ):
                        raise ValueError(
                            f"unknown slo_class {raw_slo!r}; configured: "
                            f"{sorted(slo_classes)}"
                        )
                    kwargs["slo_class"] = raw_slo
                if data.get("denoise_steps") is not None:
                    # block-diffusion models: forwards that reveal a block
                    # (it must divide the model's block length; the
                    # engine says so where it does not); the server's
                    # default is --denoise-steps
                    kwargs["denoise_steps"] = int(data["denoise_steps"])
                raw_tenant = data.get("tenant")
                if raw_tenant is not None:
                    # multi-tenant identity (engine/scheduler.py):
                    # tenant-weighted apportionment within each SLO
                    # class, per-tenant queue quota shed, per-tenant
                    # TTFT/TPOT EWMAs. Free-form label.
                    if not isinstance(raw_tenant, str) or not raw_tenant:
                        raise ValueError(
                            "tenant must be a non-empty string"
                        )
                    kwargs["tenant"] = raw_tenant
                raw_adapter = data.get("adapter")
                if raw_adapter is not None and raw_adapter != engine.cfg.name:
                    # runtime LoRA adapter selection (engine/adapters.py):
                    # the request's decode rows ride the named adapter's
                    # device page inside the one compiled mixed program.
                    # The base model's own name means "no adapter" so
                    # callers can pass their model id unconditionally.
                    if not isinstance(raw_adapter, str):
                        raise ValueError("adapter must be a string")
                    if adapters is None:
                        raise ValueError(
                            "adapter serving is not configured: start "
                            "with --adapter-slots (and --continuous + "
                            "--kv-pool-blocks)"
                        )
                    if not adapters.is_registered(raw_adapter):
                        raise ValueError(
                            f"unknown adapter {raw_adapter!r}; "
                            f"registered: {adapters.names()}"
                        )
                    kwargs["adapter"] = raw_adapter
                nbeams = data.get("num_beams")
                if nbeams is not None and int(nbeams) > 1:
                    # deterministic beam search (HF num_beams semantics);
                    # beam requests run solo (pure max-score search)
                    kwargs["num_beams"] = int(nbeams)
                    kwargs["length_penalty"] = float(
                        data.get("length_penalty", 1.0)
                    )
                    kwargs["early_stopping"] = _parse_bool(
                        data.get("early_stopping", False), "early_stopping"
                    )
                raw_bias = data.get("logit_bias")
                if raw_bias is not None:
                    # {token_id: bias} added to the raw logits every sample
                    # (OpenAI semantics; the engine validates ids/backend)
                    if not isinstance(raw_bias, dict):
                        raise ValueError("logit_bias must be an object of "
                                         "token_id -> bias")
                    kwargs["logit_bias"] = {
                        int(k): float(v) for k, v in raw_bias.items()
                    }
                raw_con = data.get("constraint")
                if raw_con is not None:
                    # grammar-constrained structured output (constrain/):
                    # {"regex": ...} | {"choices": [...]} |
                    # {"json_schema": {...}} | {"json_object": true}.
                    # Spec validation happens engine-side
                    # (parse_constraint_spec) -> invalid_request 400.
                    if not isinstance(raw_con, dict):
                        raise ValueError(
                            "constraint must be an object with one of "
                            "'regex', 'choices', 'json_schema', "
                            "'json_object'"
                        )
                    kwargs["constraint"] = raw_con
                raw_stop = data.get("stop")
                if raw_stop is not None:
                    # OpenAI-style textual stop sequences: one string or a
                    # list of strings
                    if isinstance(raw_stop, str):
                        raw_stop = [raw_stop]
                    if not (
                        isinstance(raw_stop, list)
                        and all(isinstance(s, str) for s in raw_stop)
                    ):
                        raise ValueError("stop must be a string or list of strings")
                    kwargs["stop"] = raw_stop
                kv_hint, prefill_only, kv_push_to = self._kv_headers()
                if kv_hint is not None:
                    kwargs["kv_hint"] = kv_hint
                if prefill_only:
                    # handoff phase 1: prefill + shadow flush + one
                    # token; the router discards the token and hands the
                    # prefix digest to a decode-class replica — so the
                    # body's stream flag is ignored here (the STREAM
                    # happens on the decode replica, transparently)
                    kwargs["prefill_only"] = True
                    if kv_push_to:
                        kwargs["kv_push_to"] = kv_push_to
                if not prefill_only and _parse_bool(
                    data.get("stream", False), "stream"
                ):
                    # NDJSON token streaming: one {"delta": ...} line per
                    # decode chunk, final line = the standard envelope with
                    # "done": true. Requires --continuous (the solo engine
                    # decodes entirely on-device; there is nothing to
                    # stream per-token).
                    if continuous is None or prompts is not None:
                        self._send(400, {
                            "error": "streaming requires --continuous and a "
                            "single 'prompt'",
                        })
                        return
                    kwargs["debug"] = _parse_bool(data.get("debug", False), "debug")
                    kwargs["speculative"] = _parse_bool(
                        data.get("speculative", False), "speculative"
                    )
                    kwargs["logprobs"] = _parse_bool(
                        data.get("logprobs", False), "logprobs"
                    )
                    self._count(200)
                    self.send_response(200)
                    self.send_header("Content-Type", "application/x-ndjson")
                    if self._rid:
                        self.send_header("X-Request-Id", self._rid)
                    if self._trace_ctx is not None:
                        self.send_header(
                            "X-Trace-Id", self._trace_ctx.trace_id
                        )
                    self.end_headers()
                    sp = self._stream_span(kwargs)
                    gen = continuous.stream(prompt, **kwargs)
                    try:
                        for ev in gen:
                            self.wfile.write(json.dumps(ev).encode() + b"\n")
                            self.wfile.flush()
                    except OSError:
                        # client went away mid-stream: closing the
                        # generator cancels the request — the engine kills
                        # its slot at the next chunk boundary so the fleet
                        # serves queued work instead of a dead socket
                        gen.close()
                    finally:
                        if sp is not None:
                            engine.trace_store.end_span(sp)
                    return
                if prompts is not None:
                    # batched form: "prompts": [...] -> one fleet, N results
                    if not isinstance(prompts, list):
                        raise ValueError("prompts must be a list of strings")
                    if kwargs.get("logit_bias"):
                        raise ValueError(
                            "logit_bias requires a single 'prompt'"
                        )
                    if kwargs.get("num_beams", 1) > 1:
                        raise ValueError(
                            "num_beams requires a single 'prompt'"
                        )
                    if queue is not None:
                        # same bounded backpressure as singles; full -> 429
                        result = queue.submit_batch(prompts, **kwargs)
                    else:
                        result = engine.generate_batch(prompts, **kwargs)
                else:
                    # debug=true adds top-5 first-token predictions
                    # (reference's debug prints, orchestration.py:172-178)
                    kwargs["debug"] = _parse_bool(data.get("debug", False), "debug")
                    # speculative=true: greedy prompt-lookup speculation
                    # (faster on repetitive text; argmax-equivalent — exact
                    # in fp32, bf16 may resolve numerical near-ties
                    # differently)
                    kwargs["speculative"] = _parse_bool(
                        data.get("speculative", False), "speculative"
                    )
                    # logprobs=true: per-generated-token log-probabilities
                    # (raw model distribution; single-device backend)
                    kwargs["logprobs"] = _parse_bool(
                        data.get("logprobs", False), "logprobs"
                    )
                    # the same dispatch ladder as the OpenAI routes —
                    # continuous (in-flight batching, engine/continuous.py)
                    # > bounded queue (serving/queue.py) > bare engine —
                    # via the one span-recording point, so /generate and
                    # /v1/* requests trace identically
                    result = self._run_single(prompt, kwargs)
            except (TypeError, ValueError) as e:
                self._send(400, {"error": f"bad parameter: {e}"})
                return
            err_type = result.get("error_type")
            headers = None
            if result.get("status") == "success":
                code = 200
            elif err_type == "invalid_request":
                code = 400
            elif err_type == "deadline_exceeded":
                # the request's OWN deadline_ms budget expired: 504, and
                # nobody — router included — may retry it (the budget is
                # just as spent wherever the retry lands)
                code = 504
            elif err_type == "cancelled":
                # client went away (or the stream was torn down): 499
                # (nginx convention) so access logs can tell a dead
                # client from a server fault; never router-retried
                code = 499
            elif err_type in ("timeout", "unavailable", "draining"):
                # timeout: deadline exceeded (reference's per-hop failure,
                # orchestration.py:118,131). unavailable: the continuous
                # scheduler exhausted its restart budget. draining: raced
                # the drain flag inside the engine — all service-
                # unavailable, all retryable elsewhere.
                code = 503
                if err_type != "timeout":
                    headers = {"Retry-After": str(RETRY_AFTER_S)}
            elif err_type == "overloaded":
                # bounded queue full (serving/queue.py or the continuous
                # admission queue): shed load, with the queue-depth-derived
                # Retry-After hint so overload backoff is server-directed
                # exactly like the drain path's
                code = 429
                headers = {
                    "Retry-After": str(
                        result.get("retry_after_s", RETRY_AFTER_S)
                    )
                }
            else:
                # includes "poison": the request itself crashed the
                # scheduler K times — a server-side fault answer, and the
                # one 5xx a client must NOT blindly retry
                code = 500
            self._send(code, result, headers=headers)

    return Handler


class _ServerState:
    """Mutable flags shared between the server object and its handler
    class (the handler closes over this; InferenceServer.drain flips it)."""

    __slots__ = ("draining",)

    def __init__(self):
        self.draining = False


class InferenceServer:
    """Owns the HTTP server + engine; start()/shutdown() for embedding in
    tests, serve_forever() for the CLI (which installs the SIGTERM →
    graceful-drain handler)."""

    def __init__(self, engine, host: str = "0.0.0.0", port: int = 5000,
                 max_tokens_cap: int = 30, queue=None, continuous=None,
                 drain_deadline_s: float = 30.0,
                 wedge_unready_s: float = 10.0):
        self.engine = engine
        self.queue = queue
        self.continuous = continuous
        self.drain_deadline_s = float(drain_deadline_s)
        self.state = _ServerState()
        self.httpd = ThreadingHTTPServer(
            (host, port),
            make_handler(engine, max_tokens_cap, queue=queue,
                         continuous=continuous, state=self.state,
                         wedge_unready_s=wedge_unready_s),
        )
        self.port = self.httpd.server_address[1]

    def start(self) -> threading.Thread:
        t = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        t.start()
        return t

    def drain(self, deadline_s: Optional[float] = None) -> bool:
        """Graceful drain, the SIGTERM path: flip readiness (new requests
        get 503 + Retry-After, /ready goes 503), let queued + in-flight
        work finish up to the deadline, then stop the HTTP server and
        close the engines. Ordering matters: edge first (no new
        admissions), then the batching layers (their own queues), then
        the bare engine's in-flight lock. Returns True when everything
        finished inside the deadline."""
        deadline = (
            self.drain_deadline_s if deadline_s is None else float(deadline_s)
        )
        t0 = time.time()
        self.state.draining = True
        ok = True

        def left() -> float:
            return max(0.0, deadline - (time.time() - t0))

        if self.continuous is not None:
            ok = self.continuous.drain(left()) and ok
        if self.queue is not None:
            ok = self.queue.drain(left()) and ok
        if hasattr(self.engine, "drain"):  # MirroredEngine proxies lack it
            ok = self.engine.drain(left()) and ok
        self.engine.metrics.histogram(
            "dli_drain_duration_seconds",
            "graceful-drain wall time (SIGTERM / drain())", ("component",),
        ).labels(component="server").observe(time.time() - t0)
        from ..utils.logging import get_logger

        get_logger("server").info(
            "drained", ok=ok, seconds=round(time.time() - t0, 3)
        )
        self.shutdown()
        return ok

    def install_signal_handlers(self):
        """SIGTERM → graceful drain (must run on the main thread; the
        handler only spawns the drain thread, so it returns immediately).
        The second SIGTERM is left at default disposition semantics: the
        drain already owns shutdown, and repeated signals must not stack
        drain threads."""
        import signal

        def _on_term(signum, frame):
            if self.state.draining:
                return  # drain already in flight
            self.state.draining = True  # flip readiness before the thread spawns
            threading.Thread(
                target=self.drain, name="sigterm-drain", daemon=True
            ).start()

        signal.signal(signal.SIGTERM, _on_term)

    def serve_forever(self):
        from ..utils.logging import configure, get_logger

        configure()  # JSON-lines handler; entry-point-only (library-safe)
        self.install_signal_handlers()
        get_logger("server").info(
            "serving", port=self.port,
            routes=["/generate", "/health", "/ready", "/workers", "/stats",
                    "/metrics", "/profiler/*", "/debug/traces",
                    "/debug/flight"],
        )
        print(f"🚀 serving on :{self.port} — /generate /health /ready /workers /metrics /")
        self.httpd.serve_forever()
        # serve_forever returns when drain()/shutdown() stopped the
        # listener — SIGTERM ends as a clean exit 0

    def shutdown(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        if self.queue is not None:
            self.queue.close()
        if self.continuous is not None:
            self.continuous.close()


# every tokenizer format the converter carries into a store: BPE json,
# config, GPT-2 vocab/merges, and sentencepiece .model (Llama-2-style
# dirs ship ONLY tokenizer.model — missing it here would silently serve
# byte-garbled text, the exact failure strict loading exists to prevent)
_TOKENIZER_FILES = (
    "tokenizer.json", "tokenizer_config.json", "vocab.json", "tokenizer.model",
)


def _has_tokenizer_files(path: str) -> bool:
    import os

    return any(os.path.exists(os.path.join(path, f)) for f in _TOKENIZER_FILES)


def _load_checkpoint(args, mesh_cfg):
    """(cfg, params) for --checkpoint: a local store dir (manifest.json) or
    a HF checkpoint dir (config.json + safetensors).

    On a multi-device mesh a store restores directly into mesh-sharded
    arrays (models/checkpoint.load_params_sharded) — each host reads only
    its shards' pages off mmap. quant/LoRA need host-side full params
    first (quantize/merge run before placement), so those paths take the
    full load. This is the serving entry the reference's whole design is
    for: real TinyLlama weights behind /generate
    (/root/reference/orchestration.py:34-47)."""
    import os

    path = args.checkpoint
    if os.path.exists(os.path.join(path, "manifest.json")):
        from ..models.checkpoint import load_params, load_params_sharded

        sharded_ok = (
            mesh_cfg.n_devices > 1 and args.quant is None and args.lora is None
        )
        if sharded_ok:
            from ..parallel.mesh import build_mesh

            cfg, params = load_params_sharded(path, build_mesh(mesh_cfg))
        else:
            cfg, params = load_params(path)
        if args.dtype and args.dtype != cfg.dtype:
            raise SystemExit(
                f"--dtype {args.dtype} conflicts with the checkpoint's "
                f"recorded dtype {cfg.dtype!r}; re-convert with --dtype "
                f"{args.dtype} instead"
            )
        return cfg, params
    if os.path.exists(os.path.join(path, "config.json")):
        from ..models.convert import load_hf_checkpoint

        return load_hf_checkpoint(path, dtype=args.dtype or "bfloat16")
    raise SystemExit(
        f"--checkpoint {path}: neither a local store (manifest.json) nor "
        f"a HF checkpoint dir (config.json + *.safetensors)"
    )


def main(argv: Optional[list] = None):
    from ..config import EngineConfig, MeshConfig
    from ..runtime import create_engine

    ap = argparse.ArgumentParser(description="distributed_llm_inference_tpu server")
    ap.add_argument("--model", default="tinyllama-1.1b")
    ap.add_argument(
        "--checkpoint", default=None, metavar="DIR",
        help="serve REAL weights: a local checkpoint store dir "
             "(models/checkpoint.py; produced by `python -m "
             "distributed_llm_inference_tpu.models.convert`) or a "
             "HuggingFace checkpoint dir (config.json + *.safetensors). "
             "Overrides --model; on a multi-device mesh a store loads "
             "shard-by-shard off mmap so no host materializes the full "
             "model (the reference re-downloads the whole model on every "
             "worker, /root/reference/Worker1.py:60-77)",
    )
    ap.add_argument(
        "--tokenizer", default=None, metavar="PATH",
        help="HF tokenizer dir/name to serve with (loaded strict: a bad "
             "path fails startup instead of silently degrading to the "
             "byte-level fallback). Defaults to tokenizer files found in "
             "--checkpoint DIR, else the offline byte tokenizer",
    )
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=5000)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1, help="pipeline stages")
    ap.add_argument(
        "--microbatches", type=int, default=1, metavar="M",
        help="M > 1 serves the zero-bubble 1F1B schedule (BASELINE config "
             "5): batched requests split into M microbatches chasing each "
             "other around the pp ring (needs --pp >= 2 and M >= pp); solo "
             "requests ride the batched path",
    )
    ap.add_argument("--sp", type=int, default=1, help="context-parallel ring size")
    ap.add_argument(
        "--sp-strategy", default="ring", choices=["ring", "ulysses"],
        help="long-context prefill strategy over the sp axis: 'ring' "
             "(K/V rotate via ppermute) or 'ulysses' (two all-to-alls "
             "re-shard sequence<->heads; needs heads divisible by sp)",
    )
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--ep", type=int, default=1, help="expert-parallel width (MoE)")
    ap.add_argument("--dtype", default=None, choices=[None, "float32", "bfloat16"])
    ap.add_argument(
        "--attn-impl", default=None, choices=[None, "auto", "xla", "pallas"],
        help="attention implementation: 'pallas' = the flash kernel "
             "(ops/flash_attention.py), 'xla' = einsum + mask (XLA fuses "
             "it), 'auto' = pallas when legal for the model AND running "
             "on TPU (CPU interpret mode is never auto-selected); default "
             "keeps the model config's setting (xla)",
    )
    ap.add_argument(
        "--lora", default=None, metavar="DIR",
        help="PEFT-format LoRA adapter directory to merge into the base "
             "weights at load (W + alpha/r * BA; before quantization) — "
             "the SINGLE-adapter fast path: zero per-step delta cost, "
             "but the whole server speaks that one adapter. Serve many "
             "adapters concurrently with --adapter-slots/--adapter "
             "instead (the same adapter cannot be used both ways)",
    )
    ap.add_argument(
        "--adapter-slots", type=int, default=0, metavar="N",
        help="runtime LoRA adapter pool (engine/adapters.py): reserve N "
             "device pages of paged A/B factors next to the resident "
             "base weights; requests select a registered adapter by "
             "name ('adapter' on /generate, 'model' on the OpenAI "
             "routes) and decode through ONE compiled program whatever "
             "the adapter mix. Needs --continuous + --kv-pool-blocks "
             "(the ragged paged fleet); 0 = disabled",
    )
    ap.add_argument(
        "--adapter-rank", type=int, default=8, metavar="R",
        help="pool page rank: every registered adapter is zero-padded "
             "to rank R (registration rejects adapters with a larger "
             "trained rank)",
    )
    ap.add_argument(
        "--adapter", action="append", default=None, metavar="NAME=DIR",
        help="register a PEFT-format LoRA adapter directory under NAME "
             "at startup (repeatable); requests then address it by "
             "name. Requires --adapter-slots; more adapters than slots "
             "is fine — pages are refcounted and LRU-swapped on demand",
    )
    ap.add_argument(
        "--tenant-weight", action="append", default=None, metavar="NAME=W",
        help="per-tenant fairness weight on the continuous fleet "
             "(repeatable): within each SLO class, queued tenants split "
             "the class's token budget in proportion to their weights "
             "(unlisted tenants weigh 1.0); requests carry their tenant "
             "in the 'tenant' field",
    )
    ap.add_argument(
        "--tenant-queue-share", type=float, default=0.5, metavar="F",
        help="per-tenant admission-queue quota as a fraction of the "
             "continuous queue bound: one tenant's queued requests "
             "beyond max(4, F * queue-bound) shed with 429 + "
             "Retry-After so a flooding tenant cannot starve the "
             "others' admission; 1.0 disables the quota",
    )
    ap.add_argument(
        "--draft-model", default=None, metavar="NAME",
        help="attach a smaller same-tokenizer model as a speculative "
             "draft: greedy requests with \"speculative\": true verify "
             "the draft's proposals (several tokens per target forward "
             "on text the draft predicts well; single chip or a pp mesh "
             "— the ring runs the draft replicated)",
    )
    ap.add_argument(
        "--quant", default=None, choices=[None, "int8", "int4"],
        help="weight-only quantization: int8 halves decode HBM bytes/token "
             "(llama family); int4 halves the WEIGHT FOOTPRINT again "
             "(packed nibbles, group-wise scales) — the capacity pick for "
             "fitting bigger models",
    )
    ap.add_argument(
        "--kv-quant", default=None, choices=[None, "int8"],
        help="KV-CACHE quantization: int8 K/V with per-(token, head) "
             "scales halves cache HBM — 2x the --continuous slots or "
             "context window at the same budget (llama family; EVERY "
             "topology: single chip, pp/tp/dp/1F1B meshes, --sp rings; "
             "composes with --prefix-cache, --kv-pool-blocks — an int8 "
             "block pool stacks both HBM levers — and --attn-impl "
             "pallas, whose kernels dequantize in their prologues)",
    )
    ap.add_argument(
        "--pp-wire-quant", default=None, choices=[None, "int8"],
        help="quantized inter-stage transfers: int8 + per-token-row fp32 "
             "scales on every pp/sp activation hand-off (microstep ring, "
             "1F1B, sp chunk rotation, final-stage broadcast) — ~4x "
             "fewer ICI bytes at fp32 (~2x at bf16), the binding "
             "constraint for deeper pipelines; default off = "
             "bit-identical wire (greedy output toleranced when on)",
    )
    ap.add_argument("--max-tokens-cap", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--deadline", type=float, default=None, metavar="SECONDS",
        help="per-request wall-clock deadline; overruns return a 503 "
             "timeout envelope (reference: 30s per worker hop)",
    )
    ap.add_argument(
        "--drain-deadline", type=float, default=30.0, metavar="SECONDS",
        help="graceful-drain budget on SIGTERM: readiness flips "
             "immediately (503 + Retry-After on new requests, /ready "
             "503), in-flight requests get this long to finish, then the "
             "process exits cleanly",
    )
    ap.add_argument(
        "--restart-budget", type=int, default=3, metavar="N",
        help="continuous-scheduler supervisor: how many CONSECUTIVE "
             "crashes to absorb (restart + re-admit in-flight requests "
             "as continuation prefills) before declaring the fleet dead; "
             "a healthy decode chunk resets the window",
    )
    ap.add_argument(
        "--poison-strikes", type=int, default=2, metavar="K",
        help="quarantine a request implicated in K consecutive "
             "scheduler crash-restarts (error_type 'poison'), instead of "
             "letting it take the fleet down with it",
    )
    ap.add_argument(
        "--faults", default=None, metavar="SPEC",
        help="arm the deterministic fault-injection harness "
             "(utils/faults.py), e.g. 'decode_launch:transient:on=3'; "
             "the DLI_FAULTS env var is the config-file-free spelling. "
             "Chaos drills only — never in front of real traffic",
    )
    ap.add_argument(
        "--trace-sample-rate", type=float, default=0.0, metavar="F",
        help="fraction of traced requests that also get launch-level "
             "device-time attribution on the continuous fleet: sampled "
             "requests' mixed/chunk launches record dispatch->fetch "
             "spans (host timestamps keyed by launch seq — never an "
             "extra device sync) into GET /debug/traces/{trace_id}. "
             "0 (default) keeps the hot path allocation-free",
    )
    ap.add_argument(
        "--wedge-unready", type=float, default=10.0, metavar="SECONDS",
        help="flip GET /ready to 503 (reason 'wedged') while an abandoned "
             "deadline-overrun device call has been stuck this long — the "
             "router tier's health probes then eject the replica until "
             "the call drains (0 disables; needs --deadline to ever "
             "trigger; liveness /health stays 200 throughout)",
    )
    ap.add_argument(
        "--restore-dir", default=None, metavar="DIR",
        help="warm-state persistence for --continuous with "
             "--kv-pool-blocks (engine/shadow.py): graceful drain "
             "(SIGTERM / rolling restart) serializes the shadowed KV "
             "blocks + block-prefix chains here, and startup restores "
             "them into the fresh pool — the replica rejoins with a "
             "WARM prefix cache (needs --prefix-cache > 0)",
    )
    ap.add_argument(
        "--replica-class", default="mixed",
        choices=["mixed", "prefill", "decode"],
        help="disaggregation class for the router tier (serving/"
             "router.py): 'prefill' replicas take fresh long-prompt work "
             "and hand the finished prefix to a 'decode' replica by "
             "chunk digest over the KV fabric; 'mixed' (default) serves "
             "everything. Engine behavior is identical — this labels "
             "/health and the dli_kv_fabric_* metrics' role",
    )
    ap.add_argument(
        "--no-kv-fabric", action="store_true",
        help="disable the cross-replica KV fabric (the GET /kv/{digest} "
             "surface and X-KV-Transfer-* fetch hints); the shadow "
             "store stays purely local (crash recovery / --restore-dir)",
    )
    ap.add_argument(
        "--kv-fabric-timeout", type=float, default=5.0, metavar="SECONDS",
        help="hard deadline on one fabric fetch; a dead or wedged peer "
             "costs at most this long before admission prefills locally",
    )
    ap.add_argument(
        "--no-kv-shadow", action="store_true",
        help="disable the warm-recovery shadow store (supervisor "
             "restarts and --restore-dir starts then recover cold, "
             "re-prefilling every salvaged request from its full prompt)",
    )
    ap.add_argument(
        "--kv-disk-dir", default=None, metavar="DIR",
        help="disk tier (tier 2) of the KV cache hierarchy: LRU-evicted "
             "host-shadow entries demote into parent-chained chunk files "
             "here instead of dropping, and every shadow read surface "
             "(prefix planning, warm recovery, preemption swap, the "
             "fabric) promotes hits back out — the replica's logical "
             "prefix cache becomes disk-bounded. Default: no disk tier",
    )
    ap.add_argument(
        "--kv-disk-blocks", type=int, default=0, metavar="N",
        help="disk-tier bound in blocks (chunk files, LRU). 0 = auto: "
             "8x the host shadow tier",
    )
    ap.add_argument(
        "--no-kv-stream", action="store_true",
        help="pull fabric chains as one whole-manifest blob instead of "
             "chunk-at-a-time streamed frames (the streamed pull "
             "overlaps the wire with the importing replica's pool "
             "scatters; this pins the pre-stream behavior)",
    )
    ap.add_argument(
        "--kv-health-digests", type=int, default=64, metavar="N",
        help="cap on the resident-chain digests /health advertises for "
             "router residency bootstrap (MRU-first, host tier before "
             "disk) — keeps bootstrap payloads O(1) however deep the "
             "disk tier grows",
    )
    ap.add_argument(
        "--spec-decode", action="store_true",
        help="fleet-wide speculative decoding on the continuous ragged "
             "paged fleet: EVERY eligible greedy slot submits draft-then-"
             "verify rows inside the mixed launch (without this flag only "
             "requests passing \"speculative\": true speculate); the SLO "
             "scheduler throttles drafting to 0 under decode TPOT "
             "pressure, and greedy output stays bit-identical",
    )
    ap.add_argument(
        "--spec-draft-len", type=int, default=4, metavar="K",
        help="drafted tokens per mixed-launch verify row (0 disables the "
             "fleet speculation machinery entirely)",
    )
    ap.add_argument(
        "--denoise-steps", type=int, default=0, metavar="N",
        help="block-diffusion models (sdar-30b-a3b-chat): the default "
             "number of denoising forwards that reveal a block, for "
             "requests without a \"denoise_steps\" field; it must divide "
             "the model's block length (0 = the block length: one "
             "position a forward)",
    )
    ap.add_argument(
        "--spec-draft-model", default=None, metavar="NAME",
        help="draft the fleet's verify rows with a small same-tokenizer "
             "model's device-side greedy chain (shares the block tables "
             "over its own pool) instead of n-gram lookup; an attached "
             "--draft-model takes precedence over loading NAME",
    )
    ap.add_argument(
        "--die-on-wedge", type=float, default=None, metavar="SECONDS",
        help="exit the process (code 17) once an abandoned deadline-overrun "
             "device call has been stuck this long — a supervisor restart "
             "is the only real recovery from a wedged accelerator runtime; "
             "/health reports \"degraded\" with the stuck age either way "
             "(needs --deadline)",
    )
    ap.add_argument(
        "--queue", type=int, default=0, metavar="N",
        help="bounded request queue of depth N in front of the engine: "
             "concurrent singles coalesce into ragged batched fleets, "
             "full queue returns 429 (0 = disabled)",
    )
    ap.add_argument(
        "--queue-max-batch", type=int, default=8,
        help="largest coalesced fleet the queue dispatcher forms",
    )
    ap.add_argument(
        "--queue-wait-ms", type=float, default=5.0,
        help="coalescing window before a fleet is cut",
    )
    ap.add_argument(
        "--continuous", type=int, default=0, metavar="SLOTS",
        help="continuous (in-flight) batching: a fleet of SLOTS KV-cache "
             "rows decodes in lock-step and new requests join free slots "
             "mid-flight (llama + gpt2 families; single chip or a pp mesh "
             "with dp=1; 0 = disabled; mutually exclusive with --queue)",
    )
    ap.add_argument(
        "--continuous-chunk", type=int, default=16,
        help="decode steps per device round-trip in continuous mode (the "
             "most: a chunk ends when its last live row does)",
    )
    ap.add_argument(
        "--continuous-max-seq", type=int, default=None, metavar="N",
        help="per-slot KV budget for --continuous (prompt + generated "
             "tokens per request; default: the model's max_seq_len). The "
             "fleet pins SLOTS x N of KV in HBM — cap it to what you "
             "actually serve: 8 slots x 4096 on a 7B-class model is "
             "~8.5 GB bf16 before weights",
    )
    ap.add_argument(
        "--kv-pool-blocks", type=int, default=None, metavar="N",
        help="block-paged KV for --continuous (llama family, single chip "
             "or a dp=1 pp/tp mesh — the pool shards layers over pp): "
             "a shared pool of N blocks replaces the dense SLOTS x max-seq "
             "fleet — HBM is a function of aggregate in-flight tokens and "
             "admission backpressures on pool exhaustion (engine/paged.py)",
    )
    ap.add_argument(
        "--state-snapshots", type=int, default=0,
        help="recurrent states a paged fleet keeps for prefix hits to "
             "restore (a model of linear-attention layers; 0: two a slot)",
    )
    ap.add_argument(
        "--kv-block-size", type=int, default=16,
        help="tokens per KV pool block (with --kv-pool-blocks)",
    )
    ap.add_argument(
        "--continuous-lag", type=int, default=2,
        help="decode chunks in flight before blocking on the oldest "
             "fetch (>1 hides a device-fetch RTT larger than a chunk's "
             "compute; EOS/stop noticed up to LAG chunks late)",
    )
    ap.add_argument(
        "--prefix-cache", type=int, default=0, metavar="N",
        help="keep N chunk-aligned prompt-prefix KV snapshots on device; "
             "requests sharing a stored prefix prefill only their tail "
             "(TTFT scales with new tokens, not the prompt)",
    )
    ap.add_argument(
        "--coordinator", default=None, metavar="HOST:PORT",
        help="multi-host DCN bring-up: jax.distributed coordinator address "
             "(use with --num-processes/--process-id on every host)",
    )
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument(
        "--warmup", action="store_true",
        help="pre-compile every (prefill, decode) bucket before serving "
             "(first requests then never pay jit latency)",
    )
    args = ap.parse_args(argv)

    if args.die_on_wedge and not args.deadline:
        # checked BEFORE the (potentially minutes-long) model load
        raise SystemExit(
            "--die-on-wedge needs --deadline: wedges are detected by "
            "deadline-overrun calls that never drain"
        )
    if args.adapter and not args.adapter_slots:
        raise SystemExit(
            "--adapter needs --adapter-slots N: the runtime pool's "
            "device pages are reserved at engine build"
        )
    if args.adapter_slots and (
        args.continuous <= 0 or args.kv_pool_blocks is None
    ):
        # also pre-model-load: a pool no request could ever select
        # (the adapter path rides the ragged paged fleet's mixed
        # launch) is a misconfiguration, not a degraded mode
        raise SystemExit(
            "--adapter-slots needs --continuous SLOTS with "
            "--kv-pool-blocks N: runtime adapters ride the ragged "
            "paged fleet's mixed launch"
        )
    adapter_specs = []
    for spec in args.adapter or ():
        name, sep, path = spec.partition("=")
        if not sep or not name or not path:
            raise SystemExit(f"--adapter {spec!r}: expected NAME=DIR")
        adapter_specs.append((name, path))
    tenant_weights = []
    for spec in args.tenant_weight or ():
        name, sep, w = spec.partition("=")
        if not sep or not name:
            raise SystemExit(
                f"--tenant-weight {spec!r}: expected NAME=WEIGHT"
            )
        try:
            tenant_weights.append((name, float(w)))
        except ValueError:
            raise SystemExit(
                f"--tenant-weight {spec!r}: WEIGHT must be a number"
            ) from None
    from ..utils import faults as _faults

    if args.faults:
        try:
            _faults.arm(args.faults)
        except ValueError as e:
            raise SystemExit(f"--faults: {e}") from e
        print(f"💥 fault injection armed: {args.faults}")
    elif _faults.arm_from_env() is not None:
        print(f"💥 fault injection armed from DLI_FAULTS")
    from ..utils import compile_cache

    # restarts (and --warmup) reuse compiled programs: JAX_COMPILATION_
    # CACHE_DIR where the operator set it, <checkout>/.xla_cache otherwise
    print(f"🗄  compile cache: {compile_cache.enable()}")

    if args.coordinator or args.num_processes is not None or args.process_id is not None:
        from ..parallel.mesh import multihost_initialize

        multihost_initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    import jax as _jax

    if _jax.process_count() > 1 and (args.continuous > 0 or args.queue > 0):
        # checked BEFORE the checkpoint load + warmup (the expensive
        # steps): batching by request ARRIVAL TIMING cannot mirror
        # deterministically across processes
        raise SystemExit(
            "--continuous/--queue batch by request ARRIVAL TIMING, "
            "which cannot mirror deterministically across processes; "
            "mirrored multi-process serving drives the bare engine. "
            "For admission layers on a multi-process fleet, use the "
            "MPMD stage runtime (serving/stage_runtime.py --frontend): "
            "its controller owns arrival timing and drives stages over "
            "the stage transport"
        )
    mesh_cfg = MeshConfig(
        dp=args.dp, pp=args.pp, sp=args.sp, tp=args.tp, ep=args.ep
    )
    model, params, dtype = args.model, None, args.dtype
    if args.checkpoint:
        model, params = _load_checkpoint(args, mesh_cfg)
        dtype = None  # the checkpoint's recorded dtype governs
    tokenizer = None
    tok_src = args.tokenizer or (
        args.checkpoint if args.checkpoint and _has_tokenizer_files(args.checkpoint)
        else None
    )
    if tok_src:
        from ..utils.tokenizer import load_tokenizer

        # strict: serving real weights through the byte fallback produces
        # garbled text with status "success" (round-2 review weak #6)
        tokenizer = load_tokenizer(tok_src, strict=True)
    elif args.checkpoint:
        print(
            "⚠️  --checkpoint without a tokenizer: responses will be "
            "byte-decoded. Pass --tokenizer PATH for real text."
        )
    engine = create_engine(
        model,
        mesh_cfg=mesh_cfg,
        engine_cfg=EngineConfig(
            request_deadline_s=args.deadline,
            prefix_cache_entries=args.prefix_cache,
            state_snapshots=args.state_snapshots,
            kv_shadow=not args.no_kv_shadow,
            kv_fabric=not args.no_kv_fabric,
            kv_fabric_timeout_s=args.kv_fabric_timeout,
            kv_disk_dir=args.kv_disk_dir,
            kv_disk_blocks=args.kv_disk_blocks,
            kv_fabric_stream=not args.no_kv_stream,
            kv_health_digests=args.kv_health_digests,
            replica_class=args.replica_class,
            spec_decode=args.spec_decode,
            spec_draft_len=args.spec_draft_len,
            denoise_steps=args.denoise_steps,
            spec_draft_model=args.spec_draft_model,
            pp_wire_quant=args.pp_wire_quant,
            adapter_slots=args.adapter_slots,
            adapter_rank=args.adapter_rank,
            tenant_weights=tuple(tenant_weights),
            tenant_max_queue_share=args.tenant_queue_share,
            trace_sample_rate=args.trace_sample_rate,
        ),
        microbatches=args.microbatches,
        params=params,
        dtype=dtype,
        quant=args.quant,
        kv_quant=args.kv_quant,
        attn_impl=args.attn_impl,
        tokenizer=tokenizer,
        seed=args.seed,
        sp_strategy=args.sp_strategy,
        draft_model=args.draft_model,
        lora=args.lora,
    )
    for name, path in adapter_specs:
        try:
            # fails startup loudly on a bad directory, rank overflow,
            # shape mismatch, or the --lora merge-at-load collision
            engine.adapters.register(name, path)
        except (ValueError, OSError) as e:
            raise SystemExit(f"--adapter {name}={path}: {e}") from e
    if adapter_specs:
        print(
            f"🎛  {len(adapter_specs)} adapter(s) registered: "
            f"{', '.join(n for n, _ in adapter_specs)}"
        )
    if args.die_on_wedge:

        def _wedge_reaper():
            import os as _os

            while True:
                time.sleep(max(1.0, min(args.die_on_wedge / 4, 10.0)))
                age = engine.max_wedged_age()
                if age is not None and age > args.die_on_wedge:
                    print(
                        f"💀 wedged device call stuck {age:.0f}s > "
                        f"--die-on-wedge {args.die_on_wedge:g}s; exiting "
                        f"for a supervisor restart"
                    )
                    _os._exit(17)

        threading.Thread(target=_wedge_reaper, daemon=True).start()

    def warm_bucket_ladder():
        print("⏳ warming up (compiling all bucket shapes)...")
        try:
            stats = engine.warmup()
        except ValueError as e:
            # backend bucket-validation errors (e.g. a prefill bucket not
            # divisible by sp on a context-parallel mesh) should name the
            # fix, not crash startup with a bare traceback
            raise SystemExit(
                f"--warmup failed: {e}\nfix the engine prefill_buckets / "
                f"mesh shape so every bucket is servable, or start without "
                f"--warmup"
            ) from e
        print(f"✅ warm: {stats['programs']} programs in {stats['seconds']}s")

    if args.warmup and args.continuous <= 0:
        warm_bucket_ladder()
    if _jax.process_count() > 1:
        # multi-process SPMD serving (the reference's N-machine shape,
        # Worker1.py:248-266): every process built the same engine above
        # (warmup included — identical program sequence; --continuous/
        # --queue were rejected before the model load); process 0 now
        # serves HTTP and broadcasts each request so followers mirror the
        # device program launches (serving/multihost.py).
        from .multihost import MirroredEngine, follower_loop

        if _jax.process_index() != 0:
            print(
                f"🛰  follower {_jax.process_index()}/{_jax.process_count()}"
                f" mirroring leader requests"
            )
            follower_loop(engine, _jax.process_index())
            return
        engine = MirroredEngine(engine)
    queue = None
    continuous = None
    if args.continuous > 0 and args.queue > 0:
        raise SystemExit(
            "--continuous and --queue are mutually exclusive: in-flight "
            "batching already provides bounded admission + batching"
        )
    if args.kv_pool_blocks is not None and args.continuous <= 0:
        raise SystemExit("--kv-pool-blocks requires --continuous")
    if args.continuous > 0:
        from ..engine.continuous import ContinuousEngine

        continuous = ContinuousEngine(
            engine, n_slots=args.continuous, chunk_steps=args.continuous_chunk,
            chunk_lag=args.continuous_lag, slot_max_seq=args.continuous_max_seq,
            kv_pool_blocks=args.kv_pool_blocks,
            kv_block_size=args.kv_block_size,
            restart_budget=args.restart_budget,
            poison_strikes=args.poison_strikes,
            restore_dir=args.restore_dir,
        )
        if args.warmup:
            if not continuous.paged:
                # a dense fleet ingests through the engine's own bucket
                # programs. A paged fleet runs ONE mixed
                # program whatever the prompt length: the ~100-program
                # solo/batched ladder (tens of seconds each to compile at
                # real widths on the chip) is not its serving path, and
                # solo-contract requests (seed / logprobs / beams) compile
                # theirs on first use
                warm_bucket_ladder()
            w = continuous.warmup()
            if not w["ok"]:
                raise SystemExit(
                    f"--warmup failed on the continuous engine: {w}\n"
                    f"fix the configuration or start without --warmup"
                )
            print(f"✅ continuous warm in {w['seconds']}s")
    elif args.queue > 0:
        from .queue import BatchingQueue

        queue = BatchingQueue(
            engine, max_queue=args.queue, max_batch=args.queue_max_batch,
            max_wait_ms=args.queue_wait_ms,
        )
    try:
        InferenceServer(
            engine, args.host, args.port, args.max_tokens_cap, queue=queue,
            continuous=continuous, drain_deadline_s=args.drain_deadline,
            wedge_unready_s=args.wedge_unready,
        ).serve_forever()
    finally:
        if hasattr(engine, "shutdown_followers"):
            # release the follower loops (blocked in the broadcast
            # collective) so a leader shutdown doesn't strand N-1 hung
            # processes until the distributed heartbeat reaps them
            engine.shutdown_followers()


if __name__ == "__main__":
    main()
