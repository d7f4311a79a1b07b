"""Per-request stage tracing: request ids + host-side span breakdowns.

Every request gets a `Trace` carrying a `request_id` (client-supplied via
the `X-Request-Id` header, or generated) and an ordered set of stage
spans — queue_wait, constraint_compile, admission, prefill, decode,
detokenize — recorded as HOST-side timestamps only. Nothing here crosses
into traced XLA code: a checkpoint is a `time.perf_counter()` read around
an already-host-blocking boundary (block_until_ready, a queue pop), so
the no-host-callback discipline of the compiled decode loops is untouched.

The span model is CONTIGUOUS: `checkpoint(name)` attributes the time
since the previous checkpoint (or trace creation) to `name`, so the spans
sum to ≈ the end-to-end latency by construction — the property that makes
a `timings` breakdown trustworthy for "where did this slow request spend
its time". Repeated checkpoints under one name accumulate (a chunked
decode records one growing `decode` span, not N).

The breakdown is returned in each response's `timings` field and logged
as one structured `request_done` event (utils/logging.py attaches the
request_id to every record logged inside `request_id_context`).

Fleet-wide tracing (ISSUE 17) grows this module from stage timer to span
tree: W3C-style `traceparent` ids (`SpanContext`, parse/format helpers)
propagate across every inter-process hop — client → router dispatch /
failover attempts → replica → KV-fabric pulls → prefill→decode handoff —
and each process records spans into its bounded in-memory store
(serving/trace_store.TraceStore). The `Trace` stage timer now also keeps
absolute-timestamped segments so a finished request's contiguous stage
breakdown can be exported as child spans of the replica's request span
with real wall-clock bounds. A `FlightRecorder` (bounded ring of
control-plane events) lives here too: engine-side code records
admissions, scheduler plans, preemptions, fabric fetches and restarts
into it; the supervisor dumps it into crash reports and
`GET /debug/flight` serves it live.

Everything here stays strictly host-side: nothing crosses into traced
XLA code, and the launch-level attribution the continuous engine records
under `engine_cfg.trace_sample_rate` is host timestamps keyed by launch
seq — never an extra device sync.

`PhaseClock` (ISSUE 24) is the same contiguous model one level down: the
continuous engine's worker thread marks the phase it enters, the time up
to the next mark goes to `dli_worker_phase_seconds_total{phase}`, and
each interval is one `jax.profiler.TraceAnnotation`, so a `/profiler`
trace shows what the host did beside the device's own events.

`LaunchTimer` (ISSUE 53) is the worker's reading of the device it feeds,
with no profiler: the fetch is the one place the worker blocks, the device
runs launches in dispatch order, so two consecutive fetches that both had to
wait bound one launch's device time; and `PhaseClock.device_empty` gives the
seconds in which no launch was unfetched to the phase the worker was in
(`dli_device_empty_seconds_total{phase}`).

The device's half of a launch (ISSUE 38): a trace's `XLA Ops` events carry
an instruction's name (`%fusion.12`) and nothing of the `jax.named_scope`
labels the step programs are written under (`STEP_SCOPES`). So while a
profiler session is open the engine's two launch seams keep the abstract
arguments of each step program's first dispatch (`abstract_call`), and when
the session ends `serving.server._Profiler` compiles those programs anew
(`fresh_hlo_text`), maps instruction -> scope (`scope_map`) and writes
`program_scopes.json` beside the profile (`write_program_scopes`).
"""

from __future__ import annotations

import collections
import json
import os
import re
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

_SAFE_ID = re.compile(r"^[A-Za-z0-9_\-\.:]{1,128}$")

# W3C traceparent: version "00", 32-hex trace id, 16-hex parent span id,
# 2-hex flags (bit 0 = sampled). The all-zero ids are invalid per spec.
_TRACEPARENT = re.compile(
    r"^00-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)


def new_request_id() -> str:
    return "req-" + uuid.uuid4().hex[:20]


def sanitize_request_id(raw) -> Optional[str]:
    """A client-supplied id, or None if absent/unusable. Constrained to a
    safe charset + length: the id is echoed into headers, logs, and
    metrics-adjacent output — it must never be an injection vector."""
    if not isinstance(raw, str):
        return None
    raw = raw.strip()
    return raw if _SAFE_ID.match(raw) else None


# -- W3C-style trace context -------------------------------------------------
def new_trace_id() -> str:
    return uuid.uuid4().hex  # 32 hex chars


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


class SpanContext:
    """One hop's trace context: the trace id, the CURRENT span id (the
    parent of anything started under this context), and the sampled flag.
    Immutable by convention; `child()` derives the next hop's context."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = bool(sampled)

    @classmethod
    def new_root(cls, sampled: bool = True) -> "SpanContext":
        return cls(new_trace_id(), new_span_id(), sampled)

    def child(self, span_id: Optional[str] = None) -> "SpanContext":
        return SpanContext(
            self.trace_id, span_id or new_span_id(), self.sampled
        )

    def header(self) -> str:
        """The `traceparent` header value for the NEXT hop (this
        context's span id is the downstream parent)."""
        return (
            f"00-{self.trace_id}-{self.span_id}-"
            f"{'01' if self.sampled else '00'}"
        )

    def __repr__(self):  # debug output only
        return f"SpanContext({self.header()})"


def parse_traceparent(raw) -> Optional[SpanContext]:
    """Parse an inbound `traceparent` header; None on absent/malformed
    (the hop then starts a fresh root — propagation degrades, never
    errors). Only version 00 is accepted; all-zero ids are invalid."""
    if not isinstance(raw, str):
        return None
    m = _TRACEPARENT.match(raw.strip().lower())
    if not m:
        return None
    trace_id, span_id, flags = m.groups()
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return SpanContext(trace_id, span_id, bool(int(flags, 16) & 1))


def sample_decision(trace_id: str, rate: float) -> bool:
    """Deterministic per-trace sampling for launch-level profiling
    (engine_cfg.trace_sample_rate): a pure function of the trace id — no
    RNG on the hot path, and every process agrees on the decision.
    rate <= 0 never samples; rate >= 1 always does."""
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    return int(trace_id[:8], 16) / float(0x100000000) < rate


_MAX_SEGMENTS = 256  # bounded per-request segment log (span-tree export)


class Trace:
    """Ordered, contiguous stage spans for one request."""

    __slots__ = ("request_id", "_t0", "_wall0", "_last", "_spans",
                 "_segments", "_lock")

    def __init__(self, request_id: Optional[str] = None):
        self.request_id = request_id or new_request_id()
        now = time.perf_counter()
        self._t0 = now
        # wall-clock anchor for absolute span export: abs(t) =
        # _wall0 + (t - _t0). One pair read at construction so the
        # perf_counter deltas (monotonic, the timing source of record)
        # map onto a wall timeline consistent across processes to within
        # clock skew.
        self._wall0 = time.time()
        self._last = now
        self._spans: "collections.OrderedDict[str, float]" = (
            collections.OrderedDict()
        )
        # absolute-timestamped (name, start, end) segments, bounded — the
        # span-tree export reads these; the contiguous accumulator above
        # stays the `timings` source so the two views cannot diverge on
        # totals
        self._segments: collections.deque = collections.deque(
            maxlen=_MAX_SEGMENTS
        )
        # a deadline-abandoned generation keeps checkpointing from its
        # daemon thread while the caller reads timings(): cheap lock
        self._lock = threading.Lock()

    def checkpoint(self, name: str) -> float:
        """Attribute time since the last checkpoint to span `name`."""
        now = time.perf_counter()
        with self._lock:
            dur = now - self._last
            self._segments.append((name, self._last, now))
            self._last = now
            self._spans[name] = self._spans.get(name, 0.0) + dur
        return dur

    def add(self, name: str, seconds: float):
        """Record an externally-measured span (e.g. a queue wait measured
        by the dispatcher on another thread)."""
        with self._lock:
            self._spans[name] = self._spans.get(name, 0.0) + float(seconds)

    def spans(self) -> dict:
        with self._lock:
            return dict(self._spans)

    def segments(self) -> list:
        """[(name, start_wall, end_wall)] — the absolute-timestamped
        stage segments, chronological. The span-tree export turns these
        into child spans of the process's request span."""
        with self._lock:
            off = self._wall0 - self._t0
            return [(n, a + off, b + off) for n, a, b in self._segments]

    @property
    def start_wall(self) -> float:
        return self._wall0

    def timings(self) -> dict:
        """`{"<span>_s": dur, ..., "total_s": wall}` in chronological span
        order. Spans sum to ≈ total_s (the unspanned tail is whatever ran
        after the last checkpoint — response assembly, envelope fill)."""
        now = time.perf_counter()
        with self._lock:
            out = {f"{k}_s": round(v, 6) for k, v in self._spans.items()}
            out["total_s"] = round(now - self._t0, 6)
        return out


WORKER_PHASES = (
    "wait_work", "reap", "admit", "plan", "dispatch", "fetch_wait",
    "distribute",
)
# the phases in which the thread blocks: one may outlast a profiler
# session, which keeps no interval still open at its end
WAIT_PHASES = ("wait_work", "fetch_wait")


# the scopes of a step program, as `jax.named_scope` labels where the work
# is written (models/, engine/paged.py, engine/generate.py): a block runs
# from its input norm to its output projection, and a residual add or a
# norm between two blocks belongs to the block it feeds. `mla_absorb` and
# `sparse_select` nest under `attn`, `linear_scan` under `linear_attn`,
# `ssm_scan` under `ssm_mix`, `delta_conv` and `delta_scan` under `delta_mix`.
# A device trace is read by these names
# (`scope_map`).
STEP_SCOPES = (
    "embed", "attn", "mla_absorb", "ffn", "moe_route", "moe_dispatch",
    "moe_experts", "moe_combine", "moe_shared", "conv_mix", "linear_attn",
    "linear_scan", "sparse_select", "ssm_mix", "ssm_scan", "head", "sample",
    "delta_mix", "delta_conv", "delta_scan",
)
PROGRAM_SCOPES_FILE = "program_scopes.json"

_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_HLO_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?(%?[\w.\-]+) = (?:\(.*?\)|\S+) ([\w\-]+)\("
)
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
# instructions that never run as an operation of their own
_HLO_NO_OP = frozenset(
    ("parameter", "get-tuple-element", "tuple", "bitcast", "constant")
)


def _labels(op_name: str) -> list:
    """`jit(f)/while/body/attn/mla_absorb/dot_general` -> [attn, mla_absorb]
    (a label nested in itself, a labelled function calling another, once)."""
    out = []
    for part in op_name.split("/"):
        if part in STEP_SCOPES and out[-1:] != [part]:
            out.append(part)
    return out


def scope_map(hlo_text: str) -> dict:
    """Optimized HLO text (`compiled.as_text()`; one module or several) ->
    {module name: {instruction name: {"scope": [...], "mixed": n}}}.

    An instruction's name is what a device trace prints (`%fusion.12`;
    names are unique within a module). `scope` holds the `STEP_SCOPES`
    labels of the instruction's own `op_name`, outermost first; a fusion
    whose own `op_name` holds none takes the labels most of its fused
    instructions hold. `mixed` (fusions only, else 0) is the number of
    DIFFERENT innermost labels among the fused instructions: 0 or 1 is a
    fusion of one scope's work, more says its time belongs to several.
    Instructions inside a fused computation are no operations of their own
    and are left out, as are parameters, tuples and constants."""
    out = {}
    for chunk in re.split(r"(?m)^(?=HloModule )", hlo_text):
        m = _HLO_MODULE.match(chunk)
        if m:
            out[m.group(1)] = _module_scopes(chunk)
    return out


def _module_scopes(text: str) -> dict:
    computations = {}  # name -> [(instruction, opcode, labels, fused or None)]
    body = None
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            body = computations.setdefault(head.group(1), [])
            continue
        inst = _HLO_INSTRUCTION.match(line) if body is not None else None
        if inst is None:
            continue
        name, opcode = inst.groups()
        op = _HLO_OP_NAME.search(line)
        fused = _HLO_FUSED.search(line) if opcode == "fusion" else None
        body.append((name, opcode, _labels(op.group(1)) if op else [],
                     fused.group(1) if fused else None))
    inside = {f for rows in computations.values() for _, _, _, f in rows if f}
    out = {}
    for comp, rows in computations.items():
        if comp in inside:
            continue
        for name, opcode, labels, fused in rows:
            if opcode in _HLO_NO_OP:
                continue
            mixed = 0
            if fused is not None:
                held = [tuple(r[2]) for r in computations.get(fused, ())
                        if r[2]]
                mixed = len({h[-1] for h in held})
                if not labels and held:
                    labels = list(collections.Counter(held).most_common(1)[0][0])
            out[name] = {"scope": labels, "mixed": mixed}
    return out


def abstract_call(args: tuple, kwargs: dict) -> tuple:
    """A dispatch's arguments with every array replaced by its shape and
    dtype (nothing of a donated buffer is kept alive); static arguments and
    None pass through. No sharding is kept: a one-device program lowers
    from these as its dispatch did."""
    import jax

    def abstract(a):
        if isinstance(a, jax.Array):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return a

    return jax.tree.map(abstract, (tuple(args), dict(kwargs)))


def fresh_hlo_text(lowered) -> str:
    """Compile a lowered program for its optimized HLO text WITH THIS
    TREE'S metadata. Two caches stand in the way of that. The persistent
    compile cache's key strips debug info (jax/_src/cache_key.py), so a
    plain `.compile()` may hand back an executable an OLDER tree compiled,
    under that tree's `op_name`s: the metadata goes into the key for this
    one compile (thread-local), whose entry is then this tree's own, and
    warm on the next session of the same tree. And a lowering of the same
    function and shapes is one object in the process, which keeps its first
    executable: a compiler option (set to its default, so the program is the
    same) makes JAX compile past that."""
    from jax._src import config as jax_config

    with jax_config.compilation_cache_include_metadata_in_key(True):
        return lowered.compile(
            compiler_options={"xla_embed_ir_in_executable": False}
        ).as_text()


def write_program_scopes(trace_dir: str, lowerings: dict) -> str:
    """{program: a callable that lowers it} -> `<trace_dir>/
    program_scopes.json`: {"vocabulary": [...], "programs": {module name:
    {instruction: {"scope", "mixed"}}}}. Returns the file's path.

    Each program is compiled anew with this tree's metadata
    (`fresh_hlo_text`); the instruction names of that compile are the ones a
    trace of the running program prints, because the compiler numbers its
    instructions from the program and its metadata, and both are this
    tree's. (Where the process runs an executable that ANOTHER tree compiled,
    a metadata-free cache hit, a few small instructions may be numbered
    otherwise: read on described-chip compiles of two trees, ISSUE 38. That
    takes a program with no Mosaic kernel, whose body carries the file and
    line of its call stack into the cache's key; the trace's reader counts
    the seconds whose name the map does not hold, and refuses a map that
    names under 99%.) The programs compile side by side, a thread each: the
    caller is a `/profiler/stop` that a client waits for."""
    def program(lower):
        return scope_map(fresh_hlo_text(lower()))

    programs = {}
    with ThreadPoolExecutor(max(1, len(lowerings))) as pool:
        for part in pool.map(program, lowerings.values()):
            programs.update(part)
    path = os.path.join(trace_dir, PROGRAM_SCOPES_FILE)
    with open(path, "w") as f:
        json.dump({"vocabulary": list(STEP_SCOPES), "programs": programs}, f)
    return path


class PhaseClock:
    """Contiguous phase timer of ONE thread (the continuous engine's
    worker), in the model of `Trace.checkpoint`: `mark(phase)` says which
    phase the thread enters now; the time until the next mark is added to
    that phase's child of `family` (a counter labeled `phase`), so the
    phases sum to the thread's wall time by construction. Each interval
    is also one `jax.profiler.TraceAnnotation` on the profiler's clock,
    named `phase.<phase>` or, where the caller gives one, `span` with
    `attrs` as the event's stats (the launch record rides the dispatch
    interval this way). The profiler keeps only intervals that began AND
    ended inside a session, so every interval carries `prev` (the phase
    it followed: the one open when the session began is put back from
    the first recorded) and a wait is preceded by an instant
    `begin.<name>` marker (the one open when the session ended runs from
    its marker to the end). Outside a profiler session an annotation is
    a constructor call and two no-op methods.

    `device_empty` is the owner's to set (the engine: True when a fetch
    returns and no launch is left unfetched, False when a dispatch's jitted
    call has returned): while it is set an interval that closes is added to
    `empty`'s child of its phase as well, so the seconds the device's queue
    stood empty split by what the thread was doing, `wait_work` (no
    request) against the rest (the chip waits for Python). A lower bound on
    the device's idle time: it may also stand still behind a launch whose
    result nobody has fetched yet (`LaunchTimer`'s `ready_early`)."""

    __slots__ = ("_children", "_empty", "device_empty", "_phase", "_last",
                 "_open", "_annotation")

    def __init__(self, family, empty, phases=WORKER_PHASES):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._children = {p: family.labels(phase=p) for p in phases}
        self._empty = {p: empty.labels(phase=p) for p in phases}
        self.device_empty = False
        self._phase: Optional[str] = None
        self._last = time.perf_counter()
        self._open = None

    def mark(self, phase: Optional[str], span: Optional[str] = None, /,
             **attrs) -> float:
        """Close the open interval, open `phase` (None: stop the clock).
        Returns the perf_counter reading that ends the one and begins the
        other."""
        now = time.perf_counter()
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        if self._phase is not None:
            took = now - self._last
            self._children[self._phase].inc(took)
            if self.device_empty:
                self._empty[self._phase].inc(took)
        prev, self._phase, self._last = self._phase, phase, now
        if phase is not None:
            name = span or f"phase.{phase}"
            if phase in WAIT_PHASES:
                with self._annotation(f"begin.{name}", **attrs):
                    pass
            self._open = self._annotation(name, prev=prev or "", **attrs)
            self._open.__enter__()
        return now


LAUNCH_PHASES = ("mixed", "chunk")
# how a fetched launch's device time came out: known, or why not
LAUNCH_TIMINGS = ("timed", "ready_early", "queue_empty")


class LaunchTimer:
    """A launch's device time as the thread that feeds the device can
    know it, with no profiler and no device sync of its own. The device
    runs launches in dispatch order and the worker blocks in one place,
    the fetch of a launch's result. If a fetch found its result NOT ready
    (`jax.Array.is_ready()` read before the blocking read), its return is
    when the launch ended, up to the fetch's own latency. So launch n is
    TIMED where its own fetch and the fetch before it both had to wait and
    n was dispatched before n - 1 ended: the device went from the one
    straight to the other, and `device_s` = return(n) - return(n - 1); the
    fetches' latencies telescope out of a sum over consecutive launches.
    What else was dispatched between the two launches (a key split, a
    restored prefix block's scatter) is in the later one's time. Otherwise
    the launch is counted and left out of the seconds: `queue_empty` (the
    fetch before it had returned when its dispatch ended, or there was
    none: nothing ran when it was enqueued, and its start is somewhere in
    its dispatch) or `ready_early` (the worker came late to this result or
    to the one before, so one end is unknown, and the device may have stood
    still behind it). State: the last fetch's return and whether it had
    waited; nothing grows with the run.

    Counted per launch kind (`LAUNCH_PHASES`), at the fetch: `seconds` +=
    device_s and `steps` += the steps the device RAN in timed launches
    (their quotient is a device step time over every timed launch since
    start-up), `timing` += 1 by outcome, `row_seconds` += device_s x the
    launch's decoding rows (the seconds decoding rows lived through, by the
    kind of launch they rode)."""

    __slots__ = ("_seconds", "_steps", "_timing", "_row_seconds",
                 "_last_return", "_last_waited")

    def __init__(self, seconds, steps, timing, row_seconds):
        self._seconds = {p: seconds.labels(phase=p) for p in LAUNCH_PHASES}
        self._steps = {p: steps.labels(phase=p) for p in LAUNCH_PHASES}
        self._row_seconds = {
            p: row_seconds.labels(phase=p) for p in LAUNCH_PHASES
        }
        self._timing = {
            (p, s): timing.labels(phase=p, state=s)
            for p in LAUNCH_PHASES for s in LAUNCH_TIMINGS
        }
        self.reset()

    def reset(self):
        """The loop starts (anew): nothing is in flight that will be
        fetched, the next launch meets an empty queue."""
        self._last_return: Optional[float] = None
        self._last_waited = False

    def returned(self, phase: str, t_dispatched: float, ready: bool,
                 t: float, steps_run: int, decode_rows: int) -> tuple:
        """The fetch of a launch of kind `phase` returned at `t`; `ready`:
        its result was there when the worker arrived. `t_dispatched`: the
        end of its dispatch (all three are `time.perf_counter()` readings).
        Returns (outcome, device_s); device_s is 0.0 unless timed."""
        last, waited = self._last_return, self._last_waited
        self._last_return, self._last_waited = t, not ready
        if last is None or last <= t_dispatched:
            state, device_s = "queue_empty", 0.0
        elif ready or not waited:
            state, device_s = "ready_early", 0.0
        else:
            state, device_s = "timed", t - last
            self._seconds[phase].inc(device_s)
            self._steps[phase].inc(steps_run)
            if decode_rows:
                self._row_seconds[phase].inc(device_s * decode_rows)
        self._timing[phase, state].inc()
        return state, device_s


class FlightRecorder:
    """Bounded ring of recent control-plane events for one engine.

    The crash-forensics companion of the span store: admissions,
    scheduler plans (budget splits), preemptions, fabric fetches,
    quarantines and restarts append here as cheap host-side dicts; the
    ring is dumped into the supervisor's crash report, served live at
    `GET /debug/flight`, and persisted next to `--restore-dir` on a
    crash — so a poison-quarantine or restart-loop episode is
    reconstructable after the fact. Strictly host-side control-plane
    code; never called from anywhere decode-launch-adjacent except
    behind the existing per-event seams (admission, plan, preempt,
    fetch, restart), all of which already do host work."""

    __slots__ = ("_events", "_lock", "_seq", "capacity")

    def __init__(self, capacity: int = 512):
        self.capacity = int(capacity)
        self._events: collections.deque = collections.deque(
            maxlen=self.capacity
        )
        self._lock = threading.Lock()
        self._seq = 0

    def record(self, kind: str, **fields):
        """Append one event. `fields` must already be JSON-safe scalars
        (the dump is json.dumps'd into crash reports verbatim)."""
        with self._lock:
            self._seq += 1
            ev = {"seq": self._seq, "ts": round(time.time(), 6),
                  "kind": kind}
            if fields:
                ev.update(fields)
            self._events.append(ev)

    def events(self, limit: Optional[int] = None) -> list:
        with self._lock:
            out = list(self._events)
        return out[-limit:] if limit else out

    def dump(self) -> dict:
        """The /debug/flight + crash-report payload."""
        events = self.events()
        return {
            "capacity": self.capacity,
            "recorded_total": self._seq,
            "events": events,
        }
