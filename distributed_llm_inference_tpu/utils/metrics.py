"""Dependency-free metrics registry with a Prometheus text renderer.

The serving stack's measurement substrate (ISSUE 2): Counter / Gauge /
Histogram families, labeled (`engine` / `route` / `model` / ...), all
thread-safe, rendered two ways from ONE store:

  * `render()` — Prometheus text exposition (served at `GET /metrics`);
  * `snapshot()` — the JSON view (`/stats` sections).

Both views read the same family objects, so they cannot diverge: every
number in `/stats` that has a Prometheus counterpart is computed from the
same Counter/Gauge/Histogram the exposition renders.

Design notes:
  * No prometheus_client dependency — the container must not grow deps;
    the text format is three line shapes (`# HELP`, `# TYPE`, samples).
  * Histograms use FIXED log-spaced latency buckets (DEFAULT_TIME_BUCKETS)
    so TTFT on a TPU (~ms) and on the CPU fallback (~s) land in resolvable
    buckets from one layout, and bucket layouts never vary per process.
    Each histogram child also keeps a bounded window of raw observations
    (same width as the engine's rolling sample deque) so the JSON view can
    report EXACT p50/p90/p99 over recent traffic while Prometheus gets the
    standard cumulative buckets.
  * Label cardinality is capped per family (default MAX_SERIES): past the
    cap, new label sets collapse into one `"_other_"` series instead of
    growing without bound — an attacker-controlled label (route, model)
    must never be a memory-growth primitive.
  * Registration is get-or-create and idempotent; re-registering a name
    with a different type/labelnames raises (silent reuse would interleave
    two meanings under one exposition family).
"""

from __future__ import annotations

import collections
import math
import threading
import time
from typing import Optional, Sequence

# Log-spaced latency buckets (seconds): sub-ms TPU decode steps through
# multi-minute CPU-fallback requests land in distinct buckets.
DEFAULT_TIME_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)
# Small-integer-count buckets (batch sizes, fleet occupancy).
DEFAULT_SIZE_BUCKETS = (1, 2, 4, 8, 16, 32, 64)
# dli_launch_steps_ahead: scheduler steps, linear (0 for an idle chip,
# 16 a decode chunk; the latency buckets would put them all in one)
STEPS_AHEAD_BUCKETS = tuple(range(0, 65, 4))
# dli_admission_wait_seconds is registered by three components; on the
# continuous engine it has always been the whole wait for a first token
# on the server, not a queue wait
ADMISSION_WAIT_HELP = (
    "enqueue until dispatch (queue=\"batching\"); on the continuous "
    "engine enqueue until the request's first token was fetched, prefill "
    "included: dli_queue_wait_seconds + dli_prefill_seconds"
)
# the continuous engine's slot turnover (registered by the engine for a
# stable scrape schema and by the fleet that counts them)
SLOT_RELEASE_HELP = (
    "slots vacated, by what said so: model = the host position model saw "
    "the row's budget end in a launch already dispatched (the slot is let "
    "again before that launch is fetched), fetch = every other release "
    "(a fetched launch showed the row ended or it was killed; a reaped "
    "prefill; a preemption)"
)
# a decode chunk ends when its last live row does (engine/paged.
# steps_while_active): how often that engages
CHUNK_STEPS_HELP = (
    "steps of dispatched decode chunks by what the device did with them: "
    "run = forwards that ran, cut = those the chunk's exit saved (no row "
    "of the fleet was active any more); run + cut = chunks x chunk_steps"
)
# the paged kernels' loop steps (engine/continuous._kv_walk_steps)
ATTN_WALK_STEPS_HELP = (
    "loop steps of the paged kernels' walk per layer (host position "
    "model): a step folds up to P pages, P from the shapes "
    "(ops/paged_attention._walk_shape), so walked KV positions / block "
    "size / this = pages a loop step"
)
# the continuous engine's step-time series: the old histogram, which is a
# host time, and the worker's own reading of the device it feeds
# (utils/tracing.LaunchTimer, PhaseClock.device_empty); registered by the
# engine for a stable scrape schema and by the fleet that counts them
DECODE_STEP_HELP = (
    "launch to fetch over the steps the launch ran, per fetch: the wait "
    "behind the launches dispatched ahead of it is inside (under lag 2, two "
    "of them), so NOT a device step time: that is "
    "dli_launch_device_seconds_total / dli_launch_device_steps_total"
)
LAUNCH_DEVICE_SECONDS_HELP = (
    "device seconds of TIMED launches by kind (mixed step / decode chunk): "
    "the time between two consecutive fetches that both found their result "
    "not ready, the later launch dispatched before the earlier ended"
)
LAUNCH_DEVICE_STEPS_HELP = (
    "steps the device ran in timed launches (a mixed launch 1, a chunk its "
    "steps_run): dli_launch_device_seconds_total over this is the device's "
    "step time by kind, with no profiler"
)
LAUNCH_TIMING_HELP = (
    "fetched launches by whether their device time is known: timed, "
    "ready_early = the worker came late to this result or the one before "
    "(the device may have stood still behind it), queue_empty = nothing "
    "was running when it was enqueued"
)
DECODE_ROW_SECONDS_HELP = (
    "device seconds of timed launches x their decoding rows, by kind: the "
    "seconds decoding rows lived through in mixed steps (another request's "
    "prefill beside them) against pure-decode chunks"
)
DEVICE_EMPTY_HELP = (
    "worker-thread seconds during which no launch was dispatched and "
    "unfetched (the device's queue stood empty), by the worker's phase: "
    "wait_work = no request to serve, every other phase = the chip waits "
    "for Python; a lower bound on the device's idle time"
)
SLOT_TURNOVER_HELP = (
    "scheduler steps dispatched between a row's last live step by the "
    "host position model and the first prefill chunk of the slot's next "
    "tenant, for rows that ended while requests were queued"
)

# fleets of a model with recurrent layers (ModelConfig.conv_layers)
PREFIX_STATE_TOKENS_HELP = (
    "prompt tokens of prefix hits whose recurrent state at the hit's depth "
    "came from the last shared block's state tail (every hit of such a "
    "fleet: the tail's prefill computes on the state a cold prefill would "
    "have reached)"
)
CONV_STATE_RESETS_HELP = (
    "slots let to a tenant with zeroed recurrent state (a cold start: no "
    "prefix hit), whatever the previous tenant left in the slot"
)
CONV_TAIL_WRITES_HELP = (
    "pool blocks whose recurrent-state tail a launch wrote (the launch "
    "that fills the block's last position, prefill or decode), by the host "
    "position model"
)

# fleets of a model of sparse and linear attention layers
# (ModelConfig.linear_layers, models/minicpm_sala.py)
LINEAR_STATE_RESETS_HELP = (
    "slots let to a tenant with a zeroed linear-attention state (a cold "
    "start: no prefix hit restored a snapshot), whatever the previous "
    "tenant left in the slot"
)
LINEAR_STATE_ROWS_HELP = (
    "slot-steps of the matrix-state layers' state leaf by state: "
    "touched = a row that carried a token, whose state the scan read and "
    "wrote (the launch records' state_rows), held = slots x the launch's "
    "steps; touched / held is the share of the leaf a launch has to move"
)
# fleets of a model of state-space layers (models/granite_hybrid.py: a
# convolution state AND a matrix state a slot)
SSM_STATE_RESETS_HELP = (
    "slots let to a tenant with zeroed state-space states (a cold start: "
    "no prefix hit restored a snapshot), whatever the previous tenant left "
    "in the slot"
)
# fleets of a model with delta-rule layers (ModelConfig.delta_layers,
# models/solar_open2.py)
DELTA_STATE_ROWS_HELP = (
    "row-steps whose float32 matrix state the delta rule's program read and "
    "wrote (the launch records' state_rows), by launch phase, a layer"
)
DELTA_CHUNKS_HELP = (
    "chunks of the flat token axis those row-steps' tokens were cut into "
    "(ops/delta_rule.CHUNK places each; the launch records' delta_chunks), "
    "by launch phase, a layer: chunks / rows is how many chunks a state's "
    "trip through the program serves (1 in decode: a decode chunk's row-step "
    "counts one, though its one-token form cuts no chunk)"
)
SPARSE_SCORED_KEYS_HELP = (
    "compressed keys the sparse layers' selection scored, a layer and KV "
    "head: each row of a launch's once a step, up to the row's length (the "
    "launch records' ck_scored)"
)
SPARSE_ROWS_HELP = (
    "row-steps of the sparse attention layers by branch: dense = fewer "
    "positions visible than the dense length (plain causal attention), "
    "sparse = the selected read"
)

# a pool grouped by layer kind, and one chip's share of the experts
KV_GROUP_BLOCKS_HELP = (
    "blocks of each group of the paged pool by state: live = held by a "
    "row's table, cached = held by the prefix index alone, free; a pool "
    "of one group has the global group only"
)
KV_WINDOW_RELEASED_HELP = (
    "window-group blocks rows gave back while they went on (every "
    "position of the block below the row's last query's window), by the "
    "host position model"
)
KV_WINDOW_GIVEN_HELP = (
    "window-group blocks rows were given while they went on (a block for "
    "every logical block a launch's queries write), by the host position "
    "model; a row that ends gives the rest back uncounted"
)
KV_GROUP_BLOCK_BYTES_HELP = (
    "bytes of one pool block of a group, all its layers' K and V rows as "
    "the pool's leaves hold them: a block of either group holds the same "
    "positions and not the same bytes"
)
MOE_PAIRS_HELP = (
    "live token-expert pairs the routers chose: held = those whose expert "
    "lives on this chip (computed here), routed = all of them; equal where "
    "every expert is held"
)

# block-diffusion fleets (ModelConfig.diffusion_block > 0)
DIFFUSION_FORWARDS_HELP = (
    "row-forwards of a block-diffusion fleet by the host position model, "
    "by kind: denoise = the forward reveals masked positions of the row's "
    "open block (the one that reveals the last emits the block), commit = "
    "the forward only writes a clean block's K/V (none: a clean block's "
    "commit rides the next block's first denoise forward, counted once, "
    "as denoise)"
)
DIFFUSION_FUSED_HELP = (
    "denoise row-forwards of a block-diffusion fleet that carried the "
    "clean block below the open one and wrote its K/V (the fused commit), "
    "by the host position model"
)
DIFFUSION_TOKENS_HELP = (
    "tokens a block-diffusion fleet delivered (counted at the fetch of "
    "the forward that revealed the last mask of their block)"
)

MAX_SERIES = 64  # label-set cap per family
WINDOW = 256  # raw-observation window per histogram child (matches
# the engine's rolling sample deque, so JSON percentiles line up)

_OTHER = "_other_"  # collapsed label value once a family hits MAX_SERIES


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile, the SAME formula engine.stats() has always
    used — one copy so the JSON and registry views can never disagree."""
    if not values:
        return None
    vals = sorted(values)
    idx = min(len(vals) - 1, int(round(q * (len(vals) - 1))))
    return round(vals[idx], 4)


def _escape_label(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _labels_str(labelnames: tuple, labelvalues: tuple, extra: str = "") -> str:
    parts = [
        f'{n}="{_escape_label(v)}"' for n, v in zip(labelnames, labelvalues)
    ]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _Child:
    """One labeled series. All mutation under the family lock."""

    __slots__ = ("_family",)

    def __init__(self, family: "_Family"):
        self._family = family


class CounterChild(_Child):
    __slots__ = ("_value",)

    def __init__(self, family):
        super().__init__(family)
        self._value = 0.0

    def inc(self, n: float = 1.0):
        if n < 0:
            raise ValueError("counters only go up")
        with self._family._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._family._lock:
            return self._value


class GaugeChild(_Child):
    __slots__ = ("_value",)

    def __init__(self, family):
        super().__init__(family)
        self._value = 0.0

    def set(self, v: float):
        with self._family._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0):
        with self._family._lock:
            self._value += n

    def dec(self, n: float = 1.0):
        self.inc(-n)

    @property
    def value(self) -> float:
        with self._family._lock:
            return self._value


class HistogramChild(_Child):
    __slots__ = ("_bucket_counts", "_sum", "_count", "_window",
                 "_exemplars")

    def __init__(self, family):
        super().__init__(family)
        self._bucket_counts = [0] * (len(family.buckets) + 1)  # +Inf last
        self._sum = 0.0
        self._count = 0
        self._window = collections.deque(maxlen=WINDOW)
        # bucket index -> (trace_id, value, ts): the most recent traced
        # observation per bucket, so a p99 bucket links to one concrete
        # inspectable trace (GET /debug/traces/{trace_id}). Bounded by
        # construction (<= len(buckets)+1 entries); exposed in the JSON
        # snapshot, not the text exposition (the 0.0.4 format has no
        # exemplar syntax).
        self._exemplars: dict = {}

    def observe(self, v: float, trace_id: Optional[str] = None):
        v = float(v)
        with self._family._lock:
            i = 0
            buckets = self._family.buckets
            while i < len(buckets) and v > buckets[i]:
                i += 1
            self._bucket_counts[i] += 1
            self._sum += v
            self._count += 1
            self._window.append(v)
            if trace_id is not None:
                self._exemplars[i] = (trace_id, v, time.time())

    def exemplars(self) -> dict:
        """{bucket_le: {trace_id, value, ts}} for buckets that have seen
        a traced observation."""
        with self._family._lock:
            items = dict(self._exemplars)
        les = tuple(self._family.buckets) + (math.inf,)
        return {
            _fmt(les[i]): {
                "trace_id": t, "value": round(v, 6), "ts": round(ts, 3),
            }
            for i, (t, v, ts) in sorted(items.items())
        }

    @property
    def count(self) -> int:
        with self._family._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._family._lock:
            return self._sum

    def window_values(self) -> list:
        with self._family._lock:
            return list(self._window)

    def percentile(self, q: float) -> Optional[float]:
        """Exact nearest-rank percentile over the recent-observation
        window — the number /stats reports for this series."""
        return percentile(self.window_values(), q)


_CHILD_TYPES = {
    "counter": CounterChild,
    "gauge": GaugeChild,
    "histogram": HistogramChild,
}


class _Family:
    """One metric family: a name, a type, and its labeled children."""

    def __init__(self, name: str, mtype: str, help_: str,
                 labelnames: tuple, buckets: Optional[tuple],
                 max_series: int):
        self.name = name
        self.type = mtype
        self.help = help_
        self.labelnames = labelnames
        self.buckets = tuple(float(b) for b in (buckets or ()))
        self.max_series = max_series
        self._lock = threading.Lock()
        self._children: "collections.OrderedDict[tuple, _Child]" = (
            collections.OrderedDict()
        )

    def labels(self, **labelvalues):
        got = tuple(sorted(labelvalues))
        if got != tuple(sorted(self.labelnames)):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, got {got}"
            )
        key = tuple(str(labelvalues[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                if len(self._children) >= self.max_series:
                    # cardinality cap: collapse into one overflow series
                    key = (_OTHER,) * len(self.labelnames)
                    child = self._children.get(key)
                    if child is None:
                        child = _CHILD_TYPES[self.type](self)
                        self._children[key] = child
                else:
                    child = _CHILD_TYPES[self.type](self)
                    self._children[key] = child
            return child

    def _items(self):
        with self._lock:
            return list(self._children.items())

    # -- rendering -----------------------------------------------------------
    def render_lines(self) -> list:
        out = []
        if self.help:
            out.append(f"# HELP {self.name} {self.help}")
        out.append(f"# TYPE {self.name} {self.type}")
        for key, child in self._items():
            if self.type in ("counter", "gauge"):
                out.append(
                    f"{self.name}{_labels_str(self.labelnames, key)} "
                    f"{_fmt(child.value)}"
                )
                continue
            with self._lock:
                counts = list(child._bucket_counts)
                total, s = child._count, child._sum
            cum = 0
            for b, c in zip(self.buckets + (math.inf,), counts):
                cum += c
                le = f'le="{_fmt(b)}"'
                out.append(
                    f"{self.name}_bucket"
                    f"{_labels_str(self.labelnames, key, le)} {cum}"
                )
            out.append(
                f"{self.name}_sum{_labels_str(self.labelnames, key)} "
                f"{_fmt(s)}"
            )
            out.append(
                f"{self.name}_count{_labels_str(self.labelnames, key)} "
                f"{total}"
            )
        return out

    def snapshot(self) -> dict:
        series = []
        for key, child in self._items():
            entry = {"labels": dict(zip(self.labelnames, key))}
            if self.type in ("counter", "gauge"):
                entry["value"] = child.value
            else:
                entry["count"] = child.count
                entry["sum"] = round(child.sum, 6)
                entry["p50"] = child.percentile(0.5)
                entry["p90"] = child.percentile(0.9)
                entry["p99"] = child.percentile(0.99)
                ex = child.exemplars()
                if ex:
                    entry["exemplars"] = ex
            series.append(entry)
        return {"type": self.type, "help": self.help, "series": series}


class MetricsRegistry:
    """Get-or-create registry of metric families.

    Each serving process typically owns ONE registry reachable from the
    engine (`engine.metrics`); the queue / continuous engine / prefix
    cache / constraint table all register into it so `GET /metrics`
    covers the whole stack in one scrape.
    """

    def __init__(self, max_series: int = MAX_SERIES):
        self._lock = threading.Lock()
        self._families: "collections.OrderedDict[str, _Family]" = (
            collections.OrderedDict()
        )
        self.max_series = max_series

    def _register(self, name: str, mtype: str, help_: str,
                  labelnames: Sequence[str], buckets=None) -> _Family:
        labelnames = tuple(labelnames)
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.type != mtype or fam.labelnames != labelnames:
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{fam.type}{fam.labelnames}, not "
                        f"{mtype}{labelnames}"
                    )
                return fam
            fam = _Family(
                name, mtype, help_, labelnames, buckets, self.max_series
            )
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> _Family:
        return self._register(name, "counter", help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> _Family:
        return self._register(name, "gauge", help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_TIME_BUCKETS) -> _Family:
        return self._register(name, "histogram", help, labelnames, buckets)

    def get(self, name: str) -> Optional[_Family]:
        with self._lock:
            return self._families.get(name)

    def families(self) -> list:
        with self._lock:
            return list(self._families.values())

    def render(self) -> str:
        """Prometheus text exposition (format version 0.0.4)."""
        lines = []
        for fam in self.families():
            lines.extend(fam.render_lines())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """The JSON view over the same families the exposition renders."""
        return {f.name: f.snapshot() for f in self.families()}


# Process-global default for callers with no engine in reach (none of the
# serving stack uses it — each engine owns its registry — but library
# users get a working default).
REGISTRY = MetricsRegistry()
