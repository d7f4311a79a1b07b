"""InferenceEngine: the request-level decode engine (reference L3).

Replaces `Orchestrator.generate_with_sampling`
(/root/reference/orchestration.py:69-228): tokenize → chat-template →
prefill (TTFT) → decode loop → detokenize → perf stats, with the same
response schema (`prompt`, `response`, `status`, `time_taken`,
`tokens_generated`, `tokens_per_sec` — orchestration.py:211-218) plus
first-class `ttft_s` (BASELINE.json's p50-TTFT metric is a measurement, not
a print).

Single-owner by construction: one lock serializes generations — the
reference's shared-global Flask state would interleave worker calls across
concurrent requests with no locking (SURVEY.md §5 race note).

The compute backend is pluggable: `SingleDeviceBackend` (this file) runs
the whole model on one chip; `parallel.pipeline.PipelineBackend` runs
N stages over a mesh with the same (prefill, decode) interface.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import EngineConfig, ModelConfig
from ..models import api as M
from ..utils import faults
from ..utils.logging import get_logger, request_id_context
from ..utils.metrics import (
    ADMISSION_WAIT_HELP, ATTN_WALK_STEPS_HELP, CHUNK_STEPS_HELP,
    DECODE_ROW_SECONDS_HELP, DECODE_STEP_HELP, DEFAULT_SIZE_BUCKETS,
    DEVICE_EMPTY_HELP,
    DIFFUSION_FORWARDS_HELP, DIFFUSION_FUSED_HELP, DIFFUSION_TOKENS_HELP,
    LAUNCH_DEVICE_SECONDS_HELP, LAUNCH_DEVICE_STEPS_HELP, LAUNCH_TIMING_HELP,
    SLOT_RELEASE_HELP, SLOT_TURNOVER_HELP,
    STEPS_AHEAD_BUCKETS, MetricsRegistry,
)
from ..utils.probe import device_summary
from ..utils.tokenizer import load_tokenizer
from ..utils.tracing import FlightRecorder, Trace, abstract_call
from ..serving.trace_store import TraceStore
from . import generate as G
from .prefix import PrefixCache

log = get_logger("engine")

DECODE_BUCKETS = (16, 32, 64, 128, 256, 512, 1024)
# generate_batch pads the row count up to one of these (compile-once per
# batch bucket, like the prompt/decode buckets)
BATCH_BUCKETS = (1, 2, 4, 8, 16)


def batch_buckets_for(granularity: int) -> tuple:
    """Batch-bucket ladder for a backend's row-count quantum.

    gran 1 -> BATCH_BUCKETS; gran g > 1 -> (g, 2g, 4g, ...) up past
    BATCH_BUCKETS[-1], so every batch size the API admits maps to a
    bucket that warmup() compiled — the request path and warmup MUST
    share this ladder or --warmup's 'no request pays jit latency'
    contract breaks for granularities that divide no power of two."""
    if granularity <= 1:
        return BATCH_BUCKETS
    out = [granularity]
    while out[-1] < BATCH_BUCKETS[-1]:
        out.append(out[-1] * 2)
    return tuple(out)
# prompt-lookup speculation: drafted tokens verified per forward (the KV
# headroom _clamp_decode reserves past the last emitted token)
SPEC_DRAFT_LEN = 4


class SingleDeviceBackend:
    """Whole model on one device: prefill + while-loop decode, both jitted."""

    name = "single-device"
    n_stages = 1
    # Ragged (left-padded, per-row valid_start) batches; PipelineBackend
    # threads valid_start too, so pp meshes serve the same request surface.
    supports_ragged = True

    def __init__(self, cfg: ModelConfig, params):
        self.cfg = cfg
        self.params = params

    def init_cache(self, batch: int, max_seq: int):
        return M.init_kv_cache(self.cfg, batch, max_seq=max_seq)

    def prefill(self, tokens, prompt_len, cache, key, sampling,
                valid_start=None, presence=None, bias=None):
        # pos always passed as a traced array so ordinary prefill, warmup,
        # and the chunked final chunk all share one compiled program per
        # bucket shape. presence [B, V] (repetition-penalty token set) and
        # bias [V] (OpenAI logit_bias) are None on the default path —
        # such requests trace their own program variant, the
        # reference-parity path stays untouched.
        return G.prefill(
            self.cfg, self.params, tokens, prompt_len, cache, key, sampling,
            valid_start, jnp.int32(0), presence, bias,
        )

    # chunked prefill (prompts longer than the largest bucket); the engine
    # uses these on any backend that exposes them (this one and the SPMD
    # PipelineBackend) and falls back to the bucket-limit error elsewhere
    def extend(self, tokens, pos, cache):
        return G.extend(self.cfg, self.params, tokens, pos, cache)

    def prefill_at(self, tokens, pos, valid_len, cache, key, sampling,
                   presence=None, bias=None):
        return G.prefill(
            self.cfg, self.params, tokens, valid_len, cache, key, sampling,
            None, pos, presence, bias,
        )

    def decode(self, first_token, cache, start_pos, limit, key, sampling,
               valid_start=None, presence=None, counts=None, bias=None,
               constraint=None, *, max_steps, with_logprobs=False):
        return G.decode(
            self.cfg, self.params, first_token, cache, start_pos, limit, key,
            sampling, valid_start, presence, counts, bias, constraint,
            max_steps=max_steps, with_logprobs=with_logprobs,
        )

    # OpenAI logit_bias ([V] added to raw logits each sample)
    supports_bias = True
    # grammar-constrained decoding (constrain/): FSM state + mask tables
    # threaded through decode; first token rides the bias operand
    supports_constrain = True
    # teacher-forced scoring (OpenAI echo+logprobs / lm-eval loglikelihood)
    supports_score = True

    def score_chunk(self, tokens, pos, cache, *, top_n=0):
        return G.score_chunk(
            self.cfg, self.params, tokens, pos, cache, top_n=top_n
        )
    # deterministic beam search (HF generate(num_beams=N) semantics);
    # the KV cache reorders by parent beam with a batched gather
    supports_beam = True

    def decode_beam(self, logits0, cache, start_pos, limit, length_penalty,
                    *, max_steps, num_beams, early_stopping):
        return G.decode_beam(
            self.cfg, self.params, logits0, cache, start_pos, limit,
            length_penalty, max_steps=max_steps, num_beams=num_beams,
            early_stopping=early_stopping,
        )

    # greedy prompt-lookup speculative decode (engine opts in per request)
    supports_speculative = True
    # HF-parity repetition penalty (presence-tracked decode variants)
    supports_presence = True
    # OpenAI frequency/presence penalties (generated-count state)
    supports_counts = True
    # per-token logprobs (decode program variant with a logprob buffer)
    supports_logprobs = True
    # slot decode for continuous batching (engine/continuous.py);
    # PipelineBackend provides a shard_map equivalent
    supports_slots = True

    def decode_slots(self, state, cache, key, sparams, *, num_steps):
        return G.decode_slots(
            self.cfg, self.params, state, cache, key, sparams,
            num_steps=num_steps,
        )

    # constrained slot decode (continuous fleets with grammar-constrained
    # tenants; the fleet tables come from constrain/fleet.py)
    supports_constrained_slots = True

    def decode_slots_constrained(self, state, cache, key, sparams, fsm,
                                 cmask, ctrans, *, num_steps):
        return G.decode_slots_constrained(
            self.cfg, self.params, state, cache, key, sparams, fsm, cmask,
            ctrans, num_steps=num_steps,
        )

    # block-paged KV for the continuous fleet (engine/paged.py): pool +
    # block tables instead of n_slots x max_seq dense rows. Both families
    # — the attn_hook seam the pool writes ride is shared (gpt2's block
    # routes through llama.default_attn_hook since round 5).
    @property
    def supports_paged(self):
        return self.cfg.arch in M.FAMILIES  # every family's hook is paged

    def init_paged_pool(self, n_blocks, block_size, n_slots=None,
                        **snapshots):
        # n_slots: a model with recurrent layers keeps a state a slot
        # beside the blocks (engine/paged.init_pool); n_snapshots: and a
        # pool of states the prefix index restores
        from . import paged as P

        return P.init_pool(self.cfg, n_blocks, block_size, n_slots=n_slots,
                           **snapshots)

    def decode_slots_paged(self, state, pool, table, key, sparams, *,
                           num_steps, pages=None, **diffusion):
        from . import paged as P

        return P.decode_slots_paged(
            self.cfg, self.params, state, pool, table, key, sparams,
            num_steps=num_steps, pages=pages, **diffusion,
        )

    # warm-recovery shadow seam (engine/shadow.py): single-device only
    # for now — the pp backend's layer-sharded pool would need shard_map
    # twins for the gather/scatter, so pp fleets recover cold (the
    # continuous engine gates on these attributes)
    def gather_shadow_blocks(self, pool, block_ids):
        from . import paged as P

        return P.gather_shadow_blocks(pool, block_ids)

    def restore_shadow_blocks(self, pool, blocks, block_ids):
        from . import paged as P

        return P.restore_shadow_blocks(pool, blocks, block_ids)

    # ragged ingest (engine/paged.py): admission prefills straight into
    # the pool through the ragged kernel/gather — no scratch, no insert
    # scatter, no bucket ladder. PipelineBackend provides shard_map twins.
    def extend_ragged_paged(self, tokens, tok_row, tok_pos, meta, pool,
                            table, pages=None):
        from . import paged as P

        return P.extend_ragged_paged(
            self.cfg, self.params, tokens, tok_row, tok_pos, meta, pool,
            table, pages=pages,
        )

    def prefill_ragged_paged(self, tokens, tok_row, tok_pos, meta, pool,
                             table, sample_at, key, sampling, presence=None,
                             bias=None, pages=None):
        from . import paged as P

        return P.prefill_ragged_paged(
            self.cfg, self.params, tokens, tok_row, tok_pos, meta, pool,
            table, sample_at, key, sampling, presence=presence, bias=bias,
            pages=pages,
        )

    def arm_slot_paged(self, state, sparams, slot, *arm):
        from . import paged as P

        return P.arm_slot_only(self.cfg, state, sparams, slot, *arm)

    # mixed scheduler launch (engine/scheduler.py): every active decode
    # row plus budget-sliced prefill chunks in ONE ragged program —
    # decode tokens/positions gathered from slot state on device,
    # completing admissions sample + arm in the same pass.
    def mixed_step_ragged(self, tokens, tok_row, tok_pos, dec_flag, meta,
                          pool, table, state, sparams, key, dec_idx, arm,
                          spec=None, spec_toks=None, dev=None, pages=None,
                          snaps=None, **diffusion):
        from . import paged as P

        return P.mixed_step_ragged(
            self.cfg, self.params, tokens, tok_row, tok_pos, dec_flag,
            meta, pool, table, state, sparams, key, dec_idx, arm,
            spec=spec, spec_toks=spec_toks, dev=dev, pages=pages,
            snaps=snaps, **diffusion,
        )

    def lower_step(self, name: str, args: tuple, kwargs: dict):
        """Lower the step program `name` (engine/paged: decode_slots_paged,
        mixed_step_ragged) again from a dispatch's abstract arguments
        (utils/tracing.abstract_call), over this backend's weights: what a
        profiler session's end compiles for the program's instruction ->
        scope map. Nothing runs and no buffer is touched."""
        from . import paged as P

        (params,), _ = abstract_call((self.params,), {})
        return getattr(P, name).lower(self.cfg, params, *args, **kwargs)

    # paged adapter pool (engine/adapters.py): the lora leaves live in
    # self.params["layers"]; a load is one donation-aliased write per
    # factor stack with the page id TRACED (no recompile across pages)
    def write_adapter_page(self, page, updates):
        from .adapters import _page_write

        layers = dict(self.params["layers"])
        page = jnp.int32(page)
        for leaf, (a, b) in updates.items():
            for suffix, val in (("a", a), ("b", b)):
                name = f"lora_{leaf}_{suffix}"
                layers[name] = _page_write(
                    layers[name], page,
                    jnp.asarray(val, self.cfg.jnp_dtype),
                )
        self.params = dict(self.params)
        self.params["layers"] = layers

    def ragged_program_count(self) -> int:
        """Compiled ragged-ingest program count (jit cache entries of the
        two launch programs) — the dli_ragged_compiled_programs gauge:
        flat after warmup proves no per-shape recompile."""
        from . import paged as P

        return (
            P.extend_ragged_paged._cache_size()
            + P.prefill_ragged_paged._cache_size()
        )

    def decode_speculative(self, first_token, cache, hist, hist_len, limit,
                           *, max_steps, draft_len):
        return G.decode_speculative(
            self.cfg, self.params, first_token, cache, hist, hist_len, limit,
            max_steps=max_steps, draft_len=draft_len,
        )

    # two-model (draft) speculative decode — engine.set_draft() wires the
    # draft model in; the combined verify program runs both models
    supports_draft = True

    def decode_draft_speculative(self, dcfg, dparams, first_token, cache,
                                 dcache, start_pos, limit, *, max_steps,
                                 draft_len):
        return G.decode_draft_speculative(
            self.cfg, self.params, dcfg, dparams, first_token, cache,
            dcache, start_pos, limit, max_steps=max_steps,
            draft_len=draft_len,
        )

    def health(self) -> list[dict]:
        """Per-device health: a timed device probe, the in-process analogue
        of the reference's 5s-timeout /workers sweep
        (orchestration.py:306-329)."""
        from ..utils.probe import device_memory, probe_device

        dev = jax.devices()[0]
        return [{
            "stage": 0, "devices": [str(dev)],
            "memory": [device_memory(dev)], **probe_device(dev),
        }]


class InferenceEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any = None,
        backend: Any = None,
        tokenizer: Any = None,
        engine_cfg: EngineConfig = EngineConfig(),
        seed: int = 0,
    ):
        if backend is None:
            if params is None:
                params = M.init_params(cfg, jax.random.PRNGKey(seed))
            backend = SingleDeviceBackend(cfg, params)
        self.cfg = cfg
        self.backend = backend
        self.engine_cfg = engine_cfg
        self.tokenizer = tokenizer or load_tokenizer(
            None, pad_id=cfg.pad_token_id, bos_id=cfg.bos_token_id, eos_id=cfg.eos_token_id
        )
        self._lock = threading.Lock()
        self._key = jax.random.PRNGKey(seed)
        self.request_count = 0
        # Rolling per-request perf samples for p50/p90/p99 TTFT + throughput
        # (BASELINE.json's metric is p50 TTFT — a measurement, not a print).
        # Own lock, NOT self._lock: that one is held for a whole generation,
        # and /health must not block behind a multi-second decode.
        self._samples = collections.deque(maxlen=256)
        self._samples_lock = threading.Lock()
        self._samples_total = 0  # guarded-by: _samples_lock
        # Metrics registry (utils/metrics.py): owned per engine so tests /
        # embedded engines never cross-talk; the server, queue, continuous
        # engine, prefix cache, and constraint table all register into it,
        # and GET /metrics renders it. _record_sample is the ONE seam that
        # feeds both this registry and the rolling deque above, so the
        # /stats JSON view and the Prometheus view cannot diverge.
        self.metrics = MetricsRegistry()
        self._m_ttft = self.metrics.histogram(
            "dli_ttft_seconds", "time to first token", ("engine",)
        )
        self._m_tpot = self.metrics.histogram(
            "dli_tpot_seconds", "inter-token time (decode)", ("engine",)
        )
        self._m_duration = self.metrics.histogram(
            "dli_request_duration_seconds", "end-to-end request latency",
            ("engine",),
        )
        self._m_requests = self.metrics.counter(
            "dli_requests_total", "served generations", ("engine", "model")
        )
        self._m_failures = self.metrics.counter(
            "dli_request_failures_total", "failed generations",
            ("engine", "error_type"),
        )
        self._m_tokens = self.metrics.counter(
            "dli_tokens_generated_total", "generated tokens", ("engine",)
        )
        self._m_batch_size = self.metrics.histogram(
            "dli_batch_rows", "rows per batched fleet", ("engine",),
            buckets=DEFAULT_SIZE_BUCKETS,
        )
        self._m_speculative = self.metrics.counter(
            "dli_speculative_requests_total",
            "requests served speculatively (acceptance stays on device; "
            "no host callback inside the verify loop)", ("engine",),
        )
        # Pre-register the cross-component families (queue, continuous
        # fleet, prefix cache, constraint table, paged pool) so a scrape's
        # SCHEMA is stable across server configs — a bare solo server
        # exposes the full catalog shape, and components attaching later
        # (serving/queue.py, engine/continuous.py, ...) get-or-create the
        # same families and simply add their labeled series.
        self.metrics.gauge(
            "dli_queue_depth", "requests waiting for dispatch", ("queue",)
        )
        self.metrics.counter(
            "dli_queue_shed_total", "requests shed with 429", ("queue",)
        )
        self.metrics.histogram(
            "dli_admission_wait_seconds", ADMISSION_WAIT_HELP, ("queue",),
        )
        # its two halves on the continuous engine, and why a head waited
        self.metrics.histogram(
            "dli_queue_wait_seconds",
            "enqueue until a slot and pool blocks were granted (re-waits "
            "of a blocked head included)", ("queue",),
        )
        self.metrics.histogram(
            "dli_prefill_seconds",
            "grant until the request's first token was fetched (chunked "
            "prefill shares its steps with the decode rows)", ("queue",),
        )
        self.metrics.counter(
            "dli_admission_blocked_total",
            "scheduler iterations that left the head of the queue "
            "waiting, by what it waited for", ("reason",),
        )
        self.metrics.gauge("dli_slots_total", "continuous-fleet decode slots")
        self.metrics.gauge(
            "dli_slots_occupied", "continuous-fleet slots serving a request"
        )
        self.metrics.histogram(
            "dli_decode_step_seconds", DECODE_STEP_HELP, ("engine",),
        )
        self.metrics.counter(
            "dli_preemptions_total",
            "slots killed before their budget drained", ("reason",),
        )
        # graceful-degradation families (engine/continuous.py preemption
        # + the deadline/cancellation surface): preempt->resume latency,
        # cancellations by cause, end-to-end deadline_ms overruns
        self.metrics.histogram(
            "dli_preempted_resume_seconds",
            "preemption to successful re-admission latency",
        )
        self.metrics.counter(
            "dli_cancelled_total",
            "requests cancelled before completion", ("cause",),
        )
        self._m_deadline_exceeded = self.metrics.counter(
            "dli_deadline_exceeded_total",
            "requests failed by their end-to-end deadline_ms",
        ).labels()
        self.metrics.counter(
            "dli_prefix_cache_hits_total",
            "prefix-cache hits (tail actually planned and spliced)",
            ("scope",),
        )
        self.metrics.counter(
            "dli_prefix_cache_misses_total", "prefix-cache misses",
            ("scope",),
        )
        self.metrics.counter(
            "dli_prefix_cache_evictions_total",
            "prefix snapshots evicted by the LRU bound", ("scope",),
        )
        self.metrics.gauge(
            "dli_prefix_cache_entries", "resident prefix snapshots",
            ("scope",),
        )
        # failure-containment families (engine/continuous.py supervisor +
        # the serving drain path): restarts, salvaged re-admissions,
        # quarantined requests, drain latency
        self.metrics.counter(
            "dli_scheduler_restarts_total",
            "continuous-scheduler supervisor restarts", ("engine",),
        )
        self.metrics.counter(
            "dli_requests_recovered_total",
            "in-flight requests re-admitted (continuation prefill) after "
            "a scheduler restart", ("engine",),
        )
        self.metrics.counter(
            "dli_poison_requests_total",
            "requests quarantined as poison after repeated crash "
            "implication", ("engine",),
        )
        self.metrics.histogram(
            "dli_drain_duration_seconds",
            "graceful-drain wall time (SIGTERM / drain())", ("component",),
        )
        # warm-recovery families (engine/shadow.py + the continuous
        # supervisor's restore path): shadow residency/traffic, blocks
        # restored into rebuilt pools, and the per-salvage recompute
        # cost warm recovery exists to shrink
        self.metrics.gauge(
            "dli_shadow_blocks",
            "host-shadowed paged-KV blocks resident for warm recovery",
        )
        self.metrics.counter(
            "dli_shadow_copies_total",
            "paged-KV blocks copied device->host into the shadow store",
        )
        self.metrics.counter(
            "dli_shadow_dropped_total",
            "shadow blocks dropped (copier backpressure or a failed "
            "device->host transfer)",
        )
        self.metrics.counter(
            "dli_shadow_restored_blocks_total",
            "shadowed blocks scattered back into a rebuilt pool "
            "(supervisor restart or --restore-dir start)",
        )
        self.metrics.counter(
            "dli_recovery_tokens_recomputed_total",
            "prompt tokens re-prefilled for crash-recovery re-admissions "
            "(warm recovery bounds this by the partial tail block)",
            ("engine",),
        )
        # KV-fabric families (serving/kv_fabric.py — labeled by the
        # continuous engine's fetch client when the fabric is live;
        # role = this replica's --replica-class): cross-replica chain
        # fetches, their outcomes, wire bytes, and fetch latency
        self.metrics.counter(
            "dli_kv_fabric_fetches_total",
            "cross-replica /kv chain fetches attempted", ("role",),
        )
        self.metrics.counter(
            "dli_kv_fabric_hits_total",
            "fabric fetches that returned a verified chain", ("role",),
        )
        self.metrics.counter(
            "dli_kv_fabric_misses_total",
            "fabric fetches that fell back to local prefill (404, "
            "dead/wedged peer, failed content-key recheck)", ("role",),
        )
        self.metrics.counter(
            "dli_kv_fabric_bytes_total",
            "wire bytes of verified fabric chains moved, by serving tier "
            "(host/disk = pull source at the peer, push = proactive "
            "POST /kv at the prefill->decode handoff)",
            ("role", "tier"),
        )
        self.metrics.histogram(
            "dli_kv_fabric_fetch_seconds",
            "fabric fetch wall time, failures included",
        )
        # KV tier-hierarchy families (engine/shadow.py — ARCHITECTURE.md
        # "Tiered KV"): per-tier occupancy plus promotion/demotion flow
        # between HBM pool (tier 0), host shadow (tier 1), disk chunk
        # files (tier 2)
        self.metrics.gauge(
            "dli_kv_tier_entries",
            "KV blocks resident per cache tier (host = shadow DRAM, "
            "disk = persisted chunk files)", ("tier",),
        )
        self.metrics.gauge(
            "dli_kv_tier_bytes",
            "approximate bytes resident per KV cache tier", ("tier",),
        )
        self.metrics.counter(
            "dli_kv_tier_promotions_total",
            "KV blocks promoted up the tier hierarchy, by destination "
            "tier (host = disk->DRAM load, pool = scattered into HBM)",
            ("tier",),
        )
        self.metrics.counter(
            "dli_kv_tier_demotions_total",
            "KV blocks demoted down the tier hierarchy, by destination "
            "tier (disk = host-LRU spill or copier-backpressure spill)",
            ("tier",),
        )
        self.metrics.counter(
            "dli_kv_tier_disk_hits_total",
            "lookups served from the disk tier (chunk files loaded and "
            "verified on a read that missed the host tier)",
        )
        # wedge observability (engine._with_deadline): abandoned
        # deadline-overrun device calls still occupying the device — the
        # serving edge flips /ready 503 past --wedge-unready off the
        # same state, so the router tier ejects a wedged replica
        self._m_wedged = self.metrics.gauge(
            "dli_engine_wedged",
            "abandoned deadline-overrun device calls still running "
            "(nonzero = wedged; /ready reports 503 past --wedge-unready)",
        ).labels()
        # ragged-ingest families (engine/continuous.py labels them when
        # the ragged path is live): launch composition, padding-tile
        # overhead, exact-depth prefix reuse, and the compiled-program
        # gauge that makes the no-recompile-per-tail invariant observable
        self.metrics.counter(
            "dli_ragged_rows_total",
            "ragged-launch rows by kind (prefill chunk / decode token)",
            ("kind",),
        )
        self.metrics.counter(
            "dli_ragged_tiles_total",
            "ragged-launch query tiles by liveness (live / pad — a pad "
            "tile is one program that walks no KV block)", ("state",),
        )
        self.metrics.counter(
            "dli_mixed_tokens_total",
            "flat tokens of mixed scheduler launches: live (a decode, verify "
            "or prompt token) and computed (the axis the token-wise layers "
            "ran on: engine/scheduler.live_width)", ("state",),
        )
        self.metrics.counter(
            "dli_ragged_launches_total",
            "launches by program: ragged ingest (extend / prefill) and "
            "scheduler steps (mixed / chunk)", ("phase",),
        )
        self.metrics.counter(
            "dli_ragged_exact_prefix_hits_total",
            "prefix hits reused at exact chunk depth (no bucket "
            "degradation — the ragged path's planner win)",
        )
        self.metrics.gauge(
            "dli_ragged_compiled_programs",
            "compiled ragged ingest programs (flat after warmup = no "
            "per-tail-shape recompile)",
        )
        # SLO-aware chunked-prefill scheduler families (engine/
        # scheduler.py labels them when the chunked path is live): mixed-
        # launch composition plus per-class admission state — pre-
        # registered here so a scrape's schema is stable across configs
        self.metrics.counter(
            "dli_sched_step_tokens_total",
            "flat tokens launched by the chunked-prefill scheduler, by "
            "kind (decode rows / prefill chunk tokens)", ("kind",),
        )
        self.metrics.counter(
            "dli_sched_prefill_chunks_total",
            "prefill chunks interleaved into mixed scheduler launches",
        )
        self.metrics.counter(
            "dli_sched_decode_rows_total",
            "decode rows carried by scheduler launches (a pure-decode "
            "chunk counts its row-steps)",
        )
        self.metrics.gauge(
            "dli_sched_step_width_tokens",
            "flat-token width of the mixed scheduler launch in the kernel's "
            "tile layout (derived from the model unless step_token_budget is "
            "set; the axis the model computes is /stats scheduler.live_width)",
        )
        # launch-record families (engine/continuous.py counts them at
        # every dispatch, mixed step or pure-decode chunk): KV positions
        # attended against walked, work dispatched ahead of a launch,
        # and the worker thread's wall time by phase
        self.metrics.counter(
            "dli_attn_kv_tokens_total",
            "KV positions per layer and KV head: attended = the fewest "
            "the launch's rows need (host position model, window-"
            "clipped), walked = what the kernels' block loops cover",
            ("phase", "state"),
        )
        self.metrics.counter(
            "dli_attn_walk_steps_total", ATTN_WALK_STEPS_HELP, ("phase",),
        )
        self.metrics.counter(
            "dli_decode_chunk_steps_total", CHUNK_STEPS_HELP, ("state",),
        )
        self.metrics.histogram(
            "dli_launch_steps_ahead",
            "scheduler steps dispatched and unfetched when a launch was "
            "dispatched", ("phase",), buckets=STEPS_AHEAD_BUCKETS,
        )
        self.metrics.counter(
            "dli_slot_release_total", SLOT_RELEASE_HELP, ("by",),
        )
        self.metrics.histogram(
            "dli_slot_turnover_steps", SLOT_TURNOVER_HELP,
            buckets=STEPS_AHEAD_BUCKETS,
        )
        self.metrics.counter(
            "dli_diffusion_row_forwards_total", DIFFUSION_FORWARDS_HELP,
            ("kind",),
        )
        self.metrics.counter(
            "dli_diffusion_fused_commits_total", DIFFUSION_FUSED_HELP,
        )
        self.metrics.counter(
            "dli_diffusion_tokens_total", DIFFUSION_TOKENS_HELP,
        )
        self.metrics.counter(
            "dli_worker_phase_seconds_total",
            "wall time of the scheduler's worker thread by phase "
            "(contiguous: the phases sum to the thread's life)",
            ("phase",),
        )
        # the worker's reading of the device it feeds (utils/tracing.
        # LaunchTimer, PhaseClock.device_empty): always on, no profiler
        self.metrics.counter(
            "dli_device_empty_seconds_total", DEVICE_EMPTY_HELP, ("phase",),
        )
        self.metrics.counter(
            "dli_launch_device_seconds_total", LAUNCH_DEVICE_SECONDS_HELP,
            ("phase",),
        )
        self.metrics.counter(
            "dli_launch_device_steps_total", LAUNCH_DEVICE_STEPS_HELP,
            ("phase",),
        )
        self.metrics.counter(
            "dli_launch_timing_total", LAUNCH_TIMING_HELP, ("phase", "state"),
        )
        self.metrics.counter(
            "dli_decode_row_seconds_total", DECODE_ROW_SECONDS_HELP,
            ("phase",),
        )
        # fleet speculative-decoding families (engine/continuous.py
        # labels them when the mixed fleet speculates — ISSUE 13):
        # draft/accept/reject token flow, verify-row launches by draft
        # source, and the accepted-tokens-per-launch distribution
        self.metrics.counter(
            "dli_spec_drafted_tokens_total",
            "draft tokens submitted in mixed-launch verify rows",
        )
        self.metrics.counter(
            "dli_spec_accepted_tokens_total",
            "draft tokens accepted (matched the model's own argmax and "
            "were emitted)",
        )
        self.metrics.counter(
            "dli_spec_rejected_tokens_total",
            "draft tokens rejected by the traced verify",
        )
        self.metrics.counter(
            "dli_spec_launches_total",
            "verify rows launched inside mixed scheduler steps, by draft "
            "source", ("mode",),
        )
        self.metrics.histogram(
            "dli_spec_tokens_per_launch",
            "tokens emitted per verify row (accepted drafts + the "
            "correction token; > 1 is the speculation win)",
            buckets=DEFAULT_SIZE_BUCKETS,
        )
        # adaptive drafting (device-derived metadata, ISSUE 15): the
        # planned K per verify row and the fleet-mean per-slot
        # acceptance EWMA the adaptive throttle steers by
        self.metrics.histogram(
            "dli_spec_draft_len",
            "planned draft length K per verify row (after the adaptive "
            "per-slot throttle)",
            buckets=DEFAULT_SIZE_BUCKETS,
        )
        self.metrics.gauge(
            "dli_spec_accept_ewma",
            "fleet-mean per-slot draft acceptance-rate EWMA (0..1)",
        )
        self.metrics.gauge(
            "dli_slo_queue_depth",
            "queued requests per SLO class and tenant", ("slo_class", "tenant"),
        )
        self.metrics.counter(
            "dli_slo_shed_total",
            "requests shed with 429 by SLO admission control (class drain "
            "estimate over the TTFT target, or queue full)", ("slo_class",),
        )
        # multi-tenant adapter-serving families (engine/adapters.py pool +
        # the continuous engine's per-tenant quota shed): pool residency /
        # reserved HBM, page traffic, and tenant-level shedding
        self.metrics.gauge(
            "dli_adapter_pool_resident",
            "adapters resident in device pool pages (referenced + LRU)",
        )
        self.metrics.gauge(
            "dli_adapter_pool_bytes",
            "HBM bytes reserved by the paged adapter leaves (all pages, "
            "base page included)",
        )
        self.metrics.counter(
            "dli_adapter_loads_total",
            "adapter page writes into the device pool",
        )
        self.metrics.counter(
            "dli_adapter_evictions_total",
            "resident adapters dropped from their page (LRU reclaim; "
            "referenced pages are never evicted)",
        )
        self.metrics.counter(
            "dli_adapter_swaps_total",
            "page loads that displaced another adapter (evict + write on "
            "one page)",
        )
        self.metrics.counter(
            "dli_tenant_shed_total",
            "requests shed with 429 by per-tenant quota control (router "
            "inflight share or scheduler queue share)", ("tenant",),
        )
        # pp wire-format families (ops/wire_quant.py + the SPMD backends'
        # static per-launch accounting): inter-stage activation bytes per
        # ICI link by transfer family, and whether the int8 wire is on.
        # Byte counts are host-side arithmetic from program shapes at the
        # launch seams — nothing is traced, decode while_loops count
        # their full ring-pass upper bound.
        self.metrics.counter(
            "dli_pp_wire_bytes_total",
            "inter-stage activation bytes shipped on the pp/sp wire, by "
            "transfer family", ("path",),
        )
        self.metrics.gauge(
            "dli_pp_wire_quant",
            "1 when the int8 inter-stage wire format "
            "(EngineConfig.pp_wire_quant) is active on this backend",
        ).labels().set(
            1.0 if getattr(self.backend, "wire_quant", None) else 0.0
        )
        if hasattr(self.backend, "attach_wire_metrics"):
            self.backend.attach_wire_metrics(self.metrics)
        # Build identity (ISSUE 17 satellite): one always-1 gauge whose
        # LABELS carry the version/runtime/config identity — the standard
        # Prometheus build_info idiom, joinable against every other
        # dli_* series. Kept to 4 literal labels (metrics-labels rule):
        # the pp-wire/model-quant knobs collapse into one `knobs` string.
        from .. import __version__ as _dli_version
        self.metrics.gauge(
            "dli_build_info",
            "build/version identity (value is always 1; the labels are "
            "the payload — join against any dli_* series)",
            ("version", "jax", "replica_class", "knobs"),
        ).labels(
            version=_dli_version,
            jax=jax.__version__,
            replica_class=engine_cfg.replica_class,
            knobs=(
                f"quant={cfg.quant or 'none'}"
                f",kv={cfg.kv_quant or 'none'}"
                f",wire={engine_cfg.pp_wire_quant or 'none'}"
            ),
        ).set(1.0)
        # Fleet tracing (ISSUE 17): the per-process span store this
        # engine's serving edge records into (replica request spans,
        # stage-segment child spans, fabric pulls, sampled launch
        # attribution), and the control-plane flight recorder the
        # continuous supervisor dumps into crash reports. Both bounded,
        # both host-side only.
        self.trace_store = TraceStore(
            service=f"replica-{engine_cfg.replica_class}"
        )
        self.flight = FlightRecorder()
        # Paged runtime LoRA adapter pool (engine/adapters.AdapterPool) —
        # wired by create_engine (EngineConfig.adapter_slots > 0) or
        # adapters.attach_adapter_pool; None = base-only serving.
        self.adapters = None
        # Reusable KV cache buffer: allocated once, donated to prefill/decode
        # each request and replaced by the returned buffer. Stale contents
        # between requests are harmless — prefill rewrites slots [0, bucket)
        # and the causal mask hides every slot beyond the current position.
        self._cache = None
        # Same donate-and-restore pattern per batch bucket: without it every
        # batched request allocates (and drops) a Bb x max_seq cache — multi-
        # GB HBM churn on the hot batched path.
        self._batch_caches: dict[int, Any] = {}
        # Prefix KV snapshots (engine/prefix.py); disabled at 0 entries,
        # for backends that cannot resume ingestion at an offset (no
        # extend/prefill_at — snapshots could be stored but never
        # spliced), and auto-disabled for cache layouts that cannot
        # snapshot/splice (checked against the live buffer later).
        self._prefix = None
        if engine_cfg.prefix_cache_entries > 0:
            if hasattr(self.backend, "prefill_at"):
                self._prefix = PrefixCache(
                    engine_cfg.prefix_cache_entries, engine_cfg.prefix_chunk,
                    registry=self.metrics, scope="solo",
                )
            else:
                log.info("prefix_cache_disabled", reason="backend lacks prefill_at")
        # Two-model speculative decoding (set_draft): (dcfg, dparams) of a
        # smaller same-tokenizer model + its reusable donated KV cache
        self._draft = None
        self._draft_cache = None
        # Grammar-constraint compiled-artifact cache (constrain/): LRU by
        # canonical constraint hash. The token vocab + trie are built once
        # (lazily — tokenizer byte extraction is per-engine, not per-spec)
        # and shared by every compile; artifacts keep their device tables
        # warm so repeated constraints re-upload nothing.
        self._constraint_cache = collections.OrderedDict()
        self._constraint_vocab = None
        self._constraint_trie = None
        # own lock: the continuous worker thread and request threads both
        # compile (engine._lock is held for whole generations — a compile
        # must not queue behind a multi-second decode)
        self._constraint_lock = threading.Lock()
        # Abandoned (deadline-overrun) device calls still running on their
        # daemon threads: token -> {"what", "since"}. /health flips to
        # "degraded" while any exists (a hung device call is the failure
        # mode this names), and the server's optional
        # --die-on-wedge reaper exits the process off max_wedged_age().
        self._wedged: dict = {}  # guarded-by: _wedged_lock
        self._wedged_lock = threading.Lock()

    def set_draft(self, dcfg: ModelConfig, dparams: Any = None,
                  seed: int = 1):
        """Attach a draft model for two-model speculative decoding.

        The draft must share the target's tokenizer/vocab (token ids are
        compared against the target's argmax); the single-device backend
        and the pp pipeline (replicated draft inside the ring) run the
        combined verify program.
        """
        if dparams is None:
            dparams = M.init_params(dcfg, jax.random.PRNGKey(seed))
        if dcfg.vocab_size != self.cfg.vocab_size:
            raise ValueError(
                f"draft vocab {dcfg.vocab_size} != target vocab "
                f"{self.cfg.vocab_size}; draft and target must share a "
                f"tokenizer"
            )
        if not getattr(self.backend, "supports_draft", False):
            raise ValueError(
                f"backend {self.backend.name!r} does not support draft-model "
                f"speculation; serve on the single-device or pipeline backend"
            )
        self._draft = (dcfg, dparams)
        self._draft_cache = None

    # -- helpers ------------------------------------------------------------
    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _with_deadline(self, fn, what: str, deadline_s: Optional[float] = None,
                       exceeded_type: str = "timeout"):
        """Run fn() under the configured per-request deadline.

        TPU-native analogue of the reference's per-hop 30s timeout
        (orchestration.py:118,131): a request that overruns gets a timeout
        envelope (error_type "timeout" -> HTTP 503) while the stuck call is
        abandoned to a daemon thread. The engine lock frees when that
        thread finishes, so one wedged device call delays — but never
        permanently wedges — subsequent requests; they time out cleanly
        against the same deadline until the lock frees.

        deadline_s overrides the configured server-wide cap (the
        end-to-end deadline_ms surface passes the request's remaining
        budget); exceeded_type names the envelope's error_type —
        "deadline_exceeded" (HTTP 504, never router-retried) when the
        request's own budget is the binding constraint.
        """
        deadline = (
            deadline_s if deadline_s is not None
            else self.engine_cfg.request_deadline_s
        )
        if not deadline:
            return fn()
        box: dict = {}
        token = object()

        def run():
            try:
                box["result"] = fn()
            except BaseException as e:  # re-raised on the caller thread
                box["exc"] = e
            finally:
                # the abandoned call finally drained: /health un-degrades.
                # box["done"] is flipped under the SAME lock that guards
                # registration, so a call finishing exactly at the deadline
                # can never leave a permanent stale entry (Thread.is_alive
                # cannot arbitrate this — it stays True past this finally)
                with self._wedged_lock:
                    box["done"] = True
                    self._wedged.pop(token, None)
                    self._m_wedged.set(len(self._wedged))

        t = threading.Thread(target=run, daemon=True, name=f"engine-{what}")
        t.start()
        t.join(deadline)
        if t.is_alive():
            log.error("request_deadline_exceeded", what=what, deadline_s=deadline)
            with self._wedged_lock:
                if not box.get("done"):
                    # `since` = the moment of ABANDONMENT (not call start:
                    # the reported age — and --die-on-wedge's threshold —
                    # count time stuck PAST the deadline), on the monotonic
                    # clock (a wall-clock NTP step must never exit(17) a
                    # healthy process)
                    self._wedged[token] = {
                        "what": what, "since": time.monotonic(),
                    }
                    self._m_wedged.set(len(self._wedged))
            return {
                "error": f"Error: request exceeded the {deadline:g}s deadline",
                "status": "failed",
                "error_type": exceeded_type,
            }
        if "exc" in box:
            raise box["exc"]
        return box["result"]

    def wedged_info(self) -> list[dict]:
        """Abandoned deadline-overrun calls still occupying the device:
        [{"what", "age_s"}] — age counted from ABANDONMENT (deadline
        overrun), oldest first. Empty = not wedged."""
        now = time.monotonic()
        with self._wedged_lock:
            entries = [
                {"what": e["what"], "age_s": round(now - e["since"], 1)}
                for e in self._wedged.values()
            ]
        return sorted(entries, key=lambda e: -e["age_s"])

    def max_wedged_age(self) -> Optional[float]:
        info = self.wedged_info()
        return info[0]["age_s"] if info else None

    def _buckets(self):
        return tuple(b for b in self.engine_cfg.prefill_buckets if b <= self.cfg.max_seq_len)

    def _clamp_decode(
        self, frame: int, max_tokens: int, headroom: int = 0,
        capacity: Optional[int] = None,
    ) -> tuple[int, int]:
        """Cache-capacity discipline in ONE place: frame + generated (+
        `headroom` scratch slots, e.g. speculative drafts written past the
        last emitted token) must fit the cache capacity (update_kv_cache
        clamps silently out of range — never allow it), also bounded by the
        largest compiled decode bucket. capacity defaults to max_seq_len;
        the continuous engine passes its per-slot budget (a slot class
        smaller than the model's window). Returns (max_tokens,
        decode_bucket)."""
        cap = capacity if capacity is not None else self.cfg.max_seq_len
        max_tokens = max(
            1,
            min(
                int(max_tokens),
                cap - frame - 1 - headroom,
                DECODE_BUCKETS[-1],
            ),
        )
        return max_tokens, G.pick_bucket(DECODE_BUCKETS, max_tokens)

    def _plan(self, longest_prompt: int, max_tokens: int):
        """Bucketing/clamping for BATCHED requests (left-padded: the whole
        bucket is the position frame). Single requests plan through
        _plan_ingest. Returns (bucket, max_tokens, decode_bucket)."""
        buckets = self._buckets()
        if not buckets or longest_prompt > buckets[-1]:
            raise ValueError(
                f"prompt length {longest_prompt} exceeds max prefill bucket "
                f"{buckets[-1] if buckets else 0}"
            )
        bucket = G.pick_bucket(buckets, longest_prompt)
        max_tokens, decode_bucket = self._clamp_decode(bucket, max_tokens)
        return bucket, max_tokens, decode_bucket

    def _row_tokens(self, first_id: int, row_out, n: int) -> list:
        """Assemble one row's emitted ids (stop-token-as-first excluded,
        matching the reference's break-before-append,
        orchestration.py:181-186)."""
        head = [first_id] if first_id not in self.cfg.all_stop_ids else []
        return head + [int(t) for t in list(row_out[:n])]

    @staticmethod
    def _truncate_at_stop(text: str, stop) -> tuple:
        """Cut `text` at the EARLIEST occurrence of any stop string
        (OpenAI-style "stop" sequences — the stop text itself is excluded,
        matching the stop-token break-before-append discipline). Returns
        (text, hit: bool)."""
        if not stop:
            return text, False
        cut = min(
            (i for i in (text.find(s) for s in stop if s) if i >= 0),
            default=-1,
        )
        if cut < 0:
            return text, False
        return text[:cut], True

    def _record_sample(self, ttft: float, per_stream_tps: float, tokens: int,
                       elapsed: Optional[float] = None,
                       engine: str = "solo",
                       trace_id: Optional[str] = None):
        """Per-STREAM throughput sample (batch requests divide by B), so
        /stats percentiles stay comparable to the single-stream metric.

        The ONE seam feeding both observability views: the rolling deque
        (/stats percentiles) and the registry histograms (/metrics). Only
        recorded traffic reaches either — warmup never calls this, so it
        is excluded from both views identically.

        trace_id, when the request carried a fleet trace context, becomes
        the latency histograms' EXEMPLAR: each bucket remembers the most
        recent (trace_id, value) that landed in it, so a p99 bucket in
        the JSON snapshot links to one concrete inspectable trace."""
        with self._samples_lock:
            self._samples.append(
                {"ttft_s": ttft, "tokens_per_sec": per_stream_tps, "tokens": tokens}
            )
            self._samples_total += 1
        self._m_ttft.labels(engine=engine).observe(ttft, trace_id=trace_id)
        self._m_tokens.labels(engine=engine).inc(tokens)
        if elapsed is not None:
            self._m_duration.labels(engine=engine).observe(
                elapsed, trace_id=trace_id
            )
            if tokens > 1:
                # TPOT (inter-token time): decode wall over the tokens
                # after the first — the metric that exposes slow steps
                # independently of prompt length
                self._m_tpot.labels(engine=engine).observe(
                    max(0.0, elapsed - ttft) / (tokens - 1),
                    trace_id=trace_id,
                )

    # -- main entry ----------------------------------------------------------
    def generate(
        self,
        prompt: str,
        max_tokens: int = 20,
        temperature: float = 0.7,
        top_k: int = 50,
        top_p: float = 0.9,
        greedy: bool = False,
        chat: bool = True,
        seed: Optional[int] = None,
        debug: bool = False,
        speculative: bool = False,
        min_p: float = 0.0,
        repetition_penalty: float = 1.0,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        stop: Optional[list] = None,
        logprobs: bool = False,
        logit_bias: Optional[dict] = None,
        num_beams: int = 1,
        length_penalty: float = 1.0,
        early_stopping: bool = False,
        constraint: Optional[dict] = None,
        request_id: Optional[str] = None,
        slo_class: Optional[str] = None,
        deadline_ms: Optional[float] = None,
        _trace: Optional[Trace] = None,
    ) -> dict:
        """Full generation; returns the reference-schema response dict.

        debug=True adds "top_predictions": the top-5 first-token
        candidates with probabilities (the reference prints these,
        orchestration.py:172-178; here they are response data, not stdout).
        speculative=True uses prompt-lookup self-speculation for GREEDY
        requests on capable backends (several tokens per forward on
        repetitive text; every emitted token is still an argmax — exact
        vs plain greedy in fp32, while bf16 may resolve numerical
        near-ties differently); ignored otherwise.
        min_p / repetition_penalty: HF-parity sampling extensions
        (MinPLogitsWarper / RepetitionPenaltyLogitsProcessor; 0.0 / 1.0 =
        off). A repetition penalty disables speculation: it changes the
        argmax the draft verification compares against.
        frequency_penalty / presence_penalty: the OpenAI penalties over
        GENERATED-token counts (logits -= fp*count + pp*(count>0); 0.0 =
        off, the usual [-2, 2] range accepted). Like the repetition
        penalty they ride the pre-warper slot, apply to greedy argmax
        too, and disable speculation.
        logit_bias: {token_id: bias} added to the raw logits at every
        sample (OpenAI semantics; -100/+100 ban/force). Also disables
        speculation (it changes the verify argmax), and reported
        token_logprobs stay the RAW model distribution.
        num_beams > 1: deterministic beam search (HF generate(num_beams=N,
        do_sample=False) semantics; length_penalty / early_stopping as in
        HF). Sampling params / speculation / logprobs / bias are ignored
        on the beam path — it is a pure max-score search (HF ignores them
        the same way) — EXCEPT the OpenAI penalties, which reject loudly:
        they alter which continuation wins, so dropping them would change
        results silently rather than fall back to documented semantics.
        """
        t_start = time.time()
        trace = _trace if _trace is not None else Trace(request_id)
        if self.cfg.recurrent:
            # a row's recurrent state lives in the paged fleet's pool
            return {
                "error": f"Error: {self.cfg.name} keeps a recurrent state "
                "a row and is served by the continuous engine "
                "(--continuous N --kv-pool-blocks M) only: no seed / debug "
                "/ logprobs / logit_bias / beams / constraint / speculative "
                "contract",
                "status": "failed", "error_type": "invalid_request",
            }
        if self.cfg.diffusion_block:
            # this loop decodes one token a forward; a block-diffusion
            # model is served by the continuous engine's paged fleet
            return {
                "error": f"Error: {self.cfg.name} generates by diffusion "
                "over blocks and is served by the continuous engine "
                "(--continuous N --kv-pool-blocks M) only",
                "status": "failed", "error_type": "invalid_request",
            }

        with request_id_context(trace.request_id):
            dl_s, dl_type = self._resolve_deadline(deadline_ms)
            if dl_s is not None and dl_s <= 0:
                # end-to-end budget already spent (queue/router hops ate
                # it): fail before touching the device
                self._m_deadline_exceeded.inc()
                result = {
                    "error": "Error: request exceeded its deadline_ms "
                    "budget before generation",
                    "status": "failed",
                    "error_type": "deadline_exceeded",
                }
                return self._finish_request(result, trace, engine="solo")
            result = self._generate_traced(
                prompt, max_tokens, temperature, top_k, top_p, greedy, chat,
                seed, debug, speculative, min_p, repetition_penalty,
                frequency_penalty, presence_penalty, stop, logprobs,
                logit_bias, num_beams, length_penalty, early_stopping,
                constraint, t_start, trace,
                deadline_s=dl_s, exceeded_type=dl_type,
            )
            if result.get("error_type") == "deadline_exceeded":
                self._m_deadline_exceeded.inc()
            if slo_class is not None:
                # admission priority is a fleet concept (the continuous
                # scheduler's SLO classes); the solo path serves directly
                # but accepts + echoes the class so fleet fallbacks and
                # class-tagged clients keep one request schema
                result.setdefault("slo_class", slo_class)
            return self._finish_request(result, trace, engine="solo")

    def _resolve_deadline(self, deadline_ms) -> tuple:
        """(deadline_s, exceeded_type) for a request carrying an
        end-to-end deadline_ms: the binding constraint is the smaller of
        the request's remaining budget and the server-wide
        request_deadline_s cap; the envelope's error_type follows the
        binding one ("deadline_exceeded" -> HTTP 504, never retried by
        the router — "timeout" -> 503 keeps its legacy semantics)."""
        cfg_s = self.engine_cfg.request_deadline_s
        if deadline_ms is None:
            return None if not cfg_s else cfg_s, "timeout"
        req_s = float(deadline_ms) / 1e3
        if cfg_s and cfg_s < req_s:
            return cfg_s, "timeout"
        return req_s, "deadline_exceeded"

    def _generate_traced(
        self, prompt, max_tokens, temperature, top_k, top_p, greedy, chat,
        seed, debug, speculative, min_p, repetition_penalty,
        frequency_penalty, presence_penalty, stop, logprobs, logit_bias,
        num_beams, length_penalty, early_stopping, constraint, t_start,
        trace, deadline_s=None, exceeded_type="timeout",
    ) -> dict:
        if constraint is not None and (num_beams > 1 or speculative):
            # grammar constraints do not compose with beam search (no
            # per-beam FSM state threads the beam reorder) nor with
            # speculative verify (the draft argmax comparison ignores the
            # mask) in this PR — reject loudly, never silently drop the
            # grammar (a "guaranteed-valid JSON" promise silently broken
            # is the worst possible failure mode)
            what = "num_beams > 1" if num_beams > 1 else "speculative"
            msg = f"constraint does not compose with {what}"
            log.warning("invalid_request", error=msg)
            return {"error": f"Error: {msg}", "status": "failed",
                    "error_type": "invalid_request"}

        if num_beams > 1 and (frequency_penalty != 0.0 or presence_penalty != 0.0):
            # the beam path is a pure max-score search with no per-beam
            # count tracking: reject loudly instead of silently returning
            # unpenalized output. (Sampling params / logprobs / bias stay
            # silently ignored on beams — HF-parity semantics the
            # docstring documents; the penalties have no such precedent.)
            msg = (
                "frequency_penalty/presence_penalty are not supported with "
                "num_beams > 1; drop the penalties or use sampling"
            )
            log.warning("invalid_request", error=msg)
            return {"error": f"Error: {msg}", "status": "failed",
                    "error_type": "invalid_request"}

        def locked():
            with self._lock:
                # lock wait = this engine's queueing delay (requests
                # arriving through serving/queue.py fold their dispatcher
                # wait into the same span via the shared trace)
                trace.checkpoint("queue_wait")
                if num_beams > 1:
                # jaxlint: disable=blocking-under-lock -- the engine lock IS the device-serialization point; a generation holds it end to end by design
                    return self._beam_locked(
                        prompt, max_tokens, num_beams, length_penalty,
                        early_stopping, chat, t_start, stop, trace,
                    )
                # jaxlint: disable=blocking-under-lock -- the engine lock IS the device-serialization point; a generation holds it end to end by design
                return self._generate_locked(
                    prompt, max_tokens, temperature, top_k, top_p, greedy, chat,
                    seed, t_start, debug, speculative, min_p,
                    repetition_penalty, stop, logprobs, logit_bias,
                    frequency_penalty, presence_penalty, constraint, trace,
                )

        try:
            return self._with_deadline(
                locked, "generate", deadline_s=deadline_s,
                exceeded_type=exceeded_type,
            )
        except ValueError as e:
            # caller-caused (e.g. prompt longer than the largest prefill
            # bucket): tagged so the serving edge can answer 400, not 500
            log.warning("invalid_request", error=str(e))
            return {"error": f"Error: {e}", "status": "failed",
                    "error_type": "invalid_request"}
        except Exception as e:  # error envelope (orchestration.py:220-228)
            log.error("generate_failed", exc_info=True, error=str(e))
            return {"error": f"Error: {e}", "status": "failed"}

    def _finish_request(self, result: dict, trace: Trace, engine: str,
                        record: bool = True) -> dict:
        """Attach the trace to the envelope, count it, and log ONE
        structured `request_done` event. Shared by the solo/batch/beam
        paths and the continuous engine's finalizer (record=False for
        warmup traffic — excluded from metrics exactly like /stats)."""
        result.setdefault("request_id", trace.request_id)
        result.setdefault("timings", trace.timings())
        if not record:
            return result
        status = result.get("status")
        if status == "success":
            self._m_requests.labels(engine=engine, model=self.cfg.name).inc()
            if result.get("speculative"):
                self._m_speculative.labels(engine=engine).inc()
        else:
            self._m_failures.labels(
                engine=engine,
                error_type=result.get("error_type", "internal"),
            ).inc()
        log.info(
            "request_done", request_id=trace.request_id, status=status,
            engine=engine, tokens=result.get("tokens_generated"),
            **result["timings"],
        )
        return result

    def _plan_ingest(self, prompt_len: int, p0: int, buckets: tuple,
                     capacity: Optional[int] = None):
        """Plan feeding ids[p0:] into the cache at offset p0.

        Returns (n_full, rem, bucket, chunk) — n_full full-`chunk`
        extend() calls then a final `bucket`-padded sampling chunk of
        `rem` valid tokens — or None when this backend/bucket layout
        cannot ingest from that offset (callers retry with p0=0 or
        raise). The final chunk is a PADDED bucket whose pads also write
        K/V: its end must stay inside the cache capacity (default
        max_seq_len; the continuous engine plans against its per-slot
        budget) or update_kv_cache's silent clamp would overwrite real
        prompt slots.
        """
        cap = capacity if capacity is not None else self.cfg.max_seq_len
        if not buckets:
            return None
        if prompt_len > cap - 2:
            # capacity guard on EVERY path (not just chunked): a prefix-
            # cache hit with a short tail must reject exactly the prompts
            # the cold path rejects, or acceptance becomes a function of
            # cache state and decode's first KV write can silently clamp
            return None
        tail = prompt_len - p0
        chunk = buckets[-1]
        n_full = max(0, (tail - 1) // chunk)  # leaves >= 1 sampling token
        rem = tail - n_full * chunk
        needs_offset_ops = p0 > 0 or n_full > 0
        if needs_offset_ops and not hasattr(self.backend, "extend"):
            return None
        fitting = [
            b for b in buckets
            if b >= rem and p0 + n_full * chunk + b <= cap
        ]
        if not fitting:
            return None
        return n_full, rem, fitting[0], chunk

    def _ingest(self, ids, p0, plan, cache, key, sampling, presence=None,
                bias=None, backend=None):
        """Feed ids[p0:] into `cache` per a `_plan_ingest` plan: n_full
        full-chunk extend() calls, then the final bucket-padded sampling
        chunk (prefill at offset 0, prefill_at otherwise). Shared by the
        solo engine, the continuous engine's admission path, AND the
        draft model's prompt ingest (backend override) — one copy of the
        ingest sequence to fix. Returns (first, logits, cache).
        presence: optional [1, V] repetition-penalty token set for the
        first-token sample."""
        be = backend if backend is not None else self.backend
        n_full, rem, bucket, chunk = plan
        pad = self.cfg.pad_token_id
        for c in range(n_full):
            chunk_tokens = jnp.asarray(
                [ids[p0 + c * chunk : p0 + (c + 1) * chunk]], jnp.int32
            )
            cache = be.extend(
                chunk_tokens, jnp.int32(p0 + c * chunk), cache
            )
        tail_start = p0 + n_full * chunk
        tokens = jnp.asarray(
            [ids[tail_start:] + [pad] * (bucket - rem)], jnp.int32
        )
        # bias passed only when set: backends without logit_bias support
        # (no `bias` kwarg) still serve the default path — non-None is
        # already rejected upstream by the supports_bias gate
        kw = {"presence": presence}
        if bias is not None:
            kw["bias"] = bias
        if tail_start == 0:
            return be.prefill(
                tokens, jnp.int32(len(ids)), cache, key, sampling, **kw
            )
        return be.prefill_at(
            tokens, jnp.int32(tail_start), jnp.int32(rem), cache, key,
            sampling, **kw,
        )

    def _prefix_plan(self, prefix, ids: list, capacity: Optional[int] = None,
                     ragged: bool = False, adapter: Optional[str] = None):
        """Prefix lookup + ingest planning, ONE copy for every serving
        path: lookup -> plan the tail -> cold fallback when no tail plan
        fits -> mark hit/miss on the PLANNED outcome (a lookup hit that
        fell back cold is a miss). Returns (p0, entry, plan).

        `prefix` is any PLANNER implementing the two-method protocol
          lookup(ids) -> (p0, entry, key)   # reusable depth + opaque entry
          mark(key, hit)                    # counters + LRU promotion
        — engine/prefix.PrefixCache (dense snapshots: entry is a KV
        pytree the caller splices) and engine/block_prefix.BlockPrefixIndex
        (paged fleets: entry is the shared physical block ids the caller
        maps into the request's block table) both satisfy it; None means
        a plain cold plan. What "reuse" physically does with `entry` is
        the caller's business — this helper owns only the depth/plan/mark
        discipline, which is identical across planners.

        ragged=True (paged admission through the ragged ingest,
        engine/paged.extend_ragged_paged): there is no bucket ladder to
        fit, so ANY tail length >= 1 is serveable and the deepest lookup
        depth is used AS IS — exact-chunk-depth reuse, never degraded.
        The plan is the ("ragged", tail_len) sentinel; only the capacity
        guard can reject (same bound as the cold path, so acceptance
        stays independent of cache state).

        adapter: runtime adapter name for content-keyed planners — the
        adapter changes the KV bytes, so BlockPrefixIndex keys chains
        under a per-adapter root and two adapters (or an adapter and the
        base) never share blocks even for identical prompts. Dense
        PrefixCache planners don't take it (adapter requests bypass them
        entirely — they run the paged fleet)."""
        buckets = self._buckets()
        prompt_len = len(ids)
        p0, entry, pkey = 0, None, None
        if prefix is not None:
            if adapter is not None:
                p0, entry, pkey = prefix.lookup(ids, adapter=adapter)
            else:
                p0, entry, pkey = prefix.lookup(ids)
        if ragged:
            cap = capacity if capacity is not None else self.cfg.max_seq_len
            ok = 1 <= prompt_len <= cap - 2
            plan = ("ragged", prompt_len - p0) if ok else None
            if plan is None or not p0:
                entry = None
                if plan is None:
                    p0 = 0
            if prefix is not None:
                prefix.mark(pkey, hit=bool(p0), depth=p0)
            return p0, entry, plan
        plan = self._plan_ingest(prompt_len, p0, buckets, capacity)
        # Depth degradation (BUCKETED fallback path only — the ragged
        # branch above never degrades): the deepest reuse offset can
        # leave a tail no prefill bucket fits inside the capacity (e.g. a
        # hit at offset 96 in a 128-token window with a 64-token smallest
        # bucket). Both reuse mechanisms serve ANY aligned depth (a
        # snapshot splices its first p0 slots; a block chain maps its
        # first p0/bs blocks), so walk down one planner granule at a time
        # before giving the whole prefix up — partial reuse beats cold.
        step = getattr(prefix, "chunk", 0)
        while plan is None and p0 > step > 0:
            p0 -= step
            plan = self._plan_ingest(prompt_len, p0, buckets, capacity)
        if plan is None and p0:
            p0 = 0
            plan = self._plan_ingest(prompt_len, 0, buckets, capacity)
        if not p0:
            entry = None
        if prefix is not None:
            prefix.mark(pkey, hit=bool(p0) and plan is not None, depth=p0)
        return p0, entry, plan

    def _ingest_with_prefix(
        self, prefix, ids, p0, entry, plan, cache, key, sampling,
        presence=None, bias=None,
    ):
        """Splice a prefix hit, run the shared ingest sequence, store the
        (now complete) prompt KV back into the prefix cache. The
        splice-before-ingest / store-after-ingest ordering is correctness-
        critical (the stored snapshot must cover the whole prompt)."""
        if entry is not None:
            cache = prefix.splice(entry, cache, p0)
        first, logits, cache = self._ingest(
            ids, p0, plan, cache, key, sampling, presence=presence, bias=bias
        )
        if prefix is not None:
            prefix.store(ids, len(ids), cache)
        return first, logits, cache

    def _draft_ingest(self, ids: list, dcache):
        """Prefill the whole prompt into the DRAFT model's cache (two-model
        speculation): the SAME _ingest sequence as the target, driven
        through a single-device backend view over (dcfg, dparams) — one
        ingest copy to fix. No prefix cache (correctness over draft-side
        TTFT); the draft's sampled first token is discarded, only its KV
        matters."""
        dcfg, dparams = self._draft
        plan = self._plan_ingest(len(ids), 0, self._buckets())
        if plan is None:  # main path already accepted this prompt
            raise ValueError(
                f"prompt length {len(ids)} exceeds draft ingest capacity"
            )
        _, _, dcache = self._ingest(
            ids, 0, plan, dcache, jax.random.PRNGKey(0),
            G.default_sampling(greedy=True),
            backend=SingleDeviceBackend(dcfg, dparams),
        )
        return dcache

    # guarded-by: _lock
    def _beam_locked(self, prompt, max_tokens, num_beams, length_penalty,
                     early_stopping, chat, t_start, stop, trace=None):
        """Deterministic beam search (engine side): prefill the prompt
        ONCE (batch 1), tile the prompt KV and first-position logits to
        [num_beams] rows, then G.decode_beam. Tiling instead of an
        [num_beams]-row prefill saves (num_beams-1) prompt forwards AND
        keeps the logits contract backend-independent — a fleet-granular
        backend's fleet prefill returns zero-width logits by design, which
        an [num_beams]-row prefill would hand decode_beam whenever
        num_beams lands on the fleet granularity."""
        cfg = self.cfg
        self.request_count += 1
        if not getattr(self.backend, "supports_beam", False):
            raise ValueError(
                f"backend {self.backend.name!r} does not support beam "
                f"search; serve num_beams > 1 on the single-device or pipeline backend"
            )
        if not 2 <= num_beams <= 16:
            raise ValueError("num_beams must be between 2 and 16")
        text = self.render_chat(prompt) if chat else prompt
        ids = self.tokenizer.encode(text)
        prompt_len = len(ids)
        buckets = self._buckets()
        if not buckets or prompt_len > buckets[-1]:
            raise ValueError(
                f"prompt length {prompt_len} exceeds max prefill bucket "
                f"{buckets[-1] if buckets else 0} (beam search prefills in "
                f"one bucket)"
            )
        bucket = G.pick_bucket(buckets, prompt_len)
        max_tokens, decode_bucket = self._clamp_decode(prompt_len, max_tokens)
        pad = cfg.pad_token_id
        row = ids + [pad] * (bucket - prompt_len)
        tokens = jnp.asarray([row], jnp.int32)
        cache1 = self._cache or self.backend.init_cache(1, cfg.max_seq_len)
        self._cache = None  # donated into prefill; restored below
        sampling = G.default_sampling(greedy=True)
        _, logits, cache1 = self.backend.prefill(
            tokens, jnp.int32(prompt_len), cache1, jax.random.PRNGKey(0),
            sampling,
        )
        # every beam starts from the same prompt: tile batch axis 1 of
        # each cache leaf (KVQuant scale leaves ride the same recipe one
        # rank down) and the [1, V] first-position logits
        cache = jax.tree.map(
            lambda x: jnp.tile(x, (1, num_beams) + (1,) * (x.ndim - 2)),
            cache1,
        )
        logits = jnp.tile(logits, (num_beams, 1))
        ttft = time.time() - t_start
        if trace is not None:
            trace.checkpoint("prefill")
        out, n_gen, scores, cache = self.backend.decode_beam(
            logits, cache, jnp.int32(prompt_len), jnp.int32(max_tokens),
            jnp.float32(length_penalty), max_steps=decode_bucket,
            num_beams=num_beams, early_stopping=early_stopping,
        )
        out = jax.block_until_ready(out)
        self._cache = cache1  # the batch-1 scratch, stale rows masked
        if trace is not None:
            trace.checkpoint("decode")

        beams = []
        for b in range(num_beams):
            n = int(n_gen[b])
            txt = self.tokenizer.decode(
                [int(t) for t in np.asarray(out[b][:n])],
                skip_special_tokens=True,
            )
            txt, b_stopped = self._truncate_at_stop(txt, stop)
            beams.append({
                "text": txt, "score": round(float(scores[b]), 6),
                "tokens": n, "stopped": b_stopped,
            })
        best = beams[0]
        if trace is not None:
            trace.checkpoint("detokenize")
        elapsed = time.time() - t_start
        n = best["tokens"]
        tps = n / elapsed if elapsed > 0 else 0.0
        self._record_sample(ttft, tps, n, elapsed=elapsed)
        log.info(
            "beam_request", model=cfg.name, backend=self.backend.name,
            num_beams=num_beams, tokens=n, elapsed_s=round(elapsed, 3),
        )
        result = {
            "prompt": prompt,
            "response": best["text"],
            "status": "success",
            "time_taken": f"{elapsed:.2f}s",
            "tokens_generated": n,
            "prompt_tokens": prompt_len,
            "tokens_per_sec": f"{tps:.2f}",
            "ttft_s": round(ttft, 4),
            "backend": self.backend.name,
            "num_beams": num_beams,
            "beams": beams,
            "finish_reason": (
                "stop" if best["stopped"] or n < max_tokens else "length"
            ),
        }
        if best["stopped"]:
            result["stopped"] = True
        return result

    def score(self, prompt: str, top_n: int = 0) -> dict:
        """Teacher-forced per-token log-probabilities of `prompt` itself
        (no generation): the OpenAI echo+logprobs+max_tokens=0 pattern
        that evaluation harnesses use for loglikelihood scoring. top_n
        (0..5): also return each position's top-N alternatives (lm-eval
        reads them for its is_greedy check)."""
        t_start = time.time()

        def locked():
            with self._lock:
                return self._score_locked(prompt, int(top_n), t_start)

        try:
            return self._with_deadline(locked, "score")
        except ValueError as e:
            log.warning("invalid_request", error=str(e))
            return {"error": f"Error: {e}", "status": "failed",
                    "error_type": "invalid_request"}
        except Exception as e:  # noqa: BLE001 - envelope discipline
            log.error("score_failed", exc_info=True, error=str(e))
            return {"error": f"Error: {e}", "status": "failed"}

    # guarded-by: _lock
    def _score_locked(self, prompt: str, top_n: int, t_start: float) -> dict:
        cfg = self.cfg
        self.request_count += 1
        if not getattr(self.backend, "supports_score", False):
            raise ValueError(
                f"backend {self.backend.name!r} does not support scoring; "
                f"serve echo/logprobs scoring on the single-device or pipeline backend"
            )
        if not 0 <= top_n <= 5:
            raise ValueError("top_n must be between 0 and 5")
        ids = self.tokenizer.encode(prompt)
        if len(ids) < 2:
            raise ValueError("scoring needs at least 2 tokens")
        buckets = self._buckets()
        if not buckets or len(ids) > cfg.max_seq_len:
            raise ValueError(
                f"prompt length {len(ids)} exceeds max_seq_len "
                f"{cfg.max_seq_len}"
            )
        # chunk plan, mirroring chunked prefill: full chunks of the
        # largest bucket, then a padded final bucket; the KV cache chains
        # the chunks and each chunk's LAST distribution scores the next
        # chunk's first token across the boundary
        chunk = buckets[-1]
        n_full = max(0, (len(ids) - 1) // chunk)
        rem = len(ids) - n_full * chunk
        fitting = [b for b in buckets if b >= rem]
        if not fitting or n_full * chunk + fitting[0] > cfg.max_seq_len:
            raise ValueError(
                f"prompt length {len(ids)} cannot be chunk-scored within "
                f"max_seq_len {cfg.max_seq_len}"
            )
        bucket = fitting[0]

        cache = self._cache or self.backend.init_cache(1, cfg.max_seq_len)
        self._cache = None  # donated scratch; restored below
        pad = cfg.pad_token_id
        lps: list = []
        tops: list = []
        prev_last = None  # np [V]: last distribution of the previous chunk

        def _top_dict(values, ids_):
            # distinct token ids can decode to the SAME string (byte-level
            # tokenizers); keep the best (first, descending) logprob per
            # string — the OpenAI dict format can't carry both
            d: dict = {}
            for v, i in zip(values, ids_):
                s = self.tokenizer.decode([int(i)])
                if s not in d:
                    d[s] = round(float(v), 6)
            return d

        def _boundary(tok: int):
            # score a chunk's first token from the PREVIOUS chunk's last
            # position (host-side: one [V] row per chunk)
            lps.append(float(prev_last[tok]))
            if top_n:
                idx = np.argpartition(-prev_last, top_n - 1)[:top_n]
                idx = idx[np.argsort(-prev_last[idx])]
                tops.append(_top_dict(prev_last[idx], idx))

        for c in range(n_full + 1):
            if c < n_full:
                rows = ids[c * chunk : (c + 1) * chunk]
                toks = jnp.asarray([rows], jnp.int32)
            else:
                rows = ids[n_full * chunk :]
                toks = jnp.asarray(
                    [rows + [pad] * (bucket - rem)], jnp.int32
                )
            within, top_v, top_i, last_lp, cache = self.backend.score_chunk(
                toks, jnp.int32(c * chunk), cache, top_n=top_n
            )
            within = np.asarray(within[0])
            top_v_np = np.asarray(top_v[0])
            top_i_np = np.asarray(top_i[0])
            if c > 0:
                _boundary(rows[0])
            valid = (len(rows) if c < n_full else rem) - 1
            lps.extend(float(x) for x in within[:valid])
            if top_n:
                for t in range(valid):
                    tops.append(_top_dict(top_v_np[t], top_i_np[t]))
            prev_last = np.asarray(last_lp[0])
        self._cache = cache

        lps = [round(x, 6) for x in lps]
        elapsed = time.time() - t_start
        result = {
            "prompt": prompt,
            "status": "success",
            "prompt_tokens": len(ids),
            # OpenAI convention: the first token has no conditional
            "token_logprobs": [None] + lps,
            "token_strings": [self.tokenizer.decode([t]) for t in ids],
            "logprob_sum": round(sum(lps), 6),
            "time_taken": f"{elapsed:.2f}s",
            "backend": self.backend.name,
        }
        if top_n:
            result["top_logprobs"] = [None] + tops
        return result

    def render_chat(self, prompt_or_messages) -> str:
        """Chat-format a user prompt string (or a full OpenAI-style
        message list) with the model's template. ONE copy of the
        template dispatch for the solo / batch / beam / continuous /
        OpenAI paths. cfg.chat_template == "hf" renders through the
        serving tokenizer's own jinja template (the one the checkpoint
        shipped with) — requires an HF tokenizer carrying one."""
        from .chat import format_chat_messages

        messages = (
            [{"role": "user", "content": prompt_or_messages}]
            if isinstance(prompt_or_messages, str)
            else prompt_or_messages
        )
        if self.cfg.chat_template == "hf":
            if not getattr(self.tokenizer, "has_chat_template", False):
                raise ValueError(
                    "chat_template='hf' needs an HF tokenizer with a chat "
                    "template; the serving tokenizer has none"
                )
            return self.tokenizer.apply_chat_template(messages)
        return format_chat_messages(
            messages, arch=self.cfg.arch, template=self.cfg.chat_template
        )

    def _compile_constraint(self, raw: dict):
        """Wire-format constraint -> CompiledConstraint through the engine
        LRU (engine_cfg.constraint_cache_entries). ValueError (malformed
        spec / unsupported schema / oversized DFA) propagates to the
        caller's invalid_request envelope."""
        from .. import constrain as C

        if not getattr(self.backend, "supports_constrain", False):
            raise ValueError(
                f"backend {self.backend.name!r} does not support "
                f"constrained decoding; serve constrained requests on the "
                f"single-device or pipeline backend"
            )
        spec = C.parse_constraint_spec(raw)
        key = C.constraint_key(spec)
        with self._constraint_lock:
            art = self._constraint_cache.get(key)
            if art is not None:
                self._constraint_cache.move_to_end(key)
                return art
            if self._constraint_vocab is None:
                self._constraint_vocab = C.TokenVocab.from_tokenizer(
                    self.tokenizer, self.cfg.vocab_size,
                    eos_ids=self.cfg.all_stop_ids,
                    special_ids=(self.cfg.pad_token_id, self.cfg.bos_token_id),
                )
                from ..constrain.tables import _build_trie

                self._constraint_trie = _build_trie(self._constraint_vocab)
            art = C.compile_constraint(
                spec, self._constraint_vocab, self._constraint_trie
            )
            self._constraint_cache[key] = art
            while len(self._constraint_cache) > max(
                1, self.engine_cfg.constraint_cache_entries
            ):
                self._constraint_cache.popitem(last=False)
            return art

    @staticmethod
    def _constraint_bias(art, bias):
        """Fold the start-state mask into the (possibly absent) logit_bias
        operand for the FIRST token (sampled by prefill, before any decode
        fsm exists): -1e9 on banned tokens can never be resurrected by a
        +100 user bias, and the constrained prefill reuses the compiled
        bias program variants instead of growing new ones."""
        mask_bias = jnp.asarray(art.start_bias())
        return mask_bias if bias is None else bias + mask_bias

    def _bias_array(self, logit_bias):
        """{token_id: bias} -> dense [V] f32 on validated ids, or None.

        Dense because the sampler adds it to the logits row every step
        (a scatter of a handful of floats — the [V] array is tiny next
        to one decode step's weight traffic)."""
        if not logit_bias:
            return None
        if not getattr(self.backend, "supports_bias", False):
            raise ValueError(
                f"backend {self.backend.name!r} does not support logit_bias; "
                f"serve biased requests on the single-device or pipeline backend"
            )
        import numpy as np

        b = np.zeros((self.cfg.vocab_size,), np.float32)
        for tid, v in logit_bias.items():
            t = int(tid)
            if not 0 <= t < self.cfg.vocab_size:
                raise ValueError(
                    f"logit_bias token id {t} outside vocab "
                    f"[0, {self.cfg.vocab_size})"
                )
            b[t] = float(v)
        return jnp.asarray(b)

    def _presence_rows(self, rows: list) -> jnp.ndarray:
        """[len(rows), V] bool: each row's token-id set, built host-side in
        numpy (the full prompt is already a host list — no device pass
        needed, and chunked prefill / prefix-cache hits see every token)."""
        import numpy as np

        out = np.zeros((len(rows), self.cfg.vocab_size), bool)
        for b, ids in enumerate(rows):
            out[b, np.asarray(ids, dtype=np.int64)] = True
        return jnp.asarray(out)

    def _decode_textual_stop_chunks(
        self, first, cache, prompt_len, max_tokens, key_dec, sampling, dkw,
        logprobs, stop, cart=None,
    ):
        """Bounded-chunk decode when textual `stop` sequences are set
        (round-2 review weak #4: the post-hoc check decoded the full
        budget — a 512-token request hitting its stop at token 5 burned
        507 wasted steps on device).

        Decodes chunks that ESCALATE up the DECODE_BUCKETS ladder (16, 32,
        64, ... — every rung a program --warmup already compiled): a stop
        matching early costs one small chunk, while a stop that never
        matches costs O(log budget) round-trips instead of budget/16.
        Checks the accumulated text between chunks and stops the moment a
        stop sequence appears; the caller's existing _truncate_at_stop
        does the exact final truncation. Stop-less requests never enter
        this path, so their device-call count is unchanged. Sampled
        (non-greedy) requests draw from a per-chunk key stream —
        deterministic for a fixed seed, but a different stream than the
        single-call path (greedy output is identical).

        Returns (out [1, N] np.int32, n_gen [1] np.int32, step_lps
        [1, N] np.float32 or None, cache).
        """
        import numpy as np

        budget = max_tokens - 1  # first token already sampled by prefill
        collected: list = []
        lps: list = []
        token = first
        pos = int(prompt_len)
        first_id = int(first[0])
        finished = first_id in self.cfg.all_stop_ids
        rung = 0
        while budget > 0 and not finished:
            chunk_bucket = DECODE_BUCKETS[min(rung, len(DECODE_BUCKETS) - 1)]
            rung += 1
            limit = min(budget, chunk_bucket)
            key_dec, sub = jax.random.split(key_dec)
            if logprobs:
                out_i, n_i, cache, lps_i = self.backend.decode(
                    token, cache, jnp.int32(pos), jnp.int32(limit), sub,
                    sampling, max_steps=chunk_bucket, with_logprobs=True,
                    **dkw,
                )
            else:
                lps_i = None
                out_i, n_i, cache = self.backend.decode(
                    token, cache, jnp.int32(pos), jnp.int32(limit), sub,
                    sampling, max_steps=chunk_bucket, **dkw,
                )
            n = int(n_i[0])
            row = [int(t) for t in np.asarray(out_i[0][:n])]
            collected += row
            if lps_i is not None:
                lps += [float(x) for x in np.asarray(lps_i[0][:n])]
            if n < limit:  # EOS early-exit inside the chunk
                finished = True
                break
            budget -= n
            pos += n
            # presence chunks: mark this chunk's tokens before the next
            if dkw.get("presence") is not None and row:
                pres = dkw["presence"]
                pres = pres.at[0, jnp.asarray(row, jnp.int32)].set(True)
                dkw = dict(dkw, presence=pres)
            if dkw.get("counts") is not None and row:
                # scatter-add accumulates duplicate ids within the chunk
                cnt = dkw["counts"]
                cnt = cnt.at[0, jnp.asarray(row, jnp.int32)].add(1)
                dkw = dict(dkw, counts=cnt)
            if cart is not None and row:
                # re-walk the chunk's tokens through the host transition
                # table so the next chunk resumes at the right FSM state
                # (a handful of numpy lookups per chunk, not per token)
                fsm_host = int(np.asarray(dkw["constraint"][0])[0])
                for t in row:
                    fsm_host = cart.advance(fsm_host, t)
                dkw = dict(dkw, constraint=(
                    jnp.asarray([fsm_host], jnp.int32),
                ) + dkw["constraint"][1:])
            text = self.tokenizer.decode(
                ([first_id] if first_id not in self.cfg.all_stop_ids else [])
                + collected,
                skip_special_tokens=True,
            )
            if any(s in text for s in stop):
                break
            token = jnp.asarray([row[-1]], jnp.int32) if row else token
        out = np.asarray([collected], np.int32)
        n_gen = np.asarray([len(collected)], np.int32)
        step_lps = np.asarray([lps], np.float32) if logprobs else None
        return out, n_gen, step_lps, cache

    # guarded-by: _lock
    def _generate_locked(
        self, prompt, max_tokens, temperature, top_k, top_p, greedy, chat,
        seed, t_start, debug=False, speculative=False, min_p=0.0,
        repetition_penalty=1.0, stop=None, logprobs=False, logit_bias=None,
        frequency_penalty=0.0, presence_penalty=0.0, constraint=None,
        trace=None,
    ):
        # chaos hook (utils/faults.py point "solo"): inside the deadline
        # wrapper, so a wedge_s > deadline rule exercises the abandoned-
        # call path — engine._wedged fills, /ready flips 503 past
        # --wedge-unready, and the router ejects the replica until the
        # sleep drains (the DLI_FAULTS wedge drill in tests/test_router)
        faults.check("solo", tag=prompt)
        cfg = self.cfg
        self.request_count += 1
        bias = self._bias_array(logit_bias)
        cart = self._compile_constraint(constraint) if constraint else None
        if cart is not None:
            bias = self._constraint_bias(cart, bias)
            if trace is not None:
                trace.checkpoint("constraint_compile")
        text = self.render_chat(prompt) if chat else prompt
        ids = self.tokenizer.encode(text)
        prompt_len = len(ids)

        buckets = self._buckets()
        if self._cache is None:
            self._cache = self.backend.init_cache(1, cfg.max_seq_len)
        if self._prefix is not None and not PrefixCache.compatible(self._cache):
            # e.g. the context-parallel backend's slot-tagged cache; checked
            # against the live buffer so a warmup()-initialized cache is
            # covered too
            log.info("prefix_cache_disabled", reason="cache layout")
            self._prefix = None

        # prefix-cache lookup + ingest plan (shared helper; engine/prefix.py)
        p0, entry, plan = self._prefix_plan(self._prefix, ids)
        if plan is None:
            if prompt_len > cfg.max_seq_len - 2:
                raise ValueError(
                    f"prompt length {prompt_len} exceeds the cache capacity "
                    f"(max_seq_len {cfg.max_seq_len} less decode headroom)"
                )
            if (
                buckets
                and prompt_len > buckets[-1]
                and hasattr(self.backend, "extend")
            ):
                raise ValueError(
                    f"prompt length {prompt_len} cannot be chunk-prefilled: "
                    f"no prefill bucket fits the final chunk within "
                    f"max_seq_len {cfg.max_seq_len}"
                )
            raise ValueError(
                f"prompt length {prompt_len} exceeds max prefill bucket "
                f"{buckets[-1] if buckets else 0}"
            )
        n_full, rem, bucket, chunk = plan
        if logprobs and not getattr(self.backend, "supports_logprobs", False):
            raise ValueError(
                f"backend {self.backend.name!r} does not support per-token "
                f"logprobs; serve logprobs requests on the single-device or "
                f"pipeline backend"
            )
        spec_ok = (
            speculative
            and greedy
            # a repetition/OpenAI penalty or logit bias changes the argmax
            # the draft verification compares against — plain decode
            # instead; and the speculative loop records no per-step
            # logprobs
            and repetition_penalty == 1.0
            and frequency_penalty == 0.0
            and presence_penalty == 0.0
            and bias is None
            and not logprobs
        )
        # draft-model speculation wins over prompt-lookup when a draft is
        # attached (helps arbitrary text, not just self-repeating text)
        use_draft = (
            spec_ok
            and self._draft is not None
            and getattr(self.backend, "supports_draft", False)
        )
        use_spec = (
            spec_ok
            and not use_draft
            and getattr(self.backend, "supports_speculative", False)
        )
        max_tokens, decode_bucket = self._clamp_decode(
            prompt_len, max_tokens,
            headroom=SPEC_DRAFT_LEN if (use_spec or use_draft) else 0,
        )

        sampling = G.default_sampling(
            temperature, top_k, top_p, greedy, min_p, repetition_penalty,
            frequency_penalty, presence_penalty,
        )
        # presence (repetition-penalty token set): only materialized when
        # the penalty is on, so the reference-parity path keeps its exact
        # compiled programs
        if repetition_penalty != 1.0 and not getattr(
            self.backend, "supports_presence", False
        ):
            raise ValueError(
                f"backend {self.backend.name!r} does not support "
                f"repetition_penalty; serve penalized requests on the "
                f"single-device or pipeline backend"
            )
        oai_pen = frequency_penalty != 0.0 or presence_penalty != 0.0
        if oai_pen and not getattr(self.backend, "supports_counts", False):
            raise ValueError(
                f"backend {self.backend.name!r} does not support "
                f"frequency_penalty/presence_penalty; serve penalized "
                f"requests on the single-device or pipeline backend"
            )
        presence = (
            self._presence_rows([ids]) if repetition_penalty != 1.0 else None
        )
        key = jax.random.PRNGKey(seed) if seed is not None else self._next_key()
        key_pre, key_dec = jax.random.split(key)

        cache = self._cache
        self._cache = None  # donated below; restored from the decode result
        first, logits, cache = self._ingest_with_prefix(
            self._prefix, ids, p0, entry, plan, cache, key_pre, sampling,
            presence=presence, bias=bias,
        )
        first = jax.block_until_ready(first)
        ttft = time.time() - t_start
        if trace is not None:
            trace.checkpoint("prefill")

        if use_draft:
            dcfg, dparams = self._draft
            dcache = self._draft_cache
            self._draft_cache = None
            if dcache is None:
                dcache = M.init_kv_cache(dcfg, 1, max_seq=cfg.max_seq_len)
            dcache = self._draft_ingest(ids, dcache)
            out, n_gen, cache, dcache = self.backend.decode_draft_speculative(
                dcfg, dparams, first, cache, dcache, jnp.int32(prompt_len),
                jnp.int32(max_tokens - 1), max_steps=decode_bucket,
                draft_len=SPEC_DRAFT_LEN,
            )
            self._draft_cache = dcache
        elif use_spec:
            # H is static per model so the program compiles once
            H = cfg.max_seq_len + SPEC_DRAFT_LEN + 2
            hist = jnp.zeros((1, H), jnp.int32)
            hist = jax.lax.dynamic_update_slice(
                hist, jnp.asarray([ids], jnp.int32), (jnp.int32(0), jnp.int32(0))
            )
            out, n_gen, cache = self.backend.decode_speculative(
                first, cache, hist, jnp.int32(prompt_len),
                jnp.int32(max_tokens - 1), max_steps=decode_bucket,
                draft_len=SPEC_DRAFT_LEN,
            )
        else:
            if presence is not None:
                presence = G.presence_update(presence, first.reshape(1))
            step_lps = None
            dkw = {"presence": presence}
            if oai_pen:
                # OpenAI-penalty state: GENERATED counts only, seeded with
                # the (generated) first token — prompt tokens excluded
                dkw["counts"] = G.count_update(
                    jnp.zeros((1, cfg.vocab_size), jnp.int32),
                    first.reshape(1),
                )
            if bias is not None:  # backends without the kwarg stay untouched
                dkw["bias"] = bias
            if cart is not None:
                # FSM state after the (bias-masked) first token, computed
                # host-side off the already-fetched first id — the decode
                # loop then advances it on device, zero host syncs/token
                fsm0 = cart.advance(cart.start, int(first[0]))
                cm, ct = cart.device_tables()
                dkw["constraint"] = (
                    jnp.asarray([fsm0], jnp.int32), cm, ct
                )
            if stop:
                # textual stops: decode in bounded chunks and quit at the
                # first match instead of burning the full budget on device
                out, n_gen, step_lps, cache = self._decode_textual_stop_chunks(
                    first, cache, prompt_len, max_tokens, key_dec, sampling,
                    dkw, logprobs, stop, cart=cart,
                )
            elif logprobs:
                out, n_gen, cache, step_lps = self.backend.decode(
                    first, cache, jnp.int32(prompt_len),
                    jnp.int32(max_tokens - 1), key_dec, sampling,
                    max_steps=decode_bucket, with_logprobs=True, **dkw,
                )
            else:
                out, n_gen, cache = self.backend.decode(
                    first, cache, jnp.int32(prompt_len),
                    jnp.int32(max_tokens - 1), key_dec, sampling,
                    max_steps=decode_bucket, **dkw,
                )
        out = jax.block_until_ready(out)
        self._cache = cache
        if trace is not None:
            trace.checkpoint("decode")

        gen_ids = self._row_tokens(int(first[0]), out[0], int(n_gen[0]))
        response = self.tokenizer.decode(gen_ids, skip_special_tokens=True)
        response, stopped = self._truncate_at_stop(response, stop)
        if trace is not None:
            trace.checkpoint("detokenize")

        token_logprobs = None
        token_strings = None
        if logprobs:
            # first token: log_softmax of the prefill logits (raw model
            # distribution, OpenAI convention); decode steps recorded by
            # the with_logprobs decode variant. Covers every GENERATED
            # token (textual stop truncation cuts text, not this list).
            import numpy as np

            token_logprobs = []
            if int(first[0]) not in self.cfg.all_stop_ids:
                lp0 = jax.nn.log_softmax(logits[0].astype(jnp.float32))
                token_logprobs.append(round(float(lp0[int(first[0])]), 6))
            if step_lps is not None:
                token_logprobs += [
                    round(float(x), 6)
                    for x in np.asarray(step_lps[0][: int(n_gen[0])])
                ]
            # per-position token text alongside the logprobs (OpenAI's
            # logprobs objects carry both); zip-truncated defensively —
            # gen_ids excludes a terminal EOS exactly when its logprob
            # entry was skipped above
            token_strings = [
                self.tokenizer.decode([t])
                for t, _ in zip(gen_ids, token_logprobs)
            ]

        top_predictions = None
        if debug and logits.shape[-1] > 0:  # 1F1B may return 0-width logits
            from ..ops.sampling import top_n_probs

            probs, tids = top_n_probs(logits, 5)
            top_predictions = [
                {
                    "token": self.tokenizer.decode([int(t)]),
                    "id": int(t),
                    "prob": round(float(p), 5),
                }
                for p, t in zip(probs[0], tids[0])
            ]

        elapsed = time.time() - t_start
        n = len(gen_ids)
        tps = n / elapsed if elapsed > 0 else 0.0
        self._record_sample(ttft, tps, n, elapsed=elapsed)
        log.info(
            "request", model=cfg.name, backend=self.backend.name,
            prompt_len=prompt_len, bucket=bucket, tokens=n,
            ttft_s=round(ttft, 4), tokens_per_sec=round(tps, 2),
            elapsed_s=round(elapsed, 3),
        )
        result = {
            "prompt": prompt,
            "response": response,
            "status": "success",
            "time_taken": f"{elapsed:.2f}s",
            "tokens_generated": n,
            "prompt_tokens": prompt_len,
            "tokens_per_sec": f"{tps:.2f}",
            "ttft_s": round(ttft, 4),
            "backend": self.backend.name,
            # why generation ended, judged against the CLAMPED budget (the
            # requested max_tokens may have been lowered near max_seq_len —
            # the serving edge cannot reconstruct that)
            "finish_reason": (
                "stop" if stopped or n < max_tokens else "length"
            ),
        }
        if p0:
            result["prefix_cached_tokens"] = p0
        if stopped:
            result["stopped"] = True  # a textual stop sequence fired
        if token_logprobs is not None:
            result["token_logprobs"] = token_logprobs
            result["token_strings"] = token_strings
        if use_spec or use_draft:
            result["speculative"] = True
            # which path served (the continuous mixed fleet reports
            # "fleet" with spec_drafted/spec_accepted counts; the solo
            # loops keep acceptance entirely on device and report counts
            # only through tokens_generated)
            result["spec_path"] = "solo"
        if cart is not None:
            result["constrained"] = True
        if use_draft:
            result["draft_model"] = self._draft[0].name
        if top_predictions is not None:
            result["top_predictions"] = top_predictions
        return result

    # -- warmup --------------------------------------------------------------
    def warmup(self, decode_buckets=None, batch_buckets=None) -> dict:
        """Pre-compile every serving program so no request pays jit latency.

        BASELINE.json's target is p50 TTFT — that requires warm-compiled
        caches for every (prefill bucket, decode bucket) shape, not
        compile-on-first-request (SURVEY.md §7 'TTFT < 500 ms' note).
        Covers:
          * one single-stream prefill program per prefill bucket (shared
            with the chunked-prefill final chunk — `pos` is traced);
          * the extend() chunk program when the backend supports chunking
            (single-device AND the SPMD pipeline);
          * one single-stream decode program per decode bucket;
          * the batched/ragged programs — (batch bucket x prefill bucket)
            prefills with a valid_start operand and (batch bucket x decode
            bucket) decodes — when the backend supports ragged batches
            (round-1 gap: the first batched request on a warm server still
            paid a full compile).
        Sampling params are traced scalars, so one program covers every
        temperature/top-k/top-p/greedy combination.

        batch_buckets: None = auto (all of BATCH_BUCKETS when the
        model/backend can serve batches, else none); pass () to skip
        batched warming or a tuple to warm specific batch sizes.

        Returns {"programs": N, "seconds": wall}.
        """
        t0 = time.time()
        decode_buckets = tuple(decode_buckets or DECODE_BUCKETS)
        gran = getattr(self.backend, "batch_granularity", 1)
        if batch_buckets is None:
            can_batch = (
                self.cfg.arch == "llama"
                and getattr(self.backend, "supports_ragged", False)
            )
            # the SAME ladder the request path picks from — fleet-granular
            # backends (gran > 1, always llama: create_backend rejects the
            # rest) warm (g, 2g, ...) instead of the power-of-two buckets
            batch_buckets = batch_buckets_for(gran) if can_batch else ()
        sampling = G.default_sampling(greedy=True)
        key = jax.random.PRNGKey(0)
        n = 0
        buckets = self._buckets()
        if not buckets:
            # an empty bucket layout would leave `first` unset below and
            # crash the decode warm loop with an opaque TypeError
            raise ValueError(
                f"warmup needs at least one prefill bucket <= max_seq_len "
                f"{self.cfg.max_seq_len}; got prefill_buckets="
                f"{self.engine_cfg.prefill_buckets}"
            )
        pad = self.cfg.pad_token_id
        with self._lock:
            # single-stream programs: EVERY backend serves solo requests
            # batch-1 (fleet-granular backends dispatch solo rows to their
            # inherited plain-ring programs), so warm them everywhere
            cache = self._cache or self.backend.init_cache(1, self.cfg.max_seq_len)
            self._cache = None
            first = None
            for bucket in buckets:
                tokens = jnp.full((1, bucket), pad, jnp.int32)
                first, _, cache = self.backend.prefill(
                    tokens, jnp.int32(1), cache, key, sampling
                )
                n += 1
            if hasattr(self.backend, "extend"):
                chunk_tokens = jnp.full((1, buckets[-1]), pad, jnp.int32)
                cache = self.backend.extend(chunk_tokens, jnp.int32(0), cache)
                n += 1
            for db in decode_buckets:
                # limit=0: compiles the while_loop program, executes 0 steps
                _, _, cache = self.backend.decode(
                    first, cache, jnp.int32(1), jnp.int32(0), key, sampling,
                    max_steps=db,
                )
                n += 1
            if getattr(self.backend, "supports_presence", False):
                # repetition-penalty (presence) program variants — 'no
                # request pays jit latency' covers penalized requests too.
                # Single-stream only: batched penalized programs compile on
                # first use (rarer path; the grid would double warmup).
                pres1 = jnp.zeros((1, self.cfg.vocab_size), bool)
                for bucket in buckets:
                    tokens = jnp.full((1, bucket), pad, jnp.int32)
                    first, _, cache = self.backend.prefill(
                        tokens, jnp.int32(1), cache, key, sampling,
                        presence=pres1,
                    )
                    n += 1
                for db in decode_buckets:
                    _, _, cache = self.backend.decode(
                        first, cache, jnp.int32(1), jnp.int32(0), key,
                        sampling, presence=pres1, max_steps=db,
                    )
                    n += 1
            if getattr(self.backend, "supports_logprobs", False):
                # the with_logprobs decode variant compiles separately
                # (static flag adds a logprob buffer to the loop carry)
                for db in decode_buckets:
                    _, _, cache, _ = self.backend.decode(
                        first, cache, jnp.int32(1), jnp.int32(0), key,
                        sampling, max_steps=db, with_logprobs=True,
                    )
                    n += 1
            if self._draft is not None and getattr(
                self.backend, "supports_draft", False
            ):
                # speculative requests route to the DRAFT path when a
                # draft is attached — warm ITS programs (ingest per
                # bucket + the chunked-extend variant + the combined
                # verify loop per decode bucket); the prompt-lookup
                # program would be dead weight
                dcfg, dparams = self._draft
                dcache = self._draft_cache
                self._draft_cache = None
                if dcache is None:
                    dcache = M.init_kv_cache(
                        dcfg, 1, max_seq=self.cfg.max_seq_len
                    )
                for bucket in buckets:
                    dcache = self._draft_ingest([pad] * bucket, dcache)
                    n += 1
                chunked_len = buckets[-1] + 1
                if self._plan_ingest(chunked_len, 0, buckets) is not None:
                    dcache = self._draft_ingest([pad] * chunked_len, dcache)
                    n += 1
                for db in decode_buckets:
                    _, _, cache, dcache = self.backend.decode_draft_speculative(
                        dcfg, dparams, first, cache, dcache, jnp.int32(1),
                        jnp.int32(0), max_steps=db,
                        draft_len=SPEC_DRAFT_LEN,
                    )
                    n += 1
                self._draft_cache = dcache
            elif getattr(self.backend, "supports_speculative", False):
                # speculative programs too — 'no request pays jit latency'
                # includes speculative=true requests
                H = self.cfg.max_seq_len + SPEC_DRAFT_LEN + 2
                hist = jnp.zeros((1, H), jnp.int32)
                for db in decode_buckets:
                    _, _, cache = self.backend.decode_speculative(
                        first, cache, hist, jnp.int32(1), jnp.int32(0),
                        max_steps=db, draft_len=SPEC_DRAFT_LEN,
                    )
                    n += 1
            # jaxlint: disable=blocking-under-lock -- warmup compiles under the engine lock on purpose: no request may interleave half-warmed programs
            jax.block_until_ready(cache)
            self._cache = cache  # first real request reuses the buffer

            # batched/ragged programs. Only the LARGEST warmed bucket's
            # cache is retained afterwards: keeping one per bucket would
            # pin sum(BATCH_BUCKETS) x max_seq of KV in HBM (multi-GB for
            # an 8B-class model) whether or not batched traffic ever
            # arrives — the compile warmth is what matters; reallocating a
            # zeroed cache is cheap next to a compile.
            for Bb in batch_buckets:
                bcache = self._batch_caches.pop(Bb, None)
                if bcache is None:
                    bcache = self.backend.init_cache(Bb, self.cfg.max_seq_len)
                valid_start = jnp.zeros((Bb,), jnp.int32)
                bfirst = None
                for bucket in buckets:
                    tokens = jnp.full((Bb, bucket), pad, jnp.int32)
                    bfirst, _, bcache = self.backend.prefill(
                        tokens, jnp.int32(bucket), bcache, key, sampling,
                        valid_start,
                    )
                    n += 1
                for db in decode_buckets:
                    _, _, bcache = self.backend.decode(
                        bfirst, bcache, jnp.int32(buckets[-1]), jnp.int32(0),
                        key, sampling, valid_start, max_steps=db,
                    )
                    n += 1
                # jaxlint: disable=blocking-under-lock -- warmup compiles under the engine lock on purpose: no request may interleave half-warmed programs
                jax.block_until_ready(bcache)
                self._batch_caches[Bb] = bcache
            for Bb in sorted(batch_buckets)[:-1]:
                self._batch_caches.pop(Bb, None)
        out = {"programs": n, "seconds": round(time.time() - t0, 2)}
        log.info("warmup", **out)
        return out

    # -- batched entry -------------------------------------------------------
    def generate_batch(
        self,
        prompts: list,
        max_tokens: int = 20,
        temperature: float = 0.7,
        top_k: int = 50,
        top_p: float = 0.9,
        greedy: bool = False,
        chat: bool = True,
        seed: Optional[int] = None,
        min_p: float = 0.0,
        repetition_penalty: float = 1.0,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        stop: Optional[list] = None,
        constraint: Optional[dict] = None,
        request_id: Optional[str] = None,
        slo_class: Optional[str] = None,
        deadline_ms: Optional[float] = None,
        _trace: Optional[Trace] = None,
    ) -> dict:
        """One forward fleet for N prompts (shared sampling params).

        Ragged prompts are LEFT-padded to a shared bucket: every row then
        shares one position frame (prefill length == bucket, decode starts
        at bucket), and per-row pad slots are masked via valid_start. RoPE
        is relative, so the uniform per-row shift is harmless — which is
        also why this is llama-family only (GPT-2's learned absolute
        positions are not shift-invariant). The reference can't batch at
        all: one request at a time, batch dim hardcoded to 1
        (/root/reference/orchestration.py:98,144).
        """
        t_start = time.time()
        trace = _trace if _trace is not None else Trace(request_id)

        def locked():
            with self._lock:
                trace.checkpoint("queue_wait")
                # jaxlint: disable=blocking-under-lock -- the engine lock IS the device-serialization point; a generation holds it end to end by design
                return self._generate_batch_locked(
                    prompts, max_tokens, temperature, top_k, top_p, greedy,
                    chat, seed, t_start, min_p, repetition_penalty, stop,
                    frequency_penalty, presence_penalty, constraint, trace,
                )

        with request_id_context(trace.request_id):
            dl_s, dl_type = self._resolve_deadline(deadline_ms)
            if dl_s is not None and dl_s <= 0:
                self._m_deadline_exceeded.inc()
                return self._finish_request(
                    {
                        "error": "Error: request exceeded its deadline_ms "
                        "budget before generation",
                        "status": "failed",
                        "error_type": "deadline_exceeded",
                    },
                    trace, engine="batch",
                )
            try:
                result = self._with_deadline(
                    locked, "generate_batch", deadline_s=dl_s,
                    exceeded_type=dl_type,
                )
                if result.get("error_type") == "deadline_exceeded":
                    self._m_deadline_exceeded.inc()
            except ValueError as e:
                log.warning("invalid_batch_request", error=str(e))
                result = {"error": f"Error: {e}", "status": "failed",
                          "error_type": "invalid_request"}
            except Exception as e:
                log.error("generate_batch_failed", exc_info=True, error=str(e))
                result = {"error": f"Error: {e}", "status": "failed"}
            return self._finish_request(result, trace, engine="batch")

    # guarded-by: _lock
    def _generate_batch_locked(
        self, prompts, max_tokens, temperature, top_k, top_p, greedy, chat,
        seed, t_start, min_p=0.0, repetition_penalty=1.0, stop=None,
        frequency_penalty=0.0, presence_penalty=0.0, constraint=None,
        trace=None,
    ):
        cfg = self.cfg
        if not prompts or not all(isinstance(p, str) and p for p in prompts):
            raise ValueError("prompts must be a non-empty list of non-empty strings")
        if cfg.arch != "llama":
            raise ValueError(
                f"batched generation is llama-family only (left-padding needs "
                f"relative positions); model arch is {cfg.arch!r}"
            )
        if not getattr(self.backend, "supports_ragged", False):
            raise ValueError(
                f"backend {self.backend.name!r} does not support ragged "
                f"batches; serve batches on a ragged-capable backend"
            )
        self.request_count += 1
        B = len(prompts)
        if B > BATCH_BUCKETS[-1]:
            raise ValueError(
                f"batch size {B} exceeds the maximum {BATCH_BUCKETS[-1]}; "
                f"split the request"
            )
        texts = [self.render_chat(p) if chat else p for p in prompts]
        ids = [self.tokenizer.encode(t) for t in texts]
        plens = [len(i) for i in ids]
        bucket, max_tokens, decode_bucket = self._plan(max(plens), max_tokens)

        # pad the batch up to a bucketed size so XLA compiles one program
        # per (B-bucket, prefill-bucket, decode-bucket) triple, not per
        # client batch size; dummy rows are single-pad prompts, sliced off
        # the results below. Fleet-granular backends (1F1B: rows % dp*M
        # == 0) use the granularity ladder — the same one warmup compiles.
        gran = getattr(self.backend, "batch_granularity", 1)
        Bb = G.pick_bucket(batch_buckets_for(gran), B)
        pad = cfg.pad_token_id
        rows = ids + [[pad]] * (Bb - B)
        row_lens = plens + [1] * (Bb - B)
        tokens = jnp.asarray(
            [[pad] * (bucket - n) + row for row, n in zip(rows, row_lens)],
            jnp.int32,
        )
        valid_start = jnp.asarray([bucket - n for n in row_lens], jnp.int32)
        sampling = G.default_sampling(
            temperature, top_k, top_p, greedy, min_p, repetition_penalty,
            frequency_penalty, presence_penalty,
        )
        if repetition_penalty != 1.0 and not getattr(
            self.backend, "supports_presence", False
        ):
            raise ValueError(
                f"backend {self.backend.name!r} does not support "
                f"repetition_penalty; serve penalized requests on the "
                f"single-device or pipeline backend"
            )
        oai_pen = frequency_penalty != 0.0 or presence_penalty != 0.0
        if oai_pen and not getattr(self.backend, "supports_counts", False):
            raise ValueError(
                f"backend {self.backend.name!r} does not support "
                f"frequency_penalty/presence_penalty; serve penalized "
                f"requests on the single-device or pipeline backend"
            )
        presence = (
            self._presence_rows(rows) if repetition_penalty != 1.0 else None
        )
        # shared grammar constraint: all rows decode under the SAME tables
        # (one [S, V] pair broadcast), each row walking its own FSM state
        cart = self._compile_constraint(constraint) if constraint else None
        key = jax.random.PRNGKey(seed) if seed is not None else self._next_key()
        key_pre, key_dec = jax.random.split(key)

        # reusable batch-bucket cache (donated below, restored after decode);
        # stale rows are invisible behind the ragged causal mask
        cache = self._batch_caches.pop(Bb, None)
        if cache is None:
            cache = self.backend.init_cache(Bb, cfg.max_seq_len)
        pkw = {"presence": presence}
        if cart is not None:
            # first-token mask rides the bias operand ([V] broadcasts
            # row-wise), exactly like the solo path
            pkw["bias"] = self._constraint_bias(cart, None)
        if cart is not None and trace is not None:
            trace.checkpoint("constraint_compile")
        first, logits, cache = self.backend.prefill(
            tokens, jnp.int32(bucket), cache, key_pre, sampling, valid_start,
            **pkw,
        )
        first = jax.block_until_ready(first)
        ttft = time.time() - t_start
        if trace is not None:
            trace.checkpoint("prefill")

        # dummy padding rows start "finished" (first token forced to EOS),
        # so the decode loop's all-finished early exit still fires when the
        # real rows are done
        if Bb > B:
            first = first.at[B:].set(cfg.eos_token_id)
        if presence is not None:
            presence = G.presence_update(presence, first)
        counts = None
        if oai_pen:
            # generated-count rows seeded with each row's first token
            # (dummy pad rows got EOS firsts above — they never emit)
            counts = G.count_update(
                jnp.zeros((Bb, cfg.vocab_size), jnp.int32), first
            )
        bkw = {}
        if cart is not None:
            # per-row FSM states after each row's first token (host numpy
            # walk off the already-fetched firsts; dummy pad rows got EOS
            # firsts above — they start finished, their state is inert)
            firsts = np.asarray(first)
            fsm0 = np.asarray(
                [cart.advance(cart.start, int(t)) for t in firsts], np.int32
            )
            cm, ct = cart.device_tables()
            bkw["constraint"] = (jnp.asarray(fsm0), cm, ct)
        out, n_gen, cache = self.backend.decode(
            first, cache, jnp.int32(bucket), jnp.int32(max_tokens - 1),
            key_dec, sampling, valid_start, presence, counts,
            max_steps=decode_bucket, **bkw,
        )
        out = jax.block_until_ready(out)
        if trace is not None:
            trace.checkpoint("decode")
        # keep at most ONE batch cache (the bucket just used): an entry per
        # bucket would re-pin sum(BATCH_BUCKETS) x max_seq of KV in HBM —
        # the footprint warmup's keep-only-largest eviction exists to avoid
        self._batch_caches.clear()
        self._batch_caches[Bb] = cache

        results = []
        total_tokens = 0
        for b in range(B):  # dummy pad rows [B, Bb) sliced off here
            row = self._row_tokens(int(first[b]), out[b], int(n_gen[b]))
            total_tokens += len(row)
            text = self.tokenizer.decode(row, skip_special_tokens=True)
            text, row_stopped = self._truncate_at_stop(text, stop)
            entry = {
                "prompt": prompts[b],
                "response": text,
                "tokens_generated": len(row),
                "prompt_tokens": plens[b],
                "status": "success",
                "finish_reason": (
                    "stop" if row_stopped or len(row) < max_tokens
                    else "length"
                ),
            }
            if row_stopped:
                entry["stopped"] = True
            results.append(entry)
        if trace is not None:
            trace.checkpoint("detokenize")
        elapsed = time.time() - t_start
        tps = total_tokens / elapsed if elapsed > 0 else 0.0
        self._record_sample(ttft, tps / B, total_tokens, elapsed=elapsed,
                            engine="batch")
        self._m_batch_size.labels(engine="batch").observe(B)
        log.info(
            "batch_request", model=cfg.name, backend=self.backend.name,
            batch=B, batch_bucket=Bb, bucket=bucket, tokens=total_tokens,
            ttft_s=round(ttft, 4), aggregate_tokens_per_sec=round(tps, 2),
            elapsed_s=round(elapsed, 3),
        )
        result = {
            "results": results,
            "status": "success",
            "batch_size": B,
            "time_taken": f"{elapsed:.2f}s",
            "tokens_generated": total_tokens,
            "tokens_per_sec": f"{tps:.2f}",
            "ttft_s": round(ttft, 4),
            "backend": self.backend.name,
        }
        if cart is not None:
            result["constrained"] = True
        return result

    # -- perf stats ----------------------------------------------------------
    def stats(self) -> dict:
        """Rolling p50/p90/p99 over recent requests (TTFT seconds,
        tokens/sec) plus the lifetime sample count.

        Snapshot under the samples lock: /stats and /health are served from
        other threads while a generate() may be appending to the deque.
        The percentile formula is utils.metrics.percentile — the SAME one
        the registry histograms use for their window percentiles, and both
        are fed by the one _record_sample seam, so this JSON view and the
        /metrics view agree by construction.
        """
        from ..utils.metrics import percentile as pct

        with self._samples_lock:
            samples = list(self._samples)
            samples_total = self._samples_total

        ttfts = [s["ttft_s"] for s in samples]
        tpss = [s["tokens_per_sec"] for s in samples]
        out = {
            "window": len(samples),
            "samples_total": samples_total,
            "ttft_p50_s": pct(ttfts, 0.5),
            "ttft_p90_s": pct(ttfts, 0.9),
            "ttft_p99_s": pct(ttfts, 0.99),
            "tokens_per_sec_p50": pct(tpss, 0.5),
            "tokens_per_sec_p90": pct(tpss, 0.9),
            "tokens_per_sec_p99": pct(tpss, 0.99),
            "tokens_total": sum(s["tokens"] for s in samples),
        }
        if self._prefix is not None:
            out["prefix_cache"] = self._prefix.stats()
        # exemplars: the metrics -> traces pivot (ISSUE 17). Each latency
        # bucket names the most recent traced request that landed in it,
        # so a p99 outlier in this JSON view links straight to one
        # assembled trace at GET /debug/traces/{trace_id}.
        snap = self.metrics.snapshot()
        exemplars: dict = {}
        for fam in ("dli_ttft_seconds", "dli_tpot_seconds",
                    "dli_request_duration_seconds"):
            for series in snap.get(fam, {}).get("series", []):
                if series.get("exemplars"):
                    exemplars.setdefault(fam, {}).update(
                        series["exemplars"]
                    )
        if exemplars:
            out["exemplars"] = exemplars
        return out

    def drain(self, deadline_s: Optional[float] = None) -> bool:
        """Wait for any in-flight generation to finish (the engine lock is
        held for a whole request). The solo engine has no queue of its own
        — the serving drain path rejects NEW work at the HTTP edge first,
        so once the lock frees the engine is idle. Returns False when the
        deadline expired with a request still running."""
        t0 = time.time()
        while self._lock.locked():
            if deadline_s is not None and time.time() - t0 > deadline_s:
                return False
            time.sleep(0.05)
        return True

    # -- health (reference /health + /workers, orchestration.py:297-329) ----
    def health(self) -> dict:
        out = {
            "status": "healthy",
            "model": self.cfg.name,
            "backend": self.backend.name,
            "n_stages": getattr(self.backend, "n_stages", 1),
            "device": device_summary(),
            "requests_served": self.request_count,
            "stats": self.stats(),
        }
        wedged = self.wedged_info()
        if wedged:
            # an abandoned device call is still holding the backend: new
            # requests will burn their deadline and 503 until it drains —
            # tell the monitor the truth (and how long it has been stuck)
            out["status"] = "degraded"
            out["wedged"] = wedged
        return out

    def workers(self) -> dict:
        stages = self.backend.health()
        if self._lock.locked():
            # a generation holds the device(s): a timed-out probe means
            # "queued behind real work", not unreachable — report busy so
            # monitoring doesn't flap to offline exactly when loaded
            for s in stages:
                if s.get("status") == "offline":
                    s["status"] = "busy"
                    s["error"] = "probe queued behind an in-flight generation"
        return {
            "workers": {f"stage_{s['stage']}": s for s in stages},
            "total": len(stages),
        }
