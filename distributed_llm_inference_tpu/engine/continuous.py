"""Continuous (in-flight) batching: slot-based decode with mid-flight admission.

The serving ladder so far:
  * reference: one request at a time, batch dim hardcoded to 1
    (/root/reference/orchestration.py:98,144);
  * serving/queue.py: dispatch-time coalescing — a burst becomes one ragged
    fleet, but the fleet drains to completion before the next group starts,
    so a long generation head-of-line blocks everything behind it.

Here a fixed fleet of `n_slots` KV-cache rows decodes in lock-step
(engine/generate.py decode_slots — per-row positions, per-slot sampling
params), and a new request is admitted the moment any slot frees: its
prompt prefills on a batch=1 scratch cache (reusing the engine's bucketed /
chunked prefill machinery) and splices into the free row (insert_slot)
while the other slots keep decoding. Decode runs in chunks of `chunk_steps`
with ONE device->host fetch per chunk, and the next chunk is launched
BEFORE the previous chunk's tokens are fetched (lag-1 pipelining), so the
TPU queue never drains on host round-trips: a fetch is a host sync, and
with one chunk already queued behind it the sync overlaps compute.

Attribution discipline: each launched chunk snapshots the slot->request
assignment. A chunk in flight when a slot is freed and re-admitted would
otherwise credit the old tenant's (masked, pad) emissions to the new one.
On the chunked paged scheduler a slot is let again before its old
tenant's last launch is fetched, as soon as the host position model has
seen the row's budget end in a launch already dispatched
(_release_ended): the old tenant is the slot's RETIRING tenant until
that fetch, credited by the snapshot and finalized from the launch's own
active row (ARCHITECTURE.md "Scheduler": who holds a slot).

Backends: the single-device backend runs the fleet as a plain jit
(engine/generate.decode_slots); the pp PipelineBackend runs the same fleet
inside its shard_map ring (parallel/pipeline._build_decode_slots — each
step is S gated microsteps, dp must be 1). Llama AND gpt2 families: slots
need no left-padding (every slot starts at position 0), so gpt2's learned
absolute positions stay exact — the one batching mode gpt2 supports.
Seeded / debug requests fall back to the solo engine — their contracts
(deterministic RNG stream, single-stream prefill logits) are per-request,
not per-fleet. Greedy `speculative` requests run IN-FLEET on ragged paged
chunked fleets (draft-then-verify rows inside the mixed launch — see
"Speculative decoding" below); only fleets without the mixed program
still serve them solo.

Speculative decoding (ISSUE 13; ARCHITECTURE.md "Speculative decoding"):
eligible greedy decode slots submit a [current + K-token draft] VERIFY
row instead of a 1-token decode row in the mixed scheduler launch — the
ragged kernel already serves arbitrary-length rows, so verifying K
drafts costs ~one decode step of weight streaming and emits up to K+1
tokens. Drafts are host-planned n-gram lookups against the slot's own
fetched history (engine/scheduler.ngram_draft; zero extra weights) or,
cfg-gated, a small draft model's device-side greedy chain sharing the
fleet's block tables (engine_cfg.spec_draft_model). Accept/reject is
fully traced (engine/paged.spec_verify — match-prefix + correction token
on device, packed into the existing fetch), the slot's position simply
advances by the accepted count (rejected draft K/V beyond the new
frontier is overwritten before it can be attended or shadow-captured),
and the host position model resyncs from the fetched advance. The
launch metadata is device-derived (ISSUE 15): the kernel reads each
decode/verify row's q_start and per-token positions from the
device-resident slot state (engine/paged.DeviceMeta +
apply_device_meta), so an unfetched verify row never freezes its slot:
every eligible slot submits a verify row EVERY scheduler step, back to
back under lag pipelining, the host drafts from an OPTIMISTIC history
(fetched tokens + its own pending predicted windows — a misprediction
only lowers acceptance, never correctness: the verify accepts only the
model's own argmax), and the packed fetch confirms emissions after the
fact. Per-slot adaptive K (TokenBudgetScheduler.spec_slot_k): an
acceptance-rate EWMA fed from the same fetch sizes each slot's next
draft between 0 and spec_draft_len. Speculated tokens debit
step_token_budget (TokenBudgetScheduler.spec_draft_len), so the SLO
layer throttles K to 0 under decode TPOT pressure — speculation
accelerates idle fleets and self-disables under load. Greedy output is
bit-identical to non-speculative decode (spec_verify replicates
slot_step token for token), crash/preemption salvage included
(unfetched verify emissions drop exactly like unfetched chunks).

Failure containment (ARCHITECTURE.md "Failure containment"): the worker
loop runs under a SUPERVISOR (_loop/_supervise). A crash anywhere in the
scheduler releases every fleet-held resource (block tables decref'd,
constraint rows freed, cached prefix chains dropped), rebuilds the
device-side fleet, and restarts the loop under a bounded consecutive-crash
budget with exponential backoff. Live requests are salvaged: their prompt
and fetched tokens are host-side, so each is re-admitted as a CONTINUATION
prefill (prompt + tokens-so-far) — greedy output across a crash is
bit-identical to a fault-free run. Requests admitted since the last
healthy fetch form the crash SUSPECT set; recovery re-admits one request
per healthy chunk so a recurring crash implicates exactly one suspect,
and a request implicated poison_strikes times is quarantined alone
(error_type "poison") while its fleet-mates survive. Every path is
exercised deterministically in CI via utils/faults.py injection points
(tests/test_faults.py).

Warm-state recovery (ARCHITECTURE.md "Warm recovery"): paged fleets
shadow every FILLED pool block host-side as it becomes immutable
(engine/shadow.py — async device->host copies off the scheduler
thread), so a supervisor restart scatters the shadowed blocks back
into the rebuilt pool, re-learns their block-prefix chains, and each
salvage re-admission re-prefills ONLY its partial tail block instead
of the whole prompt (dli_recovery_tokens_recomputed_total measures
it). Graceful drain persists the shadow to --restore-dir and startup
restores it, so the router's rolling restarts hand replicas back in
with a WARM prefix cache (tests/test_recovery.py chaos matrix).
"""

from __future__ import annotations

import collections
import functools
import json
import os
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.delta_rule import chunks_of
from ..utils import faults
from ..utils.logging import get_logger
from ..utils.metrics import (
    ADMISSION_WAIT_HELP, ATTN_WALK_STEPS_HELP, CHUNK_STEPS_HELP,
    CONV_STATE_RESETS_HELP,
    CONV_TAIL_WRITES_HELP, DECODE_ROW_SECONDS_HELP, DECODE_STEP_HELP,
    DEFAULT_SIZE_BUCKETS, DELTA_CHUNKS_HELP, DELTA_STATE_ROWS_HELP,
    DEVICE_EMPTY_HELP, DIFFUSION_FORWARDS_HELP,
    DIFFUSION_FUSED_HELP, DIFFUSION_TOKENS_HELP, KV_GROUP_BLOCK_BYTES_HELP,
    KV_GROUP_BLOCKS_HELP, KV_WINDOW_GIVEN_HELP, KV_WINDOW_RELEASED_HELP,
    LAUNCH_DEVICE_SECONDS_HELP,
    LAUNCH_DEVICE_STEPS_HELP, LAUNCH_TIMING_HELP, LINEAR_STATE_RESETS_HELP,
    LINEAR_STATE_ROWS_HELP, MOE_PAIRS_HELP,
    PREFIX_STATE_TOKENS_HELP, SLOT_RELEASE_HELP, SLOT_TURNOVER_HELP,
    SPARSE_ROWS_HELP, SPARSE_SCORED_KEYS_HELP, SSM_STATE_RESETS_HELP,
    STEPS_AHEAD_BUCKETS,
)
from ..utils.retry import overload_retry_after
from ..utils.tracing import (
    LaunchTimer, PhaseClock, Trace, abstract_call, sample_decision,
)
from . import generate as G
from .block_prefix import chunk_digests

log = get_logger("continuous")

# _admit_one sentinel: the paged pool has no blocks for this request right
# now — requeue it (front) and retry after the next release
_BLOCKED = object()


class _Request:
    __slots__ = (
        "prompt", "kwargs", "done", "result", "t_start", "ttft",
        "first_id", "tokens", "slot", "enqueued", "budget",
        "stream_q", "streamed_text", "record", "prefix_hit_tokens",
        "cancelled", "prompt_tokens", "block_ids", "need", "cart",
        "trace", "salvaged", "strikes", "allowed", "slo",
        "ids", "shadow_depth", "recovering",
        "deadline_at", "cancel_cause", "preemptions", "preempted_at",
        "resume_seq", "drop_seq", "kv_hint", "fabric_blocks",
        "promoted_blocks",
        "spec_want", "spec_drafted", "spec_accepted", "spec_launches",
        "adapter", "tenant", "adapter_page", "trace_ctx", "profiled",
        "queue_wait_s",
    )

    def __init__(self, prompt: str, kwargs: dict, stream_q=None,
                 request_id=None, kv_hint=None, adapter=None, tenant=None,
                 trace_ctx=None):
        self.prompt = prompt
        # multi-tenant adapter serving (engine/adapters.py): registered
        # adapter name (None = base model), the tenant the request bills
        # against, and — once admitted — the HBM adapter page its launch
        # rows select (0 = the base page; held via the pool's refcount
        # from admission to release)
        self.adapter = adapter
        self.tenant = tenant
        self.adapter_page: Optional[int] = None
        # SLO class name (engine/scheduler.py): resolved against the
        # configured classes at enqueue; drives prefill-budget
        # apportionment, shed decisions, and class-aware Retry-After
        self.slo = kwargs.pop("slo_class", None)
        self.kwargs = kwargs
        # per-request stage trace (utils/tracing.py): queue_wait /
        # admission / decode / detokenize spans + the request id echoed
        # in the response and the X-Request-Id header
        self.trace = Trace(request_id)
        # fleet trace context (ISSUE 17): the SpanContext parsed from the
        # inbound traceparent header (None = untraced request). profiled
        # flips True only when the deterministic per-trace sample
        # decision under engine_cfg.trace_sample_rate says this request
        # gets launch-level attribution spans.
        self.trace_ctx = trace_ctx
        self.profiled = False
        # `queue_wait` stage spans since the last grant, summed over the
        # re-waits of a blocked head; observed into
        # dli_queue_wait_seconds at admission and reset there
        self.queue_wait_s = 0.0
        self.done = threading.Event()
        self.result: Optional[dict] = None
        self.enqueued = time.time()
        self.t_start = self.enqueued
        self.ttft: float = 0.0
        self.first_id: Optional[int] = None
        self.tokens: list[int] = []
        self.slot: Optional[int] = None
        self.budget: int = 0
        # token streaming (NDJSON serving): events land here as chunks
        # process; None = non-streaming request
        self.stream_q = stream_q
        self.streamed_text = ""  # chars already emitted (BPE-safe deltas)
        self.record = True  # False: warmup traffic, kept out of /stats
        self.prefix_hit_tokens = 0  # prompt tokens served from the prefix cache
        self.cancelled = False  # client went away; free the slot early
        self.prompt_tokens = 0  # set at admission (tokenized prompt length)
        self.block_ids = None  # paged mode: this request's pool blocks
        # paged mode: FRESH blocks required after the mapped shared head
        # (set on the 1st admission attempt; drives the head-of-queue
        # backpressure check)
        self.need = None
        # grammar constraint (constrain/): (CompiledConstraint, fleet-table
        # row offset) once admitted; None = unconstrained
        self.cart = None
        # crash recovery (the scheduler supervisor): tokens generated
        # before a scheduler crash, re-prefilled as a continuation on
        # re-admission so greedy decode resumes bit-exactly
        self.salvaged: list[int] = []
        # crash-restarts this request was implicated in (suspect set at
        # crash time); poison_strikes of them quarantine it
        self.strikes = 0
        # total generated-token cap fixed at FIRST admission (clamped
        # max_tokens) — re-admissions shrink their budget against it
        self.allowed: Optional[int] = None
        # warm-recovery shadow bookkeeping (engine/shadow.py): the
        # admitted token sequence (prompt + salvaged continuation — the
        # content the request's pool blocks hold) and how many of its
        # full blocks have been handed to the shadow copier
        self.ids: Optional[list] = None
        self.shadow_depth = 0
        # set while the recovery path re-admits this request — drives
        # the dli_recovery_tokens_recomputed_total accounting
        self.recovering = False
        # end-to-end deadline (deadline_ms on /generate and the OpenAI
        # routes, propagated via X-Request-Deadline-Ms through the
        # router): absolute wall-clock expiry, checked ONLY at launch
        # boundaries on the host (never inside compiled code); None =
        # no per-request deadline (engine_cfg.request_deadline_s still
        # applies as the server-wide cap)
        dl = kwargs.pop("deadline_ms", None)
        self.deadline_at = (
            self.enqueued + float(dl) / 1e3 if dl is not None else None
        )
        # why the cancel flag was flipped (dli_cancelled_total{cause})
        self.cancel_cause = "disconnect"
        # SLO-aware KV preemption (engine_cfg.preempt_policy): how many
        # times this request was evicted mid-decode to make pool room —
        # at max_preemptions_per_req it becomes immune — and when it was
        # last parked (feeds dli_preempted_resume_seconds)
        self.preemptions = 0
        self.preempted_at: float = 0.0
        # swap path: the token sequence whose shadowed chain the resume
        # re-admission restores (None = drop-and-recompute)
        self.resume_seq = None
        # launch-seq barrier: emissions fetched from chunks launched
        # BEFORE this seq are dropped (a preempted victim's in-flight
        # chunks are regenerated after resume, exactly like the crash
        # salvage contract)
        self.drop_seq = 0
        # KV-fabric handoff hint (the router's X-KV-Transfer-* headers):
        # {"peer": url, "digest": hex} naming where this prompt's prefix
        # chain is resident. Consumed on the FIRST admission attempt —
        # retries/requeues/salvages never re-fetch (the first import
        # either landed in the block-prefix index or the fallback is
        # local prefill).
        self.kv_hint = kv_hint
        # blocks imported over the fabric for this request (envelope
        # observability: the router reads it to score handoff outcomes)
        self.fabric_blocks = 0
        self.promoted_blocks = 0
        # speculative decoding (mixed-fleet draft-then-verify): the
        # request asked for it ("speculative": true — fleet-wide
        # engine_cfg.spec_decode makes every eligible greedy request a
        # candidate too), plus per-request draft/accept/launch counts
        # for the envelope
        self.spec_want = bool(kwargs.get("speculative"))
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_launches = 0


class ContinuousEngine:
    """In-flight batching front end over an InferenceEngine's model/backend.

    submit() blocks until the request's envelope is ready (same response
    schema as InferenceEngine.generate, plus "continuous": true and the
    admission depth it shared the fleet with).
    """

    def __init__(
        self,
        engine: Any,
        n_slots: int = 8,
        chunk_steps: int = 16,
        max_queue: Optional[int] = None,
        chunk_lag: int = 2,
        slot_max_seq: Optional[int] = None,
        kv_pool_blocks: Optional[int] = None,
        kv_block_size: int = 16,
        restart_budget: int = 3,
        restart_backoff_s: float = 0.05,
        poison_strikes: int = 2,
        kv_shadow: Optional[bool] = None,
        restore_dir: Optional[str] = None,
    ):
        cfg = engine.cfg
        from ..models.api import FAMILIES

        if cfg.arch not in FAMILIES:
            raise ValueError(
                f"continuous batching supports the families of models/api.py "
                f"({', '.join(FAMILIES)}); model arch is {cfg.arch!r}"
            )
        if cfg.recurrent:
            # (before the dense fleet would be built for it)
            from .paged import refuse_unsupported_latent

            refuse_unsupported_latent(cfg, no_pool=kv_pool_blocks is None)
        if cfg.latent_dim and kv_pool_blocks is None:
            raise ValueError(
                f"{cfg.name}: a latent-attention model's fleet is paged "
                f"(pass kv_pool_blocks): there is no dense latent fleet"
            )
        if not getattr(engine.backend, "supports_slots", False):
            raise ValueError(
                f"backend {engine.backend.name!r} does not support slot "
                f"decode; continuous batching runs on the single-device "
                f"backend or a pp pipeline mesh with dp == 1"
            )
        self.engine = engine
        self.cfg = cfg
        self.backend = engine.backend
        self.n_slots = int(n_slots)
        self.chunk_steps = int(chunk_steps)
        # The waiting room, where none is given: at least a fleet's worth,
        # so a closed loop of twice as many callers as slots is never
        # refused (a caller whose answer ends sends its next request before
        # the freed slot has taken one from the queue).
        self.max_queue = (max(64, 2 * self.n_slots) if max_queue is None
                          else int(max_queue))
        # How many decode chunks may be in flight on the device before the
        # worker blocks on the oldest chunk's fetch. 1 = classic lag-1
        # (fetch N-1 overlaps compute N). Higher absorbs a host-side
        # sync + bookkeeping pass that takes LONGER than a chunk's compute
        # (the device would idle every chunk at lag-1) at the cost of
        # noticing EOS/stop/cancel up to `lag` chunks late — bounded
        # compute waste, never wrong output. Whether a local chip ever
        # needs more than 1 is not measured.
        self.chunk_lag = max(1, int(chunk_lag))
        # Failure containment (the supervisor wrapped around _loop_inner):
        # how many CONSECUTIVE crashes the scheduler absorbs before it
        # declares the fleet dead (a healthy fetch resets the window), the
        # backoff base doubled per consecutive crash, and how many crash
        # implications (suspect-set membership at crash time) quarantine a
        # request as poison.
        self.restart_budget = max(0, int(restart_budget))
        self.restart_backoff_s = float(restart_backoff_s)
        self.poison_strikes = max(1, int(poison_strikes))
        # SLO-aware KV preemption (graceful degradation under memory
        # pressure): when the pool still cannot place an admission after
        # the evict-unreferenced-chains retry, _preempt_for evicts the
        # lowest-SLO-weight / youngest decoding victim instead of
        # stalling the queue (policy: "swap" pushes the victim's filled
        # blocks to the host shadow first, "recompute" drops them,
        # "off" restores the wait-for-release behavior).
        self.preempt_policy = str(engine.engine_cfg.preempt_policy)
        if self.preempt_policy not in ("swap", "recompute", "off"):
            raise ValueError(
                f"preempt_policy must be 'swap', 'recompute', or 'off', "
                f"got {self.preempt_policy!r}"
            )
        self.max_preemptions = max(
            0, int(engine.engine_cfg.max_preemptions_per_req)
        )
        # preempted requests parked for re-admission (served BEFORE the
        # regular queue — a victim must not also lose its queue position)
        self._resume: list[_Request] = []
        self.preempted_total = 0

        # Per-slot KV budget (round-2 review weak #7): the fleet cache pins
        # n_slots x slot_max_seq of KV in HBM for the server's lifetime —
        # at Llama-2-7B/4096/8-slot scale that is ~8.5 GB bf16 BEFORE
        # weights when sized to the model window. slot_max_seq caps the
        # slot class: allocation becomes a function of the configured
        # budget, and admission plans/clamps against it (prompts beyond it
        # are rejected, decode budgets clamped to fit).
        self.slot_max_seq = min(
            int(slot_max_seq or cfg.max_seq_len), cfg.max_seq_len
        )
        # Block-paged KV (engine/paged.py): fleet memory becomes a function
        # of the POOL (aggregate in-flight tokens), not n_slots x window —
        # the round-2 "n_slots x max_seq pinned HBM" review item's stretch
        # goal. Admission allocates blocks, release frees them, and a
        # request that can't get blocks waits in the queue (backpressure).
        # A paged fleet's admissions prefill straight into the pool in
        # fixed-width flat-token launches through the ragged kernel (one
        # program for any prompt length); the prefill-bucket ladder is the
        # dense fleet's.
        self.paged = kv_pool_blocks is not None
        buckets = engine._buckets()
        if not self.paged and buckets and self.slot_max_seq < buckets[0]:
            # the bucketed ingest plan needs at least one prefill bucket
            # inside the slot class — a smaller budget would start a
            # healthy-looking server that rejects EVERY request
            raise ValueError(
                f"slot_max_seq={self.slot_max_seq} is smaller than the "
                f"smallest prefill bucket {buckets[0]}; raise it or shrink "
                f"engine_cfg.prefill_buckets"
            )
        if self.paged:
            if not getattr(engine.backend, "supports_paged", False):
                raise ValueError(
                    f"backend {engine.backend.name!r} does not support "
                    f"paged KV (llama/gpt2 family, single device or a "
                    f"dp=1 pp/tp mesh); drop kv_pool_blocks or use the "
                    f"dense fleet"
                )
            from . import paged as P

            self._P = P
            self.kv_block_size = int(kv_block_size)
            if self.kv_block_size < 1:
                raise ValueError("kv_block_size must be >= 1")
            # logical blocks per slot, and a row's table in tokens (what
            # the gather path reads a row: _kv_walk)
            self._max_blocks = -(-self.slot_max_seq // self.kv_block_size)
            self._scratch_seq = self._max_blocks * self.kv_block_size
            if int(kv_pool_blocks) - 1 < self._max_blocks:
                raise ValueError(
                    f"kv_pool_blocks={kv_pool_blocks} cannot hold one "
                    f"full slot-class request ({self._max_blocks} blocks "
                    f"of {self.kv_block_size} + the trash block); raise it "
                    f"or shrink slot_max_seq"
                )
            self._pool_blocks = int(kv_pool_blocks)
            # A pool grouped by layer kind (cfg.kv_groups: window and
            # global layers in one stack, models/afmoe.py, mimo_v2.py): this
            # allocator, `_table` and `req.block_ids` are the GLOBAL
            # group's, as for every model; the window group has its own
            # free list and tables (engine/paged.WindowBlocks), sized from
            # the same number (`group_blocks`), and a launch carries both
            # tables side by side (`_launch_table`).
            self._wgrp = None
            if len(cfg.kv_groups) > 1:
                from .scheduler import live_width

                # the most tokens ONE row carries in a launch: the axis the
                # model computes, not the tile layout's width
                launch = max(self.chunk_steps, live_width(
                    cfg, self.n_slots, 8,
                    engine.engine_cfg.step_token_budget))
                self._group_blocks = P.group_blocks(
                    cfg, self._pool_blocks, P.window_row_budget(
                        cfg.attn_window, launch, self.kv_block_size),
                    self.n_slots, self.kv_block_size)
                self._wgrp = P.WindowBlocks(
                    self._group_blocks[1], self.n_slots, self._max_blocks,
                    self.kv_block_size, cfg.attn_window, launch,
                )
            # A model whose layers keep a matrix state a slot
            # (cfg.linear_layers: linear attention, state-space mixers), too
            # large to keep one a block: a pool of state snapshots beside the
            # K/V pool, which the prefix index gives out
            # (engine/block_prefix.py). A prompt's prefill leaves its states
            # at its last two whole-block boundaries there (`_snap_points`), and
            # a hit is as deep as the deepest block that has one.
            self._snap_pool = 0
            if cfg.linear_layers:
                self._snap_pool = int(engine.engine_cfg.state_snapshots) \
                    or 2 * self.n_slots
            self.cache = self._init_pool()
            self._alloc = P.BlockAllocator(
                self._pool_blocks, registry=engine.metrics
            )
            # host-side block tables; device copy rebuilt lazily on change
            self._table = np.zeros(
                (self.n_slots, self._max_blocks), np.int32
            )
            self._table_dev = None
            # per-slot adapter page ids (engine/adapters.py): 0 = the
            # base page, set beside the block-table row at admission and
            # zeroed with it at release. Worker-thread-mutated like
            # _table; every paged launch carries a snapshot of it.
            self._slot_pages = np.zeros((self.n_slots,), np.int32)
            # query-tile granularity of the ragged kernel's flat token
            # axis; the launch width rounds up to a whole number of tiles
            self._ragged_tile = 8
            self._ragged_width = -(
                -max(1, int(engine.engine_cfg.ragged_width))
                // self._ragged_tile
            ) * self._ragged_tile
        else:
            self._wgrp = None
            self._ragged_tile = 8
            self._scratch_seq = self.slot_max_seq
            self.cache = self.backend.init_cache(
                self.n_slots, self.slot_max_seq
            )
        # SLO-aware chunked-prefill scheduler (engine/scheduler.py): the
        # ragged paged fleet stops prefilling admissions whole — each
        # scheduler step is ONE mixed launch of every active decode row
        # plus budget-sliced prefill chunks. The TokenBudgetScheduler is
        # built for EVERY fleet mode (its SLO classification, per-class
        # feedback, shed decisions, and class-aware Retry-After apply to
        # admission regardless of ingest strategy); only the step
        # planning needs the mixed ragged program.
        from .scheduler import (
            TokenBudgetScheduler, live_width, parse_slo_classes, step_width,
        )

        self._slo = parse_slo_classes(engine.engine_cfg)
        launch = (cfg, self.n_slots, self._ragged_tile,
                  engine.engine_cfg.step_token_budget)
        self._sched = TokenBudgetScheduler(
            self._slo, engine.engine_cfg.slo_default_class,
            step_width(*launch),
            self._ragged_tile, self.n_slots, registry=engine.metrics,
            tenant_weights=engine.engine_cfg.tenant_weights,
            live_width=live_width(*launch),
        )
        self._chunked = bool(
            self.paged and engine.engine_cfg.chunked_prefill
        )
        # chunked-mode host state: pending PrefillJobs (arrival order),
        # slot -> job for slots whose prompt is still landing, and the
        # host's position model per slot. On a fleet that can speculate
        # (_spec_capable) the kernel reads decode/verify positions from
        # slot state and this model is a LAGGED accounting view (launch
        # entries carry it only as a placeholder; verify fetches catch
        # it up by the accepted advance); with spec_draft_len 0 it must
        # be exact for live rows — it IS the decode tiles' kernel
        # metadata there (over-advance on rows that went inactive since
        # the last fetch is masked garbage, the frozen-row argument)
        self._jobs: list = []
        self._prefilling: dict = {}
        self._host_pos = np.zeros((self.n_slots,), np.int64)
        # the position at which each slot's budget runs out (armed
        # position + req.budget): decode rows the host still launches
        # past it, until the fetch tells it the row has ended, are dead
        # on the device and read no KV (the launch record's kv_tokens)
        self._host_end = np.zeros((self.n_slots,), np.int64)
        self._idle_arm = None
        if self._chunked:
            from . import paged as _P_arm

            self._sched_width = self._sched.width
            # the axis the mixed step's token-wise layers run on
            # (engine/scheduler.live_width; the width itself but where the
            # launch is fleet tiles + budget)
            self._live_width = self._sched.live_width
            self._idle_arm = _P_arm.idle_mixed_arm(
                self.n_slots, cfg.vocab_size
            )
        # Speculative decoding on the mixed fleet (ISSUE 13 + 15):
        # eligible greedy decode slots submit [current + K-draft] verify
        # rows inside the mixed launch. q_start / per-token positions
        # derive ON DEVICE from slot state (engine/paged.DeviceMeta), so
        # verify rows launch EVERY step, back to back; the host keeps a
        # FIFO of pending (unfetched) verify launches per slot
        # (_spec_pending) carrying each launch's predicted window so
        # n-gram drafting continues from the optimistic frontier, plus
        # the advance upper bound for the block-capacity clamp.
        ecfg = engine.engine_cfg
        self._spec_k_max = max(0, int(getattr(ecfg, "spec_draft_len", 0)))
        self._spec_auto = bool(getattr(ecfg, "spec_decode", False))
        self._spec_capable = bool(self._chunked and self._spec_k_max > 0)
        # A model with recurrent layers (cfg.recurrent) keeps a state a slot
        # in the pool, and a state tail a block (cfg.state_tails) or a pool
        # of snapshots (engine/paged.init_pool, StateRows). The host's
        # part: a tenant's first prefill chunk is launched as RAGGED_FIRST,
        # so the program starts the row from zeros or from the tail or
        # snapshot under a prefix hit, never from what the slot's previous
        # tenant left; no row is drafted for (a rejected token would
        # already be in the state).
        self._recurrent = cfg.recurrent
        if self._recurrent or self._wgrp is not None:
            # (a grouped pool: the position model decides which window
            # blocks a row holds, so it has to be exact: no verify rows)
            self._P.refuse_unsupported_latent(
                cfg, spec=self._spec_auto
                or bool(getattr(ecfg, "spec_draft_model", None)),
            )
            self._spec_k_max, self._spec_auto = 0, False
            self._spec_capable = False
        # Generation by diffusion over blocks (cfg.diffusion_block > 0,
        # engine/paged.DiffState): a decode row is its open block, a step
        # is one forward, and every forward reveals some of the block's
        # masked positions; the one that reveals the last emits the
        # block, and the next block's first forward carries the clean
        # block in front of the open one and so commits its K/V. The
        # count a forward reveals is fixed per row at admission, so the
        # host's position model is exact in FORWARDS: for such a fleet
        # `_host_pos[b]` counts the forwards dispatched for the row and
        # `_host_end[b]` the forwards its budget takes (`_blk_*`: the
        # row's committed prompt length, the forwards of its first
        # block, which a prompt's remainder can shorten, and of every
        # later one), and `_blk_at` turns a forward's index into the
        # row's length and what the forward carries.
        self._blk = int(cfg.diffusion_block)
        self._diff = None
        if self._blk:
            if not self._chunked:
                raise ValueError(
                    f"{cfg.name}: a block-diffusion model is served by the "
                    f"chunked ragged paged scheduler (pass kv_pool_blocks; "
                    f"keep chunked_prefill on)"
                )
            if (self.kv_block_size % self._blk
                    or self._ragged_tile % (2 * self._blk)):
                # (a row's forward is one query tile: the owed block and
                # the open one)
                raise ValueError(
                    f"{cfg.name}: kv_block_size ({self.kv_block_size}) and "
                    f"half the query tile ({self._ragged_tile}) must be "
                    f"whole multiples of the diffusion block ({self._blk})"
                )
            # a forward already carries a whole block a row: no drafting
            self._spec_k_max, self._spec_auto = 0, False
            self._spec_capable = False
            self._diff = self._P.init_diffusion(cfg, self.n_slots)
            self._idle_darm = self._P.init_diffusion(cfg, self.n_slots)
            self._denoise_default = int(
                getattr(ecfg, "denoise_steps", 0) or self._blk
            )
            self._check_denoise(self._denoise_default)
        self._blk_base = np.zeros((self.n_slots,), np.int64)
        self._blk_first = np.ones((self.n_slots,), np.int64)
        self._blk_later = np.ones((self.n_slots,), np.int64)
        self._blk_skip = np.zeros((self.n_slots,), np.int64)
        # slot -> FIFO of dicts per unfetched verify launch ({req, nd,
        # pred (drafts + predicted correction, n-gram mode), adv
        # (position-advance upper bound nd + 1)})
        self._spec_pending: dict = {}
        # amortized decode-chunk launches not yet fetched: their
        # emissions are unpredictable many-token advances, so drafting
        # pauses while any are outstanding (positions stay exact either
        # way — they derive on device)
        self._chunk_unfetched = 0
        self._row_inflight = np.zeros((self.n_slots,), np.int64)
        self.spec_launches = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        # verify rows launched while an earlier one was still unfetched
        # — the back-to-back counter the lag-pipelining tests pin
        self.spec_pipelined = 0
        # cfg-gated draft model (the decode_draft_speculative flavor):
        # a small same-tokenizer model proposes drafts device-side,
        # batched over the fleet, over its OWN pool leaves indexed by
        # the SAME block tables — draft KV shares the target pool's
        # allocation lifecycle for free. An attached engine.set_draft()
        # draft takes precedence over loading the named config.
        self._draft_mode = False
        self._dcfg = self._dparams = self._dpool = None
        if self._spec_capable and getattr(ecfg, "spec_draft_model", None):
            if engine._draft is None:
                from ..models.registry import get_model_config

                engine.set_draft(get_model_config(ecfg.spec_draft_model))
            self._dcfg, self._dparams = engine._draft
            if self._dcfg.arch not in ("llama", "gpt2"):
                raise ValueError(
                    f"spec_draft_model must be a llama/gpt2-family config "
                    f"(the paged hook seam); got {self._dcfg.arch!r}"
                )
            self._dpool = self._P.init_pool(
                self._dcfg, self._pool_blocks, self.kv_block_size
            )
            self._draft_mode = True
        self.state, self.sparams = G.init_slots(self.n_slots, cfg.vocab_size)
        # Grammar-constraint fleet state (constrain/): per-slot FSM rows
        # into the COMBINED resident table (row 0 = the free state every
        # unconstrained slot sits at). The table registry is built lazily
        # on the first constrained admission; while any constrained slot
        # is active the worker launches the constrained slot program
        # (decode_slots_constrained — fsm chains device-side between
        # chunks), otherwise the untouched plain program.
        self._fsm = jnp.zeros((self.n_slots,), jnp.int32)
        from ..constrain import FleetConstraintTable

        self._ctable = FleetConstraintTable(
            cfg.vocab_size,
            max_states=engine.engine_cfg.constraint_fleet_states,
            registry=engine.metrics,
        )
        # the dense fleet's scratch matches its logical extent: the insert
        # splices the whole row. A paged fleet prefills straight into the
        # pool and carries no scratch cache at all.
        self._scratch = (
            None if self.paged
            else self.backend.init_cache(1, self._scratch_seq)
        )
        # guarded-by: _cv
        self._assignment: list[Optional[_Request]] = [None] * self.n_slots
        # A slot has one owner (_assignment) and at most one RETIRING
        # tenant: a request whose row the host position model saw end in
        # a launch already dispatched (_release_ended). Its slot was let
        # again at that dispatch; its last emissions and its end arrive
        # with that launch's fetch, attributed by the launch's snapshot.
        # guarded-by: _cv
        self._retiring: list[Optional[_Request]] = [None] * self.n_slots
        # Prefix reuse, one planner per fleet mode (both drive the shared
        # engine._prefix_plan seam):
        #   * paged: block-level sharing (engine/block_prefix.py) — a hit
        #     MAPS the cached physical blocks into the request's table
        #     (refcounted, dedup'd in pool HBM), no snapshot, no splice;
        #   * dense: own PrefixCache instance (engine/prefix.py), NOT
        #     shared with the solo engine's — the solo path touches its
        #     cache under the engine lock while this worker thread runs
        #     lock-free; separate instances cost duplicate snapshots at
        #     worst, never a race.
        self._prefix = None
        self._bpx = None
        if engine.engine_cfg.prefix_cache_entries > 0:
            if self.paged:
                from .block_prefix import BlockPrefixIndex

                side = {} if self._wgrp is None else {
                    "side": self._wgrp.alloc, "window": cfg.attn_window}
                if cfg.linear_layers:
                    side["snapshots"] = self._snap_pool
                self._bpx = BlockPrefixIndex(
                    self._alloc, self.kv_block_size,
                    registry=engine.metrics, **side,
                )
                if self._wgrp is not None:
                    self._wgrp.index = self._bpx
            else:
                from .prefix import PrefixCache

                if PrefixCache.compatible(self._scratch):
                    self._prefix = PrefixCache(
                        engine.engine_cfg.prefix_cache_entries,
                        engine.engine_cfg.prefix_chunk,
                        registry=engine.metrics, scope="continuous",
                    )
                else:
                    log.info("prefix_cache_disabled", reason="cache layout")

        # Warm-state recovery (engine/shadow.py): host-side crash-
        # consistent shadow of filled pool blocks. Requires the paged
        # fleet (block immutability is the consistency argument), the
        # block-prefix index (restore re-enters through the ordinary
        # prefix-hit machinery), and a backend with the shadow
        # gather/scatter programs (the single device AND the pp pipeline
        # — parallel/pipeline's layer-local shard_map twins — so
        # pp-sharded pools recover warm too).
        self._shadow = None
        self._restore_dir = restore_dir
        self._needs_restore = False
        self.shadow_restored_total = 0
        use_shadow = (
            engine.engine_cfg.kv_shadow if kv_shadow is None else kv_shadow
        )
        if self.paged:
            # a pool with routed counts (a latent pool, models/mla_moe.py;
            # the llama family's routed layer) carries neither of these
            self._P.refuse_unsupported_latent(
                cfg, kv_shadow=use_shadow and self._bpx is not None,
                unchunked=not self._chunked,
            )
        if (
            self.paged and use_shadow and self._bpx is not None
            and hasattr(self.backend, "gather_shadow_blocks")
        ):
            from .shadow import ShadowStore

            self._shadow = ShadowStore(
                self.kv_block_size,
                max_blocks=(
                    engine.engine_cfg.kv_shadow_blocks
                    or 2 * self._pool_blocks
                ),
                registry=engine.metrics,
                # tier 2 (ARCHITECTURE.md "Tiered KV"): host-LRU
                # evictions demote into chunk files here instead of
                # dropping, and every shadow read surface promotes hits
                # back out — None keeps the flat PR-9 behavior
                disk_dir=engine.engine_cfg.kv_disk_dir,
                max_disk_blocks=engine.engine_cfg.kv_disk_blocks,
            )
            if restore_dir and self._shadow.load(restore_dir):
                # persisted warm state (a drained predecessor's blocks +
                # chain metadata): restored by the worker thread before
                # it serves anything — same path as the crash restore
                self._needs_restore = True
        # fixed gather width of the shadow capture program: one compiled
        # program serves every capture batch (callers pad by repeating)
        self._shadow_gather_w = 8
        # fixed restore width: restores pad to a multiple of this (pad
        # rows scatter garbage into the write-only TRASH block), so one
        # compiled restore program serves the common case — and it is
        # PRE-WARMED here so a crash's restore never pays jit latency
        # inside the recovery window (same discipline as warmup())
        self._shadow_restore_w = 32
        if self._shadow is not None:
            W = self._shadow_restore_w
            zeros = jax.tree.map(
                lambda pl: jnp.zeros(
                    (W, pl.shape[0]) + pl.shape[2:], pl.dtype
                ),
                self.cache,
            )
            self.cache = self.backend.restore_shadow_blocks(
                self.cache, zeros,
                jnp.zeros((W,), jnp.int32),  # all rows -> trash block
            )
        # Cross-replica KV fabric (serving/kv_fabric.py): this replica's
        # fetch client, plus the serving half's gate. Rides the SAME
        # stack as warm recovery — the shadow store holds the servable
        # chains, the pre-warmed restore program scatters fetched ones,
        # the block-prefix index registers them — so fabric imports are
        # bit-exact by the identical content-key argument.
        self.replica_class = str(engine.engine_cfg.replica_class)
        if self.replica_class not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"replica_class must be 'prefill', 'decode', or 'mixed', "
                f"got {self.replica_class!r}"
            )
        self._fabric = None
        self.fabric_serving = bool(
            self._shadow is not None and engine.engine_cfg.kv_fabric
        )
        if self.fabric_serving:
            from ..serving.kv_fabric import KVFabricClient

            self._fabric = KVFabricClient(
                registry=engine.metrics, role=self.replica_class,
                timeout_s=engine.engine_cfg.kv_fabric_timeout_s,
            )
        # streamed pulls (chunk-at-a-time frames, scatter overlapping
        # the wire) vs the PR-11 whole-manifest pull; and the /health
        # residency-bootstrap cap (MRU-first — the disk tier makes the
        # full resident set unbounded)
        self._fabric_stream = bool(engine.engine_cfg.kv_fabric_stream)
        self._kv_health_digests = max(
            1, int(engine.engine_cfg.kv_health_digests)
        )
        # Paged LoRA adapter serving (engine/adapters.py): the engine's
        # AdapterPool, honored only on fleets whose launch programs can
        # carry the traced pages operand (ragged paged — every other
        # fleet rejects adapter requests at submit with a 400 envelope).
        self._adapters = (
            getattr(engine, "adapters", None)
            if self.paged else None
        )
        self._tenant_max_share = float(
            engine.engine_cfg.tenant_max_queue_share
        )
        self._cv = threading.Condition()
        self._queue: list[_Request] = []  # guarded-by: _cv
        # tenants that have ever queued (guarded-by: _cv) — keeps the
        # per-tenant queue-depth gauge schema stable after they drain
        self._gauge_tenants: set = {""}
        self._closed = False  # guarded-by: _cv
        self._key = jax.random.PRNGKey(int(time.time()) & 0x7FFFFFFF)
        # supervisor state (all worker-thread-mutated; readiness reads are
        # racy-but-monotone flags)
        self._draining = False  # guarded-by: _cv
        self._dead = False        # restart budget exhausted
        self._restarting = False  # mid crash-recovery (readiness = False)
        self._recovery: list[_Request] = []  # salvaged, awaiting re-admission
        # requests admitted since the last healthy fetch — the crash
        # suspect set (see _supervise / _process)
        self._suspects: set = set()
        self._admitting: Optional[_Request] = None
        self._consecutive_crashes = 0
        self._mutation_seq = 0  # bumped per admission; chunks snapshot it
        # Launch-level device-time attribution (ISSUE 17,
        # engine_cfg.trace_sample_rate): launch records appended at
        # dispatch and closed at the matching packed fetch — matched by
        # the launch's own perf_counter timestamp, so lag-pipelined
        # launches attribute correctly with ZERO extra device syncs.
        # At rate 0 (the default) the ONLY hot-path cost is one float
        # compare: _prof_note_launch is never called, the deque stays
        # empty, nothing allocates.
        self._trace_rate = float(engine.engine_cfg.trace_sample_rate)
        self._prof_active = 0  # profiled requests seen in the last launch
        self._launch_log: collections.deque = collections.deque()
        # The launch record (ISSUE 24): ONE dict per launch, built at the
        # dispatch seam from what the loop already holds (_launch_record)
        # and closed at the matching fetch. It feeds the counters, the
        # `launch.<phase>` profiler annotation, the flight `plan` event
        # and the sampled per-tenant span above — always on, no device
        # read. _launch_seq numbers launches; _steps_inflight is the
        # scheduler steps dispatched and not yet fetched.
        self._launch_seq = 0
        self._steps_inflight = 0
        # a profiler session's record of the step programs it dispatched
        # (`trace_step_programs`); None outside one
        self._step_calls: Optional[dict] = None
        # scheduler steps dispatched since the loop began, and per slot
        # the count at which the position model put its row's last live
        # step (None: no row ended there with a queue waiting, or the
        # next tenant's first prefill chunk has been dispatched) — what
        # dli_slot_turnover_steps measures from
        self._steps_dispatched = 0
        self._ended_at: list[Optional[int]] = [None] * self.n_slots
        # uniform sliding window only: a per-layer pattern has no one
        # width, and is counted as full attention (overstates there)
        self._kv_window = (
            self.cfg.attn_window
            if self.cfg.attn_window_pattern == "all"
            and self.cfg.attn_window_layer_types is None else None
        )
        # a stack of window and global layers (cfg.layer_types) is counted
        # per kind: (layers, window) of each, the global kind first
        self._kv_kinds = None
        if self.cfg.kinds_of_attention:
            self._kv_window = None
            slide = sum(k == "sliding_attention"
                        for k in self.cfg.layer_types)
            if self.cfg.attn_window and slide:
                self._kv_kinds = ((self.cfg.n_layers - slide, None),
                                  (slide, self.cfg.attn_window))
        # sparse attention layers (models/minicpm_sala.py) read a SELECTED
        # set of blocks past the dense length: the position model counts
        # what is read (`_kv_span`, `_kv_walk`) and, beside it, what a range
        # walk would (`_sparse_fields`)
        self._sparse = self.cfg if self.cfg.sparse_layers else None
        # whether attention reads the pool through the paged kernels'
        # block walk (_kv_walk) or gathers whole tables
        self._kv_walks = self.paged and self.cfg.attn_impl == "pallas"
        # pages a loop step of the walk folds, by step program: the decode
        # chunk's tile is one query (a block-diffusion row's owed and open
        # blocks), a mixed launch's the scheduler's
        self._walk_pages = {}
        if self._kv_walks:
            self._walk_pages = {
                "chunk": self._walk_pages_of(2 * self._blk or 1),
                "mixed": self._walk_pages_of(self._ragged_tile),
            }
        # observability
        self.admitted = 0  # guarded-by: _cv
        self.completed = 0  # guarded-by: _cv
        self.peak_occupancy = 0  # guarded-by: _cv
        self.restarts_total = 0
        self.recovered_total = 0
        self.poisoned_total = 0
        # registry families (engine.metrics — the one registry /metrics
        # scrapes): fleet occupancy, queue depth, admission waits, chunk
        # launch-to-fetch step time, preemptions
        m = engine.metrics
        m.gauge(
            "dli_slots_total", "continuous-fleet decode slots"
        ).labels().set(self.n_slots)
        self._m_occupied = m.gauge(
            "dli_slots_occupied", "continuous-fleet slots serving a request"
        ).labels()
        self._m_depth = m.gauge(
            "dli_queue_depth", "requests waiting for dispatch", ("queue",)
        ).labels(queue="continuous")
        self._m_admission_wait = m.histogram(
            "dli_admission_wait_seconds", ADMISSION_WAIT_HELP, ("queue",),
        ).labels(queue="continuous")
        # its two halves (ISSUE 24), observed together with it, once per
        # admission: the request's accumulated `queue_wait` stage spans
        # and its `admission` span (utils/tracing.Trace.checkpoint)
        self._m_queue_wait = m.histogram(
            "dli_queue_wait_seconds",
            "enqueue until a slot and pool blocks were granted (re-waits "
            "of a blocked head included)", ("queue",),
        ).labels(queue="continuous")
        self._m_prefill = m.histogram(
            "dli_prefill_seconds",
            "grant until the request's first token was fetched (chunked "
            "prefill shares its steps with the decode rows)", ("queue",),
        ).labels(queue="continuous")
        self._m_blocked = m.counter(
            "dli_admission_blocked_total",
            "scheduler iterations that left the head of the queue "
            "waiting, by what it waited for", ("reason",),
        )
        self._m_slot_release = m.counter(
            "dli_slot_release_total", SLOT_RELEASE_HELP, ("by",),
        )
        self._m_turnover = m.histogram(
            "dli_slot_turnover_steps", SLOT_TURNOVER_HELP,
            buckets=STEPS_AHEAD_BUCKETS,
        ).labels()
        self._m_step = m.histogram(
            "dli_decode_step_seconds", DECODE_STEP_HELP, ("engine",),
        ).labels(engine="continuous")
        self._m_preempt = m.counter(
            "dli_preemptions_total",
            "slots killed before their budget drained", ("reason",),
        )
        self._m_shed = m.counter(
            "dli_queue_shed_total", "requests shed with 429", ("queue",)
        ).labels(queue="continuous")
        # multi-tenant admission quota (family pre-registered in
        # engine/engine.py): requests shed because one tenant's queued
        # share crossed engine_cfg.tenant_max_queue_share
        self._m_tenant_shed = m.counter(
            "dli_tenant_shed_total",
            "requests shed with 429 by the per-tenant queue quota",
            ("tenant",),
        )
        # graceful-degradation families (pre-registered in
        # engine/engine.py): preempt->resume latency, cancellations by
        # cause, deadline overruns
        self._m_resume_s = m.histogram(
            "dli_preempted_resume_seconds",
            "preemption to successful re-admission latency",
        ).labels()
        self._m_cancelled = m.counter(
            "dli_cancelled_total",
            "requests cancelled before completion", ("cause",),
        )
        self._m_deadline_exceeded = m.counter(
            "dli_deadline_exceeded_total",
            "requests failed by their end-to-end deadline_ms",
        ).labels()
        self._m_restarts = m.counter(
            "dli_scheduler_restarts_total",
            "continuous-scheduler supervisor restarts", ("engine",),
        ).labels(engine="continuous")
        self._m_recovered = m.counter(
            "dli_requests_recovered_total",
            "in-flight requests re-admitted (continuation prefill) after "
            "a scheduler restart", ("engine",),
        ).labels(engine="continuous")
        self._m_poison = m.counter(
            "dli_poison_requests_total",
            "requests quarantined as poison after repeated crash "
            "implication", ("engine",),
        ).labels(engine="continuous")
        self._m_drain = m.histogram(
            "dli_drain_duration_seconds",
            "graceful-drain wall time (SIGTERM / drain())", ("component",),
        ).labels(component="continuous")
        # warm-recovery accounting (families pre-registered in
        # engine/engine.py): how much prefill each salvage re-admission
        # actually recomputed (warm recovery bounds it by the partial
        # tail block) and how many shadowed blocks restores scattered
        # back into rebuilt pools
        self._m_recovery_recomputed = m.counter(
            "dli_recovery_tokens_recomputed_total",
            "prompt tokens re-prefilled for crash-recovery re-admissions "
            "(warm recovery bounds this by the partial tail block)",
            ("engine",),
        ).labels(engine="continuous")
        self._m_shadow_restored = m.counter(
            "dli_shadow_restored_blocks_total",
            "shadowed blocks scattered back into a rebuilt pool "
            "(supervisor restart or --restore-dir start)",
        ).labels()
        # ragged-ingest observability (families pre-registered in
        # engine/engine.py for schema stability): launch composition,
        # padding overhead, exact-depth reuse, compiled-program gauge
        self._m_ragged_rows = m.counter(
            "dli_ragged_rows_total",
            "ragged-launch rows by kind (prefill chunk / decode token)",
            ("kind",),
        )
        self._m_ragged_tiles = m.counter(
            "dli_ragged_tiles_total",
            "ragged-launch query tiles by liveness (live / pad — a pad "
            "tile is one program that walks no KV block)", ("state",),
        )
        mixed_tokens = m.counter(
            "dli_mixed_tokens_total",
            "flat tokens of mixed scheduler launches: live (a decode, verify "
            "or prompt token) and computed (the axis the token-wise layers "
            "ran on: engine/scheduler.live_width)", ("state",),
        )
        self._m_mixed_tokens = {
            s: mixed_tokens.labels(state=s) for s in ("live", "computed")}
        self._m_ragged_launches = m.counter(
            "dli_ragged_launches_total",
            "launches by program: ragged ingest (extend / prefill) and "
            "scheduler steps (mixed / chunk)", ("phase",),
        )
        self._m_ragged_exact = m.counter(
            "dli_ragged_exact_prefix_hits_total",
            "prefix hits reused at exact chunk depth (no bucket "
            "degradation — the ragged path's planner win)",
        ).labels()
        self._m_ragged_programs = m.gauge(
            "dli_ragged_compiled_programs",
            "compiled ragged ingest programs (flat after warmup = no "
            "per-tail-shape recompile)",
        ).labels()
        # chunked-prefill scheduler families (pre-registered in
        # engine/engine.py): mixed-launch composition — how much of each
        # step's flat-token budget went to decode rows vs prefill chunks
        self._m_sched_tokens = m.counter(
            "dli_sched_step_tokens_total",
            "flat tokens launched by the chunked-prefill scheduler, by "
            "kind (decode rows / prefill chunk tokens)", ("kind",),
        )
        self._m_sched_chunks = m.counter(
            "dli_sched_prefill_chunks_total",
            "prefill chunks interleaved into mixed scheduler launches",
        ).labels()
        self._m_sched_rows = m.counter(
            "dli_sched_decode_rows_total",
            "decode rows carried by scheduler launches (a pure-decode "
            "chunk counts its row-steps)",
        ).labels()
        # the derived launch width (engine/scheduler.step_width), set once;
        # how far it engages is on the launch record (tiles / tiles_live)
        if self._chunked:
            m.gauge(
                "dli_sched_step_width_tokens",
                "flat-token width of the mixed scheduler launch in the kernel's "
                "tile layout (derived from the model unless step_token_budget is "
                "set; the axis the model computes is /stats scheduler.live_width)",
            ).labels().set(self._sched_width)
        # launch-record families (ISSUE 24, pre-registered in
        # engine/engine.py): KV positions attention had to read against
        # those the kernels' block loops walked, how much work was dispatched
        # ahead of each launch, and where the worker thread's time went
        self._m_kv_tokens = m.counter(
            "dli_attn_kv_tokens_total",
            "KV positions per layer and KV head: attended = the fewest "
            "the launch's rows need (host position model, window-"
            "clipped), walked = what the kernels' block loops cover; a "
            "fleet with sparse attention layers adds visible = every "
            "position at or below the query (what a range walk reads) and "
            "selected = what the selection lets it read (attended, again)",
            ("phase", "state"),
        )
        self._m_walk_steps = m.counter(
            "dli_attn_walk_steps_total", ATTN_WALK_STEPS_HELP, ("phase",),
        )
        self._m_chunk_steps = m.counter(
            "dli_decode_chunk_steps_total", CHUNK_STEPS_HELP, ("state",),
        )
        # routed experts (a latent pool's "routed" leaf, models/mla_moe.py):
        # its shape [2, expert layers, experts], or None for a model
        # without them. The counts ride each launch's packed fetch.
        routed = self.cache.get("routed") if self.paged else None
        self._routed_shape = None if routed is None else tuple(routed.shape)
        # one chip's share of the experts: the counts' last column is the
        # pairs routed to experts held elsewhere (models/afmoe.py)
        self._expert_share = bool(
            routed is not None and self.cfg.experts_held < self.cfg.n_experts
        )
        self._m_moe_pairs = m.counter(
            "dli_moe_pairs_total", MOE_PAIRS_HELP, ("where",),
        )
        self._m_group_blocks = m.gauge(
            "dli_kv_group_blocks", KV_GROUP_BLOCKS_HELP, ("group", "state"),
        )
        self._m_win_released = m.counter(
            "dli_kv_window_blocks_released_total", KV_WINDOW_RELEASED_HELP,
        ).labels()
        self._m_win_given = m.counter(
            "dli_kv_window_blocks_given_total", KV_WINDOW_GIVEN_HELP,
        ).labels()
        if self.paged:
            block_bytes = m.gauge(
                "dli_kv_group_block_bytes", KV_GROUP_BLOCK_BYTES_HELP,
                ("group",))
            for group, leaves in zip(self.cfg.kv_groups,
                                     self._P.GROUP_LEAVES):
                held = [self.cache[n] for n in leaves if n in self.cache]
                if held:  # (a latent pool has other leaves and no groups)
                    block_bytes.labels(group=group).set(sum(
                        a.nbytes // a.shape[1]
                        for a in jax.tree.leaves(held)))
        self._groups_noted = 0.0
        self._note_groups()
        self._m_moe_tokens = m.counter(
            "dli_moe_expert_tokens_total",
            "token-expert pairs the routed experts computed", ("phase",),
        )
        self._m_moe_touched = m.counter(
            "dli_moe_experts_touched_total",
            "experts that got at least one token, summed over expert "
            "layers and scheduler steps", ("phase",),
        )
        self._m_moe_slots = m.counter(
            "dli_moe_expert_slots_total",
            "expert layers x experts x scheduler steps launched: what "
            "dli_moe_experts_touched_total is a share of", ("phase",),
        )
        self._m_diff_forwards = m.counter(
            "dli_diffusion_row_forwards_total", DIFFUSION_FORWARDS_HELP,
            ("kind",),
        )
        if self._blk:
            # no forward only commits: the kind stays, and is scraped, at 0
            self._m_diff_forwards.labels(kind="commit")
        self._m_diff_fused = m.counter(
            "dli_diffusion_fused_commits_total", DIFFUSION_FUSED_HELP,
        ).labels()
        self._m_diff_tokens = m.counter(
            "dli_diffusion_tokens_total", DIFFUSION_TOKENS_HELP,
        ).labels()
        self._m_state_tokens = m.counter(
            "dli_prefix_state_tokens_total", PREFIX_STATE_TOKENS_HELP,
        ).labels()
        self._m_conv_resets = m.counter(
            "dli_conv_state_resets_total", CONV_STATE_RESETS_HELP,
        ).labels()
        self._m_conv_tails = m.counter(
            "dli_conv_tail_writes_total", CONV_TAIL_WRITES_HELP,
        ).labels()
        self._m_lin_resets = m.counter(
            "dli_linear_state_resets_total", LINEAR_STATE_RESETS_HELP,
        ).labels()
        self._m_ssm_resets = m.counter(
            "dli_ssm_state_resets_total", SSM_STATE_RESETS_HELP,
        ).labels()
        # the one a cold start of this fleet counts, by what its layers keep
        self._m_state_resets = {
            (True, False): self._m_conv_resets,
            (False, True): self._m_lin_resets,
            (True, True): self._m_ssm_resets,
        }.get((bool(cfg.conv_layers), bool(cfg.linear_layers)))
        self._m_sparse_rows = m.counter(
            "dli_sparse_rows_total", SPARSE_ROWS_HELP, ("branch",),
        )
        self._m_lin_rows = m.counter(
            "dli_linear_state_rows_total", LINEAR_STATE_ROWS_HELP, ("state",),
        )
        self._m_ck_scored = m.counter(
            "dli_sparse_scored_keys_total", SPARSE_SCORED_KEYS_HELP,
        ).labels()
        if cfg.sparse_layers:
            for branch in ("dense", "sparse"):
                self._m_sparse_rows.labels(branch=branch)
        if cfg.linear_layers:
            for state in ("touched", "held"):
                self._m_lin_rows.labels(state=state)
        # delta-rule layers (cfg.delta_layers): the row-steps whose state
        # the rule's program moved and the chunks their tokens were cut into
        self._m_delta_rows = m.counter(
            "dli_delta_state_rows_total", DELTA_STATE_ROWS_HELP, ("phase",),
        )
        self._m_delta_chunks = m.counter(
            "dli_delta_chunks_total", DELTA_CHUNKS_HELP, ("phase",),
        )
        self._m_steps_ahead = m.histogram(
            "dli_launch_steps_ahead",
            "scheduler steps dispatched and unfetched when a launch was "
            "dispatched", ("phase",), buckets=STEPS_AHEAD_BUCKETS,
        )
        self._clock = PhaseClock(
            m.counter(
                "dli_worker_phase_seconds_total",
                "wall time of the scheduler's worker thread by phase "
                "(contiguous: the phases sum to the thread's life)",
                ("phase",),
            ),
            m.counter(
                "dli_device_empty_seconds_total", DEVICE_EMPTY_HELP,
                ("phase",),
            ),
        )
        # the device's time a launch as the worker can know it (ISSUE 53):
        # told at every fetch, always on, no device read of its own
        self._timer = LaunchTimer(
            m.counter("dli_launch_device_seconds_total",
                      LAUNCH_DEVICE_SECONDS_HELP, ("phase",)),
            m.counter("dli_launch_device_steps_total",
                      LAUNCH_DEVICE_STEPS_HELP, ("phase",)),
            m.counter("dli_launch_timing_total", LAUNCH_TIMING_HELP,
                      ("phase", "state")),
            m.counter("dli_decode_row_seconds_total",
                      DECODE_ROW_SECONDS_HELP, ("phase",)),
        )
        # fleet speculative-decoding families (pre-registered in
        # engine/engine.py): draft/accept/reject token flow, verify-row
        # launches by draft source, tokens-per-launch distribution
        self._m_spec_drafted = m.counter(
            "dli_spec_drafted_tokens_total",
            "draft tokens submitted in mixed-launch verify rows",
        ).labels()
        self._m_spec_accepted = m.counter(
            "dli_spec_accepted_tokens_total",
            "draft tokens accepted (matched the model's own argmax and "
            "were emitted)",
        ).labels()
        self._m_spec_rejected = m.counter(
            "dli_spec_rejected_tokens_total",
            "draft tokens rejected by the traced verify",
        ).labels()
        self._m_spec_launches = m.counter(
            "dli_spec_launches_total",
            "verify rows launched inside mixed scheduler steps, by draft "
            "source", ("mode",),
        )
        self._m_spec_hist = m.histogram(
            "dli_spec_tokens_per_launch",
            "tokens emitted per verify row (accepted drafts + the "
            "correction token; > 1 is the speculation win)",
            buckets=DEFAULT_SIZE_BUCKETS,
        ).labels()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="continuous-engine"
        )
        self._thread.start()

    # -- client side ---------------------------------------------------------
    def _needs_solo(self, kwargs: dict) -> bool:
        """Contracts slots cannot honor (deterministic RNG stream, single-
        stream prefill logits, per-token logprob buffers) run solo on the
        wrapped engine — one condition shared by submit() and stream().
        Speculative requests run IN-FLEET on ragged paged chunked fleets
        (draft-then-verify rows inside the mixed launch; non-greedy /
        penalized ones simply decode plainly there) — the solo fallback
        remains only for seeded/debug contracts and for fleets without
        the mixed program."""
        if (
            kwargs.get("seed") is not None
            or bool(kwargs.get("debug"))
            or (bool(kwargs.get("speculative")) and not self._spec_capable)
            or bool(kwargs.get("logprobs"))
            # slots share one sampling program; a per-request [V] bias
            # isn't in the slot params
            or bool(kwargs.get("logit_bias"))
            # beam search is its own batched program
            or int(kwargs.get("num_beams", 1) or 1) > 1
        ):
            return True
        if kwargs.get("constraint") is not None:
            # constrained slots need the constrained slot program (dense
            # fleet only in this PR — the paged pool falls back) and a
            # fleet table the DFA can ever fit; otherwise the solo engine
            # serves the constraint with its own per-request tables
            if self.paged or not getattr(
                self.backend, "supports_constrained_slots", False
            ):
                return True
            try:
                art = self.engine._compile_constraint(kwargs["constraint"])
            except ValueError:
                return True  # solo re-raises into the 400 envelope
            if not self._ctable.fits(art):
                return True
        return False

    def _note_queue_locked(self):  # guarded-by: _cv
        """Refresh the global + per-(SLO class, tenant) queue-depth
        gauges (caller holds the lock). One helper so every queue
        mutation keeps both views consistent. Tenants ever seen stay in
        the gauge schema (so a drained tenant's series reads 0, not its
        stale last value)."""
        self._m_depth.set(len(self._queue))
        counts: dict = {}
        for r in self._queue:
            t = r.tenant or ""
            self._gauge_tenants.add(t)
            counts[(r.slo, t)] = counts.get((r.slo, t), 0) + 1
        for name in self._slo:
            for t in self._gauge_tenants:
                self._sched.set_depth(
                    name, counts.get((name, t), 0), tenant=t
                )

    def _class_depth_locked(self, cls_name: str) -> int:  # guarded-by: _cv
        return sum(1 for r in self._queue if r.slo == cls_name)

    def _cancel_env(self, req: _Request) -> dict:
        """The cancelled envelope (HTTP 499 at the edge; the router
        never re-dispatches it) + the cause-labeled counter."""
        self._m_cancelled.labels(cause=req.cancel_cause).inc()
        return {
            "error": "Error: request cancelled", "status": "failed",
            "error_type": "cancelled",
        }

    def _deadline_env(self, req: _Request, where: str = "") -> dict:
        """The deadline_exceeded envelope (HTTP 504 at the edge; the
        router never re-dispatches it — the budget is the REQUEST's
        property, not the replica's)."""
        self._m_deadline_exceeded.inc()
        suffix = f" {where}" if where else ""
        return {
            "error": f"Error: request exceeded its deadline_ms "
            f"budget{suffix}",
            "status": "failed",
            "error_type": "deadline_exceeded",
        }

    @staticmethod
    def _past_deadline(req: _Request, now: Optional[float] = None) -> bool:
        return req.deadline_at is not None and (
            now if now is not None else time.time()
        ) >= req.deadline_at

    def _enqueue(self, req: _Request) -> Optional[dict]:
        """Admit a request to the bounded queue. Returns an error envelope
        (caller delivers it OUTSIDE any lock — a streaming caller yields to
        a possibly-slow socket write) or None on success.

        SLO admission control (engine/scheduler.py): the request's class
        resolves here; a full queue AND an over-target sheddable class
        both shed with 429, and in BOTH cases Retry-After derives from
        the CLASS's queue drain estimate (depth x observed per-request
        service time), never the global queue depth — a deep batch
        backlog must not tell an interactive client to stay away."""
        cls = self._sched.classify(req.slo)
        req.slo = cls.name
        if self._past_deadline(req):
            # fail-fast: an already-expired request must not spend a
            # prefill launch or a single pool block (tests assert zero
            # allocations for these)
            return self._deadline_env(req, where="before admission")
        with self._cv:
            if self._closed:
                return {
                    "error": "Error: server shutting down", "status": "failed",
                    "error_type": "overloaded",
                }
            if self._draining:
                # graceful drain: the serving edge maps this to HTTP 503
                # with a Retry-After header — the load balancer's cue to
                # take this replica out while in-flight work finishes
                return {
                    "error": "Error: server draining", "status": "failed",
                    "error_type": "draining",
                }
            class_depth = self._class_depth_locked(cls.name)
            if len(self._queue) >= self.max_queue:
                log.warning("queue_full", depth=len(self._queue),
                            slo_class=cls.name)
                self.engine.flight.record(
                    "shed", reason="queue_full",
                    request_id=req.trace.request_id,
                    depth=len(self._queue), slo_class=cls.name,
                )
                self._m_shed.inc()
                self._sched.count_shed(cls.name)
                return {
                    "error": f"Error: request queue full ({self.max_queue})",
                    "status": "failed",
                    "error_type": "overloaded",
                    "slo_class": cls.name,
                    "retry_after_s": self._sched.retry_after_s(
                        cls, class_depth
                    ),
                }
            if req.tenant is not None and self._tenant_max_share < 1.0:
                # tenant quota: one tenant's queued share of the bounded
                # queue is capped (beyond a small absolute floor — the
                # share is meaningless at tiny depths) so a tenant
                # flooding the queue sheds before OTHER tenants start
                # eating 429s off the global queue-full check
                from .scheduler import MIN_SHED_DEPTH

                t_depth = sum(
                    1 for r in self._queue if r.tenant == req.tenant
                )
                t_cap = max(
                    MIN_SHED_DEPTH,
                    int(self.max_queue * self._tenant_max_share),
                )
                if t_depth >= t_cap:
                    log.warning(
                        "tenant_shed", tenant=req.tenant, depth=t_depth,
                        cap=t_cap, slo_class=cls.name,
                    )
                    self.engine.flight.record(
                        "shed", reason="tenant_quota",
                        request_id=req.trace.request_id,
                        tenant=req.tenant, depth=t_depth, cap=t_cap,
                    )
                    self._m_shed.inc()
                    self._m_tenant_shed.labels(tenant=req.tenant).inc()
                    return {
                        "error": (
                            f"Error: tenant {req.tenant!r} is at its "
                            f"queue quota ({t_cap} of {self.max_queue})"
                        ),
                        "status": "failed",
                        "error_type": "overloaded",
                        "slo_class": cls.name,
                        "tenant": req.tenant,
                        "retry_after_s": self._sched.retry_after_s(
                            cls, class_depth
                        ),
                    }
            if self._sched.should_shed(cls, class_depth):
                # the class's drain estimate already overruns its TTFT
                # target: admitting would burn prefill budget on a
                # request whose SLO is unmeetable — shed it now with the
                # class-local horizon
                log.warning(
                    "slo_shed", slo_class=cls.name, depth=class_depth,
                    ttft_target_s=cls.ttft_target_s,
                )
                self.engine.flight.record(
                    "shed", reason="slo_drain",
                    request_id=req.trace.request_id,
                    slo_class=cls.name, depth=class_depth,
                )
                self._m_shed.inc()
                self._sched.count_shed(cls.name)
                return {
                    "error": (
                        f"Error: {cls.name} queue drain estimate exceeds "
                        f"the {cls.ttft_target_s:g}s TTFT target"
                    ),
                    "status": "failed",
                    "error_type": "overloaded",
                    "slo_class": cls.name,
                    "retry_after_s": self._sched.retry_after_s(
                        cls, class_depth
                    ),
                }
            self._queue.append(req)
            self._note_queue_locked()
            self._cv.notify_all()
        return None

    def _adapter_reject(self, adapter, kwargs) -> Optional[dict]:
        """400-style envelope for adapter requests the fleet cannot
        serve — no attached pool (the fleet is not ragged-paged or
        engine_cfg.adapter_slots is 0), an unregistered adapter name, or
        a solo-contract request (the solo engine serves only the one
        merged/base model — runtime adapter selection lives in the
        fleet's paged launch programs). None = serveable."""
        if adapter is None:
            return None

        def env(msg):
            return {
                "error": f"Error: {msg}", "status": "failed",
                "error_type": "invalid_request", "adapter": adapter,
            }

        if self._adapters is None:
            return env(
                "adapter serving needs the ragged paged fleet with an "
                "attached adapter pool (engine_cfg.adapter_slots > 0)"
            )
        if not self._adapters.is_registered(adapter):
            return env(f"unknown adapter {adapter!r}")
        if self._needs_solo(kwargs):
            return env(
                "adapter requests cannot combine with solo-engine "
                "contracts (seed / debug / logprobs / logit_bias / "
                "beams / constraints)"
            )
        return None

    def submit(self, prompt: str, **kwargs) -> dict:
        # KV-fabric handoff surface (serving/kv_fabric.py): the hint is
        # consumed at admission; prefill_only serves the disaggregation
        # handshake's phase 1 — prefill (and shadow) the prompt, sample
        # one token, and only answer once the shadow copies have LANDED,
        # so the decode-class replica's immediate fetch finds the chain
        # resident instead of racing the copier thread.
        kv_hint = kwargs.pop("kv_hint", None)
        kv_push_to = kwargs.pop("kv_push_to", None) or None
        trace_ctx = kwargs.pop("trace_ctx", None)
        adapter = kwargs.pop("adapter", None) or None
        tenant = kwargs.pop("tenant", None) or None
        err = self._adapter_reject(adapter, kwargs)
        if err is not None:
            return err
        prefill_only = bool(kwargs.pop("prefill_only", False))
        if prefill_only:
            kwargs["max_tokens"] = 1
        err = self._diffusion_reject(kwargs)
        if err is not None:
            return err
        if self._needs_solo(kwargs):
            return self.engine.generate(prompt, **kwargs)
        req = _Request(prompt, kwargs,
                       request_id=kwargs.pop("request_id", None),
                       kv_hint=kv_hint, adapter=adapter, tenant=tenant,
                       trace_ctx=trace_ctx)
        if trace_ctx is not None and trace_ctx.sampled:
            req.profiled = sample_decision(
                trace_ctx.trace_id, self._trace_rate
            )
        err = self._enqueue(req)
        if err is not None:
            return err
        req.done.wait()
        if prefill_only and isinstance(req.result, dict):
            if self._shadow is not None:
                self._shadow.flush(timeout_s=10.0)
            req.result.setdefault("prefill_only", True)
            if kv_push_to:
                # proactive chain push (the handoff's phase 1.5): the
                # chain is resident NOW — POST it to the decode replica
                # the router pre-picked, so phase 2's admission finds
                # the prefix host-resident instead of round-tripping a
                # pull. Any failure silently keeps the pull fallback.
                pushed = self._fabric_push(req, kv_push_to)
                if pushed:
                    req.result["kv_pushed"] = pushed
        return req.result

    def stream(self, prompt: str, **kwargs):
        """Generator of streaming events for one request.

        Yields `{"delta": str, "tokens_so_far": N}` as decode chunks land
        (first event after prefill, then one per chunk with new tokens) and
        finally the standard response envelope (with "done": true). The
        caller iterates on its own thread (e.g. an HTTP handler writing
        NDJSON lines); the worker thread pushes into a per-request queue.

        Seeded / debug requests cannot stream (they run solo on the
        wrapped engine, which decodes entirely on-device) — one final
        envelope event is yielded instead. Speculative requests stream
        normally on spec-capable fleets (verify-row emissions land per
        fetched step, like any chunk).
        """
        kv_hint = kwargs.pop("kv_hint", None)
        trace_ctx = kwargs.pop("trace_ctx", None)
        adapter = kwargs.pop("adapter", None) or None
        tenant = kwargs.pop("tenant", None) or None
        err = self._adapter_reject(adapter, kwargs)
        if err is not None:
            yield {**err, "done": True}
            return
        err = self._diffusion_reject(kwargs)
        if err is not None:
            yield {**err, "done": True}
            return
        if self._needs_solo(kwargs):
            out = self.engine.generate(prompt, **kwargs)
            out["done"] = True
            yield out
            return
        import queue as _queue

        q: _queue.Queue = _queue.Queue()
        req = _Request(prompt, kwargs, stream_q=q,
                       request_id=kwargs.pop("request_id", None),
                       kv_hint=kv_hint, adapter=adapter, tenant=tenant,
                       trace_ctx=trace_ctx)
        if trace_ctx is not None and trace_ctx.sampled:
            req.profiled = sample_decision(
                trace_ctx.trace_id, self._trace_rate
            )
        err = self._enqueue(req)  # error yielded OUTSIDE the engine lock:
        if err is not None:  # the consumer may block on a slow socket write
            yield {**err, "done": True}
            return
        try:
            while True:
                ev = q.get()
                yield ev
                if ev.get("done"):
                    return
        finally:
            # consumer abandoned the generator mid-stream (client socket
            # dropped, handler called close()): cancel so the slot frees
            # for queued requests instead of decoding to its full budget
            if not req.done.is_set():
                self.cancel(req)

    def cancel(self, req: _Request, cause: str = "disconnect"):
        """Cancel a request: dequeue it if still waiting (queue or the
        preemption resume queue), or flag it for the worker to kill its
        slot — and free its blocks/constraint row — at the next launch
        boundary. `cause` labels dli_cancelled_total."""
        req.cancel_cause = cause
        with self._cv:
            if req in self._queue or req in self._resume:
                if req in self._queue:
                    self._queue.remove(req)
                    self._note_queue_locked()
                else:
                    self._resume.remove(req)
                req.result = self._cancel_env(req)
                self._push_final(req)
                return
            req.cancelled = True
            # wake the worker: a cancel must free the slot within one
            # scheduler step even when nothing else is queued
            self._cv.notify_all()

    def _stream_tokens(self, req: _Request, final: bool = False, pre=None):
        """Push the not-yet-streamed suffix of req's text (worker thread).

        Deltas are computed on the FULL decoded text, and text ending in
        U+FFFD is held back until more tokens arrive: a multi-byte grapheme
        whose bytes straddle a chunk boundary decodes to a replacement char
        now and the real character later AT THE SAME LENGTH, so streaming
        it would make the joined deltas diverge from the final response.
        final=True flushes everything (a genuine trailing U+FFFD included)
        so concat(deltas) == response exactly. Text past a textual stop
        sequence is never streamed — and because a stop string may SPAN a
        chunk boundary, the last max(len(stop))-1 characters are held back
        until the next chunk resolves them (vLLM-style hold-back); the
        final flush emits exactly up to the truncation.
        pre: optional (gen_ids, text, hit) from the caller's _gen_text —
        avoids re-decoding the full sequence per chunk."""
        gen_ids, text, _ = pre if pre is not None else self._gen_text(req)
        if not gen_ids:
            return
        if not final:
            text = text.rstrip("�")
            stop = req.kwargs.get("stop") or ()
            hold = max((len(s) for s in stop if s), default=0) - 1
            if hold > 0:
                text = text[: max(len(req.streamed_text), len(text) - hold)]
        if len(text) > len(req.streamed_text):
            delta = text[len(req.streamed_text):]
            req.streamed_text = text
            req.stream_q.put({"delta": delta, "tokens_so_far": len(gen_ids)})

    @property
    def ready(self) -> bool:
        """Load-balancer readiness: False while draining, while the
        supervisor is mid-restart, or once the scheduler is closed or
        dead. Liveness (/health, process up) is deliberately separate —
        a restart-looping scheduler is alive but should take no new
        traffic."""
        return not (
            self._draining or self._restarting or self._dead or self._closed
        )

    def _work_pending(self) -> bool:
        """Anything the fleet still owes a response for: queued, assigned
        to a slot, mid-admission (popped from the queue but not yet
        spliced — invisible to both), or salvaged awaiting re-admission."""
        return bool(
            self._queue
            or any(r is not None for r in self._assignment)
            or any(r is not None for r in self._retiring)
            or self._admitting is not None
            or self._recovery
            or self._resume
        )

    def drain(self, deadline_s: Optional[float] = None) -> bool:
        """Graceful drain: stop admitting NEW requests (draining envelope
        → HTTP 503 + Retry-After at the serving edge), then wait for the
        queue and every in-flight slot to finish, up to deadline_s.
        Returns True when fully drained; stragglers past the deadline are
        failed by the caller's close(). Idempotent."""
        t0 = time.time()
        self.engine.flight.record("drain", deadline_s=deadline_s)
        with self._cv:
            self._draining = True
            self._cv.notify_all()
        drained = True
        with self._cv:
            while self._work_pending():
                if self._closed or self._dead:
                    # a dead scheduler cannot drain its backlog; close()
                    # already failed (or will fail) the stragglers
                    drained = not self._work_pending()
                    break
                left = (
                    None if deadline_s is None
                    else deadline_s - (time.time() - t0)
                )
                if left is not None and left <= 0:
                    drained = False
                    break
                self._cv.wait(
                    timeout=0.1 if left is None else min(left, 0.1)
                )
        if self._shadow is not None and self._restore_dir:
            # warm handoff for the respawn (the router's rolling-restart
            # path): persist the shadow — blocks + chain metadata — so
            # `--restore-dir` starts the successor with a warm
            # block-prefix cache instead of a cold pool
            try:
                self._shadow.flush(timeout_s=5.0)
                self._shadow.save(self._restore_dir)
            except Exception as e:  # noqa: BLE001 - a failed persist only
                log.error("shadow_persist_failed", error=str(e))  # colder
        self._m_drain.observe(time.time() - t0)
        log.info(
            "continuous_drained", ok=drained,
            seconds=round(time.time() - t0, 3),
        )
        return drained

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._thread.join(timeout=10)
        fail = {
            "error": "Error: server shutting down", "status": "failed",
            "error_type": "overloaded",
        }
        with self._cv:
            pending = self._queue[:] + self._resume[:]
            self._queue.clear()
            self._resume.clear()
            self._note_queue_locked()
        for req in pending + [
            r for r in self._retiring + self._assignment if r is not None
        ]:
            if req.result is None:
                req.result = dict(fail)
            self._push_final(req)
        if self._shadow is not None:
            self._shadow.close()

    @staticmethod
    def _snapshot(host_array: np.ndarray):
        """Device COPY of host state that later admissions mutate in place
        (the block table, the per-slot adapter pages). jnp.asarray may
        alias a numpy buffer zero-copy on the CPU backend, and launches
        are asynchronous: an in-flight launch would then read the NEXT
        admission's table row — a finished slot's lagged decode row wrote
        its garbage K/V through the new occupant's row, into the cached
        prefix blocks that row maps. jnp.array copies, but the copy
        itself may still be pending when it returns (a host-to-device
        transfer reads its source until it completes), and a slot's row
        is rewritten right after the launch that carries its last step
        is dispatched (_release_ended): the snapshot is therefore taken
        on the host first, into an array nothing else ever holds."""
        return jnp.asarray(host_array.copy())

    def warmup(self) -> dict:
        """Compile the slot programs (scratch prefill for the smallest
        bucket, insert_slot, decode_slots chunk, pack_chunk) by serving one
        real throwaway request through the fleet. The wrapped engine's
        warmup() separately covers every prefill bucket — together no
        client request pays jit latency (p50-TTFT discipline)."""
        t0 = time.time()
        req = _Request(
            "warmup",
            dict(max_tokens=self.chunk_steps + 2, greedy=True, chat=False),
        )
        # compile-only traffic: its multi-second jit TTFT must not land in
        # /stats (it would skew the very p50 TTFT warmup exists to protect)
        # nor count as a served request
        req.record = False
        err = self._enqueue(req)
        if err is not None:
            return {"ok": False, "seconds": 0.0, **err}
        req.done.wait()
        out = {
            "ok": (req.result or {}).get("status") == "success",
            "seconds": round(time.time() - t0, 2),
        }
        log.info("continuous_warmup", **out)
        return out

    def stats(self) -> dict:
        with self._cv:
            out = {
                "slots": self.n_slots,
                "occupied": sum(r is not None for r in self._assignment),
                "queued": len(self._queue),
                "admitted": self.admitted,
                "completed": self.completed,
                "peak_occupancy": self.peak_occupancy,
                "chunk_steps": self.chunk_steps,
            }
        out["preemption"] = {
            "policy": self.preempt_policy,
            "max_per_request": self.max_preemptions,
            "preempted_total": self.preempted_total,
            "parked": len(self._resume),
        }
        out["supervisor"] = {
            "ready": self.ready,
            "draining": self._draining,
            "dead": self._dead,
            "restarts": self.restarts_total,
            "recovered": self.recovered_total,
            "poisoned": self.poisoned_total,
            "consecutive_crashes": self._consecutive_crashes,
            "restart_budget": self.restart_budget,
        }
        if self.paged:
            out["paged"] = {
                "block_size": self.kv_block_size,
                "pool_blocks": self._alloc.n_blocks,
                "free_blocks": self._alloc.free_blocks,
                "shared_blocks": self._alloc.shared_blocks,
                "cached_blocks": (
                    self._bpx.stats()["cached_blocks"]
                    if self._bpx is not None else 0
                ),
                "ragged_width": self._ragged_width,
            }
            if self._wgrp is not None:
                # (the numbers above are the global group's)
                out["paged"]["window_group"] = {
                    "pool_blocks": self._wgrp.alloc.n_blocks,
                    "free_blocks": self._wgrp.alloc.free_blocks,
                    "row_budget": self._wgrp.row_budget,
                    "released_blocks": self._wgrp.released,
                    "given_blocks": self._wgrp.given,
                    **(self._bpx.side_stats() if self._bpx is not None
                       else {}),
                }
        if self._shadow is not None:
            out["shadow"] = {
                **self._shadow.stats(),
                "restored_blocks": self.shadow_restored_total,
            }
        if self._fabric is not None:
            out["kv_fabric"] = {
                **self._fabric.stats(),
                "serving": self.fabric_serving,
            }
        if self._adapters is not None:
            out["adapters"] = self._adapters.stats()
        out["slo"] = {
            "default": self._sched.default_name,
            "classes": {
                name: {
                    "ttft_target_s": c.ttft_target_s,
                    "tpot_target_s": c.tpot_target_s,
                    "weight": c.weight,
                    "sheddable": c.sheddable,
                    "ttft_ewma_s": self._sched.feedback[name].ttft_ewma,
                    "tpot_ewma_s": self._sched.feedback[name].tpot_ewma,
                }
                for name, c in self._slo.items()
            },
        }
        if self._chunked:
            out["scheduler"] = {
                "chunked_prefill": True,
                "step_width": self._sched_width,
                "live_width": self._live_width,
                "tile": self._ragged_tile,
                "prefilling": len(self._jobs),
            }
        if self._spec_capable:
            out["speculative"] = {
                "mode": "draft_model" if self._draft_mode else "ngram",
                "draft_len": self._spec_k_max,
                "fleet_wide": self._spec_auto,
                "launches": self.spec_launches,
                "drafted_tokens": self.spec_drafted,
                "accepted_tokens": self.spec_accepted,
                "inflight_rows": sum(
                    len(v) for v in self._spec_pending.values()
                ),
                # verify rows launched while an earlier one was still
                # unfetched — >0 proves lag-pipelined speculation
                "pipelined_launches": self.spec_pipelined,
            }
        cstats = self._ctable.stats()
        if cstats["resident"]:
            out["constraints"] = cstats
        if self._prefix is not None:
            out["prefix_cache"] = self._prefix.stats()
        elif self._bpx is not None:
            out["prefix_cache"] = self._bpx.stats()
        return out

    # -- worker thread -------------------------------------------------------
    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _loop(self):
        """Supervisor: a scheduler crash is recoverable and request-
        scoped, not fleet-fatal. Each exception out of _loop_inner goes
        through one _supervise round — release every fleet-held resource,
        strike/quarantine suspects, rebuild the fleet, re-admit salvaged
        requests — under a bounded consecutive-crash budget with
        exponential backoff. A dead worker must never hang clients: the
        give-up path fails everything with clean envelopes."""
        while True:
            try:
                self._loop_inner()
                return  # clean exit: close() flipped _closed
            except Exception as e:  # noqa: BLE001 - contained by the supervisor
                if not self._supervise(e):
                    return

    def _casualties(self) -> list:
        """Detach every live in-flight request (plus the one mid-
        admission, if any) from the dead fleet. Order: running tenants
        first, the just-admitting request last — recovery re-admits in
        this order, so vindicated tenants re-enter before the newest
        (most suspicious) one."""
        with self._cv:
            # a retiring tenant's last launch died unfetched: salvaged
            # from its fetched tokens like any tenant (oldest, so first)
            running = [
                r for r in self._retiring + self._assignment
                if r is not None and not r.done.is_set()
            ]
            self._assignment = [None] * self.n_slots
            self._retiring = [None] * self.n_slots
            admitting, self._admitting = self._admitting, None
        # chunked-prefill state dies with the fleet: jobs' requests are
        # casualties above (they sat in _assignment from job start), and
        # progress resets — the rebuilt pool holds none of their chunks,
        # so recovery re-plans each salvage from its last durable
        # boundary (zero; `done` was chunk-aligned by construction)
        self._jobs = []
        self._prefilling = {}
        self._host_pos[:] = 0
        self._host_end[:] = 0
        self._ended_at = [None] * self.n_slots
        # speculation bookkeeping dies with the fleet too: unfetched
        # verify rows are unfetched launches (their emissions drop, the
        # salvage record holds fetched tokens only — same contract);
        # pending windows and the chunk-fetch gate reset with them
        self._spec_pending.clear()
        self._chunk_unfetched = 0
        self._row_inflight[:] = 0
        if (
            admitting is not None and admitting not in running
            and not admitting.done.is_set()
        ):
            running.append(admitting)
        return running

    def _release_fleet_resources(self, reqs: list):
        """Return every device/host resource the dead fleet holds:
        constraint-table rows, paged pool blocks, cached block-prefix
        chains, block-table rows. Shared by the restart and the give-up
        paths — leaking these on loop death (blocks never decref'd, rows
        never freed) was the failure mode this layer exists to fix."""
        for req in reqs:
            if req.cart is not None:
                self._ctable.release(req.cart[0].key)
                req.cart = None
            if self.paged and req.block_ids is not None:
                self._alloc.decref(req.block_ids)
                req.block_ids = None
            req.adapter_page = None
        if self._adapters is not None:
            # adapter-page refcounts reset wholesale: every holder was
            # detached above, and the device content SURVIVES the crash
            # (the lora leaves live in params, never in a donated launch
            # buffer) — recovery re-admissions re-acquire still-resident
            # pages for free
            self._adapters.reset_refs()
        if self._bpx is not None:
            # cached chains point into the pool buffer the rebuild below
            # replaces — drop them (and the index's refs) wholesale
            self._bpx.clear()
        if self._wgrp is not None:
            self._wgrp.reset()  # (the index dropped its references above)
        if self.paged:
            self._table[:] = 0
            self._table_dev = None
            self._slot_pages[:] = 0
            if self._alloc.outstanding:
                # the explicit releases above must zero the books; a
                # mismatch is an accounting bug — surface it loudly, then
                # reset so the restarted fleet has no phantom holders
                log.error(
                    "kv_pool_leak_on_crash",
                    outstanding=self._alloc.outstanding,
                )
                self._alloc.reset()

    def _rebuild_fleet(self):
        """Fresh device-side fleet state for the restarted loop. Buffers
        the crashed iteration may have donated mid-program (fleet cache /
        pool, scratch) are rebuilt outright — cheaper than proving a
        half-executed donation chain left them intact. The dense prefix
        cache keeps its snapshots (standalone arrays, never donated)."""
        if self.paged:
            self.cache = self._init_pool()
            self._table = np.zeros(
                (self.n_slots, self._max_blocks), np.int32
            )
            self._table_dev = None
        else:
            self.cache = self.backend.init_cache(
                self.n_slots, self.slot_max_seq
            )
        self._scratch = (
            None if self.paged
            else self.backend.init_cache(1, self._scratch_seq)
        )
        self.state, self.sparams = G.init_slots(
            self.n_slots, self.cfg.vocab_size
        )
        self._fsm = jnp.zeros((self.n_slots,), jnp.int32)
        if self._blk:
            self._diff = self._P.init_diffusion(self.cfg, self.n_slots)
        if self._draft_mode:
            # the draft pool is rebuilt outright like the target pool
            # (it may have been donated mid-crash); its content is pure
            # draft-quality state — recovered tenants re-prefill it
            # through the ordinary admission fill
            self._dpool = self._P.init_pool(
                self._dcfg, self._pool_blocks, self.kv_block_size
            )

    def _init_pool(self):
        """The fleet's zeroed pool; with a state a slot where the model has
        recurrent layers (engine/paged.init_pool)."""
        state = {"n_slots": self.n_slots} if self.cfg.recurrent else {}
        if self.cfg.linear_layers:
            state["n_snapshots"] = self._snap_pool
        blocks = self._pool_blocks if self._wgrp is None \
            else self._group_blocks
        return self.backend.init_paged_pool(
            blocks, self.kv_block_size, **state
        )

    def _launch_table(self):
        """The block tables a launch carries: the global group's, with the
        window group's beside it on the block axis where the pool has one
        (engine/paged._group_table cuts them apart again)."""
        if self._wgrp is None:
            return self._snapshot(self._table)
        return self._snapshot(
            np.concatenate([self._table, self._wgrp.table], axis=1))

    def _note_groups(self, every: float = 0.5):
        """dli_kv_group_blocks, at most every `every` seconds (the cached
        count walks the index)."""
        now = time.monotonic()
        if not self.paged or now - self._groups_noted < every:
            return
        self._groups_noted = now
        bpx = self._bpx
        groups = [("global", self._alloc,
                   bpx.evictable_blocks() if bpx is not None else 0)]
        if self._wgrp is not None:
            groups.append(("window", self._wgrp.alloc,
                           bpx.side_evictable() if bpx is not None else 0))
        for group, alloc, cached in groups:
            g = self._m_group_blocks
            g.labels(group=group, state="free").set(alloc.free_blocks)
            g.labels(group=group, state="cached").set(cached)
            g.labels(group=group, state="live").set(
                alloc.outstanding - cached)

    def _window_ensure(self, rows) -> dict:
        """Before a launch is built: a window-group block for every
        position the launch's rows write ((slot, first position, tokens)
        each; a pool of one group has nothing to do). Returns the launch
        record's fields of the group's turnover: the blocks given now, and
        those the rows give back once the launch is dispatched
        (`_window_release`, which the record comes before)."""
        if self._wgrp is None:
            return {}
        before = self._wgrp.given
        for b, start, n in rows:
            self._wgrp.ensure(b, start, n)
        given = self._wgrp.given - before
        if given:
            self._m_win_given.inc(given)
            self._table_dev = None
        return {
            "window_blocks_given": given,
            "window_blocks_released": sum(
                self._wgrp.releasable(b, start + n - 1)
                for b, start, n in rows),
        }

    def _window_release(self, rows):
        """After the launch's dispatch: each row gives back the window
        blocks no later query of it can read. The launch holds its own
        snapshot of the tables, and the device runs launches in order."""
        if self._wgrp is None:
            return
        before = self._wgrp.released
        for b, start, n in rows:
            self._wgrp.release_below(b, start + n - 1)
        if self._wgrp.released != before:
            self._m_win_released.inc(self._wgrp.released - before)
            self._table_dev = None

    def _shadow_capture(self, req: _Request, written: Optional[int] = None):
        """Hand req's newly FILLED pool blocks to the shadow copier
        (worker thread; engine/shadow.py). `written` = tokens known to
        be in the pool for this row (mid-chunked-prefill callers pass
        job progress); None derives it from the fetched token stream —
        the last sampled token's K/V is not yet written, hence the -1.
        The gather is dispatched AFTER the launch that filled the
        blocks (device execution order makes the bytes final); only the
        enqueue happens here, the device->host copy runs on the shadow
        thread — the scheduler loop never blocks."""
        if self._shadow is None or req.block_ids is None or req.ids is None:
            return
        if req.adapter is not None:
            # adapter-conditioned KV never enters the shadow: the store
            # (and the fabric it serves) keys chains by TOKEN CONTENT
            # alone, and an adapter's KV differs from the base model's
            # for the same tokens — persisting it would poison warm
            # restores and cross-replica imports with wrong-model bytes
            return
        bs = self.kv_block_size
        if written is None:
            head = (
                [req.first_id]
                if req.first_id is not None
                and req.first_id not in self.cfg.all_stop_ids else []
            )
            gen = head + req.tokens
            written = len(req.ids) + max(0, len(gen) - 1)
            seq_tokens = req.ids + gen
        else:
            seq_tokens = req.ids
        full = min(written // bs, len(req.block_ids))
        if full <= req.shadow_depth:
            return
        # chaos hook BEFORE the dedup: a repeat prompt whose blocks are
        # all resident must still exercise the shadow_copy drill
        faults.check("shadow_copy", tag=req.prompt)
        new_keys, new_blocks = [], []
        for i in range(req.shadow_depth, full):
            key = tuple(seq_tokens[: (i + 1) * bs])
            if not self._shadow.has(key):
                new_keys.append(key)
                new_blocks.append(int(req.block_ids[i]))
        req.shadow_depth = full
        if not new_keys:
            return
        W = self._shadow_gather_w
        for off in range(0, len(new_keys), W):
            keys = new_keys[off : off + W]
            ids = new_blocks[off : off + W]
            padded = ids + [ids[-1]] * (W - len(ids))  # one program, any n
            dev = self.backend.gather_shadow_blocks(
                self.cache, jnp.asarray(padded, jnp.int32)
            )
            self._shadow.put_async(
                keys, jax.tree.leaves(dev), self._mutation_seq
            )

    def _restore_shadow(self) -> int:
        """Scatter shadowed chains back into a FRESH pool (one restore
        launch) and register them into the block-prefix index, so
        salvage re-admissions — and post-restart traffic — hit them
        through the ordinary prefix machinery. Runs on the worker
        thread strictly BEFORE any re-admission (start of _loop_inner),
        under the supervisor: a crash mid-restore is contained like any
        scheduler crash, the partial registration is released by the
        next round's clear(), and the restore simply runs again (the
        double-fault drill in tests/test_recovery.py). Returns blocks
        restored."""
        if self._shadow is None or self._bpx is None:
            return 0
        # pending captures from before the crash land first, so the
        # restore depth is deterministic (the chaos matrix depends on it)
        self._shadow.flush(timeout_s=10.0)
        faults.check("shadow_copy", tag="restore")
        # leave one slot-class of headroom: restored chains are
        # evictable (refcount 1, index-held), but admission should not
        # have to evict just to place the first request
        budget = self._alloc.free_blocks - self._max_blocks
        entries, leaf_keys = self._shadow.select(budget)
        if not entries:
            return 0
        blocks = self._alloc.alloc(len(entries))
        if blocks is None:
            return 0
        bs = self.kv_block_size
        # pad to the fixed restore width (pre-warmed program): pad rows
        # repeat row 0's data and scatter it into the write-only TRASH
        # block — same discard as ungated pp microsteps
        W = self._shadow_restore_w
        pad = (-len(entries)) % W
        ids_padded = blocks + [self._P.TRASH_BLOCK] * pad
        try:
            stacked = []
            for i in range(len(entries[0][1].leaves)):
                arr = np.stack([e.leaves[i] for _, e in entries])
                if pad:
                    arr = np.concatenate(
                        [arr, np.repeat(arr[:1], pad, axis=0)]
                    )
                stacked.append(jnp.asarray(arr))
            restored = jax.tree.unflatten(
                jax.tree.structure(self.cache), stacked
            )
            self.cache = self.backend.restore_shadow_blocks(
                self.cache, restored, jnp.asarray(ids_padded, jnp.int32)
            )
        except Exception as e:  # noqa: BLE001 - a bad persisted shadow
            # (config drift across a restart) must cold-start, not
            # crash-loop the supervisor
            log.warning("shadow_restore_invalid", error=str(e))
            self._alloc.decref(blocks)
            self._shadow.clear()
            return 0
        assigned = {key: b for (key, _), b in zip(entries, blocks)}
        for leaf in leaf_keys:
            row_blocks = [
                assigned[leaf[: (i + 1) * bs]]
                for i in range(len(leaf) // bs)
            ]
            self._bpx.import_chain(list(leaf), row_blocks)
        # the index holds its own reference per cached block now; drop
        # the allocation's — restored chains end at refcount 1
        # (index-held, evictable), the steady-state cached-chain invariant
        self._alloc.decref(blocks)
        n = len(entries)
        self._shadow.count_pool_promotion(n)
        self.shadow_restored_total += n
        self._m_shadow_restored.inc(n)
        log.info(
            "shadow_restored", blocks=n, chains=len(leaf_keys),
            free_blocks=self._alloc.free_blocks,
        )
        return n

    # -- cross-replica KV fabric (serving/kv_fabric.py; ARCHITECTURE.md
    # "KV fabric & disaggregation") ------------------------------------------
    def fabric_chain(self, digest: str):
        """Wire bytes for the resident shadow chain ending at `digest`,
        or None (the server's GET /kv/{digest} -> 404). Any thread: the
        shadow store is lock-protected and the encode reads host arrays
        only — the HTTP handler serves peers without touching the
        scheduler loop."""
        if not self.fabric_serving:
            return None
        from ..serving.kv_fabric import serve_chain

        return serve_chain(self._shadow, digest)

    def fabric_chain_stream(self, digest: str):
        """(n_chunks, tier, frame iterator) for the resident chain ending
        at `digest`, or None — the server's streamed GET /kv/{digest}
        body (X-KV-Stream: 1). Same any-thread contract as
        fabric_chain, but frames encode lazily, one block at a time."""
        if not self.fabric_serving:
            return None
        from ..serving.kv_fabric import serve_chain_stream

        return serve_chain_stream(self._shadow, digest)

    def fabric_digest_tier(self, digest: str):
        """The shallowest shadow tier holding `digest` ("host" | "disk" |
        None) — the server labels X-KV-Tier and bytes{tier} off this."""
        if not self.fabric_serving:
            return None
        return self._shadow.digest_tier(digest)

    def fabric_accept_push(self, data: bytes):
        """The POST /kv route's body (any thread): validate a peer's
        proactively pushed chain against its OWN content key (the
        digest is recomputed from the payload's tokens — nothing
        external to trust) and land it in the host shadow tier, where
        the phase-2 admission's promotion path scatters it pool-ward
        without a pull round-trip. Returns the response dict, or None
        (-> 400) on a payload that fails validation."""
        if not self.fabric_serving or self._shadow is None:
            return None
        from ..serving.kv_fabric import FabricPayloadError, decode_push

        try:
            digest, keys, per_block = decode_push(
                data, self.kv_block_size
            )
        except FabricPayloadError as e:
            log.warning("fabric_push_rejected", error=str(e))
            return None
        n = self._shadow.put_host(keys, per_block, self._mutation_seq)
        self.engine.flight.record(
            "fabric_push_in", digest=str(digest)[:16], blocks=n,
        )
        return {"accepted": n, "digest": digest}

    def fabric_digests(self, limit: Optional[int] = None) -> list:
        """Resident chain digests, MRU first, host tier before disk —
        the /health field the router's residency bootstrap reads.
        Capped (default --kv-health-digests): the disk tier makes the
        full resident set unbounded, and bootstrap payloads must stay
        O(1) however deep it grows."""
        if not self.fabric_serving:
            return []
        if limit is None:
            limit = self._kv_health_digests
        return self._shadow.resident_digests(limit=limit)

    def _fabric_prefetch(self, req: _Request, ids: list):
        """Consume req's handoff hint (worker thread, at the admission
        host boundary — strictly BEFORE the prefix plan, so a successful
        import is just a deeper local hit). The fallback ladder: local
        chain already covers the prompt -> skip; fetch 404 / dead peer /
        timeout / failed recheck -> local prefill; pool too full to place
        the chain -> import what fits (a chain prefix is still a valid
        chain). Nothing here can fail the request."""
        hint, req.kv_hint = req.kv_hint, None
        if (
            hint is None or self._fabric is None or self._bpx is None
            or not self.paged
        ):
            return
        peer = hint.get("peer") if isinstance(hint, dict) else None
        digest = hint.get("digest") if isinstance(hint, dict) else None
        if not peer or not digest:
            return
        bs = self.kv_block_size
        # deepest depth the planner could ever use (it caps reuse to
        # leave >= 1 tail token); a local chain at that depth makes the
        # fetch pure waste
        cap = max(0, (len(ids) - 1) // bs) * bs
        p0_local, _, _ = self._bpx.lookup(ids)
        if cap <= 0 or p0_local >= cap:
            return
        if self._shadow is not None and self._shadow.has_resident(
            tuple(ids[:cap])
        ):
            # a proactive push (or an earlier demotion) already landed
            # the full chain in the local tier hierarchy: the promotion
            # pass scatters it without a wire round-trip
            return
        streamed = self._fabric_stream
        tier = ""
        if streamed:
            res = self._fabric.fetch_stream(
                peer, digest, bs, ctx=req.trace_ctx,
                request_id=req.trace.request_id,
                store=self.engine.trace_store,
            )
            hit = False
            if res is not None:
                _n_chunks, tier, blocks_iter = res
                hit, req.fabric_blocks = self._import_fabric_stream(
                    blocks_iter
                )
        else:
            fetched = self._fabric.fetch(
                peer, digest, bs, ctx=req.trace_ctx,
                request_id=req.trace.request_id,
                store=self.engine.trace_store,
            )
            hit = fetched is not None
            tier = getattr(self._fabric, "last_tier", "") if hit else ""
        self.engine.flight.record(
            "fabric_fetch", request_id=req.trace.request_id, peer=peer,
            digest=str(digest)[:16], hit=hit, tier=tier,
            streamed=streamed,
        )
        if not streamed and fetched is not None:
            keys, leaves = fetched
            req.fabric_blocks = self._import_fabric_chain(keys, leaves)

    def _import_fabric_chain(self, keys: list, per_block_leaves: list) -> int:
        """Scatter a verified fetched chain into the pool (the SAME
        pre-warmed restore program warm recovery uses), register it into
        the block-prefix index, and feed it to the local shadow so this
        replica can onward-serve it through /kv. Returns blocks imported
        (0 when the pool has no headroom — local prefill still works)."""
        # one slot-class of headroom, like _restore_shadow: an import
        # must never make the admission it serves unplaceable. Under
        # steady-state load the free list is empty while the pool is
        # full of COLD refcount-1 cached chains — reclaim those first
        # (the same evict-and-retry the admission path uses) so tier
        # promotion is never starved by its own tier-0 occupancy.
        budget = self._alloc.free_blocks - self._max_blocks
        if budget < len(keys) and self._bpx is not None:
            self._bpx.evict(len(keys) - budget)
            budget = self._alloc.free_blocks - self._max_blocks
        if budget <= 0:
            return 0
        if len(keys) > budget:
            keys = keys[:budget]
            per_block_leaves = per_block_leaves[:budget]
        blocks = self._alloc.alloc(len(keys))
        if blocks is None:
            return 0
        W = self._shadow_restore_w
        pad = (-len(keys)) % W
        ids_padded = blocks + [self._P.TRASH_BLOCK] * pad
        try:
            stacked = []
            for j in range(len(per_block_leaves[0])):
                arr = np.stack([pb[j] for pb in per_block_leaves])
                if pad:
                    arr = np.concatenate(
                        [arr, np.repeat(arr[:1], pad, axis=0)]
                    )
                stacked.append(jnp.asarray(arr))
            restored = jax.tree.unflatten(
                jax.tree.structure(self.cache), stacked
            )
            self.cache = self.backend.restore_shadow_blocks(
                self.cache, restored, jnp.asarray(ids_padded, jnp.int32)
            )
        except Exception as e:  # noqa: BLE001 - a leaf-shape mismatch
            # (peer config drift the digest cannot see) must degrade to
            # a cold prefill, never crash the scheduler
            log.warning("fabric_import_invalid", error=str(e))
            self._alloc.decref(blocks)
            return 0
        self._bpx.import_chain(list(keys[-1]), blocks)
        if self._shadow is not None:
            self._shadow.put_host(
                keys, per_block_leaves, self._mutation_seq
            )
            self._shadow.count_pool_promotion(len(keys))
        # the index now holds its reference per cached block; drop the
        # allocation's — imported chains end refcount-1 (evictable),
        # exactly like restored ones
        self._alloc.decref(blocks)
        log.info(
            "fabric_imported", blocks=len(keys),
            free_blocks=self._alloc.free_blocks,
        )
        return len(keys)

    def _scatter_stream_batch(self, batch: list, keys: list,
                              leaves_kept: list, blocks: list) -> bool:
        """Scatter one batch of streamed (key, leaves) frames into
        freshly allocated pool blocks through the pre-warmed restore
        program — the streamed import's unit of network/device overlap
        (JAX dispatches the scatter asynchronously, so the device works
        while the next frames are still on the wire). Appends to the
        caller's ledgers only on success; False = pool dry or a
        leaf-shape mismatch (the caller keeps its already-scattered
        prefix — a chain prefix is still a valid chain)."""
        blk = self._alloc.alloc(len(batch))
        if blk is None and self._bpx is not None:
            # cold cached chains are reclaimable, exactly as at admission
            self._bpx.evict(len(batch) - self._alloc.free_blocks)
            blk = self._alloc.alloc(len(batch))
        if blk is None:
            return False
        W = self._shadow_restore_w
        pad = (-len(batch)) % W
        ids_padded = blk + [self._P.TRASH_BLOCK] * pad
        try:
            stacked = []
            for j in range(len(batch[0][1])):
                arr = np.stack([leaves[j] for _, leaves in batch])
                if pad:
                    arr = np.concatenate(
                        [arr, np.repeat(arr[:1], pad, axis=0)]
                    )
                stacked.append(jnp.asarray(arr))
            restored = jax.tree.unflatten(
                jax.tree.structure(self.cache), stacked
            )
            self.cache = self.backend.restore_shadow_blocks(
                self.cache, restored, jnp.asarray(ids_padded, jnp.int32)
            )
        except Exception as e:  # noqa: BLE001 - peer leaf-shape drift
            log.warning("fabric_stream_scatter_invalid", error=str(e))
            self._alloc.decref(blk)
            return False
        for (key, leaves), b in zip(batch, blk):
            keys.append(key)
            leaves_kept.append(leaves)
            blocks.append(b)
        # jaxlint: disable=resource-lifecycle -- blk handed to the caller's `blocks` ledger: registered on final-digest verify or decref'd on stream failure
        return True

    def _import_fabric_stream(self, blocks_iter) -> tuple:
        """Consume a verified /kv stream (kv_fabric.fetch_stream's block
        iterator), scattering frames into the pool in restore-width
        batches AS THEY ARRIVE — decode's tail prefill overlaps the
        pull instead of waiting behind a whole-manifest buffer. Nothing
        is REGISTERED until the stream finishes cleanly (the iterator's
        final content-key recheck): on tamper, truncation, or a died
        socket mid-stream the scattered-but-unregistered blocks are
        simply decref'd — unreachable garbage, bit-identical fallback
        to local prefill, the same bar the whole-blob path meets.
        Returns (verified, blocks_imported); budget-truncated imports
        still drain and verify every frame before registering the
        prefix that fit."""
        # cold refcount-1 cached chains count toward the budget — the
        # per-batch scatter evicts them on demand (same reclaim the
        # admission path uses), so a pool full of cold prefixes never
        # starves a streamed import
        budget = (
            self._alloc.free_blocks
            + (self._bpx.evictable_blocks() if self._bpx is not None else 0)
            - self._max_blocks
        )
        if budget <= 0:
            blocks_iter.close()  # settles the client's hit/miss + span
            return False, 0
        W = self._shadow_restore_w
        keys: list = []  # scattered, parents-first
        leaves_kept: list = []
        blocks: list = []  # their pool ids, aligned
        batch: list = []
        pool_dry = False
        verified = False
        try:
            for key, leaves in blocks_iter:
                if pool_dry or len(keys) + len(batch) >= budget:
                    continue  # verify-drain the tail; import what fit
                batch.append((key, leaves))
                if len(batch) == W:
                    if not self._scatter_stream_batch(
                        batch, keys, leaves_kept, blocks
                    ):
                        pool_dry = True
                    batch = []
            if batch and not pool_dry:
                self._scatter_stream_batch(
                    batch, keys, leaves_kept, blocks
                )
            verified = True
        except Exception as e:  # noqa: BLE001 - FabricPayloadError /
            # socket death mid-stream: one outcome, local prefill
            log.warning("fabric_stream_rejected", error=str(e))
        finally:
            blocks_iter.close()
        if not verified or not keys:
            if blocks:
                self._alloc.decref(blocks)
            return verified, 0
        self._bpx.import_chain(list(keys[-1]), blocks)
        if self._shadow is not None:
            self._shadow.put_host(
                keys, leaves_kept, self._mutation_seq
            )
            self._shadow.count_pool_promotion(len(keys))
        self._alloc.decref(blocks)
        log.info(
            "fabric_stream_imported", blocks=len(keys),
            free_blocks=self._alloc.free_blocks,
        )
        return True, len(keys)

    def _promote_local_chain(self, req: _Request, ids: list):
        """Tier promotion at admission (worker thread, after any fabric
        prefetch, strictly BEFORE the prefix plan): when the shadow
        hierarchy — host tier or DISK tier — holds a deeper contiguous
        chain for this prompt than the pool's block-prefix index does,
        load it (disk hits promote host-ward inside entries_for, each
        chunk file content-key-verified) and scatter it through the
        same import path a fabric fetch uses. A disk-resident warm
        prefix re-enters in one restore launch instead of a cold
        re-prefill; a corrupt chunk file rejects into exactly that cold
        re-prefill. Nothing here can fail the request."""
        if (
            self._shadow is None or self._bpx is None or not self.paged
            or req.adapter is not None  # adapter KV is fenced from
            # every token-keyed reuse surface (PR 16)
        ):
            return
        bs = self.kv_block_size
        cap = max(0, (len(ids) - 1) // bs) * bs
        if cap <= 0:
            return
        p0_local, _, _ = self._bpx.lookup(ids)
        if p0_local >= cap:
            return
        depth = 0
        for nb in range(cap // bs, p0_local // bs, -1):
            if self._shadow.has_resident(tuple(ids[: nb * bs])):
                depth = nb
                break
        if depth == 0:
            return
        keys = [tuple(ids[: (i + 1) * bs]) for i in range(depth)]
        entries = self._shadow.entries_for(keys)
        if entries is None:
            return  # churned out / corrupt chunk file: cold prefill
        imported = self._import_fabric_chain(
            keys, [e.leaves for e in entries]
        )
        if imported:
            req.promoted_blocks = imported
            self.engine.flight.record(
                "tier_promote", request_id=req.trace.request_id,
                blocks=imported, depth=depth * bs,
            )

    def _fabric_push(self, req: _Request, peer_url: str) -> int:
        """Phase 1.5 of the prefill->decode handoff: encode this
        finished request's deepest shadow chain and POST it to the
        decode replica the router pre-picked (X-KV-Push-To), so phase
        2's admission finds the prefix already host-resident there —
        no pull round-trip on the decode critical path. Runs on the
        submit() caller's HTTP thread AFTER the shadow flush (the chain
        is resident by construction), never the scheduler loop. Any
        failure returns 0 — the pull path remains the fallback."""
        res = req.result if isinstance(req.result, dict) else None
        ds = (res or {}).get("kv_digests") or []
        if not ds or self._fabric is None:
            return 0
        digest = ds[-1]  # deepest chain the decode peer will want
        data = self.fabric_chain(digest)
        if data is None:
            return 0
        accepted = self._fabric.push_chain(
            peer_url, data, ctx=req.trace_ctx,
            request_id=req.trace.request_id,
            store=self.engine.trace_store,
        )
        self.engine.flight.record(
            "fabric_push", request_id=req.trace.request_id,
            peer=peer_url, digest=str(digest)[:16],
            accepted=-1 if accepted is None else accepted,
        )
        return accepted or 0

    # -- SLO-aware KV preemption (graceful degradation under memory
    # pressure; ARCHITECTURE.md "Preemption & cancellation") ----------------
    def _alloc_with_pressure(self, req: _Request) -> Optional[list]:
        """`req.need` fresh blocks through the full memory-pressure
        ladder: plain alloc → evict unreferenced cached chains → preempt
        a victim (whose chains the next evict round can reclaim) → None
        (the caller requeues with _BLOCKED). Worker thread only."""
        blk_ids = self._alloc.alloc(req.need)
        while blk_ids is None:
            if self._bpx is not None:
                self._bpx.evict(req.need - self._alloc.free_blocks)
                blk_ids = self._alloc.alloc(req.need)
                if blk_ids is not None:
                    return blk_ids
            if not self._preempt_for(req):
                return None
            blk_ids = self._alloc.alloc(req.need)
        return blk_ids

    def _victim_for(self, req: _Request) -> Optional[_Request]:
        """The decoding tenant to evict so `req` can be placed, or None.
        Candidates: assigned, still running, NOT mid-prefill (a chunked
        job's partial blocks are not yet a restorable chain), below the
        preemption cap, and not outranking the beneficiary's SLO weight.
        The scheduler's policy object picks lowest-weight / youngest."""
        with self._cv:
            cands = [
                r for b, r in enumerate(self._assignment)
                if r is not None and not r.done.is_set() and r is not req
                and b not in self._prefilling
                and r.preemptions < self.max_preemptions
            ]
        if not cands:
            return None
        return self._sched.select_victim(
            [(r, self._sched.classify(r.slo), r.enqueued) for r in cands],
            self._sched.classify(req.slo),
        )

    def _preempt_for(self, req: _Request) -> bool:
        """Evict one decoding victim to make pool room for `req` (worker
        thread, called when allocation failed even after the
        evict-unreferenced-chains retry). Returns True when a victim was
        preempted (its blocks decref'd — the caller re-runs the evict +
        alloc retry, which can now reclaim the victim's index-cached
        chains too).

        The victim's host-side record (prompt + fetched tokens) is the
        same salvage contract a supervisor restart uses, so its resume
        re-admission is greedy bit-identical; under preempt_policy
        "swap" its filled blocks are pushed to the host shadow FIRST
        (synchronous flush) so the resume restores them in one scatter
        and re-prefills only the tail — a backlogged copier falls back
        to drop-and-recompute. Emissions from the victim's still-in-
        flight chunks are dropped via the drop_seq barrier (regenerated
        after resume), exactly like unfetched chunks across a crash."""
        if self.preempt_policy == "off":
            return False
        victim = self._victim_for(req)
        if victim is None:
            return False
        faults.check("preempt", tag=victim.prompt)
        swapped = False
        if self.preempt_policy == "swap" and self._shadow is not None:
            # capture any blocks filled since the last fetch, then wait
            # for every pending copy to LAND — only resident entries are
            # restorable, and a half-shadowed chain is worthless
            self._shadow_capture(victim)
            swapped = self._shadow.flush(timeout_s=5.0)
        # fold the fetched token stream into the salvage record before
        # releasing anything (the continuation re-prefill's source)
        head = (
            [victim.first_id]
            if victim.first_id is not None
            and victim.first_id not in self.cfg.all_stop_ids else []
        )
        if swapped and victim.ids is not None and victim.adapter is None:
            victim.resume_seq = list(victim.ids) + head + victim.tokens
        else:
            # adapter victims always drop-and-recompute: their KV never
            # enters the shadow (base-keyed content store), so there is
            # no chain to restore — the recompute resume is still greedy
            # bit-identical via the salvage record
            victim.resume_seq = None
        victim.salvaged = victim.salvaged + head + victim.tokens
        victim.first_id = None
        victim.tokens = []
        victim.preemptions += 1
        victim.preempted_at = time.time()
        # launch-seq barrier: chunks launched before this point may still
        # fetch emissions for the victim's old slot — drop them (they are
        # regenerated after resume; appending them post-fold would
        # corrupt the salvage order)
        self._mutation_seq += 1
        victim.drop_seq = self._mutation_seq
        if victim.slot is not None:
            self.state = G.kill_slot(self.state, victim.slot)
        self._free_slot_resources(victim)
        victim.slot = None
        victim.need = None
        victim.prefix_hit_tokens = 0
        victim.ids = None
        victim.shadow_depth = 0
        self.preempted_total += 1
        self._m_preempt.labels(reason="pool").inc()
        self.engine.flight.record(
            "preempt", request_id=victim.trace.request_id,
            policy=self.preempt_policy, swap=swapped,
            preemptions=victim.preemptions, slo_class=victim.slo,
            beneficiary=req.trace.request_id,
            **self._alloc.span_attrs(),
        )
        log.info(
            "request_preempted", policy=self.preempt_policy, swap=swapped,
            preemptions=victim.preemptions, slo_class=victim.slo,
            beneficiary_class=req.slo, request_id=victim.trace.request_id,
        )
        with self._cv:
            self._resume.append(victim)
            self._cv.notify_all()
        return True

    def _prepare_resume(self, req: _Request):
        """Swap-preemption's warm half (worker thread, just before the
        resume re-admission): scatter the victim's shadowed chain back
        into freshly allocated pool blocks (the pre-warmed fixed-width
        restore program) and re-register it into the block-prefix index,
        so the ordinary admission path below prefix-hits it and
        re-prefills ONLY the tail past the deepest restored block. Any
        shortfall (entries evicted from the shadow, pool still tight)
        degrades to a colder re-prefill — never an error."""
        seq = req.resume_seq
        if seq is None or self._shadow is None or self._bpx is None:
            req.resume_seq = None
            return
        bs = self.kv_block_size
        # same reuse cap as BlockPrefixIndex.lookup: at least one tail
        # token must remain for the sampling chunk
        cap_full = max(0, (len(seq) - 1) // bs)
        p0, entry, _ = self._bpx.lookup(seq)
        have = p0 // bs
        keys = []
        for i in range(have, cap_full):
            key = tuple(seq[: (i + 1) * bs])
            if not self._shadow.has_resident(key):
                break  # a chain with a hole cannot be registered
            keys.append(key)
        if not keys:
            req.resume_seq = None  # nothing restorable, ever
            return
        blocks = self._alloc.alloc(len(keys))
        if blocks is None and self._bpx is not None:
            self._bpx.evict(len(keys) - self._alloc.free_blocks)
            blocks = self._alloc.alloc(len(keys))
        if blocks is None:
            # pool still tight (the admission below will _BLOCK and
            # requeue): KEEP resume_seq so the retry after the next
            # release still restores warm instead of recomputing
            return
        entries = self._shadow.entries_for(keys)
        if entries is None:
            self._alloc.decref(blocks)
            req.resume_seq = None
            return
        try:
            W = self._shadow_restore_w
            for off in range(0, len(keys), W):
                ids = blocks[off : off + W]
                batch = entries[off : off + W]
                pad = W - len(ids)
                ids_p = ids + [self._P.TRASH_BLOCK] * pad
                stacked = []
                for i in range(len(batch[0].leaves)):
                    arr = np.stack([e.leaves[i] for e in batch])
                    if pad:
                        arr = np.concatenate(
                            [arr, np.repeat(arr[:1], pad, axis=0)]
                        )
                    stacked.append(jnp.asarray(arr))
                restored = jax.tree.unflatten(
                    jax.tree.structure(self.cache), stacked
                )
                self.cache = self.backend.restore_shadow_blocks(
                    self.cache, restored, jnp.asarray(ids_p, jnp.int32)
                )
        except BaseException:
            # a crash mid-restore is contained by the supervisor, but
            # these blocks are not yet tracked anywhere — release them
            # before the unwind or the pool leaks
            self._alloc.decref(blocks)
            raise
        req.resume_seq = None
        row_blocks = list(entry or []) + blocks
        self._bpx.import_chain(
            list(seq[: len(row_blocks) * bs]), row_blocks
        )
        # the index holds its own reference now; restored chains end at
        # refcount 1 (index-held, evictable) like every cached chain
        self._alloc.decref(blocks)
        self._m_shadow_restored.inc(len(blocks))
        log.info(
            "preempt_resume_restored", blocks=len(blocks),
            request_id=req.trace.request_id,
        )

    def _supervise(self, exc: Exception) -> bool:
        """One crash-containment round. Returns True to restart the loop,
        False to give up (budget exhausted or closing)."""
        self._restarting = True
        self._consecutive_crashes += 1
        log.error(
            "continuous_loop_crashed", exc_info=True, error=str(exc),
            consecutive=self._consecutive_crashes,
        )
        # crash flight recorder (ISSUE 17): the event ring's tail goes
        # into the crash report (the structured log record below) and
        # the FULL dump is persisted next to --restore-dir, so a
        # poison-quarantine or restart-loop episode is reconstructable
        # after the process is gone. Persist failures only cost the
        # forensics file — containment proceeds regardless.
        self.engine.flight.record(
            "crash", error=str(exc),
            consecutive=self._consecutive_crashes,
        )
        flight = self.engine.flight.dump()
        log.error(
            "crash_flight_recorder",
            recorded_total=flight["recorded_total"],
            tail=flight["events"][-20:],
        )
        if self._restore_dir:
            try:
                os.makedirs(self._restore_dir, exist_ok=True)
                with open(
                    os.path.join(self._restore_dir, "flight_crash.json"),
                    "w",
                ) as f:
                    json.dump(
                        {"error": str(exc),
                         "consecutive": self._consecutive_crashes,
                         **flight},
                        f,
                    )
            except OSError as e:
                log.warning("flight_persist_failed", error=str(e))
        casualties = self._casualties()
        for req in casualties:
            if req in self._suspects:
                req.strikes += 1
        self._suspects.clear()
        self._release_fleet_resources(casualties)
        survivors = []
        for req in casualties:
            if req.strikes >= self.poison_strikes:
                # implicated in poison_strikes consecutive crash-restarts:
                # fail it ALONE; its fleet-mates are salvaged below
                self.poisoned_total += 1
                self._m_poison.inc()
                self.engine.flight.record(
                    "quarantine", request_id=req.trace.request_id,
                    strikes=req.strikes,
                )
                log.error(
                    "request_quarantined", strikes=req.strikes,
                    request_id=req.trace.request_id,
                )
                req.result = {
                    "error": f"Error: request quarantined after "
                    f"implication in {req.strikes} scheduler crashes "
                    f"(last: {exc})",
                    "status": "failed",
                    "error_type": "poison",
                }
                self._push_final(req)
            else:
                survivors.append(req)
        if self._closed or self._consecutive_crashes > self.restart_budget:
            with self._cv:
                self._dead = not self._closed
                self._closed = True
                pending = self._queue[:]
                self._queue.clear()
                self._note_queue_locked()
                self._cv.notify_all()
            fail = {
                "error": f"Error: continuous scheduler died after "
                f"{self._consecutive_crashes} consecutive crashes "
                f"(restart budget {self.restart_budget}): {exc}",
                "status": "failed",
                "error_type": "unavailable",
            }
            # self._recovery: salvaged requests a previous round never got
            # to re-admit (a crash mid-recovery) — they hang otherwise.
            # self._resume: preempted requests parked for re-admission
            # (host-side only, resources already released) — same hazard.
            for req in survivors + pending + self._recovery + self._resume:
                if req.result is None:
                    req.result = dict(fail)
                self._push_final(req)
            self._recovery = []
            self._resume = []
            self._restarting = False
            self.engine.flight.record(
                "scheduler_dead", restarts=self.restarts_total,
            )
            log.error(
                "continuous_scheduler_dead", restarts=self.restarts_total
            )
            return False
        # exponential backoff: a crash loop must not spin the host
        time.sleep(min(
            self.restart_backoff_s * (2 ** (self._consecutive_crashes - 1)),
            5.0,
        ))
        self._rebuild_fleet()
        # warm recovery: the restarted loop restores shadowed blocks
        # into the fresh pool BEFORE re-admitting anything. Deliberately
        # not done here: _supervise runs inside _loop's except handler,
        # where a restore crash (the double-fault drill) would escape
        # containment — _loop_inner owns the restore under the
        # supervisor instead.
        self._needs_restore = self._shadow is not None
        # Salvage: prompt + tokens generated so far are host-side. The
        # restarted loop re-admits each request as a CONTINUATION prefill
        # (prompt + salvaged tokens), so greedy decode resumes bit-exactly
        # where the fetched token stream stopped — tokens lost in
        # unfetched in-flight chunks are simply regenerated.
        for req in survivors:
            head = (
                [req.first_id]
                if req.first_id is not None
                and req.first_id not in self.cfg.all_stop_ids else []
            )
            req.salvaged = req.salvaged + head + req.tokens
            req.first_id = None
            req.tokens = []
            req.slot = None
            req.need = None
            req.prefix_hit_tokens = 0
            # shadow bookkeeping resets with the fleet: the re-admission
            # gets fresh blocks (content keys dedup re-captures)
            req.ids = None
            req.shadow_depth = 0
        # a crash mid-recovery leaves earlier salvage in self._recovery
        # (already reset — never re-admitted): keep it, after this round's
        # survivors (who were vindicated tenants before the crash)
        self._recovery = survivors + [
            r for r in self._recovery if not r.done.is_set()
        ]
        self.restarts_total += 1
        self._m_restarts.inc()
        self.engine.flight.record(
            "restart", restart=self.restarts_total,
            salvaged=len(survivors),
        )
        log.info(
            "continuous_scheduler_restarted", restart=self.restarts_total,
            salvaged=len(survivors),
        )
        return True

    def _run_recovery(self):
        """Serialized re-admission of salvaged requests: ONE request per
        healthy chunk, so a recurring crash implicates exactly the
        request just re-admitted (the suspect set narrows to a singleton)
        instead of striking every fleet-mate — the mechanism that
        isolates a poison request within poison_strikes restarts while
        the rest of the fleet survives."""
        try:
            if self._wgrp is not None and self._recovery:
                # a grouped pool takes chunked admissions only: the
                # salvaged requests go back to the FRONT of the queue and
                # re-enter as jobs (prompt + what they had generated)
                with self._cv:
                    self._queue[:0] = self._recovery
                    self._recovery.clear()
                    self._note_queue_locked()
            while self._recovery:
                if self._closed:
                    # close() fails queued + assigned requests, but the
                    # not-yet-readmitted salvage is in neither place
                    fail = {
                        "error": "Error: server shutting down",
                        "status": "failed", "error_type": "overloaded",
                    }
                    while self._recovery:
                        r = self._recovery.pop(0)
                        if r.result is None:
                            r.result = dict(fail)
                        self._push_final(r)
                    return
                req = self._recovery[0]
                if (
                    req.allowed is not None
                    and len(req.salvaged) >= req.allowed
                ):
                    # budget already consumed pre-crash (the crash cut the
                    # loop between the last fetch and finalize)
                    self._recovery.pop(0)
                    self._finalize(req)
                    continue
                with self._cv:
                    free = [
                        b for b, r in enumerate(self._assignment)
                        if r is None
                    ]
                if not free:
                    # more casualties than slots (a crash mid-admission):
                    # decode until a tenant completes and frees one
                    chunk = self._launch_chunk()
                    if chunk is None:
                        break  # unreachable: no free slot implies tenants
                    self._process(chunk)
                    continue
                self._recovery.pop(0)
                self._suspects.add(req)
                self._mutation_seq += 1
                # recomputed-prefill accounting: the re-admission below
                # counts its tail into dli_recovery_tokens_recomputed_total
                req.recovering = True
                # survives an exception unwind on purpose — the
                # supervisor's pointer to a request cut mid-re-admission
                self._admitting = req
                first_dev = self._admit_one(req, free[0])
                self._admitting = None
                if first_dev is _BLOCKED:
                    # the rebuilt pool/table cannot take it right now
                    # (another recovered tenant holds the blocks): back to
                    # the FRONT of the normal queue
                    with self._cv:
                        self._queue.insert(0, req)
                        self._note_queue_locked()
                    continue
                if first_dev is None:
                    continue  # failed fast (cancelled/deadline); result set
                req.first_id = int(np.asarray(first_dev)[0])
                if not req.ttft:
                    req.ttft = time.time() - req.t_start
                self.recovered_total += 1
                self._m_recovered.inc()
                self._post_admit(req)
                # one synchronous chunk = the healthy step that vindicates
                # this re-admission before the next one joins the fleet
                chunk = self._launch_chunk()
                if chunk is not None:
                    self._process(chunk)
        finally:
            self._restarting = False

    # -- block diffusion: the host's position model, in forwards --------------
    def _check_denoise(self, steps: int) -> int:
        """denoise_steps of a request (or the server's default): a forward
        reveals block / denoise_steps masked positions, a whole number."""
        if steps < 1 or self._blk % steps:
            raise ValueError(
                f"denoise_steps must divide the model's block length "
                f"{self._blk}, got {steps}"
            )
        return steps

    def _diffusion_reject(self, kwargs: dict) -> Optional[dict]:
        """What a block-diffusion fleet cannot honor: the solo engine's
        contracts (it decodes one token a forward) and the penalties (a
        block's tokens are chosen together, from logits no earlier token
        of the block conditioned)."""
        if not self._blk:
            return None
        bad = self._needs_solo(kwargs) or any(
            float(kwargs.get(name, neutral)) != neutral
            for name, neutral in (("repetition_penalty", 1.0),
                                  ("frequency_penalty", 0.0),
                                  ("presence_penalty", 0.0))
        )
        if not bad:
            try:
                self._check_denoise(int(kwargs.get(
                    "denoise_steps", self._denoise_default)))
                return None
            except (TypeError, ValueError) as e:
                msg = str(e)
        else:
            msg = (f"{self.cfg.name} generates by diffusion over blocks: no "
                   f"seed / debug / logprobs / logit_bias / beams / "
                   f"constraint / speculative / penalty contract")
        return {"error": f"Error: {msg}", "status": "failed",
                "error_type": "invalid_request"}

    def _blk_plan(self, slot: int, head: int, reveal: int, max_tokens: int):
        """Arm the position model of a row whose open block starts with
        `head` prompt tokens: forwards a block = its denoise forwards (a
        block's commit rides the next block's first); the budget takes
        ceil((head + max_tokens) / block) blocks."""
        B = self._blk
        self._blk_skip[slot] = head
        self._blk_first[slot] = -(-(B - head) // reveal)
        self._blk_later[slot] = B // reveal
        blocks = -(-(head + max_tokens) // B)
        self._host_pos[slot] = 0
        self._host_end[slot] = (
            self._blk_first[slot] + (blocks - 1) * self._blk_later[slot]
        )

    def _blk_at(self, fwd):
        """(the row's clean length before forward `fwd` = the open block's
        first position, whether `fwd` carries the block below it as an
        owed one, masked positions it reveals) for forward indices fwd
        [slots, ...] (numpy-broadcasting over trailing axes). At
        `_host_end` the length is that of the row's last block's end."""
        ex = (slice(None),) + (None,) * (np.ndim(fwd) - 1)
        first, later = self._blk_first[ex], self._blk_later[ex]
        in_first = fwd < first
        rest = np.maximum(fwd - first, 0)
        block = np.where(in_first, 0, 1 + rest // later)
        within = np.where(in_first, fwd, rest % later)
        masks = np.where(in_first, self._blk - self._blk_skip[ex], self._blk)
        reveal = self._blk // later
        shown = np.clip(masks - within * reveal, 0, reveal)
        return (self._blk_base[ex] + block * self._blk,
                ~in_first & (within == 0), shown)

    def _blk_fields(self, fwd, alive, forwards: int) -> dict:
        """The launch record's diffusion fields for a launch of `forwards`
        forwards: row-forwards `fwd` of which `alive` are live on the
        device, counted with the record. Every row-forward reveals
        (`denoise`); none only writes a clean block (`commit`), and
        `fused_rows` of them carried an owed block."""
        _, owed, shown = self._blk_at(fwd)
        fields = {
            "forwards": forwards,
            "denoise_rows": int(np.sum(alive)),
            "commit_rows": 0,
            "fused_rows": int(np.sum(alive & owed)),
            "revealed_tokens": int(np.sum(shown * alive)),
        }
        self._m_diff_forwards.labels(kind="denoise").inc(
            fields["denoise_rows"])
        self._m_diff_fused.inc(fields["fused_rows"])
        return fields

    def _blk_reads(self, fwd):
        """(first query position, query tokens) of forwards `fwd`: the open
        block, from the owed block's start where one rides."""
        at, owed, _ = self._blk_at(fwd)
        return at - owed * self._blk, (1 + owed) * self._blk

    # -- the launch record (ISSUE 24) -----------------------------------------
    def _snap_points(self, p0: int, prompt_len: int) -> tuple:
        """The positions at which a prompt's prefill leaves its state in
        the snapshot pool: where its last whole block ends and one block
        below (a later prompt that shares all but this one's last few
        tokens, a document asked another question, hits at one of the
        two), each at least two blocks past the hit's depth p0 (a prompt
        that adds less to a cached one adds no depth worth a snapshot)."""
        bs = self.kv_block_size
        last = prompt_len // bs
        low = p0 // bs + 2 if p0 else 1
        return tuple(b * bs for b in (last - 1, last) if b >= low)

    def _state_fields(self, spans, state_rows: int, steps: int = 1,
                      restored: int = 0, resets: int = 0, fresh: int = 0,
                      placed=None):
        """The launch record's fields of a fleet with recurrent layers, by
        the host position model. spans: (first position, tokens) of every
        live row of the launch; restored / resets: what the tenants whose
        first chunk rides this launch start from: prompt tokens below a
        restored tail or snapshot, slots let with zeroed state. Where the
        pool keeps a tail a block (cfg.state_tails), a token that fills its
        block's last position leaves it (`conv_tail_writes`, blocks, not x
        layers). Where it keeps a matrix state a slot (a snapshot pool):
        `state_fresh_rows`, the `fresh` rows that start from zeros or a
        snapshot in this launch, and, unless `_sparse_fields` counts them,
        `state_rows`, the row-steps that read and write a state (a decode
        row a step, a prefill chunk once) of the slots x `steps` held. Where
        layers fold their state by the delta rule (cfg.delta_layers):
        `delta_chunks`, the chunks of the flat axis those rows' tokens were
        cut into, from `placed`, (flat place on the model's axis, tokens) of
        each (None: a decode chunk, a token and so a chunk a row-step)."""
        fields = {}
        if self.cfg.state_tails:
            bs = self.kv_block_size
            tails = sum((st + n) // bs - st // bs for st, n in spans)
            self._m_conv_tails.inc(tails)
            fields["conv_tail_writes"] = int(tails)
        fields.update(state_restored_tokens=int(restored),
                      conv_state_resets=int(resets))
        if self._snap_pool:
            fields["state_fresh_rows"] = int(fresh)
            if self._sparse is None:
                self._m_lin_rows.labels(state="touched").inc(state_rows)
                self._m_lin_rows.labels(state="held").inc(
                    self.n_slots * steps)
                fields["state_rows"] = int(state_rows)
        if self.cfg.delta_layers:
            phase = "chunk" if placed is None else "mixed"
            chunks = int(state_rows) if placed is None else sum(
                chunks_of(at, n) for at, n in placed)
            self._m_delta_rows.labels(phase=phase).inc(state_rows)
            self._m_delta_chunks.labels(phase=phase).inc(chunks)
            fields["delta_chunks"] = chunks
        return fields

    def _launch_record(self, phase: str, steps: int, kv_tokens: int,
                       kv_grid_tokens: int, kv_walk_steps: int,
                       row_steps: int, **fields) -> dict:
        """The ONE record of a launch, built at the dispatch seam before
        the jitted call: host integers the loop already holds, no device
        read. `kv_tokens` is the fewest KV positions (per layer and KV
        head) attention must read for the launch's rows, from the host
        position model; `kv_grid_tokens` the positions attention covers
        to read them (`_kv_walk`: whole blocks of each live row's live
        range under the paged kernels, every row's whole table under
        the gather path) and `kv_walk_steps` the loop steps the kernels'
        walk takes over them (per layer; 0 under the gather path: pages
        a loop step = kv_grid_tokens / block size / kv_walk_steps, 1.0
        where a shape's compute block is one page);
        `steps_ahead` the scheduler steps dispatched
        and not yet fetched, `queue_empty` whether that is none (the device
        has nothing queued: its idle time until this launch is the host's
        or the traffic's, `PhaseClock.device_empty`); `row_steps` the
        decode row-steps it carries
        (a chunk's rows run up to `steps` each). Counted here; the
        caller hands it to the `launch.<phase>` annotation, the flight
        `plan` event and the sampled per-tenant span, and the fetch
        closes it by `seq`."""
        self._launch_seq += 1
        rec = {
            "phase": phase, "seq": self._launch_seq, "steps": steps,
            "decode_rows": 0, "prefill_chunks": 0, "prefill_tokens": 0,
            "spec_drafted": 0, "steps_ahead": self._steps_inflight,
            "queue_empty": int(self._steps_inflight == 0),
            "kv_tokens": int(kv_tokens),
            "kv_grid_tokens": int(kv_grid_tokens),
            "kv_walk_steps": int(kv_walk_steps),
        }
        rec.update(fields)
        self._steps_inflight += steps
        self._steps_dispatched += steps
        self._m_ragged_launches.labels(phase=phase).inc()
        self._m_sched_rows.inc(row_steps)
        self._m_sched_tokens.labels(kind="decode").inc(row_steps)
        self._m_kv_tokens.labels(phase=phase, state="attended").inc(
            rec["kv_tokens"]
        )
        self._m_kv_tokens.labels(phase=phase, state="walked").inc(
            rec["kv_grid_tokens"]
        )
        self._m_walk_steps.labels(phase=phase).inc(rec["kv_walk_steps"])
        self._m_steps_ahead.labels(phase=phase).observe(rec["steps_ahead"])
        return rec

    def _kv_fields(self, phase: str, attended, walked) -> dict:
        """The launch record's KV counts under a layer's window (None: the
        whole context): attended(window) the launch's sum, walked(window)
        what each of its tiles walks (`_kv_walk`). A stack of one kind:
        `kv_tokens`, `kv_grid_tokens`, per layer and K/V head, and
        `kv_walk_steps`, the loop steps the tiles' walks take a layer
        (`_kv_walk_steps`). A stack of window and global layers adds each
        kind's own (`kv_tokens_global`, ..., per layer OF THE KIND), and
        the three are then the kinds' sums by their layer counts, so that
        attended / walked and walked pages / steps stay shares of one
        thing."""
        def counts(window, group=0):
            walk = walked(window)
            return (int(attended(window)), int(np.sum(walk)),
                    int(np.sum(self._kv_walk_steps(phase, walk, group))))

        names = ("kv_tokens", "kv_grid_tokens", "kv_walk_steps")
        if self._kv_kinds is None:
            return dict(zip(names, counts(self._kv_window)))
        out = dict.fromkeys(names, 0)
        for group, (name, (layers, window)) in enumerate(
                zip(("global", "window"), self._kv_kinds)):
            a, w, n = counts(window, group)
            out[f"kv_tokens_{name}"], out[f"kv_grid_tokens_{name}"] = a, w
            for key, count in zip(names, (a, w, n)):
                out[key] += layers * count
        return out

    def _sparse_fields(self, phase: str, visible, steps: int) -> dict:
        """The launch record's fields of a fleet with sparse attention
        layers: `visible`, the positions at or below each live row-step's
        (last) query; `kv_tokens` beside it counts what is read. `steps`:
        the launch's, for the share of the state leaf its rows are.
        `ck_scored`: the compressed keys the selection's scoring reads, a
        sparse layer and KV head: a row's, once a step the row is in, up to
        its length (ops/sparse_select.py), where a gather of every tile's
        whole table read tiles x the table's width / the stride."""
        visible = np.asarray(visible).reshape(-1)
        sparse = int(np.sum(visible >= self._sparse.sparse_dense_len))
        read = int(np.sum(self._kv_span(visible, 0)))
        scored = int(np.sum(-(-visible // self._sparse.sparse_stride)))
        self._m_ck_scored.inc(scored)
        # (dli_attn_kv_tokens_total's further states for such a fleet)
        self._m_kv_tokens.labels(phase=phase, state="visible").inc(
            int(visible.sum()))
        self._m_kv_tokens.labels(phase=phase, state="selected").inc(read)
        self._m_sparse_rows.labels(branch="sparse").inc(sparse)
        self._m_sparse_rows.labels(branch="dense").inc(len(visible) - sparse)
        self._m_lin_rows.labels(state="touched").inc(len(visible))
        self._m_lin_rows.labels(state="held").inc(self.n_slots * steps)
        return {"kv_tokens_visible": int(visible.sum()), "ck_scored": scored,
                "sparse_rows": sparse, "state_rows": len(visible)}

    def _kv_span(self, start, length=1, window=-1):
        """KV positions a row must read whose last query sits at
        `start + length - 1`: everything up to and including it, clipped
        to the sliding window (-1: the model's uniform one, None: none;
        numpy-broadcasting)."""
        n = start + length
        if self._sparse is not None:
            # a selected read: top-k blocks, the last of them the query's
            # own as far as it is filled
            c, bs = self._sparse, self.kv_block_size
            most = (c.sparse_topk - 1) * bs + (n - 1) % bs + 1
            return np.where(n < c.sparse_dense_len, n, np.minimum(n, most))
        window = self._kv_window if window == -1 else window
        return n if window is None else np.minimum(n, window)

    def _kv_walk(self, start, length=1, window=-1):
        """KV positions the paged kernels' block loop covers for a query
        tile of `length` queries from position `start`: (needed - first)
        x block size, ops/paged_attention._ragged_live_range's arithmetic
        in numpy, and nothing for a tile that holds no query
        (numpy-broadcasting; tests/test_launch_record.py holds the two
        together). Under the gather path (attn_impl "xla", and the dense
        fleet) every row reads its whole table, live or not."""
        if not self._kv_walks:
            return np.full(np.broadcast(start, length).shape,
                           self._scratch_seq)
        bs = self.kv_block_size
        window = self._kv_window if window == -1 else window
        last = start + np.maximum(length, 1) - 1
        needed = np.clip(-(-(last + 1) // bs), 1, self._max_blocks)
        if self._sparse is not None:
            # the page list of the tile's LAST query: a decode row's own,
            # the least a tile of several queries walks (the union of its
            # queries' choices is the device's to know)
            c = self._sparse
            pages = np.where(last + 1 < c.sparse_dense_len, needed,
                             np.minimum(needed, c.sparse_topk))
            return np.where(length > 0, pages * bs, 0)
        first = 0 if window is None else np.minimum(
            np.maximum(start - window + 1, 0) // bs, needed - 1
        )
        return np.where(length > 0, (needed - first) * bs, 0)

    def _walk_pages_of(self, tq: int):
        """P of the paged kernels' walk for query tiles of tq tokens over
        this fleet's pool, from the function the kernels take it from: one
        number a group of the pool (cfg.kv_groups' order; a pool of one
        kind has one), since a grouped pool's rows, their own kinds', may
        walk differently."""
        from ..ops.kv_quant import KVQuant
        from ..ops.paged_attention import walk_pages_per_step

        if "kw" in self.cache:
            return tuple(
                walk_pages_per_step(self.cache[kn], self.cfg.n_heads, tq,
                                    self._max_blocks) for kn in ("k", "kw"))
        if self._sparse is not None:  # a page list a KV head
            return (walk_pages_per_step(self.cache["k"], self.cfg.n_heads, tq,
                                        self._max_blocks, listed=True),)
        leaf = next(
            a for a in jax.tree.leaves(
                self.cache, is_leaf=lambda a: isinstance(a, KVQuant))
            if isinstance(a, KVQuant) or a.ndim == 5
        )
        return (walk_pages_per_step(leaf, self.cfg.n_heads, tq,
                                    self._max_blocks,
                                    latent=self.cfg.latent_dim > 0),)

    def _kv_walk_steps(self, phase: str, walk, group: int = 0):
        """Loop steps the paged kernels run over `walk`, the positions
        `_kv_walk` counts a tile: its pages over the pages a step of
        `phase`'s program folds, rounded up (ops/paged_attention.
        _walk_shape's P; `group`'s own under a grouped pool). The gather
        path loops over nothing."""
        if not self._kv_walks:
            return np.zeros_like(walk)
        pages = self._walk_pages[phase][group]
        return -(-(walk // self.kv_block_size) // pages)

    # -- launch-level device-time attribution (ISSUE 17) ---------------------
    def _prof_note_launch(self, t_launch: float, snapshot, rec: dict):
        """Open one per-tenant attribution record for a launch (worker
        thread, called at the dispatch boundary ONLY behind the
        `self._trace_rate > 0` guard — at the default rate 0 this method
        is unreachable from the hot path). Its attrs are the launch
        record's. It closes at the matching packed fetch
        (_prof_close_launch), keyed by the launch's own perf_counter
        timestamp: fetches drain the inflight deque FIFO in launch
        order, so lag-pipelined launches attribute correctly without
        any extra device sync."""
        targets = [
            (r.trace_ctx.trace_id, r.trace_ctx.span_id)
            for r in snapshot
            if r is not None and r.profiled and r.trace_ctx is not None
        ]
        self._prof_active = len(targets)
        if not targets:
            return
        self._launch_log.append({
            "t_launch": t_launch,
            "wall": time.time(),
            "kind": rec["phase"],
            "targets": targets,
            "attrs": rec,
        })

    def _prof_close_launch(self, t_launch: float, **attrs):
        """Close the oldest launch record IF it belongs to the fetch
        being processed (exact float equality on the launch timestamp —
        unrecorded launches between recorded ones just don't match), and
        emit one `launch.<kind>` span per profiled tenant into the
        engine's span store, parented under that request's inbound span
        so the assembled tree nests router → replica → launch."""
        if not self._launch_log or self._launch_log[0]["t_launch"] != t_launch:
            return
        rec = self._launch_log.popleft()
        t1 = time.time()
        span_attrs = dict(rec["attrs"])
        span_attrs.update(attrs)
        span_attrs["launch_to_fetch_s"] = round(
            time.perf_counter() - t_launch, 6
        )
        store = self.engine.trace_store
        for trace_id, parent in rec["targets"]:
            store.add_span(
                trace_id, f"launch.{rec['kind']}", rec["wall"], t1,
                parent_id=parent, attrs=span_attrs,
            )

    def trace_step_programs(self, on: bool) -> dict:
        """The profiler session's seam (serving.server._Profiler). on: from
        now the first dispatch of each step program keeps its abstract
        arguments. Off: nothing is kept, and what was comes back as
        {program: a callable that lowers it again}, from which the session's
        end writes instruction -> scope beside the profile
        (utils/tracing.write_program_scopes). A backend that cannot lower
        its step programs from abstract arguments (a pp mesh's) keeps
        nothing."""
        lower = getattr(self.backend, "lower_step", None)
        calls, self._step_calls = self._step_calls, (
            {} if on and lower is not None else None
        )
        return {
            name: functools.partial(lower, name, *call)
            for name, call in (calls or {}).items()
        }

    def _step_program(self, name: str, *args, **kwargs):
        """Dispatch the backend's step program `name` (the decode chunk or
        the mixed step). Outside a profiler session that is all: one
        attribute is tested."""
        calls = self._step_calls
        if calls is not None and name not in calls:
            calls[name] = abstract_call(args, kwargs)
        return getattr(self.backend, name)(*args, **kwargs)

    def _launch_chunk(self):
        """Launch one decode chunk over the current fleet (paged /
        constrained / plain slot program — state, cache, and fsm chain
        device-side between launches, so no fetch is needed to launch the
        next chunk). K = chunk_steps is what is DISPATCHED; the paged
        program runs steps only while a row of the fleet is active
        (engine/paged.steps_while_active), so a chunk whose last live row
        ends at its j-th step costs j forwards, and a dead fleet's none.
        The record says what the position model expects of it
        (`steps_live`, the largest of the rows' live steps; `steps` stays
        K) and the fetch what the device did (`steps_run`). Host
        bookkeeping that counts steps (`_steps_dispatched`,
        `_steps_inflight`, `_ended_at`, dli_slot_turnover_steps) counts
        DISPATCHED steps: they are known here, without a fetch. Returns
        the inflight tuple (packed results dev array, assignment
        snapshot, launch time, mutation seq, launch record) or None when
        no slot is active."""
        if not any(r is not None for r in self._assignment):
            return None
        faults.check("decode_launch", tag=",".join(
            r.prompt for r in self._assignment if r is not None
        ))
        pages = None
        wrows = []
        if self._wgrp is not None:
            wrows = [
                (b, int(self._host_pos[b]),
                 int(min(self.chunk_steps,
                         self._host_end[b] - self._host_pos[b])))
                for b, r in enumerate(self._assignment)
                if r is not None and self._host_pos[b] < self._host_end[b]
            ]
        wfields = self._window_ensure(wrows)
        if self.paged:
            if self._table_dev is None:
                self._table_dev = self._launch_table()
            # adapter serving: the per-slot page snapshot rides every
            # launch (pages=None when no pool is attached — a DISTINCT
            # compiled program that lowers byte-identically to the
            # pre-adapter build)
            if self._adapters is not None:
                pages = self._snapshot(self._slot_pages)
        snapshot = list(self._assignment)
        K = self.chunk_steps
        # host position model: row b runs min(K, steps left of its
        # budget) steps from _host_pos[b], each reading its clipped
        # length so far
        rows = np.array([r is not None for r in snapshot])
        live = np.clip(self._host_end - self._host_pos, 0, K) * rows
        step = np.arange(K)
        at = self._host_pos[:, None] + step
        alive = step < live[:, None]  # the device holds the row active
        span, diff_fields = 1, {}
        if self._blk:
            # `at` counts forwards: each reads the row's whole cache and
            # its open block
            diff_fields = self._blk_fields(at, alive, K)
            at, span = self._blk_reads(at)
        if self._recurrent:
            diff_fields = self._state_fields(
                [(int(self._host_pos[b]), int(live[b]))
                 for b in np.flatnonzero(live)], int(live.sum()), steps=K,
            )
        if self._sparse is not None:
            diff_fields.update(
                self._sparse_fields("chunk", (at + 1)[alive], K))
        rec = self._launch_record(
            "chunk", K,
            **self._kv_fields(
                "chunk",
                lambda w: np.sum(self._kv_span(at, span, w) * alive),
                lambda w: self._kv_walk(at, alive * span, w)),
            row_steps=int(live.sum()),
            decode_rows=int(np.count_nonzero(live)),
            steps_live=int(live.max()), **diff_fields, **wfields,
        )
        # every believed-active slot advances K: a row that outlives the
        # chunk forces all K steps, one that dies mid-chunk is frozen
        # whether the steps behind it run or not (the frozen-row rule)
        self._host_pos[rows] += K
        self._clock.mark("dispatch", "launch.chunk", **rec)
        steps_run = K  # the dense slot programs scan: every step runs
        if self._blk:
            emitted, mask, self.state, self.cache, self._diff, steps_run = (
                self._step_program(
                    "decode_slots_paged",
                    self.state, self.cache, self._table_dev,
                    self._next_key(), self.sparams,
                    num_steps=self.chunk_steps, diff=self._diff,
                )
            )
        elif self.paged:
            emitted, mask, self.state, self.cache, steps_run = (
                self._step_program(
                    "decode_slots_paged",
                    self.state, self.cache, self._table_dev,
                    self._next_key(), self.sparams,
                    num_steps=self.chunk_steps, pages=pages,
                )
            )
        elif self._ctable.any_active:
            # >= 1 constrained tenant: the constrained slot program
            # (two extra gathers; free rows make it a no-op for
            # unconstrained slots). The fsm chunk output chains
            # device-side exactly like state/cache.
            cm, ct = self._ctable.device_tables()
            emitted, mask, self.state, self.cache, self._fsm = (
                self.backend.decode_slots_constrained(
                    self.state, self.cache, self._next_key(),
                    self.sparams, self._fsm, cm, ct,
                    num_steps=self.chunk_steps,
                )
            )
        else:
            emitted, mask, self.state, self.cache = (
                self.backend.decode_slots(
                    self.state, self.cache, self._next_key(),
                    self.sparams, num_steps=self.chunk_steps,
                )
            )
        packed = G.pack_chunk(emitted, mask, self.state.active, steps_run)
        if self._routed_shape is not None:
            packed = self._P.pack_routed(packed, self.cache["routed"])
        self._window_release(wrows)
        t_launch = self._clock.mark("plan")
        self._clock.device_empty = False  # the device has this launch queued
        if self._trace_rate > 0.0:
            self._prof_note_launch(t_launch, snapshot, rec)
        if self._chunked:
            # row b's last live step is this chunk's live[b]-th
            self._release_ended(snapshot, live - K)
        return (packed, snapshot, t_launch, self._mutation_seq, rec)

    def _release_ended(self, snapshot, last_live=0):
        """Release by the host position model (chunked paged scheduler).
        Called right after a launch's dispatch and its advance of
        `_host_pos`: a row of the launch with `_host_pos[b] >=
        _host_end[b]` has its budget's last step inside a launch that is
        already dispatched. The device freezes a row at remaining == 0
        and runs programs in dispatch order, so the row is dead for
        every later launch whatever the fetch will say (an EOS, a stop
        or a kill only ends it sooner), and slot b is let again now,
        not chunk_lag fetches later. The tenant becomes the slot's
        RETIRING tenant: `_distribute` still credits it the launch's
        emissions by the snapshot and finalizes it from the launch's own
        active row, so both kinds of release meet in `_finalize`.

        Its pool blocks go back with the slot: every program that reads
        them is dispatched, a later tenant's writes ride later programs,
        and what the prefix index holds stays under the index's own
        refcounts. The one reader that is not a dispatched program is
        the shadow capture `_distribute` queues after the fetch: with a
        shadow store the blocks stay the retiring tenant's until then.

        The bound has to be exact, so the fetch keeps releasing a row
        whose steps the model does not bound: a constrained row (its FSM
        row is the slot's device state), a slot with a verify row in
        this launch or unfetched (`_host_pos` lags by an accept count
        only the fetch knows), and a slot whose previous retiring tenant
        is still unfetched (a slot has at most one). `last_live[b]`
        places the row's last live step among the steps dispatched so
        far (a chunk's row can end before the chunk does, and the chunk
        itself ends on the device with its last live row: the count stays
        in DISPATCHED steps, the one unit the host knows without a
        fetch)."""
        ended = self._host_pos >= self._host_end
        if not ended.any():
            return
        at = self._steps_dispatched + np.broadcast_to(
            last_live, ended.shape
        )
        for b, req in enumerate(snapshot):
            if (
                req is None or not ended[b]
                or self._assignment[b] is not req
                or self._retiring[b] is not None
                or b in self._spec_pending or req.cart is not None
            ):
                continue
            self._free_slot_resources(req, by="model")
            with self._cv:
                queued = bool(self._queue or self._resume)
            self._ended_at[b] = int(at[b]) if queued else None

    def _loop_inner(self):
        # In-flight decode chunks, oldest first. Launch up to chunk_lag
        # chunks before blocking on the oldest fetch, so the device stays
        # fed even when the fetch RTT exceeds a chunk's compute. Admission
        # (insert_slot) and kill (kill_slot) mutate the FUTURE-most state,
        # which is exactly the one the next launch uses.
        inflight: collections.deque = collections.deque()
        # a restart abandoned any in-flight launches — their attribution
        # records can never be closed (the fetches died with the crash)
        self._launch_log.clear()
        self._steps_inflight = 0
        self._clock.mark("admit")  # restore + recovery are re-admission
        # nothing is dispatched that will be fetched: the device's queue is
        # empty as far as this loop knows, and the next launch has no
        # predecessor to be timed from
        self._clock.device_empty = True
        self._timer.reset()
        # warm restore FIRST (supervisor restart or --restore-dir start):
        # the rebuilt pool takes the shadowed blocks back in one scatter
        # and the block-prefix index re-learns the chains, so the
        # serialized salvage re-admissions below hit them and re-prefill
        # only their partial tail. Runs under the supervisor: a crash
        # here is contained, resources released, and the restore retried
        # next round (tests/test_recovery.py double-fault leg).
        if self._needs_restore:
            self._needs_restore = False
            self._restore_shadow()
        # after a supervisor restart: serially re-admit salvaged requests
        # (no-op on a clean start; also clears the restarting flag)
        self._run_recovery()
        if self._chunked:
            # SLO-aware chunked-prefill scheduling (engine/scheduler.py):
            # admissions land chunk by chunk inside mixed launches
            # instead of prefilling whole before the fleet advances
            self._sched_loop(inflight)
            return
        while True:
            self._clock.mark("wait_work")
            with self._cv:
                while (
                    not self._queue
                    and not self._resume
                    and not any(self._assignment)
                    and not inflight
                    and not self._closed
                ):
                    self._cv.wait()
                if self._closed:
                    self._clock.mark(None)
                    return
                queue_head = bool(self._queue or self._resume)
            self._note_groups()
            self._clock.mark("admit")
            if queue_head:
                self._admit()
            self._clock.mark("plan")
            chunk = self._launch_chunk()
            launched = chunk is not None
            if launched:
                inflight.append(chunk)
            # Block on the oldest chunk when MORE than chunk_lag chunks
            # are unprocessed (so chunk_lag=1 keeps one outstanding after
            # draining — the classic fetch-N-1-overlaps-compute-N) — or
            # when nothing launched (all slots looked idle to the host:
            # drain so finished requests finalize and new work can wake us)
            while inflight and (len(inflight) > self.chunk_lag
                                or not launched):
                self._process(inflight.popleft())
                launched = True  # drain one per wakeup once non-empty

    # -- chunked-prefill scheduler loop (engine/scheduler.py) ----------------
    def _sched_loop(self, inflight: collections.deque):
        """Token-budget scheduling: each iteration starts any queued
        requests a free slot + pool blocks can take (as PrefillJobs — no
        device work yet), then launches ONE step. With pending prefill
        work the step is a MIXED ragged launch (every active decode row
        plus budget-sliced prefill chunks — engine/paged.
        mixed_step_ragged); a fleet with no prefill pending falls back to
        the amortized multi-step decode chunk, which runs the identical
        slot_step math over the same pool. Lag pipelining, crash
        supervision, drain, and recovery all work exactly as in the
        whole-prefill loop — mixed steps plan from the host position
        model and gather decode tokens from slot state ON DEVICE, so no
        fetch is ever needed to launch the next step."""
        while True:
            self._clock.mark("wait_work")
            with self._cv:
                while (
                    not self._queue
                    and not self._resume
                    and not any(self._assignment)
                    and not inflight
                    and not self._closed
                ):
                    self._cv.wait()
                if self._closed:
                    self._clock.mark(None)
                    return
            self._clock.mark("reap")
            self._reap_jobs()
            self._note_groups()
            self._clock.mark("admit")
            self._start_jobs()
            self._clock.mark("plan")
            spec_rows = self._plan_spec()
            if self._jobs or spec_rows or self._spec_pending:
                # mixed step: prefill chunks and/or verify rows ride the
                # flat token axis with the decode rows. A slot whose
                # verify row is still unfetched keeps the fleet on the
                # mixed program too (its next row's positions derive
                # from slot state, and staying mixed keeps the
                # per-launch emission bookkeeping uniform while verify
                # fetches are pending)
                step = self._launch_mixed(spec_rows)
            else:
                step = self._launch_chunk()
                if step is not None:
                    # drafting pauses until this launch's many-token
                    # emissions are fetched (_chunk_unfetched); the host
                    # position model advanced inside _launch_chunk
                    self._chunk_unfetched += 1
            launched = step is not None
            if launched:
                inflight.append(step)
            while inflight and (len(inflight) > self.chunk_lag
                                or not launched):
                self._process_any(inflight.popleft())
                launched = True

    def _process_any(self, step):
        if isinstance(step, tuple) and step and step[0] == "mixed":
            self._process_mixed(step)
        else:
            self._process(step)
            if self._chunk_unfetched > 0:
                self._chunk_unfetched -= 1

    def _reap_jobs(self):
        """Fail pending prefills whose client went away or whose deadline
        passed BEFORE spending more budget on them (the mid-decode
        equivalents live in _distribute)."""
        deadline = self.engine.engine_cfg.request_deadline_s
        now = time.time()
        for job in list(self._jobs):
            req = job.req
            if req.cancelled:
                req.result = self._cancel_env(req)
            elif self._past_deadline(req, now):
                req.result = self._deadline_env(req, where="mid-prefill")
            elif deadline and now - req.t_start > deadline:
                req.result = {
                    "error": f"Error: request exceeded the {deadline:g}s "
                    "deadline",
                    "status": "failed",
                    "error_type": "timeout",
                }
            else:
                continue
            self._m_preempt.labels(
                reason="cancelled" if req.cancelled else "deadline"
            ).inc()
            self._release(req)  # drops the job via the slot mapping

    # -- adapter page lifecycle (engine/adapters.py) -------------------------
    def _acquire_adapter(self, req: _Request) -> bool:
        """Pin req's adapter page (refcount + HBM upload on a miss) for
        the request's whole slot tenure. Acquired FIRST in admission —
        before any block incref — so every unwind path below it only has
        to release what it took. False = every page is referenced by
        other in-flight requests right now (backpressure, same contract
        as pool-block exhaustion). Base requests are a no-op (page 0)."""
        if req.adapter is None or req.adapter_page is not None:
            return True
        page = self._adapters.acquire(req.adapter)
        if page is None:
            return False
        req.adapter_page = page
        return True

    def _release_adapter(self, req: _Request):
        """Drop req's adapter-page reference (idempotent). The page
        stays RESIDENT at refcount 0 (LRU-parked) — the next request for
        the same adapter re-acquires it without a device write."""
        if req.adapter_page is not None and self._adapters is not None:
            self._adapters.release(req.adapter)
        req.adapter_page = None

    def _start_jobs(self):
        """Move queued requests into PrefillJobs while a slot and pool
        blocks are available. Host-side only — tokenize, plan prefix
        reuse, allocate blocks, install the slot's block table; the
        prompt lands chunk by chunk in subsequent mixed launches. Same
        suspect/_admitting crash discipline as whole-prefill admission."""
        while True:
            with self._cv:
                # preempted requests resume first (see _admit)
                from_resume = bool(self._resume)
                if not from_resume and not self._queue:
                    return
                free = [
                    b for b, r in enumerate(self._assignment) if r is None
                ]
                if not free:
                    self._m_blocked.labels(reason="slot").inc()
                    return
                if not from_resume:
                    head = self._queue[0]
                    if (
                        head.need is not None
                        and head.need > self._alloc.free_blocks + (
                            self._bpx.evictable_blocks()
                            if self._bpx is not None else 0
                        )
                    ):
                        # the admission policy's capacity leg: a previously
                        # sized head that still cannot get blocks (even by
                        # evicting every unreferenced cached chain) waits
                        # for a release — no re-tokenize/replan churn per
                        # step. Preemption happens INSIDE the admission
                        # attempt (the pressure ladder), so a head whose
                        # shortfall a victim could cover is sized with
                        # need=None on its first attempt and reaches it.
                        self._m_blocked.labels(reason="blocks").inc()
                        return
                    req = self._queue.pop(0)
                    self._note_queue_locked()
                else:
                    req = self._resume.pop(0)
            if (
                from_resume and req.allowed is not None
                and len(req.salvaged) >= req.allowed
            ):
                self._finalize(req)
                continue
            try:
                self._suspects.add(req)
                self._mutation_seq += 1
                # survives an exception unwind ON PURPOSE (see _admit)
                self._admitting = req
                if from_resume:
                    # swap-preemption resume: restore the shadowed chain
                    # so the prefix plan below hits it (tail-only chunks)
                    self._prepare_resume(req)
                if req.kwargs.get("constraint") is not None:
                    # constrained requests keep the whole-prefill
                    # admission path (the mixed program carries no
                    # first-token bias operand; _needs_solo routes public
                    # constrained traffic solo anyway — this preserves
                    # the constraint-table backpressure/leak discipline
                    # for embedded callers)
                    first_dev = self._admit_one(req, free[0])
                    self._admitting = None
                    if first_dev is _BLOCKED:
                        self._requeue_blocked(req, from_resume)
                        return
                    if first_dev is not None:
                        req.first_id = int(np.asarray(first_dev)[0])
                        if not req.ttft:
                            req.ttft = time.time() - req.t_start
                        if from_resume and req.preempted_at:
                            self._m_resume_s.observe(
                                time.time() - req.preempted_at
                            )
                        self._post_admit(req)
                    continue
                started = self._start_job(req, free[0])
                self._admitting = None
                if started is _BLOCKED:
                    self._requeue_blocked(req, from_resume)
                    return
                if (
                    started is not None and from_resume
                    and req.preempted_at
                ):
                    self._m_resume_s.observe(time.time() - req.preempted_at)
            except ValueError as e:
                self._admitting = None
                # a validation error can fire AFTER the block grant /
                # constraint-row acquire (e.g. a malformed sampling
                # kwarg float()s late): release everything this failed
                # admission holds or the pool bleeds per bad request —
                # the PR-4 _BLOCKED leak shape on the error path
                self._free_slot_resources(req)
                log.warning("invalid_request", error=str(e))
                req.result = {
                    "error": f"Error: {e}", "status": "failed",
                    "error_type": "invalid_request",
                }
                self._push_final(req)
            # any other exception escapes to the supervisor (crash
            # containment + suspect implication), exactly like _admit

    def _requeue_blocked(self, req: _Request, from_resume: bool):
        """An admission attempt came back _BLOCKED (the attempt counted
        why): back to the FRONT of where it came from (FIFO fairness);
        the fleet keeps decoding until a release frees what it waits
        for."""
        with self._cv:
            if from_resume:
                self._resume.insert(0, req)
            else:
                self._queue.insert(0, req)
                self._note_queue_locked()

    def _start_job(self, req: _Request, slot: int):
        """Plan one chunked admission: tokenize, prefix-reuse lookup at
        EXACT chunk depth, clamp the budget, allocate + map pool blocks,
        and queue the PrefillJob. Returns _BLOCKED when the pool cannot
        take it (caller requeues at the front), None when the request
        failed fast (result already set), or the job."""
        eng, cfg = self.engine, self.cfg
        faults.check("admission", tag=req.prompt)
        req.queue_wait_s += req.trace.checkpoint("queue_wait")
        if req.cancelled:
            req.result = self._cancel_env(req)
            self._push_final(req)
            return None
        if self._past_deadline(req):
            # end-to-end deadline_ms expired while queued: zero prefill,
            # zero pool blocks spent on it
            req.result = self._deadline_env(req, where="while queued")
            self._push_final(req)
            return None
        deadline = eng.engine_cfg.request_deadline_s
        if deadline and time.time() - req.enqueued > deadline:
            req.result = {
                "error": f"Error: request exceeded the {deadline:g}s "
                "deadline while queued",
                "status": "failed",
                "error_type": "timeout",
            }
            self._push_final(req)
            return None
        if not self._acquire_adapter(req):
            # every adapter page is referenced by other in-flight
            # requests: backpressure exactly like pool-block exhaustion
            # (the caller requeues at the front; a release frees a page)
            self._m_blocked.labels(reason="adapter").inc()
            return _BLOCKED
        k = req.kwargs
        text = (
            eng.render_chat(req.prompt)
            if k.get("chat", True) else req.prompt
        )
        ids = eng.tokenizer.encode(text)
        req.prompt_tokens = len(ids)
        if req.salvaged:
            # crash-recovery continuation: prompt + pre-crash tokens
            ids = ids + list(req.salvaged)
        prompt_len = len(ids)
        diffusion = None
        if self._blk:
            # the prompt's whole blocks are what prefill commits; its last
            # partial block stays open, the head of the first generated one
            steps = self._check_denoise(
                int(k.get("denoise_steps", self._denoise_default))
            )
            whole = prompt_len // self._blk * self._blk
            diffusion = (ids[whole:], self._blk // steps)
        if req.kv_hint is not None and req.adapter is None:
            # same remote-hit seam as the whole-prefill admission: a
            # fetched chain becomes a deeper exact-depth hit below.
            # Adapter requests never prefetch — the fabric serves BASE
            # KV chains keyed by token content alone.
            self._fabric_prefetch(req, ids)
        # tier promotion: a host/disk-shadowed chain deeper than the
        # pool's becomes a deeper exact-depth hit below, same as a
        # fabric import (self-gates; can never fail the request)
        self._promote_local_chain(req, ids)
        p0, entry, plan = eng._prefix_plan(
            self._bpx, ids, capacity=self.slot_max_seq, ragged=True,
            adapter=req.adapter,
        )
        if plan is None:
            raise ValueError(
                f"prompt length {prompt_len} exceeds the slot capacity "
                f"(slot_max_seq {self.slot_max_seq})"
            )
        max_tokens, _ = eng._clamp_decode(
            prompt_len, int(k.get("max_tokens", 20)) - len(req.salvaged),
            capacity=self.slot_max_seq,
        )
        if req.allowed is None:
            req.allowed = max_tokens
        else:
            max_tokens = min(max_tokens, req.allowed - len(req.salvaged))
        if req.recovering:
            # a salvage that fell back through the queue (_BLOCKED) and
            # re-entered as a chunked job still counts its recomputed tail
            self._m_recovery_recomputed.inc(prompt_len - p0)
            req.recovering = False
        faults.check("alloc", tag=req.prompt)
        need_total = self._P.blocks_needed(
            prompt_len, max_tokens, self.kv_block_size
        )
        shared = list(entry)[: p0 // self.kv_block_size] if p0 else []
        n_shared = len(shared)
        req.need = need_total - n_shared
        if shared:
            # holders land on block_ids immediately (see _admit_one): a
            # crash inside the pressure ladder releases them cleanly
            self._alloc.incref(shared)
            req.block_ids = list(shared)
        if self._wgrp is not None and not self._wgrp.admit(
            slot, need_total,
            *(self._bpx.side_blocks(shared) if shared else (0, [])),
        ):
            # the window group cannot promise the row its budget yet
            if shared:
                self._alloc.decref(shared)
            req.block_ids = None
            self._release_adapter(req)
            self._m_blocked.labels(reason="blocks").inc()
            return _BLOCKED
        # same pressure ladder as the whole-prefill admission: evict
        # cached chains, then preempt a decoding victim before stalling
        blk_ids = self._alloc_with_pressure(req)
        if blk_ids is None:
            if self._wgrp is not None:
                self._wgrp.release_row(slot)
            if shared:
                self._alloc.decref(shared)
            req.block_ids = None
            self._release_adapter(req)
            self._m_blocked.labels(reason="blocks").inc()
            return _BLOCKED
        req.block_ids = shared + blk_ids
        table_row = np.zeros((self._max_blocks,), np.int32)
        table_row[:need_total] = req.block_ids
        req.prefix_hit_tokens = p0
        if p0:
            self._m_ragged_exact.inc()
        if self._recurrent:
            # the tail of the last shared block (or its snapshot) gives the
            # state at p0 back, so every token of the hit is restored; a
            # cold start lets the slot with zeroed state
            if p0:
                self._m_state_tokens.inc(p0)
            else:
                self._m_state_resets.inc()
        rp = float(k.get("repetition_penalty", 1.0))
        presence_row = (
            np.asarray(eng._presence_rows([ids])[0]) if rp != 1.0
            else np.zeros((cfg.vocab_size,), bool)
        )
        sampling = (
            float(k.get("temperature", 0.7)), int(k.get("top_k", 50)),
            float(k.get("top_p", 0.9)), bool(k.get("greedy", False)),
            float(k.get("min_p", 0.0)), rp,
            float(k.get("frequency_penalty", 0.0)),
            float(k.get("presence_penalty", 0.0)),
        )
        from .scheduler import PrefillJob

        job = PrefillJob(
            req, ids, p0, prompt_len, max_tokens, slot, sampling,
            presence_row, table_row, self._sched.classify(req.slo),
        )
        if diffusion is not None:
            # the job lands the committed blocks alone (p0, a multiple of
            # the pool's block size, never passes them)
            job.ids, job.prompt_len = ids[:whole], whole
            job.diffusion = diffusion
        if self._snap_pool and self._bpx is not None:
            if p0:  # (the lookup cut the hit to a block that has one)
                job.snap_from = self._bpx.snap_of(shared[-1], pin=True)
            job.snap_at = self._snap_points(p0, prompt_len)
        self._table[slot] = table_row
        self._table_dev = None
        self._slot_pages[slot] = req.adapter_page or 0
        self._host_pos[slot] = 0
        # a new tenant's stream predicts nothing about the previous
        # one's: its adaptive-K acceptance EWMA starts fresh
        self._sched.spec_reset(slot)
        req.slot = slot
        # the admitted token sequence: shadow capture keys off it, and
        # the n-gram draft planner reads it as the slot's history head
        req.ids = ids
        req.shadow_depth = 0
        with self._cv:
            self._assignment[slot] = req
        self._jobs.append(job)
        self._prefilling[slot] = job
        log.info(
            "prefill_started", slot=slot, prompt_len=prompt_len,
            tail=job.remaining, prefix_hit=p0, slo_class=job.cls.name,
            request_id=req.trace.request_id,
        )
        return job

    # -- speculative decoding: host-side planning (ISSUE 13) -----------------
    # jaxlint: decode-unreachable -- host-side eligibility check over request kwargs (scheduler worker thread only)
    def _spec_req_ok(self, req: Optional[_Request]) -> bool:
        """Is this tenant a speculation candidate? Greedy only (the
        verify compares the model's own argmax) with every logit-
        mutating knob at its disabled value, so the verify argmax and
        slot_step's penalized argmax coincide bitwise; and the request
        (or the fleet, via engine_cfg.spec_decode) opted in."""
        if req is None or not (self._spec_auto or req.spec_want):
            return False
        k = req.kwargs
        return (
            bool(k.get("greedy", False))
            and float(k.get("repetition_penalty", 1.0)) == 1.0
            and float(k.get("frequency_penalty", 0.0)) == 0.0
            and float(k.get("presence_penalty", 0.0)) == 0.0
            and k.get("constraint") is None
        )

    # jaxlint: decode-unreachable -- host-side launch planning over Python lists (scheduler worker thread only)
    def _plan_spec(self) -> dict:
        """Plan this step's verify rows: {slot: (n_draft, drafts|None,
        pred|None)} (drafts None = device draft-model proposals; pred =
        the optimistic window — drafts + predicted correction — pending
        fetches extend the drafting history with).

        An unfetched verify row never disqualifies its slot — positions
        derive on device, so the only gates are DRAFT QUALITY ones: no
        amortized decode chunk may be unfetched (many-token
        unpredictable advances), every pending launch carrying the slot
        must be a verify launch of THIS tenant with a predicted window
        (a pending plain row adds one token the host cannot predict),
        and — n-gram mode — the optimistic history must offer at least
        a 2-token window (draft + predicted correction) so back-to-back
        drafts stay frontier-aligned under full accept.

        The scheduler picks the global K (0 under decode TPOT pressure
        — speculation self-disables under load), each slot's K is then
        sized by its acceptance EWMA (spec_slot_k — adaptive drafting),
        and clamped to its allocated blocks so a verify write can never
        run the lblk clamp into a live block; the clamp uses the
        PESSIMISTIC frontier (host position + every pending launch's
        maximum advance), since the device may already sit that far
        ahead."""
        if not self._spec_capable:
            return {}
        cand = []
        for b, req in enumerate(self._assignment):
            if (
                req is None or b in self._prefilling
                or req.done.is_set() or req.cancelled
                or not self._spec_req_ok(req)
            ):
                continue
            pending = self._spec_pending.get(b, [])
            if any(e["req"] is not req for e in pending):
                continue  # stale entries from the slot's previous
                # tenant: wait for their fetches to drain
            if not self._draft_mode:
                # the n-gram planner needs an ALIGNED optimistic
                # history; the draft model needs none of these
                # gates (it proposes from true device state)
                if self._chunk_unfetched:
                    continue
                if self._row_inflight[b] > len(pending):
                    continue  # pending PLAIN rows: 1 unpredictable
                    # token each — drafting would desync the frontier
                if any(e["pred"] is None for e in pending):
                    continue
            cand.append(b)
        if not cand:
            return {}
        n_active = sum(
            1 for b, r in enumerate(self._assignment)
            if r is not None and b not in self._prefilling
        )
        k = self._sched.spec_draft_len(
            self._spec_k_max, len(cand), n_active - len(cand),
            active_classes={
                r.slo for b, r in enumerate(self._assignment)
                if r is not None and b not in self._prefilling
            },
            jobs_pending=bool(self._jobs),
        )
        if k <= 0:
            return {}
        bs = self.kv_block_size
        out = {}
        for b in cand:
            req = self._assignment[b]
            # never draft past the slot's allocated blocks: the verify
            # writes K/V at pos..pos+k, and positions beyond the table
            # tail-redirect to the trash block, but positions past
            # MB*bs would CLAMP into the slot's own last live block.
            # pos is the DEVICE frontier, which may lead the host model
            # by every pending launch's advance — clamp against the
            # upper bound, not the lagged host value.
            from .scheduler import spec_block_cap

            pending = self._spec_pending.get(b, [])
            frontier = int(self._host_pos[b]) + sum(
                e["adv"] for e in pending
            )
            blocks = len(req.block_ids) if req.block_ids else 0
            cap = spec_block_cap(blocks, bs, frontier)
            # adaptive drafting: the slot's acceptance EWMA sizes its
            # next draft (0 = plain decode row, no verify tiles)
            kb = min(k, cap, self._sched.spec_slot_k(b, k))
            if kb < 1:
                continue
            if self._draft_mode:
                out[b] = (kb, None, None)
                continue
            head = (
                [req.first_id]
                if req.first_id is not None
                and req.first_id not in self.cfg.all_stop_ids else []
            )
            from .scheduler import ngram_draft

            hist = (req.ids or []) + head + req.tokens
            # optimistic frontier: assume every pending verify row
            # fully accepts its predicted window. Wrong guesses only
            # reject (the verify admits nothing but the model's own
            # argmax); the fetch replaces prediction with truth.
            # Draft kb tokens and PREDICT the correction too
            # (window[-1]) so the next back-to-back plan stays
            # frontier-aligned under full accept.
            for e in pending:
                hist = hist + e["pred"]
            window = ngram_draft(hist, kb + 1)
            if len(window) >= 2:
                out[b] = (len(window) - 1, window[:-1], window)
        return out

    def _launch_mixed(self, spec_rows: Optional[dict] = None):
        """ONE scheduler step: every active decode row plus the budget
        slice of pending prefill chunks — and, for slots in `spec_rows`
        ({slot: (n_draft, drafts|None, pred|None)}), a [current + draft]
        verify row instead of the 1-token decode row — in one mixed
        ragged launch. On a fleet that can speculate every decode/verify
        row's positions are substituted on device (DeviceMeta), so the
        launch is exact even while earlier verify rows are unfetched.
        Returns the inflight tuple ("mixed", packed dev, decode snapshot,
        {slot: req} completions, launch time, mutation seq, spec
        bookkeeping, launch record) or None when the fleet is empty."""
        P = self._P
        spec_rows = spec_rows or {}
        # positions come from slot state on a fleet that can speculate,
        # so an unfetched verify row never freezes its slot: every
        # assigned decode slot rows EVERY step
        active = [
            b for b, r in enumerate(self._assignment)
            if r is not None and b not in self._prefilling
        ]
        # speculated tokens debit the step budget exactly like prefill
        # tokens: a verify row reserves ceil((1+k)/tile) query tiles
        tile = self._ragged_tile
        n_decode_tiles = sum(
            -(-(1 + spec_rows[b][0]) // tile) if b in spec_rows else 1
            for b in active
        )
        plan = self._sched.plan(
            n_decode_tiles, self._jobs,
            active_classes={
                self._assignment[b].slo for b in active
            },
            n_decode_tokens=len(active) + sum(
                spec_rows[b][0] for b in active if b in spec_rows),
        )
        # a block-diffusion prompt whose whole blocks are all mapped from
        # the prefix index (or shorter than a block) has no chunk to land:
        # its slot is armed by this launch all the same
        landed = [job for job in self._jobs if job.remaining == 0]
        if not active and not plan and not landed:
            return None
        faults.check("decode_launch", tag=",".join(
            r.prompt for r in self._assignment if r is not None
        ))
        if plan:
            faults.check("prefill", tag=",".join(
                job.req.prompt for job, _ in plan
            ))
        W, B = self._sched_width, self.n_slots
        Bd = self._blk
        if Bd:
            # a decode row is its open block, behind the owed block where
            # this forward carries one: `block` or 2 x `block` query
            # tokens of one tile (the start is a placeholder: the
            # device's own state.pos is substituted, as for a verify row)
            fwd = self._host_pos.copy()
            starts, spans = self._blk_reads(fwd)
            alive_now = fwd < self._host_end
        entries = []
        for b in active:
            if Bd:
                entries.append(
                    (b, int(starts[b]), int(spans[b]), P.RAGGED_PREFILL)
                )
            elif b in spec_rows:
                # verify row: [current + k drafts] — a short prefill-kind
                # row over the slot's own block table (the whole point:
                # the ragged kernel already serves it, no new kernel)
                entries.append((
                    b, int(self._host_pos[b]), 1 + spec_rows[b][0],
                    P.RAGGED_PREFILL,
                ))
            else:
                entries.append(
                    (b, int(self._host_pos[b]), 1, P.RAGGED_DECODE)
                )
        chunk_list = []
        snaps_taken = snaps_restored = 0
        if self._snap_pool:
            # (by slot: the snapshot a row starts from, and the one it leaves)
            snap_restore = np.full((B,), -1, np.int32)
            snap_take = np.full((B,), -1, np.int32)
        for job, n in plan:
            start = job.p0 + job.done
            first = self._recurrent and job.done == 0
            kind = P.RAGGED_FIRST if first else P.RAGGED_PREFILL
            if self._snap_pool:
                # a chunk ends where a snapshot is due, and the launch
                # leaves the row's state there in the snapshot pool
                due = min((at for at in job.snap_at if at > start),
                          default=None)
                if due is not None and start + n >= due:
                    n = due - start
                    take = self._bpx.snap_alloc()
                    if take >= 0:
                        job.snaps[due // self.kv_block_size - 1] = take
                        snap_take[job.slot] = take
                        snaps_taken += 1
                if first and job.snap_from >= 0:
                    snap_restore[job.slot] = job.snap_from
                    self._bpx.snap_unpin(job.snap_from)
                    snaps_restored += 1
            entries.append((job.slot, start, n, kind))
            chunk_list.append((job, n, start))
        meta, tok_row, tok_pos, offsets, stats = P.build_ragged_meta(
            entries, width=W, tile=tile,
        )
        tokens_live = sum(n for _, _, n, _ in entries)
        if tokens_live > self._live_width:
            raise ValueError(
                f"launch overflow: {tokens_live} live tokens, the mixed "
                f"step computes {self._live_width}")
        # (a decode row whose budget ran out is dead on the device)
        wrows = [] if self._wgrp is None else [
            (b, start, n) for i, (b, start, n, _) in enumerate(entries)
            if i >= len(active) or self._host_pos[b] < self._host_end[b]
        ]
        wfields = self._window_ensure(wrows)
        dev_dev = None
        toks = np.zeros((W,), np.int32)
        dec_flag = np.zeros((W,), bool)
        dec_idx = np.full((B,), -1 if Bd else 0, np.int32)
        n_dec = len(active)
        if Bd:
            # every token of a decode row comes from the device's
            # DiffState, at offsets from state.pos that start one block
            # below it where the owed block rides (mixed_step_ragged)
            *dev_np, dec_idx[active] = P.build_block_meta(
                entries, offsets, [spans[b] > Bd for b in active],
                block=Bd, width=W, tile=tile,
            )
            dev_dev = P.DeviceMeta(*map(jnp.asarray, dev_np))
        elif self._spec_capable:
            # mark every decode/verify entry (the first n_dec) for
            # on-device position substitution — the host start values
            # above are placeholders for those rows
            dev_dev = P.DeviceMeta(*map(jnp.asarray, P.build_device_meta(
                entries, offsets, n_dec, width=W, tile=tile,
            )))
        K1 = self._spec_k_max + 1
        sp_on = np.zeros((B,), bool)
        sp_idx = np.zeros((B, K1), np.int32)
        sp_nd = np.zeros((B,), np.int32)
        dec_on = np.zeros((B,), bool)
        for b, off in () if Bd else zip(active, offsets[:n_dec]):
            # the entry's FIRST flat slot is dec_flag-substituted from
            # device state (token AND position) for plain decode rows
            # and verify rows alike
            dec_flag[off] = True
            if b in spec_rows:
                kb, drafts, _pred = spec_rows[b]
                sp_on[b] = True
                sp_nd[b] = kb
                idxs = off + np.arange(K1, dtype=np.int32)
                idxs[kb + 1:] = off + kb  # pad by repeating the last
                sp_idx[b] = idxs
                if drafts is not None:  # n-gram drafts ride the host plan
                    toks[off + 1 : off + 1 + kb] = drafts
            else:
                dec_on[b] = True
                dec_idx[b] = off
        completions = {}
        arm = self._idle_arm
        arm_np = None
        landing = list(zip(chunk_list, offsets[n_dec:])) + [
            ((job, 0, job.p0), 0) for job in landed
        ]
        for (job, n, start), off in landing:
            toks[off : off + n] = job.ids[start : start + n]
            if job.done == 0 and self._ended_at[job.slot] is not None:
                # the slot's next tenant starts to land: the steps
                # dispatched since the previous row's last live step
                self._m_turnover.observe(
                    self._steps_dispatched - self._ended_at[job.slot]
                )
                self._ended_at[job.slot] = None
            job.done += n
            if job.remaining == 0:
                # final chunk: the launch samples this admission's first
                # token and arms its slot ON DEVICE (vectorized arm_slot
                # in mixed_step_ragged); the host learns the first token
                # from the same packed fetch as the decode results
                if arm_np is None:
                    arm_np = self._fresh_arm()
                (on, idx, plen, mtk, sp, presence) = arm_np
                s = job.slot
                on[s] = True
                idx[s] = off + n - 1
                plen[s] = job.prompt_len
                mtk[s] = job.max_tokens
                (sp[0][s], sp[1][s], sp[2][s], sp[3][s], sp[4][s],
                 sp[5][s], sp[6][s], sp[7][s]) = job.sampling
                presence[s] = job.presence_row
                completions[s] = job.req
                # (a diffusion row's budget is whole: no first token
                # comes out of its prefill)
                job.req.budget = job.max_tokens - (0 if Bd else 1)
        diffusion = {}
        if Bd:
            # (completion-free steps reuse the device-resident idle rows,
            # as they reuse the idle arm)
            diffusion = {"diff": self._diff, "darm": self._idle_darm}
        if Bd and completions:
            d_open = np.full((B, Bd), self.cfg.mask_token_id, np.int32)
            d_skip = np.zeros((B,), np.int32)
            d_reveal = np.ones((B,), np.int32)
            for s, req in completions.items():
                head, reveal = self._prefilling[s].diffusion
                d_open[s, : len(head)] = head
                d_skip[s], d_reveal[s] = len(head), reveal
            # (an armed row owes nothing: admission committed every block
            # below its open one)
            diffusion["darm"] = P.DiffState(*map(jnp.asarray, (
                d_open, d_skip, d_reveal, np.zeros((B, Bd), np.int32),
                np.zeros((B,), bool),
            )))
        if arm_np is not None:
            (on, idx, plen, mtk, sp, presence) = arm_np
            arm = P.MixedArm(
                jnp.asarray(on), jnp.asarray(idx), jnp.asarray(plen),
                jnp.asarray(mtk),
                G.SlotParams(*(jnp.asarray(a) for a in sp)),
                jnp.asarray(presence),
            )
        if self._table_dev is None:
            self._table_dev = self._launch_table()
        # the spec operands ride only when needed: launches with no
        # verify row dispatch the plain program — the pre-speculation
        # fast path, byte-identical
        spec_plan_dev = spec_toks_dev = None
        spec_meta = None
        if self._draft_mode:
            # keep the DRAFT pool tracking the canonical stream: every
            # mixed step lands its prefill chunks and each decode row's
            # current token (dec_flag-substituted from slot state, like
            # the target) in the draft model's pool — so the propose
            # chain's context matches the target's position for
            # position. Launches the fleet serves through the amortized
            # chunk program leave draft-pool holes; those only ever
            # degrade draft QUALITY (acceptance is verified against the
            # target's own argmax).
            self._dpool = P.mixed_fill_draft(
                self._dcfg, self._dparams, jnp.asarray(toks),
                jnp.asarray(tok_row), jnp.asarray(tok_pos),
                jnp.asarray(dec_flag), jnp.asarray(meta), self._dpool,
                self._table_dev, self.state.token, self.state.pos,
                dev=dev_dev,
            )
        if spec_rows:
            spec_plan_dev = P.SpecPlan(
                jnp.asarray(dec_on), jnp.asarray(sp_on),
                jnp.asarray(sp_idx), jnp.asarray(sp_nd),
            )
            spec_meta = {
                b: (self._assignment[b], spec_rows[b][0])
                for b in spec_rows
            }
            if self._draft_mode:
                # batched greedy draft chain from every slot's current
                # (token, pos) over the shared block tables; the
                # proposals feed the mixed program as a device operand —
                # zero host syncs anywhere in the draft path
                spec_toks_dev, self._dpool = P.draft_propose_paged(
                    self._dcfg, self._dparams, self.state.token,
                    self.state.pos, self._dpool, self._table_dev,
                    draft_len=self._spec_k_max,
                )
        # adapter serving: the per-slot page snapshot rides the launch
        # (row -> page via the same tok_row indirection as the block
        # table; page 0 = base). pages=None when no pool is attached —
        # a distinct program that lowers byte-identically to before.
        pages_dev = (
            self._snapshot(self._slot_pages)
            if self._adapters is not None else None
        )
        # the launch record: a decode / verify row whose budget ran out
        # before this step (its fetch is still on the way) is dead on
        # the device and reads nothing; a prefill chunk reads its prefix
        # and itself once (a lower bound: the kernel reads per query
        # tile)
        live_tiles = stats["tiles"] - stats["pad_tiles"]
        diff_fields = {}
        if Bd:
            rode = np.zeros((B,), bool)
            rode[active] = True
            diff_fields = self._blk_fields(fwd, rode & alive_now, 1)
        if self._recurrent:
            firsts = [(job, st) for job, _, st in chunk_list if st == job.p0]
            touched = [(int(self._host_pos[b]), 1) for b in active
                       if self._host_pos[b] < self._host_end[b]] \
                + [(st, n) for _, n, st in chunk_list]
            # where each of them lies on the axis the model runs on: its
            # tile's place, or under a packed axis the live tokens before it
            at = offsets if self._live_width >= W else np.cumsum(
                [0] + [n for _, _, n, _ in entries[:-1]])
            placed = [(int(a), n)
                      for i, ((b, _, n, _), a) in enumerate(zip(entries, at))
                      if i >= n_dec or self._host_pos[b] < self._host_end[b]]
            diff_fields = self._state_fields(
                touched, len(touched),
                restored=sum(st for _, st in firsts),
                resets=sum(1 for _, st in firsts if st == 0),
                fresh=len(firsts), placed=placed,
            )
            if self._snap_pool:
                diff_fields["state_snapshots_taken"] = snaps_taken
                diff_fields["state_snapshots_restored"] = snaps_restored
        if self._sparse is not None:
            diff_fields.update(self._sparse_fields(
                "mixed", [int(self._host_pos[b]) + 1 for b in active
                 if self._host_pos[b] < self._host_end[b]]
                + [st + n for _, n, st in chunk_list], 1))
        rec = self._launch_record(
            "mixed", 1,
            **self._kv_fields(
                "mixed",
                lambda w: sum(
                    int(self._kv_span(start, n, w))
                    for b, start, n, _ in entries[:n_dec]
                    if self._host_pos[b] < self._host_end[b]
                ) + sum(int(self._kv_span(st, n, w))
                        for _, n, st in chunk_list),
                lambda w: self._kv_walk(meta[:, 1], meta[:, 2], w)),
            row_steps=n_dec,
            decode_rows=n_dec, prefill_chunks=len(chunk_list),
            prefill_tokens=sum(n for _, n, _ in chunk_list),
            spec_drafted=sum(nd for nd, _, _ in spec_rows.values()),
            tiles=stats["tiles"], tiles_live=live_tiles,
            tokens_live=tokens_live, tokens_computed=self._live_width,
            **diff_fields, **wfields,
        )
        self._clock.mark("dispatch", "launch.mixed", **rec)
        out = self._step_program(
            "mixed_step_ragged",
            jnp.asarray(toks), jnp.asarray(tok_row),
            jnp.asarray(tok_pos), jnp.asarray(dec_flag),
            jnp.asarray(meta), self.cache, self._table_dev,
            self.state, self.sparams, self._next_key(),
            jnp.asarray(dec_idx), arm,
            spec=spec_plan_dev, spec_toks=spec_toks_dev,
            dev=dev_dev, pages=pages_dev, **diffusion,
            **({"snaps": (jnp.asarray(snap_restore), jnp.asarray(snap_take))}
               if self._snap_pool else {}),
            # (the program every other fleet dispatched: no operand more)
            **({"live_width": self._live_width}
               if self._live_width < W else {}),
        )
        if Bd:
            *out, self._diff = out
        packed, self.state, self.sparams, self.cache = out
        t_launch = self._clock.mark("plan")
        self._clock.device_empty = False  # the device has this launch queued
        # host position model + completion bookkeeping AFTER the launch
        # is enqueued (the arming rode the program itself). Verify rows
        # do NOT advance here: their advance is data-dependent (the
        # accept count), so the host resyncs from the packed fetch; the
        # pending launch is recorded (predicted window + advance bound)
        # and the slot keeps submitting rows.
        for b in active:
            self._row_inflight[b] += 1
            if b in spec_rows:
                nd, _drafts, pred = spec_rows[b]
                lst = self._spec_pending.setdefault(b, [])
                if lst:
                    self.spec_pipelined += 1
                lst.append({
                    "req": self._assignment[b], "nd": nd,
                    "pred": pred, "adv": nd + 1,
                })
            else:
                self._host_pos[b] += 1
        if spec_rows:
            mode = "draft_model" if self._draft_mode else "ngram"
            drafted = rec["spec_drafted"]
            self._m_spec_launches.labels(mode=mode).inc(len(spec_rows))
            self._m_spec_drafted.inc(drafted)
            self.spec_launches += len(spec_rows)
            self.spec_drafted += drafted
            for b, (nd, _, _) in spec_rows.items():
                self._sched.count_spec_plan(nd)
                req = self._assignment[b]
                if req is not None:
                    req.spec_launches += 1
                    req.spec_drafted += nd
        if self._wgrp is not None and self._bpx is not None:
            # a grouped pool registers a prompt chunk by chunk: a window
            # block has to be the index's before its row gives it back
            # (below), or a document longer than a window would keep only
            # its last one. Whole blocks only, complete once this launch
            # lands; later launches serialize behind it on the device.
            for job, _, _ in chunk_list:
                self._bpx.register(
                    job.ids, job.p0 + job.done, job.req.block_ids,
                    adapter=job.req.adapter,
                    side_blocks=dict(self._wgrp.held(job.slot)),
                    resume=job.registered,
                )
                job.registered = self._bpx.resume
        self._window_release(wrows)
        for slot, req in completions.items():
            job = self._prefilling.pop(slot)
            self._jobs.remove(job)
            if Bd:
                self._blk_base[slot] = job.prompt_len
                self._blk_plan(slot, len(job.diffusion[0]),
                               job.diffusion[1], job.max_tokens)
            else:
                self._host_pos[slot] = job.prompt_len
                self._host_end[slot] = job.prompt_len + req.budget
            if self._bpx is not None and self._wgrp is None:
                # full prompt blocks are complete + immutable once this
                # launch lands; later gathers serialize behind it on
                # device — same register point as the whole-prefill path.
                # Adapter requests register under their ADAPTER root:
                # the KV bytes are adapter-conditioned, so only requests
                # of the same adapter may reuse them.
                self._bpx.register(
                    job.ids, job.prompt_len, req.block_ids,
                    adapter=req.adapter, snaps=job.snaps,
                )
                # (an index past the prompt's whole blocks found no block)
                self._bpx.snap_release(job.snaps.values())
        if self._shadow is not None:
            # chunk crossed a block boundary -> those blocks are now
            # immutable; the capture gather dispatches BEHIND the mixed
            # launch above, so it reads their final content
            for job, _, _ in chunk_list:
                self._shadow_capture(job.req, written=job.p0 + job.done)
        # flight recorder: the launch record with the scheduler's budget
        # split — only steps that actually interleaved prefill work are
        # recorded (pure-decode steps would flood the ring with no
        # forensic value)
        if chunk_list or spec_rows:
            self.engine.flight.record(
                "plan", **rec, spec_rows=len(spec_rows),
                budget=self._sched.last_plan,
            )
        self._m_sched_chunks.inc(rec["prefill_chunks"])
        self._m_sched_tokens.labels(kind="prefill").inc(
            rec["prefill_tokens"]
        )
        if stats["prefill_rows"]:
            self._m_ragged_rows.labels(kind="prefill").inc(
                stats["prefill_rows"]
            )
        if stats["decode_rows"]:
            self._m_ragged_rows.labels(kind="decode").inc(
                stats["decode_rows"]
            )
        self._m_ragged_tiles.labels(state="pad").inc(stats["pad_tiles"])
        self._m_ragged_tiles.labels(state="live").inc(live_tiles)
        self._m_mixed_tokens["live"].inc(tokens_live)
        self._m_mixed_tokens["computed"].inc(self._live_width)
        # decode snapshot: only rows DECODING at launch (mid-prefill rows
        # emit nothing; the completing slot's first decode token arrives
        # with the NEXT launch) — attribution discipline as ever
        snapshot = [
            self._assignment[b] if b in active else None for b in range(B)
        ]
        if self._trace_rate > 0.0:
            self._prof_note_launch(t_launch, snapshot, rec)
        self._release_ended(snapshot)
        return (
            "mixed", packed, snapshot, completions, t_launch,
            self._mutation_seq, spec_meta, rec,
        )

    def _fresh_arm(self):
        """Mutable numpy MixedArm builder (one per launch WITH
        completions; completion-free steps reuse the device-resident
        idle arm and ship no [B, V] presence buffer)."""
        B, V = self.n_slots, self.cfg.vocab_size
        return (
            np.zeros((B,), bool), np.zeros((B,), np.int32),
            np.zeros((B,), np.int32), np.zeros((B,), np.int32),
            [
                np.ones((B,), np.float32), np.zeros((B,), np.int32),
                np.ones((B,), np.float32), np.ones((B,), bool),
                np.zeros((B,), np.float32), np.ones((B,), np.float32),
                np.zeros((B,), np.float32), np.zeros((B,), np.float32),
            ],
            np.zeros((B, V), bool),
        )

    def _process_mixed(self, step):
        """Fetch one mixed step's packed results: first-token bookkeeping
        for admissions that completed their prefill in that launch,
        verify-row resync/accounting (position advance, accept counts),
        then the shared decode distribution (stop/cancel/deadline/
        finalize) over the combined emission matrix."""
        (_, packed_dev, snapshot, completions, t_launch, seq, spec_meta,
         rec) = step
        faults.check("fetch", tag=",".join(
            r.prompt for r in snapshot if r is not None
        ))
        # [5, B] plain / [5 + 2*(K+1) + 1, B] with a SpecPlan — still the
        # ONE fetch per step
        packed = self._fetch(packed_dev, t_launch, rec)
        E = max(1, self._blk)  # emission rows: a block-diffusion forward's block
        em, mk = packed[:E], packed[E : 2 * E].astype(bool)
        active, firsts, armed = packed[2 * E : 2 * E + 3]
        now = time.time()
        for slot, req in completions.items():
            if req.done.is_set() or req.drop_seq > seq:
                # drop_seq: the tenant was preempted after this step
                # launched — its completion bookkeeping is stale (the
                # resume re-admission regenerates the first token)
                continue
            if not self._blk:
                # (a diffusion row's first tokens come with its first
                # clean block: _distribute stamps its ttft then)
                req.first_id = int(firsts[slot])
                if not req.ttft:
                    req.ttft = now - req.t_start
            prefill_s = req.trace.checkpoint("admission")  # chunked prefill
            with self._cv:
                self.admitted += 1
                if req.record:
                    self.engine.request_count += 1
                occ = sum(r is not None for r in self._assignment)
                self.peak_occupancy = max(self.peak_occupancy, occ)
            self._m_occupied.set(occ)
            self._observe_admission(req, now - req.enqueued, prefill_s)
            log.info(
                "admitted", slot=slot, prompt_len=req.prompt_tokens,
                budget=req.budget, occupancy=occ, chunked=True,
                request_id=req.trace.request_id,
            )
            self._post_admit(req)
        prof_acc = 0  # accepted draft tokens in THIS launch (attribution)
        if spec_meta:
            # combined emission matrix: decode rows keep their one
            # token in row 0, verify rows splice their whole emission
            # stream — _distribute then applies the shared stop/cancel/
            # deadline/finalize/shadow discipline to both uniformly
            B = self.n_slots
            K1 = self._spec_k_max + 1
            sp_emit = packed[5 : 5 + K1]
            sp_mask = packed[5 + K1 : 5 + 2 * K1].astype(bool)
            sp_adv = packed[5 + 2 * K1]
            emitted, mask = em[0], mk[0]
            em = np.zeros((K1, B), emitted.dtype)
            mk = np.zeros((K1, B), bool)
            em[0] = emitted
            mk[0] = mask
            for slot, (req, nd) in spec_meta.items():
                em[:, slot] = sp_emit[:, slot]
                mk[:, slot] = sp_mask[:, slot]
                pend = self._spec_pending.get(slot)
                if pend:
                    # this fetch confirms the slot's OLDEST pending
                    # verify launch (fetches are FIFO) — its predicted
                    # window retires; the actual emissions land in
                    # req.tokens via _distribute below
                    pend.pop(0)
                    if not pend:
                        del self._spec_pending[slot]
                n_emit = int(sp_mask[:, slot].sum())
                acc = max(0, n_emit - 1)
                if (
                    self._assignment[slot] is req
                    and not req.done.is_set() and req.drop_seq <= seq
                ):
                    # position resync: the verify advanced the slot by
                    # the accepted count (+1 on an EOS step) — the host
                    # model catches up
                    self._host_pos[slot] += int(sp_adv[slot])
                    # adaptive-K feedback: the slot's acceptance EWMA
                    # sizes its next draft (same packed fetch, zero
                    # extra syncs)
                    self._sched.observe_spec(slot, nd, acc)
                self._m_spec_accepted.inc(acc)
                self._m_spec_rejected.inc(max(0, nd - acc))
                self._m_spec_hist.observe(n_emit)
                self.spec_accepted += acc
                req.spec_accepted += acc
                prof_acc += acc
        self._distribute(em, mk, active.astype(bool), snapshot, seq=seq)
        for b, r in enumerate(snapshot):
            if r is not None and self._row_inflight[b] > 0:
                self._row_inflight[b] -= 1
        # close this launch's attribution record (empty deque at sample
        # rate 0 — the guard is one truthiness check, no allocation)
        if self._launch_log:
            self._prof_close_launch(t_launch, spec_accepted=prof_acc)
        self._consecutive_crashes = 0
        if seq >= self._mutation_seq:
            self._suspects.clear()

    def _admit(self):
        """Prefill + splice every queued request a free slot can take.

        The whole admission wave's first tokens come back in ONE stacked
        fetch at the end (the EOS/budget decision already happened on
        device inside insert_slot) — per-request blocking fetches would
        sync the host with the device once per admission.
        """
        wave = []  # (req, first_dev [1]) admitted this round
        while True:
            with self._cv:
                # preempted requests resume FIRST: a victim must not also
                # lose its place behind the queue that evicted it
                from_resume = bool(self._resume)
                if not from_resume and not self._queue:
                    break
                free = [b for b, r in enumerate(self._assignment) if r is None]
                if not free:
                    self._m_blocked.labels(reason="slot").inc()
                    break
                if (
                    not from_resume
                    and self.paged
                    and self._queue[0].need is not None
                    and self._queue[0].need > self._alloc.free_blocks + (
                        self._bpx.evictable_blocks()
                        if self._bpx is not None else 0
                    )
                ):
                    # a prior attempt already sized this request (need =
                    # FRESH blocks after any mapped shared head) and the
                    # pool still can't take it even by evicting every
                    # unreferenced cached chain — don't re-tokenize/replan
                    # on every chunk iteration; wait for a release
                    self._m_blocked.labels(reason="blocks").inc()
                    break
                if from_resume:
                    req = self._resume.pop(0)
                else:
                    req = self._queue.pop(0)
                    self._note_queue_locked()
            if (
                from_resume and req.allowed is not None
                and len(req.salvaged) >= req.allowed
            ):
                # budget fully consumed before the preemption landed:
                # finalize straight from the salvage record
                self._finalize(req)
                continue
            try:
                # suspect-set bookkeeping: this request mutates the fleet
                # now; until a chunk launched after this point fetches
                # clean, a scheduler crash implicates it (_supervise)
                self._suspects.add(req)
                self._mutation_seq += 1
                # _admitting stays set through an exception unwind ON
                # PURPOSE: the supervisor reads it to salvage the request
                # a crash cut mid-admission (a finally here would erase
                # the crash's only pointer to it and hang the caller)
                self._admitting = req
                if from_resume:
                    # swap-preemption resume: restore the shadowed chain
                    # into the pool first so _admit_one's prefix plan
                    # hits it and re-prefills only the tail
                    self._prepare_resume(req)
                first_dev = self._admit_one(req, free[0])
                self._admitting = None
                if first_dev is _BLOCKED:
                    # paged pool exhausted: requeue at the FRONT (FIFO
                    # fairness) and stop admitting until a release frees
                    # blocks — the fleet keeps decoding meanwhile
                    self._requeue_blocked(req, from_resume)
                    break
                if first_dev is not None:  # None: failed fast (e.g. queued
                    if from_resume and req.preempted_at:
                        self._m_resume_s.observe(
                            time.time() - req.preempted_at
                        )
                    wave.append((req, first_dev))  # past deadline), result set
            except ValueError as e:
                self._admitting = None
                # release the failed admission's grants (pool blocks,
                # constraint row): a validation error raised between the
                # grant and the insert (late float() of a malformed
                # sampling kwarg, a constraint compile) must not leak —
                # the PR-4 _BLOCKED leak shape on the error path
                self._free_slot_resources(req)
                log.warning("invalid_request", error=str(e))
                req.result = {
                    "error": f"Error: {e}", "status": "failed",
                    "error_type": "invalid_request",
                }
                self._push_final(req)
            # any OTHER exception escapes to the supervisor: the crash is
            # contained there (restart + salvage via _admitting), the
            # request is implicated via the suspect set, and a
            # deterministic admission failure quarantines it within
            # poison_strikes restarts instead of failing fleet-mates
        if not wave:
            return
        firsts = np.asarray(jnp.concatenate([f for _, f in wave]))
        now = time.time()
        for (req, _), first_id in zip(wave, firsts):
            req.first_id = int(first_id)
            if not req.ttft:  # resumed victims keep their first TTFT
                req.ttft = now - req.t_start
            self._post_admit(req)

    def _post_admit(self, req: _Request):
        """First-token bookkeeping shared by the admission wave and the
        recovery path: stop-token-first / zero-budget requests finalize
        immediately (mirroring insert_slot's on-device decision);
        constrained slots arm their fleet-table FSM row — the DFA
        advanced over any salvaged continuation tokens, then the first
        token — BEFORE the next chunk launch (same future-most-state
        contract as insert_slot); streaming clients get their first
        event right after TTFT."""
        self.engine.flight.record(
            "admit", request_id=req.trace.request_id, slot=req.slot,
            prompt_tokens=req.prompt_tokens, budget=req.budget,
            slo_class=req.slo,
            **(self._alloc.span_attrs() if self.paged else {}),
        )
        if req.first_id in self.cfg.all_stop_ids or req.budget == 0:
            self._finalize(req)
            return
        if req.cart is not None:
            cart, off = req.cart
            st = cart.start
            for t in req.salvaged:
                st = cart.advance(st, t)
            self._fsm = self._fsm.at[req.slot].set(
                off + cart.advance(st, req.first_id)
            )
        if req.stream_q is not None:
            self._stream_tokens(req)

    def _admit_one(self, req: _Request, slot: int):
        eng, cfg = self.engine, self.cfg
        if self._wgrp is not None:
            raise ValueError(
                f"{cfg.name}: a pool grouped by layer kind takes chunked "
                f"admissions only (no constraint, no whole prefill)")
        faults.check("admission", tag=req.prompt)
        # everything before this point (bounded queue + worker pickup) is
        # queueing delay; a _BLOCKED retry folds its re-wait in here too
        req.queue_wait_s += req.trace.checkpoint("queue_wait")
        if req.cancelled:
            # a _BLOCKED requeue can carry a request whose client already
            # went away (stream teardown races the pop) — drop it here
            # instead of letting it head-of-line-block the queue and then
            # burn pool blocks + a prefill on a dead request
            req.result = self._cancel_env(req)
            self._push_final(req)
            return None
        if self._past_deadline(req):
            # end-to-end deadline_ms expired while queued: fail before
            # any prefill launch or pool-block grant
            req.result = self._deadline_env(req, where="while queued")
            self._push_final(req)
            return None
        deadline = eng.engine_cfg.request_deadline_s
        if deadline and time.time() - req.enqueued > deadline:
            req.result = {
                "error": f"Error: request exceeded the {deadline:g}s deadline "
                "while queued",
                "status": "failed",
                "error_type": "timeout",
            }
            self._push_final(req)
            return
        if not self._acquire_adapter(req):
            # every adapter page is referenced by other in-flight
            # requests: backpressure, caller requeues at the front.
            # Acquired BEFORE any block incref so the unwind paths below
            # only release what they took on top of it.
            self._m_blocked.labels(reason="adapter").inc()
            return _BLOCKED
        k = req.kwargs
        text = (
            eng.render_chat(req.prompt)
            if k.get("chat", True) else req.prompt
        )
        ids = eng.tokenizer.encode(text)
        req.prompt_tokens = len(ids)
        if req.salvaged:
            # crash-recovery continuation: prefill prompt + the tokens
            # generated before the crash (all host-side), so greedy decode
            # resumes bit-exactly where the fetched stream stopped
            ids = ids + list(req.salvaged)
        prompt_len = len(ids)
        if req.kv_hint is not None and req.adapter is None:
            # router handoff hint: pull the prefix chain from the
            # resident peer BEFORE planning, so the plan below sees it
            # as an ordinary (deeper) block-prefix hit; every fetch
            # failure degrades to the cold plan. Adapter requests never
            # prefetch — fabric chains are BASE-model KV keyed by token
            # content alone.
            self._fabric_prefetch(req, ids)
        # tier promotion: a host/disk-shadowed chain deeper than the
        # pool's block-prefix index becomes a deeper exact-depth hit in
        # the plan below — the disk tier's re-entry point (self-gates;
        # can never fail the request)
        self._promote_local_chain(req, ids)
        # prefix lookup + ingest plan: the solo engine's shared planner
        # helper (one copy of the lookup/cold-fallback/mark discipline);
        # the planner is mode-specific — block-chain index (paged) or
        # dense snapshot cache. ragged=True (a paged fleet) plans the
        # tail as fixed-width launches with NO bucket ladder, so the
        # deepest cached chain is reused at EXACT chunk depth — the
        # degradation walk only runs for the dense fleet's buckets.
        p0, entry, plan = eng._prefix_plan(
            self._bpx if self.paged else self._prefix, ids,
            capacity=self.slot_max_seq, ragged=self.paged,
            adapter=req.adapter,
        )
        if plan is None:
            raise ValueError(
                f"prompt length {prompt_len} exceeds the slot capacity "
                f"(slot_max_seq {self.slot_max_seq})"
            )
        max_tokens, _ = eng._clamp_decode(
            prompt_len, int(k.get("max_tokens", 20)) - len(req.salvaged),
            capacity=self.slot_max_seq,
        )
        if req.allowed is None:
            req.allowed = max_tokens  # total generated-token cap, fixed once
        else:
            # re-admission: never exceed the cap fixed at first admission
            max_tokens = min(max_tokens, req.allowed - len(req.salvaged))
        if req.recovering:
            # warm recovery's headline number: the tail this salvage
            # re-admission actually re-prefills (everything past the
            # restored/mapped head; cold recovery recomputes it all)
            self._m_recovery_recomputed.inc(prompt_len - p0)
            req.recovering = False
        table_row = None
        if self.paged:
            faults.check("alloc", tag=req.prompt)
            need_total = self._P.blocks_needed(
                prompt_len, max_tokens, self.kv_block_size
            )
            # map exactly the planned depth's worth of the entry
            shared = list(entry)[: p0 // self.kv_block_size] if p0 else []
            n_shared = len(shared)
            # need records the FRESH-block shortfall for the head-of-queue
            # backpressure check — the mapped head costs no new blocks
            req.need = need_total - n_shared
            if shared:
                # hold the mapped chain NOW: this admission's own eviction
                # (below) must never reclaim the blocks it is about to
                # map. block_ids carries the holders immediately so a
                # crash inside the pressure ladder (the preempt fault
                # point) releases them through the supervisor's unwind.
                self._alloc.incref(shared)
                req.block_ids = list(shared)
            # full pressure ladder: evict unreferenced cached chains,
            # then PREEMPT a decoding victim (engine_cfg.preempt_policy)
            # instead of stalling — "pool full" is a policy decision now
            blk_ids = self._alloc_with_pressure(req)
            if blk_ids is None:
                if shared:
                    self._alloc.decref(shared)
                req.block_ids = None
                self._release_adapter(req)
                self._m_blocked.labels(reason="blocks").inc()
                return _BLOCKED  # pool exhausted; caller requeues at front
            req.block_ids = shared + blk_ids
            table_row = np.zeros((self._max_blocks,), np.int32)
            table_row[: need_total] = req.block_ids  # tail stays at trash
        if k.get("constraint") is not None:
            # compiled-artifact reuse by constraint hash (the engine LRU),
            # then residency in the fleet's combined table; a full table
            # backpressures exactly like the paged pool
            cart = eng._compile_constraint(k["constraint"])
            req.trace.checkpoint("constraint_compile")
            off = self._ctable.acquire(cart)
            if off is None:
                if req.block_ids is not None:
                    # blocks were granted above: release them (decref —
                    # the mapped head just loses this holder) or every
                    # constraint-backpressure retry would re-allocate and
                    # orphan the first grant
                    self._alloc.decref(req.block_ids)
                    req.block_ids = None
                self._release_adapter(req)
                self._m_blocked.labels(reason="constraint").inc()
                return _BLOCKED  # retry after a release frees rows
            req.cart = (cart, off)
        sampling = G.default_sampling(
            k.get("temperature", 0.7), k.get("top_k", 50),
            k.get("top_p", 0.9), k.get("greedy", False),
            k.get("min_p", 0.0), k.get("repetition_penalty", 1.0),
            k.get("frequency_penalty", 0.0), k.get("presence_penalty", 0.0),
        )
        key = self._next_key()
        scratch = None
        if not self.paged:
            scratch = self._scratch
            self._scratch = None
        req.prefix_hit_tokens = p0
        # repetition-penalty state: the prompt's token-id set, host-built.
        # The fleet always carries presence (a 1.0 penalty is an exact
        # no-op in the sampler), but the prefill's first-token sample only
        # gets it when the penalty is on — keeping the default prefill
        # program identical to the solo path's.
        rp = float(k.get("repetition_penalty", 1.0))
        presence = eng._presence_rows([ids]) if rp != 1.0 else None
        try:
            faults.check("prefill", tag=req.prompt)
            bias = None
            if req.cart is not None:
                # first-token mask from the DFA state the salvaged
                # continuation lands on (the cold path's start state when
                # salvaged is empty — state_bias(start) == start_bias)
                art = req.cart[0]
                st = art.start
                for t in req.salvaged:
                    st = art.advance(st, t)
                bias = jnp.asarray(art.state_bias(st))
            if self.paged:
                # ragged ingest: the tail prefills STRAIGHT INTO THE POOL
                # (flat-token launches through the ragged kernel) — no
                # scratch, no shared-head gather, no insert scatter. A
                # prefix hit attends the mapped blocks in place, at the
                # exact depth the planner found.
                if p0:
                    self._m_ragged_exact.inc()
                first = self._ragged_ingest(
                    ids, p0, table_row, key, sampling, presence, bias,
                    page=req.adapter_page,
                )
            else:
                # shared splice/ingest/store sequence (engine/engine.py) —
                # same machinery, same ordering as the solo path. A
                # grammar constraint masks the FIRST token through the
                # bias operand (engine._constraint_bias), same as solo.
                first, _, scratch = eng._ingest_with_prefix(
                    self._prefix, ids, p0, entry, plan, scratch, key,
                    sampling, presence=presence, bias=bias,
                )
            # prefill token is emitted token #0 (unless EOS — break-before-
            # append); the EOS check happens inside insert_slot on device
            req.budget = max_tokens - 1
            presence_row = (
                presence[0] if presence is not None
                else jnp.zeros((cfg.vocab_size,), bool)
            )
            # one arming-argument tuple for both modes (the dense and
            # paged inserts share generate.arm_slot; sharing the argument
            # list here keeps the call sites from drifting either)
            arm = (
                first[0], jnp.int32(prompt_len), jnp.int32(max_tokens),
                sampling.temperature, sampling.top_k, sampling.top_p,
                sampling.greedy, sampling.min_p, sampling.rep_penalty,
                sampling.freq_penalty, sampling.pres_penalty,
                presence_row,
            )
            if self.paged:
                # the prompt's K/V is ALREADY in the pool blocks: arm the
                # slot's state only (shared generate.arm_slot semantics)
                self.state, self.sparams = self.backend.arm_slot_paged(
                    self.state, self.sparams, slot, *arm
                )
                self._table[slot] = table_row
                self._table_dev = None  # rebuilt at the next chunk launch
                # the slot decodes under the request's adapter page from
                # its first chunk launch (0 = base)
                self._slot_pages[slot] = req.adapter_page or 0
                # chunked mode reaches here through RECOVERY's serialized
                # whole-prefill re-admissions: seed the host position
                # model so subsequent mixed launches plan this row exactly
            else:
                self.cache, self.state, self.sparams = G.insert_slot(
                    cfg, self.cache, scratch, self.state, self.sparams, slot,
                    *arm,
                )
                self._scratch = scratch
            # seed the host position model (every fleet mode: the launch
            # record reads it; chunked mode reaches here through
            # RECOVERY's serialized whole-prefill re-admissions, and
            # subsequent mixed launches plan this row from it)
            self._host_pos[slot] = prompt_len
            self._host_end[slot] = prompt_len + req.budget
            # a whole prefill lands no first chunk to measure a turnover to
            self._ended_at[slot] = None
        except BaseException:
            if req.block_ids is not None:
                # admission died after the block grant (failed prefill,
                # device error): release the blocks (decref — the mapped
                # shared head just loses this holder) or the pool leaks
                self._alloc.decref(req.block_ids)
                req.block_ids = None
            if req.cart is not None:
                # same discipline for the constraint residency refcount
                self._ctable.release(req.cart[0].key)
                req.cart = None
            self._release_adapter(req)  # and the adapter-page refcount
            raise
        finally:
            if not self.paged and self._scratch is None:
                # a failed extend/prefill may have consumed (donated) the
                # scratch buffer mid-sequence; a permanently-None scratch
                # would fail every later admission — reallocate (a paged
                # fleet never holds a scratch at all)
                self._scratch = self.backend.init_cache(1, self._scratch_seq)
        if self.paged and self._bpx is not None:
            # index the prompt's full blocks (complete + immutable once
            # the insert scatter above lands — decode and tail writes only
            # target later positions): the request's own fresh blocks
            # become cached chains, the mapped head is promoted. Later
            # admissions' gathers serialize behind this insert on device.
            # Adapter requests register under their adapter root — the
            # KV bytes are adapter-conditioned.
            self._bpx.register(ids, prompt_len, req.block_ids,
                               adapter=req.adapter)
        # the admitted token sequence: shadow capture keys off it, the
        # n-gram draft planner reads it as the slot's history head
        req.ids = ids
        req.shadow_depth = 0
        if self._shadow is not None:
            # shadow the prompt's full blocks (same immutability point
            # as the register above); the gather rides the launch queue
            # behind the prefill, the copy lands on the shadow thread
            self._shadow_capture(req, written=prompt_len)
        req.slot = slot
        prefill_s = req.trace.checkpoint("admission")  # prefill + splice
        with self._cv:
            self._assignment[slot] = req
            self.admitted += 1
            if req.record:
                eng.request_count += 1
            occ = sum(r is not None for r in self._assignment)
            self.peak_occupancy = max(self.peak_occupancy, occ)
        self._m_occupied.set(occ)
        self._observe_admission(req, time.time() - req.enqueued, prefill_s)
        log.info(
            "admitted", slot=slot, prompt_len=prompt_len,
            budget=req.budget, occupancy=occ,
            request_id=req.trace.request_id,
        )
        return first  # [1] device array; the wave fetches these together

    def _ragged_launch_args(self, chunk_ids, start):
        """Build one ragged launch's device operands (host-side planning —
        engine/paged.build_ragged_meta — plus the flat token buffer) and
        count its composition into the dli_ragged_* families."""
        P = self._P
        W, tile = self._ragged_width, self._ragged_tile
        meta, tok_row, tok_pos, _, stats = P.build_ragged_meta(
            [(0, start, len(chunk_ids), P.RAGGED_PREFILL)],
            width=W, tile=tile,
        )
        toks = np.zeros((W,), np.int32)
        toks[: len(chunk_ids)] = chunk_ids
        self._m_ragged_rows.labels(kind="prefill").inc(stats["prefill_rows"])
        if stats["decode_rows"]:
            self._m_ragged_rows.labels(kind="decode").inc(
                stats["decode_rows"]
            )
        self._m_ragged_tiles.labels(state="pad").inc(stats["pad_tiles"])
        self._m_ragged_tiles.labels(state="live").inc(
            stats["tiles"] - stats["pad_tiles"]
        )
        return (
            jnp.asarray(toks), jnp.asarray(tok_row), jnp.asarray(tok_pos),
            jnp.asarray(meta),
        )

    def _ragged_ingest(self, ids, p0, table_row, key, sampling, presence,
                       bias, page=None):
        """Prefill ids[p0:] straight into the pool through the ragged
        launch programs: whole-width extend launches for the body of the
        tail, then ONE width-padded prefill launch that samples the first
        token off the tail's last flat position. Exactly two compiled
        programs serve EVERY tail length (the recompile guard the
        analysis ragged rule pins), and a prefix hit's mapped shared head
        is attended in place through the block table — no gather, no
        insert scatter, no bucket ladder. Returns the [1] first-token
        device array (the admission wave's stacked-fetch contract).

        `page`: the admission's adapter page id (engine/adapters.py) —
        rides every TARGET launch as the [1] per-row pages operand so
        prompt KV is computed under the adapter's delta. Draft-model
        twins stay base-only (draft quality, never correctness)."""
        be = self.backend
        W = self._ragged_width
        tail = ids[p0:]
        n_full = max(0, (len(tail) - 1) // W)  # leaves >= 1 sampling token
        table1 = jnp.asarray(
            np.asarray(table_row, np.int32)[None, :]
        )  # [1, MB]: this admission's single fleet row
        pages1 = (
            jnp.asarray(np.asarray([page or 0], np.int32))
            if self._adapters is not None else None
        )
        for c in range(n_full):
            toks, tok_row, tok_pos, meta = self._ragged_launch_args(
                tail[c * W : (c + 1) * W], p0 + c * W
            )
            self.cache = be.extend_ragged_paged(
                toks, tok_row, tok_pos, meta, self.cache, table1,
                pages=pages1,
            )
            if self._draft_mode:
                # draft-model speculation: the prompt must land in the
                # draft pool too (draft_spec_loop's prefill-into-BOTH
                # contract) — same launch plan, draft weights
                self._dpool = self._P.extend_ragged_paged(
                    self._dcfg, self._dparams, toks, tok_row, tok_pos,
                    meta, self._dpool, table1,
                )
            self._m_ragged_launches.labels(phase="extend").inc()
        rem = tail[n_full * W :]
        toks, tok_row, tok_pos, meta = self._ragged_launch_args(
            rem, p0 + n_full * W
        )
        if self._draft_mode:
            self._dpool = self._P.extend_ragged_paged(
                self._dcfg, self._dparams, toks, tok_row, tok_pos,
                meta, self._dpool, table1,
            )
        first, _, self.cache = be.prefill_ragged_paged(
            toks, tok_row, tok_pos, meta, self.cache, table1,
            jnp.int32(len(rem) - 1), key, sampling,
            presence=presence, bias=bias, pages=pages1,
        )
        self._m_ragged_launches.labels(phase="prefill").inc()
        if hasattr(be, "ragged_program_count"):
            # warmup compiles show as the gauge's settle point; a gauge
            # that keeps climbing under steady traffic is a
            # recompile-per-admission regression (also machine-checked by
            # the analysis ragged rule on the lowered programs)
            self._m_ragged_programs.set(be.ragged_program_count())
        return first

    def _fetch(self, packed_dev, t_launch: float, rec: dict):
        """The ONE blocking fetch that closes a launch record: the wait
        is the `fetch.<phase>` interval of the worker's clock, everything
        after it `distribute`. A chunk's packed rows end in the count of
        steps the device ran (it stops at its last live row's end): the
        record closes with `steps_run`, which rides the span that follows
        the fetch, and dli_decode_chunk_steps_total counts the dispatched
        steps as run | cut.

        The fetch is also where the worker learns the device's time
        (`utils/tracing.LaunchTimer`): whether the result was ready when it
        arrived (`ready`, on the `fetch.<phase>` span) and when the
        blocking read returned, taken right behind it so that a routed
        fleet's unpacking is in no launch's time. The outcome rides the
        span that follows (`timed`, `device_us`), and the pair
        dli_launch_device_seconds_total / dli_launch_device_steps_total is
        the device's step time by launch kind. dli_decode_step_seconds is
        NOT that: it is launch-to-fetch over the steps that RAN (a mixed
        launch: 1; a dead fleet's empty chunk counts as 1), so under lag-N
        pipelining it holds the wait behind the launches dispatched ahead
        (`steps_per_s.batch` reads its count). When the last unfetched
        launch is fetched the device's queue is empty, and the clock gives
        the seconds until the next dispatch to the phases they pass in
        (`PhaseClock.device_empty`)."""
        phase, steps_run = rec["phase"], rec["steps"]
        ready = packed_dev.is_ready()
        self._clock.mark(
            "fetch_wait", f"fetch.{phase}", seq=rec["seq"], ready=int(ready)
        )
        packed = np.asarray(packed_dev)
        t_fetched = time.perf_counter()
        if phase == "chunk":
            # the last of the chunk's own rows (G.pack_chunk: [2K+2, B]),
            # in front of whatever pack_routed appended
            K = self.chunk_steps * max(1, self._blk)
            steps_run = rec["steps_run"] = int(packed[2 * K + 1, 0])
            self._m_chunk_steps.labels(state="run").inc(steps_run)
            self._m_chunk_steps.labels(state="cut").inc(
                rec["steps"] - steps_run)
        timing, device_s = self._timer.returned(
            phase, t_launch, ready, t_fetched, steps_run, rec["decode_rows"]
        )
        # what the fetch learned: on the span that follows it
        after = {"seq": rec["seq"], "timed": int(timing == "timed"),
                 "device_us": int(device_s * 1e6)}
        if phase == "chunk":
            after["steps_run"] = steps_run
        if self._routed_shape is not None:
            # what the launch's expert layers routed came in the same
            # array: it closes the record and rides the span that follows
            # the fetch, with the launch's seq
            packed, counts = self._P.unpack_routed(packed, self._routed_shape)
            away = 0
            if self._expert_share:
                away, counts = int(counts[0, :, -1].sum()), counts[:, :, :-1]
            rec["moe_pairs"] = int(counts[0].sum())
            self._m_moe_pairs.labels(where="held").inc(rec["moe_pairs"])
            self._m_moe_pairs.labels(where="routed").inc(
                rec["moe_pairs"] + away)
            rec["moe_experts_touched"] = int(counts[1].sum())
            slots = counts[1].size * steps_run
            self._m_moe_tokens.labels(phase=phase).inc(rec["moe_pairs"])
            self._m_moe_touched.labels(phase=phase).inc(
                rec["moe_experts_touched"]
            )
            self._m_moe_slots.labels(phase=phase).inc(slots)
            after.update(
                moe_pairs=rec["moe_pairs"],
                moe_experts_touched=rec["moe_experts_touched"],
                moe_expert_slots=slots,
            )
        now = self._clock.mark("distribute", **after)
        self._steps_inflight -= rec["steps"]
        if self._steps_inflight == 0:
            self._clock.device_empty = True  # until the next dispatch ends
        self._m_step.observe(max(0.0, now - t_launch) / max(1, steps_run))
        return packed

    def _observe_admission(self, req: _Request, wait_s: float,
                           prefill_s: float):
        """A request's first token is on the server: the whole wait
        since enqueue, and its two halves — the `queue_wait` stage spans
        since the last grant and the `admission` span just closed."""
        queue_s, req.queue_wait_s = req.queue_wait_s, 0.0
        if req.record:
            self._m_admission_wait.observe(wait_s)
            self._m_queue_wait.observe(queue_s)
            self._m_prefill.observe(prefill_s)

    def _process(self, chunk):
        """Fetch one decode chunk's packed results and distribute/finalize."""
        packed_dev, snapshot, t_launch, seq, rec = chunk
        faults.check("fetch", tag=",".join(
            r.prompt for r in snapshot if r is not None
        ))
        # [2K+2, B] — the ONE fetch per chunk
        packed = self._fetch(packed_dev, t_launch, rec)
        K = self.chunk_steps * max(1, self._blk)  # a forward's block, row by row
        emitted = packed[:K]
        mask = packed[K : 2 * K].astype(bool)
        active = packed[2 * K].astype(bool)
        self._distribute(emitted, mask, active, snapshot, seq=seq)
        if self._launch_log:
            self._prof_close_launch(t_launch)
        # healthy step: the fleet (as launched) fetched clean — reset the
        # supervisor's consecutive-crash window, and vindicate suspects
        # when no admission happened after this chunk's launch (an older
        # chunk's clean fetch says nothing about a newer tenant)
        self._consecutive_crashes = 0
        if seq >= self._mutation_seq:
            self._suspects.clear()

    def _distribute(self, emitted, mask, active, snapshot, seq=None):
        """Attribute one fetched launch's emissions ([K, B] + final
        active row) to the snapshot's tenants and handle stop / cancel /
        deadline / finalize — ONE copy for the decode-chunk and mixed-
        scheduler fetch paths. `seq` is the chunk's launch-time mutation
        seq: a preempted victim's drop_seq barrier discards emissions
        from chunks launched before its eviction (they are regenerated
        after resume — appending them would corrupt the salvage order)."""
        deadline = self.engine.engine_cfg.request_deadline_s
        now = time.time()
        for b, req in enumerate(snapshot):
            if req is None or req.done.is_set():
                continue  # freed/killed tenant's masked leftovers
            if seq is not None and req.drop_seq > seq:
                continue  # preempted after this chunk launched
            # a retiring tenant's slot was let again when this launch (or
            # a later one) was dispatched: its emissions and its end
            # still arrive here by the snapshot, but the slot's device
            # state is the next tenant's and is never killed from here
            owns = self._assignment[b] is req
            mine = owns or self._retiring[b] is req
            new = emitted[mask[:, b], b]
            req.tokens.extend(int(t) for t in new)
            if self._blk and len(new):
                self._m_diff_tokens.inc(len(new))
                if not req.ttft:  # the first clean block has arrived
                    req.ttft = now - req.t_start
            if len(new) and self._shadow is not None:
                # decode crossed a block boundary? shadow the newly
                # immutable blocks (token content is host-side now, the
                # filling launch was fetched — device order guarantees
                # the gathered bytes are final)
                self._shadow_capture(req)
            gen = None
            if len(new) and req.kwargs.get("stop"):
                gen = self._gen_text(req)  # ONE full decode per chunk
                if gen[2]:
                    # a textual stop sequence fired: kill the slot NOW —
                    # the fleet serves queued work instead of decoding
                    # text the client will never see (solo truncates
                    # post-hoc; the chunk boundary makes early
                    # termination actually save here)
                    if owns:
                        self.state = G.kill_slot(self.state, b)
                        self._m_preempt.labels(reason="stop").inc()
                    self._finalize(req, pre=gen)
                    continue
                if req.stream_q is not None:
                    self._stream_tokens(req, pre=gen)
            elif req.stream_q is not None and len(new):
                self._stream_tokens(req)
            if not mine:
                continue
            if not active[b]:
                self._finalize(req, pre=gen)  # reuse this chunk's decode
            elif req.cancelled:
                # client gone: kill the slot so the fleet admits the next
                # queued request instead of decoding to the dead request's
                # full budget
                if owns:
                    self.state = G.kill_slot(self.state, b)
                self._m_preempt.labels(reason="cancelled").inc()
                log.info("request_cancelled", slot=b, cause=req.cancel_cause)
                req.result = self._cancel_env(req)
                self._release(req)
            elif self._past_deadline(req, now):
                # end-to-end deadline_ms overrun mid-decode: kill the
                # slot, free blocks/constraint row NOW (checked at the
                # launch boundary only — never inside compiled code)
                if owns:
                    self.state = G.kill_slot(self.state, b)
                self._m_preempt.labels(reason="deadline").inc()
                log.info("request_deadline_ms_exceeded", slot=b)
                req.result = self._deadline_env(req)
                self._release(req)
            elif deadline and now - req.t_start > deadline:
                # in-flight overrun: kill the slot, fail the request; the
                # fleet keeps decoding for everyone else
                if owns:
                    self.state = G.kill_slot(self.state, b)
                self._m_preempt.labels(reason="deadline").inc()
                log.error("request_deadline_exceeded", slot=b, deadline_s=deadline)
                req.result = {
                    "error": f"Error: request exceeded the {deadline:g}s deadline",
                    "status": "failed",
                    "error_type": "timeout",
                }
                self._release(req)

    def _gen_text(self, req: _Request) -> tuple:
        """(generated ids — crash-salvaged continuation included — then
        stop-truncated text, stop hit) for req."""
        head = (
            [req.first_id]
            if req.first_id is not None
            and req.first_id not in self.cfg.all_stop_ids else []
        )
        gen_ids = list(req.salvaged) + head + req.tokens
        text = self.engine.tokenizer.decode(gen_ids, skip_special_tokens=True)
        cut, hit = self.engine._truncate_at_stop(
            text, req.kwargs.get("stop")
        )
        return gen_ids, cut, hit

    def _finalize(self, req: _Request, pre=None):
        req.trace.checkpoint("decode")  # admission end -> last chunk fetched
        gen_ids, response, stopped = (
            pre if pre is not None else self._gen_text(req)
        )
        req.trace.checkpoint("detokenize")
        if req.stream_q is not None:
            # flush the held-back tail (U+FFFD / stop hold-back), exactly
            # up to the truncation
            self._stream_tokens(req, final=True, pre=(gen_ids, response, stopped))
        elapsed = time.time() - req.t_start
        n = len(gen_ids)
        tps = n / elapsed if elapsed > 0 else 0.0
        if req.record:
            self.engine._record_sample(
                req.ttft, tps, n, elapsed=elapsed, engine="continuous",
                trace_id=(
                    req.trace_ctx.trace_id
                    if req.trace_ctx is not None else None
                ),
            )
            # SLO feedback: the same per-request TTFT/TPOT samples the
            # timing histograms record feed the scheduler's per-class
            # EWMAs — drain estimates, urgency, and decode protection
            self._sched.observe(
                req.slo, req.ttft or None,
                max(0.0, elapsed - req.ttft) / (n - 1) if n > 1 else None,
            )
            # per-tenant twin of the same samples (tenant EWMAs for the
            # operator's fairness view; no-op for anonymous requests)
            self._sched.observe_tenant(
                req.tenant, req.ttft or None,
                max(0.0, elapsed - req.ttft) / (n - 1) if n > 1 else None,
            )
        req.result = {
            "prompt": req.prompt,
            "response": response,
            "status": "success",
            "time_taken": f"{elapsed:.2f}s",
            "tokens_generated": n,
            "prompt_tokens": req.prompt_tokens,
            "tokens_per_sec": f"{tps:.2f}",
            "ttft_s": round(req.ttft, 4),
            "backend": "continuous",
            "continuous": True,
            # allowed is the total generated-token cap fixed at first
            # admission (budget + 1 there; re-admissions shrink budget but
            # keep allowed, so recovered requests report honestly)
            "finish_reason": (
                "stop" if stopped or n < (
                    req.allowed if req.allowed is not None
                    else req.budget + 1
                ) else "length"
            ),
        }
        if req.slo is not None:
            req.result["slo_class"] = req.slo
        if req.adapter is not None:
            req.result["adapter"] = req.adapter
        if req.tenant is not None:
            req.result["tenant"] = req.tenant
        if req.salvaged:
            # served across a scheduler restart (continuation prefill)
            req.result["recovered"] = True
        if req.preemptions:
            # evicted for pool pressure and resumed (swap or recompute)
            req.result["preempted"] = req.preemptions
        if req.spec_launches or (req.spec_want and self._spec_req_ok(req)):
            # which path served + the draft/accept counts (the solo
            # loops report spec_path "solo" with acceptance on device;
            # a non-greedy/penalized "speculative" request decodes
            # plainly and — like solo — carries no speculative marker)
            req.result["speculative"] = True
            req.result["spec_path"] = "fleet"
            req.result["spec_drafted"] = req.spec_drafted
            req.result["spec_accepted"] = req.spec_accepted
        if req.prefix_hit_tokens:
            req.result["prefix_cached_tokens"] = req.prefix_hit_tokens
        if req.fabric_blocks:
            # prefix blocks pulled over the KV fabric instead of
            # prefilled: the router scores handoff outcomes off this
            req.result["kv_fabric_blocks"] = req.fabric_blocks
        if req.promoted_blocks:
            # prefix blocks promoted out of the local shadow hierarchy
            # (a pushed chain, or a host/disk-tier warm hit) instead of
            # prefilled — a handoff served by a push scores off this
            req.result["kv_promoted_blocks"] = req.promoted_blocks
        if (
            self.fabric_serving and req.ids is not None
            and req.adapter is None
        ):
            # the prompt chain's parent-chained digests (deepest last):
            # the router learns digest->replica residency from these,
            # and a handoff's phase-2 hint carries the deepest one.
            # Adapter requests export NONE — their KV was never
            # shadowed (content keys are base-model-only), so
            # advertising residency would hand out wrong-model bytes
            ds = chunk_digests(
                req.ids, self.kv_block_size,
                max_chunks=len(req.ids) // self.kv_block_size,
            )
            if ds:
                req.result["kv_digests"] = ds[-8:]
        if req.cart is not None:
            req.result["constrained"] = True
        if stopped:
            req.result["stopped"] = True  # a textual stop sequence fired
        log.info(
            "completed", slot=req.slot, tokens=n, elapsed_s=round(elapsed, 3),
            tokens_per_sec=round(tps, 2),
        )
        self._release(req)

    def _free_slot_resources(self, req: _Request, by: str = "fetch"):
        """Return every fleet-held resource of `req` (constraint row +
        FSM reset, pool blocks, block-table row, slot assignment) WITHOUT
        finalizing it — shared by _release (completion/cancel/deadline),
        _preempt_for (the request lives on, parked for resume) and
        _release_ended (`by` "model": the request lives on as the slot's
        retiring tenant until its last launch is fetched)."""
        slot = req.slot
        if slot is not None and self._retiring[slot] is req:
            # the slot went back when the position model saw the row end:
            # its table row, adapter page and assignment are the next
            # tenant's. Only what the retiring tenant kept for a host
            # reader (its blocks, under a shadow store) is left to return
            with self._cv:
                self._retiring[slot] = None
            slot = None
        if self._chunked and slot is not None:
            # mid-prefill teardown (cancel / deadline / EOS-on-first of a
            # just-armed admission): drop the job so the planner stops
            # scheduling chunks for a dead tenant
            job = self._prefilling.pop(slot, None)
            if job is not None and job in self._jobs:
                self._jobs.remove(job)
                if self._snap_pool and self._bpx is not None:
                    # snapshots its launches wrote find no block now
                    self._bpx.snap_release(job.snaps.values())
                    if job.done == 0 and job.snap_from >= 0:
                        self._bpx.snap_unpin(job.snap_from)
        if req.cart is not None:
            # refcount down; the slot's FSM row back to the free state so
            # the row is inert under any still-constrained chunk program
            self._ctable.release(req.cart[0].key)
            if slot is not None:
                self._fsm = self._fsm.at[jnp.int32(slot)].set(0)
            req.cart = None
        if self.paged and req.block_ids is not None:
            # Worker-thread-only mutation (like all allocator use). DECREF,
            # not free: blocks cached by the block-prefix index (or mapped
            # by other live tables) survive this request and keep serving
            # prefix hits; only sole-holder blocks return to the free
            # list. Those freed blocks may be re-granted before in-flight
            # chunks drain: safe, because device execution is serialized
            # in launch order and the new tenant's insert scatter
            # overwrites its whole logical extent before any later decode
            # chunk — and this slot's table row reverts to trash at the
            # next table rebuild, so its frozen row can't touch the old
            # blocks in any chunk launched after this point. (A frozen
            # row's overrun clamp only ever writes the request's OWN last
            # allocated block, which is never a registered/shared one —
            # see ARCHITECTURE.md "Block sharing".)
            # A model release under a shadow store keeps them: the
            # capture _distribute queues after the tenant's last fetch is
            # a host reader, not a dispatched program — they go back
            # when the retiring tenant is finalized.
            if by != "model" or self._shadow is None:
                self._alloc.decref(req.block_ids)
                req.block_ids = None
            if slot is not None:
                self._table[slot] = 0
                self._table_dev = None
        if self._wgrp is not None and slot is not None:
            self._wgrp.release_row(slot)
            self._table_dev = None
        if self.paged and slot is not None:
            # the slot reverts to the base page; later launches carrying
            # the frozen row read page 0 (the all-zero delta — inert)
            self._slot_pages[slot] = 0
        self._release_adapter(req)
        with self._cv:
            if slot is not None and self._assignment[slot] is req:
                self._assignment[slot] = None
                if by == "model":
                    # in the same breath: drain() must never see a
                    # request in neither place
                    self._retiring[slot] = req
                self._m_slot_release.labels(by=by).inc()
            occ = sum(r is not None for r in self._assignment)
            self._cv.notify_all()
        self._m_occupied.set(occ)

    def _release(self, req: _Request):
        self._free_slot_resources(req)
        with self._cv:
            self.completed += 1
        self._push_final(req)

    def _push_final(self, req: _Request):
        """Single completion point: attach the trace (request_id +
        timings), count + log the request (warmup traffic excluded via
        record=False — same exclusion as /stats), then deliver. Streaming
        clients get the terminal envelope event (done: true) on their
        queue, then the done flag unblocks submit()."""
        if req.result is not None:
            self.engine._finish_request(
                req.result, req.trace, engine="continuous",
                record=req.record,
            )
        if req.stream_q is not None and req.result is not None:
            out = dict(req.result)
            out["done"] = True
            req.stream_q.put(out)
        req.done.set()
