"""SPMD pipeline-parallel runtime: all stages in one compiled program.

This replaces the reference's entire distributed fabric — the orchestrator
POSTing JSON activations to worker Flask servers over ngrok tunnels, twice
per token (/root/reference/orchestration.py:114-137, Worker1.py:208-245) —
with a single `jax.shard_map` program over the `pp` mesh axis:

  * each device holds one stage: a contiguous shard of the stacked layer
    params and of the stacked KV cache (parallel/partition.py);
  * the activation hand-off is `lax.ppermute` over the ICI ring — the
    TPU-native form of the reference's HTTP hop (boundaries #2/#3 in
    SURVEY.md §3.1);
  * one microstep = every stage applies its layer shard to its current
    buffer, then the ring shifts; a stage's cache write is gated on the
    microstep owning it, so speculative compute on stale buffers is
    discarded at slice granularity;
  * after S microsteps the last stage's output has rotated to stage 0; a
    masked `psum` broadcasts that [B, 1, D] activation, every device
    computes its VOCAB SHARD of the logits (parallel/vocab.py — embed and
    head are vocab-sharded over pp, not replicated) and the all_gather'd
    logits are identical everywhere, so every device samples the SAME next
    token with the same key — the decode loop (`lax.while_loop`) then
    continues entirely on-device, with zero host round-trips per token.

Latency shape: batch-1 decode costs S microsteps/token (the classic
pipeline bubble — the whole model's FLOPs, just spread over stages);
microbatching (parallel.schedule) fills the bubble for batched configs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..analysis import comms
from ..config import ModelConfig
from ..engine.generate import (
    SamplingParams, count_update, presence_update, stop_mask,
)
from ..models import api as M
from ..ops.sampling import sample_token
from ..ops.wire_quant import masked_psum, wire_bytes, wire_ppermute
from .mesh import AXIS_DP, AXIS_EP, AXIS_PP, AXIS_TP
from .partition import (
    cache_spec, init_sharded_cache, layer_specs, shard_params, shared_specs,
)
from .vocab import embed_sharded, unembed_sharded


def _ring_perm(S: int):
    return [(j, (j + 1) % S) for j in range(S)]


def _replicated_specs(nt_cls):
    """Fully-replicated PartitionSpec tree for a NamedTuple class (slot
    state/params enter every shard_map whole) — field-count-proof: adding
    a field to the NamedTuple updates every spec site automatically."""
    return nt_cls(*([P()] * len(nt_cls._fields)))


class SPMDBackendBase:
    """Shared scaffolding for the SPMD mesh backends.

    Owns the mesh-axis bookkeeping, parameter sharding, shard_map partial,
    per-max_steps decode-program memoization, dp key decorrelation, and the
    per-stage health report. Subclasses implement `_build_prefill()` and
    `_build_decode(max_steps)`.
    """

    name = "spmd-base"
    # HF-parity repetition penalty: subclasses whose builders accept the
    # presence variants set this True (PipelineBackend); others reject
    # loudly at build time
    supports_presence = False

    def __init__(self, cfg: ModelConfig, params: dict, mesh: Mesh,
                 wire_quant=None):
        if wire_quant not in (None, "int8"):
            # same error shape as EngineConfig's validation — backends
            # constructed directly (tests, embedders) fail identically
            raise ValueError(
                f"pp_wire_quant must be None or 'int8', got {wire_quant!r}"
            )
        self.cfg = cfg
        self.mesh = mesh
        self.dp = int(mesh.shape.get(AXIS_DP, 1))
        self.pp = int(mesh.shape[AXIS_PP])
        self.tp = int(mesh.shape.get(AXIS_TP, 1))
        self.ep = int(mesh.shape.get(AXIS_EP, 1))
        self.n_stages = self.pp
        self.tp_axis = AXIS_TP if self.tp > 1 else None
        self.ep_axis = AXIS_EP if self.ep > 1 else None
        # int8 wire format (EngineConfig.pp_wire_quant, ops/wire_quant.py):
        # _wire_ring quantizes the microstep ring's ppermute hops,
        # _wire_bcast the masked-psum broadcasts of the final-stage
        # window. Both stay False on a singleton pp axis — there is no
        # wire, and a quantize round trip there would break the
        # pp == 1 exact-degeneration contract. The context backend
        # widens _wire_bcast for its sp axis (sp >= 2 always transfers).
        self.wire_quant = wire_quant
        self._wire_ring = wire_quant is not None and self.pp > 1
        self._wire_bcast = wire_quant is not None and self.pp > 1
        # dli_pp_wire_bytes_total family — attached by the engine
        # (attach_wire_metrics); accounting is host-side static
        # arithmetic at program-call seams, never traced
        self._wire_metrics = None
        self.shared, self.layers = shard_params(cfg, params, mesh)
        self._layer_specs = layer_specs(cfg, self.layers)
        self._shared_specs = shared_specs(self.shared)
        self._shard = functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False
        )
        # memoized compiled shard_map programs beyond the core pair
        # (extend / ragged variants), keyed by (kind, flags)
        self._programs: dict = {}
        self._prefill = self._build_prefill()
        self._decode_cache: dict = {}

    # -- engine interface ---------------------------------------------------
    def init_cache(self, batch: int, max_seq: int):
        return init_sharded_cache(self.cfg, self.mesh, batch, max_seq)

    def prefill(self, tokens, prompt_len, cache, key, sampling,
                valid_start=None, presence=None):
        if valid_start is not None:
            raise NotImplementedError(
                f"{self.name} does not support ragged (valid_start) batches"
            )
        if presence is not None:
            raise NotImplementedError(
                f"{self.name} does not support repetition-penalty presence"
            )
        return self._prefill(
            self.shared, self.layers, tokens, prompt_len, cache, key, sampling
        )

    def decode(self, first_token, cache, start_pos, limit, key, sampling,
               valid_start=None, presence=None, counts=None, bias=None,
               constraint=None, *, max_steps, with_logprobs=False):
        """One dispatch for every subclass: programs are keyed by
        (max_steps, ragged, presence, counts, bias, constraint, logprobs);
        builders that don't support a variant raise NotImplementedError at
        build time (loud, not silently wrong)."""
        # static wire accounting: a host-int limit bounds the ring passes
        # exactly; a traced limit falls back to max_steps (never forces a
        # device sync for a byte counter)
        self._account_decode_wire(
            int(first_token.shape[0]),
            min(limit, max_steps) if isinstance(limit, int) else max_steps,
        )
        return self._decode_dispatch(
            self._decode_cache, self._variant_builder, first_token, cache,
            start_pos, limit, key, sampling, valid_start, presence, counts,
            bias, constraint, max_steps=max_steps,
            with_logprobs=with_logprobs,
        )

    def _variant_builder(self, variant):
        """variant (max_steps, ragged, pres, wc, wb, wcn, logprobs) ->
        compiled program, through the subclass's _build_decode* hooks."""
        max_steps, ragged, pres, wc, wb, wcn, with_logprobs = variant
        if wcn and not getattr(self, "supports_constrain", False):
            raise NotImplementedError(
                f"{self.name} does not support constrained decoding"
            )
        if wb or with_logprobs or wc or wcn:
            kw = {"with_constraint": True} if wcn else {}
            return self._build_decode_full(
                max_steps, ragged=ragged, with_presence=pres,
                with_counts=wc, with_bias=wb, with_logprobs=with_logprobs,
                **kw,
            )
        if ragged:
            return self._build_decode_ragged(max_steps, with_presence=pres)
        return self._build_decode(max_steps, with_presence=pres)

    def _decode_dispatch(self, memo, builder, first_token, cache, start_pos,
                         limit, key, sampling, valid_start, presence, counts,
                         bias, constraint, *, max_steps, with_logprobs):
        """The ONE copy of the variant->program->args contract (memo key,
        builder selection, limit clamp, positional extra-arg order) —
        shared by the base dispatch and the 1F1B backend's plain-ring
        fallback, which passes its own memo + builder."""
        ragged = valid_start is not None
        pres = presence is not None
        wc = counts is not None
        wb = bias is not None
        wcn = constraint is not None
        variant = (max_steps, ragged, pres, wc, wb, wcn, with_logprobs)
        fn = memo.get(variant)
        if fn is None:
            fn = builder(variant)
            memo[variant] = fn
        # clamp: limit > max_steps would walk dynamic_update_slice off the
        # end of `out` (the start index clamps, corrupting the last column)
        # and inflate n_gen past the buffer
        limit = jnp.minimum(jnp.int32(limit), jnp.int32(max_steps))
        args = [
            self.shared, self.layers, first_token, cache, start_pos, limit,
            key, sampling,
        ]
        for flag, val in (
            (ragged, valid_start), (pres, presence), (wc, counts), (wb, bias)
        ):
            if flag:
                args.append(val)
        if wcn:
            args.extend(constraint)  # fsm0 [B], cmask [S, V], ctrans [S, V]
        return fn(*args)

    def health(self) -> list[dict]:
        """Per-stage liveness — the reference's /workers sweep polls each
        worker's /health with a 5 s timeout and reports online/offline/
        error (orchestration.py:306-329); here a stage is a mesh slice, so
        EVERY device in the stage's (dp, sp, tp) slice gets a tiny timed
        device op (utils/probe.py) instead of an HTTP GET — a dead
        non-first device must not report healthy (round-2 review weak #8).
        All probes run CONCURRENTLY so a fully wedged mesh still answers
        in ~one probe timeout, not devices x timeout."""
        from concurrent.futures import ThreadPoolExecutor

        from ..config import stage_layer_range
        from ..utils.probe import device_memory, probe_device

        devs = self.mesh.devices  # [dp, pp, sp, tp]
        stage_devs = [devs[:, s].reshape(-1) for s in range(self.pp)]
        flat = [d for sd in stage_devs for d in sd]
        # multi-process mesh: only THIS process's devices accept probe ops;
        # other processes' devices report "remote" (their own controller
        # probes them — a mirrored follower runs this same sweep locally)
        me = jax.process_index()

        def probe_local(d):
            if d.process_index != me:
                return {"status": "remote", "process": d.process_index}
            return probe_device(d)

        with ThreadPoolExecutor(max_workers=max(1, len(flat))) as ex:
            flat_probes = list(ex.map(probe_local, flat))
        out = []
        i = 0
        rank = {"online": 0, "remote": 1, "busy": 2, "error": 3, "offline": 4}
        for s in range(self.pp):
            probes = flat_probes[i : i + len(stage_devs[s])]
            i += len(stage_devs[s])
            worst = max(probes, key=lambda p: rank.get(p.get("status"), 2))
            stage_line = {
                "stage": s,
                "devices": [str(d) for d in stage_devs[s]],
                "memory": [
                    device_memory(d) if d.process_index == me else {}
                    for d in stage_devs[s]
                ],
                "layers": list(
                    range(*stage_layer_range(self.cfg.n_layers, self.pp, s))
                ),
                **worst,
            }
            if len(probes) > 1:
                stage_line["device_status"] = [
                    p.get("status") for p in probes
                ]
            out.append(stage_line)
        return out

    # -- the gated microstep ring — shared by every PipelineBackend
    # program AND the sp x pp composition (parallel/context.py); pp == 1
    # degenerates exactly (singleton-axis ppermute is a no-op and the
    # gate is always True) -------------------------------------------------
    def _microstep_loop(self, layers, x, cache, pos, valid_start=None,
                        attn_hook=None, attn_seq_len=None, lora_pages=None):
        """S microsteps of (apply local stage, ring-shift). Returns the
        final-stage output (landed on stage 0 by the last shift) + cache.
        attn_hook/attn_seq_len thread the paged-pool seam (cache = block
        pool, hook = engine/paged.make_paged_hook) through the same gated
        ring — one loop for the dense and paged cache strategies.
        lora_pages threads the paged-adapter delta (engine/adapters) —
        replicated per-row page ids; the lora leaves shard with their
        base projections (parallel/partition.py) so each stage computes
        its local delta shard."""
        cfg, S = self.cfg, self.pp
        s = jax.lax.axis_index(AXIS_PP)
        perm = _ring_perm(S)

        def micro(i, carry):
            buf, cache = carry
            gate = i == s
            y, cache = M.forward_layers(
                cfg, layers, buf, cache, pos, update_gate=gate,
                tp_axis=self.tp_axis, valid_start=valid_start,
                ep_axis=self.ep_axis, attn_hook=attn_hook,
                attn_seq_len=attn_seq_len, lora_pages=lora_pages,
            )
            # the inter-stage hand-off: int8 data + fp32 per-token-row
            # scales on the wire when pp_wire_quant is on (quant=False
            # IS lax.ppermute — bit-identical off path)
            buf = wire_ppermute(y, AXIS_PP, perm, quant=self._wire_ring)
            return buf, cache

        return jax.lax.fori_loop(0, S, micro, (x, cache))

    def _bcast(self, x, sel, axes=AXIS_PP, quant=None):
        """Masked psum broadcast of a single owner's [B, .., D] activation
        window — the hand-off every pp program's sampling tail starts
        with. With pp_wire_quant on, the all-reduce ships int8 data +
        fp32 scales (EQuARX recipe; ops/wire_quant.masked_psum);
        off, it is the exact masked-psum idiom this replaced."""
        if quant is None:
            quant = self._wire_bcast
        return masked_psum(x, sel, axes, quant=quant)

    # -- host-side static wire accounting (dli_pp_wire_bytes_total) ---------
    def attach_wire_metrics(self, registry):
        """Engine seam (engine/engine.py pre-registers the families): the
        backend increments per-launch byte counts computed from static
        shapes — no tracing cost, no host syncs."""
        self._wire_metrics = registry.counter(
            "dli_pp_wire_bytes_total",
            "inter-stage activation bytes shipped on the pp/sp wire, by "
            "transfer family", ("path",),
        )

    def _wire_account(self, path: str, shape, hops: int, axis_size=None,
                      quant=None):
        """Count `hops` crossings of one [..., D] activation on the wire
        (static shapes only; decode while_loops count their full
        ring-pass upper bound — documented in ARCHITECTURE.md).
        axis_size: participants on the transfer axis (default pp) — a
        singleton axis moves nothing, so it counts nothing. quant: what
        actually crossed (default: the wire knob; the sp path passes
        `or kv_quant` — an int8 cache's chunks are int8 on the wire
        with or without the knob)."""
        fam = self._wire_metrics
        if axis_size is None:
            axis_size = self.pp
        if fam is None or hops <= 0 or axis_size <= 1:
            return
        if quant is None:
            quant = self.wire_quant is not None
        itemsize = jnp.dtype(self.cfg.jnp_dtype).itemsize
        fam.labels(path=path).inc(
            wire_bytes(shape, itemsize, hops, quant=quant)
        )

    def _account_link(self, name: str, *, axis_size=None, quant=None,
                      **launch):
        """Account one launch of a named wire link (the ONE symbolic
        bytes model: analysis/comms.WIRE_LINKS). Shape and hop-count
        arithmetic live in the link table — the `--comms` report and
        these counters evaluate the same formulas, so they cannot
        drift. `launch` supplies the
        per-call params (rows/t/steps/...); topology dims default from
        the backend."""
        spec = comms.WIRE_LINKS[name]
        p = comms.params_from_config(self.cfg, **launch)
        p.setdefault("dp", self.dp)
        p.setdefault("pp", self.pp)
        sp = getattr(self, "sp", None)
        if sp is not None:
            p.setdefault("sp", sp)
        mb = getattr(self, "n_microbatches", None)
        if mb is not None:
            p.setdefault("mb", mb)
        self._wire_account(
            spec.path, spec.shape(p), spec.hops(p),
            axis_size=axis_size, quant=quant,
        )

    def _account_decode_wire(self, rows: int, steps: int):
        """Per-decode-launch accounting for the plain microstep ring:
        S ppermute hops + one broadcast per emitted token (bytes are
        PER ICI LINK — the binding quantity; dp rings are independent,
        so a dp shard's rows divide out)."""
        if self.pp <= 1:
            return
        self._account_link("pp-microstep-decode", rows=rows, steps=steps)
        self._account_link("pp-broadcast-decode", rows=rows, steps=steps)

    def _dp_key(self, key):
        """Decorrelate sampling across dp batch shards. dp=1 keeps the key
        untouched so the pipeline stays bit-identical to single-device."""
        if self.dp == 1:
            return key
        return jax.random.fold_in(key, jax.lax.axis_index(AXIS_DP))

    def _build_prefill(self):
        raise NotImplementedError

    def _build_decode(self, max_steps: int, with_presence: bool = False):
        raise NotImplementedError

    def _build_decode_ragged(self, max_steps: int, with_presence: bool = False):
        raise NotImplementedError(
            f"{self.name} does not support ragged (valid_start) batches"
        )

    def _build_decode_full(self, max_steps: int, *, ragged: bool,
                           with_presence: bool, with_bias: bool,
                           with_logprobs: bool, with_counts: bool = False):
        raise NotImplementedError(
            f"{self.name} does not support logit_bias / per-token-logprobs "
            f"/ frequency-presence-penalty-counts decode variants"
        )


class PipelineBackend(SPMDBackendBase):
    """Engine-compatible backend running (dp, pp, tp) SPMD over a mesh.

    Drop-in for SingleDeviceBackend (same init_cache/prefill/decode/health
    interface), so InferenceEngine and the serving layer are topology-
    agnostic — the reference needed three differently-coded processes for
    the same job (orchestration.py vs Worker1.py vs Worker2.py).

    Axes: `pp` stages hand activations around the ICI ring; `tp` shards
    heads/FFN within a stage (psums inside models/*.decoder_layer); `dp`
    shards the batch — each dp slice is an independent pipeline ring (its
    while-loop may even exit at a different step; no collective crosses dp).
    """

    name = "pipeline"
    # Ragged left-padded batches thread valid_start through the llama-family
    # mask; the engine checks arch before requesting them.
    supports_ragged = True
    supports_presence = True
    # OpenAI frequency/presence penalties (counts-tracked decode variants)
    supports_counts = True
    # grammar-constrained decoding (constrain/): the FSM gathers run on
    # the REPLICATED logits/tables after the vocab-shard all_gather, so
    # every device samples and advances the same state — identical to the
    # single-device stack by construction
    supports_constrain = True

    # -- chunked prefill (engine: prompts beyond the largest bucket) --------
    def extend(self, tokens, pos, cache):
        """Run a full prompt chunk at offset `pos` (no logits/sampling),
        mirroring engine.generate's chunked-prefill contract with
        SingleDeviceBackend (engine/generate.py extend)."""
        fn = self._programs.get("extend")
        if fn is None:
            fn = self._build_extend()
            self._programs["extend"] = fn
        self._account_link(
            "pp-microstep-prefill",
            rows=int(tokens.shape[0]), t=int(tokens.shape[1]),
        )
        return fn(self.shared, self.layers, tokens, pos, cache)

    def prefill_at(self, tokens, pos, valid_len, cache, key, sampling,
                   presence=None, bias=None):
        """Final chunked-prefill chunk at traced offset `pos`; samples the
        first token off position pos + valid_len - 1."""
        return self._prefill_any(
            tokens, pos, valid_len, cache, key, sampling, None, presence, bias
        )

    def prefill(self, tokens, prompt_len, cache, key, sampling,
                valid_start=None, presence=None, bias=None):
        return self._prefill_any(
            tokens, jnp.int32(0), prompt_len, cache, key, sampling,
            valid_start, presence, bias,
        )

    def _prefill_any(self, tokens, pos, valid_len, cache, key, sampling,
                     valid_start, presence=None, bias=None):
        ragged = valid_start is not None
        pres = presence is not None
        wb = bias is not None
        fn = self._programs.get(("prefill", ragged, pres, wb))
        if fn is None:
            fn = self._build_prefill_pos(ragged, pres, wb)
            self._programs[("prefill", ragged, pres, wb)] = fn
        args = [self.shared, self.layers, tokens, pos, valid_len, cache, key, sampling]
        if ragged:
            args.append(valid_start)
        if pres:
            args.append(presence)
        if wb:
            args.append(bias)
        B, T = int(tokens.shape[0]), int(tokens.shape[1])
        self._account_link("pp-microstep-prefill", rows=B, t=T)
        self._account_link("pp-broadcast-prefill", rows=B)
        return fn(*args)

    def _build_prefill(self):
        # base-class hook: the pos=0 non-ragged program, via the shared
        # builder (prefill()/prefill_at() both route through _prefill_any)
        fn = self._build_prefill_pos(False, False)
        self._programs[("prefill", False, False, False)] = fn
        return lambda shared, layers, tokens, prompt_len, cache, key, sampling: fn(
            shared, layers, tokens, jnp.int32(0), prompt_len, cache, key, sampling
        )

    def _build_prefill_pos(self, ragged: bool, with_presence: bool = False,
                           with_bias: bool = False):
        cfg, S = self.cfg, self.pp

        def body(shared, layers, tokens, pos, valid_len, cache, key, sampling,
                 *extra):
            i = 0
            valid_start = presence = bias = None
            if ragged:
                valid_start = extra[i]
                i += 1
            if with_presence:
                presence = extra[i]
                i += 1
            if with_bias:
                bias = extra[i]
                i += 1
            s = jax.lax.axis_index(AXIS_PP)
            key = self._dp_key(key)
            x = embed_sharded(cfg, shared, tokens, pos, S)
            buf, cache = self._microstep_loop(layers, x, cache, pos, valid_start)
            # the real final-stage output lives on stage 0; broadcast the
            # [B, 1, D] slice (not the vocab row) then compute the vocab-
            # sharded logits everywhere
            last = jax.lax.dynamic_slice_in_dim(buf, valid_len - 1, 1, axis=1)
            last = self._bcast(last, s == 0)
            logits = unembed_sharded(cfg, shared, last, S)[:, 0, :]
            first = sample_token(
                key, logits, *sampling, presence=presence, bias=bias
            )
            return first, logits, cache

        specs = [
            self._shared_specs, self._layer_specs, P(AXIS_DP), P(), P(),
            cache_spec(self.cfg), P(), P(),
        ]
        if ragged:
            specs.append(P(AXIS_DP))
        if with_presence:
            specs.append(P(AXIS_DP))
        if with_bias:
            specs.append(P())  # [V] bias replicates: logits are replicated
        shmapped = self._shard(
            body,
            in_specs=tuple(specs),
            out_specs=(P(AXIS_DP), P(AXIS_DP), cache_spec(self.cfg)),
        )
        return jax.jit(shmapped, donate_argnums=(5,))

    def _build_extend(self):
        cfg = self.cfg

        def body(shared, layers, tokens, pos, cache):
            x = embed_sharded(cfg, shared, tokens, pos, self.pp)
            _, cache = self._microstep_loop(layers, x, cache, pos)
            return cache

        shmapped = self._shard(
            body,
            in_specs=(
                self._shared_specs, self._layer_specs, P(AXIS_DP), P(),
                cache_spec(self.cfg),
            ),
            out_specs=cache_spec(self.cfg),
        )
        return jax.jit(shmapped, donate_argnums=(4,))

    # -- continuous batching (slot decode) over the pp ring -----------------
    @property
    def supports_slots(self) -> bool:
        """Slot decode (engine/continuous.py) on the pipeline mesh: the
        fleet's B rows are SLOTS, not data shards, so the host's slot
        bookkeeping requires dp == 1 (tp/ep replicate the batch and
        compose fine). Both families: gpt2's learned positions are exact
        in slots mode — every slot starts at position 0 (no left-pad)."""
        return self.dp == 1 and self.cfg.arch in ("llama", "gpt2")

    def _account_slots_wire(self, rows: int, num_steps: int):
        """Slot-decode chunk: S ring hops + one broadcast per step."""
        self._account_link("pp-microstep-slots", rows=rows, steps=num_steps)
        self._account_link("pp-broadcast-slots", rows=rows, steps=num_steps)

    def decode_slots(self, state, cache, key, sparams, *, num_steps):
        fn = self._programs.get(("slots", num_steps))
        if fn is None:
            fn = self._build_decode_slots(num_steps)
            self._programs[("slots", num_steps)] = fn
        self._account_slots_wire(int(state.token.shape[0]), num_steps)
        return fn(self.shared, self.layers, state, cache, key, sparams)

    def _build_decode_slots(self, num_steps: int):
        """shard_map slot-decode chunk: same per-row-position fleet as
        engine/generate.decode_slots, but each step's forward is S ring
        microsteps over the pp stages (cache writes gated per microstep,
        exactly like plain pipeline decode). Sampling keys/params are
        replicated, so every device computes identical tokens and state —
        the host reads one copy."""
        cfg, S = self.cfg, self.pp
        from ..engine.generate import slot_step

        def body(shared, layers, state, cache, key, sparams):
            def step(carry, sub):
                state, cache = carry
                x = embed_sharded(cfg, shared, state.token[:, None], state.pos, S)
                buf, cache = self._microstep_loop(layers, x, cache, state.pos)
                s = jax.lax.axis_index(AXIS_PP)
                last = self._bcast(buf[:, -1:, :], s == 0)
                logits = unembed_sharded(cfg, shared, last, S)[:, 0, :]
                # shared per-step sampling/bookkeeping (engine/generate.py):
                # the cross-backend token-parity guarantee lives in ONE place
                new, emit, can_emit = slot_step(cfg, state, sparams, logits, sub)
                return (new, cache), (emit, can_emit)

            subs = jax.random.split(key, num_steps)
            (state, cache), (emitted, emit_mask) = jax.lax.scan(
                step, (state, cache), subs
            )
            return emitted, emit_mask, state, cache

        from ..engine.generate import SlotParams, SlotState

        state_specs = _replicated_specs(SlotState)
        sparam_specs = _replicated_specs(SlotParams)
        shmapped = self._shard(
            body,
            in_specs=(
                self._shared_specs, self._layer_specs, state_specs,
                cache_spec(self.cfg), P(), sparam_specs,
            ),
            out_specs=(P(), P(), state_specs, cache_spec(self.cfg)),
        )
        return jax.jit(shmapped, donate_argnums=(3,))

    # -- constrained slot decode on the pp ring ------------------------------
    @property
    def supports_constrained_slots(self) -> bool:
        """Grammar-constrained tenants in the continuous fleet on a pp
        mesh: same dp == 1 slot constraint as decode_slots."""
        return self.supports_slots

    def decode_slots_constrained(self, state, cache, key, sparams, fsm,
                                 cmask, ctrans, *, num_steps):
        fn = self._programs.get(("slots_cn", num_steps))
        if fn is None:
            fn = self._build_decode_slots_constrained(num_steps)
            self._programs[("slots_cn", num_steps)] = fn
        self._account_slots_wire(int(state.token.shape[0]), num_steps)
        return fn(self.shared, self.layers, state, cache, key, sparams,
                  fsm, cmask, ctrans)

    def _build_decode_slots_constrained(self, num_steps: int):
        """Constrained twin of _build_decode_slots: the shared
        slot_step_constrained (engine/generate.py) runs on the replicated
        logits, so tokens AND fsm states are identical on every device —
        the same one-copy parity guarantee as the unconstrained fleet."""
        cfg, S = self.cfg, self.pp
        from ..engine.generate import slot_step_constrained

        def body(shared, layers, state, cache, key, sparams, fsm, cmask,
                 ctrans):
            def step(carry, sub):
                state, cache, fsm = carry
                x = embed_sharded(cfg, shared, state.token[:, None], state.pos, S)
                buf, cache = self._microstep_loop(layers, x, cache, state.pos)
                s = jax.lax.axis_index(AXIS_PP)
                last = self._bcast(buf[:, -1:, :], s == 0)
                logits = unembed_sharded(cfg, shared, last, S)[:, 0, :]
                new, emit, can_emit, fsm = slot_step_constrained(
                    cfg, state, sparams, logits, sub, fsm, cmask, ctrans
                )
                return (new, cache, fsm), (emit, can_emit)

            subs = jax.random.split(key, num_steps)
            (state, cache, fsm), (emitted, emit_mask) = jax.lax.scan(
                step, (state, cache, fsm), subs
            )
            return emitted, emit_mask, state, cache, fsm

        from ..engine.generate import SlotParams, SlotState

        state_specs = _replicated_specs(SlotState)
        sparam_specs = _replicated_specs(SlotParams)
        shmapped = self._shard(
            body,
            in_specs=(
                self._shared_specs, self._layer_specs, state_specs,
                cache_spec(self.cfg), P(), sparam_specs, P(), P(), P(),
            ),
            out_specs=(P(), P(), state_specs, cache_spec(self.cfg), P()),
        )
        return jax.jit(shmapped, donate_argnums=(3,))

    # -- block-paged KV on the pp ring (round-3 review #2: the flagship
    # memory feature on the reference's flagship topology) ------------------
    @property
    def supports_paged(self) -> bool:
        """Paged slot decode on the pipeline mesh: same constraints as
        dense slots (dp == 1 — slot rows are slots, not data shards).
        Both families ride the shared attn_hook seam the pool writes use
        (gpt2's block routes through llama.default_attn_hook since
        round 5)."""
        return self.dp == 1 and self.cfg.arch in ("llama", "gpt2")

    def init_paged_pool(self, n_blocks, block_size):
        from .partition import init_sharded_pool

        return init_sharded_pool(self.cfg, self.mesh, n_blocks, block_size)

    def decode_slots_paged(self, state, pool, table, key, sparams, *,
                           num_steps, pages=None):
        mkey = ("slots_paged", num_steps, pages is not None)
        fn = self._programs.get(mkey)
        if fn is None:
            fn = self._build_decode_slots_paged(num_steps, pages is not None)
            self._programs[mkey] = fn
        self._account_slots_wire(int(state.token.shape[0]), num_steps)
        args = [self.shared, self.layers, state, pool, table, key, sparams]
        if pages is not None:
            args.append(pages)
        # the mesh twin keeps its scan (an exit every stage of the ring has
        # to agree on: ROADMAP C), so it ran every step it was given
        return (*fn(*args), num_steps)

    # -- warm-recovery shadow gather/scatter on the pp ring ------------------
    # shard_map twins of engine/paged.gather_shadow_blocks /
    # restore_shadow_blocks: both moves are LAYER-LOCAL (a stage reads or
    # writes its own layer shard of every requested block), so the
    # host-side shadow store sees the same [N, L, ...] stacked leaves as
    # on a single device — pp-sharded pools now recover WARM instead of
    # cold (the ROADMAP follow-up seam from the warm-recovery PR).
    def gather_shadow_blocks(self, pool, block_ids):
        fn = self._programs.get("gather_shadow")
        if fn is None:
            fn = self._build_gather_shadow()
            self._programs["gather_shadow"] = fn
        return fn(pool, block_ids)

    def _build_gather_shadow(self):
        cfg = self.cfg
        from ..engine import paged as EP
        from .partition import pool_spec, shadow_block_spec

        def body(shared_pool, block_ids):
            return EP._gather_shadow(shared_pool, block_ids)

        shmapped = self._shard(
            body,
            in_specs=(pool_spec(cfg), P()),
            out_specs=shadow_block_spec(cfg),
        )
        # the pool is mapped shared state here — read, never donated
        # (live block tables keep reading these buffers), exactly like
        # the single-device program's inverse-donation rule
        return jax.jit(shmapped)

    def restore_shadow_blocks(self, pool, blocks, block_ids):
        fn = self._programs.get("restore_shadow")
        if fn is None:
            fn = self._build_restore_shadow()
            self._programs["restore_shadow"] = fn
        return fn(pool, blocks, block_ids)

    def _build_restore_shadow(self):
        cfg = self.cfg
        from ..engine import paged as EP
        from .partition import pool_spec, shadow_block_spec

        def body(pool, blocks, block_ids):
            return EP._restore_shadow(pool, blocks, block_ids)

        shmapped = self._shard(
            body,
            in_specs=(pool_spec(cfg), shadow_block_spec(cfg), P()),
            out_specs=pool_spec(cfg),
        )
        return jax.jit(shmapped, donate_argnums=(0,))

    # -- ragged paged ingest on the pp ring (engine/paged.py twins) ----------
    # Same dp == 1 / family constraints as the rest of the paged fleet
    # (`supports_paged`). The flat token axis is fleet-shaped (W rows of
    # T=1 at per-token positions), so it rides the same gated microstep
    # ring as paged slot decode — ungated microsteps redirect their block
    # writes to the trash block through the ragged hook's update_gate,
    # exactly like the decode hook.
    def extend_ragged_paged(self, tokens, tok_row, tok_pos, meta, pool,
                            table, pages=None):
        mkey = ("extend_ragged_paged", pages is not None)
        fn = self._programs.get(mkey)
        if fn is None:
            fn = self._build_extend_ragged_paged(pages is not None)
            self._programs[mkey] = fn
        self._account_link(
            "pp-microstep-prefill", rows=int(tokens.shape[0]), t=1
        )
        args = [self.shared, self.layers, tokens, tok_row, tok_pos, meta,
                pool, table]
        if pages is not None:
            args.append(pages)
        return fn(*args)

    def _build_extend_ragged_paged(self, with_pages: bool = False):
        """shard_map twin of engine/paged.extend_ragged_paged: each of the
        S ring microsteps runs the local layer shard over the flat token
        fleet with the ragged fill hook; the pool is donated (updated in
        place), the table/metadata/adapter pages replicate."""
        cfg = self.cfg
        from ..engine import paged as EP
        from .partition import pool_spec

        def body(shared, layers, tokens, tok_row, tok_pos, meta, pool,
                 table, *extra):
            pages = extra[0] if with_pages else None
            hook = EP.make_ragged_fill_hook(table, meta, tok_row)
            x = embed_sharded(cfg, shared, tokens[:, None], tok_pos, self.pp)
            _, pool = self._microstep_loop(
                layers, x, pool, tok_pos, attn_hook=hook, attn_seq_len=1,
                lora_pages=EP._token_pages(pages, tok_row),
            )
            return pool

        specs = [
            self._shared_specs, self._layer_specs, P(), P(), P(), P(),
            pool_spec(cfg), P(),
        ]
        if with_pages:
            specs.append(P())
        shmapped = self._shard(
            body,
            in_specs=tuple(specs),
            out_specs=pool_spec(cfg),
        )
        return jax.jit(shmapped, donate_argnums=(6,))

    def prefill_ragged_paged(self, tokens, tok_row, tok_pos, meta, pool,
                             table, sample_at, key, sampling, presence=None,
                             bias=None, pages=None):
        pres = presence is not None
        wb = bias is not None
        wp = pages is not None
        mkey = ("prefill_ragged_paged", pres, wb, wp)
        fn = self._programs.get(mkey)
        if fn is None:
            fn = self._build_prefill_ragged_paged(pres, wb, wp)
            self._programs[mkey] = fn
        args = [self.shared, self.layers, tokens, tok_row, tok_pos, meta,
                pool, table, sample_at, key, sampling]
        if pres:
            args.append(presence)
        if wb:
            args.append(bias)
        if wp:
            args.append(pages)
        self._account_link(
            "pp-microstep-prefill", rows=int(tokens.shape[0]), t=1
        )
        self._account_link("pp-broadcast-prefill", rows=1)
        return fn(*args)

    def _build_prefill_ragged_paged(self, with_presence: bool,
                                    with_bias: bool,
                                    with_pages: bool = False):
        """Final ragged launch on the ring: after the microstep loop the
        real final-stage output sits on stage 0; the sampled flat position
        is sliced there, psum-broadcast, and unembedded through the vocab
        shards — the same replicated-logits sampling discipline as every
        other pp program, so tokens are identical on every device."""
        cfg, S = self.cfg, self.pp
        from ..engine import paged as EP
        from .partition import pool_spec

        def body(shared, layers, tokens, tok_row, tok_pos, meta, pool,
                 table, sample_at, key, sampling, *extra):
            i = 0
            presence = bias = pages = None
            if with_presence:
                presence = extra[i]
                i += 1
            if with_bias:
                bias = extra[i]
                i += 1
            if with_pages:
                pages = extra[i]
                i += 1
            hook = EP.make_ragged_fill_hook(table, meta, tok_row)
            s = jax.lax.axis_index(AXIS_PP)
            x = embed_sharded(cfg, shared, tokens[:, None], tok_pos, S)
            buf, pool = self._microstep_loop(
                layers, x, pool, tok_pos, attn_hook=hook, attn_seq_len=1,
                lora_pages=EP._token_pages(pages, tok_row),
            )
            last = jax.lax.dynamic_slice_in_dim(buf, sample_at, 1, axis=0)
            last = self._bcast(last, s == 0)  # [1, 1, D]
            logits = unembed_sharded(cfg, shared, last, S)[:, 0, :]
            first = sample_token(
                key, logits, *sampling, presence=presence, bias=bias
            )
            return first, logits, pool

        specs = [
            self._shared_specs, self._layer_specs, P(), P(), P(), P(),
            pool_spec(cfg), P(), P(), P(), P(),
        ]
        if with_presence:
            specs.append(P())
        if with_bias:
            specs.append(P())
        if with_pages:
            specs.append(P())
        shmapped = self._shard(
            body,
            in_specs=tuple(specs),
            out_specs=(P(), P(), pool_spec(cfg)),
        )
        return jax.jit(shmapped, donate_argnums=(6,))

    def arm_slot_paged(self, state, sparams, slot, *arm):
        # state/sparams are replicated — the shared jitted arm program
        # (engine/paged.arm_slot_only) runs on them directly, no shard_map
        from ..engine import paged as EP

        return EP.arm_slot_only(self.cfg, state, sparams, slot, *arm)

    # -- paged adapter pool writes (engine/adapters.AdapterPool seam) --------
    def write_adapter_page(self, page, updates):
        """shard_map twin of the single-device adapter page write: each
        host [L, ...] factor stack is padded/reordered to the ring's
        padded layer layout (partition.pad_stacked_layers — uneven pp
        splits put each stage's padding at its own tail), sharded like
        its buffer (parallel/partition.py lora specs), and written into
        `page` of the donated lora leaves. `page` is traced — loading
        into any page runs ONE compiled program per leaf set."""
        from .partition import pad_stacked_layers

        host = {}
        for leaf, (a, b) in updates.items():
            host[f"lora_{leaf}_a"] = jnp.asarray(a, self.cfg.jnp_dtype)
            host[f"lora_{leaf}_b"] = jnp.asarray(b, self.cfg.jnp_dtype)
        vals = pad_stacked_layers(self.cfg, host, self.pp)
        names = tuple(sorted(vals))
        mkey = ("adapter_write", names)
        fn = self._programs.get(mkey)
        if fn is None:
            fn = self._build_adapter_write(names)
            self._programs[mkey] = fn
        new = fn(
            {n: self.layers[n] for n in names}, jnp.int32(page), vals
        )
        self.layers.update(new)

    def _build_adapter_write(self, names):
        bspecs = {n: self._layer_specs[n] for n in names}
        vspecs = {
            n: P(*((tuple(s)[:1]) + tuple(s)[2:]))
            for n, s in bspecs.items()
        }

        def body(bufs, page, vals):
            return {n: bufs[n].at[:, page].set(vals[n]) for n in bufs}

        shmapped = self._shard(
            body, in_specs=(bspecs, P(), vspecs), out_specs=bspecs,
        )
        return jax.jit(shmapped, donate_argnums=(0,))

    def ragged_program_count(self) -> int:
        """Compiled ragged-ingest programs resident on this backend (the
        dli_ragged_compiled_programs gauge: flat after warmup = no
        per-tail recompile)."""
        return sum(
            1 for k in self._programs
            if isinstance(k, tuple) and k
            and k[0] in ("extend_ragged_paged", "prefill_ragged_paged")
        )

    # -- mixed scheduler step on the pp ring (engine/scheduler.py) -----------
    # The chunked-prefill scheduler's mixed launch: decode rows + prefill
    # chunks in one program.
    def mixed_step_ragged(self, tokens, tok_row, tok_pos, dec_flag, meta,
                          pool, table, state, sparams, key, dec_idx, arm,
                          spec=None, spec_toks=None, dev=None, pages=None,
                          live_width=None):
        mkey = ("mixed_step_ragged", spec is not None,
                spec_toks is not None, dev is not None, pages is not None,
                live_width)
        fn = self._programs.get(mkey)
        if fn is None:
            fn = self._build_mixed_step_ragged(
                spec is not None, spec_toks is not None, dev is not None,
                pages is not None, live_width,
            )
            self._programs[mkey] = fn
        args = [self.shared, self.layers, tokens, tok_row, tok_pos,
                dec_flag, meta, pool, table, state, sparams, key,
                dec_idx, arm]
        if spec is not None:
            args.append(spec)
        if spec_toks is not None:
            args.append(spec_toks)
        if dev is not None:
            args.append(dev)
        if pages is not None:
            args.append(pages)
        self._account_link(
            "pp-microstep-prefill", rows=int(tokens.shape[0]), t=1
        )
        # two replicated-logits gathers (decode rows + arm positions),
        # plus the K+1 verify positions per slot on the spec variant
        bh = 2 + (int(spec.idx.shape[1]) if spec is not None else 0)
        self._account_link(
            "pp-broadcast-prefill", rows=int(dec_idx.shape[0]), bh=bh
        )
        return fn(*args)

    def _build_mixed_step_ragged(self, with_spec: bool = False,
                                 with_spec_toks: bool = False,
                                 with_dev: bool = False,
                                 with_pages: bool = False,
                                 live_width=None):
        """shard_map twin of engine/paged.mixed_step_ragged: the flat
        token fleet (decode rows gathered from the replicated slot state,
        prefill chunks from the host plan) runs the S ring microsteps
        with the ragged fill hook (ungated microsteps trash-redirect
        their pool writes); the decode and first-token positions are
        gathered off stage 0's real output, psum-broadcast, and
        unembedded through the vocab shards — then the SHARED
        engine/paged.mixed_epilogue advances/arm-s the slots, so tokens
        are identical on every device and cannot drift from the
        single-device program. The speculative variants (with_spec /
        with_spec_toks) gather the verify rows' positions through the
        same replicated-logits seam and run the SHARED
        engine/paged.spec_verify inside the epilogue — pp verify rows
        are token-identical to the single chip by construction. The
        with_dev variant applies the SHARED engine/paged.
        apply_device_meta substitution (decode/verify positions derived
        from the replicated slot state) before the hook sees the plan —
        device-derived metadata cannot drift across backends either.
        live_width (engine/scheduler.live_width, where it is under the
        launch's width): the ring runs the live tokens packed side by
        side (the SHARED engine/paged.model_axis), as the single device
        does."""
        cfg, S = self.cfg, self.pp
        from ..engine import paged as EP
        from ..engine.generate import SlotParams, SlotState
        from .partition import pool_spec

        def body(shared, layers, tokens, tok_row, tok_pos, dec_flag, meta,
                 pool, table, state, sparams, key, dec_idx, arm, *extra):
            spec = spec_toks = dev = pages = None
            i = 0
            if with_spec:
                spec = extra[i]
                i += 1
            if with_spec_toks:
                spec_toks = extra[i]
                i += 1
            if with_dev:
                dev = extra[i]
                i += 1
            if with_pages:
                pages = extra[i]
            if dev is not None:
                meta, tok_pos = EP.apply_device_meta(
                    meta, tok_row, tok_pos, dev, state.pos
                )
            s = jax.lax.axis_index(AXIS_PP)
            rows_ix = jnp.maximum(tok_row, 0)
            toks = jnp.where(dec_flag, state.token[rows_ix], tokens)
            if spec is not None and spec_toks is not None:
                # draft-model proposals scattered into the flat axis —
                # same drop-out-of-range recipe as the single device
                K = spec_toks.shape[1]
                jk = jnp.arange(K, dtype=jnp.int32)[None, :]
                want = spec.on[:, None] & (jk < spec.n_draft[:, None])
                tgt = jnp.where(
                    want, spec.idx[:, 1:], jnp.int32(toks.shape[0])
                )
                toks = toks.at[tgt.reshape(-1)].set(
                    spec_toks.reshape(-1), mode="drop"
                )
            pos = jnp.where(dec_flag, state.pos[rows_ix], tok_pos)
            compact, own, own_toks, pos = EP.model_axis(
                live_width, tok_row, toks, pos)
            x = embed_sharded(cfg, shared, own_toks[:, None], pos, S)
            buf, pool = self._microstep_loop(
                layers, x, pool, pos, attn_seq_len=1,
                attn_hook=EP.make_ragged_fill_hook(
                    table, meta, tok_row, compact=compact),
                lora_pages=EP._token_pages(pages, own),
            )
            if compact is not None:
                buf = buf[compact[1]]  # the idx operands name the tile layout

            def replicated_logits(idx):
                sel = buf[idx]  # [N, 1, D]
                sel = self._bcast(sel, s == 0)
                return unembed_sharded(cfg, shared, sel, S)[:, 0, :]

            sp_logits = sp_draft = None
            if spec is not None:
                B, K1 = spec.idx.shape
                sp_logits = replicated_logits(
                    spec.idx.reshape(-1)
                ).reshape(B, K1, -1)
                sp_draft = toks[spec.idx[:, 1:]]
            packed, state, sparams = EP.mixed_epilogue(
                cfg, state, sparams, replicated_logits(dec_idx),
                replicated_logits(arm.idx), key, arm,
                spec=spec, sp_logits=sp_logits, sp_draft=sp_draft,
            )
            return packed, state, sparams, pool

        state_specs = _replicated_specs(SlotState)
        sparam_specs = _replicated_specs(SlotParams)
        arm_specs = EP.MixedArm(
            P(), P(), P(), P(), _replicated_specs(SlotParams), P()
        )
        specs = [
            self._shared_specs, self._layer_specs, P(), P(), P(), P(),
            P(), pool_spec(cfg), P(), state_specs, sparam_specs, P(),
            P(), arm_specs,
        ]
        if with_spec:
            specs.append(EP.SpecPlan(P(), P(), P(), P()))
        if with_spec_toks:
            specs.append(P())
        if with_dev:
            specs.append(EP.DeviceMeta(P(), P(), P(), P()))
        if with_pages:
            specs.append(P())
        shmapped = self._shard(
            body,
            in_specs=tuple(specs),
            out_specs=(P(), state_specs, sparam_specs, pool_spec(cfg)),
        )
        return jax.jit(shmapped, donate_argnums=(7,))

    def _build_decode_slots_paged(self, num_steps: int,
                                  with_pages: bool = False):
        """Paged twin of _build_decode_slots: each of the S ring
        microsteps runs the local layer shard over the slot fleet with the
        paged attn_hook (engine/paged.make_paged_hook); pool writes are
        gated per microstep by redirecting ungated scatters to the trash
        block. Shares slot_step, so cross-backend/cross-mode token parity
        is structural."""
        cfg, S = self.cfg, self.pp
        from ..engine import paged as EP
        from ..engine.generate import SlotParams, SlotState, slot_step
        from .partition import pool_spec

        def body(shared, layers, state, pool, table, key, sparams, *extra):
            pages = extra[0] if with_pages else None
            bs = pool["k"].shape[3]
            MB = table.shape[1]
            s = jax.lax.axis_index(AXIS_PP)

            def step(carry, sub):
                state, pool = carry
                hook = EP.make_paged_hook(table, state.active)
                x = embed_sharded(
                    cfg, shared, state.token[:, None], state.pos, S
                )
                buf, pool = self._microstep_loop(
                    layers, x, pool, state.pos, attn_hook=hook,
                    attn_seq_len=MB * bs, lora_pages=pages,
                )
                last = self._bcast(buf[:, -1:, :], s == 0)
                logits = unembed_sharded(cfg, shared, last, S)[:, 0, :]
                new, emit, can_emit = slot_step(cfg, state, sparams, logits, sub)
                return (new, pool), (emit, can_emit)

            subs = jax.random.split(key, num_steps)
            (state, pool), (emitted, emit_mask) = jax.lax.scan(
                step, (state, pool), subs
            )
            return emitted, emit_mask, state, pool

        state_specs = _replicated_specs(SlotState)
        sparam_specs = _replicated_specs(SlotParams)
        specs = [
            self._shared_specs, self._layer_specs, state_specs,
            pool_spec(cfg), P(), P(), sparam_specs,
        ]
        if with_pages:
            specs.append(P())
        shmapped = self._shard(
            body,
            in_specs=tuple(specs),
            out_specs=(P(), P(), state_specs, pool_spec(cfg)),
        )
        return jax.jit(shmapped, donate_argnums=(3,))

    def _build_decode(self, max_steps: int, with_presence: bool = False):
        return self._build_decode_any(
            max_steps, ragged=False, with_presence=with_presence
        )

    def _build_decode_ragged(self, max_steps: int, with_presence: bool = False):
        return self._build_decode_any(
            max_steps, ragged=True, with_presence=with_presence
        )

    def _build_decode_full(self, max_steps: int, *, ragged: bool,
                           with_presence: bool, with_bias: bool,
                           with_logprobs: bool, with_counts: bool = False,
                           with_constraint: bool = False):
        # OpenAI logit_bias and per-token logprobs on the pp mesh (round-2
        # review #3: the full request surface on every topology) — the
        # logits are replicated after the vocab-shard all_gather, so both
        # reduce to the same local ops the single-device path runs
        return self._build_decode_any(
            max_steps, ragged=ragged, with_presence=with_presence,
            with_counts=with_counts, with_bias=with_bias,
            with_logprobs=with_logprobs, with_constraint=with_constraint,
        )

    def _build_decode_any(self, max_steps: int, *, ragged: bool,
                          with_presence: bool = False,
                          with_counts: bool = False,
                          with_bias: bool = False,
                          with_logprobs: bool = False,
                          with_constraint: bool = False):
        cfg, S = self.cfg, self.pp
        from ..engine.generate import fsm_advance, fsm_allowed

        def body(shared, layers, first_token, cache, start_pos, limit, key,
                 sampling, *extra):
            i = 0
            valid_start = presence0 = counts0 = bias = None
            fsm0 = cmask = ctrans = None
            if ragged:
                valid_start = extra[i]
                i += 1
            if with_presence:
                presence0 = extra[i]
                i += 1
            if with_counts:
                counts0 = extra[i]
                i += 1
            if with_bias:
                bias = extra[i]
                i += 1
            if with_constraint:
                fsm0, cmask, ctrans = extra[i: i + 3]
                i += 3
            s = jax.lax.axis_index(AXIS_PP)
            key = self._dp_key(key)
            B = first_token.shape[0]
            pad = jnp.int32(cfg.pad_token_id)
            out0 = jnp.full((B, max_steps), pad, jnp.int32)
            finished0 = stop_mask(cfg, first_token)
            pres0 = (
                presence0 if with_presence else jnp.zeros((B, 1), jnp.bool_)
            )
            cnt0 = counts0 if with_counts else jnp.zeros((B, 1), jnp.int32)
            lp0 = jnp.zeros((B, max_steps if with_logprobs else 1), jnp.float32)

            def cond(c):
                step, _, _, _, _, finished, _, _, _, _, _ = c[:11]
                return (step < limit) & ~jnp.all(finished)

            def step_fn(c):
                (step, token, pos, cache, key, finished, out, n_gen, pres,
                 cnt, lps) = c[:11]
                fsm = c[11] if with_constraint else None
                x = embed_sharded(cfg, shared, token[:, None], pos, S)
                buf, cache = self._microstep_loop(layers, x, cache, pos, valid_start)
                # broadcast stage 0's real [B, 1, D] output (a masked psum
                # of activations, NOT the [B, vocab] fp32 logits round-1
                # shipped), then every stage computes its vocab shard and
                # the all_gather'd logits are identical everywhere — so the
                # sampled token needs no further collective
                last = self._bcast(buf[:, -1:, :], s == 0)
                logits = unembed_sharded(cfg, shared, last, S)[:, 0, :]
                key, sub = jax.random.split(key)
                nxt = sample_token(
                    sub, logits, *sampling,
                    presence=pres if with_presence else None,
                    counts=cnt if with_counts else None,
                    bias=bias,
                    allowed=(
                        fsm_allowed(cmask, fsm) if with_constraint else None
                    ),
                )
                if with_presence:
                    pres = presence_update(pres, nxt)
                is_eos = stop_mask(cfg, nxt)
                newly = finished | is_eos
                if with_counts:
                    cnt = count_update(cnt, nxt, ~newly)
                emit = jnp.where(newly, pad, nxt)
                out = jax.lax.dynamic_update_slice(
                    out, emit[:, None], (jnp.int32(0), step)
                )
                if with_logprobs:
                    # raw-distribution logprob of the emitted token (the
                    # OpenAI convention — before temperature/filters/bias),
                    # same as engine/generate.decode's variant
                    logp = jax.nn.log_softmax(
                        logits.astype(jnp.float32), axis=-1
                    )
                    tok_lp = jnp.take_along_axis(logp, nxt[:, None], axis=-1)
                    lps = jax.lax.dynamic_update_slice(
                        lps, tok_lp, (jnp.int32(0), step)
                    )
                n_gen = n_gen + (~newly).astype(jnp.int32)
                token = jnp.where(newly, pad, nxt)
                nc = (step + 1, token, pos + 1, cache, key, newly, out,
                      n_gen, pres, cnt, lps)
                if with_constraint:
                    nc = nc + (fsm_advance(ctrans, fsm, nxt, ~newly),)
                return nc

            init = (
                jnp.int32(0),
                jnp.where(finished0, pad, first_token),
                start_pos,
                cache,
                key,
                finished0,
                out0,
                jnp.zeros((B,), jnp.int32),
                pres0,
                cnt0,
                lp0,
            )
            if with_constraint:
                init = init + (fsm0,)
            final = jax.lax.while_loop(cond, step_fn, init)
            (_, _, _, cache, _, _, out, n_gen, _, _, lps) = final[:11]
            if with_logprobs:
                return out, n_gen, cache, lps
            return out, n_gen, cache

        specs = [
            self._shared_specs, self._layer_specs, P(AXIS_DP), cache_spec(self.cfg),
            P(), P(), P(), P(),
        ]
        if ragged:
            specs.append(P(AXIS_DP))
        if with_presence:
            specs.append(P(AXIS_DP))
        if with_counts:
            specs.append(P(AXIS_DP))
        if with_bias:
            specs.append(P())
        if with_constraint:
            # fsm [B] shards with the batch; the [S, V] tables replicate
            # (the gathers run on the replicated post-all_gather logits)
            specs.extend([P(AXIS_DP), P(), P()])
        out_specs = [P(AXIS_DP), P(AXIS_DP), cache_spec(self.cfg)]
        if with_logprobs:
            out_specs.append(P(AXIS_DP))
        shmapped = self._shard(
            body,
            in_specs=tuple(specs),
            out_specs=tuple(out_specs),
        )
        return jax.jit(shmapped, donate_argnums=(3,))

    # -- teacher-forced scoring / beam search over the pp ring --------------
    # (round-2 review #3: BASELINE configs 3-5 must serve the same request
    # surface as the single chip — score, logprobs, logit_bias, beams)
    supports_bias = True
    supports_logprobs = True
    supports_score = True
    supports_beam = True

    def score_chunk(self, tokens, pos, cache, *, top_n=0):
        fn = self._programs.get(("score", top_n))
        if fn is None:
            fn = self._build_score(top_n)
            self._programs[("score", top_n)] = fn
        B, T = int(tokens.shape[0]), int(tokens.shape[1])
        self._account_link("pp-microstep-prefill", rows=B, t=T)
        self._account_link("pp-broadcast-score", rows=B, t=T)
        return fn(self.shared, self.layers, tokens, pos, cache)

    def _build_score(self, top_n: int):
        """Chunked teacher-forced scoring (engine/generate.score_chunk) on
        the ring: run the chunk through the S microsteps, broadcast the
        final-stage [B, T, D] activations from stage 0, compute replicated
        logits from the vocab shards, then the SAME score_post tail as the
        single-device path — bit-consistent by construction."""
        cfg, S = self.cfg, self.pp
        from ..engine.generate import score_post

        def body(shared, layers, tokens, pos, cache):
            s = jax.lax.axis_index(AXIS_PP)
            x = embed_sharded(cfg, shared, tokens, pos, S)
            buf, cache = self._microstep_loop(layers, x, cache, pos)
            full = self._bcast(buf, s == 0)
            logits = unembed_sharded(cfg, shared, full, S)
            return score_post(logits, tokens, top_n) + (cache,)

        shmapped = self._shard(
            body,
            in_specs=(
                self._shared_specs, self._layer_specs, P(AXIS_DP), P(),
                cache_spec(self.cfg),
            ),
            out_specs=(
                P(AXIS_DP), P(AXIS_DP), P(AXIS_DP), P(AXIS_DP), cache_spec(self.cfg)
            ),
        )
        return jax.jit(shmapped, donate_argnums=(4,))

    @property
    def supports_speculative(self) -> bool:
        """Prompt-lookup speculation on the pp ring: one T=1+g verify
        forward costs the same S microsteps as a single token, so g
        accepted tokens amortize the batch-1 ring bubble g-fold — the
        speculation win is LARGER on a pipeline than on one chip. B=1
        only, so dp must be 1 (serving engines always are)."""
        return self.dp == 1

    def decode_speculative(self, first_token, cache, hist, hist_len, limit,
                           *, max_steps, draft_len):
        key_ = ("spec", max_steps, draft_len)
        fn = self._programs.get(key_)
        if fn is None:
            fn = self._build_speculative(max_steps, draft_len)
            self._programs[key_] = fn
        # upper bound: one [1, 1+G, D] verify window per spec cycle
        self._account_link(
            "pp-microstep-spec", rows=1, draft=draft_len, steps=max_steps
        )
        self._account_link(
            "pp-broadcast-spec", rows=1, draft=draft_len, steps=max_steps
        )
        return fn(
            self.shared, self.layers, first_token, cache, hist,
            jnp.int32(hist_len), jnp.int32(limit),
        )

    def _build_speculative(self, max_steps: int, draft_len: int):
        """engine/generate.spec_loop inside shard_map: the verify forward
        is ring microsteps + a masked psum of the [1, 1+G, D] window +
        vocab-shard logits; the n-gram matching / acceptance bookkeeping
        runs replicated on every device (identical logits in, identical
        argmaxes out)."""
        cfg, S = self.cfg, self.pp
        from ..engine.generate import spec_loop

        def body(shared, layers, first_token, cache, hist, hist_len, limit):
            s = jax.lax.axis_index(AXIS_PP)

            def fwd(tokens_in, cache, pos):
                x = embed_sharded(cfg, shared, tokens_in, pos, S)
                buf, cache = self._microstep_loop(layers, x, cache, pos)
                full = self._bcast(buf, s == 0)
                return unembed_sharded(cfg, shared, full, S), cache

            return spec_loop(
                cfg, fwd, first_token, cache, hist, hist_len, limit,
                max_steps=max_steps, draft_len=draft_len,
            )

        shmapped = self._shard(
            body,
            in_specs=(
                self._shared_specs, self._layer_specs, P(), cache_spec(self.cfg),
                P(), P(), P(),
            ),
            out_specs=(P(), P(), cache_spec(self.cfg)),
        )
        return jax.jit(shmapped, donate_argnums=(3,))

    @property
    def supports_draft(self) -> bool:
        """Two-model draft speculation on the pp ring (dp == 1, B=1)."""
        return self.dp == 1

    def decode_draft_speculative(self, dcfg, dparams, first_token, cache,
                                 dcache, start_pos, limit, *, max_steps,
                                 draft_len):
        key_ = ("draft", dcfg, max_steps, draft_len)
        fn = self._programs.get(key_)
        if fn is None:
            fn = self._build_draft_speculative(dcfg, max_steps, draft_len)
            self._programs[key_] = fn
        self._account_link(
            "pp-microstep-spec", rows=1, draft=draft_len, steps=max_steps
        )
        self._account_link(
            "pp-broadcast-spec", rows=1, draft=draft_len, steps=max_steps
        )
        return fn(
            self.shared, self.layers, dparams, first_token, cache, dcache,
            jnp.int32(start_pos), jnp.int32(limit),
        )

    def _build_draft_speculative(self, dcfg, max_steps: int, draft_len: int):
        """engine/generate.draft_spec_loop inside shard_map: the target
        verify forward is ring microsteps + masked psum + vocab-shard
        logits; the SMALL draft model runs fully replicated on every
        device (its params/cache enter with P() specs) — redundant
        compute, but far cheaper than scattering a model whose point is
        being tiny, and every device derives identical proposals."""
        cfg, S = self.cfg, self.pp
        from ..engine.generate import draft_spec_loop

        def body(shared, layers, dparams, first_token, cache, dcache,
                 start_pos, limit):
            s = jax.lax.axis_index(AXIS_PP)

            def fwd(tokens_in, cache, pos):
                x = embed_sharded(cfg, shared, tokens_in, pos, S)
                buf, cache = self._microstep_loop(layers, x, cache, pos)
                full = self._bcast(buf, s == 0)
                return unembed_sharded(cfg, shared, full, S), cache

            def dfwd(tok_11, dc, p):
                x = M.embed(dcfg, dparams, tok_11, p)
                x, dc = M.forward_layers(dcfg, dparams["layers"], x, dc, p)
                return M.unembed(dcfg, dparams, x), dc

            return draft_spec_loop(
                cfg, fwd, dfwd, first_token, cache, dcache, start_pos,
                limit, max_steps=max_steps, draft_len=draft_len,
            )

        # the draft's params/cache are replicated pytrees: a bare P() is a
        # valid PYTREE PREFIX spec covering every leaf
        shmapped = self._shard(
            body,
            in_specs=(
                self._shared_specs, self._layer_specs, P(), P(),
                cache_spec(self.cfg), P(), P(), P(),
            ),
            out_specs=(P(), P(), cache_spec(self.cfg), P()),
        )
        return jax.jit(shmapped, donate_argnums=(4, 5))

    def decode_beam(self, logits0, cache, start_pos, limit, length_penalty,
                    *, max_steps, num_beams, early_stopping):
        if self.dp > 1:
            # beams are one hypothesis set, not data shards: the in-program
            # top-k / cache reorder spans all rows, which a dp slice of the
            # batch axis cannot see (serving engines are dp=1 anyway)
            raise NotImplementedError("beam search needs dp == 1")
        key_ = ("beam", max_steps, num_beams, early_stopping)
        fn = self._programs.get(key_)
        if fn is None:
            fn = self._build_beam(max_steps, num_beams, early_stopping)
            self._programs[key_] = fn
        steps = min(limit, max_steps) if isinstance(limit, int) else max_steps
        self._account_slots_wire(num_beams, steps)
        return fn(
            self.shared, self.layers, logits0, cache, start_pos,
            jnp.int32(limit), jnp.float32(length_penalty),
        )

    def _build_beam(self, max_steps: int, num_beams: int,
                    early_stopping: bool):
        """HF-parity beam search on the pp ring: the entire algorithm is
        engine/generate.beam_loop — only the forward step differs (ring
        microsteps + masked psum + vocab-shard unembed). The beam
        bookkeeping runs replicated on every device (identical logits in,
        identical argsorts out), and each device reorders its own local KV
        shard by parent beam; dp must be 1 (the engine's serving meshes
        always are)."""
        cfg, S = self.cfg, self.pp
        from ..engine.generate import beam_loop

        def body(shared, layers, logits0, cache, start_pos, limit,
                 length_penalty):
            s = jax.lax.axis_index(AXIS_PP)

            def fwd(last, cache, pos):
                x = embed_sharded(cfg, shared, last, pos, S)
                buf, cache = self._microstep_loop(layers, x, cache, pos)
                lastb = self._bcast(buf[:, -1:, :], s == 0)
                logits = unembed_sharded(cfg, shared, lastb, S)[:, 0, :]
                return logits, cache

            return beam_loop(
                cfg, fwd, logits0, cache, start_pos, limit, length_penalty,
                max_steps=max_steps, num_beams=num_beams,
                early_stopping=early_stopping,
            )

        shmapped = self._shard(
            body,
            in_specs=(
                self._shared_specs, self._layer_specs, P(), cache_spec(self.cfg),
                P(), P(), P(),
            ),
            out_specs=(P(), P(), P(), cache_spec(self.cfg)),
        )
        return jax.jit(shmapped, donate_argnums=(3,))
