"""Single-device decode engine: prefill + early-exit decode loop.

TPU-native replacement for the reference's hot loop
(/root/reference/orchestration.py:109-196), which re-embeds and re-runs the
*full* sequence through every stage per token with no KV cache. Here:

  * **prefill** is one jit call over the (bucket-padded) prompt — this is
    the TTFT-critical path; right-padding is safe without extra masking
    because pad slots sit at positions > prompt_len-1, are never attended
    by valid queries (causal mask), and are overwritten by decode tokens
    before any valid query can reach them;
  * **decode** is one jit call: a `lax.while_loop` over steps with the KV
    cache threaded through (donated, so XLA updates it in place in HBM),
    the fused sampler inside the loop, and early exit when every row hits
    EOS — zero Python per token;
  * logits are only computed for the positions that get sampled (the
    reference runs lm_head over the whole sequence every step,
    orchestration.py:140-144).

Batch rows share one prompt length (serving uses batch=1; batched callers
pass equal-length prompts).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..models import api as M
from ..ops.sampling import sample_token


class SamplingParams(NamedTuple):
    """Traced sampling knobs (one compiled program serves all values).

    Field order matches ops/sampling.sample_token's positional tail, so
    `sample_token(key, logits, *sampling, presence)` is the universal call.
    min_p / rep_penalty are HF-parity extensions (MinPLogitsWarper /
    RepetitionPenaltyLogitsProcessor); their disabled values (0.0 / 1.0)
    reproduce the reference's exact stack.
    """

    temperature: jnp.ndarray  # f32 scalar
    top_k: jnp.ndarray  # i32 scalar, <=0 disables
    top_p: jnp.ndarray  # f32 scalar, >=1 disables
    greedy: jnp.ndarray  # bool scalar
    min_p: jnp.ndarray  # f32 scalar, <=0 disables
    rep_penalty: jnp.ndarray  # f32 scalar, 1.0 disables
    freq_penalty: jnp.ndarray  # f32 scalar, 0.0 disables (OpenAI)
    pres_penalty: jnp.ndarray  # f32 scalar, 0.0 disables (OpenAI)


def default_sampling(
    temperature=0.7, top_k=50, top_p=0.9, greedy=False, min_p=0.0,
    rep_penalty=1.0, freq_penalty=0.0, pres_penalty=0.0,
) -> SamplingParams:
    return SamplingParams(
        jnp.float32(temperature), jnp.int32(top_k), jnp.float32(top_p),
        jnp.bool_(greedy), jnp.float32(min_p), jnp.float32(rep_penalty),
        jnp.float32(freq_penalty), jnp.float32(pres_penalty),
    )


def count_update(
    counts: jnp.ndarray, tokens: jnp.ndarray, active: jnp.ndarray = None
) -> jnp.ndarray:
    """Increment tokens [B]'s generated-count in counts [B, V] (OpenAI
    frequency/presence-penalty state). active [B]: rows whose emission
    really happened (finished rows keep forwarding pad; their counts are
    frozen so a later tenant of the row starts clean arithmetic)."""
    V = counts.shape[-1]
    hit = (
        jnp.arange(V, dtype=jnp.int32)[None, :] == tokens[:, None]
    ).astype(counts.dtype)
    if active is not None:
        hit = hit * active.astype(counts.dtype)[:, None]
    return counts + hit


def presence_update(presence: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Mark tokens [B] as seen in presence [B, V] (repetition penalty
    state). One [B, V] compare-or per decode step — trivia next to the
    forward."""
    V = presence.shape[-1]
    hit = jnp.arange(V, dtype=jnp.int32)[None, :] == tokens[:, None]
    return presence | hit


def fsm_allowed(cmask: jnp.ndarray, fsm: jnp.ndarray) -> jnp.ndarray:
    """Allowed-token mask rows for the current FSM states: one gather
    ([S, V] table x [B] states -> [B, V]) inside the compiled loop — the
    grammar constraint's entire per-token mask cost (constrain/)."""
    return jnp.take(cmask, fsm, axis=0)


def fsm_advance(ctrans: jnp.ndarray, fsm: jnp.ndarray, tokens: jnp.ndarray,
                active: jnp.ndarray) -> jnp.ndarray:
    """Advance FSM states through the sampled tokens ([S, V] transition
    table gather); rows with active=False (finished / idle slots) keep
    their state frozen."""
    nxt = jnp.take_along_axis(
        jnp.take(ctrans, fsm, axis=0), tokens[:, None], axis=-1
    )[:, 0]
    return jnp.where(active, nxt, fsm)


def stop_mask(cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    """True where a token is a stop token (eos OR any cfg.stop_token_ids,
    e.g. Gemma-it's <end_of_turn> — instruct checkpoints end their turn
    with it and rarely emit <eos> mid-chat). cfg is static under jit, so
    the comparisons unroll to a handful of fused equals."""
    m = tokens == jnp.int32(cfg.eos_token_id)
    for t in cfg.stop_token_ids:
        m = m | (tokens == jnp.int32(t))
    return m


def _forward_step(cfg, params, tokens, cache, pos, valid_start=None):
    """One chunk through the stack; logits only at the final chunk position."""
    x = M.embed(cfg, params, tokens, pos)
    x, cache = M.forward_layers(
        cfg, params["layers"], x, cache, pos, valid_start=valid_start
    )
    logits = M.unembed(cfg, params, x[:, -1:, :])
    return logits[:, 0, :], cache


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def prefill(
    cfg: ModelConfig, params, tokens, prompt_len, cache, key,
    sampling: SamplingParams, valid_start=None, pos=None, presence=None,
    bias=None,
):
    """Run the padded prompt (or final chunked-prefill chunk), sample the
    first token.

    tokens: [B, T_bucket] right-padded (or LEFT-padded for ragged batches,
    with valid_start [B] = each row's first real slot); prompt_len: scalar
    int32 — the number of valid tokens IN THIS CHUNK (shared by the batch;
    for left-padded batches this is the bucket length). pos: traced chunk
    offset into the cache (None == 0) — the chunked-prefill engine passes
    the running offset after its extend() calls, and because pos is traced
    the same compiled program serves every offset.
    Returns (first_token [B], logits [B,V], cache).
    """
    if pos is None:
        pos = jnp.int32(0)
    x = M.embed(cfg, params, tokens, pos)
    x, cache = M.forward_layers(
        cfg, params["layers"], x, cache, pos, valid_start=valid_start
    )
    # logits only at the last *valid* chunk position (traced start is fine
    # for dynamic_slice; prompt_len >= 1 by the engine's contract)
    last = jax.lax.dynamic_slice_in_dim(x, prompt_len - 1, 1, axis=1)  # [B,1,D]
    logits = M.unembed(cfg, params, last)[:, 0, :]
    # presence [B, V]: the prompt's token-id set (host-built from the FULL
    # id list, so chunked prefill and prefix-cache hits see every token) —
    # feeds the HF-parity repetition penalty; None = penalty off
    # bias [V] or [B, V]: OpenAI logit_bias added to raw logits (None = off)
    first = sample_token(key, logits, *sampling, presence=presence, bias=bias)
    return first, logits, cache


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def extend(cfg: ModelConfig, params, tokens, pos, cache):
    """Chunked-prefill step: run a FULL chunk of prompt at offset `pos`
    into the cache, producing no logits/samples. The engine feeds prompts
    longer than the largest prefill bucket through repeated extend() calls
    before a final `prefill(..., pos=...)` chunk — compile cost stays one
    program per chunk shape, while supported prompt length grows to
    max_seq_len. (The reference caps everything at 30 output tokens and
    O(n²) recompute instead, /root/reference/orchestration.py:347.)"""
    x = M.embed(cfg, params, tokens, pos)
    _, cache = M.forward_layers(cfg, params["layers"], x, cache, pos)
    return cache


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_steps", "with_logprobs"),
    donate_argnames=("cache",),
)
def decode(
    cfg: ModelConfig,
    params,
    first_token,
    cache,
    start_pos,
    limit,
    key,
    sampling: SamplingParams,
    valid_start=None,
    presence=None,
    counts=None,
    bias=None,
    constraint=None,
    *,
    max_steps: int,
    with_logprobs: bool = False,
):
    """Early-exit decode loop after prefill.

    first_token: [B] (already counted as generated token #0 unless EOS).
    start_pos: scalar int32 = prompt_len (first_token's K/V lands there).
    limit: traced cap on steps this call (clamped to the static max_steps),
    so one compiled program serves every requested max_tokens in the bucket.

    Returns (tokens [B, max_steps] — pad-masked after EOS, EOS excluded,
    matching the reference's break-before-append at orchestration.py:181-186
    — and n_gen [B] counting tokens emitted by THIS loop). With
    with_logprobs=True a 4th output [B, max_steps] f32 carries each
    emitted token's log-probability under the RAW model distribution
    (log_softmax of the step logits — before temperature/filters, the
    OpenAI-logprobs convention).

    constraint: None, or (fsm0 [B] i32, cmask [S, V] bool, ctrans [S, V]
    i32) — grammar-constrained decoding (constrain/): each step masks the
    logits with cmask[fsm] and advances fsm = ctrans[fsm, token], both
    gathers inside the compiled loop (zero host work per token). The fsm
    carry exists ONLY in constrained traces, so unconstrained programs
    compile to byte-identical HLO.
    """
    B = first_token.shape[0]
    # clamp: limit > max_steps would walk dynamic_update_slice off the end
    # of `out` (the start index clamps, corrupting the last column) and
    # inflate n_gen past the buffer
    limit = jnp.minimum(limit, jnp.int32(max_steps))
    pad = jnp.int32(cfg.pad_token_id)
    out0 = jnp.full((B, max_steps), pad, jnp.int32)
    finished0 = stop_mask(cfg, first_token)
    # presence [B, V]: repetition-penalty state (prompt + emitted so far,
    # first_token marked by the caller); None = penalty off, carried as a
    # dummy so the loop structure stays static
    use_presence = presence is not None
    pres0 = presence if use_presence else jnp.zeros((B, 1), jnp.bool_)
    # counts [B, V] int32: OpenAI frequency/presence-penalty state over
    # GENERATED tokens only (first_token counted by the caller); None =
    # penalties off, carried as a dummy so the loop structure stays static
    use_counts = counts is not None
    cnt0 = counts if use_counts else jnp.zeros((B, 1), jnp.int32)

    lp0 = jnp.zeros((B, max_steps if with_logprobs else 1), jnp.float32)
    # constraint carry only exists in constrained traces (see docstring)
    use_fsm = constraint is not None
    if use_fsm:
        fsm0, cmask, ctrans = constraint

    def cond(c):
        step, _, _, _, _, finished, _, _, _, _, _ = c[:11]
        return (step < limit) & ~jnp.all(finished)

    def body(c):
        step, token, pos, cache, key, finished, out, n_gen, pres, cnt, lps = c[:11]
        fsm = c[11] if use_fsm else None
        logits, cache = _forward_step(
            cfg, params, token[:, None], cache, pos, valid_start
        )
        key, sub = jax.random.split(key)
        nxt = sample_token(
            sub, logits, *sampling, presence=pres if use_presence else None,
            counts=cnt if use_counts else None, bias=bias,
            allowed=fsm_allowed(cmask, fsm) if use_fsm else None,
        )
        if use_presence:
            pres = presence_update(pres, nxt)
        is_eos = stop_mask(cfg, nxt)
        newly_finished = finished | is_eos
        if use_counts:
            cnt = count_update(cnt, nxt, ~newly_finished)
        emit = jnp.where(newly_finished, pad, nxt)
        out = jax.lax.dynamic_update_slice(out, emit[:, None], (jnp.int32(0), step))
        if with_logprobs:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            tok_lp = jnp.take_along_axis(logp, nxt[:, None], axis=-1)
            lps = jax.lax.dynamic_update_slice(lps, tok_lp, (jnp.int32(0), step))
        n_gen = n_gen + (~newly_finished).astype(jnp.int32)
        token = jnp.where(newly_finished, pad, nxt)
        nc = (
            step + 1, token, pos + 1, cache, key, newly_finished, out, n_gen,
            pres, cnt, lps,
        )
        if use_fsm:
            nc = nc + (fsm_advance(ctrans, fsm, nxt, ~newly_finished),)
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
    if use_fsm:
        init = init + (fsm0,)
    final = jax.lax.while_loop(cond, body, init)
    (_, _, _, cache, _, _, out, n_gen, _, _, lps) = final[:11]
    if with_logprobs:
        return out, n_gen, cache, lps
    return out, n_gen, cache


# -- continuous batching (slot decode) ---------------------------------------
#
# JetStream-style in-flight batching: a fixed fleet of B cache slots decodes
# in lock-step, and new requests join a FREE slot mid-flight (prefilled on a
# scratch cache, spliced in) instead of waiting for the whole batch to
# finish. Each slot row sits at its own sequence position, so the forward
# runs with a per-row `pos` vector (models/llama.forward_layers slots mode).
# The reference serves strictly one request at a time
# (/root/reference/orchestration.py:98,144); dispatch-time coalescing
# (serving/queue.py) batches a burst but still drains it to completion —
# this removes that head-of-line blocking.


class SlotParams(NamedTuple):
    """Per-slot sampling knobs, all [B]-shaped (broadcast row-wise through
    sample_token, so slots with different temperatures/top-k/top-p/greedy/
    min-p/repetition-penalty decode together in one program)."""

    temperature: jnp.ndarray  # f32 [B]
    top_k: jnp.ndarray  # i32 [B]
    top_p: jnp.ndarray  # f32 [B]
    greedy: jnp.ndarray  # bool [B]
    min_p: jnp.ndarray  # f32 [B]
    rep_penalty: jnp.ndarray  # f32 [B]
    freq_penalty: jnp.ndarray  # f32 [B] (OpenAI frequency_penalty)
    pres_penalty: jnp.ndarray  # f32 [B] (OpenAI presence_penalty)


class SlotState(NamedTuple):
    """Device-side per-slot decode state.

    token: last emitted token (its K/V not yet written); pad when inactive.
    pos: cache position where `token`'s K/V lands on the next forward —
         exactly plain decode's start_pos contract.
    active: slot is mid-generation.
    remaining: tokens this slot may still emit (admission sets
         max_tokens - 1: the prefill token was #0, like decode's limit).
    presence: [B, V] seen-token set per slot (repetition-penalty state:
         prompt + emitted; armed by insert_slot, updated every step).
    counts: [B, V] generated-token counts per slot (OpenAI frequency/
         presence-penalty state: emitted only, prompt excluded; armed by
         insert_slot with the first token, updated every step).
    """

    token: jnp.ndarray  # i32 [B]
    pos: jnp.ndarray  # i32 [B]
    active: jnp.ndarray  # bool [B]
    remaining: jnp.ndarray  # i32 [B]
    presence: jnp.ndarray  # bool [B, V]
    counts: jnp.ndarray  # i32 [B, V]


def init_slots(n_slots: int, vocab_size: int) -> tuple[SlotState, SlotParams]:
    z = jnp.zeros((n_slots,), jnp.int32)
    return (
        SlotState(
            z, z, jnp.zeros((n_slots,), bool), z,
            jnp.zeros((n_slots, vocab_size), bool),
            jnp.zeros((n_slots, vocab_size), jnp.int32),
        ),
        SlotParams(
            jnp.ones((n_slots,), jnp.float32),
            z,
            jnp.ones((n_slots,), jnp.float32),
            jnp.ones((n_slots,), bool),
            jnp.zeros((n_slots,), jnp.float32),
            jnp.ones((n_slots,), jnp.float32),
            jnp.zeros((n_slots,), jnp.float32),
            jnp.zeros((n_slots,), jnp.float32),
        ),
    )


# NOTE: only `cache` is donated in the slot programs. The host keeps live
# references into the returned SlotState across chunk launches (lag-1
# pipelining reads state.active from the PREVIOUS chunk after the next one
# has been launched) — donating state would invalidate those buffers. The
# state arrays are a few hundred bytes; the cache is the only buffer worth
# updating in place.
@functools.partial(
    jax.jit, static_argnames=("cfg", "num_steps"), donate_argnames=("cache",)
)
def decode_slots(
    cfg: ModelConfig,
    params,
    state: SlotState,
    cache,
    key,
    sparams: SlotParams,
    *,
    num_steps: int,
):
    """Advance every slot `num_steps` tokens (inactive slots ride along,
    masked). One compiled program per (n_slots, num_steps).

    Inactive rows still forward their pad token and write K/V at their
    (frozen) pos — garbage confined to their own cache row, overwritten
    before it can ever be attended (write-then-attend ordering inside the
    layer), exactly the padded-prefill argument. Gating them out would save
    nothing: the batch dimension is fixed.

    Returns (emitted [num_steps, B], emit_mask [num_steps, B] bool — True
    where a real token was emitted, the host's only token-vs-pad oracle —
    state, cache).
    """
    def body(carry, sub):
        state, cache = carry
        logits, cache = _forward_step(
            cfg, params, state.token[:, None], cache, state.pos
        )
        new, emit, can_emit = slot_step(cfg, state, sparams, logits, sub)
        return (new, cache), (emit, can_emit)

    subs = jax.random.split(key, num_steps)
    (state, cache), (emitted, emit_mask) = jax.lax.scan(
        body, (state, cache), subs
    )
    return emitted, emit_mask, state, cache


@functools.partial(
    jax.jit, static_argnames=("cfg", "num_steps"), donate_argnames=("cache",)
)
def decode_slots_constrained(
    cfg: ModelConfig,
    params,
    state: SlotState,
    cache,
    key,
    sparams: SlotParams,
    fsm,
    cmask,
    ctrans,
    *,
    num_steps: int,
):
    """decode_slots under the fleet constraint tables: identical chunk
    contract plus the fsm [B] carry chained device-side between chunks
    (admission/release set rows host-side; decode never syncs). The
    continuous engine launches this program only while >= 1 constrained
    slot is active — pure-unconstrained fleets dispatch the untouched
    decode_slots. Returns (emitted, emit_mask, state, cache, fsm)."""
    def body(carry, sub):
        state, cache, fsm = carry
        logits, cache = _forward_step(
            cfg, params, state.token[:, None], cache, state.pos
        )
        new, emit, can_emit, fsm = slot_step_constrained(
            cfg, state, sparams, logits, sub, fsm, cmask, ctrans
        )
        return (new, cache, fsm), (emit, can_emit)

    subs = jax.random.split(key, num_steps)
    (state, cache, fsm), (emitted, emit_mask) = jax.lax.scan(
        body, (state, cache, fsm), subs
    )
    return emitted, emit_mask, state, cache, fsm


@jax.named_scope("sample")  # the penalty pass, warpers, choice, slot state
def slot_step(cfg: ModelConfig, state: SlotState, sparams: SlotParams,
              logits, key, allowed=None):
    """ONE copy of the per-step slot sampling/bookkeeping — the single-chip
    decode_slots scan and the pipeline's shard_map slots program both call
    this, so the cross-backend token-parity guarantee can't drift.
    allowed [B, V]: optional grammar-constraint mask rows (the constrained
    slot programs gather them from the fleet table — slot_step_constrained).
    Returns (new_state, emit [B], can_emit [B])."""
    pad = jnp.int32(cfg.pad_token_id)
    nxt = sample_token(
        key,
        logits,
        sparams.temperature[:, None],
        sparams.top_k[:, None],
        sparams.top_p[:, None],
        # OR-ing idle rows into "greedy" keeps the all-greedy sampler
        # bypass live when a retired slot still carries a previous
        # sampled tenant's False flag — idle rows' tokens are masked
        # downstream, so their branch only matters for speed
        sparams.greedy | ~state.active,
        sparams.min_p[:, None],
        sparams.rep_penalty[:, None],
        sparams.freq_penalty[:, None],
        sparams.pres_penalty[:, None],
        presence=state.presence,
        counts=state.counts,
        allowed=allowed,
    )
    # break-before-append EOS semantics (orchestration.py:181-186)
    can_emit = state.active & ~stop_mask(cfg, nxt) & (state.remaining > 0)
    emit = jnp.where(can_emit, nxt, pad)
    new = SlotState(
        token=jnp.where(can_emit, nxt, pad),
        pos=state.pos + state.active.astype(jnp.int32),
        active=can_emit & (state.remaining > 1),
        remaining=state.remaining - can_emit.astype(jnp.int32),
        presence=presence_update(state.presence, nxt),
        counts=count_update(state.counts, nxt, can_emit),
    )
    return new, emit, can_emit


def slot_step_constrained(cfg: ModelConfig, state: SlotState,
                          sparams: SlotParams, logits, key, fsm, cmask,
                          ctrans):
    """slot_step under the FLEET constraint tables (constrain/fleet.py):
    fsm [B] indexes the combined table — row 0 is the free state, so
    unconstrained slots ride the same two gathers as a no-op. ONE copy for
    the single-chip and pp shard_map constrained slot programs.
    Returns (new_state, emit [B], can_emit [B], new_fsm [B])."""
    new, emit, can_emit = slot_step(
        cfg, state, sparams, logits, key, allowed=fsm_allowed(cmask, fsm)
    )
    # emit == the sampled token exactly where can_emit; frozen elsewhere
    return new, emit, can_emit, fsm_advance(ctrans, fsm, emit, can_emit)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def insert_slot(
    cfg: ModelConfig,
    cache,
    scratch,
    state: SlotState,
    sparams: SlotParams,
    slot,
    first_token,
    prompt_len,
    max_tokens,
    temperature,
    top_k,
    top_p,
    greedy,
    min_p,
    rep_penalty,
    freq_penalty,
    pres_penalty,
    presence_row,
):
    """Splice a freshly prefilled scratch cache (batch=1, same max_seq) into
    slot row `slot` and arm its state. The whole scratch row is copied —
    one compiled program for every prompt length; the copy is one
    HBM-contiguous row (~tens of MB, microseconds at HBM bandwidth) and
    stale high positions are never attended.

    The decode budget (max_tokens - 1: the prefill token is emitted token
    #0) and the EOS-on-first check are computed ON DEVICE, so admission
    never blocks on fetching the first token — the host batches those
    fetches across a whole admission wave (one host sync, not one per
    request).
    """
    slot = jnp.int32(slot)

    def splice(big, small):
        start = (jnp.int32(0), slot) + (jnp.int32(0),) * (big.ndim - 2)
        return jax.lax.dynamic_update_slice(big, small, start)

    cache = jax.tree.map(splice, cache, scratch)
    state, sparams = arm_slot(
        cfg, state, sparams, slot, first_token, prompt_len, max_tokens,
        temperature, top_k, top_p, greedy, min_p, rep_penalty,
        freq_penalty, pres_penalty, presence_row,
    )
    return cache, state, sparams


def arm_slot(cfg, state, sparams, slot, first_token, prompt_len, max_tokens,
             temperature, top_k, top_p, greedy, min_p, rep_penalty,
             freq_penalty, pres_penalty, presence_row):
    """Arm slot row `slot`'s decode state + sampling knobs after its prompt
    K/V landed. ONE copy of the budget / EOS-on-first / presence arming —
    insert_slot (dense fleet) and engine/paged.arm_slot_only (block pool)
    both call this, so the admission semantics can't drift."""
    budget = jnp.where(
        stop_mask(cfg, first_token), jnp.int32(0), jnp.maximum(max_tokens - 1, 0)
    )
    # presence_row [V]: the prompt's token-id set + the first token
    # (host-built) — the slot's repetition-penalty state
    presence_row = presence_row | (
        jnp.arange(state.presence.shape[-1], dtype=jnp.int32) == first_token
    )
    # counts_row [V]: the slot's OpenAI-penalty state starts at just the
    # first (generated) token — the prompt is excluded by OpenAI semantics
    counts_row = (
        jnp.arange(state.counts.shape[-1], dtype=jnp.int32) == first_token
    ).astype(jnp.int32)
    state = SlotState(
        token=state.token.at[slot].set(first_token),
        pos=state.pos.at[slot].set(prompt_len),
        active=state.active.at[slot].set(budget > 0),
        remaining=state.remaining.at[slot].set(budget),
        presence=state.presence.at[slot].set(presence_row),
        counts=state.counts.at[slot].set(counts_row),
    )
    sparams = SlotParams(
        temperature=sparams.temperature.at[slot].set(temperature),
        top_k=sparams.top_k.at[slot].set(top_k),
        top_p=sparams.top_p.at[slot].set(top_p),
        greedy=sparams.greedy.at[slot].set(greedy),
        min_p=sparams.min_p.at[slot].set(min_p),
        rep_penalty=sparams.rep_penalty.at[slot].set(rep_penalty),
        freq_penalty=sparams.freq_penalty.at[slot].set(freq_penalty),
        pres_penalty=sparams.pres_penalty.at[slot].set(pres_penalty),
    )
    return state, sparams


@jax.jit
def kill_slot(state: SlotState, slot):
    """Force-deactivate a slot (per-request deadline overrun)."""
    return state._replace(active=state.active.at[jnp.int32(slot)].set(False))


@jax.jit
def pack_chunk(emitted, emit_mask, active, steps_run):
    """Pack one decode chunk's host-bound results into a single int32 array
    [2K+2, B] (emitted / mask / final active / the steps the chunk ran, the
    count in every column), so the per-chunk device->host cost is ONE
    transfer — each fetch is a host sync, and three of them would triple
    the loop's per-chunk overhead."""
    return jnp.concatenate(
        [
            emitted,
            emit_mask.astype(jnp.int32),
            active.astype(jnp.int32)[None, :],
            jnp.full((1, active.shape[0]), steps_run, jnp.int32),
        ],
        axis=0,
    )


def pick_bucket(buckets: tuple, n: int) -> int:
    """Smallest bucket >= n (compile-once-per-bucket shape discipline)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"length {n} exceeds largest bucket {buckets[-1]}")


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_steps", "draft_len"),
    donate_argnames=("cache",),
)
def decode_speculative(
    cfg: ModelConfig,
    params,
    first_token,
    cache,
    hist,
    hist_len,
    limit,
    *,
    max_steps: int,
    draft_len: int = 4,
):
    """Greedy decode with prompt-lookup (n-gram) self-speculation.

    Batch-1 decode is HBM-bound: a T=1+g forward streams the same weight
    bytes as T=1, so verifying g drafted tokens costs ~one normal step.
    Each iteration drafts the g tokens that followed the most recent
    earlier occurrence of the current 2-gram in the token history
    (prompt + generated so far), runs ONE forward over [current, draft],
    and accepts the longest prefix where the draft matches the model's
    own greedy argmax — plus the model's correction token. Every emitted
    token is the model's argmax given the accepted context: in fp32 this
    is BIT-IDENTICAL to plain greedy decode (equivalence-tested); in bf16
    the T=1+g verify matmuls can accumulate in a different order than
    T=1 steps, so numerical near-ties may resolve differently — same
    class of benign divergence as chunked vs tokenwise prefill. Useless
    drafts cost nothing but the already-paid forward; repetitive text
    (code, structured data, chat-with-quoting) accepts often and decodes
    several tokens per step (the gain is not measured on the serving
    path).

    KV discipline: the forward writes K/V for [current, draft] at
    pos..pos+g. Accepted slots hold exactly the accepted tokens' K/V; the
    first rejected slot is overwritten by the NEXT iteration's forward
    (its input starts with the correction token at that position), and
    later stale slots sit beyond the query position until overwritten —
    the same never-attended argument as padded prefill. `hist` [1, H] is
    the token history buffer (prompt written in [0, hist_len)); H bounds
    prompt + generated + draft overshoot.

    Greedy only (B=1): speculation verifies argmax, not a sampled draw.
    Returns (out [1, max_steps], n_gen [1], cache).
    """

    def fwd(tokens_in, cache, pos):
        x = M.embed(cfg, params, tokens_in, pos)
        x, cache = M.forward_layers(cfg, params["layers"], x, cache, pos)
        return M.unembed(cfg, params, x), cache

    return spec_loop(
        cfg, fwd, first_token, cache, hist, hist_len, limit,
        max_steps=max_steps, draft_len=draft_len,
    )


def spec_loop(
    cfg: ModelConfig,
    fwd,
    first_token,
    cache,
    hist,
    hist_len,
    limit,
    *,
    max_steps: int,
    draft_len: int = 4,
):
    """Backend-agnostic prompt-lookup speculation loop (the whole
    algorithm behind `decode_speculative`). `fwd(tokens [1, 1+G], cache,
    pos) -> (logits [1, 1+G, V], cache)` abstracts the verify forward:
    single-device embed/layers/unembed, or the pipeline's ring microsteps
    inside a shard_map body (parallel/pipeline.PipelineBackend) — one
    implementation, so pp speculation is consistent with the single chip
    by construction. On a pipeline, one verify forward costs the same S
    microsteps as a single token, so g accepted tokens amortize the
    batch-1 ring bubble g-fold.
    """
    G = draft_len
    H = hist.shape[1]
    pad = jnp.int32(cfg.pad_token_id)
    # out gets G+1 extra columns of scratch: each iteration writes its full
    # (1+G)-token window at the emit offset; rejected tails are overwritten
    # by later iterations and the scratch margin is sliced off at the end
    out0 = jnp.full((1, max_steps + G + 1), pad, jnp.int32)
    limit = jnp.minimum(limit, jnp.int32(max_steps))
    finished0 = stop_mask(cfg, first_token[0]) | (limit <= 0)

    def hist_at(h, i):
        return jax.lax.dynamic_slice(
            h, (jnp.int32(0), jnp.maximum(i, 0)), (1, 1)
        )[0, 0]

    # Loop invariant: `cur` is the LAST EMITTED token (counted already; its
    # K/V not yet written), `pos` its sequence position, `hlen` = pos + 1 =
    # tokens of canonical history in `hist` — exactly plain decode's
    # contract, where first_token's K/V lands at start_pos on its first
    # forward.
    def cond(c):
        _, _, _, _, _, _, n_gen, finished = c
        return (n_gen < limit) & ~finished

    def body(c):
        cur, pos, hlen, hist, cache, out, n_gen, finished = c
        # --- draft: the G tokens that followed the most recent earlier
        # occurrence of the current 2-gram in the history
        c0 = hist_at(hist, hlen - 2)
        c1 = hist_at(hist, hlen - 1)
        w0 = hist[0, : H - 1]
        w1 = hist[0, 1:]
        idx = jnp.arange(H - 1, dtype=jnp.int32)
        # the match must be strictly earlier than the current bigram
        is_match = (w0 == c0) & (w1 == c1) & (idx + 2 < hlen)
        any_match = jnp.any(is_match)
        last_match = jnp.max(jnp.where(is_match, idx, -1))
        dstart = jnp.where(any_match, last_match + 2, jnp.int32(0))
        # junk drafts (no match / overrunning hlen) are harmless: a token
        # is only accepted when it EQUALS the model's argmax
        draft = jax.lax.dynamic_slice(hist, (jnp.int32(0), dstart), (1, G))[0]

        # --- one forward over [current, draft] at pos
        tokens_in = jnp.concatenate([cur[None], draft])[None, :]  # [1, 1+G]
        logits, cache = fwd(tokens_in, cache, pos)  # [1, 1+G, V]
        window = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)  # [1+G]

        # --- accept the matched draft prefix + the correction token
        match = draft == window[:G]
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32)))
        j = jnp.arange(G + 1, dtype=jnp.int32)
        valid = j <= n_acc
        cum_eos = jnp.cumsum(stop_mask(cfg, window).astype(jnp.int32)) > 0
        emit_ok = valid & ~cum_eos  # break BEFORE appending EOS
        room = limit - n_gen
        n_emit = jnp.minimum(jnp.sum(emit_ok.astype(jnp.int32)), room)
        emit_ok = emit_ok & (j < n_emit)
        saw_eos = jnp.any(valid & cum_eos)

        out = jax.lax.dynamic_update_slice(
            out, jnp.where(emit_ok, window, pad)[None, :], (jnp.int32(0), n_gen)
        )
        # window[j] is the token at sequence position pos+1+j = hlen+j
        hist = jax.lax.dynamic_update_slice(
            hist, window[None, :], (jnp.int32(0), hlen)
        )
        cur2 = window[jnp.maximum(n_emit - 1, 0)]  # new last-emitted token
        finished2 = saw_eos | (n_emit <= 0)
        return (
            cur2,
            pos + n_emit,
            hlen + n_emit,
            hist,
            cache,
            out,
            n_gen + n_emit,
            finished2,
        )

    hist = jax.lax.dynamic_update_slice(
        hist, first_token[None, :], (jnp.int32(0), hist_len)
    )
    init = (
        first_token[0],
        hist_len,  # first_token's position == start_pos
        hist_len + 1,
        hist,
        cache,
        out0,
        jnp.int32(0),
        finished0,
    )
    _, _, _, _, cache, out, n_gen, _ = jax.lax.while_loop(cond, body, init)
    return out[:, :max_steps], n_gen[None], cache


# plain Python float, NOT jnp.float32(...): materializing a device scalar
# at module scope would force backend init on IMPORT (`--help` would take
# the chip, and a launcher's parent must stay off it)
NEG_INF_F32 = -1e9


@functools.partial(
    jax.jit, static_argnames=("cfg", "top_n"), donate_argnames=("cache",)
)
def score_chunk(cfg: ModelConfig, params, tokens, pos, cache, *,
                top_n: int = 0):
    """Teacher-forced scoring of one chunk at offset `pos`: the
    log-probability of every within-chunk token given its prefix (the
    lm-eval / OpenAI echo+logprobs loglikelihood pattern — the reference
    can only sample, orchestration.py:168). The engine chains chunks
    through the KV cache, so sequences up to max_seq_len score with
    compile-once bucket shapes, exactly like chunked prefill.

    tokens [B, T_chunk] (right-padded only in the FINAL chunk). Returns
    (within_lp [B, T-1] — entry t is log p(tokens[t+1] | prefix),
     top_v [B, T-1, top_n], top_i int32 — per-position top-N of the same
     distributions (empty when top_n == 0),
     last_lp [B, V] — the LAST position's full distribution, which scores
     the next chunk's first token across the boundary,
     cache)."""
    logits, cache = M.forward(cfg, params, tokens, cache, pos)
    return score_post(logits, tokens, top_n) + (cache,)


def score_post(logits, tokens, top_n: int):
    """Shared scoring tail: [B, T, V] teacher-forced logits -> (within_lp,
    top_v, top_i, last_lp). One implementation for the single-device and
    pipeline backends (the pipeline computes the same replicated logits
    from vocab shards — parallel/vocab.unembed_sharded)."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    within_lp = jnp.take_along_axis(
        lp[:, :-1, :], tgt[..., None], axis=-1
    )[..., 0]
    if top_n > 0:
        top_v, top_i = jax.lax.top_k(lp[:, :-1, :], top_n)
    else:
        B, Tm1 = within_lp.shape
        top_v = jnp.zeros((B, Tm1, 0), jnp.float32)
        top_i = jnp.zeros((B, Tm1, 0), jnp.int32)
    return within_lp, top_v, top_i, lp[:, -1, :]


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_steps", "num_beams", "early_stopping"),
    donate_argnames=("cache",),
)
def decode_beam(
    cfg: ModelConfig,
    params,
    logits0,
    cache,
    start_pos,
    limit,
    length_penalty,
    *,
    max_steps: int,
    num_beams: int,
    early_stopping: bool = False,
):
    """Deterministic beam search after a BATCHED prefill (HF
    `generate(num_beams=N, do_sample=False)` semantics — the reference
    only samples, /root/reference/orchestration.py:168; this is
    beyond-parity HF-generate completeness).

    logits0: [num_beams, V] prefill logits (identical rows — the engine
    tiles the prompt); cache: [L, num_beams, ...] prefilled (identical
    rows). The first expansion takes the top num_beams DISTINCT tokens of
    row 0; each later step expands every alive beam by the full vocab,
    keeps the top num_beams alive continuations (EOS candidates retire
    into a finished set scored sum_logprobs / len**length_penalty, HF
    BeamSearchScorer), and reorders the KV cache by parent beam with a
    batched gather. early_stopping=True stops once num_beams hypotheses
    finished; False keeps going while an alive beam could still beat the
    worst finished score (HF's is_done bound with best_sum_logprobs /
    cur_len**length_penalty).

    Returns (tokens [num_beams, max_steps] — the FINAL beams, best
    first, pad-masked after EOS (EOS excluded), n_gen [num_beams],
    scores [num_beams], cache).
    """
    return beam_loop(
        cfg,
        lambda last, cache, pos: _forward_step(cfg, params, last, cache, pos),
        logits0, cache, start_pos, limit, length_penalty,
        max_steps=max_steps, num_beams=num_beams, early_stopping=early_stopping,
    )


def beam_loop(
    cfg: ModelConfig,
    fwd,
    logits0,
    cache,
    start_pos,
    limit,
    length_penalty,
    *,
    max_steps: int,
    num_beams: int,
    early_stopping: bool = False,
):
    """Backend-agnostic beam-search loop (the whole algorithm behind
    `decode_beam`). `fwd(last [nb, 1], cache, pos) -> (logits [nb, V],
    cache)` abstracts the forward step: single-device `_forward_step`, or
    the pipeline ring microstep inside a shard_map body
    (parallel/pipeline.PipelineBackend._build_beam) — ONE implementation,
    so pp meshes are bit-consistent with the single chip by construction.
    """
    nb = num_beams
    V = logits0.shape[-1]
    pad = jnp.int32(cfg.pad_token_id)
    limit = jnp.minimum(limit, jnp.int32(max_steps))

    lp0 = jax.nn.log_softmax(logits0[0].astype(jnp.float32))  # [V]
    # mask stop tokens at the seed step like HF (a 1-token hypothesis from
    # the prompt's immediate EOS): still allow it as a finished candidate
    seed_scores, seed_tokens = jax.lax.top_k(lp0, nb)

    out0 = jnp.full((nb, max_steps), pad, jnp.int32)
    alive_out = out0.at[:, 0].set(seed_tokens)
    alive_scores = seed_scores  # sum of logprobs per alive beam
    alive_len = jnp.full((nb,), 1, jnp.int32)

    fin_out = out0
    fin_scores = jnp.full((nb,), NEG_INF_F32)
    fin_len = jnp.zeros((nb,), jnp.int32)

    # seed beams that ARE stop tokens retire immediately
    seed_stop = stop_mask(cfg, seed_tokens)
    pen1 = jnp.float32(1.0) ** length_penalty
    fin_scores = jnp.where(seed_stop, seed_scores / pen1, fin_scores)
    # finished hypotheses exclude the EOS token itself (reference
    # break-before-append, orchestration.py:181-186): length 0 text
    alive_scores = jnp.where(seed_stop, NEG_INF_F32, alive_scores)
    order = jnp.argsort(-fin_scores)
    fin_scores = fin_scores[order]
    fin_out = fin_out[order]
    fin_len = fin_len[order]

    def cond(c):
        (step, _, alive_scores, _, _, fin_scores, _, _, _) = c
        if early_stopping:
            more = jnp.any(fin_scores <= NEG_INF_F32 / 2)
        else:
            # an alive beam could still beat the worst finished hypothesis
            # (HF is_done: best alive sum_logprobs / cur_len**penalty)
            best_alive = jnp.max(alive_scores) / (
                jnp.maximum(step.astype(jnp.float32), 1.0) ** length_penalty
            )
            more = jnp.min(fin_scores) < best_alive
        return (step < limit) & more & jnp.any(alive_scores > NEG_INF_F32 / 2)

    def body(c):
        (step, alive_out, alive_scores, alive_len, cache, fin_scores,
         fin_out, fin_len, pos) = c
        last = jnp.take_along_axis(alive_out, (alive_len - 1)[:, None], axis=1)
        logits, cache = fwd(last, cache, pos)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))  # [nb, V]
        cand = alive_scores[:, None] + lp  # [nb, V]

        flat = cand.reshape(nb * V)
        # 2*nb candidates guarantee nb non-stop continuations survive
        top_scores, top_idx = jax.lax.top_k(flat, 2 * nb)
        parent = (top_idx // V).astype(jnp.int32)
        token = (top_idx % V).astype(jnp.int32)
        is_stop = stop_mask(cfg, token)

        # candidate sequences: parent's prefix + token (token NOT written
        # for finished hypotheses — EOS excluded from the text)
        cand_out = alive_out[parent]
        cand_len = alive_len[parent]
        write_col = jnp.clip(cand_len, 0, max_steps - 1)
        ext_out = jax.vmap(
            lambda row, col, t: row.at[col].set(t)
        )(cand_out, write_col, token)

        # finished pool: existing nb + new stop candidates, keep best nb
        new_fin_scores = jnp.where(
            is_stop,
            top_scores / (cand_len.astype(jnp.float32) ** length_penalty),
            NEG_INF_F32,
        )
        pool_scores = jnp.concatenate([fin_scores, new_fin_scores])
        pool_out = jnp.concatenate([fin_out, cand_out])
        pool_len = jnp.concatenate([fin_len, cand_len])
        keep = jnp.argsort(-pool_scores)[:nb]
        fin_scores, fin_out, fin_len = (
            pool_scores[keep], pool_out[keep], pool_len[keep]
        )

        # alive pool: best nb non-stop candidates
        alive_rank_score = jnp.where(is_stop, NEG_INF_F32, top_scores)
        keep_a = jnp.argsort(-alive_rank_score)[:nb]
        alive_scores = alive_rank_score[keep_a]
        alive_out = ext_out[keep_a]
        alive_len = cand_len[keep_a] + 1
        parents = parent[keep_a]
        # reorder every KV leaf by parent beam (batch axis 1)
        cache = jax.tree.map(
            lambda x: jnp.take(x, parents, axis=1), cache
        )
        return (step + 1, alive_out, alive_scores, alive_len, cache,
                fin_scores, fin_out, fin_len, pos + 1)

    init = (jnp.int32(1), alive_out, alive_scores, alive_len, cache,
            fin_scores, fin_out, fin_len, start_pos)
    (step, alive_out, alive_scores, alive_len, cache, fin_scores, fin_out,
     fin_len, _) = jax.lax.while_loop(cond, body, init)

    # merge: unfinished alive beams count as length-`alive_len` hypotheses
    # (budget exhausted, HF's final add of running beams)
    alive_final = alive_scores / (
        jnp.maximum(alive_len.astype(jnp.float32), 1.0) ** length_penalty
    )
    all_scores = jnp.concatenate([fin_scores, alive_final])
    all_out = jnp.concatenate([fin_out, alive_out])
    all_len = jnp.concatenate([fin_len, alive_len])
    best = jnp.argsort(-all_scores)[:nb]
    out = all_out[best]
    n_gen = all_len[best]
    # pad-mask beyond each hypothesis' length
    col = jnp.arange(max_steps, dtype=jnp.int32)[None, :]
    out = jnp.where(col < n_gen[:, None], out, pad)
    return out, n_gen, all_scores[best], cache


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "dcfg", "max_steps", "draft_len"),
    donate_argnames=("cache", "dcache"),
)
def decode_draft_speculative(
    cfg: ModelConfig,
    params,
    dcfg: ModelConfig,
    dparams,
    first_token,
    cache,
    dcache,
    start_pos,
    limit,
    *,
    max_steps: int,
    draft_len: int = 4,
):
    """Greedy decode verified against a separate (smaller) DRAFT model.

    Classic two-model speculative decoding, greedy-acceptance flavor:
    each iteration the draft model autoregressively proposes `draft_len`
    tokens (cheap — small model), the target runs ONE forward over
    [current, draft] (costing ~one normal HBM-bound step, same argument
    as `decode_speculative`), and the longest draft prefix matching the
    target's own argmax is emitted plus the target's correction token.
    Every emitted token is the target's argmax given the accepted
    context — exact vs plain greedy in fp32; bf16 near-ties may resolve
    differently (chunked-vs-tokenwise class of divergence). Unlike
    prompt-lookup (which only wins on self-repeating text), a competent
    draft model accelerates ARBITRARY text at the cost of holding its
    weights in HBM.

    KV discipline (both caches hold history < the last emitted token's
    position on loop entry — the prompt must be prefilled into BOTH):
      * draft: the proposal scan runs draft_len+1 steps from `cur`,
        writing draft K/V at pos..pos+G — one step more than it proposes,
        so a full-accept-plus-bonus iteration leaves no unwritten hole at
        pos+G for the next iteration to attend through.
      * target: the verify forward writes K/V for [cur, draft] at
        pos..pos+G. Rejected-slot staleness is overwritten before it is
        ever attended (same argument as decode_speculative).

    Greedy only, B=1. Returns (out [1, max_steps], n_gen [1], cache,
    dcache).
    """

    def fwd(tokens_in, cache, pos):
        x = M.embed(cfg, params, tokens_in, pos)
        x, cache = M.forward_layers(cfg, params["layers"], x, cache, pos)
        return M.unembed(cfg, params, x), cache

    def dfwd(tok_11, dc, p):
        x = M.embed(dcfg, dparams, tok_11, p)
        x, dc = M.forward_layers(dcfg, dparams["layers"], x, dc, p)
        return M.unembed(dcfg, dparams, x), dc

    return draft_spec_loop(
        cfg, fwd, dfwd, first_token, cache, dcache, start_pos, limit,
        max_steps=max_steps, draft_len=draft_len,
    )


def draft_spec_loop(
    cfg: ModelConfig,
    fwd,
    dfwd,
    first_token,
    cache,
    dcache,
    start_pos,
    limit,
    *,
    max_steps: int,
    draft_len: int = 4,
):
    """Backend-agnostic two-model speculation loop (the algorithm behind
    `decode_draft_speculative`). `fwd(tokens [1, 1+G], cache, pos)` is the
    TARGET verify forward; `dfwd(tok [1, 1], dcache, pos)` one DRAFT
    step. The pipeline backend supplies a ring-microstep target forward
    and a replicated draft (every device runs the small draft redundantly
    — cheaper than scattering it), so pp meshes serve draft speculation
    with the same acceptance semantics as the single chip."""
    G = draft_len
    pad = jnp.int32(cfg.pad_token_id)
    out0 = jnp.full((1, max_steps + G + 1), pad, jnp.int32)
    limit = jnp.minimum(limit, jnp.int32(max_steps))
    finished0 = stop_mask(cfg, first_token[0]) | (limit <= 0)

    def cond(c):
        _, _, _, _, _, n_gen, finished = c
        return (n_gen < limit) & ~finished

    def body(c):
        cur, pos, cache, dcache, out, n_gen, finished = c

        # --- draft chain: G+1 greedy steps from `cur` (the +1 writes
        # d_{G-1}'s K/V so a full accept leaves no cache hole; its
        # proposal is discarded)
        def dstep(carry, _):
            tok, p, dc = carry
            lg, dc = dfwd(tok[None, None], dc, p)
            nxt = jnp.argmax(lg[0, 0]).astype(jnp.int32)
            return (nxt, p + 1, dc), nxt

        (_, _, dcache), proposals = jax.lax.scan(
            dstep, (cur, pos, dcache), None, length=G + 1
        )
        draft = proposals[:G]

        # --- one target forward over [current, draft] at pos
        tokens_in = jnp.concatenate([cur[None], draft])[None, :]  # [1, 1+G]
        logits, cache = fwd(tokens_in, cache, pos)
        window = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)  # [1+G]

        # --- accept matched prefix + correction (identical emit logic to
        # decode_speculative)
        match = draft == window[:G]
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32)))
        j = jnp.arange(G + 1, dtype=jnp.int32)
        valid = j <= n_acc
        cum_eos = jnp.cumsum(stop_mask(cfg, window).astype(jnp.int32)) > 0
        emit_ok = valid & ~cum_eos
        room = limit - n_gen
        n_emit = jnp.minimum(jnp.sum(emit_ok.astype(jnp.int32)), room)
        emit_ok = emit_ok & (j < n_emit)
        saw_eos = jnp.any(valid & cum_eos)

        out = jax.lax.dynamic_update_slice(
            out, jnp.where(emit_ok, window, pad)[None, :], (jnp.int32(0), n_gen)
        )
        cur2 = window[jnp.maximum(n_emit - 1, 0)]
        finished2 = saw_eos | (n_emit <= 0)
        return (cur2, pos + n_emit, cache, dcache, out, n_gen + n_emit,
                finished2)

    init = (first_token[0], start_pos, cache, dcache, out0, jnp.int32(0),
            finished0)
    _, _, cache, dcache, out, n_gen, _ = jax.lax.while_loop(cond, body, init)
    return out[:, :max_steps], n_gen[None], cache, dcache
