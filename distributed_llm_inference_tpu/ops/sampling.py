"""Token sampling: temperature / top-k / top-p / greedy.

Behavioral spec is the reference's inline sampling stack
(/root/reference/orchestration.py:144-169): divide logits by temperature,
top-k filter, top-p nucleus filter with the keep-first-over-threshold shift,
then a categorical draw — rebuilt as pure jittable functions over
`jax.random` keys instead of torch in-place mutation, so the whole sampler
lives inside the decode `lax.scan`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = jnp.finfo(jnp.float32).min


def apply_temperature(logits: jnp.ndarray, temperature: jnp.ndarray) -> jnp.ndarray:
    """logits / temperature (reference orchestration.py:147). Guard t>0."""
    t = jnp.maximum(jnp.asarray(temperature, dtype=logits.dtype), 1e-6)
    return logits / t


def top_k_filter(logits: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Keep the k highest logits, set the rest to -inf.

    Matches reference orchestration.py:150-152 (threshold = k-th largest
    value; ties at the threshold are kept, identical to the torch topk
    comparison). k is a traced scalar; k <= 0 disables filtering.
    """
    vocab = logits.shape[-1]
    k_eff = jnp.clip(k, 1, vocab)
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]  # descending
    idx = jnp.broadcast_to(jnp.asarray(k_eff - 1), logits.shape[:-1] + (1,))
    threshold = jnp.take_along_axis(sorted_logits, idx, axis=-1)
    filtered = jnp.where(logits < threshold, NEG_INF, logits)
    return jnp.where(k <= 0, logits, filtered)


def top_p_filter(logits: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """Nucleus filtering (reference orchestration.py:155-165).

    Sort descending, softmax, cumulative sum; remove tokens whose cumulative
    probability exceeds p — shifted right one slot so the first token over
    the threshold is kept (`sorted_indices_to_remove[..., 0] = False` in the
    reference). p >= 1 disables filtering.
    """
    sort_idx = jnp.argsort(logits, axis=-1)[..., ::-1]
    sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
    probs = jax.nn.softmax(sorted_logits.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    remove = cum > p
    remove = jnp.concatenate(
        [jnp.zeros_like(remove[..., :1]), remove[..., :-1]], axis=-1
    )
    sorted_filtered = jnp.where(remove, NEG_INF, sorted_logits)
    # Scatter back to vocab order.
    inv = jnp.argsort(sort_idx, axis=-1)
    filtered = jnp.take_along_axis(sorted_filtered, inv, axis=-1)
    return jnp.where(p >= 1.0, logits, filtered)


def apply_repetition_penalty(
    logits: jnp.ndarray, presence: jnp.ndarray, penalty: jnp.ndarray
) -> jnp.ndarray:
    """HF RepetitionPenaltyLogitsProcessor semantics: for every token
    already present in the context (prompt + generated so far), positive
    logits divide by the penalty and negative logits multiply by it.
    penalty <= 0 or == 1 disables; presence: [..., V] bool."""
    p = jnp.asarray(penalty, logits.dtype)
    penalized = jnp.where(logits > 0, logits / p, logits * p)
    out = jnp.where(presence, penalized, logits)
    return jnp.where((p <= 0) | (p == 1.0), logits, out)


def apply_oai_penalties(
    logits: jnp.ndarray,
    counts: jnp.ndarray,
    freq_penalty: jnp.ndarray,
    pres_penalty: jnp.ndarray,
) -> jnp.ndarray:
    """OpenAI frequency/presence penalties over GENERATED-token counts:

        logits -= freq_penalty * count + pres_penalty * (count > 0)

    (the OpenAI API reference's published formula; counts cover sampled
    tokens only, not the prompt — the same only-the-output convention the
    major open-source OpenAI-compatible servers use, vs the HF repetition
    penalty's prompt+output membership set). 0.0 disables either term;
    counts: [..., V] int32."""
    f = jnp.asarray(freq_penalty, jnp.float32)
    pr = jnp.asarray(pres_penalty, jnp.float32)
    c = counts.astype(jnp.float32)
    out = logits - f * c - pr * (c > 0).astype(jnp.float32)
    return jnp.where((f == 0.0) & (pr == 0.0), logits, out)


def min_p_filter(logits: jnp.ndarray, min_p: jnp.ndarray) -> jnp.ndarray:
    """HF MinPLogitsWarper: drop tokens whose probability is below
    min_p * max_prob (a dynamic floor that adapts to the model's
    confidence). min_p <= 0 disables. Applied AFTER temperature, like HF's
    warper ordering."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    floor = min_p * jnp.max(probs, axis=-1, keepdims=True)
    filtered = jnp.where(probs < floor, NEG_INF, logits)
    return jnp.where(min_p <= 0.0, logits, filtered)


def suppress_token(logits: jnp.ndarray, token_id: int) -> jnp.ndarray:
    """logits with one token's entry at -inf, so that no choice, greedy or
    sampled, lands on it: a block-diffusion model's mask token is an input
    symbol, never an output (engine/paged.diffusion_step; the plain
    reference does the same before its argmax)."""
    return logits.at[..., token_id].set(NEG_INF)


def sample_token(
    key: jax.Array,
    logits: jnp.ndarray,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    greedy: jnp.ndarray,
    min_p: jnp.ndarray = None,
    rep_penalty: jnp.ndarray = None,
    freq_penalty: jnp.ndarray = None,
    pres_penalty: jnp.ndarray = None,
    presence: jnp.ndarray = None,
    counts: jnp.ndarray = None,
    bias: jnp.ndarray = None,
    allowed: jnp.ndarray = None,
) -> jnp.ndarray:
    """Full sampling stack -> int32 token ids, shape logits.shape[:-1].

    greedy is a traced bool: argmax bypass (the BASELINE configs use greedy
    decode; the reference always samples). Greedy applies the repetition
    penalty BEFORE the argmax (HF processor ordering) but ignores the
    warpers (temperature/top-k/top-p/min-p), matching HF do_sample=False.

    min_p / rep_penalty+presence are optional HF-parity extensions
    (MinPLogitsWarper / RepetitionPenaltyLogitsProcessor); None or their
    disabled values (0 / 1.0) reproduce the reference's exact stack.
    freq_penalty / pres_penalty + counts are the OpenAI penalties
    (apply_oai_penalties; 0.0 disables). The positional parameter order
    through pres_penalty matches engine.generate.SamplingParams, so
    `sample_token(key, logits, *sampling, ...)` stays the universal call;
    presence/counts/bias are state, passed by keyword.
    allowed ([..., V] bool, None = unconstrained) is the grammar-
    constraint mask (constrain/): False tokens are -inf'd after
    bias/penalties, before the warpers — greedy and sampled draws alike
    can never emit a disallowed token.

    Hot-path note: this runs inside the decode `lax.scan` every token, so
    top-k and top-p share ONE descending sort (the standalone filters above
    are the unfused behavioral spec used by tests); the draw happens in
    sorted order and maps back through the sort permutation — equivalent to
    top_p_filter(top_k_filter(.)) + categorical, with 1 sort instead of 3.
    min-p piggybacks on the same sorted probs (max prob = rank-0 prob).
    """
    logits = logits.astype(jnp.float32)
    if bias is not None:
        # OpenAI logit_bias semantics: added to the RAW logits before any
        # warper; -100/+100 effectively ban/force a token. Applies to the
        # greedy argmax too (the ban must hold under temperature 0).
        logits = logits + bias.astype(jnp.float32)
    if rep_penalty is not None and presence is not None:
        logits = apply_repetition_penalty(logits, presence, rep_penalty)
    if counts is not None and freq_penalty is not None:
        # OpenAI penalties ride the same pre-warper slot as the HF
        # repetition penalty (and apply to the greedy argmax too)
        logits = apply_oai_penalties(logits, counts, freq_penalty, pres_penalty)
    if allowed is not None:
        # grammar-constraint mask (constrain/): disallowed tokens drop to
        # -inf AFTER bias/penalties and BEFORE the warpers, so a +100
        # logit_bias can never resurrect a token the grammar forbids and
        # the greedy argmax obeys the mask too. The table compiler
        # guarantees every row keeps >= 1 allowed token (EOS at worst),
        # so the masked row can never go all -inf.
        logits = jnp.where(allowed, logits, NEG_INF)

    use_min_p = min_p is not None
    mp = jnp.float32(0.0) if min_p is None else min_p
    greedy = jnp.asarray(greedy)
    # greedy uses a true argmax (first index on ties, like torch/np), NOT
    # sort_idx[..., 0]: the reversed stable ascending argsort would break
    # ties toward the LAST index. Argmax of the PENALIZED logits: HF
    # applies processors (repetition penalty) in greedy mode too.
    all_greedy = greedy if greedy.ndim == 0 else jnp.all(greedy)

    def _argmax_only(k, lg, t, tk, tp, mp_):
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def _fused(k, lg, t, tk, tp, mp_):
        sampled = _sample_warped(use_min_p, k, lg, t, tk, tp, mp_)
        if greedy.ndim == 0:
            # only reachable with scalar greedy False (the True case took
            # the argmax branch above/below) — sampled IS the answer
            return sampled
        # per-row fleet flags: mixed fleets resolve row-wise
        return jnp.where(greedy, jnp.argmax(lg, axis=-1), sampled).astype(
            jnp.int32
        )

    operands = (key, logits, temperature, top_k, top_p, mp)
    if isinstance(all_greedy, jax.core.Tracer):
        # Inside jit/scan (every decode hot loop): the warper pipeline
        # costs a full-vocab argsort + softmax + cumsum per step, and a
        # where(greedy, ...) would keep it live even when every step is
        # an argmax. lax.cond runs only the taken branch — greedy decode
        # skips the sampler entirely (the slot fleet takes it whenever
        # ALL rows are greedy). The sampled branch is bit-identical to
        # the fused path.
        return jax.lax.cond(all_greedy, _argmax_only, _fused, *operands)
    # Eager call (tests / one-off prefills outside jit): an eager cond
    # re-traces fresh branch closures every call and XLA recompiles the
    # whole computation each time (measured 10x test-suite blowup) — a
    # concrete flag needs a plain Python branch instead.
    # jaxlint: disable=host-sync -- eager-only branch: the Tracer case returned via lax.cond above; a concrete flag costs nothing to read
    if bool(all_greedy):
        return _argmax_only(*operands)
    return _fused(*operands)


def _sample_warped(use_min_p: bool, key, logits, temperature, top_k, top_p,
                   min_p):
    """The warper pipeline + categorical draw (the non-greedy half of
    sample_token, shared by its fused and lax.cond forms)."""
    scaled = apply_temperature(logits, temperature)
    vocab = scaled.shape[-1]

    sort_idx = jnp.argsort(scaled, axis=-1)[..., ::-1]
    sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    rank = jnp.arange(vocab, dtype=jnp.int32)
    # top-k: keep ranks < k (rank ordering matches the threshold semantics
    # of top_k_filter up to ties at the threshold). k <= 0 disables.
    keep_k = jnp.where(top_k <= 0, True, rank < jnp.clip(top_k, 1, vocab))
    # top-p: shifted cumulative-probability removal, first token always kept.
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    over = cum > top_p
    keep_p = ~jnp.concatenate([jnp.zeros_like(over[..., :1]), over[..., :-1]], axis=-1)
    keep_p = jnp.where(top_p >= 1.0, True, keep_p)
    keep = keep_k & keep_p
    if use_min_p:
        # sorted descending: rank 0 holds max prob. HF's warper order is
        # temperature -> top_k -> top_p -> min_p (transformers 4.57
        # _get_logits_processor); intersecting the keep-masks here is
        # token-identical because min_p's ratio test is invariant under
        # the earlier filters' renormalization and its keep set is a
        # prefix of the sorted ranks
        keep_m = probs >= min_p * probs[..., :1]
        keep &= jnp.where(min_p <= 0.0, True, keep_m)

    sorted_filtered = jnp.where(keep, sorted_logits, NEG_INF)
    draw = jax.random.categorical(key, sorted_filtered, axis=-1)  # rank index
    sampled = jnp.take_along_axis(sort_idx, draw[..., None], axis=-1)[..., 0]
    return sampled.astype(jnp.int32)


def top_n_probs(logits: jnp.ndarray, n: int = 5):
    """Top-n (prob, token) pairs for debug observability — the reference
    prints top-5 next-token predictions for the first 3 steps
    (/root/reference/orchestration.py:172-178)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_probs, top_ids = jax.lax.top_k(probs, n)
    return top_probs, top_ids
