"""int8 wire format for inter-stage activation hand-offs.

Activations crossing pp stage boundaries are full-precision by default,
and on real TPU slices the ICI bytes of those hops — not stage compute —
are the binding constraint for deeper pipelines and larger microbatch
counts (EQuARX, PAPERS.md: quantizing XLA collectives wins 2-4x at
negligible quality cost). This module is the ONE implementation of the
symmetric per-token-row int8 quantize/dequantize both wire consumers
share:

  * the KV cache (ops/kv_quant.py) — `quantize_chunk` delegates to
    `quantize_rows` here, so cache quantization and wire quantization can
    never drift numerically;
  * the pp/sp wire (EngineConfig.pp_wire_quant = "int8") — every
    activation hand-off family quantizes immediately before the
    collective and dequantizes on landing:
      1. the gated microstep ring (parallel/pipeline._microstep_loop),
      2. the 1F1B schedule's two ppermute sites (parallel/schedule.py),
      3. the sp ring/ulysses K-V chunk hops (parallel/ring.py — int8
         caches already rotate scales; raw-dtype activations adopt the
         same recipe via the `wire` flag),
      4. the masked `psum` broadcasts of the final-stage [B, 1, D]
         window — quantize the masked operand so the all-reduce ships
         int8 data + fp32 scales, EQuARX-style (exactly one participant
         is nonzero, so the int8 sum cannot overflow).

Data + scale travel as a `WireQuant` pytree through `ppermute`/`psum`
exactly like `KVQuant` leaves do on the sp ring. Everything stays fully
traced — zero host syncs, one compiled program per topology — and the
`wire-dtype` HLO rule family (analysis/hlo.py) machine-checks that the
lowered collective-permutes really carry si8 when the knob is on.

Exactness contract: quant off (the default) is bit-identical to the
unquantized collectives — `wire_ppermute(..., quant=False)` IS
`lax.ppermute` and `masked_psum(..., quant=False)` IS the masked-psum
idiom the call sites used verbatim. Quant on is toleranced: each wire
crossing is one symmetric-int8 round trip (`wire_roundtrip`), gated by
the greedy token-match-rate tests in tests/test_wire_quant.py.
"""

from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class WireQuant:
    """int8 wire leaf: q [..., D] int8 data + s [...] fp32 per-row scales.

    A registered pytree, so a single `ppermute`/`psum` call ships data
    and scales together (two collectives in the lowered program — one
    si8, one small f32) and the loop-carry/type discipline of the
    surrounding `fori_loop`/`while_loop` is untouched.
    """

    __slots__ = ("q", "s")

    def __init__(self, q, s):
        self.q = q
        self.s = s

    def tree_flatten(self):
        return (self.q, self.s), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"WireQuant(q={self.q.shape}@{self.q.dtype}, s={self.s.shape})"


def quantize_rows(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 over the LAST axis, one fp32 scale per leading row:
    x [..., D] -> (q [..., D] int8, s [...] fp32).

    Per-row granularity keeps the quantization error independent of
    content elsewhere in the batch/sequence — a single outlier token
    poisons only its own row, never the whole tensor (the same argument
    as the KV cache's per-(token, head) scales, which are this exact
    function applied to [B, T, KV, Dh] chunks)."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1)
    s = jnp.maximum(absmax / 127.0, 1e-12)  # all-zero rows stay zero
    q = jnp.clip(jnp.round(x32 / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def wire_encode(x: jnp.ndarray) -> WireQuant:
    """Quantize an activation for the wire."""
    return WireQuant(*quantize_rows(x))


def wire_decode(w: WireQuant, dtype) -> jnp.ndarray:
    """Dequantize on landing, restoring the sender's dtype (loop carries
    stay type-stable across the hop)."""
    return (w.q.astype(jnp.float32) * w.s[..., None]).astype(dtype)


def wire_roundtrip(x: jnp.ndarray) -> jnp.ndarray:
    """The numerics of ONE wire crossing without the collective — what a
    receiving stage sees of `x`. The CPU proxy below and the
    tolerance tests replay the mesh's error profile with this."""
    return wire_decode(wire_encode(x), x.dtype)


def wire_ppermute(x: jnp.ndarray, axis_name, perm, *, quant: bool):
    """Ring hand-off: `quant=False` IS `lax.ppermute` (bit-identical —
    the off-path contract); True ships int8 data + fp32 scales as one
    WireQuant pytree and dequantizes on landing."""
    if not quant:
        return jax.lax.ppermute(x, axis_name, perm)
    w = jax.lax.ppermute(wire_encode(x), axis_name, perm)
    return wire_decode(w, x.dtype)


def masked_psum(x: jnp.ndarray, sel, axis_name, *, quant: bool):
    """Masked single-owner broadcast: psum of a one-hot-masked operand
    (the final-stage [B, .., D] window hand-off every pp program ends
    with). `quant=False` is the exact masked-psum idiom the call sites
    inlined before this helper existed; True quantizes the masked
    operand so the all-reduce ships int8 data + fp32 scales — exactly
    one participant is nonzero, so the int8 sum cannot overflow."""
    if not quant:
        return jax.lax.psum(
            jnp.where(sel, x, jnp.zeros((), x.dtype)), axis_name
        )
    w = wire_encode(x)
    q = jax.lax.psum(jnp.where(sel, w.q, jnp.zeros((), w.q.dtype)), axis_name)
    s = jax.lax.psum(jnp.where(sel, w.s, jnp.zeros((), w.s.dtype)), axis_name)
    return wire_decode(WireQuant(q, s), x.dtype)


def proxy_stage_generate(cfg, params, prompt_ids, max_new: int,
                         n_stages: int, *, quant: bool = True):
    """CPU proxy of the pp ring's WIRE NUMERICS on one device.

    Greedy prefill + decode where the activation passes one
    `wire_roundtrip` after each of `n_stages` stage applications (the S
    ring hand-offs of one microstep loop) plus one more for the masked
    psum broadcast of the sampled window — the exact per-token error
    profile of the quantized mesh programs, with no mesh. The round trip
    is ROW-local (one scale per (b, t) row), so round-tripping the whole
    buffer and slicing the sampled window is identical to slicing first.

    quant=False runs the same stage-sliced forward with no round trips —
    bit-identical to the single-device greedy path (asserted in
    tests/test_wire_quant.py), so the proxy's match rate isolates
    exactly the wire quantization.

    Used by the greedy token-match-rate gates
    (tests/test_wire_quant.py): the mesh tests' tolerance is calibrated
    against this.
    """
    ranges, fwd = _proxy_fwd(cfg, n_stages, quant)

    T = len(prompt_ids)
    from ..models import api as M

    caches = tuple(
        jax.tree.map(
            lambda a, lo=l0, hi=l1: a[lo:hi],
            M.init_kv_cache(cfg, 1, max_seq=T + max_new),
        )
        for (l0, l1) in ranges
    )
    tokens = jnp.asarray([prompt_ids], jnp.int32)
    logits, caches = fwd(params, tokens, jnp.int32(0), caches, T=T)
    tok = int(jnp.argmax(logits[0, T - 1]))
    out = [tok]
    for i in range(max_new - 1):
        logits, caches = fwd(
            params, jnp.asarray([[tok]], jnp.int32), jnp.int32(T + i),
            caches, T=1,
        )
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    return out


@_functools.lru_cache(maxsize=8)
def _proxy_fwd(cfg, n_stages: int, quant: bool):
    """Memoized stage-sliced forward for the proxy (cfg is a frozen
    dataclass — hashable), so repeated proxy calls reuse one jit
    cache."""
    from ..config import stage_layer_range

    ranges = tuple(
        stage_layer_range(cfg.n_layers, n_stages, s)
        for s in range(n_stages)
    )

    @_functools.partial(jax.jit, static_argnames=("T",))
    def fwd(params, tokens, pos, caches, *, T):
        from ..models import api as M

        x = M.embed(cfg, params, tokens, pos)
        out = []
        for s, (l0, l1) in enumerate(ranges):
            layers_s = jax.tree.map(
                lambda a, lo=l0, hi=l1: a[lo:hi], params["layers"]
            )
            x, c = M.forward_layers(cfg, layers_s, x, caches[s], pos)
            out.append(c)
            if quant:
                x = wire_roundtrip(x)  # the inter-stage ppermute hop
        if quant:
            x = wire_roundtrip(x)  # the masked-psum broadcast
        return M.unembed(cfg, params, x), tuple(out)

    return ranges, fwd


def proxy_stage_match(cfg, params, prompt_ids, max_new: int,
                      n_stages: int) -> float:
    """Teacher-forced greedy match rate of the wire-quantized forward
    against the exact one: generate `max_new` tokens exactly (no wire
    error), then re-run the QUANTIZED stage forward over the same
    history and count the positions whose argmax agrees. Per-DECISION
    agreement — one early flip does not cascade through the rest of the
    sequence the way a free-running comparison would — which is the
    quantity the quality gate should bound (it is also what a user of a
    real checkpoint experiences per step)."""
    from ..config import stage_layer_range
    from ..models import api as M

    exact = proxy_stage_generate(
        cfg, params, prompt_ids, max_new, n_stages, quant=False
    )
    T = len(prompt_ids)
    full = list(prompt_ids) + exact
    ranges = [
        stage_layer_range(cfg.n_layers, n_stages, s)
        for s in range(n_stages)
    ]
    caches = tuple(
        jax.tree.map(
            lambda a, lo=l0, hi=l1: a[lo:hi],
            M.init_kv_cache(cfg, 1, max_seq=len(full)),
        )
        for (l0, l1) in ranges
    )
    x = M.embed(cfg, params, jnp.asarray([full], jnp.int32), jnp.int32(0))
    for s, (l0, l1) in enumerate(ranges):
        layers_s = jax.tree.map(
            lambda a, lo=l0, hi=l1: a[lo:hi], params["layers"]
        )
        x, _ = M.forward_layers(cfg, layers_s, x, caches[s], jnp.int32(0))
        x = wire_roundtrip(x)
    x = wire_roundtrip(x)
    logits = M.unembed(cfg, params, x)
    pred = jnp.argmax(logits[0], axis=-1)
    hits = sum(
        int(pred[T - 1 + i]) == exact[i] for i in range(max_new)
    )
    return hits / max_new


def wire_bytes(shape, itemsize: int, hops: int, *, quant: bool) -> int:
    """Host-side static wire accounting (no tracing cost): bytes one
    activation of `shape` costs crossing `hops` hand-offs. The formula
    itself lives in analysis/comms.wire_link_bytes — the ONE
    implementation the dli_pp_wire_bytes_total counters and the
    symbolic link table evaluate."""
    from ..analysis.comms import wire_link_bytes

    return wire_link_bytes(shape, itemsize, hops, quant=quant)
