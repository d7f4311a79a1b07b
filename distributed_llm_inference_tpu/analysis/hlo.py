"""Compiled-artifact verification: assert the invariants ON the lowered
programs, not just the source.

The AST lint proves the source doesn't *write* a host sync; this pass
proves the artifact doesn't *contain* one — the two fail independently
(a dependency could lower a callback; a refactor could drop donation
without touching any linted line). Checks, all on the tiny test config
so they run in CI on CPU in seconds:

  * zero host callbacks (`pure_callback` / `io_callback` /
    `debug_callback` custom calls) in the solo AND constrained decode
    StableHLO — the zero-Python-per-token contract;
  * the decode loop really is compiled (a `stablehlo.while` is present —
    an unrolled or host-driven loop would be a silent regression);
  * donation aliasing is ACTUALLY present for the KV cache (the
    `tf.aliasing_output` attr on the donated inputs — `donate_argnames`
    that XLA rejects degrades to a copy with only a warning);
  * a two-invocation recompile guard: calling decode again with
    different *traced* values (limit, start_pos) must not grow the jit
    cache — a shape or weak-type drift here means compile-per-step in
    production;
  * on a pp mesh (gated on `jax.shard_map`, like every pp test): the
    decode program contains the ring `collective_permute` and no
    callbacks;
  * the `wire-dtype` family (EngineConfig.pp_wire_quant): with the int8
    wire ON, every full-rank `collective_permute` operand is si8 (fp32
    allowed only for the rank-(n-1) scale companions) — the byte claim
    machine-checked on the artifact — plus callbacks/donation/
    recompile-guard legs for the quantized program; with the knob OFF,
    no int8 ships at all (the bit-identity contract);
  * the `adapter-mixed` family (engine/adapters.py paged runtime LoRA):
    the adapter-conditioned mixed launch — per-slot page ids as a
    traced device gather — keeps zero callbacks, pool donation,
    IDENTICAL StableHLO across adapter mixes, and a no-recompile
    execution guard: one compiled program serves any adapter mix.

Reused by tests/test_analysis.py and tests/test_constrained_decode.py —
one implementation of the artifact assertions.
"""

from __future__ import annotations

import functools

_CALLBACK_MARKERS = ("callback",)  # pure/io/debug callback custom calls


def check_no_host_callbacks(text: str) -> list:
    """Problems if the lowered text contains any host-callback custom
    call. `text`: StableHLO (`lowered.as_text()`)."""
    low = text.lower()
    out = []
    for marker in _CALLBACK_MARKERS:
        if marker in low:
            n = low.count(marker)
            out.append(
                f"lowered program contains {n} {marker!r} occurrence(s) — "
                f"the decode hot path must run zero host callbacks"
            )
    return out


def check_while_compiled(text: str) -> list:
    if "stablehlo.while" not in text and "while" not in text.lower():
        return ["no while op in the lowered decode — the loop is not "
                "compiled (unrolled or host-driven?)"]
    return []


def check_donation(text: str, min_aliased: int = 1) -> list:
    """Donation must survive lowering: each donated input carries a
    `tf.aliasing_output` attr in the StableHLO. min_aliased: the number
    of cache leaves expected to alias (a {k, v} cache has 2)."""
    n = text.count("tf.aliasing_output")
    if n < min_aliased:
        return [
            f"only {n} aliased input(s) in the lowered program, expected "
            f">= {min_aliased} — cache donation was dropped (XLA will "
            f"copy the cache every step)"
        ]
    return []


def count_cache_leaves(cache) -> int:
    import jax

    return len(jax.tree.leaves(cache))


@functools.lru_cache(maxsize=1)
def tiny_engine():
    """The shared tiny solo engine (test-llama-tiny: vocab 256, dim 64 —
    compiles in seconds on CPU)."""
    from ..config import EngineConfig
    from ..engine.engine import InferenceEngine
    from ..models.registry import get_model_config

    cfg = get_model_config("test-llama-tiny")
    return InferenceEngine(
        cfg, engine_cfg=EngineConfig(prefill_buckets=(32,))
    )


def _decode_args(engine, constraint=None, limit=8, start_pos=4):
    import jax
    import jax.numpy as jnp

    from ..engine import generate as G

    cfg = engine.cfg
    cache = engine.backend.init_cache(1, cfg.max_seq_len)
    return (
        cfg, engine.backend.params, jnp.zeros((1,), jnp.int32), cache,
        jnp.int32(start_pos), jnp.int32(limit), jax.random.PRNGKey(0),
        G.default_sampling(greedy=True), None, None, None, None, constraint,
    )


def lower_solo_decode(engine=None, constrained: bool = False,
                      max_steps: int = 16) -> str:
    """StableHLO text of the REAL solo decode program (G.decode with its
    declared donation — not a re-wrap, which would silently drop
    donate_argnames and void the aliasing check)."""
    from ..engine import generate as G

    engine = engine or tiny_engine()
    constraint = None
    if constrained:
        art = engine._compile_constraint({"regex": "[ab]{1,8}"})
        cm, ct = art.device_tables()
        import jax.numpy as jnp

        constraint = (jnp.zeros((1,), jnp.int32), cm, ct)
    lowered = G.decode.lower(
        *_decode_args(engine, constraint), max_steps=max_steps
    )
    return lowered.as_text()


def check_no_recompile(engine=None) -> list:
    """Run the decode program twice with different TRACED values; the jit
    cache must not grow (a second entry means some 'traced' input is
    actually specializing the program — compile-per-request in prod)."""
    import jax
    import jax.numpy as jnp

    from ..engine import generate as G

    engine = engine or tiny_engine()
    cfg = engine.cfg
    sampling = G.default_sampling(greedy=True)

    def run(limit, start_pos, seed):
        cache = engine.backend.init_cache(1, cfg.max_seq_len)
        return G.decode(
            cfg, engine.backend.params, jnp.zeros((1,), jnp.int32), cache,
            jnp.int32(start_pos), jnp.int32(limit), jax.random.PRNGKey(seed),
            sampling, None, None, None, None, None, max_steps=16,
        )

    out = run(4, 2, 0)
    jax.block_until_ready(out[0])
    size_after_first = G.decode._cache_size()
    out = run(9, 5, 3)
    jax.block_until_ready(out[0])
    size_after_second = G.decode._cache_size()
    if size_after_second > size_after_first:
        return [
            f"decode recompiled across invocations with different traced "
            f"values (jit cache grew {size_after_first} -> "
            f"{size_after_second}) — limit/start_pos/key must stay traced"
        ]
    return []


def _ragged_args(engine, tail: int, width: int = 32):
    """Operand tuple for the ragged paged prefill program
    (engine/paged.prefill_ragged_paged) on the tiny config with
    attn_impl="pallas", a fresh pool (donated per run) and a `tail`-token
    prompt padded to the fixed launch `width`."""
    import jax
    import jax.numpy as jnp

    from ..engine import generate as G
    from ..engine import paged as EP

    cfg = engine.cfg.replace(attn_impl="pallas")
    bs, MB = 16, 8
    pool = EP.init_pool(cfg, MB + 2, bs)
    table = jnp.asarray([list(range(1, MB + 1))], jnp.int32)
    meta, tok_row, tok_pos, _, _ = EP.build_ragged_meta(
        [(0, 0, tail, EP.RAGGED_PREFILL)], width=width, tile=8
    )
    toks = jnp.asarray([1] * tail + [0] * (width - tail), jnp.int32)
    return (
        cfg, engine.backend.params, toks, jnp.asarray(tok_row),
        jnp.asarray(tok_pos), jnp.asarray(meta), pool, table,
        jnp.int32(tail - 1), jax.random.PRNGKey(0),
        G.default_sampling(greedy=True),
    )


def lower_ragged_prefill(engine=None, tail: int = 20, width: int = 32) -> str:
    """StableHLO of the REAL ragged paged prefill launch (the program a
    paged fleet's whole-prefill admission dispatches) — declared donation
    intact, ragged kernel selected."""
    from ..engine import paged as EP

    engine = engine or tiny_engine()
    return EP.prefill_ragged_paged.lower(
        *_ragged_args(engine, tail, width)
    ).as_text()


def check_ragged_shape_stability(engine=None) -> list:
    """Two DIFFERENT tail lengths must lower to the IDENTICAL program:
    the tail only moves traced values (token contents, metadata, the
    sample position), never shapes. Identical StableHLO text is the
    artifact-level proof that one compiled launch serves any prompt tail
    — the property that deletes the prefill-bucket ladder."""
    engine = engine or tiny_engine()
    a = lower_ragged_prefill(engine, tail=20)
    b = lower_ragged_prefill(engine, tail=27)
    if a != b:
        return [
            "ragged prefill lowered DIFFERENT programs for tails 20 and "
            "27 — some per-tail value became shape-specializing "
            "(compile-per-prompt-length in production)"
        ]
    return []


def check_ragged_no_recompile(engine=None) -> list:
    """Execute the ragged prefill with two different tail lengths; the
    jit cache must not grow (a second entry means a 'traced' operand is
    specializing the program — the bucket ladder reborn as recompiles)."""
    import jax

    from ..engine import paged as EP

    engine = engine or tiny_engine()
    out = EP.prefill_ragged_paged(*_ragged_args(engine, 20))
    jax.block_until_ready(out[0])
    size_after_first = EP.prefill_ragged_paged._cache_size()
    out = EP.prefill_ragged_paged(*_ragged_args(engine, 27))
    jax.block_until_ready(out[0])
    size_after_second = EP.prefill_ragged_paged._cache_size()
    if size_after_second > size_after_first:
        return [
            f"ragged prefill recompiled across tail lengths (jit cache "
            f"grew {size_after_first} -> {size_after_second}) — the "
            f"launch width must be the only shape"
        ]
    return []


def _mixed_args(engine, n_decode: int, chunk: int, width: int = 32):
    """Operand tuple for the mixed scheduler step program
    (engine/paged.mixed_step_ragged) on the tiny config: `n_decode`
    decode rows + one `chunk`-token prefill chunk on a 2-slot fleet with
    attn_impl="pallas" — the launch the chunked-prefill scheduler
    dispatches every step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..engine import generate as G
    from ..engine import paged as EP

    cfg = engine.cfg.replace(attn_impl="pallas")
    bs, MB, B = 16, 4, 2
    pool = EP.init_pool(cfg, 2 * MB + 2, bs)
    table = jnp.asarray(
        [list(range(1, MB + 1)), list(range(MB + 1, 2 * MB + 1))], jnp.int32
    )
    entries = [
        (b, 4 + b, 1, EP.RAGGED_DECODE) for b in range(n_decode)
    ] + [(1, 0, chunk, EP.RAGGED_PREFILL)]
    meta, tok_row, tok_pos, offsets, _ = EP.build_ragged_meta(
        entries, width=width, tile=8,
    )
    toks = np.zeros((width,), np.int32)
    dec_flag = np.zeros((width,), bool)
    dec_idx = np.zeros((B,), np.int32)
    for b in range(n_decode):
        dec_flag[offsets[b]] = True
        dec_idx[b] = offsets[b]
    off = offsets[n_decode]
    toks[off : off + chunk] = 1
    state, sparams = G.init_slots(B, cfg.vocab_size)
    arm = EP.idle_mixed_arm(B, cfg.vocab_size)._replace(
        on=jnp.asarray([False, True]),
        idx=jnp.asarray([0, off + chunk - 1], jnp.int32),
        prompt_len=jnp.asarray([0, chunk], jnp.int32),
        max_tokens=jnp.asarray([0, 4], jnp.int32),
    )
    return (
        cfg, engine.backend.params, jnp.asarray(toks), jnp.asarray(tok_row),
        jnp.asarray(tok_pos), jnp.asarray(dec_flag), jnp.asarray(meta),
        pool, table, state, sparams, jax.random.PRNGKey(0),
        jnp.asarray(dec_idx), arm,
    )


def lower_mixed_step(engine=None, n_decode: int = 1, chunk: int = 9) -> str:
    """StableHLO of the REAL mixed scheduler launch (decode rows +
    prefill chunks in one program) — declared pool donation intact."""
    from ..engine import paged as EP

    engine = engine or tiny_engine()
    return EP.mixed_step_ragged.lower(
        *_mixed_args(engine, n_decode, chunk)
    ).as_text()


def check_mixed_shape_stability(engine=None) -> list:
    """Two DIFFERENT launch compositions (decode-row count, chunk length)
    must lower to the IDENTICAL program: the scheduler re-plans the mix
    every step, so any composition-dependent shape would recompile
    per step — the chunked-prefill equivalent of the bucket ladder."""
    engine = engine or tiny_engine()
    a = lower_mixed_step(engine, n_decode=1, chunk=9)
    b = lower_mixed_step(engine, n_decode=2, chunk=14)
    if a != b:
        return [
            "mixed scheduler step lowered DIFFERENT programs for two "
            "launch compositions — some per-step plan value became "
            "shape-specializing (compile-per-step in production)"
        ]
    return []


def check_mixed_no_recompile(engine=None) -> list:
    """Execute the mixed step with two different compositions; the jit
    cache must not grow."""
    import jax

    from ..engine import paged as EP

    engine = engine or tiny_engine()
    out = EP.mixed_step_ragged(*_mixed_args(engine, 1, 9))
    jax.block_until_ready(out[0])
    size_after_first = EP.mixed_step_ragged._cache_size()
    out = EP.mixed_step_ragged(*_mixed_args(engine, 2, 14))
    jax.block_until_ready(out[0])
    size_after_second = EP.mixed_step_ragged._cache_size()
    if size_after_second > size_after_first:
        return [
            f"mixed scheduler step recompiled across launch compositions "
            f"(jit cache grew {size_after_first} -> {size_after_second}) — "
            f"the launch width must be the only shape"
        ]
    return []


def _spec_mixed_args(engine, n_spec: int, n_draft: int, chunk: int,
                     width: int = 32, k_max: int = 4):
    """Operand tuple for the SPECULATIVE mixed scheduler step, as the
    engine dispatches it: the _mixed_args fleet plus `n_spec` verify
    rows of `n_draft` drafts each (n-gram mode — the drafts ride the
    host token plan), the decode/verify rows' positions marked for
    on-device substitution (engine/paged.DeviceMeta). The accept
    pattern is pure DATA (token contents vs the model's argmax), and
    the derivation pattern and the adaptive per-slot K are plan data
    too, so every (accept pattern, K) pair must share one compiled
    program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..engine import generate as G
    from ..engine import paged as EP

    cfg = engine.cfg.replace(attn_impl="pallas")
    bs, MB, B = 16, 4, 2
    pool = EP.init_pool(cfg, 2 * MB + 2, bs)
    table = jnp.asarray(
        [list(range(1, MB + 1)), list(range(MB + 1, 2 * MB + 1))], jnp.int32
    )
    K1 = k_max + 1
    entries = [
        (b, 4 + b, (1 + n_draft) if b < n_spec else 1,
         EP.RAGGED_PREFILL if b < n_spec else EP.RAGGED_DECODE)
        for b in range(B)
    ] + [(1, 0, chunk, EP.RAGGED_PREFILL)]
    meta, tok_row, tok_pos, offsets, _ = EP.build_ragged_meta(
        entries, width=width, tile=8,
    )
    toks = np.zeros((width,), np.int32)
    dec_flag = np.zeros((width,), bool)
    dec_idx = np.zeros((B,), np.int32)
    dec_on = np.zeros((B,), bool)
    sp_on = np.zeros((B,), bool)
    sp_idx = np.zeros((B, K1), np.int32)
    sp_nd = np.zeros((B,), np.int32)
    for b in range(B):
        off = offsets[b]
        dec_flag[off] = True
        if b < n_spec:
            sp_on[b] = True
            sp_nd[b] = n_draft
            idxs = off + np.arange(K1, dtype=np.int32)
            idxs[n_draft + 1:] = off + n_draft
            sp_idx[b] = idxs
            toks[off + 1 : off + 1 + n_draft] = 1 + np.arange(n_draft)
        else:
            dec_on[b] = True
            dec_idx[b] = off
    off = offsets[B]
    toks[off : off + chunk] = 1
    state, sparams = G.init_slots(B, cfg.vocab_size)
    state = state._replace(
        active=jnp.ones((B,), bool), remaining=jnp.full((B,), 6, jnp.int32),
        pos=jnp.asarray([4, 5], jnp.int32),
    )
    arm = EP.idle_mixed_arm(B, cfg.vocab_size)
    spec = EP.SpecPlan(
        jnp.asarray(dec_on), jnp.asarray(sp_on), jnp.asarray(sp_idx),
        jnp.asarray(sp_nd),
    )
    t_on, t_off, k_on, k_off = EP.build_device_meta(
        entries, offsets, B, width=width, tile=8,
    )
    dev = EP.DeviceMeta(
        jnp.asarray(t_on), jnp.asarray(t_off),
        jnp.asarray(k_on), jnp.asarray(k_off),
    )
    return (
        cfg, engine.backend.params, jnp.asarray(toks), jnp.asarray(tok_row),
        jnp.asarray(tok_pos), jnp.asarray(dec_flag), jnp.asarray(meta),
        pool, table, state, sparams, jax.random.PRNGKey(0),
        jnp.asarray(dec_idx), arm, spec, None, dev,  # spec_toks=None
    )


def lower_spec_mixed_step(engine=None, n_spec: int = 1, n_draft: int = 3,
                          chunk: int = 9) -> str:
    """StableHLO of the REAL speculative mixed launch (verify rows +
    decode rows + prefill chunks in one program, decode/verify positions
    substituted on device from slot state) — declared pool donation
    intact, traced accept/reject inside."""
    from ..engine import paged as EP

    engine = engine or tiny_engine()
    return EP.mixed_step_ragged.lower(
        *_spec_mixed_args(engine, n_spec, n_draft, chunk)
    ).as_text()


def check_spec_mixed_shape_stability(engine=None) -> list:
    """Two DIFFERENT speculative compositions (verify-row count, draft
    length — the adaptive-K throttle's output — and chunk length) must
    lower to the IDENTICAL program: accept patterns, derivation masks
    and per-slot draft lengths are plan DATA — any composition-dependent
    shape would recompile per accept pattern or per adaptive-K change."""
    engine = engine or tiny_engine()
    a = lower_spec_mixed_step(engine, n_spec=1, n_draft=3, chunk=9)
    b = lower_spec_mixed_step(engine, n_spec=2, n_draft=2, chunk=14)
    if a != b:
        return [
            "speculative mixed step lowered DIFFERENT programs for two "
            "verify-row compositions — some per-step spec plan, "
            "derivation or adaptive-K value became shape-specializing "
            "(compile-per-accept-pattern / compile-per-K in production)"
        ]
    return []


def check_spec_mixed_no_recompile(engine=None) -> list:
    """Execute the speculative mixed step with two different verify
    compositions AND adaptive-K values; the jit cache must not grow
    (one compiled program for every accept pattern and K — the machine
    check ISSUES 13 and 15 name)."""
    import jax

    from ..engine import paged as EP

    engine = engine or tiny_engine()
    out = EP.mixed_step_ragged(*_spec_mixed_args(engine, 1, 3, 9))
    jax.block_until_ready(out[0])
    size_after_first = EP.mixed_step_ragged._cache_size()
    out = EP.mixed_step_ragged(*_spec_mixed_args(engine, 2, 2, 14))
    jax.block_until_ready(out[0])
    size_after_second = EP.mixed_step_ragged._cache_size()
    if size_after_second > size_after_first:
        return [
            f"speculative mixed step recompiled across verify "
            f"compositions (jit cache grew {size_after_first} -> "
            f"{size_after_second}) — accept patterns, derivation masks "
            f"and draft lengths must stay traced data"
        ]
    return []


@functools.lru_cache(maxsize=1)
def tiny_adapter_engine():
    """tiny_engine plus the paged runtime-LoRA leaves (slots=4, rank=4)
    and an attached AdapterPool — the engine the adapter-mixed legs
    lower against. Separate from tiny_engine: the extra leaves change
    the params pytree, so sharing would shadow its cached programs."""
    from ..config import EngineConfig
    from ..engine.adapters import attach_adapter_pool
    from ..engine.engine import InferenceEngine
    from ..models.registry import get_model_config

    cfg = get_model_config("test-llama-tiny")
    engine = InferenceEngine(
        cfg, engine_cfg=EngineConfig(prefill_buckets=(32,))
    )
    attach_adapter_pool(engine, slots=4, rank=4)
    return engine


def lower_adapter_mixed_step(engine=None, pages=(0, 1), n_decode: int = 1,
                             chunk: int = 9) -> str:
    """StableHLO of the ADAPTER-conditioned mixed scheduler launch: the
    ordinary mixed step plus the per-slot adapter page ids as a traced
    operand (engine/adapters.py; page 0 = the base page)."""
    import jax.numpy as jnp

    from ..engine import paged as EP

    engine = engine or tiny_adapter_engine()
    return EP.mixed_step_ragged.lower(
        *_mixed_args(engine, n_decode, chunk),
        pages=jnp.asarray(pages, jnp.int32),
    ).as_text()


def check_adapter_mixed_shape_stability(engine=None) -> list:
    """Two DIFFERENT adapter mixes (per-slot page assignments) on two
    DIFFERENT launch compositions must lower to the IDENTICAL program:
    page ids are traced DATA riding a device gather, so any mix-
    dependent shape would recompile per adapter mix — the multi-tenant
    equivalent of the bucket ladder."""
    engine = engine or tiny_adapter_engine()
    a = lower_adapter_mixed_step(engine, pages=(0, 1), n_decode=1, chunk=9)
    b = lower_adapter_mixed_step(engine, pages=(3, 2), n_decode=2, chunk=14)
    if a != b:
        return [
            "adapter mixed step lowered DIFFERENT programs for two "
            "adapter mixes — some page assignment became shape-"
            "specializing (compile-per-adapter-mix in production)"
        ]
    return []


def check_adapter_mixed_no_recompile(engine=None) -> list:
    """Execute the adapter mixed step with two different adapter mixes
    AND launch compositions; the jit cache must not grow — ONE compiled
    program serves any adapter mix, the acceptance invariant."""
    import jax
    import jax.numpy as jnp

    from ..engine import paged as EP

    engine = engine or tiny_adapter_engine()
    out = EP.mixed_step_ragged(
        *_mixed_args(engine, 1, 9), pages=jnp.asarray([0, 1], jnp.int32)
    )
    jax.block_until_ready(out[0])
    size_after_first = EP.mixed_step_ragged._cache_size()
    out = EP.mixed_step_ragged(
        *_mixed_args(engine, 2, 14), pages=jnp.asarray([3, 2], jnp.int32)
    )
    jax.block_until_ready(out[0])
    size_after_second = EP.mixed_step_ragged._cache_size()
    if size_after_second > size_after_first:
        return [
            f"adapter mixed step recompiled across adapter mixes (jit "
            f"cache grew {size_after_first} -> {size_after_second}) — "
            f"page ids must stay traced data"
        ]
    return []


def pp_available() -> bool:
    import jax

    return len(jax.devices()) >= 2


@functools.lru_cache(maxsize=2)
def _pp_engine(wire_quant=None):
    """Cached 2-stage pp engine on the tiny config (one per wire mode —
    the wire-dtype family lowers the SAME decode with the knob on and
    off). Caller must gate on pp_available()."""
    from ..config import EngineConfig, MeshConfig
    from ..runtime import create_engine

    return create_engine(
        "test-llama-tiny", mesh_cfg=MeshConfig(pp=2),
        engine_cfg=EngineConfig(
            prefill_buckets=(32,), pp_wire_quant=wire_quant
        ),
    )


def lower_pp_decode(max_steps: int = 4, wire_quant=None) -> str:
    """StableHLO of the pp-ring decode step (2 stages, tiny config).
    Caller must gate on pp_available()."""
    import jax
    import jax.numpy as jnp

    from ..engine import generate as G

    engine = _pp_engine(wire_quant)
    backend = engine.backend
    cache = backend.init_cache(1, engine.cfg.max_seq_len)
    fn = backend._build_decode(max_steps)
    lowered = fn.lower(
        backend.shared, backend.layers, jnp.zeros((1,), jnp.int32), cache,
        jnp.int32(4), jnp.int32(max_steps), jax.random.PRNGKey(0),
        G.default_sampling(greedy=True),
    )
    return lowered.as_text()


def _collective_operands(text: str, opname: str) -> list:
    """(rank, dtype, line) of every `opname` collective operand in the
    lowered text — the function-type clause `: (tensor<...>) -> ...`.
    (The attribute dict's `replica_groups ... : tensor<...>` has no
    paren wrapper, so the regex cannot mistake it for an operand.)"""
    import re

    ops = []
    for line in text.splitlines():
        if opname not in line:
            continue
        m = re.search(r":\s*\(tensor<([^>]+)>\)", line)
        if not m:
            continue
        parts = m.group(1).split("x")
        ops.append((len(parts) - 1, parts[-1], line.strip()[:110]))
    return ops


def _collective_permute_operands(text: str) -> list:
    return _collective_operands(text, "collective_permute")


def check_wire_dtype(text: str) -> list:
    """With pp_wire_quant="int8", every collective_permute on the pp axis
    must ship si8 DATA: the full-rank ([B, T, D]) operands are i8, and
    any non-i8 operand is a rank-(n-1) scale companion (one fp32 per
    token row). This is the machine check that the wire really carries
    int8 — the byte claim, proven on the artifact."""
    ops = _collective_permute_operands(text)
    if not ops:
        return ["no collective_permute in the wire-quantized pp decode "
                "program — the ring hand-off is missing"]
    data_rank = max(r for r, _, _ in ops)
    problems = []
    if not any(d == "i8" for r, d, _ in ops if r == data_rank):
        problems.append(
            "no si8 activation collective_permute — the pp wire is not "
            "int8 despite pp_wire_quant"
        )
    for r, d, line in ops:
        if r == data_rank and d != "i8":
            problems.append(
                f"full-rank collective_permute ships {d}, not si8: {line}"
            )
    return problems


def check_wire_off_exact(text: str) -> list:
    """With the knob OFF (the default), NO collective_permute may carry
    i8 — the off path must be the bit-identical unquantized wire."""
    bad = [
        line for r, d, line in _collective_permute_operands(text) if d == "i8"
    ]
    return [
        f"pp_wire_quant=None program ships int8 on the wire (the off "
        f"path must be bit-identical): {line}" for line in bad
    ]


def check_wire_no_recompile() -> list:
    """Run the wire-quantized pp decode twice with different TRACED
    values; neither the variant memo nor the jit cache may grow — the
    quantized programs obey the same one-program-per-topology contract
    as the plain wire."""
    import jax
    import jax.numpy as jnp

    from ..engine import generate as G

    engine = _pp_engine("int8")
    backend = engine.backend
    sampling = G.default_sampling(greedy=True)

    def run(limit, start_pos, seed):
        cache = backend.init_cache(1, engine.cfg.max_seq_len)
        return backend.decode(
            jnp.zeros((1,), jnp.int32), cache, jnp.int32(start_pos),
            jnp.int32(limit), jax.random.PRNGKey(seed), sampling,
            max_steps=8,
        )

    out = run(4, 2, 0)
    jax.block_until_ready(out[0])
    variants = len(backend._decode_cache)
    size_first = next(iter(backend._decode_cache.values()))._cache_size()
    out = run(6, 3, 1)
    jax.block_until_ready(out[0])
    size_second = next(iter(backend._decode_cache.values()))._cache_size()
    if len(backend._decode_cache) > variants or size_second > size_first:
        return [
            f"wire-quantized pp decode recompiled across invocations "
            f"(programs {variants} -> {len(backend._decode_cache)}, jit "
            f"cache {size_first} -> {size_second}) — quantize/dequantize "
            f"must stay inside the one compiled program"
        ]
    return []


def check_gather_dtype(text: str) -> list:
    """The pp decode program's all_gather is the vocab logits gather
    (the FAT_INVENTORY edge): its operand must be fp32 in BOTH wire
    modes — the wire knob quantizes the ring hand-off, never the
    logits path (sampling parity depends on exact fp32 logits)."""
    ops = _collective_operands(text, "all_gather")
    if not ops:
        return ["no all_gather in the pp decode program — the vocab-"
                "sharded logits gather (parallel/vocab.unembed_sharded) "
                "is missing"]
    return [
        f"all_gather ships {d}, not f32 — the logits gather must stay "
        f"full precision (quantizing it is the tracked FAT_INVENTORY "
        f"worklist, not a silent wire side effect): {line}"
        for r, d, line in ops if d != "f32"
    ]


def check_a2a_dtype(text: str, *, wire: bool) -> list:
    """Operand dtypes of the ulysses all_to_all exchanges (parallel/
    ring.ulysses_attend). With `wire` on, the K and V head-scatter a2a
    ship si8 data (their fp32 scale companions ride rank-(n-1) a2a);
    off, nothing on the sp wire may be int8 — the same bit-identity
    contract as the pp ring, proven per-primitive on the artifact."""
    ops = _collective_operands(text, "all_to_all")
    if not ops:
        return ["no all_to_all in the sp attend program — the ulysses "
                "head<->sequence exchange is missing"]
    data_rank = max(r for r, _, _ in ops)
    si8 = [line for r, d, line in ops if r == data_rank and d == "i8"]
    if wire and len(si8) < 2:
        return [
            f"wire-quantized ulysses attend ships {len(si8)} si8 "
            f"full-rank all_to_all (expected >= 2: K and V) — the sp "
            f"wire is not int8 despite the knob"
        ]
    if not wire and any(d == "i8" for _, d, _ in ops):
        return [
            f"wire=off ulysses attend ships int8 on the sp wire (the "
            f"off path must be bit-identical): {next(l for _, d, l in ops if d == 'i8')}"
        ]
    return []


def check_comms_graph(text: str, topology: str) -> list:
    """Cross-validate the lowered program against the statically derived
    edge set (analysis/comms.HLO_PREDICTED): every predicted StableHLO
    collective kind appears, and nothing unpredicted appears. This is
    the twin that keeps the static comms model honest — a new collective
    in the source shows up here before it ships unaccounted."""
    from .comms import STABLEHLO_COLLECTIVES, predicted_hlo_ops

    found = {k for k in STABLEHLO_COLLECTIVES if k in text}
    want = predicted_hlo_ops(topology)
    problems = []
    for k in sorted(want - found):
        problems.append(
            f"{topology}: predicted collective {k} absent from the "
            f"lowered program — the static graph "
            f"(analysis/comms.HLO_PREDICTED) is stale"
        )
    for k in sorted(found - want):
        problems.append(
            f"{topology}: lowered program contains unpredicted "
            f"collective {k} — add the edge to analysis/comms."
            f"HLO_PREDICTED (and the link table, if it moves "
            f"activation bytes)"
        )
    return problems


def lower_sp_attend(wire: bool = False) -> str:
    """StableHLO of one ulysses attention body shard_mapped over a
    2-device sp mesh (tiny head counts: H=4, KV=2 scatter over sp=2).
    Caller must gate on pp_available() — same capability set."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..parallel.mesh import AXIS_SP
    from ..parallel.ring import ulysses_attend

    mesh = Mesh(np.array(jax.devices()[:2]), (AXIS_SP,))
    B, T, H, KV, Dh = 1, 8, 4, 2, 16
    q = jnp.zeros((B, T, H, Dh), jnp.float32)
    k = jnp.zeros((B, T, KV, Dh), jnp.float32)
    v = jnp.zeros((B, T, KV, Dh), jnp.float32)

    def body(q, k, v):
        return ulysses_attend(q, k, v, AXIS_SP, wire=wire)

    shmapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, AXIS_SP), P(None, AXIS_SP), P(None, AXIS_SP)),
        out_specs=P(None, AXIS_SP),
        check_vma=False,
    )
    return jax.jit(shmapped).lower(q, k, v).as_text()


def check_pp_ring(text: str, max_per_step: int = 2) -> list:
    """The pp decode program must hand activations around the ring: at
    least one collective_permute (the lax.ppermute microstep hop), and a
    small rolled count — an unrolled ring would multiply it per
    microstep."""
    n = text.count("collective_permute")
    if n < 1:
        return ["no collective_permute in the pp decode program — the "
                "ring hand-off is missing (activations moving over host?)"]
    if n > max_per_step:
        return [
            f"{n} collective_permute ops in the pp decode program "
            f"(expected <= {max_per_step}) — the microstep ring appears "
            f"unrolled (compile time and program size scale with steps)"
        ]
    return []


def run_hlo_checks() -> dict:
    """The full artifact suite; {check_name: [problems]} (empty list ==
    pass). The CLI and the CI gate consume this."""
    results = {}
    engine = tiny_engine()

    solo = lower_solo_decode(engine)
    results["solo-decode-callbacks"] = check_no_host_callbacks(solo)
    results["solo-decode-while"] = check_while_compiled(solo)
    cache = engine.backend.init_cache(1, engine.cfg.max_seq_len)
    results["solo-decode-donation"] = check_donation(
        solo, min_aliased=count_cache_leaves(cache)
    )

    constrained = lower_solo_decode(engine, constrained=True)
    results["constrained-decode-callbacks"] = check_no_host_callbacks(
        constrained
    )
    results["constrained-decode-donation"] = check_donation(
        constrained, min_aliased=count_cache_leaves(cache)
    )

    results["recompile-guard"] = check_no_recompile(engine)

    # ragged paged ingest (engine/paged.py + the ragged kernel): the
    # admission path must stay ONE host-sync-free launch per chunk with
    # no per-tail-shape recompile — the properties that replaced the
    # prefill-bucket ladder
    ragged = lower_ragged_prefill(engine)
    results["ragged-prefill-callbacks"] = check_no_host_callbacks(ragged)
    results["ragged-shape-stability"] = check_ragged_shape_stability(engine)
    results["ragged-recompile-guard"] = check_ragged_no_recompile(engine)

    # mixed scheduler step (engine/scheduler.py + engine/paged.
    # mixed_step_ragged): the chunked-prefill launch must stay ONE
    # host-sync-free program across every per-step launch composition —
    # the scheduler re-plans the decode/prefill mix every step, so a
    # composition-dependent shape would compile per step
    mixed = lower_mixed_step(engine)
    results["sched-mixed-callbacks"] = check_no_host_callbacks(mixed)
    results["sched-mixed-donation"] = check_donation(mixed, min_aliased=2)
    results["sched-mixed-shape-stability"] = check_mixed_shape_stability(
        engine
    )
    results["sched-mixed-recompile-guard"] = check_mixed_no_recompile(engine)

    # speculative mixed step (ISSUE 13: draft-then-verify inside the
    # mixed launch; ISSUE 15: decode/verify q_start and positions
    # derived on device from slot state): the verify rows' accept/reject
    # must stay fully traced — zero host callbacks, pool donation
    # intact, and ONE compiled program across every accept pattern /
    # verify composition / adaptive-K value
    spec_mixed = lower_spec_mixed_step(engine)
    results["spec-mixed-callbacks"] = check_no_host_callbacks(spec_mixed)
    results["spec-mixed-donation"] = check_donation(spec_mixed, min_aliased=2)
    results["spec-mixed-shape-stability"] = check_spec_mixed_shape_stability(
        engine
    )
    results["spec-mixed-recompile-guard"] = check_spec_mixed_no_recompile(
        engine
    )

    # adapter-conditioned mixed step (engine/adapters.py: paged runtime
    # LoRA): the per-slot page ids are traced data riding a device
    # gather, so the multi-tenant launch must stay ONE host-sync-free
    # donated program across every adapter mix — the acceptance
    # invariant of the adapter subsystem, proven on the artifact
    adapter_engine = tiny_adapter_engine()
    adapter_mixed = lower_adapter_mixed_step(adapter_engine)
    results["adapter-mixed-callbacks"] = check_no_host_callbacks(
        adapter_mixed
    )
    results["adapter-mixed-donation"] = check_donation(
        adapter_mixed, min_aliased=2
    )
    results["adapter-mixed-shape-stability"] = (
        check_adapter_mixed_shape_stability(adapter_engine)
    )
    results["adapter-mixed-recompile-guard"] = (
        check_adapter_mixed_no_recompile(adapter_engine)
    )

    if pp_available():
        pp = lower_pp_decode()
        results["pp-decode-callbacks"] = check_no_host_callbacks(pp)
        results["pp-decode-ring"] = check_pp_ring(pp)
        # wire-dtype family (EngineConfig.pp_wire_quant, ops/
        # wire_quant.py): knob OFF must ship NO int8 on the ring (the
        # bit-identity contract, checked on the artifact); knob ON must
        # ship si8 data on every full-rank collective_permute (fp32 only
        # for the rank-(n-1) scale companions), with the usual
        # callbacks / donation / recompile-guard legs on the quantized
        # program
        results["wire-dtype-off"] = check_wire_off_exact(pp)
        wired = lower_pp_decode(wire_quant="int8")
        results["wire-dtype"] = check_wire_dtype(wired)
        # data + scale = two rolled collective_permutes per microstep hop
        results["wire-ring"] = check_pp_ring(wired, max_per_step=4)
        results["wire-callbacks"] = check_no_host_callbacks(wired)
        # donation is covered by the donate-cache AST rule for the pp
        # builders — tf.aliasing_output does not survive shard_map
        # lowering text, so the artifact leg would be vacuous here (the
        # plain pp-decode checks skip it for the same reason)
        results["wire-recompile-guard"] = check_wire_no_recompile()
        # comms-graph twin (analysis/comms.HLO_PREDICTED): the statically
        # derived edge set must match the lowered program exactly, in
        # BOTH wire modes — every predicted collective kind appears and
        # nothing unpredicted appears; plus the logits all_gather dtype
        # proof (fp32 both modes — the knob never touches the logits)
        results["comms-graph-pp"] = (
            check_comms_graph(pp, "pp-decode")
            + check_comms_graph(wired, "pp-decode")
        )
        results["gather-dtype"] = (
            check_gather_dtype(pp) + check_gather_dtype(wired)
        )
        # sp twin: the ulysses attention body lowers to all_to_all
        # exchanges only, and the a2a operand dtypes prove the sp wire
        # (int8 K/V data + fp32 scales with `wire` on; zero int8 off)
        sp_off = lower_sp_attend(False)
        sp_on = lower_sp_attend(True)
        results["comms-graph-sp"] = (
            check_comms_graph(sp_off, "sp-attend")
            + check_comms_graph(sp_on, "sp-attend")
        )
        results["a2a-dtype"] = (
            check_a2a_dtype(sp_on, wire=True)
            + check_a2a_dtype(sp_off, wire=False)
        )
    else:
        results["pp-decode (skipped: < 2 devices)"] = []
        results["wire-dtype (skipped: < 2 devices)"] = []
        results["comms-graph (skipped: < 2 devices)"] = []
        results["a2a-dtype (skipped: < 2 devices)"] = []
    return results
