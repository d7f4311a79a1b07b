"""Top-level factory: model name + mesh shape -> ready InferenceEngine.

The single entry point the serving layer and client tooling use —
the reference needed three hand-edited scripts and manual URL wiring to
assemble the same topology (SURVEY.md §2 C10).
"""

from __future__ import annotations

from typing import Any, Optional

import jax

from .config import EngineConfig, MeshConfig, ModelConfig
from .engine.engine import InferenceEngine, SingleDeviceBackend
from .models import api as M
from .models.registry import get_model_config
from .parallel.context import ContextParallelBackend
from .parallel.mesh import build_mesh
from .parallel.pipeline import PipelineBackend
from .parallel.schedule import MicrobatchPipelineBackend


def create_backend(
    model: str | ModelConfig = "tinyllama-1.1b",
    *,
    mesh_cfg: MeshConfig = MeshConfig(),
    microbatches: int = 1,
    params: Any = None,
    dtype: Optional[str] = None,
    quant: Optional[str] = None,
    kv_quant: Optional[str] = None,
    attn_impl: Optional[str] = None,
    seed: int = 0,
    sp_strategy: str = "ring",
    lora: Optional[str] = None,
    wire_quant: Optional[str] = None,
    adapter_slots: int = 0,
    adapter_rank: int = 8,
):
    """Build a compute backend alone (no engine/tokenizer around it).

    Selection: single device when the mesh is trivial; the SPMD pipeline
    for pp/tp meshes; the microbatched zero-bubble schedule
    (parallel/schedule.py, BASELINE config 5) when microbatches > 1.
    Batched workloads (dryrun, batch-serving callers) use
    the backend interface directly: batch % (dp * microbatches) == 0.
    wire_quant (EngineConfig.pp_wire_quant through create_engine):
    "int8" quantizes every inter-stage activation hand-off on the SPMD
    backends (ops/wire_quant.py); ignored on the single device — there
    is no wire. Returns (cfg, backend).
    """
    cfg = get_model_config(model) if isinstance(model, str) else model
    if dtype is not None:
        cfg = cfg.replace(dtype=dtype)
    if quant is not None:
        cfg = cfg.replace(quant=quant)
    if kv_quant is not None:
        cfg = cfg.replace(kv_quant=kv_quant)
    # kv_quant composes with EVERY topology now: single device, pp/tp/dp
    # pipeline, 1F1B (per-leaf cache specs + tree-aware row slicing), and
    # sp (the ring/cp hooks quantize on write and dequantize their local
    # slot sets — parallel/context.py).
    if attn_impl is not None:
        from .config import resolve_attn_impl

        cfg = resolve_attn_impl(cfg, attn_impl)
    # a latent-attention model (models/mla_moe.py) is served on one device
    # from a latent pool: refused here, before the weights are made
    from .engine.paged import refuse_unsupported_latent

    refuse_unsupported_latent(
        cfg, quant=cfg.quant, kv_quant=cfg.kv_quant,
        mesh=not mesh_cfg.is_trivial or microbatches > 1, lora=lora,
        adapter_slots=adapter_slots,
    )
    if sp_strategy != "ring" and mesh_cfg.sp <= 1:
        # fail loudly BEFORE any backend branch (including microbatches):
        # --sp-strategy ulysses without --sp > 1 would otherwise silently
        # run with no sequence parallelism at all
        raise ValueError(
            f"sp_strategy={sp_strategy!r} needs a context-parallel mesh "
            f"(sp > 1); got sp={mesh_cfg.sp}"
        )
    if mesh_cfg.sp > 1 and (microbatches > 1 or mesh_cfg.ep > 1):
        # checked before params init (the expensive step) and before the
        # microbatch branch, which would otherwise claim the sp-wide mesh
        # and silently replicate all work across it. sp x pp composes
        # since round 5 (the context backend runs the gated microstep
        # ring over pp with the sequence still sharded over sp).
        raise ValueError(
            "sp (context parallel) does not compose with microbatching/"
            "ep yet: the 1F1B schedule and expert dispatch assume "
            "whole-sequence activations per stage"
        )
    # weight quantization covers both families now (gpt2 projections go
    # through the quant-aware mm — ops/quant._QUANT_KEYS); an unknown arch
    # rejects inside quantize_params below — AFTER params init, since the
    # registry only carries the two supported arches anyway
    if params is None:
        params = M.init_params(cfg, jax.random.PRNGKey(seed))
    if lora is not None:
        # merge BEFORE quantization: the low-rank delta lands in the
        # dense weights, then every downstream path (quant/sharding/
        # speculation) sees one ordinary checkpoint
        from .models.lora import merge_lora

        params = merge_lora(cfg, params, lora)
    if cfg.quant is not None:
        from .ops.quant import quantize_params

        params = quantize_params(cfg, params)
    if adapter_slots:
        if microbatches > 1 or mesh_cfg.sp > 1:
            raise ValueError(
                "adapter_slots > 0 (runtime LoRA serving) rides the "
                "single-device and pp/tp pipeline backends; the 1F1B "
                "and context-parallel backends carry no adapter pages"
            )
        # install AFTER quantization (the paged lora leaves stay dense)
        # and BEFORE backend construction, so pp/tp meshes shard them
        # through the ordinary parallel/partition specs
        from .engine.adapters import install_adapter_leaves

        params = install_adapter_leaves(cfg, params, adapter_slots,
                                        adapter_rank)
    if microbatches > 1:
        if mesh_cfg.pp < 2:
            raise ValueError(
                "microbatches > 1 needs a pipeline (pp >= 2): with one "
                "stage there is no bubble to fill and the round-robin "
                "schedule would only serialize the batch"
            )
        if cfg.arch != "llama":
            # the serving path for microbatched fleets is the ragged
            # (left-padded) batch path, which needs shift-invariant
            # positions — reject at build time, not at warmup/request time
            raise NotImplementedError(
                f"microbatches > 1 serves ragged llama-family fleets only; "
                f"got arch={cfg.arch!r}"
            )
        mesh = build_mesh(mesh_cfg)
        return cfg, MicrobatchPipelineBackend(
            cfg, params, mesh, n_microbatches=microbatches,
            wire_quant=wire_quant,
        )
    if mesh_cfg.sp > 1:
        mesh = build_mesh(mesh_cfg)
        return cfg, ContextParallelBackend(
            cfg, params, mesh, sp_strategy=sp_strategy,
            wire_quant=wire_quant,
        )
    if not mesh_cfg.is_trivial:
        # sp > 1 already returned above, so a non-trivial mesh here means
        # dp/pp/tp/ep — the SPMD pipeline's axes
        mesh = build_mesh(mesh_cfg)
        return cfg, PipelineBackend(cfg, params, mesh, wire_quant=wire_quant)
    return cfg, SingleDeviceBackend(cfg, params)


def create_engine(
    model: str | ModelConfig = "tinyllama-1.1b",
    *,
    mesh_cfg: MeshConfig = MeshConfig(),
    engine_cfg: EngineConfig = EngineConfig(),
    microbatches: int = 1,
    params: Any = None,
    dtype: Optional[str] = None,
    quant: Optional[str] = None,
    kv_quant: Optional[str] = None,
    attn_impl: Optional[str] = None,
    tokenizer: Any = None,
    seed: int = 0,
    sp_strategy: str = "ring",
    draft_model: Optional[str | ModelConfig] = None,
    draft_params: Any = None,
    lora: Optional[str] = None,
) -> InferenceEngine:
    """Build an engine; pp>1 selects the SPMD pipeline backend.

    params=None random-initializes (offline bring-up / benchmarks);
    pass a converted HF pytree (models/convert.py) for real weights.
    draft_model attaches a smaller same-tokenizer model for two-model
    speculative decoding ("speculative": true greedy requests verify the
    draft's proposals instead of prompt-lookup n-grams).
    microbatches=M > 1 serves the zero-bubble 1F1B schedule (BASELINE
    config 5) through the engine: fleets decode M microbatches chasing
    each other around the pp ring, batched requests pad to a multiple of
    M, and solo requests ride the batched path.
    engine_cfg.adapter_slots > 0 installs the paged runtime LoRA leaves
    (engine/adapters.py) and hangs an AdapterPool off engine.adapters:
    requests carrying `adapter` select a page inside the one compiled
    mixed program, with `--lora` merge-at-load staying the
    single-adapter fast path (the same adapter cannot be served both
    ways).
    """
    if mesh_cfg.dp > 1:
        # the serving engine decodes batch=1, which cannot shard over dp
        # (nor split into microbatches); batched dp / microbatched decode is
        # a backend-level capability — see create_backend. Rejected before
        # params init — the expensive step — so a bad mesh fails instantly.
        raise NotImplementedError(
            "dp>1 is not available through the batch-1 serving engine; "
            "use create_backend() for dp-sharded / microbatched batched decode"
        )
    cfg, backend = create_backend(
        model, mesh_cfg=mesh_cfg, microbatches=microbatches, params=params,
        dtype=dtype, quant=quant, kv_quant=kv_quant, attn_impl=attn_impl,
        seed=seed, sp_strategy=sp_strategy, lora=lora,
        wire_quant=engine_cfg.pp_wire_quant,
        adapter_slots=engine_cfg.adapter_slots,
        adapter_rank=engine_cfg.adapter_rank,
    )
    engine = InferenceEngine(
        cfg, backend=backend, tokenizer=tokenizer, engine_cfg=engine_cfg, seed=seed
    )
    if engine_cfg.adapter_slots:
        from .engine.adapters import AdapterPool

        # merged_source records the --lora merge-at-load path so a later
        # register() of the SAME adapter (which would apply its delta on
        # top of the already-merged weights) fails loudly
        engine.adapters = AdapterPool(
            cfg, backend, engine_cfg.adapter_slots, engine_cfg.adapter_rank,
            registry=engine.metrics, merged_source=lora,
        )
    if draft_model is not None:
        dcfg = (
            get_model_config(draft_model)
            if isinstance(draft_model, str) else draft_model
        )
        if dtype is not None:
            dcfg = dcfg.replace(dtype=dtype)
        engine.set_draft(dcfg, draft_params, seed=seed + 1)
    return engine
