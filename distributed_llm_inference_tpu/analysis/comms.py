"""Static model of the package's collective graph (the comms contract).

The paper's subject is inter-device activation hand-off, and the next
levers on the ROADMAP (fp8 wire everywhere, multi-host MPMD pipeline)
both need to know exactly which arrays cross which mesh axes at what
dtype and size. This module makes that knowledge machine-checked, the
way callgraph.py did traced reachability:

  * `WIRE_LINKS` — the ONE symbolic bytes-per-launch model of every
    accounted wire link. The backends route `dli_pp_wire_bytes_total`
    accounting through `link_bytes` (parallel/pipeline.py
    `_account_link`), so the counters and the `--comms` CLI report
    derive from the same table; a
    hand-maintained per-call-seam copy cannot drift because it no
    longer exists.
  * `wire_link_bytes` — the canonical per-hop formula
    (ops/wire_quant.wire_bytes delegates here).
  * `collect_sites` — an AST walk over every `lax.{ppermute, psum,
    all_gather, all_to_all, psum_scatter, pmax, pmin}` call site plus
    the `wire_ppermute`/`masked_psum` wrappers, with resolved axis
    names and an operand-role taxonomy. The four comms-* rules
    (analysis/rules/comms_*.py) and the report are consumers.
  * `FAT_INVENTORY` — the standing machine-tracked list of collectives
    whose symbolic bytes exceed `FAT_THRESHOLD` with no quantized path
    (the ROADMAP "quantized logits all_gather" worklist as data, not
    prose). comms-fat-collective enforces both directions: a raw wide
    collective must be inventoried or suppressed, and a stale entry
    whose site disappeared is itself a violation.
  * `HLO_PREDICTED` — the per-topology set of StableHLO collective op
    kinds the model predicts; analysis/hlo.py cross-validates lowered
    programs against it (every derived edge appears, nothing
    unpredicted appears).

Import discipline: this module is jax-free (stdlib ast/dataclasses/math
only) so the CLI lint half stays cheap and ops/wire_quant can delegate
its formula here without a cycle. It deliberately does NOT import
config.py (which pulls in jax.numpy): configs are duck-typed through
`params_from_config`.

Role taxonomy (ARCHITECTURE.md "Comms contract"):
  wrapper-internal  raw lax call inside ops/wire_quant itself — the one
                    sanctioned home of raw transfer collectives
  transfer          a wire_ppermute/masked_psum wrapper call (covered)
  axis-size         `psum(1, axis)` — bookkeeping, constant-folded,
                    produces no HLO collective
  weight-reduce     tp/ep partial-sum psums in models/ — classified,
                    not flagged (weights stay resident; not a transfer)
  merge             pmax/pmin control/merge reductions (scalar-class)
  raw               anything else — a lint error on a parallel/
                    transfer path unless suppressed with a reason
"""

from __future__ import annotations

import ast
import math
from dataclasses import dataclass, field
from typing import Callable, Optional

from .callgraph import (
    PackageIndex, build_index, dotted, traced_reachable, _walk_own_body,
)

__all__ = [
    "wire_link_bytes", "LinkSpec", "WIRE_LINKS", "params_from_config",
    "link_bytes", "CollectiveSite", "collect_sites", "declared_axes",
    "FatEntry", "FAT_INVENTORY", "FAT_THRESHOLD", "REFERENCE_PARAMS",
    "HLO_PREDICTED", "STABLEHLO_COLLECTIVES", "predicted_hlo_ops",
    "link_call_sites", "build_report",
]


# -- canonical wire-bytes formula --------------------------------------------

def wire_link_bytes(shape, itemsize: int, hops: int, *, quant: bool) -> int:
    """Bytes one activation of `shape` costs crossing `hops` hand-offs.

    Quantized, a [..., D] tensor ships D int8 + one fp32 scale per
    leading row (the WireQuant pytree: si8 data + f32 scales). This is
    the ONE implementation — ops/wire_quant.wire_bytes delegates here,
    the link table below evaluates through it, and the HLO wire-dtype
    rules prove the lowered programs really ship what it counts."""
    n = math.prod(shape)
    rows = n // shape[-1]
    per_hop = n + 4 * rows if quant else n * itemsize
    return per_hop * hops


# -- the wire-link table ------------------------------------------------------

@dataclass(frozen=True)
class LinkSpec:
    """One accounted wire link: a family of identical hops whose bytes
    are a closed-form function of ModelConfig dims + launch params."""

    name: str          # link id, the `_account_link` key
    path: str          # dli_pp_wire_bytes_total `path` label it feeds
    axis: str          # mesh axis the bytes cross
    transport: str     # wrapper that ships it (wire_ppermute/masked_psum)
    symbolic: str      # human-readable shape x hops formula
    shape: Callable    # params dict -> activation shape tuple
    hops: Callable     # params dict -> hop count


def _links(*specs):
    return {s.name: s for s in specs}


# Launch params (beyond the cfg dims): rows (batch rows), t (tokens per
# row in the shipped window), steps (sample events), draft (speculative
# draft length), bh (broadcast hops), b_m (per-microbatch rows), t_chunk
# (sp sequence chunk), plus topology dp/pp/sp/mb.
WIRE_LINKS = _links(
    LinkSpec(
        "pp-microstep-decode", "microstep", "pp", "wire_ppermute",
        "(max(1, rows/dp), 1, dim) x steps*pp hops",
        lambda p: (max(1, p["rows"] // p["dp"]), 1, p["dim"]),
        lambda p: p["steps"] * p["pp"],
    ),
    LinkSpec(
        "pp-broadcast-decode", "broadcast", "pp", "masked_psum",
        "(max(1, rows/dp), 1, dim) x steps hops",
        lambda p: (max(1, p["rows"] // p["dp"]), 1, p["dim"]),
        lambda p: p["steps"],
    ),
    LinkSpec(
        "pp-microstep-prefill", "microstep", "pp", "wire_ppermute",
        "(rows, t, dim) x pp hops",
        lambda p: (p["rows"], p["t"], p["dim"]),
        lambda p: p["pp"],
    ),
    LinkSpec(
        "pp-broadcast-prefill", "broadcast", "pp", "masked_psum",
        "(rows, 1, dim) x bh hops",
        lambda p: (p["rows"], 1, p["dim"]),
        lambda p: p.get("bh", 1),
    ),
    LinkSpec(
        "pp-microstep-slots", "microstep", "pp", "wire_ppermute",
        "(rows, 1, dim) x steps*pp hops",
        lambda p: (p["rows"], 1, p["dim"]),
        lambda p: p["steps"] * p["pp"],
    ),
    LinkSpec(
        "pp-broadcast-slots", "broadcast", "pp", "masked_psum",
        "(rows, 1, dim) x steps hops",
        lambda p: (p["rows"], 1, p["dim"]),
        lambda p: p["steps"],
    ),
    LinkSpec(
        "pp-broadcast-score", "broadcast", "pp", "masked_psum",
        "(rows, t, dim) x 1 hop",
        lambda p: (p["rows"], p["t"], p["dim"]),
        lambda p: 1,
    ),
    LinkSpec(
        "pp-microstep-spec", "microstep", "pp", "wire_ppermute",
        "(rows, 1+draft, dim) x steps*pp hops",
        lambda p: (p["rows"], 1 + p["draft"], p["dim"]),
        lambda p: p["steps"] * p["pp"],
    ),
    LinkSpec(
        "pp-broadcast-spec", "broadcast", "pp", "masked_psum",
        "(rows, 1+draft, dim) x steps hops",
        lambda p: (p["rows"], 1 + p["draft"], p["dim"]),
        lambda p: p["steps"],
    ),
    LinkSpec(
        "fleet-1f1b-decode", "1f1b", "pp", "wire_ppermute",
        "(b_m, 1, dim) x (pp-1 + steps*mb) hops",
        lambda p: (p["b_m"], 1, p["dim"]),
        lambda p: p["pp"] - 1 + p["steps"] * p["mb"],
    ),
    LinkSpec(
        "fleet-broadcast-decode", "broadcast", "pp", "masked_psum",
        "(b_m, 1, dim) x steps*mb hops",
        lambda p: (p["b_m"], 1, p["dim"]),
        lambda p: p["steps"] * p["mb"],
    ),
    LinkSpec(
        "fleet-1f1b-prefill", "1f1b", "pp", "wire_ppermute",
        "(b_m, t, dim) x (mb + pp - 1) hops",
        lambda p: (p["b_m"], p["t"], p["dim"]),
        lambda p: p["mb"] + p["pp"] - 1,
    ),
    LinkSpec(
        "fleet-broadcast-prefill", "broadcast", "pp", "masked_psum",
        "(b_m, 1, dim) x mb hops",
        lambda p: (p["b_m"], 1, p["dim"]),
        lambda p: p["mb"],
    ),
    LinkSpec(
        "sp-kv-ring", "sp", "sp", "ppermute (operands pre-quantized)",
        "(rows, t_chunk, n_kv_heads, head_dim) x 2*n_layers*(sp-1) hops",
        lambda p: (p["rows"], p["t_chunk"], p["n_kv_heads"], p["head_dim"]),
        lambda p: 2 * p["n_layers"] * (p["sp"] - 1),
    ),
    LinkSpec(
        "sp-broadcast-prefill", "broadcast", "sp", "masked_psum",
        "(rows, 1, dim) x 1 hop",
        lambda p: (p["rows"], 1, p["dim"]),
        lambda p: 1,
    ),
    # The KV fabric's replica-to-replica chain transfer (GET/POST /kv,
    # serving/kv_fabric.py) — DCN, not ICI: it rides plain HTTP between
    # hosts, so its bytes never appear in any HLO collective. The shape
    # is one full chain of kv_blocks cache blocks: K and V planes
    # (2*n_layers) x block tokens x GQA kv heads x head dim. One "hop"
    # = one verified chain moved (pull or push); runtime bytes land on
    # dli_kv_fabric_bytes_total{tier=...} via the same _account_link
    # seam the ICI links use.
    LinkSpec(
        "kv-fabric-dcn", "kv", "dcn", "HTTP /kv (npz chain, streamed)",
        "(kv_blocks, 2*n_layers, kv_block, n_kv_heads, head_dim) x 1 hop",
        lambda p: (p["kv_blocks"], 2 * p["n_layers"], p["kv_block"],
                   p["n_kv_heads"], p["head_dim"]),
        lambda p: 1,
    ),
    # The MPMD stage transport's inter-PROCESS activation hand-off
    # (POST /stage/step, serving/stage_runtime.py) — like kv-fabric-dcn
    # this is DCN/HTTP, invisible to HLO. One hop = one stage boundary
    # crossed by one step's hidden states [rows, t, dim]; with
    # pp_wire_quant="int8" the body ships int8 rows + fp32 scales, so
    # the same wire_link_bytes quant formula applies to the cross-
    # process wire. Runtime bytes land on
    # dli_pp_wire_bytes_total{path="stage"}.
    LinkSpec(
        "stage-activation-dcn", "stage", "dcn",
        "HTTP /stage/step (npz hidden, int8-quantizable)",
        "(rows, t, dim) x 1 hop",
        lambda p: (p["rows"], p["t"], p["dim"]),
        lambda p: 1,
    ),
    # The last stage's reply when it closes the ring: sampled token ids
    # [rows] int32 back to the controller (never quantized — ids, not
    # activations; accounted at fp32 itemsize as 1 id per row).
    LinkSpec(
        "stage-result-dcn", "stage", "dcn",
        "HTTP /stage/step reply (sampled ids)",
        "(rows, 1, 1) x 1 hop",
        lambda p: (p["rows"], 1, 1),
        lambda p: 1,
    ),
)

# ModelConfig attrs the link formulas and fat inventory may read.
_CFG_DIMS = ("dim", "n_layers", "n_heads", "n_kv_heads", "head_dim",
             "vocab_size")


def params_from_config(cfg, **launch) -> dict:
    """Flatten a (duck-typed) ModelConfig + launch params into the flat
    dict the link formulas evaluate over. Keeps this module jax-free:
    cfg is only read through getattr, never imported."""
    p = {k: int(getattr(cfg, k)) for k in _CFG_DIMS}
    p.update(launch)
    return p


def link_bytes(name: str, params: dict, *, itemsize: int,
               quant: bool) -> int:
    """Derived wire bytes for one launch of link `name`."""
    spec = WIRE_LINKS[name]
    return wire_link_bytes(
        spec.shape(params), itemsize, spec.hops(params), quant=quant
    )


# -- static collective-site scan ----------------------------------------------

# the transfer-class lax primitives the wire-coverage contract covers
TRANSFER_PRIMS = frozenset(
    {"ppermute", "psum", "all_gather", "all_to_all", "psum_scatter"}
)
# recorded for graph completeness; exempt from wire coverage (scalar /
# control-class reductions)
_EXTRA_PRIMS = frozenset({"pmax", "pmin"})
_LAX_PRIMS = TRANSFER_PRIMS | _EXTRA_PRIMS
WRAPPERS = frozenset({"wire_ppermute", "masked_psum"})
# positional index of the axis-name argument per callable
_AXIS_ARGPOS = dict(
    {p: 1 for p in _LAX_PRIMS}, wire_ppermute=1, masked_psum=2,
)


@dataclass(frozen=True)
class CollectiveSite:
    """One collective call site in the package source."""

    module: str        # dotted module ("parallel.ring")
    path: str          # package-relative file path
    line: int
    primitive: str     # lax primitive or wrapper name
    func: str          # enclosing function qualname
    axes: tuple        # resolved axis-name strings (unresolved dropped)
    axis_sources: tuple  # provenance per axis expr (incl. unresolved)
    role: str          # taxonomy in the module docstring
    traced: bool       # enclosing function is traced-reachable
    call: ast.Call = field(compare=False, repr=False, hash=False)


def _module_str_consts(mod) -> dict:
    """Module-level `NAME = "str"` bindings, tuple-unpack included
    (parallel/mesh.py declares all five axes in one statement)."""
    out = {}
    for node in mod.tree.body:
        if not isinstance(node, ast.Assign):
            continue
        for target in node.targets:
            if isinstance(target, ast.Name):
                if isinstance(node.value, ast.Constant) and isinstance(
                    node.value.value, str
                ):
                    out[target.id] = node.value.value
            elif isinstance(target, ast.Tuple) and isinstance(
                node.value, ast.Tuple
            ) and len(target.elts) == len(node.value.elts):
                for t, v in zip(target.elts, node.value.elts):
                    if isinstance(t, ast.Name) and isinstance(
                        v, ast.Constant
                    ) and isinstance(v.value, str):
                        out[t.id] = v.value
    return out


def declared_axes(index: PackageIndex) -> frozenset:
    """Axis names the package declares: the values of every module-level
    `AXIS_* = "..."` binding (parallel/mesh.py is the real declaration
    site; fixtures declare their own)."""
    axes = set()
    for mod in index.modules.values():
        for name, value in _module_str_consts(mod).items():
            if name.startswith("AXIS_"):
                axes.add(value)
    return frozenset(axes)


def _resolve_axis_name(name: str, mod, index: PackageIndex):
    """A Name used as an axis argument -> its string value, or None."""
    consts = _module_str_consts(mod)
    if name in consts:
        return consts[name]
    imp = mod.imports.get(name)
    if imp and imp[0] == "obj":
        src = index.modules.get(imp[1])
        if src is not None:
            return _module_str_consts(src).get(imp[2])
    return None


def _resolve_axes(expr, mod, index: PackageIndex):
    """Axis expression -> (resolved names, per-element provenance).

    Handles string literals, tuples of axes (context.py broadcasts over
    (AXIS_SP, AXIS_PP)), and names resolving to module-level string
    constants here or in the imported module. Function parameters and
    attribute chains are honestly unresolved — reported, never flagged."""
    elts = expr.elts if isinstance(expr, (ast.Tuple, ast.List)) else [expr]
    axes, sources = [], []
    for e in elts:
        if isinstance(e, ast.Constant) and isinstance(e.value, str):
            axes.append(e.value)
            sources.append(f"literal:{e.value}")
        elif isinstance(e, ast.Name):
            val = _resolve_axis_name(e.id, mod, index)
            if val is not None:
                axes.append(val)
                sources.append(f"name:{e.id}={val}")
            else:
                sources.append(f"param:{e.id}")
        else:
            d = dotted(e)
            sources.append(f"expr:{d or type(e).__name__}")
    return tuple(axes), tuple(sources)


def _primitive_of(call: ast.Call) -> Optional[str]:
    """`jax.lax.ppermute(...)` / `lax.psum(...)` -> primitive name;
    `wire_ppermute(...)` / `wq.masked_psum(...)` -> wrapper name."""
    d = dotted(call.func)
    if d is None:
        return None
    parts = d.split(".")
    leaf = parts[-1]
    if leaf in _LAX_PRIMS and len(parts) >= 2 and parts[-2] == "lax":
        return leaf
    if leaf in WRAPPERS:
        return leaf
    return None


def _axis_expr(call: ast.Call, primitive: str):
    for kw in call.keywords:
        if kw.arg == "axis_name":
            return kw.value
    pos = _AXIS_ARGPOS[primitive]
    if len(call.args) > pos:
        return call.args[pos]
    return None


def _is_wrapper_module(module: str) -> bool:
    return module == "ops.wire_quant" or module.endswith(".wire_quant") \
        or module == "wire_quant"


def in_parallel(module: str) -> bool:
    """True for modules under a parallel/ package — the transfer plane
    the wire-coverage contract governs."""
    return "parallel" in module.split(".")


def _role_of(module: str, primitive: str, call: ast.Call) -> str:
    if primitive in WRAPPERS:
        return "transfer"
    if _is_wrapper_module(module):
        return "wrapper-internal"
    if primitive in _EXTRA_PRIMS:
        return "merge"
    if primitive == "psum" and call.args and isinstance(
        call.args[0], ast.Constant
    ) and call.args[0].value == 1:
        # `sp = lax.psum(1, axis)` — the axis-size idiom; constant-folded,
        # no wire bytes, no HLO collective
        return "axis-size"
    if primitive == "psum" and module.split(".")[0] == "models":
        return "weight-reduce"
    return "raw"


def collect_sites(index: PackageIndex,
                  traced: Optional[set] = None) -> list:
    """Every collective call site in the package, with resolved axes,
    role, and traced-reachability (resolved through the same callgraph
    the host/decode rules use)."""
    if traced is None:
        traced = traced_reachable(index)
    sites = []
    for mod in index.modules.values():
        for fn in mod.functions.values():
            for node in _walk_own_body(fn):
                if not isinstance(node, ast.Call):
                    continue
                prim = _primitive_of(node)
                if prim is None:
                    continue
                expr = _axis_expr(node, prim)
                if expr is None:
                    axes, sources = (), ("missing",)
                else:
                    axes, sources = _resolve_axes(expr, mod, index)
                sites.append(CollectiveSite(
                    module=mod.name,
                    path=mod.path,
                    line=node.lineno,
                    primitive=prim,
                    func=fn.qualname,
                    axes=axes,
                    axis_sources=sources,
                    role=_role_of(mod.name, prim, node),
                    traced=fn.key in traced,
                    call=node,
                ))
    return sites


# -- fat-collective inventory -------------------------------------------------

# Reference dims for symbolic-bytes evaluation in the report: a
# llama-8B-class serving shape (dim 4096, 32 layers, GQA 8 kv heads,
# 128k vocab) on a dp=1, pp=8, sp=8 mesh, an 8-row fleet decoding one
# token over a 4096-token context. Chosen for the report's headline
# numbers only — unit tests evaluate the same formulas at the
# test-llama-tiny dims they can check by hand.
REFERENCE_PARAMS = dict(
    dim=4096, n_layers=32, n_heads=32, n_kv_heads=8, head_dim=128,
    vocab_size=128256,
    dp=1, pp=8, sp=8, mb=8,
    rows=8, t=4096, t_chunk=512, steps=1, draft=4, bh=1, b_m=1,
    # KV-fabric chain transfer: a 4096-token prefix at kv_block=32
    # tokens per cache block = 256 blocks shipped per handoff
    kv_blocks=256, kv_block=32,
)

# A collective is "fat" when its symbolic bytes at the reference dims
# exceed this and no quantized path exists. 1 MiB: an order of magnitude
# above the largest quantized activation hop, an order below the logits
# gathers it exists to track.
FAT_THRESHOLD = 1 << 20


@dataclass(frozen=True)
class FatEntry:
    """One standing fat collective: a machine-tracked worklist item for
    the ROADMAP low-precision-everywhere lever."""

    module: str      # dotted module suffix ("parallel.vocab")
    func: str        # enclosing-qualname substring ("unembed_sharded")
    primitive: str
    axis: str
    dtype: str
    symbolic: str    # closed-form bytes/invocation
    bytes_fn: Callable  # params dict -> bytes/invocation
    note: str
    operand: str = ""  # operand Name at the call site, "" = any — keeps
    #                    an entry from claiming a sibling control gather


def _vocab_pad(p):
    return -(-p["vocab_size"] // p["pp"]) * p["pp"]


FAT_INVENTORY = (
    FatEntry(
        module="parallel.vocab",
        func="unembed_sharded",
        primitive="all_gather",
        axis="pp",
        dtype="float32",
        symbolic="4 * rows * t * (V_pad/pp) * (pp-1)  [V_pad = "
                 "pp*ceil(V/pp)]",
        bytes_fn=lambda p: 4 * p["rows"] * p["t"]
        * (_vocab_pad(p) // p["pp"]) * (p["pp"] - 1),
        note="the vocab-shard logits gather — the one remaining fat "
             "collective (ROADMAP: quantized logits all_gather; needs "
             "an error-tolerant top-k story before int8/fp8 ships)",
        operand="lg",
    ),
    FatEntry(
        module="parallel.context",
        func="_build_score",
        primitive="all_gather",
        axis="sp",
        dtype="float32",
        symbolic="4 * rows * (t/sp) * V * (sp-1)",
        bytes_fn=lambda p: 4 * p["rows"] * p["t_chunk"]
        * p["vocab_size"] * (p["sp"] - 1),
        note="sp scoring gathers every chunk's full-vocab logits to "
             "reassemble [B, T, V] — same quantization story as the "
             "vocab gather, lower duty cycle (score calls only)",
        operand="logits_local",
    ),
)


def fat_entry_for(site: CollectiveSite) -> Optional[FatEntry]:
    """The inventory entry covering `site`, if any."""
    for entry in FAT_INVENTORY:
        if (site.module == entry.module
                or site.module.endswith("." + entry.module)) \
                and entry.func in site.func \
                and site.primitive == entry.primitive:
            if entry.operand:
                arg = site.call.args[0] if site.call.args else None
                if not (isinstance(arg, ast.Name)
                        and arg.id == entry.operand):
                    continue
            return entry
    return None


# -- HLO twin predictions -----------------------------------------------------

# every StableHLO collective kind the scanner in analysis/hlo.py greps
# for when cross-validating a lowered program against the model
STABLEHLO_COLLECTIVES = frozenset({
    "collective_permute", "all_reduce", "all_gather", "all_to_all",
    "reduce_scatter", "collective_broadcast",
})

# Derived per-topology edge sets: the StableHLO collective kinds the
# static graph predicts for each lowered program family. pp decode =
# the wire_ppermute ring (collective_permute), the embed-shard merge +
# masked-psum broadcast (all_reduce), and the vocab logits gather
# (all_gather — the FAT_INVENTORY edge). The sp ulysses attention body
# is all_to_all head<->sequence exchanges only (its `psum(1, axis)`
# axis-size probe constant-folds away).
HLO_PREDICTED = {
    "pp-decode": frozenset({"collective_permute", "all_reduce",
                            "all_gather"}),
    "sp-attend": frozenset({"all_to_all"}),
}


def predicted_hlo_ops(topology: str) -> frozenset:
    return HLO_PREDICTED[topology]


# -- report -------------------------------------------------------------------

def link_call_sites(index: PackageIndex) -> dict:
    """{link name: [(path, line), ...]} — every `self._account_link(
    "<name>", ...)` call site in the package. The provenance half of the
    --comms report, and the proof that each table row is actually wired
    to the runtime accounting."""
    out: dict = {name: [] for name in WIRE_LINKS}
    unknown: list = []
    for mod in index.modules.values():
        for fn in mod.functions.values():
            for node in _walk_own_body(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted(node.func)
                if d is None or d.split(".")[-1] != "_account_link":
                    continue
                if not node.args or not isinstance(
                    node.args[0], ast.Constant
                ):
                    unknown.append(
                        (mod.path, node.lineno, "<non-literal link name>")
                    )
                    continue
                name = node.args[0].value
                if name in out:
                    out[name].append((mod.path, node.lineno))
                else:
                    unknown.append((mod.path, node.lineno, name))
    out["__unknown__"] = unknown
    return out


def build_report(index: Optional[PackageIndex] = None,
                 root: Optional[str] = None) -> dict:
    """The --comms report: per-link symbolic + reference bytes with
    accounting provenance, the collective-site census, and the fat
    inventory. `problems` is non-empty when the table and the package
    disagree (unknown link name at a call site, or a table row no call
    site uses) — the CLI exits nonzero on it."""
    if index is None:
        index = build_index(root)
    sites = collect_sites(index)
    call_sites = link_call_sites(index)
    problems = [
        f"{path}:{line}: _account_link names unknown link {name!r}"
        for path, line, name in call_sites.pop("__unknown__")
    ]
    links = []
    for name, spec in sorted(WIRE_LINKS.items()):
        where = call_sites.get(name, [])
        if not where:
            problems.append(
                f"link {name!r} has no _account_link call site — dead "
                "table row (delete it) or unrouted accounting"
            )
        links.append({
            "name": name,
            "path": spec.path,
            "axis": spec.axis,
            "transport": spec.transport,
            "symbolic": spec.symbolic,
            "reference_shape": list(spec.shape(REFERENCE_PARAMS)),
            "reference_hops": spec.hops(REFERENCE_PARAMS),
            "reference_bytes_raw": wire_link_bytes(
                spec.shape(REFERENCE_PARAMS), 2,
                spec.hops(REFERENCE_PARAMS), quant=False,
            ),
            "reference_bytes_quant": wire_link_bytes(
                spec.shape(REFERENCE_PARAMS), 2,
                spec.hops(REFERENCE_PARAMS), quant=True,
            ),
            "accounted_at": [f"{p}:{ln}" for p, ln in where],
        })
    site_rows = [
        {
            "file": s.path,
            "line": s.line,
            "primitive": s.primitive,
            "func": s.func,
            "axes": list(s.axes),
            "axis_sources": list(s.axis_sources),
            "role": s.role,
            "traced": s.traced,
        }
        for s in sorted(sites, key=lambda s: (s.path, s.line))
    ]
    fat_rows = []
    for entry in FAT_INVENTORY:
        matched = [
            f"{s.path}:{s.line}" for s in sites
            if fat_entry_for(s) is entry
        ]
        fat_rows.append({
            "module": entry.module,
            "func": entry.func,
            "primitive": entry.primitive,
            "axis": entry.axis,
            "dtype": entry.dtype,
            "symbolic": entry.symbolic,
            "reference_bytes": entry.bytes_fn(REFERENCE_PARAMS),
            "sites": matched,
            "note": entry.note,
        })
    return {
        "reference_params": dict(REFERENCE_PARAMS),
        "links": links,
        "sites": site_rows,
        "fat_inventory": fat_rows,
        "problems": problems,
    }


def format_report(report: dict) -> str:
    """Human rendering of build_report (the non-JSON CLI output)."""
    out = []
    out.append("wire links (bytes/launch at reference dims, itemsize=2):")
    for row in report["links"]:
        out.append(
            f"  {row['name']:<24} axis={row['axis']:<3} "
            f"path={row['path']:<10} raw={row['reference_bytes_raw']:>12,} "
            f"int8={row['reference_bytes_quant']:>12,}  {row['symbolic']}"
        )
        for where in row["accounted_at"]:
            out.append(f"      accounted at {where}")
    out.append("")
    out.append("fat-collective inventory (unquantized, above threshold):")
    for row in report["fat_inventory"]:
        sites = ", ".join(row["sites"]) or "<no matching site!>"
        out.append(
            f"  {row['module']}.{row['func']} {row['primitive']}@"
            f"{row['axis']} [{row['dtype']}] "
            f"ref={row['reference_bytes']:,} B  ({sites})"
        )
        out.append(f"      {row['symbolic']}")
        out.append(f"      {row['note']}")
    out.append("")
    by_role: dict = {}
    for s in report["sites"]:
        by_role.setdefault(s["role"], []).append(s)
    out.append("collective sites by role:")
    for role in sorted(by_role):
        out.append(f"  {role} ({len(by_role[role])}):")
        for s in by_role[role]:
            axes = ",".join(s["axes"]) or ",".join(s["axis_sources"])
            out.append(
                f"    {s['file']}:{s['line']}: {s['primitive']}@{axes} "
                f"in {s['func']}"
            )
    for p in report["problems"]:
        out.append(f"PROBLEM: {p}")
    return "\n".join(out)
