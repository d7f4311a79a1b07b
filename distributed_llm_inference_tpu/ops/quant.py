"""Weight-only int8 quantization for the decode hot path.

Batch-1 decode is HBM-bandwidth-bound: every step streams every weight
byte from HBM once (its share of the roofline is not measured on the
serving path). Halving the bytes halves the floor — so the matmul weights are stored as
**int8 with per-output-channel symmetric scales** and dequantized on-chip:

    y = (x @ q.astype(x.dtype)) * s        # scale applied to the OUTPUT

The `astype` is a convert feeding a dot, which XLA fuses into the
operand read (the int8 tensor is what crosses HBM). Applying the scale
after the matmul keeps the inner loop integer-clean and needs one
multiply per output element.

QTensor is a registered pytree, so it composes with everything that maps
over params: `lax.scan` over stacked layers slices q [L, in, out] and
s [L, out] together, `device_put`/`NamedSharding` shard both leaves, and
donation just works. Per-output-channel scales ride with their columns
under tensor parallelism (column-sharded weights shard s; row-sharded
weights replicate s).

Embeddings stay unquantized: the embed lookup is a gather (no matmul to
fuse into) and its bytes are negligible per token; norms/biases are tiny.

No reference analogue — the reference serves fp32 torch on CPU
(/root/reference/Worker1.py:64, orchestration.py:41); this is a
beyond-parity TPU-performance feature.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..config import ModelConfig

# stacked matmul weights eligible for quantization, per family; OUTPUT
# channels are the last axis for every one (weights are stored
# [L, in, out] / [in, out]). Biases, norms, and embeddings stay dense.
_QUANT_KEYS = {
    "llama": ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"),
    "gpt2": ("wq", "wk", "wv", "wo", "w_fc", "w_proj"),
}


@jax.tree_util.register_pytree_node_class
class QTensor:
    """int8 weight + per-output-channel scale; shapes q [..., in, out],
    s [..., out]."""

    __slots__ = ("q", "s")

    def __init__(self, q, s):
        self.q = q
        self.s = s

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def size(self):
        return self.q.size + self.s.size

    def tree_flatten(self):
        return (self.q, self.s), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"QTensor(q={self.q.shape}@{self.q.dtype}, s={self.s.shape})"


def quantize_tensor(w: jnp.ndarray) -> QTensor:
    """Symmetric per-output-channel int8 quantization of w [..., in, out]."""
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)  # [..., 1, out]
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QTensor(q, scale[..., 0, :])


@jax.tree_util.register_pytree_node_class
class Q4Tensor:
    """Packed int4 weight + per-(group, output-channel) scale.

    q: int8 [..., G, g//2, out] — two signed 4-bit values per byte along
    the group-row axis (group row i in the LOW nibble, row i + g/2 in the
    HIGH — halves, not interleaved pairs, so unpacking is a concatenate:
    Mosaic compiles a concat along the sublane axis where an interleaving
    reshape is an "unsupported shape cast");
    s: [..., G, out]. Each group of `g` contraction rows shares a scale
    (group-wise quantization: 4-bit needs finer scale granularity than
    int8's whole-column scales to keep reconstruction error useful).
    The group size rides as static pytree aux data so spec trees built
    for sharding keep the same treedef.
    """

    __slots__ = ("q", "s", "g")

    def __init__(self, q, s, g: int):
        self.q = q
        self.s = s
        self.g = int(g)

    @property
    def shape(self):  # logical [..., in, out]
        lead = self.q.shape[:-3]
        G, half, out = self.q.shape[-3:]
        return (*lead, G * self.g, out)

    @property
    def ndim(self):
        return self.q.ndim - 1

    @property
    def size(self):
        return self.q.size + self.s.size

    def tree_flatten(self):
        return (self.q, self.s), self.g

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    def __repr__(self):
        return (f"Q4Tensor(q={self.q.shape}@{self.q.dtype}, "
                f"s={self.s.shape}, g={self.g})")


def _unpack_int4(p: jnp.ndarray) -> jnp.ndarray:
    """int8 [..., n, out] of packed nibble halves -> int8 [..., 2n, out].

    Arithmetic shifts on int8 sign-extend, so the low nibble comes out
    via (p << 4) >> 4. Low nibbles hold rows [0, n), high nibbles rows
    [n, 2n) — a concatenate, never an interleave.
    """
    low = jnp.right_shift(jnp.left_shift(p, 4), 4)
    high = jnp.right_shift(p, 4)
    return jnp.concatenate([low, high], axis=-2)


def quantize_tensor4(w: jnp.ndarray, group: int = 64) -> Q4Tensor:
    """Symmetric group-wise int4 quantization of w [..., in, out]."""
    *lead, d_in, d_out = w.shape
    g = min(group, d_in)
    if d_in % g:
        g = d_in  # fall back to one group rather than reject odd shapes
    if g % 2:
        raise ValueError(f"int4 packing needs an even group size, got {g}")
    G = d_in // g
    w32 = w.astype(jnp.float32).reshape(*lead, G, g, d_out)
    absmax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)  # [..., G, 1, out]
    scale = jnp.maximum(absmax / 7.0, 1e-12)
    q = jnp.clip(jnp.round(w32 / scale), -7, 7).astype(jnp.int8)
    half = g // 2
    packed = jnp.bitwise_or(
        jnp.left_shift(q[..., half:, :], 4),
        jnp.bitwise_and(q[..., :half, :], jnp.int8(15)),
    )
    return Q4Tensor(packed, scale[..., 0, :], g)


def dequantize_tensor4(t: Q4Tensor, dtype=jnp.float32) -> jnp.ndarray:
    q = _unpack_int4(t.q).astype(jnp.float32)  # [..., G, g, out]
    w = q * t.s[..., None, :].astype(jnp.float32)
    lead = w.shape[:-3]
    return w.reshape(*lead, w.shape[-3] * w.shape[-2], w.shape[-1]).astype(dtype)


def dequantize_tensor(t: QTensor, dtype=jnp.float32) -> jnp.ndarray:
    return (t.q.astype(jnp.float32) * t.s[..., None, :].astype(jnp.float32)).astype(dtype)


def _q4_rows_kernel(x_ref, q_ref, s_ref, o_ref):
    """One (out-tile, group-block) step of y = x @ dequant(q4): unpack
    the PACKED block in VMEM (the whole point — only int4 bytes ever
    cross HBM), two plain 2-D dots per group (nibble halves — the
    packing is halves, not interleaved, precisely so no reshape is
    needed here), scale, accumulate into the out tile across the
    group-reduction grid dim. Plain dots only: a G-batched dot_general
    compiles pathologically in Mosaic (>7 min, never finished). Shapes:
    x [GB, R, g] f32 block, q [GB, g/2, ob] int8, s [GB, ob] f32,
    o [R, ob] f32 (revisited across the reduction)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    GB, half, ob = q_ref.shape
    acc = jnp.zeros_like(o_ref)
    for i in range(GB):  # static unroll over the small group block
        p = q_ref[i].astype(jnp.int32)
        low = jnp.right_shift(jnp.left_shift(p, 28), 28)   # rows [0, g/2)
        high = jnp.right_shift(jnp.left_shift(p, 24), 28)  # rows [g/2, g)
        x = x_ref[i].astype(jnp.float32)  # [R, g]
        part = jnp.dot(
            x[:, :half], low.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) + jnp.dot(
            x[:, half:], high.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc = acc + part * s_ref[i][None, :]
    o_ref[...] += acc


# groups per grid step: amortizes grid/DMA overhead over 8·g·ob packed
# bytes while keeping the kernel's static unroll small
_Q4_GROUP_BLOCK = 8


def q4_matmul_rows(x2d: jnp.ndarray, w: Q4Tensor, interpret: bool = None):
    """Pallas path for y = x2d @ dequant(w), x2d [R, in].

    The XLA einsum formulation of the same algebra materializes the
    unpacked int8 tensor in HBM, so the decode hot path unpacks in VMEM
    instead. The R=1 matvec shapes leave little work per kernel launch,
    so int4 is the CAPACITY lever (half int8's weight HBM: 13B-class
    fits a single v5e); neither quantized path is measured on the
    serving path (no benchmark cell quantizes). Caller guarantees the
    tiling gates."""
    from jax.experimental import pallas as pl

    from .flash_attention import resolve_interpret

    interpret = resolve_interpret(interpret)
    R, d_in = x2d.shape
    G, half, d_out = w.q.shape
    g = 2 * half
    gb = _Q4_GROUP_BLOCK if G % _Q4_GROUP_BLOCK == 0 else 1
    # [R, in] -> [G, R, g] in XLA-land (tiny tensor; Mosaic rejects the
    # lane-splitting reshape in-kernel)
    xg = jnp.swapaxes(x2d.reshape(R, G, g), 0, 1).astype(jnp.float32)
    ob = next(b for b in (512, 256, 128) if d_out % b == 0)
    out = pl.pallas_call(
        _q4_rows_kernel,
        grid=(d_out // ob, G // gb),
        in_specs=[
            pl.BlockSpec((gb, R, g), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((gb, half, ob), lambda j, i: (i, 0, j)),
            pl.BlockSpec((gb, ob), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((R, ob), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((R, d_out), jnp.float32),
        interpret=interpret,
    )(xg, w.q, w.s.astype(jnp.float32))
    return out


def _q4_kernel_ok(R: int, w: Q4Tensor) -> bool:
    """Gates for the Pallas path: few rows (decode/verify/slots — prefill
    keeps the XLA formulation, it amortizes dequant over T), int8-tile-
    friendly packed block (half % 32, out % 128), single stacked slice."""
    if w.q.ndim != 3 or R > 32:
        return False
    _, half, d_out = w.q.shape
    return half % 32 == 0 and d_out % 128 == 0


def matmul(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w for a plain array, QTensor, or Q4Tensor (dequant fused into
    the dot; for int4 the per-group partial products are scaled then
    summed — algebraically x @ dequant(w))."""
    if isinstance(w, QTensor):
        return (x @ w.q.astype(x.dtype)) * w.s.astype(x.dtype)
    if isinstance(w, Q4Tensor):
        lead = x.shape[:-1]
        R = 1
        for d in lead:
            R *= d
        if _q4_kernel_ok(R, w):
            y = q4_matmul_rows(x.reshape(R, x.shape[-1]), w)
            return y.reshape(*lead, y.shape[-1]).astype(x.dtype)
        q = _unpack_int4(w.q).astype(x.dtype)  # [G, g, out]
        G, g = q.shape[-3], q.shape[-2]
        xr = x.reshape(*x.shape[:-1], G, g)
        partial = jnp.einsum("...gi,gio->...go", xr, q)
        return (partial * w.s.astype(x.dtype)).sum(axis=-2)
    return x @ w


def expert_einsum(spec: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """einsum over an expert bank for a dense array or an int8 QTensor.

    Works for any spec whose OUTPUT keeps the scale axes — the per-
    (expert, out-channel) scale s [..., E, out] multiplies the result
    elementwise, which commutes with the contraction:
      'btd,edf->btef' (gate/up: out [b,t,e,f] * s[e,f])
      'btef,efd->bted' (down:   out [b,t,e,d] * s[e,d])
    """
    if isinstance(w, QTensor):
        return jnp.einsum(spec, x, w.q.astype(x.dtype)) * w.s.astype(x.dtype)
    return jnp.einsum(spec, x, w)


def quantize_params(cfg: ModelConfig, params: dict, mode: str = None,
                    group: int = 64) -> dict:
    """Quantize the matmul weights of a params pytree (both families —
    gpt2's projections go through the same quant-aware `mm`).

    mode: "int8" (per-output-channel scales) or "int4" (packed nibbles,
    group-wise scales — half the HBM bytes of int8 again); defaults to
    cfg.quant, then "int8". Quantizes the stacked per-layer projections
    and (when untied) the LM head; leaves embed / norms / biases
    untouched. Idempotent on already-quantized leaves.
    """
    if cfg.arch not in _QUANT_KEYS:
        raise NotImplementedError(
            f"weight-only quantization is wired for "
            f"{sorted(_QUANT_KEYS)}; got arch={cfg.arch!r}"
        )
    mode = mode or cfg.quant or "int8"
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown quantization mode {mode!r}")
    if mode == "int8":
        qfn = quantize_tensor
    else:
        # int4 row-sharding (tp) shards the GROUP axis, so a tp mesh
        # needs n_groups % tp == 0 — `group` tunes that (and fidelity)
        qfn = functools.partial(quantize_tensor4, group=group)
    out = dict(params)
    layers = dict(params["layers"])
    for k in _QUANT_KEYS[cfg.arch]:
        if k not in layers or isinstance(layers[k], (QTensor, Q4Tensor)):
            continue
        if layers[k].ndim == 3:
            layers[k] = qfn(layers[k])
        elif layers[k].ndim == 4 and mode == "int8":
            # MoE expert bank [L, E, in, out]: per-(expert, out-channel)
            # int8 scales ride the moe_ffn einsums (ops/quant.expert_einsum
            # — the elementwise scale commutes with the contraction).
            # int4 experts stay dense: the grouped-contraction layout has
            # no einsum seam yet.
            layers[k] = quantize_tensor(layers[k])
    out["layers"] = layers
    if "lm_head" in params and not isinstance(
        params["lm_head"], (QTensor, Q4Tensor)
    ):
        out["lm_head"] = qfn(params["lm_head"])
    return out
