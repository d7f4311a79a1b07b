"""ISSUE 39: the q / k / v products of a scanned layer go through
`models/llama.pin_products` before their head split, so that the chip's
compiler leaves the split behind the dot and the dot reads its layer's
weights in place (tests/cell_program_checks.py holds the compiled programs to
that). Here, on the CPU: it is the same arithmetic. Every kind of leaf and
of layer that passes the helper gives, bit for bit, what the old
`(h @ w).reshape(B, T, H, Dh)` gave, through the jitted layer scan."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import get_model_config
from distributed_llm_inference_tpu.engine.adapters import install_adapter_leaves
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models import llama, mla_moe
from distributed_llm_inference_tpu.ops.quant import QTensor, quantize_params


def _lora(cfg, params):
    """Two adapter pages of rank 4 beside the base page, drawn at random."""
    params = install_adapter_leaves(cfg, params, slots=2, rank=4)
    layers = dict(params["layers"])
    for i, name in enumerate(sorted(n for n in layers if n.startswith("lora_"))):
        leaf = layers[name]
        layers[name] = leaf.at[:, 1:].set(0.05 * jax.random.normal(
            jax.random.PRNGKey(100 + i), leaf[:, 1:].shape, leaf.dtype))
    return {**params, "layers": layers}


# name: (preset, overrides, what is done to the parameters, adapter pages a row)
CASES = {
    "mha": ("test-llama-tiny", {"n_kv_heads": 4}, None, None),
    "gqa": ("test-llama-tiny", {}, None, None),
    "whole-projection-qk-norm": ("test-olmo2-tiny", {}, None, None),
    "per-head-qk-norm-routed": ("test-sdar-tiny", {}, None, None),
    "qtensor-leaf": ("test-llama-tiny", {},
                     lambda cfg, p: quantize_params(cfg, p, "int8"), None),
    "lora-page": ("test-llama-tiny", {}, _lora, [2, 0, 1]),
    "latent-query": ("test-mla-moe-tiny", {}, None, None),
}


def _scan(cfg, params, tokens, pages):
    """The jitted layer scan over a fresh dense cache: (x, every cache leaf)."""
    def run(params, tokens):
        cache = M.init_kv_cache(cfg, tokens.shape[0], 32)
        x = M.embed(cfg, params, tokens)
        kw = {} if pages is None else {"lora_pages": jnp.asarray(pages, jnp.int32)}
        x, cache = M.forward_layers(cfg, params["layers"], x, cache,
                                    jnp.int32(0), **kw)
        return x, cache

    x, cache = jax.jit(run)(params, tokens)
    return [np.asarray(a) for a in jax.tree.leaves((x, cache))]


@pytest.mark.parametrize("case", sorted(CASES))
def test_pinned_products_are_the_old_reshaped_products_bit_for_bit(
    monkeypatch, case
):
    preset, overrides, prepare, pages = CASES[case]
    cfg = get_model_config(preset, dtype="float32", **overrides)
    params = M.init_params(cfg, jax.random.PRNGKey(5))
    if prepare is not None:
        params = prepare(cfg, params)
    if case == "qtensor-leaf":
        assert isinstance(params["layers"]["wq"], QTensor)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(3, 250, (3, 11)), jnp.int32)

    calls = []
    pin = llama.pin_products

    def counted(*products):
        calls.append(len(products))
        return pin(*products)

    for module in (llama, mla_moe):
        monkeypatch.setattr(module, "pin_products", counted)
    new = _scan(cfg, params, tokens, pages)
    # every scanned stack passed the helper: q, k, v together, or the latent
    # family's query alone in each of its two stacks
    assert calls == ([1, 1] if cfg.arch == "mla_moe" else [3]), calls
    # the old form: the product handed straight to its reshape
    for module in (llama, mla_moe):
        monkeypatch.setattr(module, "pin_products", lambda *products: products)
    old = _scan(cfg, params, tokens, pages)
    assert len(new) == len(old) > 1
    for a, b in zip(new, old):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    assert np.isfinite(new[0]).all() and np.abs(new[0]).max() > 0


def test_pin_products_hands_its_arguments_on_unchanged():
    a = jnp.arange(6.0).reshape(2, 3)
    b = jnp.ones((4,), jnp.bfloat16)
    out = jax.jit(llama.pin_products)(a, b)
    assert isinstance(out, tuple) and len(out) == 2
    assert out[0].dtype == a.dtype and out[1].dtype == b.dtype
    assert np.array_equal(out[0], a) and np.array_equal(out[1], b)
    (only,) = llama.pin_products(a)
    assert np.array_equal(only, a)
    # the barrier is in the lowered program (it is what the chip's compiler
    # must see between the product and the head split)
    text = jax.jit(lambda x, w: llama.pin_products(x @ w)[0].reshape(2, 2, 2)
                   ).lower(a, jnp.ones((3, 4))).as_text()
    assert "optimization_barrier" in text
