"""tests/dense_equal.py's dump (a chunked prefill + decode + prefix-hit
repeat of a tiny preset under one attention path) on this tree: the pool
carried through the layer scan against one cut into layer slices (ISSUE 29),
and the dump against itself (ISSUE 28). A dump is 16 launches through two
jitted programs (what a case spent 30-190 s on before ISSUE 47 was neither
arithmetic nor the kernel: run eagerly, every launch traced, lowered and
compiled the layer scan anew with the interpreted kernel inside it). The
tests that read the same (preset, path) dump share it through `carried`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dense_equal


@functools.cache
def carried(preset, impl) -> dict:
    """This tree's dump: the pool carried through the layer scan."""
    return dense_equal.dump((preset,), (impl,))


def _slice_and_restack(hook):
    """The contract before ISSUE 29, through today's hook: cut the layer's
    slice out of the pool, run the hook on that one-layer pool, put the
    slice back. What the layer scan did with the pool as its xs and ys."""

    def cut(leaf, layer):
        return None if leaf is None else jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0), leaf)

    def back(leaf, new, layer):
        return None if leaf is None else jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n[0], layer, 0),
            leaf, new)

    def sliced(cfg, q, k, v, cache_k, cache_v, pos, mask, update_gate,
               valid_start, window_flag, layer):
        attn, nk, nv = hook(cfg, q, k, v, cut(cache_k, layer),
                            cut(cache_v, layer), pos, mask, update_gate,
                            valid_start, window_flag, jnp.int32(0))
        return attn, back(cache_k, nk, layer), back(cache_v, nv, layer)

    sliced.paged, sliced.live = True, hook.live
    return sliced


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("preset", sorted(dense_equal.PRESETS))
def test_carried_pool_equals_slice_and_restack(preset, impl):
    """The pool carried through the layer scan and indexed by the layer
    holds, and yields, what a pool cut into layer slices and stacked again
    does. The same greedy tokens; logits and pool leaves to the last bits
    (the CPU backend fuses the two graphs differently, dense_equal.py's
    docstring: run from the command line, against a checkout of the parent
    and unfused, the two dumps are the same bits)."""
    got_all = carried(preset, impl)
    sliced = dense_equal.dump((preset,), (impl,), wrap=_slice_and_restack)
    assert sorted(got_all) == sorted(sliced) and len(got_all) >= 4
    for key, got in got_all.items():
        if key.endswith(".tokens"):
            np.testing.assert_array_equal(got, sliced[key])
        else:
            assert got.any()
            np.testing.assert_allclose(got, sliced[key], rtol=0, atol=1e-5)


@pytest.mark.parametrize("name", ["test-llama-tiny", "test-olmo2-tiny", "mistral-shaped"])
def test_the_dense_dump_repeats_bit_for_bit_and_the_comparison_sees_one_bit(name):
    """tests/dense_equal.py is what holds a checkout's dense path against
    another's (ISSUE 28: tokens, logits and K and V pool of a chunked
    prefill + decode + prefix-hit repeat, bit-equal). Here: two dumps of
    this tree are the same bits, so a difference between two checkouts is
    the programs'; and one flipped bit of one array is reported."""
    a = carried(name, "pallas")
    b = dense_equal.dump((name,), ("pallas",))
    assert sorted(a) == [f"{name}.pallas.{k}" for k in ("logits", "pool_k", "pool_v", "tokens")]
    assert a[f"{name}.pallas.pool_k"].any() and dense_equal.unequal(a, b) == []
    key = f"{name}.pallas.pool_v"
    b[key] = b[key].copy()
    b[key].view(np.uint32).reshape(-1)[5] ^= 1
    assert dense_equal.unequal(a, b) == [key]
