"""chip_smoke.py's fleet, compiled whole for a TPU v5e that is described,
not attached (tests/described_chip.py has the why and the rules): the
published tinyllama-1.1b's prefill, its two step programs at the smoke's
sizes (`dense_equal.programs`, once for the tests of this file) and one
decode step alone; and the tool that holds one checkout's step programs
against another's (tests/dense_equal.py).
"""

import functools
import re

import jax
import jax.numpy as jnp

import dense_equal
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine import paged as EP
from distributed_llm_inference_tpu.models import api as M
from described_chip import (  # noqa: F401 - fixtures
    DENSE_SCOPES, STEP_MODULES, assert_scopes, custom_call_names, module_name, no_persistent_cache, one_chip, placed,
    spec, topo,
)

# TinyLlama-1.1B: 4 kv heads, head_dim 64
KV = 4
POOL_BLOCKS = 3072  # chip_smoke.py's pool: >= 1 GiB of bf16 KV at bs 16
SLOTS = 8


@functools.cache
def _fleet():
    """The whole published tinyllama-1.1b in bf16 with the Pallas attention
    path selected, as chip_smoke.py serves it: the decode chunk at
    `--continuous-chunk`'s default 16 steps and the mixed step as the
    scheduler launches it (128 flat tokens, decode positions derived on
    the device), over 16-token blocks."""
    return dense_equal.programs("tinyllama-1.1b", SLOTS, POOL_BLOCKS, 2048,
                                block_size=16, layers=0)


def test_tinyllama_prefill_step_compiles_with_kernel(
    one_chip, no_persistent_cache, monkeypatch
):
    """One whole prefill (T 128) of the engine's own program: without the
    env steer the compile contains no kernel at all — resolve_interpret
    sees the CPU backend and lowers the interpreter."""
    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    fleet = _fleet()
    cfg, params = fleet.cfg, fleet.params
    S = spec(fleet.chip)  # (two descriptions' devices do not mix)
    place = functools.partial(placed, sharding=fleet.chip)
    cache = place(jax.eval_shape(
        lambda: M.init_kv_cache(cfg, 1, max_seq=cfg.max_seq_len)
    ))
    i32 = S((), jnp.int32)
    compiled = G.prefill.lower(
        cfg, params, S((1, 128), jnp.int32), i32, cache,
        place(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
        place(jax.eval_shape(lambda: G.default_sampling(greedy=True))),
        None, i32, None, None,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# Head dim 64 is not whole 128-lane tiles, so the kernel reads a padded copy
# of a layer's slice of each pool leaf (ops/paged_attention.writes_in_place)
PADDED_SLICE = POOL_BLOCKS * KV * 16 * 128 * 2


def test_tinyllama_paged_decode_chunk_compiles_with_kernel(
    one_chip, no_persistent_cache
):
    """The fleet's decode program as it is served: `decode_slots_paged` at
    `--continuous-chunk`'s default 16 steps over the block pool, whose
    attention is the paged kernel walking the table."""
    fleet = _fleet()
    # engine/scheduler.step_width / live_width: 8 slots' tiles on top of a dense
    # model's 128, which stays the axis the model computes
    assert (fleet.width, fleet.live) == (192, 128)
    compiled = fleet.compiled["decode_slots_paged"]
    text = fleet.texts["decode_slots_paged"]
    assert "tpu_custom_call" in text and "jit_decode_slots_paged" in text
    # Around a chunk loop (the scan before PR 46, `steps_while_active`
    # since: 2.317 GB either way) the compiler carries this head dim's pool
    # in the kernel's padded layout: ONE relayout of both leaves at the
    # chunk's entry and exit, 22 layers x 2 leaves x 50 MB = 2.2 GB, in
    # place of a padded copy of a layer's slice at each of 16 x 22 layer
    # steps. Held here: that copy and one step's temporaries, never a
    # second one (a carry that is not written in place).
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < (2 * fleet.cfg.n_layers + 2) * PADDED_SLICE + 2**28, temps



def test_tinyllama_paged_decode_step_compiles_with_kernel(
    one_chip, no_persistent_cache, monkeypatch
):
    """One whole decode step (T 1 per slot), the body of the chunk's loop,
    compiled as a program of its own: what `decode_slots_paged(num_steps=1)`
    was while a chunk was a scan, which the compiler unrolled at length one.
    A loop whose trip count the device decides stays a loop at a bound of
    one, and its pool carry takes the chunk's relayout (the test above)."""
    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    fleet = _fleet()
    cfg, params, pool = fleet.cfg, fleet.params, fleet.pool
    place = functools.partial(placed, sharding=fleet.chip)
    state, sparams = place(
        jax.eval_shape(lambda: G.init_slots(SLOTS, cfg.vocab_size))
    )
    table = spec(fleet.chip)((SLOTS, 2048 // 16), jnp.int32)
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))

    def one_step(params, state, pool, table, key, sparams):
        logits, pool = EP._forward_step_paged(
            cfg, params, state.token[:, None], pool, table, state.pos,
            active=state.active,
        )
        return G.slot_step(cfg, state, sparams, logits, key), pool

    compiled = jax.jit(one_step, donate_argnums=(2,)).lower(
        params, state, pool, table, key, sparams,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the padded copy is of ONE layer's slice, as it was before the pool
    # became a carry (temporaries 1.41 GB then, with the scan's second pool;
    # 0.10 GB now): never a padded copy of the stacked pool inside a step
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < 2 * PADDED_SLICE + 2**28, temps


def test_step_programs_and_kernels_carry_the_names_a_trace_is_read_by(
    one_chip, no_persistent_cache
):
    fleet = _fleet()
    names = {"decode_slots_paged": "paged_flash_attend",
             "mixed_step_ragged": "ragged_paged_attend"}
    assert set(names) == STEP_MODULES == set(fleet.texts)
    for module, kernel in names.items():
        text = fleet.texts[module]
        assert module in module_name(text), module_name(text)
        calls = custom_call_names(text)
        assert any(kernel in c for c in calls), (module, sorted(calls))
        assert_scopes(text, module, DENSE_SCOPES)
    # (that these are the strings the benchmark's configurations name is
    # held configuration by configuration: tests/cell_program_checks.py)


def test_two_compiles_compare_equal_once_source_positions_are_out(
    one_chip, no_persistent_cache
):
    """tests/dense_equal.py holds one checkout's compiled dense step programs
    against another's (ISSUE 28): `canon` leaves the instructions and the
    Mosaic kernels and takes out what only says where a line of source
    stands, so a moved line is no difference and a changed instruction is."""
    texts = dense_equal.programs("test-llama-tiny", 4, 16, 128, block_size=16).texts
    assert set(texts) == {"decode_slots_paged", "mixed_step_ragged"}
    for name, text in texts.items():
        body, kernels = dense_equal.canon(text)
        assert name in body.split("\n", 1)[0] and len(kernels) == 1
        assert "op_name=" not in body and "paged.py" not in body and "loc(" not in kernels[0]
        moved = re.sub(r"line=(\d+)", lambda m: f"line={int(m.group(1)) + 7}", text)
        assert moved != text and dense_equal.canon(moved) == (body, kernels)
        changed = text.replace(" multiply(", " add(", 1)
        assert changed != text and dense_equal.canon(changed)[0] != body
        # two trees whose metadata alone differs may number an instruction
        # differently (ISSUE 38): the same instructions in the same order
        # compare equal once renumbered by place, a changed one does not
        shifted = re.sub(r"(%[a-z_\-]+)\.(\d+)",
                         lambda m: f"{m.group(1)}.{int(m.group(2)) + 1}", body)
        assert shifted != body
        assert dense_equal.renumbered(shifted) == dense_equal.renumbered(body)
        assert dense_equal.renumbered(dense_equal.canon(changed)[0]) \
            != dense_equal.renumbered(body)
