"""The paged serving path of one checkout, as numbers to hold against
another checkout's bit for bit (ISSUE 28: a PR that touches the shared
scheduler, pool or kernel code shows that the dense models' programs did
not change; ISSUE 29: that a pool carried through the layer scan and
written in place holds what the sliced and re-stacked one held). For three
tiny dense presets, the tiny latent-attention model (both its stacks) and a
dense one with a 128-wide head (the shape at which the Pallas kernels write
the pool themselves, ops/paged_attention.writes_in_place), under both
attention paths: the greedy tokens, the logits and every pool leaf of a
chunked prefill (three ragged launches), twelve paged decode steps, and a
mixed launch in which a second row hits the first row's four full prefix
blocks. No recorded numbers: run it in both checkouts on the CPU and
compare.

Two things are left out of "bit for bit", each for a reason. The pool's
block 0 is not dumped: it is the trash block, write-only by contract
(launch padding lands there under XLA's scatter and nowhere under the
kernels' own write). And the two command-line dumps run with the CPU
backend's fusion pass off (`_under`): fused, that backend contracts a
multiply and an add into one rounding where they land in one loop, which
follows the shape of the surrounding graph, not the program's arithmetic
(RoPE's `k * cos + rot(k) * sin` beside a scatter into [N, ...] or into
[L, N, ...] differs in the last bit of K; ISSUE 29).

    python tests/dense_equal.py <checkout root> <out.npz>      # once a side
    python tests/dense_equal.py --compare <a.npz> <b.npz>      # exit 1 if unequal

tests/test_carried_pool.py runs `dump` twice on this tree and `unequal`
on the two, so that a difference between two checkouts is the programs'
and not the script's.

And the device programs themselves, with no chip attached: `programs`
compiles a model's two step programs (from the command line: cut to 2
layers) for a described v5e at a cell's sizes, the ONE place the tests
lower them (`cell_programs`: a benchmark configuration's, once a process;
tests/cell_program_checks.py holds every configuration's to the rules the
chip needs), and `canon` takes the source positions out
of the optimized HLO (the tables of files and stack frames, each
instruction's metadata, the debug locations inside each Mosaic kernel's
serialized module), so that two checkouts whose texts are then the same
run the same bits on the chip, and a token that differs between two runs
there is the scheduler's timing (which program served it), not arithmetic.

    python tests/dense_equal.py --programs <checkout root> <model> <slots> <blocks> <context> <out dir>
    python tests/dense_equal.py --programs <checkout root> cellbench/configs/<name>.json <layers, 0: all> <out dir>
    python tests/dense_equal.py --compare-programs <dir a> <dir b>   # exit 1 if unequal

The second form (ISSUE 38) takes a benchmark configuration's file: its
registry entry, overrides and sizes, so the routed, block-diffusion and
convolution-hybrid configurations' programs compare as the dense two's do.
"""
import base64
import dataclasses
import functools
import json
import os
import re
import sys
from unittest import mock

import numpy as np

PRESETS = {"test-llama-tiny": ("test-llama-tiny", {}),
           "test-olmo2-tiny": ("test-olmo2-tiny", {}),
           "mistral-shaped": ("test-llama-tiny", {"attn_window": 40}),  # GQA + a window
           "test-mla-moe-tiny": ("test-mla-moe-tiny", {}),  # a latent pool, two stacks
           "wide-head": ("test-llama-tiny", {"head_dim_override": 128})}
IMPLS = ("pallas", "xla")
BS, TILE = 16, 8


def dump(names=tuple(PRESETS), impls=IMPLS, wrap=lambda hook: hook) -> dict:
    """{"<preset>.<impl>.<tokens | logits | pool_<leaf>>": array}. wrap: what
    the ragged and the decode hook pass through before forward_layers gets
    them (tests/test_carried_pool.py holds the carried pool against a hook
    that cuts each layer's slice out and puts it back)."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_inference_tpu import get_model_config
    from distributed_llm_inference_tpu.config import resolve_attn_impl
    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.models import api as M

    out = {}
    for name in names:
        base, kw = PRESETS[name]
        for impl in impls:
            cfg = resolve_attn_impl(
                get_model_config(base, dtype="float32", eos_token_id=-1, **kw), impl)
            params = M.init_params(cfg, jax.random.PRNGKey(3))
            rng = np.random.default_rng(1)
            ids = rng.integers(3, 250, 70).astype(np.int32)
            tail = rng.integers(3, 250, 9).astype(np.int32)
            pool = P.init_pool(cfg, 24, BS)
            table = np.zeros((2, 8), np.int32)
            table[0, :6] = [3, 7, 2, 9, 5, 13]

            # one program a kind of launch, as the fleet has: compiled once
            # for the 4 ragged launches and once for the 12 decode steps (run
            # eagerly, every launch traced, lowered and compiled the layer
            # scan anew with the interpreted kernel inside it: its hook is a
            # fresh closure)
            @jax.jit
            def ragged(pool, table, flat, tok_pos, meta, tok_row):
                x = M.embed(cfg, params, flat[:, None], tok_pos)
                hook = wrap(P.make_ragged_fill_hook(table, meta, tok_row))
                x, pool = M.forward_layers(cfg, params["layers"], x, pool,
                                           tok_pos, attn_hook=hook,
                                           attn_seq_len=1)
                return M.unembed(cfg, params, x)[:, 0], pool

            @jax.jit
            def decode(pool, table, tok, pos):
                x = M.embed(cfg, params, tok, pos)
                x, pool = M.forward_layers(
                    cfg, params["layers"], x, pool, pos,
                    attn_hook=wrap(P.make_paged_hook(table)),
                    attn_seq_len=table.shape[1] * BS)
                return M.unembed(cfg, params, x[:, -1:, :])[:, 0, :], pool

            def launch(pool, entries, toks, width=32):
                meta, tok_row, tok_pos, offs, _ = P.build_ragged_meta(
                    entries, width=width, tile=TILE)
                flat = np.zeros((width,), np.int32)
                for (_, _, n, _), off, t in zip(entries, offs, toks):
                    flat[off:off + n] = t
                lg, pool = ragged(pool, jnp.array(table), jnp.asarray(flat),
                                  jnp.asarray(tok_pos), jnp.asarray(meta),
                                  jnp.asarray(tok_row))
                return np.asarray(lg), pool, offs

            logits = []
            for start, n in ((0, 24), (24, 24), (48, 22)):
                lg, pool, offs = launch(pool, [(0, start, n, P.RAGGED_PREFILL)],
                                        [ids[start:start + n]])
                logits.append(lg[offs[0]:offs[0] + n])
            tok, toks = int(logits[-1][-1].argmax()), []
            for p in range(70, 82):
                lg, pool = decode(pool, jnp.array(table[:1]),
                                  jnp.asarray([[tok]]), jnp.asarray([p], jnp.int32))
                logits.append(np.asarray(lg))
                tok = int(np.asarray(lg)[0].argmax())
                toks.append(tok)
            table[1, :6] = [3, 7, 2, 9, 11, 12]  # shares four full blocks (64 tokens)
            lg, pool, offs = launch(
                pool, [(0, 82, 1, P.RAGGED_DECODE), (1, 64, 9, P.RAGGED_PREFILL)],
                [[tok], tail])
            logits.append(lg)
            key = f"{name}.{impl}"
            out[key + ".tokens"] = np.asarray(toks, np.int32)
            out[key + ".logits"] = np.concatenate(logits)
            for leaf in sorted(set(pool) - {"routed"}):  # without the trash block
                out[f"{key}.pool_{leaf}"] = np.asarray(pool[leaf])[:, 1:]
            jax.clear_caches()  # unfused, a run's loops outnumber a process's mappings
    return out


def unequal(a, b) -> list:
    """The arrays that are not the same bits on both sides (or on one only)."""
    return sorted(k for k in set(a) | set(b)
                  if k not in a or k not in b or a[k].dtype != b[k].dtype
                  or a[k].tobytes() != b[k].tobytes())


@dataclasses.dataclass
class StepPrograms:
    """A configuration's two step programs as `programs` compiled them,
    with what an assertion needs beside their text."""
    cfg: object
    chip: object  # the described chip's sharding (None: the backend there)
    params: dict  # the abstract operands the programs were lowered with
    pool: dict
    blocks: object  # the pool's blocks (a grouped pool: one number a group)
    width: int  # the mixed launch's flat tokens
    live: int  # the axis its token-wise layers run on (scheduler.live_width)
    compiled: dict  # {program name: jax.stages.Compiled}

    @functools.cached_property
    def texts(self) -> dict:
        """{program name: optimized HLO text}."""
        return {name: c.as_text() for name, c in self.compiled.items()}


def programs(model, slots, blocks, context, block_size=128, tile=8,
             layers=2, described=True, snapshots=0,
             **overrides) -> StepPrograms:
    """`model`'s decode chunk and mixed step (cut to `layers` layers, 0: as
    registered or overridden), compiled for one chip of a described v5e:2x2
    with the kernels lowered for it (described False: for the backend that
    is there, in the registry's or the overrides' dtype). THE place the
    tests lower the two programs. Every family the paged fleet serves: a
    block-diffusion model's programs carry its DiffState, a model with
    recurrent layers a pool with a state a slot (and `snapshots` of them
    where it keeps a snapshot pool), a pool grouped by layer kind its
    groups' blocks (engine/paged.group_blocks) under two tables
    side by side; neither of the last two drafts, so no DeviceMeta."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from distributed_llm_inference_tpu.config import resolve_attn_impl
    from distributed_llm_inference_tpu.engine import generate as G
    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.engine.scheduler import live_width, step_width
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models.registry import get_model_config

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]) if described else None

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def place(make):
        return jax.tree.map(lambda a: S(a.shape, a.dtype), jax.eval_shape(make))

    if layers:
        overrides["n_layers"] = layers
    if described:
        overrides["dtype"] = "bfloat16"
    cfg = resolve_attn_impl(get_model_config(model).replace(**overrides), "pallas")
    params = place(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    state, sparams = place(lambda: G.init_slots(slots, cfg.vocab_size))
    width = step_width(cfg, slots, tile)  # what the server launches
    live = live_width(cfg, slots, tile)  # ... and the axis the model computes
    grouped = len(cfg.kv_groups) > 1
    if grouped:  # (the most tokens one row carries in a launch: the axis's)
        blocks = P.group_blocks(cfg, blocks, P.window_row_budget(
            cfg.attn_window, live, block_size), slots, block_size)
    pool = place(lambda: P.init_pool(
        cfg, blocks, block_size, n_slots=slots,
        **({"n_snapshots": snapshots} if cfg.linear_layers else {})))
    table = S((slots, len(cfg.kv_groups) * (context // block_size)), jnp.int32)
    key = place(lambda: jax.random.PRNGKey(0))
    chunk_kw, mixed_kw = {}, {}
    if cfg.diffusion_block:
        # a decode row is one tile: its open block, behind the owed one in
        # every second row
        Bd = cfg.diffusion_block
        diff = place(lambda: P.init_diffusion(cfg, slots))
        owing = [b % 2 == 0 for b in range(slots)]
        entries = [(b, 0, 2 * Bd if owe else Bd, P.RAGGED_PREFILL)
                   for b, owe in enumerate(owing)]
        meta, tok_row, tok_pos, offsets, _ = P.build_ragged_meta(
            entries, width=width, tile=tile)
        *dev, open_at = P.build_block_meta(entries, offsets, owing, block=Bd,
                                           width=width, tile=tile)
        assert open_at[:2] == [Bd, tile]
        chunk_kw = {"diff": diff}
        mixed_kw = {"dev": dev, "diff": diff, "darm": diff}
    else:
        entries = [(b, 0, 1, P.RAGGED_DECODE) for b in range(slots)]
        meta, tok_row, tok_pos, offsets, _ = P.build_ragged_meta(
            entries, width=width, tile=tile)
        if not (cfg.recurrent or grouped):
            mixed_kw = {"dev": P.build_device_meta(
                entries, offsets, slots, width=width, tile=tile)}
        if cfg.linear_layers:  # by slot: the snapshot restored, the one kept
            mixed_kw = {"snaps": (S((slots,), jnp.int32),) * 2}
    if live < width:  # as the engine dispatches it: no operand more elsewhere
        mixed_kw["live_width"] = live
    assert len(offsets) == slots  # one tile a row
    if "dev" in mixed_kw:
        mixed_kw["dev"] = P.DeviceMeta(
            *(S(a.shape, a.dtype) for a in mixed_kw["dev"]))

    def flat(a):
        return S(np.shape(a), np.asarray(a).dtype)

    # (described: resolve_interpret would see the CPU backend here and
    # lower the interpreter)
    with mock.patch.dict(os.environ,
                         {"DLI_PALLAS_INTERPRET": "0"} if described else {}):
        chunk = P.decode_slots_paged.lower(
            cfg, params, state, pool, table, key, sparams, num_steps=16,
            **chunk_kw).compile()
        mixed = P.mixed_step_ragged.lower(
            cfg, params, S((width,), jnp.int32), flat(tok_row), flat(tok_pos),
            S((width,), jnp.bool_), flat(meta), pool, table, state, sparams,
            key, S((slots,), jnp.int32),
            place(lambda: P.idle_mixed_arm(slots, cfg.vocab_size)),
            **mixed_kw).compile()
    return StepPrograms(cfg, chip, params, pool, blocks, width, live, {
        "decode_slots_paged": chunk, "mixed_step_ragged": mixed})


# The benchmark's configurations (cellbench/configs/<name>.json), by the test
# file that compiles their programs (tests/cell_program_checks.py): at most
# two a file, each in one, so that the suite's workers share them out.
CELL_FILES = {
    "test_cell_programs_kanana_lfm2": ("kanana-2-30b-a3b-7l", "lfm2-24b-a2b-9l"),
    "test_cell_programs_mistral_olmo2": ("mistral-7b-16l", "olmo2-7b-16l"),
    "test_cell_programs_granite": ("granite-4.0-h-micro",),
    "test_cell_programs_sala": ("minicpm-sala-9b-16l",),
    "test_cell_programs_sdar_trinity": ("sdar-30b-a3b-7l", "trinity-large-ep8-5l"),
    "test_cell_programs_mimo": ("mimo-v2.5-7l",),
    "test_cell_programs_solar": ("solar-open2-ep8-4l",),
}
CELL_CONFIGS = tuple(sorted(sum(CELL_FILES.values(), ())))


def cell_serving(config: str) -> dict:
    """The `serving` entry of a benchmark configuration: a name under this
    tree's cellbench/configs, or a path to such a file."""
    if not config.endswith(".json"):
        config = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "cellbench", "configs", config + ".json")
    with open(config) as f:
        return json.load(f)["serving"]


def cell_config(config: str) -> tuple:
    """(the ModelConfig a benchmark configuration serves: its registry entry
    under the file's overrides, in bfloat16; its `--continuous` slots)."""
    from distributed_llm_inference_tpu.models.registry import get_model_config

    serving = cell_serving(config)
    flags = serving["flags"]
    cfg = get_model_config(serving["base"]).replace(
        dtype="bfloat16", **serving.get("overrides", {}))
    return cfg, int(flags[flags.index("--continuous") + 1])


@functools.cache  # (one compile a configuration in a process: minutes each)
def cell_programs(config: str, layers: int = 0) -> StepPrograms:
    """`programs` of a benchmark configuration (`cell_serving`: its
    registry entry, overrides and serving flags), cut to `layers` layers
    (0: the configuration's own depth)."""
    serving = cell_serving(config)
    flags = serving["flags"]

    def flag(name):
        return int(flags[flags.index(name) + 1])

    return programs(
        serving["base"], flag("--continuous"), flag("--kv-pool-blocks"),
        flag("--continuous-max-seq"), flag("--kv-block-size"), layers=layers,
        snapshots=flag("--state-snapshots") if "--state-snapshots" in flags
        else 0,
        **serving.get("overrides", {}))


def canon(text: str) -> tuple:
    """(the instructions without source positions, [each Mosaic kernel's
    module printed without debug locations])."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    head = text.split("\n", 1)[0]
    body = text[text.index("\n\n", text.index("StackFrames")):]
    body = re.sub(r", metadata=\{[^}]*\}", "", body)
    kernels = []

    def kernel(m):
        config = json.loads(m.group(1))
        raw = base64.b64decode(config["custom_call_config"].pop("body"))
        with ctx:
            kernels.append(ir.Module.parse(raw).operation.get_asm(enable_debug_info=False))
        return f"backend_config={json.dumps(config, sort_keys=True)} <kernel {len(kernels)}>"

    body = re.sub(r'backend_config=(\{.*"custom_call_config".*\})', kernel, body)
    return head + body, kernels


def renumbered(body: str) -> str:
    """`canon`'s instructions with every name replaced by its place of
    first appearance: two compiles whose metadata alone differs may number
    a few instructions differently (`%broadcast_in_dim.62` / `.63`, read
    between 704043c and ISSUE 38's labels) while printing the same
    instructions, operands and order."""
    seen = {}
    return re.sub(r"%[\w.\-]+", lambda m: seen.setdefault(m.group(0), f"%v{len(seen)}"), body)


def _under(root: str) -> None:
    """Import the package of the checkout at `root`, on the CPU, its fusion
    pass off (the module's docstring says why)."""
    sys.path.insert(0, root)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_disable_hlo_passes=fusion")
    import distributed_llm_inference_tpu as package

    assert os.path.abspath(package.__file__).startswith(root), package.__file__


def main() -> None:
    mode = sys.argv[1]
    if mode == "--compare":
        a, b = (dict(np.load(p)) for p in sys.argv[2:4])
        bad = unequal(a, b)
        print(f"{len(a)} arrays compared, {sum(v.size for v in a.values())} numbers, "
              f"unequal: {bad}")
        sys.exit(1 if bad else 0)
    if mode == "--compare-programs":
        a, b = sys.argv[2:4]
        bad = []
        for name in sorted(set(os.listdir(a)) | set(os.listdir(b))):
            with open(os.path.join(a, name)) as fa, open(os.path.join(b, name)) as fb:
                (ta, ka), (tb, kb) = canon(fa.read()), canon(fb.read())
            same = ("identical" if ta == tb else
                    "identical but for instruction numbering"
                    if renumbered(ta) == renumbered(tb) else "DIFFER")
            print(f"{name}: {len(ta.splitlines())} lines of instructions "
                  f"{same}; {len(ka)} Mosaic kernel(s), "
                  f"{sum(map(len, ka))} characters, {'identical' if ka == kb else 'DIFFER'}")
            bad += [name] * (same == "DIFFER" or ka != kb)
        sys.exit(1 if bad else 0)
    if mode == "--programs":
        _under(os.path.abspath(sys.argv[2]))
        os.environ["DLI_PALLAS_INTERPRET"] = "0"
        import jax

        jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
        model, out = sys.argv[3], sys.argv[-1]
        os.makedirs(out, exist_ok=True)
        if model.endswith(".json"):  # <configuration file> <layers> <out dir>
            texts = cell_programs(model, int(sys.argv[4])).texts
            model = os.path.basename(model)[:-len(".json")]
        else:
            texts = programs(model, *map(int, sys.argv[4:7])).texts
        for name, text in texts.items():
            with open(os.path.join(out, f"{model}.{name}.hlo.txt"), "w") as f:
                f.write(text)
            print(model, name, len(text), "bytes of HLO text")
        return
    _under(os.path.abspath(mode))
    os.environ["DLI_PALLAS_INTERPRET"] = "1"
    out = dump()
    np.savez(sys.argv[2], **out)
    print("wrote", sys.argv[2], len(out), "arrays")


if __name__ == "__main__":
    main()
