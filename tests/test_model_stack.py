"""The contract of models/stack.py (ISSUE 59): the six unrolled families are
their leaves, their mixers and a binding of the one layer loop; none imports
a sibling, none has a whole-model end or a layer loop of its own, and the
loop refuses the same things for every one of them, in the same words."""

import ast
import os

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models import stack
from distributed_llm_inference_tpu.models.registry import get_model_config

# family module -> (a tiny preset of it, served from the paged pool alone)
FAMILIES = {
    "lfm2": ("test-lfm2-tiny", False),
    "afmoe": ("test-trinity-tiny", False),
    "mimo_v2": ("test-mimo-tiny", False),
    "minicpm_sala": ("test-sala-tiny", True),
    "granite_hybrid": ("test-granite-tiny", True),
    "solar_open2": ("test-solar-tiny", True),
}
# what a family module may import from inside the package
ALLOWED = {"stack", "experts", "config", "ops"}
MODELS = os.path.dirname(os.path.abspath(stack.__file__))


def _tree(module: str) -> ast.Module:
    with open(os.path.join(MODELS, module + ".py")) as f:
        return ast.parse(f.read())


def _package_imports(tree: ast.Module) -> set:
    """The first name under the package of every relative import."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level:
            if node.module:
                found.add(node.module.split(".")[0])
            else:  # from . import a, b
                found.update(alias.name for alias in node.names)
    return found


@pytest.mark.parametrize("module", sorted(FAMILIES))
def test_a_family_imports_the_stack_and_no_sibling(module):
    imports = _package_imports(_tree(module))
    assert "stack" in imports
    assert imports <= ALLOWED, imports - ALLOWED


@pytest.mark.parametrize("module", sorted(FAMILIES))
def test_a_family_has_no_end_and_no_layer_loop_of_its_own(module):
    tree = _tree(module)
    defined = {n.name for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)}
    assert not defined & {"embed", "unembed", "forward", "forward_layers"}
    for node in ast.walk(tree):  # the one loop over the pattern is the stack's
        if isinstance(node, (ast.For, ast.While)):
            assert "layer_types" not in ast.unparse(
                node.iter if isinstance(node, ast.For) else node.test)
    # ... and every name the dispatch calls is there (models/api.py)
    family = M._FAMILIES[get_model_config(FAMILIES[module][0]).arch]
    assert family.__name__.endswith("." + module)
    for name in ("init_params", "init_kv_cache", "embed", "unembed",
                 "forward_layers", "forward", "LEAF_KEYS", "leaf_shapes"):
        assert hasattr(family, name), name
    assert family.embed is stack.embed and family.unembed is stack.unembed
    assert family.forward_layers.func is stack.forward_layers


def test_the_stack_knows_no_family():
    """No `cfg.arch` and no family's name outside comments and docstrings:
    where two families differ, the difference is an argument."""
    tree = _tree("stack")
    docstrings = {id(n.body[0].value) for n in ast.walk(tree)
                  if isinstance(n, (ast.Module, ast.FunctionDef))
                  and ast.get_docstring(n, clean=False) is not None}
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute):
            assert node.attr != "arch", node.lineno
        text = (node.id if isinstance(node, ast.Name) else
                node.attr if isinstance(node, ast.Attribute) else
                node.name if isinstance(node, ast.alias) else
                node.value if isinstance(node, ast.Constant)
                and isinstance(node.value, str) and id(node) not in docstrings
                else "")
        assert not any(name in text for name in FAMILIES), (node.lineno, text)
    assert _package_imports(tree) <= {"config", "ops", "experts", "llama"}


@pytest.mark.parametrize("module", sorted(FAMILIES))
def test_the_loop_refuses_meshes_padding_and_a_missing_pool(module):
    preset, paged_only = FAMILIES[module]
    cfg = get_model_config(preset)
    x = jnp.zeros((1, 4, cfg.dim))
    for mesh in ({"tp_axis": "tp"}, {"ep_axis": "ep"},
                 {"update_gate": jnp.ones((), bool)}):
        with pytest.raises(ValueError, match="not sharded over pp, tp or ep"):
            M.forward_layers(cfg, {}, x, {}, 0, **mesh)
    with pytest.raises(ValueError, match="left-padded"):
        M.forward_layers(cfg, {}, x, {}, 0,
                         valid_start=jnp.zeros((1,), jnp.int32))
    if paged_only:
        with pytest.raises(ValueError, match="paged pool only"):
            M.forward_layers(cfg, {}, x, {}, 0, attn_seq_len=16)
        with pytest.raises(ValueError, match="no dense cache"):
            M.init_kv_cache(cfg, 1)
        with pytest.raises(ValueError, match="no dense-cache forward"):
            M.forward(cfg, {}, jnp.zeros((1, 4), jnp.int32), {}, 0)
    else:
        with pytest.raises(ValueError, match="not cut by layers"):
            M.init_kv_cache(cfg, 1, 16, n_layers=cfg.n_layers - 1)
        cache = M.init_kv_cache(cfg, 1, 16)
        assert all(leaf.shape[1] == 1 for leaf in cache.values())


@pytest.mark.parametrize("paged", [False, True])
def test_cached_cuts_a_dense_layer_and_hands_a_pool_whole(paged):
    """`stack.cached`: the dense cache's layer is cut and put back, the other
    layers bit for bit; a paged hook gets the leaves whole and the index."""
    k = jnp.arange(24.0).reshape(3, 2, 4)
    new = {"k": k, "v": -k, "other": jnp.ones(2)}
    seen = {}

    def attend(ck, cv, layer):
        seen.update(k=ck, v=cv, layer=layer)
        return "out", ck + 100.0, cv - 100.0

    out, got = stack.cached(new, ("k", "v"), 1, paged, attend)
    assert out == "out" and got is new and set(got) == {"k", "v", "other"}
    if paged:
        assert seen["layer"] == 1 and seen["k"].shape == (3, 2, 4)
        np.testing.assert_array_equal(got["k"], k + 100.0)
        np.testing.assert_array_equal(got["v"], -k - 100.0)
    else:
        assert seen["layer"] is None and seen["k"].shape == (2, 4)
        np.testing.assert_array_equal(seen["v"], -k[1])
        want = np.asarray(k).copy()
        want[1] += 100.0
        np.testing.assert_array_equal(got["k"], want)
        np.testing.assert_array_equal(got["v"][1], -k[1] - 100.0)
        np.testing.assert_array_equal(got["v"][::2], -k[::2])


@pytest.mark.parametrize("i", [0, 1, 2])
def test_put_replaces_one_layers_leaf_of_a_tuple(i):
    leaves = ("a", "b", "c")
    got = stack.put(leaves, i, "new")
    assert got == leaves[:i] + ("new",) + leaves[i + 1:] and len(got) == 3
    assert leaves == ("a", "b", "c")  # (a tuple: the pool's old tree stands)


def test_place_leaf_files_a_path_by_its_kind():
    params = {}
    for path in ("embed", "lm_head", "final_norm", "op_norm", "attn.wq",
                 "moe.w_gate"):
        stack.place_leaf(params, path, path)
    assert params == {
        "embed": "embed", "lm_head": "lm_head", "final_norm": "final_norm",
        "layers": {"op_norm": "op_norm", "attn": {"wq": "attn.wq"},
                   "moe": {"w_gate": "moe.w_gate"}}}
