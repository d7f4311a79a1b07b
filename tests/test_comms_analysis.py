"""The comms contract (analysis/comms.py + the four comms-* rules):
per-rule positive/negative/suppressed fixtures, symbolic-bytes units
against the known test-llama-tiny dims, the derived-table-vs-measured-
counter agreement on a real pp mesh, the derived-graph-vs-HLO round
trip, and the `--comms` CLI exit contract with a seeded raw-collective
fixture.

Selectable standalone: `pytest -m analysis`.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from distributed_llm_inference_tpu.analysis import comms, hlo
from distributed_llm_inference_tpu.analysis.callgraph import build_index
from distributed_llm_inference_tpu.analysis.lint import run_lint

pytestmark = pytest.mark.analysis

PKG_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "distributed_llm_inference_tpu",
)

def make_pkg(tmp_path, files: dict) -> str:
    root = tmp_path / "fixture_pkg"
    for rel, body in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(body))
    return str(root)


def lint(tmp_path, files, rules=None):
    return run_lint(make_pkg(tmp_path, files), rules=rules)


def rules_hit(diagnostics):
    return sorted({d.rule for d in diagnostics})


# -- comms-axis: axis names must resolve to declared mesh axes ---------------

def _axis_pkg(axis_expr):
    return {
        "parallel/mesh.py": """
            AXIS_PP = "pp"
            AXIS_SP = "sp"
        """,
        "parallel/handoff.py": f"""
            from jax import lax

            def hop(x, perm):
                return lax.ppermute(x, {axis_expr}, perm)
        """,
    }


def test_comms_axis_negative_literal(tmp_path):
    diags, _ = lint(tmp_path, _axis_pkg('"pp"'), rules=["comms-axis"])
    assert diags == []


def test_comms_axis_positive_typo(tmp_path):
    diags, _ = lint(tmp_path, _axis_pkg('"ppp"'), rules=["comms-axis"])
    assert len(diags) == 1
    d = diags[0]
    assert d.rule == "comms-axis"
    assert d.path.endswith("parallel/handoff.py")
    assert "'ppp'" in d.message and "pp" in d.message


def test_comms_axis_resolves_imported_constant(tmp_path):
    files = {
        "parallel/mesh.py": """
            AXIS_PP = "pp"
        """,
        "parallel/handoff.py": """
            from jax import lax
            from .mesh import AXIS_PP

            def hop(x, perm):
                return lax.ppermute(x, AXIS_PP, perm)
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-axis"])
    assert diags == []


def test_comms_axis_inert_without_declarations(tmp_path):
    # a bare fixture tree declares no AXIS_*: nothing to validate against
    files = {
        "parallel/handoff.py": """
            from jax import lax

            def hop(x, perm):
                return lax.ppermute(x, "anything", perm)
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-axis"])
    assert diags == []


def test_comms_axis_suppressed(tmp_path):
    files = _axis_pkg('"ppp"')
    files["parallel/handoff.py"] = """
        from jax import lax

        def hop(x, perm):
            # jaxlint: disable=comms-axis -- fixture: deliberate off-mesh axis
            return lax.ppermute(x, "ppp", perm)
    """
    diags, suppressed = lint(tmp_path, files, rules=["comms-axis"])
    assert diags == []
    assert suppressed == 1


# -- comms-wire-coverage: parallel/ transfers use the wrappers ---------------

RAW_HOP = {
    "parallel/handoff.py": """
        from jax import lax

        def hop(x, perm):
            return lax.ppermute(x, "pp", perm)
    """,
}


def test_wire_coverage_positive_raw_ppermute(tmp_path):
    diags, _ = lint(tmp_path, RAW_HOP, rules=["comms-wire-coverage"])
    assert len(diags) == 1
    d = diags[0]
    assert d.rule == "comms-wire-coverage"
    assert d.path.endswith("parallel/handoff.py")
    assert "wire_ppermute" in d.message


def test_wire_coverage_negative_wrapped(tmp_path):
    files = {
        "parallel/handoff.py": """
            from ..ops.wire_quant import wire_ppermute

            def hop(x, perm):
                return wire_ppermute(x, "pp", perm)
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-wire-coverage"])
    assert diags == []


def test_wire_coverage_negative_outside_parallel(tmp_path):
    # the contract governs the parallel/ transfer plane only
    files = {"engine/mod.py": RAW_HOP["parallel/handoff.py"]}
    diags, _ = lint(tmp_path, files, rules=["comms-wire-coverage"])
    assert diags == []


def test_wire_coverage_exempts_axis_size_and_merge(tmp_path):
    files = {
        "parallel/probe.py": """
            from jax import lax

            def probe(x):
                n = lax.psum(1, "pp")
                m = lax.pmax(x, "pp")
                return n, m
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-wire-coverage"])
    assert diags == []


def test_wire_coverage_suppressed(tmp_path):
    files = {
        "parallel/handoff.py": """
            from jax import lax

            def hop(x, perm):
                # jaxlint: disable=comms-wire-coverage -- fixture: control payload
                return lax.ppermute(x, "pp", perm)
        """,
    }
    diags, suppressed = lint(tmp_path, files, rules=["comms-wire-coverage"])
    assert diags == []
    assert suppressed == 1


# -- comms-masked-psum: quantized psum operands carry the one-hot mask -------

def test_masked_psum_positive_bare_quantized(tmp_path):
    files = {
        "parallel/bc.py": """
            from jax import lax
            from ..ops.wire_quant import quantize_rows

            def bcast(x):
                q, s = quantize_rows(x)
                return lax.psum(q, "pp"), lax.psum(s, "pp")
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-masked-psum"])
    assert len(diags) == 2
    assert all(d.rule == "comms-masked-psum" for d in diags)
    assert "overflow" in diags[0].message


def test_masked_psum_positive_through_alias(tmp_path):
    files = {
        "parallel/bc.py": """
            from jax import lax
            from ..ops.wire_quant import quantize_rows

            def bcast(x):
                q, s = quantize_rows(x)
                w = q
                return lax.psum(w, "pp")
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-masked-psum"])
    assert len(diags) == 1


def test_masked_psum_negative_where_masked(tmp_path):
    files = {
        "parallel/bc.py": """
            import jax.numpy as jnp
            from jax import lax
            from ..ops.wire_quant import quantize_rows

            def bcast(x, sel):
                q, s = quantize_rows(x)
                return lax.psum(jnp.where(sel, q, jnp.zeros_like(q)), "pp")
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-masked-psum"])
    assert diags == []


def test_masked_psum_negative_unquantized(tmp_path):
    files = {
        "parallel/bc.py": """
            from jax import lax

            def bcast(x):
                return lax.psum(x, "pp")
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-masked-psum"])
    assert diags == []


def test_masked_psum_suppressed(tmp_path):
    files = {
        "parallel/bc.py": """
            from jax import lax
            from ..ops.wire_quant import quantize_rows

            def bcast(x):
                q, s = quantize_rows(x)
                # jaxlint: disable=comms-masked-psum -- fixture: single-owner by construction
                return lax.psum(q, "pp")
        """,
    }
    diags, suppressed = lint(tmp_path, files, rules=["comms-masked-psum"])
    assert diags == []
    assert suppressed == 1


# -- comms-fat-collective: wide gathers are inventoried ----------------------

def test_fat_collective_positive_uninventoried_gather(tmp_path):
    files = {
        "parallel/gatherer.py": """
            from jax import lax

            def collect(x):
                return lax.all_gather(x, "pp")
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-fat-collective"])
    assert len(diags) == 1
    assert "FAT_INVENTORY" in diags[0].message


def test_fat_collective_negative_inventoried_site(tmp_path):
    # mirrors the real parallel/vocab.unembed_sharded site (module, func,
    # primitive, AND the `lg` operand all match the inventory entry)
    files = {
        "parallel/vocab.py": """
            from jax import lax

            def unembed_sharded(lg):
                return lax.all_gather(lg, "pp")
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-fat-collective"])
    assert diags == []


def test_fat_collective_stale_entry(tmp_path):
    # the inventory names parallel.vocab.unembed_sharded: a tree where
    # that module exists but the gather is gone must flag the stale entry
    files = {
        "parallel/vocab.py": """
            def unembed_sharded(lg):
                return lg
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["comms-fat-collective"])
    assert len(diags) == 1
    assert "stale" in diags[0].message


def test_fat_collective_suppressed(tmp_path):
    files = {
        "parallel/gatherer.py": """
            from jax import lax

            def collect(x):
                # jaxlint: disable=comms-fat-collective -- fixture: int32 control vector
                return lax.all_gather(x, "pp")
        """,
    }
    diags, suppressed = lint(tmp_path, files, rules=["comms-fat-collective"])
    assert diags == []
    assert suppressed == 1


# -- symbolic bytes: units at the known test-llama-tiny dims -----------------

def test_wire_link_bytes_formula():
    # raw: every element at itemsize; quant: int8 data + one fp32 scale
    # per leading row — times hops
    assert comms.wire_link_bytes((2, 1, 64), 4, 8, quant=False) \
        == 2 * 64 * 4 * 8
    assert comms.wire_link_bytes((2, 1, 64), 4, 8, quant=True) \
        == (2 * 64 + 4 * 2) * 8


def test_wire_bytes_delegates_to_comms():
    from distributed_llm_inference_tpu.ops.wire_quant import wire_bytes

    for shape in [(1, 1, 64), (2, 24, 64), (2, 16, 2, 16)]:
        for quant in (False, True):
            assert wire_bytes(shape, 4, 3, quant=quant) \
                == comms.wire_link_bytes(shape, 4, 3, quant=quant)


def test_link_bytes_at_tiny_dims():
    from distributed_llm_inference_tpu import get_model_config

    cfg = get_model_config("test-llama-tiny")
    p = comms.params_from_config(
        cfg, dp=1, pp=2, sp=2, mb=2, rows=2, t=32, t_chunk=16,
        steps=4, draft=3, bh=1, b_m=1,
    )
    assert p["dim"] == 64 and p["vocab_size"] == 256
    assert p["n_layers"] == 4 and p["n_kv_heads"] == 2
    # decode ring: (2, 1, 64) x steps*pp = 8 hops
    assert comms.link_bytes(
        "pp-microstep-decode", p, itemsize=4, quant=False
    ) == 2 * 64 * 4 * 8
    assert comms.link_bytes(
        "pp-microstep-decode", p, itemsize=4, quant=True
    ) == (2 * 64 + 4 * 2) * 8
    # prefill: (2, 32, 64) x pp = 2 hops
    assert comms.link_bytes(
        "pp-microstep-prefill", p, itemsize=4, quant=False
    ) == 2 * 32 * 64 * 4 * 2
    # sp kv ring: (2, 16, 2, 16) x 2*n_layers*(sp-1) = 8 hops
    assert comms.link_bytes(
        "sp-kv-ring", p, itemsize=4, quant=False
    ) == 2 * 16 * 2 * 16 * 4 * 8
    # spec verify window: (2, 1+3, 64) x steps*pp = 8 hops
    assert comms.link_bytes(
        "pp-microstep-spec", p, itemsize=4, quant=False
    ) == 2 * 4 * 64 * 4 * 8


def test_fat_inventory_vocab_bytes_at_tiny_dims():
    from distributed_llm_inference_tpu import get_model_config

    cfg = get_model_config("test-llama-tiny")
    p = comms.params_from_config(cfg, pp=2, sp=2, rows=1, t=32, t_chunk=16)
    entry = next(
        e for e in comms.FAT_INVENTORY if e.module == "parallel.vocab"
    )
    # V=256 divides pp=2: 4 bytes * 1 row * 32 tok * 128 local cols * 1 hop
    assert entry.bytes_fn(p) == 4 * 1 * 32 * 128 * 1
    assert entry.bytes_fn(comms.REFERENCE_PARAMS) > comms.FAT_THRESHOLD


# -- the real package: census, table provenance, declared axes ---------------

@pytest.fixture(scope="module")
def repo_index():
    return build_index(PKG_ROOT)


def test_declared_axes_real_package(repo_index):
    assert {"dp", "pp", "sp", "tp", "ep"} <= set(
        comms.declared_axes(repo_index)
    )


def test_vocab_logits_gather_in_census(repo_index):
    sites = comms.collect_sites(repo_index)
    gathers = [
        s for s in sites
        if s.primitive == "all_gather" and s.module == "parallel.vocab"
    ]
    assert len(gathers) == 1
    g = gathers[0]
    assert g.axes == ("pp",)
    assert g.role == "raw"
    assert comms.fat_entry_for(g) is not None


def test_wrapper_sites_classified_not_raw(repo_index):
    sites = comms.collect_sites(repo_index)
    wq = [s for s in sites if s.module == "ops.wire_quant"]
    assert wq and all(s.role == "wrapper-internal" for s in wq)


def test_repo_report_clean_and_fully_routed(repo_index):
    report = comms.build_report(index=repo_index)
    assert report["problems"] == []
    for row in report["links"]:
        assert row["accounted_at"], (
            f"link {row['name']} has no _account_link provenance"
        )
    fat = {r["module"]: r for r in report["fat_inventory"]}
    v = fat["parallel.vocab"]
    assert v["sites"] and "parallel/vocab.py" in v["sites"][0]
    assert v["reference_bytes"] > comms.FAT_THRESHOLD


def test_repo_lint_clean_all_comms_rules():
    diags, _ = run_lint(PKG_ROOT, rules=[
        "comms-axis", "comms-wire-coverage", "comms-masked-psum",
        "comms-fat-collective",
    ])
    assert diags == [], "\n".join(d.format() for d in diags)


# -- derived bytes vs measured counters on a real pp mesh --------------------

@pytest.mark.parametrize("wq", [None, "int8"])
def test_derived_bytes_match_measured_counters(wq):
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu import MeshConfig, get_model_config
    from distributed_llm_inference_tpu.engine import generate as G
    from distributed_llm_inference_tpu.runtime import create_backend
    from distributed_llm_inference_tpu.utils.metrics import MetricsRegistry

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a pp mesh")
    cfg = get_model_config(
        "test-llama-tiny", dtype="float32", eos_token_id=-1
    )
    cfg, be = create_backend(cfg, mesh_cfg=MeshConfig(pp=2), wire_quant=wq)
    reg = MetricsRegistry()
    be.attach_wire_metrics(reg)
    B, PLEN, BUCKET, STEPS = 2, 12, 16, 4
    row = ([cfg.bos_token_id] + [7] * (PLEN - 1)
           + [cfg.pad_token_id] * (BUCKET - PLEN))
    tokens = jnp.asarray([row] * B, jnp.int32)
    sampling = G.default_sampling(greedy=True)
    kp, kd = jax.random.split(jax.random.PRNGKey(0))
    cache = be.init_cache(B, 64)
    first, _, cache = be.prefill(
        tokens, jnp.int32(PLEN), cache, kp, sampling
    )
    _, n_gen, cache = be.decode(
        first, cache, jnp.int32(PLEN), jnp.int32(STEPS), kd, sampling,
        max_steps=STEPS,
    )
    np.asarray(n_gen)
    fam = reg.get("dli_pp_wire_bytes_total")
    q = wq is not None
    p = comms.params_from_config(
        cfg, dp=1, pp=2, rows=B, t=BUCKET, steps=STEPS
    )
    assert int(fam.labels(path="microstep").value) == (
        comms.link_bytes("pp-microstep-prefill", p, itemsize=4, quant=q)
        + comms.link_bytes("pp-microstep-decode", p, itemsize=4, quant=q)
    )
    assert int(fam.labels(path="broadcast").value) == (
        comms.link_bytes("pp-broadcast-prefill", p, itemsize=4, quant=q)
        + comms.link_bytes("pp-broadcast-decode", p, itemsize=4, quant=q)
    )


# -- derived graph vs lowered HLO --------------------------------------------

def test_check_comms_graph_synthetic():
    # all three predicted pp edges present, nothing else: clean
    text = ('stablehlo.collective_permute stablehlo.all_reduce '
            '"stablehlo.all_gather"')
    assert hlo.check_comms_graph(text, "pp-decode") == []
    # an unpredicted collective kind must be flagged
    extra = hlo.check_comms_graph(
        text + " stablehlo.reduce_scatter", "pp-decode"
    )
    assert len(extra) == 1 and "unpredicted" in extra[0]
    # a missing predicted edge must be flagged
    missing = hlo.check_comms_graph("no collectives here", "pp-decode")
    assert len(missing) == 3 and all("stale" in m for m in missing)
    assert hlo.check_comms_graph("stablehlo.all_to_all", "sp-attend") == []


def test_collective_operand_parser():
    line = ('%3 = "stablehlo.all_to_all"(%2) <{split_count = 2}> : '
            '(tensor<1x4x2x16xi8>) -> tensor<1x8x1x16xi8>')
    ops = hlo._collective_operands(line, "all_to_all")
    assert len(ops) == 1
    rank, dtype, _ = ops[0]
    assert rank == 4 and dtype == "i8"
    # the attribute dict's replica_groups tensor has no paren wrapper and
    # must not parse as an operand
    attr_only = 'replica_groups = dense<[[0, 1]]> : tensor<1x2xi64>'
    assert hlo._collective_operands(attr_only, "tensor") == []


def test_hlo_comms_graph_round_trip():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a pp mesh")
    pp = hlo.lower_pp_decode()
    assert hlo.check_comms_graph(pp, "pp-decode") == []
    assert hlo.check_gather_dtype(pp) == []
    wired = hlo.lower_pp_decode(wire_quant="int8")
    assert hlo.check_comms_graph(wired, "pp-decode") == []
    assert hlo.check_gather_dtype(wired) == []


def test_hlo_sp_attend_round_trip():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for an sp mesh")
    sp_off = hlo.lower_sp_attend(False)
    sp_on = hlo.lower_sp_attend(True)
    assert hlo.check_comms_graph(sp_off, "sp-attend") == []
    assert hlo.check_comms_graph(sp_on, "sp-attend") == []
    assert hlo.check_a2a_dtype(sp_off, wire=False) == []
    assert hlo.check_a2a_dtype(sp_on, wire=True) == []


# -- CLI exit contract -------------------------------------------------------

def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "distributed_llm_inference_tpu.analysis",
         *args],
        capture_output=True, text=True,
        cwd=os.path.dirname(PKG_ROOT),
    )


def test_cli_comms_clean_repo_exits_zero():
    r = _run_cli("--comms")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "wire links" in r.stdout
    assert "fat-collective inventory" in r.stdout
    assert "accounted at" in r.stdout


def test_cli_comms_json_schema():
    r = _run_cli("--comms", "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(r.stdout)
    assert data["problems"] == []
    assert data["diagnostics"] == []
    assert {l["name"] for l in data["links"]} == set(comms.WIRE_LINKS)
    assert all(l["accounted_at"] for l in data["links"])
    assert any(
        f["module"] == "parallel.vocab" for f in data["fat_inventory"]
    )


def test_cli_seeded_raw_collective_exits_nonzero(tmp_path):
    """The acceptance contract: a raw lax.ppermute seeded onto a
    parallel/ hand-off path fails the CLI with a file:line diagnostic
    naming comms-wire-coverage."""
    root = make_pkg(tmp_path, {
        "parallel/handoff.py": """
            from jax import lax

            def hop(x, perm):
                return lax.ppermute(x, "pp", perm)
        """,
    })
    r = _run_cli("--root", root)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "comms-wire-coverage" in r.stdout
    assert "handoff.py:" in r.stdout


def test_cli_comms_flags_unrouted_link(tmp_path):
    """A table row with no _account_link call site is a problem the CLI
    exits nonzero on — the provenance half of the contract (a fixture
    tree has none of the real accounting seams)."""
    root = make_pkg(tmp_path, {
        "parallel/handoff.py": """
            def hop(x):
                return x
        """,
    })
    r = _run_cli("--root", root, "--comms")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "no _account_link call site" in r.stdout
