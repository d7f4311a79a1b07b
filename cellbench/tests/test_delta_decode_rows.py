"""The reader PR 58 adds to the yardstick, on hand-made scrapes (CPU):

    python -m pytest cellbench/tests/test_delta_decode_rows.py -q

`delta_decode_chunk_row_pct`: the window's delta of
`dli_delta_state_rows_total{phase="chunk"}` over both phases.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, BENCH_DIR)

from harness import manifest, scrape  # noqa: E402

NAME = "delta_decode_chunk_row_pct"


def read(before, after):
    class Ctx:
        chunk_steps, window_s = 16, 50.0

    Ctx.before, Ctx.after = scrape.parse(before), scrape.parse(after)
    return manifest.load_module("layer_metrics", NAME).read(Ctx)


def counted(mixed, chunk):
    return (f'dli_delta_state_rows_total{{phase="mixed"}} {mixed}\n'
            f'dli_delta_state_rows_total{{phase="chunk"}} {chunk}\n'
            f'dli_delta_chunks_total{{phase="mixed"}} {2 * mixed}\n'
            f'dli_delta_chunks_total{{phase="chunk"}} {chunk}\n')


@pytest.mark.parametrize("before,after,want", [
    # 300 mixed launches of ~3 state rows beside 1,100 decode chunks' row-steps
    (counted(120, 4000), counted(1020, 26000), 100 * 22000 / 22900),
    # prompts alone: every state row a mixed launch's
    (counted(10, 500), counted(410, 500), 0.0),
    # decode alone
    (counted(10, 0), counted(10, 640), 100.0),
], ids=["docs", "prefill-only", "decode-only"])
def test_the_share_is_the_windows_delta(before, after, want):
    assert read(before, after) == pytest.approx(want)


def test_a_window_without_a_state_row_gives_none_not_zero():
    assert read(counted(500, 1280), counted(500, 1280)) is None
    assert read(counted(0, 0), counted(0, 0)) is None


def test_a_program_without_the_counter_gives_none_and_does_not_raise():
    """A configuration without delta-rule layers, or a commit before PR 57."""
    old = 'dli_ragged_launches_total{phase="mixed"} 10\n'
    assert read(old, old.replace("10", "41")) is None


def test_the_manifests_entry_lists_the_one_cell():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        man = json.load(f)
    assert man["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "tpot_ms_p50",
        "workloads": ["solar-docs-xlong"]}
    assert "kernels" in {m["layer"] for m in man["per_layer"][:-1]}
    assert NAME in {m["name"] for m in manifest.Cell(man, "solar-docs-xlong").per_layer}
    assert NAME not in {m["name"] for m in manifest.Cell(man, "granite-batch").per_layer}
