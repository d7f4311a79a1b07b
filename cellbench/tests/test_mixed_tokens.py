"""The reader PR 54 adds to the yardstick, on hand-made scrapes (CPU):

    python -m pytest cellbench/tests/test_mixed_tokens.py -q

`mixed_tokens_live_pct`: the window's delta of
`dli_mixed_tokens_total{state="live"}` over `{state="computed"}`.
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

NAME = "mixed_tokens_live_pct"


def read(before, after):
    class Ctx:
        chunk_steps, window_s = 16, 50.0

    Ctx.before, Ctx.after = scrape.parse(before), scrape.parse(after)
    return manifest.load_module("layer_metrics", NAME).read(Ctx)


def counted(live, computed):
    return (f'dli_mixed_tokens_total{{state="live"}} {live}\n'
            f'dli_mixed_tokens_total{{state="computed"}} {computed}\n'
            'dli_ragged_launches_total{phase="mixed"} 7\n')


@pytest.mark.parametrize("before,after,want", [
    # 100 mixed launches of 640 flat tokens computed on 320, 250 of them live
    (counted(1000, 6400), counted(26000, 38400), 100 * 25000 / 32000),
    # the tile layout: the same launches computed whole
    (counted(1000, 6400), counted(26000, 70400), 100 * 25000 / 64000),
    # every computed token live
    (counted(0, 0), counted(1280, 1280), 100.0),
], ids=["packed", "tiles", "full"])
def test_the_share_is_the_windows_delta(before, after, want):
    assert read(before, after) == pytest.approx(want)


def test_a_window_without_a_mixed_launch_gives_none_not_zero():
    assert read(counted(500, 1280), counted(500, 1280)) is None
    assert read(counted(0, 0), counted(0, 0)) is None


def test_a_program_without_the_counter_gives_none_and_does_not_raise():
    """The parent commit: a traced run lays this PR's files over it."""
    old = 'dli_ragged_launches_total{phase="mixed"} 10\n'
    assert read(old, old.replace("10", "41")) is None


def test_the_manifests_entry_is_the_last_and_lists_the_two_cells():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        man = json.load(f)
    names = [m["name"] for m in man["per_layer"]]
    entry = man["per_layer"][names.index(NAME)]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "model step", "moves": "tpot_ms_p50",
        "workloads": ["granite-batch", "olmo2-batch"]}
    assert "model step" in {m["layer"] for m in man["per_layer"] if m["name"] != NAME}
    for cell in entry["workloads"]:
        assert NAME in {m["name"] for m in manifest.Cell(man, cell).per_layer}
