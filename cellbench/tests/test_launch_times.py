"""Tests of what PR 53 adds to the yardstick, run on the CPU:

    python -m pytest cellbench/tests/test_launch_times.py -q

The six readers of the worker's own counters (`dli_launch_device_*`,
`dli_launch_timing_total`, `dli_decode_row_seconds_total`,
`dli_device_empty_seconds_total`) on hand-made scrapes, and
`tools/launch_times.py` on a hand-made trace and on the recorded fixture
(which predates the span fields it reads).
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
sys.path.insert(0, HERE)

from harness import manifest, scrape  # noqa: E402
from test_host_spans import FIXTURE, _xplane  # noqa: E402

NEW = ("decode_step_ms_mean", "mixed_step_ms_mean", "launch_timed_pct",
       "decode_time_in_mixed_pct", "device_empty_wait_pct", "device_empty_host_pct")


def read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


BEFORE = """
dli_launch_device_seconds_total{phase="mixed"} 1.0
dli_launch_device_seconds_total{phase="chunk"} 2.0
dli_launch_device_steps_total{phase="mixed"} 50
dli_launch_device_steps_total{phase="chunk"} 800
dli_launch_timing_total{phase="mixed",state="timed"} 50
dli_launch_timing_total{phase="mixed",state="ready_early"} 5
dli_launch_timing_total{phase="mixed",state="queue_empty"} 5
dli_launch_timing_total{phase="chunk",state="timed"} 50
dli_launch_timing_total{phase="chunk",state="ready_early"} 0
dli_launch_timing_total{phase="chunk",state="queue_empty"} 10
dli_decode_row_seconds_total{phase="mixed"} 1.5
dli_decode_row_seconds_total{phase="chunk"} 2.5
dli_device_empty_seconds_total{phase="wait_work"} 3.0
dli_device_empty_seconds_total{phase="reap"} 0.0
dli_device_empty_seconds_total{phase="admit"} 0.5
dli_device_empty_seconds_total{phase="plan"} 0.25
dli_device_empty_seconds_total{phase="dispatch"} 0.25
dli_device_empty_seconds_total{phase="fetch_wait"} 0
dli_device_empty_seconds_total{phase="distribute"} 1.0
"""
AFTER = """
dli_launch_device_seconds_total{phase="mixed"} 3.0
dli_launch_device_seconds_total{phase="chunk"} 10.0
dli_launch_device_steps_total{phase="mixed"} 150
dli_launch_device_steps_total{phase="chunk"} 4000
dli_launch_timing_total{phase="mixed",state="timed"} 150
dli_launch_timing_total{phase="mixed",state="ready_early"} 15
dli_launch_timing_total{phase="mixed",state="queue_empty"} 45
dli_launch_timing_total{phase="chunk",state="timed"} 250
dli_launch_timing_total{phase="chunk",state="ready_early"} 20
dli_launch_timing_total{phase="chunk",state="queue_empty"} 40
dli_decode_row_seconds_total{phase="mixed"} 4.5
dli_decode_row_seconds_total{phase="chunk"} 11.5
dli_device_empty_seconds_total{phase="wait_work"} 13.0
dli_device_empty_seconds_total{phase="reap"} 0.25
dli_device_empty_seconds_total{phase="admit"} 1.5
dli_device_empty_seconds_total{phase="plan"} 0.75
dli_device_empty_seconds_total{phase="dispatch"} 1.0
dli_device_empty_seconds_total{phase="fetch_wait"} 0
dli_device_empty_seconds_total{phase="distribute"} 1.5
"""


def _ctx(before, after):
    class Ctx:
        chunk_steps, window_s = 16, 50.0

    Ctx.before, Ctx.after = scrape.parse(before), scrape.parse(after)
    return Ctx


def test_the_six_readers_on_a_hand_made_window():
    ctx = _ctx(BEFORE, AFTER)
    assert read("decode_step_ms_mean", ctx) == pytest.approx(1e3 * 8.0 / 3200)
    assert read("mixed_step_ms_mean", ctx) == pytest.approx(1e3 * 2.0 / 100)
    assert read("launch_timed_pct", ctx) == pytest.approx(100 * 300 / 400)
    assert read("decode_time_in_mixed_pct", ctx) == pytest.approx(100 * 3.0 / 12.0)
    assert read("device_empty_wait_pct", ctx) == pytest.approx(100 * 10.0 / 50.0)
    assert read("device_empty_host_pct", ctx) == pytest.approx(100 * 3.0 / 50.0)


def test_a_window_with_no_timed_launch_of_a_kind_gives_none_not_zero():
    """A closed-loop window may hold no timed mixed launch, an idle one no
    launch at all: the quotient is left out, never 0 and never an error; the
    empty-queue shares still read (the whole window in `wait_work`)."""
    flat = AFTER.replace('dli_device_empty_seconds_total{phase="wait_work"} 13.0',
                         'dli_device_empty_seconds_total{phase="wait_work"} 63.0')
    ctx = _ctx(AFTER, flat)
    for name in NEW[:4]:
        assert read(name, ctx) is None, name
    assert read("device_empty_wait_pct", ctx) == pytest.approx(100.0)
    assert read("device_empty_host_pct", ctx) == pytest.approx(0.0)
    # timed chunks only: the mixed quotient alone is left out
    ctx = _ctx(BEFORE, AFTER.replace('steps_total{phase="mixed"} 150',
                                     'steps_total{phase="mixed"} 50'))
    assert read("mixed_step_ms_mean", ctx) is None
    assert read("decode_step_ms_mean", ctx) == pytest.approx(2.5)


def test_the_six_readers_return_none_from_a_program_without_the_counters():
    """The parent commit has none of the families: every reader leaves its
    metric out, none raises (a traced run lays this PR's files over it)."""
    ctx = _ctx('dli_ragged_launches_total{phase="mixed"} 10\n',
               'dli_ragged_launches_total{phase="mixed"} 41\n'
               'dli_worker_phase_seconds_total{phase="wait_work"} 4.0\n')
    for name in NEW:
        assert read(name, ctx) is None, name


def test_the_manifest_lists_the_six_together_and_on_cells_it_has():
    man = manifest.load_json(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW[0])  # appended together, in the issue's order
    assert names[at:at + 6] == list(NEW) and at >= 47
    layers = {"decode_step_ms_mean": "model step", "mixed_step_ms_mean": "model step",
              "device_empty_wait_pct": "device"}
    for m in man["per_layer"][at:at + 6]:
        assert m["source"] == "program_counter" and m["moves"] == "tpot_ms_p50"
        assert m["layer"] == layers.get(m["name"], "continuous engine")
        assert m["workloads"] and set(m["workloads"]) <= {w["name"] for w in man["workloads"]}
        assert hasattr(manifest.load_module("layer_metrics", m["name"]), "read")


# ---- tools/launch_times.py --------------------------------------------------

def _timed_trace(tmp_path):
    """Five launches, times in microseconds. Seq 4 (chunk) was dispatched
    before the trace began: its fetch and the span after it are there, its
    launch span is not. Seq 5 (mixed) ran straight behind it, seq 6 (chunk)
    behind 5: both timed. The worker then found seq 6's result fetched, the
    queue empty, and waited 1000 us for a request before seq 7 (mixed),
    which met an empty queue; seq 8 (chunk) ran behind 7 but the worker came
    late to its result. The chip is idle 5000-6300 (no launch unfetched
    5010-6230: distribute, wait_work, plan, and seq 7's dispatch) and
    7300-7400."""
    device = [
        ("XLA Modules", "jit_decode_slots_paged(12)", 100, 1900),   # seq 4
        ("XLA Modules", "jit_mixed_step_ragged(11)", 2000, 1000),   # seq 5
        ("XLA Modules", "jit_decode_slots_paged(12)", 3000, 2000),  # seq 6
        ("XLA Modules", "jit_mixed_step_ragged(11)", 6300, 1000),   # seq 7
        ("XLA Modules", "jit_decode_slots_paged(12)", 7400, 1500),  # seq 8
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 100, 1900),
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 2000, 1000),
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 3000, 2000),
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 6300, 1000),
        ("XLA Ops", "%fusion.1 = f32[] fusion()", 7400, 1500),
    ]
    spans = [
        ("phase.plan", 150, 50, {"prev": "dispatch"}),
        ("launch.mixed", 200, 50, {"prev": "plan", "seq": 5, "steps": 1, "queue_empty": 0}),
        ("phase.plan", 250, 50, {"prev": "dispatch"}),
        ("launch.chunk", 300, 50, {"prev": "plan", "seq": 6, "steps": 16, "queue_empty": 0}),
        ("phase.plan", 350, 50, {"prev": "dispatch"}),
        ("fetch.chunk", 400, 1610, {"prev": "plan", "seq": 4, "ready": 0}),
        ("phase.distribute", 2010, 90, {"prev": "fetch_wait", "seq": 4, "timed": 0,
                                        "device_us": 0, "steps_run": 16}),
        ("fetch.mixed", 2100, 910, {"prev": "distribute", "seq": 5, "ready": 0}),
        ("phase.distribute", 3010, 90, {"prev": "fetch_wait", "seq": 5, "timed": 1,
                                        "device_us": 1000}),
        ("fetch.chunk", 3100, 1910, {"prev": "distribute", "seq": 6, "ready": 0}),
        ("phase.distribute", 5010, 90, {"prev": "fetch_wait", "seq": 6, "timed": 1,
                                        "device_us": 2000, "steps_run": 16}),
        ("phase.wait_work", 5100, 1000, {"prev": "distribute"}),
        ("phase.plan", 6100, 100, {"prev": "wait_work"}),
        ("launch.mixed", 6200, 30, {"prev": "plan", "seq": 7, "steps": 1, "queue_empty": 1}),
        ("phase.plan", 6230, 20, {"prev": "dispatch"}),
        ("launch.chunk", 6250, 30, {"prev": "plan", "seq": 8, "steps": 16, "queue_empty": 0}),
        ("phase.plan", 6280, 20, {"prev": "dispatch"}),
        ("fetch.mixed", 6300, 1010, {"prev": "plan", "seq": 7, "ready": 0}),
        ("phase.distribute", 7310, 1690, {"prev": "fetch_wait", "seq": 7, "timed": 0,
                                          "device_us": 0}),
        ("fetch.chunk", 9000, 10, {"prev": "distribute", "seq": 8, "ready": 1}),
        ("phase.distribute", 9010, 40, {"prev": "fetch_wait", "seq": 8, "timed": 0,
                                        "device_us": 0, "steps_run": 16}),
    ]
    return _xplane(tmp_path, device, spans)


def test_launch_times_holds_the_workers_times_against_the_modules(tmp_path):
    import launch_times

    r = launch_times.report(_timed_trace(tmp_path))
    rows = {row["seq"]: row for row in r["launches"]}
    assert sorted(rows) == [4, 5, 6, 7, 8]
    assert [rows[s]["state"] for s in sorted(rows)] == [
        "ready_early", "timed", "timed", "queue_empty", "ready_early"]
    assert rows[5]["device_us"] == 1000 and rows[5]["module_us"] == pytest.approx(1000)
    assert rows[6]["device_us"] == 2000 and rows[6]["module_us"] == pytest.approx(2000)
    assert rows[8]["ready"] == 1 and rows[7]["queue_empty"] == 1
    assert "queue_empty" not in rows[4]  # dispatched before the trace began
    sums = r["sums"]
    assert sums["mixed"]["timed"] == 1 and sums["mixed"]["queue_empty"] == 1
    assert sums["chunk"]["timed"] == 1 and sums["chunk"]["ready_early"] == 2
    for kind, us in (("mixed", 1000), ("chunk", 2000)):
        assert sums[kind]["worker_us"] == us
        assert sums[kind]["module_us"] == pytest.approx(us)
        assert sums[kind]["worker_over_module_pct"] == pytest.approx(0.0, abs=1e-6)
        assert sums[kind]["diff_us_worst"] == pytest.approx(0.0, abs=1e-3)
    e = r["empty_queue"]
    assert e["window_s"] == pytest.approx(8800e-6)
    assert e["idle_s"] == pytest.approx(1400e-6)
    want = {"distribute": 90e-6, "wait_work": 1000e-6, "plan": 100e-6, "dispatch": 30e-6}
    assert e["empty_s_by_phase"] == pytest.approx(want)
    assert e["empty_s"] == pytest.approx(1220e-6)
    assert e["idle_inside_empty_s"] == pytest.approx(1220e-6)
    assert e["idle_elsewhere_s"] == pytest.approx(180e-6)
    assert e["empty_wait_pct"] + e["empty_host_pct"] == pytest.approx(e["empty_pct"])
    assert e["empty_pct"] <= e["idle_pct"]


@pytest.mark.skipif(not os.path.isfile(FIXTURE + ".xplane.pb"), reason="no recorded fixture")
def test_launch_times_on_the_recorded_fixture_finds_every_launch_absent(capsys, monkeypatch):
    """The fixture is PR 24's: no `timed`, no `queue_empty`, no `ready`. Every
    launch reads `absent`, nothing is summed, and the tool prints its report."""
    import launch_times

    r = launch_times.report(FIXTURE + ".xplane.pb")
    assert r["launches"] and {row["state"] for row in r["launches"]} == {"absent"}
    assert all("device_us" not in row and "ready" not in row for row in r["launches"])
    assert any("module_us" in row for row in r["launches"])
    assert all(s["timed_with_module"] == 0 and s["worker_over_module_pct"] is None
               for s in r["sums"].values())
    assert r["empty_queue"] is None
    monkeypatch.setattr(sys, "argv", ["launch_times.py", FIXTURE + ".xplane.pb"])
    launch_times.main()
    out = capsys.readouterr().out
    assert "absent" in out and "no empty-queue reading" in out
