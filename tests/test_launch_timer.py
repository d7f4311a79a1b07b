"""The worker's reading of the device it feeds (ISSUE 53;
utils/tracing.LaunchTimer, PhaseClock.device_empty), under a clock the test
holds: no engine, no JAX array, no sleep.

A script is what the worker tells the timer, in the order it happens: one
`returned` per fetched launch (its kind, when its dispatch ended, whether
its result was ready when the worker arrived, when the blocking read
returned, the steps the device ran, its decoding rows), or `RESET` where the
loop starts anew. Times in seconds on one made-up clock.
"""

import pytest

from distributed_llm_inference_tpu.utils import tracing
from distributed_llm_inference_tpu.utils.metrics import MetricsRegistry
from distributed_llm_inference_tpu.utils.tracing import (
    LAUNCH_PHASES, LAUNCH_TIMINGS, WORKER_PHASES, LaunchTimer, PhaseClock,
)

RESET = "reset"
FAMILIES = ("dli_launch_device_seconds_total", "dli_launch_device_steps_total",
            "dli_launch_timing_total", "dli_decode_row_seconds_total")


def _timer():
    m = MetricsRegistry()
    labels = {"dli_launch_timing_total": ("phase", "state")}
    return m, LaunchTimer(*(m.counter(n, "", labels.get(n, ("phase",)))
                            for n in FAMILIES))


def _values(m, name):
    return {tuple(s["labels"][k] for k in sorted(s["labels"])): s["value"]
            for s in m.snapshot()[name]["series"]}


def _play(script):
    m, timer = _timer()
    out = []
    for step in script:
        if step == RESET:
            timer.reset()
        else:
            out.append(timer.returned(*step))
    return m, out


CASES = {
    # lag 2: launches 1-3 are dispatched before the worker first blocks;
    # each later one while two are unfetched. The first met an empty queue;
    # from the second on each ran straight behind its predecessor.
    "back_to_back_behind_a_blocked_worker": (
        [("chunk", 0.10, False, 1.00, 16, 3),
         ("chunk", 0.20, False, 1.90, 16, 3),
         ("mixed", 0.30, False, 2.15, 1, 2),
         ("chunk", 1.05, False, 3.00, 16, 0)],
        [("queue_empty", 0.0), ("timed", 0.90), ("timed", 0.25), ("timed", 0.85)],
        {"seconds": {"chunk": 1.75, "mixed": 0.25}, "steps": {"chunk": 32, "mixed": 1},
         "rows": {"chunk": 0.90 * 3, "mixed": 0.25 * 2}},
    ),
    # the worker came late to launch 2's result: when 2 ended is unknown,
    # so neither 2 nor 3 (which began where 2 ended) is timed; 4 is again
    "ready_on_arrival_costs_this_one_and_the_next": (
        [("chunk", 0.10, False, 1.00, 16, 1),
         ("chunk", 0.20, True, 2.50, 16, 1),
         ("chunk", 1.10, False, 3.00, 16, 1),
         ("chunk", 2.60, False, 4.00, 16, 1)],
        [("queue_empty", 0.0), ("ready_early", 0.0), ("ready_early", 0.0),
         ("timed", 1.00)],
        {"seconds": {"chunk": 1.00}, "steps": {"chunk": 16}, "rows": {"chunk": 1.00}},
    ),
    # launch 2 was dispatched after launch 1's fetch had returned: nothing
    # ran when it was enqueued, its start is somewhere in its dispatch
    "enqueued_into_an_empty_queue": (
        [("mixed", 0.10, False, 1.00, 1, 0),
         ("mixed", 1.50, False, 2.00, 1, 4),
         ("chunk", 1.60, False, 2.80, 16, 4)],
        [("queue_empty", 0.0), ("queue_empty", 0.0), ("timed", 0.80)],
        {"seconds": {"chunk": 0.80}, "steps": {"chunk": 16}, "rows": {"chunk": 3.20}},
    ),
    # a chunk the device cut short counts the steps it RAN, not the 16
    # that were dispatched
    "a_cut_chunk_counts_the_steps_it_ran": (
        [("chunk", 0.10, False, 1.00, 16, 2),
         ("chunk", 0.20, False, 1.30, 5, 2)],
        [("queue_empty", 0.0), ("timed", 0.30)],
        {"seconds": {"chunk": 0.30}, "steps": {"chunk": 5}, "rows": {"chunk": 0.60}},
    ),
    # lag 1: launch n + 1 is dispatched, then launch n fetched
    "lag_one": (
        [("chunk", 0.10, False, 1.00, 16, 1),   # 2 dispatched at 0.20
         ("chunk", 0.20, False, 1.90, 16, 1),   # 3 dispatched at 1.05
         ("chunk", 1.05, True, 3.10, 16, 1),    # the host was late: 4 at 2.00
         ("chunk", 2.00, False, 3.70, 16, 1)],
        [("queue_empty", 0.0), ("timed", 0.90), ("ready_early", 0.0),
         ("ready_early", 0.0)],
        {"seconds": {"chunk": 0.90}, "steps": {"chunk": 16}, "rows": {"chunk": 0.90}},
    ),
    # the loop restarts with launches in flight that are never fetched: the
    # first launch after it has no predecessor, whatever the old return was
    "the_first_launch_after_a_restart": (
        [("chunk", 0.10, False, 1.00, 16, 1),
         ("chunk", 0.20, False, 1.90, 16, 1),
         RESET,
         ("chunk", 1.50, False, 2.50, 16, 1),
         ("chunk", 1.60, False, 3.50, 16, 1)],
        [("queue_empty", 0.0), ("timed", 0.90), ("queue_empty", 0.0),
         ("timed", 1.00)],
        {"seconds": {"chunk": 1.90}, "steps": {"chunk": 32}, "rows": {"chunk": 1.90}},
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_launch_timer(case):
    script, want, sums = CASES[case]
    m, got = _play(script)
    assert [s for s, _ in got] == [s for s, _ in want]
    assert [d for _, d in got] == pytest.approx([d for _, d in want])
    for name, key in (("dli_launch_device_seconds_total", "seconds"),
                      ("dli_launch_device_steps_total", "steps"),
                      ("dli_decode_row_seconds_total", "rows")):
        have = _values(m, name)
        assert set(have) == {(p,) for p in LAUNCH_PHASES}  # both kinds, from 0
        for p in LAUNCH_PHASES:
            assert have[(p,)] == pytest.approx(sums[key].get(p, 0.0)), (name, p)
    # every fetched launch is counted once, by kind and outcome
    timing = _values(m, "dli_launch_timing_total")
    assert set(timing) == {(p, s) for p in LAUNCH_PHASES for s in LAUNCH_TIMINGS}
    launches = [step for step in script if step != RESET]
    for p in LAUNCH_PHASES:
        for s in LAUNCH_TIMINGS:
            assert timing[(p, s)] == sum(
                1 for step, (state, _) in zip(launches, got)
                if step[0] == p and state == s)


def test_an_untimed_launch_adds_no_seconds():
    m, got = _play([("chunk", 0.1, True, 1.0, 16, 8), ("mixed", 0.2, True, 1.1, 1, 8)])
    assert [s for s, _ in got] == ["queue_empty", "ready_early"]
    for name in FAMILIES[:2] + FAMILIES[3:]:
        assert sum(_values(m, name).values()) == 0


# -- the empty queue by owner (PhaseClock.device_empty) -------------------------

@pytest.fixture
def clock(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(tracing.time, "perf_counter", lambda: now[0])
    m = MetricsRegistry()
    clock = PhaseClock(
        m.counter("dli_worker_phase_seconds_total", "", ("phase",)),
        m.counter("dli_device_empty_seconds_total", "", ("phase",)))

    def at(t, phase, *span, **attrs):
        now[0] = 100.0 + t
        return clock.mark(phase, *span, **attrs)

    return m, clock, at


def test_empty_seconds_go_to_the_phase_open_while_the_flag_is_set(clock):
    m, clock, at = clock
    at(0.0, "admit")
    clock.device_empty = True          # the loop's start
    at(0.5, "wait_work")               # admit 0.5, empty
    at(4.5, "reap")                    # wait_work 4.0, empty
    at(4.6, "admit")
    at(4.8, "plan")
    at(5.0, "dispatch", "launch.mixed", seq=1)
    at(5.3, "plan")                    # the dispatch's end: 0.3, still empty
    clock.device_empty = False         # the jitted call has returned
    at(5.5, "fetch_wait", "fetch.mixed", seq=1)   # plan 0.2: NOT empty
    at(7.5, "distribute", seq=1)       # fetch_wait 2.0: never empty
    clock.device_empty = True          # nothing is left unfetched
    at(7.9, "wait_work")               # distribute 0.4, empty
    at(9.9, None)                      # wait_work 2.0, empty; the clock stops
    empty = _values(m, "dli_device_empty_seconds_total")
    phases = _values(m, "dli_worker_phase_seconds_total")
    assert set(empty) == set(phases) == {(p,) for p in WORKER_PHASES}
    want = {"admit": 0.5 + 0.2, "wait_work": 4.0 + 2.0, "reap": 0.1, "plan": 0.2,
            "dispatch": 0.3, "distribute": 0.4, "fetch_wait": 0.0}
    for p in WORKER_PHASES:
        assert empty[(p,)] == pytest.approx(want[p]), p
        assert empty[(p,)] <= phases[(p,)] + 1e-9
    assert phases[("plan",)] == pytest.approx(0.4)  # 0.2 of it with a launch queued
    assert sum(phases.values()) == pytest.approx(9.9)
    assert sum(empty.values()) == pytest.approx(7.7)


def test_no_empty_seconds_while_the_flag_is_clear(clock):
    m, clock, at = clock
    at(0.0, "plan")
    at(1.0, "dispatch")
    at(2.0, "fetch_wait")
    at(3.0, None)
    assert sum(_values(m, "dli_device_empty_seconds_total").values()) == 0
    assert sum(_values(m, "dli_worker_phase_seconds_total").values()) == pytest.approx(3.0)
