"""The traced slice opens at a place in the traffic, not at a time: the
window's own loop (`child.run_window`) and `child.TraceSlice`, driven by a
clock that only a statement moves, with no profiler and no chip. A fast
program and a slow one trace the same statements on one seed; a window that
never reaches the slice's cycle, or ends inside the slice, is an error in
words, never a slice somewhere else."""

import argparse

import pytest

from benchmarks import child, lib
from benchmarks import run as bench_run

TRAFFIC = lib.load_json(lib.Spec(lib.REPO).traffic_path(
    lib.Spec(lib.REPO).cell("sf1-parquet.replay6")))
#: streams as `lib.make_streams` shapes them; the loop reads only the names
STREAMS = [[(t, f"-- {t} of stream {s}") for t in TRAFFIC["templates"]]
           for s in range(1 + TRAFFIC["window_passes"])]
SEED = 2147483659


class Slice(child.TraceSlice):
    """A TraceSlice whose profiler is a list of what was asked of it."""

    def __init__(self, wanted=1, cycle=TRAFFIC["trace_cycle"]):
        spans = child.Spans(False)
        spans.span = lambda phase, q: pytest.fail("no annotation in a test")
        super().__init__(wanted, "nowhere", cycle, TRAFFIC["trace_passes"],
                         spans, {})
        self.calls = []

    def start_profiler(self):
        self.calls.append("start")

    def stop_profiler(self):
        self.calls.append("stop")


def window(seconds, cost_s, tracing, seed=SEED):
    """The window's loop over a clock that each statement moves by
    `cost_s` (query93's first execution of a cycle by ten times that)."""
    now = [1000.0]
    full = set()

    def one(si, name, sql, t_open, first):
        assert sql == f"-- {name} of stream {si}"
        cost = cost_s
        if name == "query93" and cycles[-1] not in full:
            full.add(cycles[-1])
            cost *= 10
        now[0] += cost
        return {"stream": si, "name": name, "first": first,
                "traced": tracing.state == "on"}

    cycles = []
    statements, n_cycles, t_last, t_open = child.run_window(
        TRAFFIC, STREAMS, seed, seconds, tracing, one, cycles.append,
        clock=lambda: now[0])
    assert cycles == list(range(n_cycles)) and t_open == 1000.0
    assert t_last == now[0]
    return statements


def wanted(seed=SEED, cycle=TRAFFIC["trace_cycle"]):
    return lib.slice_statements(TRAFFIC, STREAMS, seed, cycle,
                                TRAFFIC["trace_passes"])


@pytest.mark.parametrize("seed", [7, SEED, 2**31 + 12345])
def test_a_fast_and_a_slow_program_trace_the_same_statements(seed):
    records = {}
    for cost_s in (0.1, 0.17):
        tracing = Slice()
        statements = window(45, cost_s, tracing, seed)
        record, error = tracing.record(wanted(seed))
        assert error is None and tracing.calls == ["start", "stop"]
        # the slice is cycle 2's first two passes, wherever the clock stood
        traced = [i for i, s in enumerate(statements) if s["traced"]]
        assert traced == list(range(72, 84))
        assert {s["cycle"] for s in statements[72:84]} == {2}
        assert sorted(tracing.marks) == ["slice_end", "slice_start"]
        records[cost_s] = record
    assert records[0.1] == records[0.17] == {
        "cycle": 2, "passes": 2, "statements": wanted(seed)}
    streams = lib.window_order(TRAFFIC, seed, 2)[:2]
    assert [si for si, _ in wanted(seed)] == [streams[0]] * 6 + [streams[1]] * 6
    # query93's one full execution of the cycle is in the slice
    assert [streams[0], "query93"] in wanted(seed)


def test_another_seed_or_cycle_traces_other_passes():
    slices = {(seed, cycle): tuple(map(tuple, wanted(seed, cycle)))
              for seed in (7, SEED) for cycle in (0, 1, 2)}
    assert len(set(slices.values())) > 1
    assert all(len(s) == 12 for s in slices.values())


def test_a_window_that_ends_before_the_cycle_starts_is_an_error():
    tracing = Slice()
    statements = window(8, 0.1, tracing)  # a cycle takes 4.5 s: 0 and 1 only
    assert {s["cycle"] for s in statements} == {0, 1}
    record, error = tracing.record(wanted())
    assert tracing.calls == [] and record["statements"] == []
    assert error == ("the window ended before cycle 2 started: no slice was "
                     "traced")


def test_a_window_that_ends_inside_the_slice_is_an_error():
    tracing = Slice()
    window(9.3, 0.1, tracing)  # cycle 2 opens at 9.0 s
    record, error = tracing.record(wanted())
    assert tracing.calls == ["start", "stop"], "the profiler is not left on"
    assert 0 < len(record["statements"]) < 12
    assert error.startswith("the window ended inside the traced slice: ")
    assert f"{len(record['statements'])} of the 12 statements" in error


def test_a_slice_that_closes_with_the_window_is_whole():
    """The deadline falls after the slice's last statement and before the
    next pass: nothing is missing, so nothing is wrong."""
    tracing = Slice(cycle=0)
    statements = window(2.05, 0.1, tracing)  # 5 + query93's 1.0 + 6 x 0.1
    assert len(statements) == 12
    assert tracing.record(wanted(cycle=0)) == (
        {"cycle": 0, "passes": 2, "statements": wanted(cycle=0)}, None)


def test_an_untraced_window_runs_the_same_statements_and_traces_none():
    tracing = Slice(wanted=0)
    statements = window(44.95, 0.1, tracing)
    assert tracing.calls == [] and tracing.marks == {}
    assert [(s["stream"], s["name"]) for s in statements] == [
        (s["stream"], s["name"]) for s in window(44.95, 0.1, Slice())]
    # the answers compared are those of the window's first pass alone
    assert [s["first"] for s in statements] == [True] * 6 + [False] * (
        len(statements) - 6)
    # no statement starts after the deadline; the one in flight finishes
    # (4.5 s a cycle: the last statement of the tenth starts at 44.9 s)
    assert len(statements) == 36 * 10
    assert statements[-1]["cycle"] == 9


def _run(tmp_path, **over):
    args = argparse.Namespace(
        workload="sf1-parquet.replay6", seed=SEED, seconds=3.0, trace=1,
        scale=0.01, control=None, trace_cycle=None, data_seed=None,
        cache_dir=str(tmp_path))
    vars(args).update(over)
    return bench_run.Run(args)


def test_the_parent_reports_a_slice_error_and_no_line(tmp_path):
    run = _run(tmp_path)
    words = "the window ended before cycle 2 started: no slice was traced"
    with pytest.raises(lib.BenchmarkError, match=words):
        run.traced({"slice_error": words, "device_trace": {}}, {})


def test_the_cycle_is_the_mixs_own_unless_the_command_names_one(tmp_path):
    run = _run(tmp_path)
    run.prepare()
    assert run.trace_cycle == TRAFFIC["trace_cycle"] == 2
    named = _run(tmp_path, trace_cycle=0)
    named.prepare()
    assert named.trace_cycle == 0
    # the chip child is told; the pass-only child, and an untraced run's, not
    seen = []
    named.stay_off_jax = lambda: None  # this process has jax and no child
    named.spawn = lambda name, cmd, env=None: seen.append(
        [str(c) for c in cmd]) or argparse.Namespace()
    named.chip_child("wh")
    named.chip_child("wh", "pass")
    assert seen[0][seen[0].index("--trace_cycle") + 1] == "0"
    assert "--trace_cycle" not in seen[1]
