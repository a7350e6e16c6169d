"""The four readers of the host's half of a statement (`launch_ms.stmt`,
`retrace_ms.stmt`, `host_phase_ms.stmt`, `host_other_ms.stmt`) over
hand-made runs: the values worked out by hand from spans that carry their
own host time by name, nothing from a program whose spans do not (the
parent commit), and the four adding up to `exec_host_ms.stmt` of the run."""

import pytest

import doc_rules
from benchmarks import lib

from test_benchmark_spans import EVENTS, run_with, span

NAMES = ["launch_ms.stmt", "retrace_ms.stmt", "host_phase_ms.stmt",
         "host_other_ms.stmt"]

#: what the program adds to the spans of `test_benchmark_spans.EVENTS`, by
#: (kind, exec_id): the slice's executions are 8 and 9, execution 7 ran in
#: the window before the slice
NAMED = {
    ("op_span", 7): dict(launch_ms_by={"take_rows": 500.0}, compile_ms={},
                         host_ms={"scan": 500.0}),
    ("op_span", 8): dict(
        launch_ms_by={"take_rows": 14.0, "dense_probe": 2.0,
                      "eager:join": 24.0},
        eager_calls={"join": 3},
        compile_ms={"trace": 30.0, "load": 10.0},
        host_ms={"pipeline-build": 12.0, "exec-lookup": 1.0},
        host_iv=[["take_rows", 10, 14000]]),
    ("result_span", 8): dict(launch_ms_by={"compact_indices": 1.0},
                             compile_ms={}, host_ms={"to-arrow": 3.0}),
    ("op_span", 9): dict(launch_ms_by={"fused_agg_pipeline": 5.0},
                         compile_ms={}, host_ms={"span-emit": 2.0}),
    ("result_span", 9): dict(launch_ms_by={}, compile_ms={},
                             host_ms={"to-arrow": 2.0}),
}

WITH_FIELDS = [
    {**e, **NAMED.get((e["kind"], e.get("exec_id")), {})} for e in EVENTS
] + [
    # another process's executor 8, inside the slice: not this statement's
    {**span("op_span", 1123, 50, exec_id=8,
            launch_ms_by={"take_rows": 999.0}, compile_ms={"trace": 999.0},
            host_ms={"scan": 999.0}), "app": "other"},
]

WANT = {
    # (14 + 2 + 24 + 1) + 5 over the slice's two statements
    "launch_ms.stmt": 46.0 / 2,
    # the first statement's trace 30 + load 10
    "retrace_ms.stmt": 40.0 / 2,
    # (12 + 1 + 3) + (2 + 2)
    "host_phase_ms.stmt": 20.0 / 2,
    # exec_host_ms.stmt's 200 a statement less the three above
    "host_other_ms.stmt": 200.0 - 23.0 - 20.0 - 10.0,
}


@pytest.mark.parametrize("name", NAMES)
def test_host_split_reader_over_a_run_with_the_fields(name):
    reader = lib.Spec(lib.REPO).reader("per_layer", name)
    assert reader.read(run_with(WITH_FIELDS)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_host_split_reader_reports_nothing_without_the_fields(name):
    """The parent's spans carry `launches` and `launch_ms` and none of the
    three fields: nothing to read, which is not a reading of 0."""
    reader = lib.Spec(lib.REPO).reader("per_layer", name)
    assert reader.read(run_with(EVENTS)) is None
    assert reader.read(run_with([])) is None
    untraced = run_with(WITH_FIELDS)
    del untraced["marks"]["slice_start"], untraced["marks"]["slice_end"]
    assert reader.read(untraced) is None


def test_the_four_add_up_to_exec_host_ms_of_the_same_run():
    spec = lib.Spec(lib.REPO)
    run = run_with(WITH_FIELDS)
    whole = spec.reader("per_layer", "exec_host_ms.stmt").read(run)
    parts = [spec.reader("per_layer", n).read(run) for n in NAMES]
    assert sum(parts) == pytest.approx(whole) and whole == 200.0
    # the fields change nothing the readers before them read
    for name in ("launches.stmt", "read_wait_ms.stmt", "host_reads.stmt"):
        reader = spec.reader("per_layer", name)
        assert reader.read(run) == reader.read(run_with(EVENTS))


def test_a_span_with_one_of_the_fields_is_a_program_with_them():
    """A run whose slice held no compile stage and no phase still reads 0
    for those, not nothing: the fields are there and empty."""
    events = [{**e, "launch_ms_by": {}, "compile_ms": {}, "host_ms": {}}
              if e["kind"] in ("op_span", "result_span") else e
              for e in EVENTS]
    spec = lib.Spec(lib.REPO)
    got = {n: spec.reader("per_layer", n).read(run_with(events))
           for n in NAMES}
    assert got == {"launch_ms.stmt": 0.0, "retrace_ms.stmt": 0.0,
                   "host_phase_ms.stmt": 0.0, "host_other_ms.stmt": 200.0}


@pytest.mark.parametrize("name", NAMES)
def test_host_split_reader_declares_what_its_entry_carries(name):
    spec = lib.Spec(lib.REPO)
    reader = spec.reader("per_layer", name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "executor + fused pipelines", "ms", "stmt_p50_ms", "program_span")
    entry, = [m for m in spec.doc["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"],
            entry["better"]) == (reader.LAYER, reader.UNIT, reader.MOVES,
                                 reader.SOURCE, "lower")
    # read in the two `replay6` cells; a later cell appends its name
    cells = entry["workloads"]
    assert cells[:2] == [doc_rules.PARQUET, doc_rules.LAKE]
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert names.index(name) > names.index("feedback_io_ms.stmt")
    assert doc_rules.entry_fault(spec, names.index(name)) is None
