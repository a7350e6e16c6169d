"""The readers of the program's `host_read`, `result_span`, `xla_compile`
and split `catalog_load` events, each over a hand-made run: the value
worked out by hand, and nothing where the program wrote no such event (a
program from before these spans, as the parent commit is)."""

import pytest

import doc_rules
from benchmarks import lib

S = 1_000_000_000  # ns in a second; the run's clock starts at t = 1000 s


def span(kind, start_s, dur_ms, **fields):
    """One event: `ts` its end in epoch ms, `t0_ns` its start."""
    return {"kind": kind, "app": "a", "t0_ns": int(start_s * S),
            "dur_ms": dur_ms, "ts": int(start_s * 1e3 + dur_ms), **fields}


def run_with(events):
    return {
        "marks": {"first_pass_start": 1000e3, "first_pass_end": 1060e3,
                  "rehearsal_start": 1060e3, "rehearsal_end": 1080e3,
                  "window_open": 1100e3, "window_close": 1145e3,
                  "slice_start": 1120e3, "slice_end": 1130e3},
        "first_pass": {"power_test_ms": 50_000},
        "rehearsal": [{"ms": 500.0}] * 4,
        "statements": [{"name": "query3", "status": "Completed"}] * 4,
        "events": sorted(events, key=lambda e: e["ts"]),
    }


EVENTS = [
    # first pass: two table loads, compile stages (a trace nested in a
    # trace), an AOT load, a read
    span("catalog_load", 1001, 4000, table="store_sales", read_ms=2500.0,
         encode_ms=1000.0, h2d_ms=500.0),
    span("catalog_load", 1010, 1000, table="item", read_ms=300.0,
         encode_ms=600.0, h2d_ms=100.0),
    span("xla_compile", 1020, 3000, stage="trace", fun="outer", cached=False),
    span("xla_compile", 1021, 1000, stage="trace", fun="inner", cached=False),
    span("xla_compile", 1023, 500, stage="lower", fun="outer", cached=False),
    span("xla_compile", 1024, 6000, stage="compile", fun="outer", cached=True),
    span("xla_compile", 1031, 2000, stage="compile", fun="gather",
         cached=False),
    span("aot_cache", 1040, 250, op="load", result="hit"),
    span("host_read", 1041, 1500, why="nrows", bytes=4, exec_id=1, depth=2),
    # rehearsal: three compiles, two of them fresh
    span("xla_compile", 1061, 1500, stage="compile", fun="gather",
         cached=False),
    span("xla_compile", 1063, 100, stage="compile", fun="_pad", cached=True),
    span("xla_compile", 1064, 1700, stage="compile", fun="gather",
         cached=False),
    span("xla_compile", 1066, 40, stage="trace", fun="gather", cached=False),
    # window, outside the slice: one statement
    span("op_span", 1101, 300, exec_id=7, launches={"take_rows": 20},
         launch_ms=12.0, reads=2, read_wait_ms=100.0),
    span("host_read", 1101.1, 60, why="nrows", bytes=4, exec_id=7, depth=0),
    span("host_read", 1101.2, 40, why="collect", bytes=4096, exec_id=7,
         depth=-1),
    span("result_span", 1101, 320, exec_id=7, exec_ms=300.0,
         to_arrow_ms=20.0, launches={"compact_indices": 1}),
    # the slice: two statements
    span("op_span", 1121, 380, exec_id=8,
         launches={"take_rows": 30, "dense_probe": 4}, launch_ms=20.0,
         reads=3, read_wait_ms=150.0),
    span("host_read", 1121.1, 100, why="nrows", bytes=4, exec_id=8, depth=0),
    span("host_read", 1121.2, 30, why="ngroups", bytes=4, exec_id=8, depth=0),
    span("host_read", 1121.3, 20, why="collect", bytes=4096, exec_id=8,
         depth=-1),
    span("result_span", 1121, 400, exec_id=8, exec_ms=380.0, to_arrow_ms=20.0,
         launches={}),
    span("op_span", 1122, 190, exec_id=9, launches={"fused_agg_pipeline": 1},
         launch_ms=5.0, reads=1, read_wait_ms=50.0),
    span("host_read", 1122.1, 50, why="ngroups", bytes=4, exec_id=9, depth=0),
    span("result_span", 1122, 200, exec_id=9, exec_ms=190.0, to_arrow_ms=10.0,
         launches={}),
    # a read of another process's executor 8: not this slice's statement
    {**span("host_read", 1123, 999, why="nrows", bytes=4, exec_id=8, depth=0),
     "app": "other"},
]

#: the same run as a program without the new spans and fields writes it
OLD_EVENTS = [
    {k: v for k, v in e.items()
     if k not in ("t0_ns", "read_ms", "encode_ms", "h2d_ms", "launches",
                  "launch_ms", "reads", "read_wait_ms")}
    for e in EVENTS
    if e["kind"] in ("catalog_load", "aot_cache", "op_span")
]

WANT = {
    # (20+1) + (34) + (1) launches over 4 window statements
    "launches.stmt": 56 / 4,
    # 2 + 3 + 1 + the other process's 1, over 4 statements
    "host_reads.stmt": 7 / 4,
    # the reads of the slice's executions, (100+30+20+50) ms over 2
    "read_wait_ms.stmt": 100.0,
    # (400 + 200) - (150 + 50) of these executions' own reads, over 2
    "exec_host_ms.stmt": 200.0,
    "table_read_s.first": 4.4,
    "h2d_s.first": 0.6,
    # trace 1020..1023 (the nested one inside it) + lower 1023..1023.5
    "jit_trace_s.first": 3.5,
    "xla_load_s.first": 8.0,
    # 50 s less loads 5, compile stages 1020..1023.5 + 1024..1030 +
    # 1031..1033, AOT load 0.25, read 1.5
    "unspanned_s.first": 50 - (5 + 11.5 + 0.25 + 1.5),
    "fresh_compiles.rehearsal": 2 / 4,
    # of the first pass's two compile stages one was served from disk
    "fresh_compiles.first": 1,
    "fresh_compile_s.first": 2.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value_over_a_hand_made_run(name):
    reader = lib.Spec(lib.REPO).reader("per_layer", name)
    assert reader.read(run_with(EVENTS)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reports_nothing_for_a_program_without_the_spans(name):
    reader = lib.Spec(lib.REPO).reader("per_layer", name)
    assert reader.read(run_with(OLD_EVENTS)) is None
    assert reader.read(run_with([])) is None


@pytest.mark.parametrize("name", [
    "read_wait_ms.stmt", "exec_host_ms.stmt"])
def test_slice_readers_need_a_traced_slice(name):
    """An untraced run has no `slice_start` mark: nothing to read."""
    run = run_with(EVENTS)
    del run["marks"]["slice_start"], run["marks"]["slice_end"]
    reader = lib.Spec(lib.REPO).reader("per_layer", name)
    assert reader.read(run) is None


def test_fresh_compiles_count_overlapping_compiles_each_and_their_seconds_once():
    """Two threads compiling at once: two programs, the seconds of the
    union; a pass whose every program came from disk reads 0, not nothing."""
    events = [
        span("xla_compile", 1010, 2000, stage="compile", fun="a", cached=False),
        span("xla_compile", 1011, 3000, stage="compile", fun="b", cached=False),
        span("xla_compile", 1020, 500, stage="compile", fun="c", cached=True),
        # the rehearsal's are not the first pass's
        span("xla_compile", 1061, 1500, stage="compile", fun="d", cached=False),
    ]
    spec = lib.Spec(lib.REPO)
    count = spec.reader("per_layer", "fresh_compiles.first")
    seconds = spec.reader("per_layer", "fresh_compile_s.first")
    assert count.read(run_with(events)) == 2
    assert seconds.read(run_with(events)) == pytest.approx(4.0)
    warm = run_with(events[2:])
    assert count.read(warm) == 0 and seconds.read(warm) == 0.0


def test_fresh_compile_readers_over_a_first_pass_recorded_on_the_chip():
    """`benchmarks/testdata/first_pass_compiles.v5e.json`: the compile
    stages one first pass wrote on a v5e. 372 programs went through XLA and
    231 of them compiled anew, in 13.89 s of the 15.51 s all of them took."""
    import os

    run = lib.load_json(os.path.join(
        lib.REPO, "benchmarks", "testdata", "first_pass_compiles.v5e.json"))
    assert len(run["events"]) == 372
    spec = lib.Spec(lib.REPO)
    read = {name: spec.reader("per_layer", name).read(run) for name in (
        "fresh_compiles.first", "fresh_compile_s.first", "xla_load_s.first")}
    assert read["fresh_compiles.first"] == 231
    assert read["fresh_compile_s.first"] == pytest.approx(13.8903, abs=1e-3)
    assert read["xla_load_s.first"] == pytest.approx(15.5129, abs=1e-3)
    # the rehearsal's reader finds none of them: they all ended before it
    assert spec.reader("per_layer", "fresh_compiles.rehearsal").read(
        {**run, "rehearsal": [{"ms": 1.0}]}) is None


@pytest.mark.parametrize(
    "index", range(len(lib.Spec(lib.REPO).doc["per_layer"])))
def test_the_new_metrics_are_appended_entries(index):
    """The twenty entries PR 28 left are the first twenty, each in its
    place; every entry after them has a name of its own and a reader that
    declares what it says. Appending is free, inserting and moving are not."""
    assert doc_rules.entry_fault(lib.Spec(lib.REPO), index) is None


def test_the_first_twenty_are_all_there():
    names = [m["name"] for m in lib.Spec(lib.REPO).doc["per_layer"]]
    assert names[:20] == doc_rules.FIRST_TWENTY
    assert len(set(names)) == len(names)
