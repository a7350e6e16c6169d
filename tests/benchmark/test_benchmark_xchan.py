"""The cross-channel cell `sf1-lakehouse-3ch.xchan5` and its own readers
(`setop_ms.stmt`, `scalar_subq_ms.stmt`, `scalar_subq_runs.stmt`,
`union_windows.stmt`): each reader over a hand-made run with the value
worked out by hand, and nothing where the program wrote no such event (the
parent commit has no `scalar_subquery`, and its SetOp spans do not say their
`op`); each reader declares what its entry says; the document obeys the
rules of `doc_rules.py` with the third cell in it; the mix's arithmetic
(one pass a cycle: one order for every seed); and the cell itself, once, on
the CPU at SF0.01: `correct` against the reference over fifteen answers,
nothing compiled in the window, the four new metrics in the traced line."""

import json
import os
import subprocess
import sys

import pytest

import doc_rules
from benchmarks import lib
from test_benchmark_run import DRIVER

CELL = "sf1-lakehouse-3ch.xchan5"
TEMPLATES = ["query38", "query2", "query9", "query25", "query22"]
NEW = ("setop_ms.stmt", "scalar_subq_ms.stmt", "scalar_subq_runs.stmt",
       "union_windows.stmt")
#: seconds one run of the rehearsal may take (two runs; the second finds the
#: data, the answers and the compile caches of the first)
REHEARSAL_LIMIT_S = 420


def ev(kind, end_s, dur_ms, **fields):
    """One span: `ts` its end in epoch ms, `t0_ns` its start."""
    return {"kind": kind, "app": "a", "ts": int(end_s * 1e3),
            "t0_ns": int((end_s * 1e3 - dur_ms) * 1e6), "dur_ms": dur_ms,
            **fields}


def op(end_s, dur_ms, exec_id, seq, depth, node, **fields):
    return ev("op_span", end_s, dur_ms, exec_id=exec_id, seq=seq,
              depth=depth, node=node, explain=node, rows=1, est_bytes=8,
              **fields)


def subq(end_s, dur_ms, source, cols_read=23, null=False):
    return ev("scalar_subquery", end_s, dur_ms, out_name="_c1",
              source=source, cols_read=cols_read, null=null)


def run_with(events, statements=5):
    """A traced run whose slice lies between 1120 and 1140 s and holds
    `statements` executions."""
    results = [ev("result_span", 1121 + i, 500.0, exec_id=i + 1)
               for i in range(statements)]
    return {
        "marks": {"first_pass_start": 1000e3, "first_pass_end": 1060e3,
                  "window_open": 1100e3, "window_close": 1150e3,
                  "slice_start": 1120e3, "slice_end": 1140e3},
        "statements": [{"name": "query2", "status": "Completed"}] * 14,
        "events": sorted(events + results, key=lambda e: e["ts"]),
    }


SETOP = dict(left_rows=100, right_rows=50, distinct_rows=None, key_words=None)
CHANGE = [
    # the first pass and the window before the slice: not read
    op(1010, 900.0, 90, 1, 0, "SetOp", op="intersect", **SETOP),
    subq(1011, 700.0, "executed"),
    ev("blocked_union", 1105, 40.0, windows=7, window_rows=512,
       total_rows=6000),
    # the slice, execution 1 (query38): an INTERSECT of an INTERSECT. The
    # inner one took 300 ms, 120 of them its two inputs'; the outer one 450,
    # of which the inner and a 50 ms Aggregate are children
    op(1121.1, 80.0, 1, 1, 2, "Aggregate"),
    op(1121.2, 40.0, 1, 2, 2, "Aggregate"),
    op(1121.5, 300.0, 1, 3, 1, "SetOp", op="intersect", **SETOP),
    op(1121.6, 50.0, 1, 4, 1, "Aggregate"),
    op(1121.9, 450.0, 1, 5, 0, "SetOp", op="intersect", **SETOP),
    # execution 2 (query2): a UNION ALL that was not blocked, 30 ms with two
    # scans of 10 ms under it, and one that was, in 3 windows
    op(1122.1, 10.0, 2, 1, 2, "Scan"),
    op(1122.2, 10.0, 2, 2, 2, "Scan"),
    op(1122.3, 30.0, 2, 3, 1, "SetOp", op="union_all", **SETOP),
    op(1122.9, 400.0, 2, 4, 0, "Aggregate"),
    ev("blocked_union", 1122.95, 25.0, windows=3, window_rows=512,
       total_rows=6000),
    # execution 3 (query9): three subqueries ran (400 + 500 + 300 ms), a
    # fourth was the session cache's
    subq(1123.2, 400.0, "executed"), subq(1123.5, 500.0, "executed"),
    subq(1123.7, 300.0, "executed", null=True),
    subq(1123.8, 0.05, "session-cache", cols_read=0),
    # after the slice closed
    op(1141, 800.0, 9, 1, 0, "SetOp", op="union", **SETOP),
    subq(1142, 600.0, "executed"),
    ev("blocked_union", 1143, 30.0, windows=9, window_rows=512,
       total_rows=6000),
]
#: the same run as the parent's program writes it: no `scalar_subquery`, a
#: SetOp span that does not say its `op`, a `blocked_union` with no duration
PARENT = [
    {k: v for k, v in e.items()
     if k not in SETOP and k != "op"
     and not (e["kind"] == "blocked_union" and k in ("dur_ms", "t0_ns"))}
    for e in CHANGE if e["kind"] != "scalar_subquery"]
#: a program with the spans whose slice holds no union and no subquery
QUIET = [e for e in CHANGE if not 1120 <= e["ts"] / 1e3 <= 1140]

WANT = {
    # (300 - 80 - 40) + (450 - 300 - 50) + (30 - 10 - 10) = 290 ms over 5
    "setop_ms.stmt": {"change": 58.0, "parent": None, "quiet": 0.0},
    # (400 + 500 + 300) ms over 5
    "scalar_subq_ms.stmt": {"change": 240.0, "parent": None, "quiet": 0.0},
    "scalar_subq_runs.stmt": {"change": 0.6, "parent": None, "quiet": 0.0},
    # 3 windows over 5; 0.0 where every union ran as a SetOp
    "union_windows.stmt": {"change": 0.6, "parent": None, "quiet": 0.0},
}
RUNS = {"change": CHANGE, "parent": PARENT, "quiet": QUIET}


@pytest.mark.parametrize("program", sorted(RUNS))
@pytest.mark.parametrize("name", NEW)
def test_reader_value_over_a_hand_made_run(name, program):
    reader = lib.Spec(lib.REPO).reader("per_layer", name)
    want = WANT[name][program]
    got = reader.read(run_with(RUNS[program]))
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", NEW)
def test_reader_reports_nothing_where_the_program_wrote_no_event(name):
    reader = lib.Spec(lib.REPO).reader("per_layer", name)
    assert reader.read(run_with([])) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_needs_a_slice(name):
    """An untraced run has no slice marks and no result spans."""
    reader = lib.Spec(lib.REPO).reader("per_layer", name)
    run = run_with(CHANGE, statements=0)
    del run["marks"]["slice_start"], run["marks"]["slice_end"]
    assert reader.read(run) is None


def test_a_union_all_that_was_not_blocked_reads_zero_windows():
    """0.0 is a reading: the slice's unions ran as SetOp spans."""
    reader = lib.Spec(lib.REPO).reader("per_layer", "union_windows.stmt")
    unblocked = [e for e in CHANGE if e["kind"] != "blocked_union"]
    assert reader.read(run_with(unblocked)) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_declares_its_entry(name):
    spec = lib.Spec(lib.REPO)
    index, = [i for i, m in enumerate(spec.doc["per_layer"])
              if m["name"] == name]
    assert doc_rules.entry_fault(spec, index) is None
    entry = spec.doc["per_layer"][index]
    assert CELL in entry["workloads"]
    assert doc_rules.PARQUET not in entry["workloads"]
    assert doc_rules.LAKE not in entry["workloads"]
    assert entry["layer"] == "executor + fused pipelines"
    assert entry["source"] == "program_span"
    assert lib.UNIT_RE.match(entry["unit"])
    assert entry["moves"] in {m["name"] for m in spec.metrics_of(
        spec.cell(CELL), "end_to_end")}


def test_the_document_obeys_its_rules_with_the_cell_in_it():
    assert doc_rules.faults(lib.Spec(lib.REPO)) == []


@pytest.mark.parametrize("name", doc_rules.BOTH_FIRST + doc_rules.STORAGE + [
    "launch_ms.stmt", "retrace_ms.stmt", "host_phase_ms.stmt",
    "host_other_ms.stmt"])
def test_an_accepted_metric_is_read_in_the_cell_too(name):
    """The cell's name was appended: after the names that were there."""
    entry, = [m for m in lib.Spec(lib.REPO).doc["per_layer"]
              if m["name"] == name]
    cells = entry["workloads"]
    assert cells.index(CELL) > cells.index(doc_rules.LAKE)


def test_the_cell_reports_no_query7_median():
    spec = lib.Spec(lib.REPO)
    names = {m["name"] for m in spec.metrics_of(spec.cell(CELL), "end_to_end")}
    assert not {"query7_p50_ms", "query7_lake_p50_ms"} & names
    assert {"first_pass_s", "new_stmt_ms", "replay_qps", "stmt_p50_ms",
            "setup_s"} <= names


def test_the_cell_is_the_three_channel_configuration_under_xchan5():
    spec = lib.Spec(lib.REPO)
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sf1-lakehouse-3ch-1chip", "xchan5", 1)
    entry, = [c for c in spec.doc["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == ["scale_factor", "query_templates", "tables"]
    assert entry["source"] == spec.config(cell)["source"]
    assert len(entry["source"]) <= 200


def test_the_configuration_states_the_deployment():
    spec = lib.Spec(lib.REPO)
    config = spec.config(spec.cell(CELL))
    lake = spec.config(spec.cell(doc_rules.LAKE))
    assert config["tables"] == sorted(
        lake["tables"] + ["catalog_sales", "web_sales", "inventory"])
    assert config["query_templates"] == len(TEMPLATES)
    # the same database in the same format under the same limits: only the
    # scale of what is loaded and asked differs
    for key in ("scale_factor", "data_seed", "storage_format", "decimals",
                "query_streams", "chips", "load", "power", "correct_limits",
                "assumed"):
        assert config[key] == lake[key], key
    assert len(config["guarantees"]) == 3
    assert sorted(config["reduced_why"]) == [
        "query_templates", "scale_factor", "tables"]
    for table in ("store_sales", "catalog_sales", "web_sales", "inventory"):
        assert table in config["deployment"], table


def test_the_mix_holds_the_issues_parameters():
    spec = lib.Spec(lib.REPO)
    traffic = spec.traffic(spec.cell(CELL))
    assert traffic["templates"] == TEMPLATES
    assert (traffic["order"], traffic["param_seed"], traffic["loop"],
            traffic["clients"]) == (
        "tpcds_stream_permutation", 19620718, "closed", 1)
    assert (traffic["window_passes"], traffic["trace_cycle"],
            traffic["trace_passes"]) == (1, 1, 1)
    assert set(traffic["control_templates"]) <= set(TEMPLATES)
    for key in ("what", "why_these", "stands_for"):
        assert traffic[key]


@pytest.mark.parametrize("seed", [0, 7, 2147483659, 2147620371])
def test_one_pass_a_cycle_is_one_order_for_every_seed(seed):
    """`--seed` draws the order of a cycle's passes; of one pass there is
    one order, so every seed runs the same statements in the same order and
    the slice of `trace_cycle` 1 is stream 1's five."""
    spec = lib.Spec(lib.REPO)
    traffic = spec.traffic(spec.cell(CELL))
    streams = lib.make_streams(traffic, 0.01, 0, 2)
    assert [name for name, _ in streams[0]] == TEMPLATES
    assert sorted(name for name, _ in streams[1]) == sorted(TEMPLATES)
    for cycle in range(4):
        assert lib.window_order(traffic, seed, cycle) == [1]
    assert lib.slice_statements(
        traffic, streams, seed, traffic["trace_cycle"],
        traffic["trace_passes"]) == [[1, name] for name, _ in streams[1]]


def _rehearse(cmd):
    """One run of the cell on the CPU at SF0.01, under its time limit;
    returns (standard output, the result line), held `correct`."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        cmd, env={**env, "JAX_PLATFORMS": "cpu"}, cwd=lib.REPO,
        capture_output=True, text=True, timeout=REHEARSAL_LIMIT_S)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["cells_differ"] == {"value": 0, "limit": 0}
    return p.stdout, line


@pytest.mark.rehearsal
def test_the_cell_rehearsed_on_the_cpu_is_correct(tmp_path):
    """Every phase at SF0.01 through `./nds-tpu-submit` and the lakehouse
    templates with only the look for a chip skipped: untraced, then traced
    with the slice at cycle 0 (a window of 6 s never reaches cycle 1 at
    SF1's pace; here it would, but the command is the rehearsal's)."""
    driver = tmp_path / "driver.py"
    driver.write_text(DRIVER.format(repo=lib.REPO))
    cmd = [sys.executable, str(driver), "--workload", CELL,
           "--seed", "2147483693", "--scale", "0.01", "--seconds", "6",
           "--cache_dir", str(tmp_path / "cache")]
    out, line = _rehearse(cmd + ["--trace", "0"])
    # fifteen answers: both first passes' five and the window's first five
    assert out.count("\nanswer ") == 15, out[-3000:]
    spec = lib.Spec(lib.REPO)
    cell = spec.cell(CELL)
    assert sorted(line["metrics"]) == sorted(
        m["name"] for m in spec.metrics_of(cell, "end_to_end"))
    counters, = [json.loads(ln.split(": ", 1)[1]) for ln in out.splitlines()
                 if ln.startswith("counters at window_close")]
    at_rehearsal, = [
        json.loads(ln.split(": ", 1)[1]) for ln in out.splitlines()
        if ln.startswith("counters at rehearsal_end")]
    assert lib.compiles_of(counters["jax"]) == lib.compiles_of(
        at_rehearsal["jax"])

    out, line = _rehearse(cmd + ["--trace", "1", "--trace_cycle", "0"])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert sorted(metrics) == sorted(
        m["name"] for m in spec.metrics_of(cell, "per_layer"))
    assert set(NEW) <= set(metrics)
    assert metrics["compiles.window"] == 0
    traffic = spec.traffic(cell)
    streams = lib.make_streams(traffic, 0.01, 0, 2)
    assert line["slice"] == {
        "cycle": 0, "passes": 1, "statements": lib.slice_statements(
            traffic, streams, 2147483693, 0, 1)}
    # query9's fifteen subqueries ran in the slice's five statements, and
    # query38's two INTERSECTs and query2's UNION ALL took some time
    assert metrics["scalar_subq_runs.stmt"] == pytest.approx(3.0)
    assert metrics["scalar_subq_ms.stmt"] > 0
    assert metrics["setop_ms.stmt"] > 0
    assert metrics["union_windows.stmt"] >= 0.0
    assert metrics["files_pruned_share.stmt"] >= 0
    assert metrics["lake_pin_ms.stmt"] > 0
