"""The lakehouse cell's own readers (`scan_reads.stmt`, `scan_ms.stmt`,
`files_pruned_share.stmt`, `lake_pin_ms.stmt`) over hand-made runs: the
value worked out by hand, and nothing where the program wrote no such event
(the parent commit has no `lake_pin`; a parquet session prunes nothing).
Their entries, and `feedback_io_ms.stmt`'s, were appended in PR 37 (the
readers shipped ahead of them in PRs 30 and 35): each reader declares what
its entry says, and the rules of `doc_rules.py` say which cells may list
them. And the cell itself, once, on the CPU at SF0.01: it ends `correct`
against the reference, with pruning at work, every table read at one pinned
version and all five in its result line."""

import json
import os
import sys

import pytest

import doc_rules
from benchmarks import lib, run as bench_run
from test_benchmark_run import DRIVER, _run

CELL = "sf1-lakehouse.replay6"
NEW = ("scan_reads.stmt", "scan_ms.stmt", "files_pruned_share.stmt",
       "lake_pin_ms.stmt")
#: the readers that shipped ahead of their `per_layer` entries
WAITING = NEW + ("feedback_io_ms.stmt",)


def ev(kind, end_s, dur_ms, **fields):
    """One span: `ts` its end in epoch ms, `t0_ns` its start."""
    return {"kind": kind, "app": "a", "ts": int(end_s * 1e3),
            "t0_ns": int((end_s * 1e3 - dur_ms) * 1e6), "dur_ms": dur_ms,
            **fields}


def load(end_s, dur_ms, table, loaded):
    return ev("catalog_load", end_s, dur_ms, table=table, columns=3,
              loaded=loaded, rows=1000, cache="miss" if loaded else "hit",
              read_ms=dur_ms / 2, encode_ms=dur_ms / 4, h2d_ms=dur_ms / 4)


def prune(end_s, table, total, pruned):
    return ev("scan_prune", end_s, 0.02, table=table, files_total=total,
              files_pruned=pruned, rows_bound=None)


def pin(end_s, dur_ms, table, moved=False):
    return ev("lake_pin", end_s, dur_ms, table=table, version=5, moved=moved,
              lease="acquire" if moved else "renew")


def run_with(events):
    return {
        "marks": {"first_pass_start": 1000e3, "first_pass_end": 1060e3,
                  "window_open": 1100e3, "window_close": 1145e3},
        "statements": [{"name": "query3", "status": "Completed"}] * 4,
        "events": sorted(events, key=lambda e: e["ts"]),
    }


LAKE = [
    # the first pass: every table read whole, the pins move, one scan pruned
    load(1010, 3000.0, "store_sales", 5), load(1012, 400.0, "date_dim", 2),
    pin(1001, 50.0, "store_sales", moved=True),
    prune(1001.1, "date_dim", 10, 10),
    # the window: three pruned scans read again, one scan served from the
    # device's columns; four pins; three prunes
    pin(1101.0, 2.0, "store_sales"), pin(1101.1, 1.0, "date_dim"),
    prune(1101.2, "date_dim", 4, 3), prune(1101.3, "store", 2, 0),
    load(1101.5, 0.005, "store_sales", 0), load(1101.6, 30.0, "date_dim", 2),
    load(1101.7, 6.0, "store", 2),
    pin(1120.0, 3.0, "store_sales"), pin(1120.1, 2.0, "date_dim"),
    prune(1120.2, "date_dim", 4, 3), load(1120.5, 34.0, "date_dim", 2),
    # after the window closed
    load(1146, 40.0, "date_dim", 2), pin(1146.1, 9.0, "date_dim"),
    prune(1146.2, "date_dim", 4, 4),
]
#: the same window over a parquet warehouse: every scan a hit, no pin, no prune
PARQUET = [load(1010, 9000.0, "store_sales", 5),
           load(1101.5, 0.005, "store_sales", 0),
           load(1120.5, 0.004, "date_dim", 0)]
#: the lakehouse run as the parent's program writes it: no `lake_pin`
PARENT = [e for e in LAKE if e["kind"] != "lake_pin"]

WANT = {
    # (date_dim, store, date_dim) over 4 statements; the hit is no read
    "scan_reads.stmt": {"lake": 3 / 4, "parquet": 0.0, "parent": 3 / 4},
    # (30 + 6 + 34) ms over 4
    "scan_ms.stmt": {"lake": 17.5, "parquet": 0.0, "parent": 17.5},
    # (3 + 0 + 3) of (4 + 2 + 4) files
    "files_pruned_share.stmt": {"lake": 60.0, "parquet": None, "parent": 60.0},
    # (2 + 1 + 3 + 2) ms over 4
    "lake_pin_ms.stmt": {"lake": 2.0, "parquet": None, "parent": None},
}
RUNS = {"lake": LAKE, "parquet": PARQUET, "parent": PARENT}


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


@pytest.mark.parametrize("name", [
    "scan_reads.stmt", "scan_ms.stmt", "lake_pin_ms.stmt"])
def test_per_statement_readers_need_a_window(name):
    reader = lib.Spec(lib.REPO).reader("per_layer", name)
    assert reader.read({**run_with(LAKE), "statements": []}) is None


def test_scan_ms_says_what_one_span_holds():
    doc = lib.Spec(lib.REPO).reader("per_layer", "scan_ms.stmt").__doc__
    for part in ("read", "decode", "encode", "host-to-device"):
        assert part in doc, part


@pytest.mark.parametrize("name", doc_rules.LISTED)
def test_every_accepted_metric_is_read_in_the_new_cell_too(name):
    """Each of the first twenty per-layer lists, and `feedback_io_ms.stmt`'s,
    begins with the two replay6 cells (a later cell appends its name); the
    four storage metrics list lakehouse cells and never the parquet cell;
    query7's median under the parquet cell's bound stays the parquet
    cell's alone."""
    assert doc_rules.workloads_fault(lib.Spec(lib.REPO), name) is None


def test_the_cell_is_the_lakehouse_configuration_under_replay6():
    cell = lib.Spec(lib.REPO).cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sf1-lakehouse-1chip", "replay6", 1)


@pytest.mark.parametrize("name", WAITING)
def test_a_waiting_reader_declares_its_entry(name):
    """What the reader declares is a well-formed entry of a layer and an
    end-to-end metric the benchmark names, and its entry says the same."""
    spec = lib.Spec(lib.REPO)
    reader = spec.reader("per_layer", name)
    assert lib.UNIT_RE.match(reader.UNIT)
    assert reader.SOURCE == "program_span"
    assert reader.MOVES == "stmt_p50_ms"
    assert reader.LAYER in {m["layer"] for m in spec.doc["per_layer"]}
    if name in NEW:
        assert reader.LAYER == "session + catalog"
    entry, = [m for m in spec.doc["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["unit"], entry["moves"],
            entry["source"]) == (reader.LAYER, reader.UNIT,
                                 reader.MOVES, reader.SOURCE)
    assert CELL in entry["workloads"]
    assert ("sf1-parquet.replay6" in entry["workloads"]) == (name not in NEW)


def test_the_configuration_states_the_deployment():
    spec = lib.Spec(lib.REPO)
    config = spec.config(spec.cell(CELL))
    parquet = spec.config(spec.cell("sf1-parquet.replay6"))
    assert config["storage_format"] == "lakehouse"
    assert config["load"]["flags"][:2] == ["--output_format", "lakehouse"]
    assert config["power"]["template"].endswith(
        "power_run_tpu_lakehouse.template")
    # no shape of the source differs from the parquet cell's
    for key in ("scale_factor", "decimals", "query_streams", "chips",
                "query_templates", "tables", "correct_limits"):
        assert config[key] == parquet[key], key
    assert len(config["guarantees"]) == 3
    assert "table_format" in config["assumed"]
    assert os.path.isfile(os.path.join(
        lib.REPO, config["reference"].split(":")[0]))


@pytest.mark.rehearsal
def test_the_cell_rehearsed_on_the_cpu_is_correct_and_prunes(tmp_path):
    """One traced run at SF0.01 with only the look for a chip skipped: every
    phase through `./nds-tpu-submit` and the lakehouse templates, the
    answers against sqlite's, and the new readers over the program's own
    trace."""
    driver = tmp_path / "driver.py"
    driver.write_text(DRIVER.format(repo=lib.REPO))
    p = _run([sys.executable, str(driver), "--workload", CELL,
              "--seed", "2147483659", "--seconds", "10", "--trace", "1",
              "--scale", "0.01", "--trace_cycle", "0"],
             str(tmp_path / "cache"))
    out = p.stdout.strip().splitlines()
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    line = json.loads(out[-1])
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["failed"] == 0
    assert line["compared"]["cells_differ"] == {"value": 0, "limit": 0}
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    spec = lib.Spec(lib.REPO)
    assert sorted(metrics) == sorted(
        m["name"] for m in spec.metrics_of(spec.cell(CELL), "per_layer"))
    assert set(metrics) >= set(doc_rules.LISTED) - {"query7_p50_ms"}
    assert metrics["compiles.window"] == 0
    # the five that waited for their entries are in the line now, read by
    # the harness over the program's own trace
    assert metrics["files_pruned_share.stmt"] > 0
    assert metrics["scan_reads.stmt"] > 0 and metrics["scan_ms.stmt"] > 0
    assert metrics["lake_pin_ms.stmt"] > 0
    assert metrics["feedback_io_ms.stmt"] == 0.0
    # the slice is the first two passes of the cycle the command named
    traffic = spec.traffic(spec.cell(CELL))
    assert line["slice"] == {
        "cycle": 0, "passes": 2, "statements": lib.slice_statements(
            traffic, lib.make_streams(traffic, 0.01, 0, 7), 2147483659, 0, 2)}
    run_dir = os.path.join(str(tmp_path / "cache"), "runs",
                           f"{CELL}-2147483659-t1")
    child = bench_run.load_child(run_dir)
    child["events"] = lib.read_events(os.path.join(run_dir, "trace"))
    # guarantee (b) as far as a read-only run shows it: a process pins each
    # table at one version, and only its first pin of a table moves
    pins = {}
    for e in child["events"]:
        if e["kind"] == "lake_pin":
            pins.setdefault((e["app"], e["table"]), []).append(e)
    assert len({table for _, table in pins}) == 11
    for key, of_table in pins.items():
        of_table.sort(key=lambda e: e["t0_ns"])
        assert len({e["version"] for e in of_table}) == 1, key
        assert [e["moved"] for e in of_table] == \
            [True] + [False] * (len(of_table) - 1), key
