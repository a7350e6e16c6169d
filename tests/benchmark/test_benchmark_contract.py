"""`BENCHMARK.json` and the files it names, held to the contract the driver
checks before any run: exact keys, allowed characters, every metric's reader
agreeing with its entry, the percentile rule, the result line's keys, and a
benchmark directory small enough that its cache can never have been
committed."""

import json
import os
import re

import pytest

from benchmarks import lib

REPO = lib.REPO
DOC = lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["benchmarks", "tests/benchmark"]
    assert DOC["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    # a full check with all 24 cells must fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (DOC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(DOC)) < 64 * 1024


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in DOC[group]:
            yield group, entry


@pytest.mark.parametrize("group,entry", list(_names()),
                         ids=lambda v: v["name"] if isinstance(v, dict) else v)
def test_entry_keys_names_and_units(group, entry):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }[group]
    assert allowed <= set(entry) <= allowed | (
        {"workloads"} if group in ("end_to_end", "per_layer") else set())
    assert lib.NAME_RE.match(entry["name"])
    for key in ("why", "source", "layer"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]
    if "unit" in entry:
        assert lib.UNIT_RE.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    if group == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.25
    if group == "workloads":
        assert entry["chips"] in (1, 4)
        assert lib.NAME_RE.match(entry["config"])
        assert lib.NAME_RE.match(entry["traffic"])


def test_names_are_unique_and_cells_use_every_config():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in DOC[group]]
        assert len(names) == len(set(names)), group
    metric_names = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    assert {w["config"] for w in DOC["workloads"]} == \
        {c["name"] for c in DOC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert "setup_s" in [m["name"] for m in DOC["end_to_end"]]


@pytest.mark.parametrize("config", DOC["configs"], ids=lambda c: c["name"])
def test_configuration_file_states_what_is_run(config):
    assert PATH_RE.match(config["file"])
    assert config["file"].startswith("benchmarks/configs/")
    body = lib.load_json(os.path.join(REPO, config["file"]))
    assert body["name"] == config["name"]
    for key in config["reduced"]:
        assert lib.NAME_RE.match(key)
        assert key in body and key in body["reduced_why"], key
    assert len(config["reduced"]) <= 16
    # the guarantees and the limits the comparison holds the answers to
    assert body["guarantees"]
    assert body["correct_limits"]["cells_differ"] == 0
    assert 0 < body["correct_limits"]["rel_gap_max"] <= 1e-5
    for part in ("load", "power"):
        assert os.path.isfile(os.path.join(REPO, body[part]["template"]))


@pytest.mark.parametrize("metric", DOC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader_agrees_with_its_entry(metric):
    spec = lib.Spec(REPO)
    reader = spec.reader("per_layer", metric["name"])
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"], metric["source"])
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert metric["moves"] in e2e
    cells = {w["name"] for w in DOC["workloads"]}
    assert set(metric["workloads"]) <= cells
    # each cell it is read in reports the end-to-end metric it moves
    moved = e2e[metric["moves"]]
    assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    # a reader with nothing to read returns nothing
    empty = {"events": [], "statements": [], "marks": {
        k: 0 for k in ("first_pass_start", "first_pass_end", "window_open",
                       "window_close")},
        "counters": {"rehearsal_end": {"jax": {}}, "window_close": {"jax": {}}}}
    if metric["name"] != "compiles.window":  # a count may well read 0
        assert reader.read(empty) is None


#: what a chip child hands over, cut to what the end-to-end readers read
RUN = {
    "marks": {"parent_start": 1000.0, "window_open": 91000.0},
    "first_pass": {"power_test_ms": 55004},
    "first_pass_a_s": 52.122, "first_pass_b_s": 55.004,
    "rehearsal": [{"ms": 500.0}, {"ms": 700.0}],
    "window_s": 4.0,
    "statements": [
        {"name": "query7", "status": "Completed", "ms": 900.0},
        {"name": "query3", "status": "Completed", "ms": 500.0},
        {"name": "query7", "status": "Completed", "ms": 880.0},
        {"name": "query7", "status": "Failed", "ms": 10.0},
        {"name": "query96", "status": "Completed", "ms": 400.0},
        {"name": "query7", "status": "Completed", "ms": 870.0},
    ],
}


@pytest.mark.parametrize("metric,want", [
    ("first_pass_s", 52.122), ("new_stmt_ms", 600.0), ("replay_qps", 1.25),
    ("stmt_p50_ms", 870.0), ("query7_p50_ms", 880.0), ("setup_s", 90.0),
    ("query7_lake_p50_ms", 880.0)])
def test_end_to_end_reader_reads_what_it_says(metric, want):
    """Every end-to-end metric has a reader of its own, found by its name:
    renaming or adding one edits no line of the harness. A statement that
    did not complete counts in no rate and no latency."""
    entry = {m["name"]: m for m in DOC["end_to_end"]}[metric]
    reader = lib.Spec(REPO).reader("end_to_end", metric)
    assert (reader.UNIT, reader.SOURCE) == (entry["unit"], entry["source"])
    assert reader.read(RUN) == pytest.approx(want)


@pytest.mark.parametrize("a,b,want", [
    (52.122, 55.004, 52.122), (61.5, 57.25, 57.25), (40.0, 40.0, 40.0),
    (None, 55.004, None), (52.122, None, None), (None, None, None)])
def test_first_pass_is_the_lower_of_two_and_nothing_of_one(a, b, want):
    """Two fresh processes, the lower reading; a run in which either pass
    left no reading reports no `first_pass_s` (and so no result line)."""
    run = {k: v for k, v in (("first_pass_a_s", a), ("first_pass_b_s", b),
                             ("first_pass", {"power_test_ms": 1}))
           if v is not None}
    assert lib.Spec(REPO).reader("end_to_end", "first_pass_s").read(run) == want


SHARED = ["first_pass_s", "new_stmt_ms", "replay_qps", "stmt_p50_ms"]


def test_every_end_to_end_metric_has_a_reader():
    spec = lib.Spec(REPO)
    for m in DOC["end_to_end"]:
        assert callable(spec.reader("end_to_end", m["name"]).read)
    got = {name: list(spec.read_metrics(spec.cell(name), "end_to_end", RUN))
           for name in ("sf1-parquet.replay6", "sf1-lakehouse.replay6")}
    # query7's median under a bound of each cell's own: device work alone
    # over parquet, the pruned read beside it over the lakehouse
    assert got["sf1-parquet.replay6"] == [*SHARED, "query7_p50_ms", "setup_s"]
    assert got["sf1-lakehouse.replay6"] == [
        *SHARED, "setup_s", "query7_lake_p50_ms"]


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_the_shared_metrics_and_its_own(cell):
    """Whatever cells a later PR adds: each reports the four shared metrics
    and `setup_s`, and beyond them only metrics whose `workloads` list
    names it."""
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    mine = [m["name"] for m in lib.Spec(REPO).metrics_of(cell, "end_to_end")]
    assert [n for n in mine if n in SHARED] == SHARED and "setup_s" in mine
    for name in set(mine) - {*SHARED, "setup_s"}:
        assert cell["name"] in e2e[name]["workloads"], name
    for name in [*SHARED, "setup_s"]:
        assert "workloads" not in e2e[name], name


@pytest.mark.parametrize("name", ["query7_p50_ms", "query7_lake_p50_ms"])
def test_a_window_without_query7_has_no_median_of_it(name):
    """Nothing to read, not 0: the run then reports no such metric and,
    untraced, no result line."""
    run = {"statements": [s for s in RUN["statements"]
                          if s["name"] != "query7"]}
    assert lib.Spec(REPO).reader("end_to_end", name).read(run) is None
    assert lib.Spec(REPO).reader("end_to_end", name).read(
        {"statements": []}) is None


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files_and_metrics(cell):
    spec = lib.Spec(REPO)
    assert spec.config(cell)["chips"] == cell["chips"]
    traffic = spec.traffic(cell)
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    e2e = [m["name"] for m in spec.metrics_of(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_of(cell, "per_layer")


def test_files_under_paths_are_named_from_allowed_characters():
    for top in DOC["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [x for x in dirs if x not in (".cache", "__pycache__")]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert PATH_RE.match(rel), rel


def test_the_cache_is_ignored_and_the_directory_stays_small():
    """`benchmarks/.cache/` holds gigabytes at SF1: it is ignored inside
    `benchmarks/` itself, so a copied tree ignores it too, and what is left
    is far below what a committed warehouse would weigh."""
    with open(os.path.join(REPO, "benchmarks", ".gitignore")) as f:
        assert ".cache/" in f.read().split()
    total = 0
    for d, dirs, files in os.walk(os.path.join(REPO, "benchmarks")):
        dirs[:] = [x for x in dirs if x not in (".cache", "__pycache__")]
        total += sum(os.path.getsize(os.path.join(d, f)) for f in files)
    assert total < 2 << 20, total


@pytest.mark.parametrize("n,p,want", [
    (1, 50, 1), (2, 50, 1), (3, 50, 2), (100, 95, 95), (200, 95, 190),
    (20, 95, 19), (21, 95, 20), (7, 100, 7),
])
def test_percentile_is_nearest_rank(n, p, want):
    assert lib.percentile(list(range(n, 0, -1)), p) == want


@pytest.mark.parametrize("n,p,beyond", [(200, 95, 10), (199, 95, 9),
                                        (100, 90, 10), (40, 50, 20)])
def test_a_tail_needs_ten_samples_beyond_it(n, p, beyond):
    assert lib.samples_beyond(n, p) == beyond


def test_union_of_intervals():
    assert lib.union_seconds([(0, 1), (0.5, 2), (3, 4), (3.2, 3.5)]) == 3.0
    assert lib.union_seconds([]) == 0.0


def test_result_line_has_exactly_the_contracts_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 5}
    compared = {"cells_differ": {"value": 0, "limit": 0}}
    line = json.loads(lib.result_line(
        True, 10, 0, {"setup_s": (95.3127, "s")}, device, compared))
    assert tuple(line) == lib.RESULT_KEYS + ("compared",)
    assert line["metrics"] == {"setup_s": {"value": 95.3127, "unit": "s"}}
    assert line["compared"] == compared
    traced = json.loads(lib.result_line(
        False, 10, 1, {}, device, compared,
        {"device_ops": [], "idle_gaps": []}, first_passes_s=[52.1, 55.0]))
    # the numbers compared come last, whatever else the line carries
    assert tuple(traced) == lib.RESULT_KEYS + (
        "breakdown", "first_passes_s", "compared")
    assert traced["correct"] is False and traced["failed"] == 1


def test_an_unknown_device_is_an_error_not_a_default():
    assert lib.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(lib.BenchmarkError):
        lib.device_peaks("cpu")
