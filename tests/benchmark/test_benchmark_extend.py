"""The harness is driven by data: a later PR adds a cell, a configuration, a
traffic mix and a per-layer metric as NEW files and entries, and edits no
file that is there. Here a copy of the benchmark gets one of each, and the
harness finds them by name."""

import json
import os
import shutil

import pytest

import doc_rules
from benchmarks import lib

NEW_CELL = "sf1-parquet-floats.light3"


@pytest.fixture()
def grown(tmp_path):
    shutil.copytree(
        os.path.join(lib.REPO, "benchmarks"), tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns(".cache", "__pycache__", "testdata"))
    doc = lib.load_json(os.path.join(lib.REPO, "BENCHMARK.json"))
    before = {}
    for d, _, files in os.walk(tmp_path / "benchmarks"):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    bench = tmp_path / "benchmarks"
    config = lib.load_json(bench / "configs" / "sf1-parquet-1chip.json")
    config.update(name="sf1-parquet-floats-1chip", decimals=False)
    (bench / "configs" / "sf1-parquet-floats-1chip.json").write_text(
        json.dumps(config))
    (bench / "traffic" / "light3.json").write_text(json.dumps({
        "templates": ["query96", "query3", "query36"],
        "order": "tpcds_stream_permutation", "loop": "closed", "clients": 1,
        "param_seed": 11, "window_passes": 3,
        "control_templates": ["query3"]}))
    (bench / "layer_metrics" / "execute_ms.stmt.py").write_text(
        'LAYER = "executor + fused pipelines"\nUNIT = "ms"\n'
        'MOVES = "stmt_p50_ms"\nSOURCE = "host_clock"\n\n\n'
        "def read(run):\n"
        "    ms = [s['execute_ms'] for s in run['statements']]\n"
        "    return sum(ms) / len(ms) if ms else None\n")
    (bench / "end_to_end" / "query36_p50_ms.py").write_text(
        'from benchmarks import lib\n\nUNIT = "ms"\nSOURCE = "host_clock"\n\n\n'
        "def read(run):\n"
        "    return lib.window_percentile(run, 50, 'query36')\n")
    doc["end_to_end"].append({
        "name": "query36_p50_ms", "unit": "ms", "better": "lower",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["sf1-parquet-floats.light3"]})
    doc["configs"].append({
        "name": "sf1-parquet-floats-1chip", "source": "x", "reduced": [],
        "file": "benchmarks/configs/sf1-parquet-floats-1chip.json", "why": "x"})
    doc["workloads"].append({
        "name": "sf1-parquet-floats.light3", "chips": 1, "why": "x",
        "config": "sf1-parquet-floats-1chip", "traffic": "light3"})
    # the new cell's name after the two that are there, on the list of
    # every accepted per-layer metric it wants read in it, and a 26th
    # entry of its own after the last
    for m in doc["per_layer"][:20]:
        m["workloads"].append(NEW_CELL)
    assert len(doc["per_layer"]) == 25
    doc["per_layer"].append({
        "name": "execute_ms.stmt", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "executor + fused pipelines",
        "moves": "stmt_p50_ms", "workloads": [NEW_CELL]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    yield lib.Spec(str(tmp_path))
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, f"{path} was edited"


def test_a_new_cell_is_found_by_name_alone(grown):
    cell = grown.cell("sf1-parquet-floats.light3")
    assert grown.config(cell)["decimals"] is False
    assert grown.traffic(cell)["templates"] == ["query96", "query3", "query36"]
    names = [m["name"] for m in grown.metrics_of(cell, "per_layer")]
    assert names == doc_rules.FIRST_TWENTY + ["execute_ms.stmt"]
    reader = grown.reader("per_layer", "execute_ms.stmt")
    assert reader.read({"statements": [{"execute_ms": 2.0},
                                       {"execute_ms": 4.0}]}) == 3.0
    assert reader.read({"statements": []}) is None
    # an end-to-end metric of its own, and none that names another cell
    e2e = [m["name"] for m in grown.metrics_of(cell, "end_to_end")]
    assert "query36_p50_ms" in e2e and "query7_p50_ms" not in e2e
    run = {"statements": [{"name": "query36", "status": "Completed", "ms": m}
                          for m in (700.0, 1100.0, 900.0)]}
    assert grown.reader("end_to_end", "query36_p50_ms").read(run) == 900.0
    # and the cells that were there are untouched by it
    old = grown.cell("sf1-parquet.replay6")
    assert "execute_ms.stmt" not in [
        m["name"] for m in grown.metrics_of(old, "per_layer")]


def test_a_document_that_only_grew_keeps_every_rule(grown):
    """What a later PR does (a 26th per-layer entry appended, a third
    cell's name appended to the lists) passes every rule the tests hold the
    per-layer list to, entry by entry and list by list."""
    assert len(grown.doc["per_layer"]) == 26
    assert doc_rules.faults(grown) == []
    for index in range(26):
        assert doc_rules.entry_fault(grown, index) is None
    for name in doc_rules.LISTED:
        assert doc_rules.workloads_fault(grown, name) is None
    assert doc_rules.faults(lib.Spec(lib.REPO)) == []


def _insert_before_the_last_of_the_twenty(doc):
    doc["per_layer"].insert(19, doc["per_layer"].pop())


def _swap_two_of_the_twenty(doc):
    per = doc["per_layer"]
    per[3], per[8] = per[8], per[3]


def _put_the_new_cell_first(doc):
    doc["per_layer"][2]["workloads"].insert(0, NEW_CELL)


def _list_a_parquet_cell_on_a_storage_metric(doc):
    doc["per_layer"][20]["workloads"].append(NEW_CELL)


def _list_the_lakehouse_cell_under_the_parquet_bound(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    e2e["query7_p50_ms"]["workloads"].append(doc_rules.LAKE)


def _give_an_entry_another_unit_than_its_reader(doc):
    doc["per_layer"][-1]["unit"] = "s"


def _name_an_entry_twice(doc):
    doc["per_layer"].append(dict(doc["per_layer"][-1]))


@pytest.mark.parametrize("edit", [
    _insert_before_the_last_of_the_twenty, _swap_two_of_the_twenty,
    _put_the_new_cell_first, _list_a_parquet_cell_on_a_storage_metric,
    _list_the_lakehouse_cell_under_the_parquet_bound,
    _give_an_entry_another_unit_than_its_reader, _name_an_entry_twice],
    ids=lambda f: f.__name__.strip("_"))
def test_an_insertion_a_move_or_a_wrong_list_breaks_a_rule(grown, edit):
    edit(grown.doc)
    assert doc_rules.faults(grown), edit.__name__


def test_what_is_not_there_is_an_error(grown):
    with pytest.raises(lib.BenchmarkError):
        grown.cell("sf1-orc.replay6")
    with pytest.raises(lib.BenchmarkError):
        grown.reader("per_layer", "no_such_metric")
    with pytest.raises(lib.BenchmarkError):
        grown.traffic({"traffic": "no_such_mix"})


def test_the_new_mix_goes_through_the_one_generator(grown):
    cell = grown.cell("sf1-parquet-floats.light3")
    traffic = grown.traffic(cell)
    a = lib.make_streams(traffic, 1, 0, 4)
    assert a == lib.make_streams(traffic, 1, 0, 4)
    assert a[2:] == lib.make_streams(traffic, 1, 2, 2)
    assert [n for n, _ in a[0]] == ["query96", "query3", "query36"]
    for stream in a[1:]:
        assert sorted(n for n, _ in stream) == ["query3", "query36", "query96"]
    # parameters change with every pass: no statement text repeats
    texts = [sql.split("\n", 1)[1].rsplit("-- end", 1)[0]
             for stream in a for _, sql in stream]
    assert len(set(texts)) == len(texts)
    assert lib.make_streams({**traffic, "param_seed": 12}, 1, 0, 4) != a


def test_the_seed_orders_the_passes_and_changes_no_statement(grown):
    traffic = grown.traffic(grown.cell("sf1-parquet-floats.light3"))
    orders = {tuple(lib.window_order(traffic, seed, cycle))
              for seed in (7, 2147483659, 2**31 + 12345) for cycle in range(4)}
    assert all(sorted(o) == [1, 2, 3] for o in orders)
    assert len(orders) > 1, "another seed or cycle, another order"
    assert lib.window_order(traffic, 2**31 + 12345, 2) == \
        lib.window_order(traffic, 2**31 + 12345, 2)


@pytest.mark.parametrize("name,run,want", [
    ("plan_ms.stmt", {"statements": [{"plan_ms": 1.0}, {"plan_ms": 3.0}]}, 2.0),
    ("device_busy_ms.stmt",
     {"device_trace": {"busy_s": 0.5, "statements": 10}}, 50.0),
    ("device_busy_ms.stmt", {}, None),
    ("compiles.window", {"counters": {
        "rehearsal_end": {"jax": {
            "/jax/core/compile/backend_compile_duration": [100, 9.0],
            "/jax/compilation_cache/cache_hits": [40, 0.0]}},
        "window_close": {"jax": {
            "/jax/core/compile/backend_compile_duration": [113, 9.3],
            "/jax/compilation_cache/cache_hits": [51, 0.0]}}}}, 2),
    ("compiles.window", {"counters": {"rehearsal_end": {"jax": {}},
                                      "window_close": {"jax": {}}}}, 0),
    ("exec_lookups.stmt", {"statements": [{"new_shapes": 0}, {"new_shapes": 3}]},
     1.5),
    ("new_execs.rehearsal", {"rehearsal": [
        {"aot_loaded": 1, "aot_compiled": 0}, {"aot_loaded": 0, "aot_compiled": 2}]},
     1.5),
    ("catalog_load_s.first", {
        "marks": {"first_pass_start": 100, "first_pass_end": 200},
        "events": [{"kind": "catalog_load", "ts": 150, "dur_ms": 1500.0},
                   {"kind": "catalog_load", "ts": 250, "dur_ms": 9000.0},
                   {"kind": "aot_cache", "ts": 150, "dur_ms": 7.0}]}, 1.5),
    ("aot_load_s.first", {
        "marks": {"first_pass_start": 100, "first_pass_end": 200},
        "events": [{"kind": "aot_cache", "op": "load", "ts": 150, "dur_ms": 250.0},
                   {"kind": "aot_cache", "op": "store", "ts": 160, "dur_ms": 9.0},
                   {"kind": "aot_cache", "op": "load", "ts": 300, "dur_ms": 9.0}]},
     0.25),
    ("dispatches.stmt", {
        "marks": {"window_open": 100, "window_close": 200},
        "statements": [{}, {}],
        "events": [{"kind": "pipeline_span", "ts": t} for t in (90, 110, 120, 130)]},
     1.5),
])
def test_readers_read_what_they_say(name, run, want):
    assert lib.Spec(lib.REPO).reader("per_layer", name).read(run) == want


def test_events_are_read_from_the_programs_trace_files(tmp_path):
    (tmp_path / "events-a.jsonl").write_text(
        '{"ts": 2, "kind": "x"}\n{"ts": 1, "kind": "y"}\n{"ts": 3, "ki')
    assert [e["ts"] for e in lib.read_events(str(tmp_path))] == [1, 2]
    (tmp_path / "events-b.jsonl").write_text('not json\n{"ts": 1}\n')
    with pytest.raises(lib.BenchmarkError):
        lib.read_events(str(tmp_path))
