"""The harness is driven by data: a later PR adds a cell, a configuration, a
traffic mix and a per-layer metric as NEW files and entries, and edits no
file that is there. Here a copy of the benchmark is grown by the one recipe
(`grow.py`: two cells, two configurations, two mixes, a metric of each
group), and the harness finds them by name. Every count is taken from the
real document: it grows too."""

import os
import shutil

import pytest

import doc_rules
import grow
from benchmarks import lib

LISTS = ("configs", "workloads", "end_to_end", "per_layer")


def _files(top):
    found = {}
    for d, _, files in os.walk(top):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                found[os.path.join(d, f)] = fh.read()
    return found


@pytest.fixture()
def grown(tmp_path):
    shutil.copytree(
        os.path.join(lib.REPO, "benchmarks"), tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns(".cache", "__pycache__", "testdata"))
    shutil.copy(os.path.join(lib.REPO, "BENCHMARK.json"), tmp_path)
    before = _files(tmp_path / "benchmarks")
    new = grow.grow(str(tmp_path))
    spec = lib.Spec(str(tmp_path))
    spec.new = new  # the names the recipe gave what it added
    yield spec
    after = _files(tmp_path / "benchmarks")
    for path, data in before.items():
        assert after[path] == data, f"{path} was edited"


def test_a_new_cell_is_found_by_name_alone(grown):
    new = grown.new
    cell = grown.cell(new.parquet_cell)
    assert grown.config(cell)["decimals"] is False
    assert grown.traffic(cell)["templates"] == grow.PARQUET_TEMPLATES
    names = [m["name"] for m in grown.metrics_of(cell, "per_layer")]
    assert names == doc_rules.BOTH_FIRST
    # the lakehouse cell: other tables, another count of other templates,
    # the storage metrics too, and a metric of each group that is its alone
    lake = grown.cell(new.lake_cell)
    accepted = grown.config(grown.cell(doc_rules.LAKE))
    assert set(grown.config(lake)["tables"]) - set(accepted["tables"]) == {
        "catalog_sales", "web_sales", "inventory"}
    assert grown.traffic(lake)["templates"] == grow.LAKE_TEMPLATES
    names = [m["name"] for m in grown.metrics_of(lake, "per_layer")]
    assert set(names) == {*doc_rules.BOTH_FIRST, *doc_rules.STORAGE,
                          new.per_layer}
    assert names[-1] == new.per_layer
    reader = grown.reader("per_layer", new.per_layer)
    assert reader.read({"statements": [{"execute_ms": 2.0},
                                       {"execute_ms": 4.0}]}) == 3.0
    assert reader.read({"statements": []}) is None
    # an end-to-end metric of its own, and none that names another cell
    e2e = [m["name"] for m in grown.metrics_of(lake, "end_to_end")]
    assert new.end_to_end in e2e and "query7_p50_ms" not in e2e \
        and "query7_lake_p50_ms" not in e2e
    run = {"statements": [{"name": "query22", "status": "Completed", "ms": m}
                          for m in (700.0, 1100.0, 900.0)]}
    assert grown.reader("end_to_end", new.end_to_end).read(run) == 900.0
    # and the cells that were there are untouched by it
    for name in (doc_rules.PARQUET, doc_rules.LAKE, new.parquet_cell):
        old = grown.cell(name)
        assert new.per_layer not in [
            m["name"] for m in grown.metrics_of(old, "per_layer")]
        assert new.end_to_end not in [
            m["name"] for m in grown.metrics_of(old, "end_to_end")]


def test_a_document_that_only_grew_keeps_every_rule(grown):
    """What a later PR does (a per-layer entry appended after the last, new
    cells' names appended to the lists) passes every rule the tests hold the
    per-layer list to, entry by entry and list by list; and every entry the
    real document has is where it was, its lists longer at the end alone."""
    real = lib.Spec(lib.REPO)
    n = len(real.doc["per_layer"])
    assert len(grown.doc["per_layer"]) == n + 1
    assert doc_rules.faults(grown) == []
    for index in range(n + 1):
        assert doc_rules.entry_fault(grown, index) is None
    for name in doc_rules.LISTED:
        assert doc_rules.workloads_fault(grown, name) is None
    assert doc_rules.faults(real) == []
    for group in LISTS:
        for was, now in zip(real.doc[group], grown.doc[group]):
            cells = was.get("workloads", [])
            assert now.get("workloads", [])[:len(cells)] == cells, was["name"]
            assert {**now, "workloads": cells} == {**was, "workloads": cells}
        assert len(grown.doc[group]) > len(real.doc[group]), group


def _insert_before_the_last_of_the_twenty(doc, new):
    doc["per_layer"].insert(19, doc["per_layer"].pop())


def _swap_two_of_the_twenty(doc, new):
    per = doc["per_layer"]
    per[3], per[8] = per[8], per[3]


def _put_the_new_cell_first(doc, new):
    doc["per_layer"][2]["workloads"].insert(0, new.parquet_cell)


def _cells_of(doc, name):
    entry, = [m for m in doc["per_layer"] if m["name"] == name]
    return entry["workloads"]


def _list_a_parquet_cell_on_a_storage_metric(doc, new):
    _cells_of(doc, doc_rules.STORAGE[0]).append(new.parquet_cell)


def _put_a_third_cell_before_the_second_on_feedback_io(doc, new):
    _cells_of(doc, "feedback_io_ms.stmt").insert(1, new.parquet_cell)


def _list_the_lakehouse_cell_under_the_parquet_bound(doc, new):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    e2e["query7_p50_ms"]["workloads"].append(doc_rules.LAKE)


def _give_an_entry_another_unit_than_its_reader(doc, new):
    doc["per_layer"][-1]["unit"] = "s"


def _name_an_entry_twice(doc, new):
    doc["per_layer"].append(dict(doc["per_layer"][-1]))


@pytest.mark.parametrize("edit", [
    _insert_before_the_last_of_the_twenty, _swap_two_of_the_twenty,
    _put_the_new_cell_first, _list_a_parquet_cell_on_a_storage_metric,
    _put_a_third_cell_before_the_second_on_feedback_io,
    _list_the_lakehouse_cell_under_the_parquet_bound,
    _give_an_entry_another_unit_than_its_reader, _name_an_entry_twice],
    ids=lambda f: f.__name__.strip("_"))
def test_an_insertion_a_move_or_a_wrong_list_breaks_a_rule(grown, edit):
    edit(grown.doc, grown.new)
    assert doc_rules.faults(grown), edit.__name__


def test_what_is_not_there_is_an_error(grown):
    with pytest.raises(lib.BenchmarkError):
        grown.cell("sf1-orc.replay6")
    with pytest.raises(lib.BenchmarkError):
        grown.reader("per_layer", "no_such_metric")
    with pytest.raises(lib.BenchmarkError):
        grown.traffic({"traffic": "no_such_mix"})


def test_the_new_mix_goes_through_the_one_generator(grown):
    cell = grown.cell(grown.new.parquet_cell)
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
    traffic = grown.traffic(grown.cell(grown.new.parquet_cell))
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
