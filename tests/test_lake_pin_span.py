"""The `lake_pin` span: a statement over a lakehouse warehouse emits one a
scanned table, every `Scan` of the statement carries that one version, and a
session with no lakehouse table emits none. And what a scan whose every
file the zone map pruned hands on: a table of no rows, whose string
columns have empty dictionaries."""

import io

import pyarrow as pa
import pytest

from nds_tpu.engine import plan as P
from nds_tpu.engine.session import Session
from nds_tpu.lakehouse.table import LakehouseTable
from nds_tpu.obs import critpath as CP
from nds_tpu.obs import reader as R
from nds_tpu.obs.trace import EVENT_SCHEMA, Tracer

PIN_FIELDS = ("table", "version", "moved", "lease", "dur_ms", "t0_ns")


def _table(tmp_path, name, cols, chunks=2):
    """A lakehouse table of `chunks` commits, clustered by its first
    column into files of a narrow key range each."""
    path = str(tmp_path / name)
    lt = LakehouseTable.create(
        path, schema=pa.schema([(c, a.type) for c, a in cols.items()]))
    n = len(next(iter(cols.values())))
    step = n // chunks
    for i in range(chunks):
        lt.ingest_chunk(
            pa.table({c: a.slice(i * step, step) for c, a in cols.items()}),
            f"{name}:c{i}", cluster_by=next(iter(cols)), max_file_bytes=2000)
    return path


@pytest.fixture()
def lake(tmp_path):
    n = 600
    fact = _table(tmp_path, "fact", {
        "k": pa.array(list(range(n)), pa.int64()),
        "d": pa.array([i % 20 for i in range(n)], pa.int64())})
    dim = _table(tmp_path, "dim", {
        "d": pa.array(list(range(20)), pa.int64()),
        "state": pa.array(["TN" if i % 2 else "GA" for i in range(20)])})
    s = Session(conf={"lakehouse.warehouse": str(tmp_path)})
    s.tracer = Tracer()
    s.register_lakehouse("fact", fact)
    s.register_lakehouse("dim", dim)
    return s


def _pins(session):
    return [e for e in session.tracer.events if e["kind"] == "lake_pin"]


def test_lake_pin_is_in_the_schema_beside_scan_prune():
    assert EVENT_SCHEMA["lake_pin"] == PIN_FIELDS
    assert "t0_ns" in EVENT_SCHEMA["scan_prune"]


def test_a_statement_emits_one_lake_pin_a_scanned_table(lake):
    """`fact` is scanned twice and pinned once; the first statement moves
    the pin and acquires the lease, the second finds both and renews."""
    q = ("select count(*) as n from fact a, fact b, dim "
         "where a.k = b.k and a.d = dim.d and a.k between 100 and 150")
    got = lake.sql(q).collect().to_pydict()
    assert got == {"n": [51]}
    first = _pins(lake)
    assert sorted(e["table"] for e in first) == ["dim", "fact"]
    for e in first:
        assert set(PIN_FIELDS) <= set(e)
        assert (e["moved"], e["lease"]) == (True, "acquire")
        assert isinstance(e["t0_ns"], int) and e["dur_ms"] >= 0
        assert e["ts"] >= e["t0_ns"] // 1_000_000 - 1
    assert R.validate_events(lake.tracer.events) == []
    lake.sql(q).collect()
    again = _pins(lake)[len(first):]
    assert sorted(e["table"] for e in again) == ["dim", "fact"]
    assert {(e["moved"], e["lease"]) for e in again} == {(False, "renew")}
    assert {e["table"]: e["version"] for e in again} == \
        {e["table"]: e["version"] for e in first}


def test_every_scan_of_the_statement_carries_the_pinned_version(lake):
    plan = lake.sql(
        "select a.k from fact a, fact b, dim where a.k = b.k and a.d = dim.d "
        "and dim.state = 'TN' and b.k < 40").plan
    scans = [n for n in P.walk_plan(plan) if isinstance(n, P.Scan)]
    assert sorted(n.table for n in scans) == ["dim", "fact", "fact"]
    pinned = {e["table"]: e["version"] for e in _pins(lake)}
    assert len(_pins(lake)) == 2
    for n in scans:
        assert n.lake_version == pinned[n.table]
    # a commit beside the session moves the next statement's pin, all of it
    LakehouseTable(lake.catalog.entries["dim"].path).append(pa.table({
        "d": pa.array([99], pa.int64()), "state": pa.array(["AL"])}))
    before = len(_pins(lake))
    plan = lake.sql("select count(*) as n from dim x, dim y "
                    "where x.d = y.d").plan
    (pin,) = _pins(lake)[before:]
    assert pin["version"] == pinned["dim"] + 1 and pin["moved"] is True
    assert {n.lake_version for n in P.walk_plan(plan)
            if isinstance(n, P.Scan)} == {pin["version"]}


def test_a_held_pin_resolves_nothing(lake):
    lake.sql("select count(*) as n from dim").collect()
    before = len(_pins(lake))
    with lake.catalog.hold_pins(["dim"]):
        lake.sql("select count(*) as n from dim where d < 5").collect()
    (pin,) = _pins(lake)[before:]
    assert (pin["moved"], pin["lease"]) == (False, "held")


def test_a_session_with_no_lakehouse_table_emits_none():
    s = Session()
    s.tracer = Tracer()
    s.register_arrow("t", pa.table({"k": [1, 2, 3], "v": [4, 5, 6]}))
    assert s.sql("select sum(v) as v from t where k > 1").collect() \
        .to_pydict() == {"v": [11]}
    kinds = {e["kind"] for e in s.tracer.events}
    assert "lake_pin" not in kinds and "scan_prune" not in kinds
    # and an arrow table scanned beside a lakehouse table has no pin
    assert s.catalog.pin_lakehouse("t") is None


def test_an_untraced_session_pins_all_the_same(tmp_path):
    path = _table(tmp_path, "t", {"k": pa.array(list(range(100)), pa.int64())})
    s = Session(conf={"lakehouse.warehouse": str(tmp_path)})
    s.register_lakehouse("t", path)
    s.tracer = None  # a session otherwise keeps a ring-only tracer
    plan = s.sql("select count(*) as n from t where k < 10").plan
    (scan,) = [n for n in P.walk_plan(plan) if isinstance(n, P.Scan)]
    assert scan.lake_version == s.catalog.entries["t"].pinned_version == 3


def _ev(kind, start_ms, dur_ms, **fields):
    return {"kind": kind, "app": "a", "query": "q", "dur_ms": dur_ms,
            "t0_ns": int(start_ms * 1e6), "ts": int(start_ms + dur_ms),
            **fields}


def test_profile_shows_pin_and_prune_under_plan():
    """A 100 ms statement whose result_span takes 80: of the 20 ms of
    planning, 6 are two pins and 1.5 is a prune; the rest is plan-host."""
    events = [
        _ev("query_span", 0, 100, status="Completed", retries=0),
        _ev("lake_pin", 1, 4.0, table="store_sales", version=5, moved=False,
            lease="renew"),
        _ev("lake_pin", 5, 2.0, table="date_dim", version=5, moved=False,
            lease="renew"),
        _ev("scan_prune", 8, 1.5, table="date_dim", files_total=4,
            files_pruned=3, rows_bound=18262),
        _ev("result_span", 20, 80, exec_id=1, exec_ms=70.0, to_arrow_ms=10.0),
    ]
    cp = CP.critical_path(events)
    c = cp["queries"]["q"]["causes"]
    assert c["snapshot-pin"] == 6.0 and c["prune-planning"] == 1.5
    assert c["plan-host"] == pytest.approx(12.5)
    out = io.StringIO()
    CP.render(cp, out)
    assert "snapshot-pin" in out.getvalue()
    assert "prune-planning" in out.getvalue()
    # a parquet statement's table has neither line
    cp = CP.critical_path([e for e in events
                           if e["kind"] in ("query_span", "result_span")])
    out = io.StringIO()
    CP.render(cp, out)
    assert "snapshot-pin" not in out.getvalue()
    assert cp["queries"]["q"]["causes"]["plan-host"] == 20.0


# -- a scan whose every file was pruned --------------------------------------

@pytest.mark.parametrize("where, want", [
    ("state in ('ZZ', 'ZY')", 0),       # above every file's range
    ("state like 'ZZ%' and state >= 'ZZ'", 0),
    ("upper(state) = 'ZZ' and state >= 'ZZ'", 0),
    ("state || state = 'ZZZZ' and state >= 'ZZ'", 0),
    ("state in ('GA', 'ZZ')", 10),      # the control: one value is there
], ids=["inlist", "like", "transform", "concat", "control"])
def test_a_scan_pruned_to_nothing_answers_like_a_full_scan(lake, where, want):
    """The zone map may prune every file of a scan: the table then has no
    rows, and its string column a dictionary of one value no row refers to
    (`column_from_arrow`), so that the filter, which still runs, has
    something to look its codes up in (query36's `s_state in (...)` over
    `store` at SF0.01 gathered from an empty table before)."""
    q = f"select count(*) as n from fact, dim where fact.d = dim.d and {where}"
    on = lake.sql(q).collect().to_pydict()
    off = Session(conf={"lakehouse.warehouse": lake.conf["lakehouse.warehouse"],
                        "engine.lake_prune": "off"})
    for name in ("fact", "dim"):
        off.register_lakehouse(name, lake.catalog.entries[name].path)
    assert on == off.sql(q).collect().to_pydict() == {"n": [want * 30]}
    prunes = [e for e in lake.tracer.events if e["kind"] == "scan_prune"
              and e["table"] == "dim"]
    assert prunes
    if not want:
        assert prunes[-1]["files_pruned"] == prunes[-1]["files_total"]
