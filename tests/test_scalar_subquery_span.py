"""The `scalar_subquery` event against a plain reference: a statement with
scalar subqueries (one text written twice, two over an empty relation) emits
one event a distinct subquery plan, `source` executed the first time and
session-cache when the statement comes again, `null` where the subquery
yields no row, and the answer is sqlite's. A catalog registration drops the
session's cache and the plans run again. With no tracer bound: no event and
the same answers. And the blocked union's event carries its span."""

import sqlite3

import numpy as np
import pyarrow as pa

from nds_tpu import faults
from nds_tpu.engine import expr as E
from nds_tpu.engine import plan as P
from nds_tpu.engine.session import Session
from nds_tpu.obs import critpath as CP
from nds_tpu.obs import reader as R
from nds_tpu.obs.trace import EVENT_SCHEMA, Tracer

STATEMENT = (
    "select (select max(v) from fact where k = 2) as hi, "
    "(select count(*) from fact where v > 10) as n, "
    "(select max(v) from fact where k = 2) as hi_again, "
    "(select min(v) from vacant) as null_there, "
    "(select v from vacant) as no_row, "
    "case when (select count(*) from fact where v > 10) > 5 "
    "then (select sum(v) from fact where k = 1) "
    "else (select sum(v) from fact where k = 3) end as picked "
    "from one")


def _data(seed=5):
    rng = np.random.default_rng(seed)
    return {
        "fact": {"k": [int(v) for v in rng.integers(0, 4, 400)],
                 "v": [int(v) for v in rng.integers(-20, 40, 400)],
                 "w": [int(v) for v in rng.integers(0, 9, 400)]},
        "vacant": {"v": []},
        "one": {"x": [1]},
    }


def _session(traced):
    # without the flight recorder a session has no tracer at all
    s = Session(conf={"engine.flight_recorder": "off"})
    assert s.tracer is None
    if traced:
        s.tracer = Tracer()
    for name, cols in _data().items():
        s.register_arrow(name, pa.table(
            {c: pa.array(v, pa.int64()) for c, v in cols.items()}))
    return s


def _sqlite(statement):
    db = sqlite3.connect(":memory:")
    for name, cols in _data().items():
        db.execute(f"create table {name} ({', '.join(cols)})")
        db.executemany(
            f"insert into {name} values ({', '.join('?' * len(cols))})",
            list(zip(*cols.values())))
    return [tuple(r) for r in db.execute(statement)]


def _run(session, statement=STATEMENT):
    with faults.scope("q_subq"):
        got = session.sql(statement).collect()
    return [tuple(r.values()) for r in got.to_pylist()]


def _events(session):
    return [e for e in session.tracer.events
            if e["kind"] == "scalar_subquery"]


def _subquery_plans(session, statement=STATEMENT):
    plan = session.sql(statement).plan
    return {id(e.plan) for e in P.walk_plan(plan)
            if isinstance(e, E.ScalarSubquery)}


def test_the_event_is_in_the_schema():
    assert EVENT_SCHEMA["scalar_subquery"] == (
        "out_name", "source", "cols_read", "null", "dur_ms", "t0_ns")
    assert {"dur_ms", "t0_ns"} <= set(EVENT_SCHEMA["blocked_union"])


def test_one_event_a_distinct_plan_and_the_answer_is_sqlites():
    s = _session(traced=True)
    n_plans = len(_subquery_plans(s))
    assert n_plans >= 6  # eight are written; the binder may share some
    assert _run(s) == _sqlite(STATEMENT)
    first = _events(s)
    # the CASE evaluates one of its two branches' subqueries or both, never
    # one twice: an event a plan that was evaluated, none repeated
    assert n_plans - 1 <= len(first) <= n_plans
    assert len({e["out_name"] for e in first}) == len(first)
    assert {e["source"] for e in first} == {"executed"}
    for e in first:
        assert set(EVENT_SCHEMA["scalar_subquery"]) <= set(e)
        assert e["query"] == "q_subq" and e["dur_ms"] >= 0
        assert e["ts"] >= e["t0_ns"] // 1_000_000 - 1
    # fact has three columns and vacant one: no pruning reaches a
    # subquery's scan, so each read its table whole
    assert sorted({e["cols_read"] for e in first}) == [1, 3]
    # the aggregate over no rows yields a NULL, the bare select no row
    assert [e["null"] for e in first].count(True) == 2
    assert R.validate_events(s.tracer.events) == []
    # the executed plans' operators lie inside their spans, one level down
    spans = [e for e in s.tracer.events if e["kind"] == "op_span"]
    for e in first:
        inside = [sp for sp in spans if sp["exec_id"] == e["exec_id"]
                  and sp["depth"] == e["depth"] + 1
                  and e["t0_ns"] <= sp["t0_ns"]
                  and sp["t0_ns"] + sp["dur_ms"] * 1e6
                  <= e["t0_ns"] + e["dur_ms"] * 1e6 + 1e6]
        assert inside, e
    reads = [e for e in s.tracer.events
             if e["kind"] == "host_read" and e["why"] == "scalar"]
    assert len(reads) == len(first) - 1  # no row: nothing to fetch


def test_the_second_time_the_sessions_cache_answers_and_a_registration_ends_it():
    s = _session(traced=True)
    want = _sqlite(STATEMENT)
    assert _run(s) == want
    n = len(_events(s))
    assert _run(s) == want
    again = _events(s)[n:]
    assert len(again) == n
    assert {e["source"] for e in again} == {"session-cache"}
    assert {e["cols_read"] for e in again} == {0}
    assert [e["null"] for e in again].count(True) == 2
    # a catalog registration drops the plan-result cache: the plans run
    s.register_arrow("other", pa.table({"x": [1]}))
    assert _run(s) == want
    anew = _events(s)[2 * n:]
    assert len(anew) == n and {e["source"] for e in anew} == {"executed"}


def test_with_no_tracer_no_event_and_the_same_answers():
    traced, bare = _session(traced=True), _session(traced=False)
    assert bare.tracer is None
    assert _run(bare) == _run(traced) == _sqlite(STATEMENT)
    assert _run(bare) == _run(traced)


def test_the_profiler_shows_the_subqueries_under_their_query():
    s = _session(traced=True)
    _run(s)
    _run(s)
    n = len(_events(s)) // 2
    q = CP.critical_path(s.tracer.events)["queries"]["q_subq"]
    within = q["within_execute"]["scalar-subquery"]
    assert within["executed"]["count"] == within["session-cache"]["count"] == n
    assert within["executed"]["null"] == 2
    assert within["executed"]["cols_read"] == 3 * (n - 2) + 2
    # a view into the causes, never one of them
    assert not {"scalar-subquery", "setop"} & set(q["causes"])
    lines = R.format_within(q["within_execute"])
    assert any(line.startswith("   scalar-subquery executed") for line in lines)
    prof = R.profile_events(s.tracer.events)
    assert prof["queries"]["q_subq"]["within_execute"][
        "scalar-subquery"] == within


def test_a_blocked_union_carries_its_span():
    s = _session(traced=True)
    rng = np.random.default_rng(7)
    for t in ("u1", "u2"):
        s.register_arrow(t, pa.table({
            "k": pa.array(rng.integers(1, 5, 3000), pa.int32()),
            "v": pa.array(rng.integers(-50, 50, 3000), pa.int32())}))
    s.conf["engine.union_agg_window_rows"] = 512
    with faults.scope("q_union"):
        s.sql("select k, sum(v) sv from (select k, v from u1 union all "
              "select k, v from u2) u group by k order by k").collect()
    blocked, = [e for e in s.tracer.events if e["kind"] == "blocked_union"]
    assert blocked["windows"] > 1 and blocked["dur_ms"] > 0
    assert isinstance(blocked["t0_ns"], int)
    assert blocked["ts"] >= blocked["t0_ns"] // 1_000_000 - 1
    # the blocked path ran no SetOp
    assert not [e for e in s.tracer.events
                if e["kind"] == "op_span" and e["node"] == "SetOp"]
    within = CP.critical_path(s.tracer.events)["queries"]["q_union"][
        "within_execute"]
    assert within["blocked-union"]["windows"] == blocked["windows"]
    assert "setop" not in within
    assert R.validate_events(s.tracer.events) == []
