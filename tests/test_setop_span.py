"""Set operations against a plain reference, and what their `op_span` says
of them: INTERSECT, EXCEPT, UNION and UNION ALL over seeded random tables
with NULLs, duplicates, a string column whose dictionaries differ between
the two sides, and an empty side; the engine's rows against Python `set` /
`Counter` arithmetic (NULLs compare equal in a set operation), and the
span's `op`, `left_rows`, `right_rows`, `distinct_rows`, `key_words`
against the same. With no tracer bound: the same rows and no event."""

from collections import Counter

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.engine.session import Session
from nds_tpu.obs import critpath as CP
from nds_tpu.obs import reader as R
from nds_tpu.obs.trace import Tracer

OPS = {
    "intersect": lambda a, b: Counter(set(a) & set(b)),
    "except": lambda a, b: Counter(set(a) - set(b)),
    "union": lambda a, b: Counter(set(a) | set(b)),
    "union all": lambda a, b: Counter(a) + Counter(b),
}
#: (rows of the left side, rows of the right side)
SIDES = {"both": (300, 200), "empty_right": (300, 0), "empty_left": (0, 200)}


def _rows(rng, n, words):
    """`n` rows of (k, s): few distinct keys, so duplicates; a tenth of each
    column NULL; the strings drawn from this side's own words."""
    k = [None if rng.random() < 0.1 else int(v)
         for v in rng.integers(0, 12, n)]
    s = [None if rng.random() < 0.1 else str(rng.choice(words))
         for _ in range(n)]
    return list(zip(k, s))


def _session(seed, sides, traced):
    rng = np.random.default_rng(seed)
    n_left, n_right = SIDES[sides]
    # the two dictionaries overlap and neither holds the other
    left = _rows(rng, n_left, ["ant", "bee", "cat", "dog"])
    right = _rows(rng, n_right, ["cat", "dog", "eel", "fox", "gnu"])
    # without the flight recorder a session has no tracer at all
    s = Session(conf={"engine.flight_recorder": "off"})
    assert s.tracer is None
    if traced:
        s.tracer = Tracer()
    for name, rows in (("l", left), ("r", right)):
        s.register_arrow(name, pa.table({
            "k": pa.array([r[0] for r in rows], pa.int32()),
            "s": pa.array([r[1] for r in rows], pa.string())}))
    return s, left, right


def _answer(session, op):
    got = session.sql(
        f"select k, s from l {op} select k, s from r").collect()
    return Counter(zip(got.column("k").to_pylist(),
                       got.column("s").to_pylist()))


@pytest.mark.parametrize("seed", [11, 12])
@pytest.mark.parametrize("sides", sorted(SIDES))
@pytest.mark.parametrize("op", sorted(OPS))
def test_rows_and_span_against_set_arithmetic(op, sides, seed):
    s, left, right = _session(seed, sides, traced=True)
    assert _answer(s, op) == OPS[op](left, right)
    spans = [e for e in s.tracer.events
             if e["kind"] == "op_span" and e["node"] == "SetOp"]
    assert len(spans) == 1
    span, = spans
    assert span["op"] == op.replace(" ", "_")
    assert (span["left_rows"], span["right_rows"]) == (len(left), len(right))
    if op in ("intersect", "except"):
        assert span["distinct_rows"] == len(set(left))
        # a key word and a null flag for each of the two columns, at least
        assert span["key_words"] >= 4
    else:
        assert span["distinct_rows"] is None and span["key_words"] is None
    if op != "union all" or span["rows"] is not None:
        assert span["rows"] in (None, sum(OPS[op](left, right).values()))
    assert R.validate_events(s.tracer.events) == []


@pytest.mark.parametrize("op", sorted(OPS))
def test_with_no_tracer_the_same_rows_and_no_event(op):
    traced, left, right = _session(13, "both", traced=True)
    bare, _, _ = _session(13, "both", traced=False)
    assert bare.tracer is None
    assert _answer(bare, op) == _answer(traced, op) == OPS[op](left, right)


def test_a_count_still_on_the_device_is_null_not_a_sync():
    """A filtered side's count is queued on the device when the operation
    ends: the span says null, or the count if something had read it."""
    s, left, right = _session(14, "both", traced=True)
    got = s.sql("select k, s from l where k > 3 "
                "union all select k, s from r where k < 9").collect()
    want = [r for r in left if r[0] is not None and r[0] > 3] + [
        r for r in right if r[0] is not None and r[0] < 9]
    assert got.num_rows == len(want)
    span, = [e for e in s.tracer.events
             if e["kind"] == "op_span" and e["node"] == "SetOp"]
    assert span["op"] == "union_all"
    assert span["left_rows"] in (None, sum(
        1 for r in left if r[0] is not None and r[0] > 3))
    assert span["right_rows"] in (None, sum(
        1 for r in right if r[0] is not None and r[0] < 9))
    # the one read the operator waits for is the cardinality feedback's
    # count of its output, as before the fields: none is the span's
    reads = [e["why"] for e in s.tracer.events if e["kind"] == "host_read"
             and e.get("depth") == span["depth"]
             and e.get("exec_id") == span["exec_id"]]
    assert reads in ([], ["nrows"])


def test_nested_set_operations_each_say_their_own():
    """`a INTERSECT b INTERSECT c` is a SetOp over a SetOp: the inner span
    is emitted first and neither takes the other's fields."""
    s, left, right = _session(15, "both", traced=True)
    s.register_arrow("m", pa.table({
        "k": pa.array([r[0] for r in left[:50]], pa.int32()),
        "s": pa.array([r[1] for r in left[:50]], pa.string())}))
    got = s.sql("select k, s from l intersect select k, s from m "
                "except select k, s from r").collect()
    want = (set(left) & set(left[:50])) - set(right)
    assert Counter(zip(got.column("k").to_pylist(),
                       got.column("s").to_pylist())) == Counter(want)
    inner, outer = sorted(
        (e for e in s.tracer.events
         if e["kind"] == "op_span" and e["node"] == "SetOp"),
        key=lambda e: e["seq"])
    assert (inner["op"], outer["op"]) == ("intersect", "except")
    assert inner["depth"] == outer["depth"] + 1
    assert (inner["left_rows"], inner["right_rows"]) == (len(left), 50)
    assert inner["distinct_rows"] == len(set(left))
    assert outer["right_rows"] == len(right)
    assert outer["distinct_rows"] == len(set(left) & set(left[:50]))


def test_the_profiler_shows_set_operations_under_their_query():
    s, left, right = _session(16, "both", traced=True)
    for op in sorted(OPS):
        _answer(s, op)
    within = CP.critical_path(s.tracer.events)["queries"]["<unscoped>"][
        "within_execute"]["setop"]
    assert within["count"] == 4
    assert sorted(within["by_op"]) == [
        "except", "intersect", "union", "union_all"]
    assert within["by_op"]["intersect"]["left_rows"] == len(left)
    assert within["by_op"]["intersect"]["distinct_rows"] == len(set(left))
    assert within["own_ms"] == pytest.approx(
        sum(r["own_ms"] for r in within["by_op"].values()), abs=0.01)
    lines = R.format_within({"setop": within})
    assert len(lines) == 1 and "intersect x1" in lines[0]
    prof = R.profile_events(s.tracer.events)
    assert prof["queries"]["<unscoped>"]["within_execute"]["setop"][
        "count"] == 4
    merged = R.merge_within({}, {"setop": within})
    assert R.merge_within(merged, {"setop": within})["setop"]["count"] == 8
