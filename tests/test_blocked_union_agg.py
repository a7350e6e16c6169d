"""Blocked (morsel-style) union-aggregation: the executor evaluates a
union_all feeding an aggregate in bounded row windows with partial-aggregate
merging instead of materializing the full concat (the SF10 HBM ceiling).
Blocked-path results must equal the unblocked path exactly;
non-decomposable aggregates must stay on the unblocked path.

Plus regression tests for the satellite fixes that rode along with the
blocked path (ISSUE 1): the throughput start-gate timeout fallback,
_to_ts_ms epoch windows, the join-expansion int32 guard, and
_null_rejecting_shape vs nested boolean connectives.
"""

import threading
import time
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.engine import plan as P
from nds_tpu.engine.columnar import bucket_cap
from nds_tpu.engine.session import Session

rng = np.random.default_rng(42)


def _channel(n, seed):
    r = np.random.default_rng(seed)
    ks = r.integers(1, 6, n)
    vs = r.integers(-50, 50, n)
    return pa.table(
        {
            "k": pa.array(
                [None if i % 13 == 0 else int(v) for i, v in enumerate(ks)],
                pa.int32(),
            ),
            "cat": pa.array(
                [["Books", "Music", "Shoes"][int(v) % 3] for v in ks]
            ),
            "v": pa.array(
                [None if i % 7 == 0 else int(v) for i, v in enumerate(vs)],
                pa.int32(),
            ),
            "amt": pa.array(
                [Decimal(int(v) * 7) / 100 for v in vs], pa.decimal128(7, 2)
            ),
        }
    )


def _session(window_rows=None):
    conf = {}
    if window_rows is not None:
        conf["engine.union_agg_window_rows"] = window_rows
    s = Session(conf=conf)
    for i, t in enumerate(("t1", "t2", "t3")):
        s.register_arrow(t, _channel(3000, seed=100 + i))
    return s


UNION_AGG = """
select k, sum(v) sv, min(v) mn, max(v) mx, count(v) cv, count(*) c,
       avg(v) av, sum(amt) sa
from (select k, cat, v, amt from t1
      union all
      select k, cat, v, amt from t2 where v > -40
      union all
      select k, cat, v, amt from t3) u
where v < 45
group by k
order by k
"""


def _find_agg(plan):
    out = []

    def visit(n):
        if isinstance(n, P.Aggregate):
            out.append(n)
        if isinstance(n, P.Pipeline) and n.agg is not None:
            # a fused aggregate tail is the Aggregate, detached
            out.append(n.agg)
        for c in n.children():
            if c is not None:
                visit(c)

    visit(plan)
    assert out, "no Aggregate in plan"
    return out[0]


def _run(sql, window_rows):
    s = _session(window_rows)
    r = s.sql(sql)
    return r.collect(), _find_agg(r.plan)


def test_blocked_equals_unblocked_all_decomposable_aggs():
    # huge window -> single window -> unblocked path is taken
    unblocked, agg_u = _run(UNION_AGG, 10**9)
    assert getattr(agg_u, "blocked_windows", None) is None
    # tiny window -> multi-window blocked execution
    blocked, agg_b = _run(UNION_AGG, 600)
    assert agg_b.blocked_union
    assert agg_b.blocked_windows > 1
    assert unblocked.to_pylist() == blocked.to_pylist()


def test_plan_annotation_and_bounded_window_caps():
    window = 600
    blocked, agg = _run(UNION_AGG, window)
    stats = agg.blocked_stats
    wcap = bucket_cap(window)
    assert stats["window_cap"] == wcap
    # window count is per-branch ceil-division over the window bucket
    assert stats["windows"] >= stats["total_rows"] // wcap
    # peak table capacity is bounded by the window bucket (merge concats
    # stay within 2x: window partial + group-sized accumulator), never by
    # the total union row count
    assert stats["max_table_cap"] <= 2 * wcap
    assert stats["max_table_cap"] < bucket_cap(stats["total_rows"])


def test_blocked_string_group_key():
    q = """
    select cat, sum(v) sv, count(*) c, avg(v) av
    from (select cat, v from t1 union all select cat, v from t2) u
    group by cat order by cat
    """
    unblocked, _ = _run(q, 10**9)
    blocked, agg = _run(q, 700)
    assert agg.blocked_windows > 1
    assert unblocked.to_pylist() == blocked.to_pylist()


def test_blocked_global_aggregate():
    q = """
    select sum(v) sv, min(v) mn, count(v) cv, count(*) c, avg(v) av
    from (select v from t1 union all select v from t2 where v > 0) u
    """
    unblocked, _ = _run(q, 10**9)
    blocked, agg = _run(q, 512)
    assert agg.blocked_windows > 1
    assert unblocked.to_pylist() == blocked.to_pylist()


def test_blocked_empty_after_filter():
    # every window filters to nothing: grouped output must be empty, like
    # the unblocked path's
    q = """
    select k, sum(v) sv from
    (select k, v from t1 union all select k, v from t2) u
    where v > 1000 group by k
    """
    unblocked, _ = _run(q, 10**9)
    blocked, agg = _run(q, 512)
    assert agg.blocked_windows > 1
    assert blocked.num_rows == unblocked.num_rows == 0


def test_blocked_union_through_inner_join():
    # the query5 channel shape: fact-scale union joined to a dimension
    # before aggregation — windows flow through the inner join, so the
    # full union concat (and its join pair table) never materializes
    dim = pa.table(
        {
            "dk": pa.array(range(1, 6), pa.int32()),
            "dname": pa.array([f"d{i}" for i in range(1, 6)]),
            "flag": pa.array([i % 2 for i in range(1, 6)], pa.int32()),
        }
    )
    q = """
    select d.dname, sum(u.v) sv, count(*) c, avg(u.v) av
    from (select k, v from t1 union all select k, v from t2) u, dim d
    where u.k = d.dk and d.flag = 1
    group by d.dname order by d.dname
    """

    def run(window):
        s = _session(window)
        s.register_arrow("dim", dim)
        r = s.sql(q)
        return r.collect(), _find_agg(r.plan)

    unblocked, agg_u = run(10**9)
    assert getattr(agg_u, "blocked_windows", None) is None
    blocked, agg = run(500)
    assert agg.blocked_union
    assert agg.blocked_windows > 1
    assert agg.blocked_stats["max_table_cap"] < bucket_cap(
        agg.blocked_stats["total_rows"]
    )
    assert unblocked.to_pylist() == blocked.to_pylist()


def test_blocked_rollup_over_union():
    # the query5 shape: GROUP BY ROLLUP over a multi-channel union — the
    # finest level runs windowed, coarser levels cascade from its (small)
    # output, and the full union concat never materializes
    q = """
    select cat, k, sum(v) sv, count(*) c, avg(v) av
    from (select cat, k, v from t1
          union all select cat, k, v from t2
          union all select cat, k, v from t3) u
    group by rollup(cat, k)
    order by cat, k
    """
    unblocked, agg_u = _run(q, 10**9)
    assert getattr(agg_u, "blocked_windows", None) is None
    blocked, agg = _run(q, 600)
    assert agg.blocked_union
    assert agg.blocked_windows > 1
    # only the finest level is windowed: the cascade handles the rest, so
    # the window count stays one pass over the input, not one per set
    assert agg.blocked_windows <= agg.blocked_stats["total_rows"] // bucket_cap(
        600
    ) + len(("t1", "t2", "t3"))
    assert agg.blocked_stats["max_table_cap"] < bucket_cap(
        agg.blocked_stats["total_rows"]
    )
    ul, bl = unblocked.to_pylist(), blocked.to_pylist()
    assert len(ul) == len(bl)
    for x, y in zip(ul, bl):
        for col in x:
            if isinstance(x[col], float):
                assert abs(x[col] - y[col]) < 1e-9 * max(1.0, abs(x[col]))
            else:
                assert x[col] == y[col]


def test_non_decomposable_stays_unblocked():
    q = """
    select k, count(distinct v) dv
    from (select k, v from t1 union all select k, v from t2) u
    group by k order by k
    """
    out_small, agg = _run(q, 512)
    # NOT annotated: the shape matches but count distinct does not
    # decompose over row windows, and the annotation pass now applies the
    # same plan.aggs_decomposable rule the executor's blocked path uses
    # (the verifier flags a blocked_union mark on a non-decomposable
    # aggregate as a planner violation — analysis/verifier.py)
    assert not agg.blocked_union
    assert getattr(agg, "blocked_windows", None) is None
    out_big, _ = _run(q, 10**9)
    assert out_small.to_pylist() == out_big.to_pylist()


def test_union_distinct_not_annotated():
    s = _session(512)
    r = s.sql(
        """
        select k, sum(v) sv
        from (select k, v from t1 union select k, v from t2) u
        group by k order by k
        """
    )
    agg = _find_agg(r.plan)
    assert not agg.blocked_union
    r.collect()  # still executes correctly
    assert getattr(agg, "blocked_windows", None) is None


def test_derived_window_rows_honors_conf_and_env(monkeypatch):
    s = Session(conf={"engine.union_agg_window_rows": 123})
    assert s.union_agg_window_rows(row_bytes=100) == 123
    s2 = Session()
    monkeypatch.setenv("NDS_UNION_AGG_WINDOW_ROWS", "456")
    assert s2.union_agg_window_rows(row_bytes=100) == 456
    monkeypatch.delenv("NDS_UNION_AGG_WINDOW_ROWS")
    derived = s2.union_agg_window_rows(row_bytes=90)
    # power of two within the clamp range, derived from the device budget
    assert derived & (derived - 1) == 0
    assert (1 << 16) <= derived <= (1 << 24)
    # wider rows -> same or smaller windows
    assert s2.union_agg_window_rows(row_bytes=900) <= derived


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------


def test_start_gate_pure_timeout_falls_back_ungated():
    from nds_tpu.throughput import _StartGate

    gate = _StartGate(2, timeout=0.3)  # second party never arrives
    t0 = time.time()
    got = gate.wait()
    assert isinstance(got, float) and got >= t0  # ungated start, no raise
    # a sibling arriving after the breakage also degrades, not raises
    assert isinstance(gate.wait(), float)


def test_start_gate_abort_raises_gate_broken():
    from nds_tpu.throughput import _GateBroken, _StartGate

    gate = _StartGate(2, timeout=30)
    box = {}

    def parked():
        try:
            gate.wait()
        except _GateBroken as exc:
            box["exc"] = exc

    th = threading.Thread(target=parked)
    th.start()
    time.sleep(0.05)
    gate.abort()
    th.join(5)
    assert isinstance(box.get("exc"), _GateBroken)


def test_start_gate_releases_all_with_shared_epoch():
    from nds_tpu.throughput import _StartGate

    gate = _StartGate(2, timeout=30)
    out = {}

    def one(n):
        out[n] = gate.wait()

    ts = [threading.Thread(target=one, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(5)
    assert out[0] == out[1]  # one shared release timestamp


def test_to_ts_ms_epoch_windows():
    from nds_tpu.lakehouse.dml import LakehouseError, _to_ts_ms

    assert _to_ts_ms("1700000000") == 1_700_000_000_000  # epoch seconds
    assert _to_ts_ms("1700000000000") == 1_700_000_000_000  # epoch ms
    assert _to_ts_ms(1700000000) == 1_700_000_000_000
    assert _to_ts_ms("2024-01-01 12:00:00") > 0
    # 12-digit compact datetime (~2e11) must NOT parse as epoch seconds in
    # year ~8383 — it falls through to the date parser and errors loudly
    with pytest.raises(LakehouseError):
        _to_ts_ms("202401011200")
    # 14-digit compact datetime (~2e13) likewise
    with pytest.raises(LakehouseError):
        _to_ts_ms("20240101120000")
    with pytest.raises(LakehouseError):
        _to_ts_ms("20240101")


def test_join_expand_int32_guard():
    from nds_tpu.ops.kernels import _check_pair_count

    _check_pair_count(0)
    _check_pair_count(1 << 30)  # largest safe bucket
    with pytest.raises(ValueError, match="int32"):
        _check_pair_count((1 << 30) + 1)


def test_null_rejecting_shape_boolean_connectives():
    from nds_tpu.engine import expr as E
    from nds_tpu.engine.binder import _null_rejecting_shape

    plain = E.BinOp("=", E.Col("x", "a"), E.Col("y", "b"))
    assert _null_rejecting_shape(plain)
    # null-tolerant OR nested inside an operand: NOT strict (b.y NULL can
    # still yield TRUE), must not promote a LEFT JOIN to INNER
    nested_or = E.BinOp(
        "=", E.Col("x", "a"), E.BinOp("or", E.Col("y", "b"), E.Lit(True))
    )
    assert not _null_rejecting_shape(nested_or)
    nested_and = E.BinOp(
        "<", E.BinOp("and", E.Col("y", "b"), E.Lit(False)), E.Col("x", "a")
    )
    assert not _null_rejecting_shape(nested_and)
    # the top-level comparison itself is still fine when wrapped in AND at
    # the conjunct level (callers split conjuncts before calling)
    assert not _null_rejecting_shape(
        E.BinOp("and", plain, plain)
    )  # not a comparison at the root
