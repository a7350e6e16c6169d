"""`MultiJoin`'s greedy order (`Executor._multijoin_greedy`): a step is ranked
by the rows its join is estimated to leave (`exec._est_join_rows`: live
counts and the keys' `ColStats`), and by the sum of its inputs' live rows
where it has no estimate.

On the CPU, over a query7-shaped star of a few ten thousand rows: the orders
recorded, the capacities the steps ran at, the span's fields and answers
against sqlite; never a speed (PERF.md has what the chip showed)."""

import sqlite3

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu import faults
from nds_tpu.cli import profile
from nds_tpu.engine import columnar
from nds_tpu.engine import exec as X
from nds_tpu.engine.session import Session
from nds_tpu.obs import reader as R
from nds_tpu.obs.trace import Tracer, bind

N_FACT = 40_000
N_DEMO, N_ITEM, N_PROMO, N_HD = 60_000, 600, 100, 10_000
# the date dimension holds 8,000 days; the fact table references 2,000
N_DATES, FIRST_DAY, N_DAYS_SOLD = 8_000, 3_000, 2_000


def _star():
    r = np.random.default_rng(34)
    i32 = pa.int32()
    return {
        "fact": pa.table({
            "f_demo_sk": pa.array(r.integers(0, N_DEMO, N_FACT), i32),
            "f_date_sk": pa.array(
                r.integers(FIRST_DAY, FIRST_DAY + N_DAYS_SOLD, N_FACT), i32
            ),
            "f_item_sk": pa.array(r.integers(0, N_ITEM, N_FACT), i32),
            "f_promo_sk": pa.array(r.integers(0, N_PROMO, N_FACT), i32),
            "f_hd_sk": pa.array(r.integers(0, N_HD, N_FACT), i32),
            "f_quantity": pa.array(r.integers(1, 100, N_FACT), i32),
            "f_price": pa.array(r.uniform(0, 200, N_FACT).round(2)),
        }),
        # 67 classes: one keeps 1.5% of the rows
        "demo": pa.table({
            "dm_demo_sk": pa.array(np.arange(N_DEMO), i32),
            "dm_class": pa.array(np.arange(N_DEMO) % 67, i32),
        }),
        # a year of 365 days: 4.6% of the dimension, 18% of the days sold
        "dates": pa.table({
            "d_date_sk": pa.array(np.arange(N_DATES), i32),
            "d_year": pa.array(np.arange(N_DATES) // 365, i32),
            "d_moy": pa.array(1 + (np.arange(N_DATES) // 30) % 12, i32),
        }),
        "item": pa.table({
            "i_item_sk": pa.array(np.arange(N_ITEM), i32),
            "i_group": pa.array(np.arange(N_ITEM) % 7, i32),
            "i_manager_id": pa.array(np.arange(N_ITEM) % 20, i32),
        }),
        "promo": pa.table({
            "p_promo_sk": pa.array(np.arange(N_PROMO), i32),
            "p_channel": pa.array(np.arange(N_PROMO) % 2, i32),
        }),
        # ten buckets: one keeps 10% of the rows
        "hd": pa.table({
            "h_hd_sk": pa.array(np.arange(N_HD), i32),
            "h_bucket": pa.array(np.arange(N_HD) % 10, i32),
        }),
    }


@pytest.fixture(scope="module")
def star():
    return _star()


@pytest.fixture(scope="module")
def oracle(star):
    conn = sqlite3.connect(":memory:")
    for name, t in star.items():
        conn.execute(f"create table {name} ({', '.join(t.schema.names)})")
        conn.executemany(
            f"insert into {name} values ({', '.join('?' * t.num_columns)})",
            zip(*(t.column(n).to_pylist() for n in t.schema.names)),
        )
    return conn


def _session(star, tracer=None):
    s = Session()
    s.tracer = tracer
    for name, t in star.items():
        s.register_arrow(name, t)
    return s


def _memo(session):
    (memo,) = [m for m in session.join_order_cache.values() if "steps" in m]
    return memo


# relation indices follow the FROM list. The query7 shape: the dimension
# kept at 1.5% is the largest input but for the fact table, so the
# smallest-inputs rule joined it last
Q7_SHAPED = (
    "select i_group, count(*) c, avg(f_quantity) q, sum(f_price) p"
    " from fact, demo, dates, item, promo"
    " where f_demo_sk = dm_demo_sk and f_date_sk = d_date_sk"
    " and f_item_sk = i_item_sk and f_promo_sk = p_promo_sk"
    " and dm_class = 5 and d_year = 10 and p_channel < 2"
    " group by i_group order by i_group"
)
# the year is a smaller share of its dimension (4.6%) than the bucket is of
# its own (10%), and a larger share of the days the fact table holds (18%):
# the bucket's 1,000 rows go before the year's 365
DATE_AND_BUCKET = (
    "select h_bucket, count(*) c, sum(f_price) p from fact, dates, hd"
    " where f_date_sk = d_date_sk and f_hd_sk = h_hd_sk"
    " and d_year = 10 and h_bucket = 3 group by h_bucket order by h_bucket"
)
# query3's FROM list and filters: item (a twentieth kept) goes first under
# either rule, and date_dim, relation 0, is the left side of the last join
Q3_SHAPED = (
    "select d_year, i_group, sum(f_price) p from dates, fact, item"
    " where d_date_sk = f_date_sk and f_item_sk = i_item_sk"
    " and i_manager_id = 10 and d_moy = 11"
    " group by d_year, i_group order by d_year, i_group"
)
# a key that is an expression has no estimate: its edge ranks by its inputs
EXPRESSION_KEY = Q7_SHAPED.replace(
    "f_demo_sk = dm_demo_sk", "f_demo_sk + 0 = dm_demo_sk"
)

# (statement, order joined, steps whose estimate is null, reordered)
ORDERS = {
    "query7_shaped": (Q7_SHAPED, [0, 1, 2, 4, 3], [], 1),
    "date_ranked_by_the_fact_keys_range": (
        DATE_AND_BUCKET, [0, 2, 1], [], 1,
    ),
    "query3_shaped_keeps_item_first": (Q3_SHAPED, [1, 2, 0], [], 0),
    "expression_key_ranks_by_its_inputs": (
        EXPRESSION_KEY, [0, 2, 4, 3, 1], [3], 1,
    ),
}


def _same(ours, rows):
    ours = list(zip(*(ours.column(n).to_pylist() for n in ours.schema.names)))
    assert len(ours) == len(rows) > 0
    for a, b in zip(ours, rows):
        assert a == pytest.approx(b, rel=1e-9)


@pytest.mark.parametrize("shape", sorted(ORDERS))
def test_order_recorded_span_and_answer(star, oracle, shape):
    sql, order, no_estimate, reordered = ORDERS[shape]
    tracer = Tracer()
    s = _session(star, tracer)
    with bind(tracer):
        answer = s.sql(sql).collect()
    _same(answer, oracle.execute(sql).fetchall())
    (mj,) = [e for e in tracer.events
             if e["kind"] == "op_span" and e["node"] == "MultiJoin"]
    assert mj["join_order"] == order
    assert mj["reordered"] == reordered
    assert [i for i, v in enumerate(mj["step_est_rows"]) if v is None] \
        == no_estimate
    assert len(mj["left_caps"]) == len(mj["step_est_rows"]) == len(order) - 1


def test_left_caps_fall_after_the_selective_step(star):
    """The fact side enters the first join at its own capacity and, with
    1.5% of its rows live after it, every later join packed."""
    tracer = Tracer()
    s = _session(star, tracer)
    with bind(tracer):
        s.sql(Q7_SHAPED).collect()
    (mj,) = [e for e in tracer.events
             if e["kind"] == "op_span" and e["node"] == "MultiJoin"]
    fact_cap = columnar.bucket_cap(N_FACT)
    assert mj["left_caps"] == [fact_cap, 1024, 1024, 1024]
    # the estimates are within a fifth of what the steps left
    assert mj["step_est_rows"][0] == pytest.approx(N_FACT * 0.015, rel=0.2)
    assert mj["step_est_rows"][-1] == pytest.approx(mj["rows"], rel=0.2)


@pytest.mark.parametrize("sql, steps", [
    # smallest inputs first: promo (100 rows), dates (365), item (600), demo
    (Q7_SHAPED, [("edge", 0, 4), ("edge", 0, 2), ("edge", 0, 3),
                 ("edge", 0, 1)]),
    (DATE_AND_BUCKET, [("edge", 0, 1), ("edge", 0, 2)]),
    (Q3_SHAPED, [("edge", 1, 2), ("edge", 0, 1)]),
], ids=["query7_shaped", "date_and_bucket", "query3_shaped"])
def test_without_stats_the_order_is_the_smallest_inputs(
    star, oracle, monkeypatch, sql, steps,
):
    """Tables loaded without `ColStats` join in the order PR 33's executor
    records over the same tables (taken there, at `b73ef9d`)."""
    monkeypatch.setattr(columnar, "arrow_column_stats", lambda *a, **kw: None)
    tracer = Tracer()
    s = _session(star, tracer)
    with bind(tracer):
        answer = s.sql(sql).collect()
    _same(answer, oracle.execute(sql).fetchall())
    assert _memo(s)["steps"] == steps
    (mj,) = [e for e in tracer.events
             if e["kind"] == "op_span" and e["node"] == "MultiJoin"]
    assert mj["reordered"] == 0
    assert mj["step_est_rows"] == [None] * len(steps)


def test_replay_ranks_nothing_and_reports_what_it_recorded(star, monkeypatch):
    """The rank loop is where the order's `nrows` reads are: a replayed
    statement does not enter it, and its span says what the first saw."""
    ranked = []
    est = X._est_join_rows

    def counting(*a):
        ranked.append(1)
        return est(*a)

    monkeypatch.setattr(X, "_est_join_rows", counting)
    tracer = Tracer()
    s = _session(star, tracer)
    s.conf["engine.plan_cache"] = "off"
    with bind(tracer):
        first = s.sql(Q7_SHAPED).collect()
        n_first = len(ranked)
        recorded = dict(_memo(s))
        again = s.sql(Q7_SHAPED).collect()
    # 4 + 3 + 2 + 1 candidate edges over the four steps
    assert n_first == 10 and len(ranked) == n_first
    assert again.equals(first)
    assert _memo(s) == recorded
    a, b = [e for e in tracer.events
            if e["kind"] == "op_span" and e["node"] == "MultiJoin"]
    for k in ("join_order", "step_est_rows", "left_caps", "reordered"):
        assert a[k] == b[k]
    assert b["reads"] <= a["reads"]


def test_profile_prints_the_join_steps(star, capsys):
    """`profile`'s per-operator table, beside `cols in>out`: the sum of a
    MultiJoin's `left_caps`, and a line an order under the query's own."""
    tracer = Tracer()
    s = _session(star, tracer)
    s.conf["engine.plan_cache"] = "off"
    with bind(tracer), faults.scope("star7"):
        for _ in range(2):
            s.sql(Q7_SHAPED).collect()
    events = [dict(e, query="star7") for e in tracer.events]
    prof = R.profile_events(events)
    op = prof["queries"]["star7"]["ops"]["MultiJoin"]
    fact_cap = columnar.bucket_cap(N_FACT)
    assert op["left_cap_rows"] == 2 * (fact_cap + 3 * 1024)
    assert op["reordered"] == 2
    assert op["joins"]["0>1>2>4>3"]["count"] == 2
    assert "joins" not in prof["op_totals"]["MultiJoin"]
    merged = R.merge_profiles(R.profile_events(events), prof)
    assert merged["queries"]["star7"]["ops"]["MultiJoin"]["joins"][
        "0>1>2>4>3"]["count"] == 4
    assert merged["op_totals"]["MultiJoin"]["left_cap_rows"] \
        == 4 * (fact_cap + 3 * 1024)
    capsys.readouterr()
    profile._print_ops(sorted(prof["queries"]["star7"]["ops"].items()))
    text = capsys.readouterr().out
    assert "left_caps" in text.splitlines()[0]
    (line,) = [ln for ln in text.splitlines() if "join_order" in ln]
    assert "join_order 0>1>2>4>3 x2" in line
    assert f"left_caps {fact_cap:,}/1,024/1,024/1,024" in line
    assert "step_est_rows" in line and line.endswith("reordered")
