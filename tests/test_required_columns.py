"""The execution half of required-column propagation (`P.Filter.required`,
`P.Join.required`, `P.MultiJoin.required`): a join hands on the columns
something above it reads and no others.

On the CPU and at SF0.01: counts of columns and launches, and answers
against sqlite; never a speed (PERF.md has what the chip showed)."""

import datetime
import os
import sqlite3

import numpy as np
import pytest

from shared_data import raw_data

from nds_tpu import faults
from nds_tpu.engine import exec as X
from nds_tpu.engine import expr as E
from nds_tpu.engine import plan as P
from nds_tpu.engine.session import Result, Session
from nds_tpu.io.csv import read_dat_dir
from nds_tpu.obs.trace import Tracer, bind
from nds_tpu.schema import get_schemas

SMALL = ("store_sales", "item", "date_dim", "store")


def _session(tables, tracer=None):
    s = Session(use_decimal=False)
    s.tracer = tracer
    schemas = get_schemas(use_decimal=False)
    for t in tables:
        s.register_csv_dir(t, os.path.join(raw_data(), t), schemas[t])
    return s


@pytest.fixture(scope="module")
def sqlite_small():
    """The four small tables in sqlite, dates as ISO strings."""
    conn = sqlite3.connect(":memory:")
    schemas = get_schemas(use_decimal=False)
    for t in SMALL:
        schema = schemas[t]
        arrow = read_dat_dir(
            os.path.join(raw_data(), t), schema, use_decimal=False
        )
        names = [f.name for f in schema]
        conn.execute(f"create table {t} ({', '.join(names)})")
        rows = zip(*(arrow.column(n).to_pylist() for n in names))
        conn.executemany(
            f"insert into {t} values ({', '.join('?' for _ in names)})",
            [tuple(v.isoformat() if isinstance(v, datetime.date) else v
                   for v in row) for row in rows],
        )
    return conn


def _template(q):
    from nds_tpu.datagen.query_streams import instantiate

    return instantiate(q, np.random.default_rng(1000 + q), 0.01)


def _traced(session, sql, name, monkeypatch):
    """Run one statement; (answer, its op_spans, what each probe-style join
    handed on of its right side, as `(right columns in, right columns out)`)."""
    handed = []
    augment = X.Executor._augment_join_output

    def recording(self, left, right, *a, **kw):
        out = augment(self, left, right, *a, **kw)
        handed.append((
            sorted(right.columns),
            sorted(n for n in out.columns if n in right.columns),
        ))
        return out

    monkeypatch.setattr(X.Executor, "_augment_join_output", recording)
    before = len(session.tracer.events)
    with bind(session.tracer), faults.scope(name):
        answer = session.sql(sql).collect()
    spans = [e for e in session.tracer.events[before:]
             if e["kind"] == "op_span"]
    return answer, spans, handed


def test_query7_gathers_i_item_id_alone(monkeypatch):
    """Four dimensions join the fact table; three are there for their
    filters. Before, every column of each (11: three of promotion, two of
    item, two of date_dim, four of customer_demographics) was gathered at
    the fact table's capacity; now the one that the aggregate reads."""
    s = _session(
        ("store_sales", "customer_demographics", "date_dim", "item",
         "promotion"), Tracer(),
    )
    answer, spans, handed = _traced(s, _template(7), "query7", monkeypatch)
    assert answer.num_rows > 0
    assert len(handed) == 4
    # each dimension reaches its join with its key (item: and i_item_id)
    assert sum(len(cols_in) for cols_in, _ in handed) == 5
    assert [out for _, out in handed if out] == [["item.i_item_id"]]
    (mj,) = [e for e in spans if e["node"] == "MultiJoin"]
    # 8 fact columns, the four keys and i_item_id in; the aggregate's
    # five out: ss_cdemo_sk, ss_sold_date_sk, ss_item_sk and ss_promo_sk
    # fell away by reference as their edges were consumed
    assert (mj["cols_in"], mj["cols_out"]) == (13, 5)
    # the packed keys of the three filtered dimensions and i_item_id's
    # codes, one buffer each; 20 when every column of every side was taken
    dimension_buffers = 4
    # since PR 34 customer_demographics (1.4% kept) is joined first, so at
    # this scale the fact side is under an eighth live from there on and
    # `_pack_sparse` packs what is still read of it at the head of each
    # later join: 7 columns and 2 validity buffers, then 6 and 1, then 5
    # (at SF1 it was packed already, before the last join)
    assert mj["join_order"] == [0, 1, 2, 4, 3]
    assert mj["launches"]["take_columns"] == dimension_buffers + 9 + 7 + 5


def test_query96_gathers_no_dimension_column(monkeypatch):
    """`count(*)` reads no column: the three dimensions filter the fact
    rows and hand nothing on, and one fact column carries the rows."""
    s = _session(
        ("store_sales", "household_demographics", "time_dim", "store"),
        Tracer(),
    )
    answer, spans, handed = _traced(s, _template(96), "query96", monkeypatch)
    assert answer.num_rows == 1
    assert len(handed) == 3
    assert [out for _, out in handed] == [[], [], []]
    (mj,) = [e for e in spans if e["node"] == "MultiJoin"]
    assert (mj["cols_in"], mj["cols_out"]) == (6, 1)
    # the packing of five masked join sides; 32 buffers before
    assert mj["launches"]["take_columns"] == 9
    # the dimension filters hand on their keys alone
    filters = [e for e in spans if e["node"] == "Pipeline"
               and "cols_in" in e]
    assert len(filters) == 3
    assert all(e["cols_out"] == 1 < e["cols_in"] for e in filters)


def _rows(table):
    return [
        [v.isoformat() if isinstance(v, datetime.date) else v for v in r]
        for r in zip(*(table.column(n).to_pylist() for n in table.schema.names))
    ]


def _same(ours, oracle):
    ours, oracle = _rows(ours), [list(r) for r in oracle]
    assert len(ours) == len(oracle) and len(ours) > 0
    for a, b in zip(ours, oracle):
        for x, y in zip(a, b):
            if isinstance(x, float) or isinstance(y, float):
                assert x == pytest.approx(y, rel=1e-6, abs=1e-9)
            else:
                assert x == y


# a join's filter column (i_manager_id, d_moy, s_state) read above the join:
# in the select list, in the group-by, in a predicate over a left join
FILTER_COLUMN_ABOVE = {
    "select_list": (
        "select i_item_sk, i_manager_id, d_moy, ss_quantity from store_sales,"
        " item, date_dim where ss_item_sk = i_item_sk and ss_sold_date_sk ="
        " d_date_sk and i_manager_id < 5 and d_moy = 11 and ss_quantity > 95"
        " order by i_item_sk, i_manager_id, d_moy, ss_quantity"
    ),
    "group_by": (
        "select i_manager_id, count(*) c, sum(ss_quantity) q from store_sales,"
        " item where ss_item_sk = i_item_sk and i_manager_id between 3 and 7"
        " group by i_manager_id order by i_manager_id"
    ),
    "above_a_left_join": (
        "select s_state, count(*) c from store_sales left join store on"
        " ss_store_sk = s_store_sk where s_state = 'TN' or ss_quantity > 98"
        " group by s_state order by s_state"
    ),
    "residual_of_a_left_join": (
        "select count(*) c, count(i_item_sk) m from store_sales left join item"
        " on ss_item_sk = i_item_sk and i_manager_id > ss_quantity"
    ),
}


@pytest.mark.parametrize("where", sorted(FILTER_COLUMN_ABOVE))
def test_filter_column_read_above_the_join_is_still_there(sqlite_small, where):
    sql = FILTER_COLUMN_ABOVE[where]
    s = _session(SMALL)
    _same(s.sql(sql).collect(), sqlite_small.execute(sql).fetchall())


# An equi key with duplicates on the right and a non-equi residual: the
# dense and the packed probes decline, so the sort join builds a pair
# table, filters it by the residual and hands it on.
RESIDUAL_JOIN = (
    "item a {kind} join item b on a.i_manager_id = b.i_manager_id and"
    " a.i_item_sk < b.i_item_sk"
)
# `prune_columns` does not walk a scalar subquery's plan (PERF.md, section
# 7): every join in it carries `required = None`, which means all columns
NOT_PRUNED = {
    "inner_join_in_a_scalar_subquery": (
        "select s_store_sk, (select sum(a.i_wholesale_cost +"
        " b.i_wholesale_cost + coalesce(s_store_sk, 0)) from "
        + RESIDUAL_JOIN.format(kind="inner")
        + " full outer join store on s_store_sk = a.i_item_sk) t"
        " from store order by s_store_sk"
    ),
    "left_join_in_a_scalar_subquery": (
        "select s_store_sk from store where s_store_sk < (select"
        " count(b.i_item_sk) + count(a.i_brand_id) from "
        + RESIDUAL_JOIN.format(kind="left") + ") order by s_store_sk"
    ),
}


@pytest.mark.parametrize("shape", sorted(NOT_PRUNED))
def test_a_join_nobody_pruned_hands_on_every_column(sqlite_small, shape):
    sql = NOT_PRUNED[shape]
    s = _session(("item", "store"))
    res = s.sql(sql)
    joins = [n for n in P.walk_plan(res.plan) if isinstance(n, P.Join)]
    assert joins and all(j.required is None for j in joins)
    _same(res.collect(), sqlite_small.execute(sql).fetchall())


@pytest.mark.parametrize("kind", ["inner", "left", "full"])
@pytest.mark.parametrize("required", [
    None,
    ("a.i_item_sk", "a.i_wholesale_cost", "b.i_item_sk",
     "b.i_wholesale_cost"),
    ("a.i_wholesale_cost", "b.i_wholesale_cost"),  # not the residual's
], ids=["all", "named", "without_the_residuals"])
def test_sort_join_with_a_residual_hands_on_what_required_says(
    sqlite_small, kind, required,
):
    """A hand-built `P.Join` over the sort join's residual path: with
    `required = None` the pair table keeps every column of both sides,
    with names it keeps those; the Project above reads the same either
    way and answers as sqlite does."""
    col = E.Col
    join = P.Join(
        kind, P.Scan("item", "a"), P.Scan("item", "b"),
        [col("a.i_manager_id")], [col("b.i_manager_id")],
        E.BinOp("<", col("a.i_item_sk"), col("b.i_item_sk")),
        required=required,
    )
    cost = E.BinOp(
        "+", col("a.i_wholesale_cost"), col("b.i_wholesale_cost")
    )
    s = _session(("item",))
    ex = s._executor()
    handed = ex.execute(join)
    both = 2 * len(get_schemas(use_decimal=False)["item"])
    assert len(handed.columns) == (both if required is None else len(required))
    ours = Result(s, P.Aggregate(
        [], [(E.Agg("count", None), "n"), (E.Agg("sum", cost), "w")], join,
    )).collect()
    _same(ours, sqlite_small.execute(
        "select count(*), sum(a.i_wholesale_cost + b.i_wholesale_cost) from "
        + RESIDUAL_JOIN.format(kind=kind)
    ).fetchall())


CTE = ("with agg as (select ss_item_sk k, ss_store_sk s, sum(ss_quantity) q"
       " from store_sales group by ss_item_sk, ss_store_sk) ")
SHARED = {
    # reads k and q of the shared aggregate
    "narrow": CTE + (
        "select i_brand, count(*) c from agg, item where k = i_item_sk and"
        " q > 10 and i_manager_id < 30 group by i_brand order by i_brand"
    ),
    # reads s as well, and the item filter's column above the join
    "wide": CTE + (
        "select i_brand, i_manager_id, s, sum(q) tq from agg, item where"
        " k = i_item_sk and q > 10 and i_manager_id < 30 and s is not null"
        " group by i_brand, i_manager_id, s order by i_brand, i_manager_id, s"
    ),
}


@pytest.mark.parametrize("order", ["narrow_first", "wide_first"])
def test_statements_sharing_a_cached_subtree_read_what_they_name(
    sqlite_small, order,
):
    """The aggregate is served from the plan-result cache to the second
    statement; what stands above it carries each statement's own set, so
    neither gets the other's columns."""
    tracer = Tracer()
    s = _session(SMALL, tracer)
    assert s.conf.get("engine.plan_cache", "on") == "on"
    names = ["narrow", "wide"] if order == "narrow_first" else ["wide", "narrow"]
    for i, name in enumerate(names + names[:1]):
        before = len(tracer.events)
        with bind(tracer):
            ours = s.sql(SHARED[name]).collect()
        hits = [e["hit"] for e in tracer.events[before:]
                if e["kind"] == "plan_cache"]
        assert any(hits) == (i > 0)
        _same(ours, sqlite_small.execute(SHARED[name]).fetchall())


def test_join_order_cache_tells_readers_apart():
    """Two MultiJoins alike but for what is read above them have
    fingerprints of their own, and so a recorded join order each."""
    s = _session(SMALL)
    a = s.sql("select count(*) c from store_sales, item where ss_item_sk ="
              " i_item_sk and i_manager_id < 5")
    b = s.sql("select sum(ss_quantity) c from store_sales, item where"
              " ss_item_sk = i_item_sk and i_manager_id < 5")
    (ja,) = [n for n in P.walk_plan(a.plan) if isinstance(n, P.MultiJoin)]
    (jb,) = [n for n in P.walk_plan(b.plan) if isinstance(n, P.MultiJoin)]
    assert ja.required == () and jb.required == ("store_sales.ss_quantity",)
    a.collect(), b.collect()
    assert len(s.join_order_cache) == 2


REPLAY6 = {
    3: ("store_sales", "item", "date_dim"),
    96: ("store_sales", "household_demographics", "time_dim", "store"),
    7: ("store_sales", "customer_demographics", "date_dim", "item",
        "promotion"),
    36: ("store_sales", "date_dim", "item", "store"),
    93: ("store_sales", "store_returns", "reason"),
    1: ("store_returns", "date_dim", "store", "customer"),
}


@pytest.mark.parametrize("q", sorted(REPLAY6))
def test_none_means_all_on_every_exit_the_window_takes(q, monkeypatch):
    """The same statement planned with every `required` cleared (None:
    all columns, what an unpruned plan carries) answers as the pruned
    one does: no exit of a join or a filter reads `None` as "none"."""
    from nds_tpu.engine import session as S

    sql = _template(q)
    pruned = _session(REPLAY6[q]).sql(sql)
    narrowed = [n for n in P.walk_plan(pruned.plan)
                if getattr(n, "required", None) is not None]
    assert narrowed
    prune = S.prune_columns

    def unpruned(node, catalog=None):
        node = prune(node, catalog)
        for n in P.walk_plan(node):
            if hasattr(n, "required"):
                n.required = None
        return node

    monkeypatch.setattr(S, "prune_columns", unpruned)
    cleared = _session(REPLAY6[q]).sql(sql)
    assert all(getattr(n, "required", None) is None
               for n in P.walk_plan(cleared.plan))
    assert pruned.collect().equals(cleared.collect())
