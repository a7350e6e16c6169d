"""Fused operator pipelines + shape-bucketed executable reuse (engine/fuse.py).

Contract under test: a plan's Filter/Project chains collapse into Pipeline
nodes whose fused (single-jit) execution is BIT-IDENTICAL to the eager
per-stage path — across nulls, strings, decimals, empty inputs and bucket
boundaries — while structurally identical executions reuse compiled
executables (observable through exec_cache trace events), donation +
OOM-recovery wipes stay safe, and the chains the fuser must not touch
(blocked union-aggregation wrappers, shared CTE subtrees, untraceable
host-side casts) keep their exact prior semantics.

Satellite regressions ride along: Limit-over-Sort top-k gather and the
MultiJoin join-order replay memo.
"""

import json
import os

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu import faults
from nds_tpu.engine import fuse as F
from nds_tpu.engine import plan as P
from nds_tpu.engine.session import Session
from nds_tpu.obs.trace import bind as obs_bind

rng = np.random.default_rng(7)


def _table(n, seed=0):
    r = np.random.default_rng(seed)
    ks = r.integers(0, 15, n)
    vs = r.integers(-80, 80, n)
    from decimal import Decimal

    return pa.table(
        {
            "k": pa.array(
                [None if i % 11 == 0 else int(v) for i, v in enumerate(ks)],
                pa.int32(),
            ),
            "v": pa.array(
                [None if i % 7 == 3 else int(v) for i, v in enumerate(vs)],
                pa.int64(),
            ),
            "cat": pa.array(
                [
                    None if i % 13 == 5 else ["Books", "Music", "Shoes", "Home"][int(x) % 4]
                    for i, x in enumerate(ks)
                ],
                pa.string(),
            ),
            "amt": pa.array(
                [Decimal(int(v) * 3) / 100 for v in vs], pa.decimal128(7, 2)
            ),
            "d": pa.array(
                [10957 + int(x) * 37 for x in ks], pa.int32()
            ),
        }
    )


def _sessions(n=2000, conf=None, conf_off=None):
    on = Session(conf=dict(conf or {}))
    off = Session(conf=dict(conf_off or {}, **{"engine.fuse": "off"}))
    t = _table(n)
    u = _table(n, seed=1)
    for s in (on, off):
        s.register_arrow("t", t)
        s.register_arrow("u", u)
    return on, off


EQUALITY_QUERIES = [
    # plain filter chain (mask-only pipeline, count mode)
    "select k, v from t where v > 10 and k is not null order by k, v",
    # filter + computed projection (string LIKE over dictionary)
    "select k, v * 2 vv, cat from t where cat like 'B%' and v between -50 and 50 "
    "order by k, vv",
    # IN list + CASE + decimal arithmetic
    "select k, case when v > 0 then amt else amt * -1 end aa from t "
    "where cat in ('Books', 'Shoes') order by k, aa",
    # null-sensitive predicates (three-valued logic through the fused mask)
    "select k, v from t where v <> 3 or k = 5 order by k, v",
    # chain feeding an aggregate (partial-agg input arrives fused)
    "select k, sum(v) sv, count(*) c, avg(amt) aa from t where v > -60 "
    "group by k order by k",
    # post-join linear wrappers (pipeline over a join output)
    "select x.k, x.s from (select t.k \"k\", t.v + u.v s from t, u "
    "where t.k = u.k and t.v > u.v) x where x.s > 20 order by x.k, x.s",
    # date function + projection-only pipeline
    "select k, year(cast(d as date)) y from t where v >= 0 order by k, y",
    # empty result through the fused mask
    "select k, v from t where v > 1000 order by k",
]


@pytest.mark.parametrize("qi", range(len(EQUALITY_QUERIES)))
def test_fused_path_equality(qi):
    q = EQUALITY_QUERIES[qi]
    on, off = _sessions()
    a = on.sql(q).collect()
    b = off.sql(q).collect()
    assert a.equals(b), q


def test_float_division_within_validator_epsilon():
    """The one permitted fused/unfused divergence: float64 expression
    chains may differ in the FINAL ULP (XLA's algebraic simplifier
    reassociates division chains it can see whole). Pin the bound at
    1e-12 relative — four orders of magnitude inside the validator's 1e-5
    epsilon contract (nds_tpu/validate.py:compare)."""
    import math

    on, off = _sessions()
    q = ("select k, sum(v) * 100 / (1 + sum(amt)) r from t "
         "where v > -70 group by k order by k")
    a = on.sql(q).collect().to_pylist()
    b = off.sql(q).collect().to_pylist()
    assert len(a) == len(b) and a
    for x, y in zip(a, b):
        assert x["k"] == y["k"]
        if x["r"] is None or y["r"] is None:
            assert x["r"] == y["r"]
        else:
            assert math.isclose(x["r"], y["r"], rel_tol=1e-12)


def test_fused_over_empty_table():
    on, off = _sessions()
    empty = _table(0)
    for s in (on, off):
        s.register_arrow("e", empty)
    q = "select k, v + 1 vv from e where v > 0 order by k"
    assert on.sql(q).collect().equals(off.sql(q).collect())


@pytest.mark.parametrize("n", [1023, 1024, 1025])
def test_bucket_boundary_rows(n):
    on, off = _sessions(n=n)
    q = ("select k, v - 1 w from t where v > 0 and k is not null "
         "order by k, w")
    assert on.sql(q).collect().equals(off.sql(q).collect())


def test_mark_pipelines_plan_shape():
    s, _ = _sessions()
    r = s.sql("select k, v * 2 vv from t where v > 0 and cat like 'B%'")
    # the chain collapsed into one Pipeline over the scan
    pipes = []

    def walk(n):
        if isinstance(n, P.Pipeline):
            pipes.append(n)
        for c in n.children():
            if c is not None:
                walk(c)

    walk(r.plan)
    assert len(pipes) == 1
    p = pipes[0]
    assert isinstance(p.child, P.Scan)
    # execution order: filter first, projection last
    assert isinstance(p.stages[0], P.Filter)
    assert isinstance(p.stages[-1], P.Project)
    assert all(st.child is None for st in p.stages)
    # scans alias catalog buffers: never donation-eligible
    assert p.donate_ok is False
    assert "Pipeline" in r.explain()


def test_pure_rename_chain_not_fused():
    s, _ = _sessions()
    r = s.sql("select k kk, v from t")
    assert not isinstance(r.plan, P.Pipeline)


def test_executable_reuse_and_trace_events(tmp_path):
    s = Session(conf={"engine.trace_dir": str(tmp_path)})
    s.register_arrow("t", _table(2000))
    q = "select k, v + 1 vv from t where v > 0 order by k, vv"
    s.sql(q).collect()
    s.conf["engine.plan_cache"] = "off"
    s.sql(q).collect()
    evs = [
        json.loads(line)
        for line in open(s.tracer.path, encoding="utf-8")
        if line.strip()
    ]
    ec = [e for e in evs if e["kind"] == "exec_cache"]
    ps = [e for e in evs if e["kind"] == "pipeline_span"]
    assert ec and ps
    assert ec[0]["hit"] is False and ec[-1]["hit"] is True
    assert all(e["fused"] for e in ps)
    assert all(isinstance(e["bucket"], int) for e in ec)


def test_executable_reuse_across_scale_factors():
    """Same structure + different SF (row count/bucket) => the SAME traced
    entry serves both; the trace machinery is not rebuilt (VERDICT items
    4+5: compiled-executable reuse across a stream)."""
    s = Session()
    s.register_arrow("t", _table(1500))
    q = "select k, v + 1 vv from t where v > 0 and k < 10 order by k, vv"
    expect_small = s.sql(q).collect()
    assert len(s.exec_cache.map) == 1
    entry_small = next(iter(s.exec_cache.map.values()))
    # "SF up": re-register the same schema at 8x the rows (numeric columns
    # carry no dictionaries, so the input signature is identical)
    s.register_arrow("t", _table(12000, seed=3))
    s.sql(q).collect()
    assert len(s.exec_cache.map) == 1  # same entry, no rebuild
    assert next(iter(s.exec_cache.map.values())) is entry_small
    # bucket accounting: two distinct buckets compiled, zero->more hits on
    # re-run
    assert s.exec_cache.misses >= 2
    s.conf["engine.plan_cache"] = "off"
    hits0 = s.exec_cache.hits
    s.sql(q).collect()
    assert s.exec_cache.hits > hits0
    # and the small result is reproducible after switching back
    s.register_arrow("t", _table(1500))
    assert s.sql(q).collect().equals(expect_small)


def test_unfusible_chain_pins_to_eager():
    """A numeric->string cast formats device values on host: the chain
    cannot trace, the build is attempted once, and results match the
    unfused path exactly."""
    on, off = _sessions()
    q = "select cast(v as varchar(10)) sv, k from t where v > 0 order by k, sv"
    assert on.sql(q).collect().equals(off.sql(q).collect())
    pinned = [v for v in on.exec_cache.map.values() if v is None]
    assert pinned  # the signature is pinned, not re-attempted
    # re-run still correct (eager fallback path)
    on.conf["engine.plan_cache"] = "off"
    assert on.sql(q).collect().equals(off.sql(q).collect())


def test_scalar_subquery_stays_unfused_and_correct():
    on, off = _sessions()
    q = ("select k, v from t where v > (select avg(v) from u) "
         "order by k, v")
    assert on.sql(q).collect().equals(off.sql(q).collect())


def test_blocked_union_agg_still_blocked_with_fusion():
    """The fused wrappers must stay visible to the blocked union-agg shape
    check (plan._peel_wrappers expands Pipeline nodes), and windowed
    results must equal the unfused oracle."""
    conf = {"engine.union_agg_window_rows": 512}
    on = Session(conf=dict(conf))
    off = Session(conf=dict(conf, **{"engine.fuse": "off"}))
    for s in (on, off):
        s.register_arrow("t", _table(3000))
        s.register_arrow("u", _table(3000, seed=1))
    q = """
    select k, sum(v) sv, count(*) c, avg(v) av
    from (select k, v from t where v > -70
          union all
          select k, v from u) x
    where v < 70
    group by k order by k
    """
    ra = on.sql(q)
    a = ra.collect()
    assert a.equals(off.sql(q).collect())
    # the blocked path actually engaged under fusion
    assert ra.executor.last_blocked_union is not None
    assert ra.executor.last_blocked_union["windows"] > 1


def test_donation_safety_and_oom_wipe():
    """fuse_donate=on over a join-fed pipeline (donate-eligible child):
    results stable across reruns, and an OOM-recovery wipe (new catalog
    buffers, new signatures) neither crashes nor changes results."""
    conf = {"engine.fuse_donate": "on"}
    on = Session(conf=dict(conf))
    off = Session(conf={"engine.fuse": "off"})
    for s in (on, off):
        s.register_arrow("t", _table(2000))
        s.register_arrow("u", _table(2000, seed=1))
    q = ("select x.k, x.s + 1 s1 from (select t.k \"k\", t.v + u.v s "
         "from t, u where t.k = u.k and t.v > u.v) x where x.s > 10 "
         "order by x.k, s1")
    expect = off.sql(q).collect()
    assert on.sql(q).collect().equals(expect)
    on.conf["engine.plan_cache"] = "off"
    assert on.sql(q).collect().equals(expect)
    on.recover_memory("test: simulated OOM wipe")
    assert on.sql(q).collect().equals(expect)


def test_limit_over_sort_topk():
    on, off = _sessions()
    for q in (
        "select k, v from t order by v desc, k limit 7",
        "select k, v from t where v > 0 order by k, v limit 1",
        # limit beyond the row count
        "select k, v from t where v > 78 order by v, k limit 500",
        "select cat, amt from t order by cat, amt limit 13",
    ):
        assert on.sql(q).collect().equals(off.sql(q).collect()), q


def _q3_star(n=2000, seed=11):
    """Fact, date dimension and item of query3's shape, `_table`-sized."""
    r = np.random.default_rng(seed)
    n_dates, n_items = 400, 300
    return {
        "date_dim": pa.table({
            "d_date_sk": pa.array(np.arange(n_dates), pa.int32()),
            "d_year": pa.array(1998 + np.arange(n_dates) // 120, pa.int32()),
            "d_moy": pa.array(1 + np.arange(n_dates) % 12, pa.int32()),
        }),
        "item": pa.table({
            "i_item_sk": pa.array(np.arange(n_items), pa.int32()),
            "i_brand_id": pa.array(r.integers(1, 40, n_items), pa.int32()),
            "i_brand": pa.array([f"brand#{i % 40}" for i in range(n_items)]),
            "i_manager_id": pa.array(r.integers(1, 20, n_items), pa.int32()),
        }),
        "store_sales": pa.table({
            "ss_sold_date_sk": pa.array(r.integers(0, n_dates, n), pa.int32()),
            "ss_item_sk": pa.array(r.integers(0, n_items, n), pa.int32()),
            "ss_ext_sales_price": pa.array(
                r.uniform(0, 500, n).round(2), pa.float64()
            ),
        }),
    }


JOIN_ORDER_QUERIES = {
    "two_tables": (
        "select t.k, sum(t.v) s from t, u where t.k = u.k and u.v > 0 "
        "group by t.k order by t.k"
    ),
    "q3_star": (
        "select d.d_year, i.i_brand_id brand_id, i.i_brand brand, "
        "sum(ss_ext_sales_price) sum_agg "
        "from date_dim d, store_sales, item i "
        "where d.d_date_sk = ss_sold_date_sk and ss_item_sk = i.i_item_sk "
        "and i.i_manager_id = 10 and d.d_moy = 11 "
        "group by d.d_year, i.i_brand, i.i_brand_id "
        "order by d.d_year, sum_agg desc, brand_id limit 100"
    ),
}


@pytest.mark.parametrize("shape", sorted(JOIN_ORDER_QUERIES))
def test_join_order_replay_memo(shape):
    on, _ = _sessions()
    for name, t in _q3_star().items():
        on.register_arrow(name, t)
    q = JOIN_ORDER_QUERIES[shape]
    a = on.sql(q).collect()
    assert a.num_rows > 0
    recorded = {
        fp: list(v["steps"])
        for fp, v in on.join_order_cache.items() if "steps" in v
    }
    assert recorded
    on.conf["engine.plan_cache"] = "off"
    assert on.sql(q).collect().equals(a)  # replayed order, same result
    # the steady run replayed the memo, it did not record again
    for fp, steps in recorded.items():
        assert on.join_order_cache[fp]["steps"] == steps
    # catalog change invalidates the memo
    on.register_arrow("w", _table(100))
    assert on.join_order_cache == {}


def test_input_signature_dictionary_identity():
    s, _ = _sessions()
    t = s.catalog.load("t")
    sig1 = F.input_signature(t)
    sig2 = F.input_signature(s.catalog.load("t"))
    assert sig1 == sig2  # cached catalog columns: same dictionary objects


# --- a ROLLUP over string keys keeps its pipelines (ISSUE 42) ---------------


ROLLUP_36 = """
select sum(ss_net_profit) / sum(ss_ext_sales_price) as gross_margin,
       i_category, i_class,
       grouping(i_category) + grouping(i_class) as lochierarchy,
       rank() over (partition by grouping(i_category) + grouping(i_class),
                    case when grouping(i_class) = 0 then i_category end
                    order by sum(ss_net_profit) / sum(ss_ext_sales_price) asc)
       as rank_within_parent
from store_sales, item
where i_item_sk = ss_item_sk
group by rollup (i_category, i_class)
order by lochierarchy desc,
         case when lochierarchy = 0 then i_category end,
         rank_within_parent
limit 100
"""


def _rollup_tables():
    r = np.random.default_rng(36)
    n = 2000
    item = pa.table({
        "i_item_sk": pa.array(range(1, 51), pa.int32()),
        "i_category": pa.array(
            [["Books", "Music", "Shoes"][i % 3] for i in range(50)]),
        "i_class": pa.array([f"class{i % 7}" for i in range(50)]),
    })
    store_sales = pa.table({
        "ss_item_sk": pa.array(r.integers(1, 51, n), pa.int32()),
        "ss_net_profit": pa.array(r.integers(-500, 900, n) / 4.0),
        "ss_ext_sales_price": pa.array(r.integers(100, 5000, n) / 4.0),
    })
    return item, store_sales


def _sqlite_rollup(item, store_sales):
    """query36's shape as sqlite can say it: a UNION ALL of the ROLLUP's
    three levels, the rank by level and parent."""
    import sqlite3

    conn = sqlite3.connect(":memory:")
    for name, t in (("item", item), ("store_sales", store_sales)):
        conn.execute(f"create table {name} ({', '.join(t.column_names)})")
        conn.executemany(
            f"insert into {name} values ({', '.join('?' * t.num_columns)})",
            zip(*(c.to_pylist() for c in t.columns)))
    levels = " union all ".join(
        f"select sum(ss_net_profit) / sum(ss_ext_sales_price) gross_margin, "
        f"{cat} i_category, {cls} i_class, {level} lochierarchy "
        f"from store_sales, item where i_item_sk = ss_item_sk {group}"
        for cat, cls, level, group in (
            ("i_category", "i_class", 0, "group by i_category, i_class"),
            ("i_category", "null", 1, "group by i_category"),
            ("null", "null", 2, "")))
    return conn.execute(
        "select gross_margin, i_category, i_class, lochierarchy, "
        "rank() over (partition by lochierarchy, "
        "case when lochierarchy = 0 then i_category end "
        f"order by gross_margin asc) from ({levels})").fetchall()


def _rows(table):
    key = lambda r: (-r[3], r[1] or "", r[2] or "", r[4])
    return sorted(zip(*(c.to_pylist() for c in table.columns)), key=key)


def test_a_rollup_over_string_keys_builds_its_pipelines_once(tmp_path):
    """query36's ROLLUP with a CASE above it, three times warm: the levels
    share their base columns' dictionary objects, so the dictionaries the
    concatenation hands on are the same objects at every execution and the
    Pipeline above the ROLLUP, keyed by their identity, is built once
    (`exec_cache` hit false, true, true; PR 41 read false, false, false and
    a trace + load in every warm execution)."""
    item, store_sales = _rollup_tables()
    s = Session(conf={"engine.trace_dir": str(tmp_path)})
    s.register_arrow("item", item)
    s.register_arrow("store_sales", store_sales)
    answers = []
    for i in range(3):
        # a catalog change drops the plan-result cache, as before every
        # cycle of the benchmark's window: the statement really executes
        s.register_arrow("tick", pa.table({"n": [i]}))
        with obs_bind(s.tracer), faults.scope(f"run{i}"):
            answers.append(s.sql(ROLLUP_36).collect())
    s.tracer.close()
    evs = [json.loads(line)
           for line in open(s.tracer.path, encoding="utf-8") if line.strip()]
    by_pipeline = {}
    for e in evs:
        if e["kind"] == "exec_cache":
            by_pipeline.setdefault(e["pipeline"], {}).setdefault(
                e["query"], []).append(e["hit"])
    assert by_pipeline
    for fp, runs in by_pipeline.items():
        assert set(runs) == {"run0", "run1", "run2"}, fp  # it executed
        assert not any(runs["run0"]) and all(runs["run1"] + runs["run2"]), (
            fp, runs)
    for run in ("run1", "run2"):
        spans = [e for e in evs if e.get("query") == run
                 and e["kind"] in ("op_span", "result_span")]
        assert len(spans) > 5
        assert not any(e["compile_ms"] for e in spans)
        memo = {}
        for e in spans:
            for k, n in (e.get("dict_memo") or {}).items():
                memo[k] = memo.get(k, 0) + n
        assert memo.get("same", 0) >= 4 and not memo.get("miss"), memo
    assert len(answers[0]) == 3 * 7 + 3 + 1
    assert answers[1].equals(answers[0]) and answers[2].equals(answers[0])
    want = sorted(_sqlite_rollup(item, store_sales),
                  key=lambda r: (-r[3], r[1] or "", r[2] or "", r[4]))
    got = _rows(answers[0])
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[1:] == w[1:]
        assert g[0] == pytest.approx(w[0], rel=1e-9)
