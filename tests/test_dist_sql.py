"""Distributed SQL execution: real queries on the 8-device CPU mesh must
produce identical results to the single-device engine (the project's core
TPU-first claim — reference analogue: Spark executor data parallelism,
nds/base.template:28-31)."""

import jax
import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.engine.session import Session
from nds_tpu.parallel.dist import make_mesh

N_DEV = 8


def _synth_tables(n_fact=4096, n_dates=256, n_items=128, n_stores=8, seed=0):
    rng = np.random.default_rng(seed)
    date_dim = pa.table(
        {
            "d_date_sk": np.arange(2450000, 2450000 + n_dates, dtype=np.int64),
            "d_year": (1998 + (np.arange(n_dates) // 100)).astype(np.int64),
            "d_moy": (np.arange(n_dates) % 12 + 1).astype(np.int64),
        }
    )
    item = pa.table(
        {
            "i_item_sk": np.arange(1, n_items + 1, dtype=np.int64),
            "i_brand_id": rng.integers(1, 12, n_items),
            "i_manager_id": rng.integers(1, 20, n_items),
            "i_category": pa.array(
                rng.choice(["Books", "Music", "Sports", None], n_items)
            ),
        }
    )
    store = pa.table(
        {
            "s_store_sk": np.arange(1, n_stores + 1, dtype=np.int64),
            "s_state": pa.array(rng.choice(["TN", "CA", "TX"], n_stores)),
        }
    )
    price = np.round(rng.random(n_fact) * 100, 2)
    price[rng.random(n_fact) < 0.05] = np.nan
    tickets = rng.integers(1, n_fact // 2, n_fact)
    store_sales = pa.table(
        {
            "ss_sold_date_sk": rng.integers(2450000, 2450000 + n_dates, n_fact),
            "ss_item_sk": rng.integers(1, n_items + 1, n_fact),
            "ss_ticket_number": tickets,
            "ss_store_sk": pa.array(
                np.where(
                    rng.random(n_fact) < 0.03,
                    None,
                    rng.integers(1, n_stores + 1, n_fact).astype(object),
                )
            ).cast(pa.int64()),
            "ss_quantity": rng.integers(1, 100, n_fact),
            "ss_ext_sales_price": pa.array(
                np.where(np.isnan(price), None, price.astype(object)),
                type=pa.float64(),
            ),
        }
    )
    # returns: half sampled from real sales (matching ticket+item), half junk
    n_ret = n_fact // 2
    pick = rng.integers(0, n_fact, n_ret // 2)
    ret_items = np.concatenate(
        [
            np.asarray(store_sales.column("ss_item_sk"))[pick],
            rng.integers(1, n_items + 1, n_ret - n_ret // 2),
        ]
    )
    ret_tickets = np.concatenate(
        [tickets[pick], rng.integers(n_fact, 2 * n_fact, n_ret - n_ret // 2)]
    )
    store_returns = pa.table(
        {
            "sr_item_sk": ret_items,
            "sr_ticket_number": ret_tickets,
            "sr_return_amt": np.round(rng.random(n_ret) * 50, 2),
        }
    )
    return {
        "date_dim": date_dim,
        "item": item,
        "store": store,
        "store_sales": store_sales,
        "store_returns": store_returns,
    }


def _make_session(mesh):
    s = Session(mesh=mesh)
    for name, t in _synth_tables().items():
        s.register_arrow(name, t)
    return s


@pytest.fixture(scope="module")
def oracle():
    return _make_session(None)


@pytest.fixture(scope="module")
def dist():
    assert len(jax.devices()) >= N_DEV
    return _make_session(make_mesh(N_DEV))


QUERIES = {
    "star_agg_q3": """
        select d.d_year, i.i_brand_id brand_id, sum(ss_ext_sales_price) s,
               count(*) cnt
        from date_dim d, store_sales, item i
        where d.d_date_sk = ss_sold_date_sk and ss_item_sk = i.i_item_sk
          and i.i_manager_id = 10 and d.d_moy = 11
        group by d.d_year, i.i_brand_id
        order by d.d_year, s desc, brand_id
    """,
    "filter_sort_limit": """
        select ss_item_sk, ss_quantity from store_sales
        where ss_quantity > 90 order by ss_quantity desc, ss_item_sk limit 20
    """,
    "left_join_nulls": """
        select s.s_state, count(*) c, avg(ss_quantity) aq
        from store_sales left join store s on ss_store_sk = s_store_sk
        group by s.s_state order by s.s_state
    """,
    "semi_anti": """
        select count(*) c from store_sales
        where ss_item_sk in (select i_item_sk from item where i_brand_id = 3)
          and ss_store_sk not in (select s_store_sk from store where s_state = 'TN')
    """,
    "global_agg": """
        select count(*) c, sum(ss_quantity) sq, min(ss_ext_sales_price) mn,
               max(ss_ext_sales_price) mx
        from store_sales
    """,
    "having_groups": """
        select ss_store_sk, count(*) c from store_sales
        group by ss_store_sk having count(*) > 10 order by ss_store_sk
    """,
    "window_rank": """
        select * from (
            select ss_store_sk, ss_item_sk, ss_quantity,
                   rank() over (partition by ss_store_sk
                                order by ss_quantity desc, ss_item_sk) rk
            from store_sales where ss_store_sk is not null
        ) w where rk <= 3 order by ss_store_sk, rk, ss_item_sk
    """,
    "window_running_sum": """
        select d_year, s_state, sum(sum(ss_quantity)) over
                   (partition by s_state order by d_year) cume
        from store_sales, date_dim, store
        where ss_sold_date_sk = d_date_sk and ss_store_sk = s_store_sk
        group by d_year, s_state order by s_state, d_year
    """,
    "rollup_groups": """
        select d_year, s_state, count(*) c from store_sales, date_dim, store
        where ss_sold_date_sk = d_date_sk and ss_store_sk = s_store_sk
        group by rollup(d_year, s_state) order by d_year, s_state
    """,
    "setop_except": """
        select ss_item_sk from store_sales where ss_quantity > 50
        except
        select ss_item_sk from store_sales where ss_quantity <= 50
        order by ss_item_sk
    """,
}


@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_distributed_matches_oracle(oracle, dist, qname):
    q = QUERIES[qname]
    a = oracle.sql(q).collect()
    b = dist.sql(q).collect()
    assert a.schema.names == b.schema.names
    assert a.num_rows == b.num_rows
    for col in a.schema.names:
        av, bv = a.column(col).to_pylist(), b.column(col).to_pylist()
        for x, y in zip(av, bv):
            if isinstance(x, float) and isinstance(y, float):
                assert abs(x - y) < 1e-9 or (np.isnan(x) and np.isnan(y))
            else:
                assert x == y, (qname, col, x, y)


FACT_FACT_Q = """
    select ss_item_sk, count(*) c, sum(sr_return_amt) s
    from store_sales, store_returns
    where ss_item_sk = sr_item_sk and ss_ticket_number = sr_ticket_number
    group by ss_item_sk
    order by ss_item_sk
"""


def test_exchange_join_matches_oracle():
    """Mesh fact-fact join: both sides row-sharded, hash-partitioned over the
    exchange, joined locally — must equal the single-device sort join
    (VERDICT r2 item #6; reference analogue: Spark shuffle join)."""
    conf = {"engine.exchange_min_rows": 1}
    oracle = Session(conf=conf)
    dist = Session(mesh=make_mesh(N_DEV), conf=conf)
    for name, t in _synth_tables().items():
        oracle.register_arrow(name, t)
        dist.register_arrow(name, t)
    failures = []
    dist.register_listener(failures.append)
    a = oracle.sql(FACT_FACT_Q).collect()
    b = dist.sql(FACT_FACT_Q).collect()
    assert a.num_rows == b.num_rows and a.num_rows > 0
    for col in a.schema.names:
        for x, y in zip(a.column(col).to_pylist(), b.column(col).to_pylist()):
            if isinstance(x, float):
                assert abs(x - y) < 1e-6, (col, x, y)
            else:
                assert x == y, (col, x, y)


def test_exchange_join_overflow_retries():
    """Skewed keys overflow the first capacity guess; the join must retry
    with doubled caps, emit a task-failure event, and still be correct."""
    rng = np.random.default_rng(7)
    n = 4096
    # 90% of rows share ONE key: that destination's bucket (and its local
    # pair count) overflow the 2x-balanced initial capacity
    # sparse key domain keeps the dense star-join path out of the way
    skew = np.where(rng.random(n) < 0.9, 7, rng.integers(0, 256, n))
    skew = skew * 1_000_003
    left = pa.table({"k": skew, "lv": np.arange(n, dtype=np.int64)})
    right = pa.table(
        {"k": np.arange(256, dtype=np.int64) * 1_000_003,
         "rv": np.arange(256, dtype=np.int64)}
    )
    conf = {"engine.exchange_min_rows": 1}
    oracle = Session(conf=conf)
    dist = Session(mesh=make_mesh(N_DEV), conf=conf)
    for s in (oracle, dist):
        s.register_arrow("l", left)
        s.register_arrow("r", right)
    failures = []
    dist.register_listener(failures.append)
    q = "select count(*) c, sum(lv) sl, sum(rv) sr from l, r where l.k = r.k"
    a = oracle.sql(q).collect()
    b = dist.sql(q).collect()
    assert a.to_pylist() == b.to_pylist()
    assert any("exchange join" in f for f in failures)


def _assert_tables_equal(a, b, tol=1e-9, ctx=""):
    assert a.schema.names == b.schema.names, ctx
    assert a.num_rows == b.num_rows, ctx
    for col in a.schema.names:
        for x, y in zip(a.column(col).to_pylist(), b.column(col).to_pylist()):
            if isinstance(x, float) and isinstance(y, float):
                assert abs(x - y) < tol or (np.isnan(x) and np.isnan(y)), (
                    ctx, col, x, y,
                )
            else:
                assert x == y, (ctx, col, x, y)


def _exchange_pair(conf=None, tables=None, mesh_devs=N_DEV):
    conf = {"engine.exchange_min_rows": 1, **(conf or {})}
    oracle = Session(conf=dict(conf))
    dist = Session(mesh=make_mesh(mesh_devs), conf=dict(conf))
    for name, t in (tables or {}).items():
        oracle.register_arrow(name, t)
        dist.register_arrow(name, t)
    return oracle, dist


def _spy_exchange(monkeypatch):
    """Record every _try_exchange_join outcome so tests can assert the
    exchange path actually carried the join (not a silent fallback)."""
    from nds_tpu.engine import exec as X

    taken = []
    orig = X.Executor._try_exchange_join

    def spy(self, *a, **kw):
        r = orig(self, *a, **kw)
        taken.append(r is not None)
        return r

    monkeypatch.setattr(X.Executor, "_try_exchange_join", spy)
    return taken


def test_exchange_left_join_null_keys_match_oracle(monkeypatch):
    """LEFT join through the exchange: null-keyed left rows never route but
    MUST survive null-extended, and shipped-but-unmatched rows null-extend
    from the received partition — bit-identical to the single-device path
    (ISSUE 13 satellite: null-keyed LEFT rows surviving the exchange)."""
    taken = _spy_exchange(monkeypatch)
    rng = np.random.default_rng(23)
    n = 4096
    # sparse key domain keeps the dense star-join fast path out of the way
    k = (rng.integers(0, 512, n) * 1_000_003).astype(object)
    k[rng.random(n) < 0.07] = None  # null keys: must null-extend, not drop
    left = pa.table({
        "k": pa.array(k, pa.int64()),
        "lv": np.arange(n, dtype=np.int64),
    })
    # right misses half the key domain -> plenty of unmatched left rows
    right = pa.table({
        "k": np.arange(0, 512, 2, dtype=np.int64) * 1_000_003,
        "rv": np.arange(256, dtype=np.int64) * 10,
    })
    oracle, dist = _exchange_pair(tables={"l": left, "r": right})
    q = ("select l.k, lv, rv from l left join r on l.k = r.k "
         "order by lv, rv")
    _assert_tables_equal(
        oracle.sql(q).collect(), dist.sql(q).collect(), ctx="left-null"
    )
    assert any(taken), "exchange join path was never exercised"
    # aggregate form too (null-keyed rows count, rv sums skip nulls)
    q2 = ("select count(*) c, count(rv) cr, sum(lv) sl, sum(rv) sr "
          "from l left join r on l.k = r.k")
    assert oracle.sql(q2).to_pylist() == dist.sql(q2).to_pylist()


def test_exchange_join_hot_key_skew_matches_oracle(monkeypatch):
    """One key owning >50% of the rows: the hot destination overflows the
    balanced capacity guess, the retry doubles it, and the result still
    equals the oracle — with the skew visible in the `exchange` event."""
    from nds_tpu.obs.trace import Tracer

    taken = _spy_exchange(monkeypatch)
    rng = np.random.default_rng(31)
    n = 8192
    hot = rng.random(n) < 0.6  # 60% of rows share ONE key
    k = np.where(hot, 13, rng.integers(0, 1024, n)) * 1_000_003
    left = pa.table({"k": k, "lv": np.arange(n, dtype=np.int64)})
    right = pa.table({
        "k": np.arange(1024, dtype=np.int64) * 1_000_003,
        "rv": np.arange(1024, dtype=np.int64),
    })
    oracle, dist = _exchange_pair(tables={"l": left, "r": right})
    tracer = Tracer(None)  # in-memory collector
    dist.tracer = tracer
    q = ("select count(*) c, sum(lv) sl, sum(rv) sr from l, r "
         "where l.k = r.k")
    a = oracle.sql(q).collect()
    b = dist.sql(q).collect()
    assert a.to_pylist() == b.to_pylist()
    assert any(taken)
    ex = [e for e in tracer.events if e["kind"] == "exchange"]
    assert ex, "no exchange trace evidence"
    assert any(e["skew"] > 2.0 for e in ex), ex  # hot key -> imbalance
    assert all(e["bytes_moved"] > 0 and e["partitions"] == N_DEV
               for e in ex)


def test_exchange_join_skew_feedback_drops_retries_to_zero(
    monkeypatch, tmp_path
):
    """Recorded hot-key skew seeds the NEXT session's exchange capacity
    (analysis/feedback.py): run 1 (record mode) pays the overflow-retry
    doubling and persists the measured skew; run 2 (on mode, same store
    dir) pre-splits its capacity guess from the record and lands the
    identical oracle-equal answer with ZERO retries — the rediscovery
    cost is paid once per fleet, not once per session."""
    from nds_tpu.obs.trace import Tracer

    taken = _spy_exchange(monkeypatch)
    rng = np.random.default_rng(31)
    n = 8192
    hot = rng.random(n) < 0.6  # the same hot-key shape as the probe above
    k = np.where(hot, 13, rng.integers(0, 1024, n)) * 1_000_003
    left = pa.table({"k": k, "lv": np.arange(n, dtype=np.int64)})
    right = pa.table({
        "k": np.arange(1024, dtype=np.int64) * 1_000_003,
        "rv": np.arange(1024, dtype=np.int64),
    })
    q = ("select count(*) c, sum(lv) sl, sum(rv) sr from l, r "
         "where l.k = r.k")

    def run(mode):
        oracle, dist = _exchange_pair(
            conf={"engine.feedback_dir": str(tmp_path / "fb"),
                  "engine.plan_feedback": mode},
            tables={"l": left, "r": right},
        )
        tracer = Tracer(None)
        dist.tracer = tracer
        a = oracle.sql(q).to_pylist()
        b = dist.sql(q).to_pylist()
        assert a == b, mode
        return ([e for e in tracer.events if e["kind"] == "exchange"],
                dist.feedback_store)

    ex1, store1 = run("record")
    assert ex1 and any(e["retries"] > 0 for e in ex1), ex1
    assert store1.stats["skew_records"] >= 1
    ex2, _store2 = run("on")
    assert ex2 and all(e["retries"] == 0 for e in ex2), ex2
    assert any(e["skew"] > 2.0 for e in ex2)  # data still skewed; no retry
    assert any(taken)


def test_exchange_join_empty_partitions_match_oracle(monkeypatch):
    """Keys covering only 2 of 8 destinations: six devices receive ZERO
    rows and the join must still equal the oracle (the empty-partition
    searchsorted/compaction edge)."""
    taken = _spy_exchange(monkeypatch)
    rng = np.random.default_rng(37)
    n = 4096
    # destination = hash(key) % n_dev: with only TWO distinct left keys at
    # most two devices receive left rows — at least six work on empty
    # received partitions (sparse values keep the dense path out)
    k = np.where(rng.random(n) < 0.5, 7, 11) * 1_000_003
    left = pa.table({"k": k, "lv": np.arange(n, dtype=np.int64)})
    right = pa.table({
        "k": np.arange(0, 256, dtype=np.int64) * 1_000_003,
        "rv": np.arange(256, dtype=np.int64),
    })
    oracle, dist = _exchange_pair(tables={"l": left, "r": right})
    q = ("select count(*) c, sum(lv) sl, sum(rv) sr from l, r "
         "where l.k = r.k")
    assert oracle.sql(q).to_pylist() == dist.sql(q).to_pylist()
    # left-join flavor rides the same received partitions
    q2 = ("select count(*) c, count(rv) cr from l left join r "
          "on l.k = r.k")
    assert oracle.sql(q2).to_pylist() == dist.sql(q2).to_pylist()
    assert any(taken)


def test_exchange_persistent_overflow_tiers_through_spill_pool(monkeypatch):
    """Single-key-scale skew a hash partitioning can never split: every
    retry re-overflows, and the join must tier through the host spill pool
    (planned degradation composing with scale-out) instead of aborting —
    still oracle-equal, with spill evidence recorded."""
    from nds_tpu.engine import exec as X

    # force every attempt to report overflow so the retry loop exhausts
    taken = _spy_exchange(monkeypatch)
    n = 4096
    # ONE (sparse) key owns the table; sparse values decline the dense path
    k = np.full(n, 7 * 1_000_003, dtype=np.int64)
    left = pa.table({"k": k, "lv": np.arange(n, dtype=np.int64)})
    right = pa.table({"k": np.array([7, 9], dtype=np.int64) * 1_000_003,
                      "rv": np.array([1, 2], dtype=np.int64)})
    monkeypatch.setattr(X.Executor, "_EXCHANGE_MAX_ATTEMPTS", 0)
    oracle, dist = _exchange_pair(tables={"l": left, "r": right})
    failures = []
    dist.register_listener(failures.append)
    q = "select count(*) c, sum(lv) sl, sum(rv) sr from l, r where l.k = r.k"
    a = oracle.sql(q).collect()
    b = dist.sql(q).collect()
    assert a.to_pylist() == b.to_pylist()
    assert any("spill pool" in f for f in failures), failures
    assert dist.last_spill is not None and dist.last_spill["ops"] >= 1
    assert any(taken)


def test_semi_filtered_dim_join_matches_oracle():
    """Regression for the query83/query77 mesh mismatch the SF0.01 gate
    caught: a sharded fact joined against a SEMI-filtered replicated dim
    compacts the masked dim through compact_indices — whose cumsum+scatter
    kernel the SPMD partitioner mislowers on sharded masks (rows silently
    dropped). The full shape must equal the single-device oracle."""
    rng = np.random.default_rng(5)
    nd = 73049
    dim_sk = np.arange(2415022, 2415022 + nd, dtype=np.int64)
    dval = np.array([f"v{i % 97}" for i in range(nd)])
    nf = 736  # the SF0.01 web_returns scale that exposed the truncation
    fact = pa.table({
        "wr_returned_date_sk": rng.choice(dim_sk, nf),
        "wr_return_quantity": rng.integers(1, 50, nf),
    })
    dim = pa.table({
        "d_date_sk": dim_sk, "d_date": dval,
        "d_week_seq": (np.arange(nd) // 7).astype(np.int64),
    })
    oracle_s = Session()
    dist_s = Session(mesh=make_mesh(N_DEV))
    for s in (oracle_s, dist_s):
        s.register_arrow("web_returns", fact)  # fact name -> row-sharded
        s.register_arrow("date_dim", dim)
    q = """select count(*) c, sum(wr_return_quantity) s
           from web_returns, date_dim
           where d_date in (select d_date from date_dim where d_week_seq in
               (select d_week_seq from date_dim where d_date in ('v3','v5')))
           and wr_returned_date_sk = d_date_sk"""
    a = oracle_s.sql(q).to_pylist()
    b = dist_s.sql(q).to_pylist()
    assert a == b and a[0]["c"] > 0, (a, b)


def test_sharded_agg_partial_merge_matches_oracle(dist, oracle):
    """Decomposable aggregates over a row-sharded fact reduce per shard and
    merge (the scatter-add lowers to per-chip partials + cross-chip merge
    under GSPMD) — sums/counts/extremes/avg must equal the oracle."""
    q = """
        select ss_quantity bucket, count(*) c, sum(ss_item_sk) s,
               min(ss_ext_sales_price) mn, max(ss_ext_sales_price) mx,
               avg(ss_ticket_number) aq
        from store_sales group by ss_quantity order by bucket
    """
    _assert_tables_equal(
        oracle.sql(q).collect(), dist.sql(q).collect(), ctx="agg-merge"
    )


def test_sharding_fallback_is_loud():
    """A mesh that can't divide the fact-table capacity must announce the
    replication fallback through the listener chain, never degrade silently
    (VERDICT r2 weak #3) — and since ISSUE 13 additionally emit a
    `mesh_fallback` trace event (schema-valid, metric-counted), record the
    fallback on the catalog entry, and have the verifier's replicated-dim
    rule flag every later plan scanning the replicated fact."""
    from nds_tpu.analysis.verifier import PlanVerifier
    from nds_tpu.engine import plan as P
    from nds_tpu.obs.metrics import MetricsSink
    from nds_tpu.obs.reader import validate_events
    from nds_tpu.obs.trace import Tracer

    s = Session(mesh=make_mesh(3))
    tracer = Tracer(None)  # in-memory collector
    tracer.sink = MetricsSink()
    s.tracer = tracer
    events = []
    s.register_listener(events.append)
    for name, t in _synth_tables().items():
        s.register_arrow(name, t)
    s.catalog.load("store_sales", ["ss_item_sk"])
    assert any("sharding fallback" in e for e in events)
    fb = [e for e in tracer.events if e["kind"] == "mesh_fallback"]
    assert fb and fb[0]["table"] == "store_sales" and fb[0]["n_dev"] == 3
    assert fb[0]["bytes"] > 0
    validate_events(tracer.events)  # schema contract holds
    assert (
        tracer.sink.registry.counter_value(
            "nds_mesh_fallback_total", table="store_sales"
        )
        == 1
    )
    assert s.catalog.entries["store_sales"].mesh_fallback
    # the verifier flags every later plan that scans the replicated fact
    plan = P.Scan("store_sales", "store_sales", ["ss_item_sk"])
    v = PlanVerifier(s.catalog).verify(plan, mesh=make_mesh(3))
    assert any(
        "replicated-dim" in x and "mesh fallback" in x for x in v
    ), v


def test_profile_compare_multichip_rounds(tmp_path):
    """`profile --bench` MULTICHIP mode: an old driver-wrapper round
    ({ok, tail} only — r01–r05 predate the metrics block) compares
    fail-soft (old_ratio null), a worsened mesh-vs-oracle ratio or an
    ok->not-ok flip flags regression, and the --bench handler routes
    multichip artifacts away from the sqlite_shared comparison."""
    import json

    from nds_tpu.cli.profile import _compare_multichip

    old_wrapper = tmp_path / "MULTICHIP_r05.json"
    old_wrapper.write_text(json.dumps(
        {"n_devices": 8, "rc": 0, "ok": True, "tail": "dryrun ok"}
    ))
    new_block = tmp_path / "gate.json"
    new_block.write_text(json.dumps({
        "n_devices": 8, "ok": True, "matched": 103,
        "mesh_vs_oracle_wall_ratio": 2.5,
    }))
    (rec,) = _compare_multichip(str(old_wrapper), str(new_block))
    assert rec["change"] == "headline" and rec["old_ratio"] is None
    assert rec["new_ratio"] == 2.5 and rec["queries"] == 103
    # ok -> not-ok is a regression even without ratios
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"n_devices": 8, "ok": False, "matched": 1}))
    (rec2,) = _compare_multichip(str(old_wrapper), str(bad))
    assert rec2["change"] == "regression"
    # ratio worsening > 25% between two metric rounds flags too
    older = tmp_path / "older.json"
    older.write_text(json.dumps({
        "n_devices": 8, "ok": True, "mesh_vs_oracle_wall_ratio": 1.5,
    }))
    (rec3,) = _compare_multichip(str(older), str(new_block))
    assert rec3["change"] == "regression"
    # unreadable new artifact degrades to a status_change record
    (rec4,) = _compare_multichip(str(old_wrapper), str(tmp_path / "nope"))
    assert rec4["change"] == "status_change"


def test_profile_bench_takes_multichip_artifacts_only(tmp_path, capsys):
    """The `--bench` handler has one meaning: a pair of which either side
    carries `n_devices` compares as MULTICHIP rounds; any other pair is
    one line on stderr and exit 2."""
    import json

    from nds_tpu.cli import profile as profile_cli

    old = tmp_path / "MULTICHIP_r05.json"
    old.write_text(json.dumps({"n_devices": 8, "rc": 0, "ok": True}))
    new = tmp_path / "gate.json"
    new.write_text(json.dumps({
        "n_devices": 8, "ok": True, "matched": 103,
        "mesh_vs_oracle_wall_ratio": 2.5,
    }))
    profile_cli.main(["--bench", str(old), str(new)])
    assert "multichip mesh-vs-oracle wall ratio: - -> 2.500" in (
        capsys.readouterr().out
    )
    other = tmp_path / "out_line.json"
    other.write_text(json.dumps({"sqlite_shared": {"ratio": 2.4}}))
    with pytest.raises(SystemExit) as exc:
        profile_cli.main(["--bench", str(other), str(tmp_path / "nope")])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "n_devices" in err and len(err.strip().splitlines()) == 1


def test_fact_columns_are_row_sharded(dist):
    t = dist.catalog.load("store_sales", ["ss_item_sk"])
    sharding = t.columns["ss_item_sk"].data.sharding
    assert len(sharding.device_set) == N_DEV
    # dims replicate
    d = dist.catalog.load("item", ["i_item_sk"])
    assert d.columns["i_item_sk"].data.sharding.is_fully_replicated


def test_distributed_sort_matches_oracle(monkeypatch):
    """Full-table ORDER BY under the mesh goes through the samplesort
    exchange (not an all-gathering lexsort) and matches the oracle."""
    from nds_tpu.engine import exec as X

    taken = []
    orig = X.Executor._try_dist_sort

    def spy(self, child, keys):
        r = orig(self, child, keys)
        taken.append(r is not None)
        return r

    monkeypatch.setattr(X.Executor, "_try_dist_sort", spy)
    conf = {"engine.dist_sort_min_rows": 1}
    dist_s = Session(mesh=make_mesh(N_DEV), conf=conf)
    oracle_s = Session(conf=conf)
    for name, t in _synth_tables(seed=5).items():
        dist_s.register_arrow(name, t)
        oracle_s.register_arrow(name, t)
    queries = [
        # non-null primary key, desc
        """select ss_item_sk, ss_quantity, ss_ticket_number from store_sales
           order by ss_quantity desc, ss_item_sk, ss_ticket_number""",
        # NULLABLE primary key (nulls first for asc), secondary ties
        """select ss_store_sk, ss_item_sk, ss_ticket_number from store_sales
           order by ss_store_sk, ss_item_sk, ss_ticket_number, ss_quantity""",
    ]
    for q in queries:
        got = dist_s.sql(q).collect()
        want = oracle_s.sql(q).collect()
        assert got.num_rows == want.num_rows > 0
        assert got.to_pylist() == want.to_pylist(), q
    assert any(taken), "distributed sort path was never exercised"
