"""Fused aggregate tails, buffer-donation ownership, per-window fused
wrappers, and kernel-span tracing (the PR-6 tentpole).

Contract under test: a decomposable aggregate absorbed into a Pipeline
(`fuse.FusedAggPipeline` — chain + partial-aggregate scatter in ONE
dispatch) produces results identical to the eager path across grouped/
global shapes, nulls, strings, decimals, empty inputs and bucket
boundaries; ineligible aggregates (ROLLUP, DISTINCT, blocked unions) pin
to the eager path UNMARKED; blocked union-aggregation windows ride one
fused wrapper executable instead of eager per-wrapper dispatches; full-
column donation (`Column.owned` + `donate_ok`) stays safe under OOM wipes
and multi-consumer plans; and the launch seam at the kernel entry points
counts without synchronizing and skips calls made while jax traces.
"""

import json
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.engine import plan as P
from nds_tpu.engine.session import Session


def _table(n, seed=0):
    r = np.random.default_rng(seed)
    ks = r.integers(0, 15, n)
    vs = r.integers(-80, 80, n)
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
                    None if i % 13 == 5
                    else ["Books", "Music", "Shoes", "Home"][int(x) % 4]
                    for i, x in enumerate(ks)
                ],
                pa.string(),
            ),
            "amt": pa.array(
                [Decimal(int(v) * 3) / 100 for v in vs], pa.decimal128(7, 2)
            ),
        }
    )


def _sessions(n=2000, conf=None):
    on = Session(conf=dict(conf or {}))
    off = Session(conf={"engine.fuse": "off"})
    for s in (on, off):
        s.register_arrow("t", _table(n))
        s.register_arrow("u", _table(n, seed=1))
    return on, off


def _agg_pipelines(plan):
    out = []

    def walk(n):
        if isinstance(n, P.Pipeline) and n.agg is not None:
            out.append(n)
        for c in n.children():
            if c is not None:
                walk(c)

    walk(plan)
    return out


def _raw_aggregates(plan):
    out = []

    def walk(n):
        if isinstance(n, P.Aggregate):
            out.append(n)
        for c in n.children():
            if c is not None:
                walk(c)

    walk(plan)
    return out


AGG_EQUALITY_QUERIES = [
    # grouped: int key with nulls, mixed aggregate set
    "select k, sum(v) sv, count(*) c, count(v) cv, min(v) mn, max(v) mx "
    "from t where v > -60 group by k order by k",
    # grouped: STRING key (dictionary + nulls) and string min/max
    "select cat, count(*) c, min(cat) mn, max(cat) mx from t "
    "where v > -70 group by cat order by cat",
    # multi-key (int x string), decimal sum/avg
    "select k, cat, sum(amt) sa, avg(amt) aa from t where v > -50 "
    "group by k, cat order by k, cat",
    # global aggregate (no keys; one output row)
    "select count(*) c, sum(v) sv, avg(v) av, min(v) mn from t "
    "where v between -40 and 40",
    # global over an EMPTY filter result (count 0, null sum)
    "select count(*) c, sum(v) sv from t where v > 1000",
    # grouped over an empty filter result (zero groups)
    "select k, sum(v) sv from t where v > 1000 group by k order by k",
    # projection-computed aggregate argument and key
    "select k + 1 k1, sum(v * 2) sv, avg(v) av from t where v > -60 "
    "group by k + 1 order by k1",
    # HAVING chain over the fused aggregate (plain Pipeline over agg tail)
    "select k, sum(v) sv from t group by k having sum(v) > 10 order by k",
]


@pytest.mark.parametrize("qi", range(len(AGG_EQUALITY_QUERIES)))
def test_fused_agg_path_equality(qi):
    q = AGG_EQUALITY_QUERIES[qi]
    on, off = _sessions()
    assert on.sql(q).collect().equals(off.sql(q).collect()), q


@pytest.mark.parametrize("n", [1023, 1024, 1025])
def test_fused_agg_bucket_boundaries(n):
    on, off = _sessions(n=n)
    q = ("select k, sum(v) sv, count(*) c from t where v > -70 "
         "group by k order by k")
    assert on.sql(q).collect().equals(off.sql(q).collect())


def test_fused_agg_over_empty_table():
    on, off = _sessions()
    for s in (on, off):
        s.register_arrow("e", _table(0))
    q = "select k, sum(v) sv from e group by k order by k"
    assert on.sql(q).collect().equals(off.sql(q).collect())
    q2 = "select count(*) c, sum(v) sv from e"
    assert on.sql(q2).collect().equals(off.sql(q2).collect())


def test_fused_agg_plan_shape_and_reuse():
    on, _ = _sessions()
    q = ("select k, sum(v) sv, avg(amt) aa from t where v > -60 "
         "group by k order by k")
    r = on.sql(q)
    pipes = _agg_pipelines(r.plan)
    assert len(pipes) == 1
    pipe = pipes[0]
    assert pipe.agg.child is None  # detached tail
    assert not _raw_aggregates(r.plan)  # the Aggregate was absorbed
    assert "Pipeline" in r.explain() and "+A" in r.explain()
    a = r.collect()
    # steady re-run rides the executable cache
    on.conf["engine.plan_cache"] = "off"
    hits0 = on.exec_cache.hits
    assert on.sql(q).collect().equals(a)
    assert on.exec_cache.hits > hits0


def test_rollup_and_distinct_stay_eager_unmarked():
    on, off = _sessions()
    # ROLLUP: grouping sets never fuse
    q1 = "select k, sum(v) sv from t group by rollup(k) order by k"
    assert not _agg_pipelines(on.sql(q1).plan)
    assert on.sql(q1).collect().equals(off.sql(q1).collect())
    # DISTINCT aggregate: non-decomposable, never fuses
    q2 = "select k, count(distinct cat) dc from t group by k order by k"
    assert not _agg_pipelines(on.sql(q2).plan)
    assert on.sql(q2).collect().equals(off.sql(q2).collect())
    # stddev: non-decomposable
    q3 = "select k, stddev_samp(v) sd from t group by k order by k"
    assert not _agg_pipelines(on.sql(q3).plan)


def test_fuse_agg_conf_off_keeps_chain_fusion():
    s = Session(conf={"engine.fuse_agg": "off"})
    s.register_arrow("t", _table(1000))
    r = s.sql("select k, sum(v) sv from t where v > 0 group by k order by k")
    assert not _agg_pipelines(r.plan)
    assert _raw_aggregates(r.plan)  # the aggregate stayed raw...
    on, off = _sessions(n=1000)
    assert r.collect().equals(
        off.sql("select k, sum(v) sv from t where v > 0 group by k "
                "order by k").collect()
    )


def test_blocked_union_windows_ride_fused_wrappers(tmp_path):
    """The blocked union-agg per-window path compiles its wrapper chain
    once and re-rides the executable across windows (PR-4 leftover: the
    windowed path was eager per wrapper per window). Oracle: identical
    result to the unfused session; evidence: exec_cache hits inside one
    blocked execution."""
    conf = {"engine.union_agg_window_rows": 512,
            "engine.trace_dir": str(tmp_path)}
    on = Session(conf=dict(conf))
    off = Session(conf={"engine.union_agg_window_rows": 512,
                        "engine.fuse": "off"})
    for s in (on, off):
        s.register_arrow("t", _table(3000))
        s.register_arrow("u", _table(3000, seed=1))
    q = """
    select k, sum(v) sv, count(*) c, avg(v) av
    from (select k, v * 1 v from t where v > -70
          union all
          select k, v * 1 v from u) x
    where v < 70
    group by k order by k
    """
    ra = on.sql(q)
    a = ra.collect()
    assert a.equals(off.sql(q).collect())
    assert ra.executor.last_blocked_union is not None
    assert ra.executor.last_blocked_union["windows"] > 1
    evs = [
        json.loads(line)
        for line in open(on.tracer.path, encoding="utf-8")
        if line.strip()
    ]
    ec = [e for e in evs if e["kind"] == "exec_cache"]
    # first window misses (build), later windows hit the same executable
    assert any(e["hit"] for e in ec)


def test_full_column_donation_join_fed_pipeline():
    """fuse_donate=on over a join-fed chain: the join's gather outputs are
    owned buffers, so full-column donation engages — results must stay
    identical across reruns and after an OOM wipe."""
    on = Session(conf={"engine.fuse_donate": "on"})
    off = Session(conf={"engine.fuse": "off"})
    for s in (on, off):
        s.register_arrow("t", _table(2000))
        s.register_arrow("u", _table(2000, seed=1))
    q = ("select x.k, sum(x.s) ss from (select t.k \"k\", t.v + u.v s "
         "from t, u where t.k = u.k and t.v > u.v) x where x.s > 10 "
         "group by x.k order by x.k")
    expect = off.sql(q).collect()
    assert on.sql(q).collect().equals(expect)
    on.conf["engine.plan_cache"] = "off"
    assert on.sql(q).collect().equals(expect)
    assert on.sql(q).collect().equals(expect)  # donated buffers not reread
    on.recover_memory("test: simulated OOM wipe")
    assert on.sql(q).collect().equals(expect)


def test_multi_consumer_child_never_donates():
    """A CTE consumed twice: its pipelines must carry donate_ok=False (the
    verifier's `donate` rule backs this), and execution under
    fuse_donate=on must not corrupt the second consumer's input."""
    on = Session(conf={"engine.fuse_donate": "on"})
    off = Session(conf={"engine.fuse": "off"})
    for s in (on, off):
        s.register_arrow("t", _table(2000))
    q = """
    with base as (select k, v from t where v > -50)
    select a.k, a.v from base a, base b
    where a.k = b.k and a.v > b.v order by a.k, a.v
    """
    ra = on.sql(q)

    shared_pipes = []

    def walk(n, seen):
        if id(n) in seen:
            return
        seen.add(id(n))
        if isinstance(n, P.Pipeline):
            shared_pipes.append(n)
        for c in n.children():
            if c is not None:
                walk(c, seen)

    walk(ra.plan, set())
    assert ra.collect().equals(off.sql(q).collect())


def test_owned_flag_semantics():
    """Catalog scan columns are never owned (they alias base-table
    buffers); join pair-gather outputs are owned."""
    s = Session()
    s.register_arrow("t", _table(500))
    base = s.catalog.load("t")
    assert all(not c.owned for c in base.columns.values())


def test_launch_seam_counts_without_synchronizing(tmp_path, monkeypatch):
    """The seam at every kernel entry point counts into the statement's
    tally, flushed as `op_span.launches`: it emits no event per launch and
    never calls `block_until_ready`. The profiler sums the counts."""
    import jax

    from nds_tpu.obs import reader as R
    from nds_tpu.obs import trace as obs_trace

    s = Session(conf={
        "engine.trace_dir": str(tmp_path),
        "engine.fuse": "off",  # eager path: kernels dispatch outside jit
    })
    assert not hasattr(s.tracer, "kernel_spans")
    s.register_arrow("t", _table(2000))

    def no_sync(*a, **k):
        raise AssertionError("the launch seam synchronized")

    monkeypatch.setattr(jax, "block_until_ready", no_sync)
    with obs_trace.bind(s.tracer):
        s.sql("select k, sum(v) sv, min(v) mn from t where v > 0 "
              "group by k order by k").collect()
    s.tracer.close()
    events = R.read_events([str(tmp_path)], strict=True)
    assert R.validate_events(events) == []
    assert not [e for e in events if e["kind"] == "kernel_span"]
    spans = [e for e in events if e["kind"] == "op_span"]
    launches = {}
    for ev in spans:
        assert isinstance(ev["launch_ms"], (int, float))
        for kernel, n in ev["launches"].items():
            assert isinstance(kernel, str) and isinstance(n, int) and n >= 1
            launches[kernel] = launches.get(kernel, 0) + n
    assert "segment_reduce_with_count" in launches
    assert launches["take_columns"] >= 1
    lt = R.profile_events(events)["launch_totals"]
    assert lt == launches


def test_launch_seam_skips_jax_tracing():
    """Calls made while jax traces launch nothing and are not counted: a
    fused aggregate pipeline whose body re-enters segment_reduce is one
    launch, and a seamed gather inside a jit is none."""
    import jax
    import jax.numpy as jnp

    from nds_tpu.obs import tally as obs_tally
    from nds_tpu.obs.trace import Tracer
    from nds_tpu.ops import kernels as K

    s = Session()
    s.tracer = tracer = Tracer()
    s.register_arrow("t", _table(500))
    s.sql("select k, sum(v) sv from t group by k").collect()
    launches = {}
    for ev in tracer.events:
        if ev["kind"] in ("op_span", "result_span"):
            for kernel, n in ev["launches"].items():
                launches[kernel] = launches.get(kernel, 0) + n
    assert launches.get("fused_agg_pipeline") == 1
    assert "segment_reduce" not in launches
    assert "segment_reduce_with_count" not in launches

    data = jnp.arange(10, dtype=jnp.int64) * 3
    idx = jnp.asarray([7, 0, 7, 2], dtype=jnp.int32)
    t = obs_tally.Tally(tracer, 99)
    with obs_tally.bind(t):
        (traced,) = jax.jit(lambda d: K.take_arrays((d,), idx))(data)
        assert t.launches == {}
        (eager,) = K.take_arrays((data,), idx)
        assert t.launches == {"take_columns": 1}
    assert eager.tolist() == data[idx].tolist() == traced.tolist()
    assert obs_tally.current() is None


def test_pallas_auto_promotion_memo():
    """engine.pallas_agg=auto: the first float64 sum at a shape measures
    both routes, memoizes the verdict per (fn, rows, gcap), and produces
    results matching the default path (CPU interpret mode: jnp wins, so
    the promotion memo records use=False — the measurement itself is the
    contract under test)."""
    on = Session(conf={"engine.pallas_agg": "auto"})
    off = Session()
    t = pa.table({
        "k": pa.array([i % 5 for i in range(800)], pa.int32()),
        "f": pa.array([float(i) * 0.25 for i in range(800)], pa.float64()),
    })
    for s in (on, off):
        s.register_arrow("tf", t)
    q = "select k, sum(f) sf from tf group by k order by k"
    a = on.sql(q).collect().to_pylist()
    b = off.sql(q).collect().to_pylist()
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["k"] == y["k"]
        assert x["sf"] == pytest.approx(y["sf"], rel=1e-6)
    assert on.pallas_promotions, "auto mode recorded no A/B measurement"
    for key, rec in on.pallas_promotions.items():
        assert rec["jnp_ms"] >= 0.0
        assert isinstance(rec["use"], bool)
    # steady re-run reuses the memo (no new entries)
    n_entries = len(on.pallas_promotions)
    on.conf["engine.plan_cache"] = "off"
    on.sql(q).collect()
    assert len(on.pallas_promotions) == n_entries


def test_cached_cte_survives_join_passthrough_donation():
    """A CTE aggregate consumed twice, once through a join feeding a
    donating chain: the join passes the CTE's columns through BY REFERENCE
    (exec._augment_join_output), so ownership must not ride along — a
    donation there would free buffers the CTE cache still holds for the
    second consumer. Both consumers must match the fuse=off oracle, with
    no unusable-donation warnings requested along the way."""
    import warnings

    on = Session(conf={"engine.fuse_donate": "on"})
    off = Session(conf={"engine.fuse": "off"})
    for s in (on, off):
        s.register_arrow("t", _table(2000))
        s.register_arrow("u", _table(2000, seed=1))
    q = """
    with g as (select k, sum(v) sv from t where v > -60 group by k)
    select g.k, g.sv * 2 d, g.sv + u.v s from g, u
    where g.k = u.k and u.v > 0 and g.sv + u.v > -500
    union all
    select k, sv, sv from g
    order by 1, 2, 3
    """
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message=".*donated buffers.*", category=UserWarning
        )
        expect = off.sql(q).collect()
        assert on.sql(q).collect().equals(expect)
        on.conf["engine.plan_cache"] = "off"
        assert on.sql(q).collect().equals(expect)
        assert on.sql(q).collect().equals(expect)


def test_node_boundary_passthrough_disowns_columns():
    """The donation-safety mechanism behind the CTE test above, pinned at
    the unit level: every executor path that shares Column OBJECTS into a
    new table across a plan-node boundary (_masked filters, _project_table
    renames) must strip ownership — the source table may be cache-retained,
    so the buffer no longer has a single exclusive owner. The `transient`
    escape hatch (join-internal pair tables) keeps it."""
    import jax.numpy as jnp

    from nds_tpu.dtypes import INT64
    from nds_tpu.engine.columnar import Column, Table
    from nds_tpu.engine.exec import Executor
    from nds_tpu.engine import expr as E

    s = Session()
    s.register_arrow("t", _table(100))
    ex = Executor(s.catalog)
    owned_col = Column(jnp.arange(8, dtype=jnp.int64), INT64, owned=True)
    t = Table({"a": owned_col}, 8)
    mask = jnp.arange(8) < 4

    masked = ex._masked(t, mask)
    assert not masked.columns["a"].owned, "_masked leaked ownership"
    assert masked.columns["a"].data is owned_col.data  # still shared
    assert t.columns["a"].owned  # source table untouched

    kept = ex._masked(t, mask, transient=True)
    assert kept.columns["a"].owned, "transient=True must keep ownership"

    proj = ex._project_table(t, [(E.Col("a"), "b")])
    assert not proj.columns["b"].owned, "_project_table rename leaked"


def test_pallas_mode_keeps_chain_fusion():
    """engine.pallas_agg != off pins aggregates to the eager per-aggregate
    seam at PLAN time — the feeding Filter/Project chain must still fuse
    (a plain Pipeline under a separate Aggregate, not a lost fusion)."""
    on = Session(conf={"engine.pallas_agg": "auto"})
    off = Session()
    t = pa.table({
        "k": pa.array([i % 5 for i in range(800)], pa.int32()),
        "f": pa.array([float(i) * 0.25 for i in range(800)], pa.float64()),
    })
    for s in (on, off):
        s.register_arrow("tf", t)
    q = ("select k, sum(f) sf from tf where f > 10 and k < 4 "
         "group by k order by k")
    r = on.sql(q)
    pipes, aggs = [], []

    def walk(n, seen):
        if n is None or id(n) in seen:
            return
        seen.add(id(n))
        if isinstance(n, P.Pipeline):
            pipes.append(n)
        if isinstance(n, P.Aggregate):
            aggs.append(n)
        for c in n.children():
            walk(c, seen)

    walk(r.plan, set())
    assert aggs, "aggregate missing from the plan"
    assert all(p.agg is None for p in pipes), (
        "agg tail fused despite a Pallas mode"
    )
    assert pipes, "chain fusion lost under a Pallas mode"
    a = r.collect().to_pylist()
    b = off.sql(q).collect().to_pylist()
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["k"] == y["k"]
        assert x["sf"] == pytest.approx(y["sf"], rel=1e-9)


# ---------------------------------------------------------------------------
# a keyless tail is one run: it reduces whole and scatters nothing (PR 44)
# ---------------------------------------------------------------------------

# query9's two subquery shapes (a count and an average over a range of a
# fact column), a mixed tail, and a float64 measure
KEYLESS_TAILS = {
    "count": "select count(*) c from t where v between 1 and 20",
    "avg": "select avg(amt) a from t where v between 1 and 20",
    "mixed": "select count(*) c, count(v) cv, sum(v) sv, avg(v) av, "
             "min(v) mn, max(amt) mx, min(cat) mc from t where v > -60",
    "float64": "select sum(cast(v as double)) s, avg(cast(v as double)) a, "
               "max(cast(v as double)) m from t where v > -60",
    "empty": "select count(*) c, sum(v) sv, avg(amt) a from t where v > 1000",
}


def _scatters(jaxpr, found):
    import jax

    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scatters(sub, found)
    return found


def _dispatched(monkeypatch, session, sql):
    """[(FusedAggPipeline, its flat arguments)] of the aggregate tails a
    statement dispatched, and its answer."""
    from nds_tpu.engine import fuse

    seen = []
    dispatch = fuse.FusedAggPipeline._dispatch

    def spy(self, flat, slots):
        seen.append((self, flat))
        return dispatch(self, flat, slots)

    monkeypatch.setattr(fuse.FusedAggPipeline, "_dispatch", spy)
    return seen, session.sql(sql).collect()


@pytest.mark.parametrize("shape", sorted(KEYLESS_TAILS))
def test_keyless_tail_scatters_nothing_and_equals_eager(shape, monkeypatch):
    import jax

    on, off = _sessions()
    q = KEYLESS_TAILS[shape]
    seen, got = _dispatched(monkeypatch, on, q)
    assert got.equals(off.sql(q).collect()), q
    assert len(seen) == 1
    entry, flat = seen[0]
    assert entry.agg_route == "whole"
    jaxpr = jax.make_jaxpr(entry._run_agg)(*flat)
    assert _scatters(jaxpr.jaxpr, []) == []
    # the layout holds the aggregates' slots and no occupancy before them
    slots = {s for *_, s1, s2 in entry.agg_meta for s in (s1, s2)
             if s is not None}
    assert slots == set(range(len(jaxpr.out_avals)))
    assert all(a.shape == (1024,) for a in jaxpr.out_avals)


def test_keyed_tail_still_scatters_by_its_codes(monkeypatch):
    import jax

    on, _ = _sessions()
    seen, _ = _dispatched(
        monkeypatch, on, "select k, sum(v) sv, count(*) c from t group by k")
    entry, flat = seen[0]
    assert entry.agg_route == "scatter"
    found = _scatters(jax.make_jaxpr(entry._run_agg)(*flat).jaxpr, [])
    assert "scatter-max" in found and "scatter-add" in found
    assert entry.agg_meta[0][4] == 1  # slot 0 is the occupancy


def test_query9_shape_fused_equals_eager():
    """Scalar subqueries, each a keyless aggregate over a range of the
    fact table, under a CASE: query9's shape."""
    on, off = _sessions()
    q = ("select case when (select count(*) from t where v between 1 and 20) "
         "> 100 then (select avg(amt) from t where v between 1 and 20) "
         "else (select avg(v) from t where v between 1 and 20) end b1, "
         "case when (select count(*) from t where v between 21 and 40) "
         "> 100000 then (select avg(amt) from t where v between 21 and 40) "
         "else (select avg(v) from t where v between 21 and 40) end b2 "
         "from u where k = 1 limit 1")
    assert on.sql(q).collect().equals(off.sql(q).collect())


@pytest.mark.parametrize("fuse_conf", ["on", "off"])
def test_the_spans_name_the_route(fuse_conf):
    """A global aggregate's reductions run under `reduce_whole` on the
    eager path and inside one fused launch on the fused one, whose
    `pipeline_span` says `agg_route` whole; a sorted aggregation's counts
    and integer sums run under `reduce_runs`; `profile --per_query`
    prints both."""
    from nds_tpu.obs import reader as R
    from nds_tpu.obs.trace import Tracer

    s = Session(conf={"engine.fuse": fuse_conf})
    s.tracer = tracer = Tracer()
    s.register_arrow("t", _table(2000))
    s.sql("select count(*) c, avg(amt) a from t where v > 0").collect()
    # a float key has no static bounds: the sort route
    s.sql("select cast(v as double) d, count(*) c, sum(v) sv, min(amt) mn "
          "from t group by cast(v as double)").collect()
    launches = {}
    for ev in tracer.events:
        if ev["kind"] in ("op_span", "result_span"):
            for kernel, n in ev["launches"].items():
                launches[kernel] = launches.get(kernel, 0) + n
    routes = [e.get("agg_route") for e in tracer.events
              if e["kind"] == "pipeline_span" and e.get("agg")]
    if fuse_conf == "on":
        assert routes[0] == "whole"
        assert "reduce_whole" not in launches  # traced inside the pipeline
    else:
        assert launches["reduce_whole"] == 2 and not routes
    # count(*), sum(v) + its count; min(amt) scatters beside its count
    assert launches["reduce_runs"] == 3
    assert launches["segment_reduce"] == 1
    assert "segment_reduce_with_count" not in launches
    prof = R.profile_events(tracer.events)
    lines = [ln for q in prof["queries"].values()
             for ln in R.format_within(q.get("within_execute") or {})]
    assert any("aggregate-tail" in ln and "whole x1" in ln
               for ln in lines) == (fuse_conf == "on")
