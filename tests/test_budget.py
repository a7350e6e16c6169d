"""Static plan budgeter (analysis/budget.py) + the invariants that rode
along with it: budget-vs-actual calibration over real SF0.01 data, static
blocked-window sizing parity with the runtime derivation, the ladder's
budget_shrink rung, host-RSS watermark pre-emption, the sharding verifier
rule family (seeded violations per rule), and the new lint rules
(cache-lock-discipline, unread-conf-knob).
"""

import os
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu import faults
from nds_tpu.analysis import budget as B
from nds_tpu.analysis import lint as L
from nds_tpu.analysis.verifier import (
    PlanVerifier,
    PlanVerifyError,
    verify_plan,
)
from nds_tpu.engine import expr as E
from nds_tpu.engine import plan as P
from nds_tpu.engine.session import Session, _Entry
from nds_tpu.obs import memwatch
from nds_tpu.obs.trace import EVENT_SCHEMA, Tracer
from nds_tpu.report import BenchReport
from nds_tpu.schema import get_schemas
from shared_data import DATA, raw_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# row / width model
# ---------------------------------------------------------------------------


def test_spec_table_rows_matches_generator_model():
    # exact spec dims at the defined scale points
    assert B.spec_table_rows("date_dim", 1.0) == 73049
    assert B.spec_table_rows("item", 1.0) == 18000
    assert B.spec_table_rows("item", 10.0) == 102000
    assert B.spec_table_rows("store", 1.0) == 12
    assert B.spec_table_rows("store", 10.0) == 102
    assert B.spec_table_rows("customer_demographics", 100.0) == 1920800
    # facts: orders x average lines, linear in SF
    assert B.spec_table_rows("store_sales", 1.0) == 2880000
    assert B.spec_table_rows("store_sales", 10.0) == 28800000
    assert B.spec_table_rows("catalog_sales", 1.0) == 1440000
    assert B.spec_table_rows("web_sales", 1.0) == 720000
    # returns ~10% of sales lines; inventory is the weekly cross product
    assert B.spec_table_rows("store_returns", 1.0) == 288000
    assert B.spec_table_rows("inventory", 1.0) == 261 * 9000 * 5
    # interpolation between knots is monotone
    assert (
        B.spec_table_rows("customer", 1.0)
        < B.spec_table_rows("customer", 3.0)
        < B.spec_table_rows("customer", 10.0)
    )
    assert B.spec_table_rows("not_a_table", 1.0) is None


def test_width_model_mirrors_device_layout():
    from nds_tpu.dtypes import parse_dtype

    assert B.column_row_bytes(parse_dtype("int32")) == 5
    assert B.column_row_bytes(parse_dtype("date")) == 5
    assert B.column_row_bytes(parse_dtype("string")) == 5  # int32 codes
    assert B.column_row_bytes(parse_dtype("int64")) == 9
    assert B.column_row_bytes(parse_dtype("float64")) == 9
    assert B.column_row_bytes(parse_dtype("decimal(7,2)")) == 9


def test_default_window_rows_clamps_and_pow2():
    budget = 6 << 30
    w = B.default_window_rows(54, budget)
    assert w & (w - 1) == 0  # power of two
    assert 1 << 16 <= w <= 1 << 24
    # huge rows -> floor clamp; tiny rows -> ceiling clamp
    assert B.default_window_rows(1 << 40, budget) == 1 << 16
    assert B.default_window_rows(1, budget) == 1 << 24


def test_column_domain_table():
    assert B.column_domain_table("store.s_store_id") == "store"
    assert B.column_domain_table("ss_item_sk") == "item"  # FK suffix wins
    assert B.column_domain_table("x.ss_quantity") == "store_sales"
    assert B.column_domain_table("web_site.web_name") == "web_site"
    assert B.column_domain_table("made_up") is None


# ---------------------------------------------------------------------------
# schema-only analyzer verdicts (the corpus gate's calibration points)
# ---------------------------------------------------------------------------


def _schema_session(**conf):
    sess = Session(conf={"engine.plan_budget": "off", **conf})
    for name, schema in get_schemas(True).items():
        sess.catalog.entries[name] = _Entry(schema=schema)
    return sess


def _template_plan(sess, qnum, sf):
    from nds_tpu.datagen.query_streams import instantiate
    from nds_tpu.engine.sql.parser import parse_script

    rng = np.random.default_rng(np.random.SeedSequence([0, 0]))
    stmts = list(parse_script(instantiate(qnum, rng, sf)))
    return [sess.run_stmt(s).plan for s in stmts]


def test_query5_blocked_at_sf10_direct_at_sf1():
    sess = _schema_session()
    (plan,) = _template_plan(sess, 5, 10.0)
    pb = B.analyze_plan(plan, sess.catalog, scale_factor=10.0)
    assert pb.verdict == "blocked"
    assert pb.window_rows and pb.window_rows & (pb.window_rows - 1) == 0
    assert pb.peak_blocked_bytes < pb.peak_bytes
    assert pb.peak_bytes > pb.budget_bytes >= pb.peak_blocked_bytes
    # the estimate table renders every node + the verdict line
    table = pb.table()
    assert "verdict: blocked" in table and "window_rows" in table

    (plan1,) = _template_plan(_schema_session(), 5, 1.0)
    pb1 = B.analyze_plan(plan1, _schema_session().catalog, scale_factor=1.0)
    assert pb1.verdict == "direct"
    assert pb1.window_rows is None


def test_round5_oom_set_flagged_at_sf10():
    for q in (5, 6, 7):
        sess = _schema_session()
        verdicts = [
            B.analyze_plan(p, sess.catalog, scale_factor=10.0).verdict
            for p in _template_plan(sess, q, 10.0)
        ]
        assert all(v != "direct" for v in verdicts), (q, verdicts)


def test_mesh_mode_divides_sharded_bytes_by_mesh_width():
    """Per-device model (ISSUE 13): fact-scan bytes divide by the mesh
    width, replicated dimension bytes are charged in full per device, and
    the single-device model is byte-identical to mesh_devices=None."""
    sess = _schema_session()
    (plan,) = _template_plan(sess, 3, 10.0)
    pb1 = B.analyze_plan(plan, sess.catalog, scale_factor=10.0)
    pb8 = B.analyze_plan(plan, sess.catalog, scale_factor=10.0,
                         mesh_devices=8)
    assert pb8.mesh_devices == 8 and pb1.mesh_devices is None
    assert pb8.peak_bytes < pb1.peak_bytes
    by_desc1 = {id(n.node): n for n in pb1.nodes}
    fact = dim = False
    for n8 in pb8.nodes:
        n1 = by_desc1.get(id(n8.node))
        if n1 is None or not n8.desc.startswith("Scan"):
            continue
        if n8.sharded:
            fact = True
            assert n8.alloc_bytes == n1.alloc_bytes // 8, n8.desc
        else:
            dim = True
            assert n8.alloc_bytes == n1.alloc_bytes, n8.desc  # per device
    assert fact and dim
    # identity widths: mesh_devices absent or 1 changes nothing
    pb_one = B.analyze_plan(plan, sess.catalog, scale_factor=10.0,
                            mesh_devices=1)
    assert pb_one.peak_bytes == pb1.peak_bytes
    # the per-device table says so
    assert "per device" in pb8.table() and "[sharded]" in pb8.table()


def test_mesh_mode_sf10_oom_set_goes_direct_per_device():
    """The round-5 SF10 OOM set (q5 blocked, q6/q7 spill single-device)
    admits DIRECT on the 8-device mesh — each chip's share of the sharded
    fact work fits; same pins the corpus --budget gate holds."""
    for q, single in ((5, "blocked"), (6, "spill"), (7, "spill")):
        sess = _schema_session()
        (plan,) = _template_plan(sess, q, 10.0)
        pb1 = B.analyze_plan(plan, sess.catalog, scale_factor=10.0)
        assert pb1.verdict == single, (q, pb1.verdict)
        pb8 = B.analyze_plan(plan, sess.catalog, scale_factor=10.0,
                             mesh_devices=8)
        assert pb8.verdict == "direct", (q, pb8.verdict)
        assert pb8.peak_bytes <= pb8.budget_bytes


def test_session_mesh_devices_resolution():
    """Width resolution: live session mesh wins, engine.mesh_devices conf
    covers schema-only contexts ONLY, <= 1 means single-device model."""
    sess = _schema_session()
    assert B.session_mesh_devices(sess) is None
    sess.conf["engine.mesh_devices"] = 8
    assert B.session_mesh_devices(sess) == 8
    sess.conf["engine.mesh_devices"] = 1
    assert B.session_mesh_devices(sess) is None
    sess.conf["engine.mesh_devices"] = "bogus"
    assert B.session_mesh_devices(sess) is None
    sess.mesh = _FakeMesh(4)
    assert B.session_mesh_devices(sess) == 4
    # a session with REAL data but no mesh executes single-device: a
    # stray conf key must not buy per-device admission verdicts for
    # plans that will run on one chip
    import pyarrow as pa

    live = _schema_session()
    live.conf["engine.mesh_devices"] = 8
    live.register_arrow("t", pa.table({"a": [1, 2, 3]}))
    assert B.session_mesh_devices(live) is None
    live.mesh = _FakeMesh(8)  # the live mesh still wins over everything
    assert B.session_mesh_devices(live) == 8


def test_budget_plan_records_mesh_devices_on_session():
    """The in-session hook (the one serve-mode admission consumes) models
    per-device under engine.mesh_devices and records the width."""
    sess = _schema_session()
    sess.conf["engine.plan_budget"] = "on"
    sess.conf["engine.plan_budget_sf"] = 10.0
    sess.conf["engine.mesh_devices"] = 8
    _template_plan(sess, 5, 10.0)
    rec = sess.last_plan_budget
    assert rec["verdict"] == "direct" and rec["mesh_devices"] == 8
    # q14: reject single-device, admitted per-device at 8 chips
    _template_plan(sess, 14, 10.0)
    assert sess.last_plan_budget["verdict"] == "direct"


def test_reject_raises_classified_planner():
    # q14's SF10 estimate is far beyond the reject line; with the
    # in-session hook ON it must refuse the statement at plan time
    sess = _schema_session()
    sess.conf["engine.plan_budget"] = "on"
    sess.conf["engine.plan_budget_sf"] = 10.0
    with pytest.raises(B.PlanBudgetError) as exc:
        _template_plan(sess, 14, 10.0)
    assert faults.classify(exc.value) == faults.PLANNER
    # warn mode computes + records but never rejects
    sess2 = _schema_session()
    sess2.conf["engine.plan_budget"] = "warn"
    sess2.conf["engine.plan_budget_sf"] = 10.0
    plans = _template_plan(sess2, 14, 10.0)
    assert plans and sess2.last_plan_budget["verdict"] == "reject"


def test_unknown_tables_disable_enforcement():
    sess = Session(conf={})  # default: engine.plan_budget=on
    sess.catalog.entries["mystery"] = _Entry(
        schema=get_schemas(True)["store_sales"], path="/nope", fmt="csv"
    )
    sess.register_arrow(
        "mystery", pa.table({"ss_item_sk": pa.array([1, 2], pa.int32())})
    )
    del sess.catalog.entries["mystery"]
    sess.catalog.entries["mystery_csv"] = _Entry(
        schema=get_schemas(True)["date_dim"], path="/nope", fmt="csv"
    )
    res = sess.sql("select count(*) c from mystery_csv")
    assert res is not None  # admitted despite unknown cardinality
    assert sess.last_plan_budget["verdict"] == "unknown"


def test_plan_budget_event_emitted():
    sess = _schema_session()
    sess.conf["engine.plan_budget"] = "warn"
    sess.conf["engine.plan_budget_sf"] = 1.0
    sess.tracer = Tracer()  # in-memory
    _template_plan(sess, 3, 1.0)
    evs = [e for e in sess.tracer.events if e["kind"] == "plan_budget"]
    assert len(evs) == 1
    assert set(EVENT_SCHEMA["plan_budget"]) <= set(evs[0])
    assert evs[0]["verdict"] == "direct"


# ---------------------------------------------------------------------------
# blocked-window sizing: static annotation vs runtime derivation parity
# ---------------------------------------------------------------------------


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
            "v": pa.array(
                [None if i % 7 == 0 else int(v) for i, v in enumerate(vs)],
                pa.int32(),
            ),
            "amt": pa.array(
                [Decimal(int(v) * 7) / 100 for v in vs], pa.decimal128(7, 2)
            ),
        }
    )


UNION_AGG = """
select k, sum(v) sv, min(v) mn, max(v) mx, count(v) cv, avg(v) av,
       sum(amt) sa
from (select k, v, amt from t1
      union all
      select k, v, amt from t2 where v > -40
      union all
      select k, v, amt from t3) u
where v < 45
group by k
order by k
"""


def _union_session(**conf):
    s = Session(conf=conf)
    for i, t in enumerate(("t1", "t2", "t3")):
        s.register_arrow(t, _channel(3000, seed=100 + i))
    return s


def test_static_window_annotation_matches_runtime_sizing():
    # oracle: the unwindowed result
    oracle = _union_session().sql(UNION_AGG).to_pylist()

    # runtime-derived sizing (conf knob, the PR-1 path)
    runtime = _union_session(**{"engine.union_agg_window_rows": 512})
    r1 = runtime.sql(UNION_AGG)
    assert r1.to_pylist() == oracle
    rt_stats = runtime.last_blocked_union
    assert rt_stats and rt_stats["window_rows"] == 512

    # statically-chosen sizing: the budgeter's budget_window_rows
    # annotation (placed by _annotate_blocked_windows exactly as a
    # blocked verdict would) must route through the same windowed
    # executor with the same window and produce the identical result
    static = _union_session()
    res = static.sql(UNION_AGG)
    B._annotate_blocked_windows(res.plan, 512)
    assert res.to_pylist() == oracle
    st_stats = static.last_blocked_union
    assert st_stats and st_stats["window_rows"] == 512
    assert st_stats["windows"] == rt_stats["windows"]
    assert st_stats["max_table_cap"] == rt_stats["max_table_cap"]

    # explicit conf still wins over a static annotation
    both = _union_session(**{"engine.union_agg_window_rows": 1024})
    res2 = both.sql(UNION_AGG)
    B._annotate_blocked_windows(res2.plan, 512)
    assert res2.to_pylist() == oracle
    assert both.last_blocked_union["window_rows"] == 1024


def test_annotated_plan_verifies_clean():
    static = _union_session(**{"engine.verify_plans": "all"})
    res = static.sql(UNION_AGG)
    B._annotate_blocked_windows(res.plan, 512)
    verify_plan(res.plan, static.catalog)  # annotation coverage accepts it


# ---------------------------------------------------------------------------
# ladder: budget_shrink consumes the static prediction
# ---------------------------------------------------------------------------


def test_ladder_budget_shrink_first_rung():
    sess = _union_session()
    sess.last_plan_budget = {
        "verdict": "over",
        "peak_bytes": 5 << 30,
        "budget_bytes": 4 << 30,
        "window_rows": 2048,
    }
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    report = BenchReport(sess)
    summary = report.report_on(flaky, retry_oom=True, name="q")
    assert summary["queryStatus"][-1] == "CompletedWithTaskFailures"
    rungs = [r["rung"] for r in summary["ladder"]]
    assert rungs[0] == "budget_shrink"
    assert summary["ladder"][0]["window_rows"] == 2048
    assert sess.conf["engine.union_agg_window_rows"] == 2048
    assert len(attempts) == 2  # one failure + one recovered retry


def test_ladder_skips_budget_shrink_without_windowing_seam():
    # an `over` verdict on a plan with NO blocked-union seam carries no
    # window recommendation: budget_shrink would be recover_retry with a
    # conf side-effect later statements' static sizing can't undo
    sess = _union_session()
    sess.last_plan_budget = {"verdict": "over", "window_rows": None}
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    summary = BenchReport(sess).report_on(flaky, retry_oom=True, name="q")
    rungs = [r["rung"] for r in summary["ladder"]]
    assert rungs[0] == "recover_retry"
    assert "engine.union_agg_window_rows" not in sess.conf

    # an explicit window already at/below the recommendation means the
    # failed attempt ran it — re-applying the same value is pointless
    sess2 = _union_session(**{"engine.union_agg_window_rows": 2048})
    sess2.last_plan_budget = {"verdict": "blocked", "window_rows": 2048}
    attempts2 = []

    def flaky2():
        attempts2.append(1)
        if len(attempts2) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    summary2 = BenchReport(sess2).report_on(flaky2, retry_oom=True, name="q")
    assert [r["rung"] for r in summary2["ladder"]][0] == "recover_retry"

    # a blocked-verdict plan already ANNOTATED with the static window ran
    # it and OOM'd anyway: budget_shrink must not rerun the identical
    # configuration, and the shrink rung must halve BELOW the failed
    # static window instead of jumping to the (larger) degraded default
    sess3 = _union_session()
    sess3.last_plan_budget = {
        "verdict": "blocked", "window_rows": 65536, "annotated": True,
    }
    attempts3 = []

    def always_oom():
        attempts3.append(1)
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    summary3 = BenchReport(sess3).report_on(
        always_oom, retry_oom=True, name="q"
    )
    rungs3 = [r["rung"] for r in summary3["ladder"]]
    assert rungs3 == ["recover_retry", "shrink_union_window"]
    assert sess3.conf["engine.union_agg_window_rows"] == 32768


def test_watermark_never_grows_past_static_recommendation(monkeypatch):
    # conf unset + a static window SMALLER than the degraded default
    # (annotated or not): the watermark write must clamp to it — conf
    # wins over the annotation, so a larger conf value would GROW windows
    monkeypatch.setattr(memwatch, "rss_bytes", lambda: 1 << 30)
    sess = _union_session(**{"engine.host_rss_watermark": 1})
    sess.last_plan_budget = {
        "verdict": "blocked", "window_rows": 65536, "annotated": True,
    }
    BenchReport(sess).report_on(lambda: None, name="q")
    assert sess.conf["engine.union_agg_window_rows"] == 65536


def test_watermark_fires_once_per_excursion(monkeypatch):
    # RSS stays above the watermark across queries: only the FIRST query
    # of the excursion shrinks; the latch re-arms after RSS drops below
    rss = {"v": 1 << 30}
    monkeypatch.setattr(memwatch, "rss_bytes", lambda: rss["v"])
    import nds_tpu.report as report_mod

    monkeypatch.setattr(report_mod, "rss_bytes", lambda: rss["v"],
                        raising=False)
    sess = _union_session(**{"engine.host_rss_watermark": 1000})
    s1 = BenchReport(sess).report_on(lambda: None, name="q1")
    assert any(
        r["rung"] == "host_watermark_shrink" for r in s1["ladder"]
    )
    first = sess.conf["engine.union_agg_window_rows"]
    s2 = BenchReport(sess).report_on(lambda: None, name="q2")
    assert "ladder" not in s2  # same excursion: no second shrink
    assert sess.conf["engine.union_agg_window_rows"] == first
    # excursion ends -> latch re-arms -> a new crossing shrinks again
    rss["v"] = 10
    BenchReport(sess).report_on(lambda: None, name="q3")
    assert sess._rss_above_watermark is False
    rss["v"] = 1 << 30
    s4 = BenchReport(sess).report_on(lambda: None, name="q4")
    assert any(
        r["rung"] == "host_watermark_shrink" for r in s4["ladder"]
    )
    assert sess.conf["engine.union_agg_window_rows"] == first // 2


def test_budget_shrink_applies_when_explicit_window_eclipsed_static():
    # conf pins a LARGE window, so the blocked-verdict annotation never
    # ran (conf wins): the prediction is still applicable and the first
    # rung must shrink to it
    sess = _union_session(**{"engine.union_agg_window_rows": 1 << 23})
    sess.last_plan_budget = {
        "verdict": "blocked", "window_rows": 65536, "annotated": False,
    }
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    summary = BenchReport(sess).report_on(flaky, retry_oom=True, name="q")
    assert [r["rung"] for r in summary["ladder"]][0] == "budget_shrink"
    assert sess.conf["engine.union_agg_window_rows"] == 65536


def test_budget_plan_annotated_false_under_explicit_window():
    # the in-session hook must record annotated=False when an explicit
    # window eclipses the annotation at execution time
    sess = _schema_session()
    sess.conf.update({
        "engine.plan_budget": "on",
        "engine.plan_budget_sf": 10.0,
        "engine.union_agg_window_rows": 1 << 23,
    })
    _template_plan(sess, 5, 10.0)
    rec = sess.last_plan_budget
    assert rec["verdict"] == "blocked" and rec["annotated"] is False
    # without the explicit window the annotation IS in effect
    sess2 = _schema_session()
    sess2.conf.update({
        "engine.plan_budget": "on", "engine.plan_budget_sf": 10.0,
    })
    _template_plan(sess2, 5, 10.0)
    assert sess2.last_plan_budget["annotated"] is True


def test_env_window_never_grows_under_watermark(monkeypatch):
    # an env-forced tiny window (conf unset) must not be eclipsed by a
    # larger conf value written by the watermark shrink
    monkeypatch.setattr(memwatch, "rss_bytes", lambda: 1 << 30)
    monkeypatch.setenv("NDS_UNION_AGG_WINDOW_ROWS", "4096")
    sess = _union_session(**{"engine.host_rss_watermark": 1})
    BenchReport(sess).report_on(lambda: None, name="q")
    assert sess.conf["engine.union_agg_window_rows"] <= 4096


def test_failed_parquet_count_still_falls_back_to_scale_model(tmp_path):
    sess = _schema_session()
    sess.catalog.entries["store_sales"] = _Entry(
        schema=get_schemas(True)["store_sales"],
        path=str(tmp_path / "nope"), fmt="parquet",
    )
    stats = B.CatalogStats(sess.catalog, scale_factor=None)
    assert stats.table_rows("store_sales") is None  # probe failed
    # the failed probe is memoized, but a declared scale factor must
    # still supply the cardinality instead of pinning `unknown`
    stats_sf = B.CatalogStats(sess.catalog, scale_factor=1.0)
    assert stats_sf.table_rows("store_sales") == 2880000


def test_ladder_unchanged_without_prediction():
    sess = _union_session()
    sess.last_plan_budget = {"verdict": "direct", "window_rows": None}
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    summary = BenchReport(sess).report_on(flaky, retry_oom=True, name="q")
    rungs = [r["rung"] for r in summary["ladder"]]
    assert rungs[0] == "recover_retry"  # the pre-budgeter ladder


# ---------------------------------------------------------------------------
# host-RSS watermark pre-emption
# ---------------------------------------------------------------------------


def test_memory_sampler_watermark_fires_once(monkeypatch):
    calls = []
    monkeypatch.setattr(memwatch, "rss_bytes", lambda: 1000)
    s = memwatch.MemorySampler(
        interval_s=0.001, watermark_bytes=500, on_watermark=calls.append
    )
    with s:
        import time

        time.sleep(0.05)
    assert s.watermark_fired
    assert calls == [1000]  # once, with the crossing sample


def test_report_on_watermark_preemption(monkeypatch):
    monkeypatch.setattr(memwatch, "rss_bytes", lambda: 1 << 30)
    sess = _union_session(**{"engine.host_rss_watermark": 1})
    sess.tracer = Tracer()
    report = BenchReport(sess)
    result = {}

    def run():
        result["rows"] = sess.sql(UNION_AGG).to_pylist()

    summary = report.report_on(run, name="uq")
    assert summary["queryStatus"][-1] == "CompletedWithTaskFailures"
    assert summary["retries"] == 0  # pre-emption is not a retry
    entries = [
        r for r in summary["ladder"]
        if r["rung"] == "host_watermark_shrink"
    ]
    assert entries and entries[0]["kind"] == faults.HOST_OOM
    # the window conf shrank for later statements
    assert sess.conf["engine.union_agg_window_rows"] >= 4096
    evs = [e for e in sess.tracer.events if e["kind"] == "mem_watermark"]
    assert evs and evs[0]["watermark_bytes"] == 1
    assert result["rows"]  # the query itself completed


def test_window_loop_shrinks_under_pressure():
    oracle = _union_session().sql(UNION_AGG).to_pylist()
    sess = _union_session(**{"engine.union_agg_window_rows": 8192})
    sess._mem_pressure = True  # as the watermark callback would set it
    res = sess.sql(UNION_AGG)
    assert res.to_pylist() == oracle
    stats = sess.last_blocked_union
    # the loop consumed the pressure flag and halved the remaining windows
    assert stats["window_cap"] == 4096
    assert sess._mem_pressure is False


# ---------------------------------------------------------------------------
# sharding verifier rules (seeded violation per rule)
# ---------------------------------------------------------------------------


class _FakeDevices:
    def __init__(self, n):
        self.size = n


class _FakeMesh:
    def __init__(self, n):
        self.devices = _FakeDevices(n)


def _catalog_with(nrows=None):
    sess = _schema_session()
    if nrows:
        for name, n in nrows.items():
            sess.catalog.entries[name].nrows = n
    return sess.catalog


def test_sharding_exchange_arity_non_pow2_mesh():
    cat = _catalog_with({"store_sales": 1000})
    plan = P.Scan("store_sales", "store_sales", ["ss_item_sk"])
    v = PlanVerifier(cat).verify(plan, mesh=_FakeMesh(3))
    assert any("exchange-arity" in x for x in v)
    # a fact cap that does not divide the mesh would silently replicate
    assert any("replicated-dim" in x and "store_sales" in x for x in v)
    # power-of-two mesh: clean
    assert PlanVerifier(cat).verify(plan, mesh=_FakeMesh(8)) == []


def test_sharding_replicated_dim_too_large():
    cat = _catalog_with({"customer": 1 << 29})  # ~0.5G rows, way past 2 GiB
    plan = P.Scan("customer", "customer", ["c_customer_sk", "c_birth_year"])
    v = PlanVerifier(cat).verify(plan, mesh=_FakeMesh(8))
    assert any(
        "replicated-dim" in x and "customer" in x for x in v
    )
    # without a mesh the sharding family does not run at all
    assert PlanVerifier(cat).verify(plan) == []


def test_sharding_axis_mixed_setop():
    cat = _catalog_with({"store_sales": 2048, "date_dim": 100})
    left = P.Project(
        [(E.Col("store_sales.ss_item_sk"), "x")],
        P.Scan("store_sales", "store_sales", ["ss_item_sk"]),
    )
    right = P.Project(
        [(E.Col("date_dim.d_date_sk"), "x")],
        P.Scan("date_dim", "date_dim", ["d_date_sk"]),
    )
    plan = P.SetOp("union_all", left, right)
    v = PlanVerifier(cat).verify(plan, mesh=_FakeMesh(8))
    assert any("sharding-axis" in x for x in v)


def test_physical_annotation_coverage():
    cat = _catalog_with({"date_dim": 100})
    scan = P.Scan("date_dim", "date_dim", ["d_date_sk"])
    proj = P.Project([(E.Col("date_dim.d_date_sk"), "x")], scan)
    proj._topk_safe = True  # stray: not a Sort
    v = PlanVerifier(cat).verify(proj)
    assert any("physical-annotation" in x and "_topk_safe" in x for x in v)

    agg = P.Aggregate(
        keys=[(E.Col("date_dim.d_date_sk"), "k")],
        aggs=[(E.Agg("count", None), "c")],
        child=P.Scan("date_dim", "date_dim", ["d_date_sk"]),
    )
    agg.budget_window_rows = 4096  # not a blocked-union aggregate
    v = PlanVerifier(cat).verify(agg)
    assert any(
        "physical-annotation" in x and "budget_window_rows" in x for x in v
    )

    agg2 = P.Aggregate(
        keys=[(E.Col("date_dim.d_date_sk"), "k")],
        aggs=[(E.Agg("count", None), "c")],
        child=P.Scan("date_dim", "date_dim", ["d_date_sk"]),
    )
    agg2.donate_ok = True  # only Pipelines own the donation contract
    v = PlanVerifier(cat).verify(agg2)
    assert any(
        "physical-annotation" in x and "donate_ok" in x for x in v
    )

    with pytest.raises(PlanVerifyError):
        verify_plan(proj, cat)


# ---------------------------------------------------------------------------
# lint: cache-lock-discipline + unread-conf-knob
# ---------------------------------------------------------------------------


def test_lint_cache_lock_discipline():
    # the rule retired into analysis/concurrency.py's guarded-by
    # (ISSUE 20 satellite): findings now carry the new name, and the
    # historical pragma keeps silencing via the alias table
    bad = (
        "def f(session, fp, sig):\n"
        "    session.exec_cache.map[(fp, sig)] = None\n"
        "    session.join_order_cache.setdefault(fp, {})\n"
        "    session.plan_cache.clear()\n"
    )
    findings = L.lint_source(bad, "engine/whatever.py")
    hits = [f for f in findings if f.rule == "guarded-by"]
    assert len(hits) == 3

    good = (
        "def f(session, fp, sig):\n"
        "    with session.cache_lock:\n"
        "        session.exec_cache.map[(fp, sig)] = None\n"
        "        session.plan_cache.clear()\n"
    )
    assert [
        f for f in L.lint_source(good, "engine/whatever.py")
        if f.rule == "guarded-by"
    ] == []

    # local-alias taint: a cache fetched into a variable is still a cache
    alias = (
        "def f(self, node, out):\n"
        "    cache = self._session_cache()\n"
        "    cache.put(node, out)\n"
    )
    hits = [
        f for f in L.lint_source(alias, "engine/whatever.py")
        if f.rule == "guarded-by"
    ]
    assert len(hits) == 1

    # pragma with justification silences a known-sound site
    pragma = (
        "def f(session):\n"
        "    # single-threaded init  # nds-lint: disable=cache-lock-discipline\n"
        "    session.plan_cache.clear()\n"
    )
    assert [
        f for f in L.lint_source(pragma, "engine/whatever.py")
        if f.rule == "guarded-by"
    ] == []


def test_lint_unread_conf_knob(tmp_path):
    pkg = tmp_path / "nds_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'X = conf.get("engine.real_knob", 1)\n', encoding="utf-8"
    )
    (tmp_path / "README.md").write_text(
        "| `engine.real_knob` | used |\n| `engine.ghost_knob` | dead |\n",
        encoding="utf-8",
    )
    findings = L.run_unread_knob_lint(str(tmp_path))
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "unread-conf-knob"
    assert "engine.ghost_knob" in f.message and f.path == "README.md"
    # the live tree is clean (also covered by test_lint_clean_over_real_tree)
    assert L.run_unread_knob_lint() == []


# ---------------------------------------------------------------------------
# budget-vs-actual calibration over real SF0.01 data (the slack contract)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sf001_session():
    raw_data()
    schemas = get_schemas(True)
    sess = Session(conf={})
    for t in ("store_sales", "store_returns", "date_dim", "item", "store"):
        sess.register_csv_dir(t, os.path.join(DATA, t), schemas[t])
    return sess


CALIBRATION_STREAM = (
    ("scan_filter_count",
     "select count(*) c from store_sales where ss_quantity > 0"),
    ("join_agg",
     "select d_year, sum(ss_ext_sales_price) s, count(*) c "
     "from store_sales, date_dim where ss_sold_date_sk = d_date_sk "
     "group by d_year order by d_year"),
    ("union_agg",
     "select k, sum(v) sv, count(*) c from "
     "(select ss_item_sk k, ss_quantity v from store_sales "
     " union all "
     " select sr_item_sk k, sr_return_quantity v from store_returns) u "
     "group by k order by k limit 20"),
    ("topk",
     "select i_item_id, i_current_price from item "
     "order by i_current_price desc limit 10"),
    ("star_join",
     "select s_store_name, d_moy, sum(ss_net_paid) t from store_sales, "
     "date_dim, store where ss_sold_date_sk = d_date_sk and "
     "ss_store_sk = s_store_sk and d_year = 2000 "
     "group by s_store_name, d_moy order by t desc limit 50"),
)


@pytest.mark.slow
def test_budget_vs_actual_calibration(sf001_session):
    """The calibration contract: for every query of the SF0.01 stream,
    run with memory high-water tracing on, the largest actually
    materialized plan-node working set (op_span est_bytes — the exact
    byte rule the plan cache budgets with) must stay within
    CALIBRATION_SLACK x the static peak estimate. A model change that
    starts under-estimating real materialization breaks here."""
    sess = sf001_session
    for name, sql in CALIBRATION_STREAM:
        sess.conf["engine.plan_cache"] = "off"
        sess.tracer = Tracer()  # fresh in-memory stream per query
        report = BenchReport(sess)
        box = {}

        def run():
            res = sess.sql(sql)
            box["plan"] = res.plan
            box["rows"] = res.to_pylist()

        with faults.scope(name):
            summary = report.report_on(run, name=name)
        assert summary["queryStatus"][-1] == "Completed", (name, summary)
        # memoryHighWater tracing was on and recorded a real peak
        assert summary.get("memoryHighWater", {}).get("bytes"), name
        pb = B.analyze_plan(box["plan"], sess.catalog)
        spans = [
            e for e in sess.tracer.events if e["kind"] == "op_span"
        ]
        assert spans, name
        actual_peak = max(int(e["est_bytes"] or 0) for e in spans)
        assert actual_peak <= pb.peak_bytes * B.CALIBRATION_SLACK, (
            f"{name}: actual node high-water {actual_peak} exceeds "
            f"{B.CALIBRATION_SLACK}x the static peak {pb.peak_bytes}"
        )
        # and the static estimate is not vacuous: within 4 orders of
        # magnitude of reality (a model regression to astronomic bounds
        # would admit nothing at real scale)
        assert pb.peak_bytes <= actual_peak * 10_000, name
        assert box["rows"], name
