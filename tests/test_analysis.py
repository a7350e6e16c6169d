"""Static-analysis subsystem: plan-IR verifier + engine lint.

The verifier must (a) pass every legitimately bound + rewritten plan —
queries run identically with `engine.verify_plans=all` — and (b) catch each
seeded invariant violation: unresolved/duplicate schema, a Pipeline
wrapping a shared or still-attached node (the deliberately-broken-rewrite
acceptance case), out-of-scope join keys, SetOp arity drift, a top-k sort
key missing from the Sort input, a blocked_union annotation on a
non-decomposable aggregate, and a LEFT->INNER promotion whose conjunct is
not null-rejecting. PlanVerifyError classifies as a `planner` failure and
the report ladder fails fast (no retry).

The lint must fire on a seeded violation of every rule, honor the
`# nds-lint: disable=<rule>` pragma, and run CLEAN over the real tree —
the same gate ci/tier1-check enforces. The golden-sync test keeps every
emitted `kind` literal and obs/trace.py:EVENT_SCHEMA equal, so schema
drift breaks tier-1 instead of the tolerant reader.
"""

import ast
import dataclasses
import importlib.util
import json
import os
import textwrap

import pyarrow as pa
import pytest

from nds_tpu import faults
from nds_tpu.analysis import lint as L
from nds_tpu.analysis.verifier import (
    PlanVerifier,
    PlanVerifyError,
    resolve_level,
    verify_plan,
)
from nds_tpu.engine import expr as E
from nds_tpu.engine import plan as P
from nds_tpu.engine.binder import Binder
from nds_tpu.engine.session import Session
from nds_tpu.engine.sql.parser import parse_sql
from nds_tpu.obs.trace import DEPRECATED_EVENT_KINDS, EVENT_SCHEMA, Tracer
from nds_tpu.report import BenchReport

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _session(conf=None):
    s = Session(conf=conf)
    s.register_arrow(
        "t1",
        pa.table(
            {
                "k": pa.array([1, 2, 2, None, 5], pa.int32()),
                "v": pa.array([10, 20, 30, 40, 50], pa.int32()),
                "s": pa.array(["a", "b", "b", "c", "a"]),
            }
        ),
    )
    s.register_arrow(
        "t2",
        pa.table(
            {
                "k": pa.array([2, 2, 5, 7], pa.int32()),
                "w": pa.array([1, 2, 3, None], pa.int32()),
            }
        ),
    )
    return s


def _find_node(plan, typ):
    seen = set()

    def visit(v):
        if isinstance(v, (P.PlanNode, E.Expr)):
            if id(v) in seen:
                return None
            seen.add(id(v))
            if isinstance(v, typ):
                return v
            for f in dataclasses.fields(v):
                r = visit(getattr(v, f.name))
                if r is not None:
                    return r
        elif isinstance(v, (list, tuple)):
            for x in v:
                r = visit(x)
                if r is not None:
                    return r
        return None

    return visit(plan)


# ---------------------------------------------------------------------------
# verifier: clean plans stay clean (and still execute)
# ---------------------------------------------------------------------------


def test_verified_queries_execute_identically():
    plain = _session()
    checked = _session(conf={"engine.verify_plans": "all"})
    queries = [
        "select k, sum(v) sv from t1 group by k order by k",
        "select t1.k, t1.v, t2.w from t1, t2 where t1.k = t2.k order by 1, 2, 3",
        # LEFT->INNER promotion shape (records promotion evidence)
        "select count(*) c from t1 left join t2 on t1.k = t2.k where t2.w > 0",
        # blocked-union annotation shape
        "select k, sum(v) sv from (select k, v from t1 union all "
        "select k, v from t1) u group by k order by k",
        # top-k over sort
        "select k, v from t1 order by v desc limit 2",
        "select s, rank() over (partition by s order by v) r from t1 "
        "order by s, r",
    ]
    for q in queries:
        assert checked.sql(q).to_pylist() == plain.sql(q).to_pylist(), q


def test_resolve_level_validates():
    assert resolve_level(None) == "off"
    assert resolve_level({"engine.verify_plans": "final"}) == "final"
    assert resolve_level({"engine.verify_plans": "ALL"}) == "all"
    with pytest.raises(ValueError):
        resolve_level({"engine.verify_plans": "sometimes"})


def test_verify_level_env_knob(monkeypatch):
    monkeypatch.setenv("NDS_VERIFY_PLANS", "final")
    assert resolve_level({}) == "final"
    monkeypatch.delenv("NDS_VERIFY_PLANS")


# ---------------------------------------------------------------------------
# verifier: seeded violations
# ---------------------------------------------------------------------------


def test_unresolved_column_flagged():
    s = _session()
    plan = P.Project([(E.Col("zzz"), "x")], P.Scan("t1", "t1"))
    v = PlanVerifier(s.catalog).verify(plan)
    assert len(v) == 1 and "unresolved column 'zzz'" in v[0]


def test_duplicate_output_names_flagged():
    s = _session()
    plan = P.Project(
        [(E.Col("t1.k"), "x"), (E.Col("t1.v"), "x")], P.Scan("t1", "t1")
    )
    v = PlanVerifier(s.catalog).verify(plan)
    assert v and "duplicate output column 'x'" in v[0]


def test_pipeline_wrapping_shared_node_flagged():
    # the deliberately-broken-rewrite acceptance case: one detached stage
    # object referenced by two Pipelines is a shared wrapper absorbed by
    # mistake (it defeats the executor's by-identity result reuse)
    s = _session()
    stage = P.Filter(E.BinOp(">", E.Col("t1.k"), E.Lit(1)), None)
    p1 = P.Pipeline(stages=[stage], child=P.Scan("t1", "t1"))
    p2 = P.Pipeline(stages=[stage], child=P.Scan("t1", "u1"))
    root = P.SetOp(
        "union_all",
        P.Project([(E.Col("t1.k"), "a")], p1),
        P.Project([(E.Col("u1.k"), "a")], p2),
    )
    v = PlanVerifier(s.catalog).verify(root)
    assert any("shared node" in x for x in v)
    with pytest.raises(PlanVerifyError, match="shared node"):
        verify_plan(root, s.catalog, stage="mark_pipelines")


def test_pipeline_attached_stage_child_flagged():
    s = _session()
    scan = P.Scan("t1", "t1")
    stage = P.Filter(E.BinOp(">", E.Col("t1.k"), E.Lit(1)), scan)
    root = P.Pipeline(stages=[stage], child=scan)
    v = PlanVerifier(s.catalog).verify(root)
    assert any("attached child" in x for x in v)


def test_pipeline_unfusible_stage_expr_flagged():
    s = _session()
    sub = E.ScalarSubquery(
        plan=P.Aggregate([], [(E.Agg("count", None), "_n")], P.Scan("t2", "t2")),
        out_name="_n",
    )
    stage = P.Filter(E.BinOp(">", E.Col("t1.k"), sub), None)
    root = P.Pipeline(stages=[stage], child=P.Scan("t1", "t1"))
    v = PlanVerifier(s.catalog).verify(root)
    assert any("not fusible" in x for x in v)


def test_join_keys_outside_child_flagged():
    s = _session()
    j = P.Join(
        "inner", P.Scan("t1", "t1"), P.Scan("t2", "t2"),
        [E.Col("t1.k")], [E.Col("t1.k")],  # right key binds to LEFT child
    )
    v = PlanVerifier(s.catalog).verify(j)
    assert any("right join key" in x and "t1.k" in x for x in v)


def test_multijoin_edge_scope_flagged():
    s = _session()
    mj = P.MultiJoin(
        relations=[P.Scan("t1", "t1"), P.Scan("t2", "t2")],
        edges=[(0, 1, E.Col("t2.k"), E.Col("t2.k"))],  # left expr: wrong rel
    )
    v = PlanVerifier(s.catalog).verify(mj)
    assert any("must bind to relation 0" in x for x in v)


def test_setop_arity_and_alignment_flagged():
    s = _session()
    a = P.Project([(E.Col("t1.k"), "a")], P.Scan("t1", "t1"))
    b = P.Project(
        [(E.Col("t2.k"), "a"), (E.Col("t2.w"), "b")], P.Scan("t2", "t2")
    )
    v = PlanVerifier(s.catalog).verify(P.SetOp("union_all", a, b))
    assert any("1 vs 2 columns" in x for x in v)
    c = P.Project([(E.Col("t2.k"), "renamed")], P.Scan("t2", "t2"))
    v2 = PlanVerifier(s.catalog).verify(P.SetOp("union_all", a, c))
    assert any("misaligned column names" in x for x in v2)


def test_limit_over_sort_missing_key_flagged():
    s = _session()
    root = P.Limit(3, P.Sort([(E.Col("nope"), True, None)], P.Scan("t1", "t1")))
    v = PlanVerifier(s.catalog).verify(root)
    assert any("unresolved column 'nope'" in x for x in v)


def test_shared_sort_marked_topk_safe_flagged():
    # cross-pass invariant: fuse.mark_pipelines may only set _topk_safe on
    # a single-consumer Sort — a shared Sort gathered top-k for one parent
    # would truncate the other parent's input
    s = _session()
    sort = P.Sort([(E.Col("t1.v"), True, None)], P.Scan("t1", "t1"))
    sort._topk_safe = True
    root = P.SetOp(
        "union_all",
        P.Project([(E.Col("t1.k"), "a")], P.Limit(2, sort)),
        P.Project([(E.Col("t1.k"), "a")], sort),
    )
    v = PlanVerifier(s.catalog).verify(root)
    assert any("multiple consumers" in x for x in v)
    # single-consumer _topk_safe is clean
    ok = P.Limit(2, P.Sort([(E.Col("t1.v"), True, None)], P.Scan("t1", "t1")))
    ok.child._topk_safe = True
    assert PlanVerifier(s.catalog).verify(ok) == []


def test_pipeline_agg_tail_clean_and_seeded_violations():
    """The PR-6 invariant class 1: an aggregate-tail Pipeline must carry a
    detached, unshared, plain-shaped, fully decomposable aggregate."""
    s = _session(conf={"engine.verify_plans": "all"})
    # the organic fused plan verifies clean at `all` strictness (executes
    # through _finish_plan's per-pass verification) and executes correctly
    r = s.sql("select k, sum(v) sv from t1 where v > 10 group by k "
              "order by k")
    assert r.collect() is not None
    pipes = []

    def walk(n):
        if isinstance(n, P.Pipeline) and n.agg is not None:
            pipes.append(n)
        for c in n.children():
            if c is not None:
                walk(c)

    walk(r.plan)
    assert pipes, "aggregate did not fuse into a Pipeline tail"
    pipe = pipes[0]
    # seed 1: non-decomposable aggregate set in the tail
    good_aggs = pipe.agg.aggs
    pipe.agg.aggs = [(E.Agg("sum", E.Col("t1.v"), distinct=True), "sv")]
    v = PlanVerifier(s.catalog).verify(r.plan)
    assert any("non-decomposable" in x and "pipeline-agg" in x for x in v)
    pipe.agg.aggs = good_aggs
    # seed 2: non-plain shape (grouping sets / blocked_union on the tail)
    pipe.agg.grouping_sets = [[0], []]
    v = PlanVerifier(s.catalog).verify(r.plan)
    assert any("plain-shaped" in x for x in v)
    pipe.agg.grouping_sets = None
    pipe.agg.blocked_union = True
    v = PlanVerifier(s.catalog).verify(r.plan)
    assert any("plain-shaped" in x for x in v)
    pipe.agg.blocked_union = False
    # seed 3: the tail still attached to a child subtree
    pipe.agg.child = P.Scan("t1", "t1")
    v = PlanVerifier(s.catalog).verify(r.plan)
    assert any("attached child" in x for x in v)
    pipe.agg.child = None
    # seed 4: the aggregate tail shared with another plan site
    shared_root = P.SetOp(
        "union_all",
        P.Project([(E.Col("k"), "a")], pipe),
        P.Project([(E.Col("sv"), "a")],
                  P.Pipeline(stages=[], child=P.Scan("t1", "u1"),
                             agg=pipe.agg)),
    )
    v = PlanVerifier(s.catalog).verify(shared_root)
    assert any("referenced elsewhere" in x for x in v)
    # restored plan verifies clean again
    assert PlanVerifier(s.catalog).verify(r.plan) == []


def test_donate_ok_seeded_violations():
    """The PR-6 invariant class 2: donate_ok never where another consumer
    or a cross-statement cache can still observe the child's buffers."""
    s = _session()
    # multi-consumer child: one subtree feeding two donating pipelines
    scan = P.Scan("t1", "t1")
    shared = P.Filter(E.BinOp(">", E.Col("t1.k"), E.Lit(0)), scan)
    p1 = P.Pipeline(
        stages=[P.Filter(E.BinOp(">", E.Col("t1.v"), E.Lit(1)), None)],
        child=shared, donate_ok=True,
    )
    p2 = P.Pipeline(
        stages=[P.Filter(E.BinOp(">", E.Col("t1.v"), E.Lit(2)), None)],
        child=shared, donate_ok=False,
    )
    root = P.SetOp(
        "union_all",
        P.Project([(E.Col("t1.k"), "a")], p1),
        P.Project([(E.Col("t1.k"), "a")], p2),
    )
    v = PlanVerifier(s.catalog).verify(root)
    assert any("donate" in x and "multiple consumers" in x for x in v)
    # cache-retained child: an Aggregate's result lives in the session
    # plan cache beyond this call — donating its buffers corrupts it
    agg = P.Aggregate(
        [(E.Col("t1.k"), "k")], [(E.Agg("sum", E.Col("t1.v")), "sv")],
        P.Scan("t1", "t1"),
    )
    bad = P.Pipeline(
        stages=[P.Filter(E.BinOp(">", E.Col("sv"), E.Lit(1)), None)],
        child=agg, donate_ok=True,
    )
    v = PlanVerifier(s.catalog).verify(bad)
    assert any("donate" in x and "retains" in x for x in v)
    # the same shape without the flag is clean
    bad.donate_ok = False
    assert PlanVerifier(s.catalog).verify(bad) == []


def test_lint_undocumented_conf_knob():
    # a knob no doc mentions flags; every documented knob passes
    bad = 'x = conf.get("engine.definitely_not_a_real_knob")\n'
    fs = L.lint_source(bad, "engine/session.py")
    assert [f.rule for f in fs] == ["undocumented-conf-knob"]
    good = 'x = conf.get("engine.fuse", "on")\n'
    assert L.lint_source(good, "engine/session.py") == []
    # subscript writes count as reads of the knob too
    bad2 = 'conf["engine.not_documented_either"] = 1\n'
    fs = L.lint_source(bad2, "power.py")
    assert [f.rule for f in fs] == ["undocumented-conf-knob"]
    # pragma silences with justification
    ok = ('# internal probe knob, never user-facing\n'
          '# nds-lint: disable=undocumented-conf-knob\n'
          'x = conf.get("engine.secret_internal_probe")\n')
    assert L.lint_source(ok, "engine/session.py") == []


def test_unimplemented_scalar_function_flagged():
    # the verifier's function table must not drift AHEAD of the evaluator:
    # ifnull/nvl are not implemented by Evaluator._eval_func, so a plan
    # using them must fail verification, not crash at execution
    s = _session()
    plan = P.Project(
        [(E.Func("ifnull", (E.Col("t1.k"), E.Lit(0))), "x")],
        P.Scan("t1", "t1"),
    )
    v = PlanVerifier(s.catalog).verify(plan)
    assert any("unknown scalar function 'ifnull'" in x for x in v)


def test_blocked_union_nondecomposable_flagged_and_not_annotated():
    s = _session()
    # regression (satellite fix): the annotation pass itself now applies
    # plan.aggs_decomposable — a distinct aggregate over a union shape is
    # NOT marked
    r = s.sql(
        "select k, count(distinct v) dv from (select k, v from t1 "
        "union all select k, v from t1) u group by k"
    )
    agg = _find_node(r.plan, P.Aggregate)
    assert agg is not None and not agg.blocked_union
    # verifier half: a hand-forced annotation on that aggregate is flagged
    agg.blocked_union = True
    v = PlanVerifier(s.catalog).verify(r.plan)
    assert any("non-decomposable aggregate" in x for x in v)
    # and the decomposable shape still annotates + verifies clean
    r2 = s.sql(
        "select k, sum(v) sv from (select k, v from t1 "
        "union all select k, v from t1) u group by k"
    )
    agg2 = _find_node(r2.plan, P.Aggregate)
    assert agg2 is not None and agg2.blocked_union
    assert PlanVerifier(s.catalog).verify(r2.plan) == []


def test_blocked_union_on_non_union_input_flagged():
    # fuse_agg off: keep the raw Aggregate in the plan (fusion would absorb
    # it into a Pipeline tail, where the plain-shape check fires instead)
    s = _session(conf={"engine.fuse_agg": "off"})
    r = s.sql("select k, sum(v) sv from t1 group by k")
    agg = _find_node(r.plan, P.Aggregate)
    agg.blocked_union = True  # no union_all anywhere below
    v = PlanVerifier(s.catalog).verify(r.plan)
    assert any("not a union_all chain" in x for x in v)


def test_left_inner_promotion_cross_check():
    s = _session()
    stmt = parse_sql(
        "select count(*) c from t1 left join t2 on t1.k = t2.k "
        "where t2.w > 0"
    )
    binder = Binder(s.catalog)
    plan = binder.bind(stmt)
    # the binder recorded evidence, and the evidence verifies clean
    assert binder.promotions and binder.promotions[0]["refs"]
    verify_plan(plan, s.catalog, promotions=binder.promotions)
    # a promotion claimed from a null-TOLERANT conjunct must be flagged
    bad = [{"conjunct": E.UnaryOp("isnull", E.Col("w")), "refs": ["t2.w"]},
           {"conjunct": E.BinOp(">", E.Col("w"), E.Lit(0)), "refs": []}]
    v = PlanVerifier(s.catalog).verify(plan, promotions=bad)
    assert any("NOT null-rejecting" in x for x in v)
    assert any("without any reference" in x for x in v)


def test_plan_verify_events_emitted():
    s = _session(conf={"engine.verify_plans": "all"})
    s.tracer = Tracer()  # in-memory
    s.sql("select k from t1 where v > 10")
    evs = [e for e in s.tracer.events if e["kind"] == "plan_verify"]
    stages = [e["stage"] for e in evs]
    assert stages == [
        "bind", "prune_columns", "mark_blocked_union_aggs",
        "mark_pipelines", "plan_budget",
    ]
    assert all(e["ok"] for e in evs)
    assert "plan_verify" in EVENT_SCHEMA
    # failing verification still emits its event (ok=False) before raising
    t = Tracer()
    bad = P.Project([(E.Col("zzz"), "x")], P.Scan("t1", "t1"))
    with pytest.raises(PlanVerifyError):
        verify_plan(bad, s.catalog, stage="bind", tracer=t)
    ev = [e for e in t.events if e["kind"] == "plan_verify"][0]
    assert ev["ok"] is False and ev["violations"] == 1
    assert "unresolved column" in ev["first"]


def test_planverifyerror_is_planner_and_fails_fast():
    err = PlanVerifyError("bind", ["schema: unresolved column 'x'"])
    assert faults.classify(err) == faults.PLANNER
    # the ladder must NOT retry a deterministic verifier hit even with
    # retry_oom granted
    s = _session()
    calls = []

    def boom():
        calls.append(1)
        raise err

    rep = BenchReport(s)
    summary = rep.report_on(boom, retry_oom=True, name="q")
    assert summary["queryStatus"] == ["Failed"]
    assert summary["failureKind"] == faults.PLANNER
    assert summary["retries"] == 0
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# lint rules: seeded violations + pragma mechanism
# ---------------------------------------------------------------------------


def test_lint_mutable_module_global():
    src = "CACHE = {}\n"
    assert [f.rule for f in L.lint_source(src, "engine/foo.py")] == [
        "mutable-module-global"
    ]
    assert L.lint_source(src, "io/fs.py") == []  # out of scope
    ok = "CACHE = {}  # nds-lint: disable=mutable-module-global\n"
    assert L.lint_source(ok, "engine/foo.py") == []
    g = "def f():\n    global STATE\n    STATE = 1\n"
    assert [f.rule for f in L.lint_source(g, "ops/k.py")] == [
        "mutable-module-global"
    ]


def test_lint_perf_counter():
    src = "import time\nt0 = time.time()\nd = time.time() - t0\n"
    fs = L.lint_source(src, "power.py")
    assert [f.rule for f in fs] == ["perf-counter"] and fs[0].line == 3
    # epoch stamps without subtraction are fine
    assert L.lint_source(
        "import time\nts = int(time.time() * 1000)\n", "power.py"
    ) == []
    # pragma on the line above disables
    ok = (
        "import time\nt0 = time.time()\n"
        "# nds-lint: disable=perf-counter\nd = time.time() - t0\n"
    )
    assert L.lint_source(ok, "power.py") == []


def test_lint_atomic_write():
    src = "f = open(p, 'w')\n"
    assert [f.rule for f in L.lint_source(src, "report.py")] == [
        "atomic-write"
    ]
    assert L.lint_source(src, "engine/exec.py") == []  # harness scope only
    assert L.lint_source("f = open(p)\n", "report.py") == []  # read mode


def test_lint_host_sync_in_fuse():
    src = textwrap.dedent(
        """
        class FusedPipeline:
            def _run_full(self, *flat):
                n = int(flat[0].shape[0])  # static shape: fine
                return np.asarray(flat[1])
        """
    )
    fs = L.lint_source(src, "engine/fuse.py")
    assert [f.rule for f in fs] == ["host-sync-in-fuse"]
    assert "np.asarray" in fs[0].message
    # same code outside the traced bodies is not flagged
    assert L.lint_source(src.replace("_run_full", "call"),
                         "engine/fuse.py") == []


def test_lint_local_import():
    src = "def f():\n    import os\n    return os\n"
    assert [f.rule for f in L.lint_source(src, "engine/exec.py")] == [
        "local-import"
    ]
    assert L.lint_source(src, "power.py") == []  # hot modules only
    # an import inside a NESTED function reports exactly once (ast.walk
    # reaches it from both the outer and inner FunctionDef)
    nested = "def outer():\n    def inner():\n        import os\n"
    assert len(L.lint_source(nested, "engine/exec.py")) == 1


def test_lint_trace_event_schema():
    bad_kind = "tracer.emit('no_such_kind', a=1)\n"
    fs = L.lint_source(bad_kind, "engine/exec.py")
    assert [f.rule for f in fs] == ["trace-event-schema"]
    missing = "tracer.emit('query_span', query=q)\n"
    fs = L.lint_source(missing, "report.py")
    assert fs and "dur_ms" in fs[0].message
    # **fields forwards are only checkable at runtime (profile --check)
    assert L.lint_source("tracer.emit('query_span', **ev)\n", "report.py") == []
    good = (
        "tracer.emit('plan_cache', node=n, hit=True)\n"
    )
    assert L.lint_source(good, "engine/exec.py") == []


def test_lint_metric_names_derive_from_event_kinds():
    """trace-event-schema's obs/metrics.py half: the live-metric taxonomy
    must anchor to EVENT_SCHEMA (ISSUE 8 satellite)."""
    # family mapped to a kind that is not in EVENT_SCHEMA
    bad_kind = 'METRIC_KINDS = {"nds_bogus_total": "bogus"}\n'
    fs = L.lint_source(bad_kind, "obs/metrics.py")
    assert [f.rule for f in fs] == ["trace-event-schema"]
    assert "not an obs/trace.py:EVENT_SCHEMA kind" in fs[0].message
    # family whose name does not embed its source kind
    free = 'METRIC_KINDS = {"nds_free_total": "query_span"}\n'
    fs = L.lint_source(free, "obs/metrics.py")
    assert fs and "does not embed its source event kind" in fs[0].message
    # a registry mutator called with an unregistered literal name
    unreg = (
        'METRIC_KINDS = {"nds_query_span_total": "query_span"}\n'
        'reg.inc("nds_query_span_total", status=s)\n'
        'reg.inc("nds_rogue_total")\n'
    )
    fs = L.lint_source(unreg, "obs/metrics.py")
    assert len(fs) == 1 and "nds_rogue_total" in fs[0].message
    # the same source outside obs/metrics.py is not metric-checked
    assert L.lint_source(bad_kind, "obs/reader.py") == []
    # clean: derived names, registered mutator calls
    clean = (
        'METRIC_KINDS = {"nds_exec_cache_total": "exec_cache"}\n'
        'reg.inc("nds_exec_cache_total", result="hit")\n'
    )
    assert L.lint_source(clean, "obs/metrics.py") == []


def test_metric_kinds_sync_with_event_schema():
    """Golden sync for the live-metric taxonomy: the shipped METRIC_KINDS
    maps every family to a live EVENT_SCHEMA kind and embeds the kind in
    the family name — and the AST view the lint rule checks agrees with
    the runtime dict (no drift between what lint sees and what runs)."""
    from nds_tpu.obs.metrics import METRIC_KINDS

    for name, kind in METRIC_KINDS.items():
        assert kind in EVENT_SCHEMA, (name, kind)
        assert kind in name, (name, kind)
    path = os.path.join(L.package_root(), "obs", "metrics.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    parsed = {k: v for k, (v, _line) in L.metric_kinds_literal(tree).items()}
    assert parsed == dict(METRIC_KINDS)


def test_lint_clean_over_real_tree():
    findings = L.run_lint()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_rebases_repo_root_onto_package():
    # linting from the REPO root must not silently skip the path-scoped
    # rules (a false-clean) — run_lint rebases onto the nds_tpu package
    assert L.run_lint(ROOT) == []
    pkg = L.run_lint()
    # and the rebase sees the same files the direct package run sees
    assert {f.path for f in pkg} == {f.path for f in L.run_lint(ROOT)}


def test_emitted_kinds_sync_with_event_schema():
    """Golden sync: every kind literal emitted anywhere in nds_tpu/ is in
    EVENT_SCHEMA, and every non-deprecated EVENT_SCHEMA kind has a live
    emission site — schema drift breaks tier-1, not the tolerant reader."""
    emitted = set()
    for path in L.iter_py_files(L.package_root()):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for kind, _kwargs, _star, _line in L.iter_emit_calls(tree):
            emitted.add(kind)
    assert emitted - set(EVENT_SCHEMA) == set(), (
        f"emitted kinds missing from EVENT_SCHEMA: "
        f"{emitted - set(EVENT_SCHEMA)}"
    )
    live_required = set(EVENT_SCHEMA) - set(DEPRECATED_EVENT_KINDS)
    assert live_required - emitted == set(), (
        f"EVENT_SCHEMA kinds with no emission site (deprecate or emit): "
        f"{live_required - emitted}"
    )


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------


def test_validate_summary_update_is_atomic(tmp_path, monkeypatch):
    from nds_tpu import validate

    f = tmp_path / "pfx-query1-123.json"
    original = {"queryStatus": ["Completed"]}
    f.write_text(json.dumps(original))
    validate.update_summary(str(tmp_path), [], ["query1"])
    assert json.loads(f.read_text())["queryValidationStatus"] == ["Pass"]

    # crash mid-dump: the destination must keep the previous COMPLETE file
    before = f.read_text()

    def boom(*a, **k):
        raise RuntimeError("disk full mid-write")

    monkeypatch.setattr(validate.json, "dump", boom)
    with pytest.raises(RuntimeError):
        validate.update_summary(str(tmp_path), ["query1"], ["query1"])
    monkeypatch.undo()
    assert f.read_text() == before  # not torn, not truncated
    assert list(tmp_path.glob("*.tmp-*")) == []  # temp discarded


def test_hot_path_imports_hoisted():
    """Regression for the PR-3 hot-path import class: the modules the lint
    holds to module-level imports actually resolved them at import time."""
    import nds_tpu.engine.exec as xc
    import nds_tpu.engine.expr as xp

    assert hasattr(xc, "fuse") and hasattr(xc, "faults")
    assert hasattr(xc, "pc") and hasattr(xc, "_share_dictionary")
    assert hasattr(xp, "unify_dictionaries")


def test_plan_verify_corpus_subset():
    """The CI corpus tool binds + rewrites + verifies templates without
    data or execution (full 99-template run lives in ci/tier1-check)."""
    spec = importlib.util.spec_from_file_location(
        "plan_verify_corpus",
        os.path.join(ROOT, "tools", "plan_verify_corpus.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # 14 is a two-statement template; 93 is the LEFT->INNER promotion shape
    assert mod.main(["--queries", "3,14,93"]) == 0


# ---------------------------------------------------------------------------
# required-column propagation: what each Filter / Join / MultiJoin hands on
# ---------------------------------------------------------------------------


def _star_session():
    """A fact table and two dimensions, each with a key, a name that is
    read above the join and a flag that only its filter reads."""
    s = Session()
    s.register_arrow("f", pa.table({
        "a_sk": pa.array([1, 2, 2, 3], pa.int32()),
        "b_sk": pa.array([1, 1, 2, 2], pa.int32()),
        "q": pa.array([5, 6, 7, 8], pa.int32()),
    }))
    s.register_arrow("a", pa.table({
        "a_sk": pa.array([1, 2, 3], pa.int32()),
        "a_name": pa.array(["x", "y", "z"]),
        "a_flag": pa.array([1, 1, 0], pa.int32()),
    }))
    s.register_arrow("b", pa.table({
        "b_sk": pa.array([1, 2], pa.int32()),
        "b_name": pa.array(["p", "q"]),
        "b_flag": pa.array([2, 2], pa.int32()),
    }))
    return s


def _pruned(s, sql):
    """The bound plan after `prune_columns` alone: Filters still stand as
    nodes of their own (mark_pipelines folds them into Pipelines later)."""
    from nds_tpu.engine.session import prune_columns

    return prune_columns(Binder(s.catalog).bind(parse_sql(sql)), s.catalog)


def _nodes(plan, typ):
    return [n for n in P.walk_plan(plan) if isinstance(n, typ)]


def _filter_over(plan, table):
    (f,) = [n for n in _nodes(plan, P.Filter)
            if isinstance(n.child, P.Scan) and n.child.table == table]
    return f


# statement -> (type of the join node, the set it carries)
_REQUIRED = {
    "star_join_filter_only_dimensions": (
        "select a_name, sum(q) sq from f, a, b where f.a_sk = a.a_sk and "
        "f.b_sk = b.b_sk and a_flag = 1 and b_flag = 2 group by a_name",
        P.MultiJoin, ("a.a_name", "f.q"),
    ),
    "count_star_reads_no_column": (
        "select count(*) c from f, a where f.a_sk = a.a_sk and a_flag = 1",
        P.MultiJoin, (),
    ),
    "residual_predicate": (
        "select f.q from f left join a on f.a_sk = a.a_sk and a.a_flag > f.q",
        P.Join, ("f.q",),
    ),
    "left_outer_join": (
        "select f.q, a.a_name from f left join a on f.a_sk = a.a_sk",
        P.Join, ("a.a_name", "f.q"),
    ),
    "full_outer_join": (
        "select f.q, a.a_name from f full outer join a on f.a_sk = a.a_sk",
        P.Join, ("a.a_name", "f.q"),
    ),
    "rollup_and_window": (
        "select a_name, sum(q) sq, rank() over (partition by a_name "
        "order by sum(q)) r from f, a where f.a_sk = a.a_sk and a_flag = 1 "
        "group by rollup(a_name)",
        P.MultiJoin, ("a.a_name", "f.q"),
    ),
    "filter_column_read_above_the_join": (
        "select a_name, a_flag, q from f, a where f.a_sk = a.a_sk "
        "and a_flag = 1",
        P.MultiJoin, ("a.a_flag", "a.a_name", "f.q"),
    ),
}


@pytest.mark.parametrize("shape", sorted(_REQUIRED))
def test_join_carries_what_is_read_above_it(shape):
    sql, typ, want = _REQUIRED[shape]
    s = _star_session()
    plan = _pruned(s, sql)
    (join,) = _nodes(plan, typ)
    assert join.required == want
    if shape == "residual_predicate":
        assert join.residual is not None
        assert "a.a_flag" in E.col_refs(join.residual)
    # the finished plan verifies (each parent reads inside what its child
    # hands on) and answers the same with and without the verifier
    checked = _star_session()
    checked.conf["engine.verify_plans"] = "all"
    assert checked.sql(sql).to_pylist() == s.sql(sql).to_pylist()


def test_dimension_filter_hands_on_its_key_not_its_filter_column():
    sql = _REQUIRED["star_join_filter_only_dimensions"][0]
    plan = _pruned(_star_session(), sql)
    fa, fb = _filter_over(plan, "a"), _filter_over(plan, "b")
    # what the MultiJoin reads of a relation: what is read above it, and
    # every edge's keys
    assert fa.required == ("a.a_name", "a.a_sk")
    assert fb.required == ("b.b_sk",)
    # ... of this relation: what is read of the others is not in the set,
    # so the same filter under another aggregate keeps its fingerprint
    # (the fused pipelines' executables are keyed by it)
    other = _pruned(_star_session(), sql.replace("sum(q)", "sum(f.b_sk)"))
    assert P.fingerprint(_filter_over(other, "a")) == P.fingerprint(fa)
    # a filter column that is also read above the join stays
    plan = _pruned(
        _star_session(), _REQUIRED["filter_column_read_above_the_join"][0]
    )
    assert "a.a_flag" in _filter_over(plan, "a").required


def test_filter_stage_keeps_its_set_through_mark_pipelines():
    s = _star_session()
    plan = s.sql(_REQUIRED["star_join_filter_only_dimensions"][0]).plan
    stages = [st for p in _nodes(plan, P.Pipeline) for st in p.stages
              if isinstance(st, P.Filter)]
    assert len(stages) == 2
    assert all(st.required is not None for st in stages)
    assert not any("a.a_flag" in st.required or "b.b_flag" in st.required
                   for st in stages)


def _shared_filter():
    return P.Filter(
        E.BinOp(">", E.Col("t1.v"), E.Lit(0)), P.Scan("t1", "t1")
    )


def test_subtree_reached_twice_gets_the_union_of_its_readers():
    from nds_tpu.engine.session import prune_columns

    s = _session()
    shared = _shared_filter()
    plan = P.Join(
        "inner",
        P.Project([(E.Col("t1.k"), "a")], shared),
        P.Project([(E.Col("t1.s"), "b")], shared),
        [E.Col("a")], [E.Col("b")],
    )
    prune_columns(plan, s.catalog)
    assert shared.required == ("t1.k", "t1.s")
    # the scan below reads both readers' columns and the predicate's
    assert shared.child.columns == ["k", "s", "v"]
    assert PlanVerifier(s.catalog).verify(plan) == []


def test_no_reader_set_means_all():
    from nds_tpu.engine.session import prune_columns

    s = _session()
    # SetOp children, and a root the walk reaches with no set
    left, right = _shared_filter(), _shared_filter()
    root = P.Filter(
        E.BinOp(">", E.Col("t1.k"), E.Lit(0)),
        P.SetOp("union_all", left, right),
    )
    prune_columns(root, s.catalog)
    assert root.required is None
    assert left.required is None and right.required is None
    assert left.child.columns is None
    # a MaterializedScan is whole whatever reads it; the Filter over it
    # still carries its readers' names
    f = P.Filter(E.BinOp(">", E.Col("v"), E.Lit(0)), P.MaterializedScan("m"))
    prune_columns(P.Project([(E.Col("k"), "k")], f), s.catalog)
    assert f.required == ("k",)


def test_fingerprint_covers_what_reads_a_subtree():
    from nds_tpu.engine.session import prune_columns

    s = _session()

    def reader(names):
        # no Scan.columns below to tell the two apart: `required` alone
        f = P.Filter(
            E.BinOp(">", E.Col("v"), E.Lit(0)), P.MaterializedScan("m")
        )
        prune_columns(
            P.Project([(E.Col(n), n) for n in names], f), s.catalog
        )
        return f

    narrow, wide, again = reader(["k"]), reader(["k", "s"]), reader(["k"])
    assert (narrow.required, wide.required) == (("k",), ("k", "s"))
    assert P.fingerprint(narrow) != P.fingerprint(wide)
    assert P.fingerprint(narrow) == P.fingerprint(again)


def test_required_narrower_than_its_readers_flagged():
    from nds_tpu.engine.session import prune_columns

    s = _session()
    f = _shared_filter()
    plan = prune_columns(
        P.Project([(E.Col("t1.k"), "k"), (E.Col("t1.s"), "s")], f),
        s.catalog,
    )
    assert PlanVerifier(s.catalog).verify(plan) == []
    f.required = ("t1.k",)  # a reader above still names t1.s
    v = PlanVerifier(s.catalog).verify(plan)
    assert v and "t1.s" in v[0]
