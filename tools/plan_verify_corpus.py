#!/usr/bin/env python
"""Static plan-IR corpus check: verify ALL 99 TPC-DS query templates.

Instantiates every template (seeded parameters, no data), parses, binds and
runs the full rewrite stack (prune_columns -> mark_blocked_union_aggs ->
mark_pipelines) through a schema-only Session with `engine.verify_plans=all`
— so the PlanVerifier (nds_tpu/analysis/verifier.py) re-checks structural
invariants after binding and after EVERY rewrite pass, for the whole query
surface, on every CI run. Nothing executes: Results stay lazy, no table is
ever loaded, the check is CPU-only and finishes in seconds.

This is the SQLancer-style lesson applied statically: a planner bug that a
unit test's three queries miss is usually visible somewhere across the full
99-template corpus, and verifying the corpus costs less than running one
query.

`--budget` adds the static-budgeter calibration pass (analysis/budget.py):
every template is estimated schema-only against the SF1 AND SF10 TPC-DS
catalogs, and the two load-bearing calibration points are gated — at SF1
every statement must be admitted `direct` (SF1 is known to fit 103/103:
zero false positives), and at SF10 the round-5 per-query map's device-OOM
set (query5/6/7) must be flagged over-budget (>= 90% coverage). A model
change that drifts either way fails CI here, not in a run on the chip.
NDS_PLAN_BUDGET_STRICT is set for the whole run, so a budgeter crash on
any template is a hard failure too.

Usage:
    python tools/plan_verify_corpus.py [--queries 5,14,93] [--scale 1.0]
    python tools/plan_verify_corpus.py --budget

Exit status: 0 when every template binds, rewrites and verifies clean (and
the budget calibration holds); 1 otherwise. Wired into ci/tier1-check.
"""

from __future__ import annotations

import argparse
import os
import sys
from time import perf_counter

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# a budgeter crash on ANY template is a CI failure, not a degraded verdict
os.environ.setdefault("NDS_PLAN_BUDGET_STRICT", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from nds_tpu.datagen.query_streams import (  # noqa: E402
    available_templates,
    instantiate,
)
from nds_tpu.engine.session import Session, _Entry  # noqa: E402
from nds_tpu.engine.sql import ast as A  # noqa: E402
from nds_tpu.engine.sql.parser import parse_script  # noqa: E402
from nds_tpu.schema import get_schemas  # noqa: E402


def build_session(use_decimal: bool = True) -> Session:
    """A Session whose catalog knows every TPC-DS schema but holds no data:
    binding and plan rewriting only ever touch catalog.schema()."""
    sess = Session(
        use_decimal=use_decimal, conf={"engine.verify_plans": "all"}
    )
    for name, schema in get_schemas(use_decimal).items():
        sess.catalog.entries[name] = _Entry(schema=schema)
    return sess


def check_template(sess: Session, qnum: int, scale: float, rngseed: int) -> int:
    """Bind + rewrite + verify one template; returns the statement count
    (templates 14/23/24/39 carry two). Raises on any parse/bind/verify
    failure."""
    rng = np.random.default_rng(np.random.SeedSequence([rngseed, 0]))
    sql = instantiate(qnum, rng, scale)
    n = 0
    for stmt in parse_script(sql):
        if not isinstance(stmt, A.SelectStmt):
            raise TypeError(
                f"query{qnum}: expected SELECT statements only, got "
                f"{type(stmt).__name__}"
            )
        sess.run_stmt(stmt)  # binds + rewrites + verifies; never executes
        n += 1
    return n


#: the queries that device-OOM'd in the round-5 SF10 per-query map;
#: the budgeter must flag >= 90% of them
ROUND5_SF10_OOM = (5, 6, 7)

#: verdicts that carry a PLANNED degradation (statically sized windows /
#: partition counts) — the round-5 OOM set must pin onto these, not onto
#: the passive `over` (which only arms the runtime ladder)
PLANNED_DEGRADATION = ("blocked", "spill")

_VERDICT_RANK = {"direct": 0, "unknown": 1, "blocked": 2, "spill": 3,
                 "over": 4, "reject": 5}


def budget_pass(use_decimal: bool, rngseed: int) -> int:
    """Schema-only budget estimates for every template at SF1 and SF10
    (plus the SF10 per-device mesh model — the same plans analyzed under
    mesh_devices=MESH_DEVICES in the one sweep, so the corpus plans each
    template once); returns the number of calibration failures (0 ==
    gate passes)."""
    from nds_tpu.analysis import budget as B

    failures = 0
    for sf in (1.0, 10.0):
        sess = build_session(use_decimal)
        # analysis is explicit below; the in-session hook would reject
        # over-budget SF10 templates before we could record their verdicts
        sess.conf["engine.plan_budget"] = "off"
        verdicts = {}
        peaks = {}
        mesh_verdicts = {}
        mesh_peaks = {}
        t0 = perf_counter()
        for q in available_templates():
            rng = np.random.default_rng(np.random.SeedSequence([rngseed, 0]))
            sql = instantiate(q, rng, sf)
            worst = "direct"
            peak = 0
            m_worst = "direct"
            m_peak = 0
            for stmt in parse_script(sql):
                res = sess.run_stmt(stmt)
                pb = B.analyze_plan(
                    res.plan, sess.catalog, scale_factor=sf
                )
                if _VERDICT_RANK[pb.verdict] > _VERDICT_RANK[worst]:
                    worst = pb.verdict
                peak = max(peak, pb.peak_bytes)
                if sf == 10.0:
                    mb = B.analyze_plan(
                        res.plan, sess.catalog, scale_factor=sf,
                        mesh_devices=MESH_DEVICES,
                    )
                    if _VERDICT_RANK[mb.verdict] > _VERDICT_RANK[m_worst]:
                        m_worst = mb.verdict
                    m_peak = max(m_peak, mb.peak_bytes)
            verdicts[q] = worst
            peaks[q] = peak
            if sf == 10.0:
                mesh_verdicts[q] = m_worst
                mesh_peaks[q] = m_peak
        dt = perf_counter() - t0
        flagged = sorted(q for q, v in verdicts.items() if v != "direct")
        print(
            f"plan_budget_corpus: SF{sf:g}: {len(flagged)}/{len(verdicts)} "
            f"templates flagged over-budget in {dt:.1f}s "
            f"(max modeled peak {max(peaks.values()) / (1 << 30):.2f} GiB)"
        )
        if sf == 1.0:
            if flagged:
                failures += 1
                print(
                    f"plan_budget_corpus: FAIL: SF1 false positives "
                    f"{flagged} (SF1 is known to fit 103/103; every "
                    f"template must be admitted direct): "
                    + ", ".join(
                        f"q{q}={verdicts[q]}@{peaks[q] / (1 << 30):.2f}G"
                        for q in flagged
                    )
                )
        else:
            # the OOM set must land on a PLANNED degradation verdict —
            # blocked (windowed union-agg) or spill (out-of-core partition
            # counts) — so the first SF10 attempt already runs degraded
            # instead of discovering the misfit as a device OOM
            hits = [
                q for q in ROUND5_SF10_OOM
                if verdicts[q] in PLANNED_DEGRADATION
            ]
            coverage = len(hits) / len(ROUND5_SF10_OOM)
            detail = ", ".join(
                f"q{q}={verdicts[q]}@{peaks[q] / (1 << 30):.2f}G"
                for q in ROUND5_SF10_OOM
            )
            print(
                f"plan_budget_corpus: SF10 round-5 OOM set coverage "
                f"{coverage:.0%} ({detail})"
            )
            if coverage < 0.9:
                failures += 1
                print(
                    "plan_budget_corpus: FAIL: the budgeter must pin "
                    ">= 90% of the round-5 SF10 device-OOM set onto the "
                    f"{PLANNED_DEGRADATION} verdicts"
                )
            failures += _check_mesh_pins(mesh_verdicts, mesh_peaks)
    return failures


#: mesh width of the per-device calibration pass (the CI mesh gate's and
#: the virtual CPU test mesh's width)
MESH_DEVICES = 8

#: templates still rejected per-device at SF10 on the 8-wide mesh: q47's
#: fact-scale window function all-gathers under the generic rewrite (the
#: budgeter charges it in full per chip — honestly), so it stays beyond
#: the reject line until a distributed window rewrite lands. Everything
#: else admits — incl. the single-device reject set (q14/q23 and kin).
EXPECTED_MESH_REJECTS = (47,)


def _check_mesh_pins(verdicts: dict, peaks: dict) -> int:
    """Per-device calibration pins at SF10 over the 8-device mesh
    (ISSUE 13; verdicts/peaks computed in budget_pass's SF10 sweep so
    templates plan once): sharded node bytes divide by the mesh width,
    replicated dims are charged per chip. The round-5 device-OOM set
    (q5/q6/q7 — blocked/spill single-device) must re-derive to per-device
    `direct` (each chip's share fits), and the reject set must equal the
    pinned EXPECTED_MESH_REJECTS — scale-out admits everything else.
    Returns the number of calibration failures."""
    failures = 0
    detail = ", ".join(
        f"q{q}={verdicts[q]}@{peaks[q] / (1 << 30):.2f}G"
        for q in ROUND5_SF10_OOM
    )
    rejects = sorted(q for q, v in verdicts.items() if v == "reject")
    print(
        f"plan_budget_corpus: SF10 x {MESH_DEVICES}-device mesh "
        f"(per-device): OOM set {detail}; {len(rejects)} reject(s)"
    )
    bad = [q for q in ROUND5_SF10_OOM if verdicts[q] != "direct"]
    if bad:
        failures += 1
        print(
            f"plan_budget_corpus: FAIL: the round-5 SF10 OOM set must "
            f"re-derive to per-device `direct` on the {MESH_DEVICES}-wide "
            f"mesh (each chip's share of the sharded fact work fits): "
            + ", ".join(f"q{q}={verdicts[q]}" for q in bad)
        )
    if list(rejects) != list(EXPECTED_MESH_REJECTS):
        failures += 1
        print(
            f"plan_budget_corpus: FAIL: per-device SF10 reject set "
            f"{rejects} != pinned {list(EXPECTED_MESH_REJECTS)} — "
            f"scale-out must admit everything except the known "
            f"window-all-gather shape (a new reject is a model/plan "
            f"regression; an admitted q47 means the dist-window rewrite "
            f"landed and the pin should move)"
        )
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bind + rewrite + verify all TPC-DS query templates"
    )
    ap.add_argument(
        "--queries", default=None,
        help="comma-separated template numbers (default: all 99)",
    )
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--rngseed", type=int, default=0)
    ap.add_argument(
        "--float", dest="floats", action="store_true",
        help="verify under the float (non-decimal) type mapping too",
    )
    ap.add_argument(
        "--budget", action="store_true",
        help="also run the static-budgeter SF1/SF10 calibration gate",
    )
    args = ap.parse_args(argv)
    qnums = (
        [int(x) for x in args.queries.split(",")]
        if args.queries
        else available_templates()
    )
    sess = build_session(use_decimal=not args.floats)
    t0 = perf_counter()
    failures = []
    statements = 0
    for q in qnums:
        try:
            statements += check_template(sess, q, args.scale, args.rngseed)
        except Exception as exc:
            failures.append((q, exc))
            print(f"FAIL query{q}: {type(exc).__name__}: {exc}")
    dt = perf_counter() - t0
    ok = len(qnums) - len(failures)
    print(
        f"plan_verify_corpus: {ok}/{len(qnums)} templates "
        f"({statements} statements) verified at strictness=all "
        f"in {dt:.1f}s"
    )
    if failures:
        print(
            f"plan_verify_corpus: {len(failures)} template(s) FAILED: "
            f"{[q for q, _ in failures]}"
        )
        return 1
    if args.budget:
        if budget_pass(not args.floats, args.rngseed):
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
