"""Critical-path reconstruction: attribute per-query wall time to CAUSES.

The operator profiler (obs/reader.py) answers "which operator is hot";
this module answers the diagnosis question ROADMAP items 2/3 stall on:
*where does the wall clock actually go* — compile/dispatch inside plan
nodes, exchange waits (and how much of them is skew), spill IO, catalog
loads, degradation-ladder retries, watchdog-abandoned hangs, or
driver-side planning/host work. It reconstructs each query's dependency
chain from the events the engine already emits (`op_span` exec_id/seq/
depth rebuild the plan tree; `exchange`/`spill`/`catalog_load` carry
measured durations; `ladder_rung` carries the failed attempt's wall) and
rolls the evidence into a per-query cause table plus a mesh summary that
names the straggler device from per-device exchange row counts.

Attribution semantics (each bucket is wall-clock, disjoint by
construction):

    exchange-wait   measured `exchange` dur_ms (the collective, both
                    all_to_all passes + retries); `exchange-skew` is the
                    imbalance share of that wait, dur * (1 - 1/skew) —
                    what a perfectly balanced exchange would give back
    spill-io        measured `spill` dur_ms (partition + segment IO +
                    per-partition execution of the out-of-core op)
    catalog-load    measured `catalog_load` dur_ms; split into `read`
                    (storage read + Arrow decode), `encode` (dictionary
                    codes, padding, stats) and `h2d` (host-to-device copy)
                    in proportion to the span's read_ms / encode_ms /
                    h2d_ms where it carries them
    execute         logs WITHOUT `result_span` only (written before it
                    existed): remaining root op_span inclusive time, one
                    lump of device wait, launches, jax tracing, cache
                    loads and Python. With a `result_span` the statement's
                    execution is split by cause instead, each instant of
                    the timeline going to the first of these that covers
                    it (`t0_ns` + `dur_ms` intervals), so the buckets stay
                    disjoint:
    device-wait     `host_read`: the host blocked on a device-to-host read
                    (device work still queued, plus the copy)
    xla-compile     `xla_compile` stage compile that jax's persistent
                    cache did not serve
    cache-load      `xla_compile` stage compile served by that cache, and
                    `aot_cache` loads (executable deserialization)
    jit-trace       `xla_compile` stages trace and lower
    exec-lookup     `exec_cache` dur_ms (the lookup and, on a miss, the
                    pipeline build) not already under a compile stage
    launch          host time inside seamed kernel calls and fused-
                    pipeline calls: the kernel seams of the spans' own
                    `launch_ms_by` (the program has taken the reads and
                    compile stages inside them out already); for a log from
                    before that field `launch_ms` less the compile stages
                    flagged `in_seam`
    eager           host time inside the `eager:<site>` seams of
                    `launch_ms_by`: eager `jnp` work that is no kernel
                    entry (a log from before the field has none, and the
                    time is `host-python`'s there, as it was)
    host-python     the rest of the `result_span`: Python between
                    launches, dictionary work, Arrow assembly. Where the
                    spans carry `host_ms` the query's `host_python` record
                    splits it into the named phases (obs/trace.py
                    HOST_PHASES; `exec-lookup` is then a phase here and no
                    cause of its own, `scan` is the scan less its
                    `catalog_load`) and `other`: what no seam, stage or
                    phase covers, reported as one and never spread
    ladder-retry    failed attempts' wall (`ladder_rung.attempt_ms`)
    backoff-wait    deliberate sleeps between rungs (delay_s)
    hung-wait       a watchdog-abandoned attempt's budget
    ingest-decode   measured `ingest_chunk` decode_ms (Arrow decode of
                    the chunk file in a transcode worker)
    ingest-commit-wait  measured `ingest_chunk` commit_ms (staging +
                    the OCC commit, including rebase waits behind
                    concurrent writers)
    prune-planning  measured `scan_prune` dur_ms (zone-map evaluation
                    at plan time — carved out of what used to be the
                    plan-host residual)
    snapshot-pin    measured `lake_pin` dur_ms (one a scanned lakehouse
                    table a statement: the manifest head resolved, the
                    reader lease acquired or renewed) — carved out of
                    plan-host the same way
    plan-budget     measured `plan_budget` dur_ms (the static budgeter;
                    on a table's first use it reads the row count from
                    storage metadata, seconds for a partitioned fact
                    table) — carved out of plan-host the same way
    router-queue    `route_request` queue_ms: router-edge admission
                    (verdict cache lookup / /plan probe + replica pick)
                    before the first forward left the router
    router-forward  router-side upstream wire time NOT explained by
                    replica-side execution, max(forward_ms - replica
                    wall, 0) — failover retries, backoff sleeps, and
                    transfer. When a trace has route events but no
                    replica query_span (the replica died, or only the
                    router's log is at hand), the whole forward time
                    lands here and the route dur_ms IS the wall
    plan-host       the driver residual: parse/bind/rewrite/budget,
                    host-side result materialization, report overhead —
                    the same "driver time" bucket the reference's
                    profiling tool derives for non-stage wall. Counted
                    as ATTRIBUTED only while it stays a minority share
                    (<= MAX_RESIDUAL_FRAC of wall); a larger residual
                    means span evidence is missing, and the honest
                    answer is `unattributed` — the CI gate's >= 90%
                    assertion then fails instead of laundering the gap.

Beside the causes, and never added to them, a query's `within_execute`
names operator classes whose time the causes above already hold
(a scalar subquery's plan waits, launches and traces like any other):

    setop            the SetOp spans' own (exclusive) time, with their
                     count by `op` and the rows of their sides
    scalar-subquery  the `scalar_subquery` spans, inclusive of the plan
                     each ran: count and ms by `source` (executed |
                     session-cache), the columns their scans read, how
                     many yielded NULL
    blocked-union    the `blocked_union` spans: count, windows, ms
    aggregate-tail   the `pipeline_span`s of aggregate tails by `agg_route`
                     (whole | scatter | eager): a count, no time
"""

from __future__ import annotations

from .reader import (
    format_host_table, format_within, host_by_operator, within_execute,
)

#: residual share of wall beyond which plan-host stops counting as
#: attributed (evidence-coverage collapse, not driver work)
MAX_RESIDUAL_FRAC = 0.5

#: cause names in render order
CAUSE_ORDER = (
    "execute", "device-wait", "launch", "eager", "jit-trace", "xla-compile",
    "cache-load", "exec-lookup", "host-python", "exchange-wait", "spill-io",
    "catalog-load", "read", "encode", "h2d", "ladder-retry",
    "backoff-wait", "hung-wait", "ingest-decode", "ingest-commit-wait",
    "snapshot-pin", "prune-planning", "plan-budget", "router-queue",
    "router-forward",
    "plan-host",
)


def _group_query_events(events) -> dict:
    """{query name: [events]} for the kinds the reconstruction reads."""
    out = {}
    for ev in events:
        kind = ev.get("kind")
        if kind in ("op_span", "query_span", "exchange", "spill",
                    "catalog_load", "ladder_rung", "watchdog_fire",
                    "kernel_span", "ingest_chunk", "scan_prune", "lake_pin",
                    "route_request", "host_read", "result_span",
                    "xla_compile", "aot_cache", "exec_cache",
                    "plan_budget", "scalar_subquery", "blocked_union",
                    "pipeline_span"):
            q = ev.get("query") or "<unscoped>"
            out.setdefault(q, []).append(ev)
    return out


def _op_tree_chain(spans) -> list:
    """The critical chain of one query's op spans: rebuild the plan tree
    from (exec_id, seq, depth) post-order, then walk root -> heaviest
    child. Returns [{"node", "dur_ms", "depth"}...] root-first for the
    LAST executed root (the attempt that produced the result)."""
    by_exec = {}
    for ev in spans:
        by_exec.setdefault(ev.get("exec_id"), []).append(ev)
    best = None
    for evs in by_exec.values():
        evs.sort(key=lambda e: e.get("seq", 0))
        pending = {}  # depth -> [(span, children)]
        roots = []
        for ev in evs:
            d = ev.get("depth", 0)
            children = pending.pop(d + 1, [])
            rec = (ev, children)
            if d == 0:
                roots.append(rec)
            else:
                pending.setdefault(d, []).append(rec)
        if roots:
            best = roots[-1]
    if best is None:
        return []
    chain = []
    node = best
    while node is not None:
        ev, children = node
        chain.append({
            "node": ev.get("node"),
            "dur_ms": float(ev.get("dur_ms") or 0.0),
            "depth": ev.get("depth", 0),
        })
        node = max(
            children, key=lambda c: float(c[0].get("dur_ms") or 0.0),
            default=None,
        )
    return chain


# painting order of a traced execution's timeline: where several intervals
# cover an instant, the first of these gets it
_PAINT = ("device-wait", "xla-compile", "cache-load", "jit-trace",
          "exchange-wait", "spill-io", "catalog-load", "exec-lookup",
          "result")


def _interval(ev):
    """(start_ns, end_ns) of a span event: `t0_ns` where the log has it,
    else back from the emission time (`ts`, epoch ms: 1 ms resolution)."""
    dur_ns = float(ev.get("dur_ms") or 0.0) * 1e6
    t0 = ev.get("t0_ns")
    if t0 is None:
        t0 = float(ev.get("ts") or 0) * 1e6 - dur_ns
    return float(t0), float(t0) + dur_ns


def _paint_ms(intervals) -> dict:
    """Exclusive milliseconds per category of `[(start_ns, end_ns, cat)]`,
    each instant counted once, for the first category of `_PAINT` among
    those covering it."""
    rank = {c: i for i, c in enumerate(_PAINT)}
    points = []
    for a, b, cat in intervals:
        if b > a:
            points.append((a, 1, rank[cat]))
            points.append((b, -1, rank[cat]))
    points.sort()
    active = [0] * len(_PAINT)
    out = [0.0] * len(_PAINT)
    prev = None
    for t, step, k in points:
        if prev is not None and t > prev:
            for i, n in enumerate(active):
                if n:
                    out[i] += t - prev
                    break
        active[k] += step
        prev = t
    return {c: out[i] / 1e6 for c, i in rank.items()}


def _compile_cause(ev):
    if ev.get("stage") != "compile":
        return "jit-trace"
    return "cache-load" if ev.get("cached") else "xla-compile"


def _split_execution(results, spans, reads, compiles, aot_loads, lookups,
                     cats, exchanges, spills) -> dict:
    """The causes of a traced execution (see the module docstring), from
    the `result_span`s of a query and what happened under them."""
    iv = [(*_interval(e), "result") for e in results]
    iv += [(*_interval(e), "device-wait") for e in reads]
    iv += [(*_interval(e), _compile_cause(e)) for e in compiles]
    iv += [(*_interval(e), "cache-load") for e in aot_loads]
    iv += [(*_interval(e), "exec-lookup") for e in lookups]
    iv += [(*_interval(e), "catalog-load") for e in cats]
    iv += [(*_interval(e), "exchange-wait") for e in exchanges]
    iv += [(*_interval(e), "spill-io") for e in spills]
    own = list(spans) + list(results)
    named = any("host_ms" in e for e in own)
    if named:
        # the lookup and the build are phases of the spans' own
        iv = [x for x in iv if x[2] != "exec-lookup"]
    ms = _paint_ms(iv)
    out = {c: ms[c] for c in _PAINT if c != "result"}
    if named:
        # the spans say what their own launches took, by seam name, with
        # the reads and compile stages inside them taken out in the program
        launch = eager = 0.0
        for e in own:
            for name, v in (e.get("launch_ms_by") or {}).items():
                if name.startswith("eager:"):
                    eager += float(v)
                else:
                    launch += float(v)
        launch = min(launch, ms["result"])
        out["eager"] = eager = min(eager, ms["result"] - launch)
    else:
        # launches have no interval of their own (no event per launch):
        # their host time is a sum, and the compile stages that fell
        # inside seamed calls are already counted above
        in_seam = _paint_ms([
            (*_interval(e), "jit-trace") for e in compiles
            if e.get("in_seam")
        ])["jit-trace"]
        launch = sum(float(e.get("launch_ms") or 0.0) for e in own)
        launch = min(max(launch - in_seam, 0.0), ms["result"])
    out["launch"] = launch
    out["host-python"] = ms["result"] - launch - out.get("eager", 0.0)
    if named:
        phases = {}
        for e in own:
            for name, v in (e.get("host_ms") or {}).items():
                phases[name] = phases.get(name, 0.0) + float(v)
        # a scan's phase holds its catalog_load, which has causes of its own
        if "scan" in phases:
            phases["scan"] = max(phases["scan"] - ms["catalog-load"], 0.0)
        out["_host_python"] = {
            "phases": phases,
            "other": out["host-python"] - sum(phases.values()),
        }
    # a catalog load says what it spent: split its share accordingly
    parts = {
        k: sum(float(e.get(f"{k}_ms") or 0.0) for e in cats)
        for k in ("read", "encode", "h2d")
    }
    total = sum(parts.values())
    if total > 0:
        cat_ms = out["catalog-load"]
        for k, v in parts.items():
            out[k] = cat_ms * v / total
        out["catalog-load"] = 0.0
    return out


def _execution_detail(spans, results, reads, compiles) -> dict:
    """Launches by kernel, reads by `why`, compiles by `fun`."""
    launches = {}
    for e in list(spans) + list(results):
        for kernel, n in (e.get("launches") or {}).items():
            launches[kernel] = launches.get(kernel, 0) + int(n)
    by_why = {}
    for e in reads:
        rec = by_why.setdefault(e.get("why") or "?", {"count": 0, "ms": 0.0})
        rec["count"] += 1
        rec["ms"] = round(rec["ms"] + float(e.get("dur_ms") or 0.0), 3)
    by_fun = {}
    for e in compiles:
        rec = by_fun.setdefault(
            e.get("fun") or "?",
            {"count": 0, "ms": 0.0, "fresh": 0},
        )
        rec["ms"] = round(rec["ms"] + float(e.get("dur_ms") or 0.0), 3)
        if e.get("stage") == "compile":
            rec["count"] += 1
            if not e.get("cached"):
                rec["fresh"] += 1
    return {"launches": launches, "reads": by_why, "compiles": by_fun,
            "operators": host_by_operator(spans, results)}


def _skew_ms(ev) -> float:
    """The imbalance share of one exchange's wait: the time a perfectly
    balanced partition map would have given back, dur * (1 - 1/skew)."""
    try:
        dur = float(ev.get("dur_ms") or 0.0)
        skew = float(ev.get("skew") or 1.0)
    except (TypeError, ValueError):
        return 0.0
    if dur <= 0 or skew <= 1.0:
        return 0.0
    return dur * (1.0 - 1.0 / skew)


def critical_path(events) -> dict:
    """Per-query cause attribution + mesh straggler summary over one or
    more streams' events. Returns::

        {"queries": {name: {"wall_ms", "runs", "status", "causes": {...},
                            "attributed_ms", "attributed_frac",
                            "kernel_ms", "chain": [...],
                            "exchange": {...} | None}},
         "mesh": {...} | None}
    """
    queries = {}
    # mesh roll-up across queries: per-device received rows + skew cost
    mesh_rows = []
    mesh_exchange_ms = 0.0
    mesh_skew_ms = 0.0
    mesh_ops = 0
    for q, evs in sorted(_group_query_events(events).items()):
        wall = 0.0
        runs = 0
        status = None
        spans = []
        results, reads, compiles, aot_loads, lookups = [], [], [], [], []
        cats, exchanges, spills = [], [], []
        subqueries, blocked, pipelines = [], [], []
        exch_ms = skew_ms = spill_ms = cat_ms = 0.0
        ladder_ms = backoff_ms = hung_ms = kernel_ms = 0.0
        decode_ms = commit_wait_ms = prune_ms = pin_ms = budget_ms = 0.0
        route_n = 0
        route_dur_ms = route_queue_ms = route_forward_ms = 0.0
        route_status = None
        exch_rows = None  # per-device received rows, element-wise summed
        exch_worst = None  # the highest-skew exchange event
        for ev in evs:
            kind = ev["kind"]
            if kind == "query_span":
                wall += float(ev.get("dur_ms") or 0.0)
                runs += 1
                if status != "Failed":
                    status = ev.get("status")
            elif kind == "op_span":
                spans.append(ev)
            elif kind == "result_span":
                results.append(ev)
            elif kind == "host_read":
                reads.append(ev)
            elif kind == "xla_compile":
                compiles.append(ev)
            elif kind == "aot_cache":
                if ev.get("op") == "load" and ev.get("dur_ms") is not None:
                    aot_loads.append(ev)
            elif kind == "exec_cache":
                if ev.get("dur_ms") is not None:
                    lookups.append(ev)
            elif kind == "exchange":
                exchanges.append(ev)
                mesh_ops += 1
                d = float(ev.get("dur_ms") or 0.0)
                exch_ms += d
                s = _skew_ms(ev)
                skew_ms += s
                mesh_exchange_ms += d
                mesh_skew_ms += s
                per = ev.get("per_device")
                if isinstance(per, list) and per:
                    if exch_rows is None:
                        exch_rows = [0] * len(per)
                    for i, r in enumerate(per):
                        if i >= len(exch_rows):
                            exch_rows.append(0)
                        exch_rows[i] += int(r or 0)
                    while len(mesh_rows) < len(per):
                        mesh_rows.append(0)
                    for i, r in enumerate(per):
                        mesh_rows[i] += int(r or 0)
                try:
                    sk = float(ev.get("skew") or 1.0)
                except (TypeError, ValueError):
                    sk = 1.0
                if exch_worst is None or sk > exch_worst[0]:
                    exch_worst = (sk, ev)
            elif kind == "spill":
                spills.append(ev)
                spill_ms += float(ev.get("dur_ms") or 0.0)
            elif kind == "catalog_load":
                cats.append(ev)
                cat_ms += float(ev.get("dur_ms") or 0.0)
            elif kind == "ladder_rung":
                ladder_ms += float(ev.get("attempt_ms") or 0.0)
                backoff_ms += float(ev.get("delay_s") or 0.0) * 1000.0
            elif kind == "watchdog_fire":
                hung_ms += float(ev.get("budget_s") or 0.0) * 1000.0
            elif kind == "kernel_span":
                kernel_ms += float(ev.get("dur_ms") or 0.0)
            elif kind == "ingest_chunk":
                decode_ms += float(ev.get("decode_ms") or 0.0)
                commit_wait_ms += float(ev.get("commit_ms") or 0.0)
            elif kind == "scan_prune":
                prune_ms += float(ev.get("dur_ms") or 0.0)
            elif kind == "lake_pin":
                pin_ms += float(ev.get("dur_ms") or 0.0)
            elif kind == "plan_budget":
                budget_ms += float(ev.get("dur_ms") or 0.0)
            elif kind == "scalar_subquery":
                subqueries.append(ev)
            elif kind == "blocked_union":
                blocked.append(ev)
            elif kind == "pipeline_span":
                pipelines.append(ev)
            elif kind == "route_request":
                route_n += 1
                route_dur_ms += float(ev.get("dur_ms") or 0.0)
                route_queue_ms += float(ev.get("queue_ms") or 0.0)
                route_forward_ms += float(ev.get("forward_ms") or 0.0)
                if route_status != "Failed":
                    route_status = (
                        "Completed" if ev.get("status") == "completed"
                        else "Failed"
                    )
        if route_n:
            # the router hop wraps replica-side execution: the router's
            # end-to-end dur is the fleet wall (>= the replica's
            # query_span wall when both logs fold into one trace), and
            # router-forward is only the upstream time the replica wall
            # does NOT explain (failover retries, backoff, transfer) so
            # the buckets stay disjoint
            replica_wall = wall
            wall = max(wall, route_dur_ms)
            runs = runs or route_n
            status = status or route_status
            route_forward_ms = max(route_forward_ms - replica_wall, 0.0)
        root_incl = sum(
            float(e.get("dur_ms") or 0.0)
            for e in spans
            if e.get("depth", 0) == 0
        )
        # measured sub-causes live INSIDE plan-node execution; `execute`
        # is what remains of the root inclusive time after carving them
        # out (floored: an exchange that outlived its op span under
        # clock jitter must not go negative)
        execute = max(root_incl - exch_ms - spill_ms - cat_ms, 0.0)
        split = host_python = None
        if results:
            # a log with the statement's own boundary: the lump opens
            split = _split_execution(
                results, spans, reads, compiles, aot_loads, lookups, cats,
                exchanges, spills,
            )
            execute = 0.0
            host_python = split.pop("_host_python", None)
            exch_ms = split.pop("exchange-wait")
            spill_ms = split.pop("spill-io")
            cat_ms = split.pop("catalog-load")
        # hung-wait is capped at what the OTHER measured causes leave of
        # the wall (the abandoned attempt's partial spans may overlap the
        # budget; counting both would over-attribute)
        others = (
            execute + sum((split or {}).values())
            + exch_ms + spill_ms + cat_ms + ladder_ms + backoff_ms
            + decode_ms + commit_wait_ms + prune_ms + pin_ms + budget_ms
            + route_queue_ms + route_forward_ms
        )
        causes = {
            "execute": round(execute, 3),
            **{k: round(v, 3) for k, v in (split or {}).items()},
            "exchange-wait": round(exch_ms, 3),
            "spill-io": round(spill_ms, 3),
            "catalog-load": round(cat_ms, 3),
            "ladder-retry": round(ladder_ms, 3),
            "backoff-wait": round(backoff_ms, 3),
            "hung-wait": round(min(hung_ms, max(wall - others, 0.0)), 3)
            if hung_ms else 0.0,
            "ingest-decode": round(decode_ms, 3),
            "ingest-commit-wait": round(commit_wait_ms, 3),
            "snapshot-pin": round(pin_ms, 3),
            "prune-planning": round(prune_ms, 3),
            "plan-budget": round(budget_ms, 3),
            "router-queue": round(route_queue_ms, 3),
            "router-forward": round(route_forward_ms, 3),
        }
        measured = sum(causes.values())
        residual = wall - measured
        if 0.0 <= residual <= wall * MAX_RESIDUAL_FRAC:
            causes["plan-host"] = round(residual, 3)
            unattributed = 0.0
        else:
            # negative residual (cross-thread clock jitter / evidence
            # overlap) or a majority residual (missing spans): report the
            # gap honestly instead of inventing a cause for it
            causes["plan-host"] = 0.0
            unattributed = max(residual, 0.0)
        attributed = min(sum(causes.values()), wall) if wall else 0.0
        qrec = {
            "wall_ms": round(wall, 3),
            "runs": runs,
            "status": status,
            "causes": causes,
            "attributed_ms": round(attributed, 3),
            "attributed_frac": round(attributed / wall, 4) if wall else None,
            "unattributed_ms": round(unattributed, 3),
            "kernel_ms": round(kernel_ms, 3),  # overlaps execute: info only
            "chain": _op_tree_chain(spans),
            **_execution_detail(spans, results, reads, compiles),
        }
        within = within_execute(spans, subqueries, blocked, pipelines)
        if within:
            # views into the causes above, never added to them
            qrec["within_execute"] = within
        if host_python is not None:
            # `host-python` by phase, and what is left of it without a name
            qrec["host_python"] = {
                "phases": {k: round(v, 3)
                           for k, v in host_python["phases"].items()},
                "other": round(host_python["other"], 3),
            }
        if exch_worst is not None:
            sk, ev = exch_worst
            straggler = None
            if isinstance(exch_rows, list) and exch_rows and max(exch_rows):
                straggler = int(max(
                    range(len(exch_rows)), key=lambda i: exch_rows[i]
                ))
            qrec["exchange"] = {
                "ops": sum(1 for e in evs if e["kind"] == "exchange"),
                "wait_ms": round(exch_ms, 3),
                "skew_ms": round(skew_ms, 3),
                "max_skew": sk,
                "straggler_device": straggler,
                "per_device_rows": exch_rows,
            }
        else:
            qrec["exchange"] = None
        queries[q] = qrec
    mesh = None
    if mesh_ops:
        straggler = None
        if mesh_rows and max(mesh_rows):
            straggler = int(max(
                range(len(mesh_rows)), key=lambda i: mesh_rows[i]
            ))
        mesh = {
            "exchange_ops": mesh_ops,
            "exchange_ms": round(mesh_exchange_ms, 3),
            "skew_ms": round(mesh_skew_ms, 3),
            "skew_share": round(mesh_skew_ms / mesh_exchange_ms, 4)
            if mesh_exchange_ms else None,
            "straggler_device": straggler,
            "per_device_rows": mesh_rows or None,
        }
    return {"queries": queries, "mesh": mesh}


def min_attributed_frac(cp: dict):
    """The worst per-query attribution share of a `critical_path` result
    (None when it profiled no timed queries) — the CI diagnosis gate's
    >= 0.9 assertion reads this."""
    fracs = [
        q["attributed_frac"]
        for q in cp["queries"].values()
        if q["attributed_frac"] is not None
    ]
    return min(fracs) if fracs else None


def render(cp: dict, out=None) -> None:
    """Human rendering of a `critical_path` result (the profiler CLI's
    --critical-path text mode)."""
    import sys

    out = out or sys.stdout

    def p(line=""):
        print(line, file=out)

    queries = cp["queries"]
    p(f"== critical path: {len(queries)} queries")
    for q in sorted(queries):
        rec = queries[q]
        frac = rec["attributed_frac"]
        frac_s = "-" if frac is None else f"{frac:.0%}"
        status = rec.get("status") or "?"
        p(f"\n-- {q}: wall {rec['wall_ms']:,.1f} ms  {status}  "
          f"(attributed {frac_s})")
        for cause in CAUSE_ORDER:
            ms = rec["causes"].get(cause, 0.0)
            if ms <= 0:
                continue
            share = ms / rec["wall_ms"] if rec["wall_ms"] else 0.0
            p(f"   {cause:<14}{ms:>12,.1f} ms  {share:>6.1%}")
            if cause == "host-python" and rec.get("host_python"):
                hp = rec["host_python"]
                for name, v in [*sorted(hp["phases"].items(),
                                        key=lambda kv: -kv[1]),
                                ("other", hp["other"])]:
                    share = v / rec["wall_ms"] if rec["wall_ms"] else 0.0
                    p(f"     {name:<16}{v:>8,.1f} ms  {share:>6.1%}")
        if rec.get("unattributed_ms"):
            p(f"   {'unattributed':<14}{rec['unattributed_ms']:>12,.1f} ms")
        for line in format_within(rec.get("within_execute") or {}):
            p(line)
        if rec.get("launches"):
            p("   launches: " + ", ".join(
                f"{k} {n}" for k, n in sorted(
                    rec["launches"].items(), key=lambda kv: -kv[1])))
        if rec.get("reads"):
            p("   reads: " + ", ".join(
                f"{why} {r['count']} ({r['ms']:,.1f} ms)"
                for why, r in sorted(
                    rec["reads"].items(), key=lambda kv: -kv[1]["ms"])))
        if rec.get("compiles"):
            top = sorted(rec["compiles"].items(),
                         key=lambda kv: -kv[1]["ms"])[:8]
            p("   compiles: " + ", ".join(
                f"{fun} {c['count']} ({c['fresh']} fresh, {c['ms']:,.1f} ms)"
                for fun, c in top))
        for line in format_host_table(sorted(
                (rec.get("operators") or {}).items(),
                key=lambda kv: -kv[1]["excl_ms"])):
            p(line)
        if rec["chain"]:
            hops = " -> ".join(
                f"{c['node']} {c['dur_ms']:,.0f}ms" for c in rec["chain"][:6]
            )
            p(f"   chain: {hops}")
        ex = rec.get("exchange")
        if ex is not None and ex["wait_ms"]:
            dev = (
                f"device {ex['straggler_device']}"
                if ex["straggler_device"] is not None else "unknown device"
            )
            p(f"   exchange: {ex['ops']} op(s), {ex['wait_ms']:,.1f} ms "
              f"wait; straggler {dev} (max skew {ex['max_skew']:.2f}x, "
              f"skew cost {ex['skew_ms']:,.1f} ms)")
    mesh = cp.get("mesh")
    if mesh:
        dev = (
            f"device {mesh['straggler_device']}"
            if mesh["straggler_device"] is not None else "unknown device"
        )
        share = mesh["skew_share"]
        share_s = "-" if share is None else f"{share:.0%}"
        p(f"\n== mesh: {mesh['exchange_ops']} exchange(s), "
          f"{mesh['exchange_ms']:,.1f} ms on the interconnect; straggler "
          f"{dev}; skew share of the exchange gap {share_s} "
          f"({mesh['skew_ms']:,.1f} ms a balanced partition map would "
          f"give back)")
