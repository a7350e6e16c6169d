"""Plan executor: interprets a logical plan over device Tables.

Eager, operator-at-a-time execution. Each operator is built from the jitted
kernels in nds_tpu.ops.kernels over power-of-two-bucketed buffers, so the
shapes XLA compiles stay bounded while live row counts vary freely. Join
ordering inside MultiJoin is greedy over *actual* row counts — eager
execution's answer to AQE (reference: nds/properties/aqe-on.properties:1).

The executor is the engine the reference delegates to Spark executors + the
rapids plugin (reference: nds/nds_power.py:125-135 spark.sql -> collect).
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from dataclasses import replace as _dc_replace
from time import perf_counter as _perf
from time import time_ns as _time_ns

from .. import faults
from ..dtypes import BOOL, DType, FLOAT64, INT64
from ..obs import tally as _tally
from ..obs.tally import host_read
from ..ops import kernels as K
from . import aotcache as AOTC
from . import expr as E
from . import fuse
from . import plan as P
from . import spill as SP
from .columnar import (
    Column,
    Table,
    _dyn_slice,
    bucket_cap,
    gather_columns,
    narrow_columns,
    table_to_arrow,
    unify_dictionaries,
    sort_dictionary,
    table_device_bytes,
    window_slice,
)
from .expr import (
    Evaluator,
    _and_valid,
    _cast_column,
    _common_dtype,
    _share_dictionary,
)


class ExecError(Exception):
    pass


# executor instance ids for op-span grouping in the event log (profiling
# reconstructs span nesting per (query, executor) from seq/depth)
_EXEC_IDS = itertools.count(1)


def _resolve_bounds(datas, valids, stats_list, wanted, live):
    """(vmin, vmax) per column: from cached ColStats when present, else one
    batched min/max kernel + a single device->host transfer for ALL missing
    ranges. `wanted[i]=False` slots return None. Shared by the group-key and
    sort-key packers."""
    bounds, need = [], []
    for i, (st, w) in enumerate(zip(stats_list, wanted)):
        if not w:
            bounds.append(None)
            continue
        if st is not None and st.vmin is not None and st.vmax is not None:
            bounds.append((int(st.vmin), int(st.vmax)))
        else:
            bounds.append(None)
            need.append(i)
    if need:
        fetched = host_read(
            "bounds",
            K.batched_min_max(
                [datas[i].astype(jnp.int64) for i in need],
                [valids[i] for i in need],
                live,
            ),
        )
        for i, mm in zip(need, fetched):
            bounds[i] = (int(mm[0]), int(mm[1]))
    return bounds


def _cascade_agg_items(agg_items):
    """Re-aggregation exprs for the rollup cascade, or None when any
    aggregate doesn't decompose over partial results. sum/min/max compose
    with themselves; any count becomes a sum of the level below's counts."""
    out = []
    for a, name in agg_items:
        if a.distinct or a.fn not in ("sum", "min", "max", "count"):
            return None
        fn = "sum" if a.fn == "count" else a.fn
        out.append((E.Agg(fn, E.Col(name)), name))
    return out


def _rollup_base_aggs(agg_items):
    """(base agg list, avg items) for grouping-sets execution: every plain
    avg is decomposed into hidden sum (__cs_<name>) + count (__cc_<name>)
    columns so the cascade can compose it; the visible avg column is
    derived per part by _derive_rollup_avgs with the exact semantics of
    the direct avg path (float64, decimal descale, NULL on empty).
    Returns (None, []) when any aggregate rules the rewrite out."""
    if not P.aggs_decomposable(agg_items):
        return None, []
    avg_items = [(a, n) for a, n in agg_items if a.fn == "avg"]
    if not avg_items:
        return list(agg_items), []
    base = []
    for a, name in agg_items:
        if a.fn == "avg":
            base.append((E.Agg("sum", a.arg), f"__cs_{name}"))
            base.append((E.Agg("count", a.arg), f"__cc_{name}"))
        else:
            base.append((a, name))
    return base, avg_items


@_tally.seamed("agg")
def _derive_rollup_avgs(part: "Table", avg_items):
    if not avg_items:
        return part
    cols = dict(part.columns)
    for _, name in avg_items:
        cs = cols[f"__cs_{name}"]
        cc = cols[f"__cc_{name}"]
        n = cc.data
        val = cs.data.astype(jnp.float64) / jnp.maximum(n, 1)
        if cs.dtype.is_decimal:
            val = val / 10**cs.dtype.scale
        cols[name] = Column(val, FLOAT64, n > 0)
    return Table(cols, part.nrows_lazy, live=part.live)


def _plain_col_names(exprs, table):
    """Column names referenced by plain Col exprs, resolved the way the
    evaluator resolves them against `table` (qualified first, bare next)."""
    out = set()
    for e in exprs:
        if isinstance(e, E.Col):
            key = f"{e.table}.{e.name}" if e.table else e.name
            if key not in table.columns and e.name in table.columns:
                key = e.name
            out.add(key)
    return out


def _key_stats(expr, table):
    """The `ColStats` of a join key that is a plain column of `table`
    (resolved as the evaluator does); None for any other key."""
    if not isinstance(expr, E.Col):
        return None
    (key,) = _plain_col_names([expr], table)
    c = table.columns.get(key)
    return None if c is None else c.stats


def _est_join_rows(lt, rt, le, re_):
    """Rows the inner join `lt.le = rt.re_` is estimated to leave, from
    host integers alone (the sides' live counts, the keys' `ColStats`), or
    None where they say nothing: a key that is no plain column or has no
    stats, or neither side `unique`.

    The many side keeps the share of its rows whose key is still live on
    the unique side. That share is taken over the keys the many side can
    hold, not over the unique side's base: 366 of date_dim's 73,049 days
    are a fifth of the 1,823 days store_sales references."""
    ls, rs = _key_stats(le, lt), _key_stats(re_, rt)
    if ls is None or rs is None:
        return None
    ests = [
        many_t.nrows * min(1.0, one_t.nrows / max(
            1, min(one.base_rows, many.vmax - many.vmin + 1)
        ))
        for one, one_t, many, many_t in ((ls, lt, rs, rt), (rs, rt, ls, lt))
        if one.unique
    ]
    return min(ests) if ests else None


def _active_key_names(key_items, key_cols):
    """Group-by output rows are pairwise distinct over the active (non-
    rolled-up) key columns; probe-style joins read this to skip runtime
    uniqueness checks."""
    return frozenset(
        name for (_, name), c in zip(key_items, key_cols) if c is not None
    )


def _group_key_stats(c: "Column", n_active_keys: int):
    """Output stats for a group-by key column: bounds are the input's
    (group keys are a value subset); a single-key grouping's output is
    unique by construction — which is exactly what downstream probe-style
    joins (dense, packed) need to know to avoid a runtime uniqueness
    check."""
    st = c.subset_stats()
    if st is None:
        return None
    return _dc_replace(st, unique=(n_active_keys == 1))


class _DictStats:
    """Static bounds facade for dictionary-coded columns (codes/ranks span
    [0, len(dictionary)) by construction — no device fetch needed)."""

    __slots__ = ("vmin", "vmax", "unique", "base_rows")

    def __init__(self, vmin, vmax):
        self.vmin = vmin
        self.vmax = vmax
        self.unique = False
        self.base_rows = 0


class Executor:
    def __init__(self, catalog, on_task_failure=None, tracer=None):
        """catalog: object with .load(table_name) -> Table.

        on_task_failure(reason) is called for recoverable incidents the
        executor survives (capacity-overflow retries, fallbacks) so the
        harness can report CompletedWithTaskFailures (reference analogue:
        Spark task retries surfaced via jvm_listener).

        tracer: an obs.Tracer (defaults to the owning session's) — every
        executed plan node then records an `op_span` event with inclusive
        wall time, output rows, and estimated output bytes. Per-executor
        span state (exec id, seq, depth) is thread-safe by construction:
        each concurrent throughput stream builds its own Executor per
        statement, so streams never share span collections (the old
        module-global TRACE_NODES would have corrupted across streams)."""
        self.catalog = catalog
        self.on_task_failure = on_task_failure or (lambda reason: None)
        self._cte_cache = {}  # id(plan) -> Table
        self._scalar_cache = {}  # id(plan) -> python value
        self._fp_cache = {}  # id(plan) -> structural fingerprint
        # stats of the most recent blocked union-aggregation (tests/tools)
        self.last_blocked_union = None
        # stats of this statement's out-of-core (spilled) operator
        # executions, accumulated across ops (tests/bench evidence)
        self.last_spill = None
        self._fault_checked = False  # exec-root injection fires once
        # inside a spilled-join partition loop the mesh exchange path is
        # disabled: the partitions exist because an exchange (or the
        # budgeter) already decided the whole join can't fit — re-entering
        # the exchange per partition pair could recurse under skew
        self._exchange_disabled = False
        # what the last `_join_body` ran at and the last `_multijoin_greedy`
        # did, kept for the MultiJoin's op_span
        self._left_cap = None
        self._join_steps = None
        # what the last `_exec_setop` saw (traced executions only), kept for
        # the SetOp's op_span, and the columns the scans of a traced
        # execution asked of the catalog, read by `_scalar_value`
        self._setop_info = None
        self._scan_cols = 0
        if tracer is None:
            tracer = getattr(
                getattr(catalog, "session", None), "tracer", None
            )
        self.tracer = tracer
        self._span_depth = 0
        self._span_seq = 0
        self._exec_id = next(_EXEC_IDS) if tracer is not None else 0
        # launches and blocking reads of this statement, counted by the
        # seams of obs/tally.py while this is bound to the thread and
        # flushed as fields of the op_spans (None: untraced, seams are bare)
        self.tally = (
            _tally.Tally(tracer, self._exec_id) if tracer is not None
            else None
        )

    # plan-node types worth caching across statements: the expensive
    # pipeline breakers (a CTE body virtually always ends in one)
    _CACHEABLE = (P.Aggregate, P.Distinct, P.SetOp, P.Window)

    def _session_cache(self):
        session = getattr(self.catalog, "session", None)
        if session is None:
            return None
        if session.conf.get("engine.plan_cache", "on") == "off":
            return None
        return session.plan_cache

    def _fp(self, node) -> str:
        key = id(node)
        fp = self._fp_cache.get(key)
        if fp is None:
            fp = self._fp_cache[key] = P.fingerprint(node)
        return fp

    # pipeline breakers whose actual row count is worth a forced host
    # sync when it isn't already there: a handful per plan, and their
    # consumers are about to sync anyway. Row-preserving nodes record
    # only opportunistically (count already on host) — feedback must not
    # add a device round-trip per traced node.
    _FEEDBACK_SYNC = (P.Join, P.MultiJoin, P.Aggregate, P.Distinct,
                      P.SetOp, P.Window, P.Sort)

    def _record_feedback(self, node, out):
        """Record this node's measured cardinality into the session
        FeedbackStore (buffered in memory; Session.close() writes it).
        Only called for nodes budget_plan annotated with `node_fp` —
        i.e. engine.plan_feedback is record/on and a store exists."""
        session = getattr(self.catalog, "session", None)
        store = getattr(session, "feedback_store", None)
        if store is None:
            return
        rows = out.nrows_known
        if rows is None and (
            isinstance(node, self._FEEDBACK_SYNC)
            or (isinstance(node, P.Pipeline) and node.agg is not None)
        ):
            rows = out.nrows
        if rows is None:
            return
        est_rows = getattr(node, "est_rows", None)
        with session.cache_lock:
            err = store.record(
                node.node_fp, rows=rows, nbytes=table_device_bytes(out),
                est_rows=est_rows,
            )
        if self.tracer is not None:
            ev = dict(op="record", result="ok",
                      node=type(node).__name__, actual_rows=int(rows))
            if est_rows is not None:
                ev["est_rows"] = int(est_rows)
            if err is not None:
                ev["abs_log_err"] = round(err, 4)
            self.tracer.emit("plan_feedback", **ev)

    # ------------------------------------------------------------------
    def execute(self, node: P.PlanNode) -> Table:
        if not self._fault_checked:
            # failure-domain injection site at the executor root (once per
            # executor, i.e. per statement): `exec:<query>` faults fire
            # inside the engine proper, past plan/bind, so the harness
            # ladder sees exactly what a mid-execution device failure
            # looks like. Zero-cost when no fault spec is installed.
            self._fault_checked = True
            if faults.active():
                scope = faults.current_scope()
                if scope is not None:
                    faults.maybe_fire(f"exec:{scope}")
        key = id(node)
        if key in self._cte_cache:
            return self._cte_cache[key]
        tracer = self.tracer
        # agg-tail Pipelines are the fused form of a (cacheable) Aggregate:
        # they keep the cross-statement CTE reuse the raw node had
        cacheable = isinstance(node, self._CACHEABLE) or (
            isinstance(node, P.Pipeline) and node.agg is not None
        )
        cache = self._session_cache() if cacheable else None
        if cache is not None:
            with _tally.phase("plan-cache"), self.catalog.session.cache_lock:
                hit = cache.get(self._fp(node))
            if tracer is not None:
                tracer.emit(
                    "plan_cache", node=type(node).__name__,
                    hit=hit is not None,
                )
            if hit is not None:
                self._cte_cache[key] = hit
                return hit
        m = getattr(self, f"_exec_{type(node).__name__.lower()}")
        if tracer is not None:
            # INCLUSIVE wall time (children execute inside this frame);
            # repeated visits are cte-cache dict hits, so each node records
            # once per executor. Spans emit in completion (post-) order
            # with (exec_id, seq, depth) so the profiler can rebuild the
            # tree and derive exclusive times. The tally's counters are
            # this node's own (exclusive of children): push/pop swap them.
            depth = self._span_depth
            self._span_depth = depth + 1
            tally = self.tally
            bound = None
            if depth == 0 and _tally.current() is not tally:
                # an executor driven directly, not through Result
                bound = _tally.bind(tally)
                bound.__enter__()
            saved = tally.push(depth)
            t0_ns = _time_ns()
            t0 = _perf()
            try:
                out = m(node)
                # estimate-vs-actual accounting inside the span: a
                # pipeline-breaker record may force the queued count onto
                # the host (a host_read this node waited for), and the
                # span's actual_rows should see it
                fp = getattr(node, "node_fp", None)
                if fp is not None:
                    with _tally.phase("feedback"):
                        self._record_feedback(node, out)
            finally:
                self._span_depth = depth
                own = tally.pop(saved, t0)
                if bound is not None:
                    bound.__exit__(None, None, None)
            dur_ms = (_perf() - t0) * 1000.0
            # the span's own making (node_desc, byte counts, the emit) is
            # its parent's host time
            with _tally.phase("span-emit"):
                self._emit_op_span(node, out, depth, t0_ns, dur_ms, own, fp)
        else:
            out = m(node)
            if getattr(node, "node_fp", None) is not None:
                self._record_feedback(node, out)
        self._cte_cache[key] = out
        if cache is not None:
            with _tally.phase("plan-cache"), self.catalog.session.cache_lock:
                cache.put(self._fp(node), out)
        return out

    def _emit_op_span(self, node, out, depth, t0_ns, dur_ms, own, fp):
        """One executed plan node's `op_span`: `own` is the tally's frame
        (the node's own launches, reads, compile stages and phases)."""
        self._span_seq += 1
        nbytes = table_device_bytes(out)
        span = dict(
            exec_id=self._exec_id,
            seq=self._span_seq,
            depth=depth,
            node=type(node).__name__,
            explain=P.node_desc(node)[:90],
            t0_ns=t0_ns,
            dur_ms=round(dur_ms, 3),
            # nrows_known only: forcing a queued count would add a
            # device sync to every traced node
            rows=out.nrows_known,
            est_bytes=nbytes,
            **own,
        )
        if isinstance(node, (P.Filter, P.Join, P.MultiJoin)) or (
            isinstance(node, P.Pipeline) and node.agg is None
            and all(isinstance(st, P.Filter) for st in node.stages)
        ):
            # how far `required` narrowed what this node hands on:
            # the columns of its inputs, the columns of its output
            span["cols_in"] = sum(
                len(self._cte_cache[id(c)].columns)
                for c in node.children() if id(c) in self._cte_cache
            )
            span["cols_out"] = len(out.columns)
        if isinstance(node, P.MultiJoin) and self._join_steps:
            # the order joined (relation indices), each step's estimate
            # of the rows it leaves, the capacity its left side ran at,
            # and whether the estimates changed the order
            span.update(self._join_steps)
        if isinstance(node, P.SetOp) and self._setop_info:
            # which set operation, over how many rows a side (counts the
            # host already held: null where one is still queued), the left
            # side's distinct rows and the candidate join's key columns
            span.update(self._setop_info)
            self._setop_info = None
        if fp is not None:
            # budgeter accounting (analysis/feedback.py annotations):
            # est_rows/est_live_bytes are the STATIC model's numbers,
            # actual_* what this execution measured. `est_bytes`
            # above keeps its historical meaning (realized device
            # bytes — the calibration harness pins it)
            span["node_fp"] = fp
            span["est_rows"] = getattr(node, "est_rows", None)
            span["est_live_bytes"] = getattr(
                node, "est_live_bytes", None
            )
            span["actual_rows"] = out.nrows_known
            span["actual_bytes"] = nbytes
        self.tracer.emit("op_span", **span)

    def to_arrow(self, node: P.PlanNode) -> pa.Table:
        return table_to_arrow(self.execute(node))

    # ------------------------------------------------------------------
    def _exec_scan(self, node: P.Scan) -> Table:
        # lake_version: the plan-time snapshot pin (Session._pin_lake_scans)
        # — threading it here keeps the scan on ITS statement's snapshot
        # even when another stream sharing this session has re-pinned the
        # catalog entry, and after a device-OOM recovery wiped the cache
        # lake_files: the zone-map pruned file subset
        # (Session._prune_lake_scans) — the load opens only surviving files
        with _tally.phase("scan"):
            t = self.catalog.load(
                node.table, node.columns, lake_version=node.lake_version,
                lake_files=node.lake_files,
            )
        if self.tracer is not None:
            self._scan_cols += len(t.columns)
        uk = t.unique_key
        if uk is not None:
            uk = frozenset(f"{node.alias}.{n}" for n in uk)
        return Table(
            {f"{node.alias}.{n}": c for n, c in t.columns.items()}, t.nrows,
            unique_key=uk,
        )

    def _exec_materializedscan(self, node: P.MaterializedScan) -> Table:
        if node.name == "__dual__":
            return Table({}, 1)
        if node.table is None:
            raise ExecError(f"materialized scan {node.name} not populated")
        return node.table

    def _exec_project(self, node: P.Project) -> Table:
        return self._project_table(self.execute(node.child), node.items)

    def _project_table(self, child: Table, items) -> Table:
        ev = self._evaluator(child)
        child_cols = {id(c) for c in child.columns.values()}
        cols = {}
        renames = {}  # child column name -> output name (plain Col items)
        for e, name in items:
            c = ev.eval(e)
            # plain renames share the child's Column object: ownership must
            # not cross the node boundary (the child may be cache-retained)
            cols[name] = c.disowned() if id(c) in child_cols else c
            if isinstance(e, E.Col):
                # mirror Evaluator._eval_col resolution order
                key = f"{e.table}.{e.name}" if e.table else e.name
                if key not in child.columns and e.name in child.columns:
                    key = e.name
                renames.setdefault(key, name)
        if not cols:
            return Table({}, child.nrows)
        uk = child.unique_key
        if uk is not None and all(k in renames for k in uk):
            uk = frozenset(renames[k] for k in uk)
        else:
            uk = None
        # deferred-compaction mask rides through (masked rows hold garbage
        # expression values, which stay masked)
        return Table(cols, child.nrows_lazy, live=child.live, unique_key=uk)

    def _exec_filter(self, node: P.Filter) -> Table:
        child = self.execute(node.child)
        return self._masked(
            child.narrowed(node.required),
            self._predicate_mask(child, node.predicate),
        )

    # -- fused Filter/Project pipelines -----------------------------------
    # A Pipeline node (fuse.mark_pipelines) executes its whole chain as ONE
    # jitted function over the child's device columns: no per-node
    # dispatch, no materialized intermediates, masks deferred to the
    # pipeline boundary. Executables are reused across reruns AND across
    # structurally identical queries via the session ExecutableCache
    # (keyed on stage fingerprint + dtype signature; jax keys per capacity
    # bucket underneath). Chains that cannot trace fall back to the exact
    # eager per-stage path, and the signature is pinned so the build is
    # attempted once.

    def _aot_build_args(self, session):
        """(AotCache | None, conf signature) for a FusedPipeline build:
        the session's persistent executable cache plus the engine conf
        values that change traced code and therefore join the on-disk
        entry key (engine/aotcache.py key discipline)."""
        aot = getattr(session, "aot_cache", None) if session else None
        if aot is None:
            return None, ()
        return aot, (
            str(session.conf.get("engine.fuse_agg", "on")),
            str(session.conf.get("engine.pallas_agg", "off")),
        )

    def _exec_pipeline(self, node: P.Pipeline) -> Table:
        child = self.execute(node.child)
        session = getattr(self.catalog, "session", None)
        tracer = self.tracer
        t0 = _perf() if tracer is not None else 0.0
        t0_ns = _time_ns() if tracer is not None else 0
        out = None
        fused = False
        has_agg = node.agg is not None
        # how the aggregate tail reduced: the fused body's own route, or
        # the eager aggregation, whose kernel seams say the rest
        agg_route = "eager" if has_agg else None
        if (
            session is not None
            and session.conf.get("engine.fuse", "on") != "off"
            and child.columns
            and child.cap > 0
            # backstop only — the plan rewrite already skips agg absorption
            # under a Pallas mode (Session._finish_plan), so this fires
            # solely for plans cached before conf flipped pallas_agg on:
            # the fused scatter would bypass the per-aggregate Pallas seam
            and not (
                has_agg
                and session.conf.get("engine.pallas_agg", "off") != "off"
            )
        ):
            with _tally.phase("exec-lookup"):
                fp = getattr(node, "_stage_fp", None)
                if fp is None:
                    fp = node._stage_fp = P.fingerprint(
                        P.Pipeline(
                            stages=node.stages, child=None, agg=node.agg
                        )
                    )
                sig = fuse.input_signature(child, with_stats=has_agg)
                aot, conf_sig = self._aot_build_args(session)

                def build():
                    # traces the chain through the engine's own evaluator
                    # (the seams are nothing under a jax trace); its jax
                    # stages are `compile_ms`
                    with _tally.phase("pipeline-build"):
                        if has_agg:
                            return fuse.FusedAggPipeline(
                                node.stages, node.agg, child,
                                aot=aot, fp=fp, conf_sig=conf_sig,
                            )
                        return fuse.FusedPipeline(
                            node.stages, child, aot=aot, fp=fp,
                            conf_sig=conf_sig,
                        )
                lk_ns = _time_ns() if tracer is not None else 0
                lk0 = _perf()
                with session.cache_lock:
                    entry, hit = session.exec_cache.lookup(
                        fp, sig, child.cap, build
                    )
            if tracer is not None:
                # dur_ms: the lookup and, on a miss, the build (trace,
                # lower, compile or AOT load: those have events of their own)
                tracer.emit(
                    "exec_cache", pipeline=fp[:12], bucket=child.cap,
                    hit=hit, fused=entry is not None, t0_ns=lk_ns,
                    dur_ms=round((_perf() - lk0) * 1000.0, 3),
                )
            if entry is not None:
                donate = (
                    node.donate_ok
                    and session.conf.get("engine.fuse_donate", "off")
                    == "on"
                )
                # one launch each, whatever the chain's length
                token = (
                    self.tally.enter(
                        "fused_agg_pipeline" if has_agg else "fused_pipeline"
                    )
                    if self.tally is not None else None
                )
                try:
                    out = entry.call(child, donate)
                    fused = True
                    if has_agg:
                        agg_route = entry.agg_route
                except Exception as exc:
                    if donate:
                        # the failed call may already have donated (and so
                        # invalidated) the child's input buffers — an eager
                        # retry over those would read garbage; surface the
                        # failure to the harness ladder instead
                        raise
                    # compile/runtime failure on a chain that traced
                    # abstractly: pin the signature to the eager path
                    with session.cache_lock:
                        session.exec_cache.map[(fp, sig)] = None
                    self.on_task_failure(
                        f"pipeline fuse fallback: {str(exc)[:120]}"
                    )
                finally:
                    if token is not None:
                        self.tally.leave(token)
        if out is None:
            # eager per-stage path (_apply_wrappers wants top-down order)
            out = self._apply_wrappers(child, list(reversed(node.stages)))
            if has_agg:
                out = self._aggregate_once(
                    node.agg.keys, node.agg.aggs, None, out,
                    out.row_mask(), out.nrows_known,
                )
        if tracer is not None:
            tracer.emit(
                "pipeline_span",
                stages=len(node.stages),
                fused=fused,
                agg=has_agg,
                agg_route=agg_route,
                t0_ns=t0_ns,
                dur_ms=round((_perf() - t0) * 1000.0, 3),
                rows=out.nrows_known,
            )
        return out

    def _exec_limit(self, node: P.Limit) -> Table:
        # top-k fusion: ORDER BY .. LIMIT n computes the sort order but
        # gathers only the first bucket_cap(n) sorted rows per column —
        # the full-capacity permutation gather of every output column was
        # pure waste at fact shapes (most TPC-DS queries end in exactly
        # this shape). Requires the rewrite pass's single-consumer
        # annotation (fuse.mark_pipelines sets _topk_safe) — a shared
        # Sort's full result must compute once and serve every consumer —
        # and falls back when the distributed sort engages (it returns a
        # fully packed table).
        if (
            isinstance(node.child, P.Sort)
            and getattr(node.child, "_topk_safe", False)
            and id(node.child) not in self._cte_cache
        ):
            sort = node.child
            child = self._pack_sparse(self.execute(sort.child))
            if child.nrows_known != 0:
                words, dist = self._sort_order_words(sort, child)
                if dist is None:
                    order = K.sort_by_words(words)
                    n = min(node.n, child.nrows)
                    cap = bucket_cap(max(n, 1))
                    with _tally.eager("limit"):
                        head = order[:cap]
                    return self._take(child, head, n)
                child = dist
            n = min(node.n, child.nrows)
            return self._head(child.compacted(), n, bucket_cap(max(n, 1)))
        child = self.execute(node.child).compacted()
        n = min(node.n, child.nrows)
        return self._head(child, n, bucket_cap(n))

    @staticmethod
    @_tally.seamed("limit")
    def _head(child: Table, n, cap) -> Table:
        """The first `cap` slots of every buffer of a packed table: one
        eager slice a buffer."""
        cols = {
            name: Column(
                c.data[:cap], c.dtype,
                None if c.valid is None else c.valid[:cap],
                c.dictionary, c.subset_stats(),
            )
            for name, c in child.columns.items()
        }
        return Table(cols, n)

    def _exec_sort(self, node: P.Sort) -> Table:
        child = self._pack_sparse(self.execute(node.child))
        if child.nrows_known == 0:
            return child
        words, dist = self._sort_order_words(node, child)
        if dist is not None:
            return dist
        order = self._sort_perm_route(words)
        parts = self._spill_parts_for(node)
        if parts > 1:
            # external sort: the SAME device sort order, but the output
            # gather runs in bounded windows staged through the host spill
            # pool (sorted runs) instead of materializing every column's
            # full-capacity gather at once — results are bit-identical to
            # the direct path because the permutation is identical
            out = self._spilled_take(child, order, parts, op="sort")
            if out is not None:
                return out
        return self._take(child, order, child.nrows_lazy)

    @_tally.seamed("sort_words")
    def _sort_order_words(self, node: P.Sort, child: Table):
        """(sort words, distributed-sort result|None) for a Sort node over
        its already-executed input — shared by the full sort and the
        Limit-over-Sort top-k path."""
        ev = self._evaluator(child)
        keys = []
        cols = []
        for e, asc, nf in node.keys:
            col = ev.eval(e)
            cols.append(col)
            data = col.data
            if col.dtype.is_string:
                data, _ = sort_dictionary(col)
            if col.dtype.kind == "bool":
                data = data.astype(jnp.int32)
            if nf is None:
                nf = asc  # Spark: NULLS FIRST for ASC, NULLS LAST for DESC
            keys.append((data, col.valid, asc, nf))
        words = self._sort_words(keys, cols, child.row_mask())
        dist = self._try_dist_sort(
            child, [(w, None, True, True) for w in words]
        )
        return words, dist

    # -- sort-key word encoding -------------------------------------------
    # Every ordering in the engine (ORDER BY, group-by adjacency, window
    # partition sort) is encoded into int64 *words*, most significant
    # first, and sorted by stable LSD passes over the ONE canonical kv-sort
    # kernel per input cap (K.sort_by_words). XLA:TPU sort compiles cost
    # ~10-12 s per comparator operand at fact shapes, so per-query
    # comparator kernels were the dominant cold-start cost (q34's 3-operand
    # lexsort at 4M rows alone compiled for 102 s).
    #
    # Encoding per key, in significance order: integer-like keys with a
    # known span pack as mixed-radix fields (asc: v-vmin+1, desc: vmax-v+1;
    # null first -> 0, null last -> span-1) into shared <=62-bit words;
    # floats and huge-span ints emit a 1-bit null-rank field into the
    # shared stream plus one standalone full-width word (floats via the
    # order-preserving bit transform, descending via bitwise not). A
    # leading 1-bit live field keeps dead rows last. Exact — codes are
    # monotone (and injective) per key.

    @_tally.seamed("sort_words")
    def _sort_words(self, keys, cols, live, include_live=True):
        """keys: (data, valid, ascending, nulls_first) in major->minor
        order; cols: aligned Column|None for cached bounds (None or
        stats-less columns fetch bounds in one batched device round trip).
        Returns the int64 word list for K.sort_by_words/K.group_by_words."""
        packable = [
            not jnp.issubdtype(d.dtype, jnp.floating) for d, _, _, _ in keys
        ]
        stats_list = []
        for (d, v, _, _), c, pk in zip(keys, cols, packable):
            if c is not None and c.dictionary is not None:
                # dictionary codes/ranks span [0, len) statically: no stats
                # lookup and no device fetch needed
                stats_list.append(
                    _DictStats(0, max(len(c.dictionary) - 1, 0))
                )
            else:
                stats_list.append(c.stats if c is not None else None)
        bounds = _resolve_bounds(
            [k[0] for k in keys], [k[1] for k in keys], stats_list, packable,
            live,  # dead/padded rows must not widen the spans
        )
        # The encoding compiles as ONE jitted function per (spec, shapes)
        # key (K.build_sort_words) instead of an eager op chain per query;
        # widths quantize so queries with similar key spans share the
        # compiled encoder. Standalone words: ints fold direction via
        # order-reversing bitwise not; floats stay NATIVE f64 words (this
        # TPU toolchain cannot bitcast emulated 64-bit types) with -0.0
        # normalized, nulls masked before the NaN rank, NaN in a 1-bit
        # rank field (Spark: NaN greater than +inf), direction by negation.
        spec = []
        arrays = []
        if include_live:
            spec.append(("L",))
        for (d, v, asc, nf), pk, b in zip(keys, packable, bounds):
            if nf is None:
                nf = asc
            hv = v is not None
            if d.dtype == jnp.bool_:
                d = d.astype(jnp.int32)
            if pk:
                vmin, vmax = b
                if vmax < vmin:  # empty/all-null: constant key, skip
                    continue
                span = vmax - vmin + 3  # codes 1..span-2; 0, top for NULL
                width = K.quantize_width(max(1, int(span - 1).bit_length()))
                if width <= 62:
                    spec.append(("i", width, asc, nf, hv))
                    arrays += [d, jnp.int64(vmin), jnp.int64(vmax)]
                    if hv:
                        arrays.append(v)
                    continue
                spec.append(("I", asc, nf, hv))
            else:
                spec.append(("f", asc, nf, hv))
            arrays.append(d)
            if hv:
                arrays.append(v)
        if not spec:  # every key constant: one trivial live word
            spec.append(("L",))
        return list(K.build_sort_words(tuple(spec), live, *arrays))

    @_tally.seamed("sort_words")
    def _group_words(self, active_cols, live):
        """Word encoding for group-by adjacency (equality only): the sort
        encoding with asc/nulls-first defaults is injective, so equal words
        <=> equal keys and group enumeration order == key sort order."""
        keys = []
        for c in active_cols:
            d = c.data
            if d.dtype == jnp.bool_:
                d = d.astype(jnp.int32)
            keys.append((d, c.valid, True, True))
        return self._sort_words(keys, active_cols, live)

    # -- distributed sort -------------------------------------------------
    # ORDER BY over a mesh-sharded table: range-partitioned samplesort +
    # global rank compaction over ICI (nds_tpu/parallel/dist.py:sample_sort)
    # instead of the all-gathering lexsort the generic path would lower to.
    # Default threshold derives PER DEVICE (n_dev x this): the old flat
    # 256Ki floor was a dryrun-era cap that kept the exchange paths cold at
    # every realistic bench scale — SF0.01 fact scans must already route
    # through the collective machinery so the mesh gate exercises it.
    _DIST_SORT_MIN_ROWS_PER_DEV = 2048

    def _mesh_min_rows(self, session, conf_key, per_dev, n_dev) -> int:
        """Row threshold for a mesh collective path: explicit conf wins,
        else n_dev x per-device default (scale-out keeps the single-device
        crossover point instead of inheriting a flat pod-sized floor)."""
        v = session.conf.get(conf_key)
        if v is not None:
            try:
                return int(v)
            except (TypeError, ValueError):
                pass
        return int(n_dev) * int(per_dev)

    def _emit_exchange(self, op, n_dev, bytes_moved, counts, retries,
                       dur_ms=None, node_fp=None):
        """One `exchange` trace event per executed collective exchange:
        bytes moved over the interconnect (padded-capacity measure, both
        all_to_all passes), partition (device) count, the received-row
        skew ratio (max device / mean; 1.0 = perfectly balanced), how
        many capacity-overflow retries the step burned, the measured wall
        of the whole exchange step (`dur_ms`, retries included — the
        critical-path profiler's exchange-wait cause), and the per-device
        received-row counts (`per_device` — what names the straggler
        device).

        With a `node_fp` (plan_feedback record/on) the measured skew also
        records into the session FeedbackStore — the seed the NEXT
        execution's capacity guess consumes instead of the retry ladder —
        even when the session is untraced."""
        if self.tracer is None and node_fp is None:
            return
        c = np.asarray(host_read("exchange", counts), dtype=np.float64)
        total = float(c.sum())
        skew = 1.0
        if total > 0 and c.size:
            skew = float(c.max() / (total / c.size))
        if node_fp is not None:
            session = getattr(self.catalog, "session", None)
            store = getattr(session, "feedback_store", None)
            if store is not None:
                with session.cache_lock:
                    store.record_skew(node_fp, skew, retries=int(retries))
        if self.tracer is None:
            return
        self.tracer.emit(
            "exchange", op=op, partitions=int(n_dev),
            bytes_moved=int(bytes_moved), skew=round(skew, 3),
            retries=int(retries),
            per_device=[int(x) for x in c],
            **({"dur_ms": round(float(dur_ms), 3)}
               if dur_ms is not None else {}),
        )

    def _feedback_skew_seed(self, node_fp, n_dev) -> int:
        """Integer capacity multiplier from a recorded exchange skew for
        this plan node (plan_feedback=on), clamped to the mesh width (a
        single destination can never need more than n_dev x the balanced
        per-bucket share). 1 = no recorded skew worth seeding."""
        if node_fp is None:
            return 1
        session = getattr(self.catalog, "session", None)
        store = getattr(session, "feedback_store", None)
        if store is None:
            return 1
        # mesh-only cold path (see _try_exchange_join)
        # nds-lint: disable=local-import
        from ..analysis.feedback import resolve_feedback_mode

        if resolve_feedback_mode(session.conf) != "on":
            return 1
        with session.cache_lock:
            rec = store.lookup(node_fp)
        skew = float(((rec or {}).get("skew") or {}).get("max") or 0.0)
        if skew <= 1.25:
            return 1
        return int(min(math.ceil(skew), n_dev))

    def _try_dist_sort(self, child: Table, keys):
        if not keys:
            # every sort key was dropped by the packer (all-null/empty with
            # no stats): nothing to route on, use the local sort path
            return None
        session = getattr(self.catalog, "session", None)
        mesh = getattr(session, "mesh", None)
        if mesh is None:
            return None
        n_dev = mesh.devices.size
        min_rows = self._mesh_min_rows(
            session, "engine.dist_sort_min_rows",
            self._DIST_SORT_MIN_ROWS_PER_DEV, n_dev,
        )
        if child.nrows < min_rows:
            return None
        cap = child.cap
        if cap % n_dev or cap // n_dev == 0:
            return None
        # mesh-only cold path: keeps jax sharding/collective machinery out
        # of single-chip startup; reached once per distributed sort
        # nds-lint: disable=local-import
        from ..parallel.dist import get_sample_sort

        # transformed lexsort keys (major->minor), via the same fold as
        # K.sort_indices so the two orderings cannot diverge
        tkeys = []
        route = None
        for data, valid, asc, nf in keys:
            folded = K.fold_sort_key(data, valid, asc, nf)
            tkeys.extend(folded)
            if route is None:
                # routing value: monotone in (null_rank, value) of the primary
                # key — nulls fold to the dtype extreme so they colocate
                d = folded[-1]
                if valid is None:
                    route = d
                else:
                    if jnp.issubdtype(d.dtype, jnp.floating):
                        ext = jnp.asarray(-jnp.inf if nf else jnp.inf, d.dtype)
                    else:
                        info = jnp.iinfo(d.dtype)
                        ext = jnp.asarray(info.min if nf else info.max, d.dtype)
                    route = jnp.where(valid, d, ext)
        payload = []
        has_valid = []
        for c in child.columns.values():
            payload.append(c.data)
            has_valid.append(c.valid is not None)
        for c in child.columns.values():
            if c.valid is not None:
                payload.append(c.valid)
        live = child.row_mask()
        local_rows = cap // n_dev
        cap_route = bucket_cap(max(1, 2 * local_rows // n_dev))
        retries = 0
        ex_t0 = _perf()
        while True:
            fn = get_sample_sort(mesh, len(tkeys), len(payload), cap_route)
            out = fn(route, live, *tkeys, *payload)
            overflow = int(host_read("exchange", out[-1]))
            if overflow == 0:
                break
            if cap_route >= local_rows:  # can't overflow at this cap; bug guard
                return None
            retries += 1
            self.on_task_failure(
                f"task retry: distributed sort bucket overflow "
                f"({overflow} rows); doubling route capacity"
            )
            cap_route = min(cap_route * 2, local_rows)
        per_row = sum(int(a.dtype.itemsize) for a in tkeys + payload) + 1
        self._emit_exchange(
            "sort", n_dev,
            per_row * (n_dev * n_dev * cap_route + n_dev * cap),
            out[-2], retries, dur_ms=(_perf() - ex_t0) * 1000.0,
        )
        cols_out = out[1:1 + len(child.columns)]
        valids_out = list(out[1 + len(child.columns):-2])
        cols = {}
        vi = 0
        for i, (name, c) in enumerate(child.columns.items()):
            valid = None
            if has_valid[i]:
                valid = valids_out[vi]
                vi += 1
            cols[name] = Column(
                cols_out[i], c.dtype, valid, c.dictionary, c.subset_stats()
            )
        return Table(cols, child.nrows)

    def _exec_distinct(self, node: P.Distinct) -> Table:
        child = self.execute(node.child)
        if child.nrows_known == 0:
            return child
        return self._distinct_table(
            child, spill_parts=self._spill_parts_for(node)
        )

    # ------------------------------------------------------------------
    def _exec_setop(self, node: P.SetOp) -> Table:
        left = self.execute(node.left)
        right = self.execute(node.right)
        if node.op == "union_all":
            out = self._concat(left, right)
            self._note_setop(node, left, right)
            return out
        if node.op == "union":
            out = self._distinct_table(
                self._concat(left, right),
                spill_parts=self._spill_parts_for(node),
            )
            self._note_setop(node, left, right)
            return out
        # intersect / except: set semantics over whole rows
        dl = self._distinct_table(left)
        names = list(dl.columns)
        rnames = list(right.columns)
        lkeys, lvalids, rkeys, rvalids = [], [], [], []
        for ln, rn in zip(names, rnames):
            for lk, rk in zip(
                *self._join_key_pair(dl.columns[ln], right.columns[rn])
            ):
                lkeys.append(lk.data)
                lvalids.append(lk.valid)
                rkeys.append(rk.data)
                rvalids.append(rk.valid)
        # NULLs compare equal in set ops: fold validity into the key and add
        # one null-flag key per column on BOTH sides (sides can differ in
        # nullability; the flag lists must stay aligned)
        keys_l, keys_r = [], []
        for d, v in zip(lkeys, lvalids):
            keys_l.append(
                jnp.where(v, d, jnp.zeros((), d.dtype)) if v is not None else d
            )
        for d, v in zip(rkeys, rvalids):
            keys_r.append(
                jnp.where(v, d, jnp.zeros((), d.dtype)) if v is not None else d
            )
        zl = jnp.zeros(dl.cap, bool)
        zr = jnp.zeros(right.cap, bool)
        for lv, rv in zip(lvalids, rvalids):
            keys_l.append(~lv if lv is not None else zl)
            keys_r.append(~rv if rv is not None else zr)
        li, ri, pl, _ = K.join_candidates(
            keys_l, [None] * len(keys_l), dl.row_mask(),
            keys_r, [None] * len(keys_r), right.row_mask(),
        )
        ok = K.verify_pairs(
            li, ri, pl,
            keys_l, [None] * len(keys_l), dl.row_mask(),
            keys_r, [None] * len(keys_r), right.row_mask(),
        )
        present = K.matched_mask(li, ok, dl.cap)
        if node.op == "intersect":
            mask = present & dl.row_mask()
        else:
            mask = ~present & dl.row_mask()
        self._note_setop(node, left, right, dl, len(keys_l))
        return self._masked(dl, mask)

    def _note_setop(self, node, left, right, distinct=None, key_words=None):
        """The SetOp span's own fields, taken as the operation ends (its
        inputs' spans have been emitted by then) and only where a tracer
        will read them. `nrows_known`, as the span's `rows`: a count still
        queued on the device stays null, never a sync."""
        if self.tracer is None:
            return
        self._setop_info = dict(
            op=node.op,
            left_rows=left.nrows_known,
            right_rows=right.nrows_known,
            distinct_rows=None if distinct is None else distinct.nrows_known,
            key_words=key_words,
        )

    # ------------------------------------------------------------------
    def _exec_join(self, node: P.Join) -> Table:
        left = self.execute(node.left)
        right = self.execute(node.right)
        return self._join(
            left, right, node.kind, node.left_keys, node.right_keys,
            node.residual, node.mark_name,
            spill_parts=self._spill_parts_for(node),
            node_fp=getattr(node, "node_fp", None),
            out=node.required,
        )

    def _exec_multijoin(self, node: P.MultiJoin) -> Table:
        tables = self._execute_relations_batched(node.relations)
        self._join_steps = None
        # join-order replay ACROSS statements: the greedy cost scan reads
        # joined-intermediate row counts, which is a blocking device->host
        # sync per join step after the first.
        # Steady-state reruns and repeated stream queries replay the
        # recorded order instead (same fingerprint => same query text and
        # literals, so the recorded order stays the right one; any order
        # is correct regardless). The round-5 join-graph optimizer cost
        # q3 one such sync per steady run; test_join_order_replay_memo holds
        # the replay in place. On the chip a replayed query7 still waits
        # for five `nrows` reads an execution (PERF.md, section 5): the
        # `_pack_sparse` at the head of each join, not the order's.
        trace = None
        session = getattr(self.catalog, "session", None)
        if (
            session is not None
            and session.conf.get("engine.join_order_cache", "on") != "off"
        ):
            with _tally.phase("join-plan"), session.cache_lock:
                trace = session.join_order_cache.setdefault(
                    self._fp(node), {}
                )
        return self._multijoin_over_tables(
            tables, node.edges, trace=trace,
            spill_parts=self._spill_parts_for(node),
            node_fp=getattr(node, "node_fp", None),
            required=node.required,
        )

    def _multijoin_over_tables(self, tables, edges, trace=None,
                               spill_parts=0, node_fp=None,
                               required=None) -> Table:
        """Greedy N-way inner join over already-executed relation tables
        (shared by _exec_multijoin and the blocked union-aggregation path,
        which re-joins each union window against the other relations).
        `trace`: optional dict; the first call records its join-order
        decisions into it and later calls replay them, skipping the greedy
        cost scan — whose current[g].nrows reads are blocking device->host
        syncs that would otherwise run once per window per join step.
        `required`: the MultiJoin's (the names read above it; None: all)."""
        n = len(tables)
        if n == 1:
            return tables[0].narrowed(required)
        # adjacency: edge list by relation index
        edges = list(edges)
        merged = list(range(n))  # union-find-ish: relation -> group id

        def group(i):
            while merged[i] != i:
                i = merged[i]
            return i

        current = {i: tables[i] for i in range(n)}

        return self._multijoin_greedy(current, edges, merged, group, n, trace,
                                      spill_parts, node_fp=node_fp,
                                      required=required)

    def _execute_relations_batched(self, relations):
        """Execute a MultiJoin's relations and materialize their live
        counts with ONE device->host round trip.

        Filters produce deferred-compaction tables whose counts are queued
        asynchronously; the greedy join-order heuristic below needs host
        integers, so all still-lazy counts batch into a single
        jax.device_get instead of one blocking read per relation."""
        tables = [self.execute(r) for r in relations]
        lazy = [t for t in tables if t.nrows_known is None]
        if lazy:
            counts = host_read("nrows", [t.nrows_lazy for t in lazy])
            for t, v in zip(lazy, counts):
                t._nrows = int(v)
        return tables

    def _multijoin_greedy(self, current, edges, merged, group, n, trace=None,
                          spill_parts=0, node_fp=None, required=None):
        # greedy: repeatedly take the connecting edge whose join is
        # estimated to leave the fewest rows (`_est_join_rows`: the dimension
        # that keeps 1% of a fact table goes before the one that keeps all
        # of it, however small that one is, and `_pack_sparse` then packs
        # the fact side once for every later step), execute that join. An
        # edge without an estimate ranks by the sum of its inputs' live
        # rows, which also breaks ties, then the edge's index: without
        # stats that is the whole rule. When `trace`
        # carries recorded steps, replay them instead (identical relation
        # sets join in the same order, and replay never reads .nrows — the
        # blocked union path joins every window with zero count syncs).
        def read_after(rest):
            # what is read of a step's output: what is read above the
            # node, and the keys of the edges not yet consumed
            if required is None:
                return None
            return frozenset(required).union(
                *(E.col_refs(e) for _, _, le, re_ in rest for e in (le, re_))
            )

        replay = trace is not None and "steps" in trace
        steps = trace["steps"] if replay else []
        # beside the steps, for the node's span: each step's estimate (None:
        # it had none) and whether any step left the smallest-inputs order
        ests = trace["step_est_rows"] if replay else []
        reordered = trace["reordered"] if replay else 0
        left_caps = []
        step_i = 0
        while True:
            # the phase `join-plan` is the order's choosing or replay and
            # the step's keys; the step's join is not in it
            with _tally.phase("join-plan"):
                groups = {group(i) for i in range(n)}
                if len(groups) == 1:
                    break
                if replay:
                    kind, gi, gj = steps[step_i]
                    step_i += 1
                else:
                    best = smallest = None
                    for k, (i, j, le, re_) in enumerate(edges):
                        gi, gj = group(i), group(j)
                        if gi == gj:
                            continue
                        cost = current[gi].nrows + current[gj].nrows
                        est = _est_join_rows(
                            current[gi], current[gj], le, re_
                        )
                        rank = (cost if est is None else est, cost, k)
                        if best is None or rank < best[0]:
                            best = (rank, gi, gj, est)
                        if smallest is None or (cost, k) < smallest:
                            smallest = (cost, k)
                    if best is None:
                        kind, gi, gj = "cross", *sorted(
                            groups, key=lambda g: current[g].nrows
                        )[:2]
                        ests.append(None)
                    else:
                        kind, gi, gj = "edge", best[1], best[2]
                        ests.append(
                            None if best[3] is None else int(best[3])
                        )
                        if best[0][1:] != smallest:
                            reordered = 1
                    steps.append((kind, gi, gj))
                if kind != "cross":
                    # gather ALL edges connecting these two groups as one
                    # multi-key join
                    lkeys, rkeys = [], []
                    rest = []
                    for (i, j, le, re_) in edges:
                        if {group(i), group(j)} == {gi, gj}:
                            if group(i) == gi:
                                lkeys.append(le)
                                rkeys.append(re_)
                            else:
                                lkeys.append(re_)
                                rkeys.append(le)
                        else:
                            rest.append((i, j, le, re_))
                    edges = rest
            if kind == "cross":
                # disconnected components: cross join smallest two groups
                left_caps.append(current[gi].cap)
                joined = self._join(
                    current[gi], current[gj], "cross", [], [], None,
                    out=read_after(edges),
                )
                merged[gj] = gi
                current[gi] = joined
                continue
            joined = self._join(
                current[gi], current[gj], "inner", lkeys, rkeys, None,
                spill_parts=spill_parts, node_fp=node_fp,
                out=read_after(edges),
            )
            left_caps.append(self._left_cap)
            merged[gj] = gi
            current[gi] = joined
        if trace is not None and not replay:
            # `trace` may be a join_order_cache entry (steady replays read
            # it from other statements' threads) or a blocked-union
            # context's private memo; both callers guarantee a session
            with self.catalog.session.cache_lock:
                trace["step_est_rows"] = ests
                trace["reordered"] = reordered
                trace["steps"] = steps
        if self.tracer is not None:
            order = []
            for _, gi, gj in steps:
                order += [g for g in (gi, gj) if g not in order]
            self._join_steps = dict(
                join_order=order, step_est_rows=ests, left_caps=left_caps,
                reordered=reordered,
            )
        return current[group(0)]

    # ------------------------------------------------------------------
    def _pack_sparse(self, t: Table) -> Table:
        """Compact a deferred-compaction table whose live fraction is small:
        sort/hash consumers scale with CAP, so a 5k-of-131k masked build
        side would pay 26x its packed cost. The count is usually already
        materialized (or long since queued), so this rarely blocks."""
        if t.live is None:
            return t
        if t.nrows < max(t.cap // 8, 1024):
            return t.compacted()
        return t

    @staticmethod
    def _read_with(out, residual):
        """`out` (the names read of a join's result; None: all) and what
        the join's own `residual` reads of the same table."""
        if out is None or residual is None:
            return out
        return out | E.col_refs(residual)

    def _join(self, left, right, kind, left_keys, right_keys, residual,
              mark_name=None, spill_parts=0, node_fp=None, out=None):
        """`out`: the names read of the result (None: all). The join hands
        on those columns and no others: a key whose edge is consumed or a
        filter column whose filter is applied is not gathered (one column
        stays to carry the rows where none is named, `narrow_columns`).
        Keys and the residual are evaluated on what they name.

        The narrowing is decided here alone, on the way in and on the way
        out; inside `_join_body` a site that gathers passes `out` to
        `_sides` so as not to fetch what this drops."""
        if out is None:
            return self._join_body(
                left, right, kind, left_keys, right_keys, residual,
                mark_name, spill_parts, node_fp, None,
            )
        out = frozenset(out)
        # what neither the reader nor this join's own expressions name
        # falls away before anything packs or gathers it
        need = self._read_with(out, residual).union(
            *(E.col_refs(e) for e in (*left_keys, *right_keys))
        )
        joined = self._join_body(
            left.narrowed(need), right.narrowed(need), kind,
            left_keys, right_keys, residual, mark_name, spill_parts,
            node_fp, out,
        )
        return joined.narrowed(out if mark_name is None else out | {mark_name})

    def _join_body(self, left, right, kind, left_keys, right_keys, residual,
                   mark_name, spill_parts, node_fp, out):
        if kind == "cross":
            return self._cross_join(left, right)
        left = self._pack_sparse(left)
        right = self._pack_sparse(right)
        # the capacity this join's probe or sort runs at, for the span of
        # the MultiJoin that asked for it (`left_caps`)
        self._left_cap = left.cap
        if kind == "right":
            # swap before any matching so the residual is preserved
            return self._join_body(
                right, left, "left", right_keys, left_keys, residual,
                mark_name, spill_parts, node_fp, out,
            )
        lev = self._evaluator(left)
        rev = self._evaluator(right)
        lcols = [lev.eval(e) for e in left_keys]
        rcols = [rev.eval(e) for e in right_keys]
        lk, lv, rk, rv = [], [], [], []
        aligned = []  # (left Column, right Column) pairs, dtype-unified
        for a, b in zip(lcols, rcols):
            for ca, cb in zip(*self._join_key_pair(a, b)):
                aligned.append((ca, cb))
                lk.append(ca.data)
                lv.append(ca.valid)
                rk.append(cb.data)
                rv.append(cb.valid)
        llive = left.row_mask()
        rlive = right.row_mask()
        fast = self._try_dense_join(
            left, right, kind, lcols, rcols, lk, lv, rk, rv, llive, rlive,
            residual, mark_name, out,
        )
        if fast is not None:
            return fast
        fast = self._try_exchange_join(
            left, right, kind, left_keys, right_keys,
            lk, lv, rk, rv, llive, rlive, residual, node_fp=node_fp,
            out=out,
        )
        if fast is not None:
            return fast
        fast = self._try_packed_join(
            left, right, kind, aligned, right_keys, llive, rlive, residual,
            mark_name, out,
        )
        if fast is not None:
            return fast
        if spill_parts > 1 and kind in ("inner", "left"):
            # out-of-core tier: the generic sort join's pair expansion +
            # full-width pair-table gathers are THE additive-HBM shape of
            # build-side-too-big joins; hash-partition both sides, join
            # partition pairs one at a time (probe re-scanned per
            # partition) and stage each partition's output in the host
            # spill pool instead of accumulating it on device
            return self._spilled_join(
                left, right, kind, left_keys, right_keys, residual,
                lk, lv, llive, rk, rv, rlive, spill_parts, out=out,
            )
        li, ri, pl, total = K.join_candidates(lk, lv, llive, rk, rv, rlive)
        ok = K.verify_pairs(li, ri, pl, lk, lv, llive, rk, rv, rlive)

        if kind in ("semi", "anti", "mark"):
            if residual is not None:
                ok = self._apply_residual(ok, li, ri, left, right, residual)
            present = K.matched_mask(li, ok, left.cap)
            if kind == "mark":
                return self._mark_output(left, mark_name, present)
            mask = (present if kind == "semi" else ~present) & llive
            return self._masked(left, mask)

        count = K.mask_count(ok)
        out_cap = bucket_cap(max(count, 1))
        sel = K.compact_indices(ok, out_cap)
        pli, pri = K.take_arrays((li, ri), sel)
        if residual is not None:
            # build pair table first, filter, recompact. An outer join's
            # pair table is read by the residual alone
            pair = self._pair_table(
                left, right, pli, pri, count,
                out=self._read_with(out, residual) if kind == "inner"
                else E.col_refs(residual),
            )
            pmask = self._predicate_mask(pair, residual)
            if kind == "inner":
                return self._masked(pair, pmask)
            # outer joins: surviving pairs only count as matches. Scatter with
            # max, not set: sel's padding duplicates index 0 and a plain set
            # could clobber candidate 0's True with a padded False.
            ok2 = jnp.zeros(ok.shape, bool).at[sel].max(pmask)
            ok = ok & ok2
            count = K.mask_count(ok)
            out_cap = bucket_cap(max(count, 1))
            sel = K.compact_indices(ok, out_cap)
            pli, pri = K.take_arrays((li, ri), sel)

        if kind == "inner":
            return self._pair_table(left, right, pli, pri, count, out=out)

        if kind == "left":
            with _tally.eager("join"):
                present = K.matched_mask(li, ok, left.cap)
                unmatched = ~present & llive
                n_un = K.mask_count(unmatched)
                total_rows = count + n_un
                cap2 = bucket_cap(max(total_rows, 1))
                un_idx = K.compact_indices(unmatched, bucket_cap(max(n_un, 1)))
                all_li = jnp.concatenate([pli[:count] if count else pli[:0], un_idx[:n_un]])
                all_li = jnp.pad(all_li, (0, cap2 - all_li.shape[0]))
                all_ri = jnp.concatenate(
                    [pri[:count] if count else pri[:0], jnp.zeros(n_un, jnp.int32)]
                )
                all_ri = jnp.pad(all_ri, (0, cap2 - all_ri.shape[0]))
                rkeep = jnp.arange(cap2) < count  # right side null for appended rows
                return self._pair_table(
                    left, right, all_li, all_ri, total_rows, rkeep, out=out
                )

        if kind == "full":
            with _tally.eager("join"):
                lpresent = K.matched_mask(li, ok, left.cap)
                rpresent = K.matched_mask(ri, ok, right.cap)
                lun = ~lpresent & llive
                run = ~rpresent & rlive
                n_lu = K.mask_count(lun)
                n_ru = K.mask_count(run)
                total_rows = count + n_lu + n_ru
                cap2 = bucket_cap(max(total_rows, 1))
                lu_idx = K.compact_indices(lun, bucket_cap(max(n_lu, 1)))[:n_lu]
                ru_idx = K.compact_indices(run, bucket_cap(max(n_ru, 1)))[:n_ru]
                all_li = jnp.concatenate(
                    [pli[:count], lu_idx, jnp.zeros(n_ru, jnp.int32)]
                )
                all_ri = jnp.concatenate(
                    [pri[:count], jnp.zeros(n_lu, jnp.int32), ru_idx]
                )
                all_li = jnp.pad(all_li, (0, cap2 - all_li.shape[0]))
                all_ri = jnp.pad(all_ri, (0, cap2 - all_ri.shape[0]))
                pos = jnp.arange(cap2)
                rkeep = (pos < count) | (pos >= count + n_lu)
                lkeep = pos < count + n_lu
                return self._pair_table(
                    left, right, all_li, all_ri, total_rows, rkeep, lkeep, out
                )
        raise ExecError(f"join kind {kind}")

    # -- dense-domain star-join fast path --------------------------------
    # TPC-DS fact->dim joins hit this: single int key whose build-side
    # domain is dense (surrogate keys). Probes are elementwise gathers, so
    # the fact side never sorts, and under a mesh the probe stays local per
    # chip (build side replicated). Falls back to the sort join otherwise.
    # Plan choice is driven purely by catalog-load ColStats — zero device
    # round-trips here (the round-2 per-join masked_min_max/counts.max()
    # syncs were the 2x single-chip regression).
    _DENSE_MAX_DOMAIN = 1 << 22

    def _try_dense_join(
        self, left, right, kind, lcols, rcols, lk, lv, rk, rv, llive, rlive,
        residual, mark_name, out=None,
    ):
        if len(lk) != 1:
            return None
        if kind not in ("inner", "left", "semi", "anti", "mark"):
            return None
        if kind in ("semi", "anti", "mark") and residual is not None:
            return None
        if kind == "left" and residual is not None:
            return None
        # int-like keys on both sides only: stats exist for these alone, and
        # the gate keeps float/decimal keys (value-changing casts) off the
        # dense path entirely
        for c in (lcols[0], rcols[0]):
            if c.dtype.kind not in ("int32", "int64", "date"):
                return None
        rst = rcols[0].stats
        if rst is None:
            return None
        if kind in ("inner", "left") and not rst.unique:
            # inner/left must not expand output per probe row; without a
            # uniqueness guarantee from base-table stats, use the sort join
            return None
        rmin, rmax = rst.vmin, rst.vmax
        domain = rmax - rmin + 1
        # bound the lookup table by the BASE table's size (bounds are base-
        # table-wide even when the build side is already filtered down)
        if domain > min(
            self._DENSE_MAX_DOMAIN, max(1 << 14, 8 * max(rst.base_rows, right.cap))
        ):
            return None
        with _tally.eager("join"):
            # the casts and validity masks between the build and the probe
            rnn = K._all_valid([rv[0]], rlive)
            rkey = rk[0].astype(jnp.int64)
            table_cap = bucket_cap(domain)
            rowid1 = self._dense_build_route(rkey, rnn, rmin, table_cap)
            lnn = K._all_valid([lv[0]], llive)
            matched, ri = K.dense_probe(
                lk[0].astype(jnp.int64), lnn, rmin, rowid1, table_cap
            )
        return self._augment_join_output(
            left, right, kind, matched, ri, llive, residual, mark_name, out
        )

    @staticmethod
    def _sides(left, right, out):
        """The two sides as a join's output takes them when its reader
        names `out` (None: all): views of the named columns; where it
        names none, one left column to carry the rows."""
        if out is None:
            return left, right
        lnames = [n for n in left.columns if n in out]
        rnames = [n for n in right.columns if n in out]
        if not lnames and not rnames:
            lnames = narrow_columns(left.columns, out)
        return left.select(lnames), right.select(rnames)

    @staticmethod
    def _mark_output(left, mark_name, present):
        """A mark join's output: the left columns, by reference, and the
        "has a match" column."""
        out_cols = {n: c.disowned() for n, c in left.columns.items()}
        out_cols[mark_name] = Column(present, BOOL)
        return Table(
            out_cols, left.nrows_lazy, live=left.live,
            unique_key=left.unique_key,
        )

    @_tally.seamed("join")
    def _augment_join_output(
        self, left, right, kind, matched, ri, llive, residual, mark_name,
        out=None,
    ):
        """Left-aligned join output for probe-style paths (dense, packed):
        matched rows live in place, the right columns that `out` names
        gathered alongside by `ri`, which both probes hand over with row 0
        where unmatched — no count sync, no compaction gathers."""
        if kind in ("semi", "anti", "mark"):
            if kind == "mark":
                return self._mark_output(left, mark_name, matched)
            mask = (matched if kind == "semi" else ~matched) & llive
            return self._masked(left, mask)
        left, right = self._sides(
            left, right, self._read_with(out, residual)
        )
        if kind == "inner":
            # LEFT columns pass through by reference and are DISOWNED: the
            # left table may be a CTE/plan-cache-retained result (e.g. the
            # first relation of a MultiJoin), and a passthrough that kept
            # owned=True would let a downstream donating pipeline free
            # buffers that cached table still reads. Right-side gathers
            # are fresh buffers owned by this output alone.
            out_cols = {n: c.disowned() for n, c in left.columns.items()}
            out_cols.update(gather_columns(
                right.columns, ri, stats=Column.gather_stats, owned=True,
            ))
            pair = Table(
                out_cols, jnp.sum(matched, dtype=jnp.int32),
                live=matched, unique_key=left.unique_key,
            )
            if residual is not None:
                # pair is a function-local transient: its freshly minted
                # right-side gathers stay owned through the masked view
                return self._masked(
                    pair, self._predicate_mask(pair, residual),
                    transient=True,
                )
            return pair
        # left join: left-aligned output, unmatched rows null on the right
        out_cols = {n: c.disowned() for n, c in left.columns.items()}
        out_cols.update(gather_columns(
            right.columns, ri, matched, stats=Column.gather_stats,
        ))
        return Table(
            out_cols, left.nrows_lazy, live=left.live,
            unique_key=left.unique_key,
        )

    # -- packed-word sort-lookup join ------------------------------------
    # Exact int64 packing of the (possibly composite) join key using host-
    # known bounds (ColStats riding on columns, dictionary sizes for
    # strings): collision-free by construction, so membership needs no
    # verification and no candidate expansion. semi/anti/mark become a
    # sort + lookup regardless of right-side multiplicity; inner/left take
    # the same left-aligned augment output as the dense path when the
    # right side is known-unique on the join key from plan metadata
    # (Table.unique_key, set by group-by/distinct outputs). Zero device
    # syncs either way. The cuDF analogue is the mixed-join distinct-hash-
    # join split; this is its sort-based TPU shape.

    def _pack_key_words(self, aligned):
        """Exact int64 word per side for aligned join-key Column pairs, or
        None when bounds are unknown or exceed 62 bits (the packing itself
        is K.pack_key_words, shared with the catalog's PK verification).
        Nulls never match anyway — masked by not-null liveness — but the
        dedicated 0 slot keeps dead-row words in range."""
        bounds = []
        for ca, cb in aligned:
            if ca.dtype.is_string and cb.dtype.is_string:
                if ca.dictionary is None or cb.dictionary is None:
                    return None
                if ca.dictionary is not cb.dictionary:
                    return None  # _join_key_pair unifies; anything else bails
                bounds.append((0, max(len(ca.dictionary) - 1, 0)))
            elif ca.dtype.kind in ("int32", "int64", "date") and cb.dtype.kind in (
                "int32", "int64", "date",
            ):
                sa, sb = ca.subset_stats(), cb.subset_stats()
                if sa is None or sb is None:
                    return None
                bounds.append(
                    (min(sa.vmin, sb.vmin), max(sa.vmax, sb.vmax))
                )
            else:
                return None
        return K.pack_key_words(
            [
                [(ca.data, ca.valid) for ca, _ in aligned],
                [(cb.data, cb.valid) for _, cb in aligned],
            ],
            bounds,
        )

    def _try_packed_join(
        self, left, right, kind, aligned, right_keys, llive, rlive,
        residual, mark_name, out=None,
    ):
        if not aligned:
            return None
        if kind not in ("inner", "left", "semi", "anti", "mark"):
            return None
        if kind in ("semi", "anti", "mark", "left") and residual is not None:
            return None
        if kind in ("inner", "left"):
            # the augment output keeps one row per left row, so the right
            # side must be known-unique on the join key (plan metadata from
            # group-by/distinct); duplicated right keys are the general
            # sort join's business. Checked from metadata, never probed at
            # runtime — a wasted sort + sync on the fallback path costs
            # more than the fast path saves.
            uk = right.unique_key
            if uk is None or not uk <= _plain_col_names(right_keys, right):
                return None
        words = self._pack_key_words(aligned)
        if words is None:
            return None
        lwords, rwords = words
        lnn = K._all_valid([c.valid for c, _ in aligned], llive)
        rnn = K._all_valid([c.valid for _, c in aligned], rlive)
        found, ri = K.member_lookup(lwords, lnn, rwords, rnn)
        return self._augment_join_output(
            left, right, kind, found, ri, llive, residual, mark_name, out
        )

    # -- distributed fact-fact hash join ---------------------------------
    # When both join inputs are large under a mesh, neither fits the
    # dense/replicated star path; hash-partition both sides over ICI with
    # all_to_all and join each partition locally (the reference's Spark
    # shuffle join, rebuilt on XLA collectives: nds_tpu/parallel/dist.py).
    # Capacity overflows retry with doubled caps and emit a task-failure
    # event, so the harness reports CompletedWithTaskFailures; an overflow
    # that persists past the retries (single-key-scale skew a hash
    # partitioning cannot split) tiers through the PR-9 host spill pool
    # instead of falling back to the all-gathering sort join. Default
    # threshold derives PER DEVICE — see _DIST_SORT_MIN_ROWS_PER_DEV.
    _EXCHANGE_MIN_ROWS_PER_DEV = 256
    _EXCHANGE_MAX_ATTEMPTS = 5

    def _try_exchange_join(
        self, left, right, kind, left_keys, right_keys,
        lk, lv, rk, rv, llive, rlive, residual, node_fp=None, out=None,
    ):
        mesh = getattr(self.catalog, "session", None)
        mesh = getattr(mesh, "mesh", None)
        if mesh is None or kind not in ("inner", "left"):
            return None
        if kind == "left" and residual is not None:
            # a residual LEFT needs the direct path's match-after-filter
            # recount; decline rather than re-derive it over the exchange
            return None
        if getattr(self, "_exchange_disabled", False):
            # inside a spilled-join partition loop: those partitions exist
            # because an exchange already overflowed — re-entering the
            # exchange per partition could recurse under single-key skew
            return None
        session = self.catalog.session
        n_dev = mesh.devices.size
        min_rows = self._mesh_min_rows(
            session, "engine.exchange_min_rows",
            self._EXCHANGE_MIN_ROWS_PER_DEV, n_dev,
        )
        if left.nrows < min_rows or right.nrows < min_rows:
            return None
        if left.cap % n_dev or right.cap % n_dev:
            return None
        # mesh-only cold path (see _try_dist_sort)
        # nds-lint: disable=local-import
        from ..parallel.dist import get_exchange_hash_join

        lnn = K._all_valid(lv, llive)
        rnn = K._all_valid(rv, rlive)
        lh = K.hash_columns(lk, lv)
        rh = K.hash_columns(rk, rv)
        whole = (left, right)  # the spill tier evaluates the keys again
        # the keys ship as lk / rk: of the columns, only what is read of
        # the result (or by the residual) crosses the interconnect
        shipped = self._read_with(out, residual)
        left, right = left.narrowed(shipped), right.narrowed(shipped)

        def ship(table):
            # data buffers for every column, then ONLY the real validity
            # masks — null-free columns don't pay for an all-True mask
            # through the two all_to_all exchanges
            datas = [c.data for c in table.columns.values()]
            masks = [
                c.valid for c in table.columns.values() if c.valid is not None
            ]
            return datas, masks

        l_datas, l_masks = ship(left)
        r_datas, r_masks = ship(right)
        l_ship = l_datas + l_masks
        r_ship = r_datas + r_masks
        n_lc = len(l_ship)
        n_rc = len(r_ship)
        # per-(source, destination) bucket: each device's shard holds
        # ~nrows/n_dev rows spread over n_dev destinations, so balanced
        # sizing is 2*nrows/n_dev^2 — post-exchange each device then holds
        # ~2x its SHARD (n_dev * cap), not 2x the global table; skew is
        # covered by the overflow-retry doubling below
        cap_l = bucket_cap(max(1, (2 * left.nrows) // (n_dev * n_dev)))
        cap_r = bucket_cap(max(1, (2 * right.nrows) // (n_dev * n_dev)))
        pair_cap = bucket_cap(
            max(1, 2 * max(left.nrows, right.nrows) // n_dev)
        )
        # feedback skew seeding (analysis/feedback.py, plan_feedback=on):
        # a recorded received-row skew for THIS plan node scales the
        # balanced capacity guess up front, so a known-hot key fits on
        # attempt 1 instead of rediscovering the imbalance through the
        # overflow-retry doubling ladder below
        seed = self._feedback_skew_seed(node_fp, n_dev)
        if seed > 1:
            cap_l = bucket_cap(cap_l * seed)
            cap_r = bucket_cap(cap_r * seed)
            pair_cap = bucket_cap(pair_cap * seed)
        retries = 0
        rest = None
        used_l, used_r = cap_l, cap_r  # caps the LAST attempt shipped with
        ex_t0 = _perf()
        for _attempt in range(self._EXCHANGE_MAX_ATTEMPTS):
            fn = get_exchange_hash_join(
                mesh, len(lk), n_lc, n_rc, cap_l, cap_r, pair_cap, kind
            )
            ok, *rest = fn(
                (lh, lnn, *lk, *l_ship),
                (rh, rnn, *rk, *r_ship),
            )
            used_l, used_r = cap_l, cap_r
            overflow = int(host_read("exchange", rest[-1]))
            if overflow == 0:
                break
            retries += 1
            self.on_task_failure(
                f"task retry: exchange join capacity overflow "
                f"({overflow} rows); doubling caps"
            )
            cap_l *= 2
            cap_r *= 2
            pair_cap *= 2
        else:
            # persistent overflow: the hot destination cannot fit a fixed
            # per-device capacity (a single key owning most of the rows
            # never splits under hash partitioning). Planned degradation
            # composes with scale-out: join through the host spill pool —
            # partition outputs stage host-side, only one partition pair
            # is ever live in HBM — instead of aborting the stream or
            # all-gathering through the generic sort join.
            if rest is not None:
                self._emit_exchange(
                    "join", n_dev,
                    self._exchange_bytes(n_dev, used_l, used_r,
                                         lh, lk, l_ship, rh, rk, r_ship),
                    rest[-2], retries, dur_ms=(_perf() - ex_t0) * 1000.0,
                    node_fp=node_fp,
                )
            if str(session.conf.get("engine.spill", "auto")).lower() == "off":
                return None  # out-of-core disabled: legacy sort-join fallback
            self.on_task_failure(
                "exchange join capacity overflow persists after "
                f"{retries} retries; tiering through the host spill pool"
            )
            parts = max(self._SPILL_FORCE_PARTS, n_dev)
            self._exchange_disabled = True
            try:
                return self._spilled_join(
                    *whole, kind, left_keys, right_keys, residual,
                    lk, lv, llive, rk, rv, rlive, parts, out=out,
                )
            finally:
                self._exchange_disabled = False
        self._emit_exchange(
            "join", n_dev,
            self._exchange_bytes(n_dev, used_l, used_r,
                                 lh, lk, l_ship, rh, rk, r_ship),
            rest[-2], retries, dur_ms=(_perf() - ex_t0) * 1000.0,
            node_fp=node_fp,
        )
        l_out = rest[:n_lc]
        r_out = rest[n_lc:n_lc + n_rc]
        nl = len(left.columns)
        nr = len(right.columns)
        cols = {}
        mi = nl
        for i, (name, c) in enumerate(left.columns.items()):
            valid = None
            if c.valid is not None:
                valid = l_out[mi] & ok
                mi += 1
            cols[name] = Column(
                l_out[i], c.dtype, valid, c.dictionary, c.gather_stats(),
                owned=True,
            )
        mi = nr
        for i, (name, c) in enumerate(right.columns.items()):
            valid = None
            if c.valid is not None:
                valid = r_out[mi] & ok
                mi += 1
            cols[name] = Column(
                r_out[i], c.dtype, valid, c.dictionary, c.gather_stats(),
                owned=True,
            )
        # compacting by the pair mask keeps exactly the verified pairs; the
        # gathered (shipped_valid & ok) buffers equal shipped_valid on every
        # surviving row, so per-column nullability is preserved
        pair = Table(cols, ok.shape[0])
        result = self._compact(pair, ok)
        if residual is not None:
            result = self._compact(
                result, self._predicate_mask(result, residual)
            )
        if kind == "left":
            # LEFT completion: (a) shipped-but-unmatched rows, read back
            # from the received left partition (matched is per-received-row
            # exact — every row with the same key landed on one device);
            # (b) null-keyed live rows, which never routed (live=lnn dead
            # through the exchange) and null-extend from the local shard —
            # exactly the direct path's treatment of them
            base = n_lc + n_rc
            lrecv_live = rest[base]
            lmatched = rest[base + 1]
            lrecv = rest[base + 2:base + 2 + n_lc]
            ucols = {}
            mi = nl
            for i, (name, c) in enumerate(left.columns.items()):
                valid = None
                if c.valid is not None:
                    valid = lrecv[mi]
                    mi += 1
                ucols[name] = Column(
                    lrecv[i], c.dtype, valid, c.dictionary,
                    c.gather_stats(), owned=True,
                )
            un = self._compact(
                Table(ucols, lrecv_live.shape[0]), lrecv_live & ~lmatched
            )
            result = self._concat(result, self._null_extend_right(un, right))
            if any(v is not None for v in lv):
                nk = self._compact(left, llive & ~lnn)
                result = self._concat(
                    result, self._null_extend_right(nk, right)
                )
        return result

    def _exchange_bytes(self, n_dev, cap_l, cap_r,
                        lh, lk, l_ship, rh, rk, r_ship) -> int:
        """Interconnect traffic of one exchange-join attempt: every device
        ships n_dev buckets of cap rows per shipped array (padded-capacity
        measure — what the collective actually moves, not just live rows),
        plus one byte per row of live mask."""
        per_l = 1 + sum(
            int(a.dtype.itemsize) for a in [lh, *lk, *l_ship]
        )
        per_r = 1 + sum(
            int(a.dtype.itemsize) for a in [rh, *rk, *r_ship]
        )
        return n_dev * n_dev * (per_l * cap_l + per_r * cap_r)

    @_tally.seamed("join")
    def _null_extend_right(self, t: Table, right: Table) -> Table:
        """Append all-null right-side columns to a left-rows-only table
        (the LEFT-join null extension), dtype/dictionary-aligned with the
        real right columns so a later concat unifies cleanly."""
        cols = dict(t.columns)
        for name, c in right.columns.items():
            cols[name] = Column(
                jnp.zeros(t.cap, c.data.dtype), c.dtype,
                jnp.zeros(t.cap, bool), c.dictionary,
            )
        return Table(cols, t.nrows_lazy, live=t.live)

    @_tally.seamed("join")
    def _apply_residual(self, ok, li, ri, left, right, residual):
        count = K.mask_count(ok)
        cap = bucket_cap(max(count, 1))
        sel = K.compact_indices(ok, cap)
        pair = self._pair_table(
            left, right, *K.take_arrays((li, ri), sel), count,
            out=E.col_refs(residual),
        )
        pmask = self._predicate_mask(pair, residual)
        # max-scatter: sel's padding duplicates index 0 (see _join residual)
        return ok & jnp.zeros(ok.shape, bool).at[sel].max(pmask)

    @_tally.seamed("mask")
    def _predicate_mask(self, table: Table, predicate) -> jnp.ndarray:
        """SQL WHERE semantics: TRUE rows only (NULL/UNKNOWN filtered),
        restricted to live rows."""
        pr = self._evaluator(table).eval(predicate)
        mask = pr.data.astype(bool)
        if pr.valid is not None:
            mask = mask & pr.valid
        return mask & table.row_mask()

    @_tally.seamed("join")
    def _join_key_pair(self, a: Column, b: Column):
        """Align join key dtypes (incl. cross-dictionary string unification).
        Returns ([left_cols], [right_cols]) — one column pair for most
        types; float64 keys expand to an exact (exponent, mantissa) pair
        (bitcast on s64 does not compile on this TPU toolchain, and a
        single int64 word cannot hold a float64 injectively)."""
        if a.dtype.is_string != b.dtype.is_string:
            # implicit coercion (Spark casts the string side): parse the
            # string key as the other side's type, e.g. invn_date = d_date
            # in the LF_I maintenance function
            if a.dtype.is_string:
                a = _cast_column(a, b.dtype, a.data.shape[0])
            else:
                b = _cast_column(b, a.dtype, b.data.shape[0])
        if a.dtype.is_string or b.dtype.is_string:
            ca, cb, uni = unify_dictionaries(a, b)
            return (
                [Column(ca, a.dtype, a.valid, uni)],
                [Column(cb, b.dtype, b.valid, uni)],
            )
        if a.dtype.is_decimal or b.dtype.is_decimal:
            s = max(a.dtype.scale if a.dtype.is_decimal else 0,
                    b.dtype.scale if b.dtype.is_decimal else 0)
            target = DType("decimal", 38, s)
            return (
                [_cast_column(a, target, a.data.shape[0])],
                [_cast_column(b, target, b.data.shape[0])],
            )
        if a.dtype.kind == "float64" or b.dtype.kind == "float64":
            # kernels compare keys as int64, which would truncate floats
            def as_keys(c):
                f = _cast_column(c, FLOAT64, c.data.shape[0])
                ew, mw = K.float_key_words(f.data)
                return [Column(ew, INT64, f.valid), Column(mw, INT64, f.valid)]

            return as_keys(a), as_keys(b)

        def as_i64(c):
            out = _cast_column(c, INT64, c.data.shape[0])
            if (
                out.stats is None
                and c.stats is not None
                and c.dtype.kind in ("int32", "int64", "date", "bool")
            ):
                # value-preserving widening: bounds and uniqueness survive,
                # and the packed-join path depends on them downstream
                out = _dc_replace(out, stats=c.subset_stats())
            return out

        return [as_i64(a)], [as_i64(b)]

    def _pair_table(self, left, right, li, ri, nrows, rkeep=None, lkeep=None,
                    out=None):
        # join-output gather can repeat rows: bounds survive, uniqueness
        # dies. Every buffer below is a fresh gather output owned by this
        # table alone — marked owned so a downstream fused pipeline may
        # donate it (engine/fuse.py:_donate_slots). One gather a side, of
        # the columns `out` names (_sides);
        # rkeep / lkeep are False on the rows an outer join null-extends
        left, right = self._sides(left, right, out)
        cols = gather_columns(
            left.columns, li, lkeep, stats=Column.gather_stats, owned=True,
        )
        cols.update(gather_columns(
            right.columns, ri, rkeep, stats=Column.gather_stats, owned=True,
        ))
        return Table(cols, nrows)

    @_tally.seamed("join")
    def _cross_join(self, left, right):
        # position arithmetic below assumes packed rows
        left = left.compacted()
        right = right.compacted()
        ln, rn = left.nrows, right.nrows
        total = ln * rn
        cap = bucket_cap(max(total, 1))
        p = jnp.arange(cap)
        li = (p // max(rn, 1)).astype(jnp.int32)
        ri = (p % max(rn, 1)).astype(jnp.int32)
        li = jnp.clip(li, 0, max(left.cap - 1, 0))
        return self._pair_table(left, right, li, ri, total)

    # ------------------------------------------------------------------
    # ------------------------------------------------------------------
    def _exec_aggregate(self, node: P.Aggregate) -> Table:
        blocked = self._blocked_union_ctx(node) if node.blocked_union else None
        if node.grouping_sets is None:
            if blocked is not None:
                return self._finish_blocked_union(node, blocked)
            child, live, nlive = self._agg_input(node)
            return self._aggregate_once(
                node.keys, node.aggs, None, child, live, nlive
            )
        if blocked is not None:
            # ROLLUP over a union (the query5 shape): from-scratch levels
            # run windowed; cascade levels re-aggregate small group tables
            # as usual — the full union concat never materializes
            child = live = nlive = None
        else:
            child, live, nlive = self._agg_input(node)
        # ROLLUP: concat incrementally and never retain the per-set parts
        # (q67's nine sets at fact-scale group caps held several GB), then
        # pack the masked concat chain before downstream windows/sorts —
        # a hard device OOM was unrecoverable on the device route of
        # rounds 1-5 (not re-tested on an attached v5e), so peak memory
        # is treated as a correctness concern.
        #
        # Cascade: when every aggregate decomposes (sum/min/max/count) and
        # the sets chain by inclusion (ROLLUP prefixes do), each coarser
        # level re-aggregates the PREVIOUS level's output — one pass over
        # the fact-scale input instead of one per set (q67: nine 8.8M-row
        # passes became one + eight over <=2M group rows).
        base_aggs, avg_items = _rollup_base_aggs(node.aggs)
        casc_aggs = _cascade_agg_items(base_aggs) if base_aggs else None
        out = None
        prev = None
        prev_set = None
        sets = sorted(node.grouping_sets, key=len, reverse=True)
        for s in sets:
            if (
                prev is not None
                and casc_aggs is not None
                and set(s) <= set(prev_set)
            ):
                key_items2 = [
                    (E.Col(name), name) for (_, name) in node.keys
                ]
                part = self._aggregate_once(
                    key_items2, casc_aggs, s, prev, prev.row_mask(),
                    prev.nrows_known,
                )
            elif blocked is not None:
                part = self._blocked_union_once(node, blocked, s)
            else:
                part = self._aggregate_once(
                    node.keys, base_aggs or node.aggs, s, child, live, nlive
                )
            part = _derive_rollup_avgs(part, avg_items)
            prev, prev_set = part, s
            out = part if out is None else self._concat(out, part)
        if avg_items:
            out = Table(
                {
                    n: c
                    for n, c in out.columns.items()
                    if not n.startswith("__cs_") and not n.startswith("__cc_")
                },
                out.nrows_lazy,
                live=out.live,
            )
        if blocked is not None:
            self._annotate_blocked(node, blocked)
        return out.compacted()

    # -- blocked (morsel-style) union-aggregation -------------------------
    # A union_all feeding an aggregate (directly, through Project/Filter
    # wrappers, or as one relation of an inner MultiJoin) never
    # materializes the full concat: each branch is evaluated in bounded row
    # windows, every window is (joined against the other relations, then)
    # partially aggregated with the rollup cascade's decomposable-aggregate
    # machinery (sum/min/max/count, avg via hidden sum+count), and partials
    # merge incrementally — peak live HBM is O(window + group rows) instead
    # of O(total union rows). This is what breaks the SF10 single-chip
    # ceiling: query5's per-channel sales+returns union is a fact-scale
    # concat (~32M rows x ~6 columns per channel at SF10) joined to
    # date_dim/store before aggregation — it hard-OOMs (and irrecoverably
    # poisons) the device on the unblocked path.

    def _blocked_union_ctx(self, node: P.Aggregate):
        """Prepare windowed execution of a blocked-union aggregate: execute
        + align the union branches, execute the non-union join relations
        once, and size the window. Returns a context dict, or None when the
        shape/aggregates/size rule the blocked path out (callers fall
        through to the unblocked path)."""
        shape = P.union_agg_shape(node)
        if shape is None:
            return None
        session = getattr(self.catalog, "session", None)
        if session is None:
            return None  # no budget tracking: stay on the unblocked path
        started = (_time_ns(), _perf()) if self.tracer is not None else None
        base_aggs, avg_items = _rollup_base_aggs(node.aggs)
        if base_aggs is None:
            return None  # non-decomposable aggregate (distinct, stddev...)
        casc_aggs = _cascade_agg_items(base_aggs)
        if casc_aggs is None:
            return None
        outer, join, inner, branch_plans = shape
        branches = self._execute_relations_batched(branch_plans)
        total_rows = sum(t.nrows for t in branches)
        row_bytes = max(
            sum(
                int(c.data.dtype.itemsize) + 1  # data + validity byte
                for c in branches[0].columns.values()
            ),
            1,
        )
        # the plan budgeter's statically chosen window (budget_window_rows
        # annotation) wins over the runtime derivation; explicit conf/env
        # still win over both (session.union_agg_window_rows)
        wrows = session.union_agg_window_rows(
            row_bytes,
            static_rows=getattr(node, "budget_window_rows", None),
        )
        if total_rows <= wrows:
            # single window: the unblocked path is equivalent. Cheap bail —
            # the branch tables just executed are id-cached in _cte_cache,
            # so the fall-through SetOp execution reuses them directly.
            return None
        join_ctx = None
        if join is not None:
            mj, uidx = join
            # the dimension-side relations execute ONCE and are reused by
            # every window's join
            others = self._execute_relations_batched(
                [r for i, r in enumerate(mj.relations) if i != uidx]
            )
            it = iter(others)
            tables = [
                None if i == uidx else next(it)
                for i in range(len(mj.relations))
            ]
            join_ctx = (mj.edges, uidx, tables, mj.required)
        branches = [t.compacted() for t in branches]
        aligners = self._union_branch_aligners(branches)
        # mark the blocked path as ENTERED before any window executes: an
        # OOM raised mid-window must still be attributable to a blocked
        # plan, so the marker cannot wait for successful completion in
        # _annotate_blocked
        self.last_blocked_union = {
            "windows": 0,
            "window_rows": wrows,
            "window_cap": bucket_cap(wrows),
            "total_rows": total_rows,
            "max_table_cap": 0,
        }
        session.last_blocked_union = self.last_blocked_union
        return {
            "started": started,
            "outer_wrappers": outer,
            "join": join_ctx,
            "join_trace": {},  # first window records the order, rest replay
            "inner_wrappers": inner,
            "branches": branches,
            "aligners": aligners,
            "base_aggs": base_aggs,
            "avg_items": avg_items,
            "casc_aggs": casc_aggs,
            "window_rows": wrows,
            "window_cap": bucket_cap(wrows),
            "total_rows": total_rows,
            "windows": 0,  # accumulated across aggregation levels
            "max_table_cap": 0,
        }

    def _apply_wrappers(self, t: Table, wrappers) -> Table:
        for w in reversed(wrappers):  # innermost wrapper first
            if isinstance(w, P.Filter):
                t = self._masked(
                    t.narrowed(w.required),
                    self._predicate_mask(t, w.predicate),
                )
            else:
                t = self._project_table(t, w.items)
        return t

    def _apply_wrappers_fused(self, t: Table, wrappers, memo) -> Table:
        """Apply a top-down wrapper list through ONE fused executable when
        the chain traces — the blocked union-aggregation per-window path:
        every window of a branch shares the same shape bucket and input
        signature, so the first window builds the executable and the other
        N-1 windows ride the exec cache instead of paying an eager dispatch
        per wrapper per window. `memo` (per-blocked-context dict) caches
        the detached stage list + fingerprint per wrapper chain. Falls back
        to the exact eager per-wrapper path whenever fusion is off, the
        chain has an unfusible stage, or the build failed."""
        if not wrappers:
            return t
        session = getattr(self.catalog, "session", None)
        if (
            session is None
            or session.conf.get("engine.fuse", "on") == "off"
            or not t.columns
            or t.cap == 0
        ):
            return self._apply_wrappers(t, wrappers)
        key = tuple(id(w) for w in wrappers)
        info = memo.get(key)
        if info is None:
            stages = []
            for w in reversed(wrappers):  # execution order
                if not fuse._stage_fusible(w):
                    stages = None
                    break
                if isinstance(w, P.Filter):
                    stages.append(_dc_replace(w, child=None))
                else:
                    stages.append(P.Project(items=list(w.items), child=None))
            if stages and not fuse._chain_worth_fusing(stages):
                # pure rename/subset wrappers: the eager path reuses the
                # window's column objects outright — a compiled dispatch
                # per window would only add copies (same gate as
                # mark_pipelines)
                stages = None
            fp = (
                P.fingerprint(P.Pipeline(stages=stages, child=None))
                if stages
                else None
            )
            info = memo[key] = (fp, stages)
        fp, stages = info
        if fp is None:
            return self._apply_wrappers(t, wrappers)
        sig = fuse.input_signature(t)
        aot, conf_sig = self._aot_build_args(session)
        with session.cache_lock:
            entry, hit = session.exec_cache.lookup(
                fp, sig, t.cap,
                lambda: fuse.FusedPipeline(
                    stages, t, aot=aot, fp=fp, conf_sig=conf_sig
                ),
            )
        if self.tracer is not None:
            self.tracer.emit(
                "exec_cache", pipeline=fp[:12], bucket=t.cap, hit=hit,
                fused=entry is not None,
            )
        if entry is None:
            return self._apply_wrappers(t, wrappers)
        try:
            return entry.call(t, False)  # windows alias branch buffers
        except Exception as exc:
            with session.cache_lock:
                session.exec_cache.map[(fp, sig)] = None
            self.on_task_failure(
                f"window fuse fallback: {str(exc)[:120]}"
            )
            return self._apply_wrappers(t, wrappers)

    def _blocked_union_once(self, node: P.Aggregate, ctx, subset):
        """One aggregation level (grouping-set `subset`, or None for the
        plain shape) over the union input, evaluated window by window with
        incremental partial merging. Returns the same table an unblocked
        _aggregate_once would (hidden avg sum/count columns included)."""
        key_merge = [(E.Col(name), name) for _, name in node.keys]
        merged = None
        empty_partial = None
        session = getattr(self.catalog, "session", None)
        for b, aligner in zip(ctx["branches"], ctx["aligners"]):
            start = 0
            while start < b.nrows:
                wcap = ctx["window_cap"]
                if (
                    session is not None
                    and getattr(session, "_mem_pressure", False)
                    and wcap > 4096
                ):
                    # host-RSS watermark pre-emption (report.py via
                    # obs.memwatch): shrink the REMAINING windows before
                    # the allocator fails. Halving a power-of-two cap
                    # keeps `start` aligned (start is a multiple of every
                    # previous cap, all powers of two >= the new one).
                    session._mem_pressure = False
                    wcap = ctx["window_cap"] = max(wcap // 2, 4096)
                    self.on_task_failure(
                        f"host memory watermark: blocked-union window "
                        f"shrunk to {wcap} rows mid-query"
                    )
                w = window_slice(b, start, wcap)
                start += wcap
                ctx["windows"] += 1
                ctx["max_table_cap"] = max(ctx["max_table_cap"], w.cap)
                # branch-to-union alignment (rename/cast/dictionary remap)
                # applies per window: only O(window) aligned copies live
                wcols = list(w.columns.values())
                t = Table(
                    {
                        name: fn(wcols[ci])
                        for ci, (name, fn) in enumerate(aligner)
                    },
                    w.nrows_lazy,
                    live=w.live,
                )
                t = self._apply_wrappers_fused(
                    t, ctx["inner_wrappers"],
                    ctx.setdefault("wrapper_memo", {}),
                )
                if ctx["join"] is not None:
                    edges, uidx, others, required = ctx["join"]
                    t = self._multijoin_over_tables(
                        [t if i == uidx else o for i, o in enumerate(others)],
                        edges,
                        trace=ctx["join_trace"],
                        required=required,
                    )
                    ctx["max_table_cap"] = max(ctx["max_table_cap"], t.cap)
                t = self._apply_wrappers_fused(
                    t, ctx["outer_wrappers"],
                    ctx.setdefault("wrapper_memo", {}),
                )
                part = self._aggregate_once(
                    node.keys, ctx["base_aggs"], subset, t, t.row_mask(),
                    t.nrows_known,
                )
                if part.nrows_known == 0:
                    # keep one empty partial: its columns carry the same
                    # stub dtypes the unblocked empty-aggregate output uses
                    empty_partial = part
                    continue
                if merged is None:
                    merged = part
                else:
                    cat = self._concat(merged, part)
                    ctx["max_table_cap"] = max(
                        ctx["max_table_cap"], cat.cap
                    )
                    merged = self._aggregate_once(
                        key_merge, ctx["casc_aggs"], None, cat,
                        cat.row_mask(), cat.nrows_known,
                    )
        if merged is None:
            merged = empty_partial  # every window filtered to nothing
        return merged

    def _finish_blocked_union(self, node: P.Aggregate, ctx) -> Table:
        """The plain (non-grouping-sets) blocked aggregate: one windowed
        level, visible avgs derived, declared column order restored."""
        merged = self._blocked_union_once(node, ctx, None)
        out = _derive_rollup_avgs(merged, ctx["avg_items"])
        # restore the declared output column order (and drop the hidden
        # __cs_/__cc_ avg-decomposition columns)
        out = out.select(
            [n for _, n in node.keys]
            + [n for _, n in node.aggs if n in out.columns]
        )
        self._annotate_blocked(node, ctx)
        return out

    def _annotate_blocked(self, node: P.Aggregate, ctx):
        # plan-introspection aids (tests/tools): window count and the peak
        # per-window table capacity actually touched, which must stay
        # bounded by the window bucket — never by the total union rows
        node.blocked_windows = ctx["windows"]
        node.blocked_stats = self.last_blocked_union = {
            "windows": ctx["windows"],
            "window_rows": ctx["window_rows"],
            "window_cap": ctx["window_cap"],
            "total_rows": ctx["total_rows"],
            "max_table_cap": ctx["max_table_cap"],
        }
        # session-level marker: tests/test_budget.py reads this to tell
        # whether the statement it just ran routed through the blocked
        # path, and at which window size
        session = getattr(self.catalog, "session", None)
        if session is not None:
            session.last_blocked_union = self.last_blocked_union
        if self.tracer is not None:
            # from the branches' execution to the last window merged
            t0_ns, t0 = ctx["started"]
            self.tracer.emit(
                "blocked_union", **self.last_blocked_union, t0_ns=t0_ns,
                dur_ms=round((_perf() - t0) * 1000.0, 3),
            )

    def _union_branch_aligners(self, tables):
        """Per-branch WINDOW aligners: unify column names (leftmost branch
        wins, as in SetOp output), dtypes (common promotion) and string
        dictionaries across union branches, mirroring _concat's per-pair
        unification so windowed evaluation sees the same values the
        unblocked concat chain would. The cast/remap itself is deferred to
        each window slice — aligning the full branches up front would
        allocate branch-scale copies and reintroduce exactly the peak-HBM
        spike the blocked path exists to avoid; only dictionary-sized remap
        tables are built here. Returns one [(out_name, fn(Column)->Column)]
        list per branch, positionally aligned with the branch's columns."""
        names = list(tables[0].columns)
        per_table = [list(t.columns.values()) for t in tables]
        aligners = [[] for _ in tables]
        for ci, name in enumerate(names):
            cols = [cols_t[ci] for cols_t in per_table]
            if any(c.dtype.is_string for c in cols):
                dicts = [
                    (
                        c.dictionary
                        if c.dictionary is not None
                        else pa.array([], pa.string())
                    ).cast(pa.string())
                    for c in cols
                ]
                unified = pc.unique(pa.concat_arrays(dicts))
                for bi, d in enumerate(dicts):
                    if len(d) == 0:

                        def fn(col, _u=unified):
                            return Column(col.data, col.dtype, col.valid, _u)

                    else:
                        remap = jnp.asarray(
                            pc.index_in(d, unified)
                            .to_numpy(zero_copy_only=False)
                            .astype(np.int32)
                        )

                        def fn(col, _r=remap, _u=unified, _n=len(d)):
                            return Column(
                                _r[jnp.clip(col.data, 0, _n - 1)],
                                col.dtype,
                                col.valid,
                                _u,
                            )

                    aligners[bi].append((name, fn))
            else:
                dt = _common_dtype([c.dtype for c in cols])

                def fn(col, _dt=dt):
                    return _cast_column(col, _dt, col.data.shape[0])

                for bi in range(len(tables)):
                    aligners[bi].append((name, fn))
        return aligners

    def _agg_input(self, node: P.Aggregate):
        """Aggregation input as (table, live mask, known row count|None).
        Filters/dense joins produce deferred-compaction tables, so e.g.
        the q9 shape (15 scalar subqueries, each a global aggregate over a
        filtered fact scan) runs entirely async on device."""
        t = self.execute(node.child)
        return t, t.row_mask(), t.nrows_known

    def _aggregate_once(self, key_items, agg_items, subset, child, live,
                        nlive):
        # stash grouping state for grouping()/distinct-agg helpers, saving
        # the previous values: a scalar subquery inside an aggregate
        # argument re-enters _aggregate_once and must not clobber the
        # outer aggregation's state
        prev = (
            getattr(self, "_current_agg_keys", None),
            getattr(self, "_current_agg_live", None),
            getattr(self, "_current_agg_nlive", None),
        )
        self._current_agg_keys = key_items
        self._current_agg_live = live
        self._current_agg_nlive = nlive
        try:
            return self._aggregate_once_inner(
                key_items, agg_items, subset, child, live, nlive
            )
        finally:
            (
                self._current_agg_keys,
                self._current_agg_live,
                self._current_agg_nlive,
            ) = prev

    def _aggregate_once_inner(self, key_items, agg_items, subset, child,
                              live, nlive):
        ev = self._evaluator(child)
        key_cols = []
        for i, (e, name) in enumerate(key_items):
            if subset is not None and i not in subset:
                key_cols.append(None)
            else:
                key_cols.append(ev.eval(e))
        active = [c for c in key_cols if c is not None]

        if active and (nlive is None or nlive > 0):
            direct = self._try_direct_agg(
                child, key_items, key_cols, agg_items, subset, ev, live
            )
            if direct is not None:
                return direct

        words = None
        if active:
            words = self._group_words(active, live)
            # nlive None (fused filter mask): group_by_words syncs the count
            order, gid, ngroups = K.group_by_words(words, live, nlive)
        else:
            # single global group: one run, so no sort and no ids at all —
            # identity order, weight = live mask, and every reduction a
            # masked reduce into cell 0 (K.segment_reduce with no gid). SQL
            # yields exactly one row even over empty input (weights produce
            # the NULL/0 aggregate values).
            order = None
            gid = None
            ngroups = 1
        if ngroups == 0:
            if active:
                # empty input, grouped agg -> empty result
                return self._agg_output(
                    child, key_items, key_cols, agg_items, subset,
                    None, None, 0, ev,
                )
            ngroups = 1  # global agg over empty input yields one row
        gcap = bucket_cap(ngroups)
        with _tally.eager("agg"):
            live_sorted = live if order is None else live[order]
        return self._agg_output(
            child, key_items, key_cols, agg_items, subset,
            order, gid, ngroups, ev, gcap, live_sorted, words,
        )

    # -- direct (sort-free) aggregation ----------------------------------
    # When the combined group-key domain is small (the TPC-DS norm), group
    # ids are computed elementwise as mixed-radix codes and every aggregate
    # is one scatter-add — no sort of the fact table. Under a mesh the
    # scatter-add over row-sharded input lowers to per-chip partial
    # aggregation + a cross-chip reduction of the small group table.
    _DIRECT_AGG_MAX_DOMAIN = 1 << 22

    @_tally.seamed("agg")
    def _try_direct_agg(
        self, child, key_items, key_cols, agg_items, subset, ev, live
    ):
        if any(agg.distinct for agg, _ in agg_items):
            return None
        active = [(i, c) for i, c in enumerate(key_cols) if c is not None]
        datas, valids, mins, ranges = [], [], [], []
        domain = 1
        for _, c in active:
            # key bounds come from catalog ColStats (or are statically known
            # for dictionary codes / bools) — never from a device round-trip;
            # keys without bounds fall back to the sort-based aggregation
            if c.dtype.is_string:
                if c.dictionary is None or len(c.dictionary) == 0:
                    return None
                kmin, kmax = 0, len(c.dictionary) - 1
            elif c.dtype.kind == "bool":
                kmin, kmax = 0, 1
            elif c.dtype.kind in ("int32", "int64", "date"):
                if c.stats is None:
                    return None
                kmin, kmax = c.stats.vmin, c.stats.vmax
            else:
                return None
            data = c.data
            if data.dtype == jnp.bool_:
                data = data.astype(jnp.int32)
            krange = kmax - kmin + 1 + (1 if c.valid is not None else 0)
            domain *= krange
            if domain > self._DIRECT_AGG_MAX_DOMAIN:
                return None
            datas.append(data)
            valids.append(c.valid)
            mins.append(kmin)
            ranges.append(krange)
        domain_cap = bucket_cap(domain)
        gid = K.direct_gid(datas, valids, mins, ranges, live)
        occ, dense = K.occupancy_map(gid, live, domain_cap)
        ngroups = K.mask_count(occ)
        if ngroups == 0:
            return None
        gcap = bucket_cap(ngroups)
        gid_dense = jnp.clip(dense[gid], 0)
        occ_cells = K.compact_indices(occ, gcap).astype(jnp.int64)

        # reconstruct key columns from the occupied cell codes (reverse
        # mixed-radix decomposition; last key is least significant)
        codes = []
        rem = occ_cells
        for krange in reversed(ranges):
            codes.append(rem % krange)
            rem = rem // krange
        codes.reverse()
        cols = {}
        ai = 0
        for i, ((e, name), c) in enumerate(zip(key_items, key_cols)):
            if c is None:
                base = ev.eval(key_items[i][0])
                cols[name] = Column(
                    jnp.zeros(gcap, base.dtype.device_np_dtype()),
                    base.dtype,
                    jnp.zeros(gcap, bool),
                    base.dictionary,
                )
                continue
            code = codes[ai]
            kmin = mins[ai]
            ai += 1
            if c.valid is not None:
                valid = code != 0
                value = jnp.where(valid, kmin + code - 1, 0)
            else:
                valid = None
                value = kmin + code
            out_dtype = c.dtype.device_np_dtype()
            data = value.astype(out_dtype)
            cols[name] = Column(
                data, c.dtype, valid, c.dictionary,
                _group_key_stats(c, len(active)),
            )
        for agg, name in agg_items:
            cols[name] = self._eval_agg(
                agg, ev, None, gid_dense, gcap, live, ngroups, child, subset,
                key_cols,
            )
        return Table(cols, ngroups, unique_key=_active_key_names(key_items, key_cols))

    @_tally.seamed("agg")
    def _agg_output(
        self, child, key_items, key_cols, agg_items, subset,
        order, gid, ngroups, ev, gcap=None, live_sorted=None,
        key_words=None,
    ):
        if ngroups == 0:
            cols = {}
            for (e, name), c in zip(key_items, key_cols):
                dtype = c.dtype if c is not None else INT64
                cols[name] = Column(
                    jnp.zeros(1, dtype.device_np_dtype()), dtype,
                    jnp.zeros(1, bool),
                    c.dictionary if c is not None else None,
                )
            for agg, name in agg_items:
                cols[name] = Column(jnp.zeros(1, jnp.int64), INT64, jnp.zeros(1, bool))
            return Table(cols, 0)
        first_rows = None
        runs = None
        if order is not None:
            # the sort route's ids are sorted dense runs: their bounds are
            # read once, and every count and integer sum below is a prefix
            # difference at them instead of a scatter (None over a mesh)
            runs = K.run_bounds(gid, live_sorted, gcap, ngroups)
            first_idx = (
                K.segment_starts(gid, gcap) if runs is None else runs[0]
            )
            first_rows = order[jnp.clip(first_idx, 0, child.cap - 1)]
        n_active = sum(1 for kc in key_cols if kc is not None)
        taken = gather_columns(
            {name: c for (e, name), c in zip(key_items, key_cols)
             if c is not None},
            first_rows, stats=lambda c: _group_key_stats(c, n_active),
        )
        cols = {}
        for (e, name), c in zip(key_items, key_cols):
            if c is None:
                # rolled-up key: all null
                base = ev.eval(e)
                cols[name] = Column(
                    jnp.zeros(gcap, base.dtype.device_np_dtype()),
                    base.dtype,
                    jnp.zeros(gcap, bool),
                    base.dictionary,
                )
            else:
                cols[name] = taken[name]
        for agg, name in agg_items:
            cols[name] = self._eval_agg(
                agg, ev, order, gid, gcap, live_sorted, ngroups, child, subset,
                key_cols, key_words, runs,
            )
        return Table(cols, ngroups, unique_key=_active_key_names(key_items, key_cols))

    def _eval_agg(
        self, agg: E.Agg, ev, order, gid, gcap, live_sorted, ngroups, child,
        subset, key_cols, key_words=None, runs=None,
    ) -> Column:
        # `gid` None: a global aggregate, one run; `runs`: the bounds of
        # the sort route's sorted runs. K.segment_reduce routes by them.
        fn = agg.fn
        if fn == "grouping":
            # grouping(key) = 1 when the key is rolled away in this set.
            # The binder left grouping()'s arg as the raw key expr; the arg
            # was rewritten to the key's output Col by the post-agg rewrite,
            # so match either form against the Aggregate node's key items.
            idx = None
            for i, (ke, kn) in enumerate(self._current_agg_keys):
                if agg.arg == ke or agg.arg == E.Col(kn):
                    idx = i
                    break
            rolled = subset is not None and idx is not None and idx not in subset
            v = jnp.full(gcap, 1 if rolled else 0, jnp.int32)
            return Column(v, DType("int32"))
        if agg.distinct:
            return self._eval_distinct_agg(
                agg, ev, child, subset, key_cols, gcap, ngroups, key_words
            )
        if fn == "count" and agg.arg is None:
            counts = K.segment_reduce(
                live_sorted.astype(jnp.int64), gid, live_sorted, gcap, "count",
                runs,
            )
            return Column(counts.astype(jnp.int64), INT64)
        c = ev.eval(agg.arg)
        sdata, svalid = c.data, c.valid
        if c.dtype.is_string:
            sdata, sorted_dict = sort_dictionary(c)
        # order=None: direct (unsorted) aggregation — gid/live are row-aligned
        if order is not None:
            ((sdata, svalid),) = K.take_columns(((sdata, svalid),), order)
        weight = live_sorted if svalid is None else live_sorted & svalid
        if c.dtype.is_string:
            if fn in ("min", "max"):
                red, counts = K.segment_reduce_with_count(
                    sdata, gid, weight, gcap, fn, runs
                )
                return Column(
                    red.astype(jnp.int32), c.dtype, counts > 0, sorted_dict
                )
            raise ExecError(f"agg {fn} on string column")
        if fn == "count":
            counts = K.segment_reduce(sdata, gid, weight, gcap, "count", runs)
            return Column(counts.astype(jnp.int64), INT64)
        if fn in ("sum", "min", "max"):
            pall = self._pallas_segment_route(fn, c, sdata, gid, weight, gcap)
            if pall is not None:
                return pall
            red, counts = K.segment_reduce_with_count(
                sdata, gid, weight, gcap, fn, runs
            )
            dtype = c.dtype
            if fn == "sum" and dtype.kind == "int32":
                dtype = INT64
                red = red.astype(jnp.int64)
            return Column(red, dtype, counts > 0)
        if fn == "avg":
            s, n = K.segment_reduce_with_count(
                sdata, gid, weight, gcap, "sum", runs
            )
            nz = jnp.maximum(n, 1)
            if c.dtype.is_decimal:
                val = s.astype(jnp.float64) / (10**c.dtype.scale) / nz
            else:
                val = s.astype(jnp.float64) / nz
            return Column(val, FLOAT64, n > 0)
        if fn in ("stddev_samp", "var_samp"):
            x = sdata.astype(jnp.float64)
            if c.dtype.is_decimal:
                x = x / 10**c.dtype.scale
            s = K.segment_reduce(x, gid, weight, gcap, "sum")
            sq = K.segment_reduce(x, gid, weight, gcap, "sumsq")
            n = K.segment_reduce(
                x, gid, weight, gcap, "count", runs
            ).astype(jnp.float64)
            nz = jnp.maximum(n, 2)
            var = (sq - s * s / jnp.maximum(n, 1)) / (nz - 1)
            var = jnp.maximum(var, 0.0)
            out = jnp.sqrt(var) if fn == "stddev_samp" else var
            return Column(out, FLOAT64, n > 1)
        raise ExecError(f"aggregate {fn}")

    def _pallas_segment_route(self, fn, c, sdata, gid, weight, gcap):
        """Opt-in Pallas segment-reduce promotion for float64 measures.

        `engine.pallas_agg`: `off` (default) — the jnp/XLA scatter path;
        `on` — always route sum/min/max through the Pallas tile kernels
        (ops/pallas_kernels.py: one-hot MXU matmul for sum, VPU tile
        min/max); `auto` — MEASURED promotion: the first call at each
        (fn, input cap, group cap) shape times both paths (post-warmup, so
        compile cost is excluded) and promotes only when Pallas actually
        wins on this backend, recording both measurements as `kernel_span`
        events — promotion on data, not faith. All modes are float32
        accumulation (the reference's --floats tolerance), so float64
        measures only; exact int64/decimal reductions never route here."""
        mode = self._pallas_mode()
        if mode not in ("on", "auto") or c.dtype.kind != "float64":
            return None
        # opt-in backend: the Pallas import compiles Mosaic machinery the
        # default path never needs
        # nds-lint: disable=local-import
        from ..ops import pallas_kernels as PK

        interpret = jax.devices()[0].platform != "tpu"
        if gid is None:  # a global aggregate: the tile kernels want ids
            gid = jnp.zeros(weight.shape[0], jnp.int32)
        pgid = jnp.where(weight, gid, -1).astype(jnp.int32)
        # mask dead/null lanes: a zero one-hot entry does not neutralize
        # NaN garbage (0*NaN=NaN would poison the whole group tile)
        pvals = jnp.where(weight, sdata, 0).astype(jnp.float32)
        if mode == "auto" and not self._pallas_promoted(
            fn, sdata, gid, weight, gcap, pvals, pgid, interpret
        ):
            return None
        if fn == "sum":
            s, n = PK.segment_sums_pallas(
                pvals, pgid, gcap, interpret=interpret
            )
        else:
            s, n = PK.segment_extreme_pallas(
                pvals, pgid, gcap, fn == "max", interpret=interpret
            )
        return Column(s.astype(jnp.float64), c.dtype, n > 0)

    def _pallas_mode(self) -> str:
        session = getattr(self.catalog, "session", None)
        if session is None:
            return "off"
        return str(session.conf.get("engine.pallas_agg", "off")).lower()

    def _sort_perm_route(self, words):
        """ORDER BY permutation with optional Pallas counting-sort
        promotion (`engine.pallas_sort`): `off` (default) — the canonical
        kv-sort kernel; `on` — route eligible words through the Pallas
        counting sort (ops/pallas_kernels.sort_perm_pallas, identical
        stable ascending permutation by construction); `auto` — the same
        measured per-shape A/B as the aggregate/join routes, memoized on
        `Session.pallas_promotions` AND the persistent promotion store
        under key ("sort_perm", rows, domain). Eligible: exactly one sort
        word whose value span fits the counting domain (the span probe is
        one fused dispatch + one host sync, paid only in on/auto modes) —
        everything else stays on the canonical kernel unconditionally."""
        session = getattr(self.catalog, "session", None)
        mode = (
            str(session.conf.get("engine.pallas_sort", "off")).lower()
            if session is not None
            else "off"
        )
        if mode not in ("on", "auto") or len(words) != 1:
            return K.sort_by_words(words)
        # opt-in backend: the Pallas import compiles Mosaic machinery the
        # default path never needs — it must stay BEHIND the mode gate
        # nds-lint: disable=local-import
        from ..ops import pallas_kernels as PK

        if int(words[0].shape[0]) > PK.SORT_MAX_ROWS:
            return K.sort_by_words(words)
        w = words[0]
        lo, hi = (int(x) for x in host_read("sort_span", K.word_span(w)))
        if lo < 0 or hi >= PK.SORT_MAX_DOMAIN:
            return K.sort_by_words(words)
        # 128-aligned domain so near-identical spans share one compiled
        # kernel (and one promotion verdict)
        domain = -(-(hi + 1) // 128) * 128
        interpret = jax.devices()[0].platform != "tpu"
        if mode == "auto":
            key = ("sort_perm", int(w.shape[0]), int(domain))
            rec = self._promotion_rec(key)
            if rec is None:
                rec = self._measure_promotion(
                    key,
                    lambda: K.sort_by_words(words),
                    lambda: PK.sort_perm_pallas(
                        w, domain, interpret=interpret
                    ),
                    "sort_perm",
                )
            if not rec["use"]:
                return K.sort_by_words(words)
        return PK.sort_perm_pallas(w, domain, interpret=interpret)

    def _dense_build_route(self, rkey, rnn, rmin, table_cap):
        """Join-candidate build-table promotion (`engine.pallas_join`):
        `off` — the jnp scatter-max; `on` — the Pallas one-hot tile
        kernel (exact integer maxima, no numeric caveat; either way one
        int32 table of row + 1, `kernels.dense_build`); `auto` — the
        same measured per-shape A/B as the aggregate route, recorded as
        `kernel_span` evidence and memoized on `Session.pallas_promotions`
        under key ("dense_build", rows, table_cap)."""
        session = getattr(self.catalog, "session", None)
        mode = (
            str(session.conf.get("engine.pallas_join", "off")).lower()
            if session is not None
            else "off"
        )
        if mode not in ("on", "auto"):
            return K.dense_build(rkey, rnn, rmin, table_cap)
        # opt-in backend: the Pallas import compiles Mosaic machinery the
        # default path never needs
        # nds-lint: disable=local-import
        from ..ops import pallas_kernels as PK

        interpret = jax.devices()[0].platform != "tpu"
        if mode == "auto":
            key = ("dense_build", int(rkey.shape[0]), int(table_cap))
            rec = self._promotion_rec(key)
            if rec is None:
                rec = self._measure_promotion(
                    key,
                    lambda: K.dense_build(rkey, rnn, rmin, table_cap),
                    lambda: PK.dense_build_pallas(
                        rkey, rnn, rmin, table_cap, interpret=interpret
                    ),
                    "dense_build",
                )
            if not rec["use"]:
                return K.dense_build(rkey, rnn, rmin, table_cap)
        return PK.dense_build_pallas(
            rkey, rnn, rmin, table_cap, interpret=interpret
        )

    def _promotion_rec(self, key):
        """The memoized promotion verdict for `key`: the session memo
        first, then the PERSISTENT store (engine/aotcache.py
        PromotionStore — verdicts measured by any previous process on
        this backend environment), loaded into the memo on hit so a fleet
        measures each (kernel, shape) once, ever. None = unmeasured."""
        session = self.catalog.session
        rec = session.pallas_promotions.get(key)
        if rec is not None:
            return rec
        store = getattr(session, "promotion_store", None)
        if store is None:
            return None
        rec = store.get(AOTC.promotion_key_str(key))
        if rec is not None and "use" in rec:
            with session.cache_lock:
                session.pallas_promotions[key] = rec
            return rec
        return None

    def _measure_promotion(self, key, run_jnp, run_pallas, kname):
        """One-time measured A/B for a (kernel, shape) promotion slot:
        warm both paths (compiles land in the jit caches either way), time
        one synchronized call each, memoize the winner on the session
        (and in the persistent promotion store when one is configured)
        and emit both measurements as `kernel_span` events."""
        session = self.catalog.session

        def timed(run):
            # a measurement that has to synchronize, once per shape, ever
            # nds-lint: disable=host-read-seam
            jax.block_until_ready(run())  # warmup: exclude compile
            t0 = _perf()
            jax.block_until_ready(run())  # nds-lint: disable=host-read-seam
            return (_perf() - t0) * 1000.0

        jnp_ms = timed(run_jnp)
        # a kernel the backend's compiler refuses raises here, out of the
        # query: a verdict of "slower" for a kernel that never ran would
        # sit in the persistent store and the route would stay dark
        pallas_ms = timed(run_pallas)
        with session.cache_lock:
            rec = session.pallas_promotions[key] = {
                "jnp_ms": round(jnp_ms, 3),
                "pallas_ms": round(pallas_ms, 3),
                "use": pallas_ms < jnp_ms,
            }
        store = getattr(session, "promotion_store", None)
        if store is not None:
            # measure once, reuse forever: the verdict (keyed with the
            # backend environment) outlives this process. The store is
            # internally locked, but the mutation holds the session lock
            # anyway — the cache-lock-discipline contract all session
            # caches share
            with session.cache_lock:
                store.record(AOTC.promotion_key_str(key), rec)
        if self.tracer is not None:
            self.tracer.emit(
                "kernel_span", kernel=f"{kname}:jnp",
                dur_ms=rec["jnp_ms"], n=key[1],
            )
            self.tracer.emit(
                "kernel_span", kernel=f"{kname}:pallas",
                dur_ms=rec["pallas_ms"], n=key[1],
            )
        return rec

    def _pallas_promoted(
        self, fn, sdata, gid, weight, gcap, pvals, pgid, interpret
    ) -> bool:
        """One-time measured A/B per (fn, rows-bucket, group-bucket) shape,
        memoized on the session (`Session.pallas_promotions`): warm both
        paths (executables land in the jit caches either way), then time
        one synchronized call each; the Pallas route is used only where it
        measured faster. Both measurements emit `kernel_span` events so
        `profile` can show the promotion evidence per shape."""
        key = (fn, int(sdata.shape[0]), int(gcap))
        rec = self._promotion_rec(key)
        if rec is None:
            # nds-lint: disable=local-import
            from ..ops import pallas_kernels as PK

            def run_jnp():
                return K.segment_reduce_with_count(
                    sdata, gid, weight, gcap, fn
                )

            if fn == "sum":
                def run_pallas():
                    return PK.segment_sums_pallas(
                        pvals, pgid, gcap, interpret=interpret
                    )
            else:
                def run_pallas():
                    return PK.segment_extreme_pallas(
                        pvals, pgid, gcap, fn == "max", interpret=interpret
                    )

            rec = self._measure_promotion(
                key, run_jnp, run_pallas, f"segment_{fn}"
            )
        return rec["use"]

    @_tally.seamed("agg")
    def _eval_distinct_agg(self, agg, ev, child, subset, key_cols, gcap,
                           ngroups, key_words=None):
        """count(distinct x) / sum(distinct x): two-level grouping.

        Null values of x stay live through both passes (so every outer group
        survives and positions align with the main aggregation pass, which
        enumerates groups in the same sorted-key order) but carry zero weight
        in the final reduction (distinct aggs ignore nulls)."""
        c = ev.eval(agg.arg)
        live = self._current_agg_live
        d = c.data
        if d.dtype == jnp.bool_:
            d = d.astype(jnp.int32)
        # the main pass's outer-key words: monotone codes keep group
        # enumeration order identical across passes, so positions align
        gwords = list(key_words) if key_words else []
        if gwords:
            vwords = self._sort_words(
                [(d, c.valid, True, True)], [c], live, include_live=False
            )
            words2 = gwords + vwords
        else:
            words2 = self._sort_words([(d, c.valid, True, True)], [c], live)
        order2, gid2, ng2 = K.group_by_words(
            words2, live, self._current_agg_nlive
        )
        g2cap = bucket_cap(max(ng2, 1))
        first2 = K.segment_starts(gid2, g2cap)
        rows2 = order2[jnp.clip(first2, 0, child.cap - 1)]
        live2 = jnp.arange(g2cap) < ng2
        # re-group the distinct rows by the outer keys only. A fresh live2
        # word leads: the gathered words' embedded live bit reflects the
        # ORIGINAL rows' liveness, not the distinct slots' (dead slots gather
        # an arbitrary live row when the table has no dead tail).
        if gwords:
            okeys = [jnp.where(live2, jnp.int64(0), jnp.int64(1))]
            okeys += K.take_arrays(gwords, rows2)
            order3, gid3, ng3 = K.group_by_words(okeys, live2)
        else:
            # global distinct: reductions are order-independent
            order3 = jnp.arange(g2cap, dtype=jnp.int32)
            gid3 = jnp.zeros(g2cap, jnp.int32)
            ng3 = 1 if ng2 > 0 else 0
        if ng3 == 0:
            ng3 = 1
        g3cap = bucket_cap(ng3)
        # the distinct slots' value and validity, then in outer-group order
        (slot,) = K.take_columns(((c.data, c.valid),), rows2)
        (vals, cvalid3), (w3, _) = K.take_columns(
            (slot, (live2, None)), order3
        )
        if cvalid3 is not None:
            w3 = w3 & cvalid3
        if agg.fn == "count":
            out = K.segment_reduce(vals, gid3, w3, g3cap, "count")
            col = Column(out.astype(jnp.int64), INT64)
        elif agg.fn == "sum":
            out, n = K.segment_reduce_with_count(vals, gid3, w3, g3cap, "sum")
            col = Column(out, c.dtype if c.dtype.kind != "int32" else INT64, n > 0)
        elif agg.fn == "avg":
            s, n = K.segment_reduce_with_count(vals, gid3, w3, g3cap, "sum")
            v = s.astype(jnp.float64) / jnp.maximum(n, 1)
            if c.dtype.is_decimal:
                v = v / 10**c.dtype.scale
            col = Column(v, FLOAT64, n > 0)
        else:
            raise ExecError(f"distinct agg {agg.fn}")
        return col

    # ------------------------------------------------------------------
    def _exec_window(self, node: P.Window) -> Table:
        # windows sort and scan several word/rank arrays at the input cap:
        # always pack masked inputs first (memory AND time win)
        child = self.execute(node.child).compacted()
        out_cols = {n: c.disowned() for n, c in child.columns.items()}
        for wf, name in node.fns:
            out_cols[name] = self._eval_window(child, wf)
        return Table(out_cols, child.nrows_lazy, live=child.live)

    @_tally.seamed("window")
    def _eval_window(self, child: Table, wf: E.WindowFn) -> Column:
        ev = self._evaluator(child)
        live = child.row_mask()
        pkeys = []
        pcols = []
        for e in wf.partition_by:
            c = ev.eval(e)
            d = c.data.astype(jnp.int32) if c.data.dtype == jnp.bool_ else c.data
            pkeys.append((d, c.valid, True, True))
            pcols.append(c)
        okeys = []
        ocols = []
        for e, asc in wf.order_by:
            c = ev.eval(e)
            d = c.data
            if c.dtype.is_string:
                d, _ = sort_dictionary(c)
            if d.dtype == jnp.bool_:
                d = d.astype(jnp.int32)
            okeys.append((d, c.valid, asc, asc))
            ocols.append(c)
        # partition words carry the live bit (dead rows last); order words
        # are a separate list so partition boundaries can be read off the
        # sorted partition words alone
        pwords = self._sort_words(pkeys, pcols, live)
        owords = self._sort_words(okeys, ocols, live, include_live=False)
        order = K.sort_by_words(pwords + owords)
        sorted_words = K.take_arrays(pwords + owords, order)
        sorted_ow = list(sorted_words[len(pwords):])
        # partition group ids over sorted rows
        if pkeys:
            sorted_p = list(sorted_words[:len(pwords)])
            flags = K._word_flags(sorted_p)
            gid = K.fast_cumsum(flags.astype(jnp.int32)) - 1
            nlive = child.nrows
            ng = int(host_read("ngroups", gid[nlive - 1])) + 1 if nlive else 0
        else:
            gid = jnp.zeros(child.cap, jnp.int32)
            ng = 1 if child.nrows else 0
        gcap = bucket_cap(max(ng, 1))
        inv = jnp.zeros(child.cap, jnp.int32).at[order].set(
            jnp.arange(child.cap, dtype=jnp.int32)
        )

        fn = wf.fn
        if fn in ("rank", "dense_rank", "row_number"):
            pos = K.running_position(gid)
            if fn == "row_number":
                vals = pos + 1
            else:
                # order-group boundaries within partitions (ties share a rank)
                oflags = K._word_flags([gid] + sorted_ow)
                ogid = K.fast_cumsum(oflags.astype(jnp.int32)) - 1
                part_first = K.segment_starts(gid, gcap)
                if fn == "dense_rank":
                    # count of order-group starts since the partition start
                    cums = K.fast_cumsum(oflags.astype(jnp.int32))
                    base = cums[jnp.clip(part_first, 0, child.cap - 1)]
                    vals = cums - base[gid] + 1
                else:
                    # rank: 1 + rows before the first row of this order-group
                    n_og = int(host_read("ngroups", ogid[child.nrows - 1])) + 1 if child.nrows else 1
                    og_first_pos = K.segment_starts(ogid, bucket_cap(max(n_og, 1)))
                    vals = og_first_pos[ogid] - part_first[gid] + 1
            out_sorted = vals.astype(jnp.int64)
            data = out_sorted[inv]
            return Column(data.astype(jnp.int64), INT64, None)

        # aggregate-over-partition functions
        if fn not in ("sum", "avg", "min", "max", "count"):
            raise ExecError(f"window fn {fn}")
        if wf.arg is None and fn == "count":
            c = None
            sdata = jnp.ones(child.cap, jnp.int64)[order]
            w = live[order]
            dtype = INT64
        else:
            c = ev.eval(wf.arg)
            if c.dtype.is_string and fn in ("min", "max"):
                # rank-transform codes so min/max compares lexicographically
                # (raw dictionary codes are in encounter order)
                ranks, sorted_dict = sort_dictionary(c)
                c = Column(ranks, c.dtype, c.valid, sorted_dict)
            (sdata, svalid), (w, _) = K.take_columns(
                ((c.data, c.valid), (live, None)), order
            )
            if svalid is not None:
                w = w & svalid
            dtype = c.dtype

        # Classify the frame. SQL default: whole partition without ORDER BY,
        # RANGE UNBOUNDED PRECEDING..CURRENT ROW (including peers) with it.
        frame = wf.frame
        whole = (not wf.order_by and frame is None) or frame == (
            ("unbounded", "preceding"),
            ("unbounded", "following"),
        )
        if whole:
            red_map = {"sum": "sum", "min": "min", "max": "max",
                       "count": "count", "avg": "sum"}
            red, counts = K.segment_reduce_with_count(
                sdata, gid, w, gcap, red_map[fn]
            )
            return self._window_result(
                fn, red[gid][inv], counts[gid][inv], c, dtype
            )

        if fn in ("min", "max"):
            # running min/max (q51: `rows unbounded preceding..current row`)
            # via rank-transform + native cummax (exact; see
            # K.segmented_running_extreme — a flag-carrying
            # lax.associative_scan compiled for minutes at fact shapes)
            if frame not in (
                (("unbounded", "preceding"), ("current", None)),
                None,
            ):
                raise ExecError(f"window {fn} over frame {frame}")
            sorted_vals, rank = K.value_rank(sdata)
            scanned = K.segmented_running_extreme(
                sorted_vals, rank, gid, w, fn == "max"
            )
            cnt_run = _segment_cumsum(w.astype(jnp.int64), gid)
            if frame is None:
                # RANGE default: current row's peers (equal order keys) are
                # in-frame, so read the running value at the peer-group end
                oflags = K._word_flags([gid] + sorted_ow)
                ogid = K.fast_cumsum(oflags.astype(jnp.int32)) - 1
                n_og = int(host_read("ngroups", ogid[child.nrows - 1])) + 1 if child.nrows else 1
                ogcap = bucket_cap(max(n_og, 1))
                og_first = K.segment_starts(ogid, ogcap)
                og_count = K.segment_reduce(
                    jnp.ones_like(ogid, jnp.int64), ogid,
                    jnp.ones(ogid.shape, bool), ogcap, "count",
                )
                og_end = (og_first.astype(jnp.int64) + og_count - 1)[ogid]
                og_end = jnp.clip(og_end, 0, child.cap - 1).astype(jnp.int32)
                scanned = scanned[og_end]
                cnt_run = cnt_run[og_end]
            return self._window_result(
                fn, scanned[inv], cnt_run[inv], c, dtype
            )

        x = jnp.where(w, sdata, jnp.zeros((), sdata.dtype))
        if jnp.issubdtype(x.dtype, jnp.integer):
            x = x.astype(jnp.int64)
        csum = _segment_cumsum(x, gid)
        cnt = _segment_cumsum(w.astype(jnp.int64), gid)

        if frame is None or frame == (("unbounded", "preceding"), ("current", None)):
            if frame is None:
                # RANGE: current row's peers (equal order keys) are included,
                # so take the cumulative value at the END of the peer group
                oflags = K._word_flags([gid] + sorted_ow)
                ogid = K.fast_cumsum(oflags.astype(jnp.int32)) - 1
                n_og = int(host_read("ngroups", ogid[child.nrows - 1])) + 1 if child.nrows else 1
                ogcap = bucket_cap(max(n_og, 1))
                og_first = K.segment_starts(ogid, ogcap)
                og_count = K.segment_reduce(
                    jnp.ones_like(ogid, jnp.int64), ogid,
                    jnp.ones(ogid.shape, bool), ogcap, "count",
                )
                og_end = (og_first.astype(jnp.int64) + og_count - 1)[ogid]
                og_end = jnp.clip(og_end, 0, child.cap - 1).astype(jnp.int32)
                s_out = csum[og_end]
                c_out = cnt[og_end]
            else:
                s_out = csum
                c_out = cnt
            return self._window_result(
                fn, s_out[inv],
                c_out[inv], c, dtype,
            )

        # bounded ROWS frame: sum over [pos-a, pos+b] via cumsum differences
        (lo_n, lo_u), (hi_n, hi_u) = frame
        part_first = K.segment_starts(gid, gcap)
        pos = jnp.arange(child.cap, dtype=jnp.int64)
        start_of_part = part_first[gid].astype(jnp.int64)
        part_count = K.segment_reduce(
            jnp.ones(child.cap, jnp.int64), gid, live[order], gcap, "count"
        )
        end_of_part = start_of_part + part_count[gid] - 1

        def bound_lo_raw():
            if (lo_n, lo_u) == ("unbounded", "preceding"):
                return start_of_part
            if (lo_n, lo_u) == ("current", None):
                return pos
            if lo_u == "preceding":
                return pos - int(lo_n)
            return pos + int(lo_n)  # N following

        def bound_hi_raw():
            if (hi_n, hi_u) == ("unbounded", "following"):
                return end_of_part
            if (hi_n, hi_u) == ("current", None):
                return pos
            if hi_u == "following":
                return pos + int(hi_n)
            return pos - int(hi_n)  # N preceding

        lo_raw = bound_lo_raw()
        hi_raw = bound_hi_raw()
        # the true frame is [lo_raw, hi_raw] intersected with the partition;
        # it can be EMPTY (e.g. `2 preceding and 1 preceding` at the first
        # row) — clamping alone would fake a one-row frame
        empty = (hi_raw < lo_raw) | (hi_raw < start_of_part) | (lo_raw > end_of_part)
        lo = jnp.clip(
            jnp.maximum(lo_raw, start_of_part), 0, child.cap - 1
        ).astype(jnp.int32)
        hi = jnp.clip(
            jnp.minimum(hi_raw, end_of_part), 0, child.cap - 1
        ).astype(jnp.int32)
        s_hi = csum[hi]
        c_hi = cnt[hi]
        s_lo = jnp.where(lo > 0, csum[jnp.maximum(lo - 1, 0)], jnp.zeros((), csum.dtype))
        c_lo = jnp.where(lo > 0, cnt[jnp.maximum(lo - 1, 0)], 0)
        # _segment_cumsum restarts at partition bounds: when lo is the
        # partition start, lo-1 points into the previous partition, so the
        # baseline is 0, not csum[lo-1]
        at_start = lo == start_of_part.astype(jnp.int32)
        s_lo = jnp.where(at_start, jnp.zeros((), csum.dtype), s_lo)
        c_lo = jnp.where(at_start, 0, c_lo)
        s_out = jnp.where(empty, jnp.zeros((), csum.dtype), s_hi - s_lo)
        c_out = jnp.where(empty, 0, c_hi - c_lo)
        return self._window_result(fn, s_out[inv], c_out[inv], c, dtype)

    def _window_result(self, fn, red, counts, c, dtype):
        if fn == "count":
            return Column(counts.astype(jnp.int64), INT64)
        if fn == "avg":
            vals = red.astype(jnp.float64) / jnp.maximum(counts, 1)
            if c is not None and c.dtype.is_decimal:
                vals = vals / 10**c.dtype.scale
            return Column(vals, FLOAT64, counts > 0)
        if fn in ("min", "max"):
            return Column(red, dtype, counts > 0, None if c is None else c.dictionary)
        # sum
        out_dtype = dtype
        if dtype.kind == "int32":
            out_dtype = INT64
        return Column(red, out_dtype, counts > 0)

    # ------------------------------------------------------------------
    # shared helpers
    def _evaluator(self, table: Table) -> Evaluator:
        ex = self

        class _Ev(Evaluator):
            def _eval_scalarsubquery(self, e):
                val, dtype, dictionary = ex._scalar_value(e)
                cap = self.table.cap
                if val is None:
                    return Column(
                        jnp.zeros(cap, dtype.device_np_dtype()),
                        dtype,
                        jnp.zeros(cap, bool),
                        dictionary,
                    )
                return Column(
                    jnp.full(cap, val, dtype.device_np_dtype()),
                    dtype,
                    None,
                    dictionary,
                )

        return _Ev(table)

    def _scalar_value(self, e: E.ScalarSubquery):
        key = id(e.plan)
        if key in self._scalar_cache:
            return self._scalar_cache[key]
        tracer = self.tracer
        if tracer is not None:
            t0_ns, t0, cols0 = _time_ns(), _perf(), self._scan_cols
        cache = self._session_cache()
        fp = got = None
        if cache is not None:
            fp = self._fp(e.plan) + ":" + e.out_name
            got = cache.scalars.get(fp)
        source = "executed" if got is None else "session-cache"
        if got is None:
            # the plan may yield a deferred-compaction table whose single
            # live row is NOT at index 0 — pack before slicing
            t = self.execute(e.plan).compacted()
            col = t.columns[e.out_name]
            if t.nrows == 0:
                got = (None, col.dtype, col.dictionary)
            else:
                # one batched transfer for value + validity (vs two RTTs)
                fetch = [col.data[:1]]
                if col.valid is not None:
                    fetch.append(col.valid[:1])
                read = host_read("scalar", fetch)
                valid = True if col.valid is None else bool(read[1][0])
                got = (read[0][0] if valid else None, col.dtype,
                       col.dictionary)
            if cache is not None:
                cache.scalars[fp] = got
        self._scalar_cache[key] = got
        if tracer is not None:
            # one a distinct subquery plan a statement: the statement's own
            # memo above answers a repeat without an event. `cols_read`:
            # the columns this execution's scans asked of the catalog
            tracer.emit(
                "scalar_subquery", out_name=e.out_name, source=source,
                dur_ms=round((_perf() - t0) * 1000.0, 3), t0_ns=t0_ns,
                cols_read=self._scan_cols - cols0, null=got[0] is None,
                exec_id=self._exec_id, depth=self._span_depth - 1,
            )
        return got

    def _masked(self, table: Table, mask, transient: bool = False) -> Table:
        """Deferred compaction: keep rows in place under a live mask, with
        the count queued asynchronously (a device->host sync blocks the
        host; a full compaction also pays one gather per column). Downstream operators consume row_mask() directly; packing
        happens lazily at collect()/limit via Table.compacted().

        Columns are shared by reference, so ownership is stripped unless
        the caller passes `transient=True` to assert `table` is a
        function-local temporary no cache or second consumer retains
        (e.g. a join's just-minted pair table under a residual filter)."""
        cols = (
            dict(table.columns)
            if transient
            else {n: c.disowned() for n, c in table.columns.items()}
        )
        with _tally.eager("mask"):
            count = jnp.sum(mask, dtype=jnp.int32)
        return Table(cols, count, live=mask, unique_key=table.unique_key)

    def _compact(self, table: Table, mask) -> Table:
        count = K.mask_count(mask)
        cap = bucket_cap(max(count, 1))
        idx = K.compact_indices(mask, cap)
        return self._take(table, idx, count)

    def _take(self, table: Table, idx, nrows) -> Table:
        # idx is a permutation or de-duplicated subset of live rows
        # (sort order / compact indices), so base-table stats stay valid;
        # gather outputs are fresh owned buffers
        return Table(gather_columns(table.columns, idx, owned=True), nrows)

    def _distinct_table(self, t: Table, spill_parts=0) -> Table:
        t = self._pack_sparse(t)
        if spill_parts > 1 and t.columns:
            out = self._spilled_distinct(t, spill_parts)
            if out is not None:
                return out
        live = t.row_mask()
        words = self._group_words(list(t.columns.values()), live)
        order, gid, ng = K.group_by_words(words, live, t.nrows)
        gcap = bucket_cap(max(ng, 1))
        first = K.segment_starts(gid, gcap)
        with _tally.eager("distinct"):
            rows = order[jnp.clip(first, 0, t.cap - 1)]
        out = self._take(t, rows, ng)
        out.unique_key = frozenset(out.columns)
        return out

    # -- out-of-core (spilled) execution --------------------------------
    # The host-RAM spill pool tier (engine/spill.py): when a plan's peak
    # materialization cannot fit HBM, the three remaining additive-capacity
    # shapes — build-side-too-big hash joins, full-table sorts, whole-input
    # distinct — run partitioned/windowed with intermediates staged in the
    # budgeted host pool (disk-backed past its budget). Engagement:
    # `engine.spill` off|auto|force — `auto` (default) spills exactly the
    # nodes the static plan budgeter annotated with `spill_partitions`
    # (verdict `spill`, analysis/budget.py); `force` (set by the report
    # ladder's spill_retry rung after an unpredicted device OOM) routes
    # every eligible node. Results are identical to the direct paths:
    # the external sort reuses the direct path's exact permutation, and
    # hash partitioning is value-exact for joins/distinct (SQL leaves
    # their row order undefined; only the partition-major order differs).

    #: partitions used under `engine.spill=force` when no explicit
    #: `engine.spill_partitions` is set (the spill_retry rung sets one)
    _SPILL_FORCE_PARTS = SP.DEFAULT_FORCE_PARTITIONS

    def _spill_parts_for(self, node) -> int:
        """Partition/run count for out-of-core execution of `node`, or 0
        for the direct path. Annotation-driven in `auto` mode so unspilled
        plans pay one getattr; `force` spills every eligible node."""
        session = getattr(self.catalog, "session", None)
        if session is None:
            return 0
        mode = str(session.conf.get("engine.spill", "auto")).lower()
        if mode == "off":
            return 0
        if mode == "force":
            try:
                p = int(session.conf.get("engine.spill_partitions", 0) or 0)
            except (TypeError, ValueError):
                p = 0
            return p if p > 1 else self._SPILL_FORCE_PARTS
        try:
            return int(getattr(node, "spill_partitions", 0) or 0)
        except (TypeError, ValueError):
            return 0

    def _spill_finish(self, op, parts, pool, before, segments,
                      t0=None) -> Table:
        """Assemble a spilled op's segments into one device table, record
        the statement-level spill evidence (executor + session markers,
        `spill` trace event — with the out-of-core step's measured wall
        when the caller timed it, the critical-path spill-io cause) and
        release the segments."""
        try:
            out = SP.assemble_segments(pool, segments)
        finally:
            pool.release(segments)
        delta = {
            k: pool.stats[k] - before.get(k, 0)
            for k in ("bytes_in", "bytes_out", "evictions")
        }
        note = self.last_spill or {
            "ops": 0, "partitions": 0, "bytes_in": 0, "bytes_out": 0,
            "evictions": 0,
        }
        note["ops"] += 1
        note["partitions"] = max(note["partitions"], parts)
        for k in ("bytes_in", "bytes_out", "evictions"):
            note[k] += delta[k]
        self.last_spill = note
        session = getattr(self.catalog, "session", None)
        if session is not None:
            session.last_spill = note
        if self.tracer is not None:
            self.tracer.emit(
                "spill", op=op, partitions=parts,
                bytes_in=delta["bytes_in"], bytes_out=delta["bytes_out"],
                evictions=delta["evictions"], rows=out.nrows_known,
                **({"dur_ms": round((_perf() - t0) * 1000.0, 3)}
                   if t0 is not None else {}),
            )
        return out

    def _spilled_join(self, left, right, kind, left_keys, right_keys,
                      residual, lk, lv, llive, rk, rv, rlive, parts,
                      out=None) -> Table:
        """Partitioned (Grace-style) hash join through the spill pool: both
        sides hash-partition on the join key, each partition pair joins
        with the regular engine paths (keys/residual re-evaluated over the
        compacted partitions), and each partition's output spills to the
        host pool so only one partition's pair table is ever live in HBM.
        Exact: equal keys share a partition, so the union of per-partition
        join results is the direct join result (null-keyed left rows land
        in some partition, never match, and null-extend under LEFT —
        exactly as the direct path treats them)."""
        session = self.catalog.session
        pool = session.spill_pool
        before = dict(pool.stats)
        sp_t0 = _perf()
        lp = K.hash_columns(lk, lv) % parts
        rp = K.hash_columns(rk, rv) % parts
        segments = []
        was_disabled = self._exchange_disabled
        self._exchange_disabled = True
        try:
            for p in range(parts):
                lpart = self._compact(left, (lp == p) & llive)
                if lpart.nrows == 0 and segments:
                    continue  # empty probe side: this partition is empty
                rpart = self._compact(right, (rp == p) & rlive)
                if kind == "inner" and rpart.nrows == 0 and segments:
                    continue  # (LEFT must still null-extend its rows)
                segments.append(pool.put(self._join(
                    lpart, rpart, kind, left_keys, right_keys, residual,
                    out=out,
                )))
                session.spill_progress()
            return self._spill_finish("join", parts, pool, before, segments,
                                      t0=sp_t0)
        except BaseException:
            pool.release(segments)
            raise
        finally:
            self._exchange_disabled = was_disabled

    def _spilled_take(self, child: Table, order, parts, op="sort"):
        """External sort tail: gather the sorted output in bounded windows
        of the direct path's OWN permutation, staging each sorted run in
        the host pool, then upload the assembled result once per column —
        peak device transient is O(window x width) instead of every
        column's full-capacity gather at once. Returns None when the input
        is too small to window (callers fall through to the direct take).
        Bit-identical to the direct path: same `order`, same row order."""
        wcap = bucket_cap(max(child.cap // parts, 1))
        if wcap >= child.cap:
            return None
        session = self.catalog.session
        pool = session.spill_pool
        before = dict(pool.stats)
        sp_t0 = _perf()
        nrows = child.nrows
        segments = []
        try:
            for start in range(0, child.cap, wcap):
                n_w = min(max(nrows - start, 0), wcap)
                if n_w <= 0 and segments:
                    break
                idx = _dyn_slice(order, start, wcap)
                cols = gather_columns(
                    child.columns, idx, stats=lambda c: None
                )
                segments.append(pool.put(Table(cols, n_w)))
                session.spill_progress()
            return self._spill_finish(op, parts, pool, before, segments,
                                      t0=sp_t0)
        except BaseException:
            pool.release(segments)
            raise

    def _spilled_distinct(self, t: Table, parts):
        """Spilling distinct: partition-hash dedup. Rows hash-partition
        over ALL columns (valid flags folded in, so NULLs — which distinct
        treats as equal — colocate), each partition dedups with the direct
        sort-word machinery, and partition results stage in the host pool.
        Exact as a row set: equal rows share a partition, partitions are
        disjoint. Returns None for empty input (direct path handles it)."""
        t = t.compacted()
        if t.nrows == 0:
            return None
        session = self.catalog.session
        pool = session.spill_pool
        before = dict(pool.stats)
        sp_t0 = _perf()
        live = t.row_mask()
        h = K.hash_columns(
            [c.data for c in t.columns.values()],
            [c.valid for c in t.columns.values()],
        ) % parts
        segments = []
        try:
            for p in range(parts):
                part = self._compact(t, (h == p) & live)
                if part.nrows == 0:
                    if not segments:
                        segments.append(pool.put(part))  # schema carrier
                    continue
                segments.append(pool.put(self._distinct_table(part)))
                session.spill_progress()
            out = self._spill_finish("distinct", parts, pool, before,
                                     segments, t0=sp_t0)
        except BaseException:
            pool.release(segments)
            raise
        out.unique_key = frozenset(out.columns)
        return out

    @_tally.seamed("concat")
    def _concat(self, a: Table, b: Table) -> Table:
        """Masked concatenation: columns append at full capacity (padded to
        a power-of-two bucket) under a combined live mask — no repacking
        gathers and no count syncs (union chains were paying both per
        level)."""
        names = list(a.columns)
        bnames = list(b.columns)
        cap = bucket_cap(max(a.cap + b.cap, 1))
        pad_n = cap - a.cap - b.cap
        live = jnp.pad(
            jnp.concatenate([a.row_mask(), b.row_mask()]), (0, pad_n)
        )
        n_lazy = (
            a.nrows_lazy + b.nrows_lazy
        )  # int + int stays host; device scalars stay lazy
        cols = {}
        for an, bn in zip(names, bnames):
            ca, cb = a.columns[an], b.columns[bn]
            # unify dtypes
            if ca.dtype.is_string or cb.dtype.is_string:
                (da, db), uni = _share_dictionary([ca, cb])
                dtype = ca.dtype
                dictionary = uni
            else:
                dtype = _common_dtype([ca.dtype, cb.dtype])
                da = _cast_column(ca, dtype, ca.data.shape[0])
                db = _cast_column(cb, dtype, cb.data.shape[0])
                dictionary = None
            data = jnp.pad(jnp.concatenate([da.data, db.data]), (0, pad_n))
            if da.valid is None and db.valid is None:
                valid = None
            else:
                va = da.valid if da.valid is not None else jnp.ones(a.cap, bool)
                vb = db.valid if db.valid is not None else jnp.ones(b.cap, bool)
                valid = jnp.pad(jnp.concatenate([va, vb]), (0, pad_n))
            cols[an] = Column(data, dtype, valid, dictionary)
        return Table(cols, n_lazy, live=live)


def _segment_cumsum(x, gid):
    """Cumulative sum within segments (gid sorted ascending)."""
    total = K.fast_cumsum(x)
    n = x.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.zeros(n, bool).at[0].set(True).at[1:].max(gid[1:] != gid[:-1])
    # propagate each row's own segment-start index forward. Native cummax,
    # NOT associative_scan: the generic log-depth scan construction
    # compiles for minutes at fact shapes on this toolchain.
    seg_start = K.fast_cummax(jnp.where(is_start, idx, 0))
    base = jnp.where(
        seg_start > 0, total[jnp.maximum(seg_start - 1, 0)], jnp.zeros((), total.dtype)
    )
    return total - base
