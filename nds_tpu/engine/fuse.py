"""Pipeline fusion: whole-chain compilation of Filter/Project pipelines.

The eager executor pays a jit dispatch, an HLO round-trip, and (for
projections) a materialized intermediate per plan node. This module is the
engine's whole-stage-codegen seam (the reference gets the equivalent from
Spark fusing scan->filter->project into one compiled loop): a plan-rewrite
pass (`mark_pipelines`) replaces every maximal linear Filter/Project chain
with a single `plan.Pipeline` node, and the executor compiles that chain
as ONE jitted function over the child's device columns.

Fusion mechanics (correctness by construction):

  * The jitted function traces the SAME `expr.Evaluator` the eager path
    runs, so fused and unfused results are identical by construction —
    bit-exact for integer/decimal/date/string/bool data. Float64
    expressions can differ in the FINAL ULP only: XLA's algebraic
    simplifier sees the whole fused expression and may reassociate
    division chains that eager per-op dispatch rounds individually
    (measured <= 1e-12 relative on the windowed-ratio templates, vs the
    validator's 1e-5 epsilon contract). Host-side work the evaluator does
    over column dictionaries (LIKE lookup tables, IN lists, dictionary
    unification) runs once at trace time and bakes into the executable as
    constants — steady-state calls skip it entirely.
  * Outputs that merely pass an input buffer through (filter stages touch
    no column data; plain-Col projection items) are detected at build time
    by tracer identity and PRUNED from the jit signature: the output Table
    references the input buffers directly, and jax drops the then-unused
    inputs, so a fused filter allocates exactly what the eager
    deferred-compaction path allocates (one mask, one queued count) in one
    dispatch instead of one per plan node and expression op.
  * Masks and compaction stay deferred to the pipeline boundary: the fused
    function folds every filter predicate into a single live mask and
    queues the output count asynchronously, exactly like exec._masked.
  * When the input table has no mask (live=None), the live mask is built
    INSIDE the jit from a scalar row count (`count` mode) — no mask buffer
    crosses the boundary at all. When a mask must be passed and the chain
    consumes it (does not pass it through), `engine.fuse_donate=on`
    donates its buffer to the executable. Donation is opt-in: probe-style
    join outputs alias their left input's live mask across operator
    boundaries, and plan-cached tables outlive the statement, so blanket
    donation can invalidate a buffer another table still references (see
    README "Performance").

Shape-bucketed executable reuse: inputs already ride power-of-two capacity
buckets (columnar.bucket_cap), and jax caches one executable per (traced
function, input shapes). `ExecutableCache` keys the traced function by
(pipeline structure fingerprint, input dtype signature) and tracks the
(key, bucket) pairs already compiled, so steady-state re-runs AND
structurally identical queries across a stream reuse executables; the
hit/miss stream is observable as `exec_cache` trace events and enforced by
ci/tier1-check's microbench guard (`profile --min_exec_cache_hit_rate`).
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import replace as _dc_replace

import jax
import jax.numpy as jnp

from ..dtypes import FLOAT64, INT64
from ..obs.tally import host_read
from ..ops import kernels as K
from . import expr as E
from . import plan as P
from .aotcache import xla_cache_hits
from .columnar import (
    Column, Table, bucket_cap, narrow_columns, sort_dictionary,
)
from .expr import Evaluator


# ---------------------------------------------------------------------------
# plan rewrite: absorb Filter/Project chains into Pipeline nodes
# ---------------------------------------------------------------------------

# a pipeline child whose live mask may be donated must be a single-consumer
# intermediate no cache retains AND whose mask it owns: scans alias catalog
# buffers; Aggregate/Distinct/SetOp/Window results live in the session plan
# cache across statements; binary Join outputs alias their LEFT input's
# live mask on the left/mark augment paths (exec._augment_join_output), so
# donating their mask would invalidate a buffer the left table still
# references. MultiJoin stays eligible: its inner/cross steps always mint a
# fresh mask (matched / compacted / residual) owned by the output alone.
_NO_DONATE_CHILD = (P.Scan, P.MaterializedScan, P.Join, P.Aggregate,
                    P.Distinct, P.SetOp, P.Window)


def _expr_fusible(e) -> bool:
    """True when an expression can trace inside one jitted function:
    anything except subqueries (they execute whole plans and fetch scalars
    to the host) and aggregate/window functions (never scalar-evaluated).
    Host-side dictionary work (LIKE, IN, string functions) is fine — it
    runs at trace time over concrete dictionaries. Chains that still fail
    to trace (e.g. numeric->string casts, which format device values on
    host) are caught at build time and pinned to the eager path."""
    for x in E.walk(e):
        if isinstance(
            x, (E.SubqueryExpr, E.ScalarSubquery, E.Agg, E.WindowFn)
        ):
            return False
    return True


def _stage_fusible(n) -> bool:
    if isinstance(n, P.Filter):
        return _expr_fusible(n.predicate)
    if isinstance(n, P.Project):
        return bool(n.items) and all(_expr_fusible(e) for e, _ in n.items)
    return False


def _agg_fusible(n: P.Aggregate) -> bool:
    """True when an Aggregate can become a Pipeline's fused tail: plain
    shape only (no grouping sets — the rollup cascade re-aggregates across
    levels; no blocked_union — the windowed path owns those), every
    aggregate decomposable (sum/min/max/count/avg, no distinct — the same
    predicate the blocked-union path gates on), and every key/argument
    expression traceable. Whether the key DOMAIN is small enough for the
    direct scatter is a data property checked at build time (column stats);
    ineligible inputs pin to the eager path per input signature."""
    if n.grouping_sets is not None or n.blocked_union:
        return False
    if not P.aggs_decomposable(n.aggs):
        return False
    for e, _ in n.keys:
        if not _expr_fusible(e):
            return False
    for a, _ in n.aggs:
        if a.arg is not None and not _expr_fusible(a.arg):
            return False
    return True


def _chain_worth_fusing(stages) -> bool:
    """A pure-rename/subset chain gains nothing from compilation (the eager
    path reuses the input column objects outright); fuse only when the
    chain filters or computes something."""
    for s in stages:
        if isinstance(s, P.Filter):
            return True
        if any(not isinstance(e, E.Col) for e, _ in s.items):
            return True
    return False


def _count_refs(node) -> dict:
    """Plan-node reference counts (subquery plans riding in expressions
    included). A shared wrapper must not be absorbed into a pipeline: the
    detached copy would defeat the executor's by-identity result reuse."""
    refs = {}
    seen = set()

    def visit(v):
        if isinstance(v, (P.PlanNode, E.Expr)):
            if isinstance(v, P.PlanNode):
                refs[id(v)] = refs.get(id(v), 0) + 1
            if id(v) in seen:
                return
            seen.add(id(v))
            for f in dataclasses.fields(v):
                visit(getattr(v, f.name))
        elif isinstance(v, (list, tuple)):
            for x in v:
                visit(x)

    visit(node)
    return refs


def _donate_ok_child(cur, refs) -> bool:
    """Plan-level donation clearance for a pipeline's child: the child's
    result must be single-consumer, never retained by a cross-statement
    cache (Aggregate/Distinct/SetOp/Window AND agg-tail Pipelines live in
    the session plan cache), and never an aliasing producer
    (_NO_DONATE_CHILD). WHICH buffers are then actually donatable is a
    runtime property (Column.owned + passthrough analysis in the fused
    call); this gate only proves no OTHER plan node can observe them."""
    if refs.get(id(cur), 1) > 1:
        return False
    if isinstance(cur, _NO_DONATE_CHILD):
        return False
    if isinstance(cur, P.Pipeline) and cur.agg is not None:
        return False  # plan-cached, same as a raw Aggregate
    return True


def mark_pipelines(node: P.PlanNode, fuse_aggs: bool = True):
    """Rewrite every maximal linear Filter/Project chain (anywhere in the
    tree, subquery plans included) into one `plan.Pipeline` node; with
    `fuse_aggs` (conf `engine.fuse_agg`, on by default), a plain
    decomposable Aggregate additionally absorbs the chain FEEDING it and
    becomes the Pipeline's fused aggregate tail — the whole
    scan→filter→project→partial-aggregate run then compiles as one
    dispatch (engine/fuse.py:FusedAggPipeline).

    Returns (root, count): the root itself may head a chain, so callers
    must adopt the returned root; `count` is the number of pipelines
    created (plan-introspection aid for tests/tools)."""
    refs = _count_refs(node)
    made = 0
    seen = set()

    def chain_under(n):
        """(detached stages in execution order, chain input) for the
        maximal fusible single-consumer Filter/Project chain headed at
        `n` (possibly empty)."""
        topdown = []
        cur = n
        while isinstance(cur, (P.Filter, P.Project)) and _stage_fusible(cur):
            # shared nodes keep their identity (the executor caches results
            # by id): a chain stops at the first node with a second parent
            if refs.get(id(cur), 1) > 1:
                break
            topdown.append(cur)
            cur = cur.child
        stages = []
        for s in reversed(topdown):  # execution (innermost-first) order
            if isinstance(s, P.Filter):
                stages.append(dataclasses.replace(s, child=None))
            else:
                stages.append(P.Project(items=list(s.items), child=None))
        return stages, cur

    def absorb(n):
        """The Pipeline replacing chain head `n`, or `n` unchanged."""
        nonlocal made
        if (
            fuse_aggs
            and isinstance(n, P.Aggregate)
            and refs.get(id(n), 1) <= 1
            and _agg_fusible(n)
        ):
            # the aggregate tail + the chain feeding it fuse into ONE node;
            # a detached copy keeps the executor's by-identity caches away
            # from the original (which this rewrite discards)
            stages, cur = chain_under(n.child)
            made += 1
            return P.Pipeline(
                stages=stages,
                child=cur,
                donate_ok=_donate_ok_child(cur, refs),
                agg=P.Aggregate(
                    keys=list(n.keys), aggs=list(n.aggs), child=None
                ),
            )
        topdown_stages, cur = chain_under(n)
        if not topdown_stages or not _chain_worth_fusing(topdown_stages):
            return n
        made += 1
        return P.Pipeline(
            stages=topdown_stages,
            child=cur,
            donate_ok=_donate_ok_child(cur, refs),
        )

    def visit(v):
        if isinstance(v, (P.PlanNode, E.Expr)):
            if id(v) in seen:
                return
            seen.add(id(v))
            if isinstance(v, P.Sort):
                # single-consumer annotation for the Limit-over-Sort top-k
                # gather (exec._exec_limit): a shared Sort must execute in
                # full once, not top-k for one parent and again in full
                # for the other
                v._topk_safe = refs.get(id(v), 1) <= 1
            if isinstance(v, P.Pipeline):
                # stages/agg are detached (child=None) fragments: never
                # re-absorb them; only the real child subtree recurses —
                # and that child may itself head an absorbable shape (a
                # HAVING chain's pipeline sits over a fusible Aggregate)
                nv = absorb(v.child)
                if nv is not v.child:
                    v.child = nv
                    v.donate_ok = _donate_ok_child(nv, refs)
                visit(v.child)
                return
            for f in dataclasses.fields(v):
                cv = getattr(v, f.name)
                if isinstance(cv, P.PlanNode):
                    nv = absorb(cv)
                    if nv is not cv:
                        # Expr dataclasses are frozen; the plan field of a
                        # ScalarSubquery is excluded from hash/compare, so
                        # in-place rewrite is safe
                        object.__setattr__(v, f.name, nv)
                        cv = nv
                elif isinstance(cv, list):
                    for i, x in enumerate(cv):
                        if isinstance(x, P.PlanNode):
                            nx = absorb(x)
                            if nx is not x:
                                cv[i] = nx
                visit(cv)
        elif isinstance(v, (list, tuple)):
            for x in v:
                visit(x)

    root = absorb(node)
    visit(root)
    return root, made


# ---------------------------------------------------------------------------
# fused evaluation
# ---------------------------------------------------------------------------


class _StatsMarker:
    """Build-time stand-in for an input column's ColStats: an output column
    whose stats object survived the chain untouched maps back to the input
    column index, so every CALL resolves stats from its own input table
    (bounds captured from a trace-time sample would go stale under
    executable reuse across datasets)."""

    __slots__ = ("idx",)

    def __init__(self, idx):
        self.idx = idx


class _InCol:
    """Input-column metadata a FusedPipeline retains (device buffers must
    not outlive the call — see FusedPipeline.__init__)."""

    __slots__ = ("dtype", "has_valid", "dictionary", "has_stats")

    def __init__(self, dtype, has_valid, dictionary, has_stats):
        self.dtype = dtype
        self.has_valid = has_valid
        self.dictionary = dictionary
        self.has_stats = has_stats


class _FusedBase:
    """Shared input plumbing of the fused callables: flat-argument layout,
    abstract Table reconstruction inside the trace, stage application, and
    ownership-based donation-slot analysis."""

    def _capture_inputs(self, sample: Table):
        self.in_names = list(sample.columns)
        # metadata ONLY — never retain the sample's Column objects: an
        # entry lives for the session and a retained fact-scale .data
        # buffer would pin GBs of device memory past any OOM-recovery wipe
        self.in_meta = [
            _InCol(
                c.dtype,
                c.valid is not None,
                c.dictionary,
                c.stats is not None,
            )
            for c in sample.columns.values()
        ]
        # the dictionaries ARE retained deliberately: the cache key uses
        # id(dictionary), which stays truthful only while the object is
        # alive (a recycled address must not alias a new dict), and the
        # trace bakes their lookup tables in. Host-side, dimension-sized.

    def _input_specs(self, sample: Table):
        specs = []
        if self.live_mode == "count":
            specs.append(jax.ShapeDtypeStruct((), jnp.int32))
        elif self.live_mode in ("mask", "mask_pass"):
            specs.append(jax.ShapeDtypeStruct((sample.cap,), jnp.bool_))
        for c in sample.columns.values():
            specs.append(jax.ShapeDtypeStruct(c.data.shape, c.data.dtype))
        for c in sample.columns.values():
            if c.valid is not None:
                specs.append(jax.ShapeDtypeStruct((sample.cap,), jnp.bool_))
        return specs

    def _flat_inputs(self, flat):
        i = 0
        live = None
        if self.live_mode == "count":
            n = flat[0]
            i = 1
        elif self.live_mode in ("mask", "mask_pass"):
            live = flat[0]
            i = 1
        datas = flat[i:i + len(self.in_meta)]
        i += len(self.in_meta)
        cap = int(datas[0].shape[0]) if datas else (
            int(live.shape[0]) if live is not None else 0
        )
        if self.live_mode == "count":
            live = jnp.arange(cap, dtype=jnp.int32) < n
        cols = {}
        vi = i
        for ci, (name, c, d) in enumerate(
            zip(self.in_names, self.in_meta, datas)
        ):
            valid = None
            if c.has_valid:
                valid = flat[vi]
                vi += 1
            cols[name] = Column(
                d, c.dtype, valid, c.dictionary,
                _StatsMarker(ci) if c.has_stats else None,
            )
        nrows = jnp.sum(live, dtype=jnp.int32) if live is not None else 0
        return Table(cols, nrows, live=live)

    def _apply_stages(self, t: Table) -> Table:
        """The evaluator chain, stage by stage, inside the trace — the SAME
        Evaluator the eager path runs, so fused results match eager by
        construction."""
        for s in self.stages:
            ev = Evaluator(t)
            if isinstance(s, P.Filter):
                pr = ev.eval(s.predicate)
                mask = pr.data.astype(bool)
                if pr.valid is not None:
                    mask = mask & pr.valid
                mask = mask & t.row_mask()
                # the filter hands on what is read above it (P.Filter)
                t = Table(
                    dict(narrow_columns(t.columns, s.required)),
                    jnp.sum(mask, dtype=jnp.int32), live=mask,
                )
            else:
                cols = {name: ev.eval(e) for e, name in s.items}
                t = Table(cols, t.nrows_lazy, live=t.live)
        return t

    def _flat_args(self, table: Table):
        flat = []
        if self.live_mode == "count":
            # asarray, not int(): the count may be a still-queued 0-d
            # device scalar and must not force a sync here
            flat.append(jnp.asarray(table.nrows_lazy, dtype=jnp.int32))
        elif self.live_mode in ("mask", "mask_pass"):
            flat.append(table.row_mask())
        for c in table.columns.values():
            flat.append(c.data)
        for c in table.columns.values():
            if c.valid is not None:
                flat.append(c.valid)
        return flat

    def _analyze_donation(self, fn, specs, cap):
        """Build-time donation feasibility: (consumed slots, output aval
        templates). `consumed` is the flat input slots the compiled body
        actually reads (jaxpr dead-code elimination — an owned input that
        only fed a pruned passthrough output, or a stage value a later
        projection dropped, is DCE'd by XLA). The templates are the
        computed outputs' (dtype, shape) with the sample capacity
        normalized to "cap": jax only aliases a donated buffer into an
        output with the IDENTICAL aval, so donating without a matching
        output reclaims nothing, emits jax's unusable-donation warning on
        every compile, and forks a pointless executable variant per
        owned-pattern. (None, None) means "donate whatever ownership
        allows" — the analysis rides a jax-internal API, and any drift
        only costs those warnings, never correctness."""
        try:
            # build-time-only cold path (once per compiled executable, never
            # per call) AND a jax-internal module kept inside the guarding
            # try so an import-time rename degrades like any other drift
            # nds-lint: disable=local-import
            from jax.interpreters import partial_eval as pe

            jaxpr = jax.make_jaxpr(fn)(*specs).jaxpr
            _, used = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
            consumed = frozenset(i for i, u in enumerate(used) if u)
            outs = [
                (
                    v.aval.dtype,
                    tuple(
                        "cap" if d == cap else d for d in v.aval.shape
                    ),
                )
                for v in jaxpr.outvars
            ]
            return consumed, outs
        except Exception:
            return None, None

    # -- AOT-cached execution ---------------------------------------------
    def _init_aot(self, aot, fp, conf_sig, sample, kind: str,
                  with_stats: bool):
        """Arm persistent-executable resolution (engine/aotcache.py): the
        base key half that is fixed at build time — pipeline kind, stage
        fingerprint, content-stable input signature, relevant engine conf.
        The per-bucket half (avals + donation slots) joins at dispatch.
        `aot=None` keeps the classic in-process jit path untouched."""
        self._aot = aot
        self._aot_exec = {}  # (avals, slots) -> (compiled, from_disk)
        if aot is None:
            self._aot_base = None
            return
        self._aot_base = (
            kind, fp, aot.content_signature(sample, with_stats=with_stats),
            tuple(conf_sig or ()),
        )

    def _dispatch(self, flat, slots: tuple):
        """Run the traced body over `flat` with `slots` donated.

        Without an AOT cache this is the classic path: one jax.jit per
        donation variant, executables keyed per shape bucket inside jax.
        With one, every (avals, slots) bucket resolves its OWN compiled
        executable — disk hit deserializes (a fresh process skips XLA
        entirely), miss pays jit(fn).lower(avals).compile() ONCE and
        serializes the result for every future process. A deserialized
        executable that fails at call time is quarantined and replaced by
        a fresh compile (never a crash, and donation-armed calls re-raise
        instead of retrying over possibly-invalidated buffers)."""
        if self._aot is None:
            if slots:
                jitted = self._jit_donate.get(slots)
                if jitted is None:
                    jitted = self._jit_donate[slots] = jax.jit(
                        self._fn, donate_argnums=slots
                    )
                return jitted(*flat)
            return self._jit(*flat)
        avals = tuple((tuple(a.shape), str(a.dtype)) for a in flat)
        rec = self._aot_exec.get((avals, slots))
        if rec is None:
            rec = self._aot_exec[(avals, slots)] = self._aot_resolve(
                flat, slots, avals
            )
        compiled, from_disk = rec
        try:
            return compiled(*flat)
        except Exception:
            if not from_disk:
                raise
            # keyed correctly but unusable on this runtime (e.g. a stale
            # serialization format): quarantine the entry so NO process
            # (this one included) keeps loading it, and forget the dead
            # in-memory rec so the next attempt recompiles fresh
            self._aot.quarantine_key(self._aot_key(avals, slots))
            self._aot_exec.pop((avals, slots), None)
            if slots:
                # the failed call may already have donated (invalidated)
                # input buffers: a retry over them would read garbage —
                # surface the failure (the ladder re-runs the query, which
                # now compiles cleanly)
                raise
            compiled = self._aot_compile(flat, slots)
            self._aot_exec[(avals, slots)] = (compiled, False)
            return compiled(*flat)

    def _aot_key(self, avals, slots) -> dict:
        kind, fp, sig, conf_sig = self._aot_base
        return self._aot.entry_key(kind, fp, sig, avals, slots, conf_sig)

    def _aot_compile(self, flat, slots):
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat]
        return jax.jit(
            self._fn, donate_argnums=slots or ()
        ).lower(*specs).compile()

    def _aot_resolve(self, flat, slots, avals):
        """(compiled, from_disk) for one (avals, slots) bucket: disk load
        first, else compile + persist."""
        key = self._aot_key(avals, slots)
        compiled = self._aot.load(key)
        if compiled is not None:
            return compiled, True
        loaded_before = xla_cache_hits()
        compiled = self._aot_compile(flat, slots)
        # only what was compiled HERE is persisted: an executable jax's
        # own persistent cache served runs fine but re-serializes into an
        # entry that fails at call time (aotcache.xla_cache_hits), and
        # the next process gets it from that cache again anyway
        if xla_cache_hits() == loaded_before:
            self._aot.store(key, compiled)
        return compiled, False

    def _donate_slots(self, table: Table, flat) -> tuple:
        """Flat arg indices safe AND useful to donate for THIS call: the
        consumed live-mask input (the plan rewrite's donate_ok gate already
        proved the child single-consumer and its mask freshly minted), plus
        every data/validity buffer the producer marked Column.owned —
        excluding buffers that pass through to the output, buffers the
        executable never consumes, buffers with no same-aval computed
        output left to alias into (see _analyze_donation for both), and
        buffers appearing more than once in the argument list (a `select
        k, k k2` projection feeds one buffer twice; donating either copy
        would invalidate the other)."""
        pt = getattr(self, "passthrough", None) or ()
        pt_srcs = {s for s in pt if s is not None}
        consumed = getattr(self, "_consumed", None)
        templates = getattr(self, "_out_avals", None)
        avail = None
        if templates is not None:
            cap = table.cap
            avail = {}
            for dt, shape in templates:
                key = (
                    dt, tuple(cap if d == "cap" else d for d in shape)
                )
                avail[key] = avail.get(key, 0) + 1

        def ok(slot):
            if (
                slot in pt_srcs
                or (consumed is not None and slot not in consumed)
                or counts[id(flat[slot])] != 1
            ):
                return False
            if avail is None:
                return True
            key = (flat[slot].dtype, tuple(flat[slot].shape))
            if avail.get(key, 0) <= 0:
                return False
            avail[key] -= 1  # one output buffer aliases one donation
            return True

        counts = {}
        for x in flat:
            counts[id(x)] = counts.get(id(x), 0) + 1
        slots = []
        i = 0
        if self.live_mode == "count":
            i = 1  # 0-d scalar: nothing to donate
        elif self.live_mode in ("mask", "mask_pass"):
            if self.live_mode == "mask" and ok(0):
                slots.append(0)
            i = 1
        cols = list(table.columns.values())
        for ci, c in enumerate(cols):
            slot = i + ci
            if c.owned and ok(slot):
                slots.append(slot)
        vi = i + len(cols)
        for c in cols:
            if c.valid is None:
                continue
            if c.owned and ok(vi):
                slots.append(vi)
            vi += 1
        return tuple(slots)


class FusedPipeline(_FusedBase):
    """One compiled Filter/Project chain for one input signature.

    Built once per (stage fingerprint, input signature); jax adds one
    executable per input capacity bucket underneath the single traced
    callable. Construction traces the chain abstractly (jax.eval_shape) to
    capture output structure and the passthrough map; a chain that cannot
    trace raises, and the ExecutableCache pins its signature to the eager
    path."""

    def __init__(self, stages, sample: Table, aot=None, fp=None,
                 conf_sig=()):
        """aot/fp/conf_sig: persistent-executable resolution
        (engine/aotcache.py) — `aot` is the session AotCache (or None for
        the classic jit path), `fp` the pipeline's stage fingerprint, and
        `conf_sig` the compiled-code-relevant engine conf values that
        join the on-disk entry key."""
        self.stages = stages
        self._capture_inputs(sample)
        self.has_filter = any(isinstance(s, P.Filter) for s in stages)
        # live handling: "count" (live=None input: the mask is built inside
        # the jit from a scalar row count — no mask buffer at the boundary),
        # "mask" (explicit mask input), "none" (pure projection over an
        # unmasked table: liveness never enters the jit)
        if self.has_filter:
            self.live_mode = "count" if sample.live is None else "mask"
        else:
            self.live_mode = "none" if sample.live is None else "mask_pass"
        self.out_meta = None
        self.passthrough = None
        jax.eval_shape(self._run_full, *self._input_specs(sample))
        # outputs that pass an input buffer through are reassembled from
        # the caller's own columns; pruning them from the jit lets jax drop
        # the then-unused inputs entirely (no copies through the
        # executable)
        self._kept = [
            i for i, src in enumerate(self.passthrough) if src is None
        ]
        self._consumed, self._out_avals = self._analyze_donation(
            self._run_kept, self._input_specs(sample), sample.cap
        )
        self._fn = self._run_kept
        self._jit = jax.jit(self._run_kept)
        self._jit_donate = {}  # donate-slot tuple -> jitted callable
        self._init_aot(aot, fp, conf_sig, sample, "pipeline",
                       with_stats=False)

    # -- traced body ------------------------------------------------------
    def _run_full(self, *flat):
        t = self._apply_stages(self._flat_inputs(flat))
        # flatten outputs + capture structure (side effect: runs at trace
        # time only, with identical values on every trace)
        flat_out = []
        if self.has_filter:
            flat_out.append(t.nrows_lazy)  # queued count (0-d device)
            flat_out.append(t.live)
        self.out_data_base = len(flat_out)
        for c in t.columns.values():
            flat_out.append(c.data)
        valid_slots = []
        for c in t.columns.values():
            if c.valid is not None:
                valid_slots.append(len(flat_out))
                flat_out.append(c.valid)
            else:
                valid_slots.append(None)
        self.out_valid_slots = valid_slots
        self.out_meta = [
            (name, c.dtype, c.dictionary, c.stats)
            for name, c in t.columns.items()
        ]
        self.passthrough = [
            next((j for j, a in enumerate(flat) if o is a), None)
            for o in flat_out
        ]
        return tuple(flat_out)

    def _run_kept(self, *flat):
        out = self._run_full(*flat)
        return tuple(out[i] for i in self._kept)

    # -- call -------------------------------------------------------------
    def call(self, table: Table, donate: bool) -> Table:
        flat = self._flat_args(table)
        slots = self._donate_slots(table, flat) if donate else ()
        out = self._dispatch(flat, slots)
        # reassemble: computed slots from the executable, passthrough
        # slots straight from the caller's own buffers
        full = [None] * len(self.passthrough)
        for slot, v in zip(self._kept, out):
            full[slot] = v
        for slot, src in enumerate(self.passthrough):
            if src is not None:
                full[slot] = flat[src]
        if self.has_filter:
            nrows, live = full[0], full[1]
        else:
            nrows, live = table.nrows_lazy, table.live
        in_cols = list(table.columns.values())
        cols = {}
        for k, (name, dtype, dic, st) in enumerate(self.out_meta):
            data = full[self.out_data_base + k]
            vslot = self.out_valid_slots[k]
            valid = None if vslot is None else full[vslot]
            stats = (
                in_cols[st.idx].subset_stats()
                if isinstance(st, _StatsMarker)
                else None  # never trust stats minted at trace time
            )
            cols[name] = Column(data, dtype, valid, dic, stats)
        return Table(
            cols, nrows, live=live, unique_key=self._out_unique_key(table)
        )

    def _out_unique_key(self, table: Table):
        """Replay name flow host-side: filters preserve the input's unique
        key; projections keep it only when every key column survives as a
        plain rename (mirrors exec._project_table)."""
        uk = table.unique_key
        names = set(table.columns)
        for s in self.stages:
            if uk is None:
                return None
            if isinstance(s, P.Filter):
                if s.required is not None:
                    names = names.intersection(s.required)
                    if not uk <= names:
                        uk = None
                continue
            renames = {}
            for e, name in s.items:
                if isinstance(e, E.Col):
                    key = f"{e.table}.{e.name}" if e.table else e.name
                    if key not in names and e.name in names:
                        key = e.name
                    renames.setdefault(key, name)
            uk = (
                frozenset(renames[k] for k in uk)
                if all(k in renames for k in uk)
                else None
            )
            names = {n for _, n in s.items}
        return uk


_DIRECT_AGG_MAX_DOMAIN = 1 << 22  # mirrors exec._DIRECT_AGG_MAX_DOMAIN


class _AggKey:
    """Trace-captured metadata of one group-key column (build-time probe):
    enough to resolve static bounds and reconstruct the key column from
    occupied cell codes at call time."""

    __slots__ = ("dtype", "dictionary", "has_valid", "stats_idx")

    def __init__(self, col: Column):
        self.dtype = col.dtype
        self.dictionary = col.dictionary
        self.has_valid = col.valid is not None
        self.stats_idx = (
            col.stats.idx if isinstance(col.stats, _StatsMarker) else None
        )


class FusedAggPipeline(_FusedBase):
    """A Filter/Project chain PLUS its decomposable aggregate tail,
    compiled as one dispatch.

    The traced body runs the evaluator chain, folds filters into the live
    mask, computes mixed-radix group codes elementwise (the executor's
    direct sort-free aggregation scheme, exec._try_direct_agg — bounds are
    baked as trace constants, so the input signature carries them), and
    scatters every aggregate into a domain-bucket cell array via the same
    segment_reduce kernels the eager path dispatches one by one (a global
    aggregate has no codes and scatters nothing: one masked reduce an
    aggregate into cell 0, the call below reads it without a sync). The call
    then pays ONE host sync for the occupied-group count (exactly what the
    eager direct path pays), compacts the occupied cells, reconstructs the
    key columns from the cell codes, and gathers the aggregate values —
    small gcap-sized work after the single fact-scale dispatch.

    Build raises (and the ExecutableCache pins the signature to the eager
    path) when any key lacks static bounds, the combined domain exceeds
    the direct-aggregation cap, or an argument cannot trace — the exact
    inputs the eager path would route to its sort-based aggregation."""

    # The traced body's revision, in the `kind` half of the executable's
    # key on disk (engine/aotcache.py): nothing else of that key moves
    # when `_run_agg`'s body does, so without it an entry an older body
    # wrote would be loaded and run for this one. Raise it with the body.
    # 2 (PR 44): a keyless tail reduces whole and returns no occupancy.
    BODY_REV = 2

    def __init__(self, stages, agg: P.Aggregate, sample: Table, aot=None,
                 fp=None, conf_sig=()):
        self.stages = stages
        self.agg = agg
        # what `pipeline_span.agg_route` says: a keyless tail is one run
        # and reduces whole, a keyed one scatters by its group codes
        self.agg_route = "scatter" if agg.keys else "whole"
        self._capture_inputs(sample)
        # per-input-column host stats (vmin, vmax): the probe maps plain
        # key columns back to these; part of the cache signature, so a
        # dataset with different bounds builds its own entry
        self.in_stats = [
            (int(c.stats.vmin), int(c.stats.vmax))
            if c.stats is not None
            else None
            for c in sample.columns.values()
        ]
        # aggregation needs liveness even for a pure projection chain
        self.live_mode = "count" if sample.live is None else "mask"
        specs = self._input_specs(sample)
        # phase 1: probe the chain + key expressions abstractly to learn
        # each key's dtype/dictionary/validity and which input column its
        # stats flow from (tracer identity via _StatsMarker)
        self.key_meta = None
        jax.eval_shape(self._probe_keys, *specs)
        self._resolve_bounds()
        # phase 2: trace the real body (bounds now baked) to capture the
        # aggregate output slot layout
        self.agg_meta = None
        jax.eval_shape(self._run_agg, *specs)
        self.passthrough = ()  # aggregate outputs never alias inputs
        # agg outputs live at the (build-constant) domain cap, never the
        # input cap: normalize against sample.cap anyway so a coincident
        # equality generalizes the same way the pipeline case does
        self._consumed, self._out_avals = self._analyze_donation(
            self._run_agg, specs, sample.cap
        )
        self._fn = self._run_agg
        self._jit = jax.jit(self._run_agg)
        self._jit_donate = {}
        # stats fold into the content signature: the mixed-radix bounds
        # bake into the trace, so a dataset with different bounds is a
        # different executable on disk too
        self._init_aot(aot, fp, conf_sig, sample,
                       f"agg_pipeline.{self.BODY_REV}", with_stats=True)

    # -- build ------------------------------------------------------------
    def _probe_keys(self, *flat):
        t = self._apply_stages(self._flat_inputs(flat))
        ev = Evaluator(t)
        self.key_meta = [
            _AggKey(ev.eval(e)) for e, _ in self.agg.keys
        ]
        return ()

    def _resolve_bounds(self):
        mins, ranges = [], []
        domain = 1
        for km in self.key_meta:
            # the same bound sources the eager direct path accepts:
            # dictionary codes / bools span statically, int-like keys need
            # ColStats that survived the chain
            if km.dtype.is_string:
                if km.dictionary is None or len(km.dictionary) == 0:
                    raise ValueError("string key without a dictionary")
                kmin, kmax = 0, len(km.dictionary) - 1
            elif km.dtype.kind == "bool":
                kmin, kmax = 0, 1
            elif km.dtype.kind in ("int32", "int64", "date"):
                st = (
                    self.in_stats[km.stats_idx]
                    if km.stats_idx is not None
                    else None
                )
                if st is None:
                    raise ValueError("key without static bounds")
                kmin, kmax = st
            else:
                raise ValueError(f"key dtype {km.dtype} not direct-aggable")
            krange = kmax - kmin + 1 + (1 if km.has_valid else 0)
            domain *= krange
            if domain > _DIRECT_AGG_MAX_DOMAIN:
                raise ValueError("group-key domain exceeds the direct cap")
            mins.append(kmin)
            ranges.append(krange)
        self.mins = mins
        self.ranges = ranges
        self.domain_cap = bucket_cap(domain)

    # -- traced body ------------------------------------------------------
    def _group_codes(self, ev, live):
        """Mixed-radix group code per row (mirrors K.direct_gid; NULL takes
        the reserved 0 code per nullable key, dead rows park at cell 0 and
        are excluded by the live/weight masks)."""
        gid = jnp.zeros(live.shape[0], jnp.int64)
        for (e, _), kmin, krange in zip(self.agg.keys, self.mins,
                                        self.ranges):
            c = ev.eval(e)
            d = c.data
            if d.dtype == jnp.bool_:
                d = d.astype(jnp.int32)
            code = d.astype(jnp.int64) - kmin
            if c.valid is not None:
                code = jnp.where(c.valid, code + 1, 0)
            gid = gid * krange + code
        return jnp.where(live, gid, 0).astype(jnp.int32)

    def _run_agg(self, *flat):
        t = self._apply_stages(self._flat_inputs(flat))
        live = t.row_mask()
        ev = Evaluator(t)
        dc = self.domain_cap
        if self.agg.keys:
            gid = self._group_codes(ev, live)
            flat_out = [jnp.zeros(dc, bool).at[gid].max(live, mode="drop")]
        else:
            # a global aggregate is one run: no ids, so every reduction
            # below is a masked reduce into cell 0 (K.segment_reduce), and
            # no occupancy, which only a keyed call() reads
            gid = None
            flat_out = []
        agg_meta = []
        for a, name in self.agg.aggs:
            fn = a.fn
            if fn == "count" and a.arg is None:
                counts = K.segment_reduce(
                    live.astype(jnp.int64), gid, live, dc, "count"
                )
                agg_meta.append(("count", name, INT64, None,
                                 len(flat_out), None))
                flat_out.append(counts)
                continue
            c = ev.eval(a.arg)
            weight = live
            if c.valid is not None:
                weight = weight & c.valid
            sdata = c.data
            dictionary = None
            if c.dtype.is_string:
                if fn not in ("min", "max"):
                    raise ValueError(f"agg {fn} on string column")
                # rank transform bakes at trace time; comparing rank codes
                # is comparing strings (mirrors exec._eval_agg)
                sdata, dictionary = sort_dictionary(c)
            if fn == "count":
                counts = K.segment_reduce(sdata, gid, weight, dc, "count")
                agg_meta.append(("count", name, INT64, None,
                                 len(flat_out), None))
                flat_out.append(counts)
            elif fn in ("sum", "min", "max"):
                red, counts = K.segment_reduce_with_count(
                    sdata, gid, weight, dc, fn
                )
                dtype = c.dtype
                if c.dtype.is_string:
                    red = red.astype(jnp.int32)
                elif fn == "sum" and dtype.kind == "int32":
                    dtype = INT64
                    red = red.astype(jnp.int64)
                agg_meta.append(("valcnt", name, dtype, dictionary,
                                 len(flat_out), len(flat_out) + 1))
                flat_out.append(red)
                flat_out.append(counts)
            elif fn == "avg":
                # the jit returns RAW (sum, count); the division runs
                # eagerly in _agg_column with the eager path's exact op
                # sequence — inside the jit XLA reassociates the two
                # divisions and the result drifts an ulp from eager
                s, n = K.segment_reduce_with_count(sdata, gid, weight, dc,
                                                   "sum")
                scale = c.dtype.scale if c.dtype.is_decimal else None
                agg_meta.append(("avg", name, FLOAT64, scale,
                                 len(flat_out), len(flat_out) + 1))
                flat_out.append(s)
                flat_out.append(n)
            else:
                raise ValueError(f"aggregate {fn} not fusible")
        self.agg_meta = agg_meta
        return tuple(flat_out)

    # -- call -------------------------------------------------------------
    def call(self, table: Table, donate: bool) -> Table:
        flat = self._flat_args(table)
        slots = self._donate_slots(table, flat) if donate else ()
        out = self._dispatch(flat, slots)
        in_cols = list(table.columns.values())
        if not self.agg.keys:
            # global aggregate: exactly one output row (cell 0), over empty
            # input included — domain_cap equals the eager path's
            # bucket_cap(1) group capacity, so arrays line up unchanged
            return Table(self._agg_columns(out, None), 1,
                         unique_key=frozenset())
        occ = out[0]
        # the ONE host sync of the fused path — the same occupied-group
        # count the eager direct aggregation fetches (K.mask_count)
        ngroups = int(host_read("ngroups", jnp.sum(occ, dtype=jnp.int32)))
        if ngroups == 0:
            return self._empty_output()
        gcap = bucket_cap(ngroups)
        occ_cells = K.compact_indices(occ, gcap).astype(jnp.int64)
        # reconstruct key columns from the occupied cell codes (reverse
        # mixed-radix decomposition; last key is least significant)
        codes = []
        rem = occ_cells
        for krange in reversed(self.ranges):
            codes.append(rem % krange)
            rem = rem // krange
        codes.reverse()
        cols = {}
        n_keys = len(self.agg.keys)
        for (e, name), km, code, kmin in zip(
            self.agg.keys, self.key_meta, codes, self.mins
        ):
            if km.has_valid:
                valid = code != 0
                value = jnp.where(valid, kmin + code - 1, 0)
            else:
                valid = None
                value = kmin + code
            stats = None
            if km.stats_idx is not None:
                base = in_cols[km.stats_idx].subset_stats()
                if base is not None:
                    stats = _dc_replace(base, unique=(n_keys == 1))
            cols[name] = Column(
                value.astype(km.dtype.device_np_dtype()), km.dtype,
                valid, km.dictionary, stats, owned=True,
            )
        cols.update(self._agg_columns(out, occ_cells))
        return Table(
            cols, ngroups,
            unique_key=frozenset(n for _, n in self.agg.keys),
        )

    def _agg_columns(self, out, cells):
        """The aggregates' output columns: every slot they read is taken
        at the occupied `cells` by one gather (a global aggregate, `cells`
        None, reads the first bucket of each)."""
        slots = sorted({
            s for *_, s1, s2 in self.agg_meta for s in (s1, s2)
            if s is not None
        })
        if cells is None:
            taken = [out[s][: bucket_cap(1)] for s in slots]
        else:
            taken = K.take_arrays([out[s] for s in slots], cells)
        at_cells = dict(zip(slots, taken))
        cols = {}
        for meta in self.agg_meta:
            cols.update(self._agg_column(meta, at_cells))
        return cols

    @staticmethod
    def _agg_column(meta, at_cells):
        # 4th slot: dictionary for valcnt kinds, decimal scale for avg
        kind, name, dtype, dictionary, s1, s2 = meta
        if kind == "count":
            return {name: Column(at_cells[s1].astype(jnp.int64), INT64,
                                 owned=True)}
        if kind == "avg":
            s, n = at_cells[s1], at_cells[s2]
            nz = jnp.maximum(n, 1)
            # eager _eval_agg's exact division sequence (elementwise, so
            # running it post-gather is value-identical to pre-gather)
            if dictionary is not None:
                val = s.astype(jnp.float64) / (10**dictionary) / nz
            else:
                val = s.astype(jnp.float64) / nz
            return {name: Column(val, FLOAT64, n > 0, owned=True)}
        return {
            name: Column(at_cells[s1], dtype, at_cells[s2] > 0, dictionary,
                         owned=True)
        }

    def _empty_output(self) -> Table:
        """Mirror of the eager empty-grouped-aggregate stub
        (exec._agg_output with ngroups=0): 1-capacity columns, zero rows,
        every aggregate stubbed as a null INT64."""
        cols = {}
        for (e, name), km in zip(self.agg.keys, self.key_meta):
            cols[name] = Column(
                jnp.zeros(1, km.dtype.device_np_dtype()), km.dtype,
                jnp.zeros(1, bool), km.dictionary,
            )
        for _, name, _, _, _, _ in self.agg_meta:
            cols[name] = Column(
                jnp.zeros(1, jnp.int64), INT64, jnp.zeros(1, bool)
            )
        return Table(cols, 0)


def input_signature(table: Table, with_stats: bool = False):
    """Hashable identity of an input table's device layout: liveness mode,
    column names, dtypes, validity presence, dictionary identity (codes are
    only meaningful relative to their dictionary, and trace-time lookup
    tables bake it in). Capacity is deliberately absent — jax keys
    executables per shape bucket underneath one traced callable, which is
    exactly the shape-bucketed reuse: a query re-run (same bucket) or a
    structurally identical query at another bucket share the trace.

    `with_stats` (aggregate-tail pipelines) folds each column's host-side
    (vmin, vmax) bounds in: the fused aggregate bakes key bounds into the
    trace as mixed-radix constants, so a dataset with different bounds
    must build (and cache) its own entry."""
    sig = [table.live is not None]
    for name, c in table.columns.items():
        entry = (
            name,
            repr(c.dtype),
            c.valid is not None,
            id(c.dictionary) if c.dictionary is not None else None,
        )
        if with_stats:
            entry = entry + (
                (int(c.stats.vmin), int(c.stats.vmax))
                if c.stats is not None
                else None,
            )
        sig.append(entry)
    return tuple(sig)


class ExecutableCache:
    """Session-level cache of FusedPipeline builds keyed by (pipeline
    structure fingerprint, input signature), with per-(key, bucket)
    hit/miss accounting — the bucket level is where XLA actually compiles.
    Entries pin their dictionaries (see input_signature); a failed build is
    pinned as None so the executor stops re-attempting the fuse. LRU by
    entry count: entries hold host-side trace machinery, not device
    buffers."""

    def __init__(self, max_entries: int = 512):
        self.max_entries = max_entries
        self.map = OrderedDict()  # (fp, sig) -> FusedPipeline | None
        self.buckets = set()  # (fp, sig, cap) already compiled
        self.hits = 0
        self.misses = 0

    def lookup(self, fp, sig, cap, build):
        """(FusedPipeline | None, hit: bool)."""
        key = (fp, sig)
        if key in self.map:
            entry = self.map[key]
            self.map.move_to_end(key)
        else:
            try:
                entry = build()
            except Exception:
                entry = None  # unfusible chain: pin to the eager path
            self.map[key] = entry
            while len(self.map) > self.max_entries:
                old_key, _ = self.map.popitem(last=False)
                self.buckets = {
                    b for b in self.buckets if b[:2] != old_key
                }
        if entry is None:
            return None, False
        bkey = (fp, sig, cap)
        hit = bkey in self.buckets
        if hit:
            self.hits += 1
        else:
            self.misses += 1
            self.buckets.add(bkey)
        return entry, hit

    def clear(self):
        self.map.clear()
        self.buckets.clear()
