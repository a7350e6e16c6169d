"""Logical/physical plan IR.

The binder lowers SQL AST into this tree; the executor interprets it over
device Tables. Column identity is by unique string name ("alias.col" for base
columns, binder-generated names for derived ones), so plans carry no separate
symbol table.

This is the engine's counterpart of the Catalyst plans the reference submits
to Spark (reference: nds/nds_power.py:125-135 `spark.sql(query)`).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from . import expr as E


@dataclass
class PlanNode:
    def children(self):
        return []


@dataclass
class Scan(PlanNode):
    table: str  # catalog name
    alias: str  # column prefix in the output
    columns: list = None  # projection pushdown: subset of base columns or None
    # lakehouse snapshot pin: the manifest version this statement resolved
    # at plan time (Session._pin_lake_scans); None for non-lake tables. A
    # dataclass field on purpose — it participates in plan.fingerprint, so
    # plan-cache entries can never alias across snapshot versions.
    lake_version: int = None
    # zone-map pruning (Session._prune_lake_scans): the pinned manifest's
    # files that MAY match this scan's bound predicate (None = read all),
    # and the surviving-row upper bound the budgeter consumes. Dataclass
    # fields like lake_version — they participate in fingerprint, so a
    # pruned plan can never alias an unpruned one in the plan cache.
    lake_files: tuple = None
    prune_rows: int = None


@dataclass
class Project(PlanNode):
    items: list  # (Expr, out_name)
    child: PlanNode = None

    def children(self):
        return [self.child]


@dataclass
class Filter(PlanNode):
    predicate: E.Expr
    child: PlanNode = None
    # the columns something above this node reads (Session.prune_columns):
    # a sorted tuple of names, None == all. The executor hands on these and
    # no others. A dataclass field on purpose, as Scan.columns is: it
    # participates in fingerprint, so a cached table narrowed for one
    # consumer is never served to one that reads more.
    required: tuple = None

    def children(self):
        return [self.child]


@dataclass
class Join(PlanNode):
    kind: str  # inner | left | right | full | semi | anti | cross | mark
    left: PlanNode = None
    right: PlanNode = None
    left_keys: list = field(default_factory=list)  # Exprs over left
    right_keys: list = field(default_factory=list)  # Exprs over right
    residual: Optional[E.Expr] = None  # non-equi condition applied post-match
    mark_name: Optional[str] = None  # kind == "mark": bool "has a match" column
    required: tuple = None  # see Filter.required

    def children(self):
        return [self.left, self.right]


@dataclass
class Aggregate(PlanNode):
    keys: list  # (Expr, out_name)
    aggs: list  # (E.Agg, out_name)
    child: PlanNode = None
    grouping_sets: Optional[list] = None  # list of key-index subsets (rollup)
    # planner annotation (mark_blocked_union_aggs): the input is a union_all
    # chain reachable through Project/Filter wrappers, so the executor may
    # evaluate it in bounded row windows with partial-aggregate merging
    # instead of materializing the full concat (the SF10 HBM ceiling)
    blocked_union: bool = False

    def children(self):
        return [self.child]


@dataclass
class Window(PlanNode):
    fns: list  # (E.WindowFn, out_name)
    child: PlanNode = None

    def children(self):
        return [self.child]


@dataclass
class Sort(PlanNode):
    keys: list  # (Expr, ascending, nulls_first|None)
    child: PlanNode = None

    def children(self):
        return [self.child]


@dataclass
class Limit(PlanNode):
    n: int
    child: PlanNode = None

    def children(self):
        return [self.child]


@dataclass
class Distinct(PlanNode):
    child: PlanNode = None

    def children(self):
        return [self.child]


@dataclass
class SetOp(PlanNode):
    op: str  # union_all | union | intersect | except
    left: PlanNode = None
    right: PlanNode = None

    def children(self):
        return [self.left, self.right]


@dataclass
class MultiJoin(PlanNode):
    """N-way inner join over a predicate graph; the executor picks the join
    order greedily from *actual* post-filter row counts (eager execution makes
    real sizes available — the TPU answer to Spark's CBO/AQE, reference:
    nds/properties/aqe-on.properties)."""

    relations: list = field(default_factory=list)  # PlanNodes
    edges: list = field(default_factory=list)  # (i, j, left_expr, right_expr)
    residual: Optional[E.Expr] = None
    required: tuple = None  # see Filter.required

    def children(self):
        return list(self.relations)


@dataclass
class MaterializedScan(PlanNode):
    """Scan of an already-materialized Table (CTE result, temp view)."""

    name: str
    table: object = None  # columnar.Table


@dataclass
class Pipeline(PlanNode):
    """A maximal linear Filter/Project chain fused into one compiled unit.

    `stages` holds detached Filter/Project nodes (child=None) in EXECUTION
    order (innermost first); `child` is the chain's input. The executor
    compiles the whole chain as ONE jitted function over the child's device
    columns (engine/fuse.py) — no per-node dispatch, no materialized
    intermediates, masks deferred to the pipeline boundary — and falls back
    to eager per-stage evaluation when the chain doesn't trace (host-side
    string casts, subqueries). Structural passes that peel Project/Filter
    wrappers (blocked union-aggregation shape detection) see through this
    node via `_peel_wrappers`.

    `agg` (optional) is a detached Aggregate tail (child=None, plain shape:
    no grouping sets, no blocked_union, decomposable agg set): the fused
    body then runs the evaluator chain AND the partial-aggregate scatter in
    ONE dispatch (direct mixed-radix group codes + segment reductions over
    a domain-bucket output cap), and the Pipeline's output is the aggregate
    result. An agg-tail Pipeline is a plan-cacheable terminal node, never a
    see-through wrapper (`_peel_wrappers` stops at it)."""

    stages: list = field(default_factory=list)  # Filter/Project, child=None
    child: PlanNode = None
    # set by fuse.mark_pipelines: the child's result is single-consumer and
    # uncached, so the fused call may donate input buffers the child table
    # actually owns (its live mask; data/validity buffers marked
    # Column.owned by minting producers — see README "Performance")
    donate_ok: bool = False
    agg: Optional["Aggregate"] = None  # detached aggregate tail (child=None)

    def children(self):
        return [self.child]


import itertools as _itertools

_fp_serials = _itertools.count()


def fingerprint(node: PlanNode) -> str:
    """Stable structural identity of a plan subtree.

    Two separately-bound plans with the same structure (same scans, exprs,
    operators) get equal fingerprints, so executor results can be reused
    across statements — e.g. the shared CTE text of query14_part1/_part2
    re-resolves to the same key (reference analogue: Spark reuses nothing
    across spark.sql calls; this is the eager engine's materialized-CTE
    win). Shared subtrees are serialized once and back-referenced, which
    also keeps the cost linear in plan size."""
    import dataclasses
    import hashlib

    out = []
    memo = {}

    def emit(v):
        if isinstance(v, MaterializedScan):
            # a populated table is identity, not structure: tag it with a
            # monotonic serial (id() values are reused after GC, which
            # could alias plan-cache entries across statements)
            if v.table is None:
                t = "none"
            else:
                t = getattr(v.table, "_fp_serial", None)
                if t is None:
                    t = v.table._fp_serial = next(_fp_serials)
            out.append(f"MScan:{v.name}:{t}")
        elif isinstance(v, (PlanNode, E.Expr)):
            key = id(v)
            if key in memo:
                out.append(f"@{memo[key]}")
                return
            memo[key] = len(memo)
            out.append(type(v).__name__)
            out.append("(")
            for f in dataclasses.fields(v):
                emit(getattr(v, f.name))
            out.append(")")
        elif isinstance(v, (list, tuple)):
            out.append("[")
            for x in v:
                emit(x)
            out.append("]")
        elif v is None or isinstance(v, (str, int, float, bool, frozenset)):
            out.append(repr(v))
        else:
            # DType and other small value objects: repr is structural
            out.append(type(v).__name__ + ":" + repr(v))

    emit(node)
    return hashlib.sha256("\x00".join(out).encode()).hexdigest()


def walk_plan(root):
    """Yield every PlanNode and Expr reachable from `root` exactly once
    (id-deduplicated; subquery plans riding inside expressions included),
    via generic dataclass-field recursion — the one traversal shared by
    the annotation/analysis passes so a plan-IR field change lands in one
    place."""
    import dataclasses

    seen = set()
    stack = [root]
    while stack:
        v = stack.pop()
        if isinstance(v, (PlanNode, E.Expr)):
            if id(v) in seen:
                continue
            seen.add(id(v))
            yield v
            for f in dataclasses.fields(v):
                stack.append(getattr(v, f.name))
        elif isinstance(v, (list, tuple)):
            stack.extend(v)


def _peel_wrappers(n):
    """(Project/Filter wrapper list top-down, first non-wrapper node).

    Pipeline nodes expand into their stages: fusion must not hide a
    union-aggregation shape from the blocked-execution path (the detached
    stage nodes carry no children, which _apply_wrappers never reads).
    A Pipeline with an aggregate tail is NOT a wrapper — it terminates the
    peel like the Aggregate it absorbed would."""
    wrappers = []
    while isinstance(n, (Project, Filter, Pipeline)):
        if isinstance(n, Pipeline):
            if n.agg is not None:
                break  # aggregate tail: a terminal node, not a wrapper
            # stages are in execution (innermost-first) order; the wrapper
            # list is top-down (outermost first)
            wrappers.extend(reversed(n.stages))
        else:
            wrappers.append(n)
        n = n.child
    return wrappers, n


def union_agg_shape(node: "Aggregate"):
    """(outer_wrappers, join, inner_wrappers, union branch plans) when an
    Aggregate's input is a union_all chain reachable through Project/Filter
    wrappers — optionally with one inner MultiJoin in between whose
    relations include the union (the query5 shape: a fact-scale
    sales+returns union joined to dimension tables before the channel
    aggregation; inner joins distribute over union rows, so windows can
    flow straight through the join). `join` is None for the direct shape,
    else `(multijoin_node, union_relation_index)`. Returns None when the
    input is not this shape.

    Shared by the planner's annotation pass and the executor's blocked
    union-aggregation path so the two recognize exactly the same shapes.
    Only pure `union_all` chains qualify: UNION (distinct), INTERSECT and
    EXCEPT have whole-input set semantics that do not decompose over row
    windows, so such a SetOp terminates branch flattening instead."""
    outer, n = _peel_wrappers(node.child)
    join = None
    inner = []
    if isinstance(n, MultiJoin):
        # the FIRST union-shaped relation is the windowed side; every other
        # relation executes once and joins against each window
        for i, r in enumerate(n.relations):
            w, m = _peel_wrappers(r)
            if isinstance(m, SetOp) and m.op == "union_all":
                join = (n, i)
                inner = w
                n = m
                break
        if join is None:
            return None
    if not (isinstance(n, SetOp) and n.op == "union_all"):
        return None
    branches = []

    def collect(x):
        if isinstance(x, SetOp) and x.op == "union_all":
            collect(x.left)
            collect(x.right)
        else:
            branches.append(x)

    collect(n)
    return outer, join, inner, branches


def aggs_decomposable(agg_items) -> bool:
    """True when every aggregate of an Aggregate node decomposes over row
    windows: plain sum/min/max/count compose with themselves, avg via its
    hidden sum+count split. Distinct aggregates, stddev/var and grouping()
    do not merge over partials. The SAME predicate gates the executor's
    blocked-union machinery (exec._rollup_base_aggs) — the planner
    annotation and the runtime path must agree, and the plan verifier
    (analysis/verifier.py) checks annotations against exactly this rule."""
    return all(
        not a.distinct and a.fn in ("sum", "min", "max", "count", "avg")
        for a, _ in agg_items
    )


def mark_blocked_union_aggs(node: PlanNode) -> int:
    """Annotate every Aggregate (anywhere in the tree, subquery plans
    included) whose input is a union_all chain AND whose aggregates
    decompose over row windows: sets `blocked_union` so the executor may
    take the windowed partial-aggregation path. Grouping-set aggregates
    qualify too — their from-scratch levels run windowed and the rollup
    cascade re-aggregates the (small) results. Non-decomposable aggregate
    sets (count distinct, stddev) are NOT annotated: the windowed path
    cannot merge their partials, so annotating them would only invite an
    unsound rewrite — the verifier flags such annotations. Returns the
    number of nodes marked (plan-introspection aid for tests/tools)."""
    import dataclasses

    marked = 0
    seen = set()

    def visit(v):
        nonlocal marked
        if isinstance(v, (PlanNode, E.Expr)):
            if id(v) in seen:
                return
            seen.add(id(v))
            if (
                isinstance(v, Aggregate)
                and aggs_decomposable(v.aggs)
                and union_agg_shape(v) is not None
            ):
                v.blocked_union = True
                marked += 1
            # generic field recursion reaches subquery plans riding inside
            # expressions (E.ScalarSubquery.plan) as well as plan children
            for f in dataclasses.fields(v):
                visit(getattr(v, f.name))
        elif isinstance(v, (list, tuple)):
            for x in v:
                visit(x)

    visit(node)
    return marked


def node_desc(node: PlanNode) -> str:
    """One-line description of a SINGLE node — no child recursion (the
    op-span tracer calls this per executed node; recursing would render
    every subtree O(depth) times over a traced plan)."""
    name = type(node).__name__
    return {
        "Scan": lambda: f"Scan {node.table} as {node.alias}",
        "MaterializedScan": lambda: f"MaterializedScan {node.name}",
        "Project": lambda: f"Project [{', '.join(n for _, n in node.items)}]",
        "Filter": lambda: f"Filter {node.predicate}",
        "Join": lambda: f"Join {node.kind} on {list(zip(node.left_keys, node.right_keys))}"
        + (f" residual {node.residual}" if node.residual else ""),
        "Aggregate": lambda: f"Aggregate keys=[{', '.join(n for _, n in node.keys)}] "
        f"aggs=[{', '.join(n for _, n in node.aggs)}]"
        + (f" sets={node.grouping_sets}" if node.grouping_sets else ""),
        "Window": lambda: f"Window [{', '.join(n for _, n in node.fns)}]",
        "Sort": lambda: f"Sort {[(str(k), a) for k, a, _ in node.keys]}",
        "Limit": lambda: f"Limit {node.n}",
        "Distinct": lambda: "Distinct",
        "SetOp": lambda: f"SetOp {node.op}",
        "Pipeline": lambda: "Pipeline "
        + "".join(
            "F" if isinstance(s, Filter) else "P" for s in node.stages
        )
        + ("+A" if node.agg is not None else ""),
    }.get(name, lambda: name)()


def explain(node: PlanNode, indent=0) -> str:
    pad = "  " * indent
    out = pad + node_desc(node) + "\n"
    for c in node.children():
        if c is not None:
            out += explain(c, indent + 1)
    return out
