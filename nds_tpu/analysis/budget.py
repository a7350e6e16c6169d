"""Static plan budgeter: compile-time cardinality and peak-HBM analysis.

The reference harness budgets executor memory *statically in configuration*
(reference: nds/power_run_gpu.template:29-36 pins executor/pinned-pool sizes
before a single task runs) and lets Spark's planner pick the spill/exchange
shape up front. This engine used to discover memory misfits at runtime, one
failed dispatch at a time, via the report ladder's OOM rungs. This module is
the static half of that contract: it walks a bound + rewritten plan and
derives, per node,

  * a cardinality bound (catalog row counts, filter-selectivity heuristics,
    join key-uniqueness from TABLE_PRIMARY_KEYS, blocked-union annotations),
  * a peak-HBM byte model mirroring what exec.py actually materializes
    (power-of-two capacity buckets, gather/pair-table widths, sort key
    words, segment-reduce outputs, union concats, per-window slices),

and folds them into one **verdict** the planner acts on:

  direct            the whole plan's modeled peak fits the budget
  blocked           over budget, but the overage windows away through the
                    plan's blocked-union aggregates: execute those in
                    statically sized row windows (`window_rows` is chosen
                    here, and exec._blocked_union_ctx consumes it ahead of
                    the runtime derivation)
  over              over budget with no (sufficient) windowing seam but
                    under the reject line: admitted, with the prediction
                    stored so the report ladder's first device-OOM rung
                    applies the static recommendation instead of blind
                    halving
  reject            beyond the reject line even windowed: admission control
                    refuses the statement at plan time (PlanBudgetError,
                    classified `planner` -> the report ladder fails fast)
  unknown           some base-table cardinality is unavailable (schema-only
                    entry with no scale factor, csv/lakehouse path): the
                    verdict carries no enforcement

The model is an *upper bound with a documented slack*: capacity bucketing
rounds every row count up to a power of two and child results are assumed
live while a parent executes, so the estimate over-approximates the real
working set; selectivity heuristics may undershoot pathological filters,
which the calibration test bounds at `CALIBRATION_SLACK` (see
tests/test_budget.py). The CI gate (tools/plan_verify_corpus.py --budget)
holds the two load-bearing calibration points: every template admitted at
SF1 (known to fit 103/103), and the round-5 SF10 device-OOM set flagged
over-budget.

Knobs: conf `engine.plan_budget` / env NDS_PLAN_BUDGET = off | warn | on
(default on; warn computes + traces but never rejects), conf
`engine.plan_budget_bytes` / env NDS_PLAN_BUDGET_BYTES (modeled working-set
budget, default DEFAULT_BUDGET_BYTES), conf `engine.plan_budget_sf`
(schema-only sessions: synthesize base-table rows from the TPC-DS scale
model instead of reading data).
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from dataclasses import dataclass, field
from typing import Optional

from ..engine import expr as E
from ..engine import plan as P
from ..schema import TABLE_PARTITIONING, TABLE_PRIMARY_KEYS

# ---------------------------------------------------------------------------
# TPC-DS row-count model (python port of datagen/native/rowcounts.hpp — the
# generator and the budgeter must agree on what a scale factor means)
# ---------------------------------------------------------------------------

#: spec row counts (TPC-DS v3.2.0 table 3-2) at the defined scale knots
_SCALE_KNOTS = (1, 10, 100, 1000, 3000, 10000, 100000)

_DIM_SCALE_POINTS = {
    "call_center": (6, 24, 30, 42, 48, 54, 60),
    "catalog_page": (11718, 12000, 20400, 30000, 36000, 40000, 50000),
    "customer": (100000, 500000, 2000000, 12000000, 30000000, 65000000,
                 100000000),
    "customer_address": (50000, 250000, 1000000, 6000000, 15000000,
                         32500000, 50000000),
    "item": (18000, 102000, 204000, 300000, 360000, 402000, 502000),
    "promotion": (300, 500, 1000, 1500, 1800, 2000, 2500),
    "reason": (35, 45, 55, 65, 67, 70, 75),
    "store": (12, 102, 402, 1002, 1350, 1500, 1902),
    "warehouse": (5, 10, 15, 20, 22, 25, 30),
    "web_page": (60, 200, 2040, 3000, 3600, 4002, 5004),
    "web_site": (30, 42, 54, 60, 66, 78, 96),
}

_FIXED_ROWS = {
    "customer_demographics": 1920800,
    "household_demographics": 7200,
    "date_dim": 73049,
    "time_dim": 86400,
    "income_band": 20,
    "ship_mode": 20,
}

#: (orders at SF1, average lines per order) per sales channel; returns are
#: ~10% of sales lines (facts.hpp is_returned)
_CHANNELS = {
    "store_sales": (240000, 12.0),
    "catalog_sales": (160000, 9.0),
    "web_sales": (60000, 12.0),
}
_RETURN_FRACTION = 0.10
_INVENTORY_WEEKS = 261


def _interp_rows(points, sf: float) -> int:
    if sf <= 1.0:
        return max(int(math.ceil(points[0] * sf)), min(points[0], 2))
    for i in range(len(_SCALE_KNOTS) - 1):
        if sf <= _SCALE_KNOTS[i + 1]:
            t = (math.log(sf) - math.log(_SCALE_KNOTS[i])) / (
                math.log(_SCALE_KNOTS[i + 1]) - math.log(_SCALE_KNOTS[i])
            )
            lo = math.log(points[i])
            hi = math.log(points[i + 1])
            return int(round(math.exp(lo + t * (hi - lo))))
    return points[-1]


def spec_table_rows(table: str, sf: float) -> Optional[int]:
    """Estimated base-table rows at scale factor `sf` under the generator's
    scaling model (exact at the spec's defined scale points for dims,
    expected value for the line-count-randomized facts). None for a table
    the model doesn't know (synthetic test registrations)."""
    if table in _DIM_SCALE_POINTS:
        return _interp_rows(_DIM_SCALE_POINTS[table], sf)
    if table in _FIXED_ROWS:
        return _FIXED_ROWS[table]
    if table in _CHANNELS:
        orders, lines = _CHANNELS[table]
        return max(int(round(orders * sf * lines)), 1)
    if table.endswith("_returns"):
        sales = table[: -len("_returns")] + "_sales"
        if sales in _CHANNELS:
            orders, lines = _CHANNELS[sales]
            return max(int(round(orders * sf * lines * _RETURN_FRACTION)), 1)
    if table == "inventory":
        item = _interp_rows(_DIM_SCALE_POINTS["item"], sf)
        wh = _interp_rows(_DIM_SCALE_POINTS["warehouse"], sf)
        return _INVENTORY_WEEKS * max(item // 2, 1) * wh
    return None


# ---------------------------------------------------------------------------
# widths / budget resolution
# ---------------------------------------------------------------------------

#: minimum capacity bucket (columnar._MIN_CAP; kept literal so this module
#: never imports jax — the budgeter must run in schema-only CLI contexts)
_MIN_CAP = 1024


def bucket_cap(n: int) -> int:
    cap = _MIN_CAP
    while cap < n:
        cap *= 2
    return cap


def column_row_bytes(dtype) -> int:
    """Device bytes per row of one column: data itemsize + 1 validity byte
    (matches exec._blocked_union_ctx's row_bytes rule). Strings are int32
    dictionary codes on device; decimals are scaled int64."""
    k = dtype.kind
    if k in ("int32", "date", "string", "char", "varchar"):
        return 5
    if k == "bool":
        return 2
    return 9  # int64 / float64 / decimal


def schema_row_bytes(sch: dict) -> int:
    """Bytes per row over a name -> DType schema mapping."""
    return max(sum(column_row_bytes(dt) for dt in sch.values()), 1)


#: default modeled working-set budget. Calibrated against the corpus gate
#: with THIN margins on both sides — treat any change as a calibration
#: event, not a tuning knob: max modeled SF1 peak is 3.75 GiB (q23, 94% of
#: the line; all 103 statements must stay admitted) and the smallest
#: round-5 SF10 device-OOM estimate is 4.74 GiB (q6, must stay flagged).
#: Physically: a 16 GB v5e chip minus the 6 GB catalog residency budget
#: minus allocator/fragmentation headroom.
DEFAULT_BUDGET_BYTES = 4 << 30

#: calibration contract for the model (tests/test_budget.py): the measured
#: per-node materialization (op_span est_bytes high-water) of a query must
#: not exceed CALIBRATION_SLACK x its static peak estimate
CALIBRATION_SLACK = 2.0

#: blocked-union windows get at most this fraction of the budget (the
#: window buffers coexist with cached base tables, the per-window join
#: output and the partial-aggregate merge intermediates) — the derivation
#: Session.union_agg_window_rows used to carry inline
WINDOW_BUDGET_FRACTION = 16

#: out-of-core partition-count cap: past this, per-partition fixed costs
#: (probe re-scan, segment round trips) dominate any HBM relief
SPILL_MAX_PARTITIONS = 256

MODES = ("off", "warn", "on")


def spillable_node(v) -> bool:
    """True when a plan node owns an out-of-core rewrite the executor can
    actually run (exec._spilled_join/_spilled_take/_spilled_distinct):
    inner/left joins and MultiJoins (hash-partitioned build+probe), sorts
    (sorted runs), Distinct and UNION-distinct (partition-hash dedup).
    Everything else — semi/anti/full joins, set ops with whole-input
    semantics, aggregates (the blocked-union seam owns those) — does not
    decompose over hash partitions, and the verifier flags a
    `spill_partitions` annotation landing on one."""
    if isinstance(v, P.Join):
        return v.kind in ("inner", "left")
    if isinstance(v, (P.MultiJoin, P.Sort, P.Distinct)):
        return True
    if isinstance(v, P.SetOp):
        return v.op == "union"
    return False


def choose_spill_partitions(peak_bytes: int, budget_bytes: int) -> int:
    """Statically sized partition count: the smallest power of two that
    models the dominant transient under the budget, clamped to
    [2, SPILL_MAX_PARTITIONS]."""
    ratio = max(
        -(-int(peak_bytes) // max(int(budget_bytes), 1)), 2
    )  # ceil div
    parts = 1 << (ratio - 1).bit_length()
    return int(min(max(parts, 2), SPILL_MAX_PARTITIONS))

#: TPC-DS column-name prefix -> owning table (longest match wins). A
#: column cannot carry more distinct values than its owning table has
#: rows, so this gives the budgeter a sound static NDV bound for group
#: keys (s_store_id groups cap at |store|, not at fact scale) without any
#: runtime statistics.
_COL_PREFIX_TABLE = {
    "ss_": "store_sales", "sr_": "store_returns",
    "cs_": "catalog_sales", "cr_": "catalog_returns",
    "ws_": "web_sales", "wr_": "web_returns", "inv_": "inventory",
    "d_": "date_dim", "t_": "time_dim",
    "c_": "customer", "ca_": "customer_address",
    "cd_": "customer_demographics", "hd_": "household_demographics",
    "ib_": "income_band", "i_": "item", "p_": "promotion",
    "r_": "reason", "s_": "store", "sm_": "ship_mode",
    "w_": "warehouse", "wp_": "web_page", "web_": "web_site",
    "cc_": "call_center", "cp_": "catalog_page",
}


#: foreign-key suffix -> referenced dimension (a FK column's distinct
#: values are bounded by the referenced table's rows — tighter than the
#: owning fact's row count)
_FK_SUFFIX_TABLE = {
    "_item_sk": "item", "_date_sk": "date_dim", "_time_sk": "time_dim",
    "_customer_sk": "customer", "_store_sk": "store",
    "_warehouse_sk": "warehouse", "_promo_sk": "promotion",
    "_cdemo_sk": "customer_demographics",
    "_hdemo_sk": "household_demographics", "_addr_sk": "customer_address",
    "_web_page_sk": "web_page", "_web_site_sk": "web_site",
    "_call_center_sk": "call_center", "_catalog_page_sk": "catalog_page",
    "_ship_mode_sk": "ship_mode", "_reason_sk": "reason",
}


def column_owner_table(col_name: str) -> Optional[str]:
    """The TPC-DS table a column name belongs to by prefix convention
    ("store.s_store_id" -> "store"), or None for derived names."""
    bare = col_name.split(".")[-1]
    best = None
    for pref, table in _COL_PREFIX_TABLE.items():
        if bare.startswith(pref) and (best is None or len(pref) > len(best[0])):
            best = (pref, table)
    return best[1] if best else None


def column_domain_table(col_name: str) -> Optional[str]:
    """The table bounding a column's distinct-value count: the referenced
    dimension for FK-suffixed columns (ss_item_sk -> item), else the
    owning table by prefix."""
    bare = col_name.split(".")[-1]
    for suf, table in _FK_SUFFIX_TABLE.items():
        if bare.endswith(suf):
            return table
    return column_owner_table(col_name)


def resolve_mode(conf: Optional[dict] = None) -> str:
    v = None
    if conf:
        v = conf.get("engine.plan_budget")
    v = v or os.environ.get("NDS_PLAN_BUDGET") or "on"
    v = str(v).lower()
    if v not in MODES:
        raise ValueError(
            f"engine.plan_budget must be one of {MODES}, got {v!r}"
        )
    return v


def resolve_budget_bytes(conf: Optional[dict] = None) -> int:
    v = None
    if conf:
        v = conf.get("engine.plan_budget_bytes")
    v = v or os.environ.get("NDS_PLAN_BUDGET_BYTES")
    return int(v) if v else DEFAULT_BUDGET_BYTES


#: admission-reject line: a plan modeled beyond this is refused outright at
#: plan time (mode `on`). Well above the over-budget line on purpose — a
#: marginally-over plan is still admitted with the ladder pre-armed, only
#: plans that cannot fit the physical device (16 GB v5e HBM minus runtime
#: headroom) are rejected before burning a dispatch on them.
DEFAULT_REJECT_BYTES = 14 << 30


def resolve_reject_bytes(conf: Optional[dict] = None) -> int:
    v = None
    if conf:
        v = conf.get("engine.plan_budget_reject_bytes")
    v = v or os.environ.get("NDS_PLAN_BUDGET_REJECT_BYTES")
    return int(v) if v else DEFAULT_REJECT_BYTES


def default_window_rows(row_bytes: int, budget_bytes: int) -> int:
    """Rows per blocked-union window for `row_bytes`-wide rows under a byte
    budget: ~1/WINDOW_BUDGET_FRACTION of the budget, rounded DOWN to a
    power of two (stable slice shapes), clamped to [64Ki, 16Mi] rows. The
    session-level derivation (`Session.union_agg_window_rows`) delegates
    here; the static verdict path reuses the same clamps so plan-time and
    runtime sizing can never disagree on bounds."""
    budget = budget_bytes // WINDOW_BUDGET_FRACTION
    rows = max(budget // max(row_bytes, 1), 1)
    pow2 = 1 << (rows.bit_length() - 1)
    return int(min(max(pow2, 1 << 16), 1 << 24))


def derive_share_bytes(total_bytes: int, fraction: int,
                       lo: int, hi: int) -> int:
    """A byte budget as 1/`fraction` of a measured resource, rounded DOWN
    to a power of two and clamped to [lo, hi] — the same shape as the
    union-window derivation above (default_window_rows), generalized so
    every `auto` budget in the engine sizes itself the same way: the spill
    pool's host-RAM share (engine.spill_pool_bytes=auto) and the AOT
    executable cache's disk share (engine.aot_cache_bytes unset) both
    delegate here instead of inventing their own formula."""
    share = max(int(total_bytes) // max(int(fraction), 1), 1)
    pow2 = 1 << (share.bit_length() - 1)
    return int(min(max(pow2, lo), hi))


#: serve-mode admission sizing: one concurrently admitted request is
#: assumed to transiently hold up to this much device working set beyond
#: the catalog residency (an admitted-direct statement's modeled peak is
#: bounded by the budget line; slots = budget // this, so full occupancy
#: stays inside the same working-set budget single-stream admission uses)
SERVE_SLOT_BYTES = 1 << 30


def serve_concurrency(conf: Optional[dict] = None) -> int:
    """Admission slots (= worker-pool size) for `nds-tpu-submit serve`.

    `engine.serve_workers` / NDS_SERVE_WORKERS overrides; otherwise the
    count derives from the SAME working-set budget the plan budgeter
    admits statements against (`resolve_budget_bytes`): one slot per
    SERVE_SLOT_BYTES of budget, clamped to [1, 16]. The default 4 GiB
    budget therefore carries 4 concurrent requests — sized so the sum of
    concurrently admitted working sets stays inside what one admitted
    batch statement could have used alone."""
    v = None
    if conf:
        v = conf.get("engine.serve_workers")
    if v is None:
        v = os.environ.get("NDS_SERVE_WORKERS")
    if v:
        try:
            return max(int(v), 1)
        except (TypeError, ValueError):
            pass
    budget = resolve_budget_bytes(conf)
    return int(min(max(budget // SERVE_SLOT_BYTES, 1), 16))


def host_ram_bytes() -> int:
    """Physical host RAM in bytes (sysconf), falling back to a 16 GiB
    assumption on platforms without the counters — the `auto` budget
    derivations must never crash over a missing proc interface."""
    try:
        pages = os.sysconf("SC_PHYS_PAGES")
        page = os.sysconf("SC_PAGE_SIZE")
        if pages > 0 and page > 0:
            return int(pages) * int(page)
    except (ValueError, OSError, AttributeError):
        pass
    return 16 << 30


# ---------------------------------------------------------------------------
# catalog cardinality source
# ---------------------------------------------------------------------------


class CatalogStats:
    """Base-table row counts for the budgeter, best source first:

    1. actual loaded rows (`_Entry.nrows`) or in-memory arrow row counts;
    2. parquet/orc dataset metadata (`count_rows`, footer-only; memoized
       per entry so a session pays it once);
    3. the TPC-DS scale model when a scale factor is declared
       (conf `engine.plan_budget_sf`, schema-only sessions);
    4. None — cardinality unknown, the verdict degrades to `unknown`.
    """

    def __init__(self, catalog, scale_factor: Optional[float] = None):
        self.catalog = catalog
        self.scale_factor = scale_factor

    def table_rows(self, name: str) -> Optional[int]:
        e = self.catalog.entries.get(name) if self.catalog else None
        if e is not None:
            if e.nrows is not None:
                return int(e.nrows)
            if e.arrow is not None:
                return int(e.arrow.num_rows)
            if e.fmt in ("parquet", "orc", "lakehouse"):
                # memoized metadata count; a FAILED probe is memoized as
                # -1 but must still fall through to the scale model below
                # (a transient IO error must not pin the table to
                # `unknown` for the session's lifetime). Lakehouse tables
                # answer from the manifest (pinned snapshot when one
                # exists, else the current head) — a COLD lakehouse
                # warehouse must still produce enforceable verdicts, or a
                # serving fleet's admission edge degrades to `unknown`
                # until every table has been touched once.
                cached = getattr(e, "budget_est_rows", None)
                if cached is None:
                    try:
                        if e.fmt == "lakehouse":
                            snap = e.pinned_snapshot
                            if snap is None:
                                from ..lakehouse.table import LakehouseTable

                                snap = LakehouseTable(e.path).snapshot()
                            cached = int(snap.num_rows())
                        else:
                            cached = int(
                                self.catalog._dataset(e).count_rows()
                            )
                    except Exception:
                        cached = -1
                    e.budget_est_rows = cached
                if cached >= 0:
                    return cached
        if self.scale_factor is not None:
            return spec_table_rows(name, self.scale_factor)
        return None

    def schema(self, name: str):
        return self.catalog.schema(name) if self.catalog else None


# ---------------------------------------------------------------------------
# selectivity heuristics
# ---------------------------------------------------------------------------

_SEL_EQ = 0.1
_SEL_RANGE = 0.4
_SEL_BETWEEN = 0.3
_SEL_LIKE = 0.25
_SEL_NULL = 0.1
_SEL_FLOOR = 0.02  # conjunction floor: heuristics must not promise miracles


def selectivity(e) -> float:
    """Heuristic fraction of rows a predicate keeps, in [_SEL_FLOOR, 1].
    Deliberately coarse and floor-clamped: the budgeter needs an upper
    bound, not a cost-based optimum, so deep conjunctions stop shrinking at
    _SEL_FLOOR instead of promising near-zero cardinalities the data may
    not deliver (FK distributions are not uniform over PK domains)."""
    return max(_SEL_FLOOR, min(_raw_sel(e), 1.0))


def _raw_sel(e) -> float:
    if isinstance(e, E.BinOp):
        if e.op == "and":
            return max(_raw_sel(e.left) * _raw_sel(e.right), _SEL_FLOOR)
        if e.op == "or":
            return min(_raw_sel(e.left) + _raw_sel(e.right), 1.0)
        if e.op == "=":
            return _SEL_EQ
        if e.op in ("<", "<=", ">", ">="):
            return _SEL_RANGE
        if e.op in ("<>", "!="):
            return 0.9
        return 1.0
    if isinstance(e, E.Between):
        return _SEL_BETWEEN
    if isinstance(e, E.InList):
        return min(_SEL_EQ * max(len(e.values), 1), 0.6)
    if isinstance(e, E.Like):
        return _SEL_LIKE
    if isinstance(e, E.UnaryOp):
        if e.op == "not":
            return max(1.0 - _raw_sel(e.operand), _SEL_FLOOR)
        if e.op == "isnull":
            return _SEL_NULL
        if e.op == "isnotnull":
            return 1.0
    return 1.0


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------


class PlanBudgetError(Exception):
    """Admission control: the plan's modeled peak exceeds the budget even
    under windowed execution. Deterministic for a given catalog, so
    faults.classify maps it to the `planner` kind and the report ladder
    fails fast instead of walking OOM rungs."""

    def __init__(self, peak_bytes: int, budget_bytes: int, detail: str = ""):
        self.peak_bytes = peak_bytes
        self.budget_bytes = budget_bytes
        super().__init__(
            f"plan rejected by admission control: modeled peak "
            f"{peak_bytes / (1 << 30):.2f} GiB exceeds the "
            f"{budget_bytes / (1 << 30):.2f} GiB plan budget"
            + (f" ({detail})" if detail else "")
        )


@dataclass
class NodeEstimate:
    """Per-node static estimate. `alloc_bytes` is what executing THIS node
    materializes (output buffers + transient work: key words, pair gathers,
    sort scratch); `live_bytes` is what the node's result pins for its
    parent; `peak_bytes` is the modeled high-water of the whole subtree
    (children retained while later siblings/parent work runs). In mesh
    mode every byte figure is PER DEVICE: a `sharded` node's buffers
    divide by the mesh width, a replicated node's are charged in full on
    every chip (the layout Catalog._to_device actually places)."""

    node: object
    desc: str
    rows: int
    width: int
    cap: int
    alloc_bytes: int
    live_bytes: int
    peak_bytes: int
    blocked: bool = False
    sharded: bool = False
    children: list = field(default_factory=list)


@dataclass
class PlanBudget:
    """The analyzer's statement-level result."""

    nodes: list  # post-order NodeEstimate list
    peak_bytes: int  # modeled peak, blocked-union aggregates DIRECT
    peak_blocked_bytes: int  # modeled peak with blocked aggs windowed
    budget_bytes: int
    verdict: str  # direct | blocked | spill | over | reject | unknown
    window_rows: Optional[int] = None  # set when verdict == blocked
    #: mesh width the model divided sharded node bytes by (None = the
    #: single-device model); the verdict is then PER DEVICE — what each
    #: chip's working set must fit, with replicated relations charged on
    #: every chip
    mesh_devices: Optional[int] = None
    unknown_tables: list = field(default_factory=list)
    #: the plan carries >= 1 out-of-core seam (spillable_node) — recorded
    #: for EVERY verdict so the report ladder's spill_retry rung knows an
    #: unpredicted device OOM can retry through the spill pool
    spillable: bool = False
    spill_partitions: Optional[int] = None  # set when verdict == spill
    #: nodes whose static row estimate a recorded actual replaced
    #: (engine.plan_feedback=on; 0 = the pure static model)
    feedback_overrides: int = 0

    def table(self, limit: int = 0) -> str:
        """Human-readable per-node estimate table (explain --budget)."""
        rows = self.nodes if not limit else self.nodes[-limit:]
        out = [
            f"{'rows':>12}  {'width':>6}  {'cap':>12}  {'alloc':>10}  "
            f"{'peak':>10}  node"
        ]
        for n in rows:
            out.append(
                f"{n.rows:>12}  {n.width:>6}  {n.cap:>12}  "
                f"{_fmt_bytes(n.alloc_bytes):>10}  "
                f"{_fmt_bytes(n.peak_bytes):>10}  "
                f"{'[blocked] ' if n.blocked else ''}"
                f"{'[sharded] ' if n.sharded else ''}{n.desc[:72]}"
            )
        out.append(
            (
                f"verdict ({self.mesh_devices}-device mesh, per device): "
                if self.mesh_devices
                else "verdict: "
            )
            + f"{self.verdict}  peak={_fmt_bytes(self.peak_bytes)}"
            f" (windowed={_fmt_bytes(self.peak_blocked_bytes)})"
            f" budget={_fmt_bytes(self.budget_bytes)}"
            + (f" window_rows={self.window_rows}" if self.window_rows else "")
            + (
                f" spill_partitions={self.spill_partitions}"
                if self.spill_partitions
                else ""
            )
            + (
                f" unknown_tables={sorted(set(self.unknown_tables))}"
                if self.unknown_tables
                else ""
            )
        )
        return "\n".join(out)


def _fmt_bytes(b: int) -> str:
    if b >= 1 << 30:
        return f"{b / (1 << 30):.2f}G"
    if b >= 1 << 20:
        return f"{b / (1 << 20):.1f}M"
    if b >= 1 << 10:
        return f"{b / (1 << 10):.1f}K"
    return str(int(b))


class PlanBudgeter:
    """Walks a bound + rewritten plan bottom-up, producing NodeEstimates.

    Schema resolution is delegated to the PlanVerifier's memoized static
    dtype inference so the byte model and the verifier can never disagree
    about a node's output schema. Estimates memoize by node id: shared
    subtrees (CTE diamonds) cost one walk, and when two parents consume
    one shared result its live bytes count at each consumer — which is
    what the executor's _cte_cache really does to memory."""

    def __init__(self, catalog=None, stats: Optional[CatalogStats] = None,
                 budget_bytes: Optional[int] = None, windowed: bool = False,
                 mesh_devices: Optional[int] = None, feedback=None):
        from .verifier import InputWidthSchemas, _count_plan_refs

        self.stats = stats or CatalogStats(catalog)
        self.budget_bytes = (
            budget_bytes if budget_bytes is not None else DEFAULT_BUDGET_BYTES
        )
        #: mesh width: sharded node bytes divide by this (per-device
        #: verdict), replicated relations stay charged in full per device.
        #: 1 = the single-device model, byte-identical to pre-mesh output.
        self.n_dev = max(int(mesh_devices or 1), 1)
        #: windowed=True models blocked-union aggregates on the windowed
        #: executor path (branches materialized, concat/join/aggregate per
        #: bounded window) instead of the direct full-concat path
        self.windowed = windowed
        self._ver = InputWidthSchemas(catalog)
        self._count_refs = _count_plan_refs
        self._memo: dict = {}
        self._post: list = []
        self.unknown_tables: list = []
        #: statically derived window rows per blocked aggregate modeled in
        #: windowed mode (plan window = min over these)
        self.blocked_windows: list = []
        #: measured-cardinality overrides (engine.plan_feedback=on):
        #: {id(node): recorded actual rows} from the FeedbackStore. None
        #: (or empty) keeps the static model byte-identical; applied
        #: overrides are collected for the plan_feedback event
        self.feedback = feedback or None
        self.feedback_applied: list = []

    # -- entry ----------------------------------------------------------
    def run(self, root: P.PlanNode) -> int:
        """Walk the plan; return the modeled peak bytes. Scalar subquery
        plans execute as separate statements before the main plan, so
        their peaks are independent candidates."""
        self._ver._refs = self._count_refs(root)
        peak = self._est(root).peak_bytes
        for sub in self._subquery_plans(root):
            peak = max(peak, self._est(sub).peak_bytes)
        return peak

    def _subquery_plans(self, root):
        return [
            v.plan
            for v in P.walk_plan(root)
            if isinstance(v, E.ScalarSubquery) and v.plan is not None
        ]

    # -- helpers --------------------------------------------------------
    def _schema(self, node) -> dict:
        sch = self._ver._schema_of(node)
        return sch if sch is not None else {}

    def _width(self, node) -> int:
        return schema_row_bytes(self._schema(node))

    def _div(self, nbytes, sharded: bool) -> int:
        """Per-device share of a byte figure: sharded buffers split over
        the mesh width, everything else is charged in full on each chip
        (the replicated-dim placement). Identity on a 1-wide mesh."""
        if sharded and self.n_dev > 1:
            return int(nbytes) // self.n_dev
        return int(nbytes)

    def _finish(self, node, rows, width, alloc, children,
                live=None, blocked=False, sharded=False) -> NodeEstimate:
        rows = max(int(rows), 0)
        fb = self.feedback.get(id(node)) if self.feedback else None
        if fb is not None:
            # measured actual overrides the static estimate (clamped:
            # the recorded value is the observed MAXIMUM, so the new
            # estimate is never below anything this node has produced).
            # Allocation scales with the capacity bucket ratio — the
            # per-rule alloc terms are cap-proportional, and children's
            # own overrides were already applied bottom-up
            fb = max(int(fb), 0)
            if fb != rows:
                old_cap = bucket_cap(max(rows, 1))
                new_cap = bucket_cap(max(fb, 1))
                if new_cap != old_cap:
                    alloc = int(alloc * (new_cap / old_cap))
                    if live is not None:
                        live = int(live * (new_cap / old_cap))
                rows = fb
                self.feedback_applied.append(node)
        cap = bucket_cap(max(rows, 1))
        live_b = (
            live if live is not None else self._div(cap * width, sharded)
        )
        # executor retention model: children run left-to-right, each
        # earlier child's result stays live while later siblings execute,
        # and all children stay live while this node materializes
        peak = 0
        acc = 0
        for c in children:
            peak = max(peak, acc + c.peak_bytes)
            acc += c.live_bytes
        peak = max(peak, acc + alloc)
        est = NodeEstimate(
            node=node,
            desc=P.node_desc(node),
            rows=rows,
            width=width,
            cap=cap,
            alloc_bytes=int(alloc),
            live_bytes=int(live_b),
            peak_bytes=int(peak),
            blocked=blocked,
            sharded=bool(sharded),
        )
        self._post.append(est)
        return est

    def _est(self, node) -> NodeEstimate:
        if node is None:
            return NodeEstimate(None, "missing", 0, 1, _MIN_CAP, 0, 0, 0)
        key = id(node)
        hit = self._memo.get(key)
        if hit is not None:
            return hit
        m = getattr(self, f"_est_{type(node).__name__.lower()}", None)
        if m is None:
            est = self._finish(node, 1, self._width(node), 0, [])
        else:
            est = m(node)
        self._memo[key] = est
        return est

    # -- per-node rules (mirror exec.py materialization; sharded-ness
    # mirrors the verifier's PartitionSpec propagation so the byte model
    # and the sharding rules can never disagree about layout) ------------
    def _scan_sharded(self, table: str, cap: int) -> bool:
        """True when Catalog._to_device would row-shard this base table
        over the mesh: a registered fact (TABLE_PARTITIONING — the same
        registry table_partition_spec derives from) whose capacity bucket
        divides the mesh width (else the loud replication fallback)."""
        return (
            self.n_dev > 1
            and table in TABLE_PARTITIONING
            and cap % self.n_dev == 0
        )

    def _est_scan(self, node: P.Scan) -> NodeEstimate:
        rows = self.stats.table_rows(node.table)
        # zone-map surviving-row bound (Session._prune_lake_scans): a HARD
        # upper bound from the pinned manifest's per-file stats — tighter
        # than any table-level estimate whenever pruning fired, and a
        # usable size even for tables the stats layer knows nothing about
        prune_rows = getattr(node, "prune_rows", None)
        if prune_rows is not None:
            rows = prune_rows if rows is None else min(rows, prune_rows)
        if rows is None:
            self.unknown_tables.append(node.table)
            rows = 0
        width = self._width(node)
        cap = bucket_cap(max(rows, 1))
        sharded = self._scan_sharded(node.table, cap)
        return self._finish(
            node, rows, width, self._div(cap * width, sharded), [],
            sharded=sharded,
        )

    def _est_materializedscan(self, node: P.MaterializedScan) -> NodeEstimate:
        rows = 1
        if node.table is not None:
            known = node.table.nrows_known
            rows = known if known is not None else int(node.table.cap)
        width = self._width(node)
        # already materialized: no new allocation, but it is live input
        return self._finish(node, rows, width, 0, [])

    def _est_project(self, node: P.Project) -> NodeEstimate:
        child = self._est(node.child)
        sch = self._schema(node)
        width = schema_row_bytes(sch)
        computed = sum(
            column_row_bytes(dt)
            for (e, _name), dt in zip(node.items, sch.values())
            if not isinstance(e, E.Col)
        )
        return self._finish(
            node, child.rows, width,
            self._div(child.cap * computed, child.sharded), [child],
            sharded=child.sharded,
        )

    def _est_filter(self, node: P.Filter) -> NodeEstimate:
        child = self._est(node.child)
        rows = int(math.ceil(child.rows * selectivity(node.predicate)))
        # deferred compaction: the live mask is the only new buffer; data
        # buffers are shared with the child (capacity stays the child's)
        return self._finish(
            node, rows, child.width,
            self._div(child.cap, child.sharded), [child],
            live=self._div(child.cap * child.width, child.sharded),
            sharded=child.sharded,
        )

    def _est_pipeline(self, node: P.Pipeline) -> NodeEstimate:
        child = self._est(node.child)
        rows = child.rows
        for s in node.stages:
            if isinstance(s, P.Filter):
                rows = int(math.ceil(rows * selectivity(s.predicate)))
        if node.agg is not None:
            # the fused body runs the chain AND the partial-aggregate
            # scatter in ONE dispatch over the chain INPUT (masks deferred
            # to the boundary), so the key/sort-word working set scales
            # with the child's capacity, not the post-filter estimate
            return self._agg_estimate(node, node.agg, [child], rows,
                                      child.cap, in_sharded=child.sharded)
        width = self._width(node)
        # the fused body materializes the full output column set at the
        # input capacity in one dispatch (masks deferred to the boundary)
        return self._finish(
            node, rows, width, self._div(child.cap * width, child.sharded),
            [child], sharded=child.sharded,
        )

    def _keys_unique(self, side, keys) -> bool:
        """True when `keys` cover a declared primary key of the side's
        base table (a Scan reached through Filter/Project/Pipeline
        wrappers) — the static stand-in for the runtime unique-key probe."""
        _, base = P._peel_wrappers(side)
        if not isinstance(base, P.Scan):
            return False
        pk = TABLE_PRIMARY_KEYS.get(base.table)
        if pk is None:
            return False
        names = set()
        for k in keys:
            for c in E.walk(k):
                if isinstance(c, E.Col):
                    names.add(c.name.split(".")[-1])
        return set(pk) <= names

    def _est_join(self, node: P.Join) -> NodeEstimate:
        left = self._est(node.left)
        right = self._est(node.right)
        if node.kind == "cross":
            rows = max(left.rows, 1) * max(right.rows, 1)
        elif node.kind in ("semi", "anti", "mark"):
            rows = left.rows
        elif self._keys_unique(node.right, node.right_keys):
            rows = left.rows
        elif self._keys_unique(node.left, node.left_keys):
            rows = right.rows
        else:
            rows = max(left.rows, right.rows)
        width = self._width(node)
        cap = bucket_cap(max(rows, 1))
        sharded = left.sharded or right.sharded
        # key words (8B per side) + compaction of both inputs + the pair
        # table gathered at the output width — per side's own layout: a
        # sharded fact's words/compaction split over the mesh (exchange /
        # local probe), a replicated dim pays full on every chip
        alloc = (
            self._div(8 * left.cap + left.cap * left.width, left.sharded)
            + self._div(8 * right.cap + right.cap * right.width,
                        right.sharded)
            + self._div(cap * width, sharded)
        )
        return self._finish(node, rows, width, alloc, [left, right],
                            sharded=sharded)

    def _est_multijoin(self, node: P.MultiJoin) -> NodeEstimate:
        rels = [self._est(r) for r in node.relations]
        width = self._width(node)
        # greedy pairwise joins: output rows bounded by the largest
        # non-unique (fact-like) relation — a relation whose edges
        # collectively cover its base table's primary key (single-column
        # dims; inventory probed on date+item+warehouse, q72) matches at
        # most one row per probe combination and never expands the join;
        # the last two pair tables carry ~the full accumulated width
        edge_cols = [set() for _ in node.relations]
        for i, j, le, re_ in node.edges:
            for idx, e in ((i, le), (j, re_)):
                if 0 <= idx < len(edge_cols):
                    for c in E.walk(e):
                        if isinstance(c, E.Col):
                            edge_cols[idx].add(c.name.split(".")[-1])
        non_unique = []
        for i, r in enumerate(node.relations):
            _, base = P._peel_wrappers(r)
            pk = (
                TABLE_PRIMARY_KEYS.get(base.table)
                if isinstance(base, P.Scan)
                else None
            )
            if pk is None or not set(pk) <= edge_cols[i]:
                non_unique.append(rels[i].rows)
        rows = max(non_unique or [r.rows for r in rels] or [1])
        cap = bucket_cap(max(rows, 1))
        sharded = any(r.sharded for r in rels)
        alloc = self._div(2 * cap * width, sharded) + sum(
            self._div(8 * r.cap, r.sharded) for r in rels
        )
        return self._finish(node, rows, width, alloc, rels, sharded=sharded)

    def _agg_groups(self, agg, in_rows: int) -> int:
        """Group-count bound. Each key column's distinct values are bounded
        by its domain table's rows (FK suffix -> referenced dim, else
        owning table by prefix), and keys sharing one domain table count
        that table ONCE (all item-attribute keys together cannot exceed
        |item| combinations). Any derived key falls back to the input-rows
        bound — the executor cannot produce more groups than input rows."""
        if not agg.keys:
            return 1
        in_rows = max(in_rows, 1)
        domains = {}
        for e, _name in agg.keys:
            owner = (
                column_domain_table(e.name) if isinstance(e, E.Col) else None
            )
            rows_t = self.stats.table_rows(owner) if owner else None
            if rows_t is None:
                return in_rows
            domains[owner] = max(rows_t, 1)
        prod = 1
        for rows_t in domains.values():
            prod *= rows_t
            if prod >= in_rows:
                return in_rows
        return max(min(prod, in_rows), 1)

    def _agg_estimate(self, node, agg, children, in_rows, in_cap,
                      blocked=False, in_sharded=False) -> NodeEstimate:
        sch = self._schema(node)
        width = schema_row_bytes(sch)
        groups = self._agg_groups(agg, in_rows)
        levels = min(len(agg.grouping_sets), 3) if agg.grouping_sets else 1
        rows = groups * (2 if agg.grouping_sets else 1)
        cap = bucket_cap(max(rows, 1))
        # segment-reduce path: 2 x 8B key/sort words over the input (per
        # shard under a mesh — the scatter-add lowers to per-chip partials)
        # + the group output (x cascade levels' incremental concat), which
        # MERGES replicated (psum) and is charged in full per device
        alloc = self._div(16 * in_cap, in_sharded) + levels * cap * width
        return self._finish(node, rows, width, alloc, children,
                            blocked=blocked)

    def _est_aggregate(self, node: P.Aggregate) -> NodeEstimate:
        if node.blocked_union and self.windowed:
            shape = P.union_agg_shape(node)
            if shape is not None:
                return self._est_blocked_agg(node, shape)
        child = self._est(node.child)
        return self._agg_estimate(
            node, node, [child], child.rows, child.cap,
            blocked=bool(node.blocked_union), in_sharded=child.sharded,
        )

    def _est_blocked_agg(self, node: P.Aggregate, shape) -> NodeEstimate:
        """The windowed executor path (exec._blocked_union_ctx): union
        branches execute and stay fully materialized, but the concat never
        happens — alignment, the dimension joins and the partial aggregate
        run per bounded window, and partials merge into group-sized
        tables. Peak = branches + dims + O(window x joined width) +
        O(3 x groups x output width)."""
        outer, join, inner, branch_plans = shape
        children = [self._est(b) for b in branch_plans]
        joined_width = self._width(node.child)
        branch_width = max((c.width for c in children), default=9)
        if join is not None:
            mj, uidx = join
            children += [
                self._est(r) for i, r in enumerate(mj.relations) if i != uidx
            ]
        in_rows = sum(
            c.rows for c in children[: len(branch_plans)]
        )
        row_bytes = max(branch_width, joined_width)
        wrows = default_window_rows(row_bytes, self.budget_bytes)
        self.blocked_windows.append(wrows)
        wcap = bucket_cap(wrows)
        groups = self._agg_groups(node, in_rows)
        out_width = self._width(node)
        gcap = bucket_cap(max(groups, 1))
        # aligned window slice + per-window join pair/wrapped output +
        # key words, plus merged/part/concat group tables
        alloc = wcap * (branch_width + joined_width + 16) + 3 * gcap * out_width
        levels = min(len(node.grouping_sets), 3) if node.grouping_sets else 1
        rows = groups * (2 if node.grouping_sets else 1)
        return self._finish(node, rows, out_width, alloc * min(levels, 2),
                            children, blocked=True)

    def _est_window(self, node: P.Window) -> NodeEstimate:
        child = self._est(node.child)
        width = self._width(node)
        # NOT divided under a mesh: the generic window sort all-gathers,
        # so each device pays the full working set (the conservative
        # bound; a future dist-window rewrite can claim the division)
        alloc = 16 * child.cap + 8 * child.cap * max(len(node.fns), 1)
        return self._finish(node, child.rows, width, alloc, [child],
                            sharded=child.sharded)

    def _est_sort(self, node: P.Sort) -> NodeEstimate:
        child = self._est(node.child)
        width = child.width
        # sharded input: the samplesort exchange range-partitions, so no
        # device ever materializes the whole table (exec._try_dist_sort)
        alloc = self._div(16 * child.cap + child.cap * width, child.sharded)
        return self._finish(node, child.rows, width, alloc, [child],
                            sharded=child.sharded)

    def _est_limit(self, node: P.Limit) -> NodeEstimate:
        child = self._est(node.child)
        rows = min(child.rows, max(int(node.n), 0))
        return self._finish(node, rows, child.width, 0, [child],
                            sharded=child.sharded)

    def _est_distinct(self, node: P.Distinct) -> NodeEstimate:
        child = self._est(node.child)
        # input-side dedup work splits over shards; the deduped output
        # merges replicated (like Aggregate), so live bytes stay full
        alloc = self._div(
            16 * child.cap + child.cap * child.width, child.sharded
        )
        return self._finish(node, child.rows, child.width, alloc, [child])

    def _est_setop(self, node: P.SetOp) -> NodeEstimate:
        left = self._est(node.left)
        right = self._est(node.right)
        width = self._width(node)
        rows = left.rows + right.rows
        if node.op in ("intersect", "except"):
            rows = left.rows
        cap = bucket_cap(max(rows, 1))
        # the concat materializes both sides into one capacity bucket;
        # distinct set ops add a sort-words pass. Sharded only when BOTH
        # sides are (the verifier's sharding-axis rule forbids mixing)
        sharded = left.sharded and right.sharded
        alloc = self._div(
            cap * width + (16 * cap if node.op != "union_all" else 0),
            sharded,
        )
        if node.op == "union":
            rows = max(rows // 2, 1)
        return self._finish(node, rows, width, alloc, [left, right],
                            sharded=sharded)


# ---------------------------------------------------------------------------
# statement-level entry points
# ---------------------------------------------------------------------------


def analyze_plan(
    plan: P.PlanNode,
    catalog=None,
    scale_factor: Optional[float] = None,
    budget_bytes: Optional[int] = None,
    reject_bytes: Optional[int] = None,
    mesh_devices: Optional[int] = None,
    feedback=None,
) -> PlanBudget:
    """Analyze one bound + rewritten plan against a catalog (or the TPC-DS
    scale model when `scale_factor` is given): a direct-path pass, a
    windowed pass when the plan carries blocked-union aggregates, and the
    verdict folding both against the two budget lines:

      direct   modeled peak fits the budget
      blocked  over budget, fits once blocked-union aggregates run in
               statically sized windows (`window_rows`)
      over     over budget with no (sufficient) windowing seam, but under
               the reject line: admitted, prediction armed for the ladder
      reject   beyond the reject line even windowed — admission refuses it
      unknown  some base-table cardinality unavailable; no enforcement

    With `mesh_devices` > 1 the model is PER DEVICE: sharded node bytes
    divide by the mesh width, replicated relations are charged on every
    chip, and the verdict answers "does each chip's share fit its HBM
    budget" — the admission question a mesh session (and serve mode on
    one) actually has.

    `feedback` ({id(node): recorded actual rows}, engine.plan_feedback=on)
    replaces static per-node row estimates with measured cardinalities
    before verdict folding; None (the default, and every pre-feedback
    caller) is byte-identical to the static model."""
    stats = CatalogStats(catalog, scale_factor)
    direct = PlanBudgeter(catalog, stats, budget_bytes, windowed=False,
                          mesh_devices=mesh_devices, feedback=feedback)
    peak = direct.run(plan)
    budget = direct.budget_bytes
    reject_line = (
        reject_bytes if reject_bytes is not None else DEFAULT_REJECT_BYTES
    )
    has_blocked = any(e.blocked for e in direct._post)
    peak_blocked = peak
    window_rows = None
    if has_blocked:
        win = PlanBudgeter(catalog, stats, budget_bytes, windowed=True,
                           mesh_devices=mesh_devices, feedback=feedback)
        peak_blocked = min(win.run(plan), peak)
        if win.blocked_windows:
            window_rows = min(win.blocked_windows)
    spillable = any(
        spillable_node(v)
        for v in P.walk_plan(plan)
        if isinstance(v, P.PlanNode)
    )
    spill_partitions = None
    if direct.unknown_tables:
        verdict = "unknown"
        window_rows = None
    elif peak <= budget:
        verdict = "direct"
        window_rows = None
    elif has_blocked and peak_blocked <= budget:
        verdict = "blocked"
    elif min(peak_blocked, peak) <= reject_line:
        # admitted over budget. With an out-of-core seam the verdict is
        # `spill` (between `over` and `reject`): the overage partitions
        # away through the executor's spilled join/sort/distinct paths,
        # with the partition count chosen statically here so the first
        # attempt already runs out-of-core instead of discovering the
        # misfit as a device OOM. Seamless plans stay `over` — admitted
        # with the ladder's prediction armed, exactly as before.
        if spillable:
            verdict = "spill"
            spill_partitions = choose_spill_partitions(
                min(peak_blocked, peak), budget
            )
        else:
            verdict = "over"
        window_rows = window_rows if has_blocked else None
    else:
        verdict = "reject"
        window_rows = None
    return PlanBudget(
        nodes=list(direct._post),
        peak_bytes=peak,
        peak_blocked_bytes=peak_blocked,
        budget_bytes=budget,
        verdict=verdict,
        window_rows=window_rows,
        mesh_devices=(
            int(mesh_devices) if mesh_devices and mesh_devices > 1 else None
        ),
        unknown_tables=list(direct.unknown_tables),
        spillable=spillable,
        spill_partitions=spill_partitions,
        feedback_overrides=len(direct.feedback_applied),
    )


def emit_budget_event(tracer, pb: PlanBudget, dur_ms=None) -> None:
    """The one `plan_budget` event payload (EVENT_SCHEMA contract) —
    shared by the plan-time hook and the explain CLI so the two emission
    sites can never drift. No-op without a tracer. `dur_ms`: the analysis'
    wall, which on a table's first use includes reading its row count from
    storage metadata (`table_rows`: seconds for a partitioned fact table)."""
    if tracer is None:
        return
    tracer.emit(
        "plan_budget",
        **({} if dur_ms is None else {"dur_ms": round(dur_ms, 3)}),
        verdict=pb.verdict,
        peak_bytes=pb.peak_bytes,
        budget_bytes=pb.budget_bytes,
        peak_blocked_bytes=pb.peak_blocked_bytes,
        window_rows=pb.window_rows,
        spill_partitions=pb.spill_partitions,
        mesh_devices=pb.mesh_devices,
        nodes=len(pb.nodes),
    )


def session_mesh_devices(session) -> Optional[int]:
    """The mesh width a session's plans execute over: the live
    jax.sharding.Mesh when the session carries one, else the declared
    `engine.mesh_devices` conf — but the conf fallback applies ONLY to
    schema-only sessions (explain/corpus: catalog entries carry a schema
    and no data, so nothing will ever execute). A session with real data
    but no mesh executes single-device, and a stray conf key must not
    buy it per-device admission verdicts for plans that will run on one
    chip (q14@SF10 modeled 'direct'/8-wide would admit straight into the
    device OOM the budgeter exists to prevent). None/1 = the
    single-device model."""
    mesh = getattr(session, "mesh", None)
    if mesh is not None:
        try:
            n = int(mesh.devices.size)
        except AttributeError:
            n = int(getattr(mesh, "size", 0) or 0)
        if n > 1:
            return n
        return None  # a real 1-wide mesh: single-device, conf ignored
    entries = getattr(getattr(session, "catalog", None), "entries", {})
    if any(
        getattr(e, "arrow", None) is not None
        or getattr(e, "path", None) is not None
        for e in entries.values()
    ):
        return None  # live data, no mesh: plans execute single-device
    try:
        n = int(session.conf.get("engine.mesh_devices") or 0)
    except (TypeError, ValueError):
        n = 0
    return n if n > 1 else None


def budget_plan(plan: P.PlanNode, session) -> Optional[PlanBudget]:
    """The Session._finish_plan hook: analyze, annotate, enforce.

    * emits a `plan_budget` trace event when the session is traced;
    * verdict `blocked`: annotates every blocked-union Aggregate with the
      statically chosen `budget_window_rows` (exec consumes it ahead of
      the runtime derivation; conf/env still win);
    * verdict `reject` in mode `on`: raises PlanBudgetError;
    * stores the result on `session.last_plan_budget` so the report
      ladder's first device-OOM rung can consume the prediction.

    Returns None (and does nothing) when the budgeter is off. Analysis
    failures downgrade to a `verdict="error"` marker instead of failing
    the statement (set NDS_PLAN_BUDGET_STRICT=1 to re-raise — the corpus
    CI gate does), because a budgeting bug must not take down a query the
    runtime ladder could have carried."""
    mode = resolve_mode(session.conf)
    if mode == "off":
        session.last_plan_budget = None
        return None
    sf = session.conf.get("engine.plan_budget_sf")
    # cardinality feedback (analysis/feedback.py): compute this plan's
    # per-node store keys once, consume recorded actuals as estimate
    # overrides in mode `on`, and (below) annotate node_fp/est_rows onto
    # the nodes so the executor can record what actually happened. Store
    # absent or mode off: fb_fps stays None and NOTHING changes
    from . import feedback as _feedback

    fb_store = getattr(session, "feedback_store", None)
    fb_mode = "off"
    fb_fps = None
    fb_overrides = None
    if fb_store is not None:
        fb_mode = _feedback.resolve_feedback_mode(session.conf)
    if fb_mode != "off":
        try:
            fb_fps = _feedback.plan_node_fps(plan, session)
        except Exception:
            if os.environ.get("NDS_PLAN_BUDGET_STRICT"):
                raise
            fb_fps = None
        if fb_fps and fb_mode == "on":
            fb_overrides = {}
            with session.cache_lock:
                for nid, fp in fb_fps.items():
                    rec = fb_store.lookup(fp)
                    rows = (rec or {}).get("rows") or {}
                    if rows.get("max") is not None:
                        fb_overrides[nid] = int(rows["max"])
    t0 = time.perf_counter()
    try:
        pb = analyze_plan(
            plan,
            session.catalog,
            scale_factor=float(sf) if sf else None,
            budget_bytes=resolve_budget_bytes(session.conf),
            reject_bytes=resolve_reject_bytes(session.conf),
            mesh_devices=session_mesh_devices(session),
            feedback=fb_overrides,
        )
    except Exception as exc:
        if os.environ.get("NDS_PLAN_BUDGET_STRICT"):
            raise
        session.last_plan_budget = {"verdict": "error", "error": str(exc)}
        session.notify_failure(f"plan budgeter failed: {str(exc)[:200]}")
        return None
    emit_budget_event(
        getattr(session, "tracer", None), pb,
        dur_ms=(time.perf_counter() - t0) * 1000.0,
    )
    if fb_fps:
        # annotate estimate accounting onto the plan (the same dynamic-
        # annotation family as budget_window_rows: deliberately NOT
        # dataclass fields, so structural fingerprints and the plan cache
        # stay feedback-agnostic). The executor reads these to emit
        # op_span est-vs-actual fields and to record into the store
        for est in pb.nodes:
            fp = fb_fps.get(id(est.node))
            if fp is None:
                continue
            est.node.node_fp = fp
            est.node.est_rows = est.rows
            est.node.est_live_bytes = est.live_bytes
        tracer = getattr(session, "tracer", None)
        if tracer is not None:
            tracer.emit(
                "plan_feedback",
                op="consume" if fb_mode == "on" else "annotate",
                result="applied" if pb.feedback_overrides else "static",
                mode=fb_mode,
                lookups=len(fb_fps) if fb_mode == "on" else 0,
                hits=len(fb_overrides or {}),
                overrides=pb.feedback_overrides,
                verdict=pb.verdict,
            )
    # `warn` is observe-only: record + trace + arm the ladder, but never
    # change execution (no window annotation, no rejection) — the mode
    # the README points scale-out runs at precisely to escape enforcement
    annotate = (
        mode == "on"
        and pb.window_rows is not None
        # `spill` included: a plan whose blocked seam is insufficient on
        # its own still runs its blocked aggregates with the static
        # window (the spill annotations below handle the rest) — exactly
        # the window an `over` verdict would have armed pre-spill
        and pb.verdict in ("blocked", "spill", "over")
    )
    # an explicit conf/env window eclipses the annotation at execution
    # time (Session.union_agg_window_rows resolution order), so the
    # static window is only IN EFFECT when nothing explicit is set — the
    # ladder's budget_shrink rung keys off this to know whether the
    # failed attempt actually ran the recommendation
    explicit = session.conf.get(
        "engine.union_agg_window_rows"
    ) or os.environ.get("NDS_UNION_AGG_WINDOW_ROWS")
    session.last_plan_budget = {
        "verdict": pb.verdict,
        "peak_bytes": pb.peak_bytes,
        "budget_bytes": pb.budget_bytes,
        "window_rows": pb.window_rows,
        # mesh width the per-device model divided sharded bytes by (None
        # for the single-device model) — serve-mode admission echoes it
        "mesh_devices": pb.mesh_devices,
        "annotated": annotate and not explicit,
        # spill_retry arming: recorded for EVERY verdict — an unpredicted
        # device OOM on a direct/over-verdict plan with an out-of-core
        # seam still retries through the pool (report._next_rung)
        "spillable": pb.spillable,
        "spill_partitions": pb.spill_partitions,
        # estimate-vs-actual accounting: the feedback mode this statement
        # planned under, how many store hits were consulted and how many
        # static estimates a recorded actual replaced (serve's /statusz
        # and `profile --accuracy` read the downstream surfaces)
        "feedback_mode": fb_mode,
        "feedback_hits": len(fb_overrides or {}),
        "feedback_overrides": pb.feedback_overrides,
    }
    if annotate:
        _annotate_blocked_windows(plan, pb.window_rows)
    if mode == "on" and pb.verdict == "spill" and pb.spill_partitions:
        # statically planned degradation: the executor's auto mode spills
        # exactly these nodes (warn stays observe-only, like the window
        # annotation above)
        _annotate_spill(plan, pb.spill_partitions)
    if pb.verdict == "reject" and mode == "on":
        raise PlanBudgetError(
            pb.peak_bytes, pb.budget_bytes,
            detail="no blocked-union seam can window the overage",
        )
    return pb


def _annotate_blocked_windows(plan: P.PlanNode, window_rows: int):
    """Set `budget_window_rows` (a dynamic physical annotation, like
    `_topk_safe` — deliberately NOT a dataclass field, so structural
    fingerprints and the plan cache stay window-agnostic) on every
    blocked-union Aggregate in the tree."""
    for v in P.walk_plan(plan):
        if isinstance(v, P.Aggregate) and v.blocked_union:
            v.budget_window_rows = int(window_rows)


def _annotate_spill(plan: P.PlanNode, partitions: int):
    """Set `spill_partitions` (same dynamic-annotation family as
    `budget_window_rows`: fingerprint/plan-cache-agnostic) on every
    out-of-core-capable node — the executor's `auto` spill mode consumes
    it (exec._spill_parts_for), and the verifier's annotation-coverage
    rule checks its placement and sanity."""
    for v in P.walk_plan(plan):
        if isinstance(v, P.PlanNode) and spillable_node(v):
            v.spill_partitions = int(partitions)
