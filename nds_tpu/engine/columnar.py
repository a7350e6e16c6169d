"""Columnar data model for the TPU execution engine.

The device-resident unit of work is a `Table`: a set of named `Column`s whose
buffers are dense JAX arrays padded to a shared *capacity* (a power-of-two
bucket >= the live row count). Padding + bucketing keeps the set of shapes the
compiler sees small, so per-op `jit` caches stay warm across the 99-query
stream even though every intermediate result has a different live row count
(the TPU answer to dynamic result shapes of joins/filters — SURVEY.md §7
"hard parts" #2).

Representation choices (TPU-first, see nds_tpu/dtypes.py):
  - integers / dates        -> int32 / int64 device buffers
  - decimal(p,s)            -> scaled int64 (value * 10^s), exact add/sub/cmp
  - char/varchar/string     -> int32 dictionary codes on device, the distinct
                               values live host-side in a pyarrow array; all
                               string functions are O(|dict|) host transforms
                               plus an O(n) device gather
  - NULLs                   -> separate bool validity buffer (None == all valid)

Counterpart of the columnar-batch layer the reference delegates to cuDF device
buffers via the rapids plugin (reference: nds/power_run_gpu.template:20-41
configures it; the batches themselves live in the external engine).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, replace
from functools import lru_cache, partial
from time import perf_counter as _perf
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..dtypes import DType, parse_dtype, INT64, FLOAT64
from ..obs import tally as _tally
from ..obs.tally import host_read

jax.config.update("jax_enable_x64", True)

# Minimum capacity bucket. 8*128 = one float32 VMEM tile's worth of lanes.
_MIN_CAP = 1024


def bucket_cap(n: int) -> int:
    """Smallest power-of-two capacity >= n (>= _MIN_CAP)."""
    cap = _MIN_CAP
    while cap < n:
        cap *= 2
    return cap


def _pad_to(arr: jnp.ndarray, cap: int, fill=0) -> jnp.ndarray:
    n = arr.shape[0]
    if n == cap:
        return arr
    if n > cap:
        raise ValueError(f"array longer ({n}) than capacity ({cap})")
    return jnp.pad(arr, (0, cap - n), constant_values=fill)


@dataclass(frozen=True)
class ColStats:
    """Host-side column statistics captured once at catalog load.

    Bounds are over the column's *base-table* non-null values, so they stay
    conservatively valid through any row subset (filter/compact/sort) and any
    gather (join output). `unique` means the base column's non-null values
    are pairwise distinct — preserved by subsetting, destroyed by joins.
    The executor's fast-path plan choices (dense star-join, direct
    aggregation) read these instead of issuing device round-trips, so picking
    a physical strategy costs zero host syncs on the query hot path (the
    round-2 regression: per-join masked_min_max + counts.max() syncs).
    """

    vmin: int
    vmax: int
    unique: bool
    base_rows: int  # live rows of the base table the bounds came from


@dataclass(frozen=True)
class Column:
    """One column: device buffer + optional validity + optional dictionary.

    `data` and `valid` are padded to the owning Table's capacity; entries at
    index >= nrows are garbage and must never influence results (kernels mask
    them with an iota < nrows predicate where it matters).
    """

    data: jnp.ndarray
    dtype: DType
    valid: Optional[jnp.ndarray] = None  # bool; None == all valid
    dictionary: Optional[pa.Array] = None  # for string dtypes: distinct values
    stats: Optional[ColStats] = None  # base-table stats (see ColStats)
    # buffer OWNERSHIP: True iff this column's data/valid buffers were
    # freshly minted for this one table by its producer (join pair gathers,
    # compaction takes) and alias nothing another live table references.
    # Consumed by fused-pipeline full-column donation (engine/fuse.py):
    # only owned, single-consumer, non-passthrough buffers may be donated
    # to an executable. Conservatively False everywhere else — a False on
    # a fresh buffer only costs a missed donation, a True on an aliased
    # buffer would invalidate memory another table still reads.
    owned: bool = False

    @property
    def is_string(self) -> bool:
        return self.dtype.is_string

    def with_valid(self, valid: Optional[jnp.ndarray]) -> "Column":
        return replace(self, valid=valid)

    def disowned(self) -> "Column":
        """This column shared by reference into ANOTHER table (join/filter/
        project passthrough): two tables now reference the buffer, and the
        sharing site cannot prove the source table is transient — e.g. a
        CTE or plan-cache entry retains it across reads — so neither side
        may treat the buffer as exclusively owned. Every executor path that
        copies Column objects across a plan-node boundary must route
        through this (a stale True would let fused-pipeline donation free
        memory the retained table still reads)."""
        return replace(self, owned=False) if self.owned else self

    def subset_stats(self) -> Optional[ColStats]:
        """Stats valid for any row-subset/permutation of this column."""
        return self.stats

    def gather_stats(self) -> Optional[ColStats]:
        """Stats valid after a gather with possible repeats (join output):
        bounds survive, uniqueness does not."""
        if self.stats is None:
            return None
        return replace(self.stats, unique=False)


class Table:
    """A named collection of equal-capacity columns with a live row count.

    Deferred compaction: `live` (when set) is an explicit per-row liveness
    mask — filtered/joined rows stay in place instead of being packed to
    the front, and `nrows` may be a 0-d device scalar that only crosses to
    the host on first access. A device->host sync blocks the host until
    everything queued before it has run (chip_smoke.py measures one;
    PERF.md has the number), so producers queue the count asynchronously
    and most consumers (masks, group-by, joins, sorts) never force it."""

    __slots__ = ("columns", "_nrows", "live", "_packed", "unique_key")

    def __init__(self, columns: dict, nrows, live=None, unique_key=None):
        self.columns = columns  # name -> Column (insertion-ordered)
        self._nrows = nrows  # host int or 0-d device array (lazy)
        self.live = live  # None (first nrows rows live) or bool[cap]
        self._packed = None  # memoized compacted() result
        # frozenset of column names whose combined values are pairwise
        # distinct over live rows (group-by keys, DISTINCT output). Survives
        # row subsetting/renaming; destroyed by row-expanding gathers.
        # Probe-style joins read it to skip runtime uniqueness checks.
        self.unique_key = unique_key

    @property
    def nrows(self) -> int:
        if not isinstance(self._nrows, int):
            # device sync on first need
            self._nrows = int(host_read("nrows", self._nrows))
        return self._nrows

    @property
    def nrows_known(self):
        """The live row count if already on the host, else None."""
        return self._nrows if isinstance(self._nrows, int) else None

    @property
    def nrows_lazy(self):
        """The live row count without forcing a device sync (host int or
        0-d device array); pass through when constructing derived tables."""
        return self._nrows

    @property
    def cap(self) -> int:
        for c in self.columns.values():
            return int(c.data.shape[0])
        # column-less table (e.g. the __dual__ relation for FROM-less
        # selects): capacity must still cover the live rows
        return bucket_cap(self.nrows) if self.nrows > 0 else 0

    @property
    def names(self):
        return list(self.columns.keys())

    def column(self, name: str) -> Column:
        return self.columns[name]

    def select(self, names) -> "Table":
        uk = self.unique_key
        if uk is not None and not uk <= set(names):
            uk = None
        return Table(
            {n: self.columns[n] for n in names}, self._nrows, self.live,
            unique_key=uk,
        )

    def narrowed(self, required) -> "Table":
        """This table with the columns `required` names and no others
        (`narrow_columns`); itself where nothing falls away."""
        cols = narrow_columns(self.columns, required)
        if cols is self.columns:
            return self
        return self.select(cols)

    def rename(self, mapping: dict) -> "Table":
        uk = self.unique_key
        if uk is not None:
            uk = frozenset(mapping.get(n, n) for n in uk)
        return Table(
            {mapping.get(n, n): c for n, c in self.columns.items()},
            self._nrows,
            self.live,
            unique_key=uk,
        )

    def row_mask(self) -> jnp.ndarray:
        """Bool mask of live rows."""
        if self.live is not None:
            return self.live
        with _tally.eager("row_mask"):
            return jnp.arange(self.cap, dtype=jnp.int32) < self._nrows

    def compacted(self) -> "Table":
        """Pack live rows to the front (drops the mask). Reuses the count
        already queued in _nrows (no extra reduce/sync) and memoizes, so a
        masked table shared by several consumers compacts once."""
        if self.live is None:
            return self
        if self._packed is not None:
            return self._packed
        from ..ops import kernels as K

        count = self.nrows
        cap = bucket_cap(max(count, 1))
        idx = K.compact_indices(self.live, cap)
        self._packed = Table(
            gather_columns(self.columns, idx), count,
            unique_key=self.unique_key,
        )
        return self._packed


def narrow_columns(columns: dict, required) -> dict:
    """The columns that `required` names (any container of names; None ==
    all). A table carries its capacity and its row mask's length in its
    buffers, so where none is named one column stays to carry the rows:
    the narrowest, one without a validity buffer first (the rule
    `prune_columns` has for a bare `count(*)` scan)."""
    if required is None:
        return columns
    kept = {n: c for n, c in columns.items() if n in required}
    if len(kept) == len(columns):
        return columns
    if kept or not columns:
        return kept
    name = min(
        columns,
        key=lambda n: (columns[n].valid is not None,
                       columns[n].data.dtype.itemsize),
    )
    return {name: columns[name]}


def gather_columns(
    columns: dict, idx, keep=None, *, stats=Column.subset_stats, owned=False,
) -> dict:
    """The rows `idx` of every column of one table side, by one call of
    `kernels.take_columns` (which says what `keep` does). Dtype and
    dictionary carry over; `stats` maps a source column to the stats
    that survive this gather, `owned` is the new columns' flag."""
    from ..ops import kernels as K

    if not columns:
        return {}

    taken = K.take_columns(
        tuple((c.data, c.valid) for c in columns.values()), idx, keep
    )
    return {
        name: Column(data, c.dtype, valid, c.dictionary, stats(c), owned=owned)
        for (name, c), (data, valid) in zip(columns.items(), taken)
    }


def table_device_bytes(table: Table) -> int:
    """Device bytes held by a table's buffers (data + validity masks;
    capacity-padded shapes are static, so this never syncs the device).
    THE byte-estimation rule: the session plan-cache budget and the obs
    op_span `est_bytes` field both read it, so they cannot drift."""
    total = 0
    for c in table.columns.values():
        total += int(c.data.nbytes)
        if c.valid is not None:
            total += int(c.valid.nbytes)
    return total


# ---------------------------------------------------------------------------
# Bounded row windows (blocked union-aggregation)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cap",))
def _dyn_slice(arr: jnp.ndarray, start, cap: int) -> jnp.ndarray:
    return jax.lax.dynamic_slice_in_dim(arr, start, cap)


def window_slice(table: Table, start: int, cap: int) -> Table:
    """Rows [start, start+cap) of a compacted table as a Table of capacity
    `cap`, via per-column dynamic slices — never a full-capacity gather.

    `cap` must be a power-of-two bucket <= table.cap and `start` a multiple
    of `cap`, so the slice can never clamp (both caps are power-of-two
    buckets, hence table.cap is a multiple of cap). The start index stays a
    traced scalar, so every window of a given (shape, cap) pair shares one
    compiled slice kernel."""
    if table.live is not None:
        raise ValueError("window_slice requires a compacted table")
    if cap >= table.cap:
        return table
    if start % cap:
        raise ValueError(f"window start {start} not aligned to cap {cap}")
    nrows = min(max(table.nrows - start, 0), cap)
    cols = {}
    for name, c in table.columns.items():
        cols[name] = Column(
            _dyn_slice(c.data, start, cap),
            c.dtype,
            None if c.valid is None else _dyn_slice(c.valid, start, cap),
            c.dictionary,
            c.subset_stats(),
        )
    return Table(cols, nrows, unique_key=table.unique_key)


# ---------------------------------------------------------------------------
# Host <-> device conversion (Arrow is the host-side interchange format)
# ---------------------------------------------------------------------------


def _np_valid(arr: pa.Array) -> Optional[np.ndarray]:
    if arr.null_count == 0:
        return None
    return pc.is_valid(arr).to_numpy(zero_copy_only=False)


def _put(host: np.ndarray, h2d=None) -> jnp.ndarray:
    """Host buffer -> device array. `h2d`, a one-element list, accumulates
    the seconds spent in the copy calls (catalog_load's `h2d_ms`)."""
    if h2d is None:
        return jnp.asarray(host)
    t0 = _perf()
    out = jnp.asarray(host)
    h2d[0] += _perf() - t0
    return out


def column_from_arrow(arr: pa.ChunkedArray | pa.Array, dtype: DType, cap: int,
                      h2d=None) -> Column:
    """Decode one Arrow column into the device representation."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    valid_np = _np_valid(arr)
    if dtype.is_string:
        # Dictionary-encode on host; codes ride to HBM, values stay host-side.
        if not pa.types.is_dictionary(arr.type):
            arr = pc.dictionary_encode(arr)
        codes = np.asarray(
            arr.indices.fill_null(0).to_numpy(zero_copy_only=False), dtype=np.int32
        )
        dictionary = arr.dictionary
        if len(dictionary) == 0:
            # no rows (a lakehouse scan whose every file the zone map
            # pruned) or NULLs alone: one value no row refers to, so that
            # every lookup by code has something to gather from
            dictionary = pa.array([""], dictionary.type)
        data = _put(np.ascontiguousarray(codes), h2d)
    else:
        dictionary = None
        if dtype.is_decimal:
            if pa.types.is_decimal(arr.type):
                # decimal128 -> scaled int64: multiply by 10^s as decimal
                # (keeps exactness; p <= 18 covers all of TPC-DS), then the
                # rescale-free cast to int64 is lossless.
                import decimal

                shift = pa.scalar(decimal.Decimal(10**dtype.scale))
                scaled = pc.multiply(arr.cast(pa.decimal128(18, arr.type.scale)), shift)
                np_vals = scaled.fill_null(0).cast(pa.int64()).to_numpy(
                    zero_copy_only=False
                )
            else:
                scale = 10 ** dtype.scale
                f = arr.cast(pa.float64()).fill_null(0.0).to_numpy(zero_copy_only=False)
                np_vals = np.round(f * scale).astype(np.int64)
            np_vals = np.asarray(np_vals, dtype=np.int64)
        elif dtype.kind == "date":
            np_vals = arr.cast(pa.int32()).fill_null(0).to_numpy(zero_copy_only=False)
        else:
            npdt = dtype.device_np_dtype()
            filled = arr.fill_null(0) if arr.null_count else arr
            np_vals = np.asarray(
                filled.to_numpy(zero_copy_only=False), dtype=npdt
            )
        data = _put(np.ascontiguousarray(np_vals), h2d)
    data = _pad_to(data, cap)
    valid = None
    if valid_np is not None:
        valid = _pad_to(_put(valid_np, h2d), cap, fill=False)
    return Column(data, dtype, valid, dictionary)


# Above this many rows, per-column uniqueness (count_distinct) is skipped at
# load: only dimension-sized build sides benefit, and larger tables are
# rejected by the dense-join domain cap anyway.
_UNIQUE_STATS_MAX_ROWS = 1 << 22


def arrow_column_stats(arr, dtype: DType, nrows: int) -> Optional[ColStats]:
    """Host-side min/max/uniqueness of an integer-like Arrow column.

    One vectorized Arrow pass per column at catalog-load time buys sync-free
    physical plan choice for every query that later touches the column."""
    if dtype.kind not in ("int32", "int64", "date"):
        return None
    if nrows == 0:
        return None
    if isinstance(arr, pa.ChunkedArray) and arr.num_chunks == 0:
        return None
    if dtype.kind == "date":
        # date32 scalars don't cast to int; min/max over the day numbers
        arr = arr.cast(pa.int32())
    mm = pc.min_max(arr)
    vmin, vmax = mm["min"], mm["max"]
    if not vmin.is_valid:  # all-null column
        return None
    vmin = vmin.cast(pa.int64()).as_py()
    vmax = vmax.cast(pa.int64()).as_py()
    unique = False
    if nrows <= _UNIQUE_STATS_MAX_ROWS:
        n_valid = nrows - arr.null_count
        unique = pc.count_distinct(arr, mode="only_valid").as_py() == n_valid
    return ColStats(vmin, vmax, unique, nrows)


def table_from_arrow(
    batch: pa.Table | pa.RecordBatch, schema=None, with_stats: bool = False,
    h2d=None,
) -> Table:
    """Build a device Table from an Arrow table.

    `schema` (nds_tpu.schema.Schema) supplies logical types; if omitted they
    are inferred from the Arrow types. `with_stats` captures per-column
    ColStats (catalog loads set it; ad-hoc intermediates skip the pass).
    `h2d`: see `_put`.
    """
    nrows = batch.num_rows
    cap = bucket_cap(nrows)
    cols = {}
    if isinstance(batch, pa.RecordBatch):
        batch = pa.Table.from_batches([batch])
    for i, name in enumerate(batch.column_names):
        if schema is not None and name in schema:
            dtype = schema.field(name).dtype
        else:
            dtype = _infer_dtype(batch.schema.field(i).type)
        col = column_from_arrow(batch.column(i), dtype, cap, h2d)
        if with_stats and col.stats is None:
            stats = arrow_column_stats(batch.column(i), dtype, nrows)
            if stats is not None:
                col = replace(col, stats=stats)
        cols[name] = col
    return Table(cols, nrows)


def _infer_dtype(t: pa.DataType) -> DType:
    if pa.types.is_int32(t) or pa.types.is_int16(t) or pa.types.is_int8(t):
        return parse_dtype("int32")
    if pa.types.is_int64(t):
        return parse_dtype("int64")
    if pa.types.is_floating(t):
        return parse_dtype("float64")
    if pa.types.is_decimal(t):
        return DType("decimal", t.precision, t.scale)
    if pa.types.is_date(t):
        return parse_dtype("date")
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return parse_dtype("string")
    if pa.types.is_dictionary(t):
        return parse_dtype("string")
    if pa.types.is_boolean(t):
        return parse_dtype("int32")
    raise ValueError(f"unsupported arrow type {t}")


def column_to_arrow(col: Column, nrows: int, host=None) -> pa.Array:
    """Materialize a device column back into Arrow (collect/write path).
    `host`: optional pre-fetched (data, valid) numpy pair so callers can batch
    the device->host transfers of many columns into one round trip."""
    if host is not None:
        data, valid = host
        data = data[:nrows]
        valid = None if valid is None else valid[:nrows]
    else:
        data = host_read("collect", col.data[:nrows])
        valid = (
            None if col.valid is None
            else host_read("collect", col.valid[:nrows])
        )
    mask = None if valid is None else ~valid
    dt = col.dtype
    if dt.is_string:
        codes = pa.array(data.astype(np.int32), mask=mask)
        return pa.DictionaryArray.from_arrays(codes, col.dictionary).cast(pa.string())
    if dt.is_decimal:
        # Our int64s are *unscaled* decimal values; Arrow's int->decimal cast
        # is value-preserving, so build the decimal128 buffer directly
        # (low word = value, high word = sign extension).
        ints = data.astype("<i8")
        buf = np.empty((len(ints), 2), dtype="<i8")
        buf[:, 0] = ints
        buf[:, 1] = ints >> 63
        validity = None
        if mask is not None:
            validity = pa.array(~mask).buffers()[1]
        return pa.Array.from_buffers(
            pa.decimal128(dt.precision, dt.scale),
            len(ints),
            [validity, pa.py_buffer(buf.tobytes())],
        )
    if dt.kind == "date":
        return pa.array(data.astype(np.int32), mask=mask).cast(pa.date32())
    if dt.kind == "bool":
        return pa.array(data.astype(bool), mask=mask)
    return pa.array(data, mask=mask)


def table_to_arrow(table: Table) -> pa.Table:
    table = table.compacted()  # deferred-compaction tables pack here
    # one batched device->host round trip for every buffer (each blocking
    # np.asarray would otherwise pay its own round trip per column)
    flat = []
    for c in table.columns.values():
        flat.append(c.data)
        if c.valid is not None:
            flat.append(c.valid)
    if any(
        hasattr(x, "is_fully_addressable") and not x.is_fully_addressable
        for x in flat
    ):
        # multi-process mesh: shards live on other hosts' devices, which
        # device_get cannot read — all-gather each buffer to every process
        # first (DCN-tier result collection)
        from jax.experimental import multihost_utils

        flat = [
            multihost_utils.process_allgather(x, tiled=True)
            if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable
            else x
            for x in flat
        ]
    fetched = iter(host_read("collect", flat))
    arrays = []
    for c in table.columns.values():
        data = next(fetched)
        valid = next(fetched) if c.valid is not None else None
        arrays.append(column_to_arrow(c, table.nrows, host=(data, valid)))
    return pa.table(arrays, names=table.names)


# ---------------------------------------------------------------------------
# Dictionary utilities (string kernels run on the host over distinct values)
# ---------------------------------------------------------------------------


class _Derived(NamedTuple):
    """One derivation of `_DictMemo`."""

    inputs: tuple  # the dictionary objects it came from: held, so their
    # `id`s stay theirs
    dictionary: pa.Array  # the derived dictionary: this object, every time
    remaps: tuple  # an input: a device vector from its codes to codes of
    # `dictionary`, or None for the identity
    nbytes: int  # what the entry keeps alive, host and device together


class _DictMemo:
    """Dictionary derivations by the identity of what they derive from.

    A merged or a sorted dictionary is a function of the dictionary objects
    it is derived from, and dictionaries are immutable Arrow arrays, so one
    derivation serves every later call on the same objects: no Arrow work,
    no host-to-device put, and above all the SAME derived object, which is
    what `fuse.input_signature` keys an executable by. Keys are `id`s, so an
    entry holds its inputs (a recycled address must not alias another
    dictionary: the reasoning of `fuse._capture_inputs`). LRU by entry count
    and by bytes: entries are dimension-sized host arrays and int32 device
    vectors outside the memory budgeter, and a scan that encodes its
    dictionaries anew at every execution (a lakehouse pruned read) leaves
    an entry that can never hit at every execution; neither may grow it
    without limit. `Session.recover_memory` and `Session.close` empty it."""

    def __init__(self, max_entries: int = 128, max_bytes: int = 64 << 20):
        self.max_entries = max_entries
        self.max_bytes = max_bytes
        self.nbytes = 0
        self._entries = OrderedDict()  # (kind, id, ...) -> _Derived
        self._lock = threading.Lock()

    def derived(self, kind, inputs, derive) -> _Derived:
        """The `kind` derivation of the dictionary objects `inputs`, from
        `derive(inputs) -> (dictionary, remaps as numpy or None)` the first
        time. Counts a `hit` or a `miss` into the bound tally."""
        key = (kind, *map(id, inputs))
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
        if entry is not None:
            _tally.dict_memo("hit")
            return entry
        _tally.dict_memo("miss")
        with _tally.phase("dict-merge"):
            dictionary, remaps = derive(inputs)
        if any(r is not None for r in remaps):
            # under a jax trace (a CASE inside a fused pipeline) the vectors
            # must still be concrete arrays: constants to that trace and
            # every later one, never a tracer
            with _tally.eager("dict_remap"), jax.ensure_compile_time_eval():
                remaps = tuple(
                    None if r is None else jnp.asarray(r, dtype=jnp.int32)
                    for r in remaps
                )
        held = {id(d): d for d in (*inputs, dictionary)}
        nbytes = sum(d.nbytes for d in held.values()) + sum(
            r.nbytes for r in remaps if r is not None
        )
        entry = _Derived(tuple(inputs), dictionary, remaps, nbytes)
        with self._lock:
            # two threads may derive one key at once: the first to land
            # stays, so a key never hands out two objects
            kept = self._entries.setdefault(key, entry)
            if kept is entry:
                self.nbytes += nbytes
            # oldest first; an entry larger than the whole bound goes too
            # (its caller has it, the next call derives it again)
            while self._entries and (
                len(self._entries) > self.max_entries
                or self.nbytes > self.max_bytes
            ):
                self.nbytes -= self._entries.popitem(last=False)[1].nbytes
        return kept

    def clear(self):
        with self._lock:
            self._entries.clear()
            self.nbytes = 0

    def __len__(self):
        return len(self._entries)


_DICT_MEMO = _DictMemo()
_NO_STRINGS = pa.array([], type=pa.string())


def clear_dictionary_memo():
    """Let go of every kept derivation: its device vectors and the
    dictionaries it holds (`Session.recover_memory`, `Session.close`). The
    next call derives anew, into new objects."""
    _DICT_MEMO.clear()


@lru_cache(maxsize=1024)
def literal_dictionary(value: str) -> pa.Array:
    """A string literal's one-entry dictionary: one object a value, so a
    derivation over (a column's dictionary, this) is found again the next
    time the expression is evaluated or traced."""
    return pa.array([value], type=pa.string())


def _as_string(d: pa.Array) -> pa.Array:
    return d if d.type == pa.string() else d.cast(pa.string())


def _identity_or(remap: np.ndarray):
    """None where `remap` maps every code to itself (no gather needed)."""
    n = len(remap)
    if np.array_equal(remap, np.arange(n, dtype=remap.dtype)):
        return None
    return remap.astype(np.int32)


def _derive_merged(dicts):
    casts = [_as_string(d) for d in dicts]
    if len(casts) == 1:
        # one object on every side: its own codes already mean its entries
        return casts[0], (None,)
    unified = pc.unique(pa.concat_arrays(casts))
    remaps = tuple(
        _identity_or(pc.index_in(c, unified).to_numpy(zero_copy_only=False))
        for c in casts
    )
    if remaps[0] is None and len(unified) == len(casts[0]):
        # `pc.unique` keeps first appearance: nothing new came after the
        # first input, so the merged dictionary IS the first, entry for
        # entry; hand that object back and chains converge on it
        unified = casts[0]
    return unified, remaps


def _derive_sorted(dicts):
    d = _as_string(dicts[0])
    order = pc.array_sort_indices(d)  # indices of values in sorted order
    rank = np.empty(len(d), dtype=np.int32)
    rank[order.to_numpy(zero_copy_only=False)] = np.arange(len(d), dtype=np.int32)
    if _identity_or(rank) is None:
        return d, (None,)
    return d.take(order), (rank,)


def merge_dictionaries(dicts):
    """One dictionary holding every entry of `dicts` (pyarrow arrays or
    None, one a column), and a column's remap onto it: `(unified, remaps)`,
    `remaps[i]` a device int32 vector indexed by column i's codes, or None
    where those codes already are codes of `unified` (an empty or absent
    dictionary; the identity). Derived once per tuple of distinct objects
    (`_DictMemo`): the same inputs get the same `unified` object back.

    Every non-empty input one object (a ROLLUP's levels over one base
    column, a single-column CASE): that object is `unified`, no Arrow work
    and no dispatch; its entries are not made distinct, which is what a
    single column's own codes mean everywhere else."""
    # the non-empty objects, each once, in order of first appearance
    distinct = list(
        {id(d): d for d in dicts if d is not None and len(d)}.values()
    )
    if not distinct or (
        len(distinct) == 1 and distinct[0].type == pa.string()
    ):
        _tally.dict_memo("same")
        return (distinct[0] if distinct else _NO_STRINGS), [None] * len(dicts)
    entry = _DICT_MEMO.derived("merge", distinct, _derive_merged)
    remap_of = dict(zip(map(id, distinct), entry.remaps))
    return entry.dictionary, [remap_of.get(id(d)) for d in dicts]


def remap_codes(codes, remap):
    """`codes` through a remap of `merge_dictionaries` or a rank vector (an
    entry a code of the column's own dictionary); None: as they are."""
    if remap is None:
        return codes
    with _tally.eager("dict_remap"):
        return remap[jnp.clip(codes, 0, remap.shape[0] - 1)]


def unify_dictionaries(a: Column, b: Column):
    """Remap two string columns onto one shared dictionary.

    Needed before any cross-table comparison/join of string columns, because
    codes are only meaningful within their own dictionary. Returns
    (codes_a, codes_b, unified_dictionary). Columns that already share one
    dictionary object (common after unions/CTE reuse over the same base
    column) come back as they are; a pair of objects met before costs the
    two gathers alone (`merge_dictionaries`)."""
    unified, (ra, rb) = merge_dictionaries([a.dictionary, b.dictionary])
    return remap_codes(a.data, ra), remap_codes(b.data, rb), unified


def sort_dictionary(col: Column):
    """Return codes remapped so that code order == lexicographic value order.

    Lets ORDER BY / min / max on strings run entirely on device: comparing
    rank codes is comparing strings. The rank vector and the sorted
    dictionary are derived once a dictionary object (`_DictMemo`)."""
    d = col.dictionary
    if d is None or len(d) == 0:
        # all-null string column (e.g. c_login): nothing to rank
        return col.data, d
    entry = _DICT_MEMO.derived("sort", (d,), _derive_sorted)
    return remap_codes(col.data, entry.remaps[0]), entry.dictionary
