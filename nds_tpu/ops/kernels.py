"""Device kernel library: the cuDF-equivalent relational primitives.

These are the hot ops the reference delegates to the external RAPIDS/cuDF
engine (reference: BASELINE.json north star; nds/power_run_gpu.template:20-41
merely configures them). Here each primitive is a `jit`-compiled JAX function
over dense padded buffers:

  - compaction (filter)          cumsum + scatter + gather
  - equi-join (inner/outer/semi/anti)  hash + sort + searchsorted + verify
  - group-by aggregation         word sort + boundary flags + segment reduce
  - order-by                     word sort with null/direction folding
  - window functions             partition sort + segment scan/reduce

Design rules (TPU/XLA-first):
  * Every output is padded to a power-of-two bucket (`columnar.bucket_cap`) so
    recompiles are bounded by O(log n) distinct shapes per kernel, not O(#ops).
  * No data-dependent shapes inside jit: live counts cross to the host once
    per kernel (`int(x.sum())`) and select the bucket for the next kernel.
  * Hash matches are *candidates only*: every join verifies real key equality
    on the matched pairs, so hash collisions can never produce wrong results.
  * EVERY ordering routes through ONE canonical stable (key, iota) kv-sort
    kernel per input cap (`sort_by_words`): XLA:TPU sort compiles cost
    ~10-12 s per comparator operand at fact shapes on a 1-core host, so
    multi-key comparisons run as stable LSD passes over int64/float64 words
    instead of one multi-operand comparator kernel per query. A leading
    live word keeps padding tails at the end.
"""

from __future__ import annotations

from functools import partial, wraps

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import tally as _tally
from ..obs.tally import host_read

jax.config.update("jax_enable_x64", True)

I64 = jnp.int64
U64 = jnp.uint64


# ---------------------------------------------------------------------------
# The launch seam
#
# Plan-node op_spans say WHICH operator is slow; they cannot say how many
# programs it launched. Every decorated kernel entry point below counts
# itself into the statement's tally (obs/tally.py: a dict add, and for the
# outermost call of a nest one clock pair around its HOST side, kept under
# the call's name) and the executor flushes the counts as
# `op_span.launches` / `launch_ms` / `launch_ms_by`. Eager `jnp` work that
# is no entry point of this file has seams of its own where it runs
# (`obs/tally.py eager`, `eager:<site>`), never counted here. Nothing
# here waits for the device: a seam that synchronizes changes what it
# measures, and device time is the profiler's to report. An entry is at
# least one program launch (sort_by_words runs one sort per word and
# counts one); the gathers count their buffers, one program each. Zero
# cost with no tally bound: one thread-local read + None check per call.
# Calls made while jax is TRACING (a fused pipeline body re-entering
# segment_reduce) are skipped — they launch nothing.
# ---------------------------------------------------------------------------


def _has_jax_tracer(args) -> bool:
    for a in args:
        if isinstance(a, jax.core.Tracer):
            return True
        if isinstance(a, (list, tuple)) and _has_jax_tracer(a):
            return True
    return False


def _ktraced(name, programs=None):
    """Count the decorated entry point into the bound tally under `name`:
    once, or `programs(*args)` times where one call launches a program per
    buffer (a call of none is not counted)."""
    def deco(fn):
        @wraps(fn)
        def wrapped(*args, **kwargs):
            t = _tally.current()
            if t is None or _has_jax_tracer(args):
                return fn(*args, **kwargs)
            n = 1 if programs is None else programs(*args, **kwargs)
            token = t.enter(name, n) if n else None
            if token is None:
                return fn(*args, **kwargs)
            try:
                return fn(*args, **kwargs)
            finally:
                t.leave(token)
        return wrapped
    return deco


# The row gather. One jitted program a BUFFER, keyed by its dtype and the
# two capacities alone, so every table shares them. One program for the
# whole table side was built first and lost on the v5e: the same gathers
# take 26% to 90% more device time inside one program than launched one by
# one (PERF.md, Findings PR 26). What the eager `data[idx]` cost the host,
# jnp's indexing path walked once a buffer, is gone either way.


@jax.jit
def _gather(a, idx):
    return a[idx]


@jax.jit
def _gather_valid(valid, idx, keep):
    # a column with no validity buffer gets a copy of `keep` of its own:
    # every output is a buffer nothing else references
    return jnp.copy(keep) if valid is None else valid[idx] & keep


def _n_gathers(pairs, idx, keep=None):
    return sum(
        1 + (valid is not None or keep is not None) for _, valid in pairs
    )


@_ktraced("take_columns", _n_gathers)
def take_columns(pairs, idx, keep=None):
    """The row gather of a table side: `(data[idx], valid[idx])` for every
    `(data, valid)` pair (`valid` None where the column has no validity
    buffer), in one call behind the seam. `keep`, an optional bool mask
    over the output rows, is and-ed into every validity inside the
    validity's own gather (a None validity becomes `keep`): the null
    extension of outer joins. Indexing semantics are `data[idx]`'s own;
    outputs are fresh buffers."""
    if keep is None:
        return tuple(
            (_gather(d, idx), None if v is None else _gather(v, idx))
            for d, v in pairs
        )
    return tuple(
        (_gather(d, idx), _gather_valid(v, idx, keep)) for d, v in pairs
    )


@_ktraced("take_columns", lambda arrays, idx: len(arrays))
def take_arrays(arrays, idx):
    """`a[idx]` for every buffer of `arrays` (sort words, index vectors, one
    column's data: buffers that carry no validity), by the same programs
    and under the same name behind the seam as `take_columns`."""
    return tuple(_gather(a, idx) for a in arrays)


# ---------------------------------------------------------------------------
# Hashing
# ---------------------------------------------------------------------------


def _splitmix64(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer; good avalanche, cheap on the VPU."""
    x = x.astype(U64)
    x = (x + jnp.uint64(0x9E3779B97F4A7C15)).astype(U64)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> jnp.uint64(31))
    return x


def hash_columns(cols, valids) -> jnp.ndarray:
    """Combine N key columns (+ their null flags) into one int64 hash."""
    h = jnp.uint64(0x243F6A8885A308D3)
    for data, valid in zip(cols, valids):
        k = _splitmix64(data.astype(I64))
        if valid is not None:
            # null participates as its own distinct value
            k = jnp.where(valid, k, jnp.uint64(0xA5A5A5A5A5A5A5A5))
        h = _splitmix64(h * jnp.uint64(31) + k)
    return h.astype(I64)


# ---------------------------------------------------------------------------
# Cumulative ops
#
# XLA:TPU compile time for cumulative ops scales with the scanned-axis
# LENGTH (flat cumsum i64 at 2^20: ~16 s; cummax: ~25 s on this host). The
# blocked (recursive) form — short inner scans over a (B, T) reshape plus
# a scan of the block totals — compiles in ~1-2 s, so every engine
# cumulative routes through these. Exact for integers; float sums are
# reassociated block-wise (final-ulp differences vs a flat scan, within
# the validator's relative-epsilon contract).
# ---------------------------------------------------------------------------

_CUM_BLOCK = 512


def fast_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Exact inclusive prefix sum, compile-friendly on TPU."""
    n = x.shape[0]
    if n < 2 * _CUM_BLOCK or n % _CUM_BLOCK:
        return jnp.cumsum(x)
    b = n // _CUM_BLOCK
    y = jnp.cumsum(x.reshape(b, _CUM_BLOCK), axis=1)
    off = jnp.concatenate(
        [jnp.zeros(1, y.dtype), fast_cumsum(y[:, -1])[:-1]]
    )
    return (y + off[:, None]).reshape(-1)


def fast_cummax(x: jnp.ndarray) -> jnp.ndarray:
    """Exact inclusive prefix max, compile-friendly on TPU."""
    n = x.shape[0]
    if n < 2 * _CUM_BLOCK or n % _CUM_BLOCK:
        return jax.lax.cummax(x)
    b = n // _CUM_BLOCK
    y = jax.lax.cummax(x.reshape(b, _CUM_BLOCK), axis=1)
    m = fast_cummax(y[:, -1])
    if jnp.issubdtype(x.dtype, jnp.integer):
        lo = jnp.full((1,), jnp.iinfo(x.dtype).min, x.dtype)
    else:
        lo = jnp.full((1,), -jnp.inf, x.dtype)
    off = jnp.concatenate([lo, m[:-1]])
    return jnp.maximum(y, off[:, None]).reshape(-1)


# ---------------------------------------------------------------------------
# Compaction (filter)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=())
def _compact_full(mask: jnp.ndarray) -> jnp.ndarray:
    """Indices of True entries, packed to the front, 0-padded, full length.

    cumsum + scatter instead of jnp.nonzero: XLA:TPU compiles this ~2-4x
    faster, and keeping the output full-length means ONE compile per input
    cap regardless of the caller's out_cap (the slice below is a trivial
    compile). With compiles costing seconds per shape on a 1-core host,
    (shape x out_cap) kernel proliferation was a top cold-start cost."""
    n = mask.shape[0]
    pos = jnp.where(mask, fast_cumsum(mask.astype(jnp.int32)) - 1, n)
    return (
        jnp.zeros(n, jnp.int32)
        .at[pos]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    )


def _multi_device(x) -> bool:
    """True for a CONCRETE array actually sharded across > 1 device (mesh
    sessions). Tracers/host arrays report False — traced callers keep the
    single-device kernel choice, which is correct there by construction."""
    s = getattr(x, "sharding", None)
    if s is None:
        return False
    try:
        return len(s.device_set) > 1
    except Exception:
        return False


@partial(jax.jit, static_argnames=())
def _compact_full_sorted(mask: jnp.ndarray) -> jnp.ndarray:
    """_compact_full via the canonical kv-sort kernel: a stable ascending
    sort of (dead, index) puts live indices first in original order —
    identical output to the cumsum+scatter path (zeros past the count).

    This is the MESH-SAFE variant: jax 0.4.37's SPMD partitioner
    mislowers the blocked fast_cumsum -> where -> scatter(mode="drop")
    composition over a row-sharded mask (cross-shard scatter writes are
    dropped, so compaction silently truncates — caught by the SF0.01
    mesh-vs-oracle gate on query77/query83). The sort kernel partitions
    correctly, so sharded masks route here instead.

    Re-tested 2026-08-07 on jax 0.4.37: an 8-way forced-host-device mesh
    (xla_force_host_platform_device_count) lowers the scatter path
    correctly on CPU, so the mislowering was specific to the XLA:TPU SPMD
    pipeline. Not re-tested on jax 0.9.0, which is what is installed now.
    Keep the sorted route for sharded masks until the mesh-vs-oracle gate
    passes with it removed on real TPU devices."""
    n = mask.shape[0]
    perm = sort_by_words([(~mask).astype(jnp.int64)])
    count = jnp.sum(mask, dtype=jnp.int32)
    return jnp.where(
        jnp.arange(n, dtype=jnp.int32) < count, perm.astype(jnp.int32), 0
    )


# A sparse compaction by block select. `_compact_full` scatters n updates
# whatever the mask holds, 5.8 ns each on the v5e: 24.5 ms at store_sales'
# 4,194,304 rows to keep 65,536 (PERF.md, Findings PR 40, step 0). Here
# everything of size n is a streaming pass (each block of `_SELECT_BLOCK`
# rows packs its mask into 32-bit words and counts them) and what costs by
# the row is of size `out_cap`: one max-scatter of the n / block block
# starts, ONE gather of `out_cap` rows of a block's words, and the rank-th
# set bit of each by population counts: 0.69 ms at that shape. Two
# programs, so that the n-sized one is keyed by n alone as `_compact_full`
# is, and the other by (n / block, out_cap).
#
# The rule is on the two shapes a call has. Block select won at every ratio
# step 0 tried, down to n / out_cap = 2 (8.6 against 24.5 ms), but the
# chip lays a slot's 16 words over 128 lanes, 512 B of temporaries a slot:
# the rule stops at a ratio of 4 (a quarter of n, 0.5 GB at 4,194,304),
# which covers every packing of a sparse table (`_pack_sparse`: under an
# eighth live). Under `_SELECT_MIN_ROWS` a scatter costs what two
# dispatches cost (0.6 ms at 65,536 either way; 2.5 against 0.7 ms at
# 524,288).

_SELECT_BLOCK = 512
_SELECT_MIN_ROWS = 262_144
_SELECT_CROSSOVER = 4
_WORD = 32


@jax.jit
def _select_blocks(mask):
    """(words, off, total): block b's mask as `_SELECT_BLOCK / 32` words,
    lane l at bit l % 32 of word l // 32; off[b] the live rows before
    block b; total all of them."""
    nblocks = mask.shape[0] // _SELECT_BLOCK
    bits = mask.reshape(nblocks, _SELECT_BLOCK // _WORD, _WORD)
    words = jnp.sum(
        bits.astype(jnp.uint32) << jnp.arange(_WORD, dtype=jnp.uint32),
        axis=2, dtype=jnp.uint32,
    )
    counts = jnp.sum(
        jax.lax.population_count(words), axis=1, dtype=jnp.int32
    )
    ends = fast_cumsum(counts)
    return words, ends - counts, ends[-1]


@partial(jax.jit, static_argnames=("out_cap",))
def _select_rows(words, off, total, out_cap):
    """Output slot j reads the block that starts last at or before j, at
    rank j less that start (empty blocks share their start with the next
    live one, which has the higher id and wins the max); the row is the
    rank-th set bit of the block's words."""
    nblocks, nwords = words.shape
    j = jnp.arange(out_cap, dtype=jnp.int32)
    first = jnp.zeros(out_cap, jnp.int32).at[off].max(
        jnp.arange(1, nblocks + 1, dtype=jnp.int32), mode="drop"
    )
    live = j < total
    block = jnp.where(live, fast_cummax(first) - 1, 0)
    rank = j - fast_cummax(jnp.where(first > 0, j, 0))
    row = words[block]
    ones = jax.lax.population_count(row).astype(jnp.int32)
    before = jnp.cumsum(ones, axis=1) - ones  # set bits before each word
    # the word that holds the rank-th set bit starts last at or before it
    widx = jnp.sum(before <= rank[:, None], axis=1, dtype=jnp.int32) - 1
    here = jnp.arange(nwords, dtype=jnp.int32) == widx[:, None]
    word = jnp.sum(jnp.where(here, row, 0), axis=1, dtype=jnp.uint32)
    rank = rank - jnp.sum(jnp.where(here, before, 0), axis=1, dtype=jnp.int32)
    bit = jnp.zeros(out_cap, jnp.int32)
    for width in (16, 8, 4, 2, 1):
        low = (word >> bit.astype(jnp.uint32)) & jnp.uint32((1 << width) - 1)
        below = jax.lax.population_count(low).astype(jnp.int32)
        up = rank >= below
        rank = jnp.where(up, rank - below, rank)
        bit = jnp.where(up, bit + width, bit)
    idx = (block * nwords + widx) * _WORD + bit
    return jnp.where(live, idx, 0)


def _selects(n: int, out_cap: int) -> bool:
    """Block select for a mask large enough to matter whose output is a
    small enough share of it (the comment above has both numbers)."""
    return (
        n >= _SELECT_MIN_ROWS
        and n % _SELECT_BLOCK == 0
        and out_cap * _SELECT_CROSSOVER <= n
    )


@_ktraced("compact_select")
def _compact_select(mask, out_cap):
    return _select_rows(*_select_blocks(mask), out_cap)


@_ktraced("compact_indices")
def _compact_whole(mask, out_cap):
    if _multi_device(mask):
        full = _compact_full_sorted(mask)
    else:
        full = _compact_full(mask)
    n = mask.shape[0]
    if out_cap <= n:
        return jax.lax.slice(full, (0,), (out_cap,))
    return jnp.pad(full, (0, out_cap - n))


def compact_indices(mask: jnp.ndarray, out_cap: int) -> jnp.ndarray:
    """Indices of True entries ascending, padded with 0 to out_cap. One
    launch behind the seam, under `compact_select` where the two shapes
    send the call to block select and under `compact_indices` elsewhere
    (a dense mask, a small one, one sharded over a mesh)."""
    if _selects(mask.shape[0], out_cap) and not _multi_device(mask):
        return _compact_select(mask, out_cap)
    return _compact_whole(mask, out_cap)


def mask_count(mask: jnp.ndarray) -> int:
    return int(host_read("mask_count", jnp.sum(mask)))


# ---------------------------------------------------------------------------
# Sorting
# ---------------------------------------------------------------------------


# -- canonical kv sort ------------------------------------------------------
# XLA:TPU sort compile time is ~10-12 s per comparator operand at fact-table
# shapes (measured on the 1-core bench host), and every distinct
# (operand count, shapes) tuple is its own kernel. The engine therefore
# routes EVERY ordering through one canonical kernel: a stable
# (int64 key, int32 iota) sort — one compile per input cap, persisted in
# the XLA cache, reused by every sort/group/join in every query.
# Multi-word keys run as stable LSD passes over the same kernel.


@partial(jax.jit, static_argnames=())
def _kv_sort_perm(key: jnp.ndarray) -> jnp.ndarray:
    iota = jnp.arange(key.shape[0], dtype=jnp.int32)
    return jax.lax.sort((key, iota), num_keys=1, is_stable=True)[1]


def kv_sort_perm(key: jnp.ndarray) -> jnp.ndarray:
    """Stable ascending argsort of one int64 key via the canonical kernel."""
    return _kv_sort_perm(key.astype(I64))


@partial(jax.jit, static_argnames=())
def word_span(word: jnp.ndarray):
    """(min, max) over ONE sort word, padding included — the span probe
    for the Pallas counting-sort route (exec._sort_perm_route): every
    value in the word (live, dead, and null codes alike) is a legitimate
    sort key, so the span must cover them all. One fused dispatch; the
    caller pays the single host sync."""
    w = word.astype(I64)
    return jnp.stack([jnp.min(w), jnp.max(w)])


@_ktraced("sort_by_words")
def sort_by_words(words) -> jnp.ndarray:
    """Stable lexicographic argsort by a list of int64 words (most
    significant first): LSD radix over the canonical kv-sort kernel."""
    perm = None
    for w in reversed(words):
        k = w if perm is None else w[perm]
        p = _kv_sort_perm(k)
        perm = p if perm is None else perm[p]
    return perm


def float_key_words(x: jnp.ndarray):
    """Exact injective float64 -> (exponent, mantissa) int64 word pair for
    join-key equality: equal floats map to equal pairs, distinct to
    distinct. Built from frexp arithmetic because this TPU toolchain
    emulates 64-bit types and cannot compile bitcast-convert on s64.
    Spark semantics: -0.0 == 0.0 and NaN == NaN (normalized); +-inf get
    reserved exponent codes (frexp on non-finite input is undefined)."""
    x = x.astype(jnp.float64)
    x = jnp.where(x == 0.0, 0.0, x)  # -0.0 -> +0.0
    special = jnp.isnan(x) | jnp.isinf(x)
    m, e = jnp.frexp(jnp.where(special, 0.0, x))
    # m = j/2^53 with |j| in [2^52, 2^53): m * 2^53 is exactly integral,
    # so the pair (e, j) loses nothing. e in [-1073, 1024] for finite x.
    ew = e.astype(I64)
    mw = (m * jnp.float64(1 << 53)).astype(I64)
    ew = jnp.where(jnp.isnan(x), jnp.int64(99999), ew)
    ew = jnp.where(jnp.isinf(x) & (x > 0), jnp.int64(99998), ew)
    ew = jnp.where(jnp.isinf(x) & (x < 0), jnp.int64(-99999), ew)
    mw = jnp.where(special, 0, mw)
    return ew, mw


@_ktraced("group_by_words")
def group_by_words(words, live_mask, nlive=None):
    """group_rows over pre-encoded key words (exact encodings: equal words
    <=> equal keys). The word list must place live rows first (callers fold
    ~live into the leading word via the packer)."""
    order = sort_by_words(words)
    sorted_words = list(take_arrays(words, order))
    flags = _word_flags(sorted_words)
    gid = fast_cumsum(flags.astype(jnp.int32)) - 1
    if nlive is None:
        nlive = mask_count(live_mask)
    if nlive == 0:
        return order, gid, 0
    ngroups = int(host_read("ngroups", gid[nlive - 1])) + 1
    return order, gid, ngroups


@partial(jax.jit, static_argnames=())
def _word_flags(sorted_words):
    """Group-boundary flags from adjacent word inequality."""
    n = sorted_words[0].shape[0]
    flag = jnp.zeros(n, dtype=bool).at[0].set(True)
    for w in sorted_words:
        flag = flag.at[1:].max(w[1:] != w[:-1])
    return flag


def fold_sort_key(data, valid, ascending: bool, nulls_first: bool):
    """Direction/null folding for ONE sort key: the transformed comparison
    arrays in major->minor significance order ([null_rank, value] when the
    key is nullable, else [value]). Shared by the single-device lexsort and
    the distributed samplesort (exec._try_dist_sort) so the two orderings
    can never diverge."""
    d = data
    if jnp.issubdtype(d.dtype, jnp.integer):
        d = d.astype(I64)
    if not ascending:
        d = -d
    if valid is None:
        return [d]
    null_rank = jnp.where(valid, jnp.int32(0),
                          jnp.int32(-1 if nulls_first else 1))
    return [null_rank, jnp.where(valid, d, jnp.zeros((), d.dtype))]


# -- spec-driven word building ----------------------------------------------
# Building sort words op-by-op in eager mode costs ~0.6 s of XLA compile
# per (op, shape) instance on this host — a fresh chain per query. Instead
# the whole encoding compiles as ONE function per (spec, shapes) key, and
# field widths are quantized to a small ladder so the same compiled
# encoder serves every query whose keys have similar spans.

_WIDTH_LADDER = (2, 3, 4, 6, 8, 11, 16, 22, 32, 44, 62)


def quantize_width(w: int) -> int:
    for q in _WIDTH_LADDER:
        if w <= q:
            return q
    return 63  # force standalone


@_ktraced("build_sort_words")
@partial(jax.jit, static_argnames=("spec",))
def build_sort_words(spec, live, *arrays):
    """Encode sort keys into words under a STATIC spec.

    spec: tuple of field descriptors, major->minor:
      ("L",)                 — live bit from `live` (dead rows last)
      ("i", width, asc, nf, has_valid) — bounded int field, mixed-radix
            packed; consumes data, vmin, vmax [, valid] from `arrays`
      ("I", asc, nf, has_valid)        — unbounded int, standalone word;
            consumes data [, valid]
      ("f", asc, nf, has_valid)        — float: 1-bit NaN rank into the
            shared stream + standalone f64 word; consumes data [, valid]
    Returns the word tuple for sort_by_words / group_by_words."""
    it = iter(arrays)
    words = []
    cur = {"w": None, "bits": 0}

    def flush():
        if cur["w"] is not None:
            words.append(cur["w"])
        cur["w"] = None
        cur["bits"] = 0

    def add(code, width):
        if cur["bits"] + width > 62:
            flush()
        code = code & ((1 << width) - 1)  # clamp dead-row garbage
        cur["w"] = (
            code if cur["w"] is None else (cur["w"] << width) | code
        )
        cur["bits"] += width

    for field in spec:
        kind = field[0]
        if kind == "L":
            add(jnp.where(live, 0, 1).astype(I64), 1)
            continue
        if kind == "i":
            _, width, asc, nf, hv = field
            d = next(it).astype(I64)
            vmin = next(it)
            vmax = next(it)
            v = next(it) if hv else None
            code = (d - vmin + 1) if asc else (vmax - d + 1)
            if v is not None:
                # null first -> 0; null last -> top code (clamped by add)
                code = jnp.where(v, code, 0 if nf else (1 << width) - 1)
            add(code, width)
            continue
        _, asc, nf, hv = field
        d = next(it)
        v = next(it) if hv else None
        if v is not None:
            add(jnp.where(v, 1 if nf else 0, 0 if nf else 1).astype(I64), 1)
        if kind == "I":
            w = d.astype(I64)
            if not asc:
                w = ~w
            if v is not None:
                w = jnp.where(v, w, 0)
        else:  # float
            w = d.astype(jnp.float64)
            if v is not None:
                w = jnp.where(v, w, 0.0)  # mask nulls BEFORE the NaN rank
            w = jnp.where(w == 0.0, 0.0, w)  # -0.0 == 0.0
            nan = jnp.isnan(w)
            add(jnp.where(nan, 1 if asc else 0, 0 if asc else 1).astype(I64),
                1)
            w = jnp.where(nan, 0.0, w)
            if not asc:
                w = -w
        flush()
        words.append(w)
    flush()
    return tuple(words)


def key_words(keys, live_mask):
    """Generic word encoding for (data, valid, ascending, nulls_first) key
    tuples: a leading live word (dead rows last), then per key a 1-bit
    null-rank word when nullable, a 1-bit NaN-rank word for floats (Spark:
    NaN greater than +inf), and the value word with direction folded
    (order-reversing bitwise not for ints, negation for floats). One word
    per field — the engine's Executor._sort_words builds tighter mixed-radix
    packings with bounds; this bounds-free version serves the kernel-level
    API and tests."""
    words = [jnp.where(live_mask, jnp.int64(0), jnp.int64(1))]
    for data, valid, asc, nf in keys:
        if nf is None:
            nf = asc
        if valid is not None:
            words.append(
                jnp.where(valid, 1 if nf else 0, 0 if nf else 1).astype(I64)
            )
        if jnp.issubdtype(data.dtype, jnp.floating):
            w = data.astype(jnp.float64)
            if valid is not None:
                w = jnp.where(valid, w, 0.0)
            w = jnp.where(w == 0.0, 0.0, w)  # -0.0 == 0.0
            nan = jnp.isnan(w)
            words.append(
                jnp.where(nan, 1 if asc else 0, 0 if asc else 1).astype(I64)
            )
            w = jnp.where(nan, 0.0, w)
            if not asc:
                w = -w
        else:
            w = data.astype(I64)
            if not asc:
                w = ~w
            if valid is not None:
                w = jnp.where(valid, w, 0)
        words.append(w)
    return words


def sort_indices(keys, live_mask: jnp.ndarray) -> jnp.ndarray:
    """Stable multi-key sort; returns row order with live rows first.

    `keys` is a list of (data:int64/float64, valid:bool|None, ascending:bool,
    nulls_first:bool) in major-to-minor significance order. Runs as stable
    LSD passes over the canonical kv kernel (sort_by_words)."""
    return sort_by_words(key_words(keys, live_mask))


# ---------------------------------------------------------------------------
# Grouping (sort-based): group ids + segment reductions
# ---------------------------------------------------------------------------


def group_rows(keys, valids, live_mask, nlive=None):
    """Sort rows so equal keys are adjacent and assign group ids.

    Returns (order, gid_sorted, ngroups): `order` the sorted row order,
    `gid_sorted[i]` the 0-based group of sorted row i, `ngroups` the number of
    live groups (host int). Nulls form their own group (Spark GROUP BY
    semantics). Pass `nlive` when the live count is already known on the host
    (a Table's nrows) — it saves one device round trip per groupby."""
    tuples = [(d, v, True, True) for d, v in zip(keys, valids)]
    return group_by_words(key_words(tuples, live_mask), live_mask, nlive)


# A segment reduction routes by what its caller can state about the ids.
# `jax.ops.segment_sum` / `segment_min` / `segment_max` are a serial scatter
# on the v5e, and over int64 (emulated) into colliding cells it costs 62.6 ns
# an update at 4,194,304 rows into one cell, 82-89 at 16,777,216 sorted rows
# (PERF.md, Findings PR 44, step 0). Ids that are RUNS need none of it:
#
#   gid None          one run, a global aggregate: a masked `jnp.sum` /
#                     `jnp.min` / `jnp.max` into cell 0, the other cells as
#                     the scatter leaves them (0, or the extreme). Every
#                     op and dtype; a row-sharded input lowers to partials
#                     and one all-reduce. Seam name `reduce_whole`.
#   runs=(starts, ends)  sorted dense runs (`run_bounds` over what
#                     `group_by_words` made): `count`, and `sum` over
#                     integers, are differences of a prefix sum gathered at
#                     the run ends, in the dtype the scatter accumulates in
#                     (two's-complement wraparound cancels, so bit for bit
#                     the scatter's). Seam name `reduce_runs`. A float sum
#                     (prefix differences cancel), min and max stay on the
#                     scatter; beside one of those a count still reads the
#                     prefix, as a launch of its own.
#   neither           ids in any order: the scatter, as ever
#                     (`segment_reduce`, `segment_reduce_with_count`).
#
# Nothing is detected on the device and nothing is a knob: the caller says
# what it knows, and the op and the dtype are static.


def _masked(vals, weight, op):
    """(operand, identity) of `op` with dead / NULL rows neutralized."""
    if op == "count":
        return weight.astype(I64), jnp.zeros((), I64)
    if op == "sum":
        zero = jnp.zeros((), vals.dtype)
        return jnp.where(weight, vals, zero), zero
    if op == "sumsq":
        zero = jnp.zeros((), jnp.float64)
        return jnp.where(weight, vals.astype(jnp.float64) ** 2, zero), zero
    if op in ("min", "max"):
        ident = _extreme(vals.dtype, op == "min")
        return jnp.where(weight, vals, ident), ident
    raise ValueError(op)


def _scatter_one(vals, gid, weight, num_segments, op):
    v, _ = _masked(vals, weight, op)
    if op == "min":
        return jax.ops.segment_min(v, gid, num_segments)
    if op == "max":
        return jax.ops.segment_max(v, gid, num_segments)
    return jax.ops.segment_sum(v, gid, num_segments)


def _program(name):
    """Name the function that follows `name` before it is jitted: a device
    trace calls its programs `jit_<name>/...`, and the two scatters keep
    the names every breakdown since PR 24 has read them under, whatever the
    dispatcher in front of them is called."""
    def deco(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return deco


@_ktraced("segment_reduce")
@partial(jax.jit, static_argnames=("num_segments", "op"))
@_program("segment_reduce")
def _reduce_scatter(vals, gid, weight, num_segments, op):
    return _scatter_one(vals, gid, weight, num_segments, op)


@_ktraced("segment_reduce_with_count")
@partial(jax.jit, static_argnames=("num_segments", "op"))
@_program("segment_reduce_with_count")
def _reduce_scatter_with_count(vals, gid, weight, num_segments, op):
    return (
        _scatter_one(vals, gid, weight, num_segments, op),
        _scatter_one(vals, gid, weight, num_segments, "count"),
    )


@_ktraced("reduce_whole")
@partial(jax.jit, static_argnames=("num_segments", "ops"))
def _reduce_whole(vals, weight, num_segments, ops):
    first = jnp.arange(num_segments, dtype=jnp.int32) == 0
    out = []
    for op in ops:
        v, ident = _masked(vals, weight, op)
        if op == "min":
            r = jnp.min(v)
        elif op == "max":
            r = jnp.max(v)
        else:
            r = jnp.sum(v, dtype=v.dtype)
        out.append(jnp.where(first, r, ident))
    return tuple(out)


@_ktraced("reduce_runs")
@partial(jax.jit, static_argnames=("ops",))
def _reduce_runs(vals, weight, runs, ops):
    starts, ends = runs
    last = weight.shape[0] - 1
    hi = jnp.clip(ends - 1, 0, last)
    lo = jnp.clip(starts - 1, 0, last)
    out = []
    for op in ops:
        if op == "count":  # n < 2**31 rows: an int32 prefix holds them
            v = weight.astype(jnp.int32)
        else:
            v, _ = _masked(vals, weight, op)
        c = fast_cumsum(v)
        zero = jnp.zeros((), c.dtype)
        r = jnp.where(
            ends > starts, c[hi] - jnp.where(starts > 0, c[lo], zero), zero
        )
        out.append(r.astype(I64) if op == "count" else r)
    return tuple(out)


def _prefix_exact(vals, op) -> bool:
    """Ops a difference of prefix sums answers bit for bit as the scatter
    does: every count, and a sum over integers (decimals are)."""
    return op == "count" or (
        op == "sum" and jnp.issubdtype(vals.dtype, jnp.integer)
    )


def segment_reduce(vals, gid, weight, num_segments, op, runs=None):
    """Segment reduction with a live/validity weight mask.

    op: sum | min | max | count | sumsq. `gid` None: one segment, cell 0
    of the answer. `runs`: the `(starts, ends)` of sorted dense runs
    (`run_bounds`), beside the `gid` they were read from. The answer has
    `num_segments` cells whichever route computed it (comment above)."""
    if gid is None:
        return _reduce_whole(vals, weight, num_segments, (op,))[0]
    if runs is not None and _prefix_exact(vals, op):
        return _reduce_runs(vals, weight, runs, (op,))[0]
    return _reduce_scatter(vals, gid, weight, num_segments, op)


def _extreme(dtype, is_max):
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.max if is_max else info.min, dtype)
    return jnp.asarray(jnp.inf if is_max else -jnp.inf, dtype)


def segment_reduce_with_count(vals, gid, weight, num_segments, op,
                              runs=None):
    """(reduction, live count) per segment in ONE dispatch (two where the
    reduction scatters beside a count that need not).

    Every non-count aggregate needs both — the count drives SQL
    NULL-on-empty output validity — and issuing them as two jitted calls
    paid a second dispatch and let XLA re-derive the masked operand
    instead of sharing it."""
    if gid is None:
        return _reduce_whole(vals, weight, num_segments, (op, "count"))
    if runs is None:
        return _reduce_scatter_with_count(vals, gid, weight, num_segments, op)
    if _prefix_exact(vals, op):
        return _reduce_runs(vals, weight, runs, (op, "count"))
    return (
        _reduce_scatter(vals, gid, weight, num_segments, op),
        _reduce_runs(vals, weight, runs, ("count",))[0],
    )


@_ktraced("batched_min_max")
def batched_min_max(datas, valids, live):
    """Masked (min, max) of several int64 columns in one dispatch batch, so
    the caller pays ONE device->host transfer regardless of column count.
    Returns stacked [k, 2]; an empty/all-null column yields (0, -1) (i.e.
    vmax < vmin) so callers can detect it."""
    info = jnp.iinfo(I64)
    outs = []
    for d, v in zip(datas, valids):
        m = live if v is None else (live & v)
        mn = jnp.min(jnp.where(m, d, info.max))
        mx = jnp.max(jnp.where(m, d, info.min))
        nonempty = m.any()
        outs.append(
            jnp.stack(
                [jnp.where(nonempty, mn, 0), jnp.where(nonempty, mx, -1)]
            )
        )
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# Equi-join
# ---------------------------------------------------------------------------


def _join_prepare(rhash, rlive):
    """Sort right-side hashes; dead rows get a reserved slot at the end.
    Eager (not jitted whole) so the sort reuses the canonical kv kernel."""
    rh = jnp.where(rlive, rhash, jnp.iinfo(I64).max)
    order = _kv_sort_perm(rh)
    return rh[order], order


@partial(jax.jit, static_argnames=())
def _join_counts(rh_sorted, lhash, llive):
    lh = jnp.where(llive, lhash, jnp.iinfo(I64).min)
    lo = jnp.searchsorted(rh_sorted, lh, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(rh_sorted, lh, side="right").astype(jnp.int32)
    counts = jnp.where(llive, hi - lo, 0)
    return lo, counts


@partial(jax.jit, static_argnames=("out_cap",))
def _join_expand(lo, counts, rorder, out_cap):
    """Expand (row, count) pairs into candidate (li, ri) index pairs.

    Owner assignment is scatter + blocked prefix-max, all int32: each
    contributing row's index lands at its output-range start and cummax
    fills the range (count>0 rows have unique starts; count-0 rows park at
    out_cap and drop). The previous searchsorted over an int64
    arange(out_cap) ran ~13 s at a 16M-candidate fact join on this
    toolchain, which emulates 64-bit element types — this formulation is
    ~50 ms at the same shape."""
    counts = counts.astype(jnp.int32)
    offs = (fast_cumsum(counts) - counts).astype(jnp.int32)  # exclusive
    total = jnp.sum(counts)
    rows = jnp.arange(lo.shape[0], dtype=jnp.int32)
    starts = jnp.where(counts > 0, offs, out_cap)
    owner = jnp.full(out_cap, -1, jnp.int32).at[starts].max(rows, mode="drop")
    li = jnp.clip(fast_cummax(owner), 0, lo.shape[0] - 1)
    p = jnp.arange(out_cap, dtype=jnp.int32)
    j = p - offs[li]
    ri_sorted_pos = jnp.clip(lo[li] + j, 0, rorder.shape[0] - 1)
    ri = rorder[ri_sorted_pos]
    pair_live = p < total
    return li, ri, pair_live


@_ktraced("join_candidates")
def join_candidates(lkeys, lvalids, llive, rkeys, rvalids, rlive):
    """Hash-match candidate pairs; caller MUST verify real key equality.

    Returns (li, ri, pair_live, total_candidates). Rows with any null key
    never match (SQL equality semantics).
    """
    lh = hash_columns(lkeys, lvalids)
    rh = hash_columns(rkeys, rvalids)
    lnn = _all_valid(lvalids, llive)
    rnn = _all_valid(rvalids, rlive)
    rh_sorted, rorder = _join_prepare(rh, rnn)
    lo, counts = _join_counts(rh_sorted, lh, lnn)
    # int64 reduction + host-side guard: _join_expand's owner-assignment
    # arithmetic (exclusive cumsum, positions) runs in int32 for speed, so
    # a candidate total past 2^31 would silently wrap into garbage pair
    # indices. Fail loudly instead (such an out_cap wouldn't allocate
    # anyway; the realistic trigger is a pathological cross-join-like key).
    total = int(host_read("join_size", jnp.sum(counts, dtype=jnp.int64)))
    _check_pair_count(total)
    # genuine import cycle: engine.columnar jits through ops.kernels, so a
    # module-level import here would deadlock package init; cold path
    # (sparse-join expansion sizing), one sys.modules hit per expand
    # nds-lint: disable=local-import
    from ..engine.columnar import bucket_cap

    out_cap = bucket_cap(max(total, 1))
    li, ri, pair_live = _join_expand(lo, counts, rorder, out_cap)
    return li, ri, pair_live, total


def _check_pair_count(total: int):
    """Host-side int32-range guard for join candidate expansion: the
    output capacity is the next power-of-two bucket >= total, and that cap
    itself must stay an int32 value (it is used as the parked-row sentinel
    in the owner scatter), so the largest safe bucket is 2^30."""
    if total > 1 << 30:
        raise ValueError(
            f"join candidate count {total} exceeds the int32-safe "
            f"expansion capacity (2^30); refusing to expand (the int32 "
            f"pair arithmetic would wrap silently)"
        )


def _all_valid(valids, live):
    m = live
    for v in valids:
        if v is not None:
            m = m & v
    return m


def pack_key_words(sides, bounds):
    """Pack N aligned integer key columns into one exact int64 word per
    side. `sides` is a list of column lists (one list per side, each
    [(data, valid)] of equal length N); `bounds` is [(vmin, vmax)] per key
    (host ints, union over all sides). Layout per key: (value - vmin + 1)
    in its bit field, 0 for NULL. Returns one word array per side, or None
    when the packed width exceeds 62 bits. The single definition keeps the
    catalog's PK verification and the executor's packed join bit-for-bit
    identical."""
    shift = 0
    words = [None] * len(sides)
    for ki, (vmin, vmax) in enumerate(bounds):
        span = vmax - vmin + 2  # +1 for the NULL slot
        bits = max(1, (span - 1).bit_length())
        if shift + bits > 62:
            return None
        for si, side in enumerate(sides):
            data, valid = side[ki]
            v = data.astype(I64) - vmin + 1
            if valid is not None:
                v = jnp.where(valid, v, 0)
            part = v << shift
            words[si] = part if words[si] is None else words[si] + part
        shift += bits
    return words


@_ktraced("member_lookup")
def member_lookup(lwords, lnn, rwords, rnn):
    """Exact-word membership probe: for each left row, is its packed key
    word present among live right words, and at which right row?

    Requires collision-free words (exact packing, not hashing) — presence
    needs no verification and right-side duplicates cannot hide a match
    (`ri` is then the first duplicate in sorted order; callers needing a
    unique right side must know it from plan metadata). The sort runs
    eagerly through the shared canonical kv-sort so its per-shape compile
    is amortized with every other sorting consumer."""
    big = jnp.iinfo(I64).max
    rw = jnp.where(rnn, rwords, big)
    order = _kv_sort_perm(rw)
    return _member_probe(rw[order], order, lwords, lnn)


@partial(jax.jit, static_argnames=())
def _member_probe(rw_sorted, order, lwords, lnn):
    n = rw_sorted.shape[0]
    probe = jnp.where(lnn, lwords, jnp.int64(-1))
    lo = jnp.clip(
        jnp.searchsorted(rw_sorted, probe, side="left"), 0, n - 1
    ).astype(jnp.int32)
    # packed words are non-negative, so the -1 dead-left probe never hits
    found = lnn & (rw_sorted[lo] == probe)
    # row 0 where unmatched, as `dense_probe` hands it on
    return found, jnp.where(found, order[lo], 0)


@_ktraced("verify_pairs")
@partial(jax.jit, static_argnames=())
def verify_pairs(li, ri, pair_live, lkeys, lvalids, llive, rkeys, rvalids, rlive):
    """AND real key equality into the candidate mask (collision shield)."""
    ok = pair_live & llive[li] & rlive[ri]
    for (ld, lv), (rd, rv) in zip(zip(lkeys, lvalids), zip(rkeys, rvalids)):
        eq = ld[li].astype(I64) == rd[ri].astype(I64)
        if lv is not None:
            eq = eq & lv[li]
        if rv is not None:
            eq = eq & rv[ri]
        ok = ok & eq
    return ok


@partial(jax.jit, static_argnames=("cap",))
def matched_mask(li, ok, cap):
    """Per-left-row flag: does row have at least one verified match?"""
    return jnp.zeros(cap, dtype=bool).at[li].max(ok)


# ---------------------------------------------------------------------------
# Dense-domain join (star-join fast path)
#
# TPC-DS dimension tables key on dense surrogate keys, so a fact->dim join
# is a bounds-checked gather through a dense lookup table instead of a
# sort + searchsorted. This is both the single-chip hot path (no O(n log n)
# sort over the fact side) and the multi-chip one: probes are elementwise
# over row-sharded fact columns, the build side is replicated, so XLA/GSPMD
# keeps the whole probe local to each chip (the scaling-book "gather through
# replicated dim" layout).
# ---------------------------------------------------------------------------


@_ktraced("dense_build")
@partial(jax.jit, static_argnames=("table_cap",))
def dense_build(rkey, rlive, rmin, table_cap):
    """Build the lookup table over the key domain [rmin, rmin+table_cap):
    one int32 an entry, the build row + 1 of the key, 0 where no live row
    has it, so that one value carries presence and row and a probe reads
    the table once. Out-of-range and dead rows scatter to drop; of
    duplicates the highest row stays. Build-side uniqueness (needed by
    inner/left) is the caller's contract, established from catalog
    ColStats — not re-checked on device."""
    slot = jnp.where(rlive, rkey.astype(I64) - rmin, jnp.int64(table_cap))
    slot = jnp.where((slot >= 0) & (slot <= table_cap), slot, table_cap)
    return (
        jnp.zeros(table_cap, jnp.int32)
        .at[slot]
        .max(jnp.arange(1, rkey.shape[0] + 1, dtype=jnp.int32), mode="drop")
    )


@_ktraced("dense_probe")
@partial(jax.jit, static_argnames=("table_cap",))
def dense_probe(lkey, llive, rmin, rowid1, table_cap):
    """Per left row: matched flag + matching right row, row 0 where
    unmatched, so every row handed back indexes the build side. One gather
    from `dense_build`'s table: on the v5e a second table read by the same slot
    cost more than the first (PERF.md, Findings PR 36), and
    tests/test_kernels.py holds the program to one. The slot stays int64:
    an int32 slot measured the same."""
    slot = lkey.astype(I64) - rmin
    inb = (slot >= 0) & (slot < table_cap) & llive
    r = rowid1[jnp.clip(slot, 0, table_cap - 1)]
    matched = inb & (r > 0)
    return matched, jnp.where(matched, r - 1, 0)


# ---------------------------------------------------------------------------
# Direct (sort-free) grouping: domain-compressed group ids
#
# When the combined key domain is small (the TPC-DS norm: years, brand ids,
# channel flags...), the group id of every row is computed elementwise as a
# mixed-radix code and aggregation is one scatter-add per measure. No sort,
# and under GSPMD the scatter-add over row-sharded facts lowers to local
# partial aggregation + a cross-chip reduction (psum) of the small group
# table — the distributed groupby layout.
# ---------------------------------------------------------------------------


@_ktraced("direct_gid")
@partial(jax.jit, static_argnames=())
def direct_gid(keys, valids, mins, ranges, live):
    """Mixed-radix group code per row. Each key contributes
    (value - min + has_null) with code 0 reserved for NULL; dead rows get the
    all-zero code but are excluded by weight masks downstream."""
    gid = jnp.zeros(live.shape[0], I64)
    for data, valid, kmin, krange in zip(keys, valids, mins, ranges):
        code = data.astype(I64) - kmin
        if valid is not None:
            code = jnp.where(valid, code + 1, 0)
        gid = gid * krange + code
    return jnp.where(live, gid, 0)


@_ktraced("occupancy_map")
@partial(jax.jit, static_argnames=("domain_cap",))
def occupancy_map(gid, live, domain_cap):
    """occupied cell mask + dense renumbering (cell -> 0..ngroups-1)."""
    occ = jnp.zeros(domain_cap, bool).at[gid].max(live, mode="drop")
    dense = fast_cumsum(occ.astype(jnp.int32)) - 1
    return occ, dense


# ---------------------------------------------------------------------------
# Window helpers
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_segments",))
def segment_starts(gid, num_segments):
    """Index of the first sorted row of each segment."""
    n = gid.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    return jax.ops.segment_min(idx, gid, num_segments)


@jax.jit
def _run_flags(gid, live):
    """True at the first row of every live run of a sorted `gid`."""
    return jnp.concatenate([jnp.ones(1, bool), gid[1:] != gid[:-1]]) & live


@jax.jit
def _run_ends(starts, live, ngroups):
    g = jnp.arange(starts.shape[0], dtype=jnp.int32)
    nlive = jnp.sum(live, dtype=jnp.int32)
    following = jnp.concatenate([starts[1:], nlive[None]])
    # the last live run, and every cell past it, ends at the live count
    return (
        jnp.where(g < ngroups, starts, nlive),
        jnp.where(g + 1 < ngroups, following, nlive),
    )


def run_bounds(gid, live, num_segments, ngroups):
    """`(starts, ends)` of the runs of a sorted dense `gid` as
    `group_by_words` makes it (non-decreasing, live rows first, the live
    groups 0 .. ngroups - 1): run g is rows [starts[g], ends[g]); a cell
    past `ngroups` reads start = end = the live count, an empty run. What
    `segment_reduce(..., runs=)` takes; None for ids sharded over a mesh,
    which stay on the scatter as a sharded compaction does. The starts are
    the boundary flags compacted (`ngroups` <= `num_segments` of them, so
    block select at a fact table's rows), not `segment_starts`' scatter-min
    of every row's index."""
    if _multi_device(gid):
        return None
    starts = compact_indices(_run_flags(gid, live), num_segments)
    return _run_ends(starts, live, jnp.int32(ngroups))


@partial(jax.jit, static_argnames=())
def running_position(gid):
    """0-based position of each sorted row within its segment.

    lax.cummax, NOT lax.associative_scan: the generic log-depth scan
    construction compiles for minutes at fact shapes on this toolchain,
    while the native cumulative ops compile like cumsum."""
    n = gid.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    first = jnp.zeros(n, dtype=bool).at[0].set(True)
    first = first.at[1:].max(gid[1:] != gid[:-1])
    start_of_own = jnp.where(first, idx, 0)
    seg_start = fast_cummax(start_of_own)
    return idx - seg_start


def value_rank(x):
    """(sorted_values, rank): each row's position in the ascending global
    sort of its value, via the canonical kv kernel. Floats sort natively
    (f64 instance; -0.0 normalized, NaN last == Spark's NaN-greatest)."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        key = x.astype(jnp.float64)
        key = jnp.where(key == 0.0, 0.0, key)
    else:
        key = x.astype(I64)
    p = _kv_sort_perm(key)
    n = x.shape[0]
    rank = (
        jnp.zeros(n, jnp.int32).at[p].set(jnp.arange(n, dtype=jnp.int32))
    )
    return x[p], rank


@partial(jax.jit, static_argnames=("is_max",))
def segmented_running_extreme(vals_sorted_by_rank, rank, gid, weight,
                              is_max):
    """Running min/max within contiguous segments (gid ascending), exact
    for any dtype, without a generic associative scan (whose log-depth
    construction compiles for minutes at fact shapes on this toolchain).

    `rank`/`vals_sorted_by_rank` come from value_rank. y = gid * n + rank
    is gid-major monotone, so a native cummax over y can never leak an
    earlier segment's entry (rank < n), and mapping the winning rank back
    through the sorted values recovers the exact running extreme.
    Zero-weight rows get rank -1 (never win); a row whose segment prefix
    is all zero-weight gathers an arbitrary value — callers mask those
    via the running weight count."""
    n = jnp.int64(rank.shape[0])
    r = rank.astype(I64)
    if not is_max:
        r = n - 1 - r  # running min == running max of reversed ranks
    r = jnp.where(weight, r, -1)
    y = gid.astype(I64) * n + r
    cm = fast_cummax(y)
    win = cm - gid.astype(I64) * n
    if not is_max:
        win = n - 1 - win
    return vals_sorted_by_rank[jnp.clip(win, 0, n - 1)]
