"""Pallas TPU kernels: MXU-native segment aggregation.

`jax.ops.segment_sum` over a low-cardinality group domain lowers to an XLA
scatter-add, and TPU scatters serialize on conflicting indices — the classic
TPU weakness for groupby. The MXU-native formulation instead processes a tile
of rows at a time: build the tile's one-hot group matrix in VMEM and fold the
whole aggregation into one (8 x T) @ (T x G) matmul per tile — systolic-array
work, with the one-hot never touching HBM. Row 0 of the left matrix carries
the measure, row 1 carries ones, so a single dot yields both per-group sums
and counts.

This is the TPU-first counterpart of the hash-based groupby the reference
delegates to cuDF on GPUs (reference: nds/power_run_gpu.template:20-41
configures it; the kernel itself lives in the external RAPIDS engine).

Numerics: accumulation is float32. Per-tile dot products are exact for unit
counts (T <= 2**18 rows/tile) and for measures with <= 24 significant bits;
cross-tile accumulation is float32 pairwise within the systolic array. Use
for float measures (the --floats mode of the reference) and counts; exact
int64/decimal sums stay on the scatter path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

ROW_TILE = 2048     # fact rows per one-hot tile (the lane dimension)
GROUP_TILE = 512    # group rows per grid step (VMEM: one-hot 4 MB f32)
_SUB = 8            # one-hot tiles per grid step: the sublanes of a block


# Layout, shared by every kernel here. The row dimension is reshaped to
# (n / t, t) and a grid step takes an (8, t) block: Mosaic wants blocks of
# at least 8 sublanes by 128 lanes, and 8 * t rows make a DMA worth
# issuing. Inside a step the eight (1, t) rows are folded one at a time,
# each against a one-hot tile held TRANSPOSED, groups on sublanes and rows
# on lanes: `groups (g, t) == key (1, t)` is a sublane broadcast of the row
# as it lies in memory, where the (t, 1) column the untransposed tile needs
# would be a relayout of every block.


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _row_tiling(n: int, row_tile: int):
    """(t, padded n): lanes per one-hot tile and n rounded up to whole
    (8, t) blocks."""
    t = min(row_tile, _round_up(max(-(-n // _SUB), 1), 128))
    return t, _round_up(n, _SUB * t)


def _group_tiling(n_groups: int):
    gt = min(GROUP_TILE, _round_up(n_groups, 128))
    return gt, _round_up(n_groups, gt)


def _tile_ids(tile: int, t: int, j):
    """(tile, t) int32: the ids of group tile `j` down the sublanes, the
    same in every lane — what a (1, t) row of keys is compared with."""
    return jax.lax.broadcasted_iota(jnp.int32, (tile, t), 0) + j * tile


def _zero():
    """Block index 0 as int32: under x64 a literal 0 in an index map is an
    int64, which Mosaic refuses beside the int32 program ids."""
    return jnp.int32(0)


def _fold_rows(body, init):
    """Fold the eight rows of the current block: body(r, acc) -> acc.
    int32 bounds: under x64 a Python-int loop index is 64 bits wide, which
    Mosaic has no vector layout for."""
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(_SUB), body, init)


def _seg_kernel(group_tile: int, vals_ref, gid_ref, out_ref):
    j = pl.program_id(0)  # group tile (outer)
    i = pl.program_id(1)  # row block (inner)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    t = vals_ref.shape[1]
    groups = _tile_ids(group_tile, t, j)
    lrow = jax.lax.broadcasted_iota(jnp.int32, (8, t), 0)

    def body(r, acc):
        vals = vals_ref[pl.ds(r, 1), :]
        onehot_t = (groups == gid_ref[pl.ds(r, 1), :]).astype(jnp.float32)
        # row 0 carries the measure, row 1 ones: one dot, sums and counts
        left = jnp.where(
            lrow == 0, vals,
            jnp.where(lrow == 1, jnp.float32(1.0), jnp.float32(0.0)),
        )
        # HIGHEST precision: the TPU MXU default multiplies f32 via bf16
        # passes (~8 mantissa bits), which would break the "exact for
        # measures with <= 24 significant bits" contract; full-precision
        # f32 passes keep it
        return acc + jax.lax.dot_general(
            left, onehot_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    out_ref[:] += _fold_rows(body, jnp.zeros(out_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("n_groups", "interpret"))
def segment_sums_pallas(vals, gid, n_groups: int, interpret: bool = False):
    """Per-group (sum, count) of float32 `vals` by int32 `gid` (< 0 = dead
    row; dead rows contribute to nothing). Returns (sums f32[n_groups],
    counts f32[n_groups])."""
    n = vals.shape[0]
    if n == 0:  # grid of zero steps would return the output uninitialized
        z = jnp.zeros(n_groups, jnp.float32)
        return z, z
    t, n_pad = _row_tiling(n, ROW_TILE)
    gt, g_pad = _group_tiling(n_groups)
    vals = jnp.pad(vals.astype(jnp.float32), (0, n_pad - n))
    gid = jnp.pad(gid.astype(jnp.int32), (0, n_pad - n), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_seg_kernel, gt),
        grid=(g_pad // gt, n_pad // (_SUB * t)),
        in_specs=[
            pl.BlockSpec((_SUB, t), lambda j, i: (i, _zero())),
            pl.BlockSpec((_SUB, t), lambda j, i: (i, _zero())),
        ],
        out_specs=pl.BlockSpec((8, gt), lambda j, i: (_zero(), j)),
        out_shape=jax.ShapeDtypeStruct((8, g_pad), jnp.float32),
        interpret=interpret,
    )(vals.reshape(-1, t), gid.reshape(-1, t))
    return out[0, :n_groups], out[1, :n_groups]


def _seg_extreme_kernel(group_tile: int, is_max: bool, vals_ref, gid_ref,
                        ext_ref, cnt_ref):
    j = pl.program_id(0)  # group tile (outer)
    i = pl.program_id(1)  # row block (inner)
    fill = jnp.float32(-jnp.inf if is_max else jnp.inf)

    @pl.when(i == 0)
    def _():
        ext_ref[:] = jnp.full(ext_ref.shape, fill, jnp.float32)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)

    t = vals_ref.shape[1]
    groups = _tile_ids(group_tile, t, j)
    reduce = jnp.max if is_max else jnp.min
    pick = jnp.maximum if is_max else jnp.minimum

    def body(r, acc):
        ext, cnt = acc
        onehot_t = groups == gid_ref[pl.ds(r, 1), :]
        masked = jnp.where(onehot_t, vals_ref[pl.ds(r, 1), :], fill)
        return (
            pick(ext, reduce(masked, axis=1, keepdims=True)),
            cnt + jnp.sum(
                onehot_t.astype(jnp.float32), axis=1, keepdims=True
            ),
        )

    ext, cnt = _fold_rows(body, (ext_ref[:], cnt_ref[:]))
    ext_ref[:] = ext
    cnt_ref[:] = cnt


@functools.partial(
    jax.jit, static_argnames=("n_groups", "is_max", "interpret")
)
def segment_extreme_pallas(vals, gid, n_groups: int, is_max: bool,
                           interpret: bool = False):
    """Per-group (min-or-max, count) of float32 `vals` by int32 `gid`
    (< 0 = dead row) — the VPU tile counterpart of segment_sums_pallas:
    each row tile builds its one-hot group mask in VMEM and folds a masked
    min/max over the tile, so the XLA scatter-min/max (which serializes on
    conflicting indices on TPU) never runs. Empty groups hold the ±inf
    identity with count 0; callers mask them via the count (the same
    sentinel contract as kernels.segment_reduce)."""
    n = vals.shape[0]
    fill = jnp.float32(-jnp.inf if is_max else jnp.inf)
    if n == 0:
        return (
            jnp.full(n_groups, fill, jnp.float32),
            jnp.zeros(n_groups, jnp.float32),
        )
    t, n_pad = _row_tiling(n, ROW_TILE)
    gt, g_pad = _group_tiling(n_groups)
    vals = jnp.pad(vals.astype(jnp.float32), (0, n_pad - n))
    gid = jnp.pad(gid.astype(jnp.int32), (0, n_pad - n), constant_values=-1)
    # per-group results come out of a lane reduction, so they lie along
    # sublanes: (g, 1) columns, one per output
    col = pl.BlockSpec((gt, 1), lambda j, i: (j, _zero()))
    ext, cnt = pl.pallas_call(
        functools.partial(_seg_extreme_kernel, gt, is_max),
        grid=(g_pad // gt, n_pad // (_SUB * t)),
        in_specs=[
            pl.BlockSpec((_SUB, t), lambda j, i: (i, _zero())),
            pl.BlockSpec((_SUB, t), lambda j, i: (i, _zero())),
        ],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((g_pad, 1), jnp.float32)] * 2,
        interpret=interpret,
    )(vals.reshape(-1, t), gid.reshape(-1, t))
    return ext[:n_groups, 0], cnt[:n_groups, 0]


def _dense_build_kernel(domain_tile: int, slot_ref, out_ref):
    j = pl.program_id(0)  # domain tile (outer)
    i = pl.program_id(1)  # row block (inner)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    t = slot_ref.shape[1]
    slots = _tile_ids(domain_tile, t, j)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)

    def body(r, acc):
        # row index + 1, so that 0 is "no row": one maximum gives both
        # presence and the row. -1 = dead / out-of-range (never matches)
        rowid1 = lane + ((i * _SUB + r) * t + 1)
        hit = jnp.where(
            slots == slot_ref[pl.ds(r, 1), :], rowid1, jnp.int32(0)
        )
        return jnp.maximum(acc, jnp.max(hit, axis=1, keepdims=True))

    out_ref[:] = _fold_rows(body, out_ref[:])


@functools.partial(jax.jit, static_argnames=("table_cap", "interpret"))
def dense_build_pallas(rkey, rlive, rmin, table_cap: int,
                       interpret: bool = False):
    """Dense-domain join build table (row index + 1 per key slot, 0: no
    row) — the Pallas counterpart of `kernels.dense_build`, whose
    scatter-max serializes on TPU exactly like the groupby scatters. Each
    row tile builds its one-hot slot mask in VMEM and folds the row + 1
    maximum per domain tile; integer maxima, so results are EXACT (same
    contract as dense_build: build-side uniqueness is the caller's — with
    duplicates both formulations keep the max row index). Dead and
    out-of-range rows take slot -1 and never match a domain column."""
    n = rkey.shape[0]
    slot = rkey.astype(jnp.int64) - rmin
    slot = jnp.where(
        rlive & (slot >= 0) & (slot < table_cap), slot, jnp.int64(-1)
    ).astype(jnp.int32)
    if n == 0:
        return jnp.zeros(table_cap, jnp.int32)
    t, n_pad = _row_tiling(n, ROW_TILE)
    gt, g_pad = _group_tiling(table_cap)
    slot = jnp.pad(slot, (0, n_pad - n), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_dense_build_kernel, gt),
        grid=(g_pad // gt, n_pad // (_SUB * t)),
        in_specs=[pl.BlockSpec((_SUB, t), lambda j, i: (i, _zero()))],
        out_specs=pl.BlockSpec((gt, 1), lambda j, i: (j, _zero())),
        out_shape=jax.ShapeDtypeStruct((g_pad, 1), jnp.int32),
        interpret=interpret,
    )(slot.reshape(-1, t))
    return out[:table_cap, 0]


#: counting-sort routing caps (exec._sort_perm_route gates on them): the
#: one-hot rank tile holds the whole (padded) domain in VMEM, and ranks
#: accumulate in f32 (exact to 2**24 — matmul counts of 0/1 entries)
SORT_ROW_TILE = 256
SORT_MAX_DOMAIN = 2048
SORT_MAX_ROWS = 1 << 24


def _sort_rank_kernel(vals_ref, rank_ref, hist_ref):
    """One row block of the stable counting-rank: rank[r] = (# rows with
    the same key in PREVIOUS tiles) + (# earlier rows with the same key in
    THIS tile). The running per-key histogram rides the hist output block
    (revisited across the sequential grid, the same accumulation pattern
    as the segment kernels), a (g, 1) column like every per-key result
    here; its final state is the key histogram the caller turns into
    counting-sort offsets."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    t = vals_ref.shape[1]
    g = hist_ref.shape[0]
    keys = jax.lax.broadcasted_iota(jnp.int32, (g, t), 0)
    # strictly upper triangular ones: (onehot_t @ upper)[k, r] counts the
    # rows c < r of this tile with key k. 0/1 operands are exact in bf16
    # and the counts (<= t) in the f32 accumulator.
    upper = (
        jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    ).astype(jnp.bfloat16)

    def body(r, carry):
        onehot_t = keys == vals_ref[pl.ds(r, 1), :]
        onehot_f = onehot_t.astype(jnp.float32)
        before = jnp.dot(
            onehot_t.astype(jnp.bfloat16), upper,
            preferred_element_type=jnp.float32,
        )
        # each row picks its own key's entry out of a (g, t) column stack
        rank_ref[pl.ds(r, 1), :] = jnp.sum(
            onehot_f * (carry + before), axis=0, keepdims=True
        )
        return carry + jnp.sum(onehot_f, axis=1, keepdims=True)

    hist_ref[:] = _fold_rows(body, hist_ref[:])


@functools.partial(jax.jit, static_argnames=("domain", "interpret"))
def sort_rank_pallas(vals, domain: int, interpret: bool = False):
    """(stable within-key rank f32[n], key histogram f32[domain]) of int32
    `vals` in [0, domain); -1 marks a padded lane (contributes nothing,
    rank output unspecified). domain <= SORT_MAX_DOMAIN (the one-hot tile
    holds the whole padded domain), n <= SORT_MAX_ROWS (f32-exact
    counts)."""
    n = vals.shape[0]
    g = _round_up(max(domain, 1), 128)
    if n == 0:
        return jnp.zeros(0, jnp.float32), jnp.zeros(domain, jnp.float32)
    t, n_pad = _row_tiling(n, SORT_ROW_TILE)
    vals = jnp.pad(
        vals.astype(jnp.int32), (0, n_pad - n), constant_values=-1
    )
    rank, hist = pl.pallas_call(
        _sort_rank_kernel,
        grid=(n_pad // (_SUB * t),),
        in_specs=[pl.BlockSpec((_SUB, t), lambda i: (i, _zero()))],
        out_specs=[
            pl.BlockSpec((_SUB, t), lambda i: (i, _zero())),
            pl.BlockSpec((g, 1), lambda i: (_zero(), _zero())),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad // t, t), jnp.float32),
            jax.ShapeDtypeStruct((g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(vals.reshape(-1, t))
    return rank.reshape(-1)[:n], hist[:domain, 0]


@functools.partial(jax.jit, static_argnames=("domain", "interpret"))
def sort_perm_pallas(word, domain: int, interpret: bool = False):
    """Stable ascending argsort of one small-domain sort word — the
    Pallas counting-sort counterpart of the canonical kv-sort kernel
    (kernels._kv_sort_perm), for words whose packed value span fits
    SORT_MAX_DOMAIN (dictionary codes, tight date spans, the common
    TPC-DS ORDER BY shapes). Identical permutation to the canonical
    kernel by construction: both are stable ascending, and counting-sort
    position = offset[key] + stable within-key rank. XLA:TPU lax.sort
    compiles a fresh comparator kernel per operand/shape tuple and runs a
    serial bitonic network; this path is two MXU one-hot matmuls per row
    tile plus one collision-free scatter."""
    n = word.shape[0]
    vals = word.astype(jnp.int32)
    rank, hist = sort_rank_pallas(vals, domain, interpret=interpret)
    counts = hist.astype(jnp.int32)
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix
    pos = offsets[jnp.clip(vals, 0, domain - 1)] + rank.astype(jnp.int32)
    # positions are unique by construction: the scatter is collision-free
    return (
        jnp.zeros(n, jnp.int32)
        .at[pos]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    )


def segment_sums(vals, gid, n_groups: int):
    """Dispatch: MXU one-hot matmul kernel on TPU, XLA scatter elsewhere."""
    if jax.devices()[0].platform == "tpu":
        return segment_sums_pallas(vals, gid, n_groups)
    live = gid >= 0
    safe = jnp.where(live, gid, 0)
    v = jnp.where(live, vals.astype(jnp.float32), 0.0)
    sums = jax.ops.segment_sum(v, safe, n_groups)
    counts = jax.ops.segment_sum(live.astype(jnp.float32), safe, n_groups)
    return sums, counts
