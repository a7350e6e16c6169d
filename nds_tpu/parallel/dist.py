"""Distributed execution primitives: device mesh + sharded relational steps.

The reference scales queries via Spark executors and shuffle partitions
(reference: nds/base.template:28-31, power_run_cpu.template:20-27); the TPU
equivalent is SPMD over a jax.sharding.Mesh. The core patterns:

  * fact tables shard over the `data` mesh axis (rows), dimensions replicate;
  * star joins against dense surrogate-key dims are pure gathers;
  * aggregation is local partial segment-sum + psum over ICI (the
    shuffle-free TPC-DS groupby: group cardinality << row count);
  * large fact-fact joins hash-partition both sides with all_to_all
    (ppermute rounds) before local join.

`fused_query_step` is the single-chip jittable hot loop; `sharded_query_step`
is the same step laid out over a mesh via shard_map.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _sm
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs):
    return _sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


jax.config.update("jax_enable_x64", True)


def make_mesh(n_devices=None, axis="data"):
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"a {n_devices}-device mesh was asked for and jax reports "
                f"{len(devs)} {devs[0].platform} device(s)"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


# ---------------------------------------------------------------------------
# The flagship compiled step: star-join + filter + group aggregation.
# This is the shape of the NDS Power Run hot path (q3/q7/q19/...): scan a
# fact shard, gather dimension attributes through dense surrogate keys,
# apply dim predicates, segment-reduce measures by group key.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n_groups",))
def fused_query_step(
    fact_date_idx,  # int32[n]   fact FK -> dim row index (0-based)
    fact_item_idx,  # int32[n]
    fact_measure,   # int64[n]   scaled decimal measure
    fact_valid,     # bool[n]    live & non-null rows
    dim_date_flag,  # bool[n_dates]   date predicate (e.g. d_moy = 11)
    dim_item_group, # int32[n_items]  group key per item (-1 = filtered out)
    n_groups: int,
):
    """One fused scan->join->filter->aggregate step (single chip)."""
    ok = fact_valid
    ok = ok & dim_date_flag[fact_date_idx]
    g = dim_item_group[fact_item_idx]
    ok = ok & (g >= 0)
    vals = jnp.where(ok, fact_measure, 0)
    gids = jnp.where(ok, g, n_groups)  # dead rows -> overflow bucket
    sums = jax.ops.segment_sum(vals, gids, num_segments=n_groups + 1)
    counts = jax.ops.segment_sum(ok.astype(jnp.int64), gids, num_segments=n_groups + 1)
    return sums[:n_groups], counts[:n_groups]


def sharded_query_step(mesh: Mesh, n_groups: int):
    """Build the mesh-parallel version: fact sharded on rows, dims replicated,
    partial aggregation per chip + psum over ICI."""

    def local_step(fd, fi, fm, fv, ddf, dig):
        sums, counts = fused_query_step(fd, fi, fm, fv, ddf, dig, n_groups=n_groups)
        return jax.lax.psum(sums, "data"), jax.lax.psum(counts, "data")

    fn = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data"), P(), P()),
        out_specs=(P(), P()),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Hash-partitioned exchange: the all_to_all shuffle for fact-fact joins
# (reference's Spark shuffle, rebuilt on XLA collectives).
# ---------------------------------------------------------------------------


def partition_exchange(mesh: Mesh, cap_per_dev: int):
    """Returns a jitted fn that redistributes (key, value) rows so that every
    key lands on device hash(key) % n_devices. Rows are bucketed locally,
    padded to a fixed per-destination capacity, then exchanged with
    all_to_all over ICI.

    Returns (recv_keys, recv_vals, dropped): `dropped` is the global count of
    live rows that exceeded cap_per_dev in some destination bucket (replicated
    scalar). Callers MUST check dropped == 0 and retry with a larger capacity
    on overflow — under key skew a fixed cap silently truncating would corrupt
    join/aggregate results."""
    n_dev = mesh.devices.size

    def local(keys, vals, live):
        # keys,vals,live: [n_local]; returns [n_dev * cap] received rows
        dest = (keys % n_dev).astype(jnp.int32)
        rlive, (rk, rv), overflow = _route_by_dest(
            dest, live, n_dev, cap_per_dev, [keys, vals]
        )
        # contract: dead received slots carry key -1
        return jnp.where(rlive, rk, -1), rv, overflow

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P()),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Distributed hash join: the full shuffle join for fact-fact shapes
# (store_sales x store_returns and friends). Both sides hash-partition on the
# join-key hash with all_to_all over ICI, then every device joins its
# partition locally with static shapes — no host round-trips inside the
# compiled step. The executor drives capacity-overflow retries.
# ---------------------------------------------------------------------------


def _route_by_dest(dest, live, n_dev, cap, cols):
    """Pack rows into [n_dev, cap] buckets by destination device and exchange
    with all_to_all. Returns (recv_live, recv_cols, overflow)."""
    mdest = jnp.where(live, dest, n_dev)
    order = jnp.argsort(mdest)
    msorted = mdest[order]
    base = jnp.searchsorted(msorted, jnp.arange(n_dev), side="left")
    row = jnp.where(msorted < n_dev, msorted, n_dev)
    pos = jnp.arange(dest.shape[0]) - base[jnp.clip(row, 0, n_dev - 1)]
    overflow = ((msorted < n_dev) & (pos >= cap)).sum()
    row = jnp.where(pos < cap, row, n_dev)

    def scatter(x, fill):
        buf = jnp.full((n_dev, cap), fill, x.dtype)
        buf = buf.at[row, pos].set(x[order], mode="drop")
        return jax.lax.all_to_all(buf, "data", 0, 0, tiled=True).reshape(-1)

    rlive = scatter(live, False)
    rcols = [scatter(c, jnp.zeros((), c.dtype)) for c in cols]
    return rlive, rcols, jax.lax.psum(overflow, "data")


def _route(h, live, n_dev, cap, cols):
    """Hash routing: key lands on device hash % n_dev.
    Returns (recv_hash [n_dev*cap], recv_live, recv_cols, overflow)."""
    dest = (h.astype(jnp.uint64) % jnp.uint64(n_dev)).astype(jnp.int32)
    rlive, rcols, overflow = _route_by_dest(dest, live, n_dev, cap, [h] + cols)
    return rcols[0], rlive, rcols[1:], overflow


def exchange_hash_join(
    mesh: Mesh,
    n_lkeys: int,
    n_lcols: int,
    n_rcols: int,
    cap_l: int,
    cap_r: int,
    pair_cap: int,
    kind: str = "inner",
):
    """Factory for the mesh fact-fact join step (inner or left).

    The returned jitted fn takes
      (l_hash, l_live, l_keys..., l_cols...),
      (r_hash, r_live, r_keys..., r_cols...)
    as flat tuples and returns per-device-concatenated outputs:

      inner: (pair_ok [n_dev*pair_cap], l_out cols..., r_out cols...,
              recv_counts [n_dev], overflow scalar)
      left:  inner's outputs plus, before recv_counts:
             (l_recv_live [n_dev*cap_l], l_matched [n_dev*cap_l],
              l_recv cols... [n_dev*cap_l])

    pair_ok marks verified join pairs (hash candidates re-checked against
    the real key columns, so collisions can never fabricate rows). For a
    LEFT join the caller null-extends `l_recv_live & ~l_matched` rows (the
    shipped-but-unmatched left rows; null-keyed rows never route and stay
    the caller's problem). `recv_counts` is the per-device count of live
    received left rows — the skew evidence the `exchange` trace event
    reports (max/mean > 1 means the hash partitioning is imbalanced).
    overflow > 0 means some bucket or pair capacity was exceeded — the
    caller must retry with larger caps (executor emits a task-failure event
    and doubles, like a Spark shuffle-spill retry) and must not trust any
    other output of that attempt.
    """
    n_dev = mesh.devices.size
    imax = jnp.iinfo(jnp.int64).max
    imin = jnp.iinfo(jnp.int64).min

    def local(largs, rargs):
        lh, llive, *lrest = largs
        rh, rlive, *rrest = rargs
        lkeys, lcols = lrest[:n_lkeys], lrest[n_lkeys:]
        rkeys, rcols = rrest[:n_lkeys], rrest[n_lkeys:]
        lh2, llive2, lship, ovl = _route(
            lh, llive, n_dev, cap_l, list(lkeys) + list(lcols)
        )
        rh2, rlive2, rship, ovr = _route(
            rh, rlive, n_dev, cap_r, list(rkeys) + list(rcols)
        )
        lkeys2, lcols2 = lship[:n_lkeys], lship[n_lkeys:]
        rkeys2, rcols2 = rship[:n_lkeys], rship[n_lkeys:]
        # local sorted-probe join with a fixed pair capacity
        rh_m = jnp.where(rlive2, rh2, imax)
        order = jnp.argsort(rh_m).astype(jnp.int32)
        rh_sorted = rh_m[order]
        lh_m = jnp.where(llive2, lh2, imin)
        lo = jnp.searchsorted(rh_sorted, lh_m, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(rh_sorted, lh_m, side="right").astype(jnp.int32)
        counts = jnp.where(llive2, hi - lo, 0)
        offs = jnp.cumsum(counts) - counts
        total = jnp.sum(counts)
        p = jnp.arange(pair_cap, dtype=jnp.int64)
        li = jnp.searchsorted(offs + counts, p, side="right").astype(jnp.int32)
        li = jnp.clip(li, 0, lh2.shape[0] - 1)
        j = (p - offs[li]).astype(jnp.int32)
        ri = order[jnp.clip(lo[li] + j, 0, rh2.shape[0] - 1)]
        ok = (p < total) & llive2[li] & rlive2[ri]
        for a, b in zip(lkeys2, rkeys2):
            ok = ok & (a[li] == b[ri])
        ov_pairs = jnp.maximum(total - pair_cap, 0)
        overflow = ovl + ovr + jax.lax.psum(ov_pairs, "data")
        # per-device received-row counts as a psum'd one-hot (psum output
        # is provably replicated, which shard_map's rep check can infer;
        # a bare all_gather here is not)
        d_idx = jax.lax.axis_index("data")
        recv_counts = jax.lax.psum(
            jnp.zeros(n_dev, jnp.int64).at[d_idx].set(llive2.sum()), "data"
        )
        l_out = [c[li] for c in lcols2]
        r_out = [c[ri] for c in rcols2]
        if kind == "left":
            # matched = >= 1 verified pair enumerated for the received row
            # (only trustworthy when overflow == 0 — truncated pair
            # enumeration could miss a row's single match)
            lmatched = jnp.zeros(lh2.shape[0], bool).at[li].max(ok)
            return (
                ok, *l_out, *r_out, llive2, lmatched, *lcols2,
                recv_counts, overflow,
            )
        return (ok, *l_out, *r_out, recv_counts, overflow)

    left_extra = (
        tuple(P("data") for _ in range(2 + n_lcols)) if kind == "left" else ()
    )
    out_specs = (
        (P("data"),)
        + tuple(P("data") for _ in range(n_lcols + n_rcols))
        + left_extra
        + (P(), P())
    )
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            tuple(P("data") for _ in range(2 + n_lkeys + n_lcols)),
            tuple(P("data") for _ in range(2 + n_lkeys + n_rcols)),
        ),
        out_specs=out_specs,
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Distributed sort: range-partitioned samplesort + global rank compaction.
# The scalable ORDER BY for sharded tables — Spark's range-partitioning
# sort-shuffle (reference: spark.sql.shuffle.partitions,
# nds/power_run_cpu.template:20-27) rebuilt on XLA collectives: no device
# ever materializes the whole table.
# ---------------------------------------------------------------------------


def sample_sort(mesh: Mesh, n_keys: int, n_cols: int, cap_route: int,
                n_samples: int = 64):
    """Factory for the mesh samplesort step.

    The returned jitted fn takes (route, live, key..., col...), all sharded on
    the `data` axis, and returns
    (live_out, col_out..., recv_counts [n_dev], overflow):

      * `route` — one comparable value per row, monotone in the most-
        significant sort key (nulls pre-folded to that dtype's extremes);
      * `key...` — the transformed lexsort keys, major->minor, dead rows
        anywhere;
      * rows are range-partitioned by splitters sampled from `route`
        (equal values always colocate, so ties never straddle a device
        boundary), locally lexsorted, then shipped to their global rank
        position with a second all_to_all. The output is globally sorted
        with all live rows first — the Table layout — and no step gathers
        the full table onto one device.

    overflow > 0 means a routing bucket exceeded cap_route (key skew); the
    caller must retry with a doubled cap (cap_route == local rows can never
    overflow). `recv_counts` is the per-device count of live rows received
    in the range-partitioning pass — the skew evidence for the `exchange`
    trace event (splitter sampling keeps it near-balanced except under
    heavy duplicate-key mass).
    """
    n_dev = mesh.devices.size

    def local(route, live, *rest):
        keys = rest[:n_keys]
        cols = rest[n_keys:]
        n = route.shape[0]  # rows per device; also the output block size
        big = (
            jnp.asarray(jnp.inf, route.dtype)
            if jnp.issubdtype(route.dtype, jnp.floating)
            else jnp.asarray(jnp.iinfo(route.dtype).max, route.dtype)
        )
        rm = jnp.where(live, route, big)
        # splitters: every device samples evenly from its sorted live keys,
        # all_gathers the (tiny) sample set, and derives identical quantile
        # splitters — one collective over n_dev*n_samples scalars
        rs = jnp.sort(rm)
        nl = live.sum()
        pos = (jnp.arange(n_samples) * jnp.maximum(nl, 1)) // n_samples
        samp = rs[jnp.clip(pos, 0, n - 1)]
        samp_valid = jnp.full(n_samples, nl > 0)
        all_s = jax.lax.all_gather(samp, "data").reshape(-1)
        all_v = jax.lax.all_gather(samp_valid, "data").reshape(-1)
        ss = jnp.sort(jnp.where(all_v, all_s, big))
        v_total = all_v.sum()
        qpos = (jnp.arange(1, n_dev) * jnp.maximum(v_total, 1)) // n_dev
        splitters = ss[jnp.clip(qpos, 0, ss.shape[0] - 1)]
        dest = jnp.searchsorted(splitters, rm, side="right").astype(jnp.int32)
        rlive, shipped, overflow = _route_by_dest(
            dest, live, n_dev, cap_route, list(keys) + list(cols)
        )
        rkeys = shipped[:n_keys]
        rcols = shipped[n_keys:]
        # local full-key sort: live rows first, then by keys major->minor
        order = jnp.lexsort(tuple(reversed(rkeys)) + (~rlive,))
        live2 = rlive[order]
        cols2 = [c[order] for c in rcols]
        # global rank of each live row = my devices' live-count prefix + local
        # position (live rows are first after the sort)
        nl2 = live2.sum()
        counts = jax.lax.all_gather(nl2, "data")
        d_idx = jax.lax.axis_index("data")
        # skew evidence output: psum'd one-hot (provably replicated under
        # the rep check, unlike the all_gather above)
        recv_counts = jax.lax.psum(
            jnp.zeros(n_dev, jnp.int64).at[d_idx].set(nl2), "data"
        )
        start = jnp.where(jnp.arange(n_dev) < d_idx, counts, 0).sum()
        rank = start + jnp.arange(live2.shape[0], dtype=jnp.int64)
        dest2 = jnp.where(live2, (rank // n).astype(jnp.int32), n_dev)
        pos2 = (rank % n).astype(jnp.int32)

        def scatter2(x, fill):
            buf = jnp.full((n_dev, n), fill, x.dtype)
            buf = buf.at[dest2, pos2].set(x, mode="drop")
            r = jax.lax.all_to_all(buf, "data", 0, 0, tiled=True)
            return r.reshape(n_dev, n)

        # ranks are globally unique, so at most one source placed a row in
        # each output slot: merge across sources by masked sum / any
        placed = scatter2(live2, False)
        outs = []
        for c in cols2:
            buf = scatter2(c, jnp.zeros((), c.dtype))
            if c.dtype == jnp.bool_:
                outs.append(jnp.where(placed, buf, False).any(axis=0))
            else:
                outs.append(
                    jnp.where(placed, buf, jnp.zeros((), c.dtype)).sum(axis=0)
                )
        live_out = placed.any(axis=0)
        return (live_out, *outs, recv_counts, overflow)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(P("data") for _ in range(2 + n_keys + n_cols)),
        out_specs=(P("data"),)
        + tuple(P("data") for _ in range(n_cols))
        + (P(), P()),
    )
    return jax.jit(fn)


_SORT_CACHE = {}


def get_sample_sort(mesh, n_keys, n_cols, cap_route, n_samples=64):
    """Cached factory: one compiled samplesort per signature (see
    get_exchange_hash_join for the topology-keyed cache rationale)."""
    topo = tuple(d.id for d in mesh.devices.flat)
    key = (topo, n_keys, n_cols, cap_route, n_samples)
    if key not in _SORT_CACHE:
        _SORT_CACHE[key] = sample_sort(mesh, n_keys, n_cols, cap_route, n_samples)
    return _SORT_CACHE[key]


_XJOIN_CACHE = {}


def get_exchange_hash_join(mesh, n_lkeys, n_lcols, n_rcols, cap_l, cap_r,
                           pair_cap, kind="inner"):
    """Cached factory: one compiled exchange-join step per signature, so
    repeated joins across a query stream reuse the XLA executable. Keyed by
    the mesh's device topology (not object identity, which a recycled id()
    could alias after GC)."""
    topo = tuple(d.id for d in mesh.devices.flat)
    key = (topo, n_lkeys, n_lcols, n_rcols, cap_l, cap_r, pair_cap, kind)
    if key not in _XJOIN_CACHE:
        _XJOIN_CACHE[key] = exchange_hash_join(
            mesh, n_lkeys, n_lcols, n_rcols, cap_l, cap_r, pair_cap,
            kind=kind,
        )
    return _XJOIN_CACHE[key]
