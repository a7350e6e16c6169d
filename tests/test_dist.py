"""Distributed primitive tests on the virtual 8-device CPU mesh.

Covers the two mesh patterns the engine uses (reference analogue: Spark
executor data parallelism + shuffle, nds/base.template:28-31):
  * sharded star-query step (partial agg + psum) vs single-device oracle
  * hash-partition exchange routing + overflow detection
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from nds_tpu.parallel.dist import (
    fused_query_step,
    make_mesh,
    partition_exchange,
    sharded_query_step,
)

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N_DEV
    return make_mesh(N_DEV)


def test_sharded_star_agg_matches_oracle(mesh):
    rng = np.random.default_rng(7)
    n, n_dates, n_items, n_groups = 128 * N_DEV, 64, 32, 8
    shard = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    fd = jax.device_put(jnp.asarray(rng.integers(0, n_dates, n), jnp.int32), shard)
    fi = jax.device_put(jnp.asarray(rng.integers(0, n_items, n), jnp.int32), shard)
    fm = jax.device_put(jnp.asarray(rng.integers(0, 1000, n), jnp.int64), shard)
    fv = jax.device_put(jnp.asarray(rng.random(n) < 0.9), shard)
    ddf = jax.device_put(jnp.asarray(rng.random(n_dates) < 0.5), repl)
    dig = jax.device_put(jnp.asarray(rng.integers(-1, n_groups, n_items), jnp.int32), repl)

    step = sharded_query_step(mesh, n_groups)
    sums, counts = jax.block_until_ready(step(fd, fi, fm, fv, ddf, dig))
    ref_s, ref_c = fused_query_step(
        np.asarray(fd), np.asarray(fi), np.asarray(fm), np.asarray(fv),
        np.asarray(ddf), np.asarray(dig), n_groups=n_groups,
    )
    np.testing.assert_array_equal(np.asarray(sums), np.asarray(ref_s))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref_c))


def test_partition_exchange_routes_keys(mesh):
    rng = np.random.default_rng(3)
    n, cap = 64 * N_DEV, 64
    shard = NamedSharding(mesh, P("data"))
    keys = jax.device_put(jnp.asarray(rng.integers(0, 1000, n), jnp.int64), shard)
    vals = jax.device_put(jnp.asarray(rng.integers(0, 100, n), jnp.int64), shard)
    live = jax.device_put(jnp.asarray(rng.random(n) < 0.8), shard)

    ex = partition_exchange(mesh, cap)
    rk, rv, dropped = jax.block_until_ready(ex(keys, vals, live))
    assert int(dropped) == 0
    rk_np = np.asarray(rk).reshape(N_DEV, -1)
    for d in range(N_DEV):
        got = rk_np[d][rk_np[d] >= 0]
        assert (got % N_DEV == d).all()
    # conservation: every live key arrives exactly once
    sent = np.sort(np.asarray(keys)[np.asarray(live)])
    recvd = np.sort(np.asarray(rk)[np.asarray(rk) >= 0])
    np.testing.assert_array_equal(sent, recvd)
    # values ride with their keys
    rv_np = np.asarray(rv)
    kv = {}
    k_host, v_host, l_host = np.asarray(keys), np.asarray(vals), np.asarray(live)
    for k, v, l in zip(k_host, v_host, l_host):
        if l:
            kv.setdefault(k, []).append(v)
    got_kv = {}
    for k, v in zip(np.asarray(rk), rv_np):
        if k >= 0:
            got_kv.setdefault(k, []).append(v)
    assert {k: sorted(v) for k, v in kv.items()} == {
        k: sorted(v) for k, v in got_kv.items()
    }


def test_partition_exchange_detects_overflow(mesh):
    # all keys hash to device 0 -> bucket 0 needs n rows but cap is tiny
    n, cap = 16 * N_DEV, 2
    shard = NamedSharding(mesh, P("data"))
    keys = jax.device_put(jnp.zeros(n, jnp.int64) + 8, shard)  # 8 % 8 == 0
    vals = jax.device_put(jnp.arange(n, dtype=jnp.int64), shard)
    live = jax.device_put(jnp.ones(n, bool), shard)
    ex = partition_exchange(mesh, cap)
    _, _, dropped = jax.block_until_ready(ex(keys, vals, live))
    assert int(dropped) == n - cap * N_DEV


def test_sample_sort_global_order(mesh):
    from nds_tpu.parallel.dist import sample_sort

    rng = np.random.default_rng(9)
    n = 256 * N_DEV
    shard = NamedSharding(mesh, P("data"))
    keys = jax.device_put(
        jnp.asarray(rng.integers(-1000, 1000, n), jnp.int64), shard)
    vals = jax.device_put(jnp.arange(n, dtype=jnp.int64), shard)
    live = jax.device_put(jnp.asarray(rng.random(n) < 0.9), shard)

    fn = sample_sort(mesh, n_keys=1, n_cols=2, cap_route=64)
    live_out, k_out, v_out, counts, ov = jax.block_until_ready(
        fn(keys, live, keys, keys, vals))
    assert int(ov) == 0
    # skew evidence: per-device received counts cover every live row
    assert int(np.asarray(counts).sum()) == int(np.asarray(live).sum())
    k_host, v_host, l_host = (np.asarray(x) for x in (keys, vals, live))
    L = int(l_host.sum())
    lo, ko, vo = (np.asarray(x) for x in (live_out, k_out, v_out))
    # live rows first (the Table layout), globally sorted
    assert lo[:L].all() and not lo[L:].any()
    np.testing.assert_array_equal(ko[:L], np.sort(k_host[l_host]))
    # payload rides with its row
    got = sorted(zip(ko[:L].tolist(), vo[:L].tolist()))
    want = sorted(zip(k_host[l_host].tolist(), v_host[l_host].tolist()))
    assert got == want


def test_sample_sort_skew_overflow_and_max_cap(mesh):
    from nds_tpu.parallel.dist import sample_sort

    rng = np.random.default_rng(10)
    n = 256 * N_DEV
    local = n // N_DEV
    shard = NamedSharding(mesh, P("data"))
    # 95% of rows share one key: every one of them must land on one device
    raw = np.where(rng.random(n) < 0.95, 7, rng.integers(-500, 500, n))
    keys = jax.device_put(jnp.asarray(raw, jnp.int64), shard)
    live = jax.device_put(jnp.ones(n, bool), shard)

    small = sample_sort(mesh, n_keys=1, n_cols=1, cap_route=8)
    *_, ov = jax.block_until_ready(small(keys, live, keys, keys))
    assert int(ov) > 0  # skew detected, caller must retry

    big = sample_sort(mesh, n_keys=1, n_cols=1, cap_route=local)
    live_out, k_out, counts, ov = jax.block_until_ready(
        big(keys, live, keys, keys))
    assert int(ov) == 0  # cap == local rows can never overflow
    np.testing.assert_array_equal(np.asarray(k_out)[: n], np.sort(raw))
    # the hot key's rows all land on one device: skew is visible in the
    # received counts (max well above the balanced share)
    c = np.asarray(counts)
    assert c.max() > 2 * c.sum() / len(c)


def test_compact_indices_sharded_matches_replicated(mesh):
    """Regression (caught by the SF0.01 mesh gate on query77/query83):
    jax 0.4.37's SPMD partitioner mislowers the blocked-cumsum + scatter
    compaction over a row-sharded mask — cross-shard scatter writes drop
    and compaction silently truncates. Sharded masks must route through
    the sort-based variant and agree with the single-device kernel
    exactly (indices AND zero padding)."""
    from nds_tpu.ops import kernels as K

    shard = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(12)
    for n in (1024, 8192):
        for frac in (0.0, 0.3, 1.0):
            mask_np = rng.random(n) < frac
            mask_s = jax.device_put(jnp.asarray(mask_np), shard)
            mask_r = jnp.asarray(mask_np)
            for cap in (n // 2, n, 2 * n):
                a = np.asarray(K.compact_indices(mask_s, cap))
                b = np.asarray(K.compact_indices(mask_r, cap))
                np.testing.assert_array_equal(a, b, err_msg=str((n, frac, cap)))


@pytest.mark.parametrize("op", ["sum", "count", "min", "max"])
@pytest.mark.parametrize("dtype", ["int64", "int32", "float64"])
def test_one_run_reduces_row_sharded_rows_as_replicated_ones(mesh, op, dtype):
    """A global aggregate has no ids: `segment_reduce(vals, None, ...)` is
    a masked reduce, which over a row-sharded column lowers to partials
    and one all-reduce and answers what the replicated column answers
    (and, cell for cell, what the scatter into cell 0 does)."""
    from nds_tpu.ops import kernels as K

    shard = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(44)
    n = 1024 * N_DEV
    vals_np = (rng.normal(size=n) * 1e3).astype(dtype)
    w_np = (np.arange(n) < n - 300) & (rng.random(n) < 0.8)
    vals_s = jax.device_put(jnp.asarray(vals_np), shard)
    w_s = jax.device_put(jnp.asarray(w_np), shard)
    got = K.segment_reduce_with_count(vals_s, None, w_s, 1024, op)
    rep = K.segment_reduce_with_count(
        jnp.asarray(vals_np), None, jnp.asarray(w_np), 1024, op)
    old = K.segment_reduce_with_count(
        jnp.asarray(vals_np), jnp.zeros(n, jnp.int32), jnp.asarray(w_np),
        1024, op)
    for g, r, o in zip(got, rep, old):
        assert g.dtype == r.dtype == o.dtype and g.shape == (1024,)
        if dtype == "float64" and op == "sum":
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-12)
            np.testing.assert_allclose(np.asarray(g), np.asarray(o),
                                       rtol=1e-12)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
            np.testing.assert_array_equal(np.asarray(g), np.asarray(o))


def test_sorted_runs_over_a_mesh_keep_the_scatter(mesh):
    """Sharded ids get no run bounds (as a sharded mask gets no block
    select), so the sort route's reductions stay on the scatter there."""
    from nds_tpu.ops import kernels as K

    shard = NamedSharding(mesh, P("data"))
    n = 1024 * N_DEV
    gid_np = (np.arange(n) // 700).astype(np.int32)
    live = jnp.arange(n) < n
    assert K.run_bounds(jax.device_put(jnp.asarray(gid_np), shard),
                        jax.device_put(live, shard), 1024, 12) is None
    starts, ends = K.run_bounds(jnp.asarray(gid_np), live, 1024, 12)
    np.testing.assert_array_equal(np.asarray(starts)[:12],
                                  np.arange(12) * 700)
    np.testing.assert_array_equal(np.asarray(ends)[:11],
                                  np.arange(1, 12) * 700)
    assert int(ends[11]) == n and int(starts[12]) == int(ends[12]) == n


def test_multihost_single_process_degenerates(mesh):
    """multihost utilities: in a 1-process world initialize() is a no-op,
    global_mesh covers the local devices, and shard_rows_across_hosts is a
    plain row-sharded device_put (the DCN path needs a real pod)."""
    from nds_tpu.parallel import multihost

    multihost.initialize()  # no cluster env: must not raise
    m = multihost.global_mesh()
    assert m.devices.size == len(jax.devices())
    rows = np.arange(16 * N_DEV, dtype=np.int64)
    arr = multihost.shard_rows_across_hosts(mesh, rows)
    assert arr.shape == rows.shape
    np.testing.assert_array_equal(np.asarray(arr), rows)
    # actually sharded: each device holds 1/N of the rows
    assert len(arr.sharding.device_set) == N_DEV


def test_make_mesh_refuses_more_devices_than_there_are():
    """A mesh of fewer devices than were asked for is a different
    deployment, not a smaller one of the same."""
    with pytest.raises(ValueError, match="99-device mesh"):
        make_mesh(99)
