"""Pallas kernel tests (interpret mode — runs the real kernel logic on the
CPU mesh). What the chip's compiler makes of the same kernels is
tests/test_chip_compile.py; whether they run right and fast on the chip
is an on-chip A/B with engine.pallas_*=on (ROADMAP C2)."""

import jax.numpy as jnp
import numpy as np
import pytest

from nds_tpu.ops.pallas_kernels import (
    dense_build_pallas,
    segment_extreme_pallas,
    segment_sums,
    segment_sums_pallas,
)


def _oracle(vals, gid, n_groups):
    sums = np.zeros(n_groups, np.float64)
    counts = np.zeros(n_groups, np.float64)
    for v, g in zip(vals, gid):
        if g >= 0:
            sums[g] += v
            counts[g] += 1
    return sums, counts


@pytest.mark.parametrize(
    "n,n_groups",
    [
        (1000, 10),       # row padding, tiny group count
        (4096, 300),      # multiple row tiles, group padding
        (2048, 700),      # multiple group tiles
        (100, 1),         # single group
        (40000, 600),     # several (8, 2048) row blocks x two group tiles
    ],
)
def test_segment_sums_pallas_matches_oracle(n, n_groups):
    rng = np.random.default_rng(n + n_groups)
    vals = rng.integers(0, 1000, n).astype(np.float32)  # exact in f32
    gid = rng.integers(-1, n_groups, n).astype(np.int32)  # -1 = dead
    sums, counts = segment_sums_pallas(
        jnp.asarray(vals), jnp.asarray(gid), n_groups, interpret=True
    )
    ref_s, ref_c = _oracle(vals, gid, n_groups)
    np.testing.assert_allclose(np.asarray(sums), ref_s, rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(counts), ref_c)


def test_segment_sums_dispatcher_cpu_path():
    rng = np.random.default_rng(0)
    n, g = 5000, 37
    vals = rng.random(n).astype(np.float32)
    gid = rng.integers(-1, g, n).astype(np.int32)
    sums, counts = segment_sums(jnp.asarray(vals), jnp.asarray(gid), g)
    ref_s, ref_c = _oracle(vals, gid, g)
    np.testing.assert_allclose(np.asarray(sums), ref_s, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(counts), ref_c)


def test_segment_sums_all_dead_rows():
    gid = jnp.full(256, -1, jnp.int32)
    vals = jnp.ones(256, jnp.float32)
    sums, counts = segment_sums_pallas(vals, gid, 8, interpret=True)
    assert float(sums.sum()) == 0.0 and float(counts.sum()) == 0.0


def _extreme_oracle(vals, gid, n_groups, is_max):
    ext = np.full(n_groups, -np.inf if is_max else np.inf, np.float64)
    counts = np.zeros(n_groups, np.float64)
    for v, g in zip(vals, gid):
        if g >= 0:
            ext[g] = max(ext[g], v) if is_max else min(ext[g], v)
            counts[g] += 1
    return ext, counts


@pytest.mark.parametrize("is_max", [False, True])
@pytest.mark.parametrize(
    "n,n_groups",
    [
        (1000, 10),       # row padding, tiny group count
        (4096, 300),      # multiple row tiles, group padding
        (2048, 700),      # multiple group tiles
        (100, 1),         # single group
        (40000, 600),     # several (8, 2048) row blocks x two group tiles
    ],
)
def test_segment_extreme_pallas_matches_oracle(n, n_groups, is_max):
    rng = np.random.default_rng(n + n_groups + is_max)
    vals = rng.integers(-500, 500, n).astype(np.float32)  # exact in f32
    gid = rng.integers(-1, n_groups, n).astype(np.int32)  # -1 = dead
    ext, counts = segment_extreme_pallas(
        jnp.asarray(vals), jnp.asarray(gid), n_groups, is_max,
        interpret=True,
    )
    ref_e, ref_c = _extreme_oracle(vals, gid, n_groups, is_max)
    np.testing.assert_array_equal(np.asarray(counts), ref_c)
    # empty groups hold the ±inf identity; callers mask via count
    live = ref_c > 0
    np.testing.assert_allclose(
        np.asarray(ext)[live], ref_e[live], rtol=0, atol=0
    )
    assert np.all(np.isinf(np.asarray(ext)[~live]))


def test_segment_extreme_all_dead_rows():
    gid = jnp.full(256, -1, jnp.int32)
    vals = jnp.ones(256, jnp.float32)
    ext, counts = segment_extreme_pallas(vals, gid, 8, True, interpret=True)
    assert float(counts.sum()) == 0.0
    assert bool(jnp.all(jnp.isinf(ext)))
    # n == 0 short-circuit
    ext0, cnt0 = segment_extreme_pallas(
        jnp.zeros(0, jnp.float32), jnp.zeros(0, jnp.int32), 4, False,
        interpret=True,
    )
    assert ext0.shape == (4,) and float(cnt0.sum()) == 0.0


@pytest.mark.parametrize(
    "n,table_cap",
    [(500, 128), (4096, 1024), (100, 2048), (0, 256), (40000, 8192)],
)
def test_dense_build_pallas_matches_jnp(n, table_cap):
    from nds_tpu.ops import kernels as K

    rng = np.random.default_rng(n + table_cap)
    rmin = 10
    # unique keys (the dense path's caller contract), some out of range
    keys = rng.permutation(6 * max(table_cap, 64))[:n].astype(np.int64) + rmin - 8
    live = rng.random(n) > 0.2
    rowid1_j = K.dense_build(
        jnp.asarray(keys), jnp.asarray(live), rmin, table_cap
    )
    rowid1_p = dense_build_pallas(
        jnp.asarray(keys), jnp.asarray(live), rmin, table_cap,
        interpret=True,
    )
    # one table of row + 1, 0 where the key has no live row
    assert rowid1_p.dtype == rowid1_j.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(rowid1_j), np.asarray(rowid1_p))
    in_range = live & (keys >= rmin) & (keys < rmin + table_cap)
    assert int((np.asarray(rowid1_j) > 0).sum()) == int(in_range.sum())


def test_pallas_join_wired_through_sql():
    """engine.pallas_join=on routes the dense-join build table through the
    Pallas tile kernel (interpret mode off-TPU) with EXACT results; auto
    mode memoizes a measured verdict."""
    import pyarrow as pa
    from nds_tpu.engine.session import Session

    rng = np.random.default_rng(9)
    n = 3000
    dim = pa.table({
        "dk": pa.array(range(200), pa.int32()),
        "dv": pa.array([int(x) for x in rng.integers(0, 50, 200)],
                       pa.int64()),
    })
    fact = pa.table({
        "fk": pa.array([int(x) for x in rng.integers(0, 200, n)],
                       pa.int32()),
        "m": pa.array([int(x) for x in rng.integers(0, 1000, n)],
                      pa.int64()),
    })
    plain = Session()
    pj_on = Session(conf={"engine.pallas_join": "on"})
    pj_auto = Session(conf={"engine.pallas_join": "auto"})
    for s in (plain, pj_on, pj_auto):
        s.register_arrow("dim", dim)
        s.register_arrow("fact", fact)
    q = ("select d.dv, sum(f.m) s from fact f, dim d where f.fk = d.dk "
         "group by d.dv order by d.dv")
    expect = plain.sql(q).collect()
    assert pj_on.sql(q).collect().equals(expect)
    assert pj_auto.sql(q).collect().equals(expect)
    dense_keys = [
        k for k in pj_auto.pallas_promotions if k[0] == "dense_build"
    ]
    assert dense_keys, "auto mode never reached the dense-join A/B"


def test_pallas_agg_wired_through_sql():
    """engine.pallas_agg=on routes float SUMs through the kernel (interpret
    mode off-TPU) and matches the exact path within float32 tolerance."""
    import pyarrow as pa
    from nds_tpu.engine.session import Session

    rng = np.random.default_rng(4)
    n = 4096
    t = pa.table({
        "k": rng.integers(0, 20, n),
        "v": (rng.random(n) * 100).astype(np.float64),
    })
    exact = Session()
    fast = Session(conf={"engine.pallas_agg": "on"})
    for s in (exact, fast):
        s.register_arrow("t", t)
    q = "select k, sum(v) s, count(*) c from t group by k order by k"
    a = exact.sql(q).collect().to_pylist()
    b = fast.sql(q).collect().to_pylist()
    assert len(a) == len(b) == 20
    for ra, rb in zip(a, b):
        assert ra["k"] == rb["k"] and ra["c"] == rb["c"]
        assert abs(ra["s"] - rb["s"]) / max(abs(ra["s"]), 1) < 1e-5
    # min/max now route through the VPU tile kernel under the same knob
    q2 = "select k, min(v) mn, max(v) mx from t group by k order by k"
    a2 = exact.sql(q2).collect().to_pylist()
    b2 = fast.sql(q2).collect().to_pylist()
    assert len(a2) == len(b2) == 20
    for ra, rb in zip(a2, b2):
        assert ra["k"] == rb["k"]
        assert abs(ra["mn"] - rb["mn"]) / max(abs(ra["mn"]), 1) < 1e-5
        assert abs(ra["mx"] - rb["mx"]) / max(abs(ra["mx"]), 1) < 1e-5


@pytest.mark.parametrize(
    "n,dom",
    [
        (1, 4),         # single row
        (700, 1),       # constant key (all-equal: stability visible)
        (1000, 129),    # domain padding
        (4096, 2000),   # multiple row tiles, near the domain cap
        (9000, 300),    # several (8, 256) row blocks: the carried histogram
    ],
)
def test_sort_perm_pallas_matches_canonical_kernel(n, dom):
    """The counting-sort permutation must be IDENTICAL to the canonical
    stable kv-sort kernel — both are stable ascending, so the whole
    permutation (tie order included) must agree element for element."""
    from nds_tpu.ops.kernels import kv_sort_perm
    from nds_tpu.ops.pallas_kernels import sort_perm_pallas

    rng = np.random.default_rng(n + dom)
    w = jnp.asarray(rng.integers(0, dom, n).astype(np.int64))
    ref = kv_sort_perm(w)
    got = sort_perm_pallas(w, dom, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_pallas_sort_wired_through_sql():
    """engine.pallas_sort=on/auto routes eligible single-word ORDER BYs
    through the counting sort with IDENTICAL rows; ineligible shapes
    (multi-word keys, wide spans) fall back to the canonical kernel."""
    import pyarrow as pa
    from nds_tpu.engine.session import Session

    rng = np.random.default_rng(11)
    n = 3000
    t = pa.table({
        "k": pa.array([int(x) for x in rng.integers(0, 12, n)], pa.int32()),
        "v": pa.array([int(x) for x in rng.integers(-90, 90, n)],
                      pa.int64()),
        "wide": pa.array([int(x) for x in rng.integers(0, 1 << 40, n)],
                         pa.int64()),
    })
    plain = Session()
    ps_on = Session(conf={"engine.pallas_sort": "on"})
    ps_auto = Session(conf={"engine.pallas_sort": "auto"})
    for s in (plain, ps_on, ps_auto):
        s.register_arrow("t", t)
    # eligible: one small-span key (ties keep arrival order via the
    # stable contract, so full-row equality is meaningful)
    q = "select k, v from t where v > 0 order by k"
    expect = plain.sql(q).collect()
    assert ps_on.sql(q).collect().equals(expect)
    assert ps_auto.sql(q).collect().equals(expect)
    assert any(
        k[0] == "sort_perm" for k in ps_auto.pallas_promotions
    ), "auto mode never reached the sort A/B"
    # ineligible shapes still produce identical results via the fallback
    for q2 in (
        "select k, v from t order by k, v",        # multi-field word
        "select wide from t order by wide",        # span >> counting cap
    ):
        assert ps_on.sql(q2).collect().equals(plain.sql(q2).collect())
