"""Dense-join and direct-aggregation fast paths vs the sort-based fallback:
both physical strategies must produce identical results (the engine's AQE-ish
plan choice must never change answers)."""

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.engine.exec import Executor
from nds_tpu.engine.session import Session


def _sess(seed=0, dup_keys=False, sparse=False):
    rng = np.random.default_rng(seed)
    n_dim, n_fact = 64, 2048
    keys = np.arange(1, n_dim + 1, dtype=np.int64)
    if sparse:
        keys = keys * 1_000_003  # domain too wide for the dense table
    if dup_keys:
        keys[n_dim // 2 :] = keys[: n_dim // 2]  # non-unique build side
    dim = pa.table(
        {
            "d_sk": keys,
            "d_grp": rng.integers(0, 5, n_dim),
        }
    )
    fact = pa.table(
        {
            "f_sk": rng.choice(keys, n_fact),
            "f_val": rng.integers(0, 1000, n_fact),
        }
    )
    s = Session()
    s.register_arrow("dim", dim)
    s.register_arrow("fact", fact)
    return s


QUERIES = [
    "select d_grp, sum(f_val) s, count(*) c from fact, dim where f_sk = d_sk group by d_grp order by d_grp",
    "select count(*) c from fact where f_sk in (select d_sk from dim where d_grp = 2)",
    "select count(*) c from fact where f_sk not in (select d_sk from dim where d_grp = 2)",
    "select d_grp, count(*) c from fact left join dim on f_sk = d_sk group by d_grp order by d_grp",
]


@pytest.mark.parametrize("variant", ["plain", "dup_keys", "sparse"])
@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_fast_and_fallback_agree(variant, qi, monkeypatch):
    s = _sess(dup_keys=variant == "dup_keys", sparse=variant == "sparse")
    q = QUERIES[qi]
    fast = s.sql(q).collect()
    # force the sort-based paths
    monkeypatch.setattr(Executor, "_DENSE_MAX_DOMAIN", 0)
    monkeypatch.setattr(Executor, "_DIRECT_AGG_MAX_DOMAIN", 0)
    slow = s.sql(q).collect()
    assert fast.num_rows == slow.num_rows
    for col in fast.schema.names:
        assert fast.column(col).to_pylist() == slow.column(col).to_pylist(), (
            variant,
            q,
            col,
        )


def test_direct_agg_null_keys(monkeypatch):
    rng = np.random.default_rng(3)
    n = 512
    vals = rng.integers(0, 50, n)
    grp = np.where(rng.random(n) < 0.2, None, rng.integers(0, 4, n).astype(object))
    t = pa.table({"g": pa.array(grp, type=pa.int64()), "v": vals})
    s = Session()
    s.register_arrow("t", t)
    q = "select g, count(*) c, sum(v) sv, min(v) mn from t group by g order by g"
    fast = s.sql(q).collect()
    monkeypatch.setattr(Executor, "_DIRECT_AGG_MAX_DOMAIN", 0)
    slow = s.sql(q).collect()
    assert fast.to_pylist() == slow.to_pylist()


def test_direct_agg_string_and_bool_keys(monkeypatch):
    rng = np.random.default_rng(4)
    n = 512
    t = pa.table(
        {
            "s": pa.array(rng.choice(["a", "b", None], n)),
            "b": pa.array(rng.random(n) < 0.5),
            "v": rng.integers(0, 50, n),
        }
    )
    s = Session()
    s.register_arrow("t", t)
    q = "select s, b, count(*) c, sum(v) sv from t group by s, b order by s, b"
    fast = s.sql(q).collect()
    monkeypatch.setattr(Executor, "_DIRECT_AGG_MAX_DOMAIN", 0)
    slow = s.sql(q).collect()
    assert fast.to_pylist() == slow.to_pylist()


def test_oom_retry_reloads_all_requested_columns(monkeypatch):
    """A RESOURCE_EXHAUSTED mid-load must drop caches and reload the FULL
    requested column set (not just the missing subset), and surface a
    task-failure event."""
    t = pa.table({"a": np.arange(8, dtype=np.int64), "b": np.arange(8, dtype=np.int64)})
    s = Session()
    s.register_arrow("t", t)
    s.catalog.load("t", ["a"])  # cache column a
    failures = []
    s.register_listener(failures.append)
    from nds_tpu.engine.session import Catalog

    real = Catalog._to_device
    calls = {"n": 0}

    def flaky(self, name, arrow, e, spent=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return real(self, name, arrow, e, spent)

    monkeypatch.setattr(Catalog, "_to_device", flaky)
    out = s.catalog.load("t", ["a", "b"])
    assert set(out.columns) == {"a", "b"}
    assert failures and "device memory exhausted" in failures[0]


def test_negative_keys(monkeypatch):
    t = pa.table(
        {
            "g": np.array([-5, -5, -3, 0, 2, 2, -3], dtype=np.int64),
            "v": np.arange(7, dtype=np.int64),
        }
    )
    s = Session()
    s.register_arrow("t", t)
    q = "select g, sum(v) sv from t group by g order by g"
    fast = s.sql(q).collect()
    monkeypatch.setattr(Executor, "_DIRECT_AGG_MAX_DOMAIN", 0)
    slow = s.sql(q).collect()
    assert fast.to_pylist() == slow.to_pylist()


def test_group_key_words_match_pandas():
    """Multi-key group-bys encode keys into mixed-radix int64 words sorted
    by the canonical kv kernel; the grouping must match an independent
    pandas oracle, nulls and strings included."""
    import pandas as pd
    import pyarrow as pa
    from nds_tpu.engine.session import Session

    rng = np.random.default_rng(11)
    n = 3000
    # `a` spans a huge domain so _try_direct_agg declines and the SORTED
    # grouping path (the word-encoded one) is what runs
    t = pa.table({
        "a": rng.integers(-(2 ** 40), 2 ** 40, n),
        "b": pa.array(np.where(rng.random(n) < 0.1, None,
                               rng.integers(0, 9, n).astype(object))
                      ).cast(pa.int64()),
        "c": pa.array(rng.choice(["x", "y", "z", None], n)),
        "d": rng.integers(1990, 2005, n),
        "e": rng.integers(0, 2, n).astype(bool),
        "v": rng.integers(0, 100, n),
    })
    q = ("select a, b, c, d, e, count(*) cnt, sum(v) s from t "
         "group by a, b, c, d, e order by a, b, c, d, e")
    s = Session()
    s.register_arrow("t", t)
    got = s.sql(q).collect().to_pylist()

    df = t.to_pandas()
    exp = (
        df.groupby(["a", "b", "c", "d", "e"], dropna=False)
        .agg(cnt=("v", "size"), s=("v", "sum"))
        .reset_index()
        .sort_values(["a", "b", "c", "d", "e"], na_position="first")
    )
    expected = [
        {
            "a": int(r.a),
            "b": None if pd.isna(r.b) else int(r.b),
            "c": None if pd.isna(r.c) else r.c,
            "d": int(r.d),
            "e": bool(r.e),
            "cnt": int(r.cnt),
            "s": int(r.s),
        }
        for r in exp.itertuples()
    ]
    assert got == expected
    assert len(got) > 100


def test_sort_key_words_preserve_order():
    """ORDER BY word encoding folds direction and null position into
    monotone codes (floats via the order-preserving bit transform); every
    asc/desc x nulls-first/last combination must order rows identically to
    an independent Python comparator."""
    import pyarrow as pa
    from functools import cmp_to_key
    from nds_tpu.engine.session import Session

    rng = np.random.default_rng(23)
    n = 2500
    t = pa.table({
        "a": rng.integers(-(2 ** 35), 2 ** 35, n),
        "b": pa.array(np.where(rng.random(n) < 0.15, None,
                               rng.integers(0, 7, n).astype(object))
                      ).cast(pa.int64()),
        "s": pa.array(rng.choice(["ab", "cd", "ef", None], n)),
        "f": rng.random(n) * 10,
        "d": rng.integers(0, 4, n),
    })
    # every spec ends in `a` (effectively unique), so each ordering is total
    queries = [
        ("select * from t order by a, b, s, d",
         [("a", 1, 1), ("b", 1, 1), ("s", 1, 1), ("d", 1, 1)]),
        ("select * from t order by b desc, a, d desc, s",
         [("b", 0, 0), ("a", 1, 1), ("d", 0, 0), ("s", 1, 1)]),
        ("select * from t order by b asc nulls last, d desc, a, s desc",
         [("b", 1, 0), ("d", 0, 0), ("a", 1, 1), ("s", 0, 0)]),
        ("select * from t order by d, f desc, b, a",  # float standalone word
         [("d", 1, 1), ("f", 0, 0), ("b", 1, 1), ("a", 1, 1)]),
        ("select * from t order by s desc nulls first, b, d, a",
         [("s", 0, 1), ("b", 1, 1), ("d", 1, 1), ("a", 1, 1)]),
    ]
    s = Session()
    s.register_arrow("t", t)
    rows = t.to_pylist()
    for q, spec in queries:
        got = s.sql(q).collect().to_pylist()

        def cmp(ra, rb):
            for col, asc, nf in spec:
                va, vb = ra[col], rb[col]
                if va is None and vb is None:
                    continue
                if va is None:
                    return -1 if nf else 1
                if vb is None:
                    return 1 if nf else -1
                if va == vb:
                    continue
                lt = va < vb
                return (-1 if lt else 1) if asc else (1 if lt else -1)
            return 0

        expected = sorted(rows, key=cmp_to_key(cmp))
        assert got == expected, q


# One statement a join kind over a dense key, held to a plain Python join of
# the same rows. dim's row 0 (key 10) passes the filter and is probed: the
# lookup table tells "row 0" from "no row". fact has null keys, keys below
# dim's lowest and above its highest, and keys of filtered-out dim rows.
_DIM_KEYS = list(range(10, 74))
_DIM_VALS = [(7 * k) % 100 for k in _DIM_KEYS]  # key 10: 70, kept
_FACT_KEYS = [10, None, 3, 9, 74, 500] + [
    (11 * i) % 70 + 8 for i in range(250)
]
_LIVE = {k: v for k, v in zip(_DIM_KEYS, _DIM_VALS) if v >= 30}
_FACT = list(enumerate(_FACT_KEYS))
DENSE_KINDS = {
    "inner": (
        "select f_id, d_val from fact, (select * from dim where d_val >= 30) d"
        " where f_sk = d_sk order by f_id",
        [(i, _LIVE[k]) for i, k in _FACT if k in _LIVE],
    ),
    "left": (
        "select f_id, d_val from fact left join"
        " (select * from dim where d_val >= 30) d on f_sk = d_sk order by f_id",
        [(i, _LIVE.get(k)) for i, k in _FACT],
    ),
    "semi": (
        "select f_id from fact where f_sk in"
        " (select d_sk from dim where d_val >= 30) order by f_id",
        [(i,) for i, k in _FACT if k in _LIVE],
    ),
    "anti": (
        "select f_id from fact where not exists"
        " (select 1 from dim where d_sk = f_sk and d_val >= 30) order by f_id",
        [(i,) for i, k in _FACT if k not in _LIVE],
    ),
    "mark": (
        "select f_id from fact where f_id < 3 or exists"
        " (select 1 from dim where d_sk = f_sk and d_val >= 30) order by f_id",
        [(i,) for i, k in _FACT if i < 3 or k in _LIVE],
    ),
}


@pytest.mark.parametrize("kind", sorted(DENSE_KINDS))
def test_dense_join_kinds_against_a_python_join(kind, monkeypatch):
    s = Session()
    s.register_arrow("dim", pa.table({
        "d_sk": pa.array(_DIM_KEYS, pa.int32()),
        "d_val": pa.array(_DIM_VALS, pa.int64()),
    }))
    s.register_arrow("fact", pa.table({
        "f_id": pa.array(range(len(_FACT_KEYS)), pa.int64()),
        "f_sk": pa.array(_FACT_KEYS, pa.int32()),
    }))
    took = []
    dense = Executor._try_dense_join

    def recording(self, left, right, join_kind, *a, **kw):
        out = dense(self, left, right, join_kind, *a, **kw)
        took.append((join_kind, out is not None))
        return out

    monkeypatch.setattr(Executor, "_try_dense_join", recording)
    sql, want = DENSE_KINDS[kind]
    assert 10 in _LIVE and _FACT_KEYS[0] == 10  # dim's row 0 is probed
    got = s.sql(sql).collect()
    assert took == [(kind, True)]
    assert [tuple(r.values()) for r in got.to_pylist()] == want
