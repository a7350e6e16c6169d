"""Unit tests for the device kernel library against numpy oracles.

This exceeds the reference's test strategy on purpose (SURVEY.md §4: the
reference has no unit tests; we unit-test every kernel)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nds_tpu.engine.columnar import bucket_cap
from nds_tpu.ops import kernels as K

rng = np.random.default_rng(42)


def _pad(a, cap, fill=0):
    return np.concatenate([a, np.full(cap - len(a), fill, a.dtype)])


def _live(n, cap):
    return jnp.arange(cap) < n


class TestCompact:
    def test_compact(self):
        n, cap = 1000, 1024
        mask = rng.random(cap) < 0.3
        mask[n:] = False
        count = K.mask_count(jnp.asarray(mask))
        assert count == mask.sum()
        idx = K.compact_indices(jnp.asarray(mask), bucket_cap(count))
        np.testing.assert_array_equal(
            np.asarray(idx)[:count], np.nonzero(mask)[0]
        )


def _mask_of(kind, n, out_cap, seed):
    """A mask of n rows of one kind; of the kinds that name no share, never
    more than out_cap rows live."""
    mask = np.zeros(n, bool)
    local = np.random.default_rng(seed)
    if kind == "one row":
        mask[n // 3] = True
    elif kind == "last row alone":
        mask[-1] = True
    elif kind == "whole blocks full":
        mask[K._SELECT_BLOCK:3 * K._SELECT_BLOCK] = True
        mask[-K._SELECT_BLOCK:] = True
    elif kind == "exactly out_cap":
        mask[local.choice(n, out_cap, replace=False)] = True
    elif kind == "a power of two and one":
        mask[local.choice(n, out_cap // 2 + 1, replace=False)] = True
    elif kind != "empty":
        mask = local.random(n) < float(kind)
    return mask


class TestCompactSelect:
    """The sparse route of `compact_indices` (block select, PERF.md Findings
    PR 40) against the form it stands in for, entry for entry."""

    KINDS = ("empty", "one row", "last row alone", "whole blocks full",
             "exactly out_cap", "a power of two and one",
             "0.014", "0.12", "0.5")

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("n", [4_096, 65_536, 524_288, 4_194_304])
    def test_it_answers_what_the_scatter_answers(self, n, kind):
        out_cap = max(n // 64, 3 * K._SELECT_BLOCK)
        mask_h = _mask_of(kind, n, out_cap, seed=n % 1_000 + len(kind))
        if kind in ("0.014", "0.12", "0.5"):
            out_cap = bucket_cap(int(mask_h.sum()))  # as the engine sizes it
        assert mask_h.sum() <= out_cap <= n
        mask = jnp.asarray(mask_h)
        got = np.asarray(K._compact_select(mask, out_cap))
        want = np.asarray(K._compact_whole(mask, out_cap))
        live = np.flatnonzero(mask_h)
        np.testing.assert_array_equal(want[: live.size], live)
        assert not want[live.size:].any()
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)

    def test_more_live_rows_than_out_cap_keeps_the_first(self):
        mask = jnp.asarray(np.arange(8_192) % 3 == 0)
        np.testing.assert_array_equal(
            np.asarray(K._compact_select(mask, 1_024)),
            np.asarray(K._compact_whole(mask, 1_024)),
        )

    @staticmethod
    def _row_ops(jaxpr, found):
        """(primitive, rows) of every gather and scatter under `jaxpr`:
        a gather's output rows, a scatter's updates."""
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "gather":
                found.append((name, eqn.outvars[0].aval.shape[0]))
            elif name.startswith("scatter"):
                found.append((name, eqn.invars[2].aval.shape[0]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                TestCompactSelect._row_ops(sub, found)
        return found

    def test_nothing_of_n_rows_is_scattered_or_gathered(self):
        """The cost model, held by the programs' jaxprs: the n-sized phase
        streams; what costs by the row is one max-scatter of the block
        starts and ONE gather of `out_cap` rows. A scatter or gather of n
        rows again fails here, not on the chip."""
        n, out_cap = 4_194_304, 65_536
        mask = jax.ShapeDtypeStruct((n,), jnp.bool_)
        blocks = jax.make_jaxpr(K._select_blocks)(mask)
        assert self._row_ops(blocks.jaxpr, []) == []
        rows = jax.make_jaxpr(
            lambda r, o, t: K._select_rows(r, o, t, out_cap)
        )(*blocks.out_avals)
        assert sorted(self._row_ops(rows.jaxpr, [])) == [
            ("gather", out_cap), ("scatter-max", n // K._SELECT_BLOCK)
        ]
        whole = jax.make_jaxpr(K._compact_full)(mask)
        assert ("scatter", n) in self._row_ops(whole.jaxpr, [])

    @staticmethod
    def _launched(mask, out_cap):
        from nds_tpu.obs import tally as T
        from nds_tpu.obs.trace import Tracer

        tl = T.Tally(Tracer(), 1)
        with T.bind(tl):
            idx = K.compact_indices(mask, out_cap)
        return tl.launches, np.asarray(idx)

    @pytest.mark.parametrize("n,out_cap,form", [
        (K._SELECT_MIN_ROWS, K._SELECT_MIN_ROWS // K._SELECT_CROSSOVER,
         "compact_select"),
        (4 * K._SELECT_MIN_ROWS, 1_024, "compact_select"),
        # a dense mask, a small one, out_cap >= n: the form they had
        (K._SELECT_MIN_ROWS, 2 * K._SELECT_MIN_ROWS // K._SELECT_CROSSOVER,
         "compact_indices"),
        (K._SELECT_MIN_ROWS // 2, 1_024, "compact_indices"),
        (K._SELECT_MIN_ROWS, K._SELECT_MIN_ROWS, "compact_indices"),
        (K._SELECT_MIN_ROWS, 2 * K._SELECT_MIN_ROWS, "compact_indices"),
        (K._SELECT_MIN_ROWS + 8, 1_024, "compact_indices"),
    ])
    def test_the_two_shapes_choose_the_form(self, n, out_cap, form):
        mask_h = np.arange(n) % 1_031 == 5
        before = (K._select_blocks._cache_size(), K._compact_full._cache_size())
        launches, idx = self._launched(jnp.asarray(mask_h), out_cap)
        assert launches == {form: 1}
        grew = (K._select_blocks._cache_size() - before[0],
                K._compact_full._cache_size() - before[1])
        assert grew[form == "compact_select"] == 0
        live = np.flatnonzero(mask_h)[:out_cap]
        np.testing.assert_array_equal(idx[: live.size], live)
        assert idx.shape == (out_cap,) and not idx[live.size:].any()

    def test_a_sharded_mask_keeps_the_sorted_route(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        n = K._SELECT_MIN_ROWS
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        mask_h = np.arange(n) % 257 == 0
        mask = jax.device_put(
            jnp.asarray(mask_h), NamedSharding(mesh, PartitionSpec("data"))
        )
        before = K._select_blocks._cache_size()
        launches, idx = self._launched(mask, 2_048)
        assert launches == {"compact_indices": 1}
        assert K._select_blocks._cache_size() == before
        live = np.flatnonzero(mask_h)
        np.testing.assert_array_equal(idx[: live.size], live)


class TestSort:
    def test_single_key_asc(self):
        n, cap = 900, 1024
        data = rng.integers(0, 100, cap).astype(np.int64)
        order = K.sort_indices(
            [(jnp.asarray(data), None, True, True)], _live(n, cap)
        )
        got = data[np.asarray(order)[:n]]
        np.testing.assert_array_equal(got, np.sort(data[:n]))

    def test_desc_and_nulls(self):
        n, cap = 500, 512
        data = rng.integers(0, 50, cap).astype(np.int64)
        valid = rng.random(cap) < 0.8
        order = K.sort_indices(
            [(jnp.asarray(data), jnp.asarray(valid), False, False)],
            _live(n, cap),
        )
        o = np.asarray(order)[:n]
        vals, vs = data[o], valid[o]
        # all invalids at the end (nulls last), values descending before that
        k = vs.sum()
        assert (~vs[k:]).all()
        assert (np.diff(vals[:k]) <= 0).all()

    def test_multi_key_stability(self):
        n = cap = 1024
        k1 = rng.integers(0, 4, cap).astype(np.int64)
        k2 = rng.integers(0, 1000, cap).astype(np.int64)
        order = np.asarray(
            K.sort_indices(
                [
                    (jnp.asarray(k1), None, True, True),
                    (jnp.asarray(k2), None, False, True),
                ],
                _live(n, cap),
            )
        )
        expect = np.lexsort((-k2, k1))
        np.testing.assert_array_equal(k1[order], k1[expect])
        np.testing.assert_array_equal(k2[order], k2[expect])


class TestGroup:
    def test_group_and_sum(self):
        n, cap = 3000, 4096
        keys = rng.integers(0, 37, cap).astype(np.int64)
        vals = rng.integers(0, 1000, cap).astype(np.int64)
        live = _live(n, cap)
        order, gid, ng = K.group_rows([jnp.asarray(keys)], [None], live)
        assert ng == len(np.unique(keys[:n]))
        o = np.asarray(order)
        sums = K.segment_reduce(
            jnp.asarray(vals)[order],
            gid,
            live[order],
            bucket_cap(ng),
            "sum",
        )
        expect = {k: vals[:n][keys[:n] == k].sum() for k in np.unique(keys[:n])}
        got_keys = keys[o[:n]][np.unique(np.asarray(gid)[:n], return_index=True)[1]]
        for g, k in enumerate(sorted(expect)):
            assert int(np.asarray(sums)[g]) == expect[k], (g, k)

    def test_group_nulls_form_one_group(self):
        n = cap = 1024
        keys = rng.integers(0, 5, cap).astype(np.int64)
        valid = rng.random(cap) < 0.7
        order, gid, ng = K.group_rows(
            [jnp.asarray(keys)], [jnp.asarray(valid)], _live(n, cap)
        )
        n_distinct = len(np.unique(keys[valid])) + (1 if (~valid).any() else 0)
        assert ng == n_distinct

    def test_min_max_count(self):
        n = cap = 2048
        keys = rng.integers(0, 10, cap).astype(np.int64)
        vals = rng.normal(size=cap)
        live = _live(n, cap)
        order, gid, ng = K.group_rows([jnp.asarray(keys)], [None], live)
        svals = jnp.asarray(vals)[order]
        w = live[order]
        mins = np.asarray(K.segment_reduce(svals, gid, w, bucket_cap(ng), "min"))
        maxs = np.asarray(K.segment_reduce(svals, gid, w, bucket_cap(ng), "max"))
        counts = np.asarray(K.segment_reduce(svals, gid, w, bucket_cap(ng), "count"))
        o = np.asarray(order)
        for g in range(ng):
            k = keys[o[np.asarray(gid)[:n] == g][0]]
            sel = vals[:n][keys[:n] == k]
            assert mins[g] == pytest.approx(sel.min())
            assert maxs[g] == pytest.approx(sel.max())
            assert counts[g] == len(sel)


class TestRunReduce:
    """A segment reduction whose ids are runs does not scatter (PR 44):
    `gid` None is one run, `runs=` the bounds of sorted dense runs. Every
    answer is held to the scatter's, cell for cell and dtype for dtype."""

    CAP = 4_096
    DTYPES = ("int32", "int64", "decimal", "float64", "bool")
    # (live rows, share of live rows whose weight is dead)
    INPUTS = {"rows": (3_000, 0.2), "one_row": (1, 0.0), "one_null": (1, 1.0),
              "empty": (0, 0.0), "all_dead": (3_000, 1.0), "full": (4_096, 0.1)}

    @staticmethod
    def _vals(dtype, cap, wrap=False):
        r = np.random.default_rng(7)
        if dtype == "float64":
            return r.normal(size=cap) * 1e6
        if dtype == "bool":
            return r.random(cap) < 0.5
        if dtype == "int32":
            lo, hi = (2**31 - 1_000, 2**31 - 1) if wrap else (-2**31, 2**31 - 1)
            return r.integers(lo, hi, cap).astype(np.int32)
        if wrap:  # sums pass 2**63: two's complement wraps in both routes
            return r.integers(2**62, 2**63 - 1, cap).astype(np.int64)
        if dtype == "decimal":  # unscaled decimal(15,2) values
            return r.integers(-10**14, 10**14, cap).astype(np.int64)
        return r.integers(-2**40, 2**40, cap).astype(np.int64)

    @staticmethod
    def _ops(dtype):
        return ("count",) if dtype == "bool" else (
            "sum", "min", "max", "count", "sumsq")

    @staticmethod
    def _same(got, want, exact):
        assert got.dtype == want.dtype and got.shape == want.shape
        if exact:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        else:
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-12)

    def _weight(self, nlive, dead_share, nullable):
        r = np.random.default_rng(11)
        live = np.arange(self.CAP) < nlive
        if not nullable and dead_share < 1.0:
            return jnp.asarray(live)
        return jnp.asarray(live & (r.random(self.CAP) >= dead_share))

    @pytest.mark.parametrize("nullable", [False, True])
    @pytest.mark.parametrize("shape", sorted(INPUTS))
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_one_run_answers_what_the_scatter_answers(
            self, dtype, shape, nullable):
        vals = jnp.asarray(self._vals(dtype, self.CAP))
        weight = self._weight(*self.INPUTS[shape], nullable)
        zeros = jnp.zeros(self.CAP, jnp.int32)
        for op in self._ops(dtype):
            # a float sum reduces pairwise where the scatter adds in row
            # order: as exact or better, not bit for bit
            exact = not (op == "sumsq" or (dtype == "float64" and op == "sum"))
            want = K.segment_reduce(vals, zeros, weight, 1_024, op)
            self._same(K.segment_reduce(vals, None, weight, 1_024, op),
                       want, exact)
            if op in ("sum", "min", "max"):
                got = K.segment_reduce_with_count(vals, None, weight, 1_024, op)
                want = K.segment_reduce_with_count(vals, zeros, weight, 1_024,
                                                   op)
                self._same(got[0], want[0], exact)
                self._same(got[1], want[1], True)

    def _sorted_ids(self, nlive, ngroups):
        """Ids as `group_by_words` leaves them: dense and non-decreasing
        over the live rows, going on past `ngroups` over the dead tail."""
        r = np.random.default_rng(13)
        cuts = np.sort(r.choice(np.arange(1, max(nlive, 2)),
                                max(min(ngroups, nlive) - 1, 0), replace=False))
        flags = np.zeros(self.CAP, np.int32)
        flags[cuts] = 1
        flags[nlive:] = r.random(self.CAP - nlive) < 0.3
        return jnp.asarray(np.cumsum(flags, dtype=np.int32))

    @pytest.mark.parametrize("gcap", [64, 1_024])
    @pytest.mark.parametrize("nullable", [False, True])
    @pytest.mark.parametrize("shape", ["rows", "one_row", "all_dead", "full"])
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_sorted_runs_answer_what_the_scatter_answers(
            self, dtype, shape, nullable, gcap):
        """Dead rows sorted last with ids of their own, `gcap` above
        `ngroups`: the cells past the live groups read what the scatter
        leaves there."""
        nlive, dead_share = self.INPUTS[shape]
        vals = jnp.asarray(self._vals(dtype, self.CAP))
        weight = self._weight(nlive, dead_share, nullable)
        live = jnp.arange(self.CAP) < nlive
        gid = self._sorted_ids(nlive, 37)
        ngroups = int(gid[nlive - 1]) + 1
        runs = K.run_bounds(gid, live, gcap, ngroups)
        np.testing.assert_array_equal(
            np.asarray(runs[0])[:ngroups],
            np.asarray(K.segment_starts(gid, gcap))[:ngroups])
        for op in self._ops(dtype):
            self._same(
                K.segment_reduce(vals, gid, weight, gcap, op, runs),
                K.segment_reduce(vals, gid, weight, gcap, op), True)
            if op in ("sum", "min", "max"):
                got = K.segment_reduce_with_count(vals, gid, weight, gcap, op,
                                                  runs)
                want = K.segment_reduce_with_count(vals, gid, weight, gcap, op)
                self._same(got[0], want[0], True)
                self._same(got[1], want[1], True)

    @pytest.mark.parametrize("dtype", ["int32", "int64"])
    @pytest.mark.parametrize("route", ["whole", "runs"])
    def test_a_sum_that_wraps_wraps_as_the_scatter_does(self, route, dtype):
        vals = jnp.asarray(self._vals(dtype, self.CAP, wrap=True))
        live = jnp.arange(self.CAP) < 4_000
        if route == "whole":
            gid, runs, known = jnp.zeros(self.CAP, jnp.int32), None, None
        else:
            gid = known = self._sorted_ids(4_000, 5)
            runs = K.run_bounds(gid, live, 64, 5)
        want = K.segment_reduce(vals, gid, live, 64, "sum")
        exact = int(np.asarray(vals)[:4_000].astype(object).sum())
        assert exact > np.iinfo(np.asarray(vals).dtype).max  # it does wrap
        self._same(K.segment_reduce(vals, known, live, 64, "sum", runs),
                   want, True)

    @staticmethod
    def _launched(fn):
        from nds_tpu.obs import tally as T
        from nds_tpu.obs.trace import Tracer

        tl = T.Tally(Tracer(), 1)
        with T.bind(tl):
            fn()
        return tl.launches

    @pytest.mark.parametrize("known,op,dtype,names", [
        ("none", "sum", "int64", {"reduce_whole": 1}),
        ("none", "min", "float64", {"reduce_whole": 1}),
        ("runs", "sum", "int32", {"reduce_runs": 1}),
        # a float sum and an extreme cancel / have no prefix: the scatter,
        # and beside it the count by the prefix, a launch of its own
        ("runs", "sum", "float64", {"segment_reduce": 1, "reduce_runs": 1}),
        ("runs", "max", "int64", {"segment_reduce": 1, "reduce_runs": 1}),
        ("unknown", "sum", "int64", {"segment_reduce_with_count": 1}),
    ])
    def test_the_seam_names_the_route(self, known, op, dtype, names):
        vals = jnp.asarray(self._vals(dtype, self.CAP))
        live = jnp.arange(self.CAP) < 3_000
        gid = self._sorted_ids(3_000, 9)
        runs = K.run_bounds(gid, live, 64, 9) if known == "runs" else None
        assert self._launched(lambda: K.segment_reduce_with_count(
            vals, None if known == "none" else gid, live, 64, op, runs
        )) == names

    @pytest.mark.parametrize("known,op,dtype", [
        ("none", "sum", "int64"), ("none", "sum", "float64"),
        ("none", "count", "int64"), ("none", "min", "int32"),
        ("none", "max", "float64"), ("none", "sumsq", "float64"),
        ("runs", "sum", "int64"), ("runs", "sum", "int32"),
        ("runs", "count", "int64"),
    ])
    def test_a_reduction_over_runs_scatters_nothing(self, known, op, dtype):
        """The cost model, held by the programs' jaxprs at query22's
        shape: one run streams; sorted runs stream a prefix sum and gather
        at the `gcap` run ends. A scatter of n rows again fails here, not
        on the chip."""
        n, gcap = 16_777_216, 32_768
        vals = jax.ShapeDtypeStruct((n,), jnp.dtype(dtype))
        weight = jax.ShapeDtypeStruct((n,), jnp.bool_)
        bound = jax.ShapeDtypeStruct((gcap,), jnp.int32)
        row_ops = TestCompactSelect._row_ops
        if known == "none":
            jaxpr = jax.make_jaxpr(lambda v, w: K.segment_reduce_with_count(
                v, None, w, gcap, op) if op in ("sum", "min", "max")
                else K.segment_reduce(v, None, w, gcap, op))(vals, weight)
            assert row_ops(jaxpr.jaxpr, []) == []
            return
        jaxpr = jax.make_jaxpr(lambda v, w, s, e: K.segment_reduce(
            v, jnp.zeros(n, jnp.int32), w, gcap, op, (s, e)
        ))(vals, weight, bound, bound)
        assert sorted(set(row_ops(jaxpr.jaxpr, []))) == [("gather", gcap)]
        scattered = jax.make_jaxpr(lambda v, w: K.segment_reduce(
            v, jnp.zeros(n, jnp.int32), w, gcap, op))(vals, weight)
        assert ("scatter-add", n) in row_ops(scattered.jaxpr, [])

    def test_sharded_ids_keep_the_scatter_and_one_run_reduces_sharded(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        rows = NamedSharding(mesh, PartitionSpec("data"))
        vals_h = self._vals("int64", self.CAP)
        live_h = np.arange(self.CAP) < 3_000
        gid = jax.device_put(self._sorted_ids(3_000, 9), rows)
        vals = jax.device_put(jnp.asarray(vals_h), rows)
        live = jax.device_put(jnp.asarray(live_h), rows)
        assert K.run_bounds(gid, live, 64, 9) is None
        got = K.segment_reduce_with_count(vals, None, live, 1_024, "sum")
        assert int(got[0][0]) == int(vals_h[live_h].sum())
        assert int(got[1][0]) == 3_000 and not np.asarray(got[0])[1:].any()


class TestJoin:
    def _join_np(self, lk, rk):
        pairs = []
        for i, k in enumerate(lk):
            for j, k2 in enumerate(rk):
                if k == k2:
                    pairs.append((i, j))
        return set(pairs)

    def test_inner_join(self):
        ln, lcap = 700, 1024
        rn, rcap = 300, 512
        lk = rng.integers(0, 100, lcap).astype(np.int64)
        rk = rng.integers(0, 100, rcap).astype(np.int64)
        li, ri, pl, total = K.join_candidates(
            [jnp.asarray(lk)], [None], _live(ln, lcap),
            [jnp.asarray(rk)], [None], _live(rn, rcap),
        )
        ok = K.verify_pairs(
            li, ri, pl,
            [jnp.asarray(lk)], [None], _live(ln, lcap),
            [jnp.asarray(rk)], [None], _live(rn, rcap),
        )
        got = {
            (int(a), int(b))
            for a, b, m in zip(np.asarray(li), np.asarray(ri), np.asarray(ok))
            if m
        }
        assert got == self._join_np(lk[:ln], rk[:rn])

    def test_multi_key_join_with_nulls(self):
        ln = lcap = 512
        rn = rcap = 512
        lk1 = rng.integers(0, 20, lcap).astype(np.int64)
        lk2 = rng.integers(0, 5, lcap).astype(np.int64)
        rk1 = rng.integers(0, 20, rcap).astype(np.int64)
        rk2 = rng.integers(0, 5, rcap).astype(np.int64)
        lv = rng.random(lcap) < 0.9
        li, ri, pl, _ = K.join_candidates(
            [jnp.asarray(lk1), jnp.asarray(lk2)], [jnp.asarray(lv), None], _live(ln, lcap),
            [jnp.asarray(rk1), jnp.asarray(rk2)], [None, None], _live(rn, rcap),
        )
        ok = K.verify_pairs(
            li, ri, pl,
            [jnp.asarray(lk1), jnp.asarray(lk2)], [jnp.asarray(lv), None], _live(ln, lcap),
            [jnp.asarray(rk1), jnp.asarray(rk2)], [None, None], _live(rn, rcap),
        )
        got = {
            (int(a), int(b))
            for a, b, m in zip(np.asarray(li), np.asarray(ri), np.asarray(ok))
            if m
        }
        expect = {
            (i, j)
            for i in range(ln)
            if lv[i]
            for j in range(rn)
            if lk1[i] == rk1[j] and lk2[i] == rk2[j]
        }
        assert got == expect

    def test_semi_anti_mask(self):
        ln = lcap = 256
        rn = rcap = 128
        lk = rng.integers(0, 400, lcap).astype(np.int64)
        rk = rng.integers(0, 400, rcap).astype(np.int64)
        li, ri, pl, _ = K.join_candidates(
            [jnp.asarray(lk)], [None], _live(ln, lcap),
            [jnp.asarray(rk)], [None], _live(rn, rcap),
        )
        ok = K.verify_pairs(
            li, ri, pl,
            [jnp.asarray(lk)], [None], _live(ln, lcap),
            [jnp.asarray(rk)], [None], _live(rn, rcap),
        )
        present = np.asarray(K.matched_mask(li, ok, lcap))
        expect = np.isin(lk, rk[:rn])
        np.testing.assert_array_equal(present[:ln], expect[:ln])


class TestDenseJoin:
    """`dense_build` + `dense_probe`: one int32 table of build row + 1 over
    the key domain, read once a probe row, against a dictionary lookup."""

    RMIN = 7

    def _probe(self, rkey, rlive, lkey, llive, table_cap):
        rowid1 = K.dense_build(
            jnp.asarray(rkey, jnp.int64), jnp.asarray(rlive, bool),
            self.RMIN, table_cap,
        )
        assert rowid1.shape == (table_cap,) and rowid1.dtype == jnp.int32
        matched, ri = K.dense_probe(
            jnp.asarray(lkey, jnp.int64), jnp.asarray(llive, bool),
            self.RMIN, rowid1, table_cap,
        )
        return np.asarray(matched), np.asarray(ri)

    def _held_to_a_dictionary(self, rkey, rlive, lkey, llive, table_cap):
        row_of = {
            int(k): i for i, (k, live) in enumerate(zip(rkey, rlive))
            if live and self.RMIN <= k < self.RMIN + table_cap
        }
        matched, ri = self._probe(rkey, rlive, lkey, llive, table_cap)
        want = [live and int(k) in row_of for k, live in zip(lkey, llive)]
        np.testing.assert_array_equal(matched, want)
        # the matching row where matched, row 0 where not
        np.testing.assert_array_equal(
            ri, [row_of[int(k)] if m else 0 for k, m in zip(lkey, want)]
        )
        return matched, ri

    @pytest.mark.parametrize(
        "n_build, table_cap, n_probe",
        [(500, 1024, 4096), (64, 64, 512), (300, 128, 512), (3, 1, 64)],
    )
    def test_against_a_dictionary(self, n_build, table_cap, n_probe):
        r = np.random.default_rng(n_build + table_cap)
        # unique build keys, dead rows among them, and on both sides keys
        # below rmin and at or above rmin + table_cap
        pool = np.arange(self.RMIN - table_cap, self.RMIN + 2 * table_cap)
        rkey = r.permutation(pool)[:n_build]
        rlive = r.random(n_build) > 0.25
        lkey = r.integers(self.RMIN - 8, self.RMIN + table_cap + 8, n_probe)
        llive = r.random(n_probe) > 0.1  # nulls and dead rows alike
        # build row 0 is live, in range and probed by a live row
        rkey[rkey == self.RMIN] = rkey[0]
        rkey[0], rlive[0] = self.RMIN, True
        lkey[0], llive[0] = self.RMIN, True
        matched, ri = self._held_to_a_dictionary(
            rkey, rlive, lkey, llive, table_cap
        )
        assert matched[0] and ri[0] == 0
        assert matched.any() and not matched.all()

    def test_a_match_on_build_row_0_is_not_an_absent_key(self):
        # both read row 0; only `matched` may tell them apart
        matched, ri = self._probe([9, 8], [True, True], [9, 10, 8], [True] * 3, 4)
        assert matched.tolist() == [True, False, True]
        assert ri.tolist() == [0, 0, 1]

    @pytest.mark.parametrize("n_build", [0, 16])
    def test_a_build_side_with_no_live_row_matches_nothing(self, n_build):
        rkey = np.arange(self.RMIN, self.RMIN + n_build)
        lkey = np.arange(self.RMIN - 2, self.RMIN + 30)
        matched, ri = self._held_to_a_dictionary(
            rkey, np.zeros(n_build, bool), lkey, np.ones(len(lkey), bool), 32
        )
        assert not matched.any() and not ri.any()

    def test_of_duplicate_build_keys_the_highest_row_stays(self):
        matched, ri = self._probe(
            [8, 9, 8, 8, 9], [True, True, True, False, True], [8, 9], [True] * 2, 8
        )
        assert matched.all() and ri.tolist() == [2, 4]

    def test_a_probe_is_one_gather(self):
        """On the v5e a second table gathered by the same slot cost more
        than the first (PERF.md, Findings PR 36): a probe that reads two
        tables again fails here, not on the chip."""
        n, table_cap = 4096, 1024
        hlo = K.dense_probe.__wrapped__.lower(
            jax.ShapeDtypeStruct((n,), jnp.int64),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
            self.RMIN,
            jax.ShapeDtypeStruct((table_cap,), jnp.int32),
            table_cap=table_cap,
        ).compile().as_text()
        assert len(re.findall(r"\bgather\(", hlo)) == 1, hlo


class TestWindow:
    def test_running_position(self):
        gid = jnp.asarray(np.array([0, 0, 0, 1, 1, 2, 3, 3, 3, 3], np.int32))
        pos = np.asarray(K.running_position(gid))
        np.testing.assert_array_equal(pos, [0, 1, 2, 0, 1, 0, 0, 1, 2, 3])

    def test_segment_starts(self):
        gid = jnp.asarray(np.array([0, 0, 1, 1, 1, 2], np.int32))
        s = np.asarray(K.segment_starts(gid, 4))
        np.testing.assert_array_equal(s[:3], [0, 2, 5])


def test_sort_indices_single_key_max_value_ties_with_dead_tail():
    """The one-operand fast path folds dead rows to int64 max; stability
    must keep a LIVE max-valued row ahead of the dead tail."""
    import jax.numpy as jnp
    from nds_tpu.ops import kernels as K

    big = np.iinfo(np.int64).max
    data = jnp.asarray([5, big, 1, 777, 888], dtype=jnp.int64)  # idx 3,4 dead
    live = jnp.asarray([True, True, True, False, False])
    order = np.asarray(K.sort_indices([(data, None, True, True)], live))
    assert order.tolist()[:3] == [2, 0, 1]  # live sorted; big stays live-first
    assert set(order.tolist()[3:]) == {3, 4}

    # descending single key
    order = np.asarray(K.sort_indices([(data, None, False, True)], live))
    assert order.tolist()[:3] == [1, 0, 2]
