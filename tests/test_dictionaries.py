"""Dictionary derivations are made once a tuple of dictionary objects
(`engine/columnar.py _DictMemo`): `_share_dictionary`, `unify_dictionaries`
and `sort_dictionary` against the unmemoised computation on the same
inputs, and what a second call may no longer do."""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from nds_tpu.dtypes import STRING
from nds_tpu.engine import columnar as C
from nds_tpu.engine import expr as E
from nds_tpu.engine.columnar import Column, Table
from nds_tpu.obs import tally as T
from nds_tpu.obs.trace import Tracer


@pytest.fixture(autouse=True)
def fresh_memo():
    C._DICT_MEMO.clear()
    yield
    C._DICT_MEMO.clear()


def _col(dictionary, codes, valid=None):
    # `device_put`, not `jnp.asarray`: the puts `counted` counts are the
    # engine's alone
    return Column(
        jax.device_put(np.asarray(codes, dtype=np.int32)), STRING,
        None if valid is None else jax.device_put(np.asarray(valid, dtype=bool)),
        dictionary,
    )


def _strings(col):
    """The strings a column's rows mean (None: a NULL)."""
    codes = np.asarray(col.data)
    valid = (np.ones(len(codes), bool) if col.valid is None
             else np.asarray(col.valid))
    d = col.dictionary.to_pylist() if col.dictionary is not None else []
    return [d[c] if v and d else None for c, v in zip(codes, valid)]


def _fruit():
    return pa.array(["pear", "apple", "fig", "cherry"])


def _same_object():
    d = _fruit()
    return [_col(d, [0, 1, 2, 3]), _col(d, [3, 3, 0, 1])]


def _equal_content():
    return [_col(_fruit(), [0, 1, 2, 3]), _col(_fruit(), [3, 2, 1, 0])]


def _disjoint():
    return [_col(_fruit(), [0, 1, 2, 3]),
            _col(pa.array(["kiwi", "lime"]), [1, 0, 0, 1])]


def _overlapping():
    return [_col(_fruit(), [0, 1, 2, 3]),
            _col(pa.array(["lime", "apple", "pear", "date"]), [3, 2, 1, 0])]


def _empty_and_none():
    empty = pa.array([], type=pa.string())
    return [_col(_fruit(), [2, 1, 0, 3]),
            _col(empty, [0, 0, 0, 0], [False] * 4),
            _col(None, [0, 0, 0, 0], [False] * 4),
            _col(pa.array(["apple", "zest"]), [1, 0, 1, 0])]


def _duplicates_one_object():
    d = pa.array(["b", "a", "b", "c", "a"])  # a string function can make such
    return [_col(d, [0, 1, 2, 3, 4]), _col(d, [4, 3, 2, 1, 0])]


def _duplicates_two_objects():
    return [_col(pa.array(["b", "a", "b", "c"]), [0, 1, 2, 3]),
            _col(pa.array(["c", "c", "d"]), [0, 1, 2, 1])]


def _three_inputs():
    return [_col(_fruit(), [0, 1, 2, 3]),
            _col(pa.array(["fig", "grape"]), [1, 0, 0, 1]),
            _col(pa.array(["apple", "grape", "hazel"]), [2, 1, 0, 2])]


def _large_string():
    d = pa.array(["pear", "apple"], type=pa.large_string())
    return [_col(d, [0, 1, 1, 0]), _col(d, [1, 1, 0, 0])]


CASES = {
    "same_object": _same_object,
    "equal_content": _equal_content,
    "disjoint": _disjoint,
    "overlapping": _overlapping,
    "empty_and_none": _empty_and_none,
    "duplicates_one_object": _duplicates_one_object,
    "duplicates_two_objects": _duplicates_two_objects,
    "three_inputs": _three_inputs,
    "large_string_one_object": _large_string,
}
#: every non-empty input one object: nothing is derived, `pc.unique` is
#: skipped (so duplicate entries stay), the object comes back
ONE_OBJECT = {"same_object", "duplicates_one_object",
              "large_string_one_object"}


def _unmemoised_share(cols):
    """`_share_dictionary` as it computed before the memo (numpy gathers)."""
    dicts = [
        (c.dictionary if c.dictionary is not None
         else pa.array([], pa.string())).cast(pa.string())
        for c in cols
    ]
    unified = pc.unique(pa.concat_arrays(dicts))
    out = []
    for c, d in zip(cols, dicts):
        codes = np.asarray(c.data)
        if len(d):
            remap = pc.index_in(d, unified).to_numpy(zero_copy_only=False)
            codes = remap.astype(np.int32)[np.clip(codes, 0, len(d) - 1)]
        out.append(Column(jnp.asarray(codes), STRING, c.valid, unified))
    return out, unified


@pytest.mark.parametrize("case", sorted(CASES))
def test_share_dictionary_equals_the_unmemoised_computation(case):
    cols = CASES[case]()
    want_cols, want = _unmemoised_share(cols)
    for attempt in range(2):  # a miss, then a hit: the same answer
        got_cols, got = E._share_dictionary(cols)
        assert got.type == pa.string()
        assert all(c.dictionary is got for c in got_cols)
        for src, w, g in zip(cols, want_cols, got_cols):
            assert _strings(g) == _strings(w) == _strings(src)
            assert g.data.dtype == jnp.int32 and g.valid is src.valid
        if case in ONE_OBJECT:
            # the entries are the input's own, in its order
            assert got.to_pylist() == cols[0].dictionary.to_pylist()
        else:
            assert got.equals(want)


@pytest.mark.parametrize("case", sorted(CASES))
def test_unify_dictionaries_equals_the_unmemoised_computation(case):
    a, b = CASES[case]()[0], CASES[case]()[-1]
    if case in ONE_OBJECT:
        b = _col(a.dictionary, np.asarray(b.data))
    (wa, wb), want = _unmemoised_share([a, b])
    for attempt in range(2):
        ca, cb, got = C.unify_dictionaries(a, b)
        ga = Column(ca, STRING, a.valid, got)
        gb = Column(cb, STRING, b.valid, got)
        assert _strings(ga) == _strings(wa) == _strings(a)
        assert _strings(gb) == _strings(wb) == _strings(b)
        if case != "duplicates_one_object":
            # comparable codes: equal strings have equal codes and no others
            for x, y, s, t in zip(np.asarray(ca), np.asarray(cb),
                                  _strings(ga), _strings(gb)):
                if s is not None and t is not None:
                    assert (x == y) == (s == t)
        if case not in ONE_OBJECT:
            assert got.equals(want)


@pytest.mark.parametrize("case", sorted(CASES))
def test_sort_dictionary_equals_the_unmemoised_computation(case):
    for col in CASES[case]():
        for attempt in range(2):
            ranks, sorted_dict = C.sort_dictionary(col)
            if col.dictionary is None or not len(col.dictionary):
                assert ranks is col.data and sorted_dict is col.dictionary
                continue
            got = Column(ranks, STRING, col.valid, sorted_dict)
            assert _strings(got) == _strings(col)
            entries = sorted_dict.to_pylist()
            assert entries == sorted(col.dictionary.to_pylist())
            # rank order is string order, row by row
            rows = _strings(col)
            order = np.argsort(np.asarray(ranks), kind="stable")
            assert [rows[i] for i in order] == sorted(rows)


def test_an_already_sorted_dictionary_is_its_own_sorted_dictionary():
    d = pa.array(["a", "b", "c"])
    col = _col(d, [2, 0, 1])
    ranks, sorted_dict = C.sort_dictionary(col)
    assert sorted_dict is d and ranks is col.data  # no gather
    again = Column(ranks, STRING, None, sorted_dict)
    assert C.sort_dictionary(again)[1] is d


@pytest.fixture
def counted(monkeypatch):
    """Calls of the Arrow kernels a derivation runs and of the host-to-device
    put, counted."""
    calls = {"unique": 0, "index_in": 0, "array_sort_indices": 0,
             "asarray": 0}
    for name in ("unique", "index_in", "array_sort_indices"):
        inner = getattr(pc, name)

        def counting(*a, _inner=inner, _name=name, **kw):
            calls[_name] += 1
            return _inner(*a, **kw)

        monkeypatch.setattr(C.pc, name, counting)
    put = jnp.asarray

    def asarray(*a, **kw):
        calls["asarray"] += 1
        return put(*a, **kw)

    monkeypatch.setattr(C.jnp, "asarray", asarray)
    return calls


@pytest.mark.parametrize(
    "case", sorted(set(CASES) - ONE_OBJECT - {"equal_content"}))
def test_a_second_call_derives_nothing(case, counted):
    cols = CASES[case]()
    built = counted["asarray"]
    first_cols, first = E._share_dictionary(cols)
    _, first_remaps = C.merge_dictionaries([c.dictionary for c in cols])
    assert counted["unique"] == 1 and counted["asarray"] > built
    before = dict(counted)
    second_cols, second = E._share_dictionary(cols)
    _, second_remaps = C.merge_dictionaries([c.dictionary for c in cols])
    assert second is first
    assert counted == before  # no pc.unique, no pc.index_in, no put
    assert all(a is b for a, b in zip(first_remaps, second_remaps))
    assert any(r is not None for r in second_remaps)
    # the first input's entries are distinct in these cases, `pc.unique`
    # keeps first appearance: its remap is the identity, no gather
    if case != "duplicates_two_objects":
        assert second_remaps[0] is None
        assert second_cols[0].data is cols[0].data
    for a, b in zip(first_cols, second_cols):
        assert _strings(a) == _strings(b)


@pytest.mark.parametrize("case", sorted(ONE_OBJECT - {"large_string_one_object"}))
def test_one_object_on_every_side_costs_nothing(case, counted):
    cols = CASES[case]()
    before = dict(counted)  # building the columns put their codes
    out, unified = E._share_dictionary(cols)
    assert unified is cols[0].dictionary
    assert all(o is c for o, c in zip(out, cols))  # as they are
    ca, cb, uni = C.unify_dictionaries(cols[0], cols[1])
    assert ca is cols[0].data and cb is cols[1].data and uni is unified
    assert counted == before and not before["unique"]
    assert len(C._DICT_MEMO) == 0


def test_one_object_of_another_type_keeps_its_cast(counted):
    cols = _large_string()
    before = dict(counted)
    _, first = E._share_dictionary(cols)
    _, second = E._share_dictionary([cols[1], cols[0]])
    assert first.type == pa.string() and second is first
    assert counted == before  # a cast, kept; no unique, no put


def test_equal_content_in_two_objects_hands_back_the_first(counted):
    a, b = _equal_content()
    out, unified = E._share_dictionary([a, b])
    # nothing new came after the first input: it IS the merged dictionary
    assert unified is a.dictionary and out[0] is a
    assert _strings(out[1]) == _strings(b)
    again, unified2 = E._share_dictionary([a, b])
    assert unified2 is unified and counted["unique"] == 1


def test_sort_dictionary_second_call(counted):
    col, other = _col(_fruit(), [0, 1, 2, 3]), _col(None, [3, 2, 1, 0])
    other = Column(other.data, STRING, None, col.dictionary)
    built = counted["asarray"]
    r1, d1 = C.sort_dictionary(col)
    before = dict(counted)
    assert before["array_sort_indices"] == 1
    assert before["asarray"] == built + 1  # the rank vector, once
    r2, d2 = C.sort_dictionary(other)
    assert d2 is d1 and counted == before
    (entry,) = C._DICT_MEMO._entries.values()
    assert entry.inputs[0] is col.dictionary and entry.dictionary is d1
    assert np.asarray(r2).tolist() == np.asarray(r1).tolist()[::-1]


def test_a_chain_of_three_concats_returns_one_object_the_second_time(counted):
    a, b, c, d = (pa.array(v) for v in (
        ["x", "y"], ["y", "z"], ["w"], ["z", "v"]))

    def chain():
        (ca, cb), u_ab = E._share_dictionary(
            [_col(a, [0, 1]), _col(b, [1, 0])])
        left = _col(u_ab, np.concatenate(
            [np.asarray(ca.data), np.asarray(cb.data)]))
        (cl, cc), u_abc = E._share_dictionary([left, _col(c, [0, 0, 0, 0])])
        left = _col(u_abc, np.asarray(cl.data))
        (cl, cd), u_abcd = E._share_dictionary([left, _col(d, [0, 1, 1, 0])])
        return u_ab, u_abc, u_abcd, _strings(cl) + _strings(cd)

    first = chain()
    assert first[2].to_pylist() == ["x", "y", "z", "w", "v"]
    assert first[3] == ["x", "y", "z", "y", "z", "v", "v", "z"]
    before = dict(counted)
    assert before["unique"] == 3
    second = chain()
    assert all(x is y for x, y in zip(first[:3], second[:3]))
    assert second[3] == first[3] and counted == before


def test_the_lru_evicts_at_its_bound_and_holds_its_inputs():
    memo = C._DictMemo(max_entries=4)
    dicts = [pa.array([f"s{i}", "t"]) for i in range(6)]
    refs = [weakref.ref(d) for d in dicts]
    for d in dicts:
        memo.derived("sort", (d,), C._derive_sorted)
    assert len(memo) == 4
    kept = {e.inputs[0].to_pylist()[0] for e in memo._entries.values()}
    assert kept == {"s2", "s3", "s4", "s5"}  # the oldest two went
    # a use makes an entry the youngest
    memo.derived("sort", (dicts[2],), C._derive_sorted)
    memo.derived("sort", (pa.array(["new"]),), C._derive_sorted)
    assert "s2" in {e.inputs[0].to_pylist()[0]
                    for e in memo._entries.values()}
    ids = [id(d) for d in dicts]
    del dicts, d
    gc.collect()
    # an entry holds its inputs, so their `id`s cannot be recycled into
    # another dictionary's while the entry answers for them; an evicted
    # entry lets go
    alive = [r() is not None for r in refs]
    assert alive == [False, False, True, False, True, True]
    for r, i in zip(refs, ids):
        if r() is not None:
            assert id(r()) == i
            assert ("sort", i) in memo._entries


def test_the_lru_is_bounded_by_bytes_too():
    """Entries are dimension-sized: a few large ones must not stay because
    they are few. What an entry keeps alive is its inputs, its derived
    dictionary and its device vectors."""
    dicts = [pa.array([f"{i}-{j:06d}" for j in range(1000, 0, -1)])
             for i in range(5)]
    one = C._DictMemo().derived("sort", (dicts[0],), C._derive_sorted)
    assert one.nbytes == (dicts[0].nbytes + one.dictionary.nbytes
                          + one.remaps[0].nbytes)
    memo = C._DictMemo(max_entries=64, max_bytes=one.nbytes * 3)
    for d in dicts:
        memo.derived("sort", (d,), C._derive_sorted)
    assert len(memo) == 3 and memo.nbytes == one.nbytes * 3
    assert [e.inputs[0] for e in memo._entries.values()] == dicts[2:]
    # an entry larger than the whole bound is handed out and not kept
    tiny = C._DictMemo(max_bytes=one.nbytes - 1)
    entry = tiny.derived("sort", (dicts[0],), C._derive_sorted)
    assert entry.dictionary.to_pylist() == sorted(dicts[0].to_pylist())
    assert len(tiny) == 0 and tiny.nbytes == 0
    # an already sorted dictionary is its own: counted once, no vector
    d = pa.array(["a", "b"])
    assert C._DictMemo().derived("sort", (d,), C._derive_sorted).nbytes \
        == d.nbytes
    memo.clear()
    assert len(memo) == 0 and memo.nbytes == 0


def test_a_literal_has_one_dictionary_a_value():
    """A comparison against a string literal is looked up by the column's
    dictionary and the literal's: the literal's must be the same object at
    every evaluation, or the entry can never hit (and pushes live ones
    out)."""
    d = _fruit()
    t = Table({"x": _col(d, [0, 1, 2, 3])}, 4)
    cmp = E.BinOp("=", E.Col("x"), E.Lit("fig"))
    tl = T.Tally(Tracer(), 1)
    with T.bind(tl):
        first = np.asarray(E.Evaluator(t).eval(cmp).data).tolist()
        second = np.asarray(E.Evaluator(t).eval(cmp).data).tolist()
    assert first == second == [False, False, True, False]
    assert tl.take()["dict_memo"] == {"miss": 1, "hit": 1}
    assert len(C._DICT_MEMO) == 1
    assert C.literal_dictionary("fig") is C.literal_dictionary("fig")
    assert C.literal_dictionary("fig").to_pylist() == ["fig"]


@pytest.mark.parametrize("how", ["recover_memory", "close"])
def test_a_session_lets_the_memo_go(how):
    """The memo's device vectors are outside the memory budgeter:
    `recover_memory` promises every recoverable device allocation, and a
    session's end is the end of its dictionaries."""
    from nds_tpu.engine.session import Session

    E._share_dictionary(_overlapping())
    C.sort_dictionary(_same_object()[0])
    assert len(C._DICT_MEMO) == 2 and C._DICT_MEMO.nbytes > 0
    session = Session(conf={"app.name": "memo"})
    getattr(session, how)()
    assert len(C._DICT_MEMO) == 0 and C._DICT_MEMO.nbytes == 0


def test_a_recycled_id_cannot_alias():
    """An evicted key's address may be reused by a new dictionary: the new
    one must get its own derivation."""
    memo = C._DictMemo(max_entries=1)
    for i in range(50):
        d = pa.array([f"b{i}", f"a{i}"])
        entry = memo.derived("sort", (d,), C._derive_sorted)
        assert entry.inputs[0] is d
        assert entry.dictionary.to_pylist() == [f"a{i}", f"b{i}"]
        del d, entry


def test_a_traced_case_gives_the_eager_answer_and_the_memo_holds_no_tracer():
    from nds_tpu.dtypes import INT32

    d1 = pa.array(["n", "m", "k"])
    d2 = pa.array(["k", "p"])
    case = E.Case(
        ((E.BinOp("=", E.Col("flag"), E.Lit(1)), E.Col("x")),), E.Col("y"))
    found = {}

    def run(flag, x, y):
        t = Table({"flag": Column(flag, INT32),
                   "x": Column(x, STRING, None, d1),
                   "y": Column(y, STRING, None, d2)}, 4)
        out = E.Evaluator(t).eval(case)
        found["dictionary"] = out.dictionary
        return out.data

    args = (jnp.asarray([1, 0, 1, 0], dtype=jnp.int32),
            jnp.asarray([0, 1, 2, 0], dtype=jnp.int32),
            jnp.asarray([1, 0, 1, 1], dtype=jnp.int32))
    traced = jax.jit(run)(*args)  # the miss happens under the trace
    traced_dict = found["dictionary"]
    for entry in C._DICT_MEMO._entries.values():
        for r in entry.remaps:
            assert r is None or (
                isinstance(r, jax.Array)
                and not isinstance(r, jax.core.Tracer))
    assert len(C._DICT_MEMO) == 1
    eager = run(*args)
    assert found["dictionary"] is traced_dict
    assert np.asarray(traced).tolist() == np.asarray(eager).tolist()
    strings = _strings(Column(traced, STRING, None, traced_dict))
    assert strings == ["n", "k", "k", "p"]
    # a second trace over the same objects derives nothing
    C._DICT_MEMO._entries.move_to_end(next(iter(C._DICT_MEMO._entries)))
    assert np.asarray(jax.jit(lambda *a: run(*a) + 0)(*args)).tolist() == \
        np.asarray(eager).tolist()
    assert len(C._DICT_MEMO) == 1


def test_dict_memo_counts_on_the_bound_tally():
    tl = T.Tally(Tracer(), 1)
    a, b = _overlapping()
    with T.bind(tl):
        E._share_dictionary(_same_object())
        E._share_dictionary([a, b])
        E._share_dictionary([a, b])
        C.sort_dictionary(a)
    own = tl.take()
    assert own["dict_memo"] == {"same": 1, "miss": 2, "hit": 1}
    # a miss is a dictionary merge and a put; a hit neither
    assert set(own["host_ms"]) == {"dict-merge"}
    assert own["eager_calls"]["dict_remap"] >= 3


def test_threads_deriving_at_once_get_one_object_a_key():
    """More threads than cores over a few shared dictionaries, the switch
    interval shortened: a key never hands out two derived objects (the
    first derivation to land stays), and a memo at its bound, evicting all
    the while, still answers right."""
    import os
    import sys
    import threading

    dicts = [pa.array([f"v{j}" for j in range(i + 2, 0, -1)])
             for i in range(6)]
    roomy, tight = C._DictMemo(max_entries=64), C._DictMemo(max_entries=2)
    seen = [set() for _ in dicts]
    errors = []
    start = threading.Barrier((os.cpu_count() or 2) + 4)

    def work():
        try:
            start.wait(timeout=30)
            for round_ in range(40):
                for i, d in enumerate(dicts):
                    entry = roomy.derived("sort", (d,), C._derive_sorted)
                    seen[i].add(id(entry.dictionary))
                    other = tight.derived("sort", (d,), C._derive_sorted)
                    for e in (entry, other):
                        assert e.inputs[0] is d
                        assert e.dictionary.to_pylist() == sorted(
                            d.to_pylist())
        except BaseException as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    threads = [threading.Thread(target=work) for _ in range(start.parties)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:1]
    assert all(len(ids) == 1 for ids in seen)
    assert len(roomy) == len(dicts) and len(tight) == 2
