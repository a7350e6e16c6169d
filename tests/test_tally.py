"""The split of the `execute` lump: `host_read` at every blocking
device-to-host read, the launch tally behind the kernel seam, `result_span`
at the statement's own boundary, `xla_compile` from jax's monitoring spans,
`catalog_load` saying what it spent, one clock (`t0_ns`), and the readers
inside the program (obs/critpath.py, `profile --critical-path`).

On the CPU and at SF0.01: counts and attachment are exact here; every time
belongs to a chip run (PERF.md)."""

import os
import subprocess
import sys
import time

import jax
import numpy as np
import pyarrow as pa
import pytest

from nds_tpu import faults
from nds_tpu.analysis import lint as L
from nds_tpu.engine.session import Session
from nds_tpu.obs import critpath as CP
from nds_tpu.obs import reader as R
from nds_tpu.obs import tally as T
from nds_tpu.obs import trace as obs_trace
from nds_tpu.obs.trace import EVENT_SCHEMA, Tracer, bind

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW_KINDS = ("host_read", "result_span", "xla_compile")
WHY = {"nrows", "mask_count", "bounds", "join_size", "ngroups", "scalar",
       "collect", "sort_span", "exchange", "spill", "host_eval", "pk_verify",
       "reshard"}


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    monkeypatch.delenv("NDS_TRACE_DIR", raising=False)
    monkeypatch.delenv("NDS_METRICS_PORT", raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def raw(tmp_path_factory):
    """SF0.01 raw data of this module's own (the shared /tmp copy other
    modules use is raced for by xdist workers)."""
    out = tmp_path_factory.mktemp("sf001")
    subprocess.run(
        [sys.executable, "-m", "nds_tpu.cli.gen_data", "--scale", "0.01",
         "--parallel", "2", "--data_dir", str(out), "--overwrite_output"],
        check=True, capture_output=True, cwd=REPO,
    )
    return str(out)


def _tpcds_session(raw, tracer):
    from nds_tpu.schema import get_schemas

    s = Session()
    s.tracer = tracer
    schemas = get_schemas(True)
    for t in ("store_sales", "date_dim", "item", "time_dim",
              "household_demographics", "store"):
        s.register_csv_dir(t, os.path.join(raw, t), schemas[t])
    return s


def _statements():
    from nds_tpu.datagen.query_streams import instantiate

    rng = np.random.default_rng(7)
    return {f"query{q}": instantiate(q, rng, 0.01) for q in (3, 96)}


def _small_session(tracer=None):
    s = Session()
    if tracer is not None:
        s.tracer = tracer
    s.register_arrow("t", pa.table({
        "a": [1, 2, 3, 4, 2, 1], "b": [10, 20, 30, 40, 50, 60],
        "c": ["x", "y", "x", "z", "y", "x"]}))
    s.register_arrow("u", pa.table({"a": [1, 2, 3], "d": [7, 8, 9]}))
    return s


JOIN_SQL = ("select c, sum(b) sb, count(*) n from t join u on t.a = u.a "
            "where b > 10 group by c order by c")


def _run(session, sql, name):
    with bind(session.tracer), faults.scope(name):
        return session.sql(sql).collect()


def _counts(events, query):
    """(launches by kernel, reads by why) of one statement's events."""
    launches, reads = {}, {}
    for e in events:
        if e.get("query") != query:
            continue
        if e["kind"] in ("op_span", "result_span"):
            for k, n in e["launches"].items():
                launches[k] = launches.get(k, 0) + n
        elif e["kind"] == "host_read":
            reads[e["why"]] = reads.get(e["why"], 0) + 1
    return launches, reads


# ---------------------------------------------------------------------------
# schema, one clock
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", NEW_KINDS)
def test_new_kinds_are_in_the_golden_schema(kind):
    assert "t0_ns" in EVENT_SCHEMA[kind] and "dur_ms" in EVENT_SCHEMA[kind]
    ev = {"ts": 1, "kind": kind, "app": "a"}
    problems = R.validate_events([ev])
    assert problems and "missing fields" in problems[0]
    ev.update({f: 0 for f in EVENT_SCHEMA[kind]})
    assert R.validate_events([ev]) == []


def test_emitted_events_validate_and_every_span_carries_t0_ns(tmp_path):
    s = Session(conf={"engine.trace_dir": str(tmp_path)})
    s.register_arrow("t", pa.table({"a": [1, 2, 3], "b": [1, 2, 3]}))
    before_ns = time.time_ns()
    _run(s, "select a, sum(b) sb from t group by a order by a", "q")
    after_ns = time.time_ns()
    s.tracer.close()
    events = R.read_events([str(tmp_path)], strict=True)
    assert R.validate_events(events) == []
    kinds = {e["kind"] for e in events}
    assert set(NEW_KINDS) <= kinds  # a file tracer watches compiles too
    spans = [e for e in events if "dur_ms" in e]
    assert {"op_span", "catalog_load", "pipeline_span", "exec_cache",
            "plan_budget"} <= {e["kind"] for e in spans}
    for e in spans:
        assert isinstance(e["t0_ns"], int), e["kind"]
        assert before_ns <= e["t0_ns"] <= after_ns, e["kind"]
        # `ts` (epoch ms) stays the emission time: the span's end
        assert e["ts"] >= e["t0_ns"] // 1_000_000 - 1, e["kind"]


def test_emit_derives_t0_ns_for_sites_that_take_none():
    t = Tracer()
    t.emit("scan_prune", table="x", files_total=1, files_pruned=0,
           rows_bound=None, dur_ms=250.0)
    t.emit("plan_cache", node="Aggregate", hit=False)
    ev, no_span = t.events
    assert abs(ev["t0_ns"] - (ev["ts"] * 1_000_000 - 250_000_000)) < 2_000_000
    assert "t0_ns" not in no_span


def test_logs_without_t0_ns_still_read():
    old = [
        {"ts": 5000, "kind": "query_span", "app": "a", "query": "q",
         "dur_ms": 1000.0, "status": "Completed", "retries": 0},
        {"ts": 4900, "kind": "op_span", "app": "a", "query": "q",
         "exec_id": 1, "seq": 1, "depth": 0, "node": "Scan", "explain": "",
         "dur_ms": 800.0, "rows": 1, "est_bytes": 0},
        {"ts": 4500, "kind": "catalog_load", "app": "a", "query": "q",
         "table": "t", "columns": 1, "loaded": 1, "rows": 1, "dur_ms": 300.0,
         "cache": "miss"},
    ]
    assert R.validate_events(old) == []
    causes = CP.critical_path(old)["queries"]["q"]["causes"]
    # no result_span: the one lump, as before
    assert causes["execute"] == 500.0 and causes["catalog-load"] == 300.0
    assert CP._interval(old[2]) == (4200e6, 4500e6)


# ---------------------------------------------------------------------------
# host_read: the one seam
# ---------------------------------------------------------------------------


def test_host_read_is_the_bare_call_with_no_tally_bound():
    import jax.numpy as jnp

    assert T.current() is None
    out = T.host_read("nrows", jnp.arange(4))
    assert isinstance(out, np.ndarray) and out.tolist() == [0, 1, 2, 3]


def test_host_read_emits_counts_and_hangs_under_its_span():
    import jax.numpy as jnp

    tracer = Tracer()
    tl = T.Tally(tracer, 42)
    with T.bind(tl):
        saved = tl.push(3)
        got = T.host_read("bounds", [jnp.arange(4, dtype=jnp.int32),
                                     jnp.ones(2, bool)])
        own = tl.pop(saved)
    assert [g.tolist() for g in got] == [[0, 1, 2, 3], [True, True]]
    (ev,) = tracer.events
    assert (ev["kind"], ev["why"], ev["bytes"], ev["exec_id"],
            ev["depth"]) == ("host_read", "bounds", 18, 42, 3)
    assert own["reads"] == 1 and own["read_wait_ms"] == pytest.approx(
        ev["dur_ms"], abs=1e-3)
    assert tl.depth == -1 and tl.reads == 0  # the frame closed


@pytest.mark.parametrize("name", ["query3", "query96"])
def test_counts_repeat_and_every_event_attaches(raw, name):
    """Two executions of one SF0.01 statement: `host_read`s by `why` and
    launches by kernel are equal, and every `host_read` / `xla_compile`
    that names an executor hangs under an `op_span` of it (depth >= 0) or
    under its `result_span` (depth -1)."""
    tracer = Tracer()
    obs_trace.watch_compiles(tracer)
    s = _tpcds_session(raw, tracer)
    sql = _statements()[name]
    _run(s, sql, "warm")  # loads tables, compiles
    s.register_arrow("tick", pa.table({"n": [0]}))  # drops the result cache
    first = _run(s, sql, "first")
    s.register_arrow("tick", pa.table({"n": [1]}))
    second = _run(s, sql, "second")
    assert first.equals(second)
    events = tracer.events
    assert R.validate_events(events) == []
    l1, r1 = _counts(events, "first")
    l2, r2 = _counts(events, "second")
    assert l1 == l2 and r1 == r2
    assert sum(l1.values()) > 0 and sum(r1.values()) > 0
    assert set(r1) <= WHY and r1["collect"] == 1
    by_exec = {}
    for e in events:
        if e["kind"] == "op_span":
            by_exec.setdefault(e["exec_id"], set()).add(e["depth"])
    results = {e["exec_id"] for e in events if e["kind"] == "result_span"}
    assert len(results) == 3
    hung = 0
    for e in events:
        if e["kind"] not in ("host_read", "xla_compile"):
            continue
        if e.get("exec_id") is None:
            continue  # compiled while planning: no executor yet
        hung += 1
        assert e["exec_id"] in results
        assert e["depth"] == -1 or e["depth"] in by_exec[e["exec_id"]]
    assert hung >= sum(r1.values()) * 2
    # the op_spans' own counters are those events, no more and no fewer
    for q in ("first", "second"):
        spans = [e for e in events if e.get("query") == q
                 and e["kind"] in ("op_span", "result_span")]
        reads = [e for e in events if e.get("query") == q
                 and e["kind"] == "host_read"]
        assert sum(e["reads"] for e in spans) == len(reads)
        assert sum(e["read_wait_ms"] for e in spans) == pytest.approx(
            sum(e["dur_ms"] for e in reads), abs=0.01 * len(reads) + 0.01)


# The gathers a statement needs at SF0.01: calls of the gather (one per table
# side) and the buffers they take (one program and one counted launch each).
#   query3   3 compactions of join sides that arrive masked, 1 dense-join
#            output; the sort join's candidate pairs (li, ri) under the
#            verified selection and the 2 sides of its pair table; the
#            group-by's sort words under the sort order
#   query96  5 compactions, 1 take under the LIMIT; its 3 dense-join outputs
#            gather nothing, since count(*) reads no dimension column
# A join hands on the columns something above it reads (P.Join.required):
# before that the same calls took 33 buffers each (query96 in 9 calls).
GATHERS = {"query3": (8, 19), "query96": (6, 10)}


@pytest.mark.parametrize("name", sorted(GATHERS))
def test_a_statement_gathers_once_per_table_side(raw, name, monkeypatch):
    """A per-column loop over the gather shows here as calls by the dozen,
    a column gathered twice as more buffers. Counts repeat between
    executions, and the answer is the one the eager per-column `data[idx]`
    gives."""
    from nds_tpu.ops import kernels as K

    calls = []
    enter = T.Tally.enter

    def counting(self, kernel, n=1):
        if kernel == "take_columns":
            calls.append(n)
        return enter(self, kernel, n)

    monkeypatch.setattr(T.Tally, "enter", counting)
    tracer = Tracer()
    s = _tpcds_session(raw, tracer)
    sql = _statements()[name]
    _run(s, sql, "warm")
    answers, per_run = [], []
    for tag in ("first", "second"):
        s.register_arrow("tick", pa.table({"n": [len(answers)]}))
        del calls[:]
        answers.append(_run(s, sql, tag))
        per_run.append(list(calls))
    counts = [_counts(tracer.events, tag)[0] for tag in ("first", "second")]
    assert counts[0] == counts[1] and per_run[0] == per_run[1]
    assert (len(per_run[0]), sum(per_run[0])) == GATHERS[name]
    assert counts[0]["take_columns"] == sum(per_run[0])

    # the parent's computation: jnp's eager indexing, buffer by buffer
    monkeypatch.setattr(K, "_gather", lambda a, idx: a[idx])
    monkeypatch.setattr(
        K, "_gather_valid",
        lambda v, idx, keep: keep if v is None else v[idx] & keep)
    s.register_arrow("tick", pa.table({"n": [2]}))
    reference = _run(s, sql, "reference")
    assert answers[0].equals(reference) and answers[1].equals(reference)


@pytest.mark.parametrize("dtype", ["int32", "int64", "float64", "bool"])
def test_seamed_gather_gives_what_indexing_gives(dtype):
    import jax.numpy as jnp

    from nds_tpu.ops import kernels as K

    data = jnp.asarray(np.arange(64) % 5, dtype=dtype)
    idx = jnp.asarray([63, 0, 0, 17, 5], dtype=jnp.int32)
    (out,) = K.take_arrays((data,), idx)
    assert out.dtype == data.dtype
    assert out.tolist() == data[idx].tolist()


# -- take_columns: one call for every column that shares an index -----------

_N = 64
_IDX = {
    "plain": [63, 0, 17, 5, 1, 2, 3, 4],
    "repeats": [7, 7, 7, 0, 0, 63, 63, 7],
    # what `data[idx]` accepts today: negatives count from the end, and
    # whatever lies outside the buffer is clamped to it
    "negative": [-1, -64, -2, 0, 5, -63, 1, -5],
    "out_of_range": [64, 1000, -65, -1000, 0, 63, 2**31 - 1, -2**31],
}


def _buffers(kinds):
    import jax.numpy as jnp

    made = {
        "int32": lambda k: jnp.asarray(np.arange(_N) * 3 - k, jnp.int32),
        "int64": lambda k: jnp.asarray(np.arange(_N) * 2**33 + k, jnp.int64),
        "float64": lambda k: jnp.asarray(np.arange(_N) / 7.0 + k, jnp.float64),
        "bool": lambda k: jnp.asarray((np.arange(_N) + k) % 3 == 0),
    }
    return [made[kind](k) for k, kind in enumerate(kinds)]


def _pairs(kinds, with_valid):
    import jax.numpy as jnp

    return tuple(
        (d, jnp.asarray((np.arange(_N) + k) % 4 != 0)
         if with_valid and k % 2 == 0 else None)
        for k, d in enumerate(_buffers(kinds)))


MIXED = ("float64", "int32", "bool", "int64", "int32")


@pytest.mark.parametrize("with_valid", [False, True], ids=["bare", "valid"])
@pytest.mark.parametrize("idx_kind", list(_IDX))
@pytest.mark.parametrize("kinds", [("int32",), ("int64",), ("float64",),
                                   ("bool",), MIXED], ids="-".join)
def test_take_columns_gives_what_indexing_gives(kinds, idx_kind, with_valid):
    import jax.numpy as jnp

    from nds_tpu.ops import kernels as K

    pairs = _pairs(kinds, with_valid)
    idx = jnp.asarray(_IDX[idx_kind], dtype=jnp.int32)
    out = K.take_columns(pairs, idx)
    assert len(out) == len(pairs)
    for (d, v), (od, ov) in zip(pairs, out):
        assert od.dtype == d.dtype and od.tolist() == d[idx].tolist()
        if v is None:
            assert ov is None
        else:
            assert ov.dtype == jnp.bool_ and ov.tolist() == v[idx].tolist()


@pytest.mark.parametrize("idx_kind", list(_IDX))
def test_take_columns_ands_the_row_mask_into_every_validity(idx_kind):
    """`keep` is the null extension of an outer join: `valid[idx] & keep`,
    and `keep` itself for a column that had no validity buffer."""
    import jax.numpy as jnp

    from nds_tpu.ops import kernels as K

    pairs = _pairs(MIXED, True)
    idx = jnp.asarray(_IDX[idx_kind], dtype=jnp.int32)
    keep = jnp.asarray([True, True, False, True, False, False, True, True])
    out = K.take_columns(pairs, idx, keep)
    for (d, v), (od, ov) in zip(pairs, out):
        assert od.tolist() == d[idx].tolist()
        want = keep if v is None else v[idx] & keep
        assert ov.tolist() == want.tolist()


def test_take_columns_of_nothing_is_nothing_and_launches_nothing():
    import jax.numpy as jnp

    from nds_tpu.ops import kernels as K

    tl = T.Tally(Tracer(), 1)
    with T.bind(tl):
        assert K.take_columns((), jnp.arange(4)) == ()
    assert tl.launches == {}


def test_take_columns_outputs_alias_nothing():
    """Every output is a buffer of its own: not an input, not `keep`, not
    another output (a join output's columns are `owned` and may be donated
    one by one)."""
    import jax.numpy as jnp

    from nds_tpu.ops import kernels as K

    d = jnp.arange(_N, dtype=jnp.int64)
    idx = jnp.arange(_N, dtype=jnp.int32)  # the identity gather
    keep = jnp.ones(_N, bool)
    out = K.take_columns(((d, None), (d, keep), (d, None)), idx, keep)
    bufs = [b for pair in out for b in pair]
    ptrs = [b.unsafe_buffer_pointer() for b in bufs + [d, idx, keep]]
    assert len(set(ptrs)) == len(ptrs)


def test_take_columns_counts_its_buffers_and_nothing_under_a_trace():
    """One call, one timed seam entry; `launches` counts the programs it
    launches, one a buffer (with `keep`, a validity for every column)."""
    import jax
    import jax.numpy as jnp

    from nds_tpu.ops import kernels as K

    pairs = _pairs(MIXED, True)  # 5 data buffers, 3 of them with validity
    idx = jnp.asarray(_IDX["plain"], dtype=jnp.int32)
    keep = jnp.ones(len(_IDX["plain"]), bool)
    tl = T.Tally(Tracer(), 1)
    with T.bind(tl):
        traced = jax.jit(lambda p: K.take_columns(p, idx))(pairs)
        assert tl.launches == {}
        eager = K.take_columns(pairs, idx)
        assert tl.launches == {"take_columns": 8}
        K.take_columns(pairs, idx, keep)
        assert tl.launches == {"take_columns": 18}
        K.take_arrays([d for d, _ in pairs], idx)  # same programs, same name
        assert tl.launches == {"take_columns": 23}
        assert K.take_arrays((), idx) == ()
        assert tl.launches == {"take_columns": 23}
    for (td, tv), (ed, ev) in zip(traced, eager):
        assert td.tolist() == ed.tolist()
        assert (tv is None and ev is None) or tv.tolist() == ev.tolist()


def test_a_compaction_is_one_launch_under_the_name_of_its_form():
    """`compact_select` where the two shapes send the call to block select,
    `compact_indices` elsewhere: one launch a call either way, so a span's
    `launches` say how often the mechanism engaged and how often it
    declined, and their sum is what it was. Nothing under a trace."""
    import jax
    import jax.numpy as jnp

    from nds_tpu.ops import kernels as K

    n = K._SELECT_MIN_ROWS
    sparse = jnp.arange(n) % 1_000 == 7
    tl = T.Tally(Tracer(), 1)
    with T.bind(tl):
        jax.jit(lambda m: K.compact_indices(m, 1_024))(sparse)
        assert tl.launches == {}
        picked = K.compact_indices(sparse, 1_024)
        assert tl.launches == {"compact_select": 1}
        K.compact_indices(sparse, n)  # out_cap of a dense mask: declined
        assert tl.launches == {"compact_select": 1, "compact_indices": 1}
        K.compact_indices(sparse[: n // 2], 1_024)  # a small mask: declined
        assert tl.launches == {"compact_select": 1, "compact_indices": 2}
        K.compact_indices(sparse, 1_024)
        assert tl.launches == {"compact_select": 2, "compact_indices": 2}
    assert picked.tolist()[:3] == [7, 1_007, 2_007]


@pytest.mark.parametrize("name", ["query3", "query96"])
def test_a_statement_launches_as_much_with_block_select_engaged(
    raw, name, monkeypatch
):
    """At SF0.01 no mask is large enough for the rule, so its floor is
    lowered here: the statement's compactions move from `compact_indices`
    to `compact_select`, one for one; every other count and the answer
    stay."""
    from nds_tpu.ops import kernels as K

    tracer = Tracer()
    s = _tpcds_session(raw, tracer)
    sql = _statements()[name]
    _run(s, sql, "warm")
    s.register_arrow("tick", pa.table({"n": [0]}))
    declined = _run(s, sql, "declined")
    monkeypatch.setattr(K, "_SELECT_MIN_ROWS", 2 * K._SELECT_BLOCK)
    s.register_arrow("tick", pa.table({"n": [1]}))
    engaged = _run(s, sql, "engaged")
    assert engaged.equals(declined)
    before, reads_before = _counts(tracer.events, "declined")
    after, reads_after = _counts(tracer.events, "engaged")
    assert "compact_select" not in before and after["compact_select"] > 0
    assert (after["compact_select"] + after.get("compact_indices", 0)
            == before["compact_indices"])
    assert sum(after.values()) == sum(before.values())
    for k in set(before) - {"compact_indices"}:
        assert after[k] == before[k]
    assert reads_after == reads_before


def test_tables_with_the_same_column_types_share_their_programs():
    """The gather is one jitted program a buffer, keyed by the buffer's
    dtype and the two capacities: a second table with the same column
    types, in any order, with or without validity, compiles nothing."""
    import jax.numpy as jnp

    from nds_tpu.ops import kernels as K

    tracer = Tracer()
    obs_trace.watch_compiles(tracer)
    idx = jnp.asarray(_IDX["repeats"] * 3, dtype=jnp.int32)  # a new shape
    a, b, c, d = _pairs(("int32", "float64", "int64", "float64"), True)
    with bind(tracer):
        first = K.take_columns((a, b, c, d), idx)
        size = K._gather._cache_size()
        n_compiles = sum(e["kind"] == "xla_compile" for e in tracer.events)
        assert n_compiles >= 1
        second = K.take_columns((d, c, (a[0], None), b), idx)
    assert K._gather._cache_size() == size
    assert sum(e["kind"] == "xla_compile" for e in tracer.events) == n_compiles
    for x, y in zip(first, (second[2], second[3], second[1], second[0])):
        assert x[0].tolist() == y[0].tolist()


def test_launch_ms_leaves_out_the_reads_inside_a_seamed_call():
    tracer = Tracer()
    tl = T.Tally(tracer, 1)
    token = tl.enter("join_candidates")
    assert tl.enter("sort_by_words") is None  # nested: counted, not timed
    tl.read_wait_ms += 1e6  # a read inside the call waited "1000 s"
    time.sleep(0.002)
    tl.leave(token)
    assert tl.launches == {"join_candidates": 1, "sort_by_words": 1}
    assert tl.launch_ms < 0  # the wait was taken off, not added
    assert tl.in_seam is False


# ---------------------------------------------------------------------------
# result_span, catalog_load, xla_compile
# ---------------------------------------------------------------------------


def test_result_span_is_the_statements_boundary():
    tracer = Tracer()
    s = _small_session(tracer)
    t0 = time.perf_counter()
    _run(s, JOIN_SQL, "q")
    outside_ms = (time.perf_counter() - t0) * 1e3
    (res,) = [e for e in tracer.events if e["kind"] == "result_span"]
    roots = [e for e in tracer.events
             if e["kind"] == "op_span" and e["depth"] == 0]
    assert res["exec_id"] == roots[-1]["exec_id"]
    assert res["exec_ms"] >= roots[-1]["dur_ms"]
    assert res["dur_ms"] == pytest.approx(
        res["exec_ms"] + res["to_arrow_ms"], abs=0.01)
    assert res["dur_ms"] <= outside_ms
    # the collect's read is counted on the result_span, outside every node
    assert res["reads"] == 1
    collect = [e for e in tracer.events
               if e["kind"] == "host_read" and e["why"] == "collect"]
    assert [e["depth"] for e in collect] == [-1]
    # table() after collect() executes nothing again: no second span
    r = s.sql("select a from u")
    with bind(tracer), faults.scope("q2"):
        r.table()
        r.table()
        r.collect()
    spans = [e for e in tracer.events
             if e["kind"] == "result_span" and e.get("query") == "q2"]
    assert [(e["exec_ms"] > 0, e["to_arrow_ms"] > 0) for e in spans] == [
        (True, False), (False, True)]


def test_catalog_load_says_what_it_spent():
    tracer = Tracer()
    s = _small_session(tracer)
    _run(s, "select a, b from t", "q")
    _run(s, "select a, b from t", "q_again")
    loads = [e for e in tracer.events if e["kind"] == "catalog_load"]
    miss, hit = loads[0], loads[-1]
    assert miss["cache"] == "miss" and hit["cache"] == "hit"
    for k in ("read_ms", "encode_ms", "h2d_ms"):
        assert miss[k] >= 0 and hit[k] == 0
    assert miss["encode_ms"] > 0 and miss["h2d_ms"] > 0
    assert (miss["read_ms"] + miss["encode_ms"] + miss["h2d_ms"]
            <= miss["dur_ms"] + 0.01)


def test_untraced_load_does_not_wait_for_the_copy(monkeypatch):
    monkeypatch.setenv("NDS_FLIGHT_RECORDER", "off")
    s = _small_session()
    assert s.tracer is None
    assert s.sql("select a, b from t").collect().num_rows == 6


def test_compile_listener_names_stage_program_and_cache():
    tracer = Tracer()
    spans = obs_trace._on_compile_span
    with bind(tracer):
        spans("/jax/core/compile/jaxpr_trace_duration", 100.0, 100.5,
              fun_name="gather")
        obs_trace._on_compile_event("/jax/compilation_cache/cache_hits")
        spans("/jax/core/compile/backend_compile_duration", 101.0, 101.25,
              fun_name="jit(gather)")
        spans("/jax/core/compile/backend_compile_duration", 102.0, 103.0,
              fun_name="jit(_pad)")
        spans("/jax/some/other_duration", 1.0, 2.0, fun_name="x")
        with T.bind(T.Tally(tracer, 5)) as tl:
            token = tl.enter("sort_by_words")
            spans("/jax/core/compile/jaxpr_to_mlir_module_duration", 104.0,
                  104.1, fun_name="jit(_kv_sort_perm)")
            tl.leave(token)
    got = [(e["stage"], e["fun"], e["cached"], e["dur_ms"], e["t0_ns"])
           for e in tracer.events]
    assert got == [
        ("trace", "gather", False, 500.0, 100_000_000_000),
        ("compile", "gather", True, 250.0, 101_000_000_000),
        ("compile", "_pad", False, 1000.0, 102_000_000_000),
        ("lower", "_kv_sort_perm", False, 100.0, 104_000_000_000),
    ]
    last = tracer.events[-1]
    assert (last["exec_id"], last["depth"], last["in_seam"]) == (5, -1, True)
    assert "exec_id" not in tracer.events[0]
    assert R.validate_events(tracer.events) == []


def test_only_a_file_or_sink_tracer_watches_compiles(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(obs_trace, "watch_compiles", calls.append)
    ring_only = Session()
    assert ring_only.tracer is not None and ring_only.tracer.path is None
    assert calls == []
    filed = Session(conf={"engine.trace_dir": str(tmp_path)})
    assert calls == [filed.tracer]
    filed.tracer.close()


# ---------------------------------------------------------------------------
# the readers inside the program
# ---------------------------------------------------------------------------

MS = 1_000_000  # ns


def _ev(kind, t0_ms, dur_ms, **fields):
    return {"ts": t0_ms + dur_ms, "kind": kind, "app": "a", "query": "q",
            "t0_ns": int(t0_ms * MS), "dur_ms": float(dur_ms), **fields}


SPLIT_EVENTS = [
    _ev("query_span", 0, 1000, status="Completed", retries=0),
    _ev("result_span", 50, 900, exec_id=1, exec_ms=880.0, to_arrow_ms=20.0,
        launches={"compact_indices": 1}, launch_ms=10.0, reads=1,
        read_wait_ms=15.0),
    _ev("op_span", 50, 880, exec_id=1, seq=2, depth=0, node="MultiJoin",
        explain="", rows=1, est_bytes=0, launches={"take_columns": 12},
        launch_ms=90.0, reads=2, read_wait_ms=300.0),
    _ev("op_span", 60, 200, exec_id=1, seq=1, depth=1, node="Scan",
        explain="", rows=1, est_bytes=0, launches={}, launch_ms=0.0, reads=0,
        read_wait_ms=0.0),
    _ev("catalog_load", 60, 200, table="t", columns=1, loaded=1, rows=1,
        cache="miss", read_ms=100.0, encode_ms=60.0, h2d_ms=40.0),
    # a first-touch compile inside a seamed call inside the load's span
    _ev("xla_compile", 100, 20, stage="trace", fun="_pad", cached=False,
        exec_id=1, depth=1),
    _ev("exec_cache", 300, 100, pipeline="p", bucket=1024, hit=False),
    _ev("xla_compile", 310, 30, stage="trace", fun="pipe", cached=False,
        exec_id=1, depth=0),
    _ev("xla_compile", 315, 10, stage="trace", fun="inner", cached=False,
        exec_id=1, depth=0),
    _ev("xla_compile", 340, 40, stage="compile", fun="pipe", cached=True,
        exec_id=1, depth=0),
    _ev("aot_cache", 380, 10, op="load", result="hit"),
    _ev("xla_compile", 500, 50, stage="compile", fun="gather", cached=False,
        exec_id=1, depth=0, in_seam=True),
    _ev("host_read", 600, 250, why="nrows", bytes=4, exec_id=1, depth=0),
    _ev("host_read", 860, 50, why="join_size", bytes=8, exec_id=1, depth=0),
    _ev("host_read", 935, 15, why="collect", bytes=64, exec_id=1, depth=-1),
]


def test_critical_path_splits_execute_into_disjoint_causes():
    q = CP.critical_path(SPLIT_EVENTS)["queries"]["q"]
    c = q["causes"]
    assert c["execute"] == 0.0
    assert c["device-wait"] == 315.0
    assert c["xla-compile"] == 50.0
    assert c["cache-load"] == 50.0  # the cached compile + the AOT load
    assert c["jit-trace"] == 50.0  # 20 in the load + 30 (the nested 10 once)
    assert c["exec-lookup"] == 20.0  # 100 less trace 30, compile 40, AOT 10
    # the load's 200 ms less the 20 ms compile inside it, split 100:60:40
    assert (c["read"], c["encode"], c["h2d"]) == (90.0, 54.0, 36.0)
    assert c["catalog-load"] == 0.0
    # launch_ms 100 less the 50 ms compile that fell inside a seamed call
    assert c["launch"] == 50.0
    # the rest of the 900 ms result_span
    assert c["host-python"] == 900 - (315 + 50 + 50 + 50 + 20 + 180 + 50)
    assert c["plan-host"] == 100.0  # the query_span outside the result_span
    assert sum(c.values()) == pytest.approx(q["wall_ms"])
    assert q["attributed_frac"] == 1.0
    assert q["launches"] == {"take_columns": 12, "compact_indices": 1}
    assert q["reads"]["nrows"] == {"count": 1, "ms": 250.0}
    assert q["compiles"]["gather"] == {"count": 1, "ms": 50.0, "fresh": 1}
    assert q["compiles"]["pipe"] == {"count": 1, "ms": 70.0, "fresh": 0}


def test_plan_budget_wall_is_carved_out_of_plan_host():
    events = SPLIT_EVENTS + [
        _ev("plan_budget", 5, 40, verdict="direct", peak_bytes=1,
            budget_bytes=2)]
    c = CP.critical_path(events)["queries"]["q"]["causes"]
    assert c["plan-budget"] == 40.0 and c["plan-host"] == 60.0


def test_critical_path_of_a_real_statement_stays_attributed():
    tracer = Tracer()
    obs_trace.watch_compiles(tracer)
    s = _small_session(tracer)
    from nds_tpu.report import BenchReport

    def stmt():
        with faults.scope("q"):
            return s.sql(JOIN_SQL).collect()

    with bind(tracer):
        BenchReport(s).report_on(stmt, name="q")
    cp = CP.critical_path(tracer.events)
    q = cp["queries"]["q"]
    assert q["wall_ms"] > 0 and CP.min_attributed_frac(cp) >= 0.9
    assert sum(q["causes"].values()) <= q["wall_ms"] * 1.001
    assert all(v >= 0 for v in q["causes"].values())
    assert q["causes"]["execute"] == 0.0
    assert q["causes"]["device-wait"] > 0 and q["causes"]["launch"] > 0
    assert q["launches"]["take_columns"] >= 1 and q["reads"]["collect"]["count"] == 1


def test_profile_cli_prints_the_split_table(tmp_path, capsys):
    import json

    from nds_tpu.cli import profile as profile_cli

    log = tmp_path / "events-x.jsonl"
    log.write_text("".join(json.dumps(e) + "\n" for e in SPLIT_EVENTS))
    assert not profile_cli.main(
        [str(log), "--critical-path", "--min_attributed", "0.9"])
    out = capsys.readouterr().out
    for needle in ("device-wait", "host-python", "exec-lookup", "h2d",
                   "launches: take_columns 12", "reads: nrows 1 (250.0 ms)",
                   "compiles: pipe 1 (0 fresh, 70.0 ms)"):
        assert needle in out, needle
    assert "\n   execute " not in out
    assert not profile_cli.main([str(log)])
    assert "kernels by launches" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# the lint that keeps the counter whole
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("line", [
    "n = int(jnp.sum(mask))",
    "got = jax.device_get(x)",
    "jax.block_until_ready(out)",
    "x.block_until_ready()",
    "ok = bool(jnp.any(m))",
])
def test_lint_flags_a_read_outside_the_seam(line):
    src = f"import jax\nimport jax.numpy as jnp\n\ndef f(x, mask, m, out):\n    {line}\n"
    for path in ("engine/exec.py", "ops/kernels.py"):
        assert [f.rule for f in L.lint_source(src, path)] == ["host-read-seam"]
    assert L.lint_source(src, "obs/tally.py") == []
    pragma = src.replace(line, line + "  # nds-lint: disable=host-read-seam")
    assert L.lint_source(pragma, "engine/exec.py") == []


def test_lint_passes_the_seam_and_static_shapes():
    src = ("from ..obs.tally import host_read\nimport jax.numpy as jnp\n\n"
           "def f(x):\n"
           "    n = int(host_read('nrows', jnp.sum(x)))\n"
           "    return n + int(x.shape[0]) + int(len(x))\n")
    assert L.lint_source(src, "engine/exec.py") == []


def test_the_tree_has_no_read_outside_the_seam():
    assert [f for f in L.run_lint() if f.rule == "host-read-seam"] == []


# ---------------------------------------------------------------------------
# what the ring-only path pays
# ---------------------------------------------------------------------------


def test_new_work_on_the_ring_only_path_fits_its_budget(raw):
    """ISSUE 25's budget: what this PR adds to a statement on the ring-only
    path (the driver's untraced runs) stays under 1 ms: events per
    statement x cost per emit, plus the clock reads and the seam's adds.
    A CPU microbench of host-only work; the chip's number is in PERF.md."""
    tracer = Tracer()
    s = _tpcds_session(raw, tracer)
    sql = _statements()["query3"]
    _run(s, sql, "warm")
    s.register_arrow("tick", pa.table({"n": [0]}))
    _run(s, sql, "q")
    events = [e for e in tracer.events if e.get("query") == "q"]
    launches, reads = _counts(events, "q")
    n_reads = sum(reads.values())
    n_launch = sum(launches.values())
    n_spans = sum(1 for e in events if "t0_ns" in e)

    ring = Session().tracer  # the shape an untraced run has
    assert ring.path is None and ring.events is None
    tl = T.Tally(ring, 1)
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        ring.emit("host_read", why="nrows", bytes=4, dur_ms=0.123,
                  t0_ns=1, exec_id=1, depth=0)
    emit_us = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for _ in range(n):
        tl.leave(tl.enter("take_columns"))
    seam_us = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for _ in range(n):
        time.time_ns()
        time.perf_counter()
        time.perf_counter()
    clock_us = (time.perf_counter() - t0) / n * 1e6
    added_ms = (n_reads * (emit_us + clock_us) + n_launch * seam_us
                + n_spans * clock_us + 1 * emit_us) / 1e3
    print(f"{n_reads} reads, {n_launch} launches, {n_spans} spans a "
          f"statement; emit {emit_us:.2f} us, seam {seam_us:.2f} us, "
          f"clocks {clock_us:.2f} us: {added_ms:.3f} ms added")
    assert n_reads > 0 and n_launch > 0
    assert added_ms < 1.0


# ---------------------------------------------------------------------------
# the host's half by name: launch_ms_by, compile_ms, host_ms, host_iv
# ---------------------------------------------------------------------------

HOST_TABLES = ("store_sales", "date_dim", "item", "store",
               "customer_demographics", "promotion")


@pytest.fixture(scope="module")
def host_run(raw, tmp_path_factory):
    """query36, query3 and query7 over SF0.01, each run twice with a file
    tracer (the second execution warm), and the events read back."""
    from nds_tpu.datagen.query_streams import instantiate
    from nds_tpu.schema import get_schemas

    trace_dir = tmp_path_factory.mktemp("host_trace")
    s = Session(conf={"engine.trace_dir": str(trace_dir)})
    schemas = get_schemas(True)
    for t in HOST_TABLES:
        s.register_csv_dir(t, os.path.join(raw, t), schemas[t])
    rng = np.random.default_rng(7)
    for q in (36, 3, 7):
        sql = instantiate(q, rng, 0.01)
        _run(s, sql, f"query{q}.first")
        s.register_arrow("tick", pa.table({"n": [q]}))
        _run(s, sql, f"query{q}")
    s.tracer.close()
    return R.read_events(str(trace_dir))


def _own_spans(events, query):
    """(op_spans with `excl_ms`, result_spans) of one statement."""
    ops = [e for e in R.op_spans_with_exclusive(events)
           if e.get("query") == query]
    results = [e for e in events
               if e["kind"] == "result_span" and e.get("query") == query]
    return ops, results


def test_launch_ms_by_sums_to_launch_ms_and_a_nest_is_timed_once():
    tl = T.Tally(Tracer(), 1)
    outer = tl.enter("group_by_words")
    time.sleep(0.002)
    assert tl.enter("sort_by_words") is None  # counted, timed by the outer
    time.sleep(0.002)
    tl.leave(outer)
    tl.leave(tl.enter("take_columns", 3))
    own = tl.take()
    assert own["launches"] == {"group_by_words": 1, "sort_by_words": 1,
                               "take_columns": 3}
    assert set(own["launch_ms_by"]) == {"group_by_words", "take_columns"}
    assert own["launch_ms_by"]["group_by_words"] >= 4.0
    assert sum(own["launch_ms_by"].values()) == pytest.approx(
        own["launch_ms"], abs=2e-3)
    assert own["compile_ms"] == {} and own["host_ms"] == {}
    assert "eager_calls" not in own and "host_iv" not in own


def test_a_compile_stage_inside_a_seamed_call_is_compile_ms_not_launch():
    tracer = Tracer()
    with bind(tracer), T.bind(T.Tally(tracer, 5)) as tl:
        token = tl.enter("sort_by_words")
        time.sleep(0.03)
        now = time.time()
        # a trace of 20 ms that held an inner trace of 5 ms, innermost first
        obs_trace._on_compile_span(
            "/jax/core/compile/jaxpr_trace_duration", now - 0.015,
            now - 0.010, fun_name="inner")
        obs_trace._on_compile_span(
            "/jax/core/compile/jaxpr_trace_duration", now - 0.020, now,
            fun_name="_kv_sort_perm")
        obs_trace._on_compile_event("/jax/compilation_cache/cache_hits")
        obs_trace._on_compile_span(
            "/jax/core/compile/backend_compile_duration", now, now + 0.004,
            fun_name="jit(_kv_sort_perm)")
        tl.leave(token)
        own = tl.take()
    # each instant once: the inner trace's 5 ms are not counted twice
    assert own["compile_ms"]["trace"] == pytest.approx(20.0, abs=0.01)
    assert own["compile_ms"]["load"] == pytest.approx(4.0, abs=0.01)
    assert set(own["compile_ms"]) == {"trace", "load"}
    # the seamed call took 30 ms and more; the 24 ms of stages are not in it
    assert 0 < own["launch_ms_by"]["sort_by_words"] < 30.0 + 10.0 - 24.0 + 6.0
    assert own["launch_ms"] == pytest.approx(
        own["launch_ms_by"]["sort_by_words"], abs=2e-3)
    assert [e["in_seam"] for e in tracer.events] == [True] * 3


def test_separate_stages_add_up_under_their_names():
    tl = T.Tally(Tracer(), 1)
    tl.add_compile("load", 10.0, 10.25)  # an AOT load
    tl.add_compile("trace", 11.0, 11.1)
    tl.add_compile("load", 12.0, 12.05)
    assert tl.take()["compile_ms"] == {
        "load": pytest.approx(300.0), "trace": pytest.approx(100.0)}


def test_phases_nest_innermost_first_and_a_jax_trace_times_nothing_apart():
    tl = T.Tally(Tracer(), 1)
    seen = {}

    def body(x):
        # what a pipeline build does: the engine's own seamed code, traced
        seen["eager"] = T.eager("expr")
        seen["phase"] = T.phase("dict-merge")
        seen["kernel"] = tl.enter("segment_reduce")
        time.sleep(0.002)
        return x + 1

    with T.bind(tl):
        with T.phase("exec-lookup"):
            time.sleep(0.002)
            with T.phase("pipeline-build"):
                time.sleep(0.002)
                jax.make_jaxpr(body)(1)
            with T.eager("concat"):
                time.sleep(0.002)
        own = tl.take()
    assert seen == {"eager": T._NOTHING, "phase": T._NOTHING, "kernel": None}
    assert set(own["host_ms"]) == {"exec-lookup", "pipeline-build"}
    assert set(own["host_ms"]) <= set(T.PHASES)
    assert own["launch_ms_by"].keys() == {"eager:concat"}
    assert own["eager_calls"] == {"concat": 1}  # counted where it is timed
    assert own["launches"] == {"segment_reduce": 1}  # counted, as ever
    # the build's 2 ms and the 2 ms under the trace: the phase's, or the
    # trace stage's where a compile listener of this process reported it
    assert set(own["compile_ms"]) <= {"trace"}
    build = own["host_ms"]["pipeline-build"]
    assert build + own["compile_ms"].get("trace", 0.0) >= 3.9 and build > 1.9
    assert 1.9 < own["host_ms"]["exec-lookup"] < 4.0
    assert tl.in_seam is False


def test_an_eager_seam_is_timed_by_name_and_kept_out_of_the_launches():
    tl = T.Tally(Tracer(), 1)
    with T.bind(tl):
        with T.eager("concat"):
            time.sleep(0.002)
            tl.leave(tl.enter("take_columns"))  # a kernel inside: its own
        token = tl.enter("group_by_words")
        with T.eager("cumsum"):  # inside a kernel seam: the kernel's time
            pass
        with T.phase("dict-merge"):
            pass
        tl.leave(token)
        own = tl.take()
    assert own["launches"] == {"take_columns": 1, "group_by_words": 1}
    assert own["eager_calls"] == {"concat": 1}
    assert set(own["launch_ms_by"]) == {
        "eager:concat", "take_columns", "group_by_words"}
    assert own["launch_ms_by"]["eager:concat"] >= 2.0
    # `launch_ms` stays what it was: the kernel seams' alone
    assert own["launch_ms"] == pytest.approx(
        own["launch_ms_by"]["take_columns"]
        + own["launch_ms_by"]["group_by_words"], abs=2e-3)
    assert own["host_ms"] == {}


def test_dict_memo_counts_on_the_span_that_called():
    tl = T.Tally(Tracer(), 1)
    T.dict_memo("same")  # no tally bound: nothing, and no error
    with T.bind(tl):
        saved = tl.push(0)
        T.dict_memo("same")
        inner = tl.push(1)
        T.dict_memo("hit")
        T.dict_memo("miss")
        T.dict_memo("hit")
        child = tl.pop(inner)
        T.dict_memo("same")
        parent = tl.pop(saved)
        T.dict_memo("miss")  # outside every plan node: the result_span's
    assert child["dict_memo"] == {"hit": 2, "miss": 1}
    assert parent["dict_memo"] == {"same": 2}
    assert tl.take()["dict_memo"] == {"miss": 1}
    assert "dict_memo" not in tl.take()  # none asked for: no field


def test_dict_memo_under_a_jax_trace_counts_the_traces_one_call():
    import jax.numpy as jnp

    from nds_tpu.dtypes import STRING
    from nds_tpu.engine import columnar as C

    d = pa.array(["pear", "apple", "fig"])
    C._DICT_MEMO.clear()

    @jax.jit
    def ranks(codes):
        return C.sort_dictionary(C.Column(codes, STRING, None, d))[0]

    codes = jnp.asarray([0, 1, 2, 1], dtype=jnp.int32)
    tl = T.Tally(Tracer(), 1)
    with T.bind(tl):
        for _ in range(3):  # traced once, replayed twice: one call
            assert np.asarray(ranks(codes)).tolist() == [2, 0, 1, 0]
        traced = tl.take()
        C.sort_dictionary(C.Column(codes, STRING, None, d))
        eager = tl.take()
    # the derivation happened under the trace: a miss, with no phase and
    # no eager seam of its own (the time is the trace stage's)
    assert traced["dict_memo"] == {"miss": 1}
    assert "dict-merge" not in traced["host_ms"]
    assert not any(k.startswith("eager:") for k in traced["launch_ms_by"])
    assert eager["dict_memo"] == {"hit": 1}
    assert eager["eager_calls"] == {"dict_remap": 1}
    C._DICT_MEMO.clear()


def test_a_child_span_inside_a_kernel_seam_starts_outside_every_seam():
    tl = T.Tally(Tracer(), 1)
    saved = tl.push(0)
    token = tl.enter("fused_pipeline")
    inner = tl.push(1)
    assert tl.in_seam is False
    assert tl.enter("take_columns") is not None  # timed: its own frame
    child = tl.pop(inner)
    assert tl.in_seam is True  # the parent's seam is open again
    tl.leave(token)
    parent = tl.pop(saved)
    assert child["launches"] == {"take_columns": 1}
    assert set(parent["launch_ms_by"]) == {"fused_pipeline"}


def test_a_child_span_is_not_its_parents_open_phase():
    tl = T.Tally(Tracer(), 1)
    with T.bind(tl):
        saved = tl.push(0)
        with T.phase("join-plan"):
            inner = tl.push(1)  # a child plan node executes inside it
            time.sleep(0.01)
            child = tl.pop(inner)
        parent = tl.pop(saved)
    assert child["host_ms"] == {} and child["launch_ms_by"] == {}
    assert parent["host_ms"]["join-plan"] < 5.0  # not the child's 10 ms


@pytest.mark.parametrize("query", ["query36", "query3", "query7"])
def test_a_spans_named_time_never_exceeds_its_exclusive_time(host_run, query):
    ops, results = _own_spans(host_run, query)
    assert len(results) == 1 and len(ops) >= 5
    roots = sum(e["dur_ms"] for e in ops if e["depth"] == 0)
    spans = ops + [dict(e, excl_ms=e["dur_ms"] - roots) for e in results]
    named_total = 0.0
    for e in spans:
        assert sum(v for k, v in e["launch_ms_by"].items()
                   if not k.startswith("eager:")) == pytest.approx(
            e["launch_ms"], abs=0.01)
        assert set(e["host_ms"]) <= set(T.PHASES)
        assert set(e["compile_ms"]) <= set(T.COMPILE_STAGES)
        assert all(v >= 0 for f in ("launch_ms_by", "compile_ms", "host_ms")
                   for v in e[f].values())
        named = (e["read_wait_ms"] + sum(e["launch_ms_by"].values())
                 + sum(e["compile_ms"].values()) + sum(e["host_ms"].values()))
        # rounding to the microsecond, field by field
        assert named <= e["excl_ms"] + 0.02, (e["node"], named, e["excl_ms"])
        named_total += named
    # and the seams cover the statement: the remainder is the smaller part
    assert named_total >= 0.75 * results[0]["dur_ms"]
    eager = {k for e in spans for k in e.get("eager_calls") or ()}
    assert eager and {f"eager:{k}" for k in eager} >= {
        k for e in spans for k in e["launch_ms_by"] if k.startswith("eager:")}
    assert not any(k.startswith("eager:")
                   for e in spans for k in e["launches"])


def test_query36_builds_its_pipelines_once(host_run):
    """What `compiles.window` 0 could not see and `compile_ms` said (PR 41:
    the warm execution traced and built the Pipeline above the ROLLUP
    again, ROADMAP A3): the ROLLUP's levels share their base columns'
    dictionary objects, `_share_dictionary` hands those objects back, and
    the executable keyed by their identity is found again (PR 42)."""
    ops, results = _own_spans(host_run, "query36")
    assert not any(e["compile_ms"] for e in ops + results)
    assert not any(e["host_ms"].get("pipeline-build") for e in ops)
    memo = {}
    for e in ops + results:
        for k, n in (e.get("dict_memo") or {}).items():
            memo[k] = memo.get(k, 0) + n
    assert memo.get("same", 0) >= 4 and not memo.get("miss")
    hits = [e["hit"] for e in host_run
            if e["kind"] == "exec_cache" and e.get("query") == "query36"]
    assert hits and all(hits)
    ops3, _ = _own_spans(host_run, "query3")
    assert not any(e["compile_ms"] for e in ops3)


@pytest.mark.parametrize("query", ["query36", "query3", "query7"])
def test_host_iv_tiles_its_span_beside_the_reads(host_run, query):
    ops, results = _own_spans(host_run, query)
    reads = [e for e in host_run
             if e["kind"] == "host_read" and e.get("query") == query]
    placed = []
    for e in ops + results:
        lo, hi = e["t0_ns"], e["t0_ns"] + e["dur_ms"] * 1e6
        names = set(e["launch_ms_by"]) | set(e["host_ms"])
        for name, off_us, dur_us in e.get("host_iv", ()):
            assert name in names, (name, e["node"] if "node" in e else "")
            a = e["t0_ns"] + off_us * 1e3
            b = a + dur_us * 1e3
            assert dur_us >= 0 and off_us >= 0
            assert lo - 2e3 <= a and b <= hi + 5e3  # inside its span
            placed.append((a, b, name))
        by_name = {}
        for name, _, dur_us in e.get("host_iv", ()):
            by_name[name] = by_name.get(name, 0.0) + dur_us / 1e3
        for name, ms in {**e["launch_ms_by"], **e["host_ms"]}.items():
            # the pieces of a name add up to its milliseconds
            assert by_name.get(name, 0.0) == pytest.approx(ms, abs=0.25), name
    assert len(placed) > 20
    placed.sort()
    for (a0, b0, n0), (a1, b1, n1) in zip(placed, placed[1:]):
        assert a1 >= b0 - 2e3, (n0, n1)  # 1 us of rounding an end
    for r in reads:
        ra, rb = r["t0_ns"], r["t0_ns"] + r["dur_ms"] * 1e6
        for a, b, name in placed:
            # the two clocks are read one after the other at a span's
            # start and at a read's: tens of microseconds under load
            assert b <= ra + 5e4 or a >= rb - 5e4, (name, r["why"])


def test_no_host_iv_without_a_file_and_no_event_per_launch_or_phase(raw):
    ring = Session()  # the driver's untraced runs: ring-only
    assert ring.tracer.path is None
    collected = Tracer()  # in-memory, no file either
    for tracer in (ring.tracer, collected):
        assert T.Tally(tracer, 1).keep_iv is False
    s = _tpcds_session(raw, collected)
    _run(s, _statements()["query3"], "q")
    spans = [e for e in collected.events
             if e["kind"] in ("op_span", "result_span")]
    assert spans and not any("host_iv" in e for e in spans)
    assert all("launch_ms_by" in e and "host_ms" in e for e in spans)
    # the seams emit nothing of their own: the kinds are the old ones
    assert {e["kind"] for e in collected.events} <= set(EVENT_SCHEMA)
    assert not any(k in ("launch", "phase", "eager")
                   for k in {e["kind"] for e in collected.events})
    launches, _ = _counts(collected.events, "q")
    spans_q = [e for e in spans if e.get("query") == "q"]
    assert sum(launches.values()) > len(spans_q) > 0
    assert sum(len(e.get("eager_calls", ())) for e in spans_q) > 0


def test_with_the_recorder_off_the_seams_read_no_clock(raw, monkeypatch):
    monkeypatch.setenv("NDS_FLIGHT_RECORDER", "off")
    s = Session()
    assert s.tracer is None
    from nds_tpu.schema import get_schemas

    schemas = get_schemas(True)
    for t in ("store_sales", "date_dim", "item"):
        s.register_csv_dir(t, os.path.join(raw, t), schemas[t])
    sql = _statements()["query3"]
    s.sql(sql).collect()  # warm: nothing compiles below
    reads = []

    def counted():
        reads.append(1)
        return 0.0

    from nds_tpu.ops import kernels as K

    monkeypatch.setattr(T, "_perf", counted)
    assert T.current() is None
    with T.phase("dict-merge"), T.eager("concat"):
        pass
    K.mask_count(K.jnp.ones(8, bool))
    assert T.phase("scan") is T.phase("to-arrow")  # one shared nothing
    result = s.sql(sql)
    assert result.executor is None or result.executor.tally is None
    result.table()
    assert result.executor.tally is None
    assert reads == []


def test_the_schema_names_the_new_optional_fields():
    import inspect

    src = inspect.getsource(obs_trace)
    block = src[src.index("EVENT_SCHEMA = {"):src.index('"query_span"')]
    for field in ("launch_ms_by", "compile_ms", "host_ms", "eager_calls",
                  "host_iv"):
        assert field in block, field
    assert set(T.PHASES) == set(obs_trace.HOST_PHASES)
    assert len(T.PHASES) <= 12
    assert T.COMPILE_STAGES == ("trace", "lower", "load", "compile")
    readme = open(os.path.join(REPO, "README.md")).read()
    for name in (*T.PHASES, "launch_ms_by", "compile_ms", "host_ms",
                 "host_iv", "eager_calls", "eager:<site>"):
        assert name in readme, name


def test_real_spans_with_the_new_fields_validate(host_run):
    assert R.validate_events(host_run) == []
    ops, results = _own_spans(host_run, "query36")
    assert any("host_iv" in e for e in ops)
    assert all(isinstance(iv, list) and len(iv) == 3
               for e in ops + results for iv in e.get("host_iv", ()))


NAMED_EVENTS = [
    _ev("query_span", 0, 1000, status="Completed", retries=0),
    _ev("result_span", 50, 900, exec_id=1, exec_ms=880.0, to_arrow_ms=20.0,
        launches={"compact_indices": 1}, launch_ms=4.0, reads=1,
        read_wait_ms=15.0, launch_ms_by={"compact_indices": 4.0},
        compile_ms={}, host_ms={"to-arrow": 1.0}),
    _ev("op_span", 50, 880, exec_id=1, seq=2, depth=0, node="Aggregate",
        explain="", rows=1, est_bytes=0, launches={"take_columns": 12},
        launch_ms=40.0, reads=2, read_wait_ms=300.0,
        launch_ms_by={"take_columns": 40.0, "eager:concat": 100.0},
        eager_calls={"concat": 2}, dict_memo={"same": 8, "miss": 1},
        compile_ms={"trace": 60.0, "load": 40.0},
        host_ms={"pipeline-build": 30.0, "exec-lookup": 5.0,
                 "dict-merge": 20.0}),
    _ev("op_span", 60, 200, exec_id=1, seq=1, depth=1, node="Scan",
        explain="", rows=1, est_bytes=0, launches={}, launch_ms=0.0, reads=0,
        read_wait_ms=0.0, launch_ms_by={}, compile_ms={},
        host_ms={"scan": 198.0}),
    _ev("catalog_load", 61, 190, table="t", columns=1, loaded=1, rows=1,
        cache="miss", read_ms=100.0, encode_ms=50.0, h2d_ms=40.0),
    _ev("exec_cache", 300, 135, pipeline="p", bucket=1024, hit=False),
    _ev("xla_compile", 310, 60, stage="trace", fun="pipe", cached=False,
        exec_id=1, depth=0),
    _ev("aot_cache", 380, 40, op="load", result="hit"),
    _ev("host_read", 600, 250, why="nrows", bytes=4, exec_id=1, depth=0),
    _ev("host_read", 860, 50, why="join_size", bytes=8, exec_id=1, depth=0),
    _ev("host_read", 935, 15, why="collect", bytes=64, exec_id=1, depth=-1),
]


def test_critical_path_opens_host_python_into_the_phases_and_other():
    q = CP.critical_path(NAMED_EVENTS)["queries"]["q"]
    c = q["causes"]
    assert c["device-wait"] == 315.0
    assert (c["jit-trace"], c["cache-load"]) == (60.0, 40.0)
    # from the spans' own fields, nothing subtracted again; the eager
    # seams are a cause of their own and `launch` stays the kernel seams'
    assert (c["launch"], c["eager"]) == (44.0, 100.0)
    # the lookup is a phase now, not the exec_cache event's 135 ms
    assert c["exec-lookup"] == 0.0
    assert (c["read"], c["encode"], c["h2d"]) == (100.0, 50.0, 40.0)
    # the rest of the 900 ms result_span, one cause as ever
    assert c["host-python"] == 900 - (315 + 100 + 190 + 144)
    assert c["plan-host"] == 100.0
    assert sum(c.values()) == pytest.approx(q["wall_ms"])
    hp = q["host_python"]
    assert hp["phases"] == {
        "pipeline-build": 30.0, "exec-lookup": 5.0, "dict-merge": 20.0,
        "to-arrow": 1.0, "scan": 8.0}  # the scan's 198 less its load's 190
    assert hp["other"] == pytest.approx(c["host-python"] - 64.0)
    ops = q["operators"]
    assert set(ops) == {"Aggregate", "Scan", "(collect)"}
    assert ops["Aggregate"]["launch_ms_by"] == {
        "take_columns": 40.0, "eager:concat": 100.0}
    assert ops["Aggregate"]["eager_calls"] == {"concat": 2}
    assert ops["Aggregate"]["excl_ms"] == 680.0
    assert R.host_parts(ops["Aggregate"]) == (300.0, 140.0, 100.0, 55.0, 85.0)
    assert ops["(collect)"]["excl_ms"] == 20.0
    # a log from before the fields has no such record
    assert "host_python" not in CP.critical_path(SPLIT_EVENTS)["queries"]["q"]


def test_profile_cli_prints_the_host_table_for_a_log_with_the_fields(
        tmp_path, capsys):
    import json

    from nds_tpu.cli import profile as profile_cli

    log = tmp_path / "events-x.jsonl"
    log.write_text("".join(json.dumps(e) + "\n" for e in NAMED_EVENTS))
    assert not profile_cli.main(
        [str(log), "--critical-path", "--min_attributed", "0.9"])
    out = capsys.readouterr().out
    for needle in ("   host-python          151.0 ms   15.1%\n"
                   "     pipeline-build      30.0 ms    3.0%\n"
                   "     dict-merge          20.0 ms    2.0%\n",
                   "     other               87.0 ms    8.7%\n",
                   "operator (own ms)", "launch: eager:concat 100.0, "
                   "take_columns 40.0", "compile: trace 60.0, load 40.0",
                   "phases: pipeline-build 30.0, dict-merge 20.0, "
                   "exec-lookup 5.0", "      dict_memo: same 8, miss 1\n",
                   "(collect)"):
        assert needle in out, needle
    assert "\n   exec-lookup " not in out
    assert not profile_cli.main([str(log), "--per_query"])
    out = capsys.readouterr().out
    assert "operator (own ms)" in out and "eager:concat 100.0" in out
    assert out.count("dict_memo: same 8, miss 1") == 2  # the query, the run


GOLDEN_BEFORE_THE_FIELDS = """\
== critical path: 1 queries

-- q: wall 1,000.0 ms  Completed  (attributed 100%)
   device-wait          315.0 ms   31.5%
   launch                50.0 ms    5.0%
   jit-trace             50.0 ms    5.0%
   xla-compile           50.0 ms    5.0%
   cache-load            50.0 ms    5.0%
   exec-lookup           20.0 ms    2.0%
   host-python          185.0 ms   18.5%
   read                  90.0 ms    9.0%
   encode                54.0 ms    5.4%
   h2d                   36.0 ms    3.6%
   plan-host            100.0 ms   10.0%
   launches: take_columns 12, compact_indices 1
   reads: nrows 1 (250.0 ms), join_size 1 (50.0 ms), collect 1 (15.0 ms)
   compiles: pipe 1 (0 fresh, 70.0 ms), gather 1 (1 fresh, 50.0 ms), \
_pad 0 (0 fresh, 20.0 ms), inner 0 (0 fresh, 10.0 ms)
   chain: MultiJoin 880ms -> Scan 200ms
"""


def test_a_log_from_before_the_fields_prints_what_it_printed(tmp_path, capsys):
    """The parent commit's output for SPLIT_EVENTS, to the character."""
    import json

    from nds_tpu.cli import profile as profile_cli

    log = tmp_path / "events-x.jsonl"
    log.write_text("".join(json.dumps(e) + "\n" for e in SPLIT_EVENTS))
    assert not profile_cli.main(
        [str(log), "--critical-path", "--min_attributed", "0.9"])
    assert capsys.readouterr().out == GOLDEN_BEFORE_THE_FIELDS
    assert not profile_cli.main([str(log), "--per_query"])
    assert "operator (own ms)" not in capsys.readouterr().out


def test_merged_profiles_sum_the_new_fields_as_they_sum_launches():
    a = R.profile_events(NAMED_EVENTS)
    b = R.profile_events(NAMED_EVENTS)
    agg = a["queries"]["q"]["ops"]["Aggregate"]
    assert agg["launch_ms_by"] == {"take_columns": 40.0, "eager:concat": 100.0}
    assert agg["read_wait_ms"] == 300.0
    assert a["queries"]["q"]["collect"]["host_ms"] == {"to-arrow": 1.0}
    merged = R.merge_profiles(a, b)
    agg = merged["queries"]["q"]["ops"]["Aggregate"]
    assert agg["launch_ms_by"] == {"take_columns": 80.0, "eager:concat": 200.0}
    assert agg["compile_ms"] == {"trace": 120.0, "load": 80.0}
    assert agg["host_ms"]["pipeline-build"] == 60.0
    assert agg["eager_calls"] == {"concat": 4}
    assert agg["dict_memo"] == {"same": 16, "miss": 2}
    assert merged["op_totals"]["Scan"]["host_ms"] == {"scan": 396.0}
    assert merged["queries"]["q"]["collect"]["count"] == 2
    assert merged["collect_total"]["launch_ms_by"] == {"compact_indices": 8.0}
    old = R.merge_profiles(R.profile_events(SPLIT_EVENTS),
                           R.profile_events(SPLIT_EVENTS))
    assert "launch_ms_by" not in old["queries"]["q"]["ops"]["MultiJoin"]
    assert "collect" not in old["queries"]["q"] and "collect_total" not in old
