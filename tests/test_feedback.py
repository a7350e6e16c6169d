"""Estimate-vs-actual cardinality feedback (analysis/feedback.py): the
contract is "a learned cardinality can sharpen a verdict, never corrupt
one" — the two-run gate proves budgeter error is a measured, SHRINKING
number (run 1 records, run 2 consumes, a misestimated plan's verdict
flips and the median |log(est/actual)| strictly drops), and the store
units prove the persistence discipline (corruption quarantines as a
miss, a foreign key is a clean miss, two processes share one dir, dead
temps sweep, the LRU byte budget holds)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.analysis import feedback as FB
from nds_tpu.engine.session import Session

FP_A = "a" * 40
FP_B = "b" * 40


def _store(tmp_path, budget=1 << 30):
    return FB.FeedbackStore(str(tmp_path / "fb"), budget)


def _misest_table(n=200_000, seed=5):
    """A table whose `k < 10` selectivity the static model misestimates
    by orders of magnitude: 50k distinct keys means the filter keeps
    ~n/5000 rows while the conjunction floor models vastly more."""
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": rng.integers(0, 50_000, n).astype(np.int64),
        "v": rng.random(n),
    })


def _gate_session(tmp_path, mode, table=None, budget_bytes=8 << 20):
    s = Session(conf={
        "engine.feedback_dir": str(tmp_path / "fb"),
        "engine.plan_feedback": mode,
        "engine.plan_budget": "warn",
        "engine.plan_budget_bytes": budget_bytes,
    })
    s.register_arrow("t", table if table is not None else _misest_table())
    return s


GATE_Q = "select k, sum(v) s from t where k < 10 group by k order by k"


# ---------------------------------------------------------------------------
# the two-run gate: record, then consume; error strictly shrinks
# ---------------------------------------------------------------------------


def test_two_run_gate_verdict_flips_and_error_shrinks(tmp_path):
    """Run 1 (record): the static model's misestimate forces a `spill`
    verdict and records the actuals. Run 2 (on): the recorded actuals
    override the estimates, the verdict flips to `direct`, the result is
    identical, and the median |log(est/actual)| is STRICTLY smaller —
    the ISSUE 18 acceptance assertion."""
    s1 = _gate_session(tmp_path, "record")
    out1 = s1.sql(GATE_Q).to_pylist()
    pb1 = s1.last_plan_budget
    assert pb1["feedback_mode"] == "record"
    assert pb1["feedback_overrides"] == 0  # record NEVER changes estimates
    assert pb1["verdict"] == "spill", pb1
    med1, _mx1, n1 = s1.feedback_store.err_stats()
    assert n1 > 0 and med1 is not None
    entries, nbytes = s1.feedback_store.usage()
    assert entries > 0 and nbytes > 0

    s2 = _gate_session(tmp_path, "on")
    out2 = s2.sql(GATE_Q).to_pylist()
    pb2 = s2.last_plan_budget
    assert out2 == out1  # feedback may replan, never change answers
    assert pb2["feedback_hits"] > 0
    assert pb2["feedback_overrides"] >= 1
    assert pb2["verdict"] == "direct", pb2  # measured rows fit the budget
    assert pb2["peak_bytes"] < pb1["peak_bytes"]
    med2, _mx2, n2 = s2.feedback_store.err_stats()
    assert n2 > 0
    assert med2 < med1, (med1, med2)  # the error is a SHRINKING number


def test_feedback_off_is_static_and_silent(tmp_path):
    """Mode `off`: no store lookups, no recording, no annotations — the
    pre-feedback static model, byte-for-byte."""
    s = _gate_session(tmp_path, "off")
    s.sql(GATE_Q).to_pylist()
    pb = s.last_plan_budget
    assert pb["feedback_mode"] == "off"
    assert pb["feedback_hits"] == 0 and pb["feedback_overrides"] == 0
    assert not os.path.isdir(str(tmp_path / "fb"))  # nothing ever written


def test_scale_tag_change_invalidates_into_clean_miss(tmp_path):
    """Re-registering the table with DIFFERENT data (row count) changes
    the scale tag, so run 2's keys miss instead of consuming stale
    cardinalities recorded against the old data."""
    s1 = _gate_session(tmp_path, "record")
    s1.sql(GATE_Q).to_pylist()
    assert s1.feedback_store.usage()[0] > 0
    # same query, same store dir, but the table is a different size
    s2 = _gate_session(tmp_path, "on", table=_misest_table(n=100_000))
    s2.sql(GATE_Q).to_pylist()
    pb = s2.last_plan_budget
    assert pb["feedback_hits"] == 0 and pb["feedback_overrides"] == 0
    assert s2.feedback_store.stats["misses"] > 0


def test_mode_resolution_and_validation(monkeypatch):
    assert FB.resolve_feedback_mode({}) == "record"  # the default
    assert FB.resolve_feedback_mode({"engine.plan_feedback": "on"}) == "on"
    monkeypatch.setenv("NDS_PLAN_FEEDBACK", "off")
    assert FB.resolve_feedback_mode({}) == "off"
    with pytest.raises(ValueError):
        FB.resolve_feedback_mode({"engine.plan_feedback": "always"})
    monkeypatch.setenv("NDS_FEEDBACK_DIR", "0")
    assert FB.resolve_feedback_dir({}) is None  # "0" disables the store
    monkeypatch.setenv("NDS_FEEDBACK_DIR", "/some/dir")
    assert FB.resolve_feedback_dir({}) == "/some/dir"
    assert FB.resolve_feedback_dir(
        {"engine.feedback_dir": "/conf/dir"}
    ) == "/conf/dir"  # conf wins over env


# ---------------------------------------------------------------------------
# store units: the aot-cache persistence discipline, re-proven here
# ---------------------------------------------------------------------------


def test_record_flush_lookup_roundtrip(tmp_path):
    st = _store(tmp_path)
    err = st.record(FP_A, rows=1000, nbytes=8000, est_rows=10)
    assert err == pytest.approx(abs(np.log(10) - np.log(1000)))
    st.record(FP_A, rows=1200, nbytes=9600, est_rows=10)
    st.record_skew(FP_A, 5.16, retries=2)
    assert st.flush() == 1
    # a FRESH store instance (new process stand-in) reads it back
    st2 = _store(tmp_path)
    rec = st2.lookup(FP_A)
    assert rec["rows"]["n"] == 2
    assert rec["rows"]["max"] == 1200 and rec["rows"]["min"] == 1000
    assert rec["skew"]["max"] == pytest.approx(5.16)
    assert rec["skew"]["retries"] == 2
    assert st2.lookup(FP_B) is None
    assert st2.stats["hits"] == 1 and st2.stats["misses"] == 1
    assert st2.hit_rate() == 0.5


def test_corrupt_entry_quarantines_as_miss(tmp_path):
    st = _store(tmp_path)
    st.record(FP_A, rows=7, est_rows=7)
    st.flush()
    [name] = [n for n in os.listdir(st.dir) if n.startswith("fb-")]
    path = os.path.join(st.dir, name)
    with open(path, "wb") as f:
        f.write(b"{torn json" + os.urandom(16))
    st2 = _store(tmp_path)
    assert st2.lookup(FP_A) is None  # a miss, never a crash
    assert st2.stats["quarantined"] == 1
    names = os.listdir(st.dir)
    assert not any(n.startswith("fb-") for n in names)
    assert any(n.startswith("quarantine-") for n in names)
    # checksum mismatch (valid JSON, tampered body) quarantines too
    st2.record(FP_A, rows=7, est_rows=7)
    st2.flush()
    with open(path, "rb") as f:
        doc = json.loads(f.read())
    doc["body"]["rows"]["max"] = 999999
    with open(path, "wb") as f:
        f.write(json.dumps(doc).encode())
    st3 = _store(tmp_path)
    assert st3.lookup(FP_A) is None
    assert st3.stats["quarantined"] == 1


def test_foreign_key_is_clean_miss_not_quarantine(tmp_path):
    """A valid document whose embedded key is another fp (filename-hash
    collision stand-in): a clean miss — real data is never destroyed."""
    st = _store(tmp_path)
    st.record(FP_A, rows=7, est_rows=7)
    st.flush()
    src = os.path.join(st.dir, FB._entry_name(FP_A))
    os.rename(src, os.path.join(st.dir, FB._entry_name(FP_B)))
    st2 = _store(tmp_path)
    assert st2.lookup(FP_B) is None
    assert st2.stats["quarantined"] == 0
    assert os.path.exists(os.path.join(st.dir, FB._entry_name(FP_B)))


def test_two_process_share_through_one_dir(tmp_path):
    """A child PROCESS records and flushes; the parent's store sees the
    merged record — the serve-fleet sharing contract, minus jax."""
    st = _store(tmp_path)
    st.record(FP_A, rows=100, est_rows=10)
    st.flush()
    script = textwrap.dedent(f"""
        from nds_tpu.analysis.feedback import FeedbackStore
        st = FeedbackStore({str(tmp_path / "fb")!r}, 1 << 30)
        st.record({FP_A!r}, rows=400, est_rows=10)
        st.record_skew({FP_A!r}, 3.5, retries=1)
        assert st.flush() == 1
        print("SHARED")
    """)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    p = subprocess.run(
        [sys.executable, "-c", script], env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "SHARED" in p.stdout
    st2 = _store(tmp_path)
    rec = st2.lookup(FP_A)
    assert rec["rows"]["n"] == 2  # parent's + child's observations merged
    assert rec["rows"]["max"] == 400
    assert rec["skew"]["retries"] == 1
    assert not any(".tmp-" in n for n in os.listdir(st.dir))


def test_vacuum_sweeps_dead_temps_and_quarantines(tmp_path):
    st = _store(tmp_path)
    st.record(FP_A, rows=7)
    st.flush()
    dead = os.path.join(st.dir, f"{FB._entry_name(FP_B)}.tmp-999999-aa")
    with open(dead, "wb") as f:
        f.write(b"torn")
    live = os.path.join(st.dir, f"{FB._entry_name(FP_B)}.tmp-{os.getpid()}-bb")
    with open(live, "wb") as f:
        f.write(b"in-flight")
    quar = os.path.join(st.dir, f"quarantine-{FB._entry_name(FP_B)}.1")
    with open(quar, "wb") as f:
        f.write(b"bad")
    removed = st.vacuum()
    assert removed == 2  # the dead temp + the quarantine; never the live
    assert os.path.exists(live) and not os.path.exists(dead)
    assert not os.path.exists(quar)
    assert st.lookup(FP_A) is not None  # committed entries survive
    os.unlink(live)
    assert st.vacuum(drop_all=True) >= 1
    assert st.usage() == (0, 0)
    st2 = _store(tmp_path)
    assert st2.lookup(FP_A) is None


def test_lru_eviction_holds_byte_budget(tmp_path):
    st = _store(tmp_path)
    st.record(FP_A, rows=7, est_rows=7)
    assert st.flush() == 1
    _, size_a = st.usage()
    # budget admits ~one entry: the NEXT flush must evict the older one
    st.budget = int(size_a * 1.5)
    old = os.path.join(st.dir, FB._entry_name(FP_A))
    os.utime(old, (1, 1))  # backdate: FP_A is the LRU victim
    st.record(FP_B, rows=9, est_rows=9)
    assert st.flush() == 1
    assert st.stats["evictions"] >= 1
    assert not os.path.exists(old)
    names = [n for n in os.listdir(st.dir) if n.startswith("fb-")]
    assert names == [FB._entry_name(FP_B)]
    entries, total = st.usage()
    assert entries == 1 and total <= st.budget


# ---------------------------------------------------------------------------
# when the store is written: never by a statement; by Session.close(), by
# the exit hook, and (a report) by usage()
# ---------------------------------------------------------------------------


def _fb_files(tmp_path):
    d = str(tmp_path / "fb")
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


def _traced_gate_session(tmp_path, mode="record"):
    from nds_tpu.obs.trace import Tracer

    s = _gate_session(tmp_path, mode)
    s.tracer = Tracer(None)
    return s


def test_a_statement_writes_no_file(tmp_path):
    """`collect()` (and `to_pylist`, `table`) record into memory only: the
    store directory does not even exist after three executions, and no
    `feedback_flush` span was emitted, inside a `result_span` or out."""
    s = _traced_gate_session(tmp_path)
    s.sql(GATE_Q).collect()
    s.sql(GATE_Q).to_pylist()
    s.sql(GATE_Q).table()
    assert _fb_files(tmp_path) == []
    assert s.feedback_store.stats["records"] > 0
    assert s.feedback_store.stats["flushes"] == 0
    kinds = [e["kind"] for e in s.tracer.events]
    assert kinds.count("result_span") == 3
    assert "feedback_flush" not in kinds
    s.close()


def test_lookup_before_any_flush_returns_the_merged_record(tmp_path):
    """What the directory holds with what the process has recorded since
    folded over it: two observations on disk, two pending, nothing written
    in between, and the store's cached record not touched by the fold."""
    st = _store(tmp_path)
    st.record(FP_A, rows=100, nbytes=800, est_rows=10)
    st.record(FP_A, rows=300, nbytes=2400, est_rows=10)
    assert st.flush() == 1
    before = _fb_files(tmp_path)
    mtime = os.stat(os.path.join(st.dir, before[0])).st_mtime_ns
    st.record(FP_A, rows=50, est_rows=10)
    st.record(FP_A, rows=700, est_rows=10)
    st.record_skew(FP_A, 4.25, retries=3)
    st.record(FP_B, rows=9)
    rec = st.lookup(FP_A)
    assert rec["rows"]["n"] == 4
    assert (rec["rows"]["min"], rec["rows"]["max"]) == (50, 700)
    assert rec["rows"]["last"] == 700
    assert rec["bytes"]["n"] == 2  # the pending delta carried no bytes
    assert rec["skew"] == {"n": 1, "last": 4.25, "max": 4.25, "retries": 3}
    assert st.lookup(FP_B)["rows"]["max"] == 9  # pending alone is a hit
    assert st.lookup("c" * 40) is None
    assert st.lookup(FP_A) == rec  # the fold did not leak into `_mem`
    assert _fb_files(tmp_path) == before
    assert os.stat(os.path.join(st.dir, before[0])).st_mtime_ns == mtime
    # and the flush commits exactly what lookup answered
    assert st.flush() == 2
    fresh = _store(tmp_path).lookup(FP_A)
    fresh.pop("updated")
    rec.pop("updated")
    assert fresh == rec


def test_sessions_of_one_process_share_what_is_pending(tmp_path):
    """The buffer is one a directory and process: a second Session plans
    from the first one's actuals with no file in between (what a Throughput
    Run's streams in one process, and the two-run tests, rely on)."""
    s1 = _gate_session(tmp_path, "record")
    out1 = s1.sql(GATE_Q).to_pylist()
    assert _fb_files(tmp_path) == []
    s2 = _gate_session(tmp_path, "on")
    out2 = s2.sql(GATE_Q).to_pylist()
    assert out2 == out1
    assert s2.last_plan_budget["feedback_overrides"] >= 1
    assert s2.last_plan_budget["verdict"] == "direct"
    assert _fb_files(tmp_path) == []
    # either session's close writes the directory's whole buffer, once
    assert s2.close() > 0
    assert s1.close() == 0


def test_close_writes_the_touched_keys_once(tmp_path):
    """`Session.close()` commits exactly the keys the session's statements
    touched, as one `feedback_flush` span outside every `result_span`; a
    second close writes nothing and emits nothing; a statement run after a
    close is written by the next one."""
    s = _traced_gate_session(tmp_path)
    s.sql(GATE_Q).collect()
    touched = {e["node_fp"] for e in s.tracer.events
               if e["kind"] == "op_span" and e.get("node_fp")}
    assert touched
    n = s.close()
    assert n == len(touched)
    assert set(_fb_files(tmp_path)) == {FB._entry_name(fp) for fp in touched}
    (span,) = [e for e in s.tracer.events if e["kind"] == "feedback_flush"]
    assert span["where"] == "close" and span["keys"] == n
    assert span["bytes"] == sum(
        os.path.getsize(os.path.join(s.feedback_store.dir, f))
        for f in _fb_files(tmp_path))
    assert span["dur_ms"] >= 0 and span["t0_ns"] > 0
    results = [e for e in s.tracer.events if e["kind"] == "result_span"]
    assert all(e["t0_ns"] + e["dur_ms"] * 1e6 <= span["t0_ns"]
               for e in results)
    stamp = {f: os.stat(os.path.join(s.feedback_store.dir, f)).st_mtime_ns
             for f in _fb_files(tmp_path)}
    assert s.close() == 0
    assert sum(e["kind"] == "feedback_flush" for e in s.tracer.events) == 1
    assert stamp == {
        f: os.stat(os.path.join(s.feedback_store.dir, f)).st_mtime_ns
        for f in _fb_files(tmp_path)}
    # not terminal: the session works on, and its next close writes again
    # (fewer keys: the plan-result cache answers part of the second plan)
    s.sql(GATE_Q).collect()
    assert 0 < s.close() <= n
    st = _store(tmp_path)
    assert sorted(st.lookup(fp)["rows"]["n"] for fp in touched)[-1] == 2


def test_a_closed_tracer_is_not_written_to(tmp_path, capsys):
    """The exit hook may run after the session's owner closed its tracer
    (the benchmark's children do): the flush writes its files and emits
    nothing, instead of tripping the tracer's emit-after-close warning."""
    s = _traced_gate_session(tmp_path)
    s.sql(GATE_Q).collect()
    s.tracer.close()
    assert s.close(where="atexit") > 0
    assert "after close()" not in capsys.readouterr().out
    assert _fb_files(tmp_path)


def test_a_child_that_never_closes_leaves_its_records(tmp_path):
    """The `atexit` path: a child PROCESS runs a statement in a Session and
    returns from its main without `close()`; nothing is on disk while it
    runs its statement, and the parent reads its records afterwards."""
    script = textwrap.dedent(f"""
        import os
        import numpy as np, pyarrow as pa
        from nds_tpu.engine.session import Session
        fb = {str(tmp_path / "fb")!r}
        s = Session(conf={{"engine.feedback_dir": fb,
                          "engine.plan_feedback": "record"}})
        rng = np.random.default_rng(5)
        s.register_arrow("t", pa.table({{
            "k": rng.integers(0, 50, 1000).astype(np.int64),
            "v": rng.random(1000)}}))
        s.sql("select k, sum(v) s from t where k < 10 group by k").collect()
        assert not os.path.isdir(fb), os.listdir(fb)
        print("RECORDS", s.feedback_store.stats["records"])
    """)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path),
               JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", script], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "RECORDS" in p.stdout
    names = _fb_files(tmp_path)
    assert names and all(n.startswith("fb-") and n.endswith(".json")
                         for n in names)  # whole entries, no temps
    st = _store(tmp_path)
    recs = [st.lookup(n[len("fb-"):-len(".json")]) for n in names]
    assert all(r is not None and r["rows"]["n"] == 1 for r in recs)
    assert st.stats["quarantined"] == 0


def _budget_fixture(tmp_path, n=12):
    """A store of `n` entries of equal size with distinct, shuffled,
    backdated mtimes, at its budget: room for exactly `n` entries."""
    st = _store(tmp_path)
    fps = [f"{i:02d}" + "e" * 38 for i in range(n)]
    for fp in fps:
        st.record(fp, rows=7, est_rows=7)
    assert st.flush() == n
    order = list(range(n))
    np.random.default_rng(3).shuffle(order)
    for age, i in enumerate(order):
        path = os.path.join(st.dir, FB._entry_name(fps[i]))
        os.utime(path, (1000 + age, 1000 + age))
    sizes = {os.path.getsize(os.path.join(st.dir, FB._entry_name(fp)))
             for fp in fps}
    assert len(sizes) == 1
    st.budget = n * sizes.pop()
    return st, fps, [fps[i] for i in order]  # oldest first


def _listing_evicts(st, keep):
    """What the listing-based enforcement this store had before removed,
    in order: every flush listed the directory, summed it, and unlinked by
    (mtime, size, name) until the sum fit, never an entry just written."""
    entries = st._entries()
    total = sum(e[1] for e in entries)
    out = []
    for _mtime, size, name, _path in sorted(entries):
        if total <= st.budget:
            break
        if name in keep:
            continue
        out.append(name)
        total -= size
    return out


@pytest.mark.parametrize("warm_total", [False, True],
                         ids=["first_flush_lists", "running_total"])
def test_running_total_evicts_what_the_listing_did(
        tmp_path, monkeypatch, warm_total):
    """On a store at its budget, a flush of three new keys evicts the same
    entries, in the same order, as listing the directory at every flush
    did — whether the total comes from this flush's own listing (a store's
    first flush) or from the running total passing the budget."""
    st, fps, oldest_first = _budget_fixture(tmp_path)
    if not warm_total:
        st = _store(tmp_path, budget=st.budget)  # no total yet
    else:
        assert st._total is not None and st._total <= st.budget
    new = ["f" * 38 + f"{i:02d}" for i in range(3)]
    for fp in new:
        st.record(fp, rows=7, est_rows=7)
    # the reference needs the directory as it is just after the writes
    unlinked = []
    real_unlink = os.unlink
    want = []

    def spy(path):
        if os.path.basename(path).startswith("fb-") and ".tmp-" not in path:
            if not unlinked:
                want.extend(_listing_evicts(
                    st, {FB._entry_name(fp) for fp in new}))
            unlinked.append(os.path.basename(path))
        real_unlink(path)

    monkeypatch.setattr(os, "unlink", spy)
    listings = st.stats["listings"]
    assert st.flush() == 3
    monkeypatch.undo()
    assert unlinked == want
    assert unlinked == [FB._entry_name(fp) for fp in oldest_first[:3]]
    assert st.stats["evictions"] == 3
    assert st.stats["listings"] == listings + 1
    left = {n for n in os.listdir(st.dir) if n.startswith("fb-")}
    assert left == {FB._entry_name(fp) for fp in oldest_first[3:] + new}
    assert st._total == sum(
        os.path.getsize(os.path.join(st.dir, n)) for n in left)
    assert st._total <= st.budget


def test_a_flush_under_budget_does_not_list_the_directory(tmp_path):
    """One listing at a store's first flush; after it a flush costs what
    it writes, whatever the directory holds: no listing, no stat."""
    st = _store(tmp_path)
    for i in range(40):
        st.record(f"{i:02d}" + "d" * 38, rows=i, est_rows=1)
    assert st.flush() == 40
    assert st.stats["listings"] == 1
    total = st._total
    assert total == sum(
        os.path.getsize(os.path.join(st.dir, n)) for n in os.listdir(st.dir))
    calls = []
    real = st._entries
    st._entries = lambda: calls.append(1) or real()
    for rnd in range(5):
        st.record(FP_A, rows=rnd, est_rows=1)
        assert st.flush() == 1
    assert calls == [] and st.stats["listings"] == 1
    assert st._total > total  # every write counted, whole
    # another process filled the directory: the estimate is corrected by
    # the listing that passing the budget forces
    st.budget = st._total + 10
    st.record(FP_B, rows=1, est_rows=1)
    assert st.flush() == 1
    assert calls == [1]
    assert st._total <= st.budget


POWER_STREAM = """-- start query 1 in stream 0 using template query96.tpl
select count(*) cnt from store_sales where ss_quantity > 0
;
-- end query 1 in stream 0 using template query96.tpl

-- start query 2 in stream 0 using template query3.tpl
select d_year, count(*) c from date_dim group by d_year order by d_year limit 5
;
-- end query 2 in stream 0 using template query3.tpl
"""


@pytest.mark.parametrize("keep_session", [False, True],
                         ids=["stream_owns_session", "caller_keeps_session"])
def test_a_power_stream_writes_its_records_after_its_clock(
        tmp_path, keep_session):
    """`run_query_stream` closes the session once `Power Test Time` has
    been taken: the stream's records are on disk when it returns (whoever
    keeps the session), written by one `feedback_flush` that starts after
    the last statement ended and lies inside no `result_span`."""
    from nds_tpu.obs.reader import iter_events
    from nds_tpu.power import gen_sql_from_stream, run_query_stream
    from shared_data import raw_data

    stream = tmp_path / "query_0.sql"
    stream.write_text(POWER_STREAM)
    props = tmp_path / "p.properties"
    props.write_text(
        f"engine.feedback_dir={tmp_path / 'fb'}\n"
        f"engine.trace_dir={tmp_path / 'trace'}\n")
    session = run_query_stream(
        input_prefix=raw_data(), property_file=str(props),
        query_dict=gen_sql_from_stream(str(stream)),
        time_log_output_path=str(tmp_path / "time.csv"),
        input_format="csv", keep_session=keep_session,
    )
    assert (session is not None) == keep_session
    names = _fb_files(tmp_path)
    assert names and not any(".tmp-" in n for n in names)
    (path,) = (tmp_path / "trace").glob("events-*.jsonl")
    events = list(iter_events(str(path)))
    (span,) = [e for e in events if e["kind"] == "feedback_flush"]
    assert span["where"] == "close" and span["keys"] == len(names)
    ends = [e["t0_ns"] + e["dur_ms"] * 1e6 for e in events
            if e["kind"] in ("result_span", "query_span")]
    assert len(ends) == 4 and max(ends) <= span["t0_ns"]
    touched = {e["node_fp"] for e in events
               if e["kind"] == "op_span" and e.get("node_fp")}
    assert {FB._entry_name(fp) for fp in touched} == set(names)
    if keep_session:
        assert session.close() == 0  # nothing left to write
        session.tracer.close()


def test_concurrent_recorders_lose_no_observation(tmp_path):
    """The directory's buffer is shared by every store of the process:
    eight recording threads (a store each, more than the cores they get),
    one thread that flushes and one that looks up all the while, under a
    short switch interval. Every observation is either on disk or still
    pending at the end: none is lost between a flush's take and a record."""
    import threading

    fps = [f"{i:02d}" + "9" * 38 for i in range(5)]
    per_thread, n_threads = 400, 8
    stop = threading.Event()
    errors = []

    def recorder():
        st = _store(tmp_path)
        try:
            for i in range(per_thread):
                st.record(fps[i % len(fps)], rows=i, nbytes=8 * i, est_rows=1)
        except Exception as exc:  # the assertion below reports it
            errors.append(exc)

    flusher_store = _store(tmp_path)

    def flusher():
        try:
            while not stop.is_set():
                flusher_store.flush()
        except Exception as exc:
            errors.append(exc)

    def reader():
        st = _store(tmp_path)
        try:
            while not stop.is_set():
                for fp in fps:
                    rec = st.lookup(fp)
                    assert rec is None or rec["rows"]["n"] >= 1
        except Exception as exc:
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        helpers = [threading.Thread(target=flusher),
                   threading.Thread(target=reader)]
        recorders = [threading.Thread(target=recorder)
                     for _ in range(n_threads)]
        for t in helpers + recorders:
            t.start()
        for t in recorders:
            t.join(timeout=120)
        stop.set()
        for t in helpers:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in helpers + recorders)
    assert errors == []
    flusher_store.flush()
    fresh = _store(tmp_path)
    counts = [fresh.lookup(fp)["rows"]["n"] for fp in fps]
    assert sum(counts) == per_thread * n_threads
    assert counts == [per_thread * n_threads // len(fps)] * len(fps)
    assert not any(".tmp-" in n for n in os.listdir(fresh.dir))
