"""Failure-domain subsystem: fault injection, the classified retry/
degradation ladder, the query watchdog, atomic report writes, and
checkpointed full_bench resume.

Every recovery path is driven deterministically through the fault registry
(nds_tpu/faults.py) instead of hoping it fires under a real OOM — the
chaos-harness practice the reference gets for free from Spark's scheduler
(executor loss -> task retry; TaskFailureListener chain)."""

import json
import os
import time

import pytest

from nds_tpu import faults
from nds_tpu import full_bench as FB
from nds_tpu.io.fs import fs_open, fs_open_atomic
from nds_tpu.power import gen_sql_from_stream, run_query_stream
from nds_tpu.report import BenchReport
from nds_tpu.engine.session import Session
from shared_data import DATA, raw_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_faults(monkeypatch):
    monkeypatch.delenv("NDS_FAULT_SPEC", raising=False)
    monkeypatch.delenv("NDS_QUERY_TIMEOUT", raising=False)
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# taxonomy + registry units
# ---------------------------------------------------------------------------


def test_classify_taxonomy():
    assert faults.classify("RESOURCE_EXHAUSTED: out of HBM") == faults.DEVICE_OOM
    assert faults.classify(MemoryError()) == faults.HOST_OOM
    assert faults.classify("MemoryError") == faults.HOST_OOM
    assert faults.classify("query watchdog: exceeded budget") == faults.TIMEOUT
    assert faults.classify("OSError: Connection reset by peer") == faults.IO_TRANSIENT
    assert faults.classify(ConnectionResetError("x")) == faults.IO_TRANSIENT
    assert faults.classify("BindError: unknown column foo") == faults.PLANNER
    assert faults.classify("ExecError: bad plan") == faults.PLANNER
    assert faults.classify("ValueError: malformed stream file") == faults.DATA
    assert faults.classify("something else entirely") == faults.UNKNOWN
    # order: the watchdog marker must win over the io "timed out" pattern
    assert faults.classify("query watchdog: timed out") == faults.TIMEOUT
    # injected faults classify like their real counterparts even after the
    # report layer stringifies them
    assert (
        faults.classify("InjectedHostOOM: injected host OOM at 'q1'")
        == faults.HOST_OOM
    )
    # anchored transient patterns: a number or deterministic XLA error
    # containing "503"/"InternalError" must NOT look transient
    assert faults.classify("ValueError: shape (1503, 4) mismatch") == faults.UNKNOWN
    assert faults.classify("XlaRuntimeError: InternalError: crash") == faults.UNKNOWN
    assert faults.classify("HTTP 503 from object store") == faults.IO_TRANSIENT


def test_spec_parse_and_counts():
    r = faults.FaultRegistry.parse("oom:query5:2;io:store_sales;hang:q:30")
    assert [x.kind for x in r.rules] == ["oom", "io", "hang"]
    assert r.rules[0].remaining == 2
    assert r.rules[1].remaining == 1  # default count
    assert r.rules[2].remaining == 1  # hang fires once; arg is seconds
    assert r.rules[2].arg == 30
    # sites may contain ':' — a trailing segment is the arg only if numeric
    r2 = faults.FaultRegistry.parse("oom:exec:query3:2;io:commit:store_sales")
    assert (r2.rules[0].site, r2.rules[0].remaining) == ("exec:query3", 2)
    assert (r2.rules[1].site, r2.rules[1].remaining) == ("commit:store_sales", 1)
    with pytest.raises(ValueError, match="bad fault rule"):
        faults.FaultRegistry.parse("explode:query5")
    with pytest.raises(ValueError, match="bad fault rule"):
        faults.FaultRegistry.parse("oom")


def test_registry_fire_counts_and_kinds():
    faults.install("oom:a:1;io:b:2;crash:c")
    with pytest.raises(faults.InjectedOOM, match="RESOURCE_EXHAUSTED"):
        faults.maybe_fire("a")
    faults.maybe_fire("a")  # count exhausted -> inert
    for _ in range(2):
        with pytest.raises(faults.TransientIOError):
            faults.maybe_fire("b")
    faults.maybe_fire("b")
    with pytest.raises(faults.InjectedCrash):
        faults.maybe_fire("c")
    # crash derives from BaseException so `except Exception` can't eat it
    assert not issubclass(faults.InjectedCrash, Exception)


def test_fire_path_substring_match():
    faults.install("io:store_sales:1")
    with pytest.raises(faults.TransientIOError):
        faults.maybe_fire_path("/wh/store_sales/part-0.parquet")
    faults.maybe_fire_path("/wh/store_sales/part-1.parquet")  # exhausted
    faults.maybe_fire_path("/wh/item/part-0.parquet")  # never matched


def test_install_idempotent_keeps_counts():
    faults.install("oom:a:1")
    with pytest.raises(faults.InjectedOOM):
        faults.maybe_fire("a")
    # same spec re-installed (e.g. a second stream's Session): counts keep
    faults.install("oom:a:1")
    faults.maybe_fire("a")
    # a DIFFERENT spec rebuilds
    faults.install("oom:a:1;oom:z:1")
    with pytest.raises(faults.InjectedOOM):
        faults.maybe_fire("a")


def test_backoff_delays_jitter_bounds():
    ds = list(faults.backoff_delays(4, 0.5, cap=2.0))
    assert len(ds) == 4
    for i, d in enumerate(ds):
        assert 0 <= d <= min(0.5 * 2 ** i, 2.0)
    assert list(faults.backoff_delays(3, 0.0)) == [0.0, 0.0, 0.0]


# ---------------------------------------------------------------------------
# atomic writes + remote-open backoff
# ---------------------------------------------------------------------------


def test_fs_open_atomic_commit_and_discard(tmp_path):
    p = tmp_path / "sub" / "report.json"
    with fs_open_atomic(str(p), "w") as f:
        f.write('{"ok": 1}')
    assert json.load(open(p)) == {"ok": 1}
    # a crash mid-write must leave the previous complete content intact
    with pytest.raises(RuntimeError):
        with fs_open_atomic(str(p), "w") as f:
            f.write('{"torn"')
            raise RuntimeError("simulated crash mid-write")
    assert json.load(open(p)) == {"ok": 1}
    assert [x.name for x in p.parent.iterdir()] == ["report.json"]  # no tmp


def test_fs_open_atomic_remote(tmp_path):
    import fsspec

    url = "memory://atomic_test/report.csv"
    with fs_open_atomic(url, "w") as f:
        f.write("a,b\n1,2\n")
    with fs_open(url) as f:
        assert f.read() == "a,b\n1,2\n"
    fs = fsspec.filesystem("memory")
    assert not [p for p in fs.ls("/atomic_test") if ".tmp-" in str(p)]


def test_remote_open_retries_transient_faults(monkeypatch):
    import fsspec

    monkeypatch.setenv("NDS_IO_BACKOFF", "0")
    monkeypatch.setenv("NDS_IO_RETRIES", "3")
    fs = fsspec.filesystem("memory")
    with fs.open("/retry_test/data.txt", "w") as f:
        f.write("payload")
    faults.install("io:retry_test:2")
    with fs_open("memory://retry_test/data.txt") as f:  # 2 faults then opens
        assert f.read() == "payload"
    # budget exhausted -> the transient error surfaces
    faults.install("io:retry_test2:9")
    with fs.open("/retry_test2/data.txt", "w") as f:
        f.write("x")
    with pytest.raises(faults.TransientIOError):
        fs_open("memory://retry_test2/data.txt")


# ---------------------------------------------------------------------------
# the degradation ladder (BenchReport.report_on)
# ---------------------------------------------------------------------------


def _flaky(sequence):
    """fn failing with sequence[i] on call i (None = succeed)."""
    calls = {"n": 0}

    def fn():
        i = calls["n"]
        calls["n"] += 1
        err = sequence[i] if i < len(sequence) else None
        if err is not None:
            raise err

    fn.calls = calls
    return fn


def test_ladder_oom_recovers_once():
    sess = Session()
    fn = _flaky([faults.InjectedOOM("RESOURCE_EXHAUSTED: injected")])
    s = BenchReport(sess).report_on(fn, retry_oom=True)
    assert s["queryStatus"] == ["CompletedWithTaskFailures"]
    assert s["retries"] == 1
    assert [r["rung"] for r in s["ladder"]] == ["recover_retry"]
    assert len(s["exceptions"]) == 1 and "RESOURCE_EXHAUSTED" in s["exceptions"][0]
    assert "failureKind" not in s
    assert fn.calls["n"] == 2


def test_ladder_oom_exhausts_to_shrunken_window():
    sess = Session()
    oom = lambda: faults.InjectedOOM("RESOURCE_EXHAUSTED: injected")
    fn = _flaky([oom(), oom(), oom()])
    s = BenchReport(sess).report_on(fn, retry_oom=True)
    assert s["queryStatus"] == ["Failed"]
    assert s["failureKind"] == faults.DEVICE_OOM
    assert [r["rung"] for r in s["ladder"]] == [
        "recover_retry", "shrink_union_window",
    ]
    # the degraded blocked-union window persists on the session for the
    # rest of the stream
    assert int(sess.conf["engine.union_agg_window_rows"]) > 0
    assert s["retries"] == 2
    # EVERY attempt's error is recorded, not just the last one
    assert len(s["exceptions"]) == 3


def test_ladder_shrink_halves_explicit_window():
    sess = Session(conf={"engine.union_agg_window_rows": 65536})
    oom = lambda: faults.InjectedOOM("RESOURCE_EXHAUSTED: x")
    BenchReport(sess).report_on(_flaky([oom(), oom(), oom()]), retry_oom=True)
    assert sess.conf["engine.union_agg_window_rows"] == 32768


def test_ladder_host_oom_recovers():
    sess = Session()
    fn = _flaky([faults.InjectedHostOOM("injected host OOM at 'q1'")])
    s = BenchReport(sess).report_on(fn, retry_oom=True)
    assert s["queryStatus"] == ["CompletedWithTaskFailures"]
    assert [r["rung"] for r in s["ladder"]] == ["recover_retry"]
    # a second host OOM is terminal (no window shrink: the pressure is on
    # the host, not HBM)
    fn2 = _flaky([faults.InjectedHostOOM("injected host OOM at 'q1'")] * 2)
    s2 = BenchReport(sess).report_on(fn2, retry_oom=True)
    assert s2["queryStatus"] == ["Failed"]
    assert s2["failureKind"] == faults.HOST_OOM


def test_ladder_io_transient_backoff(monkeypatch):
    monkeypatch.setenv("NDS_IO_RETRIES", "2")
    monkeypatch.setenv("NDS_IO_BACKOFF", "0")
    sess = Session()
    fn = _flaky([faults.TransientIOError("injected transient io"),
                 faults.TransientIOError("injected transient io")])
    s = BenchReport(sess).report_on(fn, retry_oom=True)
    assert s["queryStatus"] == ["CompletedWithTaskFailures"]
    assert [r["rung"] for r in s["ladder"]] == [
        "io_backoff_retry", "io_backoff_retry",
    ]
    # a third transient failure would exhaust the 2-retry budget
    fn2 = _flaky([faults.TransientIOError("injected transient io")] * 3)
    s2 = BenchReport(sess).report_on(fn2, retry_oom=True)
    assert s2["queryStatus"] == ["Failed"]
    assert s2["failureKind"] == faults.IO_TRANSIENT


def test_ladder_deterministic_failures_never_retry():
    sess = Session()
    fn = _flaky([ValueError("BindError-ish nope"), None])
    s = BenchReport(sess).report_on(fn, retry_oom=True)
    assert s["queryStatus"] == ["Failed"]
    assert s["retries"] == 0
    assert fn.calls["n"] == 1  # exactly one attempt


def test_ladder_respects_non_idempotent_callers():
    sess = Session()
    fn = _flaky([faults.InjectedOOM("RESOURCE_EXHAUSTED: x"), None])
    s = BenchReport(sess).report_on(fn)  # DML tier: no retry_oom
    assert s["queryStatus"] == ["Failed"]
    assert s["retries"] == 0
    assert fn.calls["n"] == 1


def test_watchdog_timeout_classification():
    sess = Session(conf={"engine.query_timeout": "0.3"})

    def hang():
        time.sleep(3)

    t0 = time.time()
    s = BenchReport(sess).report_on(hang, retry_oom=True)
    elapsed = time.time() - t0
    assert s["queryStatus"] == ["Failed"]
    assert s["failureKind"] == faults.TIMEOUT
    assert s["retries"] == 0  # a hang would likely just hang again
    assert elapsed < 2.5  # the stream moved on well before the 3s hang ended


# ---------------------------------------------------------------------------
# stream-level integration: injected faults inside a real Power Run
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    raw_data()
    # mini warehouse with only the tables the smoke stream touches: the
    # power driver's table setup eagerly reads every .dat dir it finds, and
    # these tests care about failure plumbing, not 25-table ingestion time
    mini = tmp_path_factory.mktemp("mini_wh")
    for t in ("store_sales", "date_dim"):
        os.symlink(os.path.join(DATA, t), mini / t)
    return str(mini)


STREAM = """-- start query 1 in stream 0 using template query96.tpl
select count(*) cnt from store_sales where ss_quantity > 0
;
-- end query 1 in stream 0 using template query96.tpl

-- start query 2 in stream 0 using template query3.tpl
select d_year, count(*) c from date_dim group by d_year order by d_year limit 5
;
-- end query 2 in stream 0 using template query3.tpl
"""


def _run_stream(data_dir, tmp_path, **kw):
    stream = tmp_path / "query_0.sql"
    stream.write_text(STREAM)
    jdir = tmp_path / "json"
    run_query_stream(
        input_prefix=data_dir,
        property_file=None,
        query_dict=gen_sql_from_stream(str(stream)),
        time_log_output_path=str(tmp_path / "time.csv"),
        input_format="csv",
        json_summary_folder=str(jdir),
        **kw,
    )
    out = {}
    for f in os.listdir(jdir):
        s = json.load(open(os.path.join(jdir, f)))
        out[s["query"]] = s
    return out


@pytest.mark.slow
def test_injected_oom_degrades_without_poisoning_stream(data_dir, tmp_path):
    """Acceptance: an injected OOM on one query walks the ladder, the query
    recovers, and the rest of the stream completes untouched."""
    faults.install("oom:query96:1")
    st = _run_stream(data_dir, tmp_path)
    assert st["query96"]["queryStatus"] == ["CompletedWithTaskFailures"]
    assert st["query96"]["retries"] == 1
    assert [r["rung"] for r in st["query96"]["ladder"]] == ["recover_retry"]
    assert any("RESOURCE_EXHAUSTED" in e for e in st["query96"]["exceptions"])
    assert st["query3"]["queryStatus"] == ["Completed"]
    assert st["query3"]["retries"] == 0


@pytest.mark.slow
def test_injected_persistent_oom_records_classified_failure(data_dir, tmp_path):
    faults.install("oom:query96:99")  # never stops OOMing
    st = _run_stream(data_dir, tmp_path)
    assert st["query96"]["queryStatus"] == ["Failed"]
    assert st["query96"]["failureKind"] == faults.DEVICE_OOM
    assert [r["rung"] for r in st["query96"]["ladder"]] == [
        "recover_retry", "shrink_union_window",
    ]
    assert st["query3"]["queryStatus"] == ["Completed"]  # stream unpoisoned


@pytest.mark.slow
def test_injected_hang_becomes_timeout_failure(data_dir, tmp_path):
    """Acceptance: a hung query becomes a classified `timeout` failure and
    the stream's remaining queries still run."""
    faults.install("hang:query96:30")
    st = _run_stream(data_dir, tmp_path, query_timeout=6.0)
    assert st["query96"]["queryStatus"] == ["Failed"]
    assert st["query96"]["failureKind"] == faults.TIMEOUT
    assert st["query3"]["queryStatus"] == ["Completed"]
    # the watchdog cut query96 off at ~6s instead of the 30s hang
    assert st["query96"]["queryTimes"][0] < 15000


@pytest.mark.slow
def test_exec_scoped_injection_site(data_dir, tmp_path):
    """exec:<query> faults fire at the executor root, past parse/bind —
    the engine-internal injection point."""
    faults.install("oom:exec:query3:1")
    st = _run_stream(data_dir, tmp_path)
    assert st["query3"]["queryStatus"] == ["CompletedWithTaskFailures"]
    assert st["query3"]["retries"] == 1
    assert st["query96"]["queryStatus"] == ["Completed"]


def test_gen_sql_malformed_stream_entry(tmp_path):
    p = tmp_path / "query_0.sql"
    p.write_text(
        "-- start query 1 in stream 0 using template query42.tpl\n"
        "select 1 as a\n"  # no ';' terminator
    )
    with pytest.raises(ValueError, match="malformed stream file.*query42"):
        gen_sql_from_stream(str(p))


# ---------------------------------------------------------------------------
# checkpointed full_bench resume
# ---------------------------------------------------------------------------


def _stub_phases(monkeypatch, tmp_path, calls):
    """Replace every phase runner with a fake that writes the report files
    the parsers re-read, so orchestrator logic (checkpoint/resume/retry/
    metric math) runs for real without subprocess phases."""

    def note(name):
        calls.append(name)

    def fake_load(params):
        note("load_test")
        with open(params["load_test"]["report_path"], "w") as f:
            f.write("Load Test Time: 10.0 seconds\nRNGSEED used: 123\n")

    def fake_power(params):
        note("power_test")
        with open(params["power_test"]["report_path"], "w") as f:
            f.write("app-1,Power Test Time,60000\n")

    def fake_tt(params, num_streams, which):
        note(f"throughput_test_{which}")
        for n in FB.get_stream_range(num_streams, which):
            with open(f"{params['throughput_test']['report_base_path']}_{n}.csv", "w") as f:
                f.write("app,Power Start Time,100\napp,Power End Time,200\n")

    def fake_dm(params, num_streams, which):
        note(f"maintenance_test_{which}")
        for n in FB.get_stream_range(num_streams, which):
            base = params["maintenance_test"]["maintenance_report_base_path"]
            with open(f"{base}_{n}.csv", "w") as f:
                f.write("app,Data Maintenance Time,30\n")

    monkeypatch.setattr(FB, "run_data_gen", lambda p, n: note("data_gen"))
    monkeypatch.setattr(FB, "run_load_test", fake_load)
    monkeypatch.setattr(FB, "gen_streams", lambda p, n, s: note("gen_streams"))
    monkeypatch.setattr(FB, "power_test", fake_power)
    monkeypatch.setattr(FB, "throughput_test", fake_tt)
    monkeypatch.setattr(FB, "maintenance_test", fake_dm)


def _bench_params(tmp_path):
    return {
        "data_gen": {"scale_factor": 1, "parallel": 2,
                     "raw_data_path": str(tmp_path / "raw")},
        "load_test": {"output_path": str(tmp_path / "wh"),
                      "report_path": str(tmp_path / "load.txt")},
        "generate_query_stream": {"num_streams": 3,
                                  "stream_output_path": str(tmp_path / "st")},
        "power_test": {"report_path": str(tmp_path / "power.csv")},
        "throughput_test": {"report_base_path": str(tmp_path / "tt")},
        "maintenance_test": {
            "maintenance_report_base_path": str(tmp_path / "dm")},
        "metrics_report_path": str(tmp_path / "metrics.csv"),
    }


def test_full_bench_crash_then_resume_completes(monkeypatch, tmp_path):
    """Acceptance: with a crash:power_test injection the orchestrator dies
    at its checkpoint; --resume finishes from it, completed phases never
    re-run, and metrics.csv matches an uninterrupted run."""
    calls = []
    _stub_phases(monkeypatch, tmp_path, calls)
    params = _bench_params(tmp_path)
    faults.install("crash:power_test")
    with pytest.raises(faults.InjectedCrash):
        FB.run_full_bench(params)
    state_file = tmp_path / "bench_state.json"
    assert state_file.exists()
    done = set(json.load(open(state_file))["phases"])
    assert done == {"data_gen", "load_test", "gen_streams"}
    assert not (tmp_path / "metrics.csv").exists()

    # operator reruns with --resume (fault spec cleared)
    faults.reset()
    calls.clear()
    metrics = FB.run_full_bench(params, resume=True)
    # checkpointed phases were NOT re-run; the rest ran exactly once
    assert calls == ["power_test", "throughput_test_1", "maintenance_test_1",
                     "throughput_test_2", "maintenance_test_2"]
    assert metrics["perf_metric"] > 0

    # identical to an uninterrupted run over the same (stubbed) phase times
    clean = tmp_path / "clean"
    clean.mkdir()
    calls.clear()
    m2 = FB.run_full_bench(_bench_params(clean))
    assert m2["perf_metric"] == metrics["perf_metric"]
    got = (tmp_path / "metrics.csv").read_text()
    want = (clean / "metrics.csv").read_text()
    assert {l.split(",")[0]: l for l in got.splitlines()} == {
        l.split(",")[0]: l for l in want.splitlines()
    }


def test_full_bench_phase_transient_retry(monkeypatch, tmp_path):
    """A classified-transient phase failure retries within budget instead
    of killing the run."""
    calls = []
    _stub_phases(monkeypatch, tmp_path, calls)
    monkeypatch.setenv("NDS_PHASE_RETRIES", "2")
    monkeypatch.setenv("NDS_PHASE_BACKOFF", "0")
    params = _bench_params(tmp_path)
    faults.install("io:power_test:2")  # fails twice, third attempt clean
    metrics = FB.run_full_bench(params)
    assert metrics["perf_metric"] > 0
    assert calls.count("power_test") == 1  # faults fired before the runner
    state = json.load(open(tmp_path / "bench_state.json"))
    assert "power_test" in state["phases"]


def test_full_bench_phase_deterministic_failure_no_retry(monkeypatch, tmp_path):
    calls = []
    _stub_phases(monkeypatch, tmp_path, calls)
    monkeypatch.setenv("NDS_PHASE_RETRIES", "3")

    def boom(params):
        calls.append("power_test")
        raise RuntimeError("query produced wrong answer")  # not transient

    monkeypatch.setattr(FB, "power_test", boom)
    with pytest.raises(FB.PhaseError, match="power_test.*unknown"):
        FB.run_full_bench(_bench_params(tmp_path))
    assert calls.count("power_test") == 1


def test_bench_state_fingerprint_mismatch(monkeypatch, tmp_path):
    calls = []
    _stub_phases(monkeypatch, tmp_path, calls)
    params = _bench_params(tmp_path)
    FB.run_full_bench(params)
    params2 = dict(params, metrics_report_path=str(tmp_path / "metrics.csv"))
    params2["data_gen"] = dict(params["data_gen"], scale_factor=100)
    with pytest.raises(ValueError, match="different.*config"):
        FB.run_full_bench(params2, resume=True)


def test_bench_state_resume_without_checkpoint(monkeypatch, tmp_path):
    calls = []
    _stub_phases(monkeypatch, tmp_path, calls)
    metrics = FB.run_full_bench(_bench_params(tmp_path), resume=True)
    assert metrics["perf_metric"] > 0  # missing checkpoint == fresh run


# ---------------------------------------------------------------------------
# process-mode stream watchdog budget
# ---------------------------------------------------------------------------


def test_stream_wait_budget(monkeypatch):
    from nds_tpu.throughput import stream_wait_budget

    monkeypatch.delenv("NDS_STREAM_TIMEOUT", raising=False)
    monkeypatch.delenv("NDS_QUERY_TIMEOUT", raising=False)
    assert stream_wait_budget() is None  # unbounded by default
    assert stream_wait_budget(query_timeout=10, n_queries=5) == 10 * 5 + 600
    monkeypatch.setenv("NDS_QUERY_TIMEOUT", "2")
    assert stream_wait_budget(n_queries=103) == 2 * 103 + 600
    monkeypatch.setenv("NDS_STREAM_TIMEOUT", "42")
    assert stream_wait_budget(query_timeout=10) == 42
