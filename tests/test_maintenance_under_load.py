"""Maintenance-under-load tests: the harness that interleaves DM_*
refresh functions (and a lease-safe vacuum) against a live query stream,
plus the full_bench phase wiring and the tracer-lifecycle contract
(reference scenario: Iceberg/Delta maintenance racing queries under
Spark, which the serialized phases never exercised — ROADMAP item 5)."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu import faults
from nds_tpu.engine.session import Session
from nds_tpu.lakehouse.table import LakehouseTable
from nds_tpu.maintenance import _p99_ms, run_maintenance
from shared_data import raw_data, refresh_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# units + wiring (fast)
# ---------------------------------------------------------------------------


def test_p99_nearest_rank():
    assert _p99_ms([]) is None
    assert _p99_ms([5.0]) == 5.0
    assert _p99_ms([1.0, 2.0, 3.0]) == 3.0
    ts = list(range(1, 201))
    assert _p99_ms(ts) == 198  # ceil(0.99*200) = 198th rank


def test_full_bench_phase_registered_and_opt_in():
    from nds_tpu.full_bench import PHASES, maintenance_under_load_test

    assert "maintenance_under_load" in PHASES
    assert PHASES.index("maintenance_under_load") == len(PHASES) - 1
    # opt-in contract: the orchestrator computes skip from `enabled`
    for params, expect_skip in (
        ({}, True),
        ({"maintenance_under_load": {}}, True),
        ({"maintenance_under_load": {"enabled": False}}, True),
        ({"maintenance_under_load": {"enabled": True}}, False),
    ):
        mul_cfg = params.get("maintenance_under_load") or {}
        assert (not mul_cfg.get("enabled")) == expect_skip
    assert callable(maintenance_under_load_test)


def test_cli_routes_under_load_mode(monkeypatch, tmp_path):
    from nds_tpu.cli import maintenance as cli_m

    calls = {}

    def fake_mul(**kw):
        calls.update(kw)

    monkeypatch.setattr(cli_m, "run_maintenance_under_load", fake_mul)
    cli_m.main([
        "/wh", "/refresh", str(tmp_path / "log.csv"),
        "--under_load_stream", "/streams/query_1.sql",
        "--under_load_report", str(tmp_path / "r.json"),
        "--under_load_queries", "query3,query7",
        "--maintenance_queries", "LF_SS,DF_SS",
    ])
    assert calls["stream_file"] == "/streams/query_1.sql"
    assert calls["sub_queries"] == ["query3", "query7"]
    assert calls["spec_queries"] == ["LF_SS", "DF_SS"]
    assert calls["report_path"] == str(tmp_path / "r.json")


def test_dm_statement_level_conflict_retry(monkeypatch):
    """A commit conflict inside a refresh function re-runs ONLY the
    aborted statement (never the whole function — earlier statements
    already committed), bounded by NDS_LAKE_CONFLICT_RETRIES."""
    from nds_tpu.lakehouse.table import CommitConflictError
    from nds_tpu.maintenance import run_dm_query

    monkeypatch.setenv("NDS_LAKE_COMMIT_BACKOFF", "0")
    monkeypatch.setenv("NDS_LAKE_CONFLICT_RETRIES", "2")
    runs = []

    class FakeSession:
        def run_script(self, q):
            runs.append(q)
            if q == "s2" and runs.count("s2") == 1:
                raise CommitConflictError(
                    "concurrent commit conflict at version 4"
                )

    run_dm_query(FakeSession(), ["s1", "s2", "s3"], "LF_X")
    # s1 once, s2 twice (conflict + re-run), s3 once — no whole-function
    # replay
    assert runs == ["s1", "s2", "s2", "s3"]

    # budget exhaustion surfaces the conflict
    class AlwaysConflict:
        def run_script(self, q):
            raise CommitConflictError("concurrent commit conflict at v9")

    with pytest.raises(CommitConflictError):
        run_dm_query(AlwaysConflict(), ["s1"], "LF_Y")


def test_run_maintenance_closes_tracer_in_finally(monkeypatch, tmp_path):
    """PR-8 contract (satellite): the maintenance harness closes its
    session tracer on ANY exit, so a child dying mid-phase leaves a
    complete, foldable event file instead of a dangling handle."""
    import nds_tpu.maintenance as M

    captured = {}
    real_session = M.Session

    def capturing_session(*a, **kw):
        s = real_session(*a, **kw)
        captured["session"] = s
        return s

    monkeypatch.setattr(M, "Session", capturing_session)
    monkeypatch.setenv("NDS_TRACE_DIR", str(tmp_path / "traces"))
    # a bogus refresh path fails fast inside the body (register_refresh_
    # views), which is exactly the mid-phase death the contract covers
    with pytest.raises(FileNotFoundError):
        run_maintenance(
            warehouse_path=str(tmp_path / "wh-missing"),
            refresh_data_path=str(tmp_path / "refresh-missing"),
            time_log_output_path=str(tmp_path / "t.csv"),
            spec_queries=["LF_SS"],
        )
    s = captured["session"]
    assert s.tracer is not None and s.tracer._closed
    # the event file exists and is complete (trace_meta flushed at close)
    files = os.listdir(tmp_path / "traces")
    assert any(f.startswith("events-") for f in files)


# ---------------------------------------------------------------------------
# deterministic interleaving harness (fast, synthetic warehouse)
# ---------------------------------------------------------------------------


def _mini_warehouse(tmp_path, rows=64):
    """A synthetic lakehouse 'warehouse' with one fact-like table."""
    path = str(tmp_path / "fact")
    LakehouseTable.create(
        path,
        pa.table({
            "k": pa.array(np.arange(rows) % 8, type=pa.int64()),
            "v": pa.array(np.arange(rows), type=pa.int64()),
        }),
    )
    s = Session(conf={"lakehouse.warehouse": str(tmp_path)})
    s.register_lakehouse("fact", path)
    return s, path


QUERY = "select k, count(*) c, sum(v) s from fact group by k order by k"


def test_query_stream_pinned_results_invariant_under_dm_commits(tmp_path):
    """The interleaving oracle: a query pinned at version N returns
    bit-identical results whether DM_* commits land before plan time,
    between plan and execution ('during'), or after — under deterministic
    schedule control (no timing luck)."""
    s, path = _mini_warehouse(tmp_path)
    before = s.sql(QUERY).collect()  # no commits yet

    # 'during': plan now (pin v1), land an insert + a delete + a second
    # insert, wipe caches, then execute
    r = s.sql(QUERY)
    writer = LakehouseTable(path)
    writer.append(pa.table({
        "k": pa.array([3], type=pa.int64()),
        "v": pa.array([10_000], type=pa.int64()),
    }))
    kept = writer.snapshot().dataset().to_table().filter(
        pa.compute.less(pa.compute.field("v"), 10)
    )
    writer.replace(kept, operation="delete")
    s.recover_memory("test: no cache luck")
    assert r.collect().equals(before)

    # 'after': a fresh statement sees the post-maintenance state
    after = s.sql(QUERY).collect()
    assert not after.equals(before)
    assert after.num_rows >= 1


def test_interleaved_writer_thread_with_schedule_and_vacuum(tmp_path):
    """Two-thread schedule: the reader pins, signals; the maintenance
    thread appends + vacuums; reader re-executes its pinned statement and
    gets the identical table; its pinned files survived the vacuum."""
    s, path = _mini_warehouse(tmp_path)
    r = s.sql(QUERY)
    baseline = r.collect()
    pinned = threading.Event()
    maintained = threading.Event()
    results = {}

    def maintenance_thread():
        assert pinned.wait(10)
        w = LakehouseTable(path)
        w.append(pa.table({
            "k": pa.array([0], type=pa.int64()),
            "v": pa.array([777], type=pa.int64()),
        }))
        w.replace(w.snapshot().dataset().to_table())  # copy-on-write churn
        results["vacuum"] = w.vacuum(retain_last=1)
        maintained.set()

    t = threading.Thread(target=maintenance_thread, daemon=True)
    t.start()
    pinned.set()
    assert maintained.wait(30)
    t.join(10)
    # the reader's pinned snapshot survived maintenance + vacuum: its
    # lease kept every file it references
    s.recover_memory("test: re-read pinned files post-vacuum")
    assert r.collect().equals(baseline)
    # and the vacuum DID collect something (the un-leased middle version)
    assert results["vacuum"]["manifests_removed"] >= 1


# ---------------------------------------------------------------------------
# SF0.01 end-to-end (slow: runs in ci/tier1-check's standalone gate)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def data_dir():
    return raw_data()


@pytest.fixture(scope="module")
def refresh_dir():
    return refresh_data()


@pytest.fixture(scope="module")
def warehouse(data_dir, tmp_path_factory):
    wh = tmp_path_factory.mktemp("lake_mul")
    subprocess.run(
        [sys.executable, "-m", "nds_tpu.cli.transcode", data_dir, str(wh),
         str(wh / "load.report"), "--output_format", "lakehouse"],
        check=True, capture_output=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    return wh


def _scrape(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as r:
        return r.read().decode()


@pytest.mark.slow
def test_maintenance_under_load_e2e(warehouse, refresh_dir, tmp_path):
    """The full phase at SF0.01: DM functions + vacuum racing a real
    query stream, lake_commit/lake_vacuum events visible in the profile,
    nds_lake_* counters scrapeable from /metrics MID-RUN, and the report
    carrying maintenance throughput x p99 degradation."""
    from nds_tpu.datagen.query_streams import generate_streams
    from nds_tpu.maintenance import run_maintenance_under_load
    from nds_tpu.obs import metrics as M
    from nds_tpu.obs import reader as R

    streams = tmp_path / "streams"
    generate_streams(str(streams), 2, 0.01, rngseed=19620718)
    props = tmp_path / "mul.properties"
    trace_dir = tmp_path / "traces"
    props.write_text(
        "engine.metrics_port=0\n"
        f"engine.trace_dir={trace_dir}\n"
    )
    M.reset_shared()
    report_path = tmp_path / "mul_report.json"
    box = {}

    def run():
        box["report"] = run_maintenance_under_load(
            warehouse_path=str(warehouse),
            refresh_data_path=refresh_dir,
            stream_file=str(streams / "query_1.sql"),
            time_log_output_path=str(tmp_path / "mul_time.csv"),
            report_path=str(report_path),
            property_file=str(props),
            spec_queries=["LF_SS", "DF_SS"],
            sub_queries=["query3", "query7", "query52"],
        )

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # mid-run scrape: wait for the endpoint, then for the first lake
    # commit counters to land while the run is still going
    deadline = time.monotonic() + 300
    exposition = None
    while time.monotonic() < deadline and t.is_alive():
        server = M.active_server()
        if server is not None:
            try:
                text = _scrape(server.port, "/metrics")
            except OSError:
                text = ""
            if "nds_lake_commit_total" in text:
                exposition = text
                break
        time.sleep(0.25)
    t.join(600)
    assert not t.is_alive(), "under-load run did not finish"
    assert exposition is not None, (
        "nds_lake_* counters never appeared on /metrics mid-run"
    )
    assert M.validate_exposition(exposition) == []
    assert "nds_lake_commit_attempts_total" in exposition

    report = box["report"]
    assert report == json.load(open(report_path))
    assert report["dm_functions"] == 2 and report["dm_failed"] == 0
    assert report["under_load_failed"] == 0 and report["solo_failed"] == 0
    assert report["query_p99_ms_solo"] > 0
    assert report["query_p99_ms_under_load"] > 0
    assert report["query_p99_degradation"] > 0
    assert report["dm_functions_per_s"] > 0
    assert report["vacuums"] > 0

    # the profile over the run's event files carries the lake evidence
    files = R.discover_event_files(str(trace_dir))
    assert files
    events = []
    for f in files:
        events.extend(R.iter_events(f))
    prof = R.profile_events(events)
    assert prof["tallies"]["lake_commits"] > 0
    assert prof["tallies"]["lake_vacuums"] > 0
    # time log rows cover solo, under_load and dm entries
    import csv

    rows = list(csv.reader(open(tmp_path / "mul_time.csv")))
    tags = {r[1].split(":")[0] for r in rows[1:] if len(r) >= 2}
    assert {"warmup", "solo", "under_load", "dm"} <= tags
    M.reset_shared()


@pytest.mark.slow
def test_under_load_dm_thread_failure_is_loud(warehouse, refresh_dir,
                                              tmp_path):
    """A maintenance-thread failure (here: an injected io fault escaping
    the under-load vacuum) must not read as a clean completion: the
    report carries dm_error AND the runner raises after writing it."""
    from nds_tpu.maintenance import run_maintenance_under_load

    faults.install("io:vacuum:store_sales:1")
    report_path = tmp_path / "fail_report.json"
    with pytest.raises(RuntimeError, match="DM thread failed"):
        run_maintenance_under_load(
            warehouse_path=str(warehouse),
            refresh_data_path=refresh_dir,
            stream_file=_mini_stream(tmp_path),
            time_log_output_path=str(tmp_path / "fail_time.csv"),
            report_path=str(report_path),
            spec_queries=["LF_SS"],
            sub_queries=["query52"],
        )
    report = json.load(open(report_path))
    assert "TransientIOError" in report["dm_error"]
    assert report["dm_functions"] == 1  # the function itself completed


def _mini_stream(tmp_path):
    from nds_tpu.datagen.query_streams import generate_streams

    d = tmp_path / "mini_streams"
    generate_streams(str(d), 1, 0.01, rngseed=19620718)
    return str(d / "query_0.sql")
