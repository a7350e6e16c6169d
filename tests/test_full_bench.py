"""Whole-benchmark orchestrator: metric math, report parsing, stream
ranges, and a full 8-phase end-to-end run at SF0.01 producing metrics.csv
(reference: nds/nds_bench.py:334-357 metric, :367-497 phase sequencing)."""

import os
import subprocess
import sys

import pytest

from nds_tpu import full_bench as FB
from shared_data import raw_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fast, engine-friendly queries for the smoke streams (real templates are
# exercised by test_query_streams; here the orchestrator is under test)
SMOKE_QUERY = """
select d_year, count(*) c from store_sales, date_dim
where ss_sold_date_sk = d_date_sk group by d_year order by d_year
"""


def test_stream_range():
    assert FB.get_stream_range(9, 1) == [1, 2, 3, 4]
    assert FB.get_stream_range(9, 2) == [5, 6, 7, 8]
    assert FB.get_stream_range(3, 1) == [1]
    assert FB.get_stream_range(3, 2) == [2]
    assert FB.get_throughput_stream_nums(9, 2) == "5,6,7,8"


def test_perf_metric_matches_formula():
    # SF=1, Sq=2: Q=198; all phase times 3600s -> each factor in hours
    m = FB.get_perf_metric(1, 2, 3600, 1800, 900, 900, 450, 450, )
    tpt = (1800 * 2) / 3600
    ttt = (900 + 900) / 3600
    tdm = (450 + 450) / 3600
    tld = (0.01 * 2 * 3600) / 3600
    assert m == int(1 * 198 / (tpt * ttt * tdm * tld) ** 0.25)


def test_report_parsers(tmp_path):
    load = tmp_path / "load.txt"
    load.write_text(
        "Load Test Time: 12.5 seconds\n"
        "Load Test Finished at: 2026-01-01\n"
        "RNGSEED used: 07300207223\n"
    )
    assert FB.get_load_time(str(load)) == 12.5
    assert FB.get_load_end_timestamp(str(load)) == 7300207223
    power = tmp_path / "power.csv"
    power.write_text(
        "application_id,query,time/milliseconds\n"
        "app-1,query1,100\n"
        "app-1,Power Test Time,12345\n"
    )
    assert FB.get_power_time(str(power)) == 12.4
    dm = tmp_path / "dm_1.csv"
    dm.write_text("app-1,Data Maintenance Time,7.5\n")
    assert FB.get_refresh_time(str(dm)) == 7.5
    assert FB.get_maintenance_time(str(tmp_path / "dm"), 3, 1) == 7.5


def test_num_streams_must_be_odd():
    with pytest.raises(ValueError):
        FB.run_full_bench({"generate_query_stream": {"num_streams": 4}})


@pytest.fixture(scope="module")
def data_dir():
    return raw_data()


def _write_stream(path, n_queries=2):
    parts = []
    for i in range(n_queries):
        parts.append(
            f"-- start query {i + 1} in stream 0 using template query3.tpl\n"
            f"{SMOKE_QUERY}\n;\n"
            f"-- end query {i + 1} in stream 0 using template query3.tpl\n"
        )
    with open(path, "w") as f:
        f.write("\n".join(parts))


def test_full_bench_end_to_end(data_dir, tmp_path, monkeypatch):
    """All 8 phases through the real CLIs (subprocess boundaries), metric
    printed and written to metrics.csv."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    num_streams = 3
    for i in (1, 2):
        upd = f"{data_dir}_update{i}"
        if not os.path.isdir(upd):
            subprocess.run(
                [sys.executable, "-m", "nds_tpu.cli.gen_data", "--scale",
                 "0.01", "--parallel", "2", "--data_dir", upd,
                 "--update", str(i), "--overwrite_output"],
                check=True, capture_output=True, cwd=REPO,
            )
    streams = tmp_path / "streams"
    streams.mkdir()
    for n in range(num_streams):
        _write_stream(streams / f"query_{n}.sql")
    params = {
        "data_gen": {
            "scale_factor": 0.01, "parallel": 2,
            "raw_data_path": data_dir, "skip": True,
        },
        "load_test": {
            "output_path": str(tmp_path / "warehouse"),
            "warehouse_format": "lakehouse",
            "report_path": str(tmp_path / "load.txt"),
            "skip": False,
        },
        "generate_query_stream": {
            "num_streams": num_streams,
            "query_template_dir": None,
            "stream_output_path": str(streams),
            "skip": True,  # hand-written smoke streams above
        },
        "power_test": {
            "report_path": str(tmp_path / "power.csv"),
            "property_path": None,
            "output_path": None,
            "skip": False,
        },
        "throughput_test": {
            "report_base_path": str(tmp_path / "throughput"),
            "skip": False,
        },
        "maintenance_test": {
            "maintenance_report_base_path": str(tmp_path / "maintenance"),
            # all 11 functions run in test_maintenance; 2 keep this fast
            "maintenance_queries": "LF_SS,DF_SS",
            "skip": False,
        },
        "metrics_report_path": str(tmp_path / "metrics.csv"),
    }
    monkeypatch.chdir(REPO)
    metrics = FB.run_full_bench(params)
    assert metrics["perf_metric"] > 0
    assert os.path.exists(tmp_path / "metrics.csv")
    content = (tmp_path / "metrics.csv").read_text()
    assert "perf_metric" in content
    # skip/resume: re-run with every phase skipped; times re-read from the
    # report files on disk produce the same metric
    for phase in ("load_test", "power_test", "throughput_test",
                  "maintenance_test"):
        params[phase]["skip"] = True
    metrics2 = FB.run_full_bench(params)
    assert metrics2["perf_metric"] == metrics["perf_metric"]


def test_full_bench_real_generated_streams(data_dir, tmp_path, monkeypatch):
    """The pipeline with REAL generated streams (VERDICT r3 #4): stream
    generation runs for real (skip=False), and the power + throughput
    phases consume the generated stream files (a fast template subset via
    sub_queries), so stream-file -> power-driver integration (template
    ordering, the two-part query14/23/24/39 split) is exercised outside
    the timed bench (reference: nds/nds_bench.py:249-304)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    num_streams = 3
    for i in (1, 2):
        upd = f"{data_dir}_update{i}"
        if not os.path.isdir(upd):
            subprocess.run(
                [sys.executable, "-m", "nds_tpu.cli.gen_data", "--scale",
                 "0.01", "--parallel", "2", "--data_dir", upd,
                 "--update", str(i), "--overwrite_output"],
                check=True, capture_output=True, cwd=REPO,
            )
    subset = ("query3,query7,query12,query15,query19,query26,query42,"
              "query52,query96,query14_part1")
    params = {
        "data_gen": {
            "scale_factor": 0.01, "parallel": 2,
            "raw_data_path": data_dir, "skip": True,
        },
        "load_test": {
            "output_path": str(tmp_path / "warehouse"),
            "warehouse_format": "lakehouse",
            "report_path": str(tmp_path / "load.txt"),
            "skip": False,
        },
        "generate_query_stream": {
            "num_streams": num_streams,
            "query_template_dir": None,
            "stream_output_path": str(tmp_path / "streams"),
            "skip": False,  # REAL stream generation under test
        },
        "power_test": {
            "report_path": str(tmp_path / "power.csv"),
            "property_path": None,
            "output_path": None,
            "sub_queries": subset,
            "skip": False,
        },
        "throughput_test": {
            "report_base_path": str(tmp_path / "throughput"),
            "sub_queries": subset,
            "skip": False,
        },
        "maintenance_test": {
            "maintenance_report_base_path": str(tmp_path / "maintenance"),
            "maintenance_queries": "LF_SS,DF_SS",
            "skip": False,
        },
        "metrics_report_path": str(tmp_path / "metrics.csv"),
    }
    monkeypatch.chdir(REPO)
    metrics = FB.run_full_bench(params)
    assert metrics["perf_metric"] > 0
    # the generated stream files are real 99-template permutations (the
    # two-part templates split into _part1/_part2 at parse time)
    stream0 = (tmp_path / "streams" / "query_0.sql").read_text()
    assert stream0.count("-- start query") == 99
    assert "query14_part1" not in stream0  # parts carry the template name
    # power consumed the generated stream: its log holds the subset queries
    power_log = (tmp_path / "power.csv").read_text()
    for q in ("query3", "query96", "query14_part1"):
        assert q in power_log
