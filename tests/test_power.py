"""Power Run driver + bench report tests (reference behavior:
nds/nds_power.py:50-77,184-299 and nds/PysparkBenchReport.py:58-119)."""

import csv
import json
import os

import pytest

from nds_tpu.power import (
    gen_sql_from_stream,
    get_query_subset,
    load_properties,
    run_query_stream,
)
from nds_tpu.report import BenchReport
from nds_tpu.engine.session import Session
from shared_data import raw_data



@pytest.fixture(scope="module")
def data_dir():
    return raw_data()


STREAM = """-- start query 1 in stream 0 using template query96.tpl
select count(*) cnt from store_sales where ss_quantity > 0
;
-- end query 1 in stream 0 using template query96.tpl

-- start query 2 in stream 0 using template query3.tpl
select d_year, count(*) c from date_dim group by d_year order by d_year limit 5
;
-- end query 2 in stream 0 using template query3.tpl
"""

TWO_PART_STREAM = """-- start query 1 in stream 0 using template query23.tpl
select 1 as a
;
select 2 as b
;
-- end query 1 in stream 0 using template query23.tpl
"""


def test_gen_sql_from_stream(tmp_path):
    p = tmp_path / "query_0.sql"
    p.write_text(STREAM)
    qd = gen_sql_from_stream(str(p))
    assert list(qd) == ["query96", "query3"]
    assert qd["query96"].startswith("-- start query 1")
    assert "select count(*)" in qd["query96"]


def test_gen_sql_two_part_split(tmp_path):
    p = tmp_path / "query_0.sql"
    p.write_text(TWO_PART_STREAM)
    qd = gen_sql_from_stream(str(p))
    assert list(qd) == ["query23_part1", "query23_part2"]
    assert "select 1" in qd["query23_part1"]
    assert "select 2" in qd["query23_part2"]
    assert "query23_part1.tpl" in qd["query23_part1"]
    assert "query23_part2.tpl" in qd["query23_part2"]


def test_get_query_subset(tmp_path):
    p = tmp_path / "query_0.sql"
    p.write_text(STREAM)
    qd = gen_sql_from_stream(str(p))
    sub = get_query_subset(qd, ["query3"])
    assert list(sub) == ["query3"]
    with pytest.raises(Exception, match="not found"):
        get_query_subset(qd, ["query999"])


def test_load_properties(tmp_path):
    f = tmp_path / "x.properties"
    f.write_text("a.b=1\n# comment\n\nc.d = hello \n")
    assert load_properties(str(f)) == {"a.b": "1", "c.d": "hello"}


def test_run_query_stream_end_to_end(data_dir, tmp_path):
    stream = tmp_path / "query_0.sql"
    stream.write_text(STREAM)
    time_log = tmp_path / "time.csv"
    jdir = tmp_path / "json"
    out = tmp_path / "out"
    qd = gen_sql_from_stream(str(stream))
    run_query_stream(
        input_prefix=data_dir,
        property_file=None,
        query_dict=qd,
        time_log_output_path=str(time_log),
        input_format="csv",
        output_path=str(out),
        output_format="parquet",
        json_summary_folder=str(jdir),
    )
    rows = list(csv.reader(time_log.open()))
    assert rows[0] == ["application_id", "query", "time/milliseconds"]
    names = [r[1] for r in rows[1:]]
    assert "query96" in names and "query3" in names
    assert "Power Test Time" in names and "Total Time" in names
    summaries = sorted(os.listdir(jdir))
    assert len(summaries) == 2
    s = json.load(open(os.path.join(jdir, summaries[0])))
    assert s["queryStatus"] == ["Completed"]
    assert s["queryTimes"] and isinstance(s["queryTimes"][0], int)
    assert "sparkConf" in s["env"] and "envVars" in s["env"]
    # filename contract: <prefix>-<query>-<startTime>.json
    assert s["filename"].endswith(f"-{s['query']}-{s['startTime']}.json")
    # written outputs exist per query
    assert os.path.exists(out / "query96" / "part-0.parquet")


def test_failed_query_continues(data_dir, tmp_path):
    bad_stream = (
        "-- start query 1 in stream 0 using template query1.tpl\n"
        "select nonexistent_col from store_sales\n;\n"
        "-- end query 1 in stream 0 using template query1.tpl\n"
        "-- start query 2 in stream 0 using template query3.tpl\n"
        "select count(*) c from item\n;\n"
        "-- end query 2 in stream 0 using template query3.tpl\n"
    )
    stream = tmp_path / "query_0.sql"
    stream.write_text(bad_stream)
    jdir = tmp_path / "json"
    qd = gen_sql_from_stream(str(stream))
    run_query_stream(
        input_prefix=data_dir,
        property_file=None,
        query_dict=qd,
        time_log_output_path=str(tmp_path / "t.csv"),
        input_format="csv",
        json_summary_folder=str(jdir),
    )
    st = {}
    for f in os.listdir(jdir):
        s = json.load(open(os.path.join(jdir, f)))
        st[s["query"]] = s
    assert st["query1"]["queryStatus"] == ["Failed"]
    assert st["query1"]["exceptions"]
    assert st["query3"]["queryStatus"] == ["Completed"]


def test_report_redacts_secrets(monkeypatch):
    monkeypatch.setenv("MY_SECRET_KEY", "hunter2")
    monkeypatch.setenv("API_TOKEN", "tok")
    monkeypatch.setenv("SAFE_VAR", "ok")
    r = BenchReport(Session())
    r.report_on(lambda: None)
    env = r.summary["env"]["envVars"]
    assert "MY_SECRET_KEY" not in env
    assert "API_TOKEN" not in env
    assert env.get("SAFE_VAR") == "ok"


def test_report_task_failures_status():
    sess = Session()

    def flaky():
        sess.notify_failure("task retry: exchange capacity doubled")

    r = BenchReport(sess)
    summary = r.report_on(flaky)
    assert summary["queryStatus"] == ["CompletedWithTaskFailures"]
    assert summary["taskFailures"]
