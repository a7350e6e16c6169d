"""Shared-filesystem seam: the full transcode -> power -> maintenance cycle
against a non-local (memory://) warehouse URL.

The reference reaches HDFS/S3/GS in every phase (nds/nds_gen_data.py:130-180;
nds/nds_power.py:296-299 writes the extra time log via Spark precisely so it
can land on cloud storage). Here every phase exercises fsspec through
io/fs.py: lakehouse create/append/delete/rollback, stream-file reads, and
time-log/report writes all target memory:// paths.
"""

import os

import pytest

from nds_tpu.engine.session import Session
from nds_tpu.lakehouse.table import LakehouseTable
from nds_tpu.schema import get_schemas
from nds_tpu.transcode import transcode_table
import shared_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLES = ("store_sales", "date_dim", "item")


@pytest.fixture(scope="module")
def raw_data():
    return shared_data.raw_data()


@pytest.fixture(scope="module")
def mem_warehouse(raw_data):
    """Transcode three tables into a memory:// lakehouse warehouse."""
    wh = "memory://fsspec_wh"
    for t in TABLES:
        transcode_table(
            raw_data, wh, t, get_schemas()[t], output_format="lakehouse",
            output_mode="overwrite",
        )
    return wh


def test_remote_plain_formats_rejected(raw_data):
    with pytest.raises(ValueError, match="lakehouse"):
        transcode_table(
            raw_data, "memory://nope", "item", get_schemas()["item"],
            output_format="parquet", output_mode="overwrite",
        )


def test_transcode_then_power_on_memory_url(mem_warehouse, tmp_path):
    from nds_tpu.power import gen_sql_from_stream, run_query_stream

    # stream file itself on memory://
    from nds_tpu.io.fs import fs_open

    stream_url = "memory://streams/query_0.sql"
    q = (
        "select d_year, count(*) c, sum(ss_ext_sales_price) s\n"
        "from store_sales, date_dim where ss_sold_date_sk = d_date_sk\n"
        "group by d_year order by d_year\n"
    )
    with fs_open(stream_url, "w") as f:
        f.write(
            "-- start query 1 in stream 0 using template query3.tpl\n"
            f"{q};\n"
            "-- end query 1 in stream 0 using template query3.tpl\n"
        )
    queries = gen_sql_from_stream(stream_url)
    assert len(queries) == 1

    time_log_url = "memory://logs/time.csv"
    run_query_stream(
        mem_warehouse,
        None,
        queries,
        time_log_url,
        input_format="lakehouse",
        json_summary_folder=str(tmp_path / "summaries"),
    )
    with fs_open(time_log_url) as f:
        log = f.read()
    # query named after its template (reference stream-file contract)
    assert "query3" in log and "Power Test Time" in log


def test_maintenance_cycle_on_memory_url(mem_warehouse):
    """INSERT + copy-on-write DELETE + timestamp rollback on memory://."""
    import pyarrow as pa

    t = LakehouseTable(f"{mem_warehouse}/store_sales")
    rows0 = t.num_rows()
    v0 = t.current_version()
    ts0 = t._manifest(v0)["timestamp_ms"]

    sess = Session()
    sess.register_lakehouse("store_sales", f"{mem_warehouse}/store_sales")

    # INSERT (LF_SS shape): append a copy of 5 rows
    sample = t.dataset().head(5)
    t.append(sample)
    assert LakehouseTable(f"{mem_warehouse}/store_sales").num_rows() == rows0 + 5

    # DELETE (DF_SS shape): copy-on-write delete of a date range
    ds = t.dataset()
    lo = ds.head(1).column("ss_sold_date_sk")[0].as_py()
    kept = ds.to_table().filter(
        pa.compute.field("ss_sold_date_sk") != lo
    )
    t.replace(kept, operation="delete")
    assert LakehouseTable(f"{mem_warehouse}/store_sales").num_rows() == kept.num_rows

    # rollback to the pre-maintenance snapshot (nds_rollback.py semantics)
    t.rollback_to_timestamp(ts0)
    assert LakehouseTable(f"{mem_warehouse}/store_sales").num_rows() == rows0

    # and the engine reads the rolled-back snapshot
    sess2 = Session()
    sess2.register_lakehouse("store_sales", f"{mem_warehouse}/store_sales")
    out = sess2.sql("select count(*) c from store_sales").to_pylist()
    assert out[0]["c"] == rows0
