"""Transcode / Load Test phase tests (reference behavior:
nds/nds_transcode.py:45-53 partitioning, :146-215 report contract)."""

import os
import subprocess
import sys
from argparse import Namespace

import pyarrow.dataset as pads
import pytest

from nds_tpu.io.csv import iter_dat_batches, read_dat_dir
from nds_tpu.schema import get_schemas
from nds_tpu.transcode import TABLE_PARTITIONING, transcode, transcode_table
from shared_data import raw_data



@pytest.fixture(scope="module")
def data_dir():
    return raw_data()


def _args(data_dir, out, report, **kw):
    base = dict(
        input_prefix=data_dir, output_prefix=str(out), report_file=str(report),
        output_mode="errorifexists", output_format="parquet", tables=None,
        floats=False, update=False, compression=None,
    )
    base.update(kw)
    return Namespace(**base)


def test_iter_dat_batches_streams(data_dir):
    sch = get_schemas()["store_sales"]
    n_stream = sum(
        b.num_rows
        for b in iter_dat_batches(os.path.join(data_dir, "store_sales"), sch,
                                  block_size=1 << 16)
    )
    n_bulk = read_dat_dir(os.path.join(data_dir, "store_sales"), sch).num_rows
    assert n_stream == n_bulk > 0


def test_fact_table_partitioned_layout(data_dir, tmp_path):
    sch = get_schemas()["store_returns"]
    rows = transcode_table(data_dir, str(tmp_path), "store_returns", sch)
    part_col = TABLE_PARTITIONING["store_returns"]
    dirs = os.listdir(tmp_path / "store_returns")
    assert any(d.startswith(part_col + "=") for d in dirs)
    assert rows > 0


def test_dim_table_single_file(data_dir, tmp_path):
    sch = get_schemas()["item"]
    transcode_table(data_dir, str(tmp_path), "item", sch)
    files = os.listdir(tmp_path / "item")
    assert files == ["part-0.parquet"]


def test_roundtrip_equals_source(data_dir, tmp_path):
    """Parquet warehouse read-back must match the raw CSV read (including the
    hive-partition column restored with its schema dtype)."""
    table = "store_returns"
    sch = get_schemas()[table]
    transcode_table(data_dir, str(tmp_path), table, sch)
    from nds_tpu.engine.session import Session

    sess = Session()
    sess.register_parquet(table, str(tmp_path / table), sch)
    back = sess.sql(f"select * from {table}").collect()
    src = read_dat_dir(os.path.join(data_dir, table), sch)
    assert back.num_rows == src.num_rows
    key = "sr_item_sk"
    part_col = TABLE_PARTITIONING[table]
    b = back.sort_by([(part_col, "ascending"), (key, "ascending"), ("sr_ticket_number", "ascending")])
    s = src.sort_by([(part_col, "ascending"), (key, "ascending"), ("sr_ticket_number", "ascending")])
    for col in (part_col, key, "sr_return_amt"):
        assert b.column(col).to_pylist() == s.column(col).to_pylist(), col


def test_csv_warehouse_roundtrip(data_dir, tmp_path):
    """A csv-format warehouse (transcode --output_format csv) must be
    readable by the power-run session (reference parity: nds_power.py csv
    input_format reads the transcoded warehouse, not raw .dat)."""
    table = "warehouse"
    sch = get_schemas()[table]
    transcode_table(
        data_dir, str(tmp_path), table, sch, output_format="csv"
    )
    from nds_tpu.engine.session import Session

    sess = Session()
    sess.register_csv_warehouse(table, str(tmp_path / table), sch)
    back = sess.sql(f"select * from {table}").collect()
    src = read_dat_dir(os.path.join(data_dir, table), sch)
    assert back.num_rows == src.num_rows
    b = back.sort_by("w_warehouse_sk")
    s = src.sort_by("w_warehouse_sk")
    assert b.column("w_warehouse_id").to_pylist() == s.column("w_warehouse_id").to_pylist()


def test_append_mode_preserves_existing(data_dir, tmp_path):
    sch = get_schemas()["warehouse"]
    n1 = transcode_table(data_dir, str(tmp_path), "warehouse", sch)
    n2 = transcode_table(
        data_dir, str(tmp_path), "warehouse", sch, output_mode="append"
    )
    ds = pads.dataset(str(tmp_path / "warehouse"), format="parquet")
    assert ds.count_rows() == n1 + n2


def test_transcode_report_contract(data_dir, tmp_path):
    report = tmp_path / "load.report"
    out = tmp_path / "wh"
    transcode(_args(data_dir, out, report, tables=["item", "warehouse"]))
    text = report.read_text()
    assert "Load Test Time:" in text
    assert "RNGSEED used:" in text
    assert "Time to convert 'item'" in text
    assert "Time to convert 'warehouse'" in text


def test_output_mode_guard(data_dir, tmp_path):
    sch = get_schemas()["warehouse"]
    transcode_table(data_dir, str(tmp_path), "warehouse", sch)
    with pytest.raises(FileExistsError):
        transcode_table(data_dir, str(tmp_path), "warehouse", sch)
    # overwrite succeeds
    transcode_table(
        data_dir, str(tmp_path), "warehouse", sch, output_mode="overwrite"
    )
    # ignore is a no-op
    assert (
        transcode_table(
            data_dir, str(tmp_path), "warehouse", sch, output_mode="ignore"
        )
        == 0
    )


def test_orc_roundtrip(data_dir, tmp_path):
    """ORC output format parity (reference: nds_transcode.py:100-112)."""
    from nds_tpu.engine.session import Session

    schema = get_schemas()["store"]
    n = transcode_table(data_dir, str(tmp_path), "store", schema,
                        output_format="orc")
    assert n > 0
    s = Session()
    s.register_orc("store", os.path.join(str(tmp_path), "store"), schema)
    out = s.sql("select count(*) c from store").to_pylist()
    assert out == [{"c": n}]


def test_dbgen_version_table(tmp_path):
    """The generator emits the one-row dbgen_version audit table
    (reference: nds_gen_data.py:50-51)."""
    from nds_tpu.engine.session import Session

    d = str(tmp_path / "gen")
    subprocess.run(
        [sys.executable, "-m", "nds_tpu.cli.gen_data", "--scale", "0.01",
         "--parallel", "2", "--data_dir", d, "--table", "store",
         "--overwrite_output"],
        check=True, capture_output=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    path = os.path.join(d, "dbgen_version")
    assert os.path.isdir(path)
    s = Session()
    s.register_csv_dir("dbgen_version", path, get_schemas()["dbgen_version"])
    rows = s.sql(
        "select dv_version, dv_cmdline_args from dbgen_version"
    ).to_pylist()
    assert len(rows) == 1 and rows[0]["dv_version"] == "1.0.0"


def test_json_output(data_dir, tmp_path):
    """Line-delimited JSON output (reference: nds_transcode.py:61-144)."""
    import json

    schema = get_schemas()["warehouse"]
    n = transcode_table(data_dir, str(tmp_path), "warehouse", schema,
                        output_format="json")
    assert n > 0
    path = os.path.join(str(tmp_path), "warehouse", "part-0.json")
    rows = [json.loads(line) for line in open(path)]
    assert len(rows) == n and "w_warehouse_sk" in rows[0]


def test_avro_roundtrip(data_dir, tmp_path):
    """Avro container output (reference: nds_transcode.py:241-249 via the
    spark-avro plugin) — written by our own spec-subset writer and read back
    byte-exactly through the paired reader."""
    from nds_tpu.io.avro import read_avro
    from nds_tpu.io.csv import read_dat_dir

    schema = get_schemas()["store"]
    n = transcode_table(data_dir, str(tmp_path), "store", schema,
                        output_format="avro")
    assert n > 0
    files = os.listdir(os.path.join(str(tmp_path), "store"))
    assert files == ["part-0.avro"]
    rows = read_avro(os.path.join(str(tmp_path), "store", files[0]))
    src = read_dat_dir(os.path.join(data_dir, "store"), schema).to_pylist()
    assert len(rows) == len(src) == n
    for got, want in zip(rows, src):
        for k, v in want.items():
            g = got[k]
            if isinstance(v, float):
                assert abs(g - v) < 1e-12
            else:
                assert g == v, (k, g, v)
