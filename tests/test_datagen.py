import io
import os
import subprocess

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.csv as pv
import pytest

from nds_tpu.datagen.build import ensure_built
from nds_tpu.schema import get_schemas, get_maintenance_schemas

SCALE = "0.002"


@pytest.fixture(scope="module")
def datadir(tmp_path_factory):
    d = tmp_path_factory.mktemp("raw")
    from nds_tpu.cli.gen_data import main

    main(["local", "--scale", SCALE, "--parallel", "2", "--data_dir", str(d)])
    return d


@pytest.fixture(scope="module")
def updatedir(tmp_path_factory):
    d = tmp_path_factory.mktemp("raw_update")
    from nds_tpu.cli.gen_data import main

    main(["local", "--scale", SCALE, "--parallel", "2", "--data_dir", str(d), "--update", "1"])
    return d


def read_table(data_dir, table, schema):
    """Read a generated .dat table through its Arrow schema (the exact path
    the transcode phase uses)."""
    names = schema.names + ["_trailing"]
    types = {f.name: f.dtype.to_arrow() for f in schema}
    tables = []
    table_dir = os.path.join(data_dir, table)
    for fname in sorted(os.listdir(table_dir)):
        with open(os.path.join(table_dir, fname), "rb") as f:
            data = f.read()
        if not data:
            continue
        tables.append(pv.read_csv(
            io.BytesIO(data),
            read_options=pv.ReadOptions(column_names=names),
            parse_options=pv.ParseOptions(delimiter="|"),
            convert_options=pv.ConvertOptions(column_types=types, strings_can_be_null=True),
        ).drop_columns(["_trailing"]))
    return pa.concat_tables(tables)


def test_layout(datadir):
    for table in get_schemas():
        assert os.path.isdir(datadir / table), f"missing dir for {table}"


def test_all_tables_parse_with_schema(datadir):
    for table, schema in get_schemas().items():
        t = read_table(datadir, table, schema)
        assert t.num_rows > 0, table


def test_fixed_cross_product_tables(datadir):
    schemas = get_schemas()
    hd = read_table(datadir, "household_demographics", schemas["household_demographics"])
    assert hd.num_rows == 7200
    assert len(pc.unique(hd.column("hd_demo_sk"))) == 7200
    ib = read_table(datadir, "income_band", schemas["income_band"])
    assert ib.num_rows == 20


def test_date_dim_calendar(datadir):
    dd = read_table(datadir, "date_dim", get_schemas()["date_dim"])
    assert dd.num_rows == 73049
    import datetime

    row = dd.slice(0, 1).to_pylist()[0]
    assert row["d_date_sk"] == 2415022
    assert row["d_date"] == datetime.date(1900, 1, 2)
    # 2000-01-01 was a Saturday
    mask = pc.equal(dd.column("d_date_sk"), 2451545)
    y2k = dd.filter(mask).to_pylist()[0]
    assert y2k["d_year"] == 2000 and y2k["d_day_name"].strip() == "Saturday"
    assert y2k["d_quarter_name"].strip() == "2000Q1"


def test_referential_integrity(datadir):
    schemas = get_schemas()
    ss = read_table(datadir, "store_sales", schemas["store_sales"])
    item = read_table(datadir, "item", schemas["item"])
    store = read_table(datadir, "store", schemas["store"])
    item_sks = set(item.column("i_item_sk").to_pylist())
    assert set(x for x in ss.column("ss_item_sk").to_pylist()) <= item_sks
    store_sks = set(store.column("s_store_sk").to_pylist())
    assert set(x for x in ss.column("ss_store_sk").to_pylist() if x is not None) <= store_sks


def test_returns_reference_sales(datadir):
    schemas = get_schemas()
    ss = read_table(datadir, "store_sales", schemas["store_sales"])
    sr = read_table(datadir, "store_returns", schemas["store_returns"])
    # every return (ticket, item) must exist in sales
    sales_keys = set(zip(ss.column("ss_ticket_number").to_pylist(),
                         ss.column("ss_item_sk").to_pylist()))
    ret_keys = set(zip(sr.column("sr_ticket_number").to_pylist(),
                       sr.column("sr_item_sk").to_pylist()))
    assert ret_keys <= sales_keys
    # ~10% of lines return
    assert 0.02 < sr.num_rows / ss.num_rows < 0.25


def test_price_arithmetic(datadir):
    ss = read_table(datadir, "store_sales", get_schemas()["store_sales"])
    row = ss.slice(0, 200).to_pylist()
    for r in row:
        if r["ss_quantity"] is None:
            continue
        assert r["ss_ext_sales_price"] == r["ss_sales_price"] * r["ss_quantity"]
        assert r["ss_net_paid"] == r["ss_ext_sales_price"] - r["ss_coupon_amt"]
        assert r["ss_net_profit"] == r["ss_net_paid"] - r["ss_ext_wholesale_cost"]


def test_chunks_are_deterministic(tmp_path):
    binary = ensure_built()
    out1, out2 = tmp_path / "a", tmp_path / "b"
    out1.mkdir(), out2.mkdir()
    for out in (out1, out2):
        subprocess.run([binary, "-scale", "0.002", "-dir", str(out), "-table", "web_sales"],
                       check=True)
    f = "web_sales_1_1.dat"
    assert (out1 / f).read_bytes() == (out2 / f).read_bytes()


def test_update_refresh_sets(updatedir):
    schemas = get_maintenance_schemas()
    for table in schemas:
        assert os.path.isdir(updatedir / table), f"missing refresh table {table}"
    sp = read_table(updatedir, "s_purchase", schemas["s_purchase"])
    spl = read_table(updatedir, "s_purchase_lineitem", schemas["s_purchase_lineitem"])
    assert sp.num_rows > 0
    # every lineitem belongs to a purchase
    assert set(spl.column("plin_purchase_id").to_pylist()) <= set(
        sp.column("purc_purchase_id").to_pylist())
    dele = read_table(updatedir, "delete", schemas["delete"])
    assert dele.num_rows == 3  # 3 DATE1/DATE2 tuples per refresh set


def test_cluster_localhost_matches_local(tmp_path):
    """Cluster fan-out over a localhost hosts file is byte-identical to
    local generation (the shared-filesystem contract)."""
    from nds_tpu.cli import gen_data

    local = tmp_path / "local"
    gen_data.main(["local", "--scale", SCALE, "--parallel", "2",
                   "--data_dir", str(local)])
    hosts = tmp_path / "hosts.txt"
    hosts.write_text("# comment\nlocalhost\n127.0.0.1\n")
    clus = tmp_path / "cluster"
    gen_data.main(["cluster", "--scale", SCALE, "--parallel", "2",
                   "--data_dir", str(clus), "--hosts", str(hosts)])
    for table in ("store_sales", "item", "date_dim"):
        a = sorted(os.listdir(local / table))
        assert a == sorted(os.listdir(clus / table))
        for f in a:
            assert (local / table / f).read_bytes() == (clus / table / f).read_bytes()


def test_cluster_retries_failed_chunk(tmp_path, monkeypatch):
    """A chunk whose process dies is re-launched on the next host and the
    run still completes; exhausting --retries raises."""
    from nds_tpu.cli import gen_data

    real_spawn = gen_data._spawn_on_host
    first_attempt_failed = set()

    def flaky(host, cmd):
        chunk = cmd[cmd.index("-child") + 1]
        if chunk not in first_attempt_failed:
            first_attempt_failed.add(chunk)
            return subprocess.Popen(["false"])
        return real_spawn("localhost", cmd)

    monkeypatch.setattr(gen_data, "_spawn_on_host", flaky)
    hosts = tmp_path / "hosts.txt"
    hosts.write_text("hostA\nhostB\n")  # never ssh'd: spawn is patched
    out = tmp_path / "out"
    gen_data.main(["cluster", "--scale", SCALE, "--parallel", "2",
                   "--data_dir", str(out), "--hosts", str(hosts),
                   "--table", "item"])
    assert len(first_attempt_failed) == 2  # both chunks failed once
    assert sorted(os.listdir(out / "item")) == ["item_1_2.dat", "item_2_2.dat"]

    monkeypatch.setattr(gen_data, "_spawn_on_host",
                        lambda host, cmd: subprocess.Popen(["false"]))
    with pytest.raises(Exception, match="after 1 retries"):
        gen_data.main(["cluster", "--scale", SCALE, "--parallel", "2",
                       "--data_dir", str(tmp_path / "dead"), "--hosts", str(hosts),
                       "--retries", "1", "--table", "item"])


def test_range_generation(tmp_path):
    from nds_tpu.cli.gen_data import main

    d1 = tmp_path / "full"
    main(["local", "--scale", SCALE, "--parallel", "4", "--data_dir", str(d1)])
    d2 = tmp_path / "ranged"
    main(["local", "--scale", SCALE, "--parallel", "4", "--range", "1,2", "--data_dir", str(d2)])
    main(["local", "--scale", SCALE, "--parallel", "4", "--range", "3,4", "--data_dir", str(d2),
          "--overwrite_output"])
    a = sorted(os.listdir(d1 / "catalog_sales"))
    b = sorted(os.listdir(d2 / "catalog_sales"))
    assert a == b
    for f in a:
        assert (d1 / "catalog_sales" / f).read_bytes() == (d2 / "catalog_sales" / f).read_bytes()


# ---------------------------------------------------------------------------
# Spec fidelity (VERDICT r3 #7): TPC-DS Table 3-2 row counts, NULL rates,
# and official-toolkit (dsdgen) format interop.
# ---------------------------------------------------------------------------


def _count_dat_rows(data_dir, table):
    total = 0
    tdir = os.path.join(str(data_dir), table)
    for fn in os.listdir(tdir):
        with open(os.path.join(tdir, fn), "rb") as f:
            total += sum(1 for _ in f)
    return total


def test_fixed_tables_match_spec_rowcounts(datadir):
    """Scale-independent tables carry the TPC-DS Table 3-2 counts at any
    SF (reference contract: nds/nds_gen_data.py:183-244 expects official
    dsdgen table layouts)."""
    expected = {
        "date_dim": 73049,
        "time_dim": 86400,
        "customer_demographics": 1920800,
        "household_demographics": 7200,
        "income_band": 20,
        "ship_mode": 20,
    }
    for table, n in expected.items():
        assert _count_dat_rows(datadir, table) == n, table


def test_sf1_dimension_rowcounts(tmp_path):
    """SF1 dimension row counts match TPC-DS Table 3-2 exactly."""
    from nds_tpu.cli.gen_data import main

    expected = {
        "call_center": 6,
        "catalog_page": 11718,
        "customer_address": 50000,
        "customer": 100000,
        "item": 18000,
        "promotion": 300,
        "reason": 35,
        "store": 12,
        "warehouse": 5,
        "web_page": 60,
        "web_site": 30,
    }
    for table, n in expected.items():
        d = tmp_path / f"sf1_{table}"
        main(["local", "--scale", "1", "--parallel", "2",
              "--data_dir", str(d), "--table", table])
        assert _count_dat_rows(d, table) == n, table


def test_fact_rowcounts_scale_linearly(tmp_path):
    """Fact table sizes scale ~linearly with SF (TPC-DS fact scaling)."""
    from nds_tpu.cli.gen_data import main

    counts = {}
    for sf in ("0.01", "0.02"):
        d = tmp_path / f"sf{sf}"
        main(["local", "--scale", sf, "--parallel", "2",
              "--data_dir", str(d), "--table", "web_sales"])
        counts[sf] = _count_dat_rows(d, "web_sales")
    ratio = counts["0.02"] / counts["0.01"]
    assert 1.5 < ratio < 2.6, counts


def test_fact_null_rates_and_fk_domains(datadir):
    """Nullable fact FKs carry a small non-zero NULL rate (the query
    parameter generators assume mostly-populated joins), and non-null FKs
    stay inside the dimension surrogate domain."""
    schemas = get_schemas()
    ss = read_table(datadir, "store_sales", schemas["store_sales"])
    n = ss.num_rows
    for col in ("ss_customer_sk", "ss_store_sk", "ss_promo_sk",
                "ss_hdemo_sk", "ss_cdemo_sk", "ss_addr_sk"):
        nulls = ss.column(col).null_count
        assert 0 < nulls / n < 0.5, (col, nulls, n)
    # sold_date may be null (pre-history orders); domain check on non-nulls
    dd = read_table(datadir, "date_dim", schemas["date_dim"])
    dmin = pc.min(dd.column("d_date_sk")).as_py()
    dmax = pc.max(dd.column("d_date_sk")).as_py()
    dates = [x for x in ss.column("ss_sold_date_sk").to_pylist()
             if x is not None]
    assert min(dates) >= dmin and max(dates) <= dmax


def test_official_dsdgen_format_ingests(tmp_path):
    """A file in the official dsdgen output layout (pipe-delimited,
    trailing '|', ISO dates, empty string = NULL) ingests through the same
    reader the harness uses for its own generator output, so official
    toolkit data can be transcoded unchanged (reference:
    nds/nds_gen_data.py:183-244 consumes dsdgen output directly)."""
    from nds_tpu.io.csv import read_dat_dir

    wdir = tmp_path / "warehouse"
    wdir.mkdir()
    # dsdgen layout for `warehouse`: w_warehouse_sk|w_warehouse_id|...|
    rows = [
        "1|AAAAAAAABAAAAAAA|Conventional childr|977787|651|6th |Parkway|Suite 470|Midway|Williamson County|TN|31904|United States|-5.00|\n",
        "2|AAAAAAAACAAAAAAA||138504|600|View First|Avenue|Suite P|Midway|Williamson County|TN|31904|United States|-5.00|\n",
        "3|AAAAAAAADAAAAAAA|Doors canno|294242|534|Ash Laurel|Dr.|Suite 0|Midway|Williamson County|TN|31904|United States|-5.00|\n",
    ]
    (wdir / "warehouse_1_1.dat").write_text("".join(rows))
    schema = get_schemas()["warehouse"]
    arrow = read_dat_dir(str(wdir), schema, use_decimal=True)
    assert arrow.num_rows == 3
    assert arrow.column("w_warehouse_sk").to_pylist() == [1, 2, 3]
    assert arrow.column("w_warehouse_name").to_pylist()[1] is None  # empty=NULL
    assert arrow.column("w_state").to_pylist() == ["TN", "TN", "TN"]
    import decimal

    assert arrow.column("w_gmt_offset").to_pylist() == [
        decimal.Decimal("-5.00")] * 3

    # and it transcodes through the Load Test path unchanged
    from nds_tpu.transcode import transcode_table

    out = tmp_path / "pq"
    n = transcode_table(str(tmp_path), str(out), "warehouse", schema,
                        output_format="parquet", partition=False)
    assert n == 3


def test_fact_primary_keys_unique(datadir):
    """Declared TPC-DS primary keys hold in generated data (dsdgen samples
    items per ticket/order without replacement). The engine's catalog
    claims these as Table.unique_key for probe-style joins, so a violation
    here would silently corrupt join results, not just fidelity."""
    import numpy as np

    from nds_tpu.schema import TABLE_PRIMARY_KEYS

    schemas = get_schemas()
    for t in ("store_sales", "web_sales", "catalog_sales", "store_returns",
              "web_returns", "catalog_returns", "inventory"):
        pk = TABLE_PRIMARY_KEYS[t]
        tab = read_table(datadir, t, schemas[t])
        m = np.stack(
            [tab.column(c).to_numpy(zero_copy_only=False).astype(np.int64)
             for c in pk], 1,
        )
        assert len(np.unique(m, axis=0)) == tab.num_rows, t


@pytest.mark.parametrize("leftover", [False, True], ids=["cold", "after-a-dead-run"])
def test_shared_data_is_generated_once_under_racing_workers(
    tmp_path, monkeypatch, leftover,
):
    """tests/shared_data.py: workers that meet a cold temporary directory at
    once (threads here, each with its own lock descriptor as processes
    have) generate once; the others wait and find the directory there.
    Nothing is written at the target itself, so what a run that died left
    beside it is never read."""
    import threading
    import time

    import shared_data

    target = tmp_path / "shared" / "sf001"
    if leftover:
        (tmp_path / "shared" / "sf001.dead" / "store_sales").mkdir(parents=True)
    calls = []

    def fake_gen(cmd, **kw):
        out = cmd[cmd.index("--data_dir") + 1]
        calls.append(out)
        assert out != str(target) and "--overwrite_output" in cmd
        assert not target.exists()
        time.sleep(0.2)  # long enough for every thread to queue on the lock
        os.makedirs(os.path.join(out, "item"))
        with open(os.path.join(out, "item", "item.dat"), "w") as f:
            f.write("1|AAAA|")

    monkeypatch.setattr(shared_data.subprocess, "run", fake_gen)
    got = []
    threads = [
        threading.Thread(
            target=lambda: got.append(shared_data._generated(str(target))))
        for _ in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == [str(target)] * 6 and len(calls) == 1
    assert os.listdir(target) == ["item"]
    assert not os.path.exists(calls[0])  # renamed into place, nothing left


def test_shared_data_lives_under_the_runs_own_temporary_directory():
    """Two runs with a `TMPDIR` each never meet, and no path is one that a
    tree from before the helper writes (`<tmp>/nds_test_sf001`)."""
    import tempfile

    import shared_data

    root = os.path.join(tempfile.gettempdir(), "nds_tpu_tests")
    assert shared_data.DATA == os.path.join(root, "sf001")
    assert shared_data.REFRESH == os.path.join(root, "sf001_refresh")
