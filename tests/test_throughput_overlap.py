"""Throughput concurrency: prove streams genuinely overlap in time.

The reference forks one Power Run per stream (nds/nds-throughput:18-23);
our thread mode runs streams as threads whose device dispatches release
the GIL. This asserts the overlap is real — each stream's [start, end]
window (from its time log) intersects every other's — and exercises the
fork-per-process mode end-to-end as well.
"""

import csv
import os

import pytest

from nds_tpu.schema import get_schemas
from nds_tpu.throughput import run_throughput
from shared_data import DATA, raw_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_QUERY = """
select d_year, d_moy, count(*) c, sum(ss_ext_sales_price) s
from store_sales, date_dim
where ss_sold_date_sk = d_date_sk group by d_year, d_moy
order by d_year, d_moy
"""


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    raw_data()
    out = tmp_path_factory.mktemp("wh")
    from nds_tpu.transcode import transcode_table

    for t in ("store_sales", "date_dim"):
        transcode_table(DATA, str(out), t, get_schemas()[t],
                        output_format="parquet", partition=False)
    return str(out)


def _write_stream(path, n_queries):
    parts = []
    for i in range(n_queries):
        # vary a constant per query so the session plan-result cache can't
        # collapse the stream into one execution + 7 dict hits
        q = SMOKE_QUERY.replace(
            "group by", f"and d_moy <= {12 - (i % 12)} group by"
        )
        parts.append(
            f"-- start query {i + 1} in stream 0 using template query3.tpl\n"
            f"{q}\n;\n"
            f"-- end query {i + 1} in stream 0 using template query3.tpl\n"
        )
    with open(path, "w") as f:
        f.write("\n".join(parts))


def _window(log):
    start = end = None
    with open(log) as f:
        for row in csv.reader(f):
            if len(row) >= 3 and row[1] == "Power Start Time":
                start = float(row[2])
            if len(row) >= 3 and row[1] == "Power End Time":
                end = float(row[2])
    return start, end


def _summary_window_ms(folder):
    """[first query start, last query end] in ms from a stream's per-query
    JSON summaries — fractional evidence of when the stream actually ran,
    independent of the int-second time log."""
    import glob
    import json

    lo = hi = None
    for p in glob.glob(os.path.join(folder, "*.json")):
        with open(p) as f:
            s = json.load(f)
        start = s["startTime"]
        end = start + sum(s["queryTimes"])
        lo = start if lo is None else min(lo, start)
        hi = end if hi is None else max(hi, end)
    assert lo is not None, f"no summaries in {folder}"
    return lo, hi


def test_thread_streams_overlap(warehouse, tmp_path):
    # The streams rendezvous on run_throughput's start gate after setup, so
    # the int-second time-log windows share one start by construction. The
    # genuine-concurrency proof uses the per-query JSON summaries' ms
    # timestamps: if a regression serialized the streams (whole-stream GIL
    # hold), stream A's last query would end before stream B's first began
    # and the strict window intersection below would fail.
    for n in (1, 2):
        _write_stream(tmp_path / f"query_{n}.sql", 8)
    base = str(tmp_path / "tt")
    ttt = run_throughput(
        warehouse,
        {1: str(tmp_path / "query_1.sql"), 2: str(tmp_path / "query_2.sql")},
        base,
        input_format="parquet",
        json_summary_folder=str(tmp_path / "summaries"),
    )
    assert ttt > 0
    s1, e1 = _window(f"{base}_1.csv")
    s2, e2 = _window(f"{base}_2.csv")
    # gate-aligned starts: both streams record the shared release timestamp
    assert s1 == s2, (s1, e1, s2, e2)
    # Ttt spans the union of the windows (reference Ttt semantics)
    assert ttt >= max(e1, e2) - min(s1, s2)
    # strict fractional-window intersection: each stream ran a query while
    # the other was still mid-stream
    f1 = _summary_window_ms(str(tmp_path / "summaries" / "stream_1"))
    f2 = _summary_window_ms(str(tmp_path / "summaries" / "stream_2"))
    assert f1[0] < f2[1] and f2[0] < f1[1], (f1, f2)


def test_process_mode_streams(warehouse, tmp_path):
    for n in (1, 2):
        _write_stream(tmp_path / f"query_{n}.sql", 2)
    base = str(tmp_path / "tp")
    ttt = run_throughput(
        warehouse,
        {1: str(tmp_path / "query_1.sql"), 2: str(tmp_path / "query_2.sql")},
        base,
        input_format="parquet",
        mode="process",
    )
    assert ttt > 0
    for n in (1, 2):
        s, e = _window(f"{base}_{n}.csv")
        assert s is not None and e is not None and e >= s
