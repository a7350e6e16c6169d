"""External perf anchor: run the identical generated SF1 stream through
sqlite3 and record its per-query times next to the engine's.

The engine's geomean was previously self-referential (compared only to its
own earlier rounds). sqlite is the one wholly independent SQL engine baked
into this image (duckdb is not available), so its wall-clock over the same
data, same stream, same host gives an external ratio from which the
"A100-parity" north star can be extrapolated. sqlite gets a fair shake:
indexes on every surrogate-key column plus ANALYZE before timing, 60 s
per-query abort (its unindexable plans would otherwise run for hours).

Usage: python tools/sqlite_anchor.py [out.json]
Writes anchors/sqlite_sf1.json (read by bench.py into the OUT line).
"""

import json
import math
import os
import sqlite3
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests")
)

import pyarrow as pa  # noqa: E402

from nds_tpu.datagen.query_streams import generate_streams  # noqa: E402
from nds_tpu.io.csv import read_dat_dir  # noqa: E402
from nds_tpu.power import gen_sql_from_stream  # noqa: E402
from nds_tpu.schema import get_schemas  # noqa: E402
from test_oracle import _StddevSamp, _to_sqlite  # noqa: E402

DATA = os.environ.get("NDS_BENCH_DATA", "/tmp/nds_bench_sf1.0")
BUDGET_S = int(os.environ.get("NDS_SQLITE_BUDGET", "60"))


def load(conn, data_dir=DATA, tables=None):
    """Create, fill and index `tables` (default: every source table with a
    directory under `data_dir`) from the generator's .dat files."""
    import datetime

    schemas = get_schemas(use_decimal=False)
    for t, schema in schemas.items():
        path = os.path.join(data_dir, t)
        if (tables is not None and t not in tables) or not os.path.isdir(path):
            continue
        arrow = read_dat_dir(path, schema, use_decimal=False)
        conn.execute(
            f"create table {t} ({', '.join(f.name for f in schema)})"
        )
        ph = ",".join("?" * len(schema))
        dates = [
            i for i, f in enumerate(arrow.schema) if pa.types.is_date(f.type)
        ]
        # stream per record batch: to_pylist() of a whole SF1 fact table
        # would box tens of millions of Python values at once
        for batch in arrow.to_batches(max_chunksize=1 << 17):
            cols = [c.to_pylist() for c in batch.columns]
            for i in dates:
                cols[i] = [
                    v.isoformat() if isinstance(v, datetime.date) else v
                    for v in cols[i]
                ]
            conn.executemany(f"insert into {t} values ({ph})", zip(*cols))
        print(f"loaded {t}: {arrow.num_rows} rows", flush=True)
        # index every surrogate-key column: sqlite's nested-loop joins need
        # them; this is the fair (favorable-to-sqlite) configuration
        for f in schema:
            if f.name.endswith("_sk") or f.name.endswith("_number"):
                conn.execute(f"create index idx_{t}_{f.name} on {t}({f.name})")
    conn.execute("analyze")
    conn.commit()


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "anchors", "sqlite_sf1.json",
    )
    with tempfile.TemporaryDirectory() as d:
        generate_streams(d, 1, 1, rngseed=19620718)
        queries = gen_sql_from_stream(os.path.join(d, "query_0.sql"))

    conn = sqlite3.connect(":memory:")
    conn.create_aggregate("stddev_samp", 1, _StddevSamp)
    t0 = time.perf_counter()
    load(conn)
    print(f"load+index: {time.perf_counter() - t0:.1f}s", flush=True)

    per_query = {}
    failed = {}
    deadline = [0.0]

    def abort_if_late():
        return 1 if time.monotonic() > deadline[0] else 0

    conn.set_progress_handler(abort_if_late, 100_000)
    for i, (name, q) in enumerate(queries.items()):
        try:
            sql = _to_sqlite(q)
        except Exception as exc:
            failed[name] = f"lowering: {exc}"
            continue
        deadline[0] = time.monotonic() + BUDGET_S
        t0 = time.perf_counter()
        try:
            for stmt in [s for s in sql.split(";") if s.strip()]:
                cur = conn.execute(stmt)
                cur.fetchall()
            per_query[name] = time.perf_counter() - t0
            print(f"[{i+1}/{len(queries)}] {name}: {per_query[name]:.2f}s",
                  flush=True)
        except sqlite3.OperationalError as exc:
            if "interrupted" in str(exc):
                failed[name] = f"timeout (> {BUDGET_S}s)"
            else:
                failed[name] = str(exc)
            print(f"[{i+1}/{len(queries)}] {name}: {failed[name]}", flush=True)
        except Exception as exc:
            failed[name] = str(exc)
            print(f"[{i+1}/{len(queries)}] {name}: {failed[name]}", flush=True)

    result = {
        "engine": f"sqlite {sqlite3.sqlite_version} (indexed, in-memory)",
        "scale_factor": 1.0,
        "per_query_budget_s": BUDGET_S,
        "completed": len(per_query),
        "timeout_or_failed": len(failed),
        "geomean_completed_sec": (
            round(
                math.exp(
                    sum(math.log(max(t, 1e-4)) for t in per_query.values())
                    / len(per_query)
                ),
                4,
            )
            if per_query
            else None
        ),
        "per_query": {n: round(t, 3) for n, t in sorted(per_query.items())},
        "failed": failed,
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("per_query", "failed")}))


if __name__ == "__main__":
    main()
