"""q3 regression closer (ROADMAP A6): assert the join-order
memo holds the q3 shape's steady-state throughput.

Round 5 measured q3 at 2.92M fact-rows/s vs round 4's 3.31M — one extra
blocking device->host sync per steady run from the MultiJoin greedy cost
scan. `Session.join_order_cache` replays the recorded order instead; this
tool closes the loop with an executable assertion in two modes:

    python tools/q3_check.py              # structural (CI; synthetic data)
    python tools/q3_check.py --real       # measured (bench data required)

Structural mode builds a synthetic q3-shaped star (date_dim ⋈ store_sales
⋈ item, the exact bench QUERY text) and asserts the memo records the join
order on the cold run and replays it — unchanged, no re-record — on the
steady run with an identical result. Measured mode runs the real bench
measurement (NDS_BENCH_DATA, same protocol as bench.bench_q3) and fails
below NDS_Q3_MIN_ROWS_PER_SEC (default 3.2M rows/s — the round-4 rate the
memo must restore). Structural is wired into ci/tier1-check; measured
belongs to bench rounds on real data.
"""

import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

if "--real" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MIN_ROWS_PER_SEC = float(
    os.environ.get("NDS_Q3_MIN_ROWS_PER_SEC", "3200000")
)


def _q3_query():
    from bench import QUERY

    return QUERY


def _synthetic_star(n_fact=200_000, seed=11):
    import numpy as np
    import pyarrow as pa

    r = np.random.default_rng(seed)
    n_dates, n_items = 400, 300
    date_dim = pa.table(
        {
            "d_date_sk": pa.array(range(n_dates), pa.int32()),
            "d_year": pa.array(
                [1998 + (i // 120) for i in range(n_dates)], pa.int32()
            ),
            "d_moy": pa.array([1 + i % 12 for i in range(n_dates)],
                              pa.int32()),
        }
    )
    item = pa.table(
        {
            "i_item_sk": pa.array(range(n_items), pa.int32()),
            "i_brand_id": pa.array(
                [int(x) for x in r.integers(1, 40, n_items)], pa.int32()
            ),
            "i_brand": pa.array([f"brand#{i % 40}" for i in range(n_items)]),
            "i_manager_id": pa.array(
                [int(x) for x in r.integers(1, 20, n_items)], pa.int32()
            ),
        }
    )
    store_sales = pa.table(
        {
            "ss_sold_date_sk": pa.array(
                [int(x) for x in r.integers(0, n_dates, n_fact)], pa.int32()
            ),
            "ss_item_sk": pa.array(
                [int(x) for x in r.integers(0, n_items, n_fact)], pa.int32()
            ),
            "ss_ext_sales_price": pa.array(
                [round(float(x), 2) for x in r.uniform(0, 500, n_fact)],
                pa.float64(),
            ),
        }
    )
    return {"date_dim": date_dim, "store_sales": store_sales, "item": item}


def structural():
    from nds_tpu.engine.session import Session

    sess = Session(conf={"engine.plan_cache": "off"})
    for name, t in _synthetic_star().items():
        sess.register_arrow(name, t)
    q = _q3_query()
    cold = sess.sql(q).collect()
    recorded = {
        fp: dict(v) for fp, v in sess.join_order_cache.items() if "steps" in v
    }
    if not recorded:
        print("q3_check: FAILED (cold run recorded no join order — the "
              "memo is not engaging on the q3 shape)", file=sys.stderr)
        sys.exit(1)
    steady = sess.sql(q).collect()
    if not steady.equals(cold):
        print("q3_check: FAILED (replayed join order changed the result)",
              file=sys.stderr)
        sys.exit(1)
    for fp, v in recorded.items():
        now = sess.join_order_cache.get(fp)
        if now is None or now.get("steps") != v["steps"]:
            print("q3_check: FAILED (steady run re-recorded the join "
                  "order instead of replaying the memo)", file=sys.stderr)
            sys.exit(1)
    print(f"q3_check: OK (structural: {len(recorded)} join order(s) "
          f"recorded cold, replayed steady, identical result)")


def real():
    import statistics

    from bench import DATA_DIR, ensure_data
    from nds_tpu.engine.session import Session
    from nds_tpu.schema import get_schemas

    ensure_data()
    sess = Session(conf={"engine.plan_cache": "off"})
    for t, schema in get_schemas().items():
        path = os.path.join(DATA_DIR, t)
        if os.path.isdir(path):
            sess.register_csv_dir(t, path, schema)
    fact_rows = sess.catalog.load("store_sales").nrows
    q = _q3_query()
    sess.sql(q).collect()  # cold: transfer + compile + memo record
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        sess.sql(q).collect()
        times.append(time.perf_counter() - t0)
    rate = fact_rows / statistics.median(times)
    verdict = "OK" if rate >= MIN_ROWS_PER_SEC else "FAILED"
    print(f"q3_check: {verdict} (measured {rate:,.0f} fact-rows/s steady, "
          f"floor {MIN_ROWS_PER_SEC:,.0f})")
    if rate < MIN_ROWS_PER_SEC:
        sys.exit(1)


def main():
    if "--real" in sys.argv:
        real()
    else:
        structural()


if __name__ == "__main__":
    main()
