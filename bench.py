"""Benchmark entry point for the driver: JSON result lines on stdout.

Measurements on the real chip, through the full SQL engine (parse/bind/
execute on device) over generated SF>=1 data:

  1. q3 hot path (scan -> star-join -> group-aggregate -> sort): fact rows
     processed per second per chip, steady-state (post-compile). This is the
     headline metric; vs_baseline compares against the best previously
     recorded round (RECORDED_BASELINE_ROWS_PER_SEC), so regressions are
     visible instead of hard-coded away.
  2. Transcode (Load Test) rows/s: SF1 raw CSV -> parquet conversion rate
     (reference metric shape: nds/nds_transcode.py:174-205; BASELINE.md
     milestone #2).
  3. Power-Run geomean: geometric mean of per-query seconds over stream 0 of
     ALL executable templates at this scale, steady-state (reference metric
     shape: nds/nds_power.py:246-281; the TPC-DS north star in BASELINE.md).

Fail-soft contract: a complete JSON result line is (re)printed after the q3
measurement, after the transcode measurement, and after EVERY geomean query —
each line strictly supersedes the previous one, so the driver's `tail -1`
parse always sees the most complete results even if the process is killed
mid-run (the round-3 rc=124 timeout recorded nothing because the single
print sat at the very end).

Every emitted line is COMPACT: headline metrics, geomeans (steady + cold),
stream wall seconds, the engine-vs-sqlite ratio on the shared query subset,
failure counts + failed-query names, and the sf10 block — never the
per-query map (round 5's final line grew to ~1.3 MB of per-query detail and
the driver's tail window truncated its FRONT, losing the headline:
VERDICT item 2). Full per-query times and failure texts are written
atomically to a side file on every update (`detail_file` in the JSON,
default bench_detail.json next to this script, override NDS_BENCH_DETAIL).

After the SF1 stream, a secondary `sf10` block records the same metrics at
NDS scale factor 10 (wall-budgeted, fail-soft), and `sqlite_anchor` embeds
the external sqlite baseline over the identical SF1 stream (computed
offline by tools/sqlite_anchor.py into anchors/sqlite_sf1.json).

Measured SF10 state (2026-07-31, pre-blocked-path): transcode ~222k rows/s
and the first four queries complete (q3 steady 2.6s — 2.4x its SF1 time
for 10x data); query5's three-channel union (64M-row concat capacity x
~10 columns) was the single-chip HBM ceiling — it hard-OOMed the device,
poisoning the backend irrecoverably, so the loop bailed after 3
consecutive OOMs and skipped queries 5-99. The engine now routes
union-feeding-aggregate plans (through projections/filters AND inner
joins — the query5 channel shape) into blocked (morsel-style)
union-aggregation (engine/exec.py:_blocked_union_ctx): each union branch
is evaluated, joined and partially aggregated in bounded row windows
sized from the session HBM budget, so the full concat never materializes
and queries past query5 now record times or per-query errors instead of
an "aborted" marker. The consecutive-OOM bail now only counts OOMs from
queries that did NOT route through the blocked path (those can still
poison the backend).

Env knobs: NDS_BENCH_SCALE (default 1), NDS_BENCH_DATA,
NDS_BENCH_DATA_SF10 (default: NDS_BENCH_DATA + "_sf10.0", else
/tmp/nds_bench_sf10.0), NDS_BENCH_SKIP_GEOMEAN, NDS_BENCH_SKIP_TRANSCODE,
NDS_BENCH_SKIP_SF10, NDS_BENCH_SF10_BUDGET (s), NDS_BENCH_QUERY_TIMEOUT,
NDS_BENCH_QUERY_SUBSET (comma-separated query names, debug aid), and the
engine's NDS_UNION_AGG_WINDOW_ROWS (blocked union-aggregation window size;
default derived from the catalog device budget).
"""

import json
import math
import os
import signal
import statistics
import subprocess
import sys
import time

SCALE = float(os.environ.get("NDS_BENCH_SCALE", "1"))
DATA_DIR = os.environ.get("NDS_BENCH_DATA", f"/tmp/nds_bench_sf{SCALE}")
# best previously recorded single-chip q3 number (bench round 1)
RECORDED_BASELINE_ROWS_PER_SEC = 174_607
QUERY = """
select d.d_year, i.i_brand_id brand_id, i.i_brand brand,
       sum(ss_ext_sales_price) sum_agg
from date_dim d, store_sales, item i
where d.d_date_sk = ss_sold_date_sk and ss_item_sk = i.i_item_sk
  and i.i_manager_id = 10 and d.d_moy = 11
group by d.d_year, i.i_brand, i.i_brand_id
order by d.d_year, sum_agg desc, brand_id
limit 100
"""

# the one result object, mutated in place and re-printed monotonically.
# COMPACT by contract: per-query detail goes to DETAIL (side file), never
# into an emitted line. NDS_BENCH_EMIT_DETAIL=1 (the SF10 isolation child)
# folds the detail into every line so the parent can read it from stdout.
OUT = {
    "metric": "nds_q3_fact_rows_per_sec_per_chip",
    "value": None,
    "unit": "rows/s",
    "vs_baseline": None,
    "scale_factor": SCALE,
}

# full per-query evidence: {"per_query": {...}, "failed": {...},
# "sf10": {"per_query": ..., "failed": ...}} — written to DETAIL_PATH
DETAIL = {}
DETAIL_PATH = os.environ.get(
    "NDS_BENCH_DETAIL",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "bench_detail.json"),
)
SQLITE_PER_QUERY = {}  # loaded by load_sqlite_anchor (shared-subset ratio)


def _current_out():
    """The dict an output line carries right now: OUT, plus the folded-in
    main detail when NDS_BENCH_EMIT_DETAIL is set (the SF10 isolation
    child's stdout protocol). Shared by emit() and the SIGTERM flush so
    the two can never drift."""
    if os.environ.get("NDS_BENCH_EMIT_DETAIL"):
        out = dict(OUT)
        out.update(DETAIL.get("main", {}))
        return out
    return OUT


def emit():
    """Print the current result as one complete JSON line (fail-soft)."""
    print(json.dumps(_current_out()), flush=True)


def write_detail():
    """Atomically persist the per-query detail side file (tmp + rename: a
    SIGKILL mid-write must not leave a torn artifact)."""
    if os.environ.get("NDS_BENCH_SF10_CHILD"):
        # the isolation child reports through stdout (NDS_BENCH_EMIT_DETAIL)
        # and inherits the parent's DETAIL_PATH: writing here would replace
        # the parent's SF1 detail with the child's subset mid-run
        return
    try:
        tmp = DETAIL_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(DETAIL, f, indent=1, sort_keys=True)
        os.replace(tmp, DETAIL_PATH)
        OUT["detail_file"] = DETAIL_PATH
    except OSError as exc:
        # detail is evidence, not the contract: never take the run down
        print(f"detail side file failed: {exc}", file=sys.stderr)


def _on_term(signum, frame):
    # the driver's timeout sends SIGTERM before SIGKILL. Every OUT mutation
    # is already followed by emit(), so the last stdout line is current;
    # buffered print/emit here could hit a reentrant-call RuntimeError if
    # the signal lands mid-print (and that error would be swallowed by the
    # geomean loop's except). Raw writes + immediate exit only.
    try:
        # leading newline terminates any half-flushed buffered line so the
        # final line on stdout is always a complete JSON object (the
        # isolation child's detail fold-in rides _current_out, same as
        # every regular emit)
        os.write(1, ("\n" + json.dumps(_current_out()) + "\n").encode())
        os.write(2, b"SIGTERM: flushed partial results\n")
    except OSError:
        pass
    os._exit(0)


def ensure_data(scale=None, data_dir=None, parallel=4):
    scale = SCALE if scale is None else scale
    data_dir = DATA_DIR if data_dir is None else data_dir
    marker = os.path.join(data_dir, ".complete")
    if os.path.exists(marker):
        return
    here = os.path.dirname(os.path.abspath(__file__))
    subprocess.run(
        [
            sys.executable, "-m", "nds_tpu.cli.gen_data",
            "--scale", str(scale), "--parallel", str(parallel),
            "--data_dir", data_dir, "--overwrite_output",
        ],
        check=True,
        cwd=here,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    open(marker, "w").close()


def bench_q3(sess, fact_rows):
    # measured runs execute for real: the session plan-result cache would
    # otherwise turn a re-run into a dict lookup
    sess.conf["engine.plan_cache"] = "off"
    try:
        sess.sql(QUERY).collect()  # warmup: device transfer + compile cache
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            sess.sql(QUERY).collect()
            times.append(time.perf_counter() - t0)
    finally:
        sess.conf["engine.plan_cache"] = "on"
    return fact_rows / statistics.median(times)


def bench_transcode(data_dir=None):
    """CSV -> parquet transcode rate (rows/s) on the flagship fact table,
    hive-partitioned by date (the BASELINE "rows/sec/chip" fact path;
    reference metric shape: nds/nds_transcode.py:174-205)."""
    import shutil
    import tempfile

    from nds_tpu.schema import get_schemas
    from nds_tpu.transcode import transcode_table

    schemas = get_schemas()
    tables = ["store_sales"]
    out = tempfile.mkdtemp(prefix="nds_transcode_bench_")
    rows = 0
    try:
        t0 = time.perf_counter()
        for t in tables:
            rows += transcode_table(
                data_dir or DATA_DIR, out, t, schemas[t],
                output_format="parquet", output_mode="overwrite",
            )
        dt = time.perf_counter() - t0
    finally:
        shutil.rmtree(out, ignore_errors=True)
    return rows / dt


def bench_geomean(sess, block=None, scale=None, wall_budget=None):
    """Steady-state per-query seconds over stream 0 of every template.
    Writes into `block` (default: OUT itself) and re-emits after every
    query (fail-soft). `wall_budget` seconds, if set, stops the loop early
    with a truncation marker (the secondary-scale block must not starve
    the driver's overall budget)."""
    import tempfile

    from nds_tpu.datagen.query_streams import generate_streams
    from nds_tpu.power import gen_sql_from_stream

    block = OUT if block is None else block
    scale = SCALE if scale is None else scale
    wall_start = time.monotonic()
    with tempfile.TemporaryDirectory() as d:
        generate_streams(d, 1, scale, rngseed=19620718)
        queries = gen_sql_from_stream(os.path.join(d, "query_0.sql"))
    subset = os.environ.get("NDS_BENCH_QUERY_SUBSET")
    if subset:
        keep = {s.strip() for s in subset.split(",") if s.strip()}
        queries = {n: q for n, q in queries.items() if n in keep}
        if not queries:
            print(f"NDS_BENCH_QUERY_SUBSET={subset!r} matched no queries "
                  f"(names look like 'query3')", file=sys.stderr)
    detail = {}      # name -> {"cold": s, "steady": s}; steady feeds geomean
    failed = {}      # name -> error text (artifact evidence)
    consecutive_oom = 0  # poisoned-backend detector for UNBLOCKED queries

    # daemon-thread timeout: a wedged device runtime blocks inside native
    # code where signals never fire; joining a daemon thread with a timeout
    # still returns control, and daemon threads don't block process exit
    per_query_budget = int(os.environ.get("NDS_BENCH_QUERY_TIMEOUT", "900"))

    def run_with_timeout(q, budget, meta=None):
        import threading

        box = {}

        def work():
            def attempt():
                # error as TEXT, never a live exception: a held traceback
                # would pin the failed attempt's device intermediates
                # through the recovery
                r = None
                try:
                    r = sess.run_script(q)
                    if r is not None:
                        r.collect()
                    err = None
                except Exception as exc:
                    err = str(exc) or type(exc).__name__
                # blocked union-agg marker, read in the query's OWN thread:
                # the Result's executor is per-query (race-free even when a
                # previous query's wedged thread is still running); the
                # session-level marker is the fallback for statements that
                # executed eagerly (CreateTempView) outside this Result.
                # Attribution is script-scoped: a script where one
                # statement routed blocked and a DIFFERENT one OOMed
                # unblocked is still exempted — acceptable slack for a
                # bail heuristic (the abort just needs more evidence)
                ex = getattr(r, "executor", None)
                if getattr(ex, "last_blocked_union", None) is not None or (
                    getattr(sess, "last_blocked_union", None) is not None
                ):
                    box["blocked"] = True
                # out-of-core marker (same contract): a statement that
                # routed through the spill paths gets the same OOM-bail
                # exemption — its OOM is a per-query error, not backend
                # poisoning evidence
                if getattr(ex, "last_spill", None) is not None or (
                    getattr(sess, "last_spill", None) is not None
                ):
                    box["spilled"] = True
                return err

            from nds_tpu import faults

            err = attempt()
            if err is not None and faults.classify(err) == faults.DEVICE_OOM:
                # mid-execution device OOM: drop caches, retry once on a
                # clean device (one OOM must not poison the stream)
                sess.recover_memory("device memory exhausted")
                err = attempt()
                if err is not None and faults.classify(err) == faults.DEVICE_OOM:
                    sess.recover_memory("device memory exhausted")
            if err is None:
                box["ok"] = True
            else:
                box["exc"] = RuntimeError(err)

        th = threading.Thread(target=work, daemon=True)
        th.start()
        th.join(budget)
        finished_late = False
        wedged = False
        if th.is_alive():
            # grace join: distinguish slow-but-progressing from wedged; a
            # still-stuck worker must not race the next query on the shared
            # session, so a true wedge aborts the whole geomean
            th.join(60)
            if th.is_alive():
                wedged = True
            else:
                finished_late = True
        # read the blocked marker AFTER the grace join: a slow blocked query
        # sets box["blocked"] late, and the OOM-bail exemption must still
        # see it when the exception below is raised
        if meta is not None and box.get("blocked"):
            meta["blocked"] = True
        if meta is not None and box.get("spilled"):
            meta["spilled"] = True
        if wedged:
            return "wedged"
        if "exc" in box:  # real failures beat the timeout label
            raise box["exc"]
        if "ok" in box:
            # a query that only finished during the grace join still blew
            # its budget: record it as a timeout, not a success
            return "timeout" if finished_late else "ok"
        return "timeout"

    dbucket = DETAIL.setdefault("main" if block is OUT else "sf10", {})

    def update_out():
        _fill_block(block, detail, failed, wall_start)
        # persistent AOT executable cache evidence (ISSUE 11): hit/miss
        # counts ride every block next to cold_vs_steady, so a round shows
        # whether cold time was compile (misses) or disk (disk_hits) —
        # the isolation children report theirs through the same fold-in
        aot = getattr(sess, "aot_cache", None)
        if aot is not None:
            s = aot.stats
            block["aot_cache"] = {
                "disk_hits": s["disk_hits"],
                "misses": s["misses"],
                "stores": s["stores"],
            }
        dbucket["per_query"] = {
            n: {
                "cold": round(v["cold"], 2),
                "steady": round(v["steady"], 3),
                **({"spill": v["spill"]} if "spill" in v else {}),
                **(
                    {"budget_verdict": v["budget_verdict"]}
                    if "budget_verdict" in v
                    else {}
                ),
            }
            for n, v in detail.items()
        }
        if failed:
            dbucket["failed"] = {n: e[:500] for n, e in failed.items()}
        if block is OUT and SQLITE_PER_QUERY and detail:
            # engine-vs-sqlite on the SHARED subset (queries both engines
            # completed): the anchor's own geomean excludes its timeouts,
            # so the headline ratio must compare like with like
            shared = [n for n in detail if n in SQLITE_PER_QUERY]
            if shared:
                eng = _geomean([detail[n]["steady"] for n in shared])
                sq = _geomean([SQLITE_PER_QUERY[n] for n in shared])
                OUT["sqlite_shared"] = {
                    "queries": len(shared),
                    "engine_geomean_sec": round(eng, 4),
                    "sqlite_geomean_sec": round(sq, 4),
                    "ratio": round(eng / sq, 3),
                }
                # HEADLINE (ROADMAP item 3): the flat ratio rides every
                # OUT line until it crosses 1.0 — `profile --bench` diffs
                # it across rounds
                OUT["sqlite_shared_ratio"] = round(eng / sq, 3)
        write_detail()
        emit()

    for i, (name, q) in enumerate(queries.items()):
        if wall_budget is not None and time.monotonic() - wall_start > wall_budget:
            block["truncated_after"] = i
            emit()
            break
        sess.last_blocked_union = None  # set by blocked union-agg execution
        sess.last_spill = None  # set by out-of-core (spilled) execution
        meta = {}  # run_with_timeout sets meta["blocked"] when it routed
        try:
            t0 = time.perf_counter()
            status = run_with_timeout(q, per_query_budget, meta)
            cold = time.perf_counter() - t0
            if status == "ok":
                # steady-state timing measures true execution: disable the
                # session plan-result cache (the cold pass above keeps it,
                # mirroring a real Power Run sequence where e.g. part2
                # legitimately reuses part1's CTEs)
                sess.conf["engine.plan_cache"] = "off"
                try:
                    t0 = time.perf_counter()
                    status = run_with_timeout(q, per_query_budget, meta)
                    detail[name] = {
                        "cold": cold, "steady": time.perf_counter() - t0,
                    }
                    # per-query out-of-core evidence (ISSUE 9 acceptance):
                    # the spill stats + static budget verdict ride the
                    # bench detail so SF10 isolation output shows WHY a
                    # query completed degraded
                    spill_rec = getattr(sess, "last_spill", None)
                    if spill_rec:
                        detail[name]["spill"] = dict(spill_rec)
                    budget_rec = getattr(sess, "last_plan_budget", None)
                    if isinstance(budget_rec, dict) and budget_rec.get(
                        "verdict"
                    ):
                        detail[name]["budget_verdict"] = budget_rec["verdict"]
                finally:
                    sess.conf["engine.plan_cache"] = "on"
            if status == "ok":
                print(
                    f"[{i + 1}/{len(queries)}] {name}: cold={cold:.1f}s "
                    f"steady={detail[name]['steady']:.2f}s",
                    file=sys.stderr,
                )
                update_out()
                consecutive_oom = 0
                continue
            failed[name] = f"timeout (> {per_query_budget}s, {status})"
            detail.pop(name, None)
            print(f"[{i + 1}/{len(queries)}] {name}: TIMEOUT "
                  f"(> {per_query_budget}s)", file=sys.stderr)
            update_out()
            if status == "wedged":
                print("worker still stuck after grace join - backend "
                      "wedged; aborting geomean", file=sys.stderr)
                break
        except Exception as exc:
            failed[name] = str(exc) or type(exc).__name__
            print(f"[{i + 1}/{len(queries)}] {name}: FAILED {exc}",
                  file=sys.stderr)
            update_out()
            from nds_tpu import faults as _faults

            if _faults.classify(failed[name]) == _faults.DEVICE_OOM:
                # Queries that routed through the blocked union-aggregation
                # path (the SF10 OOM source, query5 and kin) no longer feed
                # the bail: their OOM is a per-query error worth recording,
                # not grounds to skip the stream. But a hard OOM on an
                # UNBLOCKED shape permanently poisoned the backend on the
                # device route of rounds 1-5, even after recover_memory
                # (not re-tested on an attached v5e), so three of those in
                # a row is taken to mean every further query would burn the
                # run budget failing the same way.
                if not meta.get("blocked") and not meta.get("spilled"):
                    if os.environ.get("NDS_BENCH_OOM_EXIT"):
                        # SF10 isolation child: a hard OOM on an unblocked
                        # plan is taken to poison this backend (above), so exit
                        # now (failure already recorded + emitted) and let
                        # the parent restart a fresh process for the
                        # remaining queries
                        block["oom_exit"] = name
                        emit()
                        sys.exit(17)
                    consecutive_oom += 1
                    if consecutive_oom >= 3:
                        block["aborted"] = (
                            "backend poisoned by device OOM on unblocked "
                            "plans; remaining queries skipped"
                        )
                        emit()
                        break
            else:
                consecutive_oom = 0


def _geomean(vals):
    return math.exp(sum(math.log(max(v, 1e-4)) for v in vals) / len(vals))


def _fill_block(block, detail, failed, wall_start):
    """Compact summary fields for an emitted block: steady + cold geomeans,
    cold/steady ratio (VERDICT items 4/5: TPC-DS times actual single
    executions, so cold must be first-class), stream wall clock, failure
    counts + names — never the per-query map (that goes to DETAIL)."""
    if detail:
        block["geomean_query_sec"] = round(
            _geomean([v["steady"] for v in detail.values()]), 4
        )
        block["cold_geomean_query_sec"] = round(
            _geomean([v["cold"] for v in detail.values()]), 4
        )
        block["cold_vs_steady"] = round(
            block["cold_geomean_query_sec"] / block["geomean_query_sec"], 3
        )
        block["slowest5"] = [
            [n, round(v["steady"], 2)]
            for n, v in sorted(
                detail.items(), key=lambda kv: -kv[1]["steady"]
            )[:5]
        ]
    block["geomean_queries"] = len(detail)
    block["stream_wall_sec"] = round(time.monotonic() - wall_start, 1)
    if failed:
        block["failed_queries"] = sorted(failed)
        block["failed_count"] = len(failed)


def load_sqlite_anchor():
    """Embed the offline-computed external sqlite baseline (same data, same
    stream, same host — tools/sqlite_anchor.py) so the engine geomean in
    this artifact always sits next to an independent engine's number."""
    p = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "anchors",
        "sqlite_sf1.json",
    )
    try:
        with open(p) as f:
            a = json.load(f)
    except Exception:
        # the anchor is an optional embellishment: a missing or truncated
        # file must never break the fail-soft artifact contract
        return
    OUT["sqlite_anchor"] = {
        k: a.get(k)
        for k in (
            "engine", "geomean_completed_sec", "completed",
            "timeout_or_failed", "per_query_budget_s",
        )
    }
    SQLITE_PER_QUERY.update(a.get("per_query") or {})


def main():
    if os.environ.get("NDS_BENCH_SF10_CHILD"):
        sf10_child_main()
        return
    signal.signal(signal.SIGTERM, _on_term)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    load_sqlite_anchor()
    ensure_data()

    from nds_tpu.engine.session import Session
    from nds_tpu.schema import get_schemas

    sess = Session()
    schemas = get_schemas()
    for t, schema in schemas.items():
        path = os.path.join(DATA_DIR, t)
        if os.path.isdir(path):
            sess.register_csv_dir(t, path, schema)
    fact_rows = sess.catalog.load("store_sales").nrows

    rows_per_sec = bench_q3(sess, fact_rows)
    OUT["value"] = round(rows_per_sec)
    OUT["vs_baseline"] = round(
        rows_per_sec / RECORDED_BASELINE_ROWS_PER_SEC, 3
    )
    emit()  # q3 headline lands no matter what happens later

    if not os.environ.get("NDS_BENCH_SKIP_TRANSCODE"):
        try:
            OUT["transcode_rows_per_sec"] = round(bench_transcode())
        except Exception as exc:
            print(f"transcode bench failed: {exc}", file=sys.stderr)
        emit()

    if not os.environ.get("NDS_BENCH_SKIP_GEOMEAN"):
        bench_geomean(sess)
    emit()

    if not os.environ.get("NDS_BENCH_SKIP_SF10") and SCALE == 1.0:
        try:
            bench_sf10(sess)
        except Exception as exc:
            OUT.setdefault("sf10", {})["error"] = str(exc)[:500]
        emit()

    if os.environ.get("NDS_BENCH_MAINT_UNDER_LOAD"):
        # opt-in robustness block: DM_* commits + a lease-safe vacuum
        # racing a query stream over a tiny lakehouse warehouse, reported
        # as maintenance throughput x query p99 degradation (the
        # full_bench maintenance_under_load phase's metric, embedded in
        # the bench artifact so rounds can track it). Fail-soft.
        try:
            OUT["maintenance_under_load"] = bench_maintenance_under_load()
        except Exception as exc:
            OUT["maintenance_under_load"] = {"error": str(exc)[:500]}
        emit()

    if os.environ.get("NDS_BENCH_SERVE"):
        # opt-in serve block (NDS_BENCH_SERVE=1): the closed-loop
        # multi-client QPS x p99 scenario (tools/serve_bench.py) beside
        # the TPC-DS composite — point lookups + heavy aggregates + DM
        # writes against the serve endpoint, snapshot-consistency
        # asserted per response. Fail-soft like the block above.
        try:
            OUT["serve"] = bench_serve()
        except Exception as exc:
            OUT["serve"] = {"error": str(exc)[:500]}
        emit()

    # carry-forward hygiene (ROADMAP): every round auto-compares its
    # sqlite_shared headline against the newest stored BENCH_r*.json via
    # the profiler's --bench comparison, instead of relying on someone
    # remembering the manual `profile --bench OLD NEW` invocation
    compare_against_baseline()
    emit()


def bench_serve():
    """Run tools/serve_bench.run_bench (in-process, ephemeral port) over
    the marker-cached SF0.01 lakehouse and return the compact headline
    fields. Knobs: NDS_BENCH_SERVE_CLIENTS (4), NDS_BENCH_SERVE_DURATION
    seconds (30)."""
    import importlib.util

    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(here, "tools", "serve_bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    r = mod.run_bench(
        clients=int(os.environ.get("NDS_BENCH_SERVE_CLIENTS", "4")),
        duration_s=float(os.environ.get("NDS_BENCH_SERVE_DURATION", "30")),
    )
    DETAIL["serve"] = r
    return {
        k: r.get(k)
        for k in (
            "qps", "p50_ms", "p99_ms", "scraped_p99_ms", "requests",
            "completed", "http_5xx", "rejected_429", "snapshot_violations",
            "dm_commits", "wall_s", "clients", "workers",
        )
    }


def compare_against_baseline():
    """Auto round comparison: diff this run's sqlite_shared headline
    against the stored baseline round (NDS_BENCH_BASELINE, else the
    newest BENCH_r*.json next to this script) through the same
    `profile --bench` comparison the manual invocation uses. Fail-soft:
    a malformed baseline must never cost the round its metrics."""
    try:
        import glob
        import tempfile

        here = os.path.dirname(os.path.abspath(__file__))
        base = os.environ.get("NDS_BENCH_BASELINE")
        if not base:
            rounds = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
            base = rounds[-1] if rounds else None
        if not base or not OUT.get("sqlite_shared"):
            return
        from nds_tpu.cli.profile import _compare_sqlite_shared

        fd, tmp = tempfile.mkstemp(suffix=".json")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(OUT, f)
            recs = _compare_sqlite_shared(base, tmp)
        finally:
            os.unlink(tmp)
        rec = next(
            (r for r in recs if r.get("change") in ("headline", "regression")),
            None,
        )
        if rec is not None:
            OUT["baseline_compare"] = {
                "baseline": os.path.basename(base),
                "old_ratio": rec.get("old_ratio"),
                "new_ratio": rec.get("new_ratio"),
                "regressed": rec.get("change") == "regression",
            }
    except Exception as exc:
        OUT["baseline_compare"] = {"error": str(exc)[:200]}


def bench_maintenance_under_load():
    """Maintenance-under-load at SF0.01 (NDS_BENCH_MAINT_UNDER_LOAD=1):
    build (once, marker-cached) a tiny raw set + refresh set + lakehouse
    warehouse + query stream under NDS_BENCH_MUL_DIR (default
    /tmp/nds_bench_mul), then run nds_tpu.maintenance.
    run_maintenance_under_load over a small query subset. Returns the
    compact report dict (p99 degradation + dm throughput)."""
    base = os.environ.get("NDS_BENCH_MUL_DIR", "/tmp/nds_bench_mul")
    raw = os.path.join(base, "raw")
    refresh = os.path.join(base, "refresh")
    wh = os.path.join(base, "warehouse")
    streams = os.path.join(base, "streams")
    here = os.path.dirname(os.path.abspath(__file__))
    ensure_data(scale=0.01, data_dir=raw, parallel=2)
    if not os.path.exists(os.path.join(refresh, ".complete")):
        subprocess.run(
            [sys.executable, "-m", "nds_tpu.cli.gen_data", "--scale",
             "0.01", "--parallel", "2", "--data_dir", refresh,
             "--update", "1", "--overwrite_output"],
            check=True, cwd=here, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        open(os.path.join(refresh, ".complete"), "w").close()
    if not os.path.exists(os.path.join(wh, ".complete")):
        subprocess.run(
            [sys.executable, "-m", "nds_tpu.cli.transcode", raw, wh,
             os.path.join(wh, "load.report"), "--output_format",
             "lakehouse", "--output_mode", "overwrite"],
            check=True, cwd=here, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        open(os.path.join(wh, ".complete"), "w").close()
    stream_file = os.path.join(streams, "query_1.sql")
    if not os.path.exists(stream_file):
        from nds_tpu.datagen.query_streams import generate_streams

        generate_streams(streams, 2, 0.01, rngseed=19620718)

    from nds_tpu.maintenance import run_maintenance_under_load

    report = run_maintenance_under_load(
        warehouse_path=wh,
        refresh_data_path=refresh,
        stream_file=stream_file,
        time_log_output_path=os.path.join(base, "mul_time.csv"),
        report_path=os.path.join(base, "mul_report.json"),
        spec_queries=os.environ.get(
            "NDS_BENCH_MUL_FUNCS", "LF_SS,DF_SS"
        ).split(","),
        sub_queries=os.environ.get(
            "NDS_BENCH_MUL_QUERIES", "query3,query7,query52"
        ).split(","),
    )
    # compact: the artifact line carries the headline fields only
    return {
        k: report.get(k)
        for k in (
            "queries", "query_p99_ms_solo", "query_p99_ms_under_load",
            "query_p99_degradation", "dm_functions", "dm_failed",
            "dm_functions_per_s", "vacuums", "vacuum_files_removed",
            "under_load_failed",
        )
    }


def _sf10_data_dir() -> str:
    """SF10 data dir: NDS_BENCH_DATA_SF10 wins outright; else a
    "_sf10.0"-suffixed sibling of NDS_BENCH_DATA (an operator redirecting
    SF1 data to a larger volume gets SF10 on the same volume, not ~10 GB
    silently dumped under /tmp); /tmp only as the last-resort default."""
    explicit = os.environ.get("NDS_BENCH_DATA_SF10")
    if explicit:
        return explicit
    base = os.environ.get("NDS_BENCH_DATA")
    if base:
        return base.rstrip("/") + "_sf10.0"
    return "/tmp/nds_bench_sf10.0"


def _sf10_session(data_dir):
    from nds_tpu.engine.session import Session
    from nds_tpu.schema import get_schemas

    sess = Session()
    # SF10 fact caps are 32M rows: a single multi-column pair table is
    # GB-scale, and one hard OOM poisoned the backend for the whole rest of
    # the stream in rounds 1-5 (not re-tested on an attached v5e). Trade
    # table-reload time for headroom.
    sess.catalog.DEVICE_BUDGET_BYTES = 3 << 30
    for t, schema in get_schemas().items():
        path = os.path.join(data_dir, t)
        if os.path.isdir(path):
            sess.register_csv_dir(t, path, schema)
    return sess


def _stream_query_names(scale):
    """Query names of stream 0 at `scale`, in stream order (the parent
    needs them to assign work to isolation children and to identify the
    query a dead child was running)."""
    import tempfile

    from nds_tpu.datagen.query_streams import generate_streams
    from nds_tpu.power import gen_sql_from_stream

    with tempfile.TemporaryDirectory() as d:
        generate_streams(d, 1, scale, rngseed=19620718)
        return list(gen_sql_from_stream(os.path.join(d, "query_0.sql")))


def _last_json_line(text):
    for line in reversed((text or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


_OOM_EXIT_RC = 17  # child recorded the OOM itself before exiting


def sf10_child_main():
    """Isolation child (NDS_BENCH_SF10_CHILD=1): run the assigned SF10
    query subset (NDS_BENCH_QUERY_SUBSET) on a fresh backend, emitting
    fail-soft JSON lines WITH per-query detail (the parent reads them from
    stdout). Exits 17 after recording an unblocked device OOM so the
    parent restarts a clean process for the remaining queries."""
    signal.signal(signal.SIGTERM, _on_term)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    os.environ["NDS_BENCH_EMIT_DETAIL"] = "1"
    os.environ["NDS_BENCH_OOM_EXIT"] = "1"
    sess = _sf10_session(_sf10_data_dir())
    budget = int(os.environ.get("NDS_BENCH_SF10_WALL_BUDGET", "2700"))
    bench_geomean(sess, block=OUT, scale=10, wall_budget=budget)
    emit()


def bench_sf10(sess_sf1):
    """Secondary block at SF10 (BASELINE ladder: the next rung after SF1;
    store_sales = 28.8M rows — fits HBM, stresses every capacity
    heuristic). Fail-soft into OUT['sf10'].

    Per-query-failure SUBPROCESS ISOLATION (VERDICT item 8): queries run
    in a child process; when one dies on a device OOM (or crashes/wedges),
    only THAT query is recorded as failed and a fresh child continues with
    the remaining ones — one OOM no longer poisons/aborts the rest of the
    block. NDS_BENCH_SF10_ISOLATION=inproc restores the single-process
    path (debug aid). The loop is wall-budgeted; a SIGTERM at any point
    still flushes whatever the block has recorded so far."""
    block = OUT.setdefault("sf10", {})
    data_dir = _sf10_data_dir()
    ensure_data(scale=10, data_dir=data_dir, parallel=8)
    block["transcode_rows_per_sec"] = round(bench_transcode(data_dir))
    emit()
    # free the SF1 session's device residency before SF10 work starts
    sess_sf1.recover_memory("switching to SF10 data")
    budget = int(os.environ.get("NDS_BENCH_SF10_BUDGET", "2700"))
    if os.environ.get("NDS_BENCH_SF10_ISOLATION", "process") == "inproc":
        bench_geomean(
            _sf10_session(data_dir), block=block, scale=10,
            wall_budget=budget,
        )
        return

    here = os.path.dirname(os.path.abspath(__file__))
    names = _stream_query_names(scale=10)
    subset = os.environ.get("NDS_BENCH_QUERY_SUBSET")
    if subset:
        keep = {s.strip() for s in subset.split(",") if s.strip()}
        names = [n for n in names if n in keep]
    # shared AOT executable cache for the isolation children (ISSUE 11):
    # every fresh child process warms its fused-pipeline executables from
    # disk instead of re-paying the whole compile footprint — the explicit
    # env pin means restarted children (and a restarted parent) agree on
    # ONE directory even if the ambient default ever changes mid-round
    from nds_tpu.engine.aotcache import resolve_aot_cache_dir

    aot_dir = resolve_aot_cache_dir()
    t_start = time.monotonic()
    detail = {}  # name -> {"cold", "steady"} (floats, parent-side)
    failed = {}
    dbucket = DETAIL.setdefault("sf10", {})

    def update_block():
        _fill_block(block, detail, failed, t_start)
        dbucket["per_query"] = dict(detail)
        if failed:
            dbucket["failed"] = {n: e[:500] for n, e in failed.items()}
        write_detail()
        emit()

    # one round-level trace context: every isolation child parents to it
    from nds_tpu.obs.trace import resolve_trace_context

    round_ctx = resolve_trace_context("sf10-round")
    remaining = list(names)
    while remaining:
        left = budget - (time.monotonic() - t_start)
        if left <= 60:
            block["truncated_after"] = len(names) - len(remaining)
            update_block()
            break
        env = dict(os.environ)
        env["NDS_BENCH_SF10_CHILD"] = "1"
        env["NDS_BENCH_QUERY_SUBSET"] = ",".join(remaining)
        env["NDS_BENCH_SF10_WALL_BUDGET"] = str(int(left))
        if aot_dir:
            env["NDS_AOT_CACHE_DIR"] = aot_dir
        # per-child trace context: the isolation child's event files (and
        # any failure bundle it flushes before dying) carry a trace_id the
        # parent minted — attribution survives pid recycling across the
        # many children a long SF10 round respawns
        round_ctx.child(f"sf10-{len(remaining)}left").export(env)
        stderr_tail = ""
        budget_kill = False
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, cwd=here, capture_output=True, text=True,
                timeout=left + 120,
            )
            rc, out_text = p.returncode, p.stdout
            stderr_tail = (p.stderr or "")[-300:]
        except subprocess.TimeoutExpired as te:
            # the parent's own wall budget (plus grace) expired: this is
            # TRUNCATION, not a query failure — the query the child was on
            # must not enter `failed` as if it broke
            rc = -9
            budget_kill = True
            out_text = te.stdout or ""
            if isinstance(out_text, bytes):
                out_text = out_text.decode("utf-8", "replace")
            err_text = te.stderr or ""
            if isinstance(err_text, bytes):
                err_text = err_text.decode("utf-8", "replace")
            stderr_tail = err_text[-300:]
        child = _last_json_line(out_text) or {}
        cpq = child.get("per_query") or {}
        cfail = child.get("failed") or {}
        caot = child.get("aot_cache")
        if isinstance(caot, dict):
            # accumulate children's cache traffic: across a whole round
            # disk_hits should dominate misses once the first child warmed
            # each shape (the "recompile the world per child" fix, visible
            # in the artifact)
            agg = block.setdefault(
                "aot_cache", {"disk_hits": 0, "misses": 0, "stores": 0}
            )
            for k in ("disk_hits", "misses", "stores"):
                agg[k] += int(caot.get(k) or 0)
        detail.update(
            {n: v for n, v in cpq.items() if isinstance(v, dict)}
        )
        failed.update(cfail)
        covered = set(cpq) | set(cfail)
        new_remaining = [n for n in remaining if n not in covered]
        progressed = bool(covered & set(remaining))
        if budget_kill:
            remaining = new_remaining
            block["truncated_after"] = len(names) - len(remaining)
            update_block()
            break
        if new_remaining and (
            not progressed or rc not in (0, _OOM_EXIT_RC)
        ):
            # the child died mid-query (or produced nothing): blame the
            # first query it had not covered, then move past it — without
            # this the loop could respawn children forever on a
            # reproducible early crash
            victim = new_remaining.pop(0)
            failed[victim] = (
                f"subprocess died (rc={rc}): {stderr_tail}"
                if rc != 0
                else "subprocess made no progress"
            )
        remaining = new_remaining
        update_block()
        # anything left (child OOM-exit, crash, wedge-abort, or its own
        # wall-budget stop) loops back: the budget check at the top
        # decides whether a fresh child continues


if __name__ == "__main__":
    main()
