#!/usr/bin/env python
"""Closed-loop serve-mode benchmark: sustained QPS x p50/p99 under a
mixed multi-tenant workload, beside the TPC-DS composite.

The batch bench rounds measure one stream at a time; a query SERVICE is
measured by what it sustains under concurrent mixed load without falling
over. This driver stands up the real `nds_tpu/serve` service (the same
construction path `nds-tpu-submit serve` uses) over a marker-cached
SF0.01 lakehouse warehouse, then runs N closed-loop clients (each sends,
waits, sends again — no open-loop request storms) with a request mix of:

  * point lookups        (dimension single-row probes)
  * heavy aggregates     (the q3 star-join/group/sort shape)
  * snapshot-consistency reads over a DM-churned table
  * DM writes            (lakehouse INSERT commits racing the readers)

and reports sustained QPS, client-side p50/p99 per class, HTTP outcome
counts, and the SERVER-side p99 scraped from the live
`nds_serve_request_dur_ms` histogram on /metrics mid-run. The
consistency readers assert per-snapshot invariants (every key's count
identical within one response), so "queries are snapshot-consistent
under racing DM commits" is a measured number (violations == 0), not a
claim.

    python tools/serve_bench.py [--clients 4] [--duration 30] [--out F]
    python tools/serve_bench.py --smoke     # the CI gate: a short run
        that must finish with zero 5xx, zero snapshot violations, zero
        admission-rejected requests, and p99 under a generous bound
    python tools/serve_bench.py --smoke --fleet [--fleet_replicas 2]
        # the same mixed load sent THROUGH the fleet router over N real
        # replica processes: fleet QPS x p99 from the router-side
        # histogram, plus an edge-reject probe (a cross-join whose
        # modeled peak is beyond the admission reject line) that must
        # come back 429 from the ROUTER with the probe tenant absent
        # from every replica's /statusz — the proof an edge-rejected
        # request never consumed a replica worker slot

Env: NDS_SERVE_BENCH_DIR (default /tmp/nds_serve_bench) for the
warehouse; the raw SF0.01 set is shared with the test suite's
marker-cached /tmp/nds_test_sf001.
"""

import argparse
import json
import math
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RAW_DATA = os.environ.get("NDS_SERVE_BENCH_RAW", "/tmp/nds_test_sf001")
BASE = os.environ.get("NDS_SERVE_BENCH_DIR", "/tmp/nds_serve_bench")

#: the q3 star shape (scan -> join -> group -> sort): the heavy class
HEAVY_SQL = """
select d.d_year, i.i_brand_id brand_id, i.i_brand brand,
       sum(ss_ext_sales_price) sum_agg
from date_dim d, store_sales, item i
where d.d_date_sk = ss_sold_date_sk and ss_item_sk = i.i_item_sk
  and i.i_manager_id = 10 and d.d_moy = 11
group by d.d_year, i.i_brand, i.i_brand_id
order by d.d_year, sum_agg desc, brand_id
limit 100
"""

POINT_SQL = (
    "select i_item_id, i_brand from item where i_item_sk = 1",
    "select d_date_id from date_dim where d_date_sk = 2450815",
    "select count(*) c from store",
)

#: the DM-churned table: 8 keys, one row per key at version 1; every DM
#: append adds exactly one more row PER KEY (v+1000 marks copies so they
#: are never re-copied), so in ANY committed snapshot all 8 per-key
#: counts are equal — a torn (non-snapshot) read shows unequal counts
CONSISTENCY_SQL = "select k, count(*) c from serve_dm group by k order by k"
DM_SQL = "insert into serve_dm select k, v + 1000 from serve_dm where v < 8"

#: the edge-reject probe: a full-width self-join + sort whose modeled
#: peak (~32 MB at SF0.01) is beyond the fleet replicas' admission
#: reject line (_FLEET_BUDGET_PROPS) with no windowing seam — the
#: router's /plan verdict probe sees `reject` and answers 429 at the
#: edge without a replica ever admitting (or even accounting) it
FLEET_REJECT_SQL = """
select a.*, b.* from store_sales a
join store_sales b on a.ss_ticket_number = b.ss_ticket_number
order by a.ss_ticket_number
"""

#: fleet replicas run with budget lines sized so the whole smoke mix is
#: verdict `direct` (heaviest shape models ~4.6 MB) while the reject
#: probe is beyond the reject line even windowed — measured values, see
#: the FLEET_REJECT_SQL note
_FLEET_BUDGET_PROPS = (
    f"engine.plan_budget_bytes={8 << 20}\n"
    f"engine.plan_budget_reject_bytes={16 << 20}\n"
)

#: one fleet replica: the real CLI construction path in a child process
#: (build_service + the serve_dm registration _start_service does)
_REPLICA_SCRIPT = """
import argparse, sys, threading
sys.path.insert(0, {repo!r})
from nds_tpu.cli.serve import build_service
ns = argparse.Namespace(
    warehouse_path=sys.argv[1], input_format="lakehouse", port=0,
    property_file=sys.argv[3], stream=None, job_dir=None, floats=False,
    aot_cache_dir=None,
)
service, server = build_service(ns)
service.session.register_lakehouse("serve_dm", sys.argv[2])
service.writer_session.register_lakehouse("serve_dm", sys.argv[2])
print(f"replica: listening on 127.0.0.1:{{server.port}}", flush=True)
threading.Event().wait()
"""


def _ensure_assets():
    """Marker-cached SF0.01 raw set + lakehouse warehouse + serve_dm."""
    if not os.path.exists(os.path.join(RAW_DATA, ".complete")):
        subprocess.run(
            [sys.executable, "-m", "nds_tpu.cli.gen_data", "--scale", "0.01",
             "--parallel", "2", "--data_dir", RAW_DATA,
             "--overwrite_output"],
            check=True, capture_output=True, cwd=REPO,
        )
        open(os.path.join(RAW_DATA, ".complete"), "w").close()
    wh = os.path.join(BASE, "warehouse")
    if not os.path.exists(os.path.join(wh, ".complete")):
        subprocess.run(
            [sys.executable, "-m", "nds_tpu.cli.transcode", RAW_DATA, wh,
             os.path.join(wh, "load.report"), "--output_format", "lakehouse",
             "--output_mode", "overwrite"],
            check=True, capture_output=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        open(os.path.join(wh, ".complete"), "w").close()
    dm_path = os.path.join(wh, "serve_dm")
    from nds_tpu.lakehouse.table import LakehouseTable

    if not LakehouseTable.is_table(dm_path):
        import numpy as np
        import pyarrow as pa

        LakehouseTable.create(dm_path, pa.table({
            "k": pa.array(np.arange(8), type=pa.int64()),
            "v": pa.array(np.arange(8), type=pa.int64()),
        }))
    return wh, dm_path


def _start_service(wh, dm_path, workers=None, job_dir=None):
    """The real CLI construction path, in-process on an ephemeral port."""
    from nds_tpu.cli.serve import build_service
    from nds_tpu.obs import metrics as obs_metrics

    obs_metrics.reset_shared()
    ns = argparse.Namespace(
        warehouse_path=wh, input_format="lakehouse", port=0,
        property_file=None, stream=None, job_dir=job_dir, floats=False,
    )
    if workers:
        os.environ["NDS_SERVE_WORKERS"] = str(workers)
    service, server = build_service(ns)
    # the DM-churn table is benchmark furniture, not a TPC-DS schema
    # table, so register_nds_tables skipped it
    service.session.register_lakehouse("serve_dm", dm_path)
    service.writer_session.register_lakehouse("serve_dm", dm_path)
    return service, server


def _spawn_replica(wh, dm_path, property_file):
    proc = subprocess.Popen(
        [sys.executable, "-c", _REPLICA_SCRIPT.format(repo=REPO),
         wh, dm_path, property_file],
        env={**os.environ, "NDS_METRICS_HOST": "127.0.0.1"},
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        m = re.search(r"listening on [^:]+:(\d+)", line)
        if m:
            return proc, int(m.group(1))
    proc.kill()
    raise SystemExit("serve_bench: fleet replica never announced a port")


def _start_fleet(wh, dm_path, n):
    """N real replica processes behind an in-process QueryRouter on its
    own listener; clients talk HTTP to the router, never a replica."""
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.obs import trace as obs_trace
    from nds_tpu.serve.router import QueryRouter

    pf = os.path.join(BASE, "fleet.properties")
    with open(pf, "w") as f:
        f.write(_FLEET_BUDGET_PROPS)
    procs, ports = [], []
    for _ in range(n):
        proc, port = _spawn_replica(wh, dm_path, pf)
        procs.append(proc)
        ports.append(port)
    obs_metrics.reset_shared()
    tracer = obs_trace.tracer_from_conf(
        {"engine.metrics_port": 0}, app_id="nds-route"
    )
    router = QueryRouter(
        [f"127.0.0.1:{p}" for p in ports], conf={}, tracer=tracer
    )
    server = obs_metrics.active_server()
    if server is None:
        raise SystemExit("serve_bench: router listener failed to bind")
    server.attach_app(router)
    obs_metrics.shared_sink().set_fleet_provider(router.fleet_snapshot)
    return procs, ports, router, server


def _stop_fleet(procs, router):
    router.close()
    for p in procs:
        if p.poll() is None:
            p.terminate()
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()


def _get_statusz(port):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/statusz", timeout=10
    ) as r:
        return json.loads(r.read().decode())


def _post(port, payload, tenant, timeout=300.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json",
                 "X-NDS-Tenant": tenant},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        try:
            body = json.loads(e.read().decode())
        except ValueError:
            body = {}
        return e.code, body


def _p(times, q):
    """Nearest-rank percentile of a ms list; None when empty."""
    if not times:
        return None
    ts = sorted(times)
    idx = max(int(math.ceil(q * len(ts))) - 1, 0)
    return round(float(ts[idx]), 3)


def _scrape_hist_p99(port, family="nds_serve_request_dur_ms"):
    """Server-side p99 estimate by inverting the live histogram's
    cumulative bucket counts (the upper bound of the bucket holding the
    99th-percentile rank)."""
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ) as r:
        text = r.read().decode()
    buckets = []
    for m in re.finditer(
        rf'{family}_bucket{{le="([^"]+)"}} (\d+)', text
    ):
        le = float("inf") if m.group(1) == "+Inf" else float(m.group(1))
        buckets.append((le, int(m.group(2))))
    if not buckets:
        return None, 0, text
    buckets.sort(key=lambda b: b[0])
    total = buckets[-1][1]
    if total == 0:
        return None, 0, text
    rank = max(int(math.ceil(0.99 * total)), 1)
    for le, cum in buckets:
        if cum >= rank:
            return (None if le == float("inf") else le), total, text
    return None, total, text


def run_bench(clients=4, duration_s=30.0, smoke=False, workers=None,
              fleet=0):
    """The closed-loop run; returns the report dict. `fleet=N` sends the
    same mix through a QueryRouter over N replica processes instead of
    one in-process service."""
    wh, dm_path = _ensure_assets()
    if fleet:
        procs, rports, router, server = _start_fleet(wh, dm_path, fleet)
        service = None
    else:
        service, server = _start_service(wh, dm_path, workers=workers)
    port = server.port
    results = []  # (class, tenant, status, ms, violation)
    results_lock = threading.Lock()
    stop = threading.Event()
    # per-client request budget in smoke mode (bounded, not timed): the
    # CI gate must be deterministic-ish in wall time
    smoke_requests = 6

    def record(cls, tenant, status, ms, violation=False):
        with results_lock:
            results.append((cls, tenant, status, ms, violation))

    def one_request(i, n):
        tenant = f"tenant-{i}"
        if i == 0 and n % 2 == 0:
            cls, payload = "dm", {"sql": DM_SQL}
        elif n % 3 == 0:
            cls, payload = "heavy", {"sql": HEAVY_SQL}
        elif n % 3 == 1:
            cls = "consistency"
            payload = {"sql": CONSISTENCY_SQL}
        else:
            cls = "point"
            payload = {"sql": POINT_SQL[n % len(POINT_SQL)]}
        t0 = time.perf_counter()
        status, body = _post(port, payload, tenant)
        ms = (time.perf_counter() - t0) * 1000.0
        violation = False
        if cls == "consistency" and status == 200:
            counts = {row[0]: row[1] for row in body.get("rows") or []}
            # one snapshot => every key appended the same number of times
            violation = len(set(counts.values())) > 1
        record(cls, tenant, status, ms, violation)

    def client(i):
        # warm this client's shapes once (cold XLA compile must not be
        # the only thing p99 measures), then the closed loop
        n = 0
        while not stop.is_set():
            one_request(i, n)
            n += 1
            if smoke and n >= smoke_requests:
                return

    what = (f"the fleet router over {fleet} replica(s)" if fleet
            else f":{port} ({service.workers} workers)")
    print(f"serve_bench: {clients} closed-loop clients against {what}",
          flush=True)
    wall_start = time.perf_counter()
    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(clients)
    ]
    for t in threads:
        t.start()
    # the edge-reject probes ride WHILE the mix is in flight, so "never
    # consumed a worker slot" is measured under real contention; kept
    # out of `results` — these 429s are the deliberate success case
    probe_results = []
    if fleet:
        for _ in range(3):
            try:
                probe_results.append(
                    _post(port, {"sql": FLEET_REJECT_SQL}, "edge-probe",
                          timeout=120.0)
                )
            except OSError:
                probe_results.append((599, {}))
    scraped_p99 = None
    scraped_total = 0
    exposition = None
    hist_family = ("nds_route_request_dur_ms" if fleet
                   else "nds_serve_request_dur_ms")
    deadline = time.monotonic() + (duration_s if not smoke else 600)
    # mid-run scrape loop: the server-side histogram must be live WHILE
    # clients are still sending (that is the "scraped mid-run" contract)
    while any(t.is_alive() for t in threads):
        if time.monotonic() >= deadline and not smoke:
            stop.set()
        try:
            p99, total, text = _scrape_hist_p99(port, family=hist_family)
            if total:
                scraped_p99, scraped_total, exposition = p99, total, text
        except OSError:
            pass
        time.sleep(0.5)
    for t in threads:
        t.join(120)
    wall_s = time.perf_counter() - wall_start
    # post-run churn check: the DM table's final state is itself one
    # consistent snapshot
    if fleet:
        status, body = _post(port, {"sql": CONSISTENCY_SQL}, "final")
        final_counts = {r[0]: r[1] for r in (body.get("rows") or [])}
        final_ok = status == 200 and len(set(final_counts.values())) == 1
    else:
        final = service.session.sql(CONSISTENCY_SQL).collect().to_pylist()
        final_counts = {r["k"]: r["c"] for r in final}
        final_ok = len(set(final_counts.values())) == 1
    from nds_tpu.obs.metrics import validate_exposition

    exposition_problems = (
        validate_exposition(exposition) if exposition else ["never scraped"]
    )
    by_class = {}
    for cls in ("point", "heavy", "consistency", "dm"):
        times = [r[3] for r in results if r[0] == cls and r[2] == 200]
        by_class[cls] = {
            "requests": sum(1 for r in results if r[0] == cls),
            "completed": len(times),
            "p50_ms": _p(times, 0.50),
            "p99_ms": _p(times, 0.99),
        }
    ok_times = [r[3] for r in results if r[2] == 200]
    report = {
        "clients": clients,
        "workers": None if fleet else service.workers,
        "wall_s": round(wall_s, 2),
        "requests": len(results),
        "completed": len(ok_times),
        "qps": round(len(ok_times) / wall_s, 3) if wall_s else None,
        "p50_ms": _p(ok_times, 0.50),
        "p99_ms": _p(ok_times, 0.99),
        "http_5xx": sum(1 for r in results if r[2] >= 500),
        "rejected_429": sum(1 for r in results if r[2] == 429),
        "snapshot_violations": sum(1 for r in results if r[4]),
        "final_snapshot_consistent": final_ok,
        "dm_commits": by_class["dm"]["completed"],
        "by_class": by_class,
        "scraped_p99_ms": scraped_p99,
        "scraped_requests": scraped_total,
        "exposition_valid": exposition_problems == [],
    }
    if fleet:
        # the never-consumed-a-slot proof: the probe tenant must be 429
        # at the router AND absent from every replica's own /statusz
        # accounting (the /plan verdict probe is slotless by contract)
        leaked = []
        for rp in rports:
            try:
                tenants = _get_statusz(rp).get("tenants") or {}
            except OSError:
                tenants = {}
            if "edge-probe" in tenants:
                leaked.append(rp)
        from nds_tpu.obs import metrics as obs_metrics

        fleet_acct = (
            obs_metrics.shared_sink().status_snapshot().get("fleet") or {}
        )
        report["fleet"] = {
            "replicas": fleet,
            "router_view": router.fleet_snapshot()["replicas"],
            "edge_probe_statuses": [s for s, _ in probe_results],
            "edge_probe_rejected": all(
                s == 429 and b.get("status") == "rejected"
                for s, b in probe_results
            ),
            "edge_rejected_total": fleet_acct.get("edge_rejected", 0),
            "slot_leak_replicas": leaked,
        }
        _stop_fleet(procs, router)
    else:
        service.close()
    from nds_tpu.obs import metrics as obs_metrics

    obs_metrics.reset_shared()
    return report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="closed-loop serve-mode QPS x p99 benchmark"
    )
    parser.add_argument("--clients", type=int, default=4)
    parser.add_argument("--duration", type=float, default=30.0,
                        help="measured seconds (ignored with --smoke)")
    parser.add_argument("--workers", type=int, default=None,
                        help="override engine.serve_workers")
    parser.add_argument("--out", help="write the report JSON here too")
    parser.add_argument(
        "--smoke", action="store_true",
        help="CI gate: short bounded run; exit 1 on any 5xx, any "
        "snapshot violation, any admission reject, or p99 over the bound",
    )
    parser.add_argument(
        "--smoke_p99_ms", type=float, default=120_000.0,
        help="generous smoke p99 bound (CPU cold compiles included)",
    )
    parser.add_argument(
        "--fleet", action="store_true",
        help="send the mix through the fleet router over real replica "
        "processes; adds the edge-reject slot-leak probe",
    )
    parser.add_argument(
        "--fleet_replicas", type=int, default=2,
        help="replica process count for --fleet (default 2)",
    )
    args = parser.parse_args(argv)
    report = run_bench(
        clients=args.clients, duration_s=args.duration, smoke=args.smoke,
        workers=args.workers,
        fleet=args.fleet_replicas if args.fleet else 0,
    )
    print(json.dumps(report, indent=2, default=str))
    if args.out:
        from nds_tpu.io.fs import fs_open_atomic

        with fs_open_atomic(args.out, "w") as f:
            json.dump(report, f, indent=2, default=str)
    if args.smoke:
        problems = []
        if report["http_5xx"]:
            problems.append(f"{report['http_5xx']} 5xx response(s)")
        if report["snapshot_violations"] or not (
            report["final_snapshot_consistent"]
        ):
            problems.append("snapshot-consistency violation under DM churn")
        if report["rejected_429"]:
            problems.append(
                f"{report['rejected_429']} unexpected 429(s) in the smoke "
                f"mix (nothing here should reject or shed)"
            )
        if report["completed"] == 0:
            problems.append("no request completed")
        p99 = report["p99_ms"] or 0
        if p99 > args.smoke_p99_ms:
            problems.append(
                f"p99 {p99:.0f} ms over the {args.smoke_p99_ms:.0f} ms bound"
            )
        if not report["exposition_valid"]:
            problems.append("/metrics exposition invalid or never scraped")
        fl = report.get("fleet")
        if fl:
            if not fl["edge_probe_rejected"]:
                problems.append(
                    f"edge-reject probe not 429/rejected at the router "
                    f"(statuses {fl['edge_probe_statuses']})"
                )
            if fl["slot_leak_replicas"]:
                problems.append(
                    f"edge-rejected tenant leaked into replica worker "
                    f"accounting on port(s) {fl['slot_leak_replicas']}"
                )
            if fl["edge_rejected_total"] < len(fl["edge_probe_statuses"]):
                problems.append("router edge_rejected counter undercounts")
        if problems:
            print("serve_bench --smoke FAILED: " + "; ".join(problems),
                  file=sys.stderr)
            return 1
        print("serve_bench --smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
