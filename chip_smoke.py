#!/usr/bin/env python3
"""Load -> Power at SF1 on one TPU chip, through the normal entry points.

The quickest proof that the system still starts on the chip: generate the
TPC-DS qualification database from a seed, Load all 24 tables to parquet
through `./nds-tpu-submit … nds_tpu.cli.transcode`, run six queries of
stream 0 through `./nds-tpu-submit templates/power_run_tpu.template
nds_tpu.cli.power`, and hold the six answers against sqlite over the same
raw data with `nds_tpu/validate.py`'s own comparison.

    python chip_smoke.py                  # one chip, SF1 (what the driver runs)
    python chip_smoke.py --mesh 4         # four chips: mesh run vs one device
    python chip_smoke.py --scale 0.01     # CPU rehearsal: runs, then FAILS

It fails, never falls back. The exit code is non-zero, and the result line
is not printed, when a phase child exits non-zero (the run stops there),
when the Power run reports another platform than `tpu`, when a query is
not `Completed`, walked a rung of the degradation ladder or measured its
memory from the host's RSS, when an answer differs from sqlite's, or when
an AOT-cached executable was quarantined. The last line of a good run is
`{"ok": true, "device": {"platform": …, "kind": …, "count": …}}`.

One process per chip: this parent never imports jax (it checks), and runs
every phase as a child, one after another. The sqlite child is held to the
CPU.
"""

from __future__ import annotations

import argparse
import csv
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SUBMIT = os.path.join(REPO, "nds-tpu-submit")

#: star-join aggregate, the cheapest statement, a four-dimension join with
#: averages, ROLLUP + rank window, a fact-fact outer join, CTE + correlated
#: subquery: eleven tables, cheap to compile, all answered by sqlite
QUERIES = ("query3", "query96", "query7", "query36", "query93", "query1")

#: the whole script must end inside the driver's 1200 s
DEADLINE_S = 1150

# blocking 4-byte device->host reads, median of 100, and what the device
# says it has: the budgeter assumes 16 GB and has never been told
_PROBE = r"""
import json, statistics, time
import jax, jax.numpy as jnp
d = jax.devices()[0]
stats = d.memory_stats() or {}
bump = jax.jit(lambda x: x + 1)
x = bump(jnp.zeros((), jnp.int32))
int(x)
reads = []
for _ in range(100):
    x = bump(x)
    x.block_until_ready()
    t0 = time.perf_counter()
    int(x)
    reads.append((time.perf_counter() - t0) * 1e3)
print(json.dumps({
    "platform": d.platform, "kind": d.device_kind, "count": len(jax.devices()),
    "bytes_limit": stats.get("bytes_limit"),
    "sync_ms_median_of_100": statistics.median(reads),
}))
"""


class Smoke:
    def __init__(self, args):
        self.args = args
        self.work = os.path.abspath(args.work_dir)
        self.logs = os.path.join(self.work, "logs")
        self.t0 = time.monotonic()
        self.failures: list[str] = []
        self.phase_s: dict[str, float] = {}

    # -- children ----------------------------------------------------------
    def run(self, name, cmd, env=None):
        """One phase, one child, its output in logs/<name>.log. A child
        that exits non-zero or outlives the deadline ends the script: no
        phase runs past a failed one."""
        log_path = os.path.join(self.logs, f"{name}.log")
        left = DEADLINE_S - (time.monotonic() - self.t0)
        t0 = time.monotonic()
        with open(log_path, "w") as log:
            # its own process group, so that a phase's own children (the
            # generator's chunk processes) die with it
            child = subprocess.Popen(
                [str(c) for c in cmd], cwd=REPO, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
                env={**os.environ, **(env or {})},
            )
            try:
                rc = child.wait(timeout=max(left, 1))
            except subprocess.TimeoutExpired:
                rc = "deadline"
            finally:
                if child.poll() is None:
                    os.killpg(child.pid, signal.SIGKILL)
                    child.wait()
        self.phase_s[name] = time.monotonic() - t0
        print(f"phase {name}: {self.phase_s[name]:.1f} s rc={rc}", flush=True)
        if rc != 0:
            with open(log_path, errors="replace") as log:
                sys.stdout.write("".join(log.readlines()[-40:]))
            self.die(f"phase {name} exited {rc} (log: {log_path})")
        return log_path

    def fail(self, why):
        self.failures.append(why)
        print(f"FAIL: {why}", flush=True)

    def die(self, why):
        self.fail(why)
        self.finish(None)

    def finish(self, device):
        if self.failures or device is None:
            print(f"chip_smoke: FAILED ({len(self.failures)}): "
                  + "; ".join(self.failures))
            sys.exit(1)
        print(json.dumps({"ok": True, "device": device}))
        sys.exit(0)

    # -- phases ------------------------------------------------------------
    def prepare(self):
        if not os.path.isfile(SUBMIT):
            print(f"chip_smoke: FAILED: {SUBMIT} is missing; this script "
                  f"drives the repository it sits in")
            sys.exit(1)
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.logs)
        # the same rule as engine/aotcache.compile_cache_root, restated
        # because this parent imports nothing of the engine
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
            REPO, ".nds_cache"
        )
        n = sum(len(files) for _, _, files in os.walk(cache))
        print(f"compile cache: {cache} ({'empty' if n == 0 else f'{n} files'}"
              f" at start)", flush=True)

    def load(self):
        a, w = self.args, self.work
        self.run("gen_data", [
            sys.executable, "-m", "nds_tpu.cli.gen_data", "local",
            "--scale", a.scale, "--parallel", 4, "--seed", a.seed,
            "--data_dir", f"{w}/raw", "--overwrite_output",
        ])
        self.run("load", [
            SUBMIT, "templates/base.template", "nds_tpu.cli.transcode",
            f"{w}/raw", f"{w}/warehouse", f"{w}/load_report.txt",
            "--output_format", "parquet", "--output_mode", "overwrite",
        ])
        self.run("gen_query_stream", [
            sys.executable, "-m", "nds_tpu.cli.gen_query_stream",
            "--streams", 1, "--scale", a.scale, "--rngseed", a.seed,
            "--output_dir", f"{w}/streams",
        ])
        # the six statements as a stream of their own, for what takes a
        # stream file and no --sub_queries (cli.validate)
        with open(f"{w}/streams/query_0.sql") as f:
            entries = f.read().split("-- start")[1:]
        by_name = {
            e[e.find("template") + 9:e.find(".tpl")]: e for e in entries
        }
        with open(f"{w}/streams/six.sql", "w") as f:
            f.write("".join("-- start" + by_name[q] for q in QUERIES))

    def power(self, tag, env=None):
        """The six queries through the launcher; returns the tallies of the
        run's trace. Every check on the run is made here."""
        w = self.work
        self.run(f"power_{tag}", [
            SUBMIT, "templates/power_run_tpu.template", "nds_tpu.cli.power",
            f"{w}/warehouse", f"{w}/streams/query_0.sql",
            f"{w}/time_{tag}.csv", "--sub_queries", ",".join(QUERIES),
            "--output_prefix", f"{w}/out_{tag}",
            "--json_summary_folder", f"{w}/json_{tag}",
        ], env={"NDS_TRACE_DIR": f"{w}/trace_{tag}", **(env or {})})
        with open(f"{w}/time_{tag}.csv") as f:
            times = {r[1]: r[2] for r in csv.reader(f)}
        for q in QUERIES:
            found = glob.glob(f"{w}/json_{tag}/*-{q}-*.json")
            if len(found) != 1:
                self.fail(f"{tag} {q}: {len(found)} summaries")
                continue
            with open(found[0]) as f:
                s = json.load(f)
            conf = s["env"]["engineConf"]
            mem = s.get("memoryHighWater") or {}
            print(f"{tag} {q}: {int(times[q]) / 1000:.3f} s "
                  f"status={s['queryStatus']} "
                  f"backend={conf['jax.backend']}x{conf['jax.device_count']} "
                  f"mem_high_water={mem.get('bytes')} ({mem.get('source')})")
            if s["queryStatus"] != ["Completed"]:
                self.fail(f"{tag} {q}: {s['queryStatus']} "
                          f"{s.get('exceptions')}")
            if conf["jax.backend"] != "tpu":
                self.fail(f"{tag} {q}: ran on {conf['jax.backend']}")
            if mem.get("source") != "device":
                self.fail(f"{tag} {q}: memory high-water read from "
                          f"{mem.get('source')}, not the device")
            if s.get("ladder"):
                self.fail(f"{tag} {q}: ladder walked: {s['ladder']}")
        print(f"{tag} power test: {int(times['Power Test Time']) / 1000:.3f} s"
              f" of {int(times['Total Time']) / 1000:.3f} s in the child")
        return self.trace_tallies(tag)

    def trace_tallies(self, tag):
        """AOT-cache and mesh counters of one Power run, by the repo's own
        trace reader, and the per-device high-water its query spans carry."""
        sys.path.insert(0, REPO)
        from nds_tpu.obs import reader

        if "jax" in sys.modules:
            self.die("the trace reader imported jax into the parent: a "
                     "parent that touches jax takes the chip from its children")
        events = reader.read_events(f"{self.work}/trace_{tag}")
        tallies = reader.profile_events(events)["tallies"]
        t = {k: tallies[k] for k in (
            "aot_disk_hits", "aot_misses", "aot_stores", "aot_quarantined",
            "aot_call_failures", "mesh_fallbacks",
        )}
        per_device = [
            ev["mem_hw_per_device"] for ev in events
            if ev["kind"] == "query_span" and ev.get("mem_hw_per_device")
        ]
        t["per_device_high_water"] = (
            [max(col) for col in zip(*per_device)] if per_device else None
        )
        print(f"{tag} trace: {json.dumps(t)}")
        if t["aot_quarantined"] or t["aot_call_failures"]:
            self.fail(f"{tag}: a cached executable did not load or run "
                      f"({t['aot_quarantined']} quarantined, "
                      f"{t['aot_call_failures']} failed at call time)")
        return t

    def probe(self):
        log = self.run("probe", [sys.executable, "-c", _PROBE])
        with open(log) as f:
            p = json.loads(f.read().strip().splitlines()[-1])
        print(f"probe: {json.dumps(p)}")
        if p["platform"] != "tpu":
            self.fail(f"jax found no accelerator: platform {p['platform']}")
        return {k: p[k] for k in ("platform", "kind", "count")}

    # -- the two runs ------------------------------------------------------
    def one_chip(self):
        self.load()
        self.power("one")
        # sqlite over the same raw data: needs no chip, so it is held off it
        self.run("reference", [
            sys.executable, __file__, "--reference-child", self.work,
        ], env={"JAX_PLATFORMS": "cpu"})
        with open(f"{self.work}/reference.json") as f:
            ref = json.load(f)
        print(f"reference: {json.dumps(ref)}")
        for q in ref["unmatched"]:
            self.fail(f"{q}: answer differs from sqlite's")
        if float(self.args.scale) >= 1:
            # the default seed was picked so that all six select rows at
            # SF1: two empty answers that agree check nothing
            for q, n in ref["rows"].items():
                if n == 0:
                    self.fail(f"{q}: the reference answer is empty")

    def mesh(self, n):
        self.load()
        t = self.power("mesh", env={"NDS_MESH_DEVICES": str(n)})
        self.power("one")
        self.run("validate", [
            sys.executable, "-m", "nds_tpu.cli.validate",
            f"{self.work}/out_mesh", f"{self.work}/out_one",
            f"{self.work}/streams/six.sql",
        ], env={"JAX_PLATFORMS": "cpu"})
        per = t["per_device_high_water"]
        print(f"mesh per-device bytes_in_use high-water: {per}")
        if per is None or len(per) != n or sum(b > 0 for b in per) < 2:
            self.fail(f"mesh: {n} devices asked for, bytes in use {per}")
        if t["mesh_fallbacks"]:
            self.fail(f"mesh: {t['mesh_fallbacks']} mesh_fallback event(s)")


def _load_sqlite(conn, data_dir, tables):
    """Create, fill and index `tables` from the generator's .dat files."""
    import datetime

    import pyarrow as pa

    from nds_tpu.io.csv import read_dat_dir
    from nds_tpu.schema import get_schemas

    for t, schema in get_schemas(use_decimal=False).items():
        path = os.path.join(data_dir, t)
        if t not in tables or not os.path.isdir(path):
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
        # sqlite's nested-loop joins need an index on every surrogate key
        for f in schema:
            if f.name.endswith("_sk") or f.name.endswith("_number"):
                conn.execute(f"create index idx_{t}_{f.name} on {t}({f.name})")
    conn.execute("analyze")
    conn.commit()


def reference_child(work):
    """sqlite over the tables the six statements read, then validate.py's
    comparison of the engine's written answers against sqlite's."""
    import re
    import sqlite3

    import pyarrow as pa
    import pyarrow.parquet as pq

    for d in ("tests", ""):
        sys.path.insert(0, os.path.join(REPO, d))
    from nds_tpu import validate
    from nds_tpu.power import gen_sql_from_stream
    from nds_tpu.schema import get_schemas
    from test_oracle import _StddevSamp, _to_sqlite

    stream = gen_sql_from_stream(f"{work}/streams/six.sql")
    lowered = {q: _to_sqlite(stream[q]) for q in QUERIES}
    text = "\n".join(lowered.values()).lower()
    tables = {t for t in get_schemas() if re.search(rf"\b{t}\b", text)}
    conn = sqlite3.connect(":memory:")
    conn.create_aggregate("stddev_samp", 1, _StddevSamp)
    t0 = time.perf_counter()
    _load_sqlite(conn, f"{work}/raw", tables)
    out = {"tables": sorted(tables),
           "load_s": round(time.perf_counter() - t0, 3),
           "query_s": {}, "rows": {}}
    for q, sql in lowered.items():
        t0 = time.perf_counter()
        cur = conn.execute(next(s for s in sql.split(";") if "select" in s))
        rows = cur.fetchall()
        out["query_s"][q] = round(time.perf_counter() - t0, 3)
        out["rows"][q] = len(rows)
        names = [f"c{i}" for i in range(len(cur.description))]
        os.makedirs(f"{work}/out_ref/{q}")
        pq.write_table(
            pa.table({n: pa.array(list(col)) for n, col in zip(
                names, zip(*rows) if rows else [[] for _ in names])}),
            f"{work}/out_ref/{q}/part-0.parquet",
        )
    out["unmatched"] = validate.iterate_queries(
        f"{work}/out_one", f"{work}/out_ref", list(QUERIES)
    )
    with open(f"{work}/reference.json", "w") as f:
        json.dump(out, f)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", default="1",
                    help="scale factor; below 1 is a CPU rehearsal")
    ap.add_argument("--seed", type=int, default=7,
                    help="seeds the data and the query stream (default: one "
                    "under which all six queries select rows at SF1)")
    ap.add_argument("--mesh", type=int,
                    help="run only Load, the six queries over an N-device "
                    "mesh, the six on one device, and cli.validate between")
    ap.add_argument("--work_dir",
                    default=os.path.join(REPO, ".chip_smoke_work"),
                    help="emptied at start; data, answers and logs")
    ap.add_argument("--reference-child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.reference_child:
        return reference_child(args.reference_child)
    smoke = Smoke(args)
    smoke.prepare()
    if args.mesh:
        smoke.mesh(args.mesh)
    else:
        smoke.one_chip()
    device = smoke.probe()
    for name, s in smoke.phase_s.items():
        print(f"seconds {name}: {s:.1f}")
    print(f"seconds total: {time.monotonic() - smoke.t0:.1f}")
    smoke.finish(device)


if __name__ == "__main__":
    main()
