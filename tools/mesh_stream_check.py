#!/usr/bin/env python
"""The SF0.01 mesh-vs-oracle CI gate (ISSUE 13, tier-1-adjacent).

Runs the FULL SF0.01 query stream twice in one process — once on the
8-device virtual CPU mesh (fact tables row-sharded over the `data` axis,
dimensions replicated, exchange joins / samplesort / partial-agg merge all
live) and once on a single-device oracle session — and requires every
statement's result to be value-identical (rows canonically ordered; the
engine runs decimals as scaled int64, so partial-aggregate merge order
cannot perturb sums).

The mesh session runs traced: the gate asserts `exchange` trace evidence
(bytes moved, partitions, skew ratio) was recorded by the stream, then runs
one deliberately hot-keyed join at realistic row counts to prove the
overflow-retry path fires (capacity doubling + retry evidence) — the two
paths the old dryrun row caps never exercised.

Artifact: a compact JSON metrics block (the new MULTICHIP round shape) is
written to --out and printed, with a fail-soft `baseline_compare` against
the newest stored MULTICHIP_r*.json via the profiler's --bench comparison.

Env knobs: NDS_MESH_GATE_DATA (data dir, default /tmp/nds_mesh_gate_sf0.01),
NDS_MESH_GATE_QUERIES (comma-separated subset, debug aid).
"""

import argparse
import json
import math
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_DEV_DEFAULT = 8


def _force_cpu_mesh(n_dev: int):
    # virtual device count must land in XLA_FLAGS BEFORE the CPU client
    # initializes; the platform is pinned through jax.config so the check
    # never reaches for an accelerator (conftest.py pattern)
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_dev} "
        + re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            os.environ.get("XLA_FLAGS", ""),
        )
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    if len(jax.devices()) < n_dev:
        raise RuntimeError(
            f"need {n_dev} CPU devices, have {len(jax.devices())}"
        )


def ensure_data(data_dir: str):
    marker = os.path.join(data_dir, ".complete")
    if os.path.exists(marker):
        return
    subprocess.run(
        [
            sys.executable, "-m", "nds_tpu.cli.gen_data",
            "--scale", "0.01", "--parallel", "2",
            "--data_dir", data_dir, "--overwrite_output",
        ],
        check=True, cwd=REPO, capture_output=True,
    )
    open(marker, "w").close()


def _sessions(data_dir: str, n_dev: int):
    from nds_tpu.engine.session import Session
    from nds_tpu.obs.trace import Tracer
    from nds_tpu.parallel.dist import make_mesh
    from nds_tpu.schema import get_schemas

    oracle = Session()
    dist = Session(mesh=make_mesh(n_dev))
    tracer = Tracer(None)  # in-memory: the gate reads events directly
    dist.tracer = tracer
    schemas = get_schemas()
    for t, schema in schemas.items():
        path = os.path.join(data_dir, t)
        if os.path.isdir(path):
            oracle.register_csv_dir(t, path, schema)
            dist.register_csv_dir(t, path, schema)
    return oracle, dist, tracer


def _canon_rows(arrow):
    """Canonical (sorted) row list: SQL leaves tie order undefined and the
    samplesort may place equal-key rows differently than the single-device
    stable sort — value equality is the contract, not tie order."""
    rows = [tuple(r.values()) for r in arrow.to_pylist()]

    def key(row):
        out = []
        for v in row:
            if v is None:
                out.append((0, ""))
            elif isinstance(v, float) and math.isnan(v):
                out.append((2, "nan"))
            else:
                out.append((1, str(v)))
        return out

    return sorted(rows, key=key)


def run_stream(oracle, dist, queries, tracer=None):
    from nds_tpu import faults

    matched, mismatched, failed = [], {}, {}
    wall_oracle = wall_mesh = 0.0

    def span(name, dur_s, status):
        # the mesh half runs outside BenchReport, so the gate emits the
        # query_span itself — `profile --critical-path` over the dumped
        # trace needs per-query wall to attribute against
        if tracer is not None:
            tracer.emit(
                "query_span", query=name,
                dur_ms=round(dur_s * 1000.0, 3), status=status, retries=0,
            )

    for i, (name, sql) in enumerate(queries.items()):
        try:
            t0 = time.perf_counter()
            a = oracle.run_script(sql)
            a_rows = _canon_rows(a.collect()) if a is not None else []
            wall_oracle += time.perf_counter() - t0
            t0 = time.perf_counter()
            try:
                with faults.scope(name):  # query-scoped exchange evidence
                    b = dist.run_script(sql)
                    b_rows = _canon_rows(b.collect()) if b is not None else []
            except Exception:
                span(name, time.perf_counter() - t0, "Failed")
                raise
            mesh_dur = time.perf_counter() - t0
            wall_mesh += mesh_dur
            span(name, mesh_dur, "Completed")
        except Exception as exc:
            failed[name] = f"{type(exc).__name__}: {str(exc)[:300]}"
            print(f"[{i + 1}/{len(queries)}] {name}: FAILED {exc}",
                  file=sys.stderr)
            continue
        if a_rows == b_rows:
            matched.append(name)
            print(f"[{i + 1}/{len(queries)}] {name}: ok "
                  f"({len(a_rows)} rows)", file=sys.stderr)
        else:
            diff = next(
                (
                    (x, y)
                    for x, y in zip(a_rows, b_rows)
                    if x != y
                ),
                (len(a_rows), len(b_rows)),
            )
            mismatched[name] = f"first difference: {str(diff)[:300]}"
            print(f"[{i + 1}/{len(queries)}] {name}: MISMATCH {diff}",
                  file=sys.stderr)
    return matched, mismatched, failed, wall_oracle, wall_mesh


def overflow_retry_probe(n_dev: int):
    """Hot-key exchange at realistic rows: >50% of a 64k-row fact on ONE
    key overflows the balanced capacity guess, so the overflow-retry
    (cap doubling) path MUST fire — asserted via the task-failure listener
    and the exchange event's retries field — and the result must equal the
    single-device oracle."""
    import numpy as np
    import pyarrow as pa

    from nds_tpu.engine.session import Session
    from nds_tpu.obs.trace import Tracer
    from nds_tpu.parallel.dist import make_mesh

    rng = np.random.default_rng(41)
    n = 1 << 16
    hot = rng.random(n) < 0.6
    k = np.where(hot, 17, rng.integers(0, 4096, n)) * 1_000_003
    left = pa.table({"k": k, "lv": np.arange(n, dtype=np.int64)})
    right = pa.table({
        "k": np.arange(4096, dtype=np.int64) * 1_000_003,
        "rv": np.arange(4096, dtype=np.int64),
    })
    oracle = Session()
    dist = Session(mesh=make_mesh(n_dev))
    tracer = Tracer(None)
    dist.tracer = tracer
    retries_seen = []
    dist.register_listener(
        lambda r: retries_seen.append(r) if "exchange join" in r else None
    )
    for s in (oracle, dist):
        s.register_arrow("l", left)
        s.register_arrow("r", right)
    from nds_tpu import faults

    q = ("select count(*) c, sum(lv) sl, sum(rv) sr from l, r "
         "where l.k = r.k")
    a = oracle.sql(q).to_pylist()
    t0 = time.perf_counter()
    with faults.scope("hotkey_probe"):
        b = dist.sql(q).to_pylist()
    tracer.emit(
        "query_span", query="hotkey_probe",
        dur_ms=round((time.perf_counter() - t0) * 1000.0, 3),
        status="Completed", retries=0,
    )
    if a != b:
        raise AssertionError(f"overflow probe mismatch: {a} vs {b}")
    ev = [e for e in tracer.events if e["kind"] == "exchange"]
    if not ev:
        raise AssertionError("overflow probe recorded no exchange event")
    retried = [e for e in ev if e["retries"] > 0]
    if not retried and not retries_seen:
        raise AssertionError(
            "hot-key probe never exercised the overflow-retry path"
        )
    skew = max(e["skew"] for e in ev)
    return {
        "retries": max(
            [e["retries"] for e in ev] + [1 if retries_seen else 0]
        ),
        "skew": skew,
        # the probe tracer's raw events ride back so --trace_dir can dump
        # them (main pops this key before the JSON artifact is written)
        "events": (tracer.events, tracer.app_id,
                   tracer.context.trace_id),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="SF0.01 mesh-vs-oracle stream gate (MULTICHIP round)"
    )
    ap.add_argument("--devices", type=int, default=N_DEV_DEFAULT)
    ap.add_argument(
        "--data_dir",
        default=os.environ.get(
            "NDS_MESH_GATE_DATA", "/tmp/nds_mesh_gate_sf0.01"
        ),
    )
    ap.add_argument(
        "--out", default="/tmp/multichip_gate.json",
        help="metrics artifact path (the new MULTICHIP round block; a "
        "bench round stores it as the repo's next MULTICHIP_r*.json)",
    )
    ap.add_argument(
        "--baseline", default=None,
        help="MULTICHIP_r*.json to compare against (default: newest in "
        "the repo root; comparison is fail-soft)",
    )
    ap.add_argument(
        "--trace_dir", default=None,
        help="also dump the gate's collected events (stream + hot-key "
        "probe) as event files under this dir — ci/tier1-check runs "
        "`profile --critical-path` over it",
    )
    args = ap.parse_args(argv)

    _force_cpu_mesh(args.devices)
    t_start = time.monotonic()
    ensure_data(args.data_dir)

    from nds_tpu.datagen.query_streams import generate_streams
    from nds_tpu.obs.reader import validate_events
    from nds_tpu.power import gen_sql_from_stream

    import tempfile

    with tempfile.TemporaryDirectory() as d:
        generate_streams(d, 1, 0.01, rngseed=19620718)
        queries = gen_sql_from_stream(os.path.join(d, "query_0.sql"))
    subset = os.environ.get("NDS_MESH_GATE_QUERIES")
    if subset:
        keep = {s.strip() for s in subset.split(",") if s.strip()}
        queries = {n: q for n, q in queries.items() if n in keep}

    oracle, dist, tracer = _sessions(args.data_dir, args.devices)
    matched, mismatched, failed, w_oracle, w_mesh = run_stream(
        oracle, dist, queries, tracer=tracer
    )

    # stream-level exchange evidence: the retired dryrun caps mean the
    # collective paths must actually fire inside the real stream
    problems = validate_events(tracer.events)
    ex = [e for e in tracer.events if e["kind"] == "exchange"]
    probe = {}
    probe_error = None
    try:
        probe = overflow_retry_probe(args.devices)
    except Exception as exc:  # recorded below; fails the gate
        probe_error = f"{type(exc).__name__}: {str(exc)[:300]}"
    probe_events = probe.pop("events", None)

    if args.trace_dir:
        # dump the in-memory streams as regular event files (meta line
        # first) so the profiler CLI reads them like any trace dir
        os.makedirs(args.trace_dir, exist_ok=True)
        chains = [(tracer.events, tracer.app_id, tracer.context.trace_id)]
        if probe_events is not None:
            chains.append(probe_events)
        from nds_tpu import __version__ as _v

        for evs, app, tid in chains:
            path = os.path.join(args.trace_dir, f"events-{app}.jsonl")
            with open(path, "w") as f:
                f.write(json.dumps({
                    "ts": int(time.time() * 1000), "kind": "trace_meta",
                    "app": app, "trace_id": tid, "pid": os.getpid(),
                    "version": _v,
                }) + "\n")
                for ev in evs:
                    f.write(json.dumps(ev, default=str) + "\n")

    ok = (
        not mismatched
        and not failed
        and not problems
        and bool(ex)
        and probe_error is None
    )
    out = {
        "metric": "nds_mesh_stream_vs_oracle",
        "n_devices": args.devices,
        "ok": ok,
        "queries": len(queries),
        "matched": len(matched),
        "mismatched": mismatched,
        "failed": failed,
        "schema_problems": problems[:5],
        "exchange_ops": len(ex),
        "exchange_bytes": sum(int(e["bytes_moved"]) for e in ex),
        "exchange_retries": sum(int(e["retries"]) for e in ex),
        "exchange_max_skew": max([float(e["skew"]) for e in ex] or [0.0]),
        "exchange_join_ops": sum(1 for e in ex if e["op"] == "join"),
        "exchange_sort_ops": sum(1 for e in ex if e["op"] == "sort"),
        "overflow_probe": probe if probe_error is None else probe_error,
        "oracle_wall_s": round(w_oracle, 2),
        "mesh_wall_s": round(w_mesh, 2),
        # summed-wall ratio (NOT a per-query geomean): one number for "how
        # much slower is the whole stream on the virtual CPU mesh"
        "mesh_vs_oracle_wall_ratio": (
            round(w_mesh / w_oracle, 3) if w_oracle > 0 else None
        ),
        "wall_s": round(time.monotonic() - t_start, 1),
    }

    # fail-soft round comparison against the newest stored MULTICHIP round
    try:
        import glob

        base = args.baseline
        if not base:
            rounds = sorted(glob.glob(os.path.join(REPO, "MULTICHIP_r*.json")))
            base = rounds[-1] if rounds else None
        if base:
            tmp = args.out + ".tmp"
            with open(tmp, "w") as f:
                json.dump(out, f)
            from nds_tpu.cli.profile import _compare_multichip

            recs = _compare_multichip(base, tmp)
            os.unlink(tmp)
            rec = next((r for r in recs if "old_ratio" in r), None)
            if rec is not None:
                out["baseline_compare"] = {
                    "baseline": os.path.basename(base),
                    "old_ratio": rec.get("old_ratio"),
                    "new_ratio": rec.get("new_ratio"),
                    "old_ok": rec.get("old_ok"),
                    "regressed": rec.get("change") == "regression",
                }
    except Exception as exc:
        out["baseline_compare"] = {"error": str(exc)[:200]}

    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    os.replace(tmp, args.out)
    print(json.dumps(out))
    if not ok:
        print(
            f"mesh_stream_check: FAILED — mismatched={sorted(mismatched)} "
            f"failed={sorted(failed)} schema_problems={len(problems)} "
            f"exchange_ops={len(ex)} probe={probe_error}",
            file=sys.stderr,
        )
        return 1
    print(
        f"mesh_stream_check ok: {len(matched)}/{len(queries)} queries "
        f"match the oracle on the {args.devices}-device mesh; "
        f"{len(ex)} exchanges moved "
        f"{out['exchange_bytes'] >> 20} MiB (max skew "
        f"{out['exchange_max_skew']:.2f}x); overflow probe retried "
        f"{probe.get('retries')}x at skew {probe.get('skew'):.2f}x",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
