"""Which Python a warm statement spends its host time in: `cProfile` of the
executions of a few templates, replayed as the benchmark's window replays
them (every statement rehearsed once, then run again with the plan-result
cache dropped), sorted by the engine's own frames.

    python tools/host_profile.py <warehouse> --input_format parquet \
        --scale 1 --templates query36,query3,query7 --out chiprun_out/hp

Step 0 of ISSUE 41: the profile places the phase seams of `obs/tally.py`
(`Tally.phase`) and names the eager `jnp` sites that became `eager:<site>`
seams. Not a benchmark: its times are a profiler's (cProfile slows Python
frames two- to threefold and C calls hardly), so only the order and the
shares mean anything; the device's and the statement's times are
`benchmarks/run.py`'s.

Per template it prints the executions' wall, the engine's frames by their
own time and by what the calls they make out of the engine take (a frame
heavy in `jax` dispatches eager work), by cumulative time, and for the span
fields the tracer wrote (`launch_ms_by`, `compile_ms`, `host_ms`) what share
of `result_span - host_read` has a name.

With `--out`, `summary.json` also holds each pipeline's `exec_cache` events
of the warm executions in order (`hit` false at every execution: the
pipeline is rebuilt and traced again) and the spans' `dict_memo` counts.
"""

from __future__ import annotations

import argparse
import cProfile
import io
import json
import os
import pstats
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("warehouse")
    ap.add_argument("--input_format", default="parquet")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--templates", default="query36,query3,query7")
    ap.add_argument("--traffic",
                    default=os.path.join(REPO, "benchmarks", "traffic",
                                         "replay6.json"))
    ap.add_argument("--streams", type=int, default=3,
                    help="streams 1..n of the mix: one execution each")
    ap.add_argument("--cycles", type=int, default=2,
                    help="profiled replays of those streams")
    ap.add_argument("--top", type=int, default=35)
    ap.add_argument("--out", help="directory for the .pstats files, the "
                    "trace and the summary")
    ap.add_argument("--no_profile", action="store_true",
                    help="replay untimed by cProfile: the span fields alone")
    return ap.parse_args(argv)


def _run(session, name, sql):
    from nds_tpu import faults

    with faults.scope(name):
        result = session.run_script(sql)
        t0 = time.perf_counter()
        if result is not None:
            result.collect()
        return (time.perf_counter() - t0) * 1e3


def _top(stats_obj, key, n, only="nds_tpu"):
    buf = io.StringIO()
    ps = pstats.Stats(stats_obj, stream=buf)
    ps.sort_stats(key)
    ps.print_stats(only, n)
    lines = buf.getvalue().splitlines()
    start = next((i for i, ln in enumerate(lines) if "ncalls" in ln), 0)
    return "\n".join(
        ln.replace(REPO + "/", "") for ln in lines[start:] if ln.strip())


def _package(func):
    path, _, name = func
    for pkg in ("jax", "pyarrow", "numpy"):
        if pkg in path or (path == "~" and pkg in name):
            return pkg
    if "/nds_tpu/obs/" in path:
        # the seams' wrappers call back into the engine: the read alone
        return "read" if name == "host_read" else None
    return "rest"


def _leaves(prof, n):
    """Per engine frame: its own ms, and the ms inside the calls it makes
    itself out of the engine, by package. A frame whose `jax` column is
    heavy dispatches eager work: where an `eager:<site>` seam belongs."""
    def engine(f):
        return "/nds_tpu/" in f[0] and "/nds_tpu/obs/" not in f[0]

    rows = {}
    for func, (_, _, own, _, callers) in pstats.Stats(prof).stats.items():
        if engine(func):
            rows.setdefault(func, {})["own"] = own
            continue
        for caller, (_, _, _, cum) in callers.items():
            pkg = _package(func)
            if engine(caller) and pkg:
                by = rows.setdefault(caller, {})
                by[pkg] = by.get(pkg, 0.0) + cum
    cols = ("own", "jax", "pyarrow", "numpy", "read", "rest")
    lines = ["".join(f"{c:>9}" for c in cols) + "  frame (ms)"]
    for func, by in sorted(rows.items(),
                           key=lambda kv: -sum(kv[1].values()))[:n]:
        lines.append(
            "".join(f"{by.get(c, 0.0) * 1e3:>9.1f}" for c in cols)
            + f"  {func[0].replace(REPO + '/', '')}:{func[1]} {func[2]}")
    return "\n".join(lines)


def named_share(events, name):
    """Of `result_span - host_read` of the statement's traced executions:
    the milliseconds under seam names, compile stages and phases, and the
    rest (`reader.host_parts`; None for a program without the fields), and
    the same per plan-node type as a table."""
    from nds_tpu.obs import reader as R

    results = [e for e in events
               if e["kind"] == "result_span" and e.get("query") == name]
    spans = [e for e in events
             if e["kind"] == "op_span" and e.get("query") == name]
    ops = R.host_by_operator(spans, results)
    total = {"excl_ms": 0.0}
    for op in ops.values():
        total["excl_ms"] += op["excl_ms"]
        R.add_host(total, op)
    parts = R.host_parts(total)
    if parts is None:
        return {"executions": len(results)}, ""
    read, launch, compiled, phases, other = parts
    out = {"executions": len(results),
           "host_total_ms": round(total["excl_ms"] - read, 3),
           "launch_ms": round(launch, 3), "compile_ms": round(compiled, 3),
           "phase_ms": round(phases, 3), "other_ms": round(other, 3)}
    for field in R.HOST_FIELDS:
        out[field] = {k: round(v, 3) for k, v in sorted(
            total[field].items(), key=lambda kv: -kv[1])}
    table = R.format_host_table(
        sorted(ops.items(), key=lambda kv: -kv[1]["excl_ms"]))
    return out, "\n".join(table)


def exec_cache_hits(events, name):
    """{pipeline: [hit, ...]} of the statement's `exec_cache` events, in
    the order they were emitted."""
    by = {}
    for e in events:
        if e["kind"] == "exec_cache" and e.get("query") == name:
            by.setdefault(e["pipeline"], []).append(bool(e["hit"]))
    return by


def main(argv=None):
    args = parse_args(argv)
    out_dir = args.out and os.path.abspath(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        os.environ["NDS_TRACE_DIR"] = os.path.join(out_dir, "trace")
    import pyarrow as pa

    from benchmarks import lib
    from nds_tpu.engine.session import Session
    from nds_tpu.obs import trace as obs_trace
    from nds_tpu.obs.reader import discover_event_files, read_events
    from nds_tpu.power import setup_tables

    traffic = lib.load_json(args.traffic)
    wanted = args.templates.split(",")
    streams = lib.make_streams(traffic, args.scale, 1, args.streams)
    session = Session(use_decimal=True, conf={"app.name": "host_profile"})
    setup_tables(session, args.warehouse, args.input_format, True, [], "hp")
    summary = {}
    with obs_trace.bind(session.tracer):
        for template in wanted:
            mine = [(name, sql) for stream in streams for name, sql in stream
                    if name == template]
            for name, sql in mine:  # the rehearsal: executables built
                _run(session, name, sql)
            prof = cProfile.Profile()
            walls = []
            for cycle in range(args.cycles):
                # a catalog change drops the plan-result cache, as before
                # every cycle of the benchmark's window
                session.register_arrow(
                    "benchmark_cycle", pa.table({"cycle": [cycle]}))
                for name, sql in mine:
                    if args.no_profile:
                        walls.append(_run(session, name + ".warm", sql))
                        continue
                    prof.enable()
                    try:
                        walls.append(_run(session, name + ".warm", sql))
                    finally:
                        prof.disable()
            print(f"\n==== {template}: {len(walls)} warm executions, collect "
                  f"ms {[round(w, 1) for w in walls]}")
            summary[template] = {"collect_ms": walls}
            if args.no_profile:
                continue
            if out_dir:
                prof.dump_stats(os.path.join(out_dir, f"{template}.pstats"))
            print("-- engine frames: own time and their direct calls out")
            print(_leaves(prof, args.top))
            print("-- cumulative time, nds_tpu frames")
            print(_top(prof, "cumulative", args.top))
    if session.tracer is not None:
        session.tracer.close()
    if out_dir:
        events = read_events(discover_event_files(os.path.join(out_dir, "trace")))
        for template in wanted:
            named, table = named_share(events, template + ".warm")
            summary[template].update(named)
            summary[template]["exec_cache"] = exec_cache_hits(
                events, template + ".warm")
            print(f"\n==== {template}: what the spans name")
            print(json.dumps(summary[template], indent=1))
            print(table)
        with open(os.path.join(out_dir, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
    session.close()


if __name__ == "__main__":
    main()
