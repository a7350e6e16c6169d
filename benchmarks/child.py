"""The chip child: the one process of a run that owns the chip.

Started by `run.py` as

    ./nds-tpu-submit <the configuration's power template> benchmarks.child ...

so the template's flags (`--input_format`, `--mesh_devices`,
`--property_file`) reach it exactly as they reach `nds_tpu.cli.power`. It
drives the program's own functions and sets no engine option:

1. first pass: stream 0 as one Power pass through
   `power.run_query_stream(..., keep_session=True)`, answers written for
   the comparison, timed by the program's own `Power Test Time`;
2. rehearsal: streams 1..`window_passes` once, statement by statement.
   Every statement is new to the session here, which is what `new_stmt_ms`
   times, and afterwards every executable the window needs is in it;
3. window: for `--seconds` seconds the same streams, cycle after cycle, in
   an order drawn from `--seed`, closed loop, one client, each statement
   through `BenchReport(session).report_on(...)` around `run_script` and
   `collect`, as `power._run_query_stream_body` runs `run_one_query`.
   Parameters change with every pass of a cycle, and a catalog
   registration before each cycle drops the plan-result cache. With
   `--seconds 0` (the warm-up child of a checkout's first run) the window
   stays empty. No
   statement starts after the deadline; the one in flight finishes and the
   window's length is the time to its end, so no slow statement is
   censored;
4. after the window: the answers of the window's first pass are written
   (compared beside the first pass's), counters and the device's
   `peak_bytes_in_use` are read and, traced, the profiler's slice reduced.

With `--pass_only` the child ends after step 1: the same first pass in a
fresh process of its own, the second reading `first_pass_s` is the lower of.

Everything is handed to the parent in `<run_dir>/child.json`; the parent
alone judges it and prints the result line.
"""

from __future__ import annotations

import argparse
import contextlib
import csv
import glob
import json
import os
import sys
import time
from collections import OrderedDict, defaultdict

from . import lib


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    # what the power templates carry
    ap.add_argument("--input_format", default="parquet")
    ap.add_argument("--mesh_devices", type=int)
    ap.add_argument("--property_file")
    # what the parent passes
    ap.add_argument("--warehouse", required=True)
    ap.add_argument("--run_dir", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--scale", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], required=True)
    ap.add_argument("--trace_cycle", type=int,
                    help="the cycle whose first passes a traced run records")
    ap.add_argument("--pass_only", action="store_true",
                    help="the first pass alone: no rehearsal, no window")
    return ap.parse_args(argv)


class Spans:
    """The benchmark's own host spans, `plan`, `execute` and `between`, as
    `TraceAnnotation`s on the profiler's clock when a slice is traced, and
    nothing otherwise. `between` is whatever the loop does outside the
    other two: BenchReport's bookkeeping, the sampler thread, this file."""

    def __init__(self, traced):
        self.traced = traced
        self._between = None

    def span(self, phase, q):
        if not self.traced:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(phase, q=q)

    def open_between(self, q):
        self._between = self.span("between", q)
        self._between.__enter__()

    def close_between(self):
        if self._between is not None:
            self._between.__exit__(None, None, None)
            self._between = None


class TraceSlice:
    """The profiler, recording the first `passes` whole passes of cycle
    `cycle` of the window: a place in the traffic, not a time. The cycle's
    order is `lib.window_order(traffic, seed, cycle)`, so two runs on one
    seed trace the same statements in the same order however fast either
    is, and the slice always holds the cycle's first pass, whose query93 no
    cached answer serves. Tracing slows the host, so it is a slice of a run
    of its own, never the run the end-to-end metrics come from."""

    def __init__(self, wanted, directory, cycle, passes, spans, marks):
        self.state = "before" if wanted else "never"
        self.directory, self.cycle, self.passes = directory, cycle, passes
        self.passes_left = passes
        self.spans, self.marks = spans, marks
        #: [stream, statement] of every statement run while it recorded
        self.statements = []

    def before_pass(self, cycle, nth):
        """Called as pass `nth` of cycle `cycle` is about to start."""
        if self.state == "before" and (cycle, nth) == (self.cycle, 0):
            self.start_profiler()
            self.marks["slice_start"] = time.time() * 1e3
            self.state = "on"
            self.spans.traced = True
        elif self.state == "on":
            self.passes_left -= 1
            if self.passes_left <= 0:
                self.stop()

    def ran(self, stream, name):
        if self.state == "on":
            self.statements.append([stream, name])

    def stop(self):
        if self.state == "on":
            self.spans.close_between()
            self.spans.traced = False
            self.stop_profiler()
            self.marks["slice_end"] = time.time() * 1e3
            self.state = "done"

    def start_profiler(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        # the engine's host code is Python: tracing every call would slow
        # what is measured
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def stop_profiler(self):
        import jax

        jax.profiler.stop_trace()

    def record(self, wanted):
        """What was traced, for the result line, and why it is no slice:
        (`slice`, error). `wanted` is `lib.slice_statements(...)`: a window
        that ends before the slice's cycle starts, or inside the slice, is
        an error of the traced run, never a slice somewhere else."""
        record = {"cycle": self.cycle, "passes": self.passes,
                  "statements": self.statements}
        if self.statements == wanted:
            return record, None
        if not self.statements:
            return record, (f"the window ended before cycle {self.cycle} "
                            f"started: no slice was traced")
        return record, (
            f"the window ended inside the traced slice: "
            f"{len(self.statements)} of the {len(wanted)} statements of "
            f"cycle {self.cycle}'s first {self.passes} passes ran")


def run_window(traffic, streams, seed, seconds, tracing, one, new_cycle,
               clock=time.perf_counter):
    """The window: the mix's streams cycle after cycle, each cycle in the
    order `lib.window_order` draws from the seed, until `seconds` have
    passed on `clock`. No statement starts after the deadline; the one in
    flight finishes. `new_cycle(cycle)` runs before each cycle,
    `one(stream, name, sql, t_open, first)` runs one statement and returns
    its record (`first`: it is of the window's first pass, whose answers
    are compared). Returns (records, cycles begun, clock at the last
    statement's end, clock at the opening)."""
    statements = []
    t_open = clock()
    deadline = t_open + seconds
    t_last = t_open
    cycle = 0
    while clock() < deadline:
        new_cycle(cycle)
        for nth, si in enumerate(lib.window_order(traffic, seed, cycle)):
            if clock() >= deadline:
                break
            tracing.before_pass(cycle, nth)
            for name, sql in streams[si]:
                if clock() >= deadline:
                    break
                rec = one(si, name, sql, t_open, cycle == 0 and nth == 0)
                rec["cycle"] = cycle
                statements.append(rec)
                tracing.ran(si, name)
                t_last = clock()
        cycle += 1
    tracing.stop()
    return statements, cycle, t_last, t_open


def run_statement(session, sql, name, spans, rec, keep):
    """One stream entry: plan, execute, collect to the host. The body of
    `power.run_one_query` with the two calls timed apart."""
    from nds_tpu import faults

    with faults.scope(name):
        faults.maybe_fire(name)
        spans.close_between()
        t0 = time.perf_counter()
        with spans.span("plan", name):
            result = session.run_script(sql)
        t1 = time.perf_counter()
        with spans.span("execute", name):
            table = result.collect() if result is not None else None
        rec["plan_ms"] = (t1 - t0) * 1e3
        rec["execute_ms"] = (time.perf_counter() - t1) * 1e3
        spans.open_between(name)
    if keep is not None:
        keep[name] = table


class CompileWatch:
    """jax's own monitoring events, counted by name: what compiled, what
    the persistent cache served. The benchmark's evidence that nothing
    compiles inside the window."""

    def __init__(self):
        import jax.monitoring as mon

        self.count = defaultdict(int)
        self.seconds = defaultdict(float)
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.count[name] += 1

    def _duration(self, name, secs, **_):
        self.count[name] += 1
        self.seconds[name] += secs

    def snapshot(self):
        return {k: [self.count[k], self.seconds.get(k, 0.0)]
                for k in sorted(self.count) if "compil" in k or "cache" in k}


def counters(session, watch):
    aot = getattr(session, "aot_cache", None)
    return {
        "aot": dict(aot.stats) if aot is not None else None,
        "exec_cache": {"hits": session.exec_cache.hits,
                       "misses": session.exec_cache.misses},
        "jax": watch.snapshot(),
    }


def hand_over(session, rd, out):
    """What the child found, whole or not at all, where the parent looks."""
    if session.tracer is not None:
        session.tracer.close()
    with open(f"{rd}/child.json.tmp", "w") as f:
        json.dump(out, f)
    os.replace(f"{rd}/child.json.tmp", f"{rd}/child.json")
    print("child: done", flush=True)


def main(argv=None):
    args = parse_args(argv)
    t_start = time.time()
    import jax

    devices = jax.devices()
    dev = devices[0]
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
        "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "marks": {"child_start": t_start * 1e3},
    }
    print(f"child: device {out['device']}", flush=True)
    watch = CompileWatch()

    from nds_tpu.obs import trace as obs_trace
    from nds_tpu.power import run_query_stream
    from nds_tpu.report import BenchReport

    traffic = lib.load_json(args.traffic)
    passes = traffic["window_passes"]
    streams = lib.make_streams(traffic, args.scale, 0, 1 + passes)
    rd = args.run_dir

    # -- first pass: the program's own Power pass over stream 0 -------------
    out["marks"]["first_pass_start"] = time.time() * 1e3
    session = run_query_stream(
        input_prefix=args.warehouse, property_file=args.property_file,
        query_dict=OrderedDict(streams[0]),
        time_log_output_path=f"{rd}/time_first.csv",
        input_format=args.input_format, use_decimal=True,
        output_path=f"{rd}/answers/s0", output_format="parquet",
        json_summary_folder=f"{rd}/json_first", keep_session=True,
        mesh_devices=args.mesh_devices,
    )
    out["marks"]["first_pass_end"] = time.time() * 1e3
    with open(f"{rd}/time_first.csv") as f:
        times = {r[1]: r[2] for r in csv.reader(f)}
    first = {"power_test_ms": int(times["Power Test Time"]),
             "total_ms": int(times["Total Time"]), "statements": {}}
    for name, _ in streams[0]:
        found = glob.glob(f"{rd}/json_first/*-{name}-*.json")
        if len(found) != 1:
            first["statements"][name] = {"status": [f"{len(found)} summaries"]}
            continue
        s = lib.load_json(found[0])
        mem = s.get("memoryHighWater") or {}
        first["statements"][name] = {
            "ms": int(times[name]), "status": s["queryStatus"],
            "backend": s["env"]["engineConf"]["jax.backend"],
            "mem_source": mem.get("source"), "mem_bytes": mem.get("bytes"),
            "ladder": s.get("ladder"), "exceptions": s.get("exceptions"),
        }
    out["first_pass"] = first
    out["counters"] = {"first_pass_end": counters(session, watch)}
    print(f"child: first pass {first['power_test_ms']} ms", flush=True)
    if args.pass_only:
        return hand_over(session, rd, out)

    aot = getattr(session, "aot_cache", None)
    spans = Spans(False)
    keep = {}

    def one(si, name, sql, t_origin, first=False):
        """One statement as the Power loop runs one, timed on this clock;
        `first`: of the window's first pass, whose answers are kept."""
        t0 = time.perf_counter()
        rec = {"stream": si, "name": name, "start_s": t0 - t_origin}
        misses0 = session.exec_cache.misses
        aot0 = dict(aot.stats) if aot is not None else None
        summary = BenchReport(session).report_on(
            run_statement, session, sql, name, spans, rec,
            keep if first else None,
            retry_oom=True, name=name,
        )
        rec["ms"] = (time.perf_counter() - t0) * 1e3
        rec["status"] = summary["queryStatus"][-1]
        rec["new_shapes"] = session.exec_cache.misses - misses0
        if aot is not None:
            rec["aot_loaded"] = aot.stats["disk_hits"] - aot0["disk_hits"]
            rec["aot_compiled"] = aot.stats["misses"] - aot0["misses"]
        if summary.get("ladder"):
            rec["ladder"] = summary["ladder"]
        if rec["status"] != "Completed":
            rec["exceptions"] = summary.get("exceptions")
        return rec

    # -- rehearsal: every statement the window will replay, once -------------
    # The engine builds an executable for every new literal: a statement new
    # to the session compiles, or loads from the disk caches, for a few
    # hundred milliseconds. That is paid and timed here (`new_stmt_ms`), so
    # that nothing compiles inside the window.
    out["marks"]["rehearsal_start"] = time.time() * 1e3
    t_reh = time.perf_counter()
    rehearsal = []
    with obs_trace.bind(session.tracer):
        for si in range(1, 1 + passes):
            for name, sql in streams[si]:
                rehearsal.append(one(si, name, sql, t_reh))
    out["rehearsal"] = rehearsal
    out["marks"]["rehearsal_end"] = time.time() * 1e3
    out["counters"]["rehearsal_end"] = counters(session, watch)
    print(f"child: rehearsal {time.perf_counter() - t_reh:.3f} s, "
          f"{len(rehearsal)} statements", flush=True)

    # -- window --------------------------------------------------------------
    import pyarrow as pa

    profile_dir = f"{rd}/profile"
    tracing = TraceSlice(args.trace, profile_dir, args.trace_cycle,
                         int(traffic.get("trace_passes", 2)), spans,
                         out["marks"])
    compared_stream = lib.window_order(traffic, args.seed, 0)[0]

    def new_cycle(cycle):
        # a catalog registration drops the plan-result cache (and the
        # join-order memo), by the engine's own rule: without it a cycle
        # would be served from the answers of the one before
        session.register_arrow(
            "benchmark_cycle", pa.table({"cycle": [cycle]}))

    out["marks"]["window_open"] = time.time() * 1e3
    with obs_trace.bind(session.tracer):
        statements, cycle, t_last, t_open = run_window(
            traffic, streams, args.seed, args.seconds, tracing, one,
            new_cycle)
    spans.close_between()
    out["marks"]["window_close"] = time.time() * 1e3
    out["window_s"] = t_last - t_open
    out["compared_stream"] = compared_stream
    out["statements"] = statements
    if args.trace:
        out["slice"], out["slice_error"] = tracing.record(
            lib.slice_statements(traffic, streams, args.seed,
                                 tracing.cycle, tracing.passes))
    out["counters"]["window_close"] = counters(session, watch)
    print(f"child: window {out['window_s']:.3f} s, "
          f"{len(statements)} statements, {cycle} cycles", flush=True)

    # -- after the window ----------------------------------------------------
    from nds_tpu.power import ensure_valid_column_names
    import pyarrow.parquet as pq

    for name, table in keep.items():
        if table is None:
            continue
        dest = f"{rd}/answers/s{compared_stream}/{name}"
        os.makedirs(dest, exist_ok=True)
        pq.write_table(ensure_valid_column_names(table),
                       f"{dest}/part-0.parquet")
    stats = dev.memory_stats() or {}
    out["memory"] = {
        "peak_bytes_in_use": max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices) or None,
        "bytes_limit": stats.get("bytes_limit"),
    }
    if args.trace:
        from .tracereduce import reduce_trace

        found = glob.glob(f"{profile_dir}/plugins/profile/*/*.xplane.pb")
        if len(found) != 1:
            out["trace_error"] = f"{len(found)} .xplane.pb under {profile_dir}"
        else:
            try:
                out["device_trace"] = reduce_trace(found[0])
            except lib.BenchmarkError as e:
                out["trace_error"] = str(e)
    return hand_over(session, rd, out)


if __name__ == "__main__":
    sys.exit(main())
