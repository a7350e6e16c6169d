#!/usr/bin/env python3
"""Do the program's spans and the profiler's host plane share a clock?

    python tools/clock_check.py <run_dir of a `--trace 1` benchmark run>

Every program event that carries `dur_ms` carries `t0_ns`, the span's start
as `time.time_ns()`. The jax profiler stamps its host plane from the same
realtime clock and writes the times of an `.xplane.pb` relative to the
session's start, which the file states (`profile_start_time`, epoch ns, on
its `Task Environment` plane): epoch = `profile_start_time` + `start_ns`.
This lays the two side by side for the statements of one traced slice: the
start of the benchmark's `execute` annotation (`benchmarks/child.py` opens
it around `Result.collect`) against the `t0_ns` of the statement's
`result_span` (`Result._run` takes it a few microseconds into that call),
matched in order. Prints one JSON object: the offsets' count, median,
extremes and quartile spread in microseconds, and the same for the two ends
(annotation end against `t0_ns + dur_ms`). An offset of microseconds means
one clock and one base: no annotation of the program's own is needed as a
tie.

Needs jax only for `jax.profiler.ProfileData` (no device).
"""

import glob
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def summary(us):
    q = statistics.quantiles(us, n=4) if len(us) > 1 else [us[0]] * 3
    return {"n": len(us), "median_us": statistics.median(us),
            "min_us": min(us), "max_us": max(us), "iqr_us": q[2] - q[0]}


def main(run_dir):
    from jax.profiler import ProfileData

    from benchmarks import lib

    (pb,) = glob.glob(f"{run_dir}/profile/plugins/profile/*/*.xplane.pb")
    marks = lib.load_json(f"{run_dir}/child.json")["marks"]
    events = lib.read_events(f"{run_dir}/trace")
    results = sorted(
        (e for e in events if e["kind"] == "result_span"
         and marks["slice_start"] <= e["ts"] <= marks["slice_end"]),
        key=lambda e: e["t0_ns"])
    notes = []
    base_ns = None
    for plane in ProfileData.from_file(pb).planes:
        if plane.name == "Task Environment":
            base_ns = dict(plane.stats).get("profile_start_time")
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "execute":
                    notes.append((e.start_ns, e.start_ns + e.duration_ns,
                                  str(dict(e.stats).get("q", ""))))
    out = {"run_dir": run_dir, "annotations": len(notes),
           "result_spans": len(results), "profile_start_time_ns": base_ns}
    if base_ns is None:
        out["error"] = "the trace states no profile_start_time"
        print(json.dumps(out))
        return 1
    notes = sorted((a + base_ns, b + base_ns, q) for a, b, q in notes)
    if len(notes) != len(results) or not notes:
        out["error"] = "the slice's annotations and result_spans do not pair"
        print(json.dumps(out))
        return 1
    for (_, _, q), r in zip(notes, results):
        if q != r.get("query"):
            out["error"] = f"order differs: annotation {q}, span {r.get('query')}"
            print(json.dumps(out))
            return 1
    out["start_offset"] = summary(
        [(r["t0_ns"] - a) / 1e3 for (a, _, _), r in zip(notes, results)])
    out["end_offset"] = summary(
        [(b - (r["t0_ns"] + r["dur_ms"] * 1e6)) / 1e3
         for (_, b, _), r in zip(notes, results)])
    out["slice_start_mark_minus_profile_start_ms"] = (
        marks["slice_start"] - base_ns / 1e6)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1].rstrip("/")))
