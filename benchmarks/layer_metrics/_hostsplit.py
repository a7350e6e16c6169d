"""What the four readers of the host's half of a statement share
(`launch_ms.stmt`, `retrace_ms.stmt`, `host_phase_ms.stmt`,
`host_other_ms.stmt`). Not a metric: no entry of `BENCHMARK.json` names it.

The program's `op_span`s and `result_span`s carry their own (exclusive)
host time by name since PR 41: `launch_ms_by` (seam name -> ms inside
outermost seamed calls and `eager:<site>` seams), `compile_ms` (trace,
lower, load, compile: jax stages and AOT loads the execution paid) and
`host_ms` (phase -> ms). Over the traced slice's executions, as
`exec_host_ms.stmt` is, the three sums and what they leave of
`result_span - host_read` add up to that metric of the same run.
"""

from benchmarks.layer_metrics._spans import (
    SLICE, between, reads_of, slice_results)

FIELDS = {"launch": "launch_ms_by", "retrace": "compile_ms",
          "phase": "host_ms"}


def slice_split(run):
    """{"launch", "retrace", "phase", "other"}: milliseconds a statement of
    the traced slice, or None where there is no slice or the program's spans
    carry no such fields (a program from before them: nothing to read)."""
    results = slice_results(run)
    if not results:
        return None
    execs = {(e["app"], e["exec_id"]) for e in results}
    spans = results + [e for e in between(run, "op_span", SLICE)
                       if (e["app"], e.get("exec_id")) in execs]
    if not any(field in e for e in spans for field in FIELDS.values()):
        return None
    n = len(results)
    out = {part: sum(sum((e.get(field) or {}).values()) for e in spans) / n
           for part, field in FIELDS.items()}
    waited = sum(e["dur_ms"] for e in reads_of(run, results))
    host = (sum(e["dur_ms"] for e in results) - waited) / n
    out["other"] = host - sum(out.values())
    return out


def part(run, name):
    split = slice_split(run)
    return None if split is None else split[name]
