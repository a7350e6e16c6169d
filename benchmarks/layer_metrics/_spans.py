"""What the readers of the program's `host_read`, `result_span`,
`xla_compile` and split `catalog_load` events share. Not a metric: no
entry of `BENCHMARK.json` names it.

Every helper returns None where the program wrote no such event or field,
as a program from before these spans does: the reader then reports nothing
and the result line leaves the metric out.
"""

from benchmarks.lib import events_between, union_seconds

FIRST = ("first_pass_start", "first_pass_end")
REHEARSAL = ("rehearsal_start", "rehearsal_end")
WINDOW = ("window_open", "window_close")
SLICE = ("slice_start", "slice_end")


def between(run, kind, marks):
    """The events of one kind that ended between two of the child's marks;
    empty where the run has no such marks (an untraced run has no slice)."""
    if not all(m in run.get("marks", {}) for m in marks):
        return []
    return events_between(run, kind, *marks)


def interval_s(event):
    """(start, end) of a span in epoch seconds, from its `t0_ns`."""
    start = event["t0_ns"] / 1e9
    return start, start + event["dur_ms"] / 1e3


def union_s(events):
    """Seconds covered by the spans, each instant once: a jitted function
    traced inside another's trace is not counted twice."""
    return union_seconds([interval_s(e) for e in events])


def compile_stages(run, marks, stages):
    """`xla_compile` events of the given stages between two marks, or None
    where the program emitted no `xla_compile` at all."""
    events = between(run, "xla_compile", marks)
    if not events:
        return None
    return [e for e in events if e.get("stage") in stages]


def slice_results(run):
    """The `result_span`s of the traced slice, one a statement, or None."""
    return between(run, "result_span", SLICE) or None


def reads_of(run, results):
    """The `host_read`s of the executions these `result_span`s close."""
    execs = {(e["app"], e["exec_id"]) for e in results}
    return [e for e in between(run, "host_read", SLICE)
            if (e["app"], e["exec_id"]) in execs]
