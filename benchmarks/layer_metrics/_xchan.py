"""What the readers of the cross-channel cell's own spans share
(`setop_ms.stmt`, `scalar_subq_ms.stmt`, `scalar_subq_runs.stmt`,
`union_windows.stmt`). Not a metric: no entry of `BENCHMARK.json` names it.

All four read the traced slice (the mix's `trace_cycle`: five statements,
each in full), as `exec_host_ms.stmt` does, and divide by its statements.
Each returns None where the program wrote nothing of the kind at all: a
program from before the `scalar_subquery` event and the SetOp span's `op`
field is left without the metric, not read as 0.
"""

from benchmarks.layer_metrics._spans import SLICE, between, slice_results


def wrote(run, kind, field=None, **where):
    """Whether the program wrote, anywhere in the run, an event of `kind`
    that carries `field` and the values of `where`."""
    return any(
        e.get("kind") == kind and (field is None or field in e)
        and all(e.get(k) == v for k, v in where.items())
        for e in run.get("events", ()))


def per_statement(run, total):
    """`total` of the slice over its statements; None without a slice."""
    results = slice_results(run)
    return total / len(results) if results else None


def setop_spans(run):
    """The slice's `op_span`s of SetOp nodes that say which operation they
    ran, each with `own_ms`: its duration less its direct children's. Spans
    are written in completion order with `depth` and a `seq` of their
    execution, so a child precedes its parent and one pass does it."""
    by_exec = {}
    for e in between(run, "op_span", SLICE):
        by_exec.setdefault((e["app"], e["exec_id"]), []).append(e)
    out = []
    for spans in by_exec.values():
        waiting = {}  # depth -> ms of finished spans that await a parent
        for e in sorted(spans, key=lambda e: e["seq"]):
            depth = e["depth"]
            own = max(e["dur_ms"] - waiting.pop(depth + 1, 0.0), 0.0)
            waiting[depth] = waiting.get(depth, 0.0) + e["dur_ms"]
            if e.get("node") == "SetOp" and "op" in e:
                out.append({**e, "own_ms": own})
    return out


def subqueries(run):
    """The slice's `scalar_subquery` spans whose plan ran (`source`
    executed; the others were answered by the session's cache), or None
    where the program writes no such event."""
    if not wrote(run, "scalar_subquery"):
        return None
    return [e for e in between(run, "scalar_subquery", SLICE)
            if e.get("source") == "executed"]
