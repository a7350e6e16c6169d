"""Host milliseconds from a statement's text to its plan
(`Session.run_script`: parse, bind, rewrite, budget, fuse), mean over the
window's statements, on the benchmark's own clock."""

LAYER = "planning"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "host_clock"


def read(run):
    plans = [s["plan_ms"] for s in run["statements"] if "plan_ms" in s]
    if not plans:
        return None
    return sum(plans) / len(plans)
