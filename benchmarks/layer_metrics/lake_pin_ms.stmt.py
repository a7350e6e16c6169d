"""Milliseconds of snapshot pinning a window statement paid at plan time:
the `lake_pin` spans that ended inside the window (one a scanned lakehouse
table a statement: the manifest head resolved, the reader lease renewed),
over the window's statements. Inside `plan_ms.stmt`. Nothing where the
program emits no such span at all: a program from before it, or a session
with no lakehouse table."""

from benchmarks.layer_metrics._window import window_spans

LAYER = "session + catalog"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    pins = window_spans(run, "lake_pin")
    if pins is None:
        return None
    return sum(e["dur_ms"] for e in pins) / len(run["statements"])
