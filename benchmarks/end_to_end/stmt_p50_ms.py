"""Median latency of the window's statements: with six templates of equal
count it sits on the third cheapest, so it follows the fixed cost of a
statement (planning, dispatch, blocking reads) more than any kernel."""

from benchmarks import lib

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    return lib.window_percentile(run, 50)
