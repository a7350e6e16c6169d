"""Statements `Completed` in the window over the window's length, which
runs to the end of the statement in flight at the deadline so that no slow
statement is censored."""

from benchmarks import lib

UNIT = "queries/s"
SOURCE = "host_clock"


def read(run):
    done = lib.completed(run)
    return len(done) / run["window_s"] if done else None
