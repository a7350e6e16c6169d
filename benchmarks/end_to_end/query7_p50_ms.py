"""Median latency of the window's query7 statements alone: the
four-dimension star join with averages, the heaviest template of the mix
whose every execution does its full work. It is one class's median, not a
tail of the mix: PERF.md section 2 says why no tail is reported."""

from benchmarks import lib

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    return lib.window_percentile(run, 50, "query7")
