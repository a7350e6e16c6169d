"""Median latency of the window's query7 statements over the ACID
warehouse: the four-dimension star join whose customer_demographics scan
the zone maps prune, so every execution opens, decodes, encodes and copies
the surviving files again beside the device work `query7_p50_ms` times in
the parquet cell. The cell's own mechanism, the pruned read, under a bound
of its own: a bound belongs to a metric, and host reads spread wider than
device work. One class's median, not a tail of the mix."""

from benchmarks import lib

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    return lib.window_percentile(run, 50, "query7")
