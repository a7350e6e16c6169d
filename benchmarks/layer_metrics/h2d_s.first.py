"""Seconds the first pass spent copying tables from the host to the device
(`catalog_load.h2d_ms`, awaited to completion once per table)."""

from benchmarks.layer_metrics._spans import FIRST, between

LAYER = "session + catalog"
UNIT = "s"
MOVES = "first_pass_s"
SOURCE = "program_span"


def read(run):
    loads = [e for e in between(run, "catalog_load", FIRST) if "h2d_ms" in e]
    if not loads:
        return None
    return sum(e["h2d_ms"] for e in loads) / 1e3
