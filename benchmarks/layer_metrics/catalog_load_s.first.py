"""Seconds the first pass spent loading tables into the catalog: storage
read, decode and host-to-device copy, as the program's own `catalog_load`
spans time them."""

from benchmarks.lib import events_between

LAYER = "session + catalog"
UNIT = "s"
MOVES = "first_pass_s"
SOURCE = "program_span"


def read(run):
    loads = events_between(run, "catalog_load", "first_pass_start",
                           "first_pass_end")
    if not loads:
        return None
    return sum(e["dur_ms"] for e in loads) / 1e3
