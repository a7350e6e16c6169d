"""Seconds the first pass spent loading AOT executables from disk
(`aot_cache` events with op `load`, hits and misses alike). Misses and
stores are on an earlier line of the run: both are 0 once the cache is
warm."""

from benchmarks.lib import events_between

LAYER = "compile caches"
UNIT = "s"
MOVES = "first_pass_s"
SOURCE = "program_span"


def read(run):
    loads = [e for e in events_between(run, "aot_cache", "first_pass_start",
                                       "first_pass_end")
             if e.get("op") == "load" and "dur_ms" in e]
    if not loads:
        return None
    return sum(e["dur_ms"] for e in loads) / 1e3
