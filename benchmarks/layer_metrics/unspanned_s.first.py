"""Seconds of the first pass that no span names: the program's own `Power
Test Time` minus the union of the first pass's `catalog_load`,
`xla_compile`, `aot_cache` load and `host_read` intervals (never
`result_span` or `op_span`, which cover everything). Planning, Python
between launches, report writing, and whatever still has no name."""

from benchmarks.layer_metrics._spans import FIRST, between, union_s

LAYER = "phase CLIs / Power loop"
UNIT = "s"
MOVES = "first_pass_s"
SOURCE = "program_span"


def read(run):
    if not between(run, "host_read", FIRST):
        return None
    named = [e for kind in ("catalog_load", "xla_compile", "host_read")
             for e in between(run, kind, FIRST)]
    named += [e for e in between(run, "aot_cache", FIRST)
              if e.get("op") == "load" and "dur_ms" in e]
    return run["first_pass"]["power_test_ms"] / 1e3 - union_s(
        [e for e in named if "t0_ns" in e])
