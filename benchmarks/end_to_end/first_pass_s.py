"""Seconds of the first pass: the program's own `Power Test Time` for
stream 0, the first execution of every statement of the mix in a fresh
process whose disk caches are warm. One sample a run. What every Power Run
pays (Tpt)."""

UNIT = "s"
SOURCE = "host_clock"


def read(run):
    first = run.get("first_pass")
    return first["power_test_ms"] / 1e3 if first else None
