"""Milliseconds a statement costs the first time its session meets it: the
mean over the rehearsal's statements, which run once, one after another,
between the first pass and the window. The engine builds an executable for
every new literal, so each holds a compile or, where the disk caches know
the program, a load, besides the statement's own work. What every statement
of a Throughput Run with per-stream parameters pays (ROADMAP A4)."""

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    ms = [s["ms"] for s in run.get("rehearsal", ())]
    return sum(ms) / len(ms) if ms else None
