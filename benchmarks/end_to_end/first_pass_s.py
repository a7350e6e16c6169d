"""Seconds of the first pass: the program's own `Power Test Time` for
stream 0, the first execution of every statement of the mix in a fresh
process whose disk caches are warm. What every Power Run pays (Tpt).

Taken twice a run, in two fresh processes one after the other: the
pass-only child (`first_pass_a_s`) and the measured child, which goes on to
the rehearsal and the window (`first_pass_b_s`). The metric is the LOWER of
the two. The pass is mostly host work (reading tables, loading and compiling
programs) on a host the run shares: a stall only ever adds seconds, so the
lower of two passes is the steadier estimate of what the program costs, and
both sides of a check are read by the same rule. Nothing of the benchmark's
own runs beside either pass (sqlite's reference has exited before the first
chip child starts). The price is set-up: one attach and one first pass more
in every run (`benchmarks/README.md` has the seconds by phase)."""

UNIT = "s"
SOURCE = "host_clock"


def read(run):
    a, b = run.get("first_pass_a_s"), run.get("first_pass_b_s")
    return None if a is None or b is None else min(a, b)
