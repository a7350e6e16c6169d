"""Seconds from the start of the parent process to the opening of the
window: data and warehouse (found, or generated and loaded), attach, first
pass, rehearsal and, in a checkout's first run, the warm-up child."""

UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return (run["marks"]["window_open"] - run["marks"]["parent_start"]) / 1e3
