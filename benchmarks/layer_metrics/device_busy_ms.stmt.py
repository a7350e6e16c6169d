"""Milliseconds the device was busy per statement of the traced slice:
the union of the device-operation intervals of the profiler's trace over
the statements executed in it. Device time, never a host clock."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "replay_qps"
SOURCE = "device_trace"


def read(run):
    trace = run.get("device_trace")
    if not trace or not trace["statements"]:
        return None
    return trace["busy_s"] * 1e3 / trace["statements"]
