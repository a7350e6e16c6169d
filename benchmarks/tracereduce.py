"""From a profiler trace (`.xplane.pb`) to device busy and idle time.

The yardstick for everything the device does: no host clock enters here.
The trace holds one plane per chip (`/device:TPU:<n>`) whose `XLA Ops` line
carries one event per operation that ran on the device, with its start and
duration on the device's clock, and a `/host:CPU` plane whose thread lines
carry the benchmark's own `TraceAnnotation`s (`plan`, `execute`, `between`,
each with the statement's name as the stat `q`) on the same time base (on a
v5e the two clocks were seen 1-2 ms apart, so a gap shorter than that is
not attributed reliably).

    window   first annotation's start .. last annotation's end
    busy     union of the device-op intervals inside the window, per chip,
             averaged over the chips
    idle     window - busy, split by the annotation that covers it

Needs jax only for `jax.profiler.ProfileData`, so it runs in the process
that owns the chip, after the window, or in a test on the committed trace.
"""

from __future__ import annotations

import re
from collections import defaultdict

from .lib import BenchmarkError, union_seconds

ANNOTATIONS = ("plan", "execute", "between")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _op_name(text):
    """`%fusion.3 = s32[8]{0} fusion(...)` -> `fusion.3`."""
    return text.split(" = ", 1)[0].lstrip("%").strip() or text[:60]


def reduce_trace(path):
    """Busy and idle seconds of the traced slice, the ten device operations
    that took most time and the ten host activities with most idle time
    under them. Times in seconds; raises where the trace holds no device
    operation or no annotation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                    e.name) for e in lines["XLA Ops"].events] \
                if "XLA Ops" in lines else []
            mods = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            devices.append((plane.name, ops, mods))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in ANNOTATIONS:
                        q = dict(e.stats).get("q", "")
                        host.append((e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9,
                                     e.name, str(q)))
    if not host:
        raise BenchmarkError(f"{path}: none of the annotations {ANNOTATIONS}")
    if not devices:
        raise BenchmarkError(f"{path}: no /device:TPU:<n> plane")
    lo = min(a for a, _, _, _ in host)
    hi = max(b for _, b, _, _ in host)

    busy_per_device = []
    op_seconds = defaultdict(float)
    idle_by = defaultdict(float)
    for _, ops, mods in devices:
        inside = _clip([(a, b) for a, b, _ in ops], lo, hi)
        busy_per_device.append(union_seconds(inside))
        # an operation's own name repeats across programs (`fusion.3`):
        # prefix the program whose module event holds its start
        mods = sorted(mods)
        mi = 0
        for a, b, name in sorted(ops):
            if b <= lo or a >= hi:
                continue
            while mi + 1 < len(mods) and mods[mi + 1][0] <= a:
                mi += 1
            prog = ""
            if mods and mods[mi][0] <= a < mods[mi][1]:
                prog = re.sub(r"\(\d+\)$", "", mods[mi][2]) + "/"
            op_seconds[prog + _op_name(name)] += min(b, hi) - max(a, lo)
        for a, b, phase, q in host:
            covered = union_seconds(_clip(inside, a, b))
            idle_by[f"{phase} {q}".strip()] += (b - a) - covered
    n = len(devices)
    busy_s = sum(busy_per_device) / n
    window_s = hi - lo
    if busy_s <= 0:
        raise BenchmarkError(f"{path}: no operation ran on the device in the "
                             f"traced slice")
    annotated = union_seconds([(a, b) for a, b, _, _ in host])
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "chips": n,
        "statements": sum(1 for _, _, phase, _ in host if phase == "execute"),
        "unannotated_s": window_s - annotated,
        "device_ops": top({k: v / n for k, v in op_seconds.items()}),
        "idle_gaps": top({k: v / n for k, v in idle_by.items()}),
    }
