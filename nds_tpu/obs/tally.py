"""Per-statement tally of kernel launches and blocking device-to-host reads.

Plan-node `op_span`s say which operator a statement's time went to; they
cannot say why the host sat inside it. Two seams answer that, and both add
into the `Tally` the statement's `Executor` binds to its thread:

  - `host_read(why, x)`: THE blocking device-to-host read. Every read of
    `engine/` and `ops/` goes through it (the `host-read-seam` lint keeps it
    so), is timed, and emits one `host_read` event carrying the executor's
    `exec_id` and the depth of the enclosing `op_span`.
  - `Tally.enter(name)` / `Tally.leave(token)`: the launch seam behind
    `ops/kernels._ktraced` and the fused-pipeline calls. Plain integer adds
    and one clock pair per outermost call; no event per launch, and nothing
    waits for the device.

`Executor.execute` moves the counters into the `op_span` it emits
(`launches`, `launch_ms`, `reads`, `read_wait_ms`, exclusive of children);
what is counted outside every plan node (the collect) lands on the
statement's `result_span`. With no tally bound, which is every thread whose
session has no tracer, `host_read` is the bare `jax.device_get` and the
launch seam one thread-local read.
"""

from __future__ import annotations

import threading
import time
from time import perf_counter as _perf

import jax

_tls = threading.local()


class Tally:
    """One executor's open counters. `depth` is the depth of the `op_span`
    now executing (-1 outside every plan node); the four counters belong to
    that span alone: `Executor.execute` swaps them out around its children
    (`push` / `pop`)."""

    __slots__ = ("tracer", "exec_id", "depth", "launches", "launch_ms",
                 "reads", "read_wait_ms", "in_seam")

    def __init__(self, tracer, exec_id):
        self.tracer = tracer
        self.exec_id = exec_id
        self.depth = -1
        # launches: seam name -> launches counted; launch_ms: host time
        # inside outermost seamed calls; reads / read_wait_ms: the host_reads
        self._zero()
        self.in_seam = False

    def push(self, depth):
        """Open a plan node's frame; returns what `pop` restores."""
        saved = (self.depth, self.launches, self.launch_ms, self.reads,
                 self.read_wait_ms)
        self.depth = depth
        self._zero()
        return saved

    def _zero(self):
        self.launches = {}
        self.launch_ms = 0.0
        self.reads = 0
        self.read_wait_ms = 0.0

    def take(self):
        """The open counters as span fields; they start again from zero."""
        own = {
            "launches": self.launches,
            "launch_ms": round(self.launch_ms, 3),
            "reads": self.reads,
            "read_wait_ms": round(self.read_wait_ms, 3),
        }
        self._zero()
        return own

    def pop(self, saved):
        """Close the frame: the node's own counters as span fields."""
        own = self.take()
        (self.depth, self.launches, self.launch_ms, self.reads,
         self.read_wait_ms) = saved
        return own

    def enter(self, name, n=1):
        """Count one seamed call as `n` launches (the gathers launch one
        program a buffer). The outermost call of a nest gets a token to
        time its host side with; a nested one (group_by_words ->
        sort_by_words) is counted and returns None."""
        self.launches[name] = self.launches.get(name, 0) + n
        if self.in_seam:
            return None
        self.in_seam = True
        return (_perf(), self.read_wait_ms)

    def leave(self, token):
        """Host milliseconds of the seamed call, less what it spent waiting
        in `host_read` (join_candidates reads its pair count), so the two
        never overlap."""
        self.in_seam = False
        t0, waited = token
        self.launch_ms += (
            (_perf() - t0) * 1000.0 - (self.read_wait_ms - waited)
        )


def current():
    """The tally bound to this thread, or None."""
    return getattr(_tls, "tally", None)


class bind:
    """Bind a tally (or None) to this thread for a `with` block."""

    __slots__ = ("tally", "prev")

    def __init__(self, tally):
        self.tally = tally

    def __enter__(self):
        self.prev = getattr(_tls, "tally", None)
        _tls.tally = self.tally
        return self.tally

    def __exit__(self, *exc):
        _tls.tally = self.prev
        return False


def _nbytes(out) -> int:
    if isinstance(out, (list, tuple)):
        return sum(_nbytes(x) for x in out)
    return int(getattr(out, "nbytes", 0))


def host_read(why: str, x):
    """`jax.device_get(x)`: block until `x` (an array or a list of them) is
    computed and copy it to the host. `why` names the site from a short
    fixed vocabulary (README "Observability")."""
    t = getattr(_tls, "tally", None)
    if t is None:
        return jax.device_get(x)
    t0_ns = time.time_ns()
    t0 = _perf()
    out = jax.device_get(x)
    dur_ms = (_perf() - t0) * 1000.0
    t.reads += 1
    t.read_wait_ms += dur_ms
    t.tracer.emit(
        "host_read", why=why, bytes=_nbytes(out), dur_ms=round(dur_ms, 3),
        t0_ns=t0_ns, exec_id=t.exec_id, depth=t.depth,
    )
    return out
