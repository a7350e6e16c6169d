"""Per-statement tally of a plan node's own host time: kernel launches,
blocking device-to-host reads, compile stages and named host phases.

Plan-node `op_span`s say which operator a statement's time went to; they
cannot say why the host sat inside it. Four seams answer that, and all add
into the `Tally` the statement's `Executor` binds to its thread:

  - `host_read(why, x)`: THE blocking device-to-host read. Every read of
    `engine/` and `ops/` goes through it (the `host-read-seam` lint keeps it
    so), is timed, and emits one `host_read` event carrying the executor's
    `exec_id` and the depth of the enclosing `op_span`.
  - `Tally.enter(name)` / `Tally.leave(token)`: the launch seam behind
    `ops/kernels._ktraced` and the fused-pipeline calls. Plain integer adds
    and one clock pair per outermost call; no event per launch, and nothing
    waits for the device. The milliseconds go under the call's name
    (`launch_ms_by`), and `launch_ms` is their sum. `eager(site)` is the
    same seam around a stretch of eager `jnp` work that is no kernel entry:
    timed under `eager:<site>` in `launch_ms_by`, counted in `eager_calls`,
    and never in `launches` or `launch_ms`, which stay the kernel seams'.
  - `phase(name)`: host work that launches nothing (a pipeline build, a
    dictionary merge, Arrow assembly), into `host_ms` under one of `PHASES`.
  - `Tally.add_compile(stage, start, end)`: a jax compile stage or an AOT
    load (`obs/trace._on_compile_span`, `engine/aotcache.py`), into
    `compile_ms` under `trace | lower | load | compile`.

Beside them one counter with no clock: `dict_memo(outcome)`, how the
dictionary derivations a span asked for were answered (`dict_memo` {same |
hit | miss: calls}; `engine/columnar.py _DictMemo`).

The four never overlap: at any instant the time belongs to the innermost
open seam or phase, a read or a compile stage inside one is taken out of
it, and a kernel seam is atomic (what opens inside it is counted under its
own name and timed under the kernel's, as `group_by_words -> sort_by_words`
always was). Under a jax trace (a pipeline build tracing the engine's own
evaluator) `phase` and `eager` are nothing: no program is launched there
and the time is the trace stage's. So `read_wait_ms + sum(launch_ms_by) + sum(compile_ms) +
sum(host_ms)` never exceeds the span's exclusive duration, and what is left
has no name yet: the readers report it as `other` and never spread it.

`Executor.execute` moves the counters into the `op_span` it emits
(exclusive of children); what is counted outside every plan node (the
collect) lands on the statement's `result_span`. When the tracer writes a
file, the tally also keeps where on the clock each piece lay (`host_iv`:
`[name, start offset in us from the span's t0_ns, dur in us]`, split
around the reads, compile stages, inner seams and child spans inside it,
so pieces never overlap); a ring-only tracer builds no list. With no tally
bound, which is every thread whose session has no tracer, `host_read` is
the bare `jax.device_get`, and the launch seam and `phase` are one
thread-local read: no clock.
"""

from __future__ import annotations

import threading
import time
from functools import wraps
from time import perf_counter as _perf

import jax
from jax.core import trace_ctx as _trace_ctx

# the vocabularies of `compile_ms` and `host_ms` live where the readers,
# which import no jax, find them
from .trace import COMPILE_STAGES, HOST_PHASES as PHASES  # noqa: F401

_tls = threading.local()

_KERNEL, _EAGER, _PHASE = 0, 1, 2


class Tally:
    """One executor's open counters. `depth` is the depth of the `op_span`
    now executing (-1 outside every plan node); the counters belong to that
    span alone: `Executor.execute` swaps them out around its children
    (`push` / `pop`)."""

    __slots__ = ("tracer", "exec_id", "depth", "launches", "launch_ms",
                 "reads", "read_wait_ms", "in_seam", "launch_ms_by",
                 "compile_ms", "host_ms", "eager_calls", "dict_memo",
                 "keep_iv", "iv",
                 "t0", "_acct_ms", "_open", "_seg_t0", "_compiled")

    def __init__(self, tracer, exec_id):
        self.tracer = tracer
        self.exec_id = exec_id
        self.depth = -1
        # intervals only where a file keeps them (obs/trace.py Tracer.path)
        self.keep_iv = getattr(tracer, "path", None) is not None
        # launches: seam name -> launches counted; launch_ms(_by): host time
        # inside outermost seamed calls; reads / read_wait_ms: the
        # host_reads; compile_ms: stage -> ms; host_ms: phase -> ms
        self._zero()
        self.in_seam = False

    def push(self, depth):
        """Open a plan node's frame; returns what `pop` restores."""
        if self.keep_iv and self._open:
            # the parent's open seam or phase stops at its child's start
            self._piece(self.iv, self._open[-1], _perf())
        saved = (self.depth, self.launches, self.launch_ms, self.reads,
                 self.read_wait_ms, self.launch_ms_by, self.compile_ms,
                 self.host_ms, self.eager_calls, self.dict_memo, self.iv,
                 self.t0, self._acct_ms, self._open, self._compiled,
                 self.in_seam)
        self.depth = depth
        self.in_seam = False
        self._zero()
        return saved

    def _zero(self):
        self.launches = {}
        self.launch_ms = 0.0
        self.reads = 0
        self.read_wait_ms = 0.0
        self.launch_ms_by = {}
        self.compile_ms = {}
        self.host_ms = {}
        self.eager_calls = {}
        self.dict_memo = {}
        self.iv = []
        # milliseconds of this frame that already belong to something (a
        # closed seam or phase, a compile stage, a child span); with
        # read_wait_ms, what an open seam or phase takes out of its own time
        self._acct_ms = 0.0
        self._open = []  # names of the open seams and phases, outermost first
        self._compiled = []  # disjoint (start_s, end_s) of counted stages
        self.t0 = self._seg_t0 = _perf()

    def take(self, t0=None):
        """The open counters as span fields; they start again from zero.
        `t0`: the span's start on `perf_counter`, which `host_iv` counts
        from (default: where this frame opened)."""
        own = {
            "launches": self.launches,
            "launch_ms": round(self.launch_ms, 3),
            "reads": self.reads,
            "read_wait_ms": round(self.read_wait_ms, 3),
            "launch_ms_by": _rounded(self.launch_ms_by),
            "compile_ms": _rounded(self.compile_ms),
            "host_ms": _rounded(self.host_ms),
        }
        if self.eager_calls:
            own["eager_calls"] = self.eager_calls
        if self.dict_memo:
            own["dict_memo"] = self.dict_memo
        if self.iv:
            if t0 is None:
                t0 = self.t0
            own["host_iv"] = [
                [name, int((a - t0) * 1e6), int((b - a) * 1e6)]
                for name, a, b in self.iv
            ]
        self._zero()
        return own

    def pop(self, saved, t0=None):
        """Close the frame: the node's own counters as span fields."""
        opened = self.t0
        own = self.take(t0)
        now = self._seg_t0  # `take` has just read the clock
        (self.depth, self.launches, self.launch_ms, self.reads,
         self.read_wait_ms, self.launch_ms_by, self.compile_ms,
         self.host_ms, self.eager_calls, self.dict_memo, self.iv,
         self.t0, self._acct_ms, self._open, self._compiled,
         self.in_seam) = saved
        # the child's whole time is not its parent's open phase's, whose
        # next piece starts here
        self._acct_ms += (now - opened) * 1000.0
        self._seg_t0 = now
        return own

    # -- the launch seam and the phases ---------------------------------

    def enter(self, name, n=1):
        """Count one seamed call as `n` launches (the gathers launch one
        program a buffer). The outermost call of a nest gets a token to
        time its host side with; a nested one (group_by_words ->
        sort_by_words), and one under a jax trace (its time is the trace
        stage's), is counted and returns None."""
        self.launches[name] = self.launches.get(name, 0) + n
        if self.in_seam or not _trace_ctx.is_top_level():
            return None
        self.in_seam = True
        return self._start(name, _KERNEL)

    def _start(self, name, kind):
        now = _perf()
        if self.keep_iv:
            if self._open:
                self._piece(self.iv, self._open[-1], now)
            self._open.append(name)
            self._seg_t0 = now
        return (now, self.read_wait_ms + self._acct_ms, name, kind)

    def leave(self, token):
        """Host milliseconds of the seamed call or phase, less what it
        spent waiting in `host_read` (join_candidates reads its pair
        count), in compile stages and in the seams and phases that opened
        inside it, so none of them overlap."""
        t0, acct0, name, kind = token
        now = _perf()
        ms = (now - t0) * 1000.0 - (
            self.read_wait_ms + self._acct_ms - acct0
        )
        self._acct_ms += ms
        if kind == _PHASE:
            self.host_ms[name] = self.host_ms.get(name, 0.0) + ms
        else:
            if kind == _KERNEL:
                self.in_seam = False
                self.launch_ms += ms
            self.launch_ms_by[name] = self.launch_ms_by.get(name, 0.0) + ms
        if self.keep_iv and self._open:
            self._piece(self.iv, self._open.pop(), now)
            self._seg_t0 = now

    def _piece(self, iv, name, end):
        """One interval of `name`, from where its last piece stopped."""
        if end > self._seg_t0:
            iv.append((name, self._seg_t0, end))

    def add_compile(self, stage, start, end):
        """A compile stage (epoch seconds) that ended now on this thread.
        Stages nest (a jitted function traced inside another's trace) and
        arrive innermost first: each instant is counted once, under the
        stage that ended first."""
        inner = 0.0
        done = self._compiled
        while done and done[-1][0] >= start:
            a, b = done.pop()
            inner += b - a
        done.append((start, end))
        ms = max((end - start - inner) * 1000.0, 0.0)
        self.compile_ms[stage] = self.compile_ms.get(stage, 0.0) + ms
        self._acct_ms += ms
        if self.keep_iv and self._open:
            # the open seam's pieces stop where the stage began
            now = _perf()
            began = now - (end - start)
            iv = self.iv
            while iv and iv[-1][1] >= began:
                iv.pop()
            if iv and iv[-1][2] > began:
                iv[-1] = (iv[-1][0], iv[-1][1], began)
            if self._seg_t0 < began:
                iv.append((self._open[-1], self._seg_t0, began))
            self._seg_t0 = now


def _rounded(by):
    return {k: round(v, 3) for k, v in by.items()}


class _Span:
    """An open phase or eager seam of the bound tally (`with` block)."""

    __slots__ = ("tally", "name", "kind", "token")

    def __init__(self, tally, name, kind):
        self.tally = tally
        self.name = name
        self.kind = kind

    def __enter__(self):
        t = self.tally
        if t.in_seam:
            # inside a kernel seam: its time is the kernel's
            self.token = None
            return self
        if self.kind == _EAGER:
            site = self.name[6:]
            t.eager_calls[site] = t.eager_calls.get(site, 0) + 1
        self.token = t._start(self.name, self.kind)
        return self

    def __exit__(self, *exc):
        if self.token is not None:
            self.tally.leave(self.token)
        return False


class _Nothing:
    """What `phase` and `eager` hand out with no tally bound."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOTHING = _Nothing()


def _bound():
    """The tally this thread's seams add into: None with none bound, and
    under a jax trace, where nothing is launched and the time is the trace
    stage's (`compile_ms`)."""
    t = getattr(_tls, "tally", None)
    if t is None or not _trace_ctx.is_top_level():
        return None
    return t


def phase(name):
    """`with phase("dict-merge"):` around host work of the executor that is
    neither a seamed call, a `host_read` nor a compile stage; `name` is one
    of `PHASES`. One clock pair and a dictionary add; no event; with no
    tally bound nothing at all."""
    t = _bound()
    return _NOTHING if t is None else _Span(t, name, _PHASE)


def eager(site):
    """`with eager("concat"):` around a stretch of eager `jnp` work outside
    every kernel entry point (pads, slices, concatenations between jitted
    parts): launch time, under the seam name `eager:<site>` in
    `launch_ms_by`, counted in `eager_calls` where it is timed, and never
    in `launches` or `launch_ms`."""
    t = _bound()
    return _NOTHING if t is None else _Span(t, "eager:" + site, _EAGER)


def dict_memo(outcome):
    """Count one dictionary derivation (`engine/columnar.py _DictMemo`)
    into the span that asked: `same` (the inputs are one object: nothing to
    derive), `hit` (derived before from these objects) or `miss` (derived
    now). Counted under a jax trace too: a pipeline build's one call is the
    only one its executable ever makes."""
    t = getattr(_tls, "tally", None)
    if t is not None:
        t.dict_memo[outcome] = t.dict_memo.get(outcome, 0) + 1


def seamed(site):
    """Decorator: the function's body is the eager seam `eager:<site>`
    (with no tally bound: the bare call)."""
    name = "eager:" + site

    def deco(fn):
        @wraps(fn)
        def wrapped(*args, **kwargs):
            t = _bound()
            if t is None:
                return fn(*args, **kwargs)
            with _Span(t, name, _EAGER):
                return fn(*args, **kwargs)
        return wrapped
    return deco


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
    t1 = _perf()
    dur_ms = (t1 - t0) * 1000.0
    t.reads += 1
    t.read_wait_ms += dur_ms
    if t.keep_iv and t._open:
        # the open seam or phase stops at the read and goes on after it
        t._piece(t.iv, t._open[-1], t0)
        t._seg_t0 = t1
    t.tracer.emit(
        "host_read", why=why, bytes=_nbytes(out), dur_ms=round(dur_ms, 3),
        t0_ns=t0_ns, exec_id=t.exec_id, depth=t.depth,
    )
    return out
