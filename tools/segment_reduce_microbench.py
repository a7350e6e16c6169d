#!/usr/bin/env python3
"""What does a segment reduction cost on the chip, by what is known of its ids?

    python tools/segment_reduce_microbench.py [--out <file.json>] [--reps 3]

`ops/kernels.py segment_reduce(vals, gid, weight, num_segments, op)` is
`jax.ops.segment_sum` over the masked column where nothing is known of the
ids: a serial scatter of n updates. Each form here is timed alone over int64
and int32 values at n = 4,194,304 (store_sales' capacity: query9's fifteen
global aggregates, query2's seven sums) and n = 16,777,216 (inventory's:
query22's ROLLUP), three quarters of the rows live, the live rows first.

  one        the scatter with every id 0, into `bucket_cap(1)` = 1,024 cells
             (a global aggregate as the engine ran it up to PR 43)
  sorted     the scatter over sorted dense ids in 1,024 and 32,768 runs (the
             sort route: ids as `group_by_words` makes them)
  random     the scatter over ids drawn evenly from 1,024 / 65,536 /
             4,194,304 cells (the direct mixed-radix route, unsorted: what
             the next issue has to price)
  whole      `segment_reduce(vals, None, ...)`: the masked `jnp.sum`
  runs       `segment_reduce(vals, gid, ..., runs=)`: a prefix sum and two
             gathers at the run ends, 1,024 and 32,768 runs
  count_*    the same three for `count` (no values: the weight alone)
  starts     `segment_starts` (a scatter-min of n row indices) against the
             boundary flags of the same ids compacted by `compact_indices`
             (block select where the shapes allow it), 1,024 and 32,768 runs

Every run form is first held to the scatter's answer, cell for cell. A
call's time is the host clock around `calls` dispatches and one
`block_until_ready`, over `calls`: the device runs them back to back, so
this is device time to the dispatch of a program. `ns_row` is that time over
n. Fails off a TPU: a CPU's number is no device number.
"""

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from nds_tpu.ops import kernels as K  # noqa: E402

SIZES = (4_194_304, 16_777_216)
ONE_CAP = 1_024
RUNS = (1_024, 32_768)
CELLS = (1_024, 65_536, 4_194_304)
LIVE_SHARE = 0.75


def starts_by_compaction(gid, live, cap):
    """The run starts as `K.run_bounds` reads them."""
    return K.compact_indices(K._run_flags(gid, live), cap)


def timed(fn, calls, reps):
    """Milliseconds a call: median and least of `reps` batches."""
    jax.block_until_ready(fn())
    per_call = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls - 1):
            fn()
        jax.block_until_ready(fn())
        per_call.append((time.perf_counter() - t0) * 1e3 / calls)
    return statistics.median(per_call), min(per_call)


def sorted_ids(n, nlive, runs):
    """Dense non-decreasing ids over the live rows, then dead rows whose
    ids go on past them (as `fast_cumsum(flags) - 1` does)."""
    live = (np.arange(nlive, dtype=np.int64) * runs // nlive).astype(np.int32)
    dead = runs + np.arange(n - nlive, dtype=np.int32) // 64
    return np.concatenate([live, dead])


def same(a, b):
    return all(
        x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(a, b)
    )


def cases(n, rng):
    """(form, dtype, cells, fn, calls) of every timed form at n rows, in
    the docstring's order; a run form is held to the scatter's answer, cell
    for cell and dtype for dtype, before it is handed out."""
    nlive = int(n * LIVE_SHARE)
    live = jnp.asarray(np.arange(n) < nlive)
    weight = live & jnp.asarray(rng.random(n) < 0.9)
    zeros = jnp.zeros(n, jnp.int32)
    slow = 2 if n > 1 << 23 else 3  # a scatter at 16,777,216 takes 1.4 s
    ids = {}
    for runs in RUNS:
        gid = jnp.asarray(sorted_ids(n, nlive, runs))
        ids[runs] = (gid, K.run_bounds(gid, live, runs, runs))

    def reduce(vals, gid, cells, op, bounds=None):
        return lambda: K.segment_reduce(vals, gid, weight, cells, op, bounds)

    def with_count(vals, gid, cells, bounds=None):
        return lambda: K.segment_reduce_with_count(
            vals, gid, weight, cells, "sum", bounds)

    for name, dtype in (("int64", np.int64), ("int32", np.int32)):
        vals = jnp.asarray(rng.integers(-(1 << 31), 1 << 31, n).astype(dtype))
        assert same(with_count(vals, zeros, ONE_CAP)(),
                    with_count(vals, None, ONE_CAP)()), ("whole", name, n)
        yield "one", name, 1, reduce(vals, zeros, ONE_CAP, "sum"), slow
        yield "whole", name, 1, reduce(vals, None, ONE_CAP, "sum"), 20
        yield "whole_with_count", name, 1, with_count(vals, None, ONE_CAP), 20
        for runs, (gid, bounds) in ids.items():
            assert same(with_count(vals, gid, runs)(),
                        with_count(vals, gid, runs, bounds)()), (name, n, runs)
            yield "sorted", name, runs, reduce(vals, gid, runs, "sum"), slow
            yield "runs", name, runs, reduce(vals, gid, runs, "sum", bounds), 10
            yield ("runs_with_count", name, runs,
                   with_count(vals, gid, runs, bounds), 10)
        for cells in CELLS:
            gid = jnp.asarray(rng.integers(0, cells, n).astype(np.int32))
            yield "random", name, cells, reduce(vals, gid, cells, "sum"), slow
    # a count reads no values: once a size
    yield "count_one", "bool", 1, reduce(zeros, zeros, ONE_CAP, "count"), slow
    yield "count_whole", "bool", 1, reduce(zeros, None, ONE_CAP, "count"), 20
    for runs, (gid, bounds) in ids.items():
        yield ("count_sorted", "bool", runs,
               reduce(zeros, gid, runs, "count"), slow)
        yield ("count_runs", "bool", runs,
               reduce(zeros, gid, runs, "count", bounds), 10)
        assert np.array_equal(
            np.asarray(K.segment_starts(gid, runs)),
            np.asarray(starts_by_compaction(gid, live, runs)),
        ), ("starts", n, runs)
        yield ("starts_scatter", "int32", runs,
               partial(K.segment_starts, gid, runs), slow)
        yield ("starts_compaction", "int32", runs,
               partial(starts_by_compaction, gid, live, runs), 10)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=44)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}: no device number here")
    rng = np.random.default_rng(args.seed)
    lines = []
    for n in SIZES:
        for form, dtype, cells, fn, calls in cases(n, rng):
            med, least = timed(fn, calls, args.reps)
            lines.append({
                "form": form, "dtype": dtype, "n": n, "cells": cells,
                "ms": med, "ms_min": least, "ns_row": med * 1e6 / n,
            })
            print(json.dumps(lines[-1]), flush=True)
    result = {"device": dev.device_kind, "seed": args.seed, "lines": lines}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
