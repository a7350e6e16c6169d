#!/usr/bin/env python3
"""What does a dense probe cost on the chip, by the form of its lookup table?

    python tools/dense_probe_microbench.py [--out <file.json>] [--reps 5]

Each form is one jitted program, as `ops/kernels.py dense_probe` is, timed
alone at the capacities a star join's first step runs at (store_sales at
4,194,304 rows, store_returns at 524,288) against the table sizes of the
dimensions it probes (store 1,024; item 32,768; date_dim and time_dim
131,072; customer_demographics 2,097,152):

  a    a bool table and an int32 table gathered by one int64 slot (the form
       the engine had up to PR 35)
  a32  the same two tables, the slot cast to int32 after the bounds check
  b    one int32 table of row + 1 (0: no row), slot int64 (the form the
       engine has since PR 36)
  c    (b) with the slot cast to int32 after the bounds check

Keys are drawn as a fact table's foreign keys are: uniform over the
dimension's rows, 4% null, and the rows past the table's live count dead
(2,880,404 of 4,194,304, the share of both sizes). Every form is first held
to form a's answer. A call's time is the host clock around `calls` dispatches
and one `block_until_ready`, over `calls`: the device runs them back to back,
so this is device time to the dispatch of one program. The build is timed the
same way in its two forms. Fails off a TPU: a CPU's number is no device
number.
"""

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

I64 = jnp.int64
LIVE_SHARE = 2_880_404 / 4_194_304
# table_cap: rows of the dimension (the key's domain)
DIMENSIONS = {1_024: 12, 32_768: 18_000, 131_072: 73_049, 2_097_152: 1_920_800}
PROBE_ROWS = (4_194_304, 524_288)


def _slot(lkey, llive, rmin, table_cap):
    slot = lkey.astype(I64) - rmin
    inb = (slot >= 0) & (slot < table_cap) & llive
    return inb, jnp.clip(slot, 0, table_cap - 1)


@partial(jax.jit, static_argnames=("table_cap", "slot32"))
def probe_two_tables(lkey, llive, rmin, presence, rows, table_cap, slot32):
    inb, slot = _slot(lkey, llive, rmin, table_cap)
    if slot32:
        slot = slot.astype(jnp.int32)
    return inb & presence[slot], rows[slot]


@partial(jax.jit, static_argnames=("table_cap", "slot32"))
def probe_one_table(lkey, llive, rmin, rowid1, table_cap, slot32):
    inb, slot = _slot(lkey, llive, rmin, table_cap)
    if slot32:
        slot = slot.astype(jnp.int32)
    r = rowid1[slot]
    matched = inb & (r > 0)
    return matched, jnp.where(matched, r - 1, 0)


def _build_slot(rkey, rlive, rmin, table_cap):
    slot = jnp.where(rlive, rkey.astype(I64) - rmin, jnp.int64(table_cap))
    return jnp.where((slot >= 0) & (slot <= table_cap), slot, table_cap)


@partial(jax.jit, static_argnames=("table_cap",))
def build_two_tables(rkey, rlive, rmin, table_cap):
    slot = _build_slot(rkey, rlive, rmin, table_cap)
    presence = jnp.zeros(table_cap, bool).at[slot].max(rlive, mode="drop")
    rows = jnp.zeros(table_cap, jnp.int32).at[slot].max(
        jnp.arange(rkey.shape[0], dtype=jnp.int32), mode="drop"
    )
    return presence, rows


@partial(jax.jit, static_argnames=("table_cap",))
def build_one_table(rkey, rlive, rmin, table_cap):
    slot = _build_slot(rkey, rlive, rmin, table_cap)
    return jnp.zeros(table_cap, jnp.int32).at[slot].max(
        jnp.arange(1, rkey.shape[0] + 1, dtype=jnp.int32), mode="drop"
    )


def timed(fn, calls, reps):
    """Milliseconds a call: median and least of `reps` batches."""
    jax.block_until_ready(fn())
    jax.block_until_ready(fn())
    per_call = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls - 1):
            fn()
        jax.block_until_ready(fn())
        per_call.append((time.perf_counter() - t0) * 1e3 / calls)
    return statistics.median(per_call), min(per_call)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=36)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}: no device number here")
    rng = np.random.default_rng(args.seed)
    lines = []
    rmin = 1
    for table_cap, dim_rows in DIMENSIONS.items():
        # the build side: the dimension at its capacity, half its rows live
        n = max(1 << (dim_rows - 1).bit_length(), 1_024)
        rkey_h = np.zeros(n, np.int64)
        rkey_h[:dim_rows] = rng.permutation(dim_rows) + rmin
        rlive_h = (np.arange(n) < dim_rows) & (rng.random(n) < 0.5)
        rkey, rlive = jnp.asarray(rkey_h), jnp.asarray(rlive_h)
        presence, rows = build_two_tables(rkey, rlive, rmin, table_cap)
        rowid1 = build_one_table(rkey, rlive, rmin, table_cap)
        assert np.array_equal(np.asarray(rowid1) > 0, np.asarray(presence))
        assert np.array_equal(
            np.maximum(np.asarray(rowid1) - 1, 0), np.asarray(rows)
        )
        for name, fn in (
            ("build_two", lambda: build_two_tables(rkey, rlive, rmin, table_cap)),
            ("build_one", lambda: build_one_table(rkey, rlive, rmin, table_cap)),
        ):
            med, least = timed(fn, 20, args.reps)
            lines.append({"what": name, "rows": n, "table_cap": table_cap,
                          "ms": med, "ms_min": least, "ns_row": med * 1e6 / n})
            print(json.dumps(lines[-1]), flush=True)
        for probe_rows in PROBE_ROWS:
            live_n = int(probe_rows * LIVE_SHARE)
            lkey_h = np.zeros(probe_rows, np.int64)
            lkey_h[:live_n] = rng.integers(rmin, rmin + dim_rows, live_n)
            llive_h = (np.arange(probe_rows) < live_n) & (
                rng.random(probe_rows) >= 0.04
            )
            lkey, llive = jnp.asarray(lkey_h), jnp.asarray(llive_h)
            forms = {
                "a": lambda: probe_two_tables(
                    lkey, llive, rmin, presence, rows, table_cap, False),
                "a32": lambda: probe_two_tables(
                    lkey, llive, rmin, presence, rows, table_cap, True),
                "b": lambda: probe_one_table(
                    lkey, llive, rmin, rowid1, table_cap, False),
                "c": lambda: probe_one_table(
                    lkey, llive, rmin, rowid1, table_cap, True),
            }
            want_m, want_r = (np.asarray(x) for x in forms["a"]())
            want_r = np.where(want_m, want_r, 0)
            for name, fn in forms.items():
                got_m, got_r = (np.asarray(x) for x in fn())
                assert np.array_equal(got_m, want_m), name
                assert np.array_equal(np.where(got_m, got_r, 0), want_r), name
                med, least = timed(fn, 10 if probe_rows > 1 << 20 else 40,
                                   args.reps)
                lines.append({
                    "what": f"probe_{name}", "rows": probe_rows,
                    "table_cap": table_cap, "matched": int(want_m.sum()),
                    "ms": med, "ms_min": least,
                    "ns_row": med * 1e6 / probe_rows,
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
