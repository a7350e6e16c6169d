#!/usr/bin/env python3
"""What does a compaction cost on the chip, by the form that finds the indices?

    python tools/compact_microbench.py [--out <file.json>] [--reps 5]

`ops/kernels.py compact_indices(mask, out_cap)` answers the indices of the
True entries ascending, 0 past the count. Each form here is one or two
jitted programs, timed alone at the shapes the engine runs (PERF.md section
5, `left_caps`): n = 4,194,304 (store_sales' capacity) with `out_cap` 65,536
(query7's second step), 131,072 (query96's) and 524,288 (query36's widest
stream); n = 2,097,152 with 32,768 (query7's filtered customer_demographics);
n = 524,288 with 65,536 and 1,024 (query1's and query36's later steps); and
as controls n = 4,194,304 with 2,097,152 (a dense mask) and n = 65,536 with
8,192 (a small one). The live count is three quarters of `out_cap`, drawn
over the first 69% of the rows as a fact table's live rows lie.

  a      `_compact_full`: prefix sum + one scatter of n updates, then a slice
         (the form the engine had up to PR 39)
  a1     the same scatter told `unique_indices`, every dead row sent to a
         dropped slot of its own
  b      block select, the block's live lanes first by the block's prefix sum
         compared against a lane iota; then per output slot the block by a
         max-scatter of n / block updates and a prefix max, and ONE gather of
         `out_cap` rows (`b128`, `b256`, `b512` by the block's lanes)
  bs     block select with the live lanes first by a sort along the minor axis
  bm     form b, the compare run 64 blocks at a time (`lax.map`)
  e      block select over bit words: the n-sized phase packs each block's
         mask into words of 32 bits and counts them; per output slot ONE
         gather of a block's words (`e32`: one word, so n / 32 block starts
         to scatter; `e128`: 4; `e512`: 16; `e2048`: 64) and the rank-th set
         bit by population counts
  c      two-level search: `searchsorted` over the block totals, then nine
         halvings inside the block over the flat prefix sum (gathers of
         `out_cap` rows only)
  d      `_compact_full_sorted` + slice (the mesh route), as a control
  k      `compact_indices` as the tree has it, rule and all

Every form is first held to form a's answer, entry for entry. A call's time
is the host clock around `calls` dispatches and one `block_until_ready`, over
`calls`: the device runs them back to back, so this is device time to the
dispatch of one program (of two, for the forms of two). `first_call_s` is
the form's first call with nothing compiled in the process
(`jax.clear_caches()` before it): trace, lower and compile of every program
it runs at that shape, or its load where the machine keeps a compile cache
on disk and an earlier shape or form compiled the same program. Fails off a
TPU: a CPU's number is no device number.
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

I32 = jnp.int32
LIVE_SPAN = 2_880_404 / 4_194_304
# (n, out_cap): where each comes from is in the docstring
SHAPES = (
    (4_194_304, 65_536), (4_194_304, 131_072), (4_194_304, 524_288),
    (2_097_152, 32_768), (524_288, 65_536), (524_288, 1_024),
    (4_194_304, 2_097_152), (65_536, 8_192),
)


@jax.jit
def full_unique(mask):
    n = mask.shape[0]
    iota = jnp.arange(n, dtype=I32)
    pos = jnp.where(mask, K.fast_cumsum(mask.astype(I32)) - 1, n + iota)
    return jnp.zeros(n, I32).at[pos].set(iota, mode="drop",
                                        unique_indices=True)


def _block_offsets(counts):
    incl = K.fast_cumsum(counts)
    return incl - counts, incl[-1]


@partial(jax.jit, static_argnames=("block",))
def blocks_iota(mask, block):
    """(rows, off, total): rows[b * block + r] the index of block b's r-th
    live row, off[b] the live rows before block b."""
    b = mask.shape[0] // block
    c = jnp.cumsum(mask.reshape(b, block).astype(I32), axis=1)
    lane = jnp.arange(block, dtype=I32)
    # lanes whose inclusive prefix sum is <= r lie before the r-th live row
    local = jnp.sum(c[:, :, None] <= lane[None, None, :], axis=1, dtype=I32)
    rows = local + (jnp.arange(b, dtype=I32) * block)[:, None]
    return (rows.reshape(-1),) + _block_offsets(c[:, -1])


@partial(jax.jit, static_argnames=("block",))
def blocks_sort(mask, block):
    b = mask.shape[0] // block
    m = mask.reshape(b, block)
    lane = jnp.arange(block, dtype=I32)
    local = jnp.sort(jnp.where(m, lane, lane + block), axis=1)
    rows = local + (jnp.arange(b, dtype=I32) * block)[:, None]
    return (rows.reshape(-1),) + _block_offsets(jnp.sum(m, axis=1, dtype=I32))


@partial(jax.jit, static_argnames=("block", "chunk"))
def blocks_iota_mapped(mask, block, chunk):
    """`blocks_iota` with the compare against the iota run `chunk` blocks
    at a time, so that no backend holds n x block compares at once."""
    b = mask.shape[0] // block
    c = jnp.cumsum(mask.reshape(b, block).astype(I32), axis=1)
    lane = jnp.arange(block, dtype=I32)
    local = jax.lax.map(
        lambda cc: jnp.sum(cc[:, :, None] <= lane, axis=1, dtype=I32),
        c.reshape(b // chunk, chunk, block),
    ).reshape(b, block)
    rows = local + (jnp.arange(b, dtype=I32) * block)[:, None]
    return (rows.reshape(-1),) + _block_offsets(c[:, -1])


WORD = 32


@partial(jax.jit, static_argnames=("block",))
def blocks_words(mask, block):
    """(words, off, total): block b's mask as block / 32 words of 32 bits,
    lane l at bit l % 32 of word l // 32."""
    b = mask.shape[0] // block
    bits = mask.reshape(b, block // WORD, WORD).astype(jnp.uint32)
    words = jnp.sum(
        bits << jnp.arange(WORD, dtype=jnp.uint32), axis=2, dtype=jnp.uint32
    )
    counts = jnp.sum(jax.lax.population_count(words), axis=1, dtype=I32)
    return (words,) + _block_offsets(counts)


def _slot_blocks(off, total, out_cap):
    """(block, rank, live) of each of the `out_cap` output slots: the block
    that starts last at or before the slot, and the slot less that start."""
    j = jnp.arange(out_cap, dtype=I32)
    first = jnp.zeros(out_cap, I32).at[off].max(
        jnp.arange(1, off.shape[0] + 1, dtype=I32), mode="drop"
    )
    blk = K.fast_cummax(first) - 1
    start = K.fast_cummax(jnp.where(first > 0, j, 0))
    live = j < total
    return jnp.where(live, blk, 0), j - start, live


@partial(jax.jit, static_argnames=("out_cap",))
def select_gather(rows, off, total, out_cap):
    """The `out_cap` phase over the blocks' local order: ONE gather."""
    block = rows.shape[0] // off.shape[0]
    blk, rank, live = _slot_blocks(off, total, out_cap)
    return jnp.where(live, rows[blk * block + rank], 0)


@partial(jax.jit, static_argnames=("out_cap", "block"))
def search_two_level(mask, out_cap, block):
    n = mask.shape[0]
    b = n // block
    c = K.fast_cumsum(mask.astype(I32))
    totals = c.reshape(b, block)[:, -1]
    j = jnp.arange(out_cap, dtype=I32)
    blk = jnp.minimum(
        jnp.searchsorted(totals, j, side="right").astype(I32), b - 1
    )
    base = blk * block
    lane = jnp.zeros(out_cap, I32)
    step = block // 2
    while step:
        # the first lane whose prefix sum passes j: skip `step` lanes while
        # the last of them has not
        lane = jnp.where(c[base + lane + step - 1] <= j, lane + step, lane)
        step //= 2
    return jnp.where(j < c[-1], base + lane, 0)


def block_select(blocks, select=None, **static):
    select = select or select_gather

    def form(mask, out_cap):
        return select(*blocks(mask, **static), out_cap)
    return form


def sliced(full):
    def form(mask, out_cap):
        return jax.lax.slice(full(mask), (0,), (out_cap,))
    return form


def forms(n):
    """name -> form(mask, out_cap) for a mask of n rows."""
    out = {"a": sliced(K._compact_full), "a1": sliced(full_unique)}
    for block in (128, 256, 512):
        if n >= 2 * block:
            out[f"b{block}"] = block_select(blocks_iota, block=block)
    if n >= 1_024:
        out["bs512"] = block_select(blocks_sort, block=512)
        out["bm512"] = block_select(
            blocks_iota_mapped, block=512, chunk=min(64, n // 512)
        )
        for block in (32, 128, 512, 2_048):
            if n >= 2 * block:
                # the tree's own `out_cap` phase: it reads the words a
                # block has off the table's shape
                out[f"e{block}"] = block_select(
                    blocks_words, K._select_rows, block=block
                )
        out["c"] = partial(search_two_level, block=512)
    if n in (4_194_304, 65_536):  # the sort compiles for seconds: twice
        out["d"] = sliced(K._compact_full_sorted)
    out["k"] = K.compact_indices
    return out


def answer(mask_h, out_cap):
    """What `compact_indices` answers, by numpy: form a is held to it and
    every other form to form a's array."""
    live = np.flatnonzero(mask_h)[:out_cap]
    return np.concatenate([live, np.zeros(out_cap - live.size, live.dtype)])


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


def draw_mask(rng, n, out_cap):
    live = min(out_cap * 3 // 4, int(n * LIVE_SPAN))
    mask = np.zeros(n, bool)
    mask[rng.choice(int(n * LIVE_SPAN), live, replace=False)] = True
    return mask


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=40)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}: no device number here")
    rng = np.random.default_rng(args.seed)
    lines = []
    for n, out_cap in SHAPES:
        mask_h = draw_mask(rng, n, out_cap)
        mask, want = jnp.asarray(mask_h), answer(mask_h, out_cap)
        for name, form in forms(n).items():
            jax.clear_caches()
            t0 = time.perf_counter()
            got = jax.block_until_ready(form(mask, out_cap))
            first = time.perf_counter() - t0
            assert np.array_equal(np.asarray(got), want), (name, n, out_cap)
            med, least = timed(
                lambda: form(mask, out_cap),
                10 if n > 1 << 20 else 40, args.reps,
            )
            lines.append({
                "form": name, "n": n, "out_cap": out_cap,
                "live": int(mask_h.sum()), "first_call_s": first,
                "ms": med, "ms_min": least,
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
