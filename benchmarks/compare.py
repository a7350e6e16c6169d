"""The comparison that decides `correct`: the timed path's answers against
the plain reference's, row by row in the order both were asked to produce.

A copy of `nds_tpu/validate.py`'s comparison (`compare`, `row_equal`,
`compare_results`; PERF.md lists the original), kept here so that no later
PR can move it, and changed in one way: instead of a yes or no at a fixed
epsilon it returns the two numbers compared, each held to a limit of its
own by the caller:

    cells_differ   rows, strings, integers, NULLs or dates that are not
                   equal, and a row count that differs counts every row
                   of the longer answer. Limit 0: an exact comparison.
    rel_gap_max    the widest |a - b| / max(|a|, |b|) over the cells where
                   either side is a float or a decimal. The engine computes
                   in exact decimals and sqlite in float64, so sound answers
                   differ by float64 rounding and by the engine's rounding
                   of a decimal quotient to its result scale, never more.
"""

from __future__ import annotations

import math
import os
from decimal import Decimal

import pyarrow.dataset as pads


def load_output(path):
    """One statement's written answer (`<dir>/part-0.parquet`)."""
    return pads.dataset(path, format="parquet").to_table()


def cell_gap(expected, actual):
    """(differs, relative gap) of one cell. NaN equals NaN; NULL equals
    only NULL; integers, strings and dates compare exactly; a float or a
    decimal on either side compares by relative gap."""
    if expected is None or actual is None:
        return not (expected is None and actual is None), 0.0
    inexact = (float, Decimal)
    numeric = (int, float, Decimal)
    if isinstance(expected, bool) or isinstance(actual, bool):
        return expected != actual, 0.0
    if isinstance(expected, numeric) and isinstance(actual, numeric) and (
        isinstance(expected, inexact) or isinstance(actual, inexact)
    ):
        a, b = float(expected), float(actual)
        if math.isnan(a) or math.isnan(b):
            return not (math.isnan(a) and math.isnan(b)), 0.0
        scale = max(abs(a), abs(b))
        if scale == 0 or a == b:
            return False, 0.0
        if math.isinf(scale):
            return True, 0.0
        return False, abs(a - b) / scale
    return expected != actual, 0.0


def compare_tables(expected, actual):
    """{"rows": [n_expected, n_actual], "cells_differ", "rel_gap_max",
    "first": a description of the first differing cell or widest gap}."""
    out = {"rows": [expected.num_rows, actual.num_rows], "cells_differ": 0,
           "rel_gap_max": 0.0, "first": None}
    if expected.num_columns != actual.num_columns:
        out["cells_differ"] = max(expected.num_rows, actual.num_rows, 1)
        out["first"] = (f"{expected.num_columns} columns against "
                        f"{actual.num_columns}")
        return out
    n = min(expected.num_rows, actual.num_rows)
    out["cells_differ"] = abs(expected.num_rows - actual.num_rows)
    if out["cells_differ"]:
        out["first"] = f"row counts {out['rows']}"
    for ci in range(expected.num_columns):
        left = expected.column(ci).to_pylist()
        right = actual.column(ci).to_pylist()
        for ri in range(n):
            differs, gap = cell_gap(left[ri], right[ri])
            if differs:
                out["cells_differ"] += 1
                if out["first"] is None:
                    out["first"] = (f"row {ri} column {ci}: "
                                    f"{left[ri]!r} against {right[ri]!r}")
            elif gap > out["rel_gap_max"]:
                out["rel_gap_max"] = gap
                out["widest"] = (f"row {ri} column {ci}: "
                                 f"{left[ri]!r} against {right[ri]!r}")
    return out


def compare_answers(reference_dir, answers_dir, keys):
    """Every statement of `keys`: the reference's answer against the timed
    path's. A missing answer differs in full."""
    per = {}
    for key in keys:
        ref = os.path.join(reference_dir, key)
        got = os.path.join(answers_dir, key)
        if not os.path.isdir(ref):
            raise FileNotFoundError(f"the reference has no answer for {key}")
        if not os.path.isdir(got):
            per[key] = {"rows": [load_output(ref).num_rows, None],
                        "cells_differ": 1, "rel_gap_max": 0.0,
                        "first": "no answer written"}
            continue
        per[key] = compare_tables(load_output(ref), load_output(got))
    return per


def verdict(per, limits):
    """The numbers compared, each beside its limit, and whether all hold."""
    cells = sum(p["cells_differ"] for p in per.values())
    gap = max((p["rel_gap_max"] for p in per.values()), default=0.0)
    numbers = {
        "cells_differ": {"value": cells, "limit": limits["cells_differ"]},
        "rel_gap_max": {"value": gap, "limit": limits["rel_gap_max"]},
    }
    ok = all(n["value"] <= n["limit"] for n in numbers.values())
    return ok, numbers
