"""How `correct` is decided, at a size a test run can hold: the comparison
itself, the plain reference over seeded SF0.01 data, and the control: the
same reference with SUM and AVG accumulated in float32, put in the
program's place, has to come out as not correct under the configurations'
own limits."""

import os
import subprocess
import sys
from decimal import Decimal

import pyarrow as pa
import pytest

from benchmarks import compare, lib, reference

LIMITS = [lib.load_json(os.path.join(lib.REPO, c["file"]))["correct_limits"]
          for c in lib.load_json(os.path.join(lib.REPO, "BENCHMARK.json"))["configs"]]


@pytest.mark.parametrize("a,b,differs,gap", [
    (1, 1, False, 0.0), (1, 2, True, 0.0), ("x", "x", False, 0.0),
    ("x", "y", True, 0.0), (None, None, False, 0.0), (None, 0, True, 0.0),
    (0.0, None, True, 0.0), (1.0, 1.0, False, 0.0), (0.0, 0.0, False, 0.0),
    (float("nan"), float("nan"), False, 0.0), (float("nan"), 1.0, True, 0.0),
    (100.0, 100.001, False, 1e-5), (Decimal("12.34"), 12.34, False, 0.0),
    (Decimal("2.00"), 1.0, False, 0.5), (3, 3.0, False, 0.0),
    (float("inf"), 1.0, True, 0.0),
])
def test_cell_gap(a, b, differs, gap):
    got_differs, got_gap = compare.cell_gap(a, b)
    assert got_differs is differs
    assert got_gap == pytest.approx(gap, rel=1e-3)


def test_tables_compare_row_by_row_in_order():
    ref = pa.table({"k": ["a", "b"], "v": [1.0, 2.0]})
    same = compare.compare_tables(ref, pa.table({"x": ["a", "b"],
                                                 "y": [Decimal("1.00"), Decimal("2.00")]}))
    assert (same["cells_differ"], same["rel_gap_max"]) == (0, 0.0)
    swapped = compare.compare_tables(ref, pa.table({"k": ["b", "a"],
                                                    "v": [2.0, 1.0]}))
    assert swapped["cells_differ"] == 2 and swapped["rel_gap_max"] == 0.5
    short = compare.compare_tables(ref, ref.slice(0, 1))
    assert short["cells_differ"] == 1 and "row counts" in short["first"]
    narrow = compare.compare_tables(ref, ref.select(["k"]))
    assert narrow["cells_differ"] > 0


@pytest.mark.parametrize("limits", LIMITS)
def test_each_number_has_a_limit_of_its_own(limits):
    ok, numbers = compare.verdict(
        {"q": {"cells_differ": 0, "rel_gap_max": limits["rel_gap_max"] / 10}},
        limits)
    assert ok and set(numbers) == {"cells_differ", "rel_gap_max"}
    ok, _ = compare.verdict(
        {"q": {"cells_differ": 0, "rel_gap_max": limits["rel_gap_max"] * 10}},
        limits)
    assert not ok
    ok, _ = compare.verdict(
        {"q": {"cells_differ": 1, "rel_gap_max": 0.0}}, limits)
    assert not ok
    # one float32 rounding of one value is already beyond the limit
    assert limits["rel_gap_max"] < 2.0 ** -25


def test_dialect_lowering():
    s = reference.to_sqlite(
        "select a, sum(x) from t where d between cast('2000-01-01' as date) "
        "and (cast('2000-01-01' as date) + interval 30 days) "
        "group by rollup(a, b)")
    assert "rollup" not in s.lower() and "union all" in s
    assert "date(date('2000-01-01'), '+30 days')" in s
    assert "cast(x as real)" in reference.to_sqlite(
        "select cast(x as decimal(15,4)) from t")


@pytest.fixture(scope="module")
def raw(tmp_path_factory):
    out = tmp_path_factory.mktemp("raw")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    subprocess.run(
        [sys.executable, "-m", "nds_tpu.cli.gen_data", "local", "--scale",
         "0.01", "--parallel", "2", "--seed", "2147483659", "--data_dir",
         str(out), "--overwrite_output"],
        check=True, capture_output=True, cwd=lib.REPO, env=env, timeout=300)
    return str(out)


@pytest.fixture(scope="module")
def answers(raw, tmp_path_factory):
    traffic = lib.load_json(os.path.join(lib.HERE, "traffic", "replay6.json"))
    stream0 = lib.make_streams(traffic, 0.01, 0, 1)[0]
    statements = {f"s0/{name}": sql for name, sql in stream0
                  if name in traffic["control_templates"]}
    dirs = {}
    for control in (None, "float32"):
        out = tmp_path_factory.mktemp(f"ref_{control}")
        info = reference.run(raw, statements, str(out), control)
        dirs[control] = (str(out), info)
    return statements, dirs


def test_the_reference_answers_with_rows(answers):
    statements, dirs = answers
    _, info = dirs[None]
    assert set(info["rows"]) == set(statements)
    assert info["tables"]["store_sales"] > 10000
    assert sum(info["rows"].values()) > 0, "empty answers check nothing"


@pytest.mark.parametrize("limits", LIMITS)
def test_the_float32_control_comes_out_as_not_correct(answers, limits):
    statements, dirs = answers
    per = compare.compare_answers(dirs[None][0], dirs["float32"][0],
                                  list(statements))
    ok, numbers = compare.verdict(per, limits)
    assert not ok, numbers
    # it fails on the gap, not on a differing row: same rows, same order
    assert numbers["cells_differ"]["value"] == 0
    assert numbers["rel_gap_max"]["value"] > 3 * limits["rel_gap_max"]
    # and the sound reference against itself passes with nothing to spare
    per = compare.compare_answers(dirs[None][0], dirs[None][0],
                                  list(statements))
    ok, numbers = compare.verdict(per, limits)
    assert ok and numbers["rel_gap_max"]["value"] == 0.0
