"""`tools/segment_reduce_microbench.py`, step 0 of PR 44, off the chip: the
forms it times are the ones its docstring names, every run form answers
what the scatter answers (the tool holds them to it before it hands them
out), and it gives no number without a TPU. What the forms cost is a chip
run's to say (PERF.md)."""

import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "segment_reduce_microbench.py")
FORMS = ("one", "whole", "whole_with_count", "sorted", "runs",
         "runs_with_count", "random", "count_one", "count_whole",
         "count_sorted", "count_runs", "starts_scatter", "starts_compaction")


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("segment_reduce_mb", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cases(tool):
    return list(tool.cases(65_536, np.random.default_rng(3)))


def test_the_forms_are_the_ones_the_docstring_names(tool, cases):
    assert tuple(dict.fromkeys(c[0] for c in cases)) == FORMS
    for name in ("one", "sorted", "random", "whole", "runs", "count_*",
                 "starts"):
        assert f"\n  {name} " in tool.__doc__
    for n in tool.SIZES + tool.RUNS + tool.CELLS:
        assert f"{n:,}" in tool.__doc__
    # 2 dtypes x (3 + 3 forms x 2 run counts + 3 cell counts) + 2 + 4 x 2
    assert len(cases) == 2 * (3 + 6 + 3) + 2 + 8


@pytest.mark.parametrize("form", FORMS)
def test_a_form_runs_at_a_small_size(cases, form):
    ran = 0
    for name, _, cells, fn, calls in cases:
        if name == form:
            out = jax.block_until_ready(fn())
            first = out[0] if isinstance(out, tuple) else out
            assert first.shape[0] in (cells, 1_024) and calls >= 2
            ran += 1
    assert ran


def test_it_gives_no_number_off_a_tpu():
    p = subprocess.run(
        [sys.executable, TOOL], capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU, found cpu: no device number here" in p.stderr
