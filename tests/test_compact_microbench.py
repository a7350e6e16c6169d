"""`tools/compact_microbench.py`, step 0 of PR 40, off the chip: every form
it times answers what `compact_indices` answers, and it gives no number
without a TPU. What the forms cost is a chip run's to say (PERF.md)."""

import importlib.util
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "compact_microbench.py")
FORMS = ("a", "a1", "b128", "b256", "b512", "bs512", "bm512", "e32", "e128",
         "e512", "e2048", "c", "d", "k")


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("compact_microbench", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_forms_are_the_ones_the_docstring_names(tool):
    assert tuple(tool.forms(65_536)) == FORMS
    for name in ("a ", "a1", "b ", "bs", "bm", "e ", "c ", "d ", "k "):
        assert f"\n  {name}" in tool.__doc__
    for n, out_cap in tool.SHAPES:
        assert f"{n:,}" in tool.__doc__ and f"{out_cap:,}" in tool.__doc__


@pytest.mark.parametrize("mask_kind", ["drawn", "empty", "last row", "dense"])
@pytest.mark.parametrize("name", FORMS)
def test_a_form_answers_what_compact_indices_answers(tool, name, mask_kind):
    n, out_cap = 65_536, 8_192
    if mask_kind == "drawn":
        mask = tool.draw_mask(np.random.default_rng(5), n, out_cap)
    else:
        mask = np.zeros(n, bool)
        mask[-1] = mask_kind == "last row"
        if mask_kind == "dense":  # more live rows than slots: the first stay
            mask[::3] = True
    want = tool.answer(mask, out_cap)
    got = np.asarray(tool.forms(n)[name](jnp.asarray(mask), out_cap))
    np.testing.assert_array_equal(got, want)


def test_it_gives_no_number_off_a_tpu():
    p = subprocess.run(
        [sys.executable, TOOL], capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU, found cpu: no device number here" in p.stderr
