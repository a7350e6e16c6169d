"""The proof that a later PR can add to the benchmark and edit nothing: the
tests of this directory, run over the real tree grown by `grow.grow`.

* A copy of what the tests read (`BENCHMARK.json`, `benchmarks/`,
  `tests/conftest.py`, `tests/benchmark/`; the program linked beside them)
  is grown by the one recipe, and `python -m pytest tests/benchmark` runs
  there, in one process, without the rehearsals: green, and with no fewer
  passes than the real tree collects for the same selection. A pin written
  into any test of the directory (a count, a list of cells, the whole of
  `per_layer`) fails here, in the PR that writes it.
* The same over the copy with one forbidden edit, an entry inserted among
  the first twenty: red, for that reason. The proof bites.
* A rehearsal (a test that starts `run.py`) is outside that run, so the
  sources are read for its kind of pin: every test that starts `run.py`
  carries the marker, and no test compares a list that growth extends
  (`per_layer`, `workloads`, `configs`, `end_to_end` of the document, a
  metric's `workloads`) by `==` with anything not taken from such a list.

To run the proof alone:
`python -m pytest tests/benchmark/test_benchmark_grown_tree.py`."""

import ast
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import grow
from benchmarks import lib

HERE = os.path.dirname(os.path.abspath(__file__))
THIS = os.path.basename(__file__)
SELECT = "not slow and not rehearsal"
#: the lists of the document, and of a metric, that a later PR extends
GROWS = ("per_layer", "workloads", "configs", "end_to_end")


def _pytest(root, *more, timeout=900):
    """The directory's tests over the tree at `root`, this file left out;
    at a low priority, because the tier-1 run's other workers time windows
    of a few seconds beside it (the rehearsals)."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    nice = ["nice", "-n", "15"] if shutil.which("nice") else []
    return subprocess.run(
        [*nice, sys.executable, "-m", "pytest", "tests/benchmark", "-q", "-rfE",
         "-m", SELECT, "-p", "no:xdist", "-p", "no:cacheprovider",
         "--rootdir", root, f"--ignore=tests/benchmark/{THIS}", *more],
        cwd=root, capture_output=True, text=True, timeout=timeout,
        env={**env, "JAX_PLATFORMS": "cpu", "COLUMNS": "200"})


def _summary(p):
    out = p.stdout[-6000:] + p.stderr[-2000:]
    return out[out.find("short test summary info"):] if \
        "short test summary info" in out else out


@pytest.fixture()
def tree(tmp_path):
    """The real tree, copied where the tests read it and linked where they
    only run it, and grown."""
    root = tmp_path / "tree"
    junk = shutil.ignore_patterns(".cache", "__pycache__")
    shutil.copytree(os.path.join(lib.REPO, "benchmarks"), root / "benchmarks",
                    ignore=junk)
    shutil.copytree(HERE, root / "tests" / "benchmark", ignore=junk)
    shutil.copy(os.path.join(lib.REPO, "tests", "conftest.py"), root / "tests")
    shutil.copy(os.path.join(lib.REPO, "BENCHMARK.json"), root)
    for name in os.listdir(lib.REPO):
        if not name.startswith(".") and not (root / name).exists() \
                and name != "chiprun_out":
            os.symlink(os.path.join(lib.REPO, name), root / name)
    new = grow.grow(str(root))
    return str(root), new


def test_the_directorys_tests_pass_over_the_real_tree_grown(tree):
    root, _ = tree
    real = _pytest(lib.REPO, "--collect-only")
    collected = re.search(r"(\d+)(?:/\d+)? tests collected", real.stdout)
    assert real.returncode == 0 and collected, _summary(real)
    p = _pytest(root)
    assert p.returncode == 0, _summary(p)
    tally = p.stdout.strip().splitlines()[-1]
    passed = int(re.search(r"(\d+) passed", tally).group(1))
    assert "skipped" not in tally and "deselected" in tally, tally
    # nothing was quietly skipped or lost: the grown tree has more cells,
    # entries and configurations, so more cases, never fewer
    assert passed > int(collected.group(1)), (tally, collected.group(0))


def test_an_entry_inserted_among_the_first_twenty_turns_them_red(tree):
    root, new = tree
    doc = new.doc
    doc["per_layer"].insert(19, doc["per_layer"].pop())
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    p = _pytest(root)
    assert p.returncode == 1, _summary(p)
    assert "test_the_new_metrics_are_appended_entries[19]" in _summary(p)
    assert "test_the_first_twenty_are_all_there" in _summary(p)
    assert f"per_layer[19] is '{new.per_layer}'" in p.stdout
    assert "append after the last entry, insert and move nothing" in p.stdout


# -- the sources, read for what the run over the grown tree cannot see -------

def _sources():
    for name in sorted(os.listdir(HERE)):
        if name.endswith(".py"):
            with open(os.path.join(HERE, name)) as f:
                yield name, ast.parse(f.read())


def _whole(node, taken):
    """Is `node` the whole of a list that growth extends, or made of one:
    `x["per_layer"]`, a name assigned from one, `len`/`sorted`/`set` of
    one, a comprehension over all of one, a sum with one. An element, a
    slice or a filtered part of one is not."""
    if isinstance(node, ast.Subscript):
        key = node.slice
        if isinstance(node.value, ast.Name) and node.value.id in taken:
            return False  # an element or a slice of a list taken whole
        if isinstance(key, ast.Constant):
            return key.value in GROWS
        # `DOC[group]`: a list of the document named by a variable
        base = node.value
        return isinstance(key, ast.Name) and (
            (isinstance(base, ast.Name) and base.id.lower() == "doc")
            or (isinstance(base, ast.Attribute) and base.attr == "doc"))
    if isinstance(node, ast.Name):
        return node.id in taken
    if isinstance(node, ast.Call):
        return any(_whole(a, taken) for a in node.args)
    if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                         ast.DictComp)):
        first = node.generators[0]
        return not first.ifs and _whole(first.iter, taken)
    if isinstance(node, ast.BinOp):
        return _whole(node.left, taken) or _whole(node.right, taken)
    return False


def _own(scope):
    """The nodes of one scope: nested functions are named, not entered."""
    todo = list(ast.iter_child_nodes(scope))
    while todo:
        node = todo.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.Lambda)):
            todo += ast.iter_child_nodes(node)


def pins_of(scope, taken=()):
    """`(line, source)` of every `==` or `!=` under `scope` with the whole
    of a list that growth extends on one side and something written on the
    other. A name assigned from such a list counts as the list, in the
    function that assigns it and in those nested in it."""
    taken, before = set(taken), None
    while taken != before:  # `a = doc[...]; b = sorted(a)`, in any order
        before = set(taken)
        for node in _own(scope):
            if isinstance(node, ast.Assign) and _whole(node.value, taken):
                taken |= {t.id for t in node.targets
                          if isinstance(t, ast.Name)}
    found = set()
    for node in _own(scope):
        if isinstance(node, (ast.FunctionDef, ast.Lambda)):
            found |= set(pins_of(node, taken))
        elif isinstance(node, ast.Compare) and any(
                isinstance(op, (ast.Eq, ast.NotEq)) for op in node.ops):
            sides = [_whole(s, taken) for s in (node.left, *node.comparators)]
            if any(sides) and not all(sides):
                found.add((node.lineno, ast.unparse(node)))
    return sorted(found)


@pytest.mark.parametrize("source, pins", [
    ('assert len(doc["per_layer"]) == 25', 1),
    ('n = len(real["per_layer"])\nassert len(doc["per_layer"]) == n + 1', 0),
    ('def t(spec):\n    names = sorted(m["name"] for m in spec.doc["per_layer"])'
     '\n    assert sorted(line) == names', 1),
    ('assert sorted(line) == sorted(\n'
     '    m["name"] for m in spec.metrics_of(cell, "per_layer"))', 0),
    ('assert entry["workloads"] == ["a", "b"]', 1),
    ('cells = by_name[name]["workloads"]\nassert cells[:2] != [A, B]', 0),
    ('got = {c["name"]: read(c) for c in DOC["workloads"]}\n'
     'assert got == {"a": 1}\nassert got["a"] == 1', 1),
    ('for group in GROUPS:\n    names = [e["name"] for e in DOC[group]]\n'
     '    assert len(names) == len(set(names))', 0),
    ('assert {w["config"] for w in DOC["workloads"]} == '
     '{c["name"] for c in DOC["configs"]}', 0),
    ('one, = [m for m in doc["per_layer"] if m["name"] == "x"]\n'
     'assert one["unit"] == "ms"', 0),
], ids=["a_count", "a_count_from_the_document", "the_whole_of_per_layer",
        "metrics_of", "a_metrics_cells", "the_first_two_cells",
        "keyed_by_every_cell", "unique_names", "two_lists_of_the_document",
        "one_entry"])
def test_the_reading_of_the_sources_finds_a_pin_and_no_lawful_use(source, pins):
    assert len(pins_of(ast.parse(source))) == pins, pins_of(ast.parse(source))


def test_no_test_compares_a_list_that_growth_extends_with_a_written_one():
    """No count of entries, cells or configurations of the real document is
    written into a test, and none compares the whole of one of its lists
    with a literal: `benchmarks/README.md`, "Adding to it". There is no
    lawful use to name: a rule about the first twenty slices them."""
    found = {name: pins_of(tree) for name, tree in _sources()}
    assert {k: v for k, v in found.items() if v} == {}


def _body(func):
    """A test's statements as source, its docstring left out."""
    return "\n".join(ast.unparse(n) for n in func.body[
        1 if ast.get_docstring(func) else 0:])


def _starts_run_py(func):
    """A child of this interpreter over `run.py`, or over the driver script
    that wraps it with only the look for a chip skipped."""
    text = _body(func)
    return "sys.executable" in text and (
        "run.py" in text or "driver" in text)


def test_every_test_that_starts_run_py_is_a_marked_rehearsal():
    """The run over the grown tree leaves out the tests that start `run.py`
    by their marker, so each carries it; and one that reads a result line's
    metrics compares them with `spec.metrics_of(cell, ...)`, the cell's own
    list, which no other cell's entries lengthen."""
    rehearsals = []
    for name, tree in _sources():
        for func in ast.walk(tree):
            if not (isinstance(func, ast.FunctionDef)
                    and func.name.startswith("test_")):
                continue
            marked = any("mark.rehearsal" in ast.unparse(d)
                         for d in func.decorator_list)
            assert marked == _starts_run_py(func), (name, func.name)
            if marked:
                rehearsals.append(func)
    assert rehearsals, "the grep finds the rehearsals"
    for func in rehearsals:
        text = _body(func)
        if re.search(r"""line\[["']metrics["']\]""", text):
            assert "metrics_of(" in text, func.name
