"""One cell, driven end to end on the CPU at SF0.01 in a temporary cache.

* As the driver runs it, the run completes every phase and then FAILS on
  the look for a chip: no result line, exit code not 0. A benchmark that
  reports from a CPU proves nothing about a chip.
* With only that look skipped (the device, each statement's backend and the
  source of its memory reading rewritten as a chip would report them), the
  rest of the run is the real one: its last line has exactly the contract's
  keys and `correct` is true.
* The same, with the timed path broken underneath: one answer of the window
  altered where it is produced, by one part in a million. `correct` comes
  out false.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import lib

CELL = "sf1-parquet.replay6"
ARGS = ["--workload", CELL, "--seed", "2147483659", "--seconds", "3",
        "--trace", "0", "--scale", "0.01"]

#: run.py with the look for a chip skipped and, if BREAK is set, one answer
#: of the window altered before the comparison reads it
DRIVER = r'''
import os, sys
sys.path.insert(0, {repo!r})
from benchmarks import lib, run

run.check_device = lambda device, cell: {{}}
real = run.load_child

def as_a_chip_would_report(run_dir):
    child = real(run_dir)
    child["memory"]["peak_bytes_in_use"] = 1 << 30
    for s in child["first_pass"]["statements"].values():
        s["backend"], s["mem_source"] = "tpu", "device"
    if os.environ.get("BREAK"):
        import pyarrow as pa, pyarrow.parquet as pq
        path = os.path.join(run_dir, "answers", f"s{{child['compared_stream']}}",
                            "query7", "part-0.parquet")
        t = pq.read_table(path)
        col = [None if v is None else float(v)
               for v in t.column(1).to_pylist()]
        col[0] *= 1 + 1e-6
        pq.write_table(t.set_column(1, t.field(1).name, pa.array(col)), path)
    return child

run.load_child = as_a_chip_would_report
sys.exit(run.main(sys.argv[1:]))
'''


def _run(cmd, cache, env_extra=None):
    """A run of the benchmark in a child, in a temporary cache. The harness
    keeps the compile caches, and with them the engine's AOT executables
    and cardinality feedback, under that cache too: a run leaves nothing in
    the checkout's `.nds_cache/` for the tests beside it to find."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(
        cmd + ["--cache_dir", cache],
        env={**env, "JAX_PLATFORMS": "cpu", **(env_extra or {})},
        cwd=lib.REPO, capture_output=True, text=True, timeout=1200)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench_cache"))


@pytest.fixture(scope="module")
def driver(tmp_path_factory):
    path = tmp_path_factory.mktemp("driver") / "driver.py"
    path.write_text(DRIVER.format(repo=lib.REPO))
    return str(path)


def test_a_cpu_run_ends_in_the_platform_failure_not_a_result(cache):
    p = _run([sys.executable, os.path.join(lib.REPO, "benchmarks", "run.py"),
              *ARGS], cache)
    out = p.stdout.strip().splitlines()
    assert p.returncode != 0, p.stdout[-3000:]
    assert out[-1].startswith("benchmark: FAILED: the cell needs 1 TPU"), out[-1]
    assert not any(line.startswith('{"correct"') for line in out)
    for phase in ("gen_data", "load", "reference_sound", "warm", "chip"):
        assert any(line.startswith(f"phase {phase}: ") and line.endswith("rc=0")
                   for line in out), (phase, p.stdout[-3000:])
    assert any(line.startswith("child: rehearsal ") for line in out)
    assert any(line.startswith("child: window ") for line in out)


def test_the_rest_of_a_run_reports_the_contracts_line(cache, driver):
    cached = os.path.isdir(os.path.join(cache, "data"))
    p = _run([sys.executable, driver, *ARGS], cache)
    out = p.stdout.strip().splitlines()
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    if cached:  # the test above ran in this process and left the seed's data
        assert not any(line.startswith(("phase gen_data", "phase load",
                                        "phase reference")) for line in out)
    line = json.loads(out[-1])
    assert tuple(line) == lib.RESULT_KEYS
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 6
    spec = lib.Spec(lib.REPO)
    want = {m["name"]: m["unit"]
            for m in spec.metrics_of(spec.cell(CELL), "end_to_end")}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # every number compared is printed beside its limit, in every run
    assert sum(line.startswith("compared ") and " limit " in line
               for line in out) == 2
    # twelve answers were compared: the first pass's six and the six of the
    # window's first pass
    assert sum(line.startswith("answer s") for line in out) == 12


def test_an_altered_answer_comes_out_as_not_correct(cache, driver):
    p = _run([sys.executable, driver, *ARGS], cache, {"BREAK": "1"})
    out = p.stdout.strip().splitlines()
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    line = json.loads(out[-1])
    assert line["correct"] is False
    assert "FAULT: answers differ from the reference's" in out
    assert line["failed"] == 0, "the statements ran; only the answer is wrong"


def test_without_the_program_there_is_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's own
    directories there is nothing to measure: exit code not 0, no line."""
    import shutil

    shutil.copy(os.path.join(lib.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(lib.REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", *ARGS], cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip().splitlines()[-1].startswith("benchmark: FAILED")
    assert '"correct"' not in p.stdout
    assert not (tmp_path / "benchmarks" / ".cache" / "data").exists()
