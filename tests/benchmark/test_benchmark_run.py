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
* `run()`'s order, with children that are not started at all: no child that
  owns the chip is spawned while a reference child lives.
* What but the answers makes a run not `correct`, in either of the two
  children whose first pass is timed.
"""

import argparse
import copy
import json
import os
import subprocess
import sys

import pytest

from benchmarks import lib
from benchmarks import run as bench_run

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
    for s in child["first_pass"]["statements"].values():
        s["backend"], s["mem_source"] = "tpu", "device"
    if "memory" not in child:  # the pass-only child
        return child
    child["memory"]["peak_bytes_in_use"] = 1 << 30
    # a CPU has no device plane for the reducer to read
    child.setdefault("device_trace", {{
        "busy_s": 1.5, "window_s": 2.0, "statements": 12,
        "unannotated_s": 0.0, "device_ops": [["fusion", 1.0]],
        "idle_gaps": [["execute", 0.5]]}})
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


@pytest.mark.rehearsal
def test_a_cpu_run_ends_in_the_platform_failure_not_a_result(cache):
    p = _run([sys.executable, os.path.join(lib.REPO, "benchmarks", "run.py"),
              *ARGS], cache)
    out = p.stdout.strip().splitlines()
    assert p.returncode != 0, p.stdout[-3000:]
    assert out[-1].startswith("benchmark: FAILED: the cell needs 1 TPU"), out[-1]
    assert not any(line.startswith('{"correct"') for line in out)
    phases = [line.split(":")[0][len("phase "):] for line in out
              if line.startswith("phase ") and line.endswith("rc=0")]
    # the reference has exited before the first child that owns the chip
    # starts; the pass-only child runs before the measured one
    refs = [f"reference_sound_query{n}" for n in (1, 3, 36, 7, 93, 96)]
    assert phases == ["gen_data", "load", *refs, "warm", "pass",
                      "chip"], p.stdout[-3000:]
    assert any(line.startswith("pass child: first pass ") for line in out)
    assert not any(line.startswith("pass child: rehearsal ") for line in out)
    assert any(line.startswith("chip child: rehearsal ") for line in out)
    assert any(line.startswith("chip child: window ") for line in out)


@pytest.mark.rehearsal
def test_the_rest_of_a_run_reports_the_contracts_line(cache, driver):
    cached = os.path.isdir(os.path.join(cache, "data"))
    p = _run([sys.executable, driver, *ARGS], cache)
    out = p.stdout.strip().splitlines()
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    if cached:  # the test above ran in this process and left the database
        assert not any(line.startswith(("phase gen_data", "phase load",
                                        "phase reference")) for line in out)
    line = json.loads(out[-1])
    assert tuple(line) == lib.RESULT_KEYS + ("first_passes_s", "compared")
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 12
    # both readings are on record, and the metric is the lower
    a, b = line["first_passes_s"]
    assert line["metrics"]["first_pass_s"]["value"] == min(a, b) > 0
    assert sum(line.startswith("first pass a (pass-only child): ")
               or line.startswith("first pass b (measured child): ")
               for line in out) == 2
    # the numbers compared, each beside its limit: last in the line and the
    # last lines of standard error
    assert line["compared"] == {
        "cells_differ": {"value": 0, "limit": 0},
        "rel_gap_max": {"value": line["compared"]["rel_gap_max"]["value"],
                        "limit": 1e-09}}
    err = p.stderr.strip().splitlines()[-2:]
    assert err[0] == "compared cells_differ: 0 limit 0", p.stderr[-500:]
    assert err[1].startswith("compared rel_gap_max: ")
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
    # eighteen answers were compared: each first pass's six and the six of
    # the window's first pass
    assert sum(line.startswith("answer s") for line in out) == 12
    assert sum(line.startswith("answer pass/s0/") for line in out) == 6


@pytest.mark.rehearsal
def test_the_rest_of_a_traced_run_reports_the_per_layer_metrics(cache, driver):
    """`--trace 1`: the program's spans are kept, the per-layer readers run
    over them, and the line carries the device's busy seconds and the
    breakdown. The measured child's first pass is the one the `.first`
    metrics read; the pass-only child runs untraced."""
    # cycle 0: the first twelve statements of the window (cycle 1 needs 48
    # of them), in ten seconds: a window that ends inside the slice is an
    # error, and on a loaded host one statement that loads a program from
    # disk has taken three seconds
    args = [a if a != "0" else "1" for a in ARGS] + ["--trace_cycle", "0"]
    assert args[args.index("--trace") + 1] == "1"
    args[args.index("--seconds") + 1] = "10"
    p = _run([sys.executable, driver, *args], cache)
    out = p.stdout.strip().splitlines()
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    line = json.loads(out[-1])
    assert tuple(line) == lib.RESULT_KEYS + (
        "breakdown", "first_passes_s", "slice", "compared")
    assert line["correct"] is True, p.stdout[-3000:]
    # what was traced: the first two passes of the cycle the command named
    # (the mix's own `trace_cycle` leaves two whole cycles before it), in the
    # order the seed gives that cycle
    traffic = lib.Spec(lib.REPO).traffic(lib.Spec(lib.REPO).cell(CELL))
    assert traffic["trace_cycle"] == 2 and traffic["trace_passes"] == 2
    wanted = lib.slice_statements(
        traffic, lib.make_streams(traffic, 0.01, 0, 7), 2147483659, 0, 2)
    assert len(wanted) == 12
    assert line["slice"] == {"cycle": 0, "passes": 2, "statements": wanted}
    assert any(said.startswith(
        "traced slice: cycle 0, 2 passes, 12 statements") for said in out)
    assert line["device"]["busy_s"] == 1.5 and line["device"]["window_s"] == 2.0
    spec = lib.Spec(lib.REPO)
    want = {m["name"] for m in spec.metrics_of(spec.cell(CELL), "per_layer")}
    assert set(line["metrics"]) <= want
    # what the first pass compiled anew, as a count and as seconds
    for name in ("fresh_compiles.first", "fresh_compile_s.first",
                 "xla_load_s.first", "fresh_compiles.rehearsal",
                 "launches.stmt", "compiles.window"):
        assert name in line["metrics"], (name, sorted(line["metrics"]))
    assert line["metrics"]["fresh_compiles.first"]["value"] >= 0
    assert line["metrics"]["fresh_compile_s.first"]["value"] <= \
        line["metrics"]["xla_load_s.first"]["value"]
    run_dir = os.path.join(cache, "runs", f"{CELL}-2147483659-t1")
    assert os.path.isdir(os.path.join(run_dir, "trace"))
    assert not os.path.exists(os.path.join(run_dir, "pass", "trace"))


@pytest.mark.rehearsal
def test_an_altered_answer_comes_out_as_not_correct(cache, driver):
    p = _run([sys.executable, driver, *ARGS], cache, {"BREAK": "1"})
    out = p.stdout.strip().splitlines()
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    line = json.loads(out[-1])
    assert line["correct"] is False
    assert "FAULT: answers differ from the reference's" in out
    assert line["failed"] == 0, "the statements ran; only the answer is wrong"


@pytest.mark.rehearsal
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


# -- run()'s order, and the judgement, with no child started ------------------

def _args(cache):
    return argparse.Namespace(workload=CELL, seed=2147483659, seconds=3.0,
                              trace=0, scale=0.01, control=None,
                              trace_cycle=None, data_seed=None,
                              cache_dir=str(cache))


class Recorded(bench_run.Run):
    """A Run whose children are never started: `spawn` and `wait` record
    what was asked of them and leave behind what the real child would."""

    def __init__(self, args):
        super().__init__(args)
        self.order = []
        self.alive = set()
        self.beside_a_chip_child = []

    def spawn(self, name, cmd, env=None):
        if name in ("warm", "pass", "chip"):
            self.beside_a_chip_child += sorted(self.alive)
        self.order.append(f"spawn {name}")
        self.alive.add(name)
        child = argparse.Namespace(name=name, cmd=[str(c) for c in cmd])
        child.log = argparse.Namespace(
            name=os.path.join(self.logs, f"{name}.log"))
        with open(child.log.name, "w") as f:
            f.write("child: done\n")
        return child

    def wait(self, child):
        self.order.append(f"wait {child.name}")
        self.alive.discard(child.name)
        cmd = child.cmd
        if child.name == "gen_data":
            os.makedirs(cmd[cmd.index("--data_dir") + 1])
        elif child.name == "load":
            os.makedirs(cmd[4])
        elif child.name in ("warm", "pass", "chip"):
            with open(os.path.join(cmd[cmd.index("--run_dir") + 1],
                                   "child.json"), "w") as f:
                json.dump({"who": child.name,
                           "pass_only": "--pass_only" in cmd}, f)

    def stay_off_jax(self):
        """The test's own process has jax; it starts no child."""

    def judge(self, child, pass_only, ref, keys):
        self.judged = (child, pass_only)
        return 0


def test_no_chip_child_is_spawned_while_a_reference_child_lives(tmp_path):
    run = Recorded(_args(tmp_path))
    assert run.run() == 0
    refs = [f"reference_sound_query{n}" for n in (1, 3, 36, 7, 93, 96)]
    assert run.order == [
        "spawn gen_data", "wait gen_data", *(f"spawn {r}" for r in refs),
        "spawn load", "wait load", *(f"wait {r}" for r in refs),
        "spawn warm", "wait warm", "spawn pass", "wait pass",
        "spawn chip", "wait chip"]
    assert run.beside_a_chip_child == []
    assert run.judged == ({"who": "chip", "pass_only": False},
                          {"who": "pass", "pass_only": True})
    # a checkout's second run finds data, warehouse, answers and warm caches:
    # the two timed children and nothing else
    again = Recorded(_args(tmp_path))
    assert again.run() == 0
    assert again.order == ["spawn pass", "wait pass", "spawn chip",
                           "wait chip"]


@pytest.mark.parametrize("seed", [1, 12345, 2147483659, 2**31 + 12345])
def test_every_seed_runs_over_the_configurations_database(tmp_path, seed):
    """The database is the configuration's (`data_seed`), never the run's:
    the generator is handed that seed whatever `--seed` says, and once one
    seed's run has made data, warehouse and answers, a run on another seed
    finds them all. `--seed` reaches the chip children alone, where it
    draws the order of the window's passes."""
    data_seed = lib.Spec(lib.REPO).config(
        lib.Spec(lib.REPO).cell(CELL))["data_seed"]
    spawned = {}

    def keeping_commands(run):
        real = run.spawn

        def spawn(name, cmd, env=None):
            child = real(name, cmd, env)
            spawned[name] = child.cmd
            return child

        run.spawn = spawn
        return run

    first = keeping_commands(Recorded(_args(tmp_path)))
    assert first.run() == 0
    gen = spawned["gen_data"]
    assert gen[gen.index("--seed") + 1] == str(data_seed) != "2147483659"
    assert os.path.basename(first.data).startswith(f"sf0.01-{data_seed}-")
    args = _args(tmp_path)
    args.seed = seed
    other = keeping_commands(Recorded(args))
    assert other.run() == 0
    assert other.data == first.data
    assert other.order == ["spawn pass", "wait pass", "spawn chip",
                           "wait chip"]
    chip = spawned["chip"]
    assert chip[chip.index("--seed") + 1] == str(seed)
    # only the command line's own option names another database
    args.data_seed = 77
    elsewhere = keeping_commands(Recorded(args))
    assert elsewhere.run() == 0
    assert os.path.basename(elsewhere.data).startswith("sf0.01-77-")
    assert spawned["gen_data"][spawned["gen_data"].index("--seed") + 1] == "77"


@pytest.mark.parametrize("seed", [3, 2147483659, 2**31 + 12345])
def test_the_reference_answers_every_stream_and_a_run_compares_two(
        tmp_path, seed):
    """sqlite answers every stream of the mix once a database, so whichever
    stream a seed's window replays first is found; a run is held to stream
    0's answers and that stream's."""
    args = _args(tmp_path)
    args.seed = seed
    run = Recorded(args)
    run.prepare()
    asked = run.statements()
    passes = run.traffic["window_passes"]
    assert len(asked) == 6 * (1 + passes)
    assert {k.split("/")[0] for k in asked} == {
        f"s{i}" for i in range(1 + passes)}
    first = lib.window_order(run.traffic, seed, 0)[0]
    keys = run.compared_keys(asked)
    assert len(keys) == 12
    assert {k.split("/")[0] for k in keys} == {"s0", f"s{first}"}
    # asked of the reference is the same whatever the seed: one directory
    other = Recorded(_args(tmp_path))
    other.prepare()
    assert other.statements() == asked


def test_a_configuration_without_a_data_seed_cannot_run(tmp_path, monkeypatch):
    real = lib.Spec.config

    def config(self, cell):
        out = dict(real(self, cell))
        del out["data_seed"]
        return out

    monkeypatch.setattr(lib.Spec, "config", config)
    with pytest.raises(lib.BenchmarkError, match="names no `data_seed`"):
        Recorded(_args(tmp_path)).prepare()


def test_the_gate_is_gone_from_the_tree():
    """The window needs no gate when no reference runs beside a chip child:
    nothing of it is left for a later PR to lean on."""
    words = ("gate_" + "wait_s", "--" + "gate", "reference" + ".done")
    for top in ("benchmarks", "tests/benchmark"):
        for d, dirs, files in os.walk(os.path.join(lib.REPO, top)):
            dirs[:] = [x for x in dirs if x not in (".cache", "__pycache__")]
            for f in files:
                if f.endswith((".py", ".md", ".json")):
                    with open(os.path.join(d, f), errors="replace") as fh:
                        text = fh.read()
                    assert not any(w in text for w in words), (d, f)


STATEMENT = {"ms": 900, "status": ["Completed"], "backend": "tpu",
             "mem_source": "device", "mem_bytes": 1 << 30, "ladder": None}
AOT = {"disk_hits": 21, "misses": 0, "quarantined": 0, "call_failures": 0}
PASS_ONLY = {"first_pass": {"statements": {"query3": dict(STATEMENT),
                                           "query7": dict(STATEMENT)}},
             "counters": {"first_pass_end": {"aot": dict(AOT)}}}
MEASURED = {**copy.deepcopy(PASS_ONLY),
            "rehearsal": [{"name": "query3", "stream": 1,
                           "status": "Completed"}],
            "statements": [{"name": "query7", "stream": 2,
                            "status": "Completed"}],
            "counters": {"window_close": {"aot": dict(AOT)}}}


def _faults(tmp_path, child, pass_only):
    return Recorded(_args(tmp_path)).faults_of(child, pass_only)


def test_a_sound_pair_of_children_has_no_fault(tmp_path):
    assert _faults(tmp_path, MEASURED, PASS_ONLY) == []


@pytest.mark.parametrize("which", ["pass_only", "measured"])
@pytest.mark.parametrize("field,value,says", [
    ("status", ["Failed"], "['Failed']"),
    ("status", ["CompletedWithTaskFailures"], "CompletedWithTaskFailures"),
    ("backend", "cpu", "ran on cpu"),
    ("mem_source", "rss", "memory read from rss"),
    ("ladder", ["spill"], "ladder ['spill']")])
def test_a_first_pass_at_fault_in_either_child_is_not_correct(
        tmp_path, which, field, value, says):
    """The pass-only child is held to what the measured child is: its pass
    may be the one `first_pass_s` reads."""
    children = {"pass_only": copy.deepcopy(PASS_ONLY),
                "measured": copy.deepcopy(MEASURED)}
    children[which]["first_pass"]["statements"]["query7"][field] = value
    faults = _faults(tmp_path, children["measured"], children["pass_only"])
    where = "pass-only first pass" if which == "pass_only" else "first pass"
    assert len(faults) == 1 and faults[0].startswith(f"{where} query7: ")
    assert says in faults[0]


@pytest.mark.parametrize("which,counter", [
    ("pass_only", "quarantined"), ("pass_only", "call_failures"),
    ("measured", "quarantined"), ("measured", "call_failures")])
def test_an_aot_executable_at_fault_in_either_child_is_not_correct(
        tmp_path, which, counter):
    children = {"pass_only": copy.deepcopy(PASS_ONLY),
                "measured": copy.deepcopy(MEASURED)}
    mark = "first_pass_end" if which == "pass_only" else "window_close"
    children[which]["counters"][mark]["aot"][counter] = 1
    faults = _faults(tmp_path, children["measured"], children["pass_only"])
    assert len(faults) == 1 and faults[0].startswith("AOT executables of the")
