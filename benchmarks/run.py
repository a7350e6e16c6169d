#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the contract's JSON object. There is
none, and the exit code is not 0, when jax finds no TPU or fewer chips than
the cell asks for, or when a phase fails to run at all.

This parent never imports jax (it checks): a parent that has touched jax
holds the chip. It reads `BENCHMARK.json`, the cell's configuration and its
traffic mix by name, and runs every phase as a child:

    gen_data    the raw data, from the       } kept under benchmarks/.cache/
                configuration's `data_seed`    data/<scale>-<data_seed>-<hash
    Load        the warehouse, in the          of what makes them>/, so only a
                configuration's format       } checkout's first run makes
    reference   sqlite's answers (beside       them; written to a temporary
                Load, never beside a pass)   } name, renamed
    pass child  the first pass alone, in a fresh process (child.py --pass_only)
    chip child  first pass + rehearsal + window (child.py)

`--seed` draws the order in which the window replays its passes and
nothing else: the database is the configuration's (`data_seed`, as the
parameters are the mix's `param_seed`), because the engine's shapes, and so
its compiles and its work, are decided by the data: every seed does the
same work on the same database, in another order.

and then judges the run: fail, never fall back. Both chip children go
through ./nds-tpu-submit, one after the other, and nothing else of the
benchmark's runs while either does: `first_pass_s` is the lower of their two
first passes.

    --scale 0.01     a CPU rehearsal: runs to the end, then fails on the
                     platform check and prints no result line
    --trace_cycle 0  a traced rehearsal whose few seconds of window never
                     reach the traffic mix's own `trace_cycle`
    --data_seed N    another database than the configuration's: the control
                     on three seeds, and whether the data changes the work
    --control float32   runs no chip child: the comparison's control, the
                     reference with float32 aggregation put in the
                     program's place, against the sound reference
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmarks import compare, lib  # noqa: E402
from benchmarks.lib import BenchmarkError  # noqa: E402

#: data directories kept (one a scale, data seed and generator)
KEEP_DATA = 8
#: a run must end inside the driver's 1200 s for a first run
DEADLINE_S = 1150
#: what makes the raw data and a warehouse: a change to any of it must not
#: find an old warehouse
DATA_SOURCES = ("nds_tpu/datagen", "nds_tpu/io", "nds_tpu/lakehouse",
                "nds_tpu/schema.py", "nds_tpu/transcode.py",
                "nds_tpu/cli/gen_data.py", "nds_tpu/cli/transcode.py")


def load_child(run_dir):
    """What the chip child handed over."""
    return lib.load_json(os.path.join(run_dir, "child.json"))


def check_device(device, cell):
    """The look for a chip: no TPU, fewer chips than the cell asks for, or a
    device whose peaks nobody wrote down, and there is no result."""
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        raise BenchmarkError(
            f"the cell needs {cell['chips']} TPU chip(s); jax found "
            f"{device['count']} x {device['platform']}: no result")
    return lib.device_peaks(device["kind"])


class Run:
    def __init__(self, args):
        self.args = args
        self.t0 = time.time()
        self.t0_mono = time.monotonic()
        self.children = []

    # -- children ------------------------------------------------------------
    def spawn(self, name, cmd, env=None):
        log = open(os.path.join(self.logs, f"{name}.log"), "w")
        child = subprocess.Popen(
            [str(c) for c in cmd], cwd=REPO, stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
            env={**os.environ, "JAX_COMPILATION_CACHE_DIR": self.compiled,
                 **(env or {})},
        )
        child.log, child.name, child.t0 = log, name, time.monotonic()
        self.children.append(child)
        return child

    def wait(self, child):
        left = DEADLINE_S - (time.monotonic() - self.t0_mono)
        try:
            rc = child.wait(timeout=max(left, 1))
        except subprocess.TimeoutExpired:
            rc = "deadline"
        self.reap(child)
        secs = time.monotonic() - child.t0
        print(f"phase {child.name}: {secs:.1f} s rc={rc}", flush=True)
        if rc != 0:
            with open(child.log.name, errors="replace") as f:
                sys.stdout.write("".join(f.readlines()[-40:]))
            raise BenchmarkError(f"phase {child.name} exited {rc} "
                                 f"(log: {child.log.name})")
        return secs

    def reap(self, child):
        """The child and whatever it started are gone when this returns."""
        try:
            os.killpg(child.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        child.wait()
        child.log.close()

    def reap_all(self):
        for child in self.children:
            self.reap(child)

    # -- set-up --------------------------------------------------------------
    def prepare(self):
        a = self.args
        spec = lib.Spec(REPO)
        self.cell = spec.cell(a.workload)
        self.config = spec.config(self.cell)
        self.traffic = spec.traffic(self.cell)
        self.spec = spec
        self.scale = a.scale if a.scale is not None else self.config["scale_factor"]
        self.trace_cycle = (a.trace_cycle if a.trace_cycle is not None
                            else self.traffic.get("trace_cycle"))
        if a.trace and self.trace_cycle is None:
            raise BenchmarkError(
                f"the traffic mix {self.cell['traffic']!r} names no "
                f"`trace_cycle`: a traced run opens its slice at that "
                f"cycle's first pass")
        if not os.path.isfile(os.path.join(REPO, "nds-tpu-submit")):
            raise BenchmarkError("no nds-tpu-submit beside benchmarks/: the "
                                 "benchmark drives the repository it sits in")
        cache = os.path.abspath(a.cache_dir)
        self.run_dir = os.path.join(
            cache, "runs", f"{a.workload}-{a.seed}-t{a.trace}")
        shutil.rmtree(self.run_dir, ignore_errors=True)
        self.logs = os.path.join(self.run_dir, "logs")
        os.makedirs(self.logs)
        if "data_seed" not in self.config:
            raise BenchmarkError(
                f"the configuration {self.cell['config']!r} names no "
                f"`data_seed`: the database is the configuration's, not "
                f"the run's")
        self.data_seed = (a.data_seed if a.data_seed is not None
                          else int(self.config["data_seed"]))
        key = lib.tree_hash(DATA_SOURCES)
        self.data = os.path.join(
            cache, "data", f"sf{self.scale}-{self.data_seed}-{key}")
        os.makedirs(self.data, exist_ok=True)
        os.utime(self.data)
        self.evict()
        # the compile caches (jax's, and under the same root the engine's
        # AOT executables and cardinality feedback) at a fixed path inside
        # the checkout, whatever the machine's environment names: the
        # program takes the directory it is given
        self.compiled = os.path.join(cache, "compiled")
        os.makedirs(self.compiled, exist_ok=True)
        n = sum(len(files) for _, _, files in os.walk(self.compiled))
        print(f"cell {a.workload} seed {a.seed} (the window's order) data "
              f"seed {self.data_seed} scale {self.scale} "
              f"trace {a.trace}; compile cache {self.compiled} ({n} files); "
              f"data {self.data}", flush=True)

    def evict(self):
        root = os.path.dirname(self.data)
        dirs = sorted((os.path.join(root, d) for d in os.listdir(root)),
                      key=os.path.getmtime, reverse=True)
        for d in dirs[KEEP_DATA:]:
            shutil.rmtree(d, ignore_errors=True)

    def ensure_raw(self):
        raw = os.path.join(self.data, "raw")
        if not os.path.isdir(raw):
            tmp = raw + ".tmp"
            shutil.rmtree(tmp, ignore_errors=True)
            self.wait(self.spawn("gen_data", [
                sys.executable, "-m", "nds_tpu.cli.gen_data", "local",
                "--scale", self.scale, "--parallel", 4,
                "--seed", self.data_seed, "--data_dir", tmp,
                "--overwrite_output",
            ]))
            os.rename(tmp, raw)
        return raw

    def statements(self, control=None):
        """What the reference answers, {key: stream entry}: every stream of
        the mix, once a database, so that whichever stream a seed's window
        replays first (`compared_keys`) its answers are found. The control
        answers stream 0's statements that the mix lists for it: its float32
        SUM and AVG are Python callbacks, which query1's correlated subquery
        calls for minutes."""
        streams = lib.make_streams(self.traffic, self.scale, 0,
                                   1 + self.traffic["window_passes"])
        if control:
            stream0 = dict(streams[0])
            return {f"s0/{t}": stream0[t]
                    for t in self.traffic["control_templates"]}
        return {f"s{si}/{name}": sql
                for si, stream in enumerate(streams) for name, sql in stream}

    def compared_keys(self, statements):
        """The answers a run is held to: stream 0's (both first passes) and
        those of the stream this seed's window replays first."""
        compared = lib.window_order(self.traffic, self.args.seed, 0)[0]
        return [k for k in statements
                if k.split("/")[0] in ("s0", f"s{compared}")]

    def start_reference(self, raw, statements, control=None):
        """sqlite's answers to `statements`: found, or children started
        beside Load, one a template. sqlite runs on one core and loads only
        the tables and columns its statements name, so the children side by
        side take the seconds of the slowest, about Load's own, where one
        child took twice that. Returns (dir, children)."""
        with open(os.path.join(HERE, "reference.py"), "rb") as f:
            h = hashlib.sha256(f.read())
        h.update(json.dumps(statements, sort_keys=True).encode())
        ref = os.path.join(
            self.data, f"ref-{control or 'sound'}-{h.hexdigest()[:16]}")
        if os.path.isdir(ref):
            return ref, []
        tmp = ref + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        children = []
        for template in sorted({key.split("/")[1] for key in statements}):
            asked = os.path.join(tmp, f"statements-{template}.json")
            with open(asked, "w") as f:
                json.dump({k: v for k, v in statements.items()
                           if k.split("/")[1] == template}, f)
            children.append(self.spawn(
                f"reference_{control or 'sound'}_{template}", [
                    sys.executable, os.path.join(HERE, "reference.py"), raw,
                    asked, tmp,
                    *(["--control", control] if control else []),
                ], env={"JAX_PLATFORMS": "cpu"}))
        return ref, children

    def finish_reference(self, ref, children):
        for child in children:
            self.wait(child)
        if children:
            os.rename(ref + ".tmp", ref)

    def ensure_warehouse(self, raw):
        load = self.config["load"]
        tables = self.config.get("tables")
        tag = hashlib.sha256(json.dumps(
            [load, tables], sort_keys=True).encode()).hexdigest()[:8]
        wh = os.path.join(
            self.data, f"wh-{self.config['storage_format']}-{tag}")
        if not os.path.isdir(wh):
            tmp = wh + ".tmp"
            shutil.rmtree(tmp, ignore_errors=True)
            self.wait(self.spawn("load", [
                os.path.join(REPO, "nds-tpu-submit"), load["template"],
                "nds_tpu.cli.transcode", raw, tmp,
                os.path.join(self.run_dir, "load_report.txt"),
                *load["flags"],
                *(["--tables", ",".join(tables)] if tables else []),
            ]))
            os.rename(tmp, wh)
        return wh

    # -- the run -------------------------------------------------------------
    def stay_off_jax(self):
        if "jax" in sys.modules:
            raise BenchmarkError("the parent imported jax: it would hold "
                                 "the chip its child needs")

    def chip_child(self, wh, name="chip"):
        """A child that owns the chip: `chip`, the measured one; `pass`, the
        first pass alone; `warm`, the measured child's phases with a window
        of no seconds. Each in a run directory of its own."""
        a = self.args
        measured = name == "chip"
        run_dir = self.run_dir if measured else os.path.join(self.run_dir, name)
        os.makedirs(run_dir, exist_ok=True)
        env = {}
        if a.trace and measured:
            env["NDS_TRACE_DIR"] = os.path.join(run_dir, "trace")
        self.stay_off_jax()
        child = self.spawn(name, [
            os.path.join(REPO, "nds-tpu-submit"),
            self.config["power"]["template"], "benchmarks.child",
            "--warehouse", wh, "--run_dir", run_dir,
            "--traffic", self.spec.traffic_path(self.cell),
            "--seed", a.seed, "--scale", self.scale,
            "--seconds", a.seconds if measured else 0,
            "--trace", a.trace if measured else 0,
            *(["--trace_cycle", self.trace_cycle]
              if a.trace and measured else []),
            *(["--pass_only"] if name == "pass" else []),
        ], env=env)
        child.run_dir = run_dir
        return child

    def warm_checkout(self, wh):
        """`first_pass_s` is a first execution with warm disk caches. The
        first run of a cell in a checkout finds them empty, so it runs the
        first pass and the rehearsal once in a child that is thrown away
        (set-up: the compile of some minutes) and leaves a marker;
        the children that are measured then find every program on disk, as
        in every later run."""
        with open(self.spec.traffic_path(self.cell), "rb") as f:
            h = hashlib.sha256(f.read())
        h.update(json.dumps(self.config, sort_keys=True).encode())
        h.update(str(self.scale).encode() + self.compiled.encode())
        marker = os.path.join(os.path.abspath(self.args.cache_dir),
                              f"warmed-{h.hexdigest()[:16]}")
        if not os.path.exists(marker):
            self.wait(self.chip_child(wh, "warm"))
            with open(marker, "w"):
                pass

    def run(self):
        a = self.args
        self.prepare()
        raw = self.ensure_raw()
        statements = self.statements()
        ref, ref_children = self.start_reference(raw, statements)
        if a.control:
            return self.control(raw, ref, ref_children)
        # Load is host-only and no metric times it: the reference may run
        # beside it. Beside a child that owns the chip nothing of the
        # benchmark's own runs: sqlite has exited before the first starts
        wh = self.ensure_warehouse(raw)
        self.finish_reference(ref, ref_children)
        self.warm_checkout(wh)
        children = {}
        for name in ("pass", "chip"):
            child = self.chip_child(wh, name)
            self.wait(child)
            with open(child.log.name, errors="replace") as f:
                sys.stdout.write("".join(
                    f"{name} {line}" for line in f
                    if line.startswith("child:")))
            children[name] = load_child(child.run_dir)
        return self.judge(children["chip"], children["pass"], ref,
                          self.compared_keys(statements))

    def control(self, raw, ref, ref_children):
        """The control's answers in the program's place: must not pass."""
        asked = self.statements(self.args.control)
        bad, bad_children = self.start_reference(raw, asked,
                                                 self.args.control)
        self.finish_reference(ref, ref_children)
        self.finish_reference(bad, bad_children)
        per = compare.compare_answers(ref, bad, list(asked))
        ok, numbers = compare.verdict(per, self.config["correct_limits"])
        for key, p in per.items():
            print(f"control {key}: {json.dumps(p)}")
        print(f"control {self.args.control} data seed {self.data_seed}: "
              f"correct={ok} {json.dumps(numbers)}")
        return 0 if not ok else 4

    # -- judgement -----------------------------------------------------------
    def faults_of(self, child, pass_only):
        """Fail, never fall back: everything but the answers that makes a
        run that reached its end not `correct`."""
        faults = []
        for where, first in (("pass-only first pass", pass_only["first_pass"]),
                             ("first pass", child["first_pass"])):
            for name, s in first["statements"].items():
                print(f"{where} {name}: {s.get('ms')} ms "
                      f"status={s['status']} backend={s.get('backend')} "
                      f"mem={s.get('mem_bytes')} ({s.get('mem_source')})")
                if s["status"] != ["Completed"]:
                    faults.append(f"{where} {name}: {s['status']} "
                                  f"{s.get('exceptions')}")
                if s.get("backend") != "tpu":
                    faults.append(f"{where} {name}: ran on {s.get('backend')}")
                if s.get("mem_source") != "device":
                    faults.append(f"{where} {name}: memory read from "
                                  f"{s.get('mem_source')}, not the device")
                if s.get("ladder"):
                    faults.append(f"{where} {name}: ladder {s['ladder']}")
        for phase in ("rehearsal", "statements"):
            for s in child[phase]:
                where = f"{phase} {s['name']} (stream {s['stream']})"
                if s.get("ladder"):
                    faults.append(f"{where}: ladder {s['ladder']}")
                if phase == "rehearsal" and s["status"] != "Completed":
                    faults.append(f"{where}: {s['status']} "
                                  f"{s.get('exceptions')}")
        for where, counters in (
                ("pass-only child", pass_only["counters"]["first_pass_end"]),
                ("measured child", child["counters"]["window_close"])):
            aot = counters["aot"] or {}
            if aot.get("quarantined") or aot.get("call_failures"):
                faults.append(f"AOT executables of the {where}: "
                              f"{aot['quarantined']} quarantined, "
                              f"{aot['call_failures']} failed at call")
        return faults

    def judge(self, child, pass_only, ref, keys):
        device = child["device"]
        peaks = check_device(device, self.cell)
        if pass_only["device"] != device:
            raise BenchmarkError(f"the pass-only child ran on "
                                 f"{pass_only['device']}, not on {device}")
        faults = self.faults_of(child, pass_only)
        firsts = [pass_only["first_pass"]["statements"],
                  child["first_pass"]["statements"]]
        stmts = child["statements"]
        done = [s for s in stmts if s["status"] == "Completed"]
        if not done:
            raise BenchmarkError("no statement completed in the window")
        failed = (len(stmts) - len(done)) + sum(
            1 for first in firsts for s in first.values()
            if s["status"] != ["Completed"])
        attempted = len(stmts) + sum(len(first) for first in firsts)

        # the two readings `first_pass_s` is the lower of, and whether each
        # pass found its programs on disk (jax's own events, counted in
        # every run, traced or not)
        for key, tag, c in (("first_pass_a_s", "a (pass-only child)", pass_only),
                            ("first_pass_b_s", "b (measured child)", child)):
            child[key] = c["first_pass"]["power_test_ms"] / 1e3
            at_end = c["counters"]["first_pass_end"]
            compiles, hits = lib.compiles_of(at_end["jax"])
            aot = at_end["aot"] or {}
            print(f"first pass {tag}: {child[key]} s; {compiles} programs "
                  f"through XLA, {compiles - hits} compiled anew, {hits} "
                  f"from jax's disk cache; AOT executables "
                  f"{aot.get('disk_hits')} loaded, {aot.get('misses')} "
                  f"compiled")

        # the answers the timed path produced: both first passes' and those
        # of the window's first pass, each cell against sqlite's
        per = compare.compare_answers(
            ref, os.path.join(self.run_dir, "answers"), keys)
        per.update({f"pass/{key}": p for key, p in compare.compare_answers(
            ref, os.path.join(self.run_dir, "pass", "answers"),
            [k for k in keys if k.startswith("s0/")]).items()})
        for key, p in per.items():
            print(f"answer {key}: rows {p['rows']} cells_differ "
                  f"{p['cells_differ']} rel_gap_max {p['rel_gap_max']:.3e}"
                  + (f" first: {p['first']}" if p["first"] else ""))
        ok, numbers = compare.verdict(per, self.config["correct_limits"])
        for name, n in numbers.items():
            print(f"compared {name}: {n['value']!r} limit {n['limit']!r}")
        if not ok:
            faults.append("answers differ from the reference's")
        for f in faults:
            print(f"FAULT: {f}")

        # earlier lines: what the result line has no room for
        by_class = {}
        for s in done:
            by_class.setdefault(s["name"], []).append(s["ms"])
        reh = child["rehearsal"]
        print(f"rehearsal: {len(reh)} statements, mean "
              f"{sum(s['ms'] for s in reh) / len(reh):.1f} ms, "
              f"{sum(s.get('aot_compiled', 0) for s in reh)} compiled, "
              f"{sum(s.get('aot_loaded', 0) for s in reh)} loaded from disk")
        print(f"window: {child['window_s']:.3f} s, {len(done)} completed of "
              f"{len(stmts)}, {stmts[-1]['cycle']} whole cycles + part; "
              f"new shapes met: {sum(s['new_shapes'] for s in stmts)}")
        print("window per class, samples and median ms: " + json.dumps({
            k: [len(v), round(lib.percentile(v, 50), 1)]
            for k, v in by_class.items()}))
        for mark in ("first_pass_end", "rehearsal_end", "window_close"):
            print(f"counters at {mark}: "
                  + json.dumps(child["counters"][mark]))

        device_out = {**device,
                      "memory_peak_bytes": child["memory"]["peak_bytes_in_use"]}
        if device_out["memory_peak_bytes"] is None:
            raise BenchmarkError("the device reports no peak_bytes_in_use")
        print(f"device: {json.dumps(device_out)} of "
              f"{child['memory']['bytes_limit']} B; peaks {json.dumps(peaks)}")
        breakdown = None
        if self.args.trace:
            metrics, breakdown = self.traced(child, device_out)
        else:
            child["marks"]["parent_start"] = self.t0 * 1e3
            metrics = self.spec.read_metrics(self.cell, "end_to_end", child)
            wanted = self.spec.metrics_of(self.cell, "end_to_end")
            if len(metrics) != len(wanted):
                raise BenchmarkError("no value for " + ", ".join(
                    m["name"] for m in wanted if m["name"] not in metrics))
        # the contract: each number compared beside its limit, as the last
        # lines of standard error and last in the result's line
        sys.stdout.flush()
        for name, n in numbers.items():
            print(f"compared {name}: {n['value']!r} limit {n['limit']!r}",
                  file=sys.stderr)
        sys.stderr.flush()
        more = {"first_passes_s": [child["first_pass_a_s"],
                                   child["first_pass_b_s"]]}
        if self.args.trace:
            # what was traced: a pair is held to equal slices
            more["slice"] = child["slice"]
        print(lib.result_line(
            not faults, attempted, failed, metrics, device_out, numbers,
            breakdown, **more))
        return 0

    def traced(self, child, device_out):
        """The per-layer metrics and the breakdown of a traced run."""
        if child["slice_error"]:
            raise BenchmarkError(child["slice_error"])
        if "device_trace" not in child:
            raise BenchmarkError(f"no device trace: {child.get('trace_error')}")
        trace = child["device_trace"]
        child["events"] = lib.read_events(os.path.join(self.run_dir, "trace"))
        device_out["busy_s"] = trace["busy_s"]
        device_out["window_s"] = trace["window_s"]
        marks, sl = child["marks"], child["slice"]
        print(f"traced slice: cycle {sl['cycle']}, {sl['passes']} passes, "
              f"{len(sl['statements'])} statements, from "
              f"{(marks['slice_start'] - marks['window_open']) / 1e3:.3f} s "
              f"to {(marks['slice_end'] - marks['window_open']) / 1e3:.3f} s "
              f"of a window of {child['window_s']:.3f} s: "
              + " ".join(f"{si}/{name}" for si, name in sl["statements"]))
        print(f"traced slice: {trace['window_s']:.3f} s, busy "
              f"{trace['busy_s']:.3f} s, idle share "
              f"{1 - trace['busy_s'] / trace['window_s']:.4f}, "
              f"{trace['statements']} statements, unannotated "
              f"{trace['unannotated_s']:.3f} s")
        return (self.spec.read_metrics(self.cell, "per_layer", child),
                {"device_ops": trace["device_ops"],
                 "idle_gaps": trace["idle_gaps"]})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--scale", type=float,
                    help="override the configuration's scale: CPU rehearsal")
    ap.add_argument("--trace_cycle", type=int,
                    help="override the traffic mix's `trace_cycle`, the "
                    "cycle whose first passes are traced: CPU rehearsal")
    ap.add_argument("--data_seed", type=int,
                    help="override the configuration's `data_seed`: the "
                    "control on other databases, and the question whether "
                    "the data changes the work")
    ap.add_argument("--control", choices=["float32"])
    ap.add_argument("--cache_dir", default=os.path.join(HERE, ".cache"),
                    help="where data, answers and run directories are kept "
                    "(tests point it at a temporary directory)")
    args = ap.parse_args(argv)
    run = Run(args)
    try:
        rc = run.run()
    except BenchmarkError as e:
        print(f"benchmark: FAILED: {e}", flush=True)
        rc = 1
    finally:
        run.reap_all()
    return rc


if __name__ == "__main__":
    sys.exit(main())
