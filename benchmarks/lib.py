"""What every part of the benchmark shares, and nothing that touches jax.

The parent (`run.py`) may never import jax: a parent that has touched it
holds the chip and its children then fail or hang. So everything here is
plain Python: reading `BENCHMARK.json` and the data files it names, the one
general traffic generator, the percentile rule, the peaks table, finding a
per-layer metric's reader by its name, and the contract's result line.
"""

from __future__ import annotations

import glob
import hashlib
import importlib.util
import json
import math
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

#: the keys of the contract's last line, and of its `device`
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class BenchmarkError(Exception):
    """The benchmark cannot run, or ran and may not report: the parent
    prints the reason and exits non-zero without a result line."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Spec:
    """`BENCHMARK.json` and, for one cell, the data files it names. A cell,
    a configuration, a traffic mix and a per-layer metric are found by name
    alone, so a later PR adds them as new files and edits none."""

    def __init__(self, root=REPO):
        self.root = root
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name):
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise BenchmarkError(
            f"no cell {name!r} in BENCHMARK.json: "
            f"{[w['name'] for w in self.doc['workloads']]}"
        )

    def config(self, cell):
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                return load_json(os.path.join(self.root, c["file"]))
        raise BenchmarkError(f"no configuration {cell['config']!r}")

    def traffic_path(self, cell):
        path = os.path.join(self.root, "benchmarks", "traffic",
                            cell["traffic"] + ".json")
        if not os.path.isfile(path):
            raise BenchmarkError(f"no traffic mix at {path}")
        return path

    def traffic(self, cell):
        return load_json(self.traffic_path(cell))

    def reader(self, group, name):
        """The reader module of one metric: `end_to_end/<name>.py` declares
        UNIT, SOURCE and `read(run)`; `layer_metrics/<name>.py` declares
        LAYER and MOVES as well. `run` is what the chip child handed over.
        A reader that finds nothing to read returns None."""
        folder = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[group]
        path = os.path.join(self.root, "benchmarks", folder, name + ".py")
        if not os.path.isfile(path):
            raise BenchmarkError(
                f"{group} metric {name!r} has no reader at {path}")
        spec = importlib.util.spec_from_file_location(
            f"{folder}_" + re.sub(r"\W", "_", name), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def read_metrics(self, cell, group, run):
        """{name: (value, unit)} of the cell's metrics of one group, each
        from its own reader; a metric with nothing to read is left out."""
        out = {}
        for m in self.metrics_of(cell, group):
            value = self.reader(group, m["name"]).read(run)
            if value is not None:
                out[m["name"]] = (value, m["unit"])
        return out

    def metrics_of(self, cell, group):
        """The `end_to_end` or `per_layer` metrics this cell reports: all
        without a `workloads` key, and those whose key lists the cell."""
        return [
            m for m in self.doc[group]
            if "workloads" not in m or cell["name"] in m["workloads"]
        ]


def device_peaks(kind):
    """Published peaks of one chip, by `device_kind`. An unknown device is
    an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise BenchmarkError(
            f"device kind {kind!r} is not in benchmarks/peaks.json "
            f"({sorted(k for k in table if not k.startswith('_'))})"
        )
    return table[kind]


def read_events(trace_dir):
    """Every event of the program's trace files (`events-*.jsonl`, one JSON
    object a line) under `trace_dir`, in time order. A torn last line, which
    a killed writer leaves, is skipped; any other bad line is an error."""
    events = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "events-*.jsonl"))):
        with open(path) as f:
            lines = f.read().splitlines()
        for i, line in enumerate(lines):
            if not line.strip():
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                if i != len(lines) - 1:
                    raise BenchmarkError(f"{path}:{i + 1}: not JSON")
    return sorted(events, key=lambda e: e.get("ts", 0))


def events_between(run, kind, start_mark, end_mark):
    """The traced events of one kind between two of the child's marks."""
    lo, hi = run["marks"][start_mark], run["marks"][end_mark]
    return [e for e in run.get("events", ())
            if e.get("kind") == kind and lo <= e.get("ts", 0) <= hi]


def compiles_of(jax_counters):
    """(programs through XLA's backend compile, those of them that jax's
    persistent cache served) from a snapshot of the chip child's
    `CompileWatch`: jax's own monitoring events, counted in every run."""
    def count(key):
        return jax_counters.get(key, [0])[0]
    return (count("/jax/core/compile/backend_compile_duration"),
            count("/jax/compilation_cache/cache_hits"))


# -- traffic -----------------------------------------------------------------

def make_streams(traffic, scale, first, count):
    """Streams `first .. first+count-1` of one traffic mix, each an ordered
    list of `(statement name, sql text)`.

    The one general generator: the mix is data (`templates`, `order`,
    `param_seed`). The parameters of stream `s` come from
    `SeedSequence([param_seed, s])` through the program's own dsqgen
    equivalent, as `generate_streams` seeds them. Stream 0 keeps the mix's
    order (it is the Power pass); every later stream holds the same
    statements in a seeded permutation with other parameters, as the
    streams of a TPC-DS Throughput Run do.

    The statements do not depend on `--seed`: that draws the order of the
    window's passes (`window_order`) and nothing else. The engine compiles
    an executable for every new literal and every data-decided capacity, so
    statements or data drawn from the run's seed would give every seed
    other compiles and other work; with one set of statements over the
    configuration's one database (`data_seed`) every seed does the same
    work, in another order.
    """
    import numpy as np

    from nds_tpu.datagen.query_streams import instantiate

    if traffic.get("order") != "tpcds_stream_permutation":
        raise BenchmarkError(f"unknown order rule {traffic.get('order')!r}")
    qnums = [int(re.fullmatch(r"query(\d+)", t).group(1))
             for t in traffic["templates"]]
    streams = []
    for s in range(first, first + count):
        rng = np.random.default_rng(
            np.random.SeedSequence([traffic["param_seed"], s]))
        order = list(qnums) if s == 0 else [
            qnums[i] for i in rng.permutation(len(qnums))
        ]
        entries = []
        for n, q in enumerate(order):
            sql = instantiate(q, rng, scale)
            # the stream-file wrapping power.py splits on, kept so the
            # statement reaches the session exactly as a Power Run's does
            entries.append((
                f"query{q}",
                f"-- start query {n + 1} in stream {s} using template "
                f"query{q}.tpl\n{sql}\n;\n"
                f"-- end query {n + 1} in stream {s} using template "
                f"query{q}.tpl\n",
            ))
        streams.append(entries)
    return streams


def window_order(traffic, seed, cycle):
    """The order in which cycle `cycle` of the window replays the mix's
    `window_passes` streams (1-based stream indices): a permutation drawn
    from the run's seed, so that every seed carries the same passes in
    another order."""
    import numpy as np

    rng = np.random.default_rng(np.random.SeedSequence([seed, cycle]))
    return [int(i) + 1 for i in rng.permutation(traffic["window_passes"])]


def slice_statements(traffic, streams, seed, cycle, passes):
    """`[stream, statement name]` of what a traced run records, in order:
    the first `passes` passes of cycle `cycle` of the window. A function of
    the mix and the seed alone: both sides of a pair trace the same
    statements, with the same parameters."""
    return [[si, name]
            for si in window_order(traffic, seed, cycle)[:passes]
            for name, _ in streams[si]]


# -- arithmetic --------------------------------------------------------------

def percentile(values, p):
    """Nearest-rank percentile: the smallest value with at least p% of the
    samples at or below it."""
    if not values:
        raise BenchmarkError("percentile of no samples")
    ordered = sorted(values)
    rank = max(math.ceil(p / 100.0 * len(ordered)), 1)
    return ordered[rank - 1]


def samples_beyond(n, p):
    """How many of n samples lie beyond the p-th percentile: a tail is
    reported only where at least ten do (choosing-metrics section 1)."""
    return n - max(math.ceil(p / 100.0 * n), 1)


def completed(run):
    """The window's statements that ended `Completed`."""
    return [s for s in run.get("statements", ()) if s["status"] == "Completed"]


def window_percentile(run, p, template=None):
    """The p-th percentile of the latencies of the window's completed
    statements: of all of them, or of one template's."""
    ms = [s["ms"] for s in completed(run)
          if template is None or s["name"] == template]
    return percentile(ms, p) if ms else None


def union_seconds(intervals):
    """Total length of the union of `(start, end)` intervals."""
    total = 0.0
    end = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def tree_hash(paths, root=REPO):
    """sha256 over the names and bytes of every file under `paths`
    (relative to root), skipping build outputs. Names what a cached
    warehouse or answer was made by."""
    h = hashlib.sha256()
    files = []
    for p in paths:
        full = os.path.join(root, p)
        if os.path.isfile(full):
            files.append(full)
            continue
        for d, dirs, names in os.walk(full):
            dirs[:] = sorted(x for x in dirs if x != "__pycache__")
            files += [
                os.path.join(d, n) for n in sorted(names)
                if not n.endswith(".pyc") and not n.startswith("ndsgen-")
            ]
    for f in sorted(files):
        h.update(os.path.relpath(f, root).encode() + b"\0")
        with open(f, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()[:16]


def result_line(correct, attempted, failed, metrics, device, compared,
                breakdown=None, **more):
    """The contract's last line. `metrics` maps name -> (value, unit);
    `compared` is every number the comparison held to a limit, beside that
    limit, and comes last; `more` is whatever else a run keeps on record."""
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": v, "unit": u} for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out.update(more)
    out["compared"] = compared
    return json.dumps(out)
