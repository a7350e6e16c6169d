"""The one recipe for growth, as code: not a test file. `grow(root)` does to
the tree at `root` (a COPY: `BENCHMARK.json` and `benchmarks/`) everything
`benchmarks/README.md` "Adding to it" lets a later PR do, at once, and
edits no file that is there and no entry of the document:

* a lakehouse configuration with other `tables` than the accepted one, a
  mix of five other templates (`window_passes` 4, a `trace_cycle` of its
  own) and their cell, `lake_cell`; a parquet configuration with floats
  for decimals, a mix of three and their cell, `parquet_cell`;
* each cell's name appended to every list `doc_rules` lets it join: the
  first twenty and `feedback_io_ms.stmt`, and for the lakehouse cell the
  four storage metrics;
* a per-layer reader and its entry appended after the last, and an
  end-to-end reader and its entry appended, both listing `lake_cell` alone.

`test_benchmark_extend.py` holds a tree grown so to the rules of
`doc_rules.py`; `test_benchmark_grown_tree.py` runs every test of this
directory over one. So a test here may hold `BENCHMARK.json` only to what
this recipe keeps. Every name begins with `grown<cells the document had>`,
which no PR's own cell, configuration, mix or metric may; a grown tree has
more cells, so it can be grown again (the tests of a grown tree do). A new
file is opened for exclusive creation: a name that is taken is an error and
never an overwrite.
"""

import json
import os
import types

import doc_rules
from benchmarks.lib import load_json

LAKE_TEMPLATES = ["query38", "query2", "query9", "query25", "query22"]
PARQUET_TEMPLATES = ["query96", "query3", "query36"]


def names(doc):
    """What `grow` calls what it adds to `doc`."""
    tag = f"grown{len(doc['workloads'])}"
    return types.SimpleNamespace(
        lake_config=f"{tag}-lakehouse-3ch-1chip", lake_mix=f"{tag}x5",
        lake_cell=f"{tag}-lakehouse-3ch.{tag}x5",
        parquet_config=f"{tag}-parquet-floats-1chip", parquet_mix=f"{tag}x3",
        parquet_cell=f"{tag}-parquet-floats.{tag}x3",
        per_layer=f"{tag}_execute_ms.stmt",
        end_to_end=f"{tag}_query22_p50_ms")


PER_LAYER_READER = (
    'LAYER = "executor + fused pipelines"\nUNIT = "ms"\n'
    'MOVES = "stmt_p50_ms"\nSOURCE = "host_clock"\n\n\n'
    "def read(run):\n"
    "    ms = [s['execute_ms'] for s in run['statements']]\n"
    "    return sum(ms) / len(ms) if ms else None\n")
END_TO_END_READER = (
    'from benchmarks import lib\n\nUNIT = "ms"\nSOURCE = "host_clock"\n\n\n'
    "def read(run):\n"
    "    return lib.window_percentile(run, 50, 'query22')\n")


def _new_file(root, rel, text):
    with open(os.path.join(root, "benchmarks", rel), "x") as f:
        f.write(text)


def _new_cell(root, doc, config, like, changes, mix, traffic, cell, why):
    """A configuration copied from the accepted one `like`, a mix and the
    cell of the two: three new entries, two new files."""
    body = load_json(
        os.path.join(root, "benchmarks", "configs", like + ".json"))
    body.update(name=config, **changes)
    _new_file(root, f"configs/{config}.json", json.dumps(body, indent=1))
    _new_file(root, f"traffic/{mix}.json", json.dumps({
        "order": "tpcds_stream_permutation", "loop": "closed", "clients": 1,
        "param_seed": 11, "trace_passes": 2, **traffic}, indent=1))
    accepted, = [c for c in doc["configs"] if c["name"] == like]
    doc["configs"].append({
        **accepted, "name": config, "why": why,
        "file": f"benchmarks/configs/{config}.json"})
    doc["workloads"].append({"name": cell, "config": config, "traffic": mix,
                             "chips": 1, "why": why})


def grow(root):
    """Grow the copied tree at `root`; returns the names it gave, with the
    grown document as `doc`."""
    doc = load_json(os.path.join(root, "BENCHMARK.json"))
    new = names(doc)
    lake = load_json(os.path.join(root, "benchmarks", "configs",
                              "sf1-lakehouse-1chip.json"))
    _new_cell(
        root, doc, new.lake_config, "sf1-lakehouse-1chip",
        {"tables": sorted(lake["tables"] + [
            "catalog_sales", "web_sales", "inventory"]),
         "query_templates": len(LAKE_TEMPLATES)},
        new.lake_mix, {"templates": LAKE_TEMPLATES, "window_passes": 4,
                       "trace_cycle": 1, "control_templates": ["query2"]},
        new.lake_cell, "the recipe's lakehouse cell: three channels and "
        "inventory, five templates no accepted cell runs")
    _new_cell(
        root, doc, new.parquet_config, "sf1-parquet-1chip",
        {"decimals": False, "query_templates": len(PARQUET_TEMPLATES)},
        new.parquet_mix, {"templates": PARQUET_TEMPLATES, "window_passes": 3,
                          "trace_cycle": 2, "control_templates": ["query3"]},
        new.parquet_cell, "the recipe's parquet cell: floats for decimals, "
        "three of the accepted mix's templates")
    # each cell's name after those that are there, on every list it may join
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in doc_rules.BOTH_FIRST:
        by_name[name]["workloads"] += [new.lake_cell, new.parquet_cell]
    for name in doc_rules.STORAGE:
        by_name[name]["workloads"].append(new.lake_cell)
    # a metric of each group with a reader of its own, after the last entry
    _new_file(root, f"layer_metrics/{new.per_layer}.py", PER_LAYER_READER)
    _new_file(root, f"end_to_end/{new.end_to_end}.py", END_TO_END_READER)
    doc["per_layer"].append({
        "name": new.per_layer, "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "executor + fused pipelines",
        "moves": "stmt_p50_ms", "workloads": [new.lake_cell]})
    doc["end_to_end"].append({
        "name": new.end_to_end, "unit": "ms", "better": "lower",
        "bound": 0.05, "source": "host_clock", "workloads": [new.lake_cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f, indent=1)
    new.doc = doc
    return new
