"""What `BENCHMARK.json`'s per-layer list has to keep so that later PRs can
add to it and edit nothing: not a test file, the rules the tests of
`test_benchmark_spans.py`, `test_benchmark_lakehouse.py`,
`test_benchmark_feedback_io.py` and `test_benchmark_extend.py` hold the
document, and a grown copy of it, to.

* The twenty entries PR 28 left stay the first twenty, in their order: an
  entry appended after the last is free, one inserted or moved is not.
* Every later entry has a name of its own and a reader that declares the
  entry's `layer`, `unit`, `moves` and `source`.
* Each of the twenty, and `feedback_io_ms.stmt`, is read in the two
  `replay6` cells first; a later cell appends its name. The storage metrics
  list lakehouse cells alone, and the parquet cell's `query7_p50_ms` never
  the lakehouse cell.

A test of this directory holds the document to these rules and to nothing
that `grow.grow` (the README's "Adding to it", as code) changes: a count of
entries, cells or configurations is taken from the document and never
written as a number, and no test compares a list that growth extends with
a written one (`test_benchmark_grown_tree.py` runs the directory's tests
over a grown tree and reads its sources for such pins).

Each rule returns None, or what is wrong in words.
"""

PARQUET, LAKE = "sf1-parquet.replay6", "sf1-lakehouse.replay6"

FIRST_TWENTY = [
    "catalog_load_s.first", "aot_load_s.first", "plan_ms.stmt",
    "dispatches.stmt", "compiles.window", "device_busy_ms.stmt",
    "new_execs.rehearsal", "exec_lookups.stmt", "launches.stmt",
    "host_reads.stmt", "read_wait_ms.stmt", "exec_host_ms.stmt",
    "table_read_s.first", "h2d_s.first", "jit_trace_s.first",
    "xla_load_s.first", "unspanned_s.first", "fresh_compiles.rehearsal",
    "fresh_compiles.first", "fresh_compile_s.first"]
#: read where a statement's scans go to storage: over a lakehouse alone
STORAGE = ["scan_reads.stmt", "scan_ms.stmt", "files_pruned_share.stmt",
           "lake_pin_ms.stmt"]
#: read in the two `replay6` cells first; a later cell appends its name
BOTH_FIRST = FIRST_TWENTY + ["feedback_io_ms.stmt"]
#: every metric whose `workloads` list a rule speaks of
LISTED = BOTH_FIRST + STORAGE + ["query7_p50_ms"]


def entry_fault(spec, index):
    """The per-layer entry at `index`: one of the first twenty in its
    place, or a later one with a name and a reader of its own."""
    entries = spec.doc["per_layer"]
    entry = entries[index]
    if index < len(FIRST_TWENTY):
        if entry["name"] != FIRST_TWENTY[index]:
            return (f"per_layer[{index}] is {entry['name']!r}, not "
                    f"{FIRST_TWENTY[index]!r}: append after the last entry, "
                    f"insert and move nothing")
        return None
    if [e["name"] for e in entries].count(entry["name"]) != 1:
        return f"per_layer has {entry['name']!r} more than once"
    reader = spec.reader("per_layer", entry["name"])
    declared = (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    said = (entry["layer"], entry["unit"], entry["moves"], entry["source"])
    if declared != said:
        return f"{entry['name']}: its reader declares {declared}, its entry {said}"
    return None


def workloads_fault(spec, name):
    """The cells the metric `name` lists."""
    by_name = {m["name"]: m for m in
               spec.doc["per_layer"] + spec.doc["end_to_end"]}
    if name not in by_name:
        return f"no metric {name!r}"
    cells = by_name[name]["workloads"]
    if name in BOTH_FIRST and cells[:2] != [PARQUET, LAKE]:
        return (f"{name} lists {cells}: the two replay6 cells come first, a "
                f"later cell appends its name")
    if name in STORAGE:
        formats = {w: spec.config(spec.cell(w))["storage_format"]
                   for w in cells}
        if LAKE not in cells or set(formats.values()) != {"lakehouse"}:
            return f"{name} lists {formats}: lakehouse cells alone"
    if name == "query7_p50_ms" and (PARQUET not in cells or LAKE in cells):
        return (f"{name} lists {cells}: its bound is the parquet cell's, "
                f"the lakehouse cell has query7_lake_p50_ms")
    return None


def faults(spec):
    """Every rule over the whole document: empty where a PR only added."""
    names = [m["name"] for m in spec.doc["per_layer"]]
    found = [f"per_layer has only {len(names)} entries"] \
        if len(names) < len(FIRST_TWENTY) else []
    found += [entry_fault(spec, i) for i in range(len(names))]
    found += [workloads_fault(spec, name) for name in LISTED]
    return [f for f in found if f]
