"""Table reads from storage per window statement: the program's
`catalog_load` events that ended inside the window and loaded a column.
Over a parquet warehouse the tables sit whole on the device after the first
pass and this reads 0; over the snapshot-manifest format a zone-map pruned
scan never touches those cached columns, so every execution of a
date-restricted statement opens its surviving files again."""

from benchmarks.layer_metrics._window import storage_reads

LAYER = "session + catalog"
UNIT = "reads/stmt"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    reads = storage_reads(run)
    if reads is None:
        return None
    return len(reads) / len(run["statements"])
