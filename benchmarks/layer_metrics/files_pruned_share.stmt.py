"""Share of a pinned snapshot's data files that zone-map pruning kept a
scan from opening: `sum(files_pruned) / sum(files_total)` over the
`scan_prune` events that ended inside the window, in percent. One event a
filtered scan of a lakehouse table whose filter the manifest's per-file
stats can judge; nothing where the window has none (a parquet warehouse,
or `engine.lake_prune=off`)."""

from benchmarks.layer_metrics._spans import WINDOW, between

LAYER = "session + catalog"
UNIT = "%"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    prunes = between(run, "scan_prune", WINDOW)
    total = sum(e["files_total"] for e in prunes)
    if not total:
        return None
    return 100.0 * sum(e["files_pruned"] for e in prunes) / total
