"""What the readers of the lakehouse cell's window spans share
(`scan_reads.stmt`, `scan_ms.stmt`, `lake_pin_ms.stmt`). Not a metric: no
entry of `BENCHMARK.json` names it."""

from benchmarks.layer_metrics._spans import WINDOW, between


def window_spans(run, kind):
    """The spans of one kind that ended inside the window, or None where
    the run has no window statements or the program wrote no such span at
    all (a program from before it: nothing to read, not a reading of 0)."""
    if not run.get("statements") or not any(
            e.get("kind") == kind for e in run.get("events", ())):
        return None
    return between(run, kind, WINDOW)


def storage_reads(run):
    """The window's `catalog_load`s that went to storage (`loaded` above 0;
    a scan served from the entry's device columns is a hit and no read)."""
    loads = window_spans(run, "catalog_load")
    return None if loads is None else [e for e in loads if e.get("loaded")]
