"""Milliseconds of table reads from storage a window statement paid: the
`dur_ms` of the `catalog_load` spans `scan_reads.stmt` counts. One span
holds the whole of a read: opening the surviving parquet files and the
Arrow decode (`read_ms`), the host encode (dictionary codes, padding,
stats: `encode_ms`) and the host-to-device copy (`h2d_ms`)."""

from benchmarks.layer_metrics._window import storage_reads

LAYER = "session + catalog"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    reads = storage_reads(run)
    if reads is None:
        return None
    return sum(e["dur_ms"] for e in reads) / len(run["statements"])
