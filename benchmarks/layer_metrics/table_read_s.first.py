"""Seconds the first pass spent reading tables from storage and encoding
them on the host (`catalog_load.read_ms` + `encode_ms`: parquet read and
Arrow decode, dictionary codes, padding, statistics)."""

from benchmarks.layer_metrics._spans import FIRST, between

LAYER = "session + catalog"
UNIT = "s"
MOVES = "first_pass_s"
SOURCE = "program_span"


def read(run):
    loads = [e for e in between(run, "catalog_load", FIRST) if "read_ms" in e]
    if not loads:
        return None
    return sum(e["read_ms"] + e["encode_ms"] for e in loads) / 1e3
