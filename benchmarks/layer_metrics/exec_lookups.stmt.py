"""Executables a window statement had to look up again although its session
had run the statement before: executable-cache misses per statement, each
answered by the AOT disk cache or by a compile request that jax's
persistent cache serves. Parquet: query36 alone (its pipelines are keyed by
a dictionary rebuilt at every execution). Lakehouse: every statement, since
a pruned scan hands the pipelines new tables."""

LAYER = "executor + fused pipelines"
UNIT = "lookups/stmt"
MOVES = "stmt_p50_ms"
SOURCE = "program_counter"


def read(run):
    stmts = run.get("statements")
    if not stmts:
        return None
    return sum(s["new_shapes"] for s in stmts) / len(stmts)
