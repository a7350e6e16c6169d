"""Dev tool: profile per-jit compile time for one query on the real chip.

Usage: python profile_compile.py query34 [query22 ...]
Runs each query cold (fresh in-process cache; JAX_COMPILATION_CACHE_DIR should
point somewhere empty to measure true cold) and logs every XLA compile with its
duration, sorted descending.
"""
import logging
import os
import sys
import time

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/nds_profile_cache")

import jax

jax.config.update("jax_log_compiles", True)

records = []


class Handler(logging.Handler):
    def emit(self, record):
        msg = record.getMessage()
        records.append((time.perf_counter(), msg))


for name in ("jax._src.interpreters.pxla", "jax._src.dispatch",
             "jax._src.compiler", "jax"):
    lg = logging.getLogger(name)
    lg.setLevel(logging.DEBUG)
    lg.addHandler(Handler())

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nds_tpu.engine.session import Session  # noqa: E402
from nds_tpu.schema import get_schemas  # noqa: E402
from nds_tpu.datagen.query_streams import generate_streams  # noqa: E402
from nds_tpu.power import gen_sql_from_stream  # noqa: E402
import tempfile  # noqa: E402

DATA_DIR = os.environ.get("NDS_BENCH_DATA", "/tmp/nds_bench_sf1.0")

with tempfile.TemporaryDirectory() as d:
    generate_streams(d, 1, 1, rngseed=19620718)
    queries = gen_sql_from_stream(os.path.join(d, "query_0.sql"))

sess = Session()
for t, schema in get_schemas().items():
    path = os.path.join(DATA_DIR, t)
    if os.path.isdir(path):
        sess.register_csv_dir(t, path, schema)

for qname in sys.argv[1:]:
    records.clear()
    t0 = time.perf_counter()
    r = sess.run_script(queries[qname])
    if r is not None:
        r.collect()
    total = time.perf_counter() - t0
    print(f"\n=== {qname}: total {total:.1f}s, {len(records)} log events ===")
    # pair "Finished XLA compilation of X in Y sec" lines with the most
    # recent "Compiling <name> with global shapes and types [...]" line
    compiles = []
    last_shapes = ""
    for ts, msg in records:
        if "global shapes and types" in msg:
            last_shapes = msg.split("global shapes and types", 1)[1][:180]
        if "Finished XLA compilation" in msg:
            try:
                head, tail = msg.rsplit(" in ", 1)
                secs = float(tail.split(" sec")[0])
                nm = head.split("Finished XLA compilation of ", 1)[1]
                compiles.append((secs, nm + " " + last_shapes))
            except Exception:
                print("??", msg[:200])
    compiles.sort(reverse=True)
    print(f"compiles: {len(compiles)}, sum {sum(s for s, _ in compiles):.1f}s")
    for secs, nm in compiles[:25]:
        print(f"  {secs:8.2f}s  {nm[:220]}")
