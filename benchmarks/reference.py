"""The plain reference: sqlite3 over the generator's raw `.dat` files.

A child of `run.py`, held to the CPU, that imports nothing of the program
and takes nothing the program has made except the seeded raw data, which is
the benchmark's input as weights are a model's. It loads the tables (and
only the columns) the statements name into an in-memory sqlite database
from `reference/tpcds_columns.json`, lowers each statement from the engine's
dialect to sqlite's, runs it, and writes the answer as
`<out>/<key>/part-0.parquet`, the layout `compare.py` reads.

    python benchmarks/reference.py <raw_dir> <statements.json> <out_dir>
        [--control float32]

sqlite answers one statement at a time on one core, and most of a child's
seconds go into loading the tables: `run.py` starts one child a template,
side by side into one `<out_dir>`, each over the few columns its own
statements name. What a child loaded and the seconds it took are the last
line of its output (its log).

`--control float32` is the comparison's control: the same reference with
every SUM and AVG accumulated in float32, the precision a later PR would be
tempted to aggregate in on a chip whose 64-bit arithmetic is emulated. Its
answers, put in the program's place, have to come out as not correct.

The dialect lowering is a copy of `tests/test_oracle.py`'s `_to_sqlite`,
`_lower_rollup` and `_StddevSamp`, and the loader of
`tools/sqlite_anchor.py`'s `load` (PERF.md lists the originals).
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sqlite3
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


# -- dialect -----------------------------------------------------------------

def _depth_profile(s):
    out, d = [], 0
    for c in s:
        if c == "(":
            d += 1
        elif c == ")":
            d -= 1
        out.append(d)
    return out


def lower_rollup(sql):
    """GROUP BY ROLLUP(k1..kk) -> UNION ALL of the k+1 GROUP BY prefixes,
    rolled-away keys replaced by NULL and grouping(ki) by 0/1 (sqlite has
    no GROUPING SETS). Keys are plain identifiers in every TPC-DS rollup
    template; a window partitioned by grouping() levels stays correct
    because each branch is exactly one level."""
    low = sql.lower()
    m = re.search(r"group\s+by\s+rollup\s*\(", low)
    if m is None:
        return sql
    depth = _depth_profile(low)
    gdepth = depth[m.start()]
    kstart = low.index("(", m.start())
    kend = kstart
    while not (low[kend] == ")" and depth[kend] == gdepth):
        kend += 1
    keys = [k.strip() for k in sql[kstart + 1:kend].split(",")]
    sel = None
    for sm in re.finditer(r"\bselect\b", low):
        if sm.start() < m.start() and depth[sm.start()] == gdepth:
            sel = sm.start()
    if sel is None:
        raise ValueError("ROLLUP without an owning SELECT")
    end = len(sql)
    j = kend + 1
    while j < len(sql):
        if low[j] == ")" and depth[j] < gdepth:
            end = j
            break
        if depth[j] == gdepth and re.match(r"order\s+by\b|limit\b", low[j:]):
            end = j
            break
        j += 1
    if sql[kend + 1:end].strip():
        raise ValueError(
            f"unsupported clause between ROLLUP and block end: "
            f"{sql[kend + 1:end]!r}"
        )
    head = sql[sel:m.start()]
    hlow = head.lower()
    hdepth = _depth_profile(hlow)
    fpos = next(fm.start() for fm in re.finditer(r"\bfrom\b", hlow)
                if hdepth[fm.start()] == 0)
    select_list = head[len("select"):fpos]
    from_where = head[fpos:]
    branches = []
    for p in range(len(keys), -1, -1):
        sl = select_list
        for ki, k in enumerate(keys):
            g = "0" if ki < p else "1"
            sl = re.sub(rf"grouping\s*\(\s*{re.escape(k)}\s*\)", g, sl,
                        flags=re.I)
        for k in keys[p:]:
            sl = re.sub(rf"\b{re.escape(k)}\b", "null", sl, flags=re.I)
        gb = f" group by {', '.join(keys[:p])}" if p else ""
        branches.append(f"select {sl} {from_where}{gb}")
    union = " union all ".join(branches)
    if end < len(sql) and sql[end] == ")":
        lowered = sql[:sel] + union + sql[end:]
    else:
        lowered = sql[:sel] + f"select * from ({union}) " + sql[end:]
    return lower_rollup(lowered)


def to_sqlite(sql):
    """Lower the engine's dialect into sqlite's. Dates live as ISO strings
    in the sqlite tables, so date(...) results compare lexicographically ==
    chronologically."""
    s = lower_rollup(sql)
    # cast(expr as date) -> date(expr): sqlite's CAST has numeric affinity
    s = re.sub(r"cast\s*\(\s*('[^']*'|[\w.]+)\s+as\s+date\s*\)",
               lambda m: f"date({m.group(1)})", s, flags=re.I)
    s = re.sub(r"\bdate\s+'([^']+)'", r"'\1'", s, flags=re.I)
    # cast(x as decimal(p,s)) -> cast(x as real): sqlite's decimal cast keeps
    # INTEGER affinity, so int/int ratios would integer-divide
    s = re.sub(
        r"cast\s*\(\s*([^()]+?)\s+as\s+decimal\s*\(\s*\d+\s*,\s*\d+\s*\)\s*\)",
        r"cast(\1 as real)", s, flags=re.I)
    operand = r"(date\([^()]*(?:\([^()]*\))?[^()]*\)|'[^']*'|[\w.]+)"
    s = re.sub(operand + r"\s*([+-])\s*interval\s+(\d+)\s+days?",
               lambda m: f"date({m.group(1)}, '{m.group(2)}{m.group(3)} days')",
               s, flags=re.I)
    return s


def statement_of(entry):
    """The one SELECT of a stream entry, lowered."""
    return next(s for s in to_sqlite(entry).split(";") if "select" in s.lower())


class StddevSamp:
    """Sample standard deviation (sqlite ships none)."""

    def __init__(self):
        self.vals = []

    def step(self, v):
        if v is not None:
            self.vals.append(float(v))

    def finalize(self):
        n = len(self.vals)
        if n < 2:
            return None
        mean = sum(self.vals) / n
        return math.sqrt(sum((x - mean) ** 2 for x in self.vals) / (n - 1))


def float32_aggregates():
    """SUM and AVG that accumulate in float32: the control."""
    import numpy as np

    f32 = np.float32

    class Sum32:
        def __init__(self):
            self.acc, self.n = f32(0), 0

        def step(self, v):
            if v is not None:
                self.acc = f32(self.acc + f32(v))
                self.n += 1

        def finalize(self):
            return float(self.acc) if self.n else None

    class Avg32(Sum32):
        def finalize(self):
            return float(f32(self.acc / f32(self.n))) if self.n else None

    return Sum32, Avg32


# -- data --------------------------------------------------------------------

def load(conn, raw_dir, text):
    """Create, fill and index the tables `text` names, with the columns it
    names, from the generator's pipe-delimited chunk files. A statement that
    reads a column without naming it (`select *` over a base table) fails
    in sqlite with `no such column`, loudly."""
    import pyarrow as pa
    import pyarrow.csv as pacsv

    with open(os.path.join(HERE, "reference", "tpcds_columns.json")) as f:
        schema = json.load(f)
    arrow_type = {"int": pa.int64(), "decimal": pa.float64()}
    loaded = {}
    for table, cols in schema.items():
        if not re.search(rf"\b{table}\b", text):
            continue
        want = [c for c, _ in cols if re.search(rf"\b{c}\b", text)]
        files = sorted(glob.glob(os.path.join(raw_dir, table, "*.dat")))
        if not files:
            raise FileNotFoundError(f"no .dat files for {table} under {raw_dir}")
        conn.execute(f"create table {table} ({', '.join(want)})")
        ph = ",".join("?" * len(want))
        rows = 0
        for path in files:
            if os.path.getsize(path) == 0:
                continue
            # rows end with a trailing '|': a phantom last column; the empty
            # string is NULL; dates are ISO strings in the file already
            t = pacsv.read_csv(
                path,
                read_options=pacsv.ReadOptions(
                    column_names=[c for c, _ in cols] + ["_trailing"]),
                parse_options=pacsv.ParseOptions(delimiter="|"),
                convert_options=pacsv.ConvertOptions(
                    column_types={c: arrow_type.get(k, pa.string())
                                  for c, k in cols},
                    include_columns=want, strings_can_be_null=True,
                    quoted_strings_can_be_null=True),
            )
            for batch in t.to_batches(max_chunksize=1 << 17):
                conn.executemany(
                    f"insert into {table} values ({ph})",
                    zip(*[c.to_pylist() for c in batch.columns]))
            rows += t.num_rows
        # sqlite joins by nested loops: without an index on every surrogate
        # key a fact-fact join runs for hours
        for c in want:
            if c.endswith("_sk") or c.endswith("_number"):
                conn.execute(f"create index idx_{table}_{c} on {table}({c})")
        loaded[table] = rows
    conn.execute("analyze")
    conn.commit()
    return loaded


def run(raw_dir, statements, out_dir, control=None):
    """Answer `statements` ({key: stream entry}) into `out_dir`."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    lowered = {k: statement_of(v) for k, v in statements.items()}
    conn = sqlite3.connect(":memory:")
    conn.create_aggregate("stddev_samp", 1, StddevSamp)
    if control == "float32":
        sum32, avg32 = float32_aggregates()
        conn.create_aggregate("sum", 1, sum32)
        conn.create_aggregate("avg", 1, avg32)
    elif control is not None:
        raise ValueError(f"unknown control {control!r}")
    t0 = time.perf_counter()
    tables = load(conn, raw_dir, "\n".join(lowered.values()).lower())
    info = {"control": control, "tables": tables,
            "load_s": time.perf_counter() - t0, "query_s": {}, "rows": {}}
    for key, sql in lowered.items():
        t0 = time.perf_counter()
        cur = conn.execute(sql)
        rows = cur.fetchall()
        info["query_s"][key] = time.perf_counter() - t0
        info["rows"][key] = len(rows)
        names = [f"c{i}" for i in range(len(cur.description))]
        os.makedirs(os.path.join(out_dir, key), exist_ok=True)
        pq.write_table(
            pa.table({n: pa.array(list(col)) for n, col in zip(
                names, zip(*rows) if rows else [[] for _ in names])}),
            os.path.join(out_dir, key, "part-0.parquet"))
    conn.close()
    return info


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("raw_dir")
    ap.add_argument("statements", help="JSON {key: stream entry}")
    ap.add_argument("out_dir")
    ap.add_argument("--control", choices=["float32"])
    args = ap.parse_args(argv)
    with open(args.statements) as f:
        statements = json.load(f)
    info = run(args.raw_dir, statements, args.out_dir, args.control)
    print(json.dumps(info))
    if "jax" in sys.modules:
        raise SystemExit("the reference imported jax")


if __name__ == "__main__":
    main()
