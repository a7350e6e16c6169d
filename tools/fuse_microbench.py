"""CI microbench guard: fused-pipeline executable reuse across a stream,
plus a measured dispatch-count reduction from aggregate-tail fusion,
plus the TWO-PROCESS persistent-AOT-cache gate.

Part 1 runs a small synthetic query stream (Filter/Project chains AND
agg-chain shapes) TWICE in one session — first pass untraced (it compiles
the executables), second pass traced — then gates on the profiler's
executable-cache hit rate over the traced pass:

    python tools/fuse_microbench.py        # exits nonzero below 80%

A steady-state re-run of a stream must reuse the compiled pipelines (the
whole point of shape-bucketed executable reuse); a refactor that silently
changes pipeline fingerprints, input signatures, or the cache keying drops
the rate to ~0 and fails this gate.

Part 2 measures steady-state device-dispatch counts (the launch seam's
tally: `op_span.launches`, kernel entry points, seamed gathers and fused
pipeline calls) for the plan
shapes of the bench's tail queries — the multi-key grouped sum/avg chain
(q4/q14's year_total), the global filtered aggregate (q9's bucket
probes), and the join-fed grouped sum (q78) — eager vs fused, and
requires the fused path to dispatch strictly fewer times on every shape.

Part 3 is the cold-start kill gate (ISSUE 11): process A runs the stream
against a fresh AOT cache dir (engine/aotcache.py) — compiling and
SERIALIZING every pipeline executable — then a separate process B runs
the same stream cold against the same dir with the XLA persistent cache
disabled. B's cold pass must resolve its executables FROM DISK (>= 80%
aot_cache disk-hit rate, read from B's trace events) and land within
1.15x of A's steady-pass wall (NDS_AOT_MB_MAX_RATIO; a small absolute
grace, NDS_AOT_MB_GRACE_S, absorbs constant per-process overhead like
tracing and table upload — recompiles cost seconds, not fractions).
All three are wired into ci/tier1-check.
"""

import os
import sys
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402

MIN_HIT_RATE = float(os.environ.get("NDS_FUSE_MICROBENCH_MIN_RATE", "0.8"))

# a miniature "stream": the chain shapes the fuser must keep compiled —
# numeric filters, string predicates over dictionaries, computed
# projections, chains feeding aggregates, post-join wrappers, sort+limit
STREAM = [
    "select k, v from t where v > 10 and k is not null order by k, v",
    "select k, v * 2 vv, cat from t where cat like 'B%' order by k, vv",
    "select k, sum(v) sv, avg(v) av from t where v > -50 group by k "
    "order by k",
    "select x.k, x.s from (select t.k \"k\", t.v + u.v s from t, u "
    "where t.k = u.k and t.v > u.v) x where x.s > 5 order by x.k, x.s "
    "limit 20",
    "select k, case when v > 0 then v else -v end a from t "
    "where cat in ('Books', 'Shoes') order by k, a limit 50",
    # agg-chain shapes: the aggregate tail must compile INTO the pipeline
    # and its executable must be reused on the second pass
    "select k, k2, sum(v) s, count(*) c from t where v > -60 "
    "group by k, k2 order by k, k2",
    "select count(*) c, avg(v) a, sum(v) s from t where v between 0 and 40",
]

# steady-state dispatch A/B: synthetic stand-ins for the tail queries'
# plan shapes (same operator chains, toy data) — eager must dispatch more
TAIL_SHAPES = {
    # q4/q14 year_total: filter + computed projection feeding a multi-key
    # grouped sum/avg
    "q4_year_total": (
        "select k, k2, sum(v) s, avg(v) a, count(*) c from t "
        "where v > -50 and k is not null group by k, k2 order by k, k2"
    ),
    # q9: ranged global aggregates over the fact scan
    "q9_global": (
        "select count(*) c, avg(v) a, sum(v) s from t "
        "where v between 0 and 40"
    ),
    # q78: join output feeding a grouped sum
    "q78_join_group": (
        "select t.k, sum(t.v) sv, sum(u.v) uv from t, u "
        "where t.k = u.k group by t.k order by t.k"
    ),
}


def _table(n, seed):
    r = np.random.default_rng(seed)
    ks = r.integers(0, 12, n)
    k2s = r.integers(0, 6, n)
    vs = r.integers(-90, 90, n)
    return pa.table(
        {
            "k": pa.array(
                [None if i % 9 == 0 else int(x) for i, x in enumerate(ks)],
                pa.int32(),
            ),
            "k2": pa.array(k2s, pa.int32()),
            "v": pa.array(vs, pa.int64()),
            "cat": pa.array(
                [["Books", "Music", "Shoes"][int(x) % 3] for x in ks],
                pa.string(),
            ),
        }
    )


def _steady_dispatches(query, fuse_conf, trace_dir):
    """Counted device dispatches of one steady-state execution: the
    statement's launch tally (`launches` of its op_spans and result_span:
    kernel entry points, seamed gathers, one per fused pipeline call). An
    undercount of the eager path (per-stage elementwise ops are not kernel
    entry points) — which only makes the fused<eager assertion stricter."""
    from nds_tpu.engine.session import Session
    from nds_tpu.obs import reader as R
    from nds_tpu.obs import trace as obs_trace

    sess = Session(conf=dict(fuse_conf, **{
        "engine.plan_cache": "off",
        "engine.trace_dir": trace_dir,
    }))
    sess.register_arrow("t", _table(3000, 1))
    sess.register_arrow("u", _table(3000, 2))
    warm_tracer, sess.tracer = sess.tracer, None
    sess.sql(query).collect()  # cold: compiles; dispatches untraced
    sess.tracer = warm_tracer
    with obs_trace.bind(sess.tracer):
        sess.sql(query).collect()  # steady: every dispatch traced
    sess.tracer.close()
    events = R.read_events([trace_dir], strict=True)
    return sum(
        sum((ev.get("launches") or {}).values())
        for ev in events if ev.get("kind") in ("op_span", "result_span")
    )


def dispatch_ab():
    """Eager-vs-fused steady dispatch counts per tail shape; fails unless
    the fused path dispatches strictly fewer times on EVERY shape."""
    import tempfile

    failures = []
    for name, q in TAIL_SHAPES.items():
        with tempfile.TemporaryDirectory(prefix="nds_mb_e_") as de, \
                tempfile.TemporaryDirectory(prefix="nds_mb_f_") as df:
            eager = _steady_dispatches(q, {"engine.fuse": "off"}, de)
            fused = _steady_dispatches(q, {}, df)
        verdict = "OK" if fused < eager else "NO REDUCTION"
        print(f"fuse_microbench: {name}: eager {eager} -> fused {fused} "
              f"dispatches ({verdict})")
        if fused >= eager:
            failures.append(name)
    if failures:
        print(
            f"fuse_microbench: FAILED (no steady dispatch reduction on: "
            f"{', '.join(failures)})",
            file=sys.stderr,
        )
        sys.exit(1)


def _aot_table(n, seed):
    """Fact-shaped tables for the two-process gate: the same columns as
    _table, but the join key's cardinality scales with n (a 12-value key
    at gate scale would make the t-join-u shape quadratic) — steady-state
    work stays meaningful next to the constant per-process overheads the
    wall-ratio gate must not be dominated by."""
    r = np.random.default_rng(seed)
    kdom = max(12, n // 16)
    ks = r.integers(0, kdom, n)
    return pa.table(
        {
            "k": pa.array(
                [None if i % 9 == 0 else int(x) for i, x in enumerate(ks)],
                pa.int32(),
            ),
            "k2": pa.array(r.integers(0, 6, n), pa.int32()),
            "v": pa.array(r.integers(-90, 90, n), pa.int64()),
            "cat": pa.array(
                [["Books", "Music", "Shoes"][int(x) % 3] for x in ks],
                pa.string(),
            ),
        }
    )


def aot_child_main():
    """One process of the two-process AOT gate (NDS_MB_AOT_ROLE=child):
    run the stream cold (wall-timed), then steady (plan cache off so every
    pipeline really executes), and report walls + the session's AOT cache
    stats as one JSON line on stdout."""
    import json
    import time

    from nds_tpu.engine.session import Session

    rows = int(os.environ.get("NDS_AOT_MB_ROWS", "200000"))
    sess = Session(conf={
        "engine.aot_cache_dir": os.environ["NDS_MB_CACHE_DIR"],
        "engine.trace_dir": os.environ["NDS_MB_TRACE_DIR"],
    })
    sess.register_arrow("t", _aot_table(rows, 1))
    sess.register_arrow("u", _aot_table(rows, 2))
    t0 = time.perf_counter()
    for q in STREAM:
        sess.sql(q).collect()
    cold_wall = time.perf_counter() - t0
    sess.conf["engine.plan_cache"] = "off"
    t0 = time.perf_counter()
    for q in STREAM:
        sess.sql(q).collect()
    steady_wall = time.perf_counter() - t0
    if sess.tracer is not None:
        sess.tracer.close()
    print(json.dumps({
        "cold_wall": cold_wall,
        "steady_wall": steady_wall,
        "aot": dict(sess.aot_cache.stats) if sess.aot_cache else None,
    }), flush=True)


def _run_aot_child(cache_dir, trace_dir, xla_cache_dir):
    import json
    import subprocess

    env = dict(os.environ)
    env["NDS_MB_AOT_ROLE"] = "child"
    env["NDS_MB_CACHE_DIR"] = cache_dir
    env["NDS_MB_TRACE_DIR"] = trace_dir
    # the gate models the PRODUCTION cold-start pair: this engine's AOT
    # cache serves the fused-pipeline executables (trace-verified below —
    # the XLA cache cannot produce aot_cache hit events) while a shared
    # XLA persistent cache covers the canonical kernels (sort/join/agg
    # entry points) the AOT layer deliberately does not own. A fresh
    # temp dir per gate run keeps both halves honest: nothing is warm
    # until process A warms it.
    env["JAX_COMPILATION_CACHE_DIR"] = xla_cache_dir
    # persist even sub-100ms kernel compiles: on CPU the canonical
    # kernels each compile in ~10ms, and 100+ of them ARE the cold start
    env["NDS_XLA_CACHE_MIN_COMPILE_S"] = "0"
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if p.returncode != 0:
        print(p.stdout, file=sys.stderr)
        print(p.stderr[-2000:], file=sys.stderr)
        raise RuntimeError(f"aot child exited rc={p.returncode}")
    for line in reversed(p.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError("aot child produced no JSON line")


def two_process_aot():
    """Process A warms the shared cache dir; a FRESH process B's cold pass
    must deserialize from disk (>= 80% aot disk-hit rate, trace-event
    evidence) and land within NDS_AOT_MB_MAX_RATIO (1.15) of A's steady
    wall (+ a small constant grace for per-process setup)."""
    import tempfile

    from nds_tpu.obs import reader as R

    # the wall bound: max(ratio x steady, steady + grace). The ratio is
    # the headline contract (recompiles cost SECONDS); the grace absorbs
    # the constant per-process cost a warmed process still pays at gate
    # scale — kernel re-tracing, catalog upload, disk loads — measured at
    # ~1.8s on the 1-core CI host against a ~2.7s steady pass. The teeth
    # check below proves the bound still catches an UNWARMED process.
    max_ratio = float(os.environ.get("NDS_AOT_MB_MAX_RATIO", "1.15"))
    grace_s = float(os.environ.get("NDS_AOT_MB_GRACE_S", "2.5"))
    min_rate = float(os.environ.get("NDS_AOT_MB_MIN_RATE", "0.8"))
    with tempfile.TemporaryDirectory(prefix="nds_mb_aot_") as root:
        cache_dir = os.path.join(root, "cache")
        xla_dir = os.path.join(root, "xla")
        trace_a = os.path.join(root, "trace_a")
        trace_b = os.path.join(root, "trace_b")
        a = _run_aot_child(cache_dir, trace_a, xla_dir)
        b = _run_aot_child(cache_dir, trace_b, xla_dir)
        prof_b = R.load_profile([trace_b], strict=True)
        rate = R.aot_disk_hit_rate(prof_b)
        print(
            f"fuse_microbench: aot two-process: A cold {a['cold_wall']:.2f}s "
            f"steady {a['steady_wall']:.2f}s; B cold {b['cold_wall']:.2f}s; "
            f"B disk-hit rate "
            f"{'-' if rate is None else f'{rate:.1%}'} (stats {b['aot']})"
        )
        failures = []
        if rate is None or rate < min_rate:
            failures.append(
                f"fresh process resolved executables from disk at rate "
                f"{rate if rate is None else round(rate, 3)} < {min_rate} "
                f"(cold start still recompiles)"
            )
        bound = max(max_ratio * a["steady_wall"], a["steady_wall"] + grace_s)
        if b["cold_wall"] > bound:
            failures.append(
                f"warmed cold wall {b['cold_wall']:.2f}s exceeds "
                f"{bound:.2f}s (= max({max_ratio} x steady, steady + "
                f"{grace_s}s))"
            )
        if a["cold_wall"] <= bound:
            # teeth check: the UNWARMED process A must exceed the bound,
            # or this gate could pass with the cache doing nothing.
            # Informational (A's cold cost shrinks as compiles get
            # cheaper, which is not a defect) — but visible in CI logs.
            print(
                f"fuse_microbench: WARNING: aot gate bound {bound:.2f}s "
                f"would not catch the unwarmed cold wall "
                f"{a['cold_wall']:.2f}s (gate losing teeth)",
                file=sys.stderr,
            )
        if failures:
            for f in failures:
                print(f"fuse_microbench: FAILED ({f})", file=sys.stderr)
            sys.exit(1)


def main():
    from nds_tpu.engine.session import Session
    from nds_tpu.obs.trace import tracer_from_conf

    with tempfile.TemporaryDirectory(prefix="nds_fuse_mb_") as trace_dir:
        sess = Session()
        sess.register_arrow("t", _table(3000, 1))
        sess.register_arrow("u", _table(3000, 2))
        # pass 1 (untraced): compile the stream's pipeline executables
        for q in STREAM:
            sess.sql(q).collect()
        # pass 2 (traced, plan-result cache off so every pipeline really
        # executes): must ride the executable cache
        sess.conf["engine.plan_cache"] = "off"
        sess.tracer = tracer_from_conf({"engine.trace_dir": trace_dir})
        for q in STREAM:
            sess.sql(q).collect()
        sess.tracer.close()

        from nds_tpu.cli import profile as profile_cli

        try:
            profile_cli.main(
                [
                    trace_dir,
                    "--check",
                    "--min_exec_cache_hit_rate",
                    str(MIN_HIT_RATE),
                ]
            )
        except SystemExit as exc:
            code = int(exc.code or 0)
            if code:
                print(
                    f"fuse_microbench: FAILED (profiler gate exit {code})",
                    file=sys.stderr,
                )
                sys.exit(code)
    dispatch_ab()
    two_process_aot()
    print("fuse_microbench: OK")


if __name__ == "__main__":
    if os.environ.get("NDS_MB_AOT_ROLE") == "child":
        aot_child_main()
    else:
        main()
