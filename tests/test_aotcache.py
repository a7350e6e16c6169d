"""Persistent AOT executable cache (engine/aotcache.py): the contract is
"a mismatched or damaged cache can cost a recompile, never a wrong result
or a crash" — every test here is one face of that, plus the fleet
behaviors (two-process warm, orphan sweep, eviction accounting,
promotion-memo persistence) ISSUE 11 requires."""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from nds_tpu import faults
from nds_tpu.engine import aotcache as AC
from nds_tpu.engine.session import Session


@pytest.fixture(autouse=True)
def _hermetic_xla_cache(tmp_path_factory):
    """Pin the XLA persistent compilation cache to a fresh directory per
    TEST: an executable LOADED from a warm XLA cache serializes into an
    unreloadable payload (the store-time validation skips it), so any
    warm XLA cache — the ambient ~/.cache/nds_xla or even this module's
    own previous test — would make store/hit assertions order-dependent.
    A fresh dir means every compile here is real and every store
    validates."""
    import contextlib

    from nds_tpu.engine import session as S

    # trip the Session-construction once-latch FIRST: otherwise the first
    # Session built inside a test re-points the cache at the ambient
    # (possibly warm) default, silently overriding the pin below
    S._enable_persistent_compile_cache()
    prev = None
    with contextlib.suppress(Exception):
        prev = jax.config.jax_compilation_cache_dir
    jax.config.update(
        "jax_compilation_cache_dir",
        str(tmp_path_factory.mktemp("xla_cache")),
    )
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _table(n=2000, seed=1):
    r = np.random.default_rng(seed)
    ks = r.integers(0, 12, n)
    return pa.table({
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
    })


def _session(tmp_path, **conf):
    sess = Session(conf={
        "engine.aot_cache_dir": str(tmp_path / "aot"), **conf,
    })
    sess.register_arrow("t", _table())
    return sess


def _tiny_compiled(mul=2.0):
    fn = lambda x: x * mul + 1.0  # noqa: E731
    return jax.jit(fn).lower(
        jax.ShapeDtypeStruct((16,), jnp.float32)
    ).compile()


def _cache(tmp_path, budget=1 << 30):
    return AC.AotCache(str(tmp_path / "aot"), budget)


def _key(cache, tag="a", cap=16):
    return cache.entry_key(
        "pipeline", f"fp-{tag}", [("live", False)],
        [((cap,), "float32")], (), ("on", "off"),
    )


# string PREDICATE but no string GROUP KEY: dictionary work runs at trace
# time, so this agg-tail executable serializes on the CPU backend (a
# string-keyed aggregate bakes rank tables whose executable does not —
# store-time validation keeps such shapes on the in-process path)
QUERY = (
    "select k, k2, sum(v) s, count(*) c from t "
    "where v > -50 and cat like 'B%' group by k, k2 order by k, k2"
)


# ---------------------------------------------------------------------------
# roundtrip + key discipline
# ---------------------------------------------------------------------------


def test_roundtrip_equality_vs_in_process_build(tmp_path):
    """A fresh session resolving from disk returns EXACTLY what the
    compiling session returned — serialize/deserialize is semantically
    invisible."""
    s1 = _session(tmp_path)
    ref = s1.sql(QUERY).collect().to_pylist()
    assert s1.aot_cache.stats["stores"] >= 1
    assert s1.aot_cache.stats["disk_hits"] == 0

    s2 = _session(tmp_path)
    out = s2.sql(QUERY).collect().to_pylist()
    assert out == ref
    assert s2.aot_cache.stats["disk_hits"] >= 1
    assert s2.aot_cache.stats["misses"] == 0


def test_environment_key_mismatch_is_clean_miss(tmp_path):
    """Any environment drift — jax version, device kind, conf flip — is a
    MISS, and the mismatched (valid) entry is left in place, never
    quarantined: another environment may still own it."""
    cache = _cache(tmp_path)
    key = _key(cache)
    assert cache.store(key, _tiny_compiled())
    assert cache.load(key) is not None

    for mutate in (
        lambda k: k["env"].__setitem__("jax", "0.0.1"),
        lambda k: k["env"].__setitem__("device_kind", "tpu-v9"),
        lambda k: k.__setitem__("conf", ["off", "off"]),
        lambda k: k.__setitem__("fp", "fp-other"),
    ):
        skew = json.loads(json.dumps(key))
        mutate(skew)
        assert cache.load(skew) is None
    # the original entry survived every mismatched probe
    assert cache.load(key) is not None
    assert cache.stats["quarantined"] == 0


@pytest.mark.parametrize("writer", ["parent", "revision_1"])
def test_an_older_aggregate_body_s_entry_is_a_clean_miss(
        tmp_path, monkeypatch, writer):
    """`FusedAggPipeline._run_agg`'s body is in no other part of the key,
    so its revision is (`BODY_REV`, in the kind): a directory warmed by a
    tree whose keyless tail scattered (the parent wrote kind
    `agg_pipeline`) must not answer for the tail that reduces whole: a
    clean miss, the old entry left where it is."""
    from nds_tpu.engine import fuse

    q = "select count(*) c, sum(v) s, avg(v) a from t where v > -50"
    init_aot = fuse._FusedBase._init_aot
    with monkeypatch.context() as m:
        if writer == "parent":
            m.setattr(
                fuse._FusedBase, "_init_aot",
                lambda self, aot, fp, conf_sig, sample, kind, with_stats:
                init_aot(self, aot, fp, conf_sig, sample,
                         kind.split(".")[0], with_stats),
            )
        else:
            m.setattr(fuse.FusedAggPipeline, "BODY_REV", 1)
        old = _session(tmp_path)
        ref = old.sql(q).collect().to_pylist()
        assert old.aot_cache.stats["stores"] >= 1
        # the same body finds its entry again: the directory is warm
        again = _session(tmp_path)
        assert again.sql(q).collect().to_pylist() == ref
        assert again.aot_cache.stats["disk_hits"] >= 1
        assert again.aot_cache.stats["misses"] == 0

    new = _session(tmp_path)
    assert new.sql(q).collect().to_pylist() == ref
    assert new.aot_cache.stats["disk_hits"] == 0
    assert new.aot_cache.stats["misses"] >= 1
    assert new.aot_cache.stats["quarantined"] == 0


def test_filename_collision_reads_as_miss_not_wrong_load(tmp_path):
    """A file whose NAME matches but whose recorded key differs (hash
    collision / foreign entry) must read as a miss: load verifies the
    full key dict, not the filename."""
    cache = _cache(tmp_path)
    key = _key(cache, "a")
    other = _key(cache, "b")
    assert cache.store(other, _tiny_compiled())
    # graft other's entry onto key's filename
    os.makedirs(cache.dir, exist_ok=True)
    os.replace(
        os.path.join(cache.dir, AC._entry_name(other)),
        os.path.join(cache.dir, AC._entry_name(key)),
    )
    assert cache.load(key) is None
    assert cache.stats["quarantined"] == 0


# ---------------------------------------------------------------------------
# corruption: clean miss + quarantine, never a crash
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("damage", ["truncate", "flip", "garbage", "empty"])
def test_corrupt_entry_is_quarantined_miss(tmp_path, damage):
    cache = _cache(tmp_path)
    key = _key(cache)
    assert cache.store(key, _tiny_compiled())
    path = os.path.join(cache.dir, AC._entry_name(key))
    raw = open(path, "rb").read()
    if damage == "truncate":
        blob = raw[: len(raw) // 2]  # torn write shape
    elif damage == "flip":
        mid = len(raw) - 20  # inside the pickled body: checksum must trip
        blob = raw[:mid] + bytes([raw[mid] ^ 0xFF]) + raw[mid + 1:]
    elif damage == "garbage":
        blob = b"not an entry at all"
    else:
        blob = b""
    with open(path, "wb") as f:
        f.write(blob)

    assert cache.load(key) is None  # never a crash
    assert cache.stats["quarantined"] == 1
    assert not os.path.exists(path)  # moved aside, not left to re-trip
    quarantined = [
        n for n in os.listdir(cache.dir) if n.startswith("quarantine-")
    ]
    assert len(quarantined) == 1
    # the slot is reusable immediately
    assert cache.store(key, _tiny_compiled())
    assert cache.load(key) is not None


def test_loaded_executable_runs_on_its_own_devices(tmp_path):
    """An entry is loaded for the devices it was compiled for, not for
    every local device: on this 8-device CPU mesh the latter makes the
    call expect eight shards of each argument (jax 0.9.0)."""
    cache = _cache(tmp_path)
    key = _key(cache)
    assert cache.store(key, _tiny_compiled())
    loaded = cache.load(key)
    out = loaded(jnp.arange(16, dtype=jnp.float32))
    assert out.tolist() == [2.0 * i + 1.0 for i in range(16)]
    assert cache.stats["call_failures"] == 0


def test_call_time_failure_is_counted_and_quarantined(tmp_path):
    """fuse._dispatch recompiles when a loaded executable raises; the
    cache must not let that pass in silence."""
    from nds_tpu.obs.trace import Tracer

    tracer = Tracer()
    cache = AC.AotCache(str(tmp_path / "aot"), 1 << 30, tracer=tracer)
    key = _key(cache)
    assert cache.store(key, _tiny_compiled())
    cache.quarantine_key(key)
    assert cache.stats["call_failures"] == 1
    assert cache.stats["quarantined"] == 1
    assert cache.load(key) is None  # gone from the committed entries
    assert [
        (e["op"], e["result"]) for e in tracer.events
        if e["kind"] == "aot_cache" and e["op"] == "call"
    ] == [("call", "failed")]


@pytest.mark.parametrize("placed", [True, False])
def test_both_compile_caches_land_under_one_root(tmp_path, monkeypatch,
                                                 placed):
    """`JAX_COMPILATION_CACHE_DIR` set: no directory is set in code and
    the AOT cache is a subdirectory of it. Unset: one fixed directory
    inside the checkout, never one under $HOME."""
    from nds_tpu.engine import session as S

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("NDS_AOT_CACHE_DIR", raising=False)
    root = str(tmp_path) if placed else os.path.join(repo, ".nds_cache")
    assert AC.compile_cache_root() == root
    assert AC.resolve_aot_cache_dir({}) == os.path.join(root, "aot_exec")
    monkeypatch.setattr(S, "_PERSISTENT_CACHE_SET", False)
    jax.config.update("jax_compilation_cache_dir", "untouched")
    S._enable_persistent_compile_cache()
    assert jax.config.jax_compilation_cache_dir == (
        "untouched" if placed else root
    )


def test_executable_served_by_the_xla_cache_is_not_stored(tmp_path):
    """Only what was compiled in this process is persisted: an executable
    jax's own persistent cache served re-serializes into an entry that
    loads and then fails when it runs (XLA:CPU, jax 0.9.0)."""
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        s1 = _session(tmp_path / "a")
        ref = s1.sql(QUERY).collect().to_pylist()
        assert s1.aot_cache.stats["stores"] >= 1
        # an empty AOT dir beside a warm XLA cache
        s2 = _session(tmp_path / "b")
        assert s2.sql(QUERY).collect().to_pylist() == ref
        assert s2.aot_cache.stats["misses"] >= 1
        assert s2.aot_cache.stats["stores"] == 0
    finally:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev
        )


def test_poisoned_entry_end_to_end_recompiles_correctly(tmp_path):
    """The acceptance contract at the session level: corrupt every stored
    entry behind a warmed cache dir — a fresh session must still return
    bit-identical results (recompile path), with the damage visible only
    as quarantine stats."""
    s1 = _session(tmp_path)
    ref = s1.sql(QUERY).collect().to_pylist()
    aot_dir = s1.aot_cache.dir
    entries = [n for n in os.listdir(aot_dir) if n.startswith("aot-")]
    assert entries
    for n in entries:
        with open(os.path.join(aot_dir, n), "r+b") as f:
            f.seek(max(os.path.getsize(os.path.join(aot_dir, n)) - 30, 0))
            f.write(b"\xde\xad\xbe\xef")

    s2 = _session(tmp_path)
    assert s2.sql(QUERY).collect().to_pylist() == ref
    assert s2.aot_cache.stats["quarantined"] >= 1
    assert s2.aot_cache.stats["disk_hits"] == 0


def test_vacuum_removes_quarantines_and_enforces_budget(tmp_path):
    cache = _cache(tmp_path)
    key = _key(cache)
    assert cache.store(key, _tiny_compiled())
    path = os.path.join(cache.dir, AC._entry_name(key))
    with open(path, "wb") as f:
        f.write(b"junk")
    assert cache.load(key) is None  # quarantines
    assert any(
        n.startswith("quarantine-") for n in os.listdir(cache.dir)
    )
    cache.vacuum()
    assert not any(
        n.startswith("quarantine-") for n in os.listdir(cache.dir)
    )
    # drop_all clears committed entries too
    assert cache.store(key, _tiny_compiled())
    cache.vacuum(drop_all=True)
    assert cache.usage() == (0, 0)


# ---------------------------------------------------------------------------
# concurrency + crash hygiene
# ---------------------------------------------------------------------------

_WARM_SCRIPT = textwrap.dedent("""
    import jax
    jax.config.update("jax_platforms", "cpu")
    # match the pytest parent's environment key (x64 + the 8-device CPU
    # host platform come from tests/conftest.py) — the parent asserts it
    # can load what the children stored
    jax.config.update("jax_enable_x64", True)
    import sys
    sys.path.insert(0, {repo!r})
    import jax.numpy as jnp
    from nds_tpu.engine import aotcache as AC

    cache = AC.AotCache({cache_dir!r}, 1 << 30)
    key = cache.entry_key(
        "pipeline", "fp-shared", [("live", False)],
        [((16,), "float32")], (), ("on", "off"),
    )
    compiled = jax.jit(lambda x: x * 2.0).lower(
        jax.ShapeDtypeStruct((16,), jnp.float32)
    ).compile()
    for _ in range(8):
        cache.store(key, compiled)
    loaded = cache.load(key)
    assert loaded is not None
    print("WARMED")
""")


def test_concurrent_two_process_warm_one_winner_no_torn_files(tmp_path):
    """Two processes racing store() on the SAME key: exactly one committed
    entry survives, it is loadable, and no .tmp- staging files leak."""
    cache_dir = str(tmp_path / "aot")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = _WARM_SCRIPT.format(repo=repo, cache_dir=cache_dir)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for _ in range(2)
    ]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        assert "WARMED" in out
    names = os.listdir(cache_dir)
    entries = [n for n in names if n.startswith("aot-") and n.endswith(".bin")]
    assert len(entries) == 1
    assert not any(".tmp-" in n for n in names)
    # the surviving entry is loadable by a third party
    cache = AC.AotCache(cache_dir, 1 << 30)
    key = cache.entry_key(
        "pipeline", "fp-shared", [("live", False)],
        [((16,), "float32")], (), ("on", "off"),
    )
    assert cache.load(key) is not None


def test_orphan_sweep_removes_dead_pid_temps_only(tmp_path):
    cache_dir = tmp_path / "aot"
    cache_dir.mkdir()
    dead = cache_dir / "aot-abc.bin.tmp-999999-aa"
    dead.write_bytes(b"torn")
    live = cache_dir / f"aot-def.bin.tmp-{os.getpid()}-bb"
    live.write_bytes(b"in-flight")
    committed = cache_dir / "aot-abc.bin"
    committed.write_bytes(b"committed")
    foreign = cache_dir / "something-else.tmp-999999-cc"
    foreign.write_bytes(b"foreign")
    removed = AC.sweep_orphans(str(cache_dir))
    assert removed == 1
    assert not dead.exists()
    assert live.exists() and committed.exists() and foreign.exists()


def test_eviction_accounting_lru_to_budget(tmp_path):
    cache = _cache(tmp_path)
    k1, k2, k3 = (_key(cache, t) for t in ("e1", "e2", "e3"))
    assert cache.store(k1, _tiny_compiled(1.0))
    size = cache.usage()[1]
    # room for ~two entries: the third store must evict the LRU one
    cache.budget = int(size * 2.5)
    assert cache.store(k2, _tiny_compiled(2.0))
    # file times tick with the kernel's coarse clock (milliseconds): give
    # the refresh a later tick than k2's write, or the two tie
    time.sleep(0.05)
    assert cache.load(k1) is not None  # refresh k1: k2 becomes LRU
    assert cache.store(k3, _tiny_compiled(3.0))
    n, total = cache.usage()
    assert total <= cache.budget
    assert cache.stats["evictions"] >= 1
    assert cache.load(k2) is None   # the LRU victim
    assert cache.load(k1) is not None
    assert cache.load(k3) is not None


# ---------------------------------------------------------------------------
# fault sites: aot:write / aot:read through the registry
# ---------------------------------------------------------------------------


def test_injected_io_fault_keeps_classifiable_identity(tmp_path):
    cache = _cache(tmp_path)
    key = _key(cache)
    try:
        faults.install("io:aot:write:1")
        with pytest.raises(faults.TransientIOError) as ei:
            cache.store(key, _tiny_compiled())
        assert faults.classify(ei.value) == faults.IO_TRANSIENT
        # the rule disarmed after one fire: the retry (the ladder's
        # io_backoff rung re-running the query) succeeds
        assert cache.store(key, _tiny_compiled())
        faults.install("io:aot:read:1")
        with pytest.raises(faults.TransientIOError):
            cache.load(key)
        assert cache.load(key) is not None
    finally:
        faults.reset()


def test_crash_mid_write_leaves_no_committed_entry(tmp_path):
    """The fs_open_atomic pattern under a crash rule: the injected crash
    (a BaseException, like SIGKILL) escapes every recovery layer, no
    committed entry appears, and the cache dir's only residue is what the
    next process's sweep removes."""
    cache = _cache(tmp_path)
    key = _key(cache)
    try:
        faults.install("crash:aot:write")
        with pytest.raises(faults.InjectedCrash):
            cache.store(key, _tiny_compiled())
    finally:
        faults.reset()
    assert cache.load(key) is None  # nothing half-published
    # a torn temp a crashed process DID leave behind (crash landing
    # mid-write rather than at the injection point) is swept once its
    # pid is dead — the committed namespace never sees it
    torn = os.path.join(
        cache.dir, f"{AC._entry_name(key)}.tmp-999999-zz"
    )
    os.makedirs(cache.dir, exist_ok=True)
    with open(torn, "wb") as f:
        f.write(b"half a header")
    assert AC.sweep_orphans(cache.dir) == 1
    assert cache.load(key) is None
    assert cache.store(key, _tiny_compiled())


def test_real_store_failure_degrades_never_raises(tmp_path, monkeypatch):
    """A REAL filesystem failure (not injected) disables stores for the
    process and returns False — queries keep running on in-process
    compiles."""
    cache = AC.AotCache(str(tmp_path / "missing" / "deep"), 1 << 30)
    monkeypatch.setattr(
        AC.os, "makedirs",
        lambda *a, **k: (_ for _ in ()).throw(OSError("disk full")),
    )
    assert cache.store(_key(cache), _tiny_compiled()) is False
    assert cache.stats["store_failures"] == 1
    assert cache._store_disabled


# ---------------------------------------------------------------------------
# promotion-memo persistence
# ---------------------------------------------------------------------------


def test_promotion_store_roundtrip(tmp_path):
    store = AC.PromotionStore(str(tmp_path / "aot"))
    key = AC.promotion_key_str(("sort_perm", 4096, 128))
    assert store.get(key) is None
    rec = {"jnp_ms": 1.0, "pallas_ms": 0.5, "use": True}
    store.record(key, rec)
    assert store.get(key) == rec
    # a fresh handle (fresh process) reads the same verdict
    assert AC.PromotionStore(str(tmp_path / "aot")).get(key) == rec


def test_promotion_store_tolerates_corruption(tmp_path):
    d = tmp_path / "aot"
    d.mkdir()
    (d / "promotions.json").write_text("{torn json")
    store = AC.PromotionStore(str(d))
    assert store.get("anything") is None
    store.record("k", {"use": False})
    assert AC.PromotionStore(str(d)).get("k") == {"use": False}


def test_persisted_promotion_verdict_skips_remeasure(tmp_path):
    """A fresh session consuming a persisted verdict must not re-measure:
    the fleet pays one A/B per (kernel, shape, backend), ever."""
    import nds_tpu.engine.exec as EX

    conf = {"engine.pallas_sort": "auto"}
    s1 = _session(tmp_path, **conf)
    sort_q = "select k, v from t where v > 0 order by k"
    ref = s1.sql(sort_q).collect().to_pylist()
    assert any(k[0] == "sort_perm" for k in s1.pallas_promotions)

    s2 = _session(tmp_path, **conf)
    orig = EX.Executor._measure_promotion

    def boom(*a, **kw):
        raise AssertionError("re-measured a persisted promotion verdict")

    EX.Executor._measure_promotion = boom
    try:
        assert s2.sql(sort_q).collect().to_pylist() == ref
    finally:
        EX.Executor._measure_promotion = orig
    assert any(k[0] == "sort_perm" for k in s2.pallas_promotions)


# ---------------------------------------------------------------------------
# observability + budget derivation satellites
# ---------------------------------------------------------------------------


def test_aot_events_ride_the_trace(tmp_path):
    from nds_tpu.obs import reader as R

    trace = tmp_path / "trace"
    s1 = _session(tmp_path, **{"engine.trace_dir": str(trace)})
    s1.sql(QUERY).collect()
    s1.tracer.close()
    prof = R.load_profile([str(trace)], strict=True)
    assert prof["tallies"]["aot_stores"] >= 1
    assert prof["tallies"]["aot_misses"] >= 1

    trace2 = tmp_path / "trace2"
    s2 = _session(tmp_path, **{"engine.trace_dir": str(trace2)})
    s2.sql(QUERY).collect()
    s2.tracer.close()
    prof2 = R.load_profile([str(trace2)], strict=True)
    assert prof2["tallies"]["aot_disk_hits"] >= 1
    assert R.aot_disk_hit_rate(prof2) == 1.0


def test_auto_budget_derivations_share_one_formula():
    from nds_tpu.analysis.budget import derive_share_bytes, host_ram_bytes
    from nds_tpu.engine.spill import resolve_pool_bytes

    # power-of-two, clamped, monotone in the resource
    assert derive_share_bytes(64 << 30, 4, 1 << 30, 64 << 30) == 16 << 30
    assert derive_share_bytes(100 << 30, 4, 1 << 30, 64 << 30) == 16 << 30
    assert derive_share_bytes(1 << 20, 4, 1 << 30, 64 << 30) == 1 << 30
    ram = host_ram_bytes()
    assert ram > 0
    auto = resolve_pool_bytes({"engine.spill_pool_bytes": "auto"})
    assert auto == derive_share_bytes(ram, 4, 1 << 30, 64 << 30)
    # auto never breaks the explicit paths
    assert resolve_pool_bytes({"engine.spill_pool_bytes": 123}) == 123
    aot = AC.resolve_aot_cache_bytes({"engine.aot_cache_bytes": "auto"}, "/")
    assert aot & (aot - 1) == 0  # power of two
    assert AC.resolve_aot_cache_bytes(
        {"engine.aot_cache_bytes": 4096}, "/"
    ) == 4096
