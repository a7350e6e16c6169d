"""Persistent on-disk AOT executable cache: compile each pipeline once, EVER.

Round-5 data put cold geomean ~20% above steady, and the gap is 100% XLA
compilation — every SF10 isolation subprocess re-paid every compile from
scratch. The reference harness gets cross-query executable reuse for free
from Spark's long-lived executor JVMs; this engine's equivalent lives here:
`FusedPipeline`/`FusedAggPipeline` dispatch resolves compiled executables
through an `AotCache`, and on a bucket-level compile the executable is
serialized (`jax.experimental.serialize_executable`) into a
fingerprint-keyed entry under `engine.aot_cache_dir` / `NDS_AOT_CACHE_DIR`.
A fresh process's first dispatch then DESERIALIZES instead of recompiling —
cold start collapses to disk-read time, and a fleet serving millions of
users compiles each pipeline once per environment, not once per process.

Key discipline (wrong-load is impossible, mismatch is a clean miss):
every entry is keyed by the full dict of everything that changes compiled
code — pipeline kind + stage fingerprint (plan.fingerprint, stable across
processes), a CONTENT-stable input signature (dtypes, validity, dictionary
content hashes, agg-key stats bounds), the flat argument avals (capacity
bucket included), donation slots, jax + jaxlib + nds_tpu versions, backend
platform + device kind + local device count, the x64 flag, and the
relevant engine conf (fuse_agg / pallas_agg). The key hashes into the
entry filename, but `load` re-verifies the FULL key dict recorded in the
entry header (a filename hash collision reads as a miss, never a wrong
load) and the payload checksum (a torn/corrupt body quarantines the file
and reads as a miss, never a crash).

Entry format: `aot-<sha256[:40]>.bin` = 8-byte magic "NDSAOT2\\n",
8-byte big-endian header length, canonical-JSON header (full key +
payload sha256 + sizes + the ids of the devices the executable runs on),
then the pickled (payload, in_tree, out_tree) from serialize_executable.
Pickle is acceptable here: entries live in a user-owned cache directory
and carry the same trust as the jax persistent compilation cache (the
payload itself is pickle-based).

Production treatment (the spill pool / lakehouse patterns):
  * atomic writes — pid-tempfile sibling + os.replace, so a concurrent
    two-process warm has one winner and a crash leaves only a `.tmp-<pid>-`
    file the orphan sweep removes once the pid is dead;
  * byte budget with LRU eviction — `engine.aot_cache_bytes` /
    NDS_AOT_CACHE_BYTES, default auto-derived as a power-of-two share of
    the cache volume's free disk (analysis/budget.derive_share_bytes, the
    same derivation the union window and spill pool use); hits refresh
    mtime so eviction is least-recently-USED, not least-recently-written;
  * crash-orphan sweep at session start (once per process per directory);
  * `aot_cache` trace events + `nds_aot_cache_*` metric families +
    profiler tallies;
  * `aot:write` / `aot:read` fault-injection sites (io/crash kinds):
    injected faults keep their classifiable identity so the report
    ladder's io_backoff rung covers cache IO, while REAL filesystem
    errors degrade the cache (store disabled / entry quarantined) and
    never fail a query — a broken cache disk costs recompiles, not
    results.

The same directory also persists the Pallas promotion memos
(`PromotionStore`): the measured jnp-vs-Pallas A/B verdicts
(engine.pallas_agg/pallas_join/pallas_sort `auto`) keyed by (kernel,
shape, backend environment), so a fleet measures each shape once.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time

from .. import faults
from .. import __version__ as _NDS_VERSION
from .lockdebug import make_lock

_MAGIC = b"NDSAOT2\n"
_ENTRY_PREFIX = "aot-"
_ENTRY_SUFFIX = ".bin"
_QUARANTINE_PREFIX = "quarantine-"
_PROMO_FILE = "promotions.json"

#: auto-budget derivation: 1/16 of the cache volume's free disk, clamped —
#: mirrors the union-window / spill-pool share-of-a-resource sizing
_BUDGET_FRACTION = 16
_BUDGET_LO = 256 << 20
_BUDGET_HI = 32 << 30


def compile_cache_root() -> str:
    """The one directory both compile caches live under: where
    `JAX_COMPILATION_CACHE_DIR` says when it is set, else the fixed
    `.nds_cache/` at the root of this checkout (git-ignored). Never under
    `$HOME` or a temporary name: the path is part of the XLA cache's key,
    and a machine that is thrown away keeps only what its caller placed."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        ".nds_cache",
    )


def resolve_aot_cache_dir(conf: dict | None = None) -> str | None:
    """Cache directory: conf `engine.aot_cache_dir`, env NDS_AOT_CACHE_DIR,
    else `aot_exec/` under `compile_cache_root()`, beside the XLA
    persistent cache. Explicit "" / "0" disables the AOT cache."""
    v = None
    if conf:
        v = conf.get("engine.aot_cache_dir")
    if v is None:
        v = os.environ.get("NDS_AOT_CACHE_DIR")
    if v is None:
        return os.path.join(compile_cache_root(), "aot_exec")
    v = str(v)
    return v if v not in ("", "0") else None


def resolve_aot_cache_bytes(conf: dict | None = None,
                            cache_dir: str | None = None) -> int:
    """Entry byte budget: conf `engine.aot_cache_bytes` /
    NDS_AOT_CACHE_BYTES; unset or "auto" derives a power-of-two share of
    the cache volume's free disk (budget.derive_share_bytes — the same
    formula the union window derives from the device budget and the spill
    pool derives from host RAM)."""
    v = None
    if conf:
        v = conf.get("engine.aot_cache_bytes")
    if v is None:
        v = os.environ.get("NDS_AOT_CACHE_BYTES")
    if v is not None and str(v).lower() not in ("", "auto"):
        try:
            return max(int(v), 0)
        except (TypeError, ValueError):
            pass
    from ..analysis.budget import derive_share_bytes

    free = None
    try:
        import shutil

        probe = cache_dir
        while probe and not os.path.isdir(probe):
            parent = os.path.dirname(probe)
            if parent == probe:
                break
            probe = parent
        if probe:
            free = shutil.disk_usage(probe).free
    except OSError:
        free = None
    if not free:
        free = _BUDGET_HI * _BUDGET_FRACTION  # unknown volume: cap at HI
    return derive_share_bytes(free, _BUDGET_FRACTION, _BUDGET_LO, _BUDGET_HI)


def environment_key() -> dict:
    """The environment half of every entry key: everything OUTSIDE the
    pipeline that changes (or invalidates) compiled code. A mismatch in
    any field is a clean miss — a cache dir shared across jax upgrades,
    backend swaps, or device generations can never serve a stale
    executable."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "nds": _NDS_VERSION,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "local_devices": jax.local_device_count(),
        "processes": jax.process_count(),
        "x64": bool(jax.config.jax_enable_x64),
    }


class _ThreadTally(threading.local):
    xla_cache_hits = 0


_tally = _ThreadTally()
# process-lifetime once-latch for the jax.monitoring listener below; worst
# case under a race is a second listener and hits counted twice, which
# still reads as "a hit happened"
# nds-lint: disable=mutable-module-global
_LISTENING = []


def _on_jax_event(event: str, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _tally.xla_cache_hits += 1


def xla_cache_hits() -> int:
    """How many compiles on THIS thread were served by jax's persistent
    compilation cache so far (jax.monitoring reports each hit on the
    compiling thread). A caller brackets a compile with two reads to learn
    whether its executable was compiled here or loaded: a loaded XLA:CPU
    executable re-serializes into an entry that deserializes cleanly and
    then fails when it RUNS ("Function … not found", jax 0.9.0), out of
    reach of any check `store` can make, so it must never be stored."""
    if not _LISTENING:
        import jax.monitoring

        _LISTENING.append(True)
        jax.monitoring.register_event_listener(_on_jax_event)
    return _tally.xla_cache_hits


def dictionary_hash(dictionary) -> str:
    """Content hash of a column dictionary (host-side Arrow string array):
    the in-process signature keys dictionaries by id(), which is truthful
    only while the object lives — an on-disk key must survive process
    death, so it hashes the VALUES. Dictionaries are dimension-sized, and
    this only runs at executable-resolution time (compile-level rarity),
    never per dispatch."""
    h = hashlib.sha256()
    try:
        for v in dictionary:
            s = v.as_py() if hasattr(v, "as_py") else v
            h.update(b"\x00" if s is None else str(s).encode("utf-8"))
            h.update(b"\x1f")
    except Exception:
        # unhashable/foreign dictionary object: key on its repr — worst
        # case a conservative extra miss, never a wrong load
        h.update(repr(dictionary).encode("utf-8", "replace"))
    return h.hexdigest()[:24]


def canonical_key_bytes(key: dict) -> bytes:
    return json.dumps(key, sort_keys=True, default=str).encode("utf-8")


def _entry_name(key: dict) -> str:
    digest = hashlib.sha256(canonical_key_bytes(key)).hexdigest()[:40]
    return f"{_ENTRY_PREFIX}{digest}{_ENTRY_SUFFIX}"


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except (PermissionError, OSError):
        return True
    return True


class AotCache:
    """One process's handle on a shared on-disk executable cache.

    Thread-safe (one lock around stats + the dictionary-hash memo; file IO
    runs unlocked — atomicity comes from tempfile+rename, and concurrent
    writers of the SAME key are idempotent last-writer-wins). Cross-process
    safety needs no lock at all: readers only ever see fully-renamed
    entries, and eviction unlinks are tolerated by re-loading as a miss.
    """

    def __init__(self, cache_dir: str, budget_bytes: int,
                 tracer=None):
        self.dir = str(cache_dir)
        self.budget = int(budget_bytes)
        # callable returning the live tracer (a Session's tracer can be
        # swapped mid-run by harness loops; capturing the object would
        # emit into a closed file)
        self._tracer = tracer if callable(tracer) else (lambda: tracer)
        self._lock = make_lock("AotCache._lock")
        self._env = environment_key()
        # bounded LRU: the tuple's strong dictionary ref keeps the id()
        # key truthful, and the cap keeps a long-lived serving session
        # that rotates datasets from pinning every dictionary it ever
        # hashed (a dropped entry just re-hashes, compile-level rarity)
        from collections import OrderedDict

        # id(dic) -> (dic, hash)                 # nds-guarded-by: _lock
        self._dict_hashes = OrderedDict()
        self._dict_hash_cap = 512
        self.stats = {  # nds-guarded-by: _lock
            "lookups": 0, "disk_hits": 0, "misses": 0, "stores": 0,
            "store_failures": 0, "quarantined": 0, "evictions": 0,
            "call_failures": 0,
        }
        self._store_disabled = False  # nds-guarded-by: _lock

    # -- events ----------------------------------------------------------
    def _emit(self, op: str, result: str, **extra):
        from ..obs import trace as obs_trace

        tracer = obs_trace.current() or self._tracer()
        if tracer is not None:
            tracer.emit("aot_cache", op=op, result=result, **extra)

    # -- keying ----------------------------------------------------------
    def entry_key(self, kind: str, fp: str, content_sig, avals,
                  donate_slots, conf_sig) -> dict:
        """The full key dict for one executable: pipeline identity +
        input layout + capacity-bucketed avals + donation + environment +
        relevant engine conf. See the module docstring for why every
        field is load-bearing."""
        return {
            "kind": kind,
            "fp": fp,
            "sig": list(content_sig),
            "avals": [[list(shape), str(dtype)] for shape, dtype in avals],
            "donate": list(donate_slots),
            "conf": list(conf_sig),
            "env": self._env,
        }

    def content_signature(self, table, with_stats: bool = False):
        """Process-independent analogue of fuse.input_signature: the same
        fields, with each dictionary's id() replaced by a content hash
        (memoized per object — the exec cache pins dictionaries, so the
        id is stable while the memo entry is)."""
        sig = [("live", table.live is not None)]
        for name, c in table.columns.items():
            dh = None
            if c.dictionary is not None:
                with self._lock:
                    hit = self._dict_hashes.get(id(c.dictionary))
                    if hit is not None:
                        self._dict_hashes.move_to_end(id(c.dictionary))
                if hit is not None and hit[0] is c.dictionary:
                    dh = hit[1]
                else:
                    dh = dictionary_hash(c.dictionary)
                    with self._lock:
                        self._dict_hashes[id(c.dictionary)] = (
                            c.dictionary, dh,
                        )
                        while len(self._dict_hashes) > self._dict_hash_cap:
                            self._dict_hashes.popitem(last=False)
            entry = (name, repr(c.dtype), c.valid is not None, dh)
            if with_stats:
                entry = entry + (
                    (int(c.stats.vmin), int(c.stats.vmax))
                    if c.stats is not None
                    else None,
                )
            sig.append(entry)
        return tuple(sig)

    # -- load / store ----------------------------------------------------
    def load(self, key: dict):
        """The deserialized compiled executable for `key`, or None (a
        miss: absent, foreign, corrupt, torn, checksum-failed, or
        environment-mismatched entry — corrupt entries are quarantined).
        Injected `aot:read` faults propagate (classifiable by the report
        ladder); real read errors are a miss."""
        path = os.path.join(self.dir, _entry_name(key))
        with self._lock:
            self.stats["lookups"] += 1
        t0 = time.perf_counter()
        t0_ns = time.time_ns()
        faults.maybe_fire("aot:read", kinds=("io", "crash"))
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            with self._lock:
                self.stats["misses"] += 1
            self._emit("load", "miss")
            return None
        except OSError:
            with self._lock:
                self.stats["misses"] += 1
            self._emit("load", "miss")
            return None
        entry = self._parse_entry(raw, key, path)
        if entry is None:
            with self._lock:
                self.stats["misses"] += 1
            return None
        body, device_ids = entry
        try:
            import jax
            from jax.experimental import serialize_executable as se

            # load for the executable's OWN devices: left to its default,
            # jax loads for every local device and the call then expects
            # one shard of each argument per device
            by_id = {d.id: d for d in jax.local_devices()}
            payload, in_tree, out_tree = pickle.loads(body)
            compiled = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids],
            )
        except Exception as exc:
            self._quarantine(path, f"deserialize failed: {exc}")
            with self._lock:
                self.stats["misses"] += 1
            return None
        try:
            os.utime(path)  # LRU: a hit refreshes recency
        except OSError:
            pass
        dur = round((time.perf_counter() - t0) * 1000.0, 3)
        with self._lock:
            self.stats["disk_hits"] += 1
        self._emit(
            "load", "hit", bytes=len(raw), dur_ms=dur, t0_ns=t0_ns,
            key=_entry_name(key),
        )
        from ..obs import tally as obs_tally

        tl = obs_tally.current()
        if tl is not None:
            # the statement's span says what it spent re-loading
            tl.add_compile("load", t0_ns / 1e9, t0_ns / 1e9 + dur / 1e3)
        return compiled

    def _parse_entry(self, raw: bytes, key: dict, path: str):
        """(validated pickled blob, execution device ids) from one raw
        entry, or None (quarantined). Full-key equality — not just the
        filename hash — and a payload checksum stand between a bad file
        and a wrong load."""
        try:
            if not raw.startswith(_MAGIC):
                raise ValueError("bad magic")
            off = len(_MAGIC)
            hlen = int.from_bytes(raw[off:off + 8], "big")
            off += 8
            header = json.loads(raw[off:off + hlen].decode("utf-8"))
            off += hlen
            body = raw[off:]
            if header.get("key") != json.loads(
                canonical_key_bytes(key).decode("utf-8")
            ):
                # filename-hash collision or foreign entry: a clean miss,
                # and NOT a quarantine — the entry may be someone else's
                # perfectly valid executable
                self._emit("load", "key_mismatch")
                return None
            if len(body) != int(header.get("body_bytes", -1)) or (
                hashlib.sha256(body).hexdigest() != header.get("body_sha256")
            ):
                raise ValueError("payload checksum mismatch")
            return body, [int(i) for i in header["devices"]]
        except Exception as exc:
            self._quarantine(path, str(exc))
            return None

    def _quarantine(self, path: str, reason: str):
        """Move a corrupt/torn/undeserializable entry aside (evidence
        survives for forensics; `cache vacuum` removes quarantines). A
        rename race with another process's quarantine/eviction is fine —
        the file is gone either way."""
        dest = os.path.join(
            self.dir,
            f"{_QUARANTINE_PREFIX}{os.path.basename(path)}.{os.getpid()}",
        )
        try:
            os.replace(path, dest)
        except OSError:
            pass
        with self._lock:
            self.stats["quarantined"] += 1
        self._emit("load", "quarantined", error=reason[:160])

    def store(self, key: dict, compiled) -> bool:
        """Serialize + atomically publish one compiled executable.
        Injected `aot:write` faults propagate (io kinds walk the report
        ladder's backoff rung; crash kinds simulate death mid-write,
        leaving a `.tmp-<pid>-` orphan for the sweep). A REAL filesystem
        failure disables further stores for this process (one warning) —
        a full/broken cache disk must cost recompiles, never queries."""
        if self._store_disabled:
            return False
        t0 = time.perf_counter()
        faults.maybe_fire("aot:write", kinds=("io", "crash"))
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            devices = compiled.runtime_executable().local_devices()
            # validate BEFORE publishing: an unreloadable payload would
            # make every future process quarantine it on first touch. One
            # extra deserialize per STORE (compile-level rarity) buys "an
            # entry on disk always loads". It does not buy "always runs":
            # an executable that was itself loaded from the XLA persistent
            # compilation cache failed right here on jax 0.4.37 ("Symbols
            # not found"), but on 0.9.0 it reloads and fails only when
            # called — callers keep those away (xla_cache_hits).
            se.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=devices
            )
            body = pickle.dumps((payload, in_tree, out_tree))
        except Exception:
            # unserializable executable (backend without AOT support, or
            # the XLA-cache-loaded case above): not an IO failure — skip
            # quietly, the in-process object still serves this process
            with self._lock:
                self.stats["store_failures"] += 1
            self._emit("store", "unserializable")
            return False
        header = canonical_key_bytes({
            "key": json.loads(canonical_key_bytes(key).decode("utf-8")),
            "body_bytes": len(body),
            "body_sha256": hashlib.sha256(body).hexdigest(),
            "devices": [d.id for d in devices],
            "created": int(time.time()),
            "pid": os.getpid(),
        })
        dest = os.path.join(self.dir, _entry_name(key))
        tmp = f"{dest}.tmp-{os.getpid()}-{hashlib.sha256(os.urandom(8)).hexdigest()[:6]}"
        try:
            os.makedirs(self.dir, exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(_MAGIC)
                f.write(len(header).to_bytes(8, "big"))
                f.write(header)
                f.write(body)
            os.replace(tmp, dest)
        except faults.FaultError:
            raise  # injected faults keep their classifiable identity
        except OSError as exc:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            with self._lock:
                self.stats["store_failures"] += 1
                disabled_now = not self._store_disabled
                self._store_disabled = True
            if disabled_now:
                print(f"aot: disabling executable stores ({exc})")
            self._emit("store", "io_error", error=str(exc)[:160])
            return False
        dur = round((time.perf_counter() - t0) * 1000.0, 3)
        with self._lock:
            self.stats["stores"] += 1
        self._emit(
            "store", "stored",
            bytes=len(body) + len(header) + len(_MAGIC) + 8,
            dur_ms=dur, key=_entry_name(key),
        )
        self._enforce_budget(keep=os.path.basename(dest))
        return True

    def quarantine_key(self, key: dict):
        """Quarantine the entry for `key` (a loaded executable that failed
        at call time: keyed correctly but unusable on this runtime). The
        caller recompiles, so the failure is counted and emitted as its
        own `call`/`failed` event — a recompile nobody hears of is how a
        broken cache stays broken."""
        with self._lock:
            self.stats["call_failures"] += 1
        self._emit("call", "failed", key=_entry_name(key))
        self._quarantine(
            os.path.join(self.dir, _entry_name(key)), "failed at call time"
        )

    # -- budget / hygiene ------------------------------------------------
    def _entries(self):
        """[(path, size, mtime)] of committed entries (temps, quarantines,
        and the promotion store are not budget-accounted entries)."""
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        for name in names:
            if not (
                name.startswith(_ENTRY_PREFIX)
                and name.endswith(_ENTRY_SUFFIX)
            ):
                continue
            path = os.path.join(self.dir, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            out.append((path, st.st_size, st.st_mtime))
        return out

    def usage(self):
        """(entry count, total bytes) of committed entries."""
        entries = self._entries()
        return len(entries), sum(s for _, s, _ in entries)

    def _enforce_budget(self, keep: str | None = None):
        """LRU eviction to the byte budget: oldest-mtime entries unlink
        first (hits refresh mtime, so this is least-recently-USED). The
        just-written entry is excluded from victimhood — a budget smaller
        than one entry must not evict what it just stored."""
        entries = self._entries()
        total = sum(s for _, s, _ in entries)
        if total <= self.budget:
            return
        victims = sorted(
            (e for e in entries if os.path.basename(e[0]) != keep),
            key=lambda e: e[2],
        )
        evicted = 0
        for path, size, _ in victims:
            if total <= self.budget:
                break
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= size
            evicted += 1
        if evicted:
            with self._lock:
                self.stats["evictions"] += evicted
            self._emit("evict", "evicted", entries=evicted)

    def vacuum(self, drop_all: bool = False):
        """Hygiene pass: dead-pid temp orphans + quarantine files are
        removed, then the budget is enforced (`drop_all` clears every
        committed entry too — the operator reset). Returns the number of
        files removed."""
        removed = sweep_orphans(self.dir)
        try:
            names = os.listdir(self.dir)
        except OSError:
            names = []
        for name in names:
            if name.startswith(_QUARANTINE_PREFIX) or (
                drop_all
                and name.startswith(_ENTRY_PREFIX)
                and name.endswith(_ENTRY_SUFFIX)
            ):
                try:
                    os.unlink(os.path.join(self.dir, name))
                    removed += 1
                except OSError:
                    pass
        if not drop_all:
            self._enforce_budget()
        self._emit("vacuum", "done", removed=removed)
        return removed


# ---------------------------------------------------------------------------
# crash hygiene: orphaned pid-tempfile sweep (the spill-pool pattern)
# ---------------------------------------------------------------------------


def sweep_orphans(cache_dir: str) -> int:
    """Remove `.tmp-<pid>-*` staging files whose owning process is dead —
    a crash mid-store must not accumulate torn temps forever. Only files
    matching the cache's own naming scheme are ever touched; a temp whose
    pid is alive (an in-flight store) is left alone."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return 0
    removed = 0
    for name in names:
        if not name.startswith((_ENTRY_PREFIX, _PROMO_FILE)):
            continue
        if ".tmp-" not in name:
            continue
        tail = name.split(".tmp-", 1)[1]
        pid_s = tail.split("-", 1)[0]
        try:
            pid = int(pid_s)
        except ValueError:
            continue
        if pid == os.getpid() or _pid_alive(pid):
            continue
        try:
            os.unlink(os.path.join(cache_dir, name))
            removed += 1
        except OSError:
            pass
    if removed:
        print(f"aot: swept {removed} orphaned temp(s) from {cache_dir}")
    return removed


# one sweep per (process, directory): per-stream Session construction must
# not re-list the cache dir. Process-lifetime once-latch; worst case under
# a race is a second, idempotent sweep.
# nds-lint: disable=mutable-module-global
_SWEPT_DIRS = set()


def sweep_at_session_start(cache_dir: str | None):
    if not cache_dir or cache_dir in _SWEPT_DIRS:
        return
    _SWEPT_DIRS.add(cache_dir)
    sweep_orphans(cache_dir)


# ---------------------------------------------------------------------------
# promotion-memo persistence: measure each (kernel, shape) once per fleet
# ---------------------------------------------------------------------------


def promotion_key_str(key) -> str:
    """The persistent form of a session promotion-memo key: the in-memory
    tuple (kernel, shape dims...) plus the backend environment, because a
    verdict measured on one device generation/jax version says nothing
    about another."""
    env = environment_key()
    parts = [str(p) for p in key] + [
        env["platform"], env["device_kind"], env["jax"],
    ]
    return "|".join(parts)


class PromotionStore:
    """Shared JSON store of measured promotion verdicts
    (`promotions.json` in the AOT cache dir): `get` returns a verdict
    record or None; `record` merges one verdict in atomically
    (read-merge-tempfile-rename; a lost concurrent-writer race drops at
    most one record, which the next session simply re-measures). All IO
    is best-effort — a broken store costs re-measurement, never a query.
    """

    def __init__(self, cache_dir: str):
        self.path = os.path.join(str(cache_dir), _PROMO_FILE)
        self._lock = make_lock("PromotionStore._lock")
        # last-read snapshot (refreshed on record)  # nds-guarded-by: _lock
        self._cache = None

    def _read(self) -> dict:
        try:
            with open(self.path, encoding="utf-8") as f:
                data = json.load(f)
            return data if isinstance(data, dict) else {}
        except (OSError, ValueError):
            return {}

    def get(self, key_str: str):
        with self._lock:
            if self._cache is None:
                self._cache = self._read()
            rec = self._cache.get(key_str)
        if rec is not None and not isinstance(rec, dict):
            return None
        return rec

    def record(self, key_str: str, rec: dict):
        with self._lock:
            data = self._read()
            data[key_str] = rec
            self._cache = data
        # file IO OUTSIDE the lock: `get` is on the planning path and
        # shares it, so a slow store write would convoy every planner
        # behind a syscall (the blocking-under-lock class). Two
        # concurrent record()s may interleave here — last rename wins the
        # whole snapshot, dropping at most one record (the documented
        # race; the next session re-measures). `data` is private to this
        # call: _read() builds a fresh dict and nothing mutates _cache
        # in place.
        tmp = (
            f"{self.path}.tmp-{os.getpid()}-"
            f"{hashlib.sha256(os.urandom(8)).hexdigest()[:6]}"
        )
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(data, f, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def count(self) -> int:
        return len(self._read())
