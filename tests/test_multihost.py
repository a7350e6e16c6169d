"""Real multi-process DCN-tier test: two OS processes, a loopback
coordinator, and a global mesh spanning both processes' CPU devices.

The reference's multi-node story is YARN executors + Netty shuffle
(reference: nds/base.template:26-31); the TPU-native counterpart is
jax.distributed + GSPMD collectives. Prior rounds only exercised the
single-process degenerate branch of parallel/multihost.py — this spawns a
genuine 2-process cluster so `jax.make_array_from_process_local_data`
(multihost.shard_rows_across_hosts) and cross-process collectives execute
for real, and runs one SQL aggregation through the Session over the
multi-process mesh against a numpy oracle.
"""

import os
import socket
import subprocess
import sys

import pytest

# The CPU skip carried since PR 3 is RETIRED (ISSUE 13): multihost.initialize
# now selects the gloo cross-process collective implementation whenever the
# process is pinned to the CPU platform, so the two-process DCN tier runs
# for real on this container — a genuine 2-process cluster over a loopback
# coordinator, cross-process psum/segment-sum, and one SQL aggregation
# through the Session over the multi-process mesh. Marked slow (two cold
# jax processes cost ~a minute); ci/tier1-check runs it standalone so
# scale-out has a CI gate at all.
pytestmark = pytest.mark.slow

_WORKER = r"""
import os, sys
import numpy as np

pid = int(sys.argv[1])
coord = sys.argv[2]

# pin the worker to the CPU through jax.config BEFORE the backend
# initializes, and set the virtual device count through XLA_FLAGS (read
# lazily at client creation) — same pattern as tests/conftest.py and
# __graft_entry__.py
import re
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 " +
    re.sub(r"--xla_force_host_platform_device_count=\d+", "",
           os.environ.get("XLA_FLAGS", ""))).strip()
import jax
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.getcwd())  # Popen cwd = repo root
from nds_tpu.parallel import multihost

multihost.initialize(coordinator_address=coord, num_processes=2, process_id=pid)

import jax.numpy as jnp

assert jax.process_count() == 2, jax.process_count()
mesh = multihost.global_mesh()
assert mesh.devices.size == 4, mesh.devices.size

# --- primitive tier: host-sharded ingestion + global reduction ------------
rows = np.arange(64, dtype=np.int64)
local = rows[pid * 32:(pid + 1) * 32]  # each process contributes its half
garr = multihost.shard_rows_across_hosts(mesh, local)
total = int(jax.jit(jnp.sum)(garr))
assert total == int(rows.sum()), (total, rows.sum())

# --- group-by over the mesh: segment-sum of host-sharded fact rows --------
keys = (rows % 5).astype(np.int32)
vals = (rows * 3).astype(np.int64)
gk = multihost.shard_rows_across_hosts(mesh, keys[pid * 32:(pid + 1) * 32])
gv = multihost.shard_rows_across_hosts(mesh, vals[pid * 32:(pid + 1) * 32])
sums = jax.jit(
    lambda k, v: jax.ops.segment_sum(v, k, num_segments=5)
)(gk, gv)
expect = [int(vals[keys == g].sum()) for g in range(5)]
got = [int(x) for x in jax.device_get(sums)]
assert got == expect, (got, expect)

# --- one SQL aggregation through the Session over the multi-process mesh --
import pyarrow as pa
from nds_tpu.engine.session import Session

n = 4096  # divisible by the 4-device mesh so fact rows shard
rng = np.random.default_rng(7)
k = rng.integers(0, 8, n)
v = rng.integers(0, 100, n)
t = pa.table({"k": pa.array(k, pa.int64()), "v": pa.array(v, pa.int64())})
sess = Session(mesh=mesh)
sess.register_arrow("t", t)
out = sess.sql(
    "select k, sum(v) s, count(*) c from t group by k order by k"
).to_pylist()
expect = [
    {"k": int(g), "s": int(v[k == g].sum()), "c": int((k == g).sum())}
    for g in sorted(set(k.tolist()))
]
assert out == expect, (out[:3], expect[:3])
print(f"WORKER{pid} OK", flush=True)
"""


def test_two_process_dcn_tier(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    from nds_tpu.parallel.multihost import worker_env

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), coord],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            # worker_env exports a per-worker trace context on top of the
            # sanitized env, so worker event files fold by trace_id
            env=worker_env(process_id=pid, base=env),
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=560)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process worker hung")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-4000:]}"
        assert f"WORKER{pid} OK" in out
