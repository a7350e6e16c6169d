"""Multi-host execution: the DCN tier of the distributed backend.

The reference scales across hosts with YARN-scheduled Spark executors and a
Netty shuffle service (reference: nds/base.template:26-31 `MASTER=yarn`,
8 executors; shuffle config power_run_cpu.template:20-27). The TPU-native
counterpart is jax.distributed: one engine process per host VM, every process
sees the global device set, GSPMD collectives ride ICI inside a slice and DCN
between slices — the same `Mesh`/`shard_map` code in `dist.py` runs unchanged
on a multi-host mesh.

Data ingestion is host-parallel by construction: the generator writes
per-chunk files (`<table>_<child>_<parallel>.dat`) and each host reads only
its own chunks, so a global sharded table is assembled with
`jax.make_array_from_process_local_data` instead of replicating the whole
table through one coordinator (the reference's HDFS-read equivalent).
"""

from __future__ import annotations

import os

import numpy as np


def _enable_cpu_collectives(jax) -> None:
    """Cross-process collectives on the CPU backend need an explicit
    implementation — jax's default ("none") raises "Multiprocess
    computations aren't implemented on the CPU backend", which kept the
    two-process DCN tier skipped on CPU since PR 3. Gloo rides the same
    TCP world the distributed coordinator already set up, so a CPU fleet
    (and the CI gate) gets real cross-process psum/all_to_all. Config
    must land BEFORE the backend initializes; only touched when the
    process is pinned to the CPU platform — TPU pods keep native ICI/DCN
    collectives."""
    platforms = str(
        getattr(jax.config, "jax_platforms", None)
        or os.environ.get("JAX_PLATFORMS")
        or ""
    )
    if "cpu" in platforms.lower():
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def initialize(coordinator_address=None, num_processes=None, process_id=None):
    """Idempotent jax.distributed bring-up.

    With no arguments, relies on TPU pod auto-detection (the runtime
    environment provides coordinator/process ids on Cloud TPU VMs). Explicit
    arguments support bare-metal/ssh fleets — the same host-list world as
    `cli/gen_data.py cluster` mode. Safe to call in single-process runs:
    initialization is skipped when no cluster environment is configured."""
    import jax

    # NOTE: do not touch jax.devices()/process_count() here — any backend
    # query initializes XLA, after which distributed.initialize() refuses to
    # run. Detect prior initialization through the distributed client state.
    try:
        from jax._src import distributed as _dist

        if _dist.global_state.client is not None:
            return  # already initialized
    except Exception:
        pass
    if coordinator_address is not None:
        if num_processes is None or process_id is None:
            raise ValueError(
                "coordinator_address requires num_processes and process_id"
            )
        _enable_cpu_collectives(jax)
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        except RuntimeError as exc:
            # keep the documented idempotency even if the private
            # global_state probe above stops working on a future jax
            if "already" not in str(exc).lower():
                raise
        return
    if num_processes is not None or process_id is not None:
        raise ValueError(
            "num_processes/process_id need an explicit coordinator_address"
        )
    # no arguments: rely on cluster auto-detection (TPU pod metadata, SLURM).
    # A plain single-host environment has nothing to detect — initialize()
    # raises there, which is the expected no-op path.
    _enable_cpu_collectives(jax)
    try:
        jax.distributed.initialize()
    except Exception:
        pass


def worker_env(process_id=None, base: dict | None = None) -> dict:
    """Subprocess environment for a spawned multihost worker: a copy of
    this process's env (or `base`) carrying a per-worker trace context
    (NDS_TRACE_CONTEXT) minted as a child of the launcher's — the
    worker's event files then fold by trace_id, the same pid-proof
    attribution the throughput parent uses for its stream children."""
    from ..obs import trace as obs_trace

    env = dict(os.environ if base is None else base)
    ctx = obs_trace.current_context() or obs_trace.resolve_trace_context(
        "multihost"
    )
    entry = (
        f"worker{process_id}" if process_id is not None else "worker"
    )
    ctx.child(entry).export(env)
    return env


def global_mesh(axis: str = "data"):
    """Mesh over the global device set (all processes). On one host this is
    exactly dist.make_mesh(); on a pod it spans every chip of every host."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), (axis,))


def shard_rows_across_hosts(mesh, local_rows: np.ndarray):
    """Assemble a globally row-sharded array from per-host local rows.

    Each process contributes the rows it loaded from its own generator
    chunks; the result is one global jax.Array sharded over the mesh's
    `data` axis with no cross-host replication of the table. In a
    single-process run this degenerates to a plain device_put with the
    row-sharded spec (the path the tests cover)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P("data"))
    if jax.process_count() == 1:
        return jax.device_put(local_rows, sharding)
    return jax.make_array_from_process_local_data(sharding, local_rows)
