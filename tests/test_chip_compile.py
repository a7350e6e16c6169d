"""The chip's own compiler, without the chip: the four Pallas kernels and a
few fused pipelines compiled for a described TPU v5e at SF1 shapes.

Interpret mode (tests/test_pallas.py) checks what the kernels compute; it
cannot see what Mosaic refuses — block shapes off the (8, 128) tiling,
64-bit values under x64, too much VMEM. The TPU compiler is installed here
and compiles for a device that is described, not attached
(`jax.experimental.topologies`). Nothing runs, so nothing here says a
kernel is right or fast.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, every xdist worker imports every test
file, and a file that loaded it while being collected would take it from
the worker that runs these tests. All of them live in this one file for
the same reason.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest
from jax.sharding import SingleDeviceSharding

from nds_tpu.engine import fuse
from nds_tpu.engine.columnar import bucket_cap
from nds_tpu.engine.session import Session
from nds_tpu.ops import pallas_kernels as PK

#: SF1 `store_sales`, and the capacity bucket the engine pads it to
FACT_ROWS = 2_880_404
FACT_CAP = bucket_cap(FACT_ROWS)
#: SF1 `date_dim` and `item`: the dense join domains of the six queries
DATE_ROWS = 73_049
ITEM_ROWS = 18_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # and can never be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, **static):
    specs = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    lowered = (
        fn.lower(*specs, **static) if hasattr(fn, "lower")
        else jax.jit(fn).lower(*specs)
    )
    return lowered.compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


# ---------------------------------------------------------------------------
# the four Pallas kernels, at GROUP_TILE = 512 / ROW_TILE = 2048
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_groups", [6, 1000])
def test_segment_sums_compiles_for_v5e(one_chip, n_groups):
    _assert_kernel(_compile(
        PK.segment_sums_pallas, one_chip,
        ((FACT_ROWS,), jnp.float32), ((FACT_ROWS,), jnp.int32),
        n_groups=n_groups,
    ))


@pytest.mark.parametrize("is_max", [False, True])
def test_segment_extreme_compiles_for_v5e(one_chip, is_max):
    _assert_kernel(_compile(
        PK.segment_extreme_pallas, one_chip,
        ((FACT_ROWS,), jnp.float32), ((FACT_ROWS,), jnp.int32),
        n_groups=1000, is_max=is_max,
    ))


@pytest.mark.parametrize("rows", [DATE_ROWS, ITEM_ROWS])
def test_dense_build_compiles_for_v5e(one_chip, rows):
    _assert_kernel(_compile(
        PK.dense_build_pallas, one_chip,
        ((rows,), jnp.int64), ((rows,), jnp.bool_), ((), jnp.int64),
        table_cap=rows,
    ))


def test_sort_perm_compiles_for_v5e(one_chip):
    _assert_kernel(_compile(
        PK.sort_perm_pallas, one_chip,
        ((FACT_ROWS,), jnp.int64), domain=PK.SORT_MAX_DOMAIN,
    ))


# ---------------------------------------------------------------------------
# the sparse compaction's two programs at store_sales' capacity (XLA, no
# Pallas): what the v5e's compiler makes of them, without the chip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("out_cap", [65_536, 524_288])
def test_block_select_compiles_for_v5e_and_holds_nothing_of_n_rows(
    one_chip, out_cap
):
    """The n-sized phase keeps no temporary at all (every pass streams) and
    hands on n / 8 bytes of words; the `out_cap` phase's temporaries are of
    `out_cap` rows, never of n."""
    from nds_tpu.ops import kernels as K

    nblocks = FACT_CAP // K._SELECT_BLOCK
    nwords = K._SELECT_BLOCK // 32
    blocks = _compile(K._select_blocks, one_chip, ((FACT_CAP,), jnp.bool_))
    mem = blocks.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes < FACT_CAP  # n / 8 of words + offsets
    rows = _compile(
        K._select_rows, one_chip,
        ((nblocks, nwords), jnp.uint32), ((nblocks,), jnp.int32),
        ((), jnp.int32), out_cap=out_cap,
    )
    hlo = rows.as_text()
    assert len(re.findall(r" gather\(", hlo)) == 1, hlo
    # a row of 16 words is laid out over 128 lanes: 512 B a slot, a few
    # such arrays at most
    assert rows.memory_analysis().temp_size_in_bytes <= 4 * 512 * out_cap


# ---------------------------------------------------------------------------
# a reduction over runs at inventory's capacity (query22's ROLLUP) and at
# store_sales' (query9's global aggregates): no scatter in what the v5e's
# compiler makes of them, and temporaries of a few columns at most
# ---------------------------------------------------------------------------

INVENTORY_CAP = 16_777_216


@pytest.mark.parametrize("dtype", ["int32", "int64"])
@pytest.mark.parametrize("route,n", [
    ("whole", FACT_CAP), ("runs", INVENTORY_CAP),
])
def test_run_reductions_compile_for_v5e_without_a_scatter(
    one_chip, route, n, dtype
):
    from nds_tpu.ops import kernels as K

    gcap = 32_768
    ops = ("sum", "count")
    if route == "whole":
        compiled = _compile(
            lambda v, w: K._reduce_whole.__wrapped__(v, w, 1_024, ops),
            one_chip, ((n,), jnp.dtype(dtype)), ((n,), jnp.bool_),
        )
    else:
        compiled = _compile(
            lambda v, w, s, e: K._reduce_runs.__wrapped__(v, w, (s, e), ops),
            one_chip, ((n,), jnp.dtype(dtype)), ((n,), jnp.bool_),
            ((gcap,), jnp.int32), ((gcap,), jnp.int32),
        )
    hlo = compiled.as_text()
    assert " scatter(" not in hlo
    if route == "whole":
        assert " gather(" not in hlo
    # the prefix sums of a value column and of a count, blocked twice
    assert compiled.memory_analysis().temp_size_in_bytes <= 6 * 8 * n


# ---------------------------------------------------------------------------
# fused pipelines: built as the engine builds them, over a small sample of
# store_sales' column types, then lowered at the SF1 capacity bucket
# ---------------------------------------------------------------------------

_PIPELINES = {
    # Filter -> Project over decimals (the scan side of q3 / q7)
    "filter_project": (
        "select ss_item_sk, ss_ext_sales_price * 2 as twice, "
        "ss_quantity + 1 as q1 from store_sales "
        "where ss_sold_date_sk between 2451000 and 2451200 "
        "and ss_quantity > 10",
        "FusedPipeline",
    ),
    # filter + grouped sums, counts and averages (q7's aggregate tail)
    "grouped_agg": (
        "select ss_store_sk, sum(ss_ext_sales_price) s, count(*) c, "
        "avg(ss_quantity) a from store_sales "
        "where ss_quantity > 10 group by ss_store_sk",
        "FusedAggPipeline",
    ),
    # the cheapest statement: one global count (q96; q9's first shape)
    "global_count": (
        "select count(*) from store_sales where ss_quantity between 5 and 60",
        "FusedAggPipeline",
    ),
    # q9's second shape: a global average over a range of the fact table
    "global_avg": (
        "select avg(ss_ext_sales_price) from store_sales "
        "where ss_quantity between 21 and 40",
        "FusedAggPipeline",
    ),
}


def _store_sales_sample(n=900):
    from decimal import Decimal

    r = np.random.default_rng(7)
    return pa.table({
        "ss_sold_date_sk": pa.array(
            r.integers(2450816, 2452642, n), pa.int32()
        ),
        "ss_item_sk": pa.array(r.integers(1, ITEM_ROWS, n), pa.int32()),
        "ss_store_sk": pa.array(
            [None if i % 17 == 0 else int(v)
             for i, v in enumerate(r.integers(1, 12, n))], pa.int32(),
        ),
        "ss_quantity": pa.array(r.integers(1, 100, n), pa.int32()),
        "ss_ext_sales_price": pa.array(
            [Decimal(int(v)) / 100 for v in r.integers(0, 2_000_000, n)],
            pa.decimal128(7, 2),
        ),
    })


@pytest.mark.parametrize("name", sorted(_PIPELINES))
def test_fused_pipeline_compiles_for_v5e(one_chip, name, tmp_path,
                                         monkeypatch):
    sql, kind = _PIPELINES[name]
    captured = []
    compile_here = fuse._FusedBase._aot_compile

    def capture(self, flat, slots):
        captured.append((
            type(self).__name__, self._fn,
            [(tuple(a.shape), a.dtype) for a in flat],
        ))
        return compile_here(self, flat, slots)

    monkeypatch.setattr(fuse._FusedBase, "_aot_compile", capture)
    sample = _store_sales_sample()
    sess = Session(conf={"engine.aot_cache_dir": str(tmp_path)})
    sess.register_arrow("store_sales", sample)
    sess.sql(sql).collect()
    built = [c for c in captured if c[0] == kind]
    assert built, f"{name}: no {kind} was built ({[c[0] for c in captured]})"
    small = bucket_cap(sample.num_rows)
    for _, fn, avals in built:
        shapes = [
            (tuple(FACT_CAP if d == small else d for d in shape), dtype)
            for shape, dtype in avals
        ]
        assert any(FACT_CAP in shape for shape, _ in shapes)
        compiled = _compile(fn, one_chip, *shapes)
        assert compiled.memory_analysis() is not None
        if name.startswith("global_"):
            # a keyless tail is one run: a masked reduce, no scatter
            assert " scatter(" not in compiled.as_text()


# ---------------------------------------------------------------------------
# the row gather under a mesh: `kernels._gather` is jitted, so on sharded
# buffers GSPMD partitions it (the eager `data[idx]` it replaced was
# dispatched op by op). Fact columns shard on rows over `data`, dimension
# columns replicate (session.py's placement); the index is whatever the
# operator before left it as.
# ---------------------------------------------------------------------------

_MESH_GATHERS = {
    # compaction or sort of a fact table
    "fact_by_sharded_index": ("data", "data"),
    # a dimension's columns under a fact-aligned join index
    "dimension_by_sharded_index": (None, "data"),
    # a fact column under an index some operator replicated
    "fact_by_replicated_index": ("data", None),
}


@pytest.mark.parametrize("dtype", ["int64", "bool"])
@pytest.mark.parametrize("case", sorted(_MESH_GATHERS))
def test_row_gather_partitions_for_a_v5e_mesh(topo, case, dtype):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from nds_tpu.ops import kernels as K

    mesh = Mesh(np.array(topo.devices).reshape(-1), ("data",))
    assert mesh.devices.size == 4
    src, idx = (NamedSharding(mesh, PS(axis)) for axis in _MESH_GATHERS[case])
    rows = DATE_ROWS if _MESH_GATHERS[case][0] is None else FACT_CAP
    data = jax.ShapeDtypeStruct((bucket_cap(rows),), dtype, sharding=src)
    index = jax.ShapeDtypeStruct((FACT_CAP,), jnp.int32, sharding=idx)
    compiled = K._gather.lower(data, index).compile()
    assert compiled.memory_analysis() is not None
    (out,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert out.mesh.devices.size == 4  # laid out on the mesh, by GSPMD
    if case == "dimension_by_sharded_index":
        # the star-query layout's point: a dimension join's gather stays
        # on its chip and its output stays sharded like the index
        assert out.spec == PS("data")
        assert not any(op in compiled.as_text()
                       for op in ("all-gather", "all-reduce", "all-to-all"))
    if dtype == "bool":
        keep = jax.ShapeDtypeStruct((FACT_CAP,), jnp.bool_, sharding=idx)
        masked = K._gather_valid.lower(data, index, keep).compile()
        assert masked.memory_analysis() is not None
