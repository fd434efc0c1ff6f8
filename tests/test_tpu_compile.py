"""The main path's operator kernels, compiled for a DESCRIBED TPU v5e.

This is the only file that describes the chip. Nothing here runs on a TPU:
the TPU compiler installed beside jax compiles for a `v5e:2x2` topology
that is described, not attached (on-chip-measurement guide, section 2), so
each case shows only that the chip's compiler ACCEPTS the kernel at TPC-H
SF1 widths and that the program fits the chip's memory. A case that passes
says nothing about results or time on the chip.

Shapes are those the planner chose for q1 and q3 at SF1 (plans printed in
PR 23): lineitem padded to 8Mi rows, orders to 2Mi, q1's group table at
2048 slots, q3's at 2Mi slots and packed to 2Mi rows, q3's first join
building 2Mi slots from orders and expanding into 8Mi rows. The one
exception is the sort, whose case says why.

Rules this file keeps (guide, section 2): the topology is described inside
a module-scoped fixture — never at import, in a `skipif` or in a
`parametrize` — because only one process may load the TPU's library and
every xdist worker imports every test file; compiles run in the test's own
process; and jax's persistent compile cache is off around them, since a
described-device executable has no local devices (tests/conftest.py's
cache-write wrapper asks for them) and could not be read back anyway.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from datafusion_distributed_tpu.ops.aggregate import AggSpec, hash_aggregate
from datafusion_distributed_tpu.ops.join import build_join_table, hash_join
from datafusion_distributed_tpu.ops.sort import SortKey, sort_table
from datafusion_distributed_tpu.ops.table import Column, Table
from datafusion_distributed_tpu.parallel.exchange import (
    shuffle_exchange,
    slot_merge_exchange,
)
from datafusion_distributed_tpu.schema import DataType

MI = 1 << 20
HBM_BYTES = 16e9  # one v5e chip (Google Cloud documentation, "TPU v5e")
AXIS = "tasks"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4]), (AXIS,))


def _shape(sharding, dtype, *dims):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


def _table(columns: dict, capacity: int, sharding,
           lead: tuple = (), masked: bool = True) -> Table:
    """A Table of shapes (no arrays: a described device holds none).
    ``masked``: every column with a validity array, as a column that holds
    a NULL has (and as arrow ingestion made every column until PR 37);
    False: none, as registration makes a column without NULLs (TPC-H's
    all are). ``lead``: extra leading axes (the mesh tier stacks per-task
    slices)."""
    cols = tuple(
        Column(_shape(sharding, np.dtype(dt.np_dtype), *lead, capacity),
               _shape(sharding, jnp.bool_, *lead, capacity) if masked
               else None, dt)
        for dt in columns.values()
    )
    return Table(tuple(columns), cols, _shape(sharding, jnp.int32, *lead))


def _compile(fn, *args, scopes=()):
    """``scopes``: operator scopes (`jax.named_scope`, listed in PERF.md)
    that the chip's optimized program must still carry in its ops'
    ``op_name`` metadata: that is where a profile of the chip finds them."""
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"program needs {total / 1e9:.1f} GB"
    text = compiled.as_text()
    for scope in scopes:
        assert f"/{scope}/" in text, f"no op of the program names {scope}"
    return compiled


Q1_AGGS = (
    [AggSpec("sum", f"v{i}", f"s{i}") for i in range(4)]
    + [AggSpec("avg", f"v{i}", f"a{i}") for i in range(3)]
    + [AggSpec("count_star", None, "n")]
)
GROUP_BY_CASES = {
    # q1: two dictionary-coded keys, eight aggregates, 2048 slots
    "q1": (dict(g0=DataType.STRING, g1=DataType.STRING,
                **{f"v{i}": DataType.FLOAT64 for i in range(4)}),
           ["g0", "g1"], Q1_AGGS, 2048, 2048),
    # q3: (l_orderkey, o_orderdate, o_shippriority) over the join's 8Mi
    # output, 2Mi slots packed into 2Mi rows
    "q3": (dict(g0=DataType.INT64, g1=DataType.DATE32, g2=DataType.INT32,
                v0=DataType.FLOAT64),
           ["g0", "g1", "g2"], [AggSpec("sum", "v0", "revenue")],
           2 * MI, 2 * MI),
}


@pytest.mark.parametrize("query", sorted(GROUP_BY_CASES))
def test_claim_loop_group_by_compiles(one_chip, query):
    columns, keys, aggs, slots, out_capacity = GROUP_BY_CASES[query]

    def kernel(t):
        return hash_aggregate(t, keys, aggs, slots, "single",
                              out_capacity=out_capacity)

    _compile(kernel, _table(columns, 8 * MI, one_chip),
             scopes=("agg.claim", "agg.reduce.sum", "table.gather"))


def _q1_with_dictionaries(one_chip, sizes, rows=8 * MI, masked=False):
    """q1's table at ``rows`` slots (8Mi: SF1; 64Mi: SF10), its two keys
    dictionary-coded with ``sizes`` values. As registration makes them
    since PR 37, no column carries a validity array (TPC-H holds no NULL):
    a domain of ``sizes[0] * sizes[1]``; ``masked``: one each, as a column
    with a NULL has, and a domain of ``(sizes[0] + 1) * (sizes[1] + 1)``."""
    from datafusion_distributed_tpu.ops.table import Dictionary

    columns = GROUP_BY_CASES["q1"][0]
    dictionaries = {
        f"g{i}": Dictionary.from_strings([f"{v:05d}" for v in range(size)])
        for i, size in enumerate(sizes)}
    t = _table(columns, rows, one_chip, masked=masked)
    return Table(t.names, tuple(
        Column(c.data, c.validity, c.dtype, dictionaries.get(name))
        for name, c in zip(t.names, t.columns)), t.num_rows)


def _slot_reductions(text: str) -> list:
    """The result types of the optimized program's reductions into a
    small vector (one a dense pass by slot), as ``s32[6]``."""
    import re

    return re.findall(r"= (\w+\[\d+\])\{[^}]*\} reduce\(", text)


@pytest.mark.parametrize("rows,masked,domain,count_passes", [
    # SF1 and SF10 as they are registered: 3 x 2 slots, and one count
    # pass for the eight aggregates (every ``valid`` is the live mask)
    (8 * MI, False, 6, 1),
    (64 * MI, False, 6, 1),
    # a mask a column (columns that hold NULLs): a NULL digit a key, and a
    # count pass an aggregate input beside `count(*)`'s
    (8 * MI, True, 12, 5),
], ids=["sf1", "sf10", "sf1_masked"])
def test_direct_group_by_compiles(one_chip, rows, masked, domain,
                                  count_passes):
    """q1 as it runs since PR 30: its two keys carry dictionaries of 3 and
    2 values, so the group ids are arithmetic on the codes and the chip's
    program holds no `while` at all. Since PR 37 the registered columns
    carry no validity array: 3 x 2 = 6 slots of the 2048 can be used (12
    with a NULL digit a key), and the per-slot counts are ONE reduction.
    At SF10's 64Mi slots the described v5e accepts the program and it
    fits the chip's 16 GB beside nothing else (`_compile`)."""
    _, keys, aggs, slots, out_capacity = GROUP_BY_CASES["q1"]
    direct: list = []

    def kernel(t):
        return hash_aggregate(t, keys, aggs, slots, "single",
                              out_capacity=out_capacity, direct=direct)

    compiled = _compile(
        kernel, _q1_with_dictionaries(one_chip, (3, 2), rows, masked),
        scopes=("agg.direct", "agg.reduce.sum", "table.gather"))
    assert direct == [domain]
    text = compiled.as_text()
    assert " while(" not in text
    assert "/agg.claim/" not in text
    reduced = _slot_reductions(text)
    assert reduced.count(f"s32[{domain}]") == count_passes, reduced
    # four sums; the three averages share theirs
    assert reduced.count(f"f32[{domain}]") == 4


@pytest.mark.parametrize("domain", ["q1", "q1_masked", "the_cut"])
def test_dense_reduction_compiles(one_chip, domain):
    """q1's reductions as they run since PR 32, at 8Mi rows over its domain
    of 6 (12 where its columns carry masks) and over a domain at the cut
    (`_DENSE_MAX_DOMAIN`): dense masked passes. The chip's compiler accepts
    them, no scatter is left under ``agg.reduce.*`` (only the pack's, over
    the slots), and no ``[domain, rows]`` operand is materialised: the
    temporaries stay within a quarter of what this compiler read: with a
    mask a column 136.4 MB at 12 slots (PR 32; 172.4 MB at the cut),
    without (PR 37) 67.6 MB at 6 slots and 135.7 MB at the cut, where one
    ``f32[12, 8Mi]`` operand alone is 403 MB. (Maskless, q1's are under
    the scatter form's 71 MB, as ISSUE 32 asked; with masks they are not:
    sibling reductions share one pass, so their inputs are live together.)"""
    import re

    from datafusion_distributed_tpu.ops import aggregate

    _, keys, aggs, slots, _ = GROUP_BY_CASES["q1"]
    cut = aggregate._DENSE_MAX_DOMAIN
    assert cut & (cut - 1) == 0 and cut >= 16
    half = cut.bit_length() // 2  # cut = 2^(half) * 2^(rest)
    masked = domain == "q1_masked"
    sizes = (1 << half, cut >> half) if domain == "the_cut" else (3, 2)
    slots = max(slots, cut)
    direct: list = []

    def kernel(t):
        return hash_aggregate(t, keys, aggs, slots, "single",
                              out_capacity=slots, direct=direct)

    compiled = _compile(
        kernel, _q1_with_dictionaries(one_chip, sizes, masked=masked),
        scopes=("agg.direct", "agg.reduce.sum"))
    assert direct == [{"q1": 6, "q1_masked": 12, "the_cut": cut}[domain]]
    text = compiled.as_text()
    assert not re.search(r'op_name="[^"]*/agg\.reduce\.[^"]*scatter', text)
    assert text.count(" scatter(") == 1  # `nonzero` of the pack
    measured = {"q1": 67_592_704, "q1_masked": 136_443_392,
                "the_cut": 135_701_504}[domain]
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries <= 1.25 * measured, temporaries


def test_hash_join_build_and_probe_compiles(one_chip):
    """q3's lineitem x orders: 2Mi slots built from orders (2Mi rows),
    lineitem (8Mi rows) probing and expanding into 8Mi rows."""
    orders = _table(dict(o_orderkey=DataType.INT64, o_custkey=DataType.INT64,
                         o_orderdate=DataType.DATE32,
                         o_shippriority=DataType.INT32), 2 * MI, one_chip)
    lineitem = _table(dict(l_orderkey=DataType.INT64,
                           l_extendedprice=DataType.FLOAT64,
                           l_discount=DataType.FLOAT64,
                           l_shipdate=DataType.DATE32), 8 * MI, one_chip)

    def kernel(probe, build):
        side = build_join_table(build, ["o_orderkey"], 2 * MI, [True])
        return hash_join(probe, side, ["l_orderkey"], "inner", 8 * MI)

    _compile(kernel, lineitem, orders,
             scopes=("join.build", "join.probe", "join.expand"))


def test_multi_key_sort_top_k_compiles(one_chip):
    """q3's ORDER BY revenue DESC, o_orderdate LIMIT 10: one `lax.sort`
    over six operands (dead-row flag, two null flags, two keys, the
    permutation). NOT at q3's SF1 width: the chip's compiler takes minutes
    over this one sort from 2^15 rows up (measured on the sandbox's host in
    PR 23: 326 s at the 2Mi rows q3 sorts at SF1 — all of q3's cold
    compile, ROADMAP S3), so the case keeps the operands and cuts the rows
    to 2^13. The whole q3 program was compiled once at SF1 shapes in PR 23
    (CHANGES.md)."""
    grouped = _table(dict(l_orderkey=DataType.INT64,
                          revenue=DataType.FLOAT64,
                          o_orderdate=DataType.DATE32,
                          o_shippriority=DataType.INT32), 1 << 13, one_chip)

    def kernel(t):
        keys = [SortKey("revenue", ascending=False), SortKey("o_orderdate")]
        return sort_table(t, keys).head(10)

    _compile(kernel, grouped, scopes=("sort.permutation",))


def test_hash_shuffle_compiles_on_four_chips(mesh4):
    """q3's lineitem shuffled on l_orderkey across four chips: 2Mi rows a
    task, 2Mi rows per destination (skew factor 4), `all_to_all` under
    `shard_map` as the mesh tier runs it."""
    sliced = NamedSharding(mesh4, P(AXIS))
    stacked = _table(dict(l_orderkey=DataType.INT64,
                          l_extendedprice=DataType.FLOAT64,
                          l_discount=DataType.FLOAT64,
                          l_shipdate=DataType.DATE32), 2 * MI, sliced,
                     lead=(4,))

    def per_task(stacked_slice):
        local = jax.tree.map(lambda x: x[0], stacked_slice)
        out, overflow = shuffle_exchange(local, ["l_orderkey"], AXIS, 4,
                                         2 * MI)
        return jax.tree.map(lambda x: x[None], (out, overflow))

    program = shard_map(per_task, mesh=mesh4, in_specs=P(AXIS),
                        out_specs=P(AXIS), check_rep=False)
    compiled = _compile(program, stacked, scopes=("exchange.shuffle",))
    assert "all-to-all" in compiled.as_text()


def test_slot_merge_compiles_on_four_chips(mesh4):
    """ClickBench q12's partial counts merged by slot range across four
    chips as the mesh tier runs it: one int32 count a slot of a
    3,124,785-code domain, padded to four ranges of 781,200 slots, beside
    a float sum, an int32 MIN and a float MAX lane. The sums lower to a
    reduce-scatter (or the all-reduce the compiler may put in its place),
    the MIN and the MAX to an `all_to_all` each."""
    per_task = 4 * 781_200
    sliced = NamedSharding(mesh4, P(AXIS))
    lanes = [_shape(sliced, dt, 4 * per_task)
             for dt in (jnp.int32, jnp.float32, jnp.int32, jnp.float32)]

    def merge(count, total, low, high):
        merged = slot_merge_exchange(
            [("sum", count), ("sum", total), ("min", low), ("max", high)],
            AXIS, 4)
        return tuple(merged)

    program = shard_map(merge, mesh=mesh4, in_specs=(P(AXIS),) * 4,
                        out_specs=(P(AXIS),) * 4, check_rep=False)
    text = _compile(program, *lanes,
                    scopes=("exchange.slot_merge",)).as_text()
    assert "reduce-scatter" in text or "all-reduce" in text
    assert text.count("all-to-all") >= 2
