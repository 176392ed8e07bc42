"""Compiles for the real chip, without the chip.

The TPU's compiler is installed in the CPU sandbox and compiles for a chip
that is DESCRIBED (``v5e:2x2``) and not attached.  These tests keep the
programs of the main path compiling there at the capacity the engine
dispatches them (1,048,576 rows), so a refusal (X64 rewrite, HBM, layout)
shows in tier-1 and not on a chip call.

Rules of this file (on-chip-measurement guide §2):
  * the only file of its kind — one xdist worker loads libtpu, and keeps
    its lock until the worker exits;
  * the topology is described inside a module-scoped fixture that skips
    when it cannot be — never at import, in a skipif or in parametrize;
  * compiles run in the test's own process with the persistent compile
    cache off (a compile-only executable cannot be read back from it).

Sort-bearing programs are NOT here: at 1,048,576 rows the cheapest variadic
sort measured 44 s to compile and q1's fused partial aggregate 263 s
(CHANGES.md, PR 22).  Only the 4,096-row kernel variants, measured under
10 s, stand in for them; the real ones are compiled in rehearsal (c).
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from spark_rapids_tpu.kernels import sort as sort_kernels

BATCH_ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branch(monkeypatch):
    """Code that asks jax.default_backend() sees the CPU during such a
    compile; steer it onto its TPU branch here, not through an option of
    the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _abstract(tree, sharding):
    """Arrays -> ShapeDtypeStructs on the described chip (nothing can be
    device_put there)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, args, sharding):
    compiled = jax.jit(fn).lower(*_abstract(args, sharding)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16 * 10**9, mem      # one v5e chip's HBM
    return compiled


def _lineitem_batch(rows):
    from spark_rapids_tpu.testing import tpch
    return tpch.gen_lineitem(rows, batch_rows=rows)[0]


def test_q6_step_compiles_at_batch_capacity(one_chip, tpu_branch):
    import __graft_entry__ as ge
    from spark_rapids_tpu.testing import tpch
    predicate_fn, value_fn = ge._q6_fns(tpch.LINEITEM_SCHEMA)

    def step(b):
        keep, kvalid = predicate_fn(b)
        vals, vvalid = value_fn(b)
        mask = keep & kvalid & vvalid & b.live_mask()
        return (jnp.sum(jnp.where(mask, vals, 0.0)),
                jnp.sum(mask.astype(jnp.int64)))

    _compile(step, (_lineitem_batch(BATCH_ROWS),), one_chip)


@pytest.mark.parametrize("key_fn", [sort_kernels.f64_total_order_u64,
                                    sort_kernels.f64_injective_u64],
                         ids=lambda f: f.__name__)
def test_f64_key_tpu_branch_compiles(one_chip, tpu_branch, key_fn):
    x = np.zeros((BATCH_ROWS,), np.float64)
    compiled = _compile(key_fn, (x,), one_chip)
    assert compiled.output_shardings is not None


def test_raw_f64_bitcast_is_refused(one_chip):
    """Why the TPU branch above exists: the chip has no raw IEEE double
    bits, and the X64 rewrite refuses the direct bitcast.  If a compiler
    upgrade starts accepting it, the split-pack emulation can go."""
    x = np.zeros((BATCH_ROWS,), np.float64)
    with pytest.raises(Exception, match="(?i)x64|unimplemented|bitcast"):
        _compile(lambda v: jax.lax.bitcast_convert_type(v, jnp.uint64),
                 (x,), one_chip)


def test_fused_filter_partial_agg_program_compiles(one_chip, tpu_branch,
                                                   monkeypatch):
    """The program the planner really builds for q6's scan side — fused
    filter + project + partial aggregate, one launch per batch — recorded
    at shared_jit from a CPU run at batch capacity, then compiled for the
    chip.  (q1's and q3's fused programs carry sorts: see module doc.)"""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.plan.execs import base
    from spark_rapids_tpu.testing import tpch

    recorded = []
    real_jit = jax.jit

    def recording_jit(fn, **kw):
        jitted = real_jit(fn, **kw)

        def call(*args):
            if not any(isinstance(x, jax.core.Tracer)
                       for x in jax.tree.leaves(args)):
                recorded.append((jitted, args))
            return jitted(*args)
        return call

    # a fresh program cache: an earlier test of this worker may already
    # hold q6's programs, and then nothing would be built (or recorded)
    monkeypatch.setattr(base, "_JIT_CACHE", collections.OrderedDict())
    monkeypatch.setattr(jax, "jit", recording_jit)
    sess = TpuSession({"spark.rapids.sql.enabled": "true"})
    df = sess.create_dataframe([_lineitem_batch(BATCH_ROWS)],
                               num_partitions=1)
    assert "TpuFusedSegment" in tpch.q6(df).physical_plan().tree_string()
    rows = tpch.q6(df).collect()
    monkeypatch.setattr(jax, "jit", real_jit)
    assert len(rows) == 1 and rows[0][0] > 0

    per_batch = [(j, a) for j, a in recorded
                 if any(getattr(x, "shape", None) == (BATCH_ROWS,)
                        for x in jax.tree.leaves(a))]
    assert per_batch, "no program took a batch at BATCH_ROWS capacity"
    for jitted, args in per_batch:
        compiled = jitted.lower(*_abstract(args, one_chip)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 4 * 10**9


SMALL = 4096     # the largest capacity whose sort compiles in seconds


def test_hash_partition_kernel_compiles_small(one_chip, tpu_branch):
    from spark_rapids_tpu.kernels.partition import hash_partition
    batch = _lineitem_batch(SMALL)
    _compile(lambda b: hash_partition(b, [0], 8, string_max_bytes=0),
             (batch,), one_chip)


def test_sort_kernel_compiles_small(one_chip, tpu_branch):
    from spark_rapids_tpu.kernels.sort import SortOrder, sort_indices
    batch = _lineitem_batch(SMALL)
    _compile(lambda b: sort_indices(b, [3], [SortOrder(True)],
                                    string_max_bytes=0),
             (batch,), one_chip)


def _char1_keys_batch(rows):
    """Two char(1) string keys and a value: the shape of q1's work batch."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
    return ColumnarBatch.from_pydict(
        {"f": ["R", "A", "N", None] * (rows // 4),
         "s": ["O", "F"] * (rows // 2),
         "v": [1.5] * rows},
        Schema.of(f=T.STRING, s=T.STRING, v=T.DOUBLE), capacity=rows)


def test_string_chunk_keys_compile_at_batch_capacity(one_chip, tpu_branch):
    """The byte loop of the string chunk keys (a trip count from the data,
    a uint64 shift by a traced amount under the X64 rewrite, a row of the
    planes updated in place) has no sort: it compiles at the capacity the
    engine dispatches, and so does the compare through an order."""
    from spark_rapids_tpu.kernels.groupby import _string_rows_equal_prev
    # the column's planes at BATCH_ROWS rows: validity and bytes one a row
    # (char(1)), offsets one more
    col = jax.tree.map(
        lambda x: np.zeros((BATCH_ROWS + (x.shape[0] - SMALL),), x.dtype),
        _char1_keys_batch(SMALL).columns[0])

    def keys_and_runs(c, idx):
        planes, steps = sort_kernels._string_chunk_planes(c, 16)
        return planes, _string_rows_equal_prev(c, planes, steps, idx)

    compiled = _compile(keys_and_runs,
                        (col, np.zeros((BATCH_ROWS,), np.int32)), one_chip)
    assert compiled.as_text().count(" while(") >= 2


def test_group_rows_on_string_keys_compiles_small(one_chip, tpu_branch):
    from spark_rapids_tpu.kernels import groupby as G
    batch = _char1_keys_batch(SMALL)

    def grouped(b):
        layout = G.group_rows(b, [0, 1], string_max_bytes=16,
                              allow_split_groups=True)
        keys = G.group_keys_output(layout, [0, 1], out_capacity=256,
                                   string_max_bytes=16)
        return keys, G.seg_sum(layout.sorted_column(2), layout, jnp.float64)

    _compile(grouped, (batch,), one_chip)


def test_join_probe_folding_its_pieces_compiles_small(one_chip, tpu_branch,
                                                      monkeypatch):
    """A shuffled join's first program with both sides as raw exchange
    pieces (six range views a side, as one reduce group of the SF1 Q21 cell
    has; here over 4,096-row backings): the in-trace slices, the two
    concats and the probe's sorts as ONE program."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
    from spark_rapids_tpu.plan.execs import base
    from spark_rapids_tpu.plan.execs.join import _JoinKernel
    from spark_rapids_tpu.shuffle.transport import RangeView
    made = []
    shared_jit = base.shared_jit
    monkeypatch.setattr(
        base, "shared_jit",
        lambda key, make, **kw: shared_jit(
            key, lambda: made.append(make()) or made[-1], **kw))
    kv = Schema.of(k=T.LONG, v=T.LONG)
    kernel = _JoinKernel([0], [0], "left_semi", kv, left_schema=kv,
                         right_schema=kv)
    kernel._jitted_probe(0, "left_semi", (SMALL // 2, SMALL))
    backing = ColumnarBatch.from_pydict(
        {"k": list(range(SMALL)), "v": [1] * SMALL}, kv, capacity=SMALL)
    views = tuple(RangeView(backing, jnp.int32(i * 512), jnp.int32(300),
                            SMALL // 4) for i in range(6))
    _compile(made[-1], (views, views), one_chip)
