"""Stage-segment fusion tests (plan/fused.py).

Differential discipline: every result is checked against the CPU oracle
AND against the unfused engine (fuseStages=false), which must agree
bitwise — fusion changes launch structure, never semantics.
"""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import round_up_pow2
from spark_rapids_tpu.expressions import col, count, lit, sum_
from tests.test_queries import assert_tpu_cpu_equal


def _sessions():
    return (TpuSession({"spark.rapids.sql.enabled": "true"}),
            TpuSession({"spark.rapids.sql.enabled": "true",
                        "spark.rapids.sql.tpu.fuseStages": "false"}))


SCHEMA = Schema.of(k=T.INT, g=T.STRING, v=T.DOUBLE)
DIM = Schema.of(dk=T.INT, name=T.STRING, flag=T.INT)


def _fact(n=4000, seed=3, nkeys=50):
    rng = np.random.RandomState(seed)
    return ColumnarBatch.from_pydict(
        {"k": (1 + rng.randint(0, nkeys, n)).tolist(),
         "g": [f"g{int(x) % 7}" for x in rng.randint(0, 100, n)],
         "v": np.round(rng.uniform(-5, 5, n), 3).tolist()}, SCHEMA)


def _dim(nkeys=50):
    return ColumnarBatch.from_pydict(
        {"dk": list(range(1, nkeys + 1)),
         "name": [f"name-{i}-{'x' * (i % 11)}" for i in range(nkeys)],
         "flag": [i % 3 for i in range(nkeys)]}, DIM)


def _query(s, dim_pred):
    fact = s.create_dataframe([_fact()], num_partitions=2)
    dim = s.create_dataframe([_dim()], num_partitions=1)
    return (fact
            .join(dim.filter(dim_pred), on=([col("k")], [col("dk")]))
            .filter(col("v") > lit(-4.0))
            .group_by("name")
            .agg(sum_("v").alias("sv"), count().alias("n"))
            .order_by("name"))


def test_fused_plan_shape_and_equality():
    fused_s, unfused_s = _sessions()
    plan = _query(fused_s, col("flag") == lit(1)).physical_plan()
    assert "TpuFusedSegment" in plan.tree_string()
    plan_u = _query(unfused_s, col("flag") == lit(1)).physical_plan()
    assert "TpuFusedSegment" not in plan_u.tree_string()
    rows_f = _query(fused_s, col("flag") == lit(1)).collect()
    rows_u = _query(unfused_s, col("flag") == lit(1)).collect()
    assert rows_f == rows_u          # BITWISE: same kernels, same order
    assert rows_f
    assert_tpu_cpu_equal(
        lambda s: _query(s, col("flag") == lit(1)), ignore_order=False)


def test_fused_empty_build_side_with_string_payload():
    """Code-review regression: an all-filtered build side used to derive
    string bucket 0 and trip the join kernel's positive-window assert."""
    fused_s, unfused_s = _sessions()
    rows_f = _query(fused_s, col("flag") == lit(99)).collect()   # no dims
    rows_u = _query(unfused_s, col("flag") == lit(99)).collect()
    assert rows_f == rows_u == []


def test_fused_left_join_and_semi():
    fused_s, unfused_s = _sessions()

    def q(s, how):
        fact = s.create_dataframe([_fact(1500, seed=9)], num_partitions=2)
        dim = s.create_dataframe([_dim(20)], num_partitions=1)
        df = fact.join(dim.filter(col("flag") <= lit(1)),
                       on=([col("k")], [col("dk")]), how=how)
        cols = ["k", "g", "v"] + ([] if how == "left_semi" else ["name"])
        return df.select(*cols).order_by("k", "g", "v")
    for how in ("left", "left_semi"):
        rows_f = q(fused_s, how).collect()
        rows_u = q(unfused_s, how).collect()
        assert rows_f == rows_u
        assert rows_f


def test_fused_launch_reduction():
    """The point of the feature: fewer program dispatches per query."""
    from spark_rapids_tpu.plan.execs.base import (
        launch_stats, reset_launch_stats)
    fused_s, unfused_s = _sessions()
    counts = {}
    for name, s in (("fused", fused_s), ("unfused", unfused_s)):
        q = _query(s, col("flag") == lit(1))
        q.collect()                  # warm compile + converge capacities
        reset_launch_stats()
        q.collect()
        counts[name] = launch_stats()["launches"]
    assert counts["fused"] < counts["unfused"], counts


def test_fused_capacity_escalation_string_payload():
    """A join whose string payload exceeds the default byte capacity must
    escalate through the feedback loop and still match the oracle."""
    n = 600
    rng = np.random.RandomState(7)
    fact = ColumnarBatch.from_pydict(
        {"k": (1 + rng.randint(0, 5, n)).tolist(),   # heavy fan-in
         "g": ["g"] * n,
         "v": np.round(rng.uniform(0, 1, n), 3).tolist()}, SCHEMA)
    dim = ColumnarBatch.from_pydict(
        {"dk": [1, 2, 3, 4, 5],
         "name": ["N" * 300, "n", "medium-name", "", "x" * 77],
         "flag": [1, 1, 1, 1, 1]}, DIM)

    def build(s):
        f = s.create_dataframe([fact], num_partitions=1)
        d = s.create_dataframe([dim], num_partitions=1)
        return (f.join(d, on=([col("k")], [col("dk")]))
                .group_by("name").agg(count().alias("n"),
                                      sum_("v").alias("sv"))
                .order_by("name"))
    rows = assert_tpu_cpu_equal(build, ignore_order=False)
    assert rows


def test_adaptive_join_over_fused_chain_replans_cleanly():
    """Regression (r5 bench q25 crash): plan-time probes used to trigger
    TpuAdaptiveJoinExec._decide BEFORE stage fusion, caching an inner
    exec that referenced chain nodes fusion later detached — execution
    then hit a childless join.  _plan_partitions + the post-pass reset
    keep the decision at runtime, over the post-fusion tree."""
    schema_f = Schema.of(a=T.INT, b=T.INT, v=T.DOUBLE)
    schema_m = Schema.of(ma=T.INT, mb=T.INT, w=T.DOUBLE)
    schema_d = Schema.of(dk=T.INT, tag=T.STRING)
    n = 4000
    rng = np.random.RandomState(5)
    fact = ColumnarBatch.from_pydict(
        {"a": (1 + rng.randint(0, 50, n)).tolist(),
         "b": (1 + rng.randint(0, 40, n)).tolist(),
         "v": np.round(rng.uniform(0, 9, n), 2).tolist()}, schema_f)
    mid = ColumnarBatch.from_pydict(
        {"ma": (1 + rng.randint(0, 50, 900)).tolist(),
         "mb": (1 + rng.randint(0, 40, 900)).tolist(),
         "w": np.round(rng.uniform(0, 9, 900), 2).tolist()}, schema_m)
    dim = ColumnarBatch.from_pydict(
        {"dk": list(range(1, 41)),
         "tag": [f"t{i % 7}" for i in range(40)]}, schema_d)

    def build(s):
        f = s.create_dataframe([fact], num_partitions=2)
        m = s.create_dataframe([mid], num_partitions=2)
        d = s.create_dataframe([dim], num_partitions=1)
        # bjoin (dim under threshold) BELOW an adaptive join (mid in the
        # ambiguous zone), with a group-by above — the q25 shape
        j = (f.join(d, on=([col("b")], [col("dk")]))
             .join(m, on=([col("a"), col("b")], [col("ma"), col("mb")]))
             .group_by("tag").agg(sum_("v").alias("sv"),
                                  sum_("w").alias("sw"))
             .order_by("tag"))
        return j

    import tests.test_queries as TQ

    def build_conf(s):
        return build(s)
    # route through the tolerant comparator (float summation order
    # differs between the fused two-phase agg and the row-order oracle)
    cpu = TpuSession({"spark.rapids.sql.enabled": "false",
                      "spark.rapids.sql.join.broadcastRowThreshold": "500"})
    tpu = TpuSession({"spark.rapids.sql.enabled": "true",
                      "spark.rapids.sql.join.broadcastRowThreshold": "500"})
    rows_c = build(cpu).collect()
    rows_t = build(tpu).collect()
    assert len(rows_t) == len(rows_c) and rows_t
    for rt, rc in zip(rows_t, rows_c):
        assert all(TQ._eq_val(a, b) for a, b in zip(rt, rc)), (rt, rc)


# -- a filter under a keyless aggregate hands over its mask -------------------
#
# Three engines answer every case: the fused program with the masked filter,
# the unfused engine (fuseStages=false: the per-op filter compacts, then the
# same partial step reduces a prefix) and the CPU oracle.  Floats compare
# within the oracle's tolerance: the rows are the same, their order in the
# sum is not.

D12_2 = T.DecimalType(12, 2)
D25_4 = T.DecimalType(25, 4)
MASK_SCHEMA = Schema.of(k=T.INT, v=T.LONG, x=T.DOUBLE, s=T.STRING,
                        d=D12_2, w=D25_4)


def _mask_batches(n, nulls, seed=11):
    """Two batches of n // 2 rows (capacity the next power of two, so the
    padding must not count), with NULLs in every column when asked."""
    rng = np.random.RandomState(seed)
    data = {"k": rng.randint(0, 9, n).tolist(),
            "v": rng.randint(-1000, 1000, n).tolist(),
            "x": np.round(rng.randn(n), 3).tolist(),
            "s": [f"s{int(i) % 23}-{'y' * (int(i) % 5)}"
                  for i in rng.randint(0, 100, n)],
            "d": rng.randint(-10**6, 10**6, n).tolist(),
            "w": [int(a) * 10**9 for a in rng.randint(-10**9, 10**9, n)]}
    if nulls:
        for c in data:
            for i in rng.choice(n, n // 6, replace=False):
                data[c][i] = None
    half = n // 2
    return [ColumnarBatch.from_pydict(
        {c: vals[lo:hi] for c, vals in data.items()}, MASK_SCHEMA)
        for lo, hi in ((0, half), (half, n))]


def _masked_aggs():
    """update_op -> aggregates whose partial buffers use it."""
    from spark_rapids_tpu.expressions import (
        approx_count_distinct, approx_percentile, bit_and, bit_or, bit_xor,
        collect_list, first, last, max_, max_by, min_, min_by, stddev)
    return {
        "count_star": [count()],
        "count_valid": [count(col("x"))],
        "sum": [sum_("x"), sum_("v")],
        "min": [min_("x"), min_("v"), min_("s")],
        "max": [max_("x"), max_("v"), max_("s")],
        "m2": [stddev("x")],
        "sum128": [sum_("d")],
        "min128": [min_("w")],
        "max128": [max_("w")],
        "hll_update": [approx_count_distinct("v")],
        "collect": [collect_list("x")],
        "td_means": [approx_percentile("x", 0.5)],
        "td_weights": [approx_percentile("x", 0.9)],
        "first": [first("v"), first("s")],
        "first_valid": [first("x", ignore_nulls=True)],
        "last": [last("v")],
        "last_valid": [last("s", ignore_nulls=True)],
        "maxby_val": [max_by("v", "x"), max_by("s", "v")],
        "minby_val": [min_by("v", "s")],
        "bit_and": [bit_and("v")],
        "bit_or": [bit_or("v")],
        "bit_xor": [bit_xor("v")],
    }


_PASS = (col("v") > lit(-400)) & (col("x") < lit(1.2))
_MASK_CASES = {
    # NULLs in the predicate's inputs (v, x) and in every aggregate's
    "nulls": (True, lambda df: df.filter(_PASS)),
    # no NULLs: only the padding of the partial batches must not count
    "partial_batch": (False, lambda df: df.filter(_PASS)),
    # SUM is NULL, COUNT is 0
    "no_row_passes": (True, lambda df: df.filter(col("v") > lit(10**6))),
    # split into two TpuFilterExecs after planning: _stack_filters
    "stacked_filters": (True, lambda df: df.filter(_PASS)),
    # filter, row-local project, filter again over the projected column
    "project_between": (True, lambda df: _project_between(
        df, MASK_SCHEMA.names)),
}


def _project_between(df, names):
    projected = {"v": (col("v") + lit(0)).alias("v"),
                 "x": (col("x") * lit(1.0)).alias("x")}
    return (df.filter(col("v") > lit(-400))
            .select(*[projected.get(c, col(c)) for c in names])
            .filter(col("x") < lit(1.2)))


def _segment(plan):
    from spark_rapids_tpu.plan.fused import TpuFusedSegmentExec
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, TpuFusedSegmentExec):
            return node
        stack.extend(node.children)
    raise AssertionError("no fused segment in\n" + plan.tree_string())


def _stack_filters(seg):
    """The optimizer merges adjacent filters, so stack two by hand: the
    segment's one AND-ed filter becomes a filter per conjunct."""
    from spark_rapids_tpu.plan.execs.basic import TpuFilterExec
    pos, = (i for i, n in enumerate(seg.chain)
            if isinstance(n, TpuFilterExec))
    merged = seg.chain[pos]
    left, right = merged.condition.children
    stacked = [TpuFilterExec(c, merged) for c in (left, right)]
    for n in stacked:
        n.children = ()               # detached, like every chain node
    seg.chain[pos:pos + 1] = stacked
    seg._sig = None


def _mask_query(s, case, aggs, n=700):
    nulls, shape = _MASK_CASES[case]
    df = s.create_dataframe(_mask_batches(n, nulls), num_partitions=2)
    return shape(df).agg(*[a.alias(f"a{i}") for i, a in enumerate(aggs)])


@pytest.mark.parametrize("case", list(_MASK_CASES))
@pytest.mark.parametrize("update_op", list(_masked_aggs()))
def test_masked_filter_matches_compaction_and_oracle(update_op, case):
    from spark_rapids_tpu.plan.engine import TpuEngine
    from spark_rapids_tpu.plan.fused import _masked_filters, _program_kind
    from tests.test_queries import _eq_val
    aggs = _masked_aggs()[update_op]
    fused_s, unfused_s = _sessions()
    cpu_s = TpuSession({"spark.rapids.sql.enabled": "false"})

    plan = _mask_query(fused_s, case, aggs).physical_plan()
    seg = _segment(plan)
    if case == "stacked_filters":
        _stack_filters(seg)
    # the case runs the op it is named after, and every filter is engaged
    assert update_op in {slot.update_op
                         for _, slot in seg.chain[0]._spec.slot_specs}
    kinds = _program_kind(seg.chain, None).split("_")
    assert "mfilter" in kinds and "filter" not in kinds, kinds
    n_filters = {"stacked_filters": 2, "project_between": 2}.get(case, 1)
    assert len(_masked_filters(seg.chain)) == n_filters

    got = TpuEngine(fused_s.conf).collect(plan)
    compacted = _mask_query(unfused_s, case, aggs).collect()
    want = _mask_query(cpu_s, case, aggs).collect()
    assert len(got) == len(compacted) == len(want) == 1
    assert _eq_val(got[0], compacted[0]), (got, compacted)
    assert _eq_val(got[0], want[0]), (got, want)
    if case == "no_row_passes":
        empty = {"count_star": 0, "count_valid": 0, "hll_update": 0,
                 "collect": []}
        assert all(v == empty.get(update_op) for v in got[0]), got


def test_every_mask_update_op_has_a_case():
    """An op enters ``_MASK_UPDATE_OPS`` only with the test above green
    for it."""
    from spark_rapids_tpu.plan.execs.aggregate import _MASK_UPDATE_OPS
    assert set(_masked_aggs()) == set(_MASK_UPDATE_OPS)


# The same five shapes over a GROUPED aggregate: the filter's mask goes to the
# grouping sort as its liveness key (kernels/groupby.py group_rows), and after
# the sort the step is the one a compacted batch runs.  Against the unfused
# engine (the per-op filter compacts) and the CPU oracle, rows in key order.

GMASK_SCHEMA = Schema.of(k=T.INT, kn=T.INT, a=T.STRING, b=T.STRING,
                         v=T.LONG, x=T.DOUBLE, s=T.STRING)


def _gmask_batches(n, nulls, seed=13):
    """Two batches of n // 2 rows: an int key, an int key with NULLs, two
    one-byte string keys (q1's shape), and inputs with NULLs when asked.
    ``x`` has no two equal values, so ``max_by`` over it has no tie."""
    rng = np.random.RandomState(seed)
    data = {"k": rng.randint(0, 7, n).tolist(),
            "kn": rng.randint(0, 5, n).tolist(),
            "a": ["RAN"[i] for i in rng.randint(0, 3, n)],
            "b": ["FO"[i] for i in rng.randint(0, 2, n)],
            "v": rng.randint(-1000, 1000, n).tolist(),
            "x": (rng.permutation(n) / (n / 4.0) - 2.0).tolist(),
            "s": [f"s{int(i) % 23}-{'y' * (int(i) % 5)}"
                  for i in rng.randint(0, 100, n)]}
    for c in ("kn",) + (("v", "x", "s") if nulls else ()):
        for i in rng.choice(n, n // 6, replace=False):
            data[c][i] = None
    half = n // 2
    return [ColumnarBatch.from_pydict(
        {c: vals[lo:hi] for c, vals in data.items()}, GMASK_SCHEMA)
        for lo, hi in ((0, half), (half, n))]


_GMASK_KEYS = {"int": ("k",), "two_strings": ("a", "b"), "null_key": ("kn",)}


def _gmask_aggs():
    """Ops that depend on the order of the rows that pass, or on how many
    of them there are."""
    from spark_rapids_tpu.expressions import (
        avg, collect_list, first, last, max_, max_by, min_, min_by)
    return {
        "sum_count_avg": [sum_("x"), sum_("v"), count(), avg("x")],
        "min_max": [min_("x"), max_("v"), min_("s"), max_("s")],
        "first_last": [first("v"), last("s"),
                       first("x", ignore_nulls=True),
                       last("v", ignore_nulls=True)],
        "collect_list": [collect_list("x")],
        "max_by": [max_by("v", "x"), max_by("s", "x"), min_by("s", "x")],
    }


def _gmask_query(s, case, keys, aggs, n=700):
    nulls, shape = _MASK_CASES[case]
    if case == "project_between":
        def shape(df):
            return _project_between(df, GMASK_SCHEMA.names)
    df = s.create_dataframe(_gmask_batches(n, nulls), num_partitions=2)
    return (shape(df).group_by(*keys)
            .agg(*[a.alias(f"a{i}") for i, a in enumerate(aggs)])
            .order_by(*keys))


@pytest.mark.parametrize("case", list(_MASK_CASES))
@pytest.mark.parametrize("keys", list(_GMASK_KEYS))
@pytest.mark.parametrize("ops", list(_gmask_aggs()))
def test_masked_filter_under_grouped_agg_matches_compaction_and_oracle(
        ops, keys, case):
    from spark_rapids_tpu.plan.engine import TpuEngine
    from spark_rapids_tpu.plan.fused import _masked_filters, _program_kind
    from tests.test_queries import _eq_val
    aggs, keys = _gmask_aggs()[ops], _GMASK_KEYS[keys]
    fused_s, unfused_s = _sessions()
    cpu_s = TpuSession({"spark.rapids.sql.enabled": "false"})

    plan = _gmask_query(fused_s, case, keys, aggs).physical_plan()
    seg, xkeys, n_out = _sliced_segment(plan)
    if case == "stacked_filters":
        _stack_filters(seg)
    kinds = _program_kind(seg.chain, (tuple(xkeys), n_out, "t")).split("_")
    assert kinds[:3] == ["fused", "agg", "mfilter"] and "filter" not in kinds
    n_filters = {"stacked_filters": 2, "project_between": 2}.get(case, 1)
    assert len(_masked_filters(seg.chain)) == n_filters

    got = TpuEngine(fused_s.conf).collect(plan)
    compacted = _gmask_query(unfused_s, case, keys, aggs).collect()
    want = _gmask_query(cpu_s, case, keys, aggs).collect()
    assert len(got) == len(compacted) == len(want)
    assert (len(got) == 0) == (case == "no_row_passes")
    for g, c, w in zip(got, compacted, want):
        assert _eq_val(tuple(g), tuple(c)), (g, c)
        assert _eq_val(tuple(g), tuple(w)), (g, w)


def _lineitem_df(s, rows=4096):
    from spark_rapids_tpu.testing import tpch
    return s.create_dataframe(tpch.gen_lineitem(rows, seed=3),
                              num_partitions=2)


def _lowered_text(seg, batch, slice_spec=None, scopes=False, bucket=0,
                  builds=()):
    """StableHLO of the segment's program for one stream batch, as
    ``_converge`` builds it (``bucket`` 0 where no string is compared,
    ``builds`` where the chain joins); with ``scopes`` also the locations
    that carry each operation's named scopes."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.plan.execs.base import collect_trace_consts
    consts = tuple(jnp.asarray(a)
                   for a in collect_trace_consts(seg._all_exprs()))
    fn = seg._make(bucket, {}, slice_spec, None)
    return jax.jit(fn).lower(batch, tuple(builds),
                             consts).as_text(debug_info=scopes)


def _compacting(monkeypatch):
    """The lowering every filter had before masks were handed over."""
    from spark_rapids_tpu.plan import fused
    monkeypatch.setattr(fused, "_masked_filters", lambda chain: frozenset())


def test_q6_program_holds_no_gather_and_no_scatter(monkeypatch):
    from spark_rapids_tpu.testing import tpch
    s, _ = _sessions()
    seg = _segment(tpch.q6(_lineitem_df(s)).physical_plan())
    batch = tpch.gen_lineitem(4096, seed=3, batch_rows=4096)[0]
    text = _lowered_text(seg, batch)
    assert "gather" not in text and "scatter" not in text
    # the scope the device trace shows
    assert "/mfilter/" in _lowered_text(seg, batch, scopes=True)
    # and the text would say so: the compacting lowering holds both
    _compacting(monkeypatch)
    text = _lowered_text(seg, batch)
    assert "gather" in text and "scatter" in text


def _spec_client(tmp_path, cell_name, rows):
    """The benchmark's client for a cell over a small table of its own,
    written as the benchmark writes it: two files of one row group."""
    from benchmark import datagen, run as bench_run
    cell = bench_run.load_cell(cell_name)
    spec = cell.config["tables"]["lineitem"]
    files = datagen.write_table(
        str(tmp_path), cell.tables["lineitem"], "lineitem", rows,
        spec["files"], rows // spec["files"], 5,
        spec["scale_factor"] * rows / spec["rows"])
    return bench_run.Client(cell, {"lineitem": files}, {"lineitem": rows})


def _spec_q1_sliced(tmp_path, rows=20000):
    """The benchmark's q1 (two char(1) string keys): the segment, its slice
    and one scan batch, of capacity 16,384, over the default group
    capacity."""
    client = _spec_client(tmp_path, "q1_parquet_sf1", rows)
    seg, keys, n_out = _sliced_segment(client.frame("q1").physical_plan())
    batch = next(iter(seg.children[0].execute_partition(0)))
    return seg, (tuple(keys), n_out, "test"), batch


def _string_gather_loops(text, capacity):
    """Calls of the ``searchsorted`` loop (one a gathered string column)
    over an offsets plane of ``capacity`` rows."""
    import re
    return len(re.findall(
        rf"call @searchsorted\w*\([^)]*\) : \(tensor<{capacity + 1}xi32>",
        text))


def test_q1_program_moves_its_rows_once(monkeypatch, tmp_path):
    """The filter under q1's grouped aggregate hands its mask to the
    grouping sort, and the grouping moves no key column (PR 34: the keys
    are read from the unsorted batch through the order, at the group
    capacity): no string gather at the batch's capacity is left, where the
    lowering whose filter compacts keeps the filter's two, and the
    filter's ``compaction_map`` and ``gather_batch`` are gone."""
    import re

    from spark_rapids_tpu.kernels.strings import MIN_BUCKET
    from spark_rapids_tpu.plan import fused
    seg, slice_spec, batch = _spec_q1_sliced(tmp_path)
    cap = batch.capacity
    assert cap == 16384 > fused.GROUP_CAP_DEFAULT
    assert fused._program_kind(seg.chain, slice_spec) == \
        "fused_agg_mfilter_slice"
    text = _lowered_text(seg, batch, slice_spec, scopes=True,
                         bucket=MIN_BUCKET)
    _compacting(monkeypatch)
    assert fused._program_kind(seg.chain, slice_spec) == \
        "fused_agg_filter_slice"
    compacting = _lowered_text(seg, batch, slice_spec, scopes=True,
                               bucket=MIN_BUCKET)
    assert _string_gather_loops(compacting, cap) == 2
    assert _string_gather_loops(text, cap) == 0
    # the keys born at the group capacity and the slice's gather: as before
    small = fused.GROUP_CAP_DEFAULT
    assert _string_gather_loops(text, small) == \
        _string_gather_loops(compacting, small) == 4
    assert "/mfilter/" in text and "/filter/" not in text
    assert "/filter/compaction_map" in compacting
    assert "/filter/gather_batch" in compacting
    # the compaction's scatter went with it, and nothing came in its place
    scatters = [len(re.findall(r"stablehlo\.scatter", t))
                for t in (text, compacting)]
    assert scatters[0] < scatters[1], scatters
    # the aggregate's inputs are what the grouping still moves
    assert "/gather_sorted" in text and "/group_rows/gather_batch" not in text


def _filter_under_join(s):
    """A filter under a join under a grouped aggregate: the join reads a
    prefix of live rows, so this filter compacts."""
    fact = s.create_dataframe([_fact()], num_partitions=2)
    dim = s.create_dataframe([_dim()], num_partitions=1)
    plan = (fact.filter(col("v") > lit(-4.0))
            .join(dim, on=([col("k")], [col("dk")]))
            .group_by("name").agg(sum_("v").alias("sv")).physical_plan())
    seg, keys, n_out = _sliced_segment(plan)
    return seg, (tuple(keys), n_out, "test"), _fact()


def _filter_topped(s):
    from spark_rapids_tpu.testing import tpch
    df = _lineitem_df(s)
    # a filter over a computed column stays above the project
    return _segment(
        df.select((col("l_quantity") + col("l_tax")).alias("q"), "l_shipdate")
        .filter(col("q") > lit(2500, D12_2)).physical_plan()), None, \
        tpch.gen_lineitem(4096, seed=3, batch_rows=4096)[0]


@pytest.mark.parametrize("shape,kind", [
    (_filter_under_join, "fused_agg_join_filter_project_slice"),
    (_filter_topped, "fused_filter_project")])
def test_other_chains_lower_as_before(monkeypatch, shape, kind):
    """A join reads a prefix of live rows, and a filter at the top hands
    its rows on: both compact, operation for operation."""
    from spark_rapids_tpu.plan.fused import _masked_filters, _program_kind
    s, _ = _sessions()
    seg, slice_spec, batch = shape(s)
    assert _masked_filters(seg.chain) == frozenset()
    assert _program_kind(seg.chain, slice_spec) == kind
    builds = tuple(seg._materialize_builds())
    text = _lowered_text(seg, batch, slice_spec, bucket=32, builds=builds)
    assert "gather" in text
    _compacting(monkeypatch)
    assert _lowered_text(seg, batch, slice_spec, bucket=32,
                         builds=builds) == text
    seg.cleanup()


def test_launches_count_under_the_engaged_kind():
    """The counter that says the mask was handed over: q6's launches are
    ``fused_agg_mfilter…``'s and so are q1's, none is counted under a
    ``…_filter…`` name (a renamed program compiles cold once: 350 s for
    q1's on the chip)."""
    from spark_rapids_tpu.plan.execs.base import (
        launch_stats, reset_launch_stats)
    from spark_rapids_tpu.testing import tpch
    s, _ = _sessions()
    df = _lineitem_df(s)
    reset_launch_stats()
    assert tpch.q6(df).collect()
    by = launch_stats()["by_program"]
    kinds = {n.rpartition("_")[0]: c for n, c in by.items()}
    assert kinds == {"fused_agg_mfilter_project": 1, "agg_combine": 1}
    reset_launch_stats()
    assert tpch.q1(df).order_by("l_linenumber").collect()
    by = launch_stats()["by_program"]
    # a digest of the cache key, which the mask is no part of and which
    # holds the filter's literal by its type (it is an argument of the
    # program); the kind before it names the chain, and says `mfilter`
    # since the grouped aggregate takes the mask
    assert by["fused_agg_mfilter_project_slice_f46377dc"] == 1, by
    assert not any("_filter" in n for n in by)


# -- a grouped partial aggregate's group capacity (g<pos> caps/feedback) ------

GSCHEMA = Schema.of(a=T.STRING, b=T.STRING, k=T.INT, v=T.DOUBLE)


def _group_batches(rows, n_batches, distinct_k):
    """Batches of ``rows`` rows (capacity: the next power of two): two
    seven-byte string keys with 3 × 2 values, an int key with
    ``distinct_k`` values (None: every row its own)."""
    out = []
    for bi in range(n_batches):
        rng = np.random.RandomState(17 + bi)
        k = (np.arange(rows) + bi * rows if distinct_k is None
             else rng.randint(0, distinct_k, rows))
        out.append(ColumnarBatch.from_pydict(
            {"a": [("A" * 7, "N" * 7, "R" * 7)[i]
                   for i in rng.randint(0, 3, rows)],
             "b": [("F" * 7, "O" * 7)[i] for i in rng.randint(0, 2, rows)],
             "k": k.tolist(),
             "v": np.round(rng.uniform(-5, 5, rows), 3).tolist()}, GSCHEMA))
    return out


_GROUP_CASES = {
    # case: (rows a batch, batches, distinct k, group keys,
    #        capacity of the partial batches, launches discarded by batch)
    # six groups in a batch of capacity 16,384: born at the default, the
    # keys' byte planes at 4,096 × 16 bytes for the input's 131,072
    "low_cardinality_strings": (12000, 2, 1, ("a", "b"), 4096, [0, 0]),
    # some 6,000 groups in a batch of capacity 16,384: the first batch runs
    # again at the input's capacity (not at 8,192: one discarded launch and
    # one more program however later batches' counts grow), the second
    # batch of the same signature starts there
    "more_groups_than_default": (12000, 2, 1000, ("a", "b", "k"), 16384,
                                 [1, 0]),
    # every key distinct: the step at the input's capacity fits them
    "all_keys_distinct": (8192, 1, None, ("k",), 8192, [1]),
    # a batch smaller than the default: its own capacity, nothing discarded
    "batch_under_default": (700, 1, 50, ("a", "k"), 1024, [0]),
}


def _group_query(s, case):
    rows, n_batches, distinct_k, keys, _, _ = _GROUP_CASES[case]
    # two partitions, a batch each: the aggregate plans as partial + final
    df = s.create_dataframe(_group_batches(rows, n_batches, distinct_k),
                            num_partitions=2)
    return (df.filter(col("v") > lit(-4.5)).group_by(*keys)
            .agg(sum_("v").alias("sv"), count().alias("n")).order_by(*keys))


def _sliced_segment(plan):
    """The map side of the plan's exchange: the fused segment and the
    slice the exchange folds into its program."""
    from spark_rapids_tpu.plan.execs.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.plan.fused import TpuFusedSegmentExec
    stack = [plan]
    while stack:
        node = stack.pop()
        if (isinstance(node, TpuShuffleExchangeExec)
                and isinstance(node.children[0], TpuFusedSegmentExec)):
            return node.children[0], node.keys, node.out_partitions
        stack.extend(node.children)
    raise AssertionError(plan.tree_string())


def _run_sliced(s, case):
    """Every (partial batch, per-partition counts, launches discarded for
    it) of the case's map side, on fresh program and capacity caches."""
    from spark_rapids_tpu.plan.execs.base import (
        launch_stats, reset_launch_stats)
    seg, keys, n_out = _sliced_segment(_group_query(s, case).physical_plan())
    out = []
    reset_launch_stats()
    for part in range(seg.num_partitions()):
        for batch, counts in seg.execute_partition_sliced(part, keys, n_out,
                                                          "t"):
            out.append((batch, np.asarray(counts),
                        launch_stats()["discarded"].get("group_cap", 0)))
            reset_launch_stats()
    seg.cleanup()
    return out


def _live_rows(batch):
    return sorted(zip(*(c.to_pylist(batch.host_num_rows())
                        for c in batch.columns)), key=repr)


@pytest.fixture
def fresh_program_caches(monkeypatch):
    """Converged capacities and programs are remembered per signature for
    the life of the process: a test that counts launches starts clean."""
    import collections

    from spark_rapids_tpu.plan import fused
    from spark_rapids_tpu.plan.execs import base
    monkeypatch.setattr(fused, "_FUSED_CAPS", collections.OrderedDict())
    monkeypatch.setattr(fused, "_FUSED_BUCKET", collections.OrderedDict())
    monkeypatch.setattr(base, "_JIT_CACHE", collections.OrderedDict())


@pytest.mark.parametrize("case", list(_GROUP_CASES))
def test_grouped_partial_agg_hands_on_its_group_capacity(
        case, monkeypatch, fresh_program_caches):
    """Under a sliced exchange the partial batches have the speculated
    group capacity, a batch with more groups runs once more and the next
    one of its signature once, and rows and per-partition counts are those
    of the step at the input's capacity and of the CPU oracle."""
    import collections

    from spark_rapids_tpu.kernels.strings import MIN_BUCKET as bucket
    from spark_rapids_tpu.plan import fused
    from spark_rapids_tpu.plan.execs import base
    rows, n_batches, _, _, want_cap, want_discarded = _GROUP_CASES[case]
    s, _ = _sessions()
    got = _run_sliced(s, case)
    assert [b.capacity for b, _, _ in got] == [want_cap] * n_batches
    assert [d for _, _, d in got] == want_discarded
    with monkeypatch.context() as m:
        # the parent's step: a default that no batch is larger than
        m.setattr(fused, "GROUP_CAP_DEFAULT", 1 << 30)
        m.setattr(fused, "_FUSED_CAPS", collections.OrderedDict())
        m.setattr(base, "_JIT_CACHE", collections.OrderedDict())
        parent = _run_sliced(s, case)
    assert [b.capacity for b, _, _ in parent] == \
        [round_up_pow2(rows)] * n_batches
    assert [d for _, _, d in parent] == [0] * n_batches
    for (b, counts, _), (pb, pcounts, _) in zip(got, parent):
        assert counts.tolist() == pcounts.tolist()
        assert _live_rows(b) == _live_rows(pb)
        for c, pc in zip(b.columns, pb.columns):
            if c.is_string_like and want_cap < pb.capacity:
                assert c.byte_capacity == min(pc.byte_capacity,
                                              want_cap * bucket)
    assert_tpu_cpu_equal(lambda sess: _group_query(sess, case),
                         ignore_order=False)


@pytest.mark.parametrize("case", list(_GROUP_CASES))
def test_masked_grouped_agg_under_a_slice_matches_the_compacting_lowering(
        case, monkeypatch, fresh_program_caches):
    """``_group_query``'s filter hands over its mask.  Under a sliced
    exchange the partial batches' rows and per-partition counts are those
    of the lowering whose filter compacts, and so is what a batch with
    more groups than the default capacity costs: one discarded launch,
    then the rows of the step at the input's capacity."""
    import collections

    from spark_rapids_tpu.plan import fused
    from spark_rapids_tpu.plan.execs import base
    _, n_batches, _, _, want_cap, want_discarded = _GROUP_CASES[case]
    s, _ = _sessions()
    seg, keys, n_out = _sliced_segment(_group_query(s, case).physical_plan())
    # (a project under the filter where the scan's columns are pruned)
    assert fused._program_kind(seg.chain, (tuple(keys), n_out, "t")) in (
        "fused_agg_mfilter_slice", "fused_agg_mfilter_project_slice")
    got = _run_sliced(s, case)
    assert [d for _, _, d in got] == want_discarded
    with monkeypatch.context() as m:
        # both lowerings are built under one cache key (the mask is no
        # part of it: a chain has one lowering outside this test)
        _compacting(m)
        m.setattr(fused, "_FUSED_CAPS", collections.OrderedDict())
        m.setattr(base, "_JIT_CACHE", collections.OrderedDict())
        compacted = _run_sliced(s, case)
    assert [d for _, _, d in compacted] == want_discarded
    assert len(got) == len(compacted) == n_batches
    for (b, counts, _), (cb, ccounts, _) in zip(got, compacted):
        assert b.capacity == cb.capacity == want_cap
        assert b.host_num_rows() == cb.host_num_rows() > 0
        assert counts.tolist() == ccounts.tolist()
        assert _live_rows(b) == _live_rows(cb)


def test_a_discarded_launch_is_one_fused_discard_span(fresh_program_caches):
    """One ``fused.discard`` span, a child of its ``fused.batch``, and
    ``group_cap`` in ``launch_stats()["discarded"]`` for the batch that
    outgrew the default in a process's first query; none once the
    signature remembers its capacity.  The span runs from the launch's
    dispatch to the feedback that condemned it: that ``fused.feedback``
    lies inside it."""
    from spark_rapids_tpu.plan.execs.base import (
        launch_stats, reset_launch_stats)
    from spark_rapids_tpu.utils import tracing
    s, _ = _sessions()
    df = _group_query(s, "more_groups_than_default")
    seen = []
    tracing.span_log.enabled = True     # a sink on: collect() keeps a trace
    try:
        for _ in range(2):
            reset_launch_stats()
            tracing.span_log.clear()
            assert df.collect()
            spans = s.last_query_trace.spans_snapshot()
            batches = [sp["id"] for sp in spans if sp["name"] == "fused.batch"]
            discards = [sp for sp in spans if sp["name"] == "fused.discard"]
            seen.append((sorted(sum(d["parent"] == b for d in discards)
                                for b in batches),
                         launch_stats()["discarded"],
                         tracing.span_log.summary().get("fused.discard",
                                                        (0, 0.0))[0]))
            for d in discards:
                assert any(sp["name"] == "fused.feedback"
                           and sp["parent"] == d["parent"]
                           and d["t0"] <= sp["t0"] and sp["t1"] <= d["t1"]
                           for sp in spans), (d, spans)
            assert all("tags" not in sp for sp in spans
                       if sp["name"].startswith("fused."))
    finally:
        tracing.span_log.enabled = False
        tracing.span_log.clear()
    assert seen == [([0, 1], {"group_cap": 1}, 1), ([0, 0], {}, 0)]


BSCHEMA = Schema.of(k=T.INT, a=T.STRING, b=T.STRING, s=T.STRING, v=T.DOUBLE,
                    x=T.INT)


def _buffer_batches(rows=12000, groups=4000):
    """Two batches of capacity 16,384 with ``groups`` groups each, just
    under the default group capacity: ``a`` and ``b`` are 16-byte strings
    that follow the key (so whichever row ``first`` picks, its value is the
    group's), ``s`` a 16-byte string and ``v`` a double that vary by row."""
    out = []
    for bi in range(2):
        rng = np.random.RandomState(29 + bi)
        k = rng.randint(0, groups, rows)
        out.append(ColumnarBatch.from_pydict(
            {"k": k.tolist(),
             "a": [f"a{i:015d}" for i in k], "b": [f"b{i:015d}" for i in k],
             "s": [f"s{i:015d}" for i in rng.randint(0, 10**9, rows)],
             "v": rng.permutation(rows).astype(float).tolist(),
             "x": rng.randint(0, 50, rows).tolist()}, BSCHEMA))
    return out


def _buffer_aggs():
    """Case -> aggregates whose partial buffers are strings or arrays."""
    from spark_rapids_tpu.expressions import (
        ConcatStrings, approx_count_distinct, approx_percentile,
        collect_list, first, last, max_, max_by, min_, min_by)
    ab = ConcatStrings(col("a"), col("b"))      # 32 bytes: twice the bucket
    return {
        # 4,000 picked strings of 32 bytes are 128,000 bytes, and 4,096 rows
        # of the 16-byte bucket 65,536: a picked value keeps the source plane
        "pick_grown_string": [first(ab), last(ab, ignore_nulls=True)],
        "pick_by_grown_string": [max_by(ab, "v"), min_by(ab, "v")],
        # order-compared under the bucket: the plane is cut to 65,536 bytes
        "extreme_string": [min_("s"), max_("s"), max_by("v", "s")],
        "collect": [collect_list("x")],
        "sketches": [approx_count_distinct("x"),
                     approx_percentile("v", 0.5)],
    }


def _buffer_query(s, case):
    df = s.create_dataframe(_buffer_batches(), num_partitions=2)
    aggs = [a.alias(f"a{i}") for i, a in enumerate(_buffer_aggs()[case])]
    return df.group_by("k").agg(*aggs).order_by("k")


@pytest.mark.parametrize("case", list(_buffer_aggs()))
def test_group_capacity_keeps_string_and_array_buffers_whole(
        case, fresh_program_caches):
    """Near ``GROUP_CAP_DEFAULT`` groups, the partial batch at the group
    capacity holds every string and array buffer whole: a picked string may
    be longer than the bucket that bounds the keys and the min/max buffers,
    so only those have their byte planes cut."""
    from spark_rapids_tpu.kernels.strings import MIN_BUCKET as bucket
    from spark_rapids_tpu.plan import fused
    from tests.test_queries import _eq_val
    # grouped approx_count_distinct plans for the device only where a
    # batch's registers fit: batchSizeRows × 2^p <= 64 M
    s = TpuSession({"spark.rapids.sql.enabled": "true",
                    "spark.rapids.sql.batchSizeRows": "16384"})
    df = _buffer_query(s, case)
    seg, keys, n_out = _sliced_segment(df.physical_plan())
    cap = fused.GROUP_CAP_DEFAULT
    spec, = (n._spec for n in seg.chain if hasattr(n, "_spec"))
    nkeys = len(spec.group_exprs)
    ordered = set(spec._string_order_slots())
    for part in range(seg.num_partitions()):
        for batch, _ in seg.execute_partition_sliced(part, keys, n_out, "t"):
            assert batch.capacity == cap
            assert 3000 < batch.host_num_rows() <= cap
            for si, c in enumerate(batch.columns[nkeys:]):
                if not c.is_string_like:
                    continue
                live = int(c.offsets[batch.host_num_rows()])
                assert live <= c.byte_capacity, (si, live, c.byte_capacity)
                assert (c.byte_capacity == cap * bucket) == (si in ordered)
    seg.cleanup()
    got = df.collect()
    want = _buffer_query(TpuSession({"spark.rapids.sql.enabled": "false"}),
                         case).collect()
    assert len(got) == len(want) > 3900
    for g, w in zip(got, want):
        assert _eq_val(tuple(g), tuple(w)), (g, w)


@pytest.mark.parametrize("keys", [("a",), ("a", "b"), ("b", "k")],
                         ids=lambda k: "_".join(k))
def test_group_capacity_with_string_keys_and_string_buffers(
        keys, fresh_program_caches):
    """String keys read from the unsorted batch at the group starts (PR
    34), beside min/max of a string and first/last: the partial batch at
    the group capacity holds the oracle's groups, its keys' byte planes cut
    to the bucket."""
    from spark_rapids_tpu.expressions import first, last, max_, min_
    from spark_rapids_tpu.kernels.strings import MIN_BUCKET as bucket
    from spark_rapids_tpu.plan import fused
    from tests.test_queries import _eq_val

    def query(s):
        df = s.create_dataframe(_buffer_batches(), num_partitions=2)
        return df.group_by(*keys).agg(
            min_("s").alias("lo"), max_("s").alias("hi"),
            first("a").alias("fa"), last("b", ignore_nulls=True).alias("lb"),
            sum_("v").alias("sv"), count().alias("n")).order_by(*keys)

    s = TpuSession({"spark.rapids.sql.enabled": "true",
                    "spark.rapids.sql.batchSizeRows": "16384"})
    seg, xkeys, n_out = _sliced_segment(query(s).physical_plan())
    cap = fused.GROUP_CAP_DEFAULT
    for part in range(seg.num_partitions()):
        for batch, _ in seg.execute_partition_sliced(part, xkeys, n_out, "t"):
            assert batch.capacity == cap
            assert 3000 < batch.host_num_rows() <= cap
            for c in batch.columns[:len(keys)]:
                assert (not c.is_string_like
                        or c.byte_capacity == cap * bucket)
    seg.cleanup()
    got = query(s).collect()
    want = query(TpuSession({"spark.rapids.sql.enabled": "false"})).collect()
    assert len(got) == len(want) > 3900
    for g, w in zip(got, want):
        assert _eq_val(tuple(g), tuple(w)), (g, w)


@pytest.mark.parametrize("cell_name,qname,program", [
    # keyless: no capacity, no caps key; the filter's literals are
    # arguments, keyed by type, so every substitution set runs this program
    ("q6_parquet_sf1", "q6", "fused_agg_mfilter_ff84b1e8"),
    # grouped: named after the key it is first built under, which holds no
    # capacity yet; `mfilter` since its filter hands over the mask
    ("q1_parquet_sf1", "q1", "fused_agg_mfilter_slice_f0e1ca90")])
def test_spec_query_programs_keep_their_names(
        tmp_path, fresh_program_caches, cell_name, qname, program):
    """A program's name is a digest of its cache key and part of the
    persistent compile cache's: the names the chip's cache holds (PERF.md
    §5) are the names a fresh process gives."""
    from spark_rapids_tpu.plan import fused
    from spark_rapids_tpu.plan.execs.base import (
        launch_stats, reset_launch_stats)
    # a small table, written as the benchmark writes it
    client = _spec_client(tmp_path, cell_name, 6000)
    reset_launch_stats()
    assert client.frame(qname).collect()
    by = launch_stats()["by_program"]
    assert by.get(program) == 2, by      # a batch from each of two files
    caps, = fused._FUSED_CAPS.values()
    assert caps == ({} if qname == "q6" else {"g0": 4096})
