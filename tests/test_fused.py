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
    "project_between": (True, lambda df: (
        df.filter(col("v") > lit(-400))
        .select(col("k"), (col("v") + lit(0)).alias("v"),
                (col("x") * lit(1.0)).alias("x"), col("s"), col("d"),
                col("w"))
        .filter(col("x") < lit(1.2)))),
}


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


def _lineitem_df(s, rows=4096):
    from spark_rapids_tpu.testing import tpch
    return s.create_dataframe(tpch.gen_lineitem(rows, seed=3),
                              num_partitions=2)


def _lowered_text(seg, batch, slice_spec=None, scopes=False):
    """StableHLO of the segment's program for one stream batch, as
    ``_converge`` builds it (no builds, bucket 0); with ``scopes`` also
    the locations that carry each operation's named scopes."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.plan.execs.base import collect_trace_consts
    consts = tuple(jnp.asarray(a)
                   for a in collect_trace_consts(seg._all_exprs()))
    fn = seg._make(0, {}, slice_spec, None)
    return jax.jit(fn).lower(batch, (), consts).as_text(debug_info=scopes)


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


def _q1_sliced(s):
    """q1's map side: the segment and the slice the exchange folds in."""
    from spark_rapids_tpu.plan.execs.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.testing import tpch
    plan = tpch.q1(_lineitem_df(s)).order_by("l_linenumber").physical_plan()
    stack = [plan]
    while stack:
        node = stack.pop()
        if (isinstance(node, TpuShuffleExchangeExec)
                and "FusedSegment" in type(node.children[0]).__name__):
            return node.children[0], (tuple(node.keys), node.out_partitions,
                                      "test")
        stack.extend(node.children)
    raise AssertionError(plan.tree_string())


def _filter_topped(s):
    df = _lineitem_df(s)
    # a filter over a computed column stays above the project
    return _segment(
        df.select((col("l_quantity") + col("l_tax")).alias("q"), "l_shipdate")
        .filter(col("q") > lit(2500, D12_2)).physical_plan()), None


@pytest.mark.parametrize("shape,kind", [
    (_q1_sliced, "fused_agg_filter_project_slice"),
    (_filter_topped, "fused_filter_project")])
def test_other_chains_lower_as_before(monkeypatch, shape, kind):
    """A grouped aggregate sorts a prefix of live rows, and a filter at
    the top hands its rows on: both compact, operation for operation."""
    from spark_rapids_tpu.plan.fused import _masked_filters, _program_kind
    from spark_rapids_tpu.testing import tpch
    s, _ = _sessions()
    seg, slice_spec = shape(s)
    assert _masked_filters(seg.chain) == frozenset()
    assert _program_kind(seg.chain, slice_spec) == kind
    batch = tpch.gen_lineitem(4096, seed=3, batch_rows=4096)[0]
    text = _lowered_text(seg, batch, slice_spec)
    assert "gather" in text
    _compacting(monkeypatch)
    assert _lowered_text(seg, batch, slice_spec) == text


def test_launches_count_under_the_engaged_kind():
    """The counter that says the mask was handed over: q6's launches are
    ``fused_agg_mfilter…``'s, q1's keep the name they had (a renamed
    program compiles cold once: 350 s for q1's on the chip)."""
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
    # the name PR 27's tree gave this program (same data, same session
    # conf): a digest of the cache key, which the mask is no part of
    assert by["fused_agg_filter_project_slice_d9b1c42e"] == 1, by
    assert not any("mfilter" in n for n in by)
