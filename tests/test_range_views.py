"""Device-resident range views for the CACHE_ONLY shuffle store
(shuffle/transport.py RangeView + CacheOnlyTransport.write_partitioned;
ISSUE 11 tentpole).

Differential discipline: the range-view path must be row-identical to
the CPU oracle over skewed / null-heavy / string-keyed / empty-partition
inputs, and the store's blocks to slice_by_counts of the same batch.
The counter-pinned tests prove the perf CLAIM: a CACHE_ONLY reduce group
is ONE fused program with the per-partition slices folded in-trace
(slice_gather_programs == 0, range_view_folds > 0), and the spill/retry
tests prove the hard part — a backing batch SHARED by several views pins
exactly once per attempt, stays spillable after an injected OOM, and is
never orphaned by a teardown that drops view-backed blocks.
"""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.expressions import col, count, lit, sum_
from tests.test_queries import assert_tpu_cpu_equal

FACT = Schema.of(k=T.INT, sk=T.STRING, v=T.DOUBLE, tag=T.STRING)

CONF = {"spark.rapids.sql.enabled": "true"}

#: the four options ISSUE 31 deleted: a conf map that still carries them
#: is read like any map with unregistered keys
OLD_HATCHES_CLOSED = {
    "spark.rapids.sql.fusion.acrossShuffle": "false",
    "spark.rapids.shuffle.pipeline.enabled": "false",
    "spark.rapids.shuffle.write.rangeSerialize": "false",
    "spark.rapids.shuffle.cacheOnly.rangeViews": "false"}


def _fact(n=5000, seed=7, nkeys=37, skew_frac=0.0, null_frac=0.15,
          empty_tail=False):
    """Skewed / null-heavy / string-keyed shuffle input.  ``empty_tail``
    routes every row to ONE key so most reduce partitions are empty."""
    rng = np.random.RandomState(seed)
    k = 1 + rng.randint(0, nkeys, n)
    if skew_frac:
        k[rng.uniform(size=n) < skew_frac] = 7
    if empty_tail:
        k[:] = 13
    nulls = rng.uniform(size=n) < null_frac
    ks = [None if dead else int(x) for x, dead in zip(k, nulls)]
    return ColumnarBatch.from_pydict(
        {"k": ks,
         "sk": [None if dead else f"key-{int(x) % nkeys}-{'y' * (x % 11)}"
                for x, dead in zip(k, nulls)],
         "v": np.round(rng.uniform(-10, 10, n), 3).tolist(),
         "tag": [f"t{int(x) % 6}" for x in rng.randint(0, 1000, n)]}, FACT)


def _norm(rows):
    return sorted(
        (tuple(round(v, 6) if isinstance(v, float) else v for v in r)
         for r in rows),
        key=lambda r: tuple((v is None, v) for v in r))


def _agg_query(s, batches, key="k"):
    """Group-by over a CACHE_ONLY exchange keyed on ``key`` — the reduce
    side consumes the exchange's pieces (fused fold when available)."""
    df = s.create_dataframe(list(batches), num_partitions=2)
    return (df.group_by(key, "tag")
            .agg(sum_("v").alias("sv"), count().alias("n"))
            .order_by(key, "tag"))


@pytest.mark.parametrize("shape", ["plain", "skewed", "null_heavy",
                                   "string_keyed", "empty_partitions"])
def test_range_view_exchange_matches_oracle(shape):
    """Row-identical to the CPU oracle across the adversarial input
    shapes, rows in order."""
    key = "k"
    kwargs = {}
    if shape == "skewed":
        kwargs = {"skew_frac": 0.7}
    elif shape == "null_heavy":
        kwargs = {"null_frac": 0.6}
    elif shape == "string_keyed":
        key = "sk"
    elif shape == "empty_partitions":
        kwargs = {"empty_tail": True, "null_frac": 0.0}
    batches = [_fact(seed=41, **kwargs), _fact(seed=42, n=2500, **kwargs)]
    assert assert_tpu_cpu_equal(
        lambda s: _agg_query(s, batches, key=key), ignore_order=False)


def test_q25_shape_counters_one_program_no_slice_gathers():
    """The acceptance pin: on a CACHE_ONLY shuffled-join shape the
    reduce group runs as ONE fused program with every map-side slice
    folded in-trace — range_view_folds > 0, slice_gather_programs == 0,
    and zero materialize fallbacks."""
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    conf = dict(CONF, **{
        "spark.rapids.sql.join.broadcastRowThreshold": "1",
        "spark.rapids.sql.join.adaptive.enabled": "false"})
    s = TpuSession(conf)
    fact = s.create_dataframe([_fact(seed=51, null_frac=0.0)],
                              num_partitions=2)
    dim = s.create_dataframe([_fact(seed=52, n=900, null_frac=0.0)],
                             num_partitions=2)
    df = (fact.join(dim.select(col("k").alias("dk"),
                               col("v").alias("w")),
                    on=([col("k")], [col("dk")]))
          .group_by("tag").agg(sum_("v").alias("sv"),
                               sum_("w").alias("sw"))
          .order_by("tag"))
    df.collect()                     # warm: compile + converge caps
    reset_local_shuffle_counters()
    rows = df.collect()
    sc = local_shuffle_counters()
    assert rows
    assert sc["range_view_blocks"] > 0, sc
    assert sc["range_view_folds"] > 0, sc
    assert sc["fused_reduce_programs"] >= 1, sc
    assert sc["slice_gather_programs"] == 0, sc
    assert sc["range_view_materializes"] == 0, sc


def test_a_sessions_conf_stays_that_sessions():
    """Session B, built with the four deleted keys set to "false", changes
    nothing for session A beside it, nor for itself: the exchange has no
    process-wide switch left, and the keys are unregistered."""
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    batches = [_fact(seed=61), _fact(seed=62, n=1800)]
    want = _norm(_agg_query(
        TpuSession({"spark.rapids.sql.enabled": "false"}),
        batches).collect())
    assert want
    a = TpuSession(dict(CONF))
    qa = _agg_query(a, batches)
    assert _norm(qa.collect()) == want
    b = TpuSession(dict(CONF, **OLD_HATCHES_CLOSED))
    for q in (qa, _agg_query(b, batches)):
        reset_local_shuffle_counters()
        rows = q.collect()
        sc = local_shuffle_counters()
        assert _norm(rows) == want
        assert sc["range_view_blocks"] > 0, sc
        assert sc["range_view_folds"] > 0, sc
        assert sc["slice_gather_programs"] == 0, sc


def test_deleted_options_and_switches_are_gone():
    from spark_rapids_tpu import config
    from spark_rapids_tpu.shuffle import transport
    keys = {e.key for e in config.all_entries()}
    assert not keys & set(OLD_HATCHES_CLOSED), keys & set(OLD_HATCHES_CLOSED)
    for name in ("set_range_serialize", "range_serialize_enabled",
                 "set_range_views", "range_views_enabled",
                 "set_pipeline_enabled", "pipeline_enabled"):
        assert not hasattr(transport, name), name


@pytest.mark.parametrize("batch_rows,folds", [(None, True), ("256", False)])
def test_per_op_join_folds_its_views_and_falls_back_past_the_bound(
        batch_rows, folds):
    """With fusion off a shuffled join is a per-op consumer of its
    exchanges.  A reduce group that is one program's work arrives as raw
    pieces and the probe program folds its views (no standalone gather);
    a partition past the in-core bound (a small batchSizeRows) takes the
    merged read, whose views slice through the standalone-gather fallback
    (counted).  Rows match the fused path either way."""
    from spark_rapids_tpu.cluster.stats import (
        local_shuffle_counters, reset_local_shuffle_counters)
    conf = dict(CONF, **{
        "spark.rapids.sql.join.broadcastRowThreshold": "1",
        "spark.rapids.sql.join.adaptive.enabled": "false"})
    if batch_rows:
        conf["spark.rapids.sql.batchSizeRows"] = batch_rows

    def query(s):
        fact = s.create_dataframe(
            [_fact(seed=71, null_frac=0.0), _fact(seed=72, n=1800)],
            num_partitions=2)
        dim = s.create_dataframe([_fact(seed=73, n=600, null_frac=0.0)],
                                 num_partitions=2)
        return (fact.join(dim.select(col("k").alias("dk"),
                                     col("v").alias("w")),
                          on=([col("k")], [col("dk")]))
                .select("k", "tag", "v", "w"))

    rows_fused = query(TpuSession(dict(conf))).collect()
    q = query(TpuSession(dict(
        conf, **{"spark.rapids.sql.tpu.fuseStages": "false"})))
    q.collect()
    reset_local_shuffle_counters()
    rows_perop = q.collect()
    sc = local_shuffle_counters()
    assert rows_fused and _norm(rows_fused) == _norm(rows_perop)
    assert sc["range_view_blocks"] > 0, sc
    assert sc["slice_gather_programs"] == 0, sc
    if folds:
        assert sc["range_view_folds"] > 0, sc
        assert sc["range_view_materializes"] == 0, sc
    else:
        assert sc["range_view_materializes"] > 0, sc


# -- transport-level spill/teardown correctness ------------------------------


def _mkbatch(lo, n=8):
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import DeviceColumn
    col_ = DeviceColumn(data=jnp.arange(lo, lo + n, dtype=jnp.int64),
                        validity=jnp.ones(n, bool), dtype=T.LONG)
    return ColumnarBatch((col_,), jnp.int32(n),
                         Schema(("n",), (T.LONG,)))


def _view_store(counts=(3, 3, 2)):
    """A CacheOnlyTransport holding ONE backing batch viewed by
    len(counts) partitions."""
    from spark_rapids_tpu.shuffle.transport import CacheOnlyTransport
    t = CacheOnlyTransport(len(counts))
    t.write_partitioned([(_mkbatch(0, sum(counts)),
                          np.asarray(counts, np.int64))])
    return t


def test_shared_backing_pins_once_per_attempt_and_survives_oom():
    """The pin-balance regression: several views of ONE backing batch in
    one attempt pin it exactly once; an injected mid-attempt OOM leaves
    it unpinned and spillable; the retry completes with correct rows."""
    from spark_rapids_tpu.memory.arena import TpuRetryOOM
    from spark_rapids_tpu.plan.execs.coalesce import (
        retry_over_stream_pieces)
    t = _view_store()
    backing = t._backings[0]
    backing.unpin()                  # make_spillable leaves no pin; be sure
    base_pins = backing._pins
    pieces = [p for part in range(3) for p in t.read_pieces(part)]
    assert len(pieces) == 3
    assert all(p.is_range_view for p in pieces)
    attempts = [0]

    def body(mats):
        attempts[0] += 1
        # all three views share ONE backing, pinned exactly once
        assert backing._pins == base_pins + 1, backing._pins
        bk = {id(m.batch) for m in mats[0]}
        assert len(bk) == 1, "views must share one materialized backing"
        if attempts[0] == 1:
            raise TpuRetryOOM("injected mid-attempt")
        return sum(int(m.count) for m in mats[0])

    assert retry_over_stream_pieces([pieces], body) == 8
    assert attempts[0] == 2
    assert backing._pins == base_pins, "pin leak on shared backing"
    assert backing.spill_to_host() > 0, "backing no longer spillable"
    t.cleanup()
    assert backing.closed


def test_view_read_fallback_after_backing_spill():
    """A spilled backing batch re-materializes for the read fallback and
    the sliced rows are exact (spill -> reload -> slice)."""
    t = _view_store((3, 3, 2))
    backing = t._backings[0]
    backing.unpin()
    assert backing.spill_to_host() > 0
    got = []
    for part in range(3):
        for b in t.read(part):
            got.extend(int(x) for x in np.asarray(b.columns[0].data)
                       [:b.host_num_rows()])
    assert got == list(range(8))
    t.cleanup()


def test_teardown_with_view_backed_blocks_never_orphans_backing():
    """The drop/teardown chaos pin: tearing the store down mid-
    consumption — some views pinned by a consumer, an OOM injected on
    the next materialize, other views never read — closes the shared
    backing exactly once and leaks nothing (the CACHE_ONLY analog of
    drop_attempt on view-backed blocks)."""
    from spark_rapids_tpu.memory.arena import device_arena
    t = _view_store((4, 2, 2))
    backing = t._backings[0]
    backing.unpin()
    # a consumer holds one view pinned mid-flight
    piece = next(iter(t.read_pieces(0)))
    piece.materialize_pinned()
    # chaos: the NEXT device materialization OOMs once (forces the spill/
    # retry path through the view store's read fallback)
    device_arena().inject_ooms(1, kind="retry")
    try:
        rows = t.read(1)
        assert sum(b.host_num_rows() for b in rows) == 2
    finally:
        device_arena().clear_injection()
    # teardown with one view still pinned, one partition never consumed
    t.cleanup()
    assert backing.closed, "backing orphaned by teardown"
    assert t._backings == [] and all(not v for v in t._views)
    # the consumer's late unpin on the closed handle is harmless
    piece.unpin()


def test_read_fallback_never_steals_concurrent_pin():
    """Review pin: a materialize that RAISES took no pin, so the read
    fallback's unwind must not unpin — an unmatched unpin would silently
    consume a CONCURRENT consumer's pin on the shared backing and let
    the spill framework free data that consumer is still reading."""
    from spark_rapids_tpu.memory.arena import TpuRetryOOM
    t = _view_store((3, 3, 2))
    backing = t._backings[0]
    backing.unpin()
    backing.materialize()            # the concurrent consumer's pin
    held = backing._pins
    calls = [0]
    orig = backing.materialize

    def flaky():
        calls[0] += 1
        if calls[0] == 1:
            raise TpuRetryOOM("injected BEFORE the pin was taken")
        return orig()

    backing.materialize = flaky
    try:
        rows = t.read(0)
    finally:
        backing.materialize = orig
    assert sum(b.host_num_rows() for b in rows) == 3
    assert calls[0] == 2             # first attempt raised, retry ran
    assert backing._pins == held, "read stole the concurrent pin"
    backing.unpin()
    t.cleanup()


def test_materialize_fallback_failure_releases_pin(monkeypatch):
    """Review pin: a failed fallback gather must release its own pin —
    the caller only learns it holds one when the call RETURNS, so a
    raise with the pin held would leave the backing unspillable until
    transport cleanup."""
    import spark_rapids_tpu.shuffle.transport as tr
    t = _view_store((2, 2, 4))
    backing = t._backings[0]
    backing.unpin()
    base = backing._pins
    piece = next(iter(t.read_pieces(2)))

    def boom(view):
        raise RuntimeError("gather failed")

    monkeypatch.setattr(tr, "_slice_view", boom)
    with pytest.raises(RuntimeError):
        piece.materialize_batch_pinned()
    assert backing._pins == base, "failed fallback leaked a pin"
    assert backing.spill_to_host() > 0, "backing no longer spillable"
    t.cleanup()


def test_residency_guard_counts_deduped_backings_against_budget():
    """Review pin: one attempt pins each view's FULL backing (deduped),
    so the residency guard must sum backing sizes, not per-view shares —
    and must never trip in bookkeeping mode (budget 0)."""
    from spark_rapids_tpu.memory.arena import device_arena
    from spark_rapids_tpu.shuffle.transport import views_over_memory_budget
    t = _view_store((3, 3, 2))
    backing = t._backings[0]
    backing.unpin()
    pieces = [p for part in range(3) for p in t.read_pieces(part)]
    arena = device_arena()
    saved = arena.budget_bytes
    try:
        arena.budget_bytes = 0
        assert not views_over_memory_budget([pieces])   # bookkeeping mode
        # per-view shares sum to ~backing size; a guard summing them
        # against a budget of 1.5x backing would NOT trip — the deduped
        # full-backing accounting must
        arena.budget_bytes = int(backing.size_bytes * 1.5)
        assert views_over_memory_budget([pieces]), \
            (backing.size_bytes, [p.nbytes for p in pieces])
        arena.budget_bytes = backing.size_bytes * 4
        assert not views_over_memory_budget([pieces])
    finally:
        arena.budget_bytes = saved
    t.cleanup()


def test_write_partitioned_blocks_match_slice_by_counts_rows():
    """Unit differential: the view store serves row-identical data to
    slice_by_counts of the SAME reordered batch + counts."""
    from spark_rapids_tpu.plan.execs.out_of_core import slice_by_counts
    from spark_rapids_tpu.shuffle.transport import CacheOnlyTransport
    counts = np.asarray([5, 0, 3], np.int64)
    reordered = _mkbatch(100, 8)
    t = CacheOnlyTransport(3)
    t.write_partitioned([(reordered, counts)])

    def rows(batches):
        return [int(x) for b in batches
                for x in np.asarray(b.columns[0].data)[:b.host_num_rows()]]

    for part, piece in enumerate(slice_by_counts(reordered, counts, 3)):
        want = rows([piece] if piece is not None else [])
        assert rows(t.read(part)) == want, part
    t.cleanup()
