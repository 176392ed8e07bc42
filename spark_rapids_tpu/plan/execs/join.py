"""Join execs.

Reference: GpuShuffledHashJoinExec / GpuBroadcastHashJoinExecBase /
GpuShuffledSizedHashJoinExec (org/apache/spark/sql/rapids/execution/
GpuHashJoin.scala — gather-map iterators at :1136).

TpuShuffledHashJoinExec: both sides arrive hash-partitioned on the join
keys (the planner inserts the exchanges); partition i joins left[i] x
right[i] with the sort-merge gather-map kernel (kernels/join.py) under the
capacity-retry loop.  Where both children are exchange readers the two
sides of a reduce group arrive as the exchange's RAW pieces and the probe
program folds them (``PieceSide``): with a condition or without, per-op or
under an adaptive join.  TpuBroadcastHashJoinExec materializes the whole
build side once (the broadcast) and streams the other side's partitions.
"""
from __future__ import annotations

import itertools
import time
from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn, round_up_pow2
from spark_rapids_tpu.expressions.core import (
    BoundReference, EvalContext, Expression)
from spark_rapids_tpu.kernels.join import (
    apply_gather_maps, conditional_join_maps, join_expand, join_gather_maps,
    join_path, join_probe)
from spark_rapids_tpu.memory.retry import with_capacity_retry, with_retry_no_split
from spark_rapids_tpu.plan.execs.base import MaterializeLock, TpuExec, timed
from spark_rapids_tpu.plan.execs.coalesce import coalesce_to_one
from spark_rapids_tpu.utils.tracing import record_range, trace_range


def _bound_ordinals(e: Expression) -> set:
    out = set()
    if isinstance(e, BoundReference):
        out.add(e.ordinal)
    for c in e.children:
        out |= _bound_ordinals(c)
    return out


def _remap_ordinals(e: Expression, mapping: dict) -> Expression:
    if isinstance(e, BoundReference):
        return BoundReference(mapping[e.ordinal], e.dtype, e.name)
    if not e.children:
        return e
    ch = tuple(_remap_ordinals(c, mapping) for c in e.children)
    if all(n is o for n, o in zip(ch, e.children)):
        return e
    return e.with_children(ch)


class PieceSide:
    """One side of one reduce group as the exchange's raw pieces
    (``stream_pieces``), for the join kernel to fold inside its probe
    program: no launch a range view, no concat launch a side.  ``rows``:
    what the pieces hold as the host knows it (``StreamPiece.rows``); the
    folded batch's capacity is that, rounded up."""

    __slots__ = ("pieces", "capacity")

    def __init__(self, pieces, rows: int):
        self.pieces = list(pieces)
        self.capacity = round_up_pow2(max(int(rows), 1))


class _Folding(NamedTuple):
    """A ``PieceSide`` inside one pin-balanced attempt: its pieces
    materialized (views at one capacity), on their way into the probe
    program as a tuple argument."""
    mats: tuple
    capacity: int


class _Probed(NamedTuple):
    """What the probe launch hands the expansion / condition launches:
    both sides as batches (folded where they came as pieces), the probe
    state, the candidate count (a device scalar), and the string bucket and
    kernel path the later programs are keyed by."""
    l: ColumnarBatch
    r: ColumnarBatch
    state: tuple
    required: jax.Array
    bucket: int
    path: str


def _side_mats(side) -> tuple:
    """What of a side the host can read before the probe has run: the
    batch, or the pieces' materializations (a view answers with its backing
    batch's columns and rows, a superset of its own)."""
    return side.mats if isinstance(side, _Folding) else (side,)


class _JoinKernel:
    """jit cache over (capacities, byte capacities, string bucket) — all
    static; shapes implicit via jax.jit retracing.

    Two program shapes:
      * plain equi-join: gather maps + output assembly in one program;
      * conditional (residual condition and/or existence/nested-loop):
        candidate pair maps (equi keys, or all pairs when keyless) ->
        gather ONLY the condition's input columns for the pair batch ->
        vectorized condition eval -> conditional_join_maps postprocess ->
        final assembly.  The reference's conditional gather iterators
        (GpuHashJoin.scala:1653) as a single XLA program.
    """

    def __init__(self, left_key_idx, right_key_idx, join_type: str,
                 schema: Schema, left_schema: Optional[Schema] = None,
                 right_schema: Optional[Schema] = None,
                 condition: Optional[Expression] = None):
        self.left_key_idx = tuple(left_key_idx)
        self.right_key_idx = tuple(right_key_idx)
        self.join_type = join_type
        self.schema = schema
        self.condition = condition
        self.conditional = (condition is not None
                            or join_type == "existence"
                            or not self.left_key_idx)
        if join_type == "cross":
            self.conditional = False

        from spark_rapids_tpu.plan.execs.base import (
            exprs_cache_key, schema_cache_key, shared_jit)
        base_key = (f"join|{self.left_key_idx}|{self.right_key_idx}|"
                    f"{join_type}|{schema_cache_key(schema)}")

        if self.conditional:
            assert left_schema is not None and right_schema is not None
            nl = len(left_schema)
            ords = sorted(_bound_ordinals(condition)) if condition is not None else []
            # (side, source ordinal) per condition input, in pair-ordinal order
            self.cond_inputs = [(0, o) if o < nl else (1, o - nl)
                                for o in ords]
            pair_names = tuple(left_schema.names) + tuple(right_schema.names)
            pair_dtypes = tuple(left_schema.dtypes) + tuple(right_schema.dtypes)
            self.cond_schema = Schema(tuple(pair_names[o] for o in ords),
                                      tuple(pair_dtypes[o] for o in ords))
            self.cond_remapped = (_remap_ordinals(
                condition, {o: j for j, o in enumerate(ords)})
                if condition is not None else None)
            if join_type in ("left_semi", "left_anti", "existence"):
                self.gather_jt = "left_semi"     # gather left side only
                self.gather_schema = (Schema(schema.names[:-1],
                                             schema.dtypes[:-1])
                                      if join_type == "existence" else schema)
            else:
                self.gather_jt = join_type
                self.gather_schema = schema
            base_key += f"|cond={exprs_cache_key([condition]) if condition is not None else 'none'}"

        def jitted_probe(bucket: int, cand_type: str, fold: tuple):
            # capacity-INDEPENDENT phase: the sorts/segment reductions run
            # once per batch pair; every capacity or byte retry reuses the
            # returned state (sort-reuse, VERDICT r3 weak #2).  ``fold``:
            # per side, None for a batch, else the capacity its pieces (a
            # tuple argument) fold to as this program's first step; a
            # folded side is handed back for the later launches
            from spark_rapids_tpu.shuffle.transport import (
                fold_pieces_in_trace)

            def run(l, r):
                lb, rb = (x if cap is None else fold_pieces_in_trace(x, cap)
                          for x, cap in zip((l, r), fold))
                probed = join_probe(lb, self.left_key_idx, rb,
                                    self.right_key_idx, cand_type,
                                    string_max_bytes=bucket)
                return probed, tuple(None if cap is None else b
                                     for b, cap in zip((lb, rb), fold))
            return run

        def jitted_expand(out_capacity: int, byte_caps: tuple, path: str):
            def run(l: ColumnarBatch, r: ColumnarBatch, state):
                li, ri, count, status = join_expand(
                    state, path, self.join_type, l.capacity, r.capacity,
                    out_capacity)
                out, gstatus = apply_gather_maps(
                    l, r, li, ri, count, self.schema, self.join_type,
                    out_capacity, dict(byte_caps))
                return out, status, gstatus
            return run

        def jitted_cond(pair_capacity: int, out_capacity: int,
                        byte_caps: tuple, bucket: int, path: str):
            import jax.numpy as jnp

            from spark_rapids_tpu.kernels.selection import (
                OOB, gather_column, required_gather_bytes_at)
            bc = dict(byte_caps)
            # deterministic (input, path) order shared with the driver's
            # retry loop (it zips requirements against the same sort)
            pair_key_list = sorted(k[1] for k in bc if k[0] == "pair")

            def run(l: ColumnarBatch, r: ColumnarBatch, state):
                cand_type = "inner" if self.left_key_idx else "cross"
                li, ri, cnt, pair_status = join_expand(
                    state, path, cand_type, l.capacity, r.capacity,
                    pair_capacity)
                pair_bytes = []
                if self.cond_remapped is None:
                    pass_mask = (li != OOB) & (ri != OOB)
                else:
                    cols = []
                    for j, (side, o) in enumerate(self.cond_inputs):
                        c = (l if side == 0 else r).columns[o]
                        idx = li if side == 0 else ri
                        caps_j = {p: bc[("pair", (jj, p))]
                                  for jj, p in pair_key_list if jj == j}
                        if caps_j:
                            cols.append(gather_column(
                                c, idx, cnt, out_capacity=pair_capacity,
                                byte_caps=caps_j))
                        else:
                            cols.append(gather_column(
                                c, idx, cnt, out_capacity=pair_capacity))
                    for jj, p in pair_key_list:
                        side, o = self.cond_inputs[jj]
                        c = (l if side == 0 else r).columns[o]
                        idx = li if side == 0 else ri
                        pair_bytes.append(
                            required_gather_bytes_at(c, p, idx, cnt))
                    pb = ColumnarBatch(tuple(cols), cnt, self.cond_schema)
                    cond = self.cond_remapped.eval(EvalContext(pb))
                    pass_mask = ((li != OOB) & (ri != OOB)
                                 & cond.validity
                                 & cond.data.astype(jnp.bool_))
                li2, ri2, count2, out_status, lmatched = conditional_join_maps(
                    li, ri, pass_mask, l.live_mask(), r.live_mask(),
                    self.join_type, out_capacity)
                final_bc = {o: v for (tag, o), v in bc.items() if tag == "out"}
                out, gstatus = apply_gather_maps(
                    l, r, li2, ri2, count2, self.gather_schema,
                    self.gather_jt, out_capacity, final_bc)
                if self.join_type == "existence":
                    live = jnp.arange(out_capacity, dtype=jnp.int32) < count2
                    safe = jnp.clip(li2, 0, l.capacity - 1)
                    ex = DeviceColumn(
                        jnp.where(live, lmatched[safe], False), live,
                        self.schema.dtypes[-1])
                    out = ColumnarBatch(tuple(out.columns) + (ex,),
                                        count2, self.schema)
                return out, pair_status, out_status, gstatus, tuple(pair_bytes)
            return run

        self._jitted_probe = lambda bucket, cand_type, fold: shared_jit(
            f"{base_key}|probe|{bucket}|{cand_type}"
            + (f"|fold={fold}" if any(fold) else ""),
            lambda: jitted_probe(bucket, cand_type, fold),
            kind="join_probe")
        if self.conditional:
            self._jitted_cond = (
                lambda pair_cap, out_cap, byte_caps, bucket, path: shared_jit(
                    f"{base_key}|{pair_cap}|{out_cap}|{byte_caps}|{bucket}"
                    f"|{path}",
                    lambda: jitted_cond(pair_cap, out_cap, byte_caps,
                                        bucket, path),
                    kind="join_cond"))
        else:
            self._jitted_expand = (
                lambda out_capacity, byte_caps, path: shared_jit(
                    f"{base_key}|expand|{out_capacity}|{byte_caps}|{path}",
                    lambda: jitted_expand(out_capacity, byte_caps, path),
                    kind="join_expand"))

    def _string_out_cols(self, l: ColumnarBatch, r: ColumnarBatch):
        """(output ordinal, nested path) -> source plane capacity for EVERY
        offsets plane in the output columns — top-level strings/arrays AND
        planes nested inside struct/map children (the capacity-retry
        unlock for struct{string} / var-width map payloads)."""
        from spark_rapids_tpu.kernels.selection import (
            nested_offset_paths, path_plane_capacity)
        out = {}
        idx = 0
        sides = ([l] if self.join_type in ("left_semi", "left_anti",
                                           "existence") else [l, r])
        for side in sides:
            for c in side.columns:
                for p in nested_offset_paths(c):
                    out[(idx, p)] = path_plane_capacity(c, p)
                idx += 1
        return out

    def _pair_string_cols(self, l: ColumnarBatch, r: ColumnarBatch):
        """(condition-input index, nested path) -> plane capacity for
        EVERY offsets plane of each condition input — top-level strings
        and planes nested inside struct/map/array inputs (the same
        per-plane capacity-retry discipline the payload gather uses;
        unlocks conditions over nested columns)."""
        from spark_rapids_tpu.kernels.selection import (
            nested_offset_paths, path_plane_capacity)
        out = {}
        for j, (side, o) in enumerate(self.cond_inputs):
            c = (l if side == 0 else r).columns[o]
            for p in nested_offset_paths(c):
                out[(j, p)] = path_plane_capacity(c, p)
        return out

    def _probe(self, l, r) -> _Probed:
        """The probe launch (no retry of its own: ``__call__`` gives it
        one).  A side is a batch or a ``_Folding``."""
        # what the probe counts: the join's own matches, or, for the
        # conditional shape, the candidate pairs the condition is run over
        cand_type = (self.join_type if not self.conditional
                     else "inner" if self.left_key_idx else "cross")
        bucket = self._key_bucket(l, r)
        path = join_path(_side_mats(l)[0], self.left_key_idx,
                         _side_mats(r)[0], self.right_key_idx, cand_type)
        fold = tuple(x.capacity if isinstance(x, _Folding) else None
                     for x in (l, r))
        (state, required), folded = self._jitted_probe(
            bucket, cand_type, fold)(
                *(x.mats if isinstance(x, _Folding) else x for x in (l, r)))
        l, r = (x if f is None else f for x, f in zip((l, r), folded))
        return _Probed(l, r, state, required, bucket, path)

    def _finish_conditional(self, probed: _Probed) -> ColumnarBatch:
        from spark_rapids_tpu.columnar.column import round_up_pow2 as rup
        from spark_rapids_tpu.memory.arena import TpuSplitAndRetryOOM
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
        l, r, state, required, bucket, path = probed
        nl, nr = l.capacity, r.capacity
        # the candidate count is exact, so pair capacity jumps straight to
        # the requirement instead of climbing a retry ladder.  The static
        # guess floors it so batches with small outputs share one compiled
        # expansion program.
        if not self.left_key_idx:
            # nested-loop candidates are ALL live pairs: exact, no retry
            pair_cap = rup(max(nl * max(nr, 1), 1))
        else:
            required = int(required)
            SHUFFLE_COUNTERS.add(join_candidate_pairs=required)
            pair_cap = max(rup(max(nl, nr, 1)), rup(max(required, 1)))
        # The analytic out_cap bounds (pair_cap [+ null-extension rows])
        # are SAFE but can be catastrophically loose: every candidate
        # pair must fit the PAIR region, but the rows that PASS the
        # condition are usually far fewer, and every downstream
        # operator's cost scales with CAPACITY, not live rows (the
        # static-shape tax).  q72's cs x inv join emitted 390k live rows
        # in a 4.19M-capacity batch (the candidate-pair bound), and its
        # whole dim-join chain then ran 10.7x oversized — the profiled
        # q72 wall.  So out_cap STARTS at the equi-join FK guess
        # (max(L, R), capped by the analytic bound) and the EXACT
        # overflow feedback (conditional_join_maps reports unclamped
        # required_rows) escalates in one jump when the guess is low —
        # one extra program run, traded against a pow2-right-sized
        # output for the entire downstream plan.
        if self.join_type in ("left_semi", "left_anti", "existence"):
            out_cap = rup(max(nl, 1))
        else:
            if self.join_type == "full":
                analytic = pair_cap + nl + nr
            elif self.join_type == "left":
                analytic = pair_cap + nl
            elif self.join_type == "right":
                analytic = pair_cap + nr
            else:
                analytic = pair_cap
            out_cap = min(rup(max(nl, nr, 1)), rup(max(analytic, 1)))
        byte_caps = {("out", o): v
                     for o, v in self._string_out_cols(l, r).items()}
        byte_caps.update({("pair", j): v
                          for j, v in self._pair_string_cols(l, r).items()})
        for _ in range(24):
            launched = time.perf_counter(), time.time()
            out, pair_status, out_status, gstatus, pair_bytes = \
                with_retry_no_split(
                    lambda: self._jitted_cond(
                        pair_cap, out_cap,
                        tuple(sorted(byte_caps.items())), bucket,
                        path)(l, r, state))
            ok = True
            need_pairs = int(pair_status.required_rows)
            if need_pairs > pair_cap:
                pair_cap = rup(need_pairs)
                ok = False
            need_out = int(out_status.required_rows)
            if need_out > out_cap:
                out_cap = rup(need_out)
                ok = False
            pair_keys = sorted(k[1] for k in byte_caps if k[0] == "pair")
            for j, req in zip(pair_keys, pair_bytes):
                if int(req) > byte_caps[("pair", j)]:
                    byte_caps[("pair", j)] = rup(int(req))
                    ok = False
            if gstatus.required_bytes:
                out_keys = sorted(k[1] for k in byte_caps if k[0] == "out")
                for o, req in zip(out_keys, gstatus.required_bytes):
                    if int(req) > byte_caps[("out", o)]:
                        byte_caps[("out", o)] = rup(int(req))
                        ok = False
            if ok:
                SHUFFLE_COUNTERS.add(join_output_rows=need_out)
                return out
            record_range("join.retry", *launched)
        raise TpuSplitAndRetryOOM("join output would not fit after retries")

    def _key_bucket(self, l, r) -> int:
        from spark_rapids_tpu.kernels import strings as SK
        pairs = []
        for lk, rk in zip(self.left_key_idx, self.right_key_idx):
            if _side_mats(l)[0].columns[lk].is_string_like:
                pairs += [(m.columns[lk], m.num_rows) for m in _side_mats(l)]
                pairs += [(m.columns[rk], m.num_rows) for m in _side_mats(r)]
        if not pairs:
            return 0
        # ONE device sync across both sides' string keys (was 2 per pair)
        return SK.bucket_for(SK.max_live_bytes_multi(pairs))

    def __call__(self, l, r) -> ColumnarBatch:
        """Join ``l`` with ``r``.  A side is a batch, or a ``PieceSide``:
        its pieces are then materialized PIN-BALANCED for exactly the probe
        launch (coalesce.retry_over_stream_pieces over both sides' lists),
        which folds them and hands the batches on; a mid-attempt OOM
        leaves every piece spillable."""
        piece_sides = [x for x in (l, r) if isinstance(x, PieceSide)]
        if not piece_sides:
            probed = with_retry_no_split(lambda: self._probe(l, r))
        else:
            from spark_rapids_tpu.plan.execs.coalesce import (
                retry_over_stream_pieces)
            from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
            from spark_rapids_tpu.shuffle.transport import (
                views_at_one_capacity)
            n_views = sum(p.is_range_view for x in piece_sides
                          for p in x.pieces)
            if n_views:
                # CACHE_ONLY range views sliced INSIDE the probe program
                # (counted once a call, not once a retry attempt)
                SHUFFLE_COUNTERS.add(range_view_folds=n_views)

            def attempt(mats):
                mats = iter(mats)
                return self._probe(*(
                    _Folding(tuple(views_at_one_capacity(next(mats))),
                             x.capacity)
                    if isinstance(x, PieceSide) else x for x in (l, r)))
            probed = retry_over_stream_pieces(
                [x.pieces for x in piece_sides], attempt)
        if self.conditional:
            return self._finish_conditional(probed)
        return self._finish_expand(probed)

    def _finish_expand(self, probed: _Probed) -> ColumnarBatch:
        from spark_rapids_tpu.columnar.column import round_up_pow2 as rup
        from spark_rapids_tpu.memory.arena import TpuSplitAndRetryOOM
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
        l, r, state, required, _, path = probed
        nl, nr = l.capacity, r.capacity
        if self.join_type == "cross":
            guess = max(nl * max(nr, 1), 1)
        elif self.join_type in ("left_semi", "left_anti"):
            guess = max(nl, 1)
        elif self.join_type == "full":
            # full outer can exceed max(L,R) whenever both sides have
            # unmatched rows; L+R never retries
            guess = max(nl + nr, 1)
        else:
            # FK-shaped equi-joins output ~probe-side rows; starting at
            # L+R doubles every downstream buffer for the common broadcast
            # case.
            guess = max(nl, nr, 1)
        # required is exact, so the expansion capacity jumps straight
        # there — no growth ladder, and every byte-capacity retry below
        # reuses the probe state.  The static guess floors the capacity so
        # small-output batches share one compiled expansion program.
        required = int(required)
        SHUFFLE_COUNTERS.add(join_candidate_pairs=required)
        cap = max(rup(guess), rup(max(required, 1)))
        byte_caps = dict(self._string_out_cols(l, r))
        for _ in range(24):
            launched = time.perf_counter(), time.time()
            out, status, gstatus = with_retry_no_split(
                lambda: self._jitted_expand(
                    cap, tuple(sorted(byte_caps.items())), path)(l, r, state))
            need_rows = int(status.required_rows)
            ok = need_rows <= cap
            if ok and gstatus.required_bytes:
                string_ords = sorted(byte_caps)
                for ordv, req in zip(string_ords, gstatus.required_bytes):
                    if int(req) > byte_caps[ordv]:
                        byte_caps[ordv] = rup(int(req))
                        ok = False
            if ok:
                SHUFFLE_COUNTERS.add(join_output_rows=need_rows)
                return out
            record_range("join.retry", *launched)
            if need_rows > cap:
                cap = rup(need_rows)
        raise TpuSplitAndRetryOOM("join output would not fit after retries")


class TpuShuffledHashJoinExec(TpuExec):
    """Joins co-partitioned sides; when a partition's combined rows exceed
    ``target_rows``, both sides are hash-sub-partitioned on the join keys
    (with the sub-partition seed) into spillable co-buckets joined pairwise
    — equal keys always share a bucket, so the union of bucket outputs is
    exactly the single-batch join for every equi-join type.  Reference:
    GpuSubPartitionHashJoin.scala."""

    def __init__(self, left: TpuExec, right: TpuExec,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str, schema: Schema,
                 target_rows: int = 1 << 20,
                 condition: Optional[Expression] = None):
        super().__init__((left, right), schema)
        self.join_type = join_type
        self.target_rows = max(int(target_rows), 1)
        # keys are bound refs into each side's schema; resolve ordinals
        self.left_key_idx = [self._ordinal(k, left.schema) for k in left_keys]
        self.right_key_idx = [self._ordinal(k, right.schema) for k in right_keys]
        self.condition = condition
        # stashed side schemas: segment fusion (plan/fused.py) detaches
        # chain nodes from their children, but the out-of-core fallback
        # still runs THIS node's per-op machinery — which must not reach
        # through self.children for schema
        self.left_schema = left.schema
        self.right_schema = right.schema
        self._kernel = _JoinKernel(self.left_key_idx, self.right_key_idx,
                                   join_type, schema,
                                   left_schema=left.schema,
                                   right_schema=right.schema,
                                   condition=condition)

    @staticmethod
    def _ordinal(key: Expression, schema: Schema) -> int:
        from spark_rapids_tpu.expressions.core import BoundReference
        assert isinstance(key, BoundReference), \
            "planner must project non-trivial join keys first"
        return key.ordinal

    def num_partitions(self) -> int:
        return self.children[0].num_partitions()

    def _join_pair(self, left, right) -> Optional[ColumnarBatch]:
        """Join one (possibly absent: None) pair of sides, each a batch or
        a ``PieceSide``, with the join type's empty-side semantics;
        returns None when no output is possible."""
        if left is None and right is None:
            return None
        if left is None:
            if self.join_type in ("inner", "left", "left_semi", "left_anti",
                                  "cross", "existence"):
                return None
            left = ColumnarBatch.empty(self.left_schema)
        if right is None:
            if self.join_type in ("inner", "right", "cross", "left_semi"):
                return None
            # left/full/anti/existence still emit left rows against an
            # empty build side
            right = ColumnarBatch.empty(self.right_schema)
        return self._kernel(left, right)

    def _execute_over_pieces(self, idx: int):
        """Both children hand out raw exchange pieces (``stream_pieces``):
        reduce group ``idx`` is joined from them, the probe program folding
        both sides, for as long as the group is one program's work by the
        rule the coalescing reader built it by (``reduce_group_in_core``
        over both sides' rows) and its backings fit the residency guard.
        Yields the group's output and returns True; returns False, with
        nothing yielded and the pieces dropped, for a group that is not (a
        single oversized partition): the merged read then streams the probe
        side or sub-partitions out of core."""
        from spark_rapids_tpu.plan.execs.coalesce import (
            maybe_shrink, pull_group_in_core)
        from spark_rapids_tpu.shuffle.transport import (
            views_over_memory_budget)
        out = None
        # a side's first pull runs its exchange's map side where nothing
        # has yet: the child's work, outside this layer's spans
        build = iter(self.children[1].stream_pieces(idx))
        first = list(itertools.islice(build, 1))
        with timed(self.op_time, "join.build"):
            rpieces, rrows = pull_group_in_core(
                itertools.chain(first, build), self.target_rows)
        if rpieces is None:
            return False
        probe = iter(self.children[0].stream_pieces(idx))
        first = list(itertools.islice(probe, 1))
        with timed(self.op_time, "join.probe"):
            lpieces, rows = pull_group_in_core(
                itertools.chain(first, probe), self.target_rows, rrows)
            in_core = (lpieces is not None and not views_over_memory_budget(
                [lpieces, rpieces]))
            if in_core:
                out = self._join_pair(
                    PieceSide(lpieces, rows - rrows) if lpieces else None,
                    PieceSide(rpieces, rrows) if rpieces else None)
                if out is not None:
                    out = maybe_shrink(out)
        del first, lpieces, rpieces
        if in_core and out is not None:
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)
        return in_core

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        if self.left_key_idx and all(hasattr(c, "stream_pieces")
                                     for c in self.children):
            if (yield from self._execute_over_pieces(idx)):
                return
        # the merged read, for sides that are not exchange readers and for
        # a reduce partition past the in-core bound.  Build (right) side
        # first: when it fits the batch target and the join type decomposes
        # by probe rows, the probe side STREAMS — each fetched-and-merged
        # chunk joins against the build while the shuffle prefetcher is
        # pulling the next one (fetch/compute overlap on the reduce side;
        # the reference streams the probe iterator the same way,
        # GpuHashJoin.scala:1868)
        right_batches = list(self.children[1].execute_partition(idx))
        right_total = sum(b.capacity for b in right_batches)
        if (self.left_key_idx
                and self.join_type in self._LEFT_SPLITTABLE
                and right_total <= self.target_rows):
            yield from self._execute_streamed_probe(idx, right_batches)
            return
        left_batches = list(self.children[0].execute_partition(idx))
        total = (sum(b.capacity for b in left_batches) + right_total)
        if (total > self.target_rows and self.join_type != "cross"
                and self.left_key_idx):
            yield from self._execute_out_of_core(left_batches, right_batches,
                                                 total)
            return
        from spark_rapids_tpu.plan.execs.coalesce import maybe_shrink
        with timed(self.op_time, "join.build"):
            # both coalesces under retry: the two concats are this exec's
            # big materializations (the join kernel retries internally)
            right = with_retry_no_split(
                lambda: coalesce_to_one(right_batches))
        with timed(self.op_time, "join.probe"):
            out = self._join_pair(
                with_retry_no_split(lambda: coalesce_to_one(left_batches)),
                right)
            if out is not None:
                out = maybe_shrink(out)
        if out is None:
            return
        self.output_rows.add(out.num_rows)
        yield self._count_out(out)

    def _execute_streamed_probe(self, idx: int,
                                right_batches) -> Iterator[ColumnarBatch]:
        """Probe-side streaming: group probe batches to the batch target
        and join each group against the (small) build side as it arrives.
        Correct exactly for _LEFT_SPLITTABLE types — every left row's
        output depends only on the full right side — and doubles as the
        skew guard: an oversized probe partition joins in bounded chunks
        instead of one unbounded concat."""
        from spark_rapids_tpu.plan.execs.coalesce import maybe_shrink
        with timed(self.op_time, "join.build"):
            build = with_retry_no_split(
                lambda: coalesce_to_one(right_batches))
        # an empty build side still DRAINS the probe child (no early
        # return): in cluster mode the probe exchange's map-side write
        # runs lazily under execute_partition, and other ranks' reduce
        # reads await this rank's map_complete — skipping the drain on a
        # locally-empty build would stall them until the completeness
        # timeout.  _join_pair returns None per group for the
        # no-output-possible types below.
        group: List[ColumnarBatch] = []
        acc = 0

        def flush():
            with timed(self.op_time, "join.probe"):
                out = self._join_pair(
                    with_retry_no_split(lambda: coalesce_to_one(group)),
                    build)
                if out is not None:
                    out = maybe_shrink(out)
            return out

        for b in self.children[0].execute_partition(idx):
            if group and acc + b.capacity > self.target_rows:
                out = flush()
                group, acc = [], 0
                if out is not None:
                    self.output_rows.add(out.num_rows)
                    yield self._count_out(out)
            group.append(b)
            acc += b.capacity
        if group:
            out = flush()
            if out is not None:
                self.output_rows.add(out.num_rows)
                yield self._count_out(out)

    def _execute_out_of_core(self, left_batches, right_batches,
                             total) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.plan.execs.out_of_core import (
            close_all, num_sub_buckets, sub_partition_spillable)
        n_b = num_sub_buckets(total, self.target_rows)
        # one ``join.out_of_core`` span a partition, around what only this
        # path does before it hands anything on: the sub-partition of both
        # sides (a span never stays open across a yield)
        with timed(self.op_time, "join.out_of_core"):
            lbuckets = sub_partition_spillable(
                iter(left_batches), self.left_key_idx, n_b,
                self.left_schema)
            del left_batches
            rbuckets = sub_partition_spillable(
                iter(right_batches), self.right_key_idx, n_b,
                self.right_schema)
            del right_batches
        try:
            for lq, rq in zip(lbuckets, rbuckets):
                # NOT retry-wrapped: the coalesced batches (which may
                # alias a single handle's batch) feed the skew-aware
                # join below, so the handles must stay pinned past the
                # coalesce — materializing inside a retry body would
                # leak one pin per attempt (pinned handles refuse to
                # spill), and unpinning per attempt would let the spill
                # free a batch the join still reads.  Pinned-ledger
                # unwind: a raise while materializing the RIGHT side
                # must still unpin the already-pinned left handles.
                pinned = []
                try:
                    with timed(self.op_time):
                        lmats = []
                        for h in lq:
                            lmats.append(h.materialize())
                            pinned.append(h)
                        rmats = []
                        for h in rq:
                            rmats.append(h.materialize())
                            pinned.append(h)
                        # tpu-lint: allow-retry-discipline(handles stay pinned through the join; per-attempt pin balance is impossible while the result outlives the coalesce)
                        left = coalesce_to_one(lmats) if lq else None
                        # tpu-lint: allow-retry-discipline(handles stay pinned through the join; per-attempt pin balance is impossible while the result outlives the coalesce)
                        right = coalesce_to_one(rmats) if rq else None
                    yield from self._join_bucket_skew_aware(left, right)
                finally:
                    # release arena reservations only after the join is
                    # done with the materialized inputs — closing earlier
                    # lets the arena admit new work against memory that
                    # is still physically resident
                    for h in pinned:
                        h.unpin()
                    for h in lq + rq:
                        h.close()
        finally:
            close_all(lbuckets)
            close_all(rbuckets)

    # join types where each LEFT row's output depends only on the full
    # right side, so a hot-key bucket can be split by left row ranges
    # (Spark AQE's skew-join split, GpuCustomShuffleReaderExec.scala:39 /
    # OptimizeSkewedJoin; right/full track right-side matches across the
    # whole bucket and cannot split this way)
    _LEFT_SPLITTABLE = ("inner", "left", "left_semi", "left_anti",
                        "existence")

    def _join_bucket_skew_aware(self, left, right):
        """Join one co-bucket; a bucket still oversized after hash
        sub-partitioning (single hot key) splits by probe-side row ranges,
        each chunk joined against the full build side."""
        splittable = (self.join_type in self._LEFT_SPLITTABLE
                      and left is not None and right is not None)
        if not splittable or left.capacity <= 2 * self.target_rows:
            with timed(self.op_time, "join.probe"):
                out = self._join_pair(left, right)
            if out is not None:
                self.output_rows.add(out.num_rows)
                yield self._count_out(out)
            return
        import jax.numpy as jnp

        from spark_rapids_tpu.kernels.selection import gather_batch
        chunk = round_up_pow2(max(self.target_rows, 1))
        n_live = left.host_num_rows()
        for lo in range(0, max(n_live, 1), chunk):
            with timed(self.op_time, "join.probe"):
                idx = jnp.arange(lo, min(lo + chunk, left.capacity),
                                 dtype=jnp.int32)
                cnt = jnp.clip(left.num_rows - lo, 0, idx.shape[0])
                piece = gather_batch(left, idx, cnt.astype(jnp.int32),
                                     out_capacity=idx.shape[0])
                out = self._join_pair(piece, right)
            if out is not None:
                self.output_rows.add(out.num_rows)
                yield self._count_out(out)

    def describe(self):
        return (f"TpuShuffledHashJoin[{self.join_type}, "
                f"lkeys={self.left_key_idx}, rkeys={self.right_key_idx}]")


class TpuBroadcastHashJoinExec(TpuExec):
    """Streams the left side; the right (build) side is materialized whole
    once and joined against every stream partition."""

    def __init__(self, left: TpuExec, right: TpuExec,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str, schema: Schema,
                 target_rows: int = 1 << 20,
                 condition: Optional[Expression] = None):
        assert join_type in ("inner", "left", "left_semi", "left_anti",
                             "cross", "existence"), \
            "broadcast build side must be on the null-extending side"
        super().__init__((left, right), schema)
        self.join_type = join_type
        self.target_rows = max(int(target_rows), 1)
        self.left_key_idx = [TpuShuffledHashJoinExec._ordinal(k, left.schema)
                             for k in left_keys]
        self.right_key_idx = [TpuShuffledHashJoinExec._ordinal(k, right.schema)
                              for k in right_keys]
        self.condition = condition
        self._kernel = _JoinKernel(self.left_key_idx, self.right_key_idx,
                                   join_type, schema,
                                   left_schema=left.schema,
                                   right_schema=right.schema,
                                   condition=condition)
        self._lock = MaterializeLock()
        self._build: Optional[ColumnarBatch] = None
        self._build_done = False

    def num_partitions(self) -> int:
        return self.children[0].num_partitions()

    def _build_side(self) -> Optional[ColumnarBatch]:
        with self._lock:
            if not self._build_done:
                batches = []
                right = self.children[1]
                for p in range(right.num_partitions()):
                    batches.extend(right.execute_partition(p))
                with trace_range("join.build"):
                    self._build = with_retry_no_split(
                        lambda: coalesce_to_one(batches))
                self._build_done = True
            return self._build

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        build = self._build_side()
        stream = list(self.children[0].execute_partition(idx))
        if not stream:
            return
        if build is None:
            if self.join_type in ("inner", "cross", "left_semi"):
                return
            build = ColumnarBatch.empty(self.children[1].schema)
        # every broadcastable join type decomposes by stream-side rows, so
        # an oversized stream partition is joined chunk-at-a-time instead
        # of coalescing past the batch target (the reference streams the
        # probe side per batch, GpuHashJoin.scala:1868)
        chunks: List[List[ColumnarBatch]] = [[]]
        acc = 0
        for b in stream:
            if chunks[-1] and acc + b.capacity > self.target_rows:
                chunks.append([])
                acc = 0
            chunks[-1].append(b)
            acc += b.capacity
        for group in chunks:
            if not group:
                continue
            with timed(self.op_time, "join.probe"):
                left = with_retry_no_split(lambda: coalesce_to_one(group))
                out = self._kernel(left, build)
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)

    def cleanup(self) -> None:
        with self._lock:
            self._build = None
            self._build_done = False
        super().cleanup()

    def describe(self):
        return (f"TpuBroadcastHashJoin[{self.join_type}, "
                f"lkeys={self.left_key_idx}, rkeys={self.right_key_idx}]")


class TpuAdaptiveJoinExec(TpuExec):
    """Runtime join-strategy choice from MATERIALIZED build-side size.

    The planner emits this when the static cardinality estimate sits in
    the ambiguous zone around the broadcast threshold: the build (right)
    side materializes first, its ACTUAL row count picks broadcast vs
    shuffled, and the inner exec runs over in-memory scans of the
    materialized batches.  The reference's sized-join build-side choice
    from exchange statistics (GpuShuffledSizedHashJoinExec.scala:829) and
    AQE's runtime re-plan, in one node.
    """

    def __init__(self, left: TpuExec, right: TpuExec, left_keys, right_keys,
                 join_type: str, schema: Schema,
                 broadcast_threshold: int, shuffle_partitions: int,
                 writer_threads: int = 4, codec: str = "none",
                 target_rows: int = 1 << 20,
                 condition: Optional[Expression] = None,
                 shuffle_mode: str = "CACHE_ONLY",
                 aqe_coalesce: bool = True,
                 fuse_inner: bool = False):
        super().__init__((left, right), schema)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.condition = condition
        self.broadcast_threshold = broadcast_threshold
        self.shuffle_partitions = shuffle_partitions
        self.writer_threads = writer_threads
        self.codec = codec
        self.target_rows = target_rows
        self.shuffle_mode = shuffle_mode
        #: the planner's post-passes (AQE reader insertion, segment
        #: fusion) run at PLAN time and never see the exchanges/join this
        #: node creates at runtime — without re-applying them here, the
        #: worst query shapes (q25's fact-fact join lands exactly in the
        #: adaptive ambiguous zone) pay per-op launches for every reduce
        #: partition while the rest of the plan is fused
        self.aqe_coalesce = aqe_coalesce
        self.fuse_inner = fuse_inner
        self._lock = MaterializeLock()
        self._inner: Optional[TpuExec] = None
        self.chosen: Optional[str] = None   # exposed for tests/explain
        #: (ClusterStatsClient, key) when distributed — the decision then
        #: reads the GLOBAL build-side row count through the driver's
        #: stats barrier, and a broadcast build gathers every rank's rows
        #: through a one-partition cross-process shuffle (VERDICT r4 #8)
        self.cluster_stats = None

    def _decide(self) -> TpuExec:
        with self._lock:
            if self._inner is not None:
                return self._inner
            right = self.children[1]
            # pulling the build side is the child's work; what follows is
            # this node's own: the count (a host sync that waits for what
            # the child queued) and the building of the inner plan
            right_parts = [list(right.execute_partition(p))
                           for p in range(right.num_partitions())]
            with trace_range("join.decide"):
                self._inner = self._plan_inner(right_parts)
            return self._inner

    def _plan_inner(self, right_parts) -> TpuExec:
        """The inner join exec, chosen from the materialized build side's
        actual row count."""
        from spark_rapids_tpu.plan.execs.exchange import (
            TpuShuffleExchangeExec)
        from spark_rapids_tpu.plan.execs.scan import TpuInMemoryScanExec
        build_rows = sum(b.host_num_rows()
                         for part in right_parts for b in part)
        if self.cluster_stats is not None:
            # distributed: the local count is this rank's share only;
            # the decision must be made from the GLOBAL count or
            # ranks would pick different physical shapes
            client, key = self.cluster_stats
            client.publish(key, [build_rows])
            build_rows = client.fetch_global(key)[0]
        right_scan = TpuInMemoryScanExec(right_parts,
                                         self.children[1].schema)
        left = self.children[0]
        if build_rows <= self.broadcast_threshold:
            self.chosen = "broadcast"
            if self.cluster_stats is not None:
                # a broadcast build must hold EVERY rank's rows: union
                # them through a one-partition cross-process shuffle
                # (each row written once by its owning rank; the
                # complete reduce read returns the full build side)
                from spark_rapids_tpu.shuffle.transport import (
                    make_transport)
                t = make_transport("MULTIPROCESS", 1,
                                   self.children[1].schema,
                                   self.writer_threads, self.codec)
                t.write((0, b) for part in right_parts for b in part)
                full = t.read(0)
                self._cluster_build_transport = t
                right_scan = TpuInMemoryScanExec(
                    [full], self.children[1].schema)
            return TpuBroadcastHashJoinExec(
                left, right_scan, self.left_keys, self.right_keys,
                self.join_type, self.schema,
                target_rows=self.target_rows,
                condition=self.condition)
        self.chosen = "shuffled"
        lex = TpuShuffleExchangeExec(
            self.shuffle_partitions, self.left_keys, left,
            mode=self.shuffle_mode,
            writer_threads=self.writer_threads, codec=self.codec,
            target_rows=self.target_rows)
        rex = TpuShuffleExchangeExec(
            self.shuffle_partitions, self.right_keys, right_scan,
            mode=self.shuffle_mode,
            writer_threads=self.writer_threads, codec=self.codec,
            target_rows=self.target_rows)
        jl: TpuExec = lex
        jr: TpuExec = rex
        if self.aqe_coalesce:
            # the runtime exchanges deserve the same AQE partition
            # coalescing the plan-time pass gives planned shuffled
            # joins (one SHARED spec keeps co-partitioning)
            from spark_rapids_tpu.plan.execs.exchange import (
                SharedCoalesceSpec, TpuCoalescedShuffleReaderExec)
            spec = SharedCoalesceSpec(self.target_rows)
            jl = TpuCoalescedShuffleReaderExec(lex, spec)
            jr = TpuCoalescedShuffleReaderExec(rex, spec)
        inner: TpuExec = TpuShuffledHashJoinExec(
            jl, jr, self.left_keys, self.right_keys,
            self.join_type, self.schema,
            target_rows=self.target_rows,
            condition=self.condition)
        if self.fuse_inner:
            # re-apply segment fusion over the runtime tree so the
            # reduce side runs fused (across the shuffle when the
            # join qualifies) instead of per-op
            from spark_rapids_tpu.plan.fused import fuse_segments
            inner = fuse_segments(inner)
        return inner

    def num_partitions(self) -> int:
        return self._decide().num_partitions()

    def execute_partition(self, idx: int) -> Iterator[ColumnarBatch]:
        inner = self._decide()
        for batch in inner.execute_partition(idx):
            self.output_rows.add(batch.num_rows)
            yield self._count_out(batch)

    def cleanup(self) -> None:
        with self._lock:
            if self._inner is not None:
                self._inner.cleanup()
                self._inner = None
                self.chosen = None
            t = getattr(self, "_cluster_build_transport", None)
            if t is not None:
                t.cleanup()
                self._cluster_build_transport = None
        super().cleanup()

    def describe(self):
        return (f"TpuAdaptiveJoin[{self.join_type}, "
                f"threshold={self.broadcast_threshold}"
                + (f", chosen={self.chosen}" if self.chosen else "") + "]")
