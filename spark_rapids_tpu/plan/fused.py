"""Stage-segment fusion: compile exchange-free exec chains into ONE XLA
program per input batch.

Reference posture being matched: the reference's per-batch iterator chain
runs entirely device-side with no host round-trips between operators
(GpuExec.scala:393 — each operator consumes the previous one's device
columnar batch inside the same task).  The per-op task engine here pays a
program launch per operator per batch; at TPC-DS q3 shape that is ~dozens
of launches per batch.  What a launch costs on a directly attached chip is
not measured.

Design — the middle point between per-op execution and whole-query SPMD
fusion (parallel/stage.py), whose one-program compile at bench scale is
recorded in CHANGES.md (PR 22):

  * a planner POST-pass (fuse_segments) finds maximal chains of
    device-pure execs along the streaming path — Project, Filter,
    BroadcastHashJoin (stream side), partial HashAggregate — and replaces
    each chain with a TpuFusedSegmentExec;
  * broadcast build sides are materialized once (host-coalesced exactly
    like TpuBroadcastHashJoinExec does) and enter the fused program as
    extra pytree arguments;
  * dynamic output sizes keep the engine's static-capacity contract: the
    fused program returns a feedback dict of true requirements (join rows,
    per-plane gather bytes, a grouped partial aggregate's group count);
    the host escalates capacities and re-runs (memory/retry.py
    discipline).  Converged capacities are cached per plan signature so
    later batches and identical queries launch once;
  * the jitted program is shared via shared_jit keyed on the canonical
    segment signature + capacities + string bucket, so identical plans
    reuse compiled programs across queries.

Fusion is NOT applied when a node needs host participation (CPU-bridge
expressions), per-batch string-window buckets (regex nodes), residual join
conditions, or string-growing projections (the static byte-window bound
for downstream group/join keys could no longer be derived from segment
inputs).  Those nodes simply break the chain and run per-op as before.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import round_up_pow2
from spark_rapids_tpu.expressions.core import (
    Alias, BoundReference, EvalContext, Expression, Literal)
from spark_rapids_tpu.kernels.selection import compaction_map, gather_batch
from spark_rapids_tpu.memory.retry import with_retry_no_split
from spark_rapids_tpu.plan.execs.base import (
    MaterializeLock,
    TpuExec,
    bind_trace_consts,
    collect_trace_consts,
    count_discarded_launch,
    shared_jit,
    timed,
    tree_uses_string_bucket,
)
from spark_rapids_tpu.utils.tracing import record_range, trace_range


# converged-capacity memory, keyed by segment signature (+ bucket): the
# SPMD executor's _SPMD_CAPS discipline — the second batch (and the next
# identical query) starts at the converged capacities and launches once
_FUSED_CAPS: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
_FUSED_CAPS_MAX = 256
_FUSED_CAPS_LOCK = threading.Lock()
# speculative string-bucket memory per segment signature: the next batch
# (and the next identical query) starts at the largest bucket ever
# validated for this plan instead of paying a pre-launch stream sync.
# LRU-bounded like _FUSED_CAPS (distinct ad-hoc plans would otherwise
# accumulate entries forever in a long-lived session).
_FUSED_BUCKET: "collections.OrderedDict[str, int]" = \
    collections.OrderedDict()


#: rows a grouped partial aggregate's output starts at inside a fused
#: program (a smaller input keeps its own capacity): the floor maybe_shrink
#: cuts a batch to.  A batch with more groups re-runs at its input's
#: capacity (_emit_node asks for it, _converge escalates) and the
#: signature remembers that: one discarded launch and one more program,
#: however the group counts of later batches grow.
GROUP_CAP_DEFAULT = 4096

#: what a caps / feedback key sizes, by its first letter ("j<pos>" and
#: "j<pos>|b<i>": a join's output rows and gather bytes; "g<pos>": a
#: grouped partial aggregate's output rows): the reason a launch that fell
#: short of it is counted under in launch_stats()["discarded"]
_CAP_REASON = {"j": "join_cap", "g": "group_cap"}


def _remember_bucket(sig: str, bucket: int) -> None:
    _FUSED_BUCKET[sig] = max(bucket, _FUSED_BUCKET.get(sig, 0))
    _FUSED_BUCKET.move_to_end(sig)
    while len(_FUSED_BUCKET) > _FUSED_CAPS_MAX:
        _FUSED_BUCKET.popitem(last=False)


def _passthrough_strings_only(exprs) -> bool:
    """True when every variable-width output of a projection is a plain
    column reference (possibly aliased) or a string literal — i.e. the
    projection cannot GROW strings past the segment inputs' byte bound."""
    for e in exprs:
        while isinstance(e, Alias):
            e = e.child
        if not getattr(e.dtype, "variable_width", False):
            continue
        if isinstance(e, (BoundReference, Literal)):
            continue
        return False
    return True


def _literal_bytes(exprs) -> int:
    m = 0

    def walk(e):
        nonlocal m
        if isinstance(e, Literal) and isinstance(e.value, str):
            m = max(m, len(e.value.encode("utf-8")))
        for c in e.children:
            walk(c)
    for e in exprs:
        walk(e)
    return m


def _fusable(node: TpuExec) -> bool:
    from spark_rapids_tpu.expressions.bridge import tree_has_bridge
    from spark_rapids_tpu.plan.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.plan.execs.basic import (
        TpuFilterExec, TpuProjectExec)
    from spark_rapids_tpu.plan.execs.join import TpuBroadcastHashJoinExec
    if isinstance(node, TpuProjectExec):
        return (not tree_has_bridge(node.exprs)
                and not tree_uses_string_bucket(node.exprs)
                and _passthrough_strings_only(node.exprs))
    if isinstance(node, TpuFilterExec):
        return (not tree_has_bridge([node.condition])
                and not tree_uses_string_bucket([node.condition]))
    if isinstance(node, TpuBroadcastHashJoinExec):
        return (node.condition is None
                and node.join_type in ("inner", "left", "left_semi",
                                       "left_anti"))
    if isinstance(node, TpuHashAggregateExec):
        return (node.mode == "partial"
                and not tree_has_bridge(node.group_exprs + node.agg_exprs)
                and not tree_uses_string_bucket(
                    node.group_exprs + node.agg_exprs))
    return False


def _fusable_shuffled_join(node: TpuExec) -> bool:
    """Can this SHUFFLED join be a fused segment's stream-side tail?

    The fused program runs the join per coalesced probe-side group
    against the full co-partition build, so the join type must decompose
    by probe rows (the join's own _LEFT_SPLITTABLE contract minus
    ``existence``, which the fused emitter does not lower) and the
    condition must be empty: a conditional join sizes its pair region from
    the probe's candidate count between two launches, which ``_converge``
    does not speculate.  It runs per-op and reads its exchanges the same
    way a tail does: raw pieces, folded inside its probe program
    (plan/execs/join.py ``PieceSide``).  The build side's size is a
    RUNTIME property — an oversized partition falls back to the per-op
    out-of-core path at execution."""
    from spark_rapids_tpu.plan.execs.join import TpuShuffledHashJoinExec
    return (isinstance(node, TpuShuffledHashJoinExec)
            and node.condition is None
            and bool(node.left_key_idx)
            and node.join_type in ("inner", "left", "left_semi",
                                   "left_anti"))


def fuse_segments(root: TpuExec) -> TpuExec:
    """Planner post-pass: wrap maximal fusable chains (top-down greedy).

    Runs after AQE reader insertion and before LORE wrapping.  Skipped for
    ICI/SPMD sessions (parallel/stage.py fuses the whole query instead).

    Segments extend THROUGH shuffled joins — the join becomes the chain's
    tail, its streamed probe side the segment's stream child and its
    co-partition build a per-partition program argument — and segments
    whose stream child is an exchange/reader consume RAW shuffle pieces,
    so reduce-side merge + probe + aggregate (+ the next exchange's
    partition step) run as ONE program per coalesced partition group."""
    from spark_rapids_tpu.plan.execs.join import TpuBroadcastHashJoinExec

    from spark_rapids_tpu.plan.execs.exchange import (
        TpuCoalescedShuffleReaderExec, TpuShuffleExchangeExec,
        TpuSinglePartitionExec)
    from spark_rapids_tpu.plan.execs.join import (
        TpuAdaptiveJoinExec, TpuShuffledHashJoinExec)

    # a stream child on the far side of a shuffle: fusing even a single
    # op above it is worth a segment — the reduce side then runs ONE
    # program per merged batch, giving the pipelined fetch actual device
    # compute to overlap with (the VERDICT r5 "fusion stops at
    # broadcast-join chains" gap; shuffled joins are first-class in the
    # reference, GpuShuffledSizedHashJoinExec.scala)
    _SHUFFLE_BOUNDARY = (TpuShuffleExchangeExec, TpuCoalescedShuffleReaderExec,
                         TpuSinglePartitionExec, TpuShuffledHashJoinExec,
                         TpuAdaptiveJoinExec)

    from spark_rapids_tpu.plan.execs.basic import (TpuFilterExec,
                                                   TpuProjectExec)
    # build-side chains fold project/filter only: a nested join or agg on
    # the build side keeps its own program (its output size is dynamic,
    # while the dim-build shapes this fold targets are pure row-wise ops)
    _BUILD_CHAIN_OPS = (TpuProjectExec, TpuFilterExec)

    def visit(node: TpuExec, under_exchange: bool = False) -> TpuExec:
        fusable_top = _fusable(node) or _fusable_shuffled_join(node)
        if fusable_top:
            chain = [node]
            cur = node
            if not _fusable_shuffled_join(node):
                while cur.children and _fusable(cur.children[0]):
                    cur = cur.children[0]
                    chain.append(cur)
                if (cur.children
                        and _fusable_shuffled_join(cur.children[0])):
                    # the shuffled join joins the chain as its TAIL: its
                    # probe (left) child becomes the stream child, its
                    # build (right) child a per-partition build input
                    cur = cur.children[0]
                    chain.append(cur)
            n_joins = sum(isinstance(n, (TpuBroadcastHashJoinExec,
                                         TpuShuffledHashJoinExec))
                          for n in chain)
            crosses_shuffle = bool(cur.children) and isinstance(
                cur.children[0], _SHUFFLE_BOUNDARY)
            # a single-op chain directly under an exchange is worth a
            # segment too: the exchange's fused map path then folds the
            # op INTO the partition/slice program (one launch per map
            # batch instead of op + slice), closing the standalone-launch
            # gap on the map side of the next shuffle
            if (n_joins >= 1 or len(chain) >= 2 or crosses_shuffle
                    or under_exchange):
                stream_child = visit(cur.children[0])
                builds, build_chains = [], []
                for n in chain:
                    if not isinstance(n, (TpuBroadcastHashJoinExec,
                                          TpuShuffledHashJoinExec)):
                        continue
                    # dim-build fold: a project/filter chain feeding a
                    # BROADCAST build runs INSIDE the consumer's program
                    # (applied in-trace to the materialized raw build)
                    # instead of as its own standalone program — the
                    # same fold the exchange's map path gives single-op
                    # chains, applied to the build side
                    bchain: List[TpuExec] = []
                    broot = n.children[1]
                    if isinstance(n, TpuBroadcastHashJoinExec):
                        while (_fusable(broot)
                               and isinstance(broot, _BUILD_CHAIN_OPS)
                               and broot.children):
                            bchain.append(broot)
                            broot = broot.children[0]
                    builds.append(visit(broot))
                    build_chains.append(bchain)
                return TpuFusedSegmentExec(chain, stream_child, builds,
                                           build_chains=build_chains)
        is_exchange = isinstance(node, TpuShuffleExchangeExec)
        node.children = tuple(visit(c, under_exchange=is_exchange)
                              for c in node.children)
        return node

    return visit(root)


def unfuse_segments(root: TpuExec) -> TpuExec:
    """Inverse of fuse_segments: rebuild the raw exec chain from every
    fused segment (re-attaching the children the segment detached).

    The SPMD stage compiler lowers raw nodes itself — a whole-query XLA
    program subsumes per-batch segment fusion — so plans headed for
    IciQueryExecutor unfuse first instead of dying on UnsupportedSpmd
    (the fusion pass is keyed to the executing backend, not the session
    shuffle mode)."""
    from spark_rapids_tpu.plan.execs.join import (
        TpuBroadcastHashJoinExec, TpuShuffledHashJoinExec)

    def visit(node: TpuExec) -> TpuExec:
        if isinstance(node, TpuFusedSegmentExec):
            cur = visit(node.children[0])
            builds = [visit(b) for b in node.children[1:]]
            # re-link the detached dim-build chains over their raw builds
            for bi, bc in enumerate(node.build_chains):
                cur_b = builds[bi]
                for op in reversed(bc):          # bottom-up re-link
                    op.children = (cur_b,)
                    cur_b = op
                builds[bi] = cur_b
            for n in reversed(node.chain):       # bottom-up re-link
                if isinstance(n, (TpuBroadcastHashJoinExec,
                                  TpuShuffledHashJoinExec)):
                    n.children = (cur,
                                  builds[node._join_build_ix[id(n)]])
                else:
                    n.children = (cur,)
                cur = n
            return cur
        node.children = tuple(visit(c) for c in node.children)
        return node

    return visit(root)


class TpuFusedSegmentExec(TpuExec):
    """Executes a fused chain (top-down list) as one program per batch.

    children = (stream_child, *build_roots) so metrics/cleanup traversal
    and the engine's partition model see the real tree.
    """

    def __init__(self, chain: List[TpuExec], stream_child: TpuExec,
                 builds: List[TpuExec],
                 build_chains: Optional[List[List[TpuExec]]] = None):
        from spark_rapids_tpu.plan.execs.join import (
            TpuBroadcastHashJoinExec, TpuShuffledHashJoinExec)
        super().__init__((stream_child,) + tuple(builds), chain[0].schema)
        self.chain = chain
        #: per build slot: top-down project/filter chain applied IN-TRACE
        #: to the materialized raw build before the join consumes it (the
        #: dim-build fold — those ops previously ran as standalone
        #: programs).  Empty list = build enters the program untouched.
        self.build_chains: List[List[TpuExec]] = (
            build_chains if build_chains is not None
            else [[] for _ in builds])
        #: runtime-EFFECTIVE fold chains, decided at _materialize_builds
        #: (an oversized raw build applies its chain eagerly and empties
        #: its slot); None until builds materialize
        self._fold_chains: Optional[List[List[TpuExec]]] = None
        self._lock = MaterializeLock()
        self._build_batches: Optional[List[Optional[ColumnarBatch]]] = None
        self._build_bytes = 0
        # join node -> build argument index, in chain order.  A SHUFFLED
        # join's build is per-PARTITION ("part"): materialized per reduce
        # partition from its co-partition reader, entering the program as
        # a tuple of pieces concatenated in-trace.  Broadcast builds
        # ("bcast") materialize once for all partitions, as before.
        self._join_build_ix: Dict[int, int] = {}
        self._build_kind: List[str] = []
        self._shuffled_join: Optional[TpuShuffledHashJoinExec] = None
        bi = 0
        for n in chain:
            if isinstance(n, (TpuBroadcastHashJoinExec,
                              TpuShuffledHashJoinExec)):
                self._join_build_ix[id(n)] = bi
                self._build_kind.append(
                    "part" if isinstance(n, TpuShuffledHashJoinExec)
                    else "bcast")
                if isinstance(n, TpuShuffledHashJoinExec):
                    self._shuffled_join = n
                bi += 1
        assert self._shuffled_join is None or \
            chain[-1] is self._shuffled_join, \
            "a shuffled join fuses only as the chain tail"
        self._lit_bytes = self._collect_literal_bytes()
        # string columns ANYWHERE in the segment (stream, builds, build
        # chains, or an intermediate schema) force a non-zero bucket
        # floor: the join and groupby kernels assert string_max_bytes > 0
        # for string keys, and an all-empty build side would otherwise
        # derive bucket 0
        self._has_any_strings = any(
            getattr(d, "variable_width", False)
            for n in ([stream_child] + list(chain) + list(builds)
                      + [bn for bc in self.build_chains for bn in bc])
            for d in n.schema.dtypes)
        self._sig: Optional[str] = None
        self._consts: Optional[tuple] = None
        # DETACH the chain from the original tree: the jitted program's
        # make-closure holds the chain nodes, and shared_jit cache entries
        # outlive queries — a chain node still linked to the stream child
        # would pin the scan's device batches forever (the shared_jit
        # no-self-capture contract, plan/execs/base.py:44).  The fused
        # exec's own children tuple carries the live subtrees instead.
        for n in chain:
            n.children = ()
        for bc in self.build_chains:
            for n in bc:
                n.children = ()

    # -- plan identity ------------------------------------------------------

    def _collect_literal_bytes(self) -> int:
        from spark_rapids_tpu.plan.execs.aggregate import TpuHashAggregateExec
        from spark_rapids_tpu.plan.execs.basic import (
            TpuFilterExec, TpuProjectExec)
        m = 0
        for n in (list(self.chain)
                  + [bn for bc in self.build_chains for bn in bc]):
            if isinstance(n, TpuProjectExec):
                m = max(m, _literal_bytes(n.exprs))
            elif isinstance(n, TpuFilterExec):
                m = max(m, _literal_bytes([n.condition]))
            elif isinstance(n, TpuHashAggregateExec):
                m = max(m, _literal_bytes(n.group_exprs + n.agg_exprs))
        return m

    def signature(self) -> str:
        if self._sig is None:
            from spark_rapids_tpu.plan.execs.base import schema_cache_key
            parts = [_exec_signature_shallow(n) for n in self.chain]
            # the STREAM schema must key the program too: chain-identical
            # segments over different stream schemas read different
            # string-ordinal feedback (the r5 fuzz cross-query cache
            # pollution — a DATE column indexed as variable-width).  Build
            # schemas likewise: the per-plane byte-capacity tags are laid
            # out from the build columns' nested offset paths.  Build
            # CHAINS too: the in-trace dim-build ops are part of the
            # program this signature names.
            stream = schema_cache_key(self.children[0].schema)
            builds = ";".join(
                schema_cache_key(b.schema)
                + ("<" + ">".join(_exec_signature_shallow(n)
                                  for n in self.build_chains[bi])
                   if self.build_chains[bi] else "")
                for bi, b in enumerate(self.children[1:]))
            self._sig = ("fused[" + ">".join(parts)
                         + f"|stream={stream}|builds={builds}]")
        return self._sig

    def _all_exprs(self) -> List[Expression]:
        return [e for n in (list(self.chain)
                            + [bn for bc in self.build_chains for bn in bc])
                for e in _bound_exprs(n)]

    # -- inputs -------------------------------------------------------------

    def num_partitions(self) -> int:
        return self.children[0].num_partitions()

    def _build_fold_limit(self, bi: int) -> int:
        """Raw-build row bound for the in-trace dim-build fold of slot
        ``bi``: the consumer join's batch target."""
        for n in self.chain:
            if self._join_build_ix.get(id(n)) == bi:
                return max(int(getattr(n, "target_rows", 1 << 20)), 1)
        return 1 << 20

    def _materialize_builds(self) -> List[Optional[ColumnarBatch]]:
        """Broadcast builds, materialized once for all partitions.  A
        shuffled join's per-partition build slot stays None here — it is
        filled per reduce partition by _partition_build_pieces.

        The dim-build fold is GATED here at runtime: the broadcast
        planner sizes builds by their POST-chain estimate, so a raw dim
        far larger than its filtered output can still plan as a
        broadcast — folding its filter in-trace would re-filter the raw
        dim (and run the join at raw capacity) on EVERY program call.
        A raw build past the consumer join's batch target applies its
        chain EAGERLY once (one standalone program, the pre-fold
        behavior) and the slot's effective fold chain empties; small
        dims (the q25/q72 shapes) keep the in-trace fold."""
        from spark_rapids_tpu.plan.execs.coalesce import coalesce_to_one
        with self._lock:
            if self._build_batches is None:
                outs: List[Optional[ColumnarBatch]] = []
                mb = 0
                fold = [list(bc) for bc in self.build_chains]
                for bi, b in enumerate(self.children[1:]):
                    if self._build_kind[bi] == "part":
                        outs.append(None)
                        continue
                    batches = []
                    for p in range(b.num_partitions()):
                        batches.extend(b.execute_partition(p))
                    merged = with_retry_no_split(
                        lambda: coalesce_to_one(batches))
                    if merged is None:
                        merged = ColumnarBatch.empty(b.schema)
                    if (fold[bi]
                            and merged.capacity > self._build_fold_limit(bi)):
                        merged = with_retry_no_split(
                            lambda: _apply_build_chain(fold[bi], merged))
                        fold[bi] = []
                    outs.append(merged)
                    mb = max(mb, _max_live_bytes(merged))
                self._build_batches = outs
                self._build_bytes = mb
                self._fold_chains = fold
            return self._build_batches

    def _effective_chains(self) -> List[List[TpuExec]]:
        """The runtime fold chains (decided by _materialize_builds); the
        static chains until builds materialize."""
        return (self._fold_chains if self._fold_chains is not None
                else self.build_chains)

    def _bucket_floor(self) -> int:
        """Pre-launch bucket WITHOUT a stream sync (a blocking fetch per
        batch stalls the dispatch pipeline).  The stream's
        actual max string bytes is validated IN-PROGRAM: the fused program
        reports it in feedback, and a too-small speculation discards the
        output and re-runs at the larger bucket — the same discipline as
        capacity overflow.  Build/literal bytes are known host-side."""
        from spark_rapids_tpu.kernels import strings as SK
        m = max(self._build_bytes, self._lit_bytes)
        if m == 0 and self._has_any_strings:
            m = 1           # kernels need a positive byte window
        return SK.bucket_for(m) if m else 0

    # -- execution ----------------------------------------------------------

    def _uses_stream_pieces(self) -> bool:
        """True when the stream child is an exchange/reader whose RAW
        pieces this segment can concat inside its own program (the
        reduce-side merge joins the fused program)."""
        return hasattr(self.children[0], "stream_pieces")

    def _stream_groups(self, idx: int, extra_pieces=()):
        """Coalesced piece groups of stream partition ``idx``, bounded by
        the exchange's batch target.  The piece pull (stage k's reduce
        fetch / unspill) runs on a lookahead thread bounded by the fetch
        in-flight byte window, so it overlaps this segment's device
        compute (shuffle/pipeline.py).

        ``extra_pieces``: pieces pinned ALONGSIDE each group in the same
        attempt (the partition's co-partition build pieces) — the
        residency degrade check must see the COMBINED pinned set, shared
        backings deduped, or two half-budget checks could jointly pin a
        full budget."""
        from spark_rapids_tpu.shuffle.pipeline import pipelined
        from spark_rapids_tpu.shuffle.transport import fetch_window_bytes
        target = max(int(getattr(self.children[0], "coalesce_target_rows",
                                 1 << 20)), 1)
        pieces = pipelined(self.children[0].stream_pieces(idx),
                           lambda p: p.nbytes, fetch_window_bytes(),
                           name="fused-stream-prefetch")
        group, acc = [], 0
        for piece in pieces:
            if group and acc + piece.capacity > target:
                yield _degrade_over_budget_group(group, extra_pieces)
                group, acc = [], 0
            group.append(piece)
            acc += piece.capacity
        if group:
            yield _degrade_over_budget_group(group, extra_pieces)

    def _partition_build_pieces(self, idx: int) -> Dict[int, list]:
        """Per-partition build inputs for the chain's shuffled join:
        build-slot index -> this reduce partition's co-partition pieces."""
        from spark_rapids_tpu.shuffle.transport import StreamPiece
        out: Dict[int, list] = {}
        for bi, root in enumerate(self.children[1:]):
            if self._build_kind[bi] != "part":
                continue
            if hasattr(root, "stream_pieces"):
                pieces = list(root.stream_pieces(idx))
            else:
                pieces = [StreamPiece.of_batch(b)
                          for b in root.execute_partition(idx)]
            if not pieces:
                pieces = [StreamPiece.of_batch(
                    ColumnarBatch.empty(root.schema))]
            out[bi] = pieces
        return out

    def _fuse_build_limit(self) -> int:
        join = self._shuffled_join
        return max(int(join.target_rows), 1) if join is not None \
            else (1 << 62)

    def execute_partition(self, idx: int):
        from spark_rapids_tpu.plan.execs.aggregate import TpuHashAggregateExec
        from spark_rapids_tpu.plan.execs.coalesce import maybe_shrink
        shrink = not isinstance(self.chain[0], TpuHashAggregateExec)

        def finish(out):
            return maybe_shrink(out) if shrink else out

        for out in self._execute_fused(idx, slice_spec=None, finish=finish):
            self.output_rows.add(out.num_rows)
            yield self._count_out(out)

    def execute_partition_sliced(self, idx: int, keys, n_out: int,
                                 exchange_sig: str):
        """Exchange integration: the fused chain AND the exchange's
        key-append + hash-partition run in the SAME program; yields
        (reordered_batch, host_counts) per input batch with ONE combined
        device fetch (feedback + per-partition counts)."""
        spec = (tuple(keys), int(n_out), exchange_sig)
        for out, counts in self._execute_fused(idx, slice_spec=spec):
            self.output_rows.add(out.num_rows)
            self.output_batches.add(1)
            yield out, counts

    def _execute_fused(self, idx: int, slice_spec=None, finish=None):
        """Common driver for both execute paths.  Without slice_spec it
        yields finished output batches (through ``finish``); with one it
        yields (reordered_batch, host_counts) pairs."""
        from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
        builds = self._materialize_builds()
        part_pieces = self._partition_build_pieces(idx)
        if part_pieces:
            from spark_rapids_tpu.shuffle.transport import (
                views_over_memory_budget)
            limit = self._fuse_build_limit()
            # two bounds: the in-program join size (sum of view/piece
            # capacities — what the in-trace concat is sized by) and the
            # range-view RESIDENCY guard (an attempt pins full backings,
            # deduped; near the arena budget the fallback's sliced
            # materialization must run instead)
            if (any(sum(p.capacity for p in pieces) > limit
                    for pieces in part_pieces.values())
                    or views_over_memory_budget(part_pieces.values())):
                # the co-partition build side outgrew the in-program
                # bound (hot-key skew): this partition runs the per-op
                # out-of-core join, with the rest of the chain still
                # fused above it
                SHUFFLE_COUNTERS.add(fused_reduce_fallbacks=1)
                yield from self._execute_fallback(
                    idx, part_pieces, slice_spec=slice_spec, finish=finish)
                return
        if self._uses_stream_pieces():
            extra = [p for ps in part_pieces.values() for p in ps]
            for group in self._stream_groups(idx, extra_pieces=extra):
                with timed(self.op_time):
                    full = self._assemble_builds(builds, part_pieces)
                    out, counts = self._run(group, full,
                                            slice_spec=slice_spec)
                SHUFFLE_COUNTERS.add(fused_reduce_programs=1)
                yield (out, counts) if slice_spec is not None \
                    else finish(out)
            return
        for batch in self.children[0].execute_partition(idx):
            with timed(self.op_time):
                full = self._assemble_builds(builds, part_pieces)
                out, counts = self._run(batch, full, slice_spec=slice_spec)
            yield (out, counts) if slice_spec is not None else finish(out)

    @staticmethod
    def _assemble_builds(builds, part_pieces):
        """Build argument list: broadcast batches + per-partition piece
        lists in slot order."""
        return [part_pieces[bi] if b is None else b
                for bi, b in enumerate(builds)]

    def _execute_fallback(self, idx: int, part_pieces, slice_spec=None,
                          finish=None):
        """Oversized co-partition build: run the shuffled join through
        its own per-op machinery (sub-partitioned spillable co-buckets,
        skew-aware splits) and keep the REST of the chain fused — each
        join output batch runs the above-join program (which still folds
        the next exchange's partition step when sliced).

        The materialized inputs stay pinned through the join by the same
        contract as the per-op path (the OOC sub-partitioning reads them
        exactly once up front)."""
        join = self._shuffled_join
        assert join is not None and len(part_pieces) == 1
        (bi, build_pieces), = part_pieces.items()
        chain_above = self.chain[:-1]
        # the shuffled join is the chain tail, so its build slot is the
        # last one: everything before it is the above-chain's builds
        builds_above = self._materialize_builds()[:bi]
        stream_pieces = (list(self.children[0].stream_pieces(idx))
                         if self._uses_stream_pieces() else None)
        pinned = []
        try:
            if stream_pieces is not None:
                left_batches = []
                for p in stream_pieces:
                    # tpu-lint: allow-retry-discipline(inputs stay pinned through the OOC sub-partition pass, which reads them exactly once up front; unpinned in the finally)
                    left_batches.append(p.materialize_batch_pinned())
                    pinned.append(p)
            else:
                left_batches = list(self.children[0].execute_partition(idx))
            right_batches = []
            for p in build_pieces:
                # tpu-lint: allow-retry-discipline(inputs stay pinned through the OOC sub-partition pass, which reads them exactly once up front; unpinned in the finally)
                right_batches.append(p.materialize_batch_pinned())
                pinned.append(p)
            total = (sum(b.capacity for b in left_batches)
                     + sum(b.capacity for b in right_batches))
            for jb in join._execute_out_of_core(left_batches, right_batches,
                                                total):
                if not chain_above and slice_spec is None:
                    yield finish(jb)
                    continue
                with timed(self.op_time):
                    out, counts = self._run(
                        jb, builds_above, slice_spec=slice_spec,
                        chain=chain_above,
                        sig=self.signature() + "|above")
                yield (out, counts) if slice_spec is not None \
                    else finish(out)
        finally:
            for p in pinned:
                p.unpin()

    def _run(self, stream, builds, slice_spec=None, chain=None, sig=None):
        """One program call as one ``fused.batch`` span: everything the
        host does for it (``stream`` arrives already pulled)."""
        with trace_range("fused.batch"):
            return self._converge(stream, builds, slice_spec, chain, sig)

    def _converge(self, stream, builds, slice_spec, chain, sig):
        """Converge-and-execute one program call.

        Every launch but the last is discarded: counted by what was too
        small (``launch_stats()["discarded"]``) and recorded as one
        ``fused.discard`` span, from its dispatch to the feedback that
        condemned it.

        ``stream`` is a single ColumnarBatch (per-batch path) or a LIST
        of StreamPieces (across-shuffle path: the group concats inside
        the program).  ``builds`` entries are broadcast batches or
        per-partition StreamPiece lists (likewise concatenated
        in-trace).  Pieces are materialized PIN-BALANCED per retry
        attempt (coalesce.retry_over_stream_pieces), so a mid-attempt
        OOM's spill can free exactly the inputs the next attempt brings
        back."""
        from spark_rapids_tpu.kernels import strings as SK
        from spark_rapids_tpu.memory.arena import TpuSplitAndRetryOOM
        from spark_rapids_tpu.plan.execs.coalesce import (
            retry_over_stream_pieces)
        if chain is None:
            chain = self.chain
        base_sig = sig if sig is not None else self.signature()
        if any(self.build_chains):
            # the runtime fold decision (eager vs in-trace per slot) must
            # key the compiled program: two executions of one static plan
            # can fold differently when build sizes differ
            base_sig += "|fold=" + "".join(
                "1" if c else "0" for c in self._effective_chains())
        sig = base_sig
        if slice_spec is not None:
            sig += f"|slice={slice_spec[2]}|{slice_spec[1]}"
        with _FUSED_CAPS_LOCK:
            bucket = max(_FUSED_BUCKET.get(base_sig, 0),
                         self._bucket_floor())
        if self._consts is None:
            self._consts = tuple(jnp.asarray(a) for a in
                                 collect_trace_consts(self._all_exprs()))
        from spark_rapids_tpu.plan.execs.base import alias_shared_jit
        group_mode = isinstance(stream, list)
        builds = list(builds)
        piece_build_ixs = [i for i, b in enumerate(builds)
                           if isinstance(b, list)]
        piece_lists = ([stream] if group_mode else []) + \
            [builds[i] for i in piece_build_ixs]
        n_views = sum(1 for lst in piece_lists for p in lst
                      if getattr(p, "is_range_view", False))
        if n_views:
            # CACHE_ONLY range views whose slice runs INSIDE this program
            # (counted once per program call, not per retry attempt)
            from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
            SHUFFLE_COUNTERS.add(range_view_folds=n_views)

        def invoke(fn):
            if not piece_lists:
                return with_retry_no_split(
                    lambda: fn(stream, tuple(builds), self._consts))

            def body(mats):
                k = 0
                s = stream
                if group_mode:
                    s = tuple(mats[0])
                    k = 1
                bs = list(builds)
                for i in piece_build_ixs:
                    bs[i] = tuple(mats[k])
                    k += 1
                return fn(s, tuple(bs), self._consts)
            return retry_over_stream_pieces(piece_lists, body)

        # rows in, for the per-query counters: a scan batch's own count
        # rides in the feedback's one transfer; a group's pieces say theirs
        rows_in = (sum(p.rows for p in stream) if group_mode
                   else stream.num_rows)

        def discard(launched, *reasons):
            count_discarded_launch(*reasons)
            record_range("fused.discard", *launched)

        caps_key = None
        caps: Dict[str, int] = {}
        kind = _program_kind(chain, slice_spec)
        for _ in range(24):
            new_key = f"{sig}|bkt={bucket}"
            if new_key != caps_key:      # first pass, or bucket escalated
                caps_key = new_key
                with _FUSED_CAPS_LOCK:
                    caps = dict(_FUSED_CAPS.get(caps_key, ()))
                    if caps_key in _FUSED_CAPS:
                        _FUSED_CAPS.move_to_end(caps_key)
            build_key = f"{caps_key}|caps={sorted(caps.items())}"
            fn = shared_jit(build_key,
                            lambda: self._make(bucket, caps, slice_spec,
                                               chain),
                            kind=kind)
            launched = time.perf_counter(), time.time()
            out, counts, fb = invoke(fn)
            with trace_range("fused.feedback"):
                # tpu-lint: allow-host-sync(overflow feedback must reach the host; one batched sync per attempt)
                fetched, host_counts, host_rows_in = jax.device_get(
                    (fb, counts,
                     rows_in if any(k[0] == "g" for k in fb) else None))
            observed = int(fetched.pop("__stream_bytes", 0))
            if observed or bucket:
                need = SK.bucket_for(max(observed, self._build_bytes,
                                         self._lit_bytes, 1))
                if need > bucket:
                    # bucket speculation too small (a live stream or
                    # co-partition build string exceeds the window):
                    # discard, re-run larger
                    with _FUSED_CAPS_LOCK:
                        _remember_bucket(base_sig, need)
                    bucket = need
                    discard(launched, "bucket")
                    continue
            escalated = set()
            for k, v in fetched.items():
                req = int(v)
                if req > caps.get(k, 0):
                    caps[k] = round_up_pow2(max(req, 1))
                    escalated.add(_CAP_REASON[k[0]])
            if escalated:
                discard(launched, *escalated)
                continue
            groups_out = [int(v) for k, v in fetched.items() if k[0] == "g"]
            if groups_out:
                from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
                SHUFFLE_COUNTERS.add(agg_partial_rows_in=int(host_rows_in),
                                     agg_partial_groups_out=sum(groups_out))
            # tracing seeded the capacity defaults AFTER build_key was
            # formed; register the program under the converged key too so
            # the next batch (and the next identical query) hits the jit
            # cache instead of recompiling byte-identically
            final_key = f"{caps_key}|caps={sorted(caps.items())}"
            if final_key != build_key:
                alias_shared_jit(build_key, final_key)
            with _FUSED_CAPS_LOCK:
                _FUSED_CAPS[caps_key] = dict(caps)
                _FUSED_CAPS.move_to_end(caps_key)
                if len(_FUSED_CAPS) > _FUSED_CAPS_MAX:
                    _FUSED_CAPS.popitem(last=False)
                _remember_bucket(base_sig, bucket)
            return out, host_counts
        raise TpuSplitAndRetryOOM(
            "fused segment capacities did not converge")

    # -- traceable program --------------------------------------------------

    def _make(self, bucket: int, caps: Dict[str, int], slice_spec=None,
              chain=None):
        """Build the traceable fn(stream, builds, consts).

        ``caps`` is mutated at trace time via setdefault (the SPMD
        _Caps.get discipline): identical plan+shapes derive identical
        defaults, so the pre-trace cache key stays deterministic.

        The closure must NOT capture ``self`` (shared_jit no-self-capture
        contract): cache entries outlive queries, and self.children pins
        the stream subtree's device batches.  It closes over the detached
        chain nodes + the build-index map only."""
        # the program's stream input is the stream child's output for the
        # full chain, but the SHUFFLED JOIN's output for the fallback's
        # above-join chain — the string-ordinal feedback must index the
        # schema the program actually receives
        stream_schema = (self.children[0].schema
                         if chain is None or chain is self.chain
                         else self._shuffled_join.schema)
        stream_string_ords = tuple(
            i for i, d in enumerate(stream_schema.dtypes)
            if getattr(d, "variable_width", False))
        return _make_program(list(self.chain if chain is None else chain),
                             dict(self._join_build_ix),
                             self._all_exprs(), bucket, caps,
                             slice_spec=slice_spec,
                             stream_string_ords=stream_string_ords,
                             build_chains=[list(bc) for bc
                                           in self._effective_chains()])

    def cleanup(self) -> None:
        with self._lock:
            self._build_batches = None
            self._build_bytes = 0
            self._fold_chains = None
        super().cleanup()

    def describe(self):
        inner = " <- ".join(type(n).__name__.replace("Tpu", "")
                            .replace("Exec", "") for n in self.chain)
        return f"TpuFusedSegment[{inner}]"

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.describe()]
        for n in self.chain:
            lines.append("  " * (indent + 1) + "* " + n.describe())
        for bi, bc in enumerate(self.build_chains):
            for n in bc:
                lines.append("  " * (indent + 1) + f"b{bi}* "
                             + n.describe())
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)


def _apply_build_chain(bc: List[TpuExec],
                       merged: ColumnarBatch) -> ColumnarBatch:
    """Eager one-shot application of a dim-build chain — ONE standalone
    jitted program over the raw merged build (the pre-fold behavior,
    used when the raw build exceeds the in-trace fold bound)."""
    from spark_rapids_tpu.plan.execs.base import schema_cache_key, shared_jit
    exprs = [e for n in bc for e in _bound_exprs(n)]
    consts = tuple(jnp.asarray(a) for a in collect_trace_consts(exprs))
    bcaps = ",".join(str(c.byte_capacity) for c in merged.columns
                     if c.offsets is not None)
    key = ("buildchain|" + ">".join(_exec_signature_shallow(n) for n in bc)
           + f"|{schema_cache_key(merged.schema)}|{merged.capacity}|{bcaps}")

    def make():
        def fn(batch, consts_):
            cmap = bind_trace_consts(exprs, consts_)
            cur = batch
            for op in reversed(bc):   # bottom-up, like the fused chain
                cur, _ = _emit_one(op, 0, cur, (), {}, cmap, 0, {}, {})
            return cur
        return fn
    return shared_jit(key, make, kind="buildchain")(merged, consts)


def _degrade_over_budget_group(group, extra_pieces=()):
    """Range-view residency guard for a stream group: when materializing
    the group's views — TOGETHER with ``extra_pieces`` pinned in the
    same attempt (the partition's build pieces), shared backings deduped
    — would pin backings past the arena budget bound
    (transport.views_over_memory_budget), slice each of the group's
    views to an INDEPENDENT batch pin-balanced (the materialize
    fallback) so the attempt's residency is the group target, not the
    deduped backings.  No budget / under budget: the group folds
    in-trace untouched."""
    from spark_rapids_tpu.shuffle.transport import (
        StreamPiece, materialize_view_batch, views_over_memory_budget)
    if not views_over_memory_budget([group, list(extra_pieces)]):
        return group
    return [StreamPiece.of_batch(materialize_view_batch(p))
            if getattr(p, "is_range_view", False) else p
            for p in group]


def _make_program(chain: List[TpuExec], join_build_ix: Dict[int, int],
                  exprs: List[Expression], bucket: int,
                  caps: Dict[str, int], slice_spec=None,
                  stream_string_ords: Tuple[int, ...] = (),
                  build_chains: Optional[List[List[TpuExec]]] = None):
    """Traceable fn(stream, builds, consts) -> (out, counts, fb).

    ``stream`` is one batch or a TUPLE of batches (a coalesced shuffle
    group, concatenated in-trace — the reduce-side merge as part of the
    same program).  ``builds`` entries are one batch (broadcast) or a
    tuple of co-partition pieces (a shuffled join's per-partition build,
    also concatenated in-trace); pieces may be CACHE_ONLY RangeViews,
    sliced in-trace by the concat.

    ``slice_spec`` = (keys, n_out, sig): additionally run the shuffle
    exchange's key-append + hash-partition INSIDE the program, returning
    per-partition counts (None otherwise).  ``stream_string_ords``: the
    stream's variable-width columns; their live byte max — together with
    every tuple-build's variable-width columns — is reported in
    feedback["__stream_bytes"] to validate the speculative bucket.

    ``build_chains``: per build slot, a top-down project/filter chain
    applied IN-TRACE to the (raw) build batch before the join reads it —
    the dim-build fold; the byte maxima feeding the speculative bucket
    are observed on the RAW build (a superset: the admitted ops never
    grow strings).

    Capacities: a join's output has ``caps["j<pos>"]`` rows, a grouped
    partial aggregate's ``caps["g<pos>"]`` (``GROUP_CAP_DEFAULT`` at
    first; the input's capacity where that is no larger), and so has
    everything above it in the chain and the batch the slice partitions.
    The program checks neither: it reports the rows each needs in ``fb``
    under the same key (an aggregate whose groups did not fit: its
    input's capacity), and ``_converge`` discards the output and runs
    again larger when one did not fit."""

    masked = _masked_filters(chain)

    def fn(stream, builds: tuple, consts: tuple):
        from spark_rapids_tpu.kernels.strings import max_live_string_bytes
        from spark_rapids_tpu.shuffle.transport import fold_pieces_in_trace
        cmap = bind_trace_consts(exprs, consts)
        feedback: Dict[str, jax.Array] = {}
        part_builds = [i for i, b in enumerate(builds)
                       if isinstance(b, tuple)]
        builds = tuple(fold_pieces_in_trace(b) if isinstance(b, tuple)
                       else b for b in builds)
        if isinstance(stream, tuple):
            stream = fold_pieces_in_trace(stream)
        byte_obs = [jnp.asarray(max_live_string_bytes(stream.columns[i],
                                                      stream.num_rows))
                    for i in stream_string_ords]
        for i in part_builds:
            # a per-partition build's string bytes are only known at
            # execution: validate them through the same speculative-
            # bucket feedback as the stream side
            b = builds[i]
            byte_obs.extend(
                jnp.asarray(max_live_string_bytes(b.columns[ci],
                                                  b.num_rows))
                for ci, d in enumerate(b.schema.dtypes)
                if getattr(d, "variable_width", False))
        if byte_obs:
            feedback["__stream_bytes"] = jnp.max(
                jnp.stack(byte_obs)).astype(jnp.int64)
        if build_chains and any(build_chains):
            # dim-build fold: each slot's project/filter chain transforms
            # the raw build INSIDE this program (bottom-up, like the main
            # chain) before the join gathers from it
            bl = list(builds)
            for bi in range(len(bl)):
                bc = build_chains[bi] if bi < len(build_chains) else []
                cur_b = bl[bi]
                for op in reversed(bc):
                    cur_b, _ = _emit_one(op, 0, cur_b, (), {}, cmap, bucket,
                                         caps, feedback)
                bl[bi] = cur_b
            builds = tuple(bl)
        cur, live = stream, None
        for pos in range(len(chain) - 1, -1, -1):
            cur, live = _emit_one(chain[pos], pos, cur, builds,
                                  join_build_ix, cmap, bucket, caps,
                                  feedback, live, pos in masked)
        if slice_spec is None:
            return cur, None, feedback
        keys, n_out, _sig = slice_spec
        from spark_rapids_tpu.kernels.partition import (
            hash_partition, round_robin_partition)
        from spark_rapids_tpu.plan.execs.exchange import append_key_columns
        with jax.named_scope("exchange_slice"):
            if not keys:
                out, counts = round_robin_partition(cur, n_out)
                return out, counts, feedback
            work, key_idx = append_key_columns(cur, keys)
            reordered, counts = hash_partition(work, key_idx, n_out,
                                               string_max_bytes=bucket)
            out = ColumnarBatch(reordered.columns[:len(cur.schema)],
                                reordered.num_rows, cur.schema)
            return out, counts, feedback

    return fn


def _row_local(exprs) -> bool:
    """True when no expression reads a row's position, the row count or a
    neighbouring row: its value at a row is then the same whether or not
    the rows a filter drops are still in the batch."""
    def walk(e) -> bool:
        return e.row_local and all(walk(c) for c in e.children)
    return all(walk(e) for e in exprs)


def _masked_filters(chain) -> frozenset:
    """Positions of the chain's filters that hand their mask to an
    aggregate instead of compacting (the chain is top-down).

    The rule: a filter whose rows reach nothing but an aggregate that
    takes a mask (``reduces_under_mask``) skips ``compaction_map`` and
    ``gather_batch``.  A keyless aggregate reduces over the whole capacity
    under the boolean ``live`` mask; a grouped one gives it to its grouping
    sort as the liveness key, which moves the rows once where a compaction
    and then the sort moved them twice.  Between the two only row-local
    projects and further filters (ANDed into the mask) may sit, and the
    aggregate's own expressions have to be row-local: they are evaluated
    on rows the filter dropped.  Every other filter (join or exchange slice
    above it with no aggregate between, top of the chain) compacts.  Read
    from the chain's shape alone, which the program's cache key already
    covers."""
    from spark_rapids_tpu.plan.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.plan.execs.basic import (
        TpuFilterExec, TpuProjectExec)
    masked, pending = set(), []
    for pos in range(len(chain) - 1, -1, -1):     # bottom-up, as emitted
        node = chain[pos]
        if isinstance(node, TpuFilterExec) and _row_local([node.condition]):
            pending.append(pos)
        elif isinstance(node, TpuHashAggregateExec):
            if (node._spec.reduces_under_mask()
                    and _row_local(node.group_exprs + node.agg_exprs)):
                masked.update(pending)
            pending = []
        elif not (isinstance(node, TpuProjectExec)
                  and _row_local(node.exprs)):
            pending = []
    return frozenset(masked)


def _node_kind(node, masked: bool = False) -> str:
    """What one chain node is called in a program's name and in the scope
    its operations carry in the device trace.  ``masked``: a filter that
    hands over its mask (``_masked_filters``)."""
    from spark_rapids_tpu.plan.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.plan.execs.basic import (
        TpuFilterExec, TpuProjectExec)
    if isinstance(node, TpuProjectExec):
        return "project"
    if isinstance(node, TpuFilterExec):
        return "mfilter" if masked else "filter"
    if isinstance(node, TpuHashAggregateExec):
        return "agg"
    return "join"


def _program_kind(chain, slice_spec) -> str:
    """``fused_<chain kinds, top down>[_slice]``, repeats folded
    (``fused_agg_project_filter``), cut to a readable length."""
    masked = _masked_filters(chain)
    kinds = [_node_kind(n, i in masked) for i, n in enumerate(chain)]
    folded = [k for i, k in enumerate(kinds) if i == 0 or k != kinds[i - 1]]
    if slice_spec is not None:
        folded.append("slice")
    return ("fused_" + "_".join(folded))[:48]


def _emit_one(node, pos: int, cur: ColumnarBatch, builds: tuple,
              join_build_ix: Dict[int, int], cmap, bucket: int,
              caps: Dict[str, int], feedback: Dict[str, jax.Array],
              live: Optional[jax.Array] = None, masked: bool = False
              ) -> Tuple[ColumnarBatch, Optional[jax.Array]]:
    """One chain node's operations, under a scope that names the node's
    kind in the device trace.  Returns the node's output and, while a
    masked filter's rows are on their way to their aggregate, the mask of
    the rows still live in it (else None: the live rows are a prefix)."""
    with jax.named_scope(_node_kind(node, masked)):
        return _emit_node(node, pos, cur, builds, join_build_ix, cmap,
                          bucket, caps, feedback, live, masked)


def _emit_node(node, pos: int, cur: ColumnarBatch, builds: tuple,
               join_build_ix: Dict[int, int], cmap, bucket: int,
               caps: Dict[str, int], feedback: Dict[str, jax.Array],
               live: Optional[jax.Array], masked: bool
               ) -> Tuple[ColumnarBatch, Optional[jax.Array]]:
    from spark_rapids_tpu.plan.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.plan.execs.basic import (
        TpuFilterExec, TpuProjectExec)
    from spark_rapids_tpu.plan.execs.join import (
        TpuBroadcastHashJoinExec, TpuShuffledHashJoinExec)

    if isinstance(node, TpuProjectExec):
        ctx = EvalContext(cur, trace_consts=cmap)
        cols = tuple(e.eval(ctx) for e in node.exprs)
        return ColumnarBatch(cols, cur.num_rows, node.schema), live

    if isinstance(node, TpuFilterExec):
        ctx = EvalContext(cur, trace_consts=cmap)
        pred = node.condition.eval(ctx)
        mask = pred.data & pred.validity & (
            cur.live_mask() if live is None else live)
        if masked:
            return cur, mask
        indices, count = compaction_map(mask)
        return gather_batch(cur, indices, count), None

    if isinstance(node, (TpuBroadcastHashJoinExec, TpuShuffledHashJoinExec)):
        assert live is None, "a masked filter's rows reach no join"
        # the shuffled join lowers through the SAME gather-map emitter as
        # the broadcast join: its "build" is simply this reduce
        # partition's co-partition side instead of a global broadcast
        return _emit_join(node, pos, cur, builds[join_build_ix[id(node)]],
                          bucket, caps, feedback), None

    assert isinstance(node, TpuHashAggregateExec), type(node).__name__
    # either way a masked filter's mask is spent here: what the aggregate
    # hands on has its live rows as a prefix again
    spec = node._spec
    if not spec.group_exprs:
        # one row whatever the input: no capacity to speculate, no caps key
        return spec._partial_step(cur, string_bucket=bucket, live=live), None
    # a grouped partial aggregate hands on as many rows as it has groups:
    # the capacity is speculated like a join's and validated by _converge
    # from the rows the feedback asks for.  The default is the same
    # whatever batch is traced first (it is part of the converged cache
    # key); at the input's capacity or over it the step is the one every
    # batch fits
    ck = f"g{pos}"
    cap = caps.setdefault(ck, GROUP_CAP_DEFAULT)
    out = spec._partial_step(
        cur, string_bucket=bucket, live=live,
        group_capacity=cap if cap < cur.capacity else None)
    # groups that did not fit ask for the input's capacity at once, not
    # for the next power of two over this batch's count: batches whose
    # group counts grow would each discard a launch and compile a program
    n = jnp.asarray(out.num_rows, jnp.int64)
    feedback[ck] = jnp.where(n > cap, cur.capacity, n)
    return out, None


def _emit_join(node, pos: int, left: ColumnarBatch, right: ColumnarBatch,
               bucket: int, caps: Dict[str, int],
               feedback: Dict[str, jax.Array]) -> ColumnarBatch:
    from spark_rapids_tpu.kernels.join import (
        apply_gather_maps, join_gather_maps)
    from spark_rapids_tpu.kernels.selection import (
        nested_offset_paths, path_plane_capacity)
    nl, nr = left.capacity, right.capacity
    if node.join_type in ("left_semi", "left_anti"):
        guess = max(nl, 1)
    else:
        # FK-shaped equi-joins output ~probe-side rows (the task
        # engine's broadcast guess); feedback escalates the rest
        guess = max(nl, nr, 1)
    ck = f"j{pos}"
    cap = caps.setdefault(ck, round_up_pow2(guess))
    byte_caps = {}
    idx = 0
    sides = ([left] if node.join_type in ("left_semi", "left_anti")
             else [left, right])
    for side in sides:
        for c in side.columns:
            for path in nested_offset_paths(c):
                tag = f"{ck}|b{idx}" + "".join(f"_{i}" for i in path)
                byte_caps[(idx, path)] = caps.setdefault(
                    tag, path_plane_capacity(c, path))
            idx += 1
    li, ri, count, status = join_gather_maps(
        left, node.left_key_idx, right, node.right_key_idx,
        node.join_type, cap, string_max_bytes=bucket)
    out, gstatus = apply_gather_maps(
        left, right, li, ri, count, node.schema, node.join_type,
        cap, byte_caps)
    feedback[ck] = jnp.asarray(status.required_rows, jnp.int64)
    if gstatus.required_bytes:
        for (ordv, path), req in zip(sorted(byte_caps),
                                     gstatus.required_bytes):
            tag = f"{ck}|b{ordv}" + "".join(f"_{i}" for i in path)
            feedback[tag] = jnp.asarray(req, jnp.int64)
    return out


def _bound_exprs(node) -> List[Expression]:
    """The expressions of a chain node that the fused program evaluates
    under its bound arguments (``_emit_node``): a project's and a
    filter's.  The program binds their literals that are arguments
    (``_all_exprs``) and its key holds those by type
    (``_exec_signature_shallow``)."""
    from spark_rapids_tpu.plan.execs.basic import (
        TpuFilterExec, TpuProjectExec)
    if isinstance(node, TpuProjectExec):
        return list(node.exprs)
    if isinstance(node, TpuFilterExec):
        return [node.condition]
    return []


def _exec_signature_shallow(node) -> str:
    """Signature of ONE node (class + schema + expression attrs), without
    recursing into children — segment identity is the chain of node
    signatures; the stream input's shapes are carried by jit retracing.
    The literals the program binds are keyed by type, so one program
    serves every value."""
    from spark_rapids_tpu.parallel.stage import _exec_signature
    from spark_rapids_tpu.plan.execs.base import bound_literals
    saved = node.children
    try:
        node.children = ()
        return _exec_signature(node, bound_literals(_bound_exprs(node)))
    finally:
        node.children = saved


def _max_live_bytes(batch: ColumnarBatch) -> int:
    from spark_rapids_tpu.kernels.strings import max_live_bytes_multi
    return max_live_bytes_multi((c, batch.num_rows) for c in batch.columns)
