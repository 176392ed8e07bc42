"""Plan rewrite: logical plan -> TPU physical plan with tagging + fallback.

The reference's architecture reproduced: a meta tree wraps every plan node
and expression (RapidsMeta.scala:648/:1112), tagging collects can't-run
reasons (willNotWorkOnGpu, RapidsMeta.scala:324), explain prints per-node
"will/won't run" lines (GpuOverrides.scala:5138-5147), and conversion emits
the TPU exec tree (convertToGpu).  Unsupported subtrees fall back to the CPU
oracle engine with an upload boundary — the analog of leaving Catalyst nodes
on CPU with row/columnar transitions inserted (GpuTransitionOverrides).

Two-phase aggregates and exchanges are planned here the way Spark+reference
plan them: partial agg -> hash exchange on keys -> final agg; global sort
gets a single-partition exchange below it (range partitioning is the
follow-on).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.expressions import core as E
from spark_rapids_tpu.expressions import aggregates as A
from spark_rapids_tpu.expressions.arithmetic import (
    Abs, Add, Divide, IntegralDivide, Multiply, Remainder, Subtract, UnaryMinus)
from spark_rapids_tpu.expressions.casts import Cast
from spark_rapids_tpu.expressions.conditional import CaseWhen, If
from spark_rapids_tpu.expressions.predicates import (
    And, Coalesce, EqualNullSafe, EqualTo, GreaterThan, GreaterThanOrEqual,
    In, IsNotNull, IsNull, LessThan, LessThanOrEqual, Not, Or)
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.execs.base import TpuExec
from spark_rapids_tpu.plan.execs.basic import (
    TpuFilterExec, TpuProjectExec, TpuUnionExec)
from spark_rapids_tpu.plan.execs.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.plan.execs.exchange import (
    TpuShuffleExchangeExec, TpuSinglePartitionExec)
from spark_rapids_tpu.plan.execs.scan import (
    TpuInMemoryScanExec, TpuParquetScanExec)
from spark_rapids_tpu.plan.execs.sort import TpuLimitExec, TpuSortExec

from spark_rapids_tpu.expressions.strings import (
    Ascii, ConcatStrings, ConcatWs, Contains, EndsWith, InitCap, Length,
    Like, Lower, Lpad, LTrim, RLike, RTrim, Reverse, Rpad, StartsWith,
    StringInstr, StringLocate, StringRepeat, StringReplace, Substring,
    Trim, Upper)

# expression classes with device twins; the TypeSig-style dtype gate is
# checked separately (supported_dtype)
_SUPPORTED_EXPRS = {
    E.Alias, E.BoundReference, E.Literal,
    Add, Subtract, Multiply, Divide, IntegralDivide, Remainder, UnaryMinus, Abs,
    And, Or, Not, IsNull, IsNotNull, In, Coalesce,
    EqualTo, EqualNullSafe, LessThan, LessThanOrEqual, GreaterThan,
    GreaterThanOrEqual,
    If, CaseWhen, Cast,
    A.Sum, A.Count, A.Min, A.Max, A.Average,
    A.VarianceSamp, A.VariancePop, A.StddevSamp, A.StddevPop,
    A.ApproximateCountDistinct,
    Length, Upper, Lower, Substring, ConcatStrings, Trim, LTrim, RTrim,
    StartsWith, EndsWith, Contains, Like, RLike, Reverse, InitCap,
    StringReplace, StringLocate, StringInstr, Ascii, StringRepeat,
    Lpad, Rpad, ConcatWs,
}

# string producers that never grow byte lengths: safe under a regex/DFA
# node whose string bucket is derived from the batch's source columns
_NON_GROWING_STRING_EXPRS = {
    E.Alias, E.BoundReference, E.Literal, Upper, Lower, Trim, Substring,
    If, CaseWhen, Coalesce,
}


def _regex_child_ok(e) -> bool:
    """Only STRING-typed subtrees feed bytes into a regex/byte-window
    kernel, so only they must be non-growing; non-string children (an If
    predicate, a substring position) are unconstrained."""
    try:
        dt = e.dtype
    except (TypeError, ValueError, NotImplementedError):
        return False
    if not getattr(dt, "variable_width", False):
        return True
    if type(e) not in _NON_GROWING_STRING_EXPRS:
        return False
    return all(_regex_child_ok(c) for c in e.children)

from spark_rapids_tpu.expressions.window import (
    CumeDist, DenseRank, FirstValue, Lag, LastValue, Lead, NthValue, Ntile,
    PercentRank, Rank, RowNumber, WindowExpression)

_SUPPORTED_EXPRS |= {WindowExpression, RowNumber, Rank, DenseRank, Lead, Lag,
                     PercentRank, CumeDist, Ntile, FirstValue, LastValue,
                     NthValue}

from spark_rapids_tpu.expressions import math as M
from spark_rapids_tpu.expressions import datetime as DT

_SUPPORTED_EXPRS |= {
    M.Sqrt, M.Cbrt, M.Exp, M.Sin, M.Cos, M.Tan, M.Atan, M.Signum,
    M.Log, M.Log10, M.Pow, M.Floor, M.Ceil, M.Round, M.IsNaN, M.NanVl,
    M.Asin, M.Acos, M.Sinh, M.Cosh, M.Tanh, M.Asinh, M.Acosh, M.Atanh,
    M.Log2, M.Log1p, M.Expm1, M.Rint, M.Degrees, M.Radians, M.Cot,
    M.Sec, M.Csc, M.Atan2, M.Hypot, M.Pmod, M.Factorial, M.LogBase,
    DT.Year, DT.Month, DT.DayOfMonth, DT.DayOfWeek, DT.DayOfYear,
    DT.Quarter, DT.Hour, DT.Minute, DT.Second, DT.DateAdd, DT.DateSub,
    DT.DateDiff, DT.AddMonths, DT.LastDay,
    DT.WeekOfYear, DT.MakeDate, DT.TruncDate, DT.NextDay, DT.MonthsBetween,
    DT.UnixSeconds, DT.UnixMillis, DT.UnixMicros, DT.SecondsToTimestamp,
    DT.MillisToTimestamp, DT.MicrosToTimestamp, DT.UnixDate,
    DT.DateFromUnixDate, DT.FromUtcTimestamp, DT.ToUtcTimestamp,
}

from spark_rapids_tpu.expressions.bitwise import (
    BitwiseAnd, BitwiseNot, BitwiseOr, BitwiseXor, ShiftLeft, ShiftRight,
    ShiftRightUnsigned)
from spark_rapids_tpu.expressions.conditional import (
    Greatest, Least, NullIf, Nvl2)
from spark_rapids_tpu.expressions.strings import (
    BitLength, Concat, Empty2Null, Left, OctetLength, Right, Translate)

_SUPPORTED_EXPRS |= {
    BitwiseAnd, BitwiseOr, BitwiseXor, BitwiseNot, ShiftLeft, ShiftRight,
    ShiftRightUnsigned,
    NullIf, Nvl2, Greatest, Least,
    Left, Right, OctetLength, BitLength, Translate, Empty2Null, Concat,
    A.BoolAnd, A.BoolOr,
}

from spark_rapids_tpu.expressions.collections import (
    ArrayContains, ArrayDistinct, ArrayExists, ArrayFilter, ArrayForAll,
    ArrayMax, ArrayMin, ArrayPosition, ArrayRemove, ArrayRepeat,
    ArraysZip, ArrayTransform, CreateArray, ElementAt, Explode, Flatten,
    GetArrayItem, MapEntries, NamedLambdaVariable, PosExplode, Size, Slice,
    SortArray, _HigherOrder)

_SUPPORTED_EXPRS |= {
    Size, ArrayContains, ArrayPosition, GetArrayItem, ElementAt,
    ArrayMin, ArrayMax, SortArray, ArrayDistinct, ArrayRemove, Slice,
    CreateArray, ArrayRepeat,
    ArrayTransform, ArrayFilter, ArrayExists, ArrayForAll,
    NamedLambdaVariable, Explode, PosExplode,
    MapEntries, Flatten, ArraysZip,
}

from spark_rapids_tpu.expressions.structs import (
    CreateMap, CreateNamedStruct, GetMapValue, GetStructField, MapKeys,
    MapValues)

_SUPPORTED_EXPRS |= {
    CreateNamedStruct, GetStructField, CreateMap, GetMapValue, MapKeys,
    MapValues,
}

from spark_rapids_tpu.expressions.map_hof import (
    MapFilter, TransformKeys, TransformValues, ZipWith, _MapHigherOrder)

# MapZipWith stays out: it evaluates through the CPU bridge
_SUPPORTED_EXPRS |= {TransformValues, TransformKeys, MapFilter, ZipWith}

from spark_rapids_tpu.expressions.zorder import RangeBucketId, ZOrderKey

_SUPPORTED_EXPRS |= {RangeBucketId, ZOrderKey}

from spark_rapids_tpu.expressions.parity import (
    BitwiseCount, BRound, UnaryPositive, WeekDay)

# the parity module's bridge-only expressions stay unregistered (they
# resolve to the CPU bridge); these four have device kernels
_SUPPORTED_EXPRS |= {UnaryPositive, WeekDay, BRound, BitwiseCount}

from spark_rapids_tpu.expressions.hashing import (
    BloomFilterMightContain, Murmur3Hash, XxHash64)
from spark_rapids_tpu.expressions.strings import GetJsonObject

from spark_rapids_tpu.expressions.hashing import HiveHash

_SUPPORTED_EXPRS |= {Murmur3Hash, XxHash64, BloomFilterMightContain,
                     GetJsonObject, HiveHash, A.Percentile,
                     A.ApproxPercentile, A.CollectList, A.CollectSet,
                     A.First, A.Last, A.MaxBy, A.MinBy,
                     A.BitAndAgg, A.BitOrAgg, A.BitXorAgg}

# dtypes device kernels support in expression compute
_COMPUTE_OK = (T.BooleanType, T.ByteType, T.ShortType, T.IntegerType,
               T.LongType, T.FloatType, T.DoubleType, T.DateType,
               T.TimestampType, T.NullType, T.StringType)


def _dtype_ok(dt: T.DataType) -> bool:
    if isinstance(dt, T.DecimalType):
        # Decimal64 fast path (long-backed) and two-limb Decimal128 (limb
        # planes ride the struct machinery; kernels/decimal.py)
        return True
    if isinstance(dt, T.ArrayType):
        # array<fixed-width> uses the segmented string layout; nested
        # arrays / array<string> need child-offset stacking (follow-on)
        et = dt.element_type
        return (et is not None and not et.variable_width
                and not isinstance(et, (T.ArrayType, T.StructType, T.MapType))
                and _dtype_ok(et))
    if isinstance(dt, T.StructType):
        return all(_dtype_ok(f.dtype) for f in dt.fields)
    if isinstance(dt, T.MapType):
        # map layout: primitive or STRING keys/values (string children get
        # their own offsets plane; nested containers inside maps are the
        # remaining follow-on)
        def _entry_ok(et):
            return (et is not None and _dtype_ok(et)
                    and not isinstance(et, (T.ArrayType, T.StructType,
                                            T.MapType)))
        return _entry_ok(dt.key_type) and _entry_ok(dt.value_type)
    return isinstance(dt, _COMPUTE_OK)


def _key_dtype_ok(dt: T.DataType) -> bool:
    return _dtype_ok(dt) and not dt.variable_width


def _struct_key_ok(dt: T.StructType) -> bool:
    """struct sort/group/join keys: every leaf fixed-width (string fields
    would need per-field byte buckets threaded through the kernels)."""
    for f in dt.fields:
        if isinstance(f.dtype, T.StructType):
            if not _struct_key_ok(f.dtype):
                return False
        elif f.dtype.variable_width or isinstance(
                f.dtype, (T.ArrayType, T.MapType)):
            return False
        elif not _dtype_ok(f.dtype):
            return False
    return True


def _key_expr_ok(e: "E.Expression") -> bool:
    """Sort/group/partition/join key gate: any fixed-width expression, or a
    *plain column reference* for strings (the execs compute the max-bytes
    bucket from the referenced column before the jitted kernel runs; a
    computed string key has no pre-computable bucket yet)."""
    try:
        dt = e.dtype
    except (TypeError, ValueError, NotImplementedError):
        return False
    if not _dtype_ok(dt):
        return False
    if isinstance(dt, T.ArrayType):
        # arrays have no sort/hash key encoding yet (row-equality over
        # nested data needs child-aware comparators; reference gates this
        # per-op in TypeSig too)
        return False
    if isinstance(dt, T.MapType):
        return False       # maps are unorderable in Spark too
    if isinstance(dt, T.StructType):
        return _struct_key_ok(dt)
    if dt.variable_width:
        while isinstance(e, E.Alias):
            e = e.child
        return isinstance(e, E.BoundReference)
    return True


class ExprMeta:
    """BaseExprMeta analog: tags one expression node.

    ``allow_bridge``: in project/filter positions an unsupported subtree
    may run through the expression-level CPU bridge instead of failing the
    whole node (GpuCpuBridgeExpression.scala analog, gated by
    spark.rapids.sql.expression.cpuBridge.enabled).
    """

    def __init__(self, expr: E.Expression, conf: Optional[RapidsConf] = None,
                 allow_bridge: bool = False):
        self.expr = expr
        self.conf = conf
        self.allow_bridge = allow_bridge
        self.children = [ExprMeta(c, conf, allow_bridge)
                         for c in expr.children]
        self.reasons: List[str] = []
        self.bridged = False

    def will_not_work(self, reason: str) -> None:
        self.reasons.append(reason)

    def _bridgeable(self) -> bool:
        if not (self.allow_bridge and self.conf is not None
                and self.conf.cpu_bridge_enabled):
            return False
        # every node of the subtree must be host-evaluable (e.g. a regex
        # pattern must compile under the CPU oracle's engine)
        def host_ok(e) -> bool:
            ce = getattr(e, "cpu_evaluable", None)
            if ce is not None and not ce():
                return False
            return all(host_ok(c) for c in e.children)
        if not host_ok(self.expr):
            return False
        from spark_rapids_tpu.expressions.aggregates import find_aggregates
        from spark_rapids_tpu.expressions.window import WindowExpression

        def structural(e) -> bool:
            if isinstance(e, WindowExpression):
                return True
            return any(structural(c) for c in e.children)
        if find_aggregates(self.expr) or structural(self.expr):
            return False
        try:
            return _dtype_ok(self.expr.dtype)
        except (TypeError, ValueError, NotImplementedError):
            return False

    def resolve_bridges(self) -> bool:
        """Bottom-up: bridge the smallest failing subtrees; returns whether
        this subtree can run (natively or via bridge)."""
        children_ok = all(c.resolve_bridges() for c in self.children)
        if not self.reasons and children_ok:
            return True
        if self._bridgeable():
            self.bridged = True
            return True
        return False

    def transformed(self) -> E.Expression:
        """The expression with bridge wrappers applied."""
        if self.bridged:
            from spark_rapids_tpu.expressions.bridge import (
                CpuBridgeExpression)
            return CpuBridgeExpression(self.expr)
        if not self.children:
            return self.expr
        new_children = tuple(c.transformed() for c in self.children)
        if all(n is o for n, o in zip(new_children, self.expr.children)):
            return self.expr
        return self.expr.with_children(new_children)

    def tag(self) -> None:
        from spark_rapids_tpu.planner.typesig import check_expr, sig_for
        e = self.expr
        if type(e) not in _SUPPORTED_EXPRS:
            self.will_not_work(f"expression {type(e).__name__} is not supported")
        else:
            # per-op type signature (TypeChecks analog), falling back to
            # the blanket device-dtype gate for unregistered ops
            sig_reason = check_expr(e)
            if sig_reason is not None:
                self.will_not_work(sig_reason)
            elif sig_for(type(e)) is None:
                try:
                    if not _dtype_ok(e.dtype):
                        self.will_not_work(
                            f"produces unsupported type {e.dtype!r}")
                except (TypeError, ValueError, NotImplementedError):
                    pass
            if isinstance(e, Cast) and not Cast.supported(e.child.dtype, e.dtype):
                self.will_not_work(
                    f"cast {e.child.dtype!r} -> {e.dtype!r} is not supported")
            if isinstance(e, Cast) and getattr(
                    e, "uses_string_bucket", False) and \
                    not _regex_child_ok(e.child):
                self.will_not_work(
                    f"string cast over {e.child!r}: only non-growing "
                    "string inputs supported (project it first)")
            if isinstance(e, (StartsWith, EndsWith, Contains)) and \
                    not isinstance(e.right, E.Literal):
                self.will_not_work(
                    "non-literal match patterns are not supported yet")
            if isinstance(e, BRound) and \
                    not isinstance(e.right, E.Literal):
                self.will_not_work(
                    "bround scale must be a literal")
            if isinstance(e, (NullIf, Greatest, Least)):
                try:
                    if e.children[0].dtype.variable_width:
                        self.will_not_work(
                            f"{type(e).__name__} over strings needs the "
                            "byte-comparator kernel (CPU bridge covers it)")
                except (TypeError, ValueError, NotImplementedError):
                    pass
            if isinstance(e, ConcatWs):
                for c in e.children:
                    try:
                        if not isinstance(c.dtype, T.StringType):
                            self.will_not_work(
                                f"concat_ws over non-string {c!r}")
                    except (TypeError, ValueError, NotImplementedError):
                        pass
            if isinstance(e, StringRepeat) and e.n > 64:
                self.will_not_work(
                    f"repeat({e.n}) exceeds the static growth bound")
            if isinstance(e, (Lpad, Rpad)):
                if e.length > 1 << 16:
                    self.will_not_work("pad length exceeds the static bound")
                if any(ord(ch) > 0x7F for ch in e.pad):
                    self.will_not_work(
                        "non-ASCII pad strings pad by bytes on device "
                        "(character padding needs the multi-byte kernel)")
            if isinstance(e, StringReplace) and not _regex_child_ok(
                    e.children[0]):
                self.will_not_work(
                    f"replace over {e.children[0]!r}: only non-growing "
                    "string inputs supported (project it first)")
            if isinstance(e, (Like, RLike)) and getattr(
                    e, "uses_string_bucket", False):
                from spark_rapids_tpu.regex import RegexUnsupported
                try:
                    e._compiled()
                except RegexUnsupported as ex:
                    self.will_not_work(
                        f"pattern {e.pattern!r} outside the supported "
                        f"regex dialect: {ex}")
                if not _regex_child_ok(e.children[0]):
                    self.will_not_work(
                        f"regex over {e.children[0]!r}: only non-growing "
                        "string inputs supported (project it first)")
            if isinstance(e, GetJsonObject):
                if not e.device_supported_path():
                    self.will_not_work(
                        f"JSON path {e.path!r}: device scanner handles "
                        "dotted object fields only (CPU bridge covers "
                        "array indexing)")
                elif not _regex_child_ok(e.child):
                    self.will_not_work(
                        f"get_json_object over {e.child!r}: only "
                        "non-growing string inputs supported")
            if isinstance(e, BloomFilterMightContain):
                try:
                    if not isinstance(e.child.dtype, T.LongType):
                        self.will_not_work(
                            "might_contain probes LONG values (Spark "
                            "BloomFilterImpl putLong semantics)")
                except (TypeError, ValueError, NotImplementedError):
                    pass
            if isinstance(e, (Murmur3Hash, XxHash64, HiveHash)):
                # USER-VISIBLE hash values must equal Apache Spark's.
                # TPU has no raw IEEE double bits (f64 is emulated), so
                # double inputs hash via the split-pack stand-in there —
                # self-consistent for internal partitioning but NOT
                # doubleToLongBits; route such expressions to the CPU
                # bridge instead of silently diverging.
                import jax as _jax
                if _jax.default_backend() == "tpu":
                    for c in e.children:
                        try:
                            if isinstance(c.dtype, T.DoubleType):
                                self.will_not_work(
                                    f"{type(e).__name__} over double "
                                    f"input {c!r}: no raw float64 bits "
                                    "on TPU (doubleToLongBits parity "
                                    "needs the CPU bridge)")
                                break
                        except (TypeError, ValueError,
                                NotImplementedError):
                            pass
            if isinstance(e, (Murmur3Hash, XxHash64)):
                for c in e.children:
                    try:
                        cd = c.dtype
                        if isinstance(cd, (T.ArrayType, T.StructType,
                                           T.MapType, T.BinaryType)):
                            self.will_not_work(
                                f"{type(e).__name__} over nested/binary "
                                f"input {c!r} not supported")
                        elif cd.variable_width and not _regex_child_ok(c):
                            self.will_not_work(
                                f"{type(e).__name__} string input {c!r} "
                                "must be non-growing (project it first)")
                    except (TypeError, ValueError, NotImplementedError):
                        pass
            if isinstance(e, (ArrayContains, ArrayPosition, ArrayRemove)):
                try:
                    if e.right.dtype.variable_width:
                        self.will_not_work(
                            f"{type(e).__name__} needle must be fixed-width")
                except (TypeError, ValueError, NotImplementedError):
                    pass
            if isinstance(e, SortArray) and not isinstance(
                    e.right, E.Literal):
                self.will_not_work("sort_array direction must be a literal")
            if isinstance(e, ArrayRepeat):
                if not isinstance(e.right, E.Literal):
                    self.will_not_work(
                        "array_repeat count must be a literal (static "
                        "element bound)")
                elif e.right.value is not None and int(e.right.value) > 1 << 16:
                    self.will_not_work(
                        "array_repeat count exceeds the static bound")
            if isinstance(e, (ArrayMin, ArrayMax)):
                try:
                    et = e.child.dtype.element_type
                    if isinstance(et, T.BooleanType):
                        self.will_not_work(
                            f"{type(e).__name__} over boolean elements")
                except (TypeError, ValueError, NotImplementedError,
                        AttributeError):
                    pass
            if isinstance(e, CreateArray):
                try:
                    if len({repr(c.dtype) for c in e.children}) > 1:
                        self.will_not_work(
                            "array() elements must share one type "
                            "(add explicit casts)")
                except (TypeError, ValueError, NotImplementedError):
                    pass
            if isinstance(e, (_HigherOrder, _MapHigherOrder, ZipWith)):
                body = e.right if isinstance(e, _HigherOrder) \
                    else e.children[-1]

                def _body_bad(x) -> Optional[str]:
                    if isinstance(x, (_HigherOrder, _MapHigherOrder,
                                      ZipWith)):
                        return "nested higher-order functions"
                    if isinstance(x, E.BoundReference):
                        dt = x.dtype
                        if dt.variable_width:
                            return (f"lambda body references variable-width "
                                    f"outer column {x!r}")
                        # nested/two-limb columns carry children planes the
                        # element-level gather does not thread through
                        if isinstance(dt, (T.StructType, T.MapType,
                                           T.ArrayType)) or (
                                isinstance(dt, T.DecimalType)
                                and dt.uses_two_limbs):
                            return (f"lambda body references nested outer "
                                    f"column {x!r}")
                    for c in x.children:
                        r = _body_bad(c)
                        if r:
                            return r
                    return None
                bad = _body_bad(body)
                if bad:
                    self.will_not_work(f"{bad} not supported on device")
        for c in self.children:
            c.tag()

    @property
    def can_run(self) -> bool:
        if self.bridged:
            return True
        return not self.reasons and all(c.can_run for c in self.children)

    def explain_lines(self, prefix: str = "") -> List[str]:
        out = []
        if self.bridged:
            why = "; ".join(self.reasons + [r for c in self.children
                                            for r in c.reasons])
            out.append(f"{prefix}*Expression {self.expr!r} will run via "
                       f"the CPU bridge ({why})")
            return out
        for r in self.reasons:
            out.append(f"{prefix}!Expression {self.expr!r} cannot run on TPU "
                       f"because {r}")
        for c in self.children:
            out.extend(c.explain_lines(prefix))
        return out


class PlanMeta:
    """SparkPlanMeta analog: tags one plan node and its expressions."""

    def __init__(self, plan: L.LogicalPlan, conf: RapidsConf):
        self.plan = plan
        self.conf = conf
        self.children = [PlanMeta(c, conf) for c in plan.children]
        self.reasons: List[str] = []
        allow_bridge = isinstance(plan, (L.Project, L.Filter, L.Generate))
        self.expr_metas: List[ExprMeta] = [
            ExprMeta(e, conf, allow_bridge) for e in self._expressions()]

    def _expressions(self) -> List[E.Expression]:
        p = self.plan
        if isinstance(p, L.Window):
            return [e for e in p.window_exprs]
        if isinstance(p, L.Project):
            return list(p.exprs)
        if isinstance(p, L.Filter):
            return [p.condition]
        if isinstance(p, L.Generate):
            return [p.generator]
        if isinstance(p, L.Expand):
            return [e for proj in p.projections for e in proj]
        if isinstance(p, L.Aggregate):
            return list(p.group_exprs) + list(p.agg_exprs)
        if isinstance(p, L.Sort):
            return [e for e, _ in p.orders]
        if isinstance(p, L.Repartition):
            return list(p.keys)
        if isinstance(p, L.Join):
            out = list(p.left_keys) + list(p.right_keys)
            if p.condition is not None:
                out.append(p.condition)
            return out
        return []

    def will_not_work(self, reason: str) -> None:
        self.reasons.append(reason)

    def tag(self) -> None:
        p = self.plan
        for em in self.expr_metas:
            em.tag()
            em.resolve_bridges()
        if not isinstance(p, (L.Project, L.Filter)):
            # regex/DFA expressions need the string bucket threading that
            # only the project/filter execs implement
            from spark_rapids_tpu.plan.execs.base import (
                tree_uses_string_bucket)
            for e in self._expressions():
                if tree_uses_string_bucket([e]):
                    self.will_not_work(
                        f"regex expression {e!r} only supported in "
                        "project/filter (move it there)")
        if isinstance(p, L.Join):
            for e in list(p.left_keys) + list(p.right_keys):
                if not _key_expr_ok(e):
                    self.will_not_work(
                        f"join key {e!r} not supported yet")
                if not isinstance(e, E.BoundReference):
                    self.will_not_work(
                        f"computed join key {e!r} not supported yet "
                        "(project it first)")
            for lk, rk in zip(p.left_keys, p.right_keys):
                try:
                    if not (lk.dtype == rk.dtype):
                        # mixed-type keys hash-partition differently on the
                        # two sides; Spark inserts casts at analysis — our
                        # frontend should too (follow-on), fall back for now
                        self.will_not_work(
                            f"join key types differ: {lk.dtype!r} vs "
                            f"{rk.dtype!r} (add explicit casts)")
                except (TypeError, ValueError, NotImplementedError):
                    pass
            if not p.left_keys and p.join_type not in ("cross",) \
                    and p.condition is None and p.join_type != "existence":
                self.will_not_work(
                    f"keyless {p.join_type} join without a condition "
                    "(use cross join)")
            # nested payloads AND nested condition inputs are fine: the
            # pair gather and the output gather both carry per-plane byte
            # capacities through the join's capacity-retry loop
            # (kernels/selection.py byte_caps; _pair_string_cols)
        if isinstance(p, L.Aggregate):
            for e in p.group_exprs:
                if not _key_expr_ok(e):
                    self.will_not_work(
                        f"grouping key {e!r} not supported yet")
            for e in p.agg_exprs:
                for sub in _non_agg_leaf_refs(e):
                    self.will_not_work(
                        f"non-aggregate column {sub!r} in aggregate output")
            from spark_rapids_tpu.expressions.aggregates import (
                ApproximateCountDistinct, find_aggregates)
            for e in p.agg_exprs:
                for agg in find_aggregates(e):
                    if not isinstance(agg, ApproximateCountDistinct):
                        continue
                    try:
                        dt = agg.input.dtype
                        ok = (dt.is_integral or isinstance(
                            dt, (T.DateType, T.TimestampType, T.BooleanType)))
                    except (TypeError, ValueError, NotImplementedError):
                        ok = False
                    if not ok:
                        self.will_not_work(
                            f"approx_count_distinct over {agg.input!r}: "
                            "device HLL hashes long-representable values "
                            "(strings/floats fall back)")
                    elif p.group_exprs and (
                            self.conf.batch_size_rows * agg.m > (1 << 26)):
                        self.will_not_work(
                            "grouped approx_count_distinct needs "
                            "batchSizeRows * 2^p <= 64M register slots "
                            f"(have {self.conf.batch_size_rows} * {agg.m}); "
                            "lower spark.rapids.sql.batchSizeBytes/rows")
            for e in p.agg_exprs:
                for agg in find_aggregates(e):
                    # ORDER-compared string inputs (min/max over strings,
                    # max_by/min_by string ordering keys) reduce over the
                    # rank surrogate whose max-bytes bucket is computed
                    # from the referenced column BEFORE the jitted kernel
                    # runs — so like string group keys they must be plain
                    # column refs (the _key_expr_ok contract)
                    ordered = []
                    if isinstance(agg, (A.Min, A.Max)):
                        ordered = [agg.children[0]]
                    elif isinstance(agg, (A.MaxBy, A.MinBy)):
                        ordered = [agg.children[1]]
                    for oe in ordered:
                        try:
                            var = oe.dtype.variable_width
                        except (TypeError, ValueError,
                                NotImplementedError):
                            var = False
                        inner = oe
                        while isinstance(inner, E.Alias):
                            inner = inner.child
                        if var and not isinstance(inner, E.BoundReference):
                            self.will_not_work(
                                f"{agg.name} string ordering input {oe!r} "
                                "must be a plain column reference "
                                "(project it first)")
            if not self.conf.variable_float_agg_enabled:
                for e in p.agg_exprs:
                    for agg in find_aggregates(e):
                        try:
                            fl = (agg.input is not None
                                  and agg.input.dtype.is_floating)
                        except (TypeError, ValueError, NotImplementedError):
                            fl = False
                        if fl and isinstance(agg, (A.Sum, A.Average)):
                            self.will_not_work(
                                f"{agg!r} over floats disabled: device "
                                "two-phase ordering varies (spark.rapids."
                                "sql.variableFloatAgg.enabled=false)")
        if isinstance(p, L.Sort):
            for e, _ in p.orders:
                if not _key_expr_ok(e):
                    self.will_not_work(
                        f"sort key {e!r} not supported yet")
        if isinstance(p, L.Repartition):
            for e in p.keys:
                if not _key_expr_ok(e):
                    self.will_not_work(
                        f"partition key {e!r} not supported yet")
        if isinstance(p, L.Window):
            self._tag_window(p)
        for c in self.children:
            c.tag()

    @property
    def this_can_run(self) -> bool:
        return not self.reasons and all(em.can_run for em in self.expr_metas)

    @property
    def can_run(self) -> bool:
        return self.this_can_run and all(c.can_run for c in self.children)

    def explain(self, indent: int = 0) -> str:
        pad = "  " * indent
        mark = "*" if self.this_can_run else "!"
        lines = [f"{pad}{mark}Exec <{self.plan.node_name()}> "
                 f"{'will' if self.this_can_run else 'will NOT'} run on TPU"]
        for r in self.reasons:
            lines.append(f"{pad}  @reason: {r}")
        for em in self.expr_metas:
            lines.extend(em.explain_lines(pad + "  "))
        for c in self.children:
            lines.append(c.explain(indent + 1))
        return "\n".join(lines)

    # -- conversion ---------------------------------------------------------

    def convert(self) -> TpuExec:
        """Emit the physical plan: TPU execs where possible, CPU-fallback
        islands elsewhere."""
        if not self.this_can_run:
            return self._fallback()
        p = self.plan
        if isinstance(p, L.InMemoryRelation):
            return TpuInMemoryScanExec(p.partitions, p.schema)
        if isinstance(p, L.CachedParquetRelation):
            from spark_rapids_tpu.plan.execs.scan import (
                TpuCachedParquetScanExec)
            return TpuCachedParquetScanExec(p.partitions, p.schema,
                                            projection=p.projection)
        # reader-facing row cap: spark.rapids.sql.reader.batchSizeRows can
        # shrink scan batches below the pipeline-wide batchSizeRows without
        # widening them (min(), so neither knob is silently ignored)
        scan_rows = min(self.conf.batch_size_rows,
                        self.conf.reader_batch_size_rows)
        if isinstance(p, L.ParquetRelation):
            return TpuParquetScanExec(
                p.paths, p.schema, p.column_pruning,
                scan_rows,
                reader_threads=self.conf.multithreaded_read_threads,
                conf=self.conf)
        if isinstance(p, L.FileRelation):
            from spark_rapids_tpu.plan.execs.scan import TpuFileScanExec
            return TpuFileScanExec(
                p.paths, p.fmt, p.schema, p.column_pruning, p.options,
                scan_rows,
                reader_threads=self.conf.multithreaded_read_threads)
        if isinstance(p, L.DeltaRelation):
            from spark_rapids_tpu.io.delta_scan import TpuDeltaScanExec
            return TpuDeltaScanExec(p.table_path, p.snapshot, p.schema)
        if isinstance(p, L.IcebergRelation):
            if p.deletes:
                from spark_rapids_tpu.io.iceberg_scan import (
                    TpuIcebergMorScanExec)
                return TpuIcebergMorScanExec(p, p.schema)
            return TpuParquetScanExec(
                [df["file_path"] for df in p.files], p.schema,
                p.projection, scan_rows,
                reader_threads=self.conf.multithreaded_read_threads,
                conf=self.conf)
        if isinstance(p, L.Project):
            child = self.children[0].convert()
            exprs = [em.transformed() for em in self.expr_metas]
            return TpuProjectExec(exprs, child, p.schema)
        if isinstance(p, L.Filter):
            cond = self.expr_metas[0].transformed()
            return TpuFilterExec(cond, self.children[0].convert())
        if isinstance(p, L.Generate):
            from spark_rapids_tpu.plan.execs.generate import TpuGenerateExec
            gen = self.expr_metas[0].transformed()
            return TpuGenerateExec(gen, p.outer, self.children[0].convert(),
                                   p.schema)
        if isinstance(p, L.Expand):
            from spark_rapids_tpu.plan.execs.misc import TpuExpandExec
            k = len(p.projections[0])
            transformed = [em.transformed() for em in self.expr_metas]
            projs = [transformed[i * k:(i + 1) * k]
                     for i in range(len(p.projections))]
            return TpuExpandExec(projs, self.children[0].convert(), p.schema)
        if isinstance(p, L.Range):
            from spark_rapids_tpu.plan.execs.misc import TpuRangeExec
            return TpuRangeExec(p.start, p.end, p.step, p.num_partitions,
                                p.schema, self.conf.batch_size_rows)
        if isinstance(p, L.Sample):
            from spark_rapids_tpu.plan.execs.misc import TpuSampleExec
            return TpuSampleExec(p.fraction, p.seed,
                                 self.children[0].convert())
        if isinstance(p, L.Union):
            return TpuUnionExec(tuple(c.convert() for c in self.children),
                                p.schema)
        if isinstance(p, L.Limit):
            return TpuLimitExec(p.n, self.children[0].convert())
        if isinstance(p, L.Repartition):
            return self._exchange(p.num_partitions, p.keys,
                                  self.children[0].convert())
        if isinstance(p, L.Sort):
            child = self.children[0].convert()
            if p.global_sort and _plan_partitions(child) > 1:
                from spark_rapids_tpu.plan.execs.range_sort import (
                    TpuRangeSortExec)
                return TpuRangeSortExec(
                    p.orders, child,
                    min(self.conf.shuffle_partitions,
                        _plan_partitions(child)),
                    small_sort_rows=self.conf.batch_size_rows)
            return TpuSortExec(p.orders, child,
                               target_rows=self.conf.batch_size_rows)
        if isinstance(p, L.Aggregate):
            return self._convert_aggregate(p)
        if isinstance(p, L.Join):
            return self._convert_join(p)
        if isinstance(p, L.Window):
            return self._convert_window(p)
        if isinstance(p, L.MapBatches):
            from spark_rapids_tpu.plan.execs.python_exec import (
                TpuMapBatchesExec)
            wconf = ((self.conf.python_worker_count,
                      self.conf.python_worker_mem)
                     if self.conf.python_worker_enabled else None)
            return TpuMapBatchesExec(p.fn, self.children[0].convert(),
                                     p.schema,
                                     whole_partition=p.whole_partition,
                                     worker_conf=wconf)
        return self._fallback()

    def _tag_window(self, p: "L.Window") -> None:
        from spark_rapids_tpu.expressions.window import (
            CumeDist, DenseRank, FirstValue, Lag, LastValue, Lead, NthValue,
            Ntile, PercentRank, Rank, RowNumber, WindowExpression)
        from spark_rapids_tpu.expressions.aggregates import (
            Average, Count, Max, Min, Sum)
        spec = p.spec
        for e in spec.partition_by:
            if not _key_expr_ok(e):
                self.will_not_work(
                    f"window partition key {e!r} not supported yet")
        for e, _ in spec.order_by:
            if not _key_expr_ok(e):
                self.will_not_work(
                    f"window order key {e!r} not supported yet")
        for e in p.window_exprs:
            inner = e.child if isinstance(e, E.Alias) else e
            if not isinstance(inner, WindowExpression):
                self.will_not_work(
                    f"window output {e!r} must be a window expression")
                continue
            if repr(inner.spec) != repr(spec):
                self.will_not_work(
                    "mixed window specs in one Window node")
            fn = inner.function
            frame = inner.spec.frame
            if isinstance(fn, (RowNumber, Rank, DenseRank, Lead, Lag,
                               PercentRank, CumeDist, Ntile)):
                continue
            if isinstance(fn, (FirstValue, LastValue, NthValue)):
                try:
                    if fn.child.dtype.variable_width:
                        self.will_not_work(
                            f"{fn.name} over strings needs offset-aware "
                            "frame gathers (fixed-width inputs only)")
                except (TypeError, ValueError, NotImplementedError):
                    pass
                frame = inner.spec.frame
                if frame.kind == "range" and not (
                        frame.is_unbounded_to_current()
                        or frame.is_unbounded_both()):
                    ob = inner.spec.order_by
                    ok = (len(ob) == 1 and ob[0][1].ascending)
                    if not ok:
                        self.will_not_work(
                            f"{fn.name} bounded range frame needs a single "
                            "ascending order key")
                continue
            if isinstance(fn, (Sum, Count, Average, Min, Max)):
                if frame.kind == "range" and not (
                        frame.is_unbounded_to_current()
                        or frame.is_unbounded_both()):
                    # bounded RANGE: binary search over the single order
                    # value (kernels/window.py frame_bounds_range) — needs
                    # one ascending fixed-width non-float key
                    ob = inner.spec.order_by
                    ok = (len(ob) == 1 and ob[0][1].ascending)
                    if ok:
                        try:
                            dt = ob[0][0].dtype
                            ok = (not dt.variable_width
                                  and not dt.is_floating)
                        except (TypeError, ValueError,
                                NotImplementedError):
                            ok = False
                    if not ok:
                        self.will_not_work(
                            f"bounded range frame {frame} needs a single "
                            "ascending fixed-width non-float order key")
                continue
            self.will_not_work(f"window function {fn!r} not supported")

    def _convert_window(self, p: "L.Window") -> TpuExec:
        from spark_rapids_tpu.plan.execs.window import TpuWindowExec
        child = self.children[0].convert()
        if _plan_partitions(child) > 1:
            if p.spec.partition_by:
                child = self._exchange(self.conf.shuffle_partitions,
                                       p.spec.partition_by, child)
            else:
                child = TpuSinglePartitionExec(child)
        return TpuWindowExec(p.window_exprs, child, p.schema,
                             target_rows=self.conf.batch_size_rows)

    def _convert_join(self, p: L.Join) -> TpuExec:
        from spark_rapids_tpu.plan.execs.basic import TpuFilterExec
        from spark_rapids_tpu.plan.execs.join import (
            TpuBroadcastHashJoinExec, TpuShuffledHashJoinExec)
        left = self.children[0].convert()
        right = self.children[1].convert()
        nparts = self.conf.shuffle_partitions
        # broadcast choice: small build (right) side + a join type whose
        # null-extension never targets the broadcast side (the reference's
        # build-side constraint, GpuBroadcastHashJoinExecBase; keyless
        # broadcastable joins are the broadcast nested-loop shape,
        # GpuBroadcastNestedLoopJoinExecBase)
        broadcastable = p.join_type in ("inner", "left", "left_semi",
                                        "left_anti", "cross", "existence")
        est = _estimate_rows(p.right)
        thr = self.conf.broadcast_row_threshold
        if broadcastable and _plan_partitions(left) > 1 and est <= thr:
            # cross keeps Spark's Filter-over-product shape (the kernel's
            # conditional path does not run for cross)
            cross_cond = p.join_type == "cross" and p.condition is not None
            join: TpuExec = TpuBroadcastHashJoinExec(
                left, right, p.left_keys, p.right_keys, p.join_type, p.schema,
                target_rows=self.conf.batch_size_rows,
                condition=None if cross_cond else p.condition)
            if cross_cond:
                join = TpuFilterExec(p.condition, join)
            return join
        if (broadcastable and _plan_partitions(left) > 1 and p.left_keys
                and p.join_type != "cross" and est <= thr * 8
                and self.conf.join_adaptive_enabled):
            # ambiguous zone: the static estimate can't be trusted either
            # way — defer the broadcast-vs-shuffled choice to runtime,
            # decided from the MATERIALIZED build-side row count
            # (GpuShuffledSizedHashJoinExec.scala:829 / AQE analog)
            from spark_rapids_tpu.plan.execs.join import TpuAdaptiveJoinExec
            mode = self.conf.shuffle_mode
            if mode not in ("CACHE_ONLY", "MULTITHREADED", "MULTIPROCESS"):
                mode = "CACHE_ONLY"
            return TpuAdaptiveJoinExec(
                left, right, p.left_keys, p.right_keys, p.join_type,
                p.schema, broadcast_threshold=thr,
                shuffle_partitions=nparts,
                writer_threads=self.conf.shuffle_writer_threads,
                codec=self.conf.shuffle_codec,
                target_rows=self.conf.batch_size_rows,
                condition=p.condition,
                shuffle_mode=mode,
                aqe_coalesce=self.conf.aqe_coalesce_partitions,
                # the runtime-shuffled decision re-applies the planner's
                # post-passes over the tree it builds (plan-time fusion
                # cannot see it); same gating as plan_query's fusion pass
                fuse_inner=(self.conf.fuse_stages
                            and self.conf.shuffle_mode != "ICI"))
        if p.join_type == "cross" or not p.left_keys:
            # cartesian / nested-loop: candidate pairs must see every
            # right row, so both sides collapse to one partition
            # (GpuCartesianProductExec)
            from spark_rapids_tpu.plan.execs.exchange import (
                TpuSinglePartitionExec)
            left = TpuSinglePartitionExec(left)
            right = TpuSinglePartitionExec(right)
        else:
            # co-partition both sides on the join keys (the reference's
            # shuffled hash join shape, GpuShuffledSizedHashJoinExec)
            if _plan_partitions(left) > 1 or _plan_partitions(right) > 1:
                left = self._exchange(nparts, p.left_keys, left)
                right = self._exchange(nparts, p.right_keys, right)
        join: TpuExec = TpuShuffledHashJoinExec(
            left, right, p.left_keys, p.right_keys, p.join_type, p.schema,
            target_rows=self.conf.batch_size_rows,
            condition=p.condition if p.join_type != "cross" else None)
        if p.condition is not None and p.join_type == "cross":
            # cross + condition: Spark's Filter-over-CartesianProduct shape
            join = TpuFilterExec(p.condition, join)
        return join

    def _convert_aggregate(self, p: L.Aggregate) -> TpuExec:
        child = self.children[0].convert()
        single = _plan_partitions(child) == 1
        if single:
            return TpuHashAggregateExec(
                p.group_exprs, p.agg_exprs, p.aggregates, child, p.schema,
                mode="complete", target_capacity=self.conf.batch_size_rows)
        partial = TpuHashAggregateExec(
            p.group_exprs, p.agg_exprs, p.aggregates, child, p.schema,
            mode="partial", target_capacity=self.conf.batch_size_rows)
        if p.group_exprs:
            nkeys = len(p.group_exprs)
            key_refs = [E.BoundReference(i, p.group_exprs[i].dtype, f"_k{i}")
                        for i in range(nkeys)]
            exchange: TpuExec = self._exchange(
                self.conf.shuffle_partitions, key_refs, partial)
        else:
            exchange = TpuSinglePartitionExec(partial)
        return TpuHashAggregateExec(
            p.group_exprs, p.agg_exprs, p.aggregates, exchange, p.schema,
            mode="final", target_capacity=self.conf.batch_size_rows)

    def _exchange(self, nparts, keys, child) -> TpuExec:
        mode = self.conf.shuffle_mode
        if mode not in ("CACHE_ONLY", "MULTITHREADED", "MULTIPROCESS"):
            # ICI mode executes whole queries SPMD (parallel/stage.py inlines
            # the all-to-all into the program); when a plan falls back to the
            # task engine, its exchanges run CACHE_ONLY
            mode = "CACHE_ONLY"
        return TpuShuffleExchangeExec(
            nparts, keys, child, mode=mode,
            writer_threads=self.conf.shuffle_writer_threads,
            codec=self.conf.shuffle_codec,
            target_rows=self.conf.batch_size_rows)

    def _fallback(self) -> TpuExec:
        from spark_rapids_tpu.plan.execs.fallback import TpuCpuFallbackExec
        return TpuCpuFallbackExec(self.plan, self.conf)


def _estimate_rows(plan: L.LogicalPlan) -> int:
    """Crude cardinality estimate for broadcast decisions (the role of the
    reference's build-side stats, GpuHashJoin.scala:1111)."""
    p = plan
    if isinstance(p, L.InMemoryRelation):
        return sum(b.host_num_rows() for part in p.partitions for b in part)
    if isinstance(p, L.ParquetRelation):
        try:
            import pyarrow.parquet as pq
            return sum(pq.ParquetFile(path).metadata.num_rows
                       for path in p.paths)
        except Exception:
            return 1 << 62
    if isinstance(p, L.Filter):
        return max(_estimate_rows(p.child) // 2, 1)
    if isinstance(p, L.Aggregate):
        return max(_estimate_rows(p.child) // 3, 1)
    if isinstance(p, L.Limit):
        return min(p.n, _estimate_rows(p.child))
    if isinstance(p, L.Join):
        return max(_estimate_rows(p.left), _estimate_rows(p.right))
    if isinstance(p, L.Union):
        return sum(_estimate_rows(c) for c in p.children)
    if p.children:
        return _estimate_rows(p.children[0])
    return 1 << 62


def _plan_partitions(node: TpuExec) -> int:
    """Plan-time partition-count probe that NEVER materializes.

    ``TpuAdaptiveJoinExec.num_partitions()`` triggers the runtime
    broadcast-vs-shuffled decision (it materializes the build side) —
    calling it during planning would cache an inner exec pointing at
    PRE-rewrite children, which later passes (stage fusion) detach;
    execution then crashes on the stale references.  Both runtime
    choices of an adaptive join keep multiple partitions, so the probe
    answers from static shape alone."""
    from spark_rapids_tpu.plan.execs.base import TpuExec as _Base
    from spark_rapids_tpu.plan.execs.basic import TpuUnionExec
    from spark_rapids_tpu.plan.execs.exchange import (
        TpuCoalescedShuffleReaderExec)
    from spark_rapids_tpu.plan.execs.join import (
        TpuAdaptiveJoinExec, TpuBroadcastHashJoinExec,
        TpuShuffledHashJoinExec)
    from spark_rapids_tpu.plan.execs.lore import TpuLoreDumpExec
    from spark_rapids_tpu.plan.fused import TpuFusedSegmentExec
    if isinstance(node, TpuAdaptiveJoinExec):
        return max(_plan_partitions(node.children[0]),
                   node.shuffle_partitions)
    if isinstance(node, TpuUnionExec):
        return sum(_plan_partitions(c) for c in node.children)
    if isinstance(node, (TpuCoalescedShuffleReaderExec,
                         TpuShuffledHashJoinExec, TpuBroadcastHashJoinExec,
                         TpuFusedSegmentExec, TpuLoreDumpExec)):
        # partition-DELEGATING nodes: reader.num_partitions() IS the AQE
        # staging point (materializes the map side), and the joins/fused
        # wrappers just forward to children[0] — recurse ourselves so an
        # adaptive join anywhere below never sees num_partitions() at
        # plan time
        return _plan_partitions(node.children[0])
    if node.children and type(node).num_partitions is _Base.num_partitions:
        # structural nodes (project/filter/sort/...) inherit the base
        # delegation; recurse for the same reason — a select() between an
        # adaptive join and its consumer must not trigger the runtime
        # decision during planning (ADVICE r5 low #2)
        return _plan_partitions(node.children[0])
    # any exec that OWNS its partitioning (exchange, range sort, scans)
    # answers num_partitions statically
    return node.num_partitions()


def _non_agg_leaf_refs(e: E.Expression) -> List[E.Expression]:
    """Column refs in agg output exprs that are outside aggregate calls."""
    if isinstance(e, A.AggregateFunction):
        return []
    if isinstance(e, (E.BoundReference, E.Col)):
        return [e]
    out = []
    for c in e.children:
        out.extend(_non_agg_leaf_refs(c))
    return out


def plan_query(plan: L.LogicalPlan, conf: Optional[RapidsConf] = None
               ) -> Tuple[TpuExec, PlanMeta]:
    """wrapAndTagPlan + convert (GpuOverrides.scala:4423,:5148 analog)."""
    from spark_rapids_tpu.planner.optimizer import prune_columns, push_filters
    from spark_rapids_tpu.planner.rules import (
        apply_logical_rules, apply_post_tag_rules)
    conf = conf or RapidsConf()
    plan = prune_columns(push_filters(plan))
    plan = apply_logical_rules(plan, conf)
    meta = PlanMeta(plan, conf)
    meta.tag()
    from spark_rapids_tpu.planner.cbo import apply_cbo
    apply_cbo(meta, conf)
    apply_post_tag_rules(meta, conf)
    exec_plan = meta.convert()
    exec_plan = _insert_aqe_readers(exec_plan, conf)
    if conf.fuse_stages and conf.shuffle_mode != "ICI":
        # stage-segment fusion (plan/fused.py): one XLA program per batch
        # per fusable chain (including single ops across a shuffle
        # boundary).  Fusion is a TASK-ENGINE shape: IciQueryExecutor
        # unfuses any segment it receives (the backend, not the session
        # shuffle mode, decides — a non-ICI-session plan handed to the
        # SPMD compiler must still compile, VERDICT r5 #1a), and ICI
        # sessions fuse the whole query in the SPMD compiler instead.
        from spark_rapids_tpu.plan.fused import fuse_segments
        exec_plan = fuse_segments(exec_plan)
    _reset_adaptive_decisions(exec_plan)
    # LORE id assignment + dump wrapping (GpuLore.tagForLore analog,
    # GpuOverrides.scala:5149)
    from spark_rapids_tpu.plan.execs.lore import apply_lore
    exec_plan = apply_lore(exec_plan, conf)
    return exec_plan, meta


def _reset_adaptive_decisions(root: TpuExec) -> None:
    """Safety net behind _plan_partitions: if ANYTHING triggered an
    adaptive join's runtime decision during planning, the cached inner
    exec references PRE-rewrite children (later passes detach fused chain
    nodes) — discard it so execution re-decides over the final tree."""
    from spark_rapids_tpu.plan.execs.join import TpuAdaptiveJoinExec
    from spark_rapids_tpu.plan.fused import TpuFusedSegmentExec

    def walk(n: TpuExec) -> None:
        if isinstance(n, TpuAdaptiveJoinExec):
            with n._lock:
                if n._inner is not None:
                    # release what the premature decision retained (a
                    # shuffled choice holds live shuffle transports, a
                    # broadcast choice the materialized build) before
                    # dropping the reference — execution re-decides over
                    # the final tree
                    n._inner.cleanup()
                    n._inner = None
                    n.chosen = None
                t = getattr(n, "_cluster_build_transport", None)
                if t is not None:
                    # a premature DISTRIBUTED broadcast decision also
                    # created the one-partition build-union shuffle;
                    # re-deciding would overwrite the reference and leak
                    # its blocks for the process lifetime
                    t.cleanup()
                    n._cluster_build_transport = None
        kids = list(n.children)
        if isinstance(n, TpuFusedSegmentExec):
            kids.extend(n.chain)
        for c in kids:
            walk(c)

    walk(root)


def _insert_aqe_readers(root: TpuExec, conf: RapidsConf) -> TpuExec:
    """POST-pass AQE partition coalescing (GpuCustomShuffleReaderExec
    analog): wrap hash exchanges feeding final aggregates / shuffled joins
    with runtime coalescing readers.  Runs AFTER every structural planning
    decision — reader.num_partitions() materializes the map side (that is
    the AQE staging point), so it must never be consulted at plan time.
    Join sides share ONE spec so co-partitioning survives the merge.
    Skipped for ICI sessions: the SPMD program inlines the exchange as an
    all-to-all with no reduce-task granularity to merge."""
    if (not conf.aqe_coalesce_partitions
            or conf.shuffle_mode == "ICI"):
        return root
    from spark_rapids_tpu.plan.execs.exchange import (
        SharedCoalesceSpec, TpuCoalescedShuffleReaderExec,
        TpuShuffleExchangeExec)
    from spark_rapids_tpu.plan.execs.join import TpuShuffledHashJoinExec

    def visit(node: TpuExec) -> None:
        kids = list(node.children)
        if (isinstance(node, TpuHashAggregateExec)
                and getattr(node, "mode", None) == "final"
                and kids and isinstance(kids[0], TpuShuffleExchangeExec)):
            kids[0] = TpuCoalescedShuffleReaderExec(
                kids[0], SharedCoalesceSpec(conf.batch_size_rows,
                                            conf.batch_size_bytes))
        elif (isinstance(node, TpuShuffledHashJoinExec) and len(kids) == 2
              and all(isinstance(k, TpuShuffleExchangeExec)
                      for k in kids)):
            spec = SharedCoalesceSpec(conf.batch_size_rows,
                                      conf.batch_size_bytes)
            kids = [TpuCoalescedShuffleReaderExec(k, spec) for k in kids]
        node.children = tuple(kids)
        for k in node.children:
            visit(k)

    visit(root)
    return root


def explain_query(plan: L.LogicalPlan, conf: Optional[RapidsConf] = None) -> str:
    conf = conf or RapidsConf()
    from spark_rapids_tpu.planner.optimizer import prune_columns, push_filters
    plan = prune_columns(push_filters(plan))
    meta = PlanMeta(plan, conf)
    meta.tag()
    from spark_rapids_tpu.planner.cbo import apply_cbo
    apply_cbo(meta, conf)
    return meta.explain()
