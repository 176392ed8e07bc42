"""Expression tree: the TPU analog of Catalyst expressions + GpuExpression.

Reference shape: every supported Catalyst expression has a GPU twin with
``columnarEval(batch): GpuColumnVector`` (reference: GpuExpressions.scala,
basicPhysicalOperators.scala:834 tiered project).  Here the twin is
``eval(ctx)`` producing a DeviceColumn of the batch's static capacity —
pure, traceable, so whole operator pipelines jit into one XLA program and
elementwise expression work fuses into neighbouring kernels for free
(the TPU answer to the reference's AST offload, AstUtil.scala).

Every expression also implements ``eval_cpu(ctx)`` with identical Spark
semantics on numpy — that is the differential oracle the test harness uses
in place of the reference's CPU-Spark session (reference:
integration_tests/src/main/python/asserts.py).

Null semantics follow Spark: nulls propagate through elementwise ops unless
the expression documents otherwise (`GpuCoalesce`, `IsNull`, boolean
three-valued logic, ...).  Canonical padding discipline (column.py) is
maintained: null/pad slots hold zero.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn


class EvalContext:
    """Device-eval context: the input batch plus cached subresults.

    ``string_bucket`` is a STATIC (trace-time) byte bound covering the
    longest live string the regex/byte-window expressions will see; execs
    whose expression trees contain such nodes compute it host-side before
    entering jit (plan/execs/base.py regex_bucket) and key their jit cache
    on it."""

    def __init__(self, batch: ColumnarBatch, string_bucket: int = 0,
                 trace_consts=None):
        self.batch = batch
        self.capacity = batch.capacity
        self.string_bucket = string_bucket
        # {id(expr): [traced arrays]} — per-expression device constants
        # (DFA tables) passed as jit arguments (plan/execs/base.py
        # collect_trace_consts); expressions fall back to their host
        # constants when absent (eager use)
        self.trace_consts = trace_consts or {}

    def live_mask(self) -> jax.Array:
        return self.batch.live_mask()


class CpuEvalContext:
    """Host-oracle context: per-ordinal (values, validity) numpy pairs.

    Fixed-width values are numpy arrays; strings are object arrays of
    str/None.  validity is bool numpy.  Storage is ordinal-indexed because
    schemas may carry duplicate names after a join (as in Spark).
    """

    def __init__(self, cols, num_rows: int, schema: Schema):
        self.cols = list(cols)          # [(values, validity), ...]
        self.num_rows = num_rows
        self.schema = schema

    def col(self, ordinal: int):
        return self.cols[ordinal]

    @staticmethod
    def from_batch(batch: ColumnarBatch) -> "CpuEvalContext":
        # ONE device->host transfer for the row count and every column
        # buffer (DeviceColumn is a pytree, so device_get returns host
        # mirrors with numpy leaves).  The old per-column
        # to_numpy/to_pylist loop issued 2+ blocking syncs per column,
        # each draining the XLA dispatch queue — the dominant cost of
        # entering the CPU bridge on wide schemas.
        # tpu-lint: allow-host-sync(one batched download at the bridge boundary)
        n_dev, host_cols = jax.device_get((batch.num_rows,
                                           list(batch.columns)))
        n = int(n_dev)
        cols = []
        for col in host_cols:
            if col.dtype.variable_width or isinstance(col.dtype,
                                                      T.StructType) \
                    or (isinstance(col.dtype, T.DecimalType)
                        and col.dtype.uses_two_limbs):
                # tpu-lint: allow-host-sync(host mirror: already downloaded)
                pylist = col.to_pylist(n)
                vals = np.empty((n,), dtype=object)
                vals[:] = pylist
                valid = np.array([v is not None for v in pylist],
                                 dtype=np.bool_)
            else:
                # tpu-lint: allow-host-sync(host mirror: already downloaded)
                vals, valid = col.to_numpy(n)
                vals = vals.copy()
            cols.append((vals, valid))
        return CpuEvalContext(cols, n, batch.schema)


class Expression:
    """Base class.  Subclasses are immutable; identity is structural."""

    children: Tuple["Expression", ...] = ()
    #: a row's value depends on that row's inputs alone: not on its
    #: position in the batch, the row count or another row.  A class that
    #: reads any of those sets it False (plan/fused.py then compacts a
    #: filter's rows before such an expression sees them).
    row_local = True

    @property
    def dtype(self) -> T.DataType:
        raise NotImplementedError

    @property
    def nullable(self) -> bool:
        return any(c.nullable for c in self.children)

    def eval(self, ctx: EvalContext) -> DeviceColumn:
        raise NotImplementedError(type(self).__name__)

    def eval_cpu(self, ctx: CpuEvalContext) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError(type(self).__name__)

    # -- resolution ---------------------------------------------------------

    def bind(self, schema: Schema) -> "Expression":
        """Resolve Col() name references to bound indices against schema."""
        new_children = tuple(c.bind(schema) for c in self.children)
        # identity compare: == is overloaded as the EqualTo DSL operator
        if all(n is o for n, o in zip(new_children, self.children)):
            return self
        return self.with_children(new_children)

    def __bool__(self):
        raise TypeError(
            "Expression has no truth value (== builds an EqualTo expression); "
            "use semantic_equals or `is None` checks")

    def with_children(self, children: Tuple["Expression", ...]) -> "Expression":
        raise NotImplementedError(
            f"{type(self).__name__} must override with_children")

    def references(self) -> set:
        out = set()
        for c in self.children:
            out |= c.references()
        return out

    # -- sugar --------------------------------------------------------------

    def alias(self, name: str) -> "Alias":
        return Alias(self, name)

    def cast(self, dtype: T.DataType) -> "Expression":
        from spark_rapids_tpu.expressions.casts import Cast
        return Cast(self, dtype)

    def _bin(self, other, cls):
        return cls(self, lit(other) if not isinstance(other, Expression) else other)

    def __add__(self, other):
        from spark_rapids_tpu.expressions.arithmetic import Add
        return self._bin(other, Add)

    def __sub__(self, other):
        from spark_rapids_tpu.expressions.arithmetic import Subtract
        return self._bin(other, Subtract)

    def __mul__(self, other):
        from spark_rapids_tpu.expressions.arithmetic import Multiply
        return self._bin(other, Multiply)

    def __truediv__(self, other):
        from spark_rapids_tpu.expressions.arithmetic import Divide
        return self._bin(other, Divide)

    def __mod__(self, other):
        from spark_rapids_tpu.expressions.arithmetic import Remainder
        return self._bin(other, Remainder)

    def __neg__(self):
        from spark_rapids_tpu.expressions.arithmetic import UnaryMinus
        return UnaryMinus(self)

    def __eq__(self, other):
        from spark_rapids_tpu.expressions.predicates import EqualTo
        return self._bin(other, EqualTo)

    def __ne__(self, other):
        from spark_rapids_tpu.expressions.predicates import Not, EqualTo
        return Not(self._bin(other, EqualTo))

    def __lt__(self, other):
        from spark_rapids_tpu.expressions.predicates import LessThan
        return self._bin(other, LessThan)

    def __le__(self, other):
        from spark_rapids_tpu.expressions.predicates import LessThanOrEqual
        return self._bin(other, LessThanOrEqual)

    def __gt__(self, other):
        from spark_rapids_tpu.expressions.predicates import GreaterThan
        return self._bin(other, GreaterThan)

    def __ge__(self, other):
        from spark_rapids_tpu.expressions.predicates import GreaterThanOrEqual
        return self._bin(other, GreaterThanOrEqual)

    def __and__(self, other):
        from spark_rapids_tpu.expressions.predicates import And
        return self._bin(other, And)

    def __or__(self, other):
        from spark_rapids_tpu.expressions.predicates import Or
        return self._bin(other, Or)

    def __invert__(self):
        from spark_rapids_tpu.expressions.predicates import Not
        return Not(self)

    def is_null(self):
        from spark_rapids_tpu.expressions.predicates import IsNull
        return IsNull(self)

    def is_not_null(self):
        from spark_rapids_tpu.expressions.predicates import IsNotNull
        return IsNotNull(self)

    # structural equality helpers (== is overloaded for the DSL)
    def semantic_equals(self, other: "Expression") -> bool:
        return repr(self) == repr(other) and type(self) is type(other)

    def __hash__(self):
        return hash(repr(self))


class UnaryExpression(Expression):
    def __init__(self, child: Expression):
        self.child = child
        self.children = (child,)

    def with_children(self, children):
        return type(self)(children[0])

    def __repr__(self):
        return f"{type(self).__name__}({self.child!r})"


class BinaryExpression(Expression):
    def __init__(self, left: Expression, right: Expression):
        self.left = left
        self.right = right
        self.children = (left, right)

    def with_children(self, children):
        return type(self)(children[0], children[1])

    symbol = "?"

    def __repr__(self):
        return f"({self.left!r} {self.symbol} {self.right!r})"


# ---------------------------------------------------------------------------
# leaves


class Col(Expression):
    """Unresolved column reference by name (resolved by bind())."""

    def __init__(self, name: str):
        self.name = name
        self.children = ()

    @property
    def dtype(self):
        raise TypeError(f"unresolved column {self.name!r} has no dtype; bind() first")

    def bind(self, schema: Schema) -> "Expression":
        idx = schema.index_of(self.name)
        return BoundReference(idx, schema.dtypes[idx], self.name)

    def references(self):
        return {self.name}

    def __repr__(self):
        return f"'{self.name}"


class BoundReference(Expression):
    """Column reference resolved to an ordinal (Catalyst BoundReference)."""

    def __init__(self, ordinal: int, dtype: T.DataType, name: str = "?"):
        self.ordinal = ordinal
        self._dtype = dtype
        self.name = name
        self.children = ()

    @property
    def dtype(self):
        return self._dtype

    @property
    def nullable(self):
        return True

    def eval(self, ctx: EvalContext) -> DeviceColumn:
        return ctx.batch.columns[self.ordinal]

    def eval_cpu(self, ctx: CpuEvalContext):
        vals, valid = ctx.col(self.ordinal)
        return vals, valid

    def references(self):
        return {self.name}

    def __repr__(self):
        return f"{self.name}#{self.ordinal}"


def _np_dtype_for(dtype: T.DataType):
    return np.dtype(dtype.np_dtype)


def _infer_literal_type(value) -> T.DataType:
    if isinstance(value, bool):
        return T.BOOLEAN
    if isinstance(value, int):
        return T.INT if -(2**31) <= value < 2**31 else T.LONG
    if isinstance(value, float):
        return T.DOUBLE
    if isinstance(value, str):
        return T.STRING
    if isinstance(value, bytes):
        return T.BINARY
    if value is None:
        return T.NULL
    import datetime
    if isinstance(value, datetime.date) and not isinstance(value, datetime.datetime):
        return T.DATE
    raise TypeError(f"cannot infer SQL type for literal {value!r}")


class Literal(Expression):
    def __init__(self, value, dtype: Optional[T.DataType] = None):
        self._dtype = dtype if dtype is not None else _infer_literal_type(value)
        import datetime
        if isinstance(self._dtype, T.DateType) and isinstance(value, datetime.date):
            value = (value - datetime.date(1970, 1, 1)).days
        self.value = value
        self.children = ()

    @property
    def dtype(self):
        return self._dtype

    @property
    def nullable(self):
        return self.value is None

    def eval(self, ctx: EvalContext) -> DeviceColumn:
        cap = ctx.capacity
        if self._dtype.variable_width:
            b = (self.value.encode("utf-8") if isinstance(self.value, str)
                 else (self.value or b""))
            n = len(b)
            data = jnp.zeros((max(n, 1),), jnp.uint8)
            if n:
                data = jnp.asarray(np.frombuffer(b, dtype=np.uint8))
            # every live row points at the same bytes via per-row offsets is
            # not expressible with shared data; replicate lazily: scalar
            # string literals are rare outside comparisons, so materialize.
            rep = jnp.tile(data, cap) if n else jnp.zeros((cap,), jnp.uint8)
            offsets = (jnp.arange(cap + 1, dtype=jnp.int32) * n)
            live = ctx.live_mask()
            valid = live & (self.value is not None)
            return DeviceColumn(rep, valid, self._dtype, offsets)
        live = ctx.live_mask()
        if self.value is None:
            data = jnp.zeros((cap,), _np_dtype_for(self._dtype) if self._dtype.jnp_dtype is None else self._dtype.jnp_dtype)
            return DeviceColumn(data, jnp.zeros((cap,), jnp.bool_), self._dtype)
        data = jnp.full((cap,), self.value, dtype=self._dtype.jnp_dtype)
        data = jnp.where(live, data, jnp.zeros((), data.dtype))
        return DeviceColumn(data, live, self._dtype)

    def eval_cpu(self, ctx: CpuEvalContext):
        n = ctx.num_rows
        if self.value is None:
            dt = object if self._dtype.variable_width else _np_dtype_for(self._dtype)
            return np.zeros((n,), dtype=dt), np.zeros((n,), np.bool_)
        if self._dtype.variable_width:
            vals = np.empty((n,), dtype=object)
            vals[:] = self.value
            return vals, np.ones((n,), np.bool_)
        return (np.full((n,), self.value, dtype=_np_dtype_for(self._dtype)),
                np.ones((n,), np.bool_))

    def __repr__(self):
        return f"lit({self.value!r})"


def lit(value, dtype: Optional[T.DataType] = None) -> Literal:
    if isinstance(value, Literal):
        return value
    return Literal(value, dtype)


def col(name: str) -> Col:
    return Col(name)


@dataclasses.dataclass(init=False, eq=False, repr=False)
class Alias(Expression):
    """Name a subexpression (projection output naming)."""

    def __init__(self, child: Expression, name: str):
        self.child = child
        self.name = name
        self.children = (child,)

    @property
    def dtype(self):
        return self.child.dtype

    @property
    def nullable(self):
        return self.child.nullable

    def with_children(self, children):
        return Alias(children[0], self.name)

    def eval(self, ctx):
        return self.child.eval(ctx)

    def eval_cpu(self, ctx):
        return self.child.eval_cpu(ctx)

    def __repr__(self):
        return f"{self.child!r} AS {self.name}"


def output_name(e: Expression, i: int) -> str:
    """Projection output column name, Spark-style."""
    if isinstance(e, Alias):
        return e.name
    if isinstance(e, (Col,)):
        return e.name
    if isinstance(e, BoundReference):
        return e.name
    return f"col{i}"


# ---------------------------------------------------------------------------
# shared helpers for elementwise expression twins


def null_propagating(validities: Sequence[jax.Array]) -> jax.Array:
    out = validities[0]
    for v in validities[1:]:
        out = out & v
    return out


def make_column(values: jax.Array, validity: jax.Array, dtype: T.DataType) -> DeviceColumn:
    """Canonical-padding constructor: zero data where invalid."""
    values = jnp.where(validity, values, jnp.zeros((), values.dtype))
    return DeviceColumn(values, validity, dtype)


def cpu_null_propagating(validities) -> np.ndarray:
    out = validities[0].copy()
    for v in validities[1:]:
        out &= v
    return out


def cpu_zero_invalid(values: np.ndarray, validity: np.ndarray) -> np.ndarray:
    if values.dtype == object:
        out = values.copy()
        out[~validity] = None
        return out
    out = values.copy()
    out[~validity] = 0
    return out
