"""Window expression surface.

Reference: window/GpuWindowExpression.scala (2152 LoC) — window specs,
frames, ranking and aggregate window functions.

A WindowExpression pairs a function (ranking fn, shift fn, or a reused
AggregateFunction) with a WindowSpec.  Evaluation happens in the window
exec (plan/execs/window.py) over a partition-sorted layout; these classes
only carry structure + the CPU-oracle row semantics.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expressions.core import Expression, lit
from spark_rapids_tpu.expressions.aggregates import AggregateFunction
from spark_rapids_tpu.kernels.sort import SortOrder

UNBOUNDED = None
CURRENT = 0


@dataclasses.dataclass(frozen=True)
class WindowFrame:
    """kind: 'rows' or 'range'.  start/end: None = unbounded, 0 = current
    row, +n / -n row offsets (rows kind only for nonzero offsets)."""

    kind: str = "range"
    start: Optional[int] = UNBOUNDED
    end: Optional[int] = CURRENT

    def is_unbounded_to_current(self) -> bool:
        return self.start is None and self.end == 0

    def is_unbounded_both(self) -> bool:
        return self.start is None and self.end is None


class WindowSpec:
    def __init__(self, partition_by: Sequence[Expression] = (),
                 order_by: Sequence[Tuple[Expression, SortOrder]] = (),
                 frame: Optional[WindowFrame] = None):
        self.partition_by = tuple(partition_by)
        parsed = []
        for o in order_by:
            if isinstance(o, tuple):
                parsed.append(o)
            else:
                parsed.append((o, SortOrder(True)))
        self.order_by = tuple(parsed)
        if frame is None:
            # Spark defaults: RANGE UNBOUNDED..CURRENT with ORDER BY,
            # whole partition without
            frame = (WindowFrame("range", UNBOUNDED, CURRENT)
                     if self.order_by else WindowFrame("range", None, None))
        self.frame = frame

    def __repr__(self):
        parts = []
        if self.partition_by:
            parts.append("partition by " + ", ".join(map(repr, self.partition_by)))
        if self.order_by:
            parts.append("order by " + ", ".join(
                f"{e!r} {o!r}" for e, o in self.order_by))
        parts.append(f"{self.frame.kind} [{self.frame.start},{self.frame.end}]")
        return "(" + " ".join(parts) + ")"


class WindowFunction(Expression):
    """Ranking / shift functions that only exist inside a window."""

    name = "winfn"
    row_local = False

    def __repr__(self):
        return f"{self.name}()"


class RowNumber(WindowFunction):
    name = "row_number"
    children = ()

    @property
    def dtype(self):
        return T.INT

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return self


class Rank(WindowFunction):
    name = "rank"
    children = ()

    @property
    def dtype(self):
        return T.INT

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return self


class DenseRank(WindowFunction):
    name = "dense_rank"
    children = ()

    @property
    def dtype(self):
        return T.INT

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return self


class Lead(WindowFunction):
    name = "lead"

    def __init__(self, child: Expression, offset: int = 1):
        self.child = child
        self.offset = offset
        self.children = (child,)

    def with_children(self, children):
        return type(self)(children[0], self.offset)

    @property
    def dtype(self):
        return self.child.dtype

    def __repr__(self):
        return f"{self.name}({self.child!r}, {self.offset})"


class Lag(Lead):
    name = "lag"


class WindowExpression(Expression):
    row_local = False

    def __init__(self, function: Expression, spec: WindowSpec):
        assert isinstance(function, (WindowFunction, AggregateFunction)), \
            f"not a window-capable function: {function!r}"
        self.function = function
        self.spec = spec
        kids = [function]
        kids += list(spec.partition_by)
        kids += [e for e, _ in spec.order_by]
        self.children = tuple(kids)

    def with_children(self, children):
        n_part = len(self.spec.partition_by)
        func = children[0]
        part = children[1:1 + n_part]
        orders = tuple(
            (e, o) for e, (_, o) in zip(children[1 + n_part:],
                                        self.spec.order_by))
        return WindowExpression(
            func, WindowSpec(part, orders, self.spec.frame))

    @property
    def dtype(self):
        return self.function.dtype

    @property
    def nullable(self):
        return True

    def __repr__(self):
        return f"{self.function!r} OVER {self.spec!r}"


def over(function: Expression, partition_by=(), order_by=(),
         frame: Optional[WindowFrame] = None) -> WindowExpression:
    """DSL: over(sum_('x'), partition_by=[col('k')], order_by=[col('t')])."""
    from spark_rapids_tpu.expressions.core import col
    pb = [col(p) if isinstance(p, str) else p for p in partition_by]
    ob = []
    for o in order_by:
        if isinstance(o, str):
            ob.append((col(o), SortOrder(True)))
        elif isinstance(o, tuple) and isinstance(o[0], str):
            ob.append((col(o[0]), o[1]))
        else:
            ob.append(o)
    return WindowExpression(function, WindowSpec(pb, ob, frame))


class PercentRank(WindowFunction):
    """(rank - 1) / (partition rows - 1); 0.0 for a single-row partition."""

    name = "percent_rank"
    children = ()

    @property
    def dtype(self):
        return T.DOUBLE

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return self


class CumeDist(WindowFunction):
    """rows <= current (peers included) / partition rows."""

    name = "cume_dist"
    children = ()

    @property
    def dtype(self):
        return T.DOUBLE

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return self


class Ntile(WindowFunction):
    """ntile(n): n near-equal buckets, remainder spread to the first ones
    (Spark NTile semantics)."""

    name = "ntile"
    children = ()

    def __init__(self, n: int):
        assert n >= 1, n
        self.n = int(n)

    @property
    def dtype(self):
        return T.INT

    @property
    def nullable(self):
        return False

    def with_children(self, children):
        return self

    def __repr__(self):
        return f"ntile({self.n})"


class FirstValue(WindowFunction):
    """first_value(col) over the frame (nulls respected — Spark default)."""

    name = "first_value"

    def __init__(self, child: Expression):
        self.child = child
        self.children = (child,)

    def with_children(self, children):
        return type(self)(children[0])

    @property
    def dtype(self):
        return self.child.dtype

    @property
    def nullable(self):
        return True

    def __repr__(self):
        return f"{self.name}({self.child!r})"


class LastValue(FirstValue):
    name = "last_value"


class NthValue(FirstValue):
    """nth_value(col, k): k-th row of the frame (1-based), null when the
    frame has fewer than k rows."""

    name = "nth_value"

    def __init__(self, child: Expression, k: int):
        assert k >= 1, k
        super().__init__(child)
        self.k = int(k)

    def with_children(self, children):
        return NthValue(children[0], self.k)

    def __repr__(self):
        return f"nth_value({self.child!r}, {self.k})"
