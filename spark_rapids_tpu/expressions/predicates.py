"""Predicate twins: comparisons, boolean logic, null tests, IN.

Reference: sql-plugin/.../predicates.scala, nullExpressions.scala.

Spark semantics encoded here:
  * NaN semantics (Spark docs "NaN Semantics", GpuGreaterThan etc.):
    NaN == NaN is TRUE; NaN is larger than every other value.
  * three-valued AND/OR (GpuAnd/GpuOr): FALSE AND null = FALSE,
    TRUE OR null = TRUE, otherwise null propagates.
  * EqualNullSafe (<=>): never null; null <=> null = TRUE.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expressions.core import (
    BinaryExpression,
    CpuEvalContext,
    EvalContext,
    Expression,
    UnaryExpression,
    cpu_null_propagating,
    cpu_zero_invalid,
    make_column,
    null_propagating,
)


def _is_float(dt: T.DataType) -> bool:
    return isinstance(dt, (T.FloatType, T.DoubleType))


def _cmp_dtype(l: T.DataType, r: T.DataType) -> T.DataType:
    """Common comparison type (numeric promotion; exact for others)."""
    if l == r:
        return l
    if isinstance(l, T.NullType):
        return r
    if isinstance(r, T.NullType):
        return l
    if isinstance(l, T.DecimalType) and isinstance(r, T.DecimalType):
        scale = max(l.scale, r.scale)
        intd = max(l.precision - l.scale, r.precision - r.scale)
        return T.DecimalType(min(intd + scale, T.DecimalType.MAX_PRECISION),
                             scale)
    return T.numeric_promote(l, r)


class BinaryComparison(BinaryExpression):
    literal_children_are_arguments = True

    @property
    def dtype(self):
        return T.BOOLEAN

    def _compare(self, lhs, rhs, xp):
        raise NotImplementedError

    # ordering rank used for the device string path: 0 = lt, 1 = eq, 2 = gt
    _string_ranks = None   # subclasses set accepted ranks

    def _prep(self, ctx: EvalContext):
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        cdt = _cmp_dtype(lc.dtype, rc.dtype)
        validity = null_propagating([lc.validity, rc.validity])
        if isinstance(cdt, T.DecimalType):
            from spark_rapids_tpu.expressions.arithmetic import _rescale_unscaled
            lhs = _rescale_unscaled(lc.data.astype(jnp.int64),
                                    lc.dtype.scale, cdt.scale, jnp)
            rhs = _rescale_unscaled(rc.data.astype(jnp.int64),
                                    rc.dtype.scale, cdt.scale, jnp)
            return lhs, rhs, validity, T.LONG
        return (lc.data.astype(cdt.jnp_dtype), rc.data.astype(cdt.jnp_dtype),
                validity, cdt)

    def _prep_cpu(self, ctx: CpuEvalContext):
        lv, lval = self.left.eval_cpu(ctx)
        rv, rval = self.right.eval_cpu(ctx)
        ldt, rdt = self.left.dtype, self.right.dtype
        if isinstance(ldt, T.DecimalType) and isinstance(rdt, T.DecimalType):
            cdt = _cmp_dtype(ldt, rdt)
            if (cdt.uses_two_limbs or ldt.uses_two_limbs
                    or rdt.uses_two_limbs):
                # exact python-int compare at the common scale
                def obj(vs, scale):
                    k = 10 ** (cdt.scale - scale)
                    out = np.empty((len(vs),), object)
                    out[:] = [int(x) * k if x is not None else 0
                              for x in vs]
                    return out
                return (obj(lv, ldt.scale), obj(rv, rdt.scale),
                        cpu_null_propagating([lval, rval]), T.STRING)
        if lv.dtype == object or rv.dtype == object:
            return lv, rv, cpu_null_propagating([lval, rval]), T.STRING
        cdt = _cmp_dtype(self.left.dtype, self.right.dtype)
        validity = cpu_null_propagating([lval, rval])
        if isinstance(cdt, T.DecimalType):
            from spark_rapids_tpu.expressions.arithmetic import _rescale_unscaled
            lhs = _rescale_unscaled(lv.astype(np.int64),
                                    self.left.dtype.scale, cdt.scale, np)
            rhs = _rescale_unscaled(rv.astype(np.int64),
                                    self.right.dtype.scale, cdt.scale, np)
            return lhs, rhs, validity, T.LONG
        return (lv.astype(cdt.np_dtype), rv.astype(cdt.np_dtype),
                validity, cdt)

    def eval(self, ctx: EvalContext):
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        if lc.is_string_like or rc.is_string_like:
            assert lc.is_string_like and rc.is_string_like
            rank = _string_cmp_rank(lc, rc)
            validity = null_propagating([lc.validity, rc.validity])
            vals = jnp.zeros((ctx.capacity,), jnp.bool_)
            for r in self._string_ranks:
                vals = vals | (rank == r)
            return make_column(vals, validity, T.BOOLEAN)
        ldt, rdt = lc.dtype, rc.dtype
        if isinstance(ldt, T.DecimalType) and isinstance(rdt, T.DecimalType):
            cdt = _cmp_dtype(ldt, rdt)
            if (cdt.uses_two_limbs or ldt.uses_two_limbs
                    or rdt.uses_two_limbs):
                # int128 compare at the common scale (the int64 path would
                # silently wrap on wide rescales)
                from spark_rapids_tpu.kernels import decimal as DK
                lh, ll = DK.limbs_of(lc, ldt)
                rh, rl = DK.limbs_of(rc, rdt)
                lh, ll = DK.rescale(lh, ll, ldt.scale, cdt.scale)
                rh, rl = DK.rescale(rh, rl, rdt.scale, cdt.scale)
                lt = DK.lt128(lh, ll, rh, rl)
                eq = DK.eq128(lh, ll, rh, rl)
                rank = jnp.where(lt, 0, jnp.where(eq, 1, 2))
                validity = null_propagating([lc.validity, rc.validity])
                vals = jnp.zeros((ctx.capacity,), jnp.bool_)
                for r in self._string_ranks:
                    vals = vals | (rank == r)
                return make_column(vals, validity, T.BOOLEAN)
        lhs, rhs, validity, cdt = self._prep(ctx)
        vals = self._compare(lhs, rhs, jnp, _is_float(cdt))
        return make_column(vals, validity, T.BOOLEAN)

    def eval_cpu(self, ctx: CpuEvalContext):
        lhs, rhs, validity, cdt = self._prep_cpu(ctx)
        if isinstance(cdt, T.StringType):
            n = len(lhs)
            out = np.zeros((n,), np.bool_)
            for i in range(n):
                if validity[i]:
                    out[i] = self._py_compare(lhs[i], rhs[i])
            return out, validity
        with np.errstate(invalid="ignore"):
            vals = self._compare(lhs, rhs, np, _is_float(cdt))
        return cpu_zero_invalid(vals, validity), validity

    def _py_compare(self, a, b) -> bool:
        raise NotImplementedError


class EqualTo(BinaryComparison):
    symbol = "="
    _string_ranks = (1,)

    def _compare(self, lhs, rhs, xp, is_float):
        eq = lhs == rhs
        if is_float:
            eq = eq | (xp.isnan(lhs) & xp.isnan(rhs))
        return eq

    def _py_compare(self, a, b):
        return a == b


class LessThan(BinaryComparison):
    symbol = "<"
    _string_ranks = (0,)

    def _compare(self, lhs, rhs, xp, is_float):
        lt = lhs < rhs
        if is_float:
            # NaN is greater than everything: l < NaN iff l is not NaN
            lt = xp.where(xp.isnan(rhs), ~xp.isnan(lhs), lt)
            lt = xp.where(xp.isnan(lhs) & ~xp.isnan(rhs), False, lt)
        return lt

    def _py_compare(self, a, b):
        return a < b


class GreaterThan(BinaryComparison):
    symbol = ">"
    _string_ranks = (2,)

    def _compare(self, lhs, rhs, xp, is_float):
        return LessThan._compare(self, rhs, lhs, xp, is_float)

    def _py_compare(self, a, b):
        return a > b


class LessThanOrEqual(BinaryComparison):
    symbol = "<="
    _string_ranks = (0, 1)

    def _compare(self, lhs, rhs, xp, is_float):
        return LessThan._compare(self, lhs, rhs, xp, is_float) | \
            EqualTo._compare(self, lhs, rhs, xp, is_float)

    def _py_compare(self, a, b):
        return a <= b


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="
    _string_ranks = (1, 2)

    def _compare(self, lhs, rhs, xp, is_float):
        return LessThan._compare(self, rhs, lhs, xp, is_float) | \
            EqualTo._compare(self, lhs, rhs, xp, is_float)

    def _py_compare(self, a, b):
        return a >= b


class EqualNullSafe(BinaryComparison):
    """<=>: null-safe equality, never returns null."""

    symbol = "<=>"

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext):
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        cdt = _cmp_dtype(lc.dtype, rc.dtype)
        lhs = lc.data.astype(cdt.jnp_dtype)
        rhs = rc.data.astype(cdt.jnp_dtype)
        eq = EqualTo._compare(self, lhs, rhs, jnp, _is_float(cdt))
        both_null = ~lc.validity & ~rc.validity
        both_valid = lc.validity & rc.validity
        vals = jnp.where(both_valid, eq, both_null)
        return make_column(vals, ctx.live_mask(), T.BOOLEAN)

    def eval_cpu(self, ctx: CpuEvalContext):
        lv, lval = self.left.eval_cpu(ctx)
        rv, rval = self.right.eval_cpu(ctx)
        cdt = _cmp_dtype(self.left.dtype, self.right.dtype)
        with np.errstate(invalid="ignore"):
            if lv.dtype == object or rv.dtype == object:
                eq = np.array([a == b for a, b in zip(lv, rv)], dtype=np.bool_)
            else:
                eq = EqualTo._compare(self, lv.astype(cdt.np_dtype),
                                      rv.astype(cdt.np_dtype), np, _is_float(cdt))
        both_null = ~lval & ~rval
        both_valid = lval & rval
        vals = np.where(both_valid, eq, both_null)
        return vals, np.ones((ctx.num_rows,), np.bool_)


class And(BinaryExpression):
    literal_children_are_arguments = True

    symbol = "AND"

    @property
    def dtype(self):
        return T.BOOLEAN

    def eval(self, ctx: EvalContext):
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        lt = lc.data & lc.validity   # true-and-valid
        rt = rc.data & rc.validity
        lf = ~lc.data & lc.validity  # false-and-valid
        rf = ~rc.data & rc.validity
        validity = (lc.validity & rc.validity) | lf | rf
        return make_column(lt & rt, validity, T.BOOLEAN)

    def eval_cpu(self, ctx: CpuEvalContext):
        lv, lval = self.left.eval_cpu(ctx)
        rv, rval = self.right.eval_cpu(ctx)
        lt = lv.astype(np.bool_) & lval
        rt = rv.astype(np.bool_) & rval
        lf = ~lv.astype(np.bool_) & lval
        rf = ~rv.astype(np.bool_) & rval
        validity = (lval & rval) | lf | rf
        return lt & rt, validity


class Or(BinaryExpression):
    literal_children_are_arguments = True

    symbol = "OR"

    @property
    def dtype(self):
        return T.BOOLEAN

    def eval(self, ctx: EvalContext):
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        lt = lc.data & lc.validity
        rt = rc.data & rc.validity
        validity = (lc.validity & rc.validity) | lt | rt
        return make_column(lt | rt, validity, T.BOOLEAN)

    def eval_cpu(self, ctx: CpuEvalContext):
        lv, lval = self.left.eval_cpu(ctx)
        rv, rval = self.right.eval_cpu(ctx)
        lt = lv.astype(np.bool_) & lval
        rt = rv.astype(np.bool_) & rval
        validity = (lval & rval) | lt | rt
        return lt | rt, validity


class Not(UnaryExpression):
    literal_children_are_arguments = True

    @property
    def dtype(self):
        return T.BOOLEAN

    def eval(self, ctx: EvalContext):
        c = self.child.eval(ctx)
        return make_column(~c.data & c.validity, c.validity, T.BOOLEAN)

    def eval_cpu(self, ctx: CpuEvalContext):
        v, valid = self.child.eval_cpu(ctx)
        return ~v.astype(np.bool_) & valid, valid


class IsNull(UnaryExpression):
    @property
    def dtype(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext):
        c = self.child.eval(ctx)
        live = ctx.live_mask()
        return make_column(~c.validity & live, live, T.BOOLEAN)

    def eval_cpu(self, ctx: CpuEvalContext):
        _, valid = self.child.eval_cpu(ctx)
        return ~valid, np.ones_like(valid)


class IsNotNull(UnaryExpression):
    @property
    def dtype(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def eval(self, ctx: EvalContext):
        c = self.child.eval(ctx)
        live = ctx.live_mask()
        return make_column(c.validity & live, live, T.BOOLEAN)

    def eval_cpu(self, ctx: CpuEvalContext):
        _, valid = self.child.eval_cpu(ctx)
        return valid.copy(), np.ones_like(valid)


class In(Expression):
    """value IN (literals...).  Spark: null value -> null; no match but a
    null in the list -> null (three-valued)."""

    def __init__(self, value: Expression, items):
        from spark_rapids_tpu.expressions.core import lit
        self.value = value
        self.items = tuple(lit(i) if not isinstance(i, Expression) else i
                           for i in items)
        self.children = (value,) + self.items

    def with_children(self, children):
        return In(children[0], children[1:])

    @property
    def dtype(self):
        return T.BOOLEAN

    def eval(self, ctx: EvalContext):
        vc = self.value.eval(ctx)
        any_null_item = any(i.nullable for i in self.items)
        hit = jnp.zeros((ctx.capacity,), jnp.bool_)
        for item in self.items:
            ic = item.eval(ctx)
            if vc.is_string_like:
                hit = hit | _string_eq(vc, ic)
            else:
                cdt = _cmp_dtype(vc.dtype, ic.dtype)
                eq = EqualTo._compare(
                    self, vc.data.astype(cdt.jnp_dtype),
                    ic.data.astype(cdt.jnp_dtype), jnp, _is_float(cdt))
                hit = hit | (eq & ic.validity)
        validity = vc.validity & (hit | (not any_null_item))
        return make_column(hit, validity, T.BOOLEAN)

    def eval_cpu(self, ctx: CpuEvalContext):
        vv, vval = self.value.eval_cpu(ctx)
        any_null_item = any(i.nullable for i in self.items)
        hit = np.zeros((ctx.num_rows,), np.bool_)
        for item in self.items:
            iv, ival = item.eval_cpu(ctx)
            if vv.dtype == object:
                eq = np.array([a == b for a, b in zip(vv, iv)], dtype=np.bool_)
            else:
                cdt = _cmp_dtype(self.value.dtype, item.dtype)
                with np.errstate(invalid="ignore"):
                    eq = EqualTo._compare(self, vv.astype(cdt.np_dtype),
                                          iv.astype(cdt.np_dtype), np,
                                          _is_float(cdt))
            hit = hit | (eq & ival)
        validity = vval & (hit | (not any_null_item))
        return hit & validity, validity

    def __repr__(self):
        return f"{self.value!r} IN {tuple(self.items)!r}"


def _string_cmp_rank(a, b, max_bytes: int = 512) -> jnp.ndarray:
    """Elementwise string ordering rank: 0 = a<b, 1 = a==b, 2 = a>b, by
    UTF-8 byte order (Spark UTF8String.binaryCompare).  Compares the sort
    kernel's packed chunk keys most-significant first; max_bytes caps the
    static chunk count (the planner falls back beyond it)."""
    from spark_rapids_tpu.kernels.sort import SortOrder, _string_data_keys
    bound = min(max(a.byte_capacity, b.byte_capacity, 1), max_bytes)
    ka = _string_data_keys(a, SortOrder(True), bound)
    kb = _string_data_keys(b, SortOrder(True), bound)
    cap = a.capacity
    decided = jnp.zeros((cap,), jnp.bool_)
    rank = jnp.ones((cap,), jnp.int8)   # default eq
    for ca, cb in zip(ka, kb):
        ne = (ca != cb) & ~decided
        rank = jnp.where(ne & (ca < cb), jnp.int8(0), rank)
        rank = jnp.where(ne & (ca > cb), jnp.int8(2), rank)
        decided = decided | (ca != cb)
    return rank


def _string_eq(a, b) -> jnp.ndarray:
    """Elementwise string equality between two string columns of equal
    capacity (validity NOT applied)."""
    alen = a.offsets[1:] - a.offsets[:-1]
    blen = b.offsets[1:] - b.offsets[:-1]
    cap = a.capacity
    max_bytes = int(a.byte_capacity)
    # compare by walking byte positions per row up to a static bound derived
    # from the buffers; vectorized: for position j, rows where j < len must
    # match.  Bound the loop by the max row length via a scan over buckets.
    # Simple robust approach: compare padded fixed-width slices in chunks.
    eq = alen == blen
    CHUNK = 64
    nchunks = (max_bytes + CHUNK - 1) // CHUNK if max_bytes else 0
    astart = a.offsets[:-1]
    bstart = b.offsets[:-1]
    pos = jnp.arange(CHUNK, dtype=jnp.int32)
    for c in range(min(nchunks, 64)):
        off = c * CHUNK
        ai = jnp.clip(astart[:, None] + off + pos[None, :], 0, a.data.shape[0] - 1)
        bi = jnp.clip(bstart[:, None] + off + pos[None, :], 0, b.data.shape[0] - 1)
        in_row = (off + pos[None, :]) < alen[:, None]
        ab = jnp.where(in_row, a.data[ai], jnp.uint8(0))
        bb = jnp.where(in_row, b.data[bi], jnp.uint8(0))
        eq = eq & jnp.all(ab == bb, axis=1)
        if (c + 1) * CHUNK >= max_bytes:
            break
    return eq


class Coalesce(Expression):
    """First non-null argument (nullExpressions.scala GpuCoalesce)."""

    def __init__(self, *exprs: Expression):
        self.children = tuple(exprs)

    def with_children(self, children):
        return Coalesce(*children)

    @property
    def dtype(self):
        return self.children[0].dtype

    @property
    def nullable(self):
        return all(c.nullable for c in self.children)

    def eval(self, ctx: EvalContext):
        out_dt = self.dtype
        cols = [c.eval(ctx) for c in self.children]
        vals = jnp.zeros((ctx.capacity,), out_dt.jnp_dtype)
        validity = jnp.zeros((ctx.capacity,), jnp.bool_)
        for c in cols:
            take = c.validity & ~validity
            vals = jnp.where(take, c.data.astype(out_dt.jnp_dtype), vals)
            validity = validity | c.validity
        return make_column(vals, validity, out_dt)

    def eval_cpu(self, ctx: CpuEvalContext):
        out_dt = self.dtype
        n = ctx.num_rows
        vals = np.zeros((n,), object if out_dt.variable_width else out_dt.np_dtype)
        validity = np.zeros((n,), np.bool_)
        for c in self.children:
            cv, cval = c.eval_cpu(ctx)
            take = cval & ~validity
            if vals.dtype == object:
                vals[take] = cv[take]
            else:
                vals = np.where(take, cv.astype(out_dt.np_dtype), vals)
            validity |= cval
        return cpu_zero_invalid(vals, validity), validity

    def __repr__(self):
        return f"coalesce({', '.join(map(repr, self.children))})"
