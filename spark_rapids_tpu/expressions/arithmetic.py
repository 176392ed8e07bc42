"""Arithmetic expression twins with Spark (non-ANSI) semantics.

Reference: sql-plugin/.../arithmetic.scala (GpuAdd, GpuSubtract, GpuMultiply,
GpuDivide, GpuIntegralDivide, GpuRemainder, GpuUnaryMinus, GpuAbs...).

Spark semantics encoded here (the compatibility spec, docs/compatibility.md):
  * integral +,-,* wrap on overflow (two's complement — XLA integer ops
    already wrap, matching the JVM);
  * Divide always produces DOUBLE for non-decimal inputs and returns NULL
    when the divisor is 0 (Spark DivModLike.isZero guard — this applies to
    doubles too: 1.0/0.0 IS NULL in Spark SQL);
  * IntegralDivide (`div`) produces LONG, NULL on zero divisor;
  * Remainder keeps the promoted input type, NULL on zero divisor;
  * other double math follows IEEE-754 (Infinity/NaN flow through).
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


def _promote(a: T.DataType, b: T.DataType) -> T.DataType:
    return T.numeric_promote(a, b)


def decimal_add_result(a: T.DecimalType, b: T.DecimalType) -> T.DecimalType:
    """Spark DecimalPrecision: add/sub result type."""
    scale = max(a.scale, b.scale)
    precision = max(a.precision - a.scale, b.precision - b.scale) + scale + 1
    return T.DecimalType(min(precision, T.DecimalType.MAX_PRECISION), scale)


def decimal_mul_result(a: T.DecimalType, b: T.DecimalType) -> T.DecimalType:
    scale = a.scale + b.scale
    precision = a.precision + b.precision + 1
    return T.DecimalType(min(precision, T.DecimalType.MAX_PRECISION),
                         min(scale, T.DecimalType.MAX_PRECISION))


def decimal_div_result(a: T.DecimalType, b: T.DecimalType) -> T.DecimalType:
    """Spark DecimalPrecision divide result with adjustPrecisionScale
    (allowPrecisionLoss default): p = p1-s1+s2+scale, scale =
    max(6, s1+p2+1), then squeeze into MAX_PRECISION preserving integral
    digits down to a min scale of 6."""
    scale = max(6, a.scale + b.precision + 1)
    precision = a.precision - a.scale + b.scale + scale
    if precision <= T.DecimalType.MAX_PRECISION:
        return T.DecimalType(precision, scale)
    int_digits = precision - scale
    min_scale = min(scale, 6)
    adj_scale = max(T.DecimalType.MAX_PRECISION - int_digits, min_scale)
    return T.DecimalType(T.DecimalType.MAX_PRECISION, adj_scale)


def _rescale_unscaled(x, from_scale: int, to_scale: int, xp):
    """int64 unscaled value rescale (to_scale >= from_scale)."""
    if to_scale == from_scale:
        return x
    return x * (10 ** (to_scale - from_scale))


def _overflow_null(vals, validity, precision: int, xp):
    """Spark non-ANSI decimal overflow -> null."""
    bound = 10 ** precision
    ok = (vals < bound) & (vals > -bound)
    return validity & ok


class BinaryArithmetic(BinaryExpression):
    """Common machinery: promote inputs, propagate nulls elementwise.

    Decimal path (Decimal64, precision <= 18 — SURVEY.md §2.1 decimal
    kernels): operands rescale to the Spark result scale as int64 unscaled
    values, overflow beyond the result precision yields NULL (non-ANSI).
    The planner gates result precisions > 18 until the two-limb int128
    kernels land."""

    literal_children_are_arguments = True

    _decimal_capable = False

    def _is_decimal(self) -> bool:
        return (isinstance(self.left.dtype, T.DecimalType)
                or isinstance(self.right.dtype, T.DecimalType))

    @property
    def dtype(self) -> T.DataType:
        if self._is_decimal():
            l, r = self.left.dtype, self.right.dtype
            assert isinstance(l, T.DecimalType) and isinstance(r, T.DecimalType), \
                "mixed decimal/non-decimal arithmetic needs casts"
            if type(self).__name__ == "Multiply":
                return decimal_mul_result(l, r)
            return decimal_add_result(l, r)
        return _promote(self.left.dtype, self.right.dtype)

    def _op(self, lhs, rhs):
        raise NotImplementedError

    def _np_op(self, lhs, rhs):
        return self._op(lhs, rhs)

    def _decimal_operands(self, ldata, rdata, xp):
        l, r = self.left.dtype, self.right.dtype
        out_dt = self.dtype
        if type(self).__name__ == "Multiply":
            return ldata.astype(xp.int64), rdata.astype(xp.int64)
        return (_rescale_unscaled(ldata.astype(xp.int64), l.scale,
                                  out_dt.scale, xp),
                _rescale_unscaled(rdata.astype(xp.int64), r.scale,
                                  out_dt.scale, xp))

    def _uses_128(self) -> bool:
        out = self.dtype
        return (out.uses_two_limbs
                or self.left.dtype.uses_two_limbs
                or self.right.dtype.uses_two_limbs)

    def _eval_decimal128(self, lc, rc, validity, out_dt):
        """Two-limb device path: rescale to the result scale, operate in
        int128, overflow beyond the result precision -> null."""
        from spark_rapids_tpu.kernels import decimal as DK
        ldt, rdt = self.left.dtype, self.right.dtype
        op = type(self).__name__
        lh, ll = DK.limbs_of(lc, ldt)
        rh, rl = DK.limbs_of(rc, rdt)
        if op == "Multiply":
            h, l, ov = DK.mul128_checked(lh, ll, rh, rl)
            validity = validity & ~ov
            prod_scale = ldt.scale + rdt.scale
            if out_dt.scale != prod_scale:
                h, l = DK.rescale(h, l, prod_scale, out_dt.scale)
        else:
            lh, ll = DK.rescale(lh, ll, ldt.scale, out_dt.scale)
            rh, rl = DK.rescale(rh, rl, rdt.scale, out_dt.scale)
            if op == "Add":
                h, l = DK.add128(lh, ll, rh, rl)
            elif op == "Subtract":
                h, l = DK.sub128(lh, ll, rh, rl)
            else:
                raise NotImplementedError(f"decimal128 {op}")
        validity = validity & ~DK.overflow(h, l, out_dt.precision)
        return DK.make_column128(h, l, validity, out_dt)

    def eval(self, ctx: EvalContext):
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        out_dt = self.dtype
        validity = null_propagating([lc.validity, rc.validity])
        if self._is_decimal():
            assert self._decimal_capable, \
                f"{type(self).__name__} has no decimal path (planner gap)"
            if self._uses_128():
                return self._eval_decimal128(lc, rc, validity, out_dt)
            lhs, rhs = self._decimal_operands(lc.data, rc.data, jnp)
            vals = self._op(lhs, rhs)
            validity = _overflow_null(vals, validity,
                                      min(out_dt.precision, 18), jnp)
            return make_column(vals, validity, out_dt)
        lhs = lc.data.astype(out_dt.jnp_dtype)
        rhs = rc.data.astype(out_dt.jnp_dtype)
        return make_column(self._op(lhs, rhs), validity, out_dt)

    def eval_cpu(self, ctx: CpuEvalContext):
        lv, lval = self.left.eval_cpu(ctx)
        rv, rval = self.right.eval_cpu(ctx)
        out_dt = self.dtype
        validity = cpu_null_propagating([lval, rval])
        if self._is_decimal():
            if self._uses_128():
                # exact python-int oracle path (object arrays)
                ldt, rdt = self.left.dtype, self.right.dtype
                op = type(self).__name__

                def ints(vs, valid):
                    return [int(x) if m and x is not None else 0
                            for x, m in zip(vs, valid)]
                lo = ints(lv, lval)
                ro = ints(rv, rval)
                if op == "Multiply":
                    vals = [a * b for a, b in zip(lo, ro)]
                    k = out_dt.scale - (ldt.scale + rdt.scale)
                else:
                    sl = 10 ** (out_dt.scale - ldt.scale)
                    sr = 10 ** (out_dt.scale - rdt.scale)
                    vals = ([a * sl + b * sr for a, b in zip(lo, ro)]
                            if op == "Add"
                            else [a * sl - b * sr for a, b in zip(lo, ro)])
                    k = 0
                if k < 0:
                    d = 10 ** (-k)

                    def half_up(v):
                        q, r = divmod(abs(v), d)
                        q += 1 if 2 * r >= d else 0
                        return -q if v < 0 else q
                    vals = [half_up(v) for v in vals]
                elif k > 0:
                    vals = [v * 10 ** k for v in vals]
                bound = 10 ** out_dt.precision
                validity = validity & np.array(
                    [-bound < v < bound for v in vals], np.bool_)
                out = np.empty((len(vals),), object)
                out[:] = [v if m else None
                          for v, m in zip(vals, validity)]
                return out, validity
            lhs, rhs = self._decimal_operands(lv, rv, np)
            with np.errstate(all="ignore"):
                vals = self._np_op(lhs, rhs)
            validity = _overflow_null(vals, validity,
                                      min(out_dt.precision, 18), np)
            return cpu_zero_invalid(vals.astype(np.int64), validity), validity
        lhs = lv.astype(out_dt.np_dtype)
        rhs = rv.astype(out_dt.np_dtype)
        with np.errstate(all="ignore"):
            vals = self._np_op(lhs, rhs)
        return cpu_zero_invalid(vals.astype(out_dt.np_dtype), validity), validity


class Add(BinaryArithmetic):
    symbol = "+"
    _decimal_capable = True

    def _op(self, lhs, rhs):
        return lhs + rhs


class Subtract(BinaryArithmetic):
    symbol = "-"
    _decimal_capable = True

    def _op(self, lhs, rhs):
        return lhs - rhs


class Multiply(BinaryArithmetic):
    symbol = "*"
    _decimal_capable = True

    def _op(self, lhs, rhs):
        return lhs * rhs


class Divide(BinaryExpression):
    """Spark Divide: double result for non-decimal inputs, NULL on zero
    divisor.  decimal/decimal divides exactly through the 256-bit
    intermediate kernel with one final HALF_UP rounding to the Spark
    result scale (reference: GpuDecimalDivide via DecimalUtils,
    arithmetic.scala:1387)."""

    literal_children_are_arguments = True

    symbol = "/"

    def _is_decimal(self) -> bool:
        return (isinstance(self.left.dtype, T.DecimalType)
                or isinstance(self.right.dtype, T.DecimalType))

    @property
    def dtype(self):
        if self._is_decimal():
            l, r = self.left.dtype, self.right.dtype
            assert isinstance(l, T.DecimalType) and isinstance(r, T.DecimalType), \
                "mixed decimal/non-decimal division needs casts"
            return decimal_div_result(l, r)
        return T.DOUBLE

    @property
    def nullable(self):
        return True

    def eval(self, ctx: EvalContext):
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        if self._is_decimal():
            from spark_rapids_tpu.kernels import decimal as DK
            ldt, rdt = self.left.dtype, self.right.dtype
            out_dt = self.dtype
            ah, al = DK.limbs_of(lc, ldt)
            bh, bl = DK.limbs_of(rc, rdt)
            # unscaled result = round(a / b * 10^(s - s1 + s2))
            shift = out_dt.scale - ldt.scale + rdt.scale
            assert shift >= 0, (ldt, rdt, out_dt)
            h, l, over, zero_div = DK.div128_by_128(ah, al, bh, bl, shift)
            validity = (null_propagating([lc.validity, rc.validity])
                        & ~zero_div & ~over
                        & ~DK.overflow(h, l, out_dt.precision))
            if out_dt.uses_two_limbs:
                return DK.make_column128(h, l, validity, out_dt)
            return make_column(l, validity, out_dt)
        lhs = lc.data.astype(jnp.float64)
        rhs = rc.data.astype(jnp.float64)
        zero_div = rhs == 0
        validity = null_propagating([lc.validity, rc.validity]) & ~zero_div
        safe = jnp.where(zero_div, jnp.ones((), rhs.dtype), rhs)
        return make_column(lhs / safe, validity, T.DOUBLE)

    def eval_cpu(self, ctx: CpuEvalContext):
        lv, lval = self.left.eval_cpu(ctx)
        rv, rval = self.right.eval_cpu(ctx)
        validity = cpu_null_propagating([lval, rval])
        if self._is_decimal():
            ldt, rdt = self.left.dtype, self.right.dtype
            out_dt = self.dtype
            shift = out_dt.scale - ldt.scale + rdt.scale
            bound = 10 ** out_dt.precision
            vals: list = []
            ok = np.zeros(len(lv), np.bool_)
            for i in range(len(lv)):
                if not validity[i]:
                    vals.append(None)
                    continue
                a, b = int(lv[i]), int(rv[i])
                if b == 0:
                    vals.append(None)
                    continue
                n = abs(a) * 10 ** shift
                q, r = divmod(n, abs(b))
                q += 1 if 2 * r >= abs(b) else 0
                q = -q if (a < 0) != (b < 0) else q
                if not (-bound < q < bound):
                    vals.append(None)
                    continue
                vals.append(q)
                ok[i] = True
            if out_dt.uses_two_limbs:
                out = np.empty((len(vals),), object)
                out[:] = vals
                return out, ok
            return (np.array([v if v is not None else 0 for v in vals],
                             np.int64), ok)
        lhs = lv.astype(np.float64)
        rhs = rv.astype(np.float64)
        zero_div = rhs == 0
        validity = validity & ~zero_div
        with np.errstate(all="ignore"):
            vals = lhs / np.where(zero_div, 1.0, rhs)
        return cpu_zero_invalid(vals, validity), validity


class IntegralDivide(BinaryExpression):
    """Spark `div`: long result, NULL on zero divisor, truncation toward
    zero (JVM semantics, not floor)."""

    symbol = "div"

    @property
    def dtype(self):
        return T.LONG

    @property
    def nullable(self):
        return True

    def eval(self, ctx: EvalContext):
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        lhs = lc.data.astype(jnp.int64)
        rhs = rc.data.astype(jnp.int64)
        zero_div = rhs == 0
        validity = null_propagating([lc.validity, rc.validity]) & ~zero_div
        safe = jnp.where(zero_div, jnp.ones((), jnp.int64), rhs)
        # JVM integer division truncates toward zero; lax div matches C
        quotient = jnp.sign(lhs) * jnp.sign(safe) * (jnp.abs(lhs) // jnp.abs(safe))
        return make_column(quotient, validity, T.LONG)

    def eval_cpu(self, ctx: CpuEvalContext):
        lv, lval = self.left.eval_cpu(ctx)
        rv, rval = self.right.eval_cpu(ctx)
        lhs = lv.astype(np.int64)
        rhs = rv.astype(np.int64)
        zero_div = rhs == 0
        validity = cpu_null_propagating([lval, rval]) & ~zero_div
        safe = np.where(zero_div, 1, rhs)
        with np.errstate(all="ignore"):
            q = np.sign(lhs) * np.sign(safe) * (np.abs(lhs) // np.abs(safe))
        return cpu_zero_invalid(q.astype(np.int64), validity), validity


class Remainder(BinaryArithmetic):
    """Spark %: JVM remainder (sign of dividend), NULL on zero divisor."""

    symbol = "%"

    @property
    def nullable(self):
        return True

    def eval(self, ctx: EvalContext):
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        out_dt = self.dtype
        lhs = lc.data.astype(out_dt.jnp_dtype)
        rhs = rc.data.astype(out_dt.jnp_dtype)
        zero_div = rhs == 0
        validity = null_propagating([lc.validity, rc.validity]) & ~zero_div
        one = jnp.ones((), rhs.dtype)
        safe = jnp.where(zero_div, one, rhs)
        if out_dt.is_floating:
            rem = jnp.where(validity, lhs - jnp.trunc(lhs / safe) * safe, 0)
        else:
            # JVM %: sign follows dividend
            rem = jnp.sign(lhs) * (jnp.abs(lhs) % jnp.abs(safe))
        return make_column(rem, validity, out_dt)

    def eval_cpu(self, ctx: CpuEvalContext):
        lv, lval = self.left.eval_cpu(ctx)
        rv, rval = self.right.eval_cpu(ctx)
        out_dt = self.dtype
        lhs = lv.astype(out_dt.np_dtype)
        rhs = rv.astype(out_dt.np_dtype)
        zero_div = rhs == 0
        validity = cpu_null_propagating([lval, rval]) & ~zero_div
        safe = np.where(zero_div, 1, rhs).astype(rhs.dtype)
        with np.errstate(all="ignore"):
            if out_dt.is_floating:
                rem = lhs - np.trunc(lhs / safe) * safe
            else:
                rem = np.sign(lhs) * (np.abs(lhs) % np.abs(safe))
        return cpu_zero_invalid(rem.astype(out_dt.np_dtype), validity), validity


class UnaryMinus(UnaryExpression):
    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, ctx: EvalContext):
        c = self.child.eval(ctx)
        return make_column(-c.data, c.validity, c.dtype)

    def eval_cpu(self, ctx: CpuEvalContext):
        v, valid = self.child.eval_cpu(ctx)
        with np.errstate(all="ignore"):
            out = -v
        return cpu_zero_invalid(out, valid), valid


class Abs(UnaryExpression):
    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, ctx: EvalContext):
        c = self.child.eval(ctx)
        return make_column(jnp.abs(c.data), c.validity, c.dtype)

    def eval_cpu(self, ctx: CpuEvalContext):
        v, valid = self.child.eval_cpu(ctx)
        with np.errstate(all="ignore"):
            out = np.abs(v)
        return cpu_zero_invalid(out, valid), valid
