"""A literal as an argument of the program: the same query with other
substitution values runs the same compiled program.

The rule (``expressions/core.py`` ``literal_is_argument``), the classes that
may hold an argument (``literal_children_are_arguments``, whose sources read
no literal's value), and the key that follows the binding (``plan/execs/
base.py`` ``bound_literals``, ``expr_cache_key``); TPC-H Q6 and Q1 with other
substitution sets through ``collect()`` (one backend compile for the first
set and none for the next, each answer its own set's); the literals that
stay constants of the program (a pattern, a LIMIT, a scale read in Python)
compile anew; and the serving tier's result cache, which still keeps two
sets apart.

On the CPU backend: compiles, rows and counts, never times."""
import ast
import collections
import importlib
import inspect
import pkgutil
import textwrap
import types

import jax.monitoring
import numpy as np
import pytest

from benchmark import compare, datagen
from benchmark.queries import q1, q6
from benchmark.tables import lineitem
from spark_rapids_tpu import types as T
from spark_rapids_tpu.api.session import TpuSession
import spark_rapids_tpu.expressions as expressions
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.core import (
    BinaryExpression, Expression, Literal, literal_is_argument)
from spark_rapids_tpu.expressions.parity import BRound
from spark_rapids_tpu.plan.execs.base import (
    bind_trace_consts, bound_literals, collect_trace_consts, expr_cache_key,
    launch_stats, program_keys_since, reset_launch_stats,
    tree_has_trace_consts)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Compiles:
    """Backend compiles of the process since the last ``take()``."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == BACKEND_COMPILE:
            self.n += 1

    def take(self) -> int:
        n, self.n = self.n, 0
        return n


COMPILES = Compiles()


@pytest.fixture
def fresh_program_caches(monkeypatch):
    """Programs and converged capacities are kept for the life of the
    process: a test that counts compiles starts from none."""
    from spark_rapids_tpu.plan import fused
    from spark_rapids_tpu.plan.execs import base
    monkeypatch.setattr(fused, "_FUSED_CAPS", collections.OrderedDict())
    monkeypatch.setattr(fused, "_FUSED_BUCKET", collections.OrderedDict())
    monkeypatch.setattr(base, "_JIT_CACHE", collections.OrderedDict())


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """A LINEITEM of 24,576 rows in two files of one row group each (the
    benchmark's writer), its session and its frame for the references."""
    rows = 24_576
    files = datagen.write_table(str(tmp_path_factory.mktemp("li")), lineitem,
                                "lineitem", rows, 2, rows // 2, 2**31 + 43,
                                rows / 6_001_215)
    cols = sorted(set(q6.COLUMNS) | set(q1.COLUMNS) | {"l_comment"})
    return types.SimpleNamespace(
        files=files, frame=datagen.read_frame(files, cols),
        session=TpuSession({"spark.rapids.sql.enabled": "true"}))


# -- the rule and the key ------------------------------------------------------

@pytest.mark.parametrize("literal,is_arg", [
    (lit(5), True), (lit(2**40), True), (lit(0.06), True), (lit(True), True),
    (Literal(8766, T.DATE), True), (Literal(1_700_000_000, T.TIMESTAMP), True),
    (Literal(6, T.DecimalType(12, 2)), True),
    # one value a row that is not one device scalar, or no value at all
    (lit("MAIL"), False), (Literal(None, T.INT), False),
    (Literal(6, T.DecimalType(30, 2)), False)])
def test_which_literal_is_an_argument(literal, is_arg):
    assert literal_is_argument(literal) is is_arg


def test_a_literal_whose_value_is_read_while_tracing_stays_a_constant():
    scale = lit(2)
    assert literal_is_argument(scale)
    assert not literal_is_argument(scale, BRound(col("x"), scale))
    assert literal_is_argument(scale, col("x") + scale)


class _ReadsItsLiteral(BinaryExpression):
    """A class that says nothing of its literal children, as a new one."""

    @property
    def dtype(self):
        return T.INT


def test_a_class_that_does_not_say_so_keeps_its_literals_constants():
    n = lit(3)
    assert not literal_is_argument(n, _ReadsItsLiteral(col("x"), n))
    e = col("x") + _ReadsItsLiteral(col("y"), n)
    assert bound_literals([e]) == frozenset()
    assert expr_cache_key(e, bound_literals([e])) != expr_cache_key(
        col("x") + _ReadsItsLiteral(col("y"), lit(4)))


def _expression_classes():
    for m in pkgutil.iter_modules(expressions.__path__):
        importlib.import_module(f"{expressions.__name__}.{m.name}")
    seen, todo = set(), [Expression]
    while todo:
        for sub in todo.pop().__subclasses__():
            if sub not in seen:
                seen.add(sub)
                todo.append(sub)
    return seen


# the package's own modules, all imported above, so that every worker
# collects the same cases
_ARGUMENT_PARENTS = sorted(
    (c for c in _expression_classes()
     if c.literal_children_are_arguments
     and c.__module__.startswith(f"{expressions.__name__}.")),
    key=lambda c: f"{c.__module__}.{c.__qualname__}")


def test_the_classes_that_may_hold_an_argument():
    names = {c.__name__ for c in _ARGUMENT_PARENTS}
    assert {"Alias", "And", "Or", "Not", "Add", "Subtract", "Multiply",
            "Divide", "LessThan", "GreaterThanOrEqual"} <= names
    assert not {"BRound", "SortArray", "ArrayRepeat", "Like",
                "StartsWith", "Substring", "Cast"} & names


@pytest.mark.parametrize("cls", _ARGUMENT_PARENTS,
                         ids=lambda c: c.__qualname__)
def test_a_class_that_may_hold_an_argument_reads_no_literal_value(cls):
    """The guard of the rule: a literal that is an argument is keyed by
    type, so a class that read its value in Python while tracing would
    bake the first value into a program every value runs.  No class that
    says its literal children can be arguments (nor a base of it in this
    package) reads a ``.value`` or looks for a ``Literal``."""
    for c in cls.__mro__:
        if not (isinstance(c, type) and issubclass(c, Expression)
                and c.__module__.startswith("spark_rapids_tpu.")):
            continue
        tree = ast.parse(textwrap.dedent(inspect.getsource(c)))
        for node in ast.walk(tree):
            assert not (isinstance(node, ast.Attribute)
                        and node.attr == "value"), (c, node.lineno)
            assert not (isinstance(node, ast.Name)
                        and node.id == "Literal"), (c, node.lineno)


def _pred(qty, dtype=T.INT):
    return (col("a") < Literal(qty, dtype)) & (col("b") == lit("x"))


def _key(e, bound=True):
    return expr_cache_key(e, bound_literals([e]) if bound else frozenset())


def test_the_key_holds_an_arguments_type_and_not_its_value():
    assert _key(_pred(24)) == _key(_pred(25))
    assert _key(_pred(24)) != _key(_pred(24, T.LONG))
    # without arguments bound, the value is a constant and keys the program
    assert _key(_pred(24), False) != _key(_pred(25), False)
    # a string stays in the key either way
    other = (col("a") < lit(24)) & (col("b") == lit("y"))
    assert _key(_pred(24)) != _key(other)


def test_collect_and_bind_walk_the_same_literals():
    e = _pred(24)
    consts = collect_trace_consts([e])
    assert [c.tolist() for c in consts] == [24]
    assert consts[0].dtype == np.int32
    bound = bind_trace_consts([e], ["traced"])
    (lit_id, arrays), = bound.items()
    assert arrays == ["traced"] and lit_id == id(e.children[0].children[1])


def test_a_literal_is_no_device_constant_of_its_own():
    from spark_rapids_tpu.expressions import Like
    assert not tree_has_trace_consts([_pred(24)])
    # a pattern off the fast paths carries its DFA's tables
    assert tree_has_trace_consts([col("a") & Like(col("b"), "a_b%c")])


def test_building_a_plan_puts_no_argument_on_the_device(table, monkeypatch):
    import jax.numpy as jnp
    from spark_rapids_tpu.planner.overrides import plan_query
    df = q6.build(table.session.read_parquet(*table.files), year=1997)
    puts = []
    real = jnp.asarray
    monkeypatch.setattr(jnp, "asarray", lambda *a, **k: (
        puts.append(a), real(*a, **k))[1])
    plan_query(df.plan, table.session.conf)
    assert puts == []
    monkeypatch.undo()
    want = q6.reference(table.frame, year=1997)
    assert compare.answer_gap(df.collect(), want, False)[0] is True


# -- the same program for every substitution set ------------------------------

@pytest.mark.parametrize("mod,sets", [
    (q6, [{"year": 1994, "discount": 6, "quantity": 24},
          {"year": 1996, "discount": 3, "quantity": 25}]),
    (q1, [{"delta": 60}, {"delta": 120}])])
def test_each_substitution_set_runs_the_first_sets_program(
        table, fresh_program_caches, mod, sets):
    answers = []
    for i, sub in enumerate(sets):
        COMPILES.take()
        reset_launch_stats()
        got = mod.build(table.session.read_parquet(*table.files),
                        **sub).collect()
        compiles, programs = COMPILES.take(), launch_stats()["by_program"]
        want = mod.reference(table.frame, **sub)
        same, gap = compare.answer_gap(got, want,
                                       getattr(mod, "ORDERED", False))
        assert same is True and gap < 1e-12, (sub, got, want)
        if i == 0:
            assert compiles >= 1
            first = programs
        else:
            assert compiles == 0, programs
            assert programs == first
        answers.append(want)
    assert answers[0] != answers[1]


def _limit(df, n):
    return df.order_by("l_orderkey", "l_linenumber").limit(n).select(
        "l_orderkey", "l_linenumber")


def _pattern(df, word):
    from spark_rapids_tpu.expressions import Like, count
    return df.filter(Like(col("l_comment"), f"%{word}%")).agg(
        count().alias("n"))


def _bround(df, d):
    from spark_rapids_tpu.expressions import sum_
    return df.select(BRound(col("l_extendedprice").cast(T.DOUBLE),
                            lit(d)).alias("p")).agg(sum_("p").alias("s"))


@pytest.mark.parametrize("build,values,compiles_anew", [
    # a LIKE pattern is built into the program's byte matcher
    (_pattern, ("the", "ly"), True),
    # bround's scale is read in Python while the program is traced
    (_bround, (-1, -3), True),
    # a LIMIT is the host's: no program holds it, so none compiles anew
    (_limit, (5, 9), False)])
def test_a_literal_the_program_reads_while_tracing_compiles_anew(
        table, fresh_program_caches, build, values, compiles_anew):
    oracle = TpuSession({"spark.rapids.sql.enabled": "false"})
    got = []
    for i, v in enumerate(values):
        COMPILES.take()
        rows = build(table.session.read_parquet(*table.files), v).collect()
        compiles = COMPILES.take()
        assert compiles >= 1 if i == 0 or compiles_anew else compiles == 0
        want = build(oracle.read_parquet(*table.files), v).collect()
        assert [tuple(r) for r in rows] == [tuple(r) for r in want], v
        got.append(rows)
    assert got[0] != got[1]


def test_the_counter_of_program_keys(table, fresh_program_caches):
    import time
    reset_launch_stats()
    assert launch_stats()["program_keys"] == 0
    q6.build(table.session.read_parquet(*table.files)).collect()
    keys = launch_stats()["program_keys"]
    assert keys == launch_stats()["programs"] >= 2
    t = time.perf_counter()
    assert program_keys_since(t) == 0
    q6.build(table.session.read_parquet(*table.files), year=1995).collect()
    assert program_keys_since(t) == keys == launch_stats()["program_keys"]


# -- the serving tier keeps the sets apart ------------------------------------

def test_the_result_cache_keeps_two_sets_apart(table):
    from spark_rapids_tpu.serving import LocalSessionRunner, QueryQueue
    conf = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.serving.cache.enabled": "true"}
    inner, runs = LocalSessionRunner(conf), []

    def counting(plan, ctx):
        runs.append(plan)
        return inner(plan, ctx)
    q = QueryQueue(counting, conf=conf)
    sets = [{"year": 1993, "discount": 4, "quantity": 24},
            {"year": 1997, "discount": 8, "quantity": 25}]
    try:
        got = [q.submit(q6.build(table.session.read_parquet(*table.files),
                                 **sub).plan) for sub in sets + sets[:1]]
    finally:
        q.close()
    assert len(runs) == 2          # two misses, then a hit
    want = [q6.reference(table.frame, **sub) for sub in sets]
    assert want[0] != want[1]
    for rows, w in zip(got, want + want[:1]):
        assert compare.answer_gap(rows, w)[0] is True
