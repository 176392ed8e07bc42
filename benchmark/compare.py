"""The comparison that decides ``correct``: every answer the timed
``collect()`` calls returned, against the plain reference's rows for the same
query and substitution set.

Rows are compared in the order in which they came where the query file says
``ORDERED`` (it has an ORDER BY), else as a multiset (sorted, then pairwise).
A field that is not a float has to be equal.  A float field is a sum or a mean in float64:
its gap is ``|got - want| / max(|want|, 1)`` and the widest gap over all
fields of all answers is one number, held to the configuration's limit.
"""
import math


def float_gap(got: float, want: float) -> float:
    gap = abs(got - want) / max(abs(want), 1.0)
    return gap if gap == gap else math.inf     # NaN is as wrong as it gets


def answer_gap(got_rows, want_rows, ordered=False):
    """(exact fields and row count right?, widest float gap) of one answer."""
    if got_rows is None or len(got_rows) != len(want_rows):
        return False, 0.0
    exact, gap = True, 0.0
    arrange = list if ordered else sorted
    for got, want in zip(arrange(map(tuple, got_rows)),
                         arrange(map(tuple, want_rows))):
        if len(got) != len(want):
            return False, gap
        for a, b in zip(got, want):
            if isinstance(b, float) and isinstance(a, float):
                gap = max(gap, float_gap(a, b))
            elif a != b or type(a) is not type(b):
                exact = False
    return exact, gap


def compare(answers, references, limits, fallback_nodes, missing,
            ordered=()) -> dict:
    """``answers``: (key, rows) of every query that returned, where a key is
    (query name, substitution set as ``traffic.sub_key`` gives it);
    ``references``: key -> rows; ``missing``: the queries that raised (a
    query that admission refused, or that had not finished when the window
    was drained, is no answer and is not counted here); ``ordered``: the
    query names whose rows come in a stated order.  Returns name ->
    {value, limit}, and ``correct`` is that every value is within its
    limit."""
    wrong, gap = 0, 0.0
    for key, rows in answers:
        exact, g = answer_gap(rows, references[key], key[0] in ordered)
        wrong += not exact
        gap = max(gap, g)
    return {
        "answers_missing": {"value": missing, "limit": 0},
        "answers_wrong": {"value": wrong, "limit": 0},
        "float_gap": {"value": gap, "limit": limits["float_gap"]},
        "fallback_nodes": {"value": fallback_nodes, "limit": 0},
    }


def is_correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())
