"""The join kernels' position maps (kernels/join.py), held bit for bit.

Three things: the slot-to-row helper against the expression it replaced
(``np.searchsorted`` over the offsets, the plain reference), the gather maps
of every join type and path against a row-by-row numpy reference, and a
structural guard: no program of a join lowers to a ``while`` (a binary
search is a loop of dependent gathers, the access pattern the chip is worst
at; ISSUE 36).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.kernels import join as J
from spark_rapids_tpu.kernels.selection import OOB

MAXL = (1 << 63) - 1
NAN = float("nan")


# -- the helper ------------------------------------------------------------

SLOT_CASES = {
    # name: (per-row output counts, out_capacity)
    "empty_rows_leading": ([0, 0, 0, 2, 1, 3], 16),
    "empty_rows_trailing": ([2, 1, 3, 0, 0, 0], 16),
    "empty_rows_in_runs": ([1, 0, 0, 2, 0, 0, 0, 3, 0, 1], 16),
    "all_rows_empty": ([0, 0, 0, 0], 8),
    "total_under_capacity": ([3, 0, 2, 4], 16),
    "total_equal_to_capacity": ([3, 0, 2, 3], 8),
    "total_over_capacity": ([3, 0, 2, 4, 0, 5, 1], 8),
    "start_at_capacity_dropped": ([4, 4, 0, 0, 2], 8),
    "one_row": ([5], 8),
    "one_row_empty": ([0], 4),
    "one_row_over_capacity": ([9], 4),
    "one_row_owns_every_slot": ([0, 0, 8, 0], 8),
    "first_row_owns_every_slot": ([8, 0, 0], 8),
    "last_row_owns_every_slot": ([0, 0, 0, 8], 8),
    "every_row_one_slot": ([1] * 8, 8),
    "capacity_of_one": ([0, 1, 1], 1),
}


def _reference_rows(offsets, k, n):
    """The expression the helper replaced, as it stood in four places."""
    return np.clip(np.searchsorted(offsets, k, side="right") - 1, 0, n - 1)


@pytest.mark.parametrize("base", [None, 0, 3, 8, 11],
                         ids=lambda b: f"base_{b}")
@pytest.mark.parametrize("case", list(SLOT_CASES))
def test_slot_rows_against_searchsorted_on_live_slots(case, base):
    counts, cap = SLOT_CASES[case]
    n = len(counts)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    row, within = J._slot_rows(
        jnp.asarray(offsets), cap,
        None if base is None else jnp.asarray(base, jnp.int64))
    assert row.dtype == within.dtype == jnp.int32
    assert row.shape == within.shape == (cap,)
    b = base or 0
    k = np.arange(cap, dtype=np.int64)
    live = (k >= b) & (k < b + offsets[n])
    want = _reference_rows(offsets, k - b, n)
    np.testing.assert_array_equal(np.asarray(row)[live], want[live])
    np.testing.assert_array_equal(np.asarray(within)[live],
                                  (k - b - offsets[want])[live])


@pytest.mark.parametrize("seed", range(6))
def test_slot_rows_random_offsets(seed):
    rng = np.random.RandomState(seed)
    for _ in range(50):
        n = int(rng.choice([1, 2, 5, 17, 64]))
        cap = int(rng.choice([1, 4, 16, 50, 128]))
        counts = rng.randint(0, 6, n)
        counts = np.where(rng.rand(n) < rng.rand(), 0, counts)
        offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        row, within = J._slot_rows(jnp.asarray(offsets), cap)
        k = np.arange(cap, dtype=np.int64)
        live = k < offsets[n]
        want = _reference_rows(offsets, k, n)
        np.testing.assert_array_equal(np.asarray(row)[live], want[live])
        np.testing.assert_array_equal(np.asarray(within)[live],
                                      (k - offsets[want])[live])


def test_slot_rows_offsets_past_int32():
    """Totals live in int64 (OverflowStatus reads them); the slots a
    capacity holds are int32 and starts past it are dropped, not wrapped."""
    offsets = np.array([0, 3, 3 + (1 << 33), 5 + (1 << 33)], np.int64)
    row, within = J._slot_rows(jnp.asarray(offsets), 8)
    np.testing.assert_array_equal(np.asarray(row), [0, 0, 0, 1, 1, 1, 1, 1])
    np.testing.assert_array_equal(np.asarray(within),
                                  [0, 1, 2, 0, 1, 2, 3, 4])


# -- the gather maps, path by path -----------------------------------------

def _batch(keys, dtype, capacity=None, extra_key=None):
    """A batch of (k[, k2], v): v is the row's index, so maps read as rows."""
    data = {"k": list(keys)}
    names, dtypes = ["k"], [dtype]
    if extra_key is not None:
        data["k2"] = list(extra_key)
        names.append("k2")
        dtypes.append(T.INT)
    data["v"] = list(range(len(keys)))
    names.append("v")
    dtypes.append(T.INT)
    return ColumnarBatch.from_pydict(
        data, Schema(tuple(names), tuple(dtypes)), capacity=capacity)


def _key_eq(a, b):
    """Spark equi-join equality of two python keys (tuples for two keys):
    null matches nothing, NaN == NaN, -0.0 == 0.0."""
    a = a if isinstance(a, tuple) else (a,)
    b = b if isinstance(b, tuple) else (b,)
    for x, y in zip(a, b):
        if x is None or y is None:
            return False
        if isinstance(x, float) and isinstance(y, float) \
                and np.isnan(x) and np.isnan(y):
            continue
        if x != y:
            return False
    return True


def _key_order(key):
    """Sort key of a build row inside the kernels' order: NaN last, -0.0
    with 0.0; only the order of EQUAL keys' rows matters to the maps, and
    that is the rows' own order, so a stable sort on this key gives it."""
    key = key if isinstance(key, tuple) else (key,)
    return tuple((1, 0.0) if isinstance(x, float) and np.isnan(x)
                 else (0, x + 0.0 if isinstance(x, float) else x)
                 for x in key)


def _reference_maps(lkeys, rkeys, join_type, out_capacity):
    """(li, ri, count, required) row by row.  Pair order: left rows in
    their own order; a left row's matches in build-key order, equal keys in
    the build rows' own order (every path sorts the build side stably);
    right/full then append the unmatched right rows in their own order."""
    pairs = []
    if join_type == "cross":
        pairs = [(i, j) for i in range(len(lkeys)) for j in range(len(rkeys))]
    else:
        order = sorted((j for j, k in enumerate(rkeys)
                        if all(x is not None for x in
                               (k if isinstance(k, tuple) else (k,)))),
                       key=lambda j: _key_order(rkeys[j]))
        r_matched = set()
        for i, lk in enumerate(lkeys):
            m = [j for j in order if _key_eq(lk, rkeys[j])]
            r_matched.update(m)
            if join_type in ("inner", "right"):
                pairs += [(i, j) for j in m]
            elif join_type in ("left", "full"):
                pairs += [(i, j) for j in m] or [(i, OOB)]
            elif join_type == "left_semi":
                pairs += [(i, OOB)] if m else []
            elif join_type == "left_anti":
                pairs += [] if m else [(i, OOB)]
        if join_type in ("right", "full"):
            pairs += [(OOB, j) for j in range(len(rkeys))
                      if j not in r_matched]
    required = len(pairs)
    count = min(required, out_capacity)
    li = np.full(out_capacity, OOB, np.int32)
    ri = np.full(out_capacity, OOB, np.int32)
    for s, (i, j) in enumerate(pairs[:count]):
        li[s], ri[s] = i, j
    return li, ri, count, required


LONG_L = [5, MAXL, None, 5, 7, MAXL - 1, 9, None, 5, -3]
LONG_R = [5, 5, None, MAXL, 7, 5, 11, MAXL, None, 7, -3, 5]
DBL_L = [NAN, -0.0, 0.0, 1.5, None, NAN, 2.5, -1.5]
DBL_R = [0.0, NAN, -0.0, NAN, 2.5, None, 0.0, 7.0]
K2_L = [1, 1, 2, None, 1, 2, 1, 1, 2, 1]
K2_R = [1, 2, 1, 1, 1, 1, 1, None, 1, 1, 1, 1]

# name: (left keys, right keys, key dtype, left capacity, right capacity)
DATA = {
    "long_dups_nulls_max": (LONG_L, LONG_R, T.LONG, 16, 16),
    "double_nan_negzero": (DBL_L, DBL_R, T.DOUBLE, 8, 8),
    "empty_build": (LONG_L, [], T.LONG, 16, 4),
    "empty_probe": ([], LONG_R, T.LONG, 4, 16),
    "all_build_keys_null": ([1, 2, None], [None, None], T.LONG, 4, 2),
    "one_key_everywhere": ([4] * 5, [4] * 6, T.LONG, 8, 8),
}
# capacities: ample, exactly the widest requirement or under it, tiny
CAPACITIES = [64, 16, 3]
SINGLE_TYPES = ["inner", "left", "left_semi", "left_anti"]
MULTI_TYPES = ["inner", "left", "right", "full", "left_semi", "left_anti"]


def _check_maps(left, lk, right, rk, lkeys, rkeys, join_type, cap, path):
    assert J.join_path(left, lk, right, rk, join_type) == path
    li, ri, count, status = J.join_gather_maps(left, lk, right, rk,
                                               join_type, cap)
    wli, wri, wcount, wreq = _reference_maps(lkeys, rkeys, join_type, cap)
    assert li.dtype == ri.dtype == jnp.int32 and li.shape == ri.shape == (cap,)
    assert int(status.required_rows) == wreq
    li, ri = np.asarray(li), np.asarray(ri)
    if path == "single" and join_type in ("left_semi", "left_anti"):
        # a mask's compaction: its count is the requirement itself (the
        # status condemns a launch over capacity) and its tail its own affair
        li, wli, wcount = li[:wcount], wli[:wcount], wreq
    assert int(count) == wcount
    np.testing.assert_array_equal(li, wli)
    np.testing.assert_array_equal(ri, wri)
    # the two-phase API that the execs use gives the same maps
    state, required = J.join_probe(left, lk, right, rk, join_type)
    assert int(required) == wreq
    li2, ri2, count2, _ = J.join_expand(state, path, join_type,
                                        left.capacity, right.capacity, cap)
    assert int(count2) == wcount
    np.testing.assert_array_equal(np.asarray(li2)[:len(li)], li)
    np.testing.assert_array_equal(np.asarray(ri2), wri)


@pytest.mark.parametrize("cap", CAPACITIES, ids=lambda c: f"cap{c}")
@pytest.mark.parametrize("join_type", SINGLE_TYPES)
@pytest.mark.parametrize("data", list(DATA))
def test_single_path_maps_bit_identical(data, join_type, cap):
    lkeys, rkeys, dtype, cl, cr = DATA[data]
    _check_maps(_batch(lkeys, dtype, cl), [0], _batch(rkeys, dtype, cr), [0],
                lkeys, rkeys, join_type, cap, "single")


@pytest.mark.parametrize("cap", CAPACITIES, ids=lambda c: f"cap{c}")
@pytest.mark.parametrize("join_type", ["right", "full"])
@pytest.mark.parametrize("data", list(DATA))
def test_multi_path_one_key_both_regions_bit_identical(data, join_type, cap):
    """right / full on one key take the multi path: the left-driven region
    and the append region of unmatched right rows, each with its own map."""
    lkeys, rkeys, dtype, cl, cr = DATA[data]
    _check_maps(_batch(lkeys, dtype, cl), [0], _batch(rkeys, dtype, cr), [0],
                lkeys, rkeys, join_type, cap, "multi")


@pytest.mark.parametrize("cap", CAPACITIES, ids=lambda c: f"cap{c}")
@pytest.mark.parametrize("join_type", MULTI_TYPES)
@pytest.mark.parametrize("data", ["long_dups_nulls_max", "empty_build",
                                  "empty_probe"])
def test_multi_path_two_keys_bit_identical(data, join_type, cap):
    lkeys, rkeys, dtype, cl, cr = DATA[data]
    l2, r2 = K2_L[:len(lkeys)], K2_R[:len(rkeys)]
    _check_maps(_batch(lkeys, dtype, cl, extra_key=l2), [0, 1],
                _batch(rkeys, dtype, cr, extra_key=r2), [0, 1],
                list(zip(lkeys, l2)), list(zip(rkeys, r2)),
                join_type, cap, "multi")


@pytest.mark.parametrize("cap", [64, 12, 5], ids=lambda c: f"cap{c}")
@pytest.mark.parametrize("n_left,n_right", [(4, 3), (1, 5), (5, 1), (0, 3),
                                            (3, 0)],
                         ids=lambda n: str(n))
def test_cross_path_maps_bit_identical(n_left, n_right, cap):
    lkeys, rkeys = list(range(n_left)), list(range(n_right))
    _check_maps(_batch(lkeys, T.LONG, 8), [], _batch(rkeys, T.LONG, 8), [],
                lkeys, rkeys, "cross", cap, "cross")


def test_single_path_random_against_reference():
    rng = np.random.RandomState(36)
    for _ in range(12):
        nl, nr = rng.randint(0, 40), rng.randint(0, 40)
        nk = rng.randint(1, 12)
        lkeys = [None if rng.rand() < 0.15 else int(rng.randint(nk))
                 for _ in range(nl)]
        rkeys = [None if rng.rand() < 0.15 else int(rng.randint(nk))
                 for _ in range(nr)]
        left, right = _batch(lkeys, T.LONG, 64), _batch(rkeys, T.LONG, 64)
        for join_type in SINGLE_TYPES:
            _check_maps(left, [0], right, [0], lkeys, rkeys, join_type,
                        int(rng.choice([32, 128, 512])), "single")


# -- the structural guard --------------------------------------------------

@pytest.fixture(scope="module")
def small_sides():
    return (_batch(LONG_L, T.LONG, 16, extra_key=K2_L[:len(LONG_L)]),
            _batch(LONG_R, T.LONG, 16, extra_key=K2_R[:len(LONG_R)]))


# (path, join type, left key ordinals, right key ordinals)
GUARDED = ([("single", t, [0], [0]) for t in SINGLE_TYPES]
           + [("multi", t, [0, 1], [0, 1]) for t in MULTI_TYPES]
           + [("cross", "cross", [], [])])


def _no_loop(lowered_text):
    assert "while" not in lowered_text


@pytest.mark.parametrize("path,join_type,lk,rk", GUARDED,
                         ids=[f"{p}-{t}" for p, t, _, _ in GUARDED])
def test_join_probe_lowers_without_a_loop(small_sides, path, join_type, lk,
                                          rk):
    left, right = small_sides
    assert J.join_path(left, lk, right, rk, join_type) == path
    _no_loop(jax.jit(
        lambda l, r: J.join_probe(l, lk, r, rk, join_type)
    ).lower(left, right).as_text())


@pytest.mark.parametrize("path,join_type,lk,rk", GUARDED,
                         ids=[f"{p}-{t}" for p, t, _, _ in GUARDED])
def test_join_expand_lowers_without_a_loop(small_sides, path, join_type, lk,
                                           rk):
    """The body of ``join_expand`` / ``join_cond`` programs: the gather maps
    from a probe's state at a static capacity."""
    left, right = small_sides
    state, _ = J.join_probe(left, lk, right, rk, join_type)
    _no_loop(jax.jit(
        lambda st: J.join_expand(st, path, join_type, left.capacity,
                                 right.capacity, 64)
    ).lower(state).as_text())


@pytest.mark.parametrize("join_type", ["inner", "left", "right", "full",
                                       "left_semi", "left_anti", "existence"])
def test_join_cond_program_lowers_without_a_loop(join_type, monkeypatch):
    """A conditional join's second program as the exec builds it (candidate
    pairs, the condition over them, conditional_join_maps, the output
    gather): Q21's ``join_cond``."""
    from spark_rapids_tpu.expressions.core import BoundReference
    from spark_rapids_tpu.plan.execs import base
    from spark_rapids_tpu.plan.execs.join import _JoinKernel
    ls = Schema.of(k=T.LONG, lv=T.INT)
    rs = Schema.of(k=T.LONG, rv=T.INT)
    if join_type in ("left_semi", "left_anti"):
        out = ls
    elif join_type == "existence":
        out = Schema(ls.names + ("exists",), ls.dtypes + (T.BOOLEAN,))
    else:
        out = Schema(ls.names + ("k_r", "rv"), ls.dtypes + rs.dtypes)
    left = ColumnarBatch.from_pydict(
        {"k": LONG_L, "lv": list(range(len(LONG_L)))}, ls, capacity=16)
    right = ColumnarBatch.from_pydict(
        {"k": LONG_R, "rv": list(range(len(LONG_R)))}, rs, capacity=16)
    cond = BoundReference(1, T.INT) != BoundReference(3, T.INT)
    made = []
    shared_jit = base.shared_jit
    monkeypatch.setattr(
        base, "shared_jit",
        lambda key, make, **kw: shared_jit(
            key, lambda: made.append(make()) or made[-1], **kw))
    kernel = _JoinKernel([0], [0], join_type, out, condition=cond,
                         left_schema=ls, right_schema=rs)
    path = J.join_path(left, [0], right, [0], "inner")
    kernel._jitted_cond(64, 64, (), 0, path)
    state, _ = J.join_probe(left, [0], right, [0], "inner")
    _no_loop(jax.jit(made[-1]).lower(left, right, state).as_text())
