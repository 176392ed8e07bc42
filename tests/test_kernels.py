"""Kernel unit tests: each kernel vs an independent pure-Python oracle."""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.kernels import hash as hk
from spark_rapids_tpu.kernels import partition as pk
from spark_rapids_tpu.kernels import selection as sel
from spark_rapids_tpu.kernels import sort as sk
from spark_rapids_tpu.kernels import groupby as gb


# -- murmur3 ----------------------------------------------------------------

def test_murmur3_int_known_values():
    # Spark: SELECT hash(0) == 933211791, hash(1) == -559580957
    # (Murmur3_x86_32 seed 42; widely documented anchor values).
    def as_i32(u):
        return u - (1 << 32) if u >= (1 << 31) else u
    assert as_i32(hk.py_hash_int(0, 42)) == 933211791
    assert as_i32(hk.py_hash_int(1, 42)) == -559580957


def test_murmur3_long_string_known_values():
    def as_i32(u):
        return u - (1 << 32) if u >= (1 << 31) else u
    # Spark: SELECT hash(1L) == -1712319331; hash('ABC') == -757602832
    # (the latter is the example in pyspark's functions.hash docstring).
    assert as_i32(hk.py_hash_long(1, 42)) == -1712319331
    assert as_i32(hk.py_hash_bytes(b"ABC", 42)) == -757602832


@pytest.mark.parametrize("dtype,vals", [
    (T.INT, [0, 1, -1, 2**31 - 1, -(2**31), 42, None]),
    (T.LONG, [0, 1, -1, 2**63 - 1, -(2**63), 123456789012345, None]),
    (T.SHORT, [0, 1, -1, 32767, -32768, None]),
    (T.BYTE, [0, 1, -1, 127, -128, None]),
    (T.BOOLEAN, [True, False, None]),
    (T.DOUBLE, [0.0, -0.0, 1.5, -1.5, 1e300, float("nan"), None]),
    (T.FLOAT, [0.0, -0.0, 1.5, -1.5, float("nan"), None]),
])
def test_murmur3_fixed_vs_oracle(dtype, vals):
    batch = ColumnarBatch.from_pydict({"k": vals}, Schema.of(k=dtype))
    got = np.asarray(hk.murmur3_hash([batch.columns[0]]))[: len(vals)]
    import math
    for i, v in enumerate(vals):
        vv = v
        if isinstance(v, float) and math.isnan(v):
            vv = float("nan")
        expect = hk.py_murmur3_row([vv], [dtype])
        assert got[i] == expect, f"row {i} value {v!r}: {got[i]} != {expect}"


def test_murmur3_string_vs_oracle():
    vals = ["", "a", "ab", "abc", "abcd", "abcde", "héllo wörld", None,
            "0123456789abcdef0123456789abcdef", "x" * 63]
    batch = ColumnarBatch.from_pydict({"s": vals}, Schema.of(s=T.STRING))
    got = np.asarray(hk.murmur3_hash([batch.columns[0]], string_max_bytes=64))[: len(vals)]
    for i, v in enumerate(vals):
        expect = hk.py_murmur3_row([v], [T.STRING])
        assert got[i] == expect, f"row {i} {v!r}: {got[i]} != {expect}"


def test_murmur3_multi_column_chaining():
    schema = Schema.of(a=T.INT, b=T.LONG, s=T.STRING)
    data = {"a": [1, None, 3], "b": [10, 20, None], "s": ["x", "yy", None]}
    batch = ColumnarBatch.from_pydict(data, schema)
    got = np.asarray(hk.murmur3_hash(list(batch.columns)))[:3]
    for i in range(3):
        expect = hk.py_murmur3_row(
            [data["a"][i], data["b"][i], data["s"][i]],
            [T.INT, T.LONG, T.STRING])
        assert got[i] == expect


# -- selection --------------------------------------------------------------

def test_filter_compaction():
    import jax.numpy as jnp
    schema = Schema.of(a=T.INT, s=T.STRING)
    batch = ColumnarBatch.from_pydict(
        {"a": [1, 2, None, 4, 5], "s": ["aa", "b", "cc", None, "eee"]}, schema)
    pred = jnp.asarray(np.array([True, False, True, True, False, False, False, False]))
    out = sel.filter_batch(batch, pred)
    assert out.to_pydict() == {"a": [1, None, 4], "s": ["aa", "cc", None]}
    # canonical: string offsets flat past live rows
    c = out.columns[1].canonicalize(out.num_rows)
    offs = np.asarray(c.offsets)
    assert (offs[4:] == offs[3]).all()


def test_gather_with_repeats_and_oob():
    import jax.numpy as jnp
    col = DeviceColumn.from_strings(["aa", "b", None, "dddd"])
    idx = jnp.asarray(np.array([3, 3, 0, sel.OOB, 1], dtype=np.int32))
    out = sel.gather_column(col, idx, jnp.asarray(5, jnp.int32),
                            out_capacity=8, out_byte_capacity=32)
    assert out.to_pylist(5) == ["dddd", "dddd", "aa", None, "b"]


def test_concat_batches():
    schema = Schema.of(a=T.INT, s=T.STRING)
    b1 = ColumnarBatch.from_pydict({"a": [1, 2], "s": ["x", None]}, schema)
    b2 = ColumnarBatch.from_pydict({"a": [None, 4], "s": ["yy", "zzz"]}, schema)
    out, status = sel.concat_batches_device([b1, b2], out_capacity=8)
    assert out.to_pydict() == {"a": [1, 2, None, 4], "s": ["x", None, "yy", "zzz"]}
    assert not status.exceeded(8, [])


def test_concat_overflow_reported():
    schema = Schema.of(a=T.INT)
    b1 = ColumnarBatch.from_pydict({"a": [1, 2, 3]}, schema)
    b2 = ColumnarBatch.from_pydict({"a": [4, 5, 6]}, schema)
    out, status = sel.concat_batches_device([b1, b2], out_capacity=4)
    assert int(status.required_rows) == 6
    assert status.exceeded(4, [])
    assert out.host_num_rows() == 4  # truncated but self-consistent


def test_gather_checked_reports_byte_overflow():
    import jax.numpy as jnp
    schema = Schema.of(s=T.STRING)
    batch = ColumnarBatch.from_pydict({"s": ["abcd", "efgh"]}, schema)
    idx = jnp.asarray(np.array([0, 1, 0, 1], dtype=np.int32))
    out, status = sel.gather_batch_checked(batch, idx, jnp.asarray(4, jnp.int32),
                                           out_capacity=4)
    # needs 16 bytes, source byte capacity is 8 -> must be reported
    assert int(status.required_bytes[0]) == 16
    assert status.exceeded(4, [batch.columns[0].byte_capacity])
    # and with explicit larger byte capacity it's correct
    out2, status2 = sel.gather_batch_checked(batch, idx, jnp.asarray(4, jnp.int32),
                                             out_capacity=4, out_byte_capacities=[16])
    assert not status2.exceeded(4, [16])
    assert out2.to_pydict() == {"s": ["abcd", "efgh", "abcd", "efgh"]}


# -- sort -------------------------------------------------------------------

def _py_sort_oracle(rows, orders):
    """Independent reference: python sort with Spark comparison rules."""
    import functools, math

    def cmp_val(a, b):
        if isinstance(a, float) or isinstance(b, float):
            # Java Double.compare total order via bit manipulation
            import struct
            def bits(x):
                u = struct.unpack("<Q", struct.pack("<d", x))[0]
                return (~u) & 0xFFFFFFFFFFFFFFFF if u >> 63 else u | (1 << 63)
            return (bits(a) > bits(b)) - (bits(a) < bits(b))
        return (a > b) - (a < b)

    def cmp_row(ra, rb):
        for (ci, order) in orders:
            a, b = ra[ci], rb[ci]
            if a is None and b is None:
                continue
            if a is None:
                return -1 if order.nulls_first else 1
            if b is None:
                return 1 if order.nulls_first else -1
            c = cmp_val(a, b)
            if c:
                return c if order.ascending else -c
        return 0

    return sorted(rows, key=functools.cmp_to_key(cmp_row))


@pytest.mark.parametrize("asc,nf", [(True, True), (True, False), (False, True), (False, False)])
def test_sort_single_key_int(asc, nf):
    vals = [5, None, 3, 8, None, 1, 3, -7]
    batch = ColumnarBatch.from_pydict({"a": vals}, Schema.of(a=T.INT))
    order = sk.SortOrder(asc, nf)
    out = sk.sort_batch(batch, [0], [order])
    rows = [(v,) for v in vals]
    expect = [r[0] for r in _py_sort_oracle(rows, [(0, order)])]
    assert out.to_pydict()["a"] == expect


def test_sort_double_total_order():
    vals = [1.5, -0.0, 0.0, float("nan"), float("inf"), float("-inf"), None, -2.5]
    batch = ColumnarBatch.from_pydict({"a": vals}, Schema.of(a=T.DOUBLE))
    out = sk.sort_batch(batch, [0], [sk.SortOrder(True, True)])
    got = out.to_pydict()["a"]
    assert got[0] is None
    assert got[1] == float("-inf")
    assert got[2] == -2.5
    # -0.0 sorts before 0.0 (Java Double.compare)
    import math
    assert math.copysign(1.0, got[3]) < 0 and got[3] == 0.0
    assert got[4] == 0.0 and math.copysign(1.0, got[4]) > 0
    assert got[5] == 1.5
    assert got[6] == float("inf")
    assert math.isnan(got[7])


def test_sort_multi_key_with_strings():
    schema = Schema.of(s=T.STRING, a=T.INT)
    data = {"s": ["b", "a", None, "b", "a", "ab\x00", "ab"],
            "a": [2, 9, 5, 1, None, 0, 0]}
    batch = ColumnarBatch.from_pydict(data, schema)
    out = sk.sort_batch(batch, [0, 1],
                        [sk.SortOrder(True, True), sk.SortOrder(False, False)])
    got = out.to_pydict()
    # nulls first on s; 'ab' < 'ab\x00' < 'b'; within s='a': desc a nulls last
    assert got["s"] == [None, "a", "a", "ab", "ab\x00", "b", "b"]
    assert got["a"] == [5, 9, None, 0, 0, 2, 1]


def test_sort_stability():
    schema = Schema.of(k=T.INT, v=T.INT)
    data = {"k": [1, 1, 1, 0, 0], "v": [10, 20, 30, 40, 50]}
    batch = ColumnarBatch.from_pydict(data, schema)
    out = sk.sort_batch(batch, [0], [sk.SortOrder(True, True)])
    assert out.to_pydict()["v"] == [40, 50, 10, 20, 30]


# -- groupby ----------------------------------------------------------------

def test_groupby_sum_count_min_max():
    import jax.numpy as jnp
    schema = Schema.of(k=T.INT, v=T.LONG)
    data = {"k": [1, 2, 1, None, 2, 1, None], "v": [10, 20, 30, 40, None, 50, 60]}
    batch = ColumnarBatch.from_pydict(data, schema)
    layout = gb.group_rows(batch, [0])
    keys = gb.group_keys_output(layout, [0])
    n = int(layout.num_groups)
    assert n == 3
    vcol = layout.sorted_batch.columns[1]
    s, sv = gb.seg_sum(vcol, layout, jnp.int64)
    c, _ = gb.seg_count_valid(vcol, layout)
    mn, mnv = gb.seg_min(vcol, layout)
    mx, _ = gb.seg_max(vcol, layout)
    key_list = keys[0].to_pylist(n)
    sums = gb.finalize_agg_column(s, sv, layout.num_groups, T.LONG).to_pylist(n)
    counts = gb.finalize_agg_column(c, jnp.ones_like(c, dtype=bool), layout.num_groups, T.LONG).to_pylist(n)
    mins = gb.finalize_agg_column(mn, mnv, layout.num_groups, T.LONG).to_pylist(n)
    maxs = gb.finalize_agg_column(mx, mnv, layout.num_groups, T.LONG).to_pylist(n)
    got = dict(zip(key_list, zip(sums, counts, mins, maxs)))
    assert got == {
        None: (100, 2, 40, 60),
        1: (90, 3, 10, 50),
        2: (20, 1, 20, 20),
    }


def test_groupby_float_normalization():
    schema = Schema.of(k=T.DOUBLE, v=T.INT)
    data = {"k": [0.0, -0.0, float("nan"), float("nan")], "v": [1, 2, 3, 4]}
    batch = ColumnarBatch.from_pydict(data, schema)
    layout = gb.group_rows(batch, [0])
    assert int(layout.num_groups) == 2  # {0.0,-0.0} and {nan,nan}


def test_groupby_all_null_group_sum_is_null():
    import jax.numpy as jnp
    schema = Schema.of(k=T.INT, v=T.INT)
    data = {"k": [7, 7], "v": [None, None]}
    batch = ColumnarBatch.from_pydict(data, schema)
    layout = gb.group_rows(batch, [0])
    vcol = layout.sorted_batch.columns[1]
    s, sv = gb.seg_sum(vcol, layout, jnp.int64)
    out = gb.finalize_agg_column(s, sv, layout.num_groups, T.LONG)
    assert out.to_pylist(1) == [None]


def test_groupby_string_keys():
    schema = Schema.of(k=T.STRING, v=T.INT)
    data = {"k": ["aa", "bb", "aa", None, "bb", "aa"], "v": [1, 2, 3, 4, 5, 6]}
    batch = ColumnarBatch.from_pydict(data, schema)
    layout = gb.group_rows(batch, [0])
    import jax.numpy as jnp
    assert int(layout.num_groups) == 3
    keys = gb.group_keys_output(layout, [0])[0].to_pylist(3)
    vcol = layout.sorted_batch.columns[1]
    s, sv = gb.seg_sum(vcol, layout, jnp.int64)
    sums = gb.finalize_agg_column(s, sv, layout.num_groups, T.LONG).to_pylist(3)
    assert dict(zip(keys, sums)) == {None: 4, "aa": 10, "bb": 7}


@pytest.mark.parametrize("keys,hashed", [
    (("k",), False), (("s",), False), (("s",), True), (("f", "k"), False),
    (("s", "k"), True)])
def test_group_rows_under_a_mask_is_group_rows_of_the_compacted_batch(
        keys, hashed):
    """``live`` in the place of ``batch.live_mask()``: the layout is the
    one the compacted batch gets, row for row (the stable sort keeps the
    rows that count in their order), whatever sat in the rows between."""
    import jax.numpy as jnp
    rng = np.random.RandomState(5)
    n = 300                              # capacity 512: padding after them
    schema = Schema.of(k=T.INT, s=T.STRING, f=T.DOUBLE, v=T.LONG)
    data = {"k": rng.randint(0, 5, n).tolist(),
            "s": [("R", "A", "N", "a longer one")[i]
                  for i in rng.randint(0, 4, n)],
            "f": rng.choice([0.0, -0.0, 1.5, float("nan")], n).tolist(),
            "v": rng.randint(-99, 99, n).tolist()}
    for c in data:
        for i in rng.choice(n, n // 8, replace=False):
            data[c][i] = None
    batch = ColumnarBatch.from_pydict(data, schema)
    kept = rng.rand(batch.capacity) < 0.6
    mask = jnp.asarray(kept) & batch.live_mask()
    key_cols = [schema.names.index(k) for k in keys]
    got = gb.group_rows(batch, key_cols, string_max_bytes=16,
                        allow_split_groups=hashed, live=mask)
    want = gb.group_rows(sel.filter_batch(batch, mask), key_cols,
                         string_max_bytes=16, allow_split_groups=hashed)
    rows = int(want.sorted_batch.num_rows)
    assert int(got.sorted_batch.num_rows) == rows == int(kept[:n].sum())
    assert int(got.num_groups) == int(want.num_groups)
    # NaN keys: compare as text
    assert repr(got.sorted_batch.to_pydict()) == \
        repr(want.sorted_batch.to_pydict())
    assert got.segment_ids.tolist() == want.segment_ids.tolist()
    assert got.boundary.tolist() == want.boundary.tolist()
    # and the rows past the prefix are canonical padding, as after a compaction
    for c, wc in zip(got.sorted_batch.columns, want.sorted_batch.columns):
        assert not bool(c.validity[rows:].any())
        assert repr(c.data.tolist()) == repr(wc.data.tolist())


# -- partition --------------------------------------------------------------

def test_hash_partition_matches_oracle_routing():
    n_parts = 4
    vals = [1, 2, 3, None, 5, 6, 7, 8, 9, 10, 11, 12]
    batch = ColumnarBatch.from_pydict({"k": vals}, Schema.of(k=T.INT))
    out, counts = pk.hash_partition(batch, [0], n_parts)
    got_rows = out.to_pydict()["k"]
    counts = np.asarray(counts)
    # oracle routing
    def route(v):
        h = hk.py_murmur3_row([v], [T.INT])
        return ((h % n_parts) + n_parts) % n_parts
    expect_parts = {}
    for v in vals:
        expect_parts.setdefault(route(v), []).append(v)
    # reconstruct slices
    offs = np.concatenate([[0], np.cumsum(counts)])
    for p in range(n_parts):
        assert got_rows[offs[p]:offs[p + 1]] == expect_parts.get(p, [])


def test_round_robin_partition():
    batch = ColumnarBatch.from_pydict({"k": [0, 1, 2, 3, 4]}, Schema.of(k=T.INT))
    out, counts = pk.round_robin_partition(batch, 2)
    assert np.asarray(counts).tolist() == [3, 2]
    assert out.to_pydict()["k"] == [0, 2, 4, 1, 3]


def test_hash_partition_long_strings_auto_bucket():
    # regression: strings longer than any default bucket must still route
    # bit-exactly (the bucket is derived from the data)
    vals = ["x" * 70, "x" * 70 + "y", "short", None]
    batch = ColumnarBatch.from_pydict({"k": vals}, Schema.of(k=T.STRING))
    out, counts = pk.hash_partition(batch, [0], 8)
    offs = np.concatenate([[0], np.cumsum(np.asarray(counts))])
    rows = out.to_pydict()["k"]
    for p in range(8):
        for v in rows[offs[p]:offs[p + 1]]:
            h = hk.py_murmur3_row([v], [T.STRING])
            assert ((h % 8) + 8) % 8 == p


def test_groupby_min_max_nan_spark_semantics():
    """Spark's total order puts NaN above +Inf: MIN skips NaN unless the
    whole group is NaN; MAX returns NaN if any value is NaN."""
    import math
    import jax.numpy as jnp
    schema = Schema.of(k=T.INT, v=T.DOUBLE)
    nan = float("nan")
    data = {"k": [1, 1, 2, 2, 3], "v": [nan, 1.0, nan, nan, 5.0]}
    batch = ColumnarBatch.from_pydict(data, schema)
    layout = gb.group_rows(batch, [0])
    keys = gb.group_keys_output(layout, [0])
    n = int(layout.num_groups)
    vcol = layout.sorted_batch.columns[1]
    mn, mnv = gb.seg_min(vcol, layout)
    mx, mxv = gb.seg_max(vcol, layout)
    mins = gb.finalize_agg_column(mn, mnv, layout.num_groups, T.DOUBLE).to_pylist(n)
    maxs = gb.finalize_agg_column(mx, mxv, layout.num_groups, T.DOUBLE).to_pylist(n)
    got = {k: (mins[i], maxs[i]) for i, k in enumerate(keys[0].to_pylist(n))}
    assert got[1][0] == 1.0           # min skips NaN
    assert math.isnan(got[1][1])      # max is NaN (NaN greatest)
    assert math.isnan(got[2][0]) and math.isnan(got[2][1])  # all-NaN group
    assert got[3] == (5.0, 5.0)


def test_f64_tpu_split_key_order_and_injectivity(monkeypatch):
    """The TPU double-double sort key (no f64 bitcast exists on chip)
    must order like the exact-bits key for every value REPRESENTABLE
    under the f32-pair emulation, and stay injective on them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.kernels import sort as SK

    vals = np.array(
        [float("-inf"), -1e30, -3.5, -1.0000001, -1.0, -0.0, 0.0,
         1e-38, 1.0, 1.5, 2.0 ** 20 + 0.25, 1e30, float("inf"),
         float("nan")], np.float64)
    # exact path (CPU backend default)
    exact = np.asarray(SK.f64_total_order_u64(jnp.asarray(vals)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    split = np.asarray(SK.f64_total_order_u64(jnp.asarray(vals)))
    # same relative order
    assert list(np.argsort(exact, kind="stable")) == \
        list(np.argsort(split, kind="stable"))
    # near-injective: at most one sub-f32-resolution tie among these
    # values (the split loses residuals below the f32 denormal floor —
    # exactly the values the f32-pair emulation cannot hold either)
    finite = split[:-1]
    assert len(np.unique(finite)) >= len(finite) - 1
    # -0.0 < 0.0 must hold in BOTH encodings
    i_neg0, i_pos0 = 5, 6
    assert exact[i_neg0] < exact[i_pos0]
    assert split[i_neg0] < split[i_pos0]
    # NaN above +inf
    assert split[-1] > split[-2]
