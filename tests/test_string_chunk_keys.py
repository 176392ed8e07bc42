"""String grouping keys as integers (PR 34): the chunk keys are built once,
in as many byte steps as the longest string present has bytes; the sort, the
group boundaries and the key output read them, and the grouping moves no key
column.  Kernels only; the engine-level cases are in test_string_keys.py,
test_fused.py and test_agg_tail.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.kernels import groupby as gb
from spark_rapids_tpu.kernels import selection as sel
from spark_rapids_tpu.kernels import sort as sk


def _unrolled_chunk_keys(col, order, max_bytes):
    """The reference: one gather a byte position of the static bucket,
    unrolled, as ``_string_data_keys`` was before it followed the data."""
    starts = col.offsets[:-1]
    lengths = col.offsets[1:] - starts
    n_chunks = max(1, -(-max_bytes // sk.BYTES_PER_CHUNK))
    keys = []
    for c in range(n_chunks):
        chunk = jnp.zeros((col.capacity,), dtype=jnp.uint64)
        for b in range(sk.BYTES_PER_CHUNK):
            pos = c * sk.BYTES_PER_CHUNK + b
            idx = jnp.clip(starts + pos, 0, col.data.shape[0] - 1)
            lane = jnp.where(pos < lengths,
                             col.data[idx].astype(jnp.uint64) + 1,
                             jnp.uint64(0))
            chunk = (chunk << 9) | lane
        if not order.ascending:
            chunk = ~chunk
        keys.append(jnp.where(col.validity, chunk, jnp.uint64(0)))
    return keys


def _strings_up_to(length, rng):
    """Strings whose longest has exactly ``length`` bytes: every shorter
    length, the empty string, nulls, a 0x00 byte next to ASCII, and
    multi-byte UTF-8 (bytes up to 0xf0) where it fits."""
    alphabet = ["a", "Z", "\x00", "é", "€", "😀", "~"]
    out = [None, "", None]
    for n in sorted({0, min(1, length), length // 2, max(length - 1, 0),
                     length}):
        for _ in range(4):
            s = ""
            while len(s.encode()) < n:
                ch = alphabet[rng.randint(len(alphabet))]
                if len((s + ch).encode()) <= n:
                    s += ch
                else:
                    s += "b"
            out.append(s)
    out.append("a" * length)
    rng.shuffle(out)
    assert max(len(s.encode()) for s in out if s is not None) == length
    return out


def _string_column(values, capacity=None):
    batch = ColumnarBatch.from_pydict({"s": values}, Schema.of(s=T.STRING),
                                      capacity=capacity)
    return batch.columns[0]


@pytest.mark.parametrize("ascending", [True, False], ids=["asc", "desc"])
@pytest.mark.parametrize("length,bucket", [
    (0, 16), (1, 16), (6, 16), (7, 16), (8, 16), (15, 16), (16, 16),
    (17, 16), (33, 64)])
def test_chunk_keys_equal_the_unrolled_reference(length, bucket, ascending):
    """Bit for bit, nulls, empty strings, multi-byte UTF-8 and the padded
    tail (capacity 64 over some 25 rows) included; 17 bytes under bucket 16
    is past the bucket and inside its third chunk, which holds 21."""
    values = _strings_up_to(length, np.random.RandomState(length + bucket))
    col = _string_column(values, capacity=64)
    order = sk.SortOrder(ascending)
    got = sk._string_data_keys(col, order, bucket)
    want = _unrolled_chunk_keys(col, order, bucket)
    assert len(got) == len(want) == -(-bucket // 7)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.uint64
        assert g.tolist() == w.tolist()
    _, steps = sk._string_chunk_planes(col, bucket)
    assert int(steps) == min(length, 7 * len(want))


def test_chunk_keys_stop_at_the_last_chunk_of_the_bucket():
    """A key past the bucket is cut where it was cut before: at the end of
    the bucket's last chunk."""
    col = _string_column(["x" * 40, "x" * 21 + "y" * 19, "x" * 20 + "y"])
    got = sk._string_data_keys(col, sk.SortOrder(True), 16)
    want = _unrolled_chunk_keys(col, sk.SortOrder(True), 16)
    assert [g.tolist() for g in got] == [w.tolist() for w in want]
    assert got[2][0] == got[2][1] != got[2][2]
    assert int(sk._string_chunk_planes(col, 16)[1]) == 21


def _count_eqns(jaxpr, pred, in_loop=False):
    """(eqns matching ``pred`` inside a while body, outside one), through
    every nested jaxpr."""
    inside = outside = 0
    for eqn in jaxpr.eqns:
        if pred(eqn):
            inside, outside = inside + in_loop, outside + (not in_loop)
        for name, sub in eqn.params.items():
            subs = sub if isinstance(sub, (list, tuple)) else [sub]
            for j in subs:
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    i, o = _count_eqns(
                        j, pred, in_loop or (eqn.primitive.name == "while"
                                             and name == "body_jaxpr"))
                    inside, outside = inside + i, outside + o
    return inside, outside


def _is_byte_gather(eqn):
    return (eqn.primitive.name == "gather"
            and eqn.invars[0].aval.dtype == jnp.uint8)


@pytest.mark.parametrize("hashed", [True, False], ids=["hashed", "chunks"])
def test_group_rows_on_two_string_keys_gathers_bytes_in_its_loops_only(
        hashed):
    """One u8 gather a key column, in the body of the byte loop whose trip
    count is the longest key's length: none unrolled (there were 84 under
    bucket 16: 21 a column, for the sort and again for the boundaries), and
    none for moving the key columns (the grouping moves none)."""
    schema = Schema.of(f=T.STRING, s=T.STRING, v=T.LONG)
    batch = ColumnarBatch.from_pydict(
        {"f": ["R", "A", "N", "R"], "s": ["O", "F", "O", "O"],
         "v": [1, 2, 3, 4]}, schema)

    def grouped(b):
        layout = gb.group_rows(b, [0, 1], string_max_bytes=16,
                               allow_split_groups=hashed)
        return (layout.indices, layout.boundary, layout.segment_ids,
                layout.num_groups, layout.sorted_column(2))

    inside, outside = _count_eqns(jax.make_jaxpr(grouped)(batch).jaxpr,
                                  _is_byte_gather)
    assert outside == 0
    assert 1 <= inside <= 2
    for ci in (0, 1):
        assert int(sk._string_chunk_planes(batch.columns[ci], 16)[1]) == 1


def _groups(batch, key_cols, **kw):
    layout = gb.group_rows(batch, key_cols, **kw)
    n = int(layout.num_groups)
    keys = [c.to_pylist(n) for c in gb.group_keys_output(layout, key_cols)]
    return n, sorted(zip(*keys), key=repr)


@pytest.mark.parametrize("hashed", [True, False], ids=["hashed", "chunks"])
def test_a_key_as_long_as_the_bucket_is_whole_and_a_longer_one_is_cut_as_before(
        hashed):
    schema = Schema.of(k=T.STRING)
    full = ["a" * 15 + "x", "a" * 15 + "y", "a" * 15 + "x"]
    n, keys = _groups(ColumnarBatch.from_pydict({"k": full}, schema), [0],
                      string_max_bytes=16, allow_split_groups=hashed)
    assert (n, keys) == (2, [("a" * 15 + "x",), ("a" * 15 + "y",)])
    # past the bucket: bytes 16..20 still sit in the third chunk, and the
    # lengths are compared, so only equal lengths that differ from byte 21
    # on merge (the caller's bucket never lets a live key get there)
    longer = ["b" * 21 + "x", "b" * 21 + "y", "b" * 20 + "zz", "b" * 21 + "xx"]
    n, _ = _groups(ColumnarBatch.from_pydict({"k": longer}, schema), [0],
                   string_max_bytes=16, allow_split_groups=hashed)
    assert n == 3


@pytest.mark.parametrize("hashed", [True, False], ids=["hashed", "chunks"])
def test_group_rows_under_a_mask_on_two_string_keys_is_compaction_then_group(
        hashed):
    """Groups, key values, ``num_groups`` and every column in sorted order:
    those of the compacted batch, whatever sat in the rows between."""
    rng = np.random.RandomState(11)
    n = 300                              # capacity 512: padding after them
    schema = Schema.of(f=T.STRING, s=T.STRING, v=T.LONG)
    data = {"f": [("R", "A", "N", "", "a longer one")[i]
                  for i in rng.randint(0, 5, n)],
            "s": [("O", "F", "é")[i] for i in rng.randint(0, 3, n)],
            "v": rng.randint(-99, 99, n).tolist()}
    for c in data:
        for i in rng.choice(n, n // 8, replace=False):
            data[c][i] = None
    batch = ColumnarBatch.from_pydict(data, schema)
    kept = rng.rand(batch.capacity) < 0.6
    mask = jnp.asarray(kept) & batch.live_mask()
    kw = dict(string_max_bytes=16, allow_split_groups=hashed)
    got = gb.group_rows(batch, [0, 1], live=mask, **kw)
    want = gb.group_rows(sel.filter_batch(batch, mask), [0, 1], **kw)
    groups = int(want.num_groups)
    assert int(got.num_groups) == groups
    assert int(got.num_rows) == int(want.num_rows) == int(kept[:n].sum())
    assert got.segment_ids.tolist() == want.segment_ids.tolist()
    assert got.boundary.tolist() == want.boundary.tolist()
    assert got.sorted_batch.to_pydict() == want.sorted_batch.to_pydict()
    for cap in (None, 32):
        gk = gb.group_keys_output(got, [0, 1], out_capacity=cap,
                                  string_max_bytes=16)
        wk = gb.group_keys_output(want, [0, 1], out_capacity=cap,
                                  string_max_bytes=16)
        for g, w in zip(gk, wk):
            assert g.capacity == (cap or batch.capacity)
            assert g.to_pylist(groups) == w.to_pylist(groups)
    # the distinct keys of the rows that count, nulls a key of their own
    rows = {(data["f"][i], data["s"][i]) for i in range(n) if kept[i]}
    keys = {tuple(k) for k in zip(*(c.to_pylist(groups) for c in gk))}
    assert keys == rows and (hashed or groups == len(rows))


def test_group_keys_come_from_the_unsorted_batch_at_the_group_starts():
    """A string key is read through the order, a fixed-width key from its
    sorted copy: the same rows either way, in key order, and a key column
    that nobody asked for in sorted order was not moved."""
    schema = Schema.of(s=T.STRING, k=T.INT, v=T.LONG)
    data = {"s": ["bb", None, "a", "bb", "a", None, "ccc"],
            "k": [2, 2, 1, 2, None, 2, 1], "v": [1, 2, 3, 4, 5, 6, 7]}
    batch = ColumnarBatch.from_pydict(data, schema)
    layout = gb.group_rows(batch, [0, 1], string_max_bytes=16)
    assert sorted(layout._sorted) == [1]
    n = int(layout.num_groups)
    s, k = (c.to_pylist(n)
            for c in gb.group_keys_output(layout, [0, 1], string_max_bytes=16))
    assert list(zip(s, k)) == [(None, 2), ("a", None), ("a", 1), ("bb", 2),
                               ("ccc", 1)]
    assert sorted(layout._sorted) == [1]
    sums, _ = gb.seg_sum(layout.sorted_column(2), layout, jnp.int64)
    assert sums[:n].tolist() == [8, 5, 3, 5, 7]
    assert sorted(layout._sorted) == [1, 2]
