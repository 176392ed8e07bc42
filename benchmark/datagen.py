"""Tables from a seed, written as Parquet.  The benchmark's own generator:
nothing here imports the program.

What every chunk needs of the whole table comes from the table file's
``prepare(seed, rows, scale_factor)``; the rows of each chunk of
``row_group_rows`` are drawn from ``default_rng([seed, chunk_index])`` alone,
so the bytes depend on the seed and the sizes and on nothing else (not on
threads, not on the order in which chunks are made).  Each file is one scan
partition; each chunk is one row group, so a scan batch never spans two.
"""
import collections
import concurrent.futures
import itertools
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

BYTE_WIDTH = {"int64": 8, "int32": 4, "decimal(12,2)": 8, "date32": 4}
MAKER_THREADS = 12      # chunks are made on this many threads at the most
CHUNKS_AHEAD = 2        # of each file's writer: bounds what is held in memory


def _string_column(values) -> pa.Array:
    """``(codes, dictionary)``: one of a few values a row; or ``(lengths,
    bytes matrix)``: row i is the first ``lengths[i]`` bytes of row i."""
    first, second = values
    if isinstance(second, list):
        return pa.DictionaryArray.from_arrays(
            pa.array(first, type=pa.int8()),
            pa.array(second, type=pa.string())).dictionary_decode()
    offsets = np.zeros(len(first) + 1, dtype=np.int32)
    np.cumsum(first, out=offsets[1:])
    data = second[np.arange(second.shape[1]) < first[:, None]]
    return pa.Array.from_buffers(
        pa.string(), len(first),
        [None, pa.py_buffer(offsets), pa.py_buffer(data)])


def _arrow_column(values, kind: str) -> pa.Array:
    if kind == "string":
        return _string_column(values)
    if kind == "decimal(12,2)":
        # decimal128 is a little-endian 128-bit integer: low limb the
        # unscaled int64, high limb its sign extension
        limbs = np.empty((len(values), 2), dtype=np.int64)
        limbs[:, 0] = values
        limbs[:, 1] = values >> 63
        return pa.Array.from_buffers(
            pa.decimal128(12, 2), len(values),
            [None, pa.py_buffer(limbs.tobytes())])
    if kind == "date32":
        return pa.array(values, type=pa.int32()).cast(pa.date32())
    return pa.array(values, type=getattr(pa, kind)())


def _chunk_table(table_mod, seed, cid, n, group_rows, prepared) -> pa.Table:
    rng = np.random.default_rng([int(seed), int(cid)])
    cols = table_mod.chunk(rng, cid * group_rows, n, prepared)
    return pa.table({name: _arrow_column(cols[name], kind)
                     for name, kind in table_mod.SCHEMA.items()})


def _write_file(path, jobs, make, pool):
    """One file, its row groups in order; the chunks are made on ``pool``,
    ``CHUNKS_AHEAD`` of them ahead of the writer."""
    jobs = iter(jobs)
    ahead = collections.deque(pool.submit(make, *job) for job in
                              itertools.islice(jobs, CHUNKS_AHEAD))
    writer = None
    try:
        while ahead:
            tbl = ahead.popleft().result()
            for job in itertools.islice(jobs, 1):
                ahead.append(pool.submit(make, *job))
            if writer is None:
                writer = pq.ParquetWriter(path, tbl.schema)
            writer.write_table(tbl, row_group_size=len(tbl))
    finally:
        if writer is not None:
            writer.close()
    return path


def write_table(root: str, table_mod, name: str, rows: int, files: int,
                row_group_rows: int, seed: int,
                scale_factor: float = 1.0) -> list:
    """Write ``rows`` rows of the table as ``files`` Parquet files with row
    groups of ``row_group_rows``; returns the paths, in partition order."""
    prepared = table_mod.prepare(seed, rows, scale_factor)
    jobs = [(cid, min(row_group_rows, rows - lo)) for cid, lo in
            enumerate(range(0, rows, row_group_rows))]
    per = -(-len(jobs) // files)
    parts = [jobs[i:i + per] for i in range(0, len(jobs), per)]
    paths = [os.path.join(root, f"{name}-{p}.parquet")
             for p in range(len(parts))]

    def make(cid, n):
        return _chunk_table(table_mod, seed, cid, n, row_group_rows,
                            prepared)

    with concurrent.futures.ThreadPoolExecutor(
            min(os.cpu_count() or 1, MAKER_THREADS)) as makers, \
            concurrent.futures.ThreadPoolExecutor(len(parts)) as writers:
        futs = [writers.submit(_write_file, path, part, make, makers)
                for path, part in zip(paths, parts)]
        return [f.result() for f in futs]


def read_frame(paths, columns, float_type="float64"):
    """The files as a pandas frame for a plain reference: decimals as
    ``float_type``, dates as days since the epoch, strings as sorted
    categories; converted in Arrow, since ``to_pandas()`` would box every
    decimal and every string in a Python object."""
    t = pa.concat_tables(pq.read_table(p, columns=list(columns))
                         for p in paths)
    cols = {}
    for cname, col in zip(t.column_names, t.columns):
        if pa.types.is_decimal(col.type):
            col = col.cast(pa.float64())
            if float_type != "float64":
                col = col.cast(getattr(pa, float_type)())
        elif pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        elif pa.types.is_string(col.type):
            col = col.combine_chunks().dictionary_encode()
        cols[cname] = col
    frame = pa.table(cols).to_pandas()
    for cname in frame.columns[frame.dtypes == "category"]:
        frame[cname] = frame[cname].cat.reorder_categories(
            sorted(frame[cname].cat.categories))
    return frame
