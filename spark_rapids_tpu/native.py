"""ctypes bindings for the native host runtime (libtpurapids.so).

The framework's spark-rapids-jni analog (SURVEY.md §2.1): the shuffle wire
serializer ("tpu-kudo", native/kudo.cpp) and the row<->columnar converter
(native/rowconv.cpp) run as C++ — these sit on host hot paths where a
Python loop would dominate.

Build: lazily compiled with g++ on first use (no pip) into native/build/
(git-ignored), keyed by the sources' content hash.  A failed build raises.
Set SPARK_RAPIDS_TPU_NO_NATIVE=1 to force the pure-Python fallbacks (used
to differential-test the native code itself).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC_DIR = os.path.join(_REPO, "native")
_BUILD_DIR = os.path.join(_SRC_DIR, "build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


class TkCol(ctypes.Structure):
    _fields_ = [
        ("validity", ctypes.c_void_p),
        ("offsets", ctypes.c_void_p),
        ("data", ctypes.c_void_p),
        ("data_bytes", ctypes.c_uint64),
        ("dtype_code", ctypes.c_uint8),
    ]


class TkOut(ctypes.Structure):
    _fields_ = [
        ("validity", ctypes.c_void_p),
        ("offsets", ctypes.c_void_p),
        ("data", ctypes.c_void_p),
        ("row_capacity", ctypes.c_uint64),
        ("data_capacity", ctypes.c_uint64),
    ]


class RcCol(ctypes.Structure):
    _fields_ = [
        ("validity", ctypes.c_void_p),
        ("offsets", ctypes.c_void_p),
        ("data", ctypes.c_void_p),
        ("byte_width", ctypes.c_uint32),
    ]


_SOURCES = ("kudo.cpp", "rowconv.cpp")
# no -march=native: the build directory is not part of a checkout but IS
# part of a disk copy, so a binary may run on another host than built it
_CXXFLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")


def _build() -> str:
    """Compile the library unless a build of exactly these sources and
    flags exists: the content hash is part of the file name, so a stale or
    foreign binary is never picked up, and concurrent builders (xdist
    workers on a fresh checkout) race only to an atomic rename."""
    import hashlib
    srcs = [os.path.join(_SRC_DIR, f) for f in _SOURCES]
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    so_path = os.path.join(_BUILD_DIR,
                           f"libtpurapids-{h.hexdigest()[:16]}.so")
    if os.path.exists(so_path):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", *_CXXFLAGS, "-o", tmp, *srcs], check=True,
                       capture_output=True, text=True, timeout=300)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"building {so_path} failed:\n{e.stderr}\n(set "
            "SPARK_RAPIDS_TPU_NO_NATIVE=1 for the pure-Python "
            "serializer)") from e
    os.replace(tmp, so_path)
    return so_path


def lib() -> Optional[ctypes.CDLL]:
    """The native library; None only under SPARK_RAPIDS_TPU_NO_NATIVE.  A
    build that fails raises — silently dropping every caller to the
    pure-Python serializer would hide a broken host path."""
    global _lib
    if os.environ.get("SPARK_RAPIDS_TPU_NO_NATIVE"):
        return None
    with _lock:
        if _lib is None:
            # tpu-lint: allow-lock-order(one-time double-checked build; holding the lock prevents two threads compiling the native lib)
            l = ctypes.CDLL(_build())
            l.tk_serialized_size.restype = ctypes.c_uint64
            l.tk_serialize.restype = ctypes.c_uint64
            l.tk_serialize_range.restype = ctypes.c_uint64
            l.tk_row_count.restype = ctypes.c_uint64
            l.tk_col_count.restype = ctypes.c_uint32
            l.tk_merge.restype = ctypes.c_uint64
            l.trow_sizes.restype = ctypes.c_uint64
            _lib = l
        return _lib


def available() -> bool:
    return lib() is not None


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


# ---------------------------------------------------------------------------
# tpu-kudo serializer API (host arrays in, bytes out and back)


def kudo_serialize(cols: List[Tuple[np.ndarray, Optional[np.ndarray],
                                    np.ndarray]], num_rows: int) -> bytes:
    """cols: [(validity bool[rows], offsets i32[rows+1]|None, data u8/any)].

    data for fixed-width columns must be exactly rows*itemsize bytes;
    for strings exactly offsets[rows] bytes.
    """
    l = lib()
    assert l is not None
    n = len(cols)
    carr = (TkCol * n)()
    keep = []   # keep arrays alive
    for i, (valid, offsets, data) in enumerate(cols):
        valid = np.ascontiguousarray(valid.astype(np.uint8))
        data = np.ascontiguousarray(data)
        keep += [valid, data]
        carr[i].validity = _ptr(valid).value
        if offsets is not None:
            offsets = np.ascontiguousarray(offsets.astype(np.int32))
            keep.append(offsets)
            carr[i].offsets = _ptr(offsets).value
            carr[i].data_bytes = int(offsets[num_rows])
        else:
            carr[i].offsets = None
            carr[i].data_bytes = data.nbytes
        carr[i].data = _ptr(data).value
        carr[i].dtype_code = 0
    size = l.tk_serialized_size(carr, n, num_rows)
    out = np.zeros((size,), np.uint8)
    written = l.tk_serialize(carr, n, num_rows, _ptr(out))
    assert written == size
    return out.tobytes()


def kudo_serialize_ranges(cols: List[Tuple[np.ndarray, Optional[np.ndarray],
                                           np.ndarray]],
                          bounds: np.ndarray,
                          prefix: bytes = b"") -> List[Optional[bytes]]:
    """Range serialization: frame one wire block per row range of a
    partition-ordered batch (the map-side contiguous-split path).

    cols: [(validity bool/u8[total_rows], offsets i32[total_rows+1]|None,
    data)] host arrays of the WHOLE batch; bounds: int[nparts+1] row
    bounds (exclusive cumsum of per-partition counts).  Returns one
    payload per partition (None for empty ranges), each byte-identical
    to serializing that range's rows alone — string offsets are rebased
    in C, everything else is pointer arithmetic into the shared arrays.
    ``prefix`` bytes (e.g. the uncompressed-codec wire tag) are laid
    down in the output buffer before serialization so the caller's
    final block needs no second full-payload copy.
    """
    l = lib()
    assert l is not None
    ncols = len(cols)
    prepared = []
    for valid, offsets, data in cols:
        prepared.append((np.ascontiguousarray(valid, dtype=np.uint8),
                         None if offsets is None else
                         np.ascontiguousarray(offsets, dtype=np.int32),
                         np.ascontiguousarray(data)))
    carr = (TkCol * ncols)()
    out: List[Optional[bytes]] = []
    for p in range(len(bounds) - 1):
        s, e = int(bounds[p]), int(bounds[p + 1])
        n = e - s
        if n == 0:
            out.append(None)
            continue
        # the views below are pointer arithmetic only; the buffers stay
        # alive because `prepared` owns every base for the whole call
        for i, (valid, offsets, data) in enumerate(prepared):
            carr[i].validity = _ptr(valid[s:]).value
            if offsets is not None:
                carr[i].offsets = _ptr(offsets[s:]).value
                carr[i].data = _ptr(data[int(offsets[s]):]).value
                carr[i].data_bytes = int(offsets[e]) - int(offsets[s])
            else:
                carr[i].offsets = None
                carr[i].data = _ptr(data[s:]).value
                carr[i].data_bytes = n * data.dtype.itemsize
            carr[i].dtype_code = 0
        size = l.tk_serialized_size(carr, ncols, n)
        np_ = len(prefix)
        buf = np.zeros((np_ + size,), np.uint8)
        if np_:
            buf[:np_] = np.frombuffer(prefix, np.uint8)
        written = l.tk_serialize_range(carr, ncols, n, _ptr(buf[np_:]))
        assert written == size
        out.append(buf.tobytes())
    return out


def kudo_merge(buffers: List[bytes], col_specs, row_capacity: int):
    """Concat-merge wire buffers.

    col_specs: [(np_dtype, is_var)] per column.  Returns
    (cols, total_rows) with cols = [(validity, offsets|None, data)] sized
    to row_capacity (canonical zero padding).
    """
    l = lib()
    assert l is not None
    n_bufs = len(buffers)
    n_cols = len(col_specs)
    keep = [np.frombuffer(b, dtype=np.uint8) for b in buffers]
    bufp = (ctypes.c_void_p * n_bufs)(*[_ptr(k).value for k in keep])
    total_rows = ctypes.c_uint64()
    col_bytes = (ctypes.c_uint64 * n_cols)()
    l.tk_merge_size(bufp, n_bufs, ctypes.byref(total_rows), col_bytes)
    rows = int(total_rows.value)
    assert rows <= row_capacity, (rows, row_capacity)
    outs = (TkOut * n_cols)()
    results = []
    for c, (np_dtype, is_var) in enumerate(col_specs):
        valid = np.zeros((row_capacity,), np.uint8)
        if is_var:
            offsets = np.zeros((row_capacity + 1,), np.int32)
            data = np.zeros((max(int(col_bytes[c]), 1),), np.uint8)
        else:
            offsets = None
            width = np.dtype(np_dtype).itemsize
            data = np.zeros((row_capacity,), np_dtype)
        outs[c].validity = _ptr(valid).value
        outs[c].offsets = _ptr(offsets).value if offsets is not None else None
        outs[c].data = _ptr(data).value
        outs[c].row_capacity = row_capacity
        outs[c].data_capacity = data.nbytes
        results.append((valid, offsets, data))
    merged = l.tk_merge(bufp, n_bufs, outs, n_cols)
    assert merged == rows
    return results, rows


# ---------------------------------------------------------------------------
# row <-> columnar API


def rows_from_columns(cols, num_rows: int):
    """cols like kudo_serialize's.  Returns (rows_buf bytes, row_offsets)."""
    l = lib()
    assert l is not None
    n = len(cols)
    carr = (RcCol * n)()
    keep = []
    for i, (valid, offsets, data) in enumerate(cols):
        valid = np.ascontiguousarray(valid.astype(np.uint8))
        data = np.ascontiguousarray(data)
        keep += [valid, data]
        carr[i].validity = _ptr(valid).value
        if offsets is not None:
            offsets = np.ascontiguousarray(offsets.astype(np.int32))
            keep.append(offsets)
            carr[i].offsets = _ptr(offsets).value
            carr[i].byte_width = 0
        else:
            carr[i].offsets = None
            carr[i].byte_width = data.dtype.itemsize
        carr[i].data = _ptr(data).value
    sizes = np.zeros((max(num_rows, 1),), np.uint64)
    total = l.trow_sizes(carr, n, num_rows, _ptr(sizes))
    out = np.zeros((max(int(total), 1),), np.uint8)
    row_offsets = np.zeros((num_rows + 1,), np.uint64)
    l.trow_from_columns(carr, n, num_rows, _ptr(out), _ptr(row_offsets))
    return out.tobytes(), row_offsets


def columns_from_rows(rows_buf: bytes, row_offsets: np.ndarray,
                      col_specs, row_capacity: int):
    """Inverse of rows_from_columns.  col_specs: [(np_dtype, is_var)]."""
    l = lib()
    assert l is not None
    num_rows = len(row_offsets) - 1
    n = len(col_specs)
    carr = (RcCol * n)()
    buf = np.frombuffer(rows_buf, dtype=np.uint8)
    offs = np.ascontiguousarray(row_offsets.astype(np.uint64))
    results = []
    for i, (np_dtype, is_var) in enumerate(col_specs):
        valid = np.zeros((row_capacity,), np.uint8)
        if is_var:
            offsets = np.zeros((row_capacity + 1,), np.int32)
            data = np.zeros((max(len(rows_buf), 1),), np.uint8)
            carr[i].byte_width = 0
        else:
            offsets = None
            data = np.zeros((row_capacity,), np_dtype)
            carr[i].byte_width = np.dtype(np_dtype).itemsize
        carr[i].validity = _ptr(valid).value
        carr[i].offsets = _ptr(offsets).value if offsets is not None else None
        carr[i].data = _ptr(data).value
        results.append((valid, offsets, data))
    l.trow_to_columns(_ptr(buf), _ptr(offs), num_rows, carr, n)
    return results
