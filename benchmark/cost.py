"""What a query has to move at the least, and the chip's peaks.

Least bytes of a query = rows of its table x the byte widths of the columns
its query file declares it reads (device widths: decimal(12,2) and int64 8,
int32 and date32 4; a string column the mean bytes of its values, which the
table file states).  It is the same whatever implements the query, and
every input byte crosses HBM at least once, so least time over device busy
time cannot pass 100%.
"""
import json
import os

from benchmark.datagen import BYTE_WIDTH

_HERE = os.path.dirname(os.path.abspath(__file__))


def peak(device_kind: str, key: str) -> float:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peaks recorded for device_kind {device_kind!r}; "
                       f"known: {sorted(peaks)}")
    return float(peaks[device_kind][key])


def bytes_per_row(query_mod, table_mod) -> float:
    return sum(table_mod.STRING_BYTES[c]
               if table_mod.SCHEMA[c] == "string"
               else BYTE_WIDTH[table_mod.SCHEMA[c]]
               for c in query_mod.COLUMNS)


def least_bytes(query_mod, table_mod, rows: int) -> float:
    return rows * bytes_per_row(query_mod, table_mod)
