"""TPC-H LINEITEM as the spec defines it (TPC-H v3: columns 1.4.1, population
4.2.3): all 16 columns at their spec types, rows derived from orders.

A table file gives the harness ``SCHEMA`` (column -> type, in file order),
``STRING_BYTES`` (mean bytes of a value of each string column),
``prepare(seed, rows, scale_factor)`` (what every chunk needs of the whole
table, drawn from the seed alone) and ``chunk(rng, lo, n, prepared)`` (rows
``lo .. lo+n`` as numpy arrays; per-row draws come from ``rng`` alone).
Decimal columns are unscaled int64 (value = unscaled / 100), dates are days
since 1970-01-01, string columns are ``(codes, dictionary)`` or
``(lengths, bytes matrix)`` as ``datagen`` takes them.

What follows the spec: an order has 1 to 7 lines, numbered from 1, that share
its key (sparse: 8 of every 32 keys are used) and its order date (uniform in
[1992-01-01, 1998-12-31 - 151 days]); l_partkey uniform in [1, SF*200,000];
l_suppkey derived from it; l_extendedprice = l_quantity * p_retailprice(part);
ship, commit and receipt dates offset from the order date; l_returnflag and
l_linestatus derived from the dates against CURRENTDATE 1995-06-17;
instructions, modes and a comment of 10 to 43 characters.  Departures (also in
the configuration files' ``assumed``): the random streams are numpy's, not
dbgen's, so rows differ from dbgen's while every distribution is the spec's;
the table stops at the spec's row count for the scale factor, so its last
order may hold fewer lines than it drew; l_comment is a random 10-to-43
character substring of a pool of sentences built from the spec's word lists
(4.2.2.14), not dbgen's 300 MB grammar text.
"""
import datetime

import numpy as np

SCHEMA = {
    "l_orderkey": "int64",
    "l_partkey": "int64",
    "l_suppkey": "int64",
    "l_linenumber": "int32",
    "l_quantity": "decimal(12,2)",
    "l_extendedprice": "decimal(12,2)",
    "l_discount": "decimal(12,2)",
    "l_tax": "decimal(12,2)",
    "l_returnflag": "string",
    "l_linestatus": "string",
    "l_shipdate": "date32",
    "l_commitdate": "date32",
    "l_receiptdate": "date32",
    "l_shipinstruct": "string",
    "l_shipmode": "string",
    "l_comment": "string",
}

INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
COMMENT_MIN, COMMENT_MAX = 10, 43
POOL_BYTES = 1 << 20
# mean bytes of a value, for the least bytes a query has to move
STRING_BYTES = {
    "l_returnflag": 1, "l_linestatus": 1,
    "l_shipinstruct": sum(map(len, INSTRUCTIONS)) / len(INSTRUCTIONS),
    "l_shipmode": sum(map(len, MODES)) / len(MODES),
    "l_comment": (COMMENT_MIN + COMMENT_MAX) / 2,
}

# a part of each of the spec's word lists (4.2.2.14)
NOUNS = ("foxes ideas theodolites pinto beans instructions dependencies "
         "excuses platelets asymptotes courts dolphins multipliers "
         "sauternes warthogs frets dinos attainments somas Tiresias "
         "patterns forges braids hockey players frays warhorses dugouts "
         "notornis epitaphs pearls tithes waters orbits gifts sheaves "
         "depths sentiments decoys realms pains grouches escapades").split()
VERBS = ("sleep wake are cajole haggle nag use boost affix detect integrate "
         "maintain nod was lose sublate solve thrash promise engage hinder "
         "print x-ray breach eat grow impress mold poach serve run dazzle "
         "snooze doze unwind kindle play hang believe doubt").split()
ADJECTIVES = ("furious sly careful blithe quick fluffy slow quiet ruthless "
              "thin close dogged daring brave stealthy permanent enticing "
              "idle busy regular final ironic even bold silent").split()
ADVERBS = ("sometimes always never furiously slyly carefully blithely "
           "quickly fluffily slowly quietly ruthlessly thinly closely "
           "doggedly daringly bravely stealthily permanently enticingly "
           "idly busily regularly finally ironically evenly boldly "
           "silently").split()
PREPOSITIONS = ("about above according to across after against along "
                "alongside of among around at atop before behind beneath "
                "beside besides between beyond by despite during except "
                "for from in place of inside instead of into near of on "
                "outside over past since through throughout to toward "
                "under until up upon without with within").split()
TERMINATORS = [".", ";", ":", "?", "!", "--"]


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


STARTDATE, CURRENTDATE, ENDDATE = (days(1992, 1, 1), days(1995, 6, 17),
                                   days(1998, 12, 31))


def _text_pool(rng: np.random.Generator) -> np.ndarray:
    """``POOL_BYTES`` of sentences, noun phrase + verb phrase + terminator,
    as a vector of bytes."""
    def pick(words, n):
        return np.array(words, dtype=object)[rng.integers(0, len(words), n)]
    n = POOL_BYTES // 24
    parts = [pick(ADVERBS, n), pick(ADJECTIVES, n), pick(NOUNS, n),
             pick(VERBS, n), pick(PREPOSITIONS, n), pick(ADJECTIVES, n),
             pick(NOUNS, n)]
    ends = pick(TERMINATORS, n)
    text = " ".join(" ".join(w) + e for *w, e in zip(*parts, ends))
    return np.frombuffer(text[:POOL_BYTES].encode(), dtype=np.uint8)


def prepare(seed: int, rows: int, scale_factor: float) -> dict:
    """Orders of the whole table: where each order's lines start and its
    order date.  About rows/4 orders, 12 bytes each."""
    rng = np.random.default_rng([int(seed), 1 << 40])
    starts, have = [], 0
    while have < rows:
        counts = rng.integers(1, 8, (rows - have) // 4 + 1024, dtype=np.int64)
        s = have + np.cumsum(counts) - counts
        starts.append(s[s < rows])
        have = int(s[-1] + counts[-1])
    line_start = np.concatenate(starts)
    pool = _text_pool(rng)
    return {
        "rows": rows,
        "line_start": line_start,
        "orderdate": rng.integers(STARTDATE, ENDDATE - 151 + 1,
                                  len(line_start), dtype=np.int32),
        "parts": max(int(round(scale_factor * 200_000)), 1),
        "suppliers": max(int(round(scale_factor * 10_000)), 4),
        "comment_windows": np.lib.stride_tricks.sliding_window_view(
            pool, COMMENT_MAX),
    }


def chunk(rng: np.random.Generator, lo: int, n: int, prepared: dict) -> dict:
    i64, i32 = np.int64, np.int32
    starts = prepared["line_start"]
    first = int(np.searchsorted(starts, lo, "right")) - 1
    last = int(np.searchsorted(starts, lo + n - 1, "right")) - 1
    bounds = np.append(starts[first:last + 1], lo + n).clip(lo, None)
    order = np.repeat(np.arange(first, last + 1, dtype=i64), np.diff(bounds))
    orderdate = prepared["orderdate"][order]

    partkey = rng.integers(1, prepared["parts"] + 1, n, dtype=i64)
    s = prepared["suppliers"]
    suppkey = (partkey + rng.integers(0, 4, n, dtype=i64)
               * (s // 4 + (partkey - 1) // s)) % s + 1
    quantity = rng.integers(1, 51, n, dtype=i64)
    retail_cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)
    shipdate = orderdate + rng.integers(1, 122, n, dtype=i32)
    receiptdate = shipdate + rng.integers(1, 31, n, dtype=i32)
    # R or A where the line was received by CURRENTDATE, else N
    returnflag = np.where(receiptdate <= CURRENTDATE,
                          rng.integers(0, 2, n, dtype=np.int8), np.int8(2))
    return {
        "l_orderkey": (order // 8) * 32 + order % 8 + 1,
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": (np.arange(lo, lo + n, dtype=i64)
                         - starts[order] + 1).astype(i32),
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * retail_cents,
        "l_discount": rng.integers(0, 11, n, dtype=i64),
        "l_tax": rng.integers(0, 9, n, dtype=i64),
        "l_returnflag": (returnflag, ["R", "A", "N"]),
        "l_linestatus": ((shipdate > CURRENTDATE).astype(np.int8),
                         ["F", "O"]),
        "l_shipdate": shipdate,
        "l_commitdate": orderdate + rng.integers(30, 91, n, dtype=i32),
        "l_receiptdate": receiptdate,
        "l_shipinstruct": (rng.integers(0, 4, n, dtype=np.int8),
                           INSTRUCTIONS),
        "l_shipmode": (rng.integers(0, 7, n, dtype=np.int8), MODES),
        "l_comment": (
            rng.integers(COMMENT_MIN, COMMENT_MAX + 1, n, dtype=i32),
            prepared["comment_windows"][
                rng.integers(0, len(prepared["comment_windows"]), n)]),
    }
