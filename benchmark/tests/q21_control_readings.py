"""The three controls of ``q21_lineitem_parquet_sf1`` at the cell's own
size: a plain reference with a fault, put in the program's place and
compared with the reference exactly as a run's answers are.  The answer has
no float field, so the float32 control of ``control_readings.py`` cannot
fail this cell; these stand in its place.  Host only; run by hand:

    python benchmark/tests/q21_control_readings.py --seeds 1,2,2147483653

(i) ``last_row_group_left_out``: the reference over the table less its last
row group: rows of the input missing.
(ii) ``exists_inside_each_batch``: EXISTS and NOT EXISTS evaluated inside
each scan batch (a row group) and not over the table, the batches' counts
added up by supplier: an order cut by a row-group boundary is judged as two
orders.
(iii) ``anti_join_without_its_condition``: the NOT EXISTS without its
``<>``: every late line finds itself among the late lines of its order, so
nothing comes out.

One JSON line per seed and control, with what ``compare`` read; each has to
come out as not ``correct``.  ``PERF.md`` section 2 holds the readings.
"""
import argparse
import collections
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import compare, datagen, run  # noqa: E402

CONFIG = "benchmark/configs/tpch_sf1_lineitem_q21_parquet.json"
QUERY = "q21_lineitem"


def ordered(numwait: dict) -> list:
    """supplier -> count as the query's rows: ``numwait`` descending, then
    supplier."""
    return sorted(numwait.items(), key=lambda r: (-r[1], r[0]))


def last_row_group_left_out(mod, frame, row_group_rows):
    kept = (len(frame) - 1) // row_group_rows * row_group_rows
    return mod.reference(frame.iloc[:kept]), mod.reference(frame)


def exists_inside_each_batch(mod, frame, row_group_rows):
    numwait = collections.Counter()
    for lo in range(0, len(frame), row_group_rows):
        numwait.update(dict(mod.reference(
            frame.iloc[lo:lo + row_group_rows])))
    return ordered(numwait), mod.reference(frame)


def anti_join_without_its_condition(mod, frame, row_group_rows):
    """NOT EXISTS (a late line of the same order): the kept lines are the
    late lines of orders with no late line."""
    late = frame[frame["l_receiptdate"] > frame["l_commitdate"]]
    kept = late[~late["l_orderkey"].isin(late["l_orderkey"])]
    return (ordered(dict(kept.groupby("l_suppkey").size())),
            mod.reference(frame))


CONTROLS = {"last_row_group_left_out": last_row_group_left_out,
            "exists_inside_each_batch": exists_inside_each_batch,
            "anti_join_without_its_condition":
            anti_join_without_its_condition}


def reading(control: str, mod, frame, row_group_rows, limits) -> dict:
    """What ``compare`` reads with the control's rows in the program's
    place, and whether that is ``correct``."""
    got, want = CONTROLS[control](mod, frame, row_group_rows)
    compared = compare.compare([((QUERY, ()), got)], {(QUERY, ()): want},
                               limits, fallback_nodes=0, missing=0,
                               ordered={QUERY} if mod.ORDERED else ())
    differ = sum(a != b for a, b in zip(got, want)) + abs(
        len(got) - len(want))
    return {"control": control, "rows_got": len(got), "rows_want": len(want),
            "rows_that_differ": differ,
            "correct": compare.is_correct(compared),
            **{k: v["value"] for k, v in compared.items()}}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=None,
                    help="another table size (a rehearsal)")
    args = ap.parse_args()
    config = run.load_json(os.path.join(ROOT, CONFIG))
    mod = run.load_module("queries", QUERY)
    spec = config["tables"][mod.TABLE]
    rows = args.rows or spec["rows"]
    for seed in map(int, args.seeds.split(",")):
        scratch = tempfile.mkdtemp(prefix="bench_control_")
        try:
            files = datagen.write_table(
                scratch, run.load_module("tables", mod.TABLE), mod.TABLE,
                rows, spec["files"], spec["row_group_rows"], seed,
                spec["scale_factor"] * rows / spec["rows"])
            frame = datagen.read_frame(files, mod.COLUMNS)
            for control in CONTROLS:
                print(json.dumps({
                    "config": config["name"], "query": QUERY, "seed": seed,
                    "rows": rows,
                    **reading(control, mod, frame, spec["row_group_rows"],
                              config["limits"])}), flush=True)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    main()
