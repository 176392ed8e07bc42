"""The two controls of ``q18_inner_parquet_sf1`` at the cell's own size: a
plain reference with a fault, put in the program's place and compared with
the reference exactly as a run's answers are.  The float32 control of
``control_readings.py`` cannot fail this cell (sums of at most seven whole
numbers under 51 are exact in float32 too); these stand in its place.
Host only; run by hand:

    python benchmark/tests/q18_control_readings.py --seeds 1,2,2147483653

(i) ``last_row_group_left_out``: the cell's own query (QUANTITY 300) over
the table less its last row group: rows of the input missing.
(ii) ``batches_not_merged``: at threshold 0 (every group compared), each
scan batch's partial sums handed on without the merge across batches: an
order whose lines straddle a row-group boundary comes out as two groups.
It is what the once-only comparison of all 1.5 M groups on the chip
(``PERF.md``) stands against: the 50 to 80 rows the HAVING keeps cannot
show such a fault unless it touches one of them.

One JSON line per seed and control, with what ``compare`` read; each has to
come out as not ``correct``.  ``PERF.md`` section 2 holds the readings.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import compare, datagen, run  # noqa: E402

CONFIG = "benchmark/configs/tpch_sf1_lineitem_q18_parquet.json"
QUERY = "q18_inner"


def last_row_group_left_out(mod, frame, row_group_rows, quantity=None):
    """(got, want) at the cell's own threshold (a test at a small size
    lowers it, so that the last row group holds an order over it)."""
    quantity = mod.QUANTITY if quantity is None else quantity
    kept = (len(frame) - 1) // row_group_rows * row_group_rows
    return (mod.reference(frame.iloc[:kept], quantity),
            mod.reference(frame, quantity))


def batches_not_merged(mod, frame, row_group_rows, quantity=None):
    """(got, want) at threshold 0, whatever ``quantity``: the partial
    aggregate of each scan batch (a row group), not merged with the next
    one's."""
    got = []
    for lo in range(0, len(frame), row_group_rows):
        got += mod.reference(frame.iloc[lo:lo + row_group_rows], 0.0)
    return got, mod.reference(frame, 0.0)


CONTROLS = {"last_row_group_left_out": last_row_group_left_out,
            "batches_not_merged": batches_not_merged}


def reading(control: str, mod, frame, row_group_rows, limits,
            quantity=None) -> dict:
    """What ``compare`` reads with the control's rows in the program's
    place, and whether that is ``correct``."""
    got, want = CONTROLS[control](mod, frame, row_group_rows, quantity)
    compared = compare.compare([((QUERY, ()), got)], {(QUERY, ()): want},
                               limits, fallback_nodes=0, missing=0)
    return {"control": control, "rows_got": len(got), "rows_want": len(want),
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
