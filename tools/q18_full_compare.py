"""Once, outside the timed path: TPC-H Q18's subquery at threshold 0 (every
group kept) over the benchmark's SF1 LINEITEM, all 1.5 M groups compared
with the plain reference (keys and row count exact, sums by
``compare.float_gap``).  The cell's timed answers hold the 50 to 80 orders
over QUANTITY 300 and cannot show a group split at a batch boundary; this
can.  Run on the chip:

    python tools/q18_full_compare.py --seed 5 [--rows N]

One JSON line; exits 0 only where the comparison holds.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import compare, datagen, run  # noqa: E402

CELL = "q18_inner_parquet_sf1"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args()
    cell = run.load_cell(CELL)
    mod = cell.queries["q18_inner"]
    spec = cell.config["tables"][mod.TABLE]
    rows = args.rows or spec["rows"]
    import jax
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.plan.execs.base import launch_stats
    scratch = tempfile.mkdtemp(prefix="q18_full_")
    try:
        files = datagen.write_table(
            scratch, cell.tables[mod.TABLE], mod.TABLE, rows, spec["files"],
            spec["row_group_rows"], args.seed,
            spec["scale_factor"] * rows / spec["rows"])
        sess = TpuSession(dict(cell.config["session_conf"]))
        t = time.perf_counter()
        got = mod.build(sess.read_parquet(*files), 0.0).collect()
        seconds = time.perf_counter() - t
        want = mod.reference(datagen.read_frame(files, mod.COLUMNS), 0.0)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    exact, gap = compare.answer_gap(got, want)
    ok = exact and gap <= cell.config["limits"]["float_gap"]
    print(json.dumps({
        "platform": jax.devices()[0].platform, "seed": args.seed,
        "rows": rows, "groups_got": len(got), "groups_want": len(want),
        "keys_and_count_exact": exact, "float_gap": gap, "equal": ok,
        "collect_s": seconds,
        "by_program": launch_stats()["by_program"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
