"""The control's readings at a cell's own size: the plain reference computed
in float32 (the nearest precision below the configuration's float64), put in
the program's place and compared with the float64 reference exactly as a
run's answers are.  Host only; run by hand:

    python benchmark/tests/control_readings.py \\
        --config benchmark/configs/tpch_sf1_lineitem_parquet.json \\
        --queries q6,q1 --seeds 1,2,3

One JSON line per seed and query: the widest float gap and whether the exact
fields agreed.  ``PERF.md`` holds the readings the limit was set from.
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--queries", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    config = run.load_json(args.config)
    for seed in map(int, args.seeds.split(",")):
        scratch = tempfile.mkdtemp(prefix="bench_control_")
        try:
            files = {}
            for qname in args.queries.split(","):
                mod = run.load_module("queries", qname)
                if mod.TABLE not in files:
                    spec = config["tables"][mod.TABLE]
                    files[mod.TABLE] = datagen.write_table(
                        scratch, run.load_module("tables", mod.TABLE),
                        mod.TABLE, spec["rows"], spec["files"],
                        spec["row_group_rows"], seed, spec["scale_factor"])
                want = mod.reference(datagen.read_frame(
                    files[mod.TABLE], mod.COLUMNS))
                got = mod.reference(datagen.read_frame(
                    files[mod.TABLE], mod.COLUMNS, "float32"))
                exact, gap = compare.answer_gap(
                    got, want, getattr(mod, "ORDERED", False))
                print(json.dumps({
                    "config": config["name"], "query": qname, "seed": seed,
                    "control": "float32", "exact_fields_equal": exact,
                    "float_gap": gap,
                    "limit": config["limits"]["float_gap"]}), flush=True)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    main()
