"""The control of `correct`: the plain reference computed in float32, the
precision below the float64 the configurations state, put in the program's
place at a cell's own size and held to the same comparison.  It has to come
out not correct; its readings are each limit's upper reading (PERF.md).
The benchmark's own runs never run it.

    python3 -m benchmark.control --workload scan_power --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from . import manifest, tpch
from .record import Checks


def control_readings(sizes: dict, sets: int, seed: int, limits: dict) -> dict:
    """The gaps of the float32 reference against the float64 one over a
    cell's table: the initial population and `sets` refresh sets, as the
    loader draws them.  `readback_value_diff` counts inserted rows that
    a float32 store would not return value for value."""
    orders, per_set = int(sizes["orders"]), int(sizes["refresh_orders"])
    rows_per_set = per_set * int(sizes["refresh_rows_per_order"])
    parts = [tpch.generate_lineitem(orders, int(sizes["rows"]), seed)]
    for k in range(1, sets + 1):
        parts.append(tpch.generate_lineitem(
            per_set, rows_per_set, [seed, k], first_order=(k - 1) * per_set,
            refresh=True))
    data = tpch.concat(parts)
    ref, low = tpch.reference(data), tpch.reference(data, np.float32)
    answers = ("q1", "q6", "sum_usd", "readback_value")
    checks = Checks({k: v for k, v in limits.items()
                     if k.startswith(answers)})
    for q in ("q6", "q1"):
        checks.note_all(tpch.compare(q, tpch.as_rows(q, low), ref))
    if "readback_value_diff" in limits:
        inserted = tpch.concat(parts[1:])
        price = inserted["l_extendedprice"]
        checks.note("readback_value_diff", int(
            (price.astype(np.float32).astype(np.float64) != price).sum()))
    table = checks.table()
    return {"seed": seed, "correct": checks.correct(),
            "compared": {k: [e["value"], e["limit"]]
                         for k, e in table.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--sets", type=int, default=1,
                    help="refresh sets in the table (1 after set-up)")
    args = ap.parse_args(argv)
    cell = manifest.Cell(manifest.load(), args.workload)
    for seed in args.seeds:
        print(json.dumps(control_readings(
            cell.config["sizes"], args.sets, seed, cell.config["limits"])),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
