"""The control of `correct` at drawn parameters: the plain reference of
`benchmark/tpch_qgen.py` computed in float32, the precision below the
float64 the configurations state, put in the program's place at a cell's
own size and held to the same comparison — `benchmark/control.py` at the
ends of the parameters' ranges: Q6's narrowest set (the least revenue: the
lowest discounts, QUANTITY 24) and its widest (the highest discounts,
QUANTITY 25), Q1 at DELTA 60 and at 120.  A line a parameter set: its own
gaps beside the limits, and whether that set alone came out correct.  The
benchmark's own runs never run it.

    python3 -m benchmark.control_qgen --workload scan_streams2 --seeds 1 2
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from . import manifest, tpch, tpch_qgen
from .record import Checks

ENDS = (("q6", {"year": 1993, "discount": 2, "quantity": 24}),
        ("q6", {"year": 1997, "discount": 9, "quantity": 25}),
        ("q1", {"delta": 60}),
        ("q1", {"delta": 120}))


def control_readings(sizes: dict, seed: int, limits: dict) -> list:
    """The gaps of the float32 reference against the float64 one over the
    cell's table as the loader draws it (the initial population and one
    refresh set), at each parameter set of `ENDS`."""
    per_set = int(sizes["refresh_orders"])
    data = tpch.concat([
        tpch.generate_lineitem(int(sizes["orders"]), int(sizes["rows"]),
                               seed),
        tpch.generate_lineitem(
            per_set, per_set * int(sizes["refresh_rows_per_order"]),
            [seed, 1], refresh=True)])
    ref = tpch_qgen.Reference(data)
    low = tpch_qgen.Reference(data, np.float32)
    out = []
    for query, params in ENDS:
        gaps = tpch.compare(query, tpch.as_rows(
            query, low.answer(query, params)), ref.answer(query, params))
        checks = Checks({k: limits[k] for k in gaps})
        checks.note_all(gaps)
        out.append({"seed": seed, "query": query, "params": params,
                    "correct": checks.correct(),
                    "compared": {k: [e["value"], e["limit"]]
                                 for k, e in checks.table().items()}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    args = ap.parse_args(argv)
    cell = manifest.Cell(manifest.load(), args.workload)
    for seed in args.seeds:
        for line in control_readings(cell.config["sizes"], seed,
                                     cell.config["limits"]):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
