"""Q1 and Q6 at drawn substitution parameters (TPC-H clause 2.4, as qgen
draws them for a query stream of the throughput test, clause 5.3.4): the
draw, the statement text, and the plain reference at any parameter set.
It imports nothing of the program and leaves `benchmark/tpch.py` as it is;
at the validation parameters the text and the answers are that file's.

Parameters of a statement, as the driver records them on its `stmt` span:

- Q1: `{"delta": d}`, d an integer in [60, 120] (clause 2.4.1.3): the
  statement reads `l_shipdate <= 1998-12-01 less d days`;
- Q6: `{"year": y, "discount": x, "quantity": q}` (clause 2.4.6.3): y in
  [1993, 1997], the ship date inside that year; x in 2..9 hundredths, the
  discount within 0.01 of it; q 24 or 25, the quantity under it.
"""
from __future__ import annotations

import numpy as np

from benchmark import tpch

DELTAS = range(60, 121)
YEARS = range(1993, 1998)
DISCOUNTS = range(2, 10)            # hundredths
QUANTITIES = (24, 25)
VALIDATION = {"q1": {"delta": 90},
              "q6": {"year": 1994, "discount": 6, "quantity": 24}}
#: 1998-12-01 as a day number since 1970-01-01
Q1_LAST_DAY = 10561
#: the first of January of 1993 ... 1998
_JAN1 = {y: int(np.datetime64(f"{y}-01-01", "D").astype(np.int64))
         for y in range(1993, 1999)}


def draw(rng, query: str) -> dict:
    """One statement's parameters, uniform over the source's ranges."""
    if query == "q1":
        return {"delta": int(rng.integers(DELTAS.start, DELTAS.stop))}
    return {"year": int(rng.integers(YEARS.start, YEARS.stop)),
            "discount": int(rng.integers(DISCOUNTS.start, DISCOUNTS.stop)),
            "quantity": int(rng.choice(QUANTITIES))}


def literals(query: str, params: dict) -> dict:
    """The numbers the statement's text holds: day numbers as integers,
    the discount's two ends as the doubles their two decimals parse to."""
    if query == "q1":
        return {"last_day": Q1_LAST_DAY - params["delta"]}
    x = params["discount"]
    return {"first_day": _JAN1[params["year"]],
            "next_year": _JAN1[params["year"] + 1],
            "low": float(f"{(x - 1) / 100:.2f}"),
            "high": float(f"{(x + 1) / 100:.2f}"),
            "quantity": params["quantity"]}


def sql(query: str, params: dict, name: str = tpch.TABLE) -> str:
    """`tpch.SQL[query]` with its literals replaced by the parameters'."""
    lit = literals(query, params)
    if query == "q1":
        swaps = [("<= 10471 ", f"<= {lit['last_day']} ")]
    else:
        swaps = [(">= 8766 ", f">= {lit['first_day']} "),
                 ("< 9131 ", f"< {lit['next_year']} "),
                 ("BETWEEN 0.05 AND 0.07 ",
                  f"BETWEEN {lit['low']:.2f} AND {lit['high']:.2f} "),
                 ("l_quantity < 24", f"l_quantity < {lit['quantity']}")]
    text = tpch.SQL[query]
    for old, new in swaps:
        if text.count(old) != 1:
            raise ValueError(f"{query}: {old!r} is not once in the text")
        text = text.replace(old, new)
    return text.format(name=name)


class Reference:
    """Q1 and Q6 over `data` (every acknowledged row of a static table)
    at any parameters, in plain numpy; `dtype=np.float32` is the control,
    as `tpch.reference`'s: the fractional columns, the products and the
    sums in float32, counts and the group key exact.  Each distinct
    parameter set is computed once and kept (at most 61 of Q1 and 80 of
    Q6).  Q6 is the masks and one sum as the text reads.  Q1's rows are
    put in order of (group, ship date) once, so that the rows of a group
    up to a ship date are one slice and a sum is numpy's over that slice:
    61 cut-off days cost one sort, not 61 passes of 16 masked sums."""

    def __init__(self, data: dict, dtype=np.float64):
        self.dtype = dtype
        self.qty = data["l_quantity"].astype(dtype)
        self.price = data["l_extendedprice"].astype(dtype)
        self.disc = data["l_discount"].astype(dtype)
        self.ship = data["l_shipdate"]
        one = dtype(1)
        disc_price = self.price * (one - self.disc)
        charge = disc_price * (one + data["l_tax"].astype(dtype))
        # a group is its two one-letter flags; as one small integer it
        # sorts as the text `tpch.groups` makes does, forty times faster
        names, code = np.unique(
            data["l_returnflag"].astype("S1").view(np.uint8) * np.int32(256)
            + data["l_linestatus"].astype("S1").view(np.uint8),
            return_inverse=True)
        order = np.lexsort((self.ship, code))
        self.groups = [chr(g >> 8) + chr(g & 255) for g in names.tolist()]
        self.edges = np.searchsorted(code[order], np.arange(len(names) + 1))
        self.ship_sorted = self.ship[order]
        self.q1_lanes = {"sum_qty": self.qty[order],
                         "sum_base_price": self.price[order],
                         "sum_disc_price": disc_price[order],
                         "sum_charge": charge[order]}
        self._kept: dict = {}

    def answer(self, query: str, params: dict) -> dict:
        """The answer in the shape `tpch.compare` takes: `{"q6": revenue}`
        or `{"q1": {group: sums and count}}`."""
        key = (query, *sorted(params.items()))
        if key not in self._kept:
            lit = literals(query, params)
            self._kept[key] = {query: (self._q1(lit) if query == "q1"
                                       else self._q6(lit))}
        return self._kept[key]

    def _q6(self, lit: dict) -> float:
        dtype = self.dtype
        m = ((self.ship >= lit["first_day"]) & (self.ship < lit["next_year"])
             & (self.disc >= dtype(lit["low"]))
             & (self.disc <= dtype(lit["high"]))
             & (self.qty < dtype(lit["quantity"])))
        return float((self.price[m] * self.disc[m]).sum(dtype=dtype))

    def _q1(self, lit: dict) -> dict:
        out = {}
        for g, lo, hi in zip(self.groups, self.edges[:-1], self.edges[1:]):
            end = lo + int(np.searchsorted(self.ship_sorted[lo:hi],
                                           lit["last_day"], side="right"))
            if end > lo:
                out[g] = {k: float(v[lo:end].sum(dtype=self.dtype))
                          for k, v in self.q1_lanes.items()}
                out[g]["count_order"] = int(end - lo)
        return out
