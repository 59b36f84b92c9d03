"""The yardstick's own copy of the TPC-H pieces: LINEITEM at its full
16-column layout (clause 1.4.1) with values shaped as dbgen draws them
(clause 4.2.3), Q1/Q6 as SQL text, the plain numpy reference and its
lower-precision control, and the comparison that decides `correct` for a
statement.  It imports nothing of the program.

Types as the program offers them (it has no DATE, CHAR or exact DECIMAL
served on the device): identifiers `bigint`/`int`, decimals `double`,
dates `int` day numbers since 1970-01-01 (clause 1.3.1 leaves a date's
internal form open), fixed and variable text `varchar`.
"""
from __future__ import annotations

import numpy as np

TABLE = "lineitem"
COLS = ("l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
        "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
        "l_receiptdate", "l_shipinstruct", "l_shipmode", "l_comment")
TEXT_COLS = ("l_returnflag", "l_linestatus", "l_shipinstruct", "l_shipmode",
             "l_comment")
DDL = ("CREATE TABLE {name} (l_orderkey bigint, l_partkey int, "
       "l_suppkey int, l_linenumber int, l_quantity double, "
       "l_extendedprice double, l_discount double, l_tax double, "
       "l_returnflag varchar(1), l_linestatus varchar(1), l_shipdate int, "
       "l_commitdate int, l_receiptdate int, l_shipinstruct varchar(25), "
       "l_shipmode varchar(10), l_comment varchar(44), "
       "PRIMARY KEY (l_orderkey, l_linenumber)) WITH tablets = {tablets}")
# day numbers: 8035 = 1992-01-01 (STARTDATE), 10591 = 1998-12-31 (ENDDATE),
# 9298 = 1995-06-17 (CURRENTDATE); the queries' substitution parameters
# are the validation ones: 8766 = 1994-01-01, 9131 = 1995-01-01,
# 10471 = 1998-12-01 less 90 days
STARTDATE, ENDDATE, CURRENTDATE = 8035, 10591, 9298
SQL = {
    "q6": ("SELECT sum(l_extendedprice * l_discount) AS revenue FROM {name} "
           "WHERE l_shipdate >= 8766 AND l_shipdate < 9131 "
           "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"),
    "q1": ("SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty, "
           "sum(l_extendedprice) AS sum_base_price, "
           "sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price, "
           "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) "
           "AS sum_charge, count(*) AS count_order FROM {name} "
           "WHERE l_shipdate <= 10471 GROUP BY l_returnflag, l_linestatus"),
}
# columns each query reads (the scan's byte model counts their lanes)
QUERY_COLUMNS = {
    "q6": ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate"),
    "q1": ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_shipdate", "l_returnflag", "l_linestatus"),
}
Q1_SUMS = ("sum_base_price", "sum_disc_price", "sum_charge")

_INSTRUCT = np.array([b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                      b"TAKE BACK RETURN"])
_MODES = np.array([b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL",
                   b"FOB"])
_WORDS = ("furiously", "sly", "careful", "blithe", "quick", "fluffy", "slow",
          "quiet", "ruthless", "thin", "close", "dogged", "daring", "brave",
          "stealthy", "permanent", "enticing", "idle", "busy", "regular",
          "final", "ironic", "even", "bold", "silent", "packages", "requests",
          "accounts", "deposits", "foxes", "ideas", "theodolites", "pinto",
          "beans", "instructions", "dependencies", "excuses", "platelets",
          "asymptotes", "courts", "dolphins", "sleep", "wake", "are", "cajole",
          "haggle", "nag", "use", "boost", "affix", "detect", "integrate",
          "among", "above", "across", "against", "along", "the")
_TEXT_POOL_BYTES = 1 << 20


def _comments(rng, n: int) -> np.ndarray:
    """`n` comments of 10 to 43 characters, cut as dbgen cuts them: a
    text pool of random words, and each comment a piece of it at a
    random offset and of a random length (clause 4.2.2.10), so nearly
    every comment is a string of its own."""
    words = np.array(_WORDS)[rng.integers(0, len(_WORDS),
                                          _TEXT_POOL_BYTES // 4)]
    pool = np.frombuffer(" ".join(words.tolist()).encode()[
        :_TEXT_POOL_BYTES], np.uint8)
    offset = rng.integers(0, len(pool) - 43, n)
    length = rng.integers(10, 44, n)
    out = np.lib.stride_tricks.sliding_window_view(pool, 43)[offset]
    out[np.arange(43) >= length[:, None]] = 0
    rows = np.arange(n)
    for end in (np.zeros(n, np.int64), length - 1):   # no blank at an end
        out[rows, end] = np.where(out[rows, end] == 32, 97, out[rows, end])
    return out.view("S43").ravel()


def _sparse_key(i: np.ndarray, refresh: bool) -> np.ndarray:
    """dbgen's sparse order keys: 8 of every 32 values are used by the
    initial population (bits 3 and 4 clear); the refresh sets take keys
    out of the gaps (bit 3 set), so no refresh key meets a loaded one."""
    return ((i >> 3) << 5) | (i & 7) | (8 if refresh else 0)


def _line_counts(rng, orders: int, rows: int) -> np.ndarray:
    """1 to 7 lineitems an order, as dbgen draws them, then single lines
    added or taken away at random orders until the table has exactly
    `rows` rows: every seed gives the same cardinality."""
    counts = rng.integers(1, 8, orders)
    while (diff := rows - int(counts.sum())) != 0:
        room = np.nonzero(counts < 7 if diff > 0 else counts > 1)[0]
        pick = rng.choice(room, min(abs(diff), len(room)), replace=False)
        counts[pick] += 1 if diff > 0 else -1
    return counts


def generate_lineitem(orders: int, rows: int, seed, first_order: int = 0,
                      refresh: bool = False, sf: float = 1.0) -> dict:
    """The lineitems of `orders` orders, `rows` rows in all, order by
    order; the same seed gives the same rows.  `seed` is anything
    `default_rng` takes.  Text columns are fixed-width byte arrays."""
    if not orders <= rows <= 7 * orders:
        raise ValueError(f"{rows} rows do not fit {orders} orders")
    rng = np.random.default_rng(seed)
    counts = _line_counts(rng, orders, rows)
    first = np.cumsum(counts) - counts
    order = np.repeat(np.arange(orders, dtype=np.int64), counts)
    n = rows
    parts, supps = max(1, int(200_000 * sf)), max(1, int(10_000 * sf))
    partkey = rng.integers(1, parts + 1, n)
    suppkey = (partkey + rng.integers(0, 4, n) * (
        supps // 4 + (partkey - 1) // supps)) % supps + 1
    quantity = rng.integers(1, 51, n)
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1, orders)[order]
    shipdate = orderdate + rng.integers(1, 122, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    returned = np.where(rng.integers(0, 2, n) == 0, b"R", b"A")
    return {
        "l_orderkey": _sparse_key(order + first_order, refresh),
        "l_partkey": partkey.astype(np.int32),
        "l_suppkey": suppkey.astype(np.int32),
        "l_linenumber": (np.arange(n) - first[order] + 1).astype(np.int32),
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": (quantity * retail_cents) / 100.0,
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_returnflag": np.where(receiptdate <= CURRENTDATE, returned,
                                 b"N").astype("S1"),
        "l_linestatus": np.where(shipdate > CURRENTDATE, b"O",
                                 b"F").astype("S1"),
        "l_shipdate": shipdate.astype(np.int32),
        "l_commitdate": (orderdate + rng.integers(30, 91, n)
                         ).astype(np.int32),
        "l_receiptdate": receiptdate.astype(np.int32),
        "l_shipinstruct": _INSTRUCT[rng.integers(0, 4, n)],
        "l_shipmode": _MODES[rng.integers(0, 7, n)],
        "l_comment": _comments(rng, n),
    }


def concat(parts: list) -> dict:
    return {c: np.concatenate([p[c] for p in parts]) for c in COLS}


def row(data: dict, i: int) -> tuple:
    """Row `i` as SQL returns it: text as `str`, numbers as Python's."""
    return tuple(data[c][i].decode() if c in TEXT_COLS else data[c][i].item()
                 for c in COLS)


def literal(value) -> str:
    return "'" + value + "'" if isinstance(value, str) else repr(value)


def groups(data: dict) -> np.ndarray:
    """Q1's group of each row as one text, returnflag then linestatus."""
    return np.char.add(data["l_returnflag"], data["l_linestatus"])


def reference(data: dict, dtype=np.float64) -> dict:
    """Q6's revenue and Q1's groups straight from the arrays.

    `dtype=np.float32` is the control: the same arithmetic with the
    fractional columns, the products and the sums held in float32, the
    precision below the float64 the configuration states.  Counts and
    the group key stay exact in both."""
    qty = data["l_quantity"].astype(dtype)
    price = data["l_extendedprice"].astype(dtype)
    disc = data["l_discount"].astype(dtype)
    tax = data["l_tax"].astype(dtype)
    ship = data["l_shipdate"]
    one = dtype(1)
    m6 = ((ship >= 8766) & (ship < 9131) & (disc >= dtype(0.05))
          & (disc <= dtype(0.07)) & (qty < dtype(24)))
    out = {"q6": float((price[m6] * disc[m6]).sum(dtype=dtype)), "q1": {}}
    m1 = ship <= 10471
    gid = groups(data)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    for g in np.unique(gid[m1]).tolist():
        mg = m1 & (gid == g)
        out["q1"][g.decode()] = {
            "sum_qty": float(qty[mg].sum(dtype=dtype)),
            "sum_base_price": float(price[mg].sum(dtype=dtype)),
            "sum_disc_price": float(disc_price[mg].sum(dtype=dtype)),
            "sum_charge": float(charge[mg].sum(dtype=dtype)),
            "count_order": int(mg.sum())}
    return out


def as_rows(query: str, ref: dict) -> list:
    """A reference answer in the shape `SqlSession.execute` returns, so
    the control can be put in the program's place."""
    if query == "q6":
        return [{"revenue": ref["q6"]}]
    return [{"l_returnflag": g[0], "l_linestatus": g[1], **v}
            for g, v in ref["q1"].items()]


def compare(query: str, rows, ref: dict) -> dict:
    """The gaps between one statement's rows and the reference, by short
    plain names.  A row set of the wrong shape reads `<q>_shape` 1 and
    nothing else; an answer is never judged by a part of it.  `sum_usd`
    is one number for both queries: the widest gap, in dollars, of a
    money SUM (Q6's revenue, Q1's three in each group), which the
    source's validation rule holds to $100 (clause 2.1.3.5)."""
    if query == "q6":
        if len(rows) != 1 or rows[0].get("revenue") is None:
            return {"q6_shape": 1}
        return {"q6_shape": 0,
                "sum_usd": abs(float(rows[0]["revenue"]) - ref["q6"])}
    want, got = ref["q1"], {}
    for r in rows:
        try:
            got[str(r["l_returnflag"]) + str(r["l_linestatus"])] = r
        except (KeyError, TypeError):
            return {"q1_shape": 1}
    if set(got) != set(want) or len(rows) != len(want):
        return {"q1_shape": 1}
    out = {"q1_shape": 0, "q1_count_diff": 0, "q1_qty_diff": 0.0,
           "sum_usd": 0.0}
    for g, w in want.items():
        r = got[g]
        if any(r.get(k) is None for k in w):
            return {"q1_shape": 1}
        out["q1_count_diff"] = max(out["q1_count_diff"],
                                   abs(int(r["count_order"])
                                       - w["count_order"]))
        out["q1_qty_diff"] = max(out["q1_qty_diff"],
                                 abs(float(r["sum_qty"]) - w["sum_qty"]))
        for k in Q1_SUMS:
            out["sum_usd"] = max(out["sum_usd"], abs(float(r[k]) - w[k]))
    return out
