"""TPC-H's customer, orders and lineitem, as much of them as Query 3 reads,
its plain reference, and the comparison that decides `correct`.

The tables follow the TPC-H specification's §4.2.3 definitions, as the
configuration's ``assumed`` says they were written from memory of it:

* customer: ``c_custkey`` 1..150,000 x SF; ``c_mktsegment`` uniform over
  the five segments, stored as a uint8 code (the segment's place in
  ``SEGMENTS``, name order);
* orders: 1,500,000 x SF rows; ``o_orderkey`` sparse, the first 8 of every
  32 keys; ``o_custkey`` uniform over the customer keys that are not a
  multiple of 3; ``o_orderdate`` uniform over [1992-01-01, 1998-12-31 - 151
  days]; ``o_shippriority`` 0;
* lineitem: 1 to 7 lines an order, uniform; ``l_quantity`` 1..50;
  ``l_partkey`` uniform over 1..200,000 x SF and ``l_extendedprice`` =
  quantity x that part's retail price, (90000 + (partkey / 10) mod 20001 +
  100 x (partkey mod 1000)) / 100; ``l_discount`` 0.00..0.10;
  ``l_shipdate`` = the order's date + 1..121 days.

Dates are int32 days since 1970-01-01, money int64 hundredths, as the
program's columnar scan reads them: ``<table>/part-0000k/<column>``, one
little-endian file a column a part (io/formats.py ``ColumnarFormat``).
The random numbers are numpy's (``default_rng(data_seed)``), not dbgen's:
the answer is not dbgen's answer.  ``--seed`` orders the rows inside each
part, so every seed of one ``data_seed`` asks the same question.

The reference is Q3 in numpy int64 over the generator's own arrays, and
imports nothing of the program: the segment's customers, the orders before
the date whose customer is one of them, their lines shipped after it, the
revenue ``l_extendedprice * (100 - l_discount)`` in ten-thousandths summed
by order, the first ten by (revenue descending, date).  The comparison
holds every committed output to the query's guarantees, each limit 0:

* ``rows_wrong``: a rank whose (orderkey, revenue to 0.0001, date,
  priority) is not the reference's row of that rank -- rows tied on
  (revenue, date) may stand in either order, so a row is right where it is
  one of the query's groups and has the rank's revenue and date, and no
  order key comes twice;
* ``rows_missing_or_extra``: how far the row count is from the answer's;
* ``lines_malformed``: a line that is not four tab-separated fields of the
  right form;
* ``commits_missing``: ``_SUCCESS`` absent or a temporary tree left.
"""
from __future__ import annotations

import datetime
import os
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")
TABLES = ("customer", "orders", "lineitem")
#: the columns a DAG reads and their file types
COLUMNS = {
    "customer": {"c_custkey": "<i4", "c_mktsegment": "u1"},
    "orders": {"o_orderkey": "<i4", "o_custkey": "<i4",
               "o_orderdate": "<i4", "o_shippriority": "<i4"},
    "lineitem": {"l_orderkey": "<i4", "l_extendedprice": "<i8",
                 "l_discount": "<i8", "l_shipdate": "<i4"},
}
LIMIT = 10
#: each number compared, with its limit: all exact comparisons
LIMITS = {"rows_wrong": 0, "rows_missing_or_extra": 0,
          "lines_malformed": 0, "commits_missing": 0}
#: guarantee broken -> what reference_output() does to the reference's output
CONTROLS = ("float32_revenue", "lineitem_dropped", "segment_ignored",
            "committed_twice")
#: a rehearsal's corpus_mib stands for this scale factor a MiB
SF_A_MIB = 0.005
LINE = re.compile(rb"^(\d+)\t(\d+)\.(\d{4})\t(\d{4})-(\d{2})-(\d{2})\t(\d+)$")


def epoch_day(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) -
            datetime.date(1970, 1, 1)).days


def _scale(params: Dict[str, Any]) -> float:
    if "corpus_mib" in params:
        return float(params["corpus_mib"]) * SF_A_MIB
    return float(params["scale_factor"])


def _tables(sf: float, data_seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    """The three tables' columns in generation order (orders by key, an
    order's lines together), with ``l_order`` (each line's order row)."""
    rng = np.random.default_rng(int(data_seed))
    n_cust, n_ord = int(150_000 * sf), int(1_500_000 * sf)
    n_part = int(200_000 * sf)
    customer = {"c_custkey": np.arange(1, n_cust + 1, dtype=np.int32),
                "c_mktsegment": rng.integers(0, len(SEGMENTS), n_cust,
                                             dtype=np.uint8)}
    i = np.arange(n_ord, dtype=np.int64)
    # the keys that are not a multiple of 3: 1, 2, 4, 5, 7, ...
    j = rng.integers(0, n_cust - n_cust // 3, n_ord)
    start, end = epoch_day("1992-01-01"), epoch_day("1998-12-31") - 151
    orders = {"o_orderkey": ((i // 8) * 32 + i % 8 + 1).astype(np.int32),
              "o_custkey": (3 * (j // 2) + j % 2 + 1).astype(np.int32),
              "o_orderdate": rng.integers(start, end + 1, n_ord
                                          ).astype(np.int32),
              "o_shippriority": np.zeros(n_ord, np.int32)}
    per_order = rng.integers(1, 8, n_ord)
    l_order = np.repeat(np.arange(n_ord, dtype=np.int32), per_order)
    n_line = len(l_order)
    partkey = rng.integers(1, n_part + 1, n_line)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    lineitem = {"l_orderkey": orders["o_orderkey"][l_order],
                "l_extendedprice": (rng.integers(1, 51, n_line) * retail
                                    ).astype(np.int64),
                "l_discount": rng.integers(0, 11, n_line).astype(np.int64),
                "l_shipdate": (orders["o_orderdate"][l_order] +
                               rng.integers(1, 122, n_line)).astype(np.int32),
                "l_order": l_order}
    return {"customer": customer, "orders": orders, "lineitem": lineitem}


def _write(dest: str, tables, parts: int, seed: int) -> int:
    """Each table as `parts` part directories (orders in key ranges, a
    line with its order), rows shuffled inside a part by `seed`; returns
    the bytes of the column files a DAG reads."""
    rng = np.random.default_rng(int(seed))
    n_cust = len(tables["customer"]["c_custkey"])
    n_ord = len(tables["orders"]["o_orderkey"])
    cust_cut = np.linspace(0, n_cust, parts + 1).astype(np.int64)
    ord_cut = np.linspace(0, n_ord, parts + 1).astype(np.int64)
    line_cut = np.searchsorted(tables["lineitem"]["l_order"], ord_cut)
    cuts = {"customer": cust_cut, "orders": ord_cut, "lineitem": line_cut}
    total = 0
    for table in TABLES:
        for p in range(parts):
            lo, hi = int(cuts[table][p]), int(cuts[table][p + 1])
            where = os.path.join(dest, table, f"part-{p:05d}")
            os.makedirs(where)
            order = lo + rng.permutation(hi - lo)
            for name, dtype in COLUMNS[table].items():
                col = tables[table][name][order].astype(dtype)
                col.tofile(os.path.join(where, name))
                total += col.nbytes
    return total


def _groups(revenue: np.ndarray, order_row: np.ndarray, orders
            ) -> Dict[str, np.ndarray]:
    """Revenue summed by order (lines of an order stand together, in order
    of order row): the query's groups."""
    if not len(order_row):
        return {k: np.zeros(0, np.int64) for k in ("key", "rev", "day",
                                                   "prio")}
    starts = np.flatnonzero(np.r_[True, order_row[1:] != order_row[:-1]])
    rows = order_row[starts]
    return {"key": orders["o_orderkey"][rows].astype(np.int64),
            "rev": np.add.reduceat(revenue, starts),
            "day": orders["o_orderdate"][rows].astype(np.int64),
            "prio": orders["o_shippriority"][rows].astype(np.int64)}


def _first(groups) -> List[Tuple[int, int, int, int]]:
    top = np.lexsort((groups["key"], groups["day"], -groups["rev"]))[:LIMIT]
    return [(int(groups["key"][t]), int(groups["rev"][t]),
             int(groups["day"][t]), int(groups["prio"][t])) for t in top]


def _query(tables, segment: str, date: int, any_segment: bool = False):
    """The reference's selection: the lines that reach the group, with
    their revenue and order row."""
    customer, orders, lineitem = (tables[t] for t in TABLES)
    in_segment = np.zeros(len(customer["c_custkey"]) + 1, dtype=bool)
    in_segment[customer["c_custkey"][
        customer["c_mktsegment"] == SEGMENTS.index(segment)]] = True
    order_ok = orders["o_orderdate"] < date
    if not any_segment:
        order_ok &= in_segment[orders["o_custkey"]]
    sel = np.flatnonzero((lineitem["l_shipdate"] > date) &
                         order_ok[lineitem["l_order"]])
    revenue = lineitem["l_extendedprice"][sel] * \
        (100 - lineitem["l_discount"][sel])
    # what reaches the join: the orders the semi-join keeps, every line the
    # scan keeps
    sent = int(order_ok.sum()) + int((lineitem["l_shipdate"] > date).sum())
    return revenue, lineitem["l_order"][sel], sent


def generate(dest: str, params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Write the three tables under `dest` and compute the answer.  Returns
    inputs (the tables' directories), input_bytes (of the column files a
    DAG reads), records (the rows the reference sends to the joins: orders
    kept and lines kept) and the reference."""
    sf = _scale(params)
    tables = _tables(sf, int(params.get("data_seed", seed)))
    input_bytes = _write(dest, tables, int(params["parts"]), seed)
    date = epoch_day(params["date"])
    revenue, order_row, sent = _query(tables, params["segment"], date)
    groups = _groups(revenue, order_row, tables["orders"])
    return {"inputs": [os.path.join(dest, t) for t in TABLES],
            "input_bytes": input_bytes,
            "records": sent,
            "reference": {
                "top": _first(groups),
                "groups": {int(k): (int(r), int(d), int(p)) for k, r, d, p in
                           zip(groups["key"], groups["rev"], groups["day"],
                               groups["prio"])},
                "lines": (revenue, order_row), "tables": tables,
                "segment": params["segment"], "date": date}}


def _parse(data: bytes) -> Tuple[List[Tuple[int, int, int, int]], int]:
    rows, bad = [], 0
    lines = data.split(b"\n")
    if lines.pop():                       # bytes behind the last newline
        bad += 1
    for line in lines:
        m = LINE.match(line)
        if m is None:
            bad += 1
            continue
        key, whole, frac, y, mo, d, prio = (int(g) for g in m.groups())
        try:
            day = (datetime.date(y, mo, d) - datetime.date(1970, 1, 1)).days
        except ValueError:
            bad += 1
            continue
        rows.append((key, whole * 10000 + frac, day, prio))
    return rows, bad


def compare(out_dir: str, reference: Dict[str, Any]) -> Dict[str, int]:
    """The numbers of one committed output directory, each held to LIMITS."""
    names = sorted(os.listdir(out_dir)) if os.path.isdir(out_dir) else []
    rows: List[Tuple[int, int, int, int]] = []
    malformed = 0
    for name in names:
        if name.startswith(("_", ".")):
            continue
        with open(os.path.join(out_dir, name), "rb") as fh:
            got, bad = _parse(fh.read())
        rows += got
        malformed += bad
    top, groups = reference["top"], reference["groups"]
    wrong, seen = 0, set()
    for rank, (key, rev, day, prio) in enumerate(rows[:len(top)]):
        right = groups.get(key) == (rev, day, prio) and \
            (rev, day) == top[rank][1:3] and key not in seen
        wrong += not right
        seen.add(key)
    committed = "_SUCCESS" in names and not any(
        n.startswith("_temporary") for n in names)
    return {"rows_wrong": wrong,
            "rows_missing_or_extra": abs(len(rows) - len(top)),
            "lines_malformed": malformed,
            "commits_missing": 0 if committed else 1}


def _lines(rows) -> bytes:
    epoch = datetime.date(1970, 1, 1)
    return b"".join(
        b"%d\t%d.%04d\t%s\t%d\n" % (
            key, rev // 10000, rev % 10000,
            (epoch + datetime.timedelta(days=day)).isoformat().encode(), prio)
        for key, rev, day, prio in rows)


def reference_output(dest: str, reference: Dict[str, Any],
                     broken: Optional[str] = None) -> None:
    """The plain reference's own committed output: its ten lines in one
    part file.  `broken` names the guarantee a control breaks:

    * ``float32_revenue``: the revenue computed and summed in float32, as a
      float path would (ranked and printed by it);
    * ``lineitem_dropped``: one line of the first-ranked order left out, as
      a probe that loses a row at a block's edge would;
    * ``segment_ignored``: the semi-join with the segment's customers left
      out: every order before the date;
    * ``committed_twice``: a second part file repeats the first's lines, as
      a re-run task committed beside the first attempt would.
    """
    orders = reference["tables"]["orders"]
    revenue, order_row = reference["lines"]
    rows = reference["top"]
    if broken == "float32_revenue":
        starts = np.flatnonzero(np.r_[True, order_row[1:] != order_row[:-1]])
        f32 = np.add.reduceat((revenue / 1e4).astype(np.float32), starts,
                              dtype=np.float32)
        groups = _groups(revenue, order_row, orders)
        groups["rev"] = np.round(f32.astype(np.float64) * 1e4).astype(
            np.int64)
        rows = _first(groups)
    elif broken == "lineitem_dropped":
        first = np.flatnonzero(orders["o_orderkey"][order_row] == rows[0][0])
        keep = np.ones(len(revenue), dtype=bool)
        keep[first[0]] = False
        rows = _first(_groups(revenue[keep], order_row[keep], orders))
    elif broken == "segment_ignored":
        rows = _first(_groups(*_query(reference["tables"],
                                      reference["segment"], reference["date"],
                                      any_segment=True)[:2], orders))
    elif broken not in (None, "committed_twice"):
        raise ValueError(f"no control {broken!r} (has: {CONTROLS})")
    os.makedirs(dest)
    pieces = [rows] + ([rows] if broken == "committed_twice" else [])
    for p, piece in enumerate(pieces):
        with open(os.path.join(dest, f"part-{p:05d}"), "wb") as fh:
            fh.write(_lines(piece))
    with open(os.path.join(dest, "_SUCCESS"), "w"):
        pass
