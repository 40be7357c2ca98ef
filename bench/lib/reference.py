"""The plain reference: star-schema aggregation in numpy float64.

It works from the intent that the traffic generator rendered (levels,
measures, filters), over the arrays that the benchmark generated, and shares
no code with the program.  An intent is a dict::

    {"levels": ["dates.d_year", "part.p_brand"],
     "measures": [["SUM", "lineorder.lo_revenue"], ["COUNT", "*"],
                  ["SUM", ["*", "lineorder.lo_extendedprice", "lineorder.lo_discount"]]],
     "filters": [["dates.d_year", "=", 1994], ["lineorder.lo_quantity", "between", [1, 20]]]}

``precision="bfloat16"`` rounds every measure value to bfloat16 before it is
aggregated (in float64): the control that a correct comparison has to fail.
"""
from __future__ import annotations

import dataclasses
import re
import threading
from typing import Optional

import ml_dtypes
import numpy as np

from .data import Data

OPS = {"+": np.add, "-": np.subtract, "*": np.multiply, "/": np.divide}


@dataclasses.dataclass
class RefTable:
    levels: list[str]
    keys: list[tuple]  # one tuple of decoded level values per row
    values: np.ndarray  # (rows, measures) float64
    scales: np.ndarray  # (rows, measures): the size that each error is taken against


def measure_columns(expr) -> list[str]:
    if expr == "*":
        return []
    if isinstance(expr, str):
        return [expr]
    return measure_columns(expr[1]) + measure_columns(expr[2])


def render_expr(expr) -> str:
    """SQL text of a measure expression, with unqualified column names."""
    if expr == "*":
        return "*"
    if isinstance(expr, str):
        return expr.split(".", 1)[1]
    return f"{render_expr(expr[1])} {expr[0]} {render_expr(expr[2])}"


_TOKEN = re.compile(r"\s*([A-Za-z_][A-Za-z_0-9]*\.[A-Za-z_][A-Za-z_0-9]*|[-+*/()])")


def parse_expr(text: str):
    """The expression tree of a signature's measure text, e.g.
    ``(lineorder.lo_discount*lineorder.lo_extendedprice)`` ->
    ``["*", "lineorder.lo_discount", "lineorder.lo_extendedprice"]``; None
    where the text holds anything but qualified columns, + - * / and
    parentheses."""
    if text.strip() == "*":
        return "*"
    toks, pos = [], 0
    while pos < len(text.rstrip()):
        m = _TOKEN.match(text, pos)
        if m is None:
            return None
        toks.append(m.group(1))
        pos = m.end()
    at = [0]

    def peek():
        return toks[at[0]] if at[0] < len(toks) else None

    def take():
        at[0] += 1
        return toks[at[0] - 1]

    def atom():
        t = peek()
        if t == "(":
            take()
            e = expr()
            if e is None or peek() != ")":
                return None
            take()
            return e
        if t is None or t in "+-*/()":
            return None
        return take()

    def chain(sub, ops):
        e = sub()
        while e is not None and peek() in ops:
            op = take()
            r = sub()
            e = None if r is None else [op, e, r]
        return e

    def expr():
        return chain(lambda: chain(atom, ("*", "/")), ("+", "-"))

    e = expr()
    return e if e is not None and at[0] == len(toks) else None


def intent_of_signature(sig: dict, date_column: Optional[str]) -> Optional[dict]:
    """The intent form of a served signature, from its public JSON fields
    (``Signature.to_json()``): levels, measures and filters, with a time
    window as two filters on ``date_column``.  None where the signature
    asks what the reference does not compute (HAVING, ORDER BY, LIMIT, a
    distinct count, a governed metric, an expression it cannot parse)."""
    if sig.get("having") or sig.get("order_by") or sig.get("limit") is not None \
            or sig.get("metric_id") is not None:
        return None
    measures = []
    for m in sig["measures"]:
        e = parse_expr(m["expr"])
        if m.get("distinct") or m["agg"] not in ("SUM", "COUNT", "MIN", "MAX", "AVG") \
                or e is None:
            return None
        measures.append([m["agg"], e])
    filters = [[f["col"], f["op"], list(f["val"]) if isinstance(f["val"], (list, tuple))
                else f["val"]] for f in sig.get("filters", ())]
    window = sig.get("time_window")
    if window:
        if date_column is None:
            return None
        filters += [[date_column, ">=", window["start"]], [date_column, "<", window["end"]]]
    return {"levels": list(sig.get("levels", ())), "measures": measures, "filters": filters}


def filter_key(filters) -> tuple:
    return tuple(sorted((f[0], f[1], repr(f[2])) for f in filters))


class Reference:
    def __init__(self, data: Data):
        self.data = data
        self._aligned: dict[str, np.ndarray] = {}
        self._levels: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        self._values: dict[str, np.ndarray] = {}
        self._masks: dict[tuple, np.ndarray] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------ columns
    def aligned(self, qualified: str) -> np.ndarray:
        """Physical values of ``table.column`` for every fact row."""
        hit = self._aligned.get(qualified)
        if hit is None:
            t, c = qualified.split(".", 1)
            col = self.data.tables[t][c].data
            if t == self.data.fact:
                hit = col
            else:
                hit = col[self.data.tables[self.data.fact][self.data.fks[t]].data]
            self._aligned[qualified] = hit
        return hit

    def level(self, qualified: str) -> tuple[np.ndarray, np.ndarray]:
        """(dense id of every fact row, physical value of each id)."""
        hit = self._levels.get(qualified)
        if hit is None:
            t, c = qualified.split(".", 1)
            col = self.data.tables[t][c].data
            uniq, inv = np.unique(col, return_inverse=True)
            if t == self.data.fact:
                ids = inv.astype(np.int32)
            else:
                ids = inv.astype(np.int32)[self.data.tables[self.data.fact][self.data.fks[t]].data]
            hit = self._levels[qualified] = (ids, uniq)
        return hit

    def value(self, expr) -> np.ndarray:
        if isinstance(expr, str):
            hit = self._values.get(expr)
            if hit is None:
                t, c = expr.split(".", 1)
                hit = self._values[expr] = self.data.tables[t][c].data.astype(np.float64)
            return hit
        return OPS[expr[0]](self.value(expr[1]), self.value(expr[2]))

    # ------------------------------------------------------------- filters
    def mask(self, filters) -> np.ndarray:
        key = filter_key(filters)
        hit = self._masks.get(key)
        if hit is None:
            n = self.data.num_rows
            hit = np.ones(n, bool)
            for col, op, val in filters:
                c = self.data.column(col)
                x = self.aligned(col)
                if op == "between":
                    lo, hi = c.encode(val[0]), c.encode(val[1])
                    hit &= (x >= lo) & (x <= hi)
                elif op == "in":
                    hit &= np.isin(x, [c.encode(v) for v in val])
                else:
                    v = c.encode(val)
                    if c.kind == "str" and v < 0:
                        hit &= op == "!="
                        continue
                    hit &= {"=": np.equal, "!=": np.not_equal, "<": np.less,
                            "<=": np.less_equal, ">": np.greater,
                            ">=": np.greater_equal}[op](x, v)
            with self._lock:
                if len(self._masks) >= 32:
                    self._masks.clear()
                self._masks[key] = hit
        return hit

    def selected_rows(self, intents) -> int:
        """Fact rows that at least one of the intents selects."""
        union = None
        for it in intents:
            m = self.mask(it["filters"])
            union = m.copy() if union is None else (union | m)
        return 0 if union is None else int(np.count_nonzero(union))

    # --------------------------------------------------------------- tables
    def table(self, intent: dict, precision: str = "float64") -> RefTable:
        levels = list(intent["levels"])
        mask = self.mask(intent["filters"])
        n_sel = int(np.count_nonzero(mask))
        if levels:
            gid = np.zeros(n_sel, np.int64)
            cards = []
            for lv in levels:
                ids, uniq = self.level(lv)
                gid = gid * len(uniq) + ids[mask]
                cards.append(len(uniq))
            n_groups = int(np.prod(cards))
        else:
            gid = np.zeros(n_sel, np.int64)
            cards, n_groups = [], 1
        if n_groups > (1 << 26):
            uniq_g, gid = np.unique(gid, return_inverse=True)
            n_groups = len(uniq_g)
        else:
            uniq_g = None
        count = np.bincount(gid, minlength=n_groups).astype(np.float64)
        cols, scales = [], []
        for agg, expr in intent["measures"]:
            if agg == "COUNT" and expr == "*":
                cols.append(count)
                scales.append(np.maximum(count, 1.0))
                continue
            v = self.value(expr)[mask]
            if precision == "bfloat16":
                v = v.astype(ml_dtypes.bfloat16).astype(np.float64)
            if agg == "COUNT":
                ones = np.isfinite(v).astype(np.float64)
                c = np.bincount(gid, ones, minlength=n_groups)
                cols.append(c)
                scales.append(np.maximum(c, 1.0))
            elif agg in ("SUM", "AVG"):
                s = np.bincount(gid, v, minlength=n_groups)
                a = np.bincount(gid, np.abs(v), minlength=n_groups)
                if agg == "AVG":
                    with np.errstate(invalid="ignore", divide="ignore"):
                        s = s / count
                        a = a / count
                cols.append(s)
                scales.append(a)
            elif agg in ("MIN", "MAX"):
                out = np.full(n_groups, np.inf if agg == "MIN" else -np.inf)
                (np.minimum if agg == "MIN" else np.maximum).at(out, gid, v)
                cols.append(out)
                scales.append(np.abs(out))
            else:
                raise ValueError(f"the reference has no {agg}")
        keep = count > 0 if levels else np.ones(1, bool)
        rows = np.nonzero(keep)[0]
        keys = self._decode(levels, cards, rows if uniq_g is None else uniq_g[rows])
        values = np.stack([c[rows] for c in cols], axis=1) if cols else np.zeros((len(rows), 0))
        sc = np.stack([s[rows] for s in scales], axis=1) if scales else values
        return RefTable(levels, keys, values, sc)

    def _decode(self, levels, cards, group_ids) -> list[tuple]:
        if not levels:
            return [()]
        comps = []
        rem = np.asarray(group_ids, np.int64)
        for card in reversed(cards):
            comps.append(rem % card)
            rem = rem // card
        comps.reverse()
        decoded = []
        for lv, comp in zip(levels, comps):
            _, uniq = self.level(lv)
            decoded.append(self.data.column(lv).decode(uniq[comp]))
        return [tuple(_native(x) for x in row) for row in zip(*decoded)]


def _native(x):
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.str_, str)):
        return str(x)
    return x


# ------------------------------------------------------------ comparison
def match_measures(intent_measures, program_measures) -> Optional[list[int]]:
    """For each of the program's measures (agg, canonical expression text),
    the index of the intent's measure that it computes; None where one has
    no counterpart."""
    import re

    def sig(agg, cols, ops):
        return (agg, tuple(sorted(cols)), tuple(sorted(ops)))

    mine = []
    for agg, expr in intent_measures:
        ops = []

        def walk(e):
            if isinstance(e, list):
                ops.append(e[0])
                walk(e[1])
                walk(e[2])

        walk(expr)
        mine.append(sig(agg, measure_columns(expr), ops))
    out = []
    for agg, text in program_measures:
        cols = re.findall(r"[a-z_][a-z_0-9]*\.[a-z_][a-z_0-9]*", text)
        ops = [] if text == "*" else [
            c for c in re.sub(r"[a-z_][a-z_0-9]*\.[a-z_][a-z_0-9]*", "", text) if c in "+-*/"]
        s = sig(agg, cols, ops)
        if s not in mine:
            return None
        out.append(mine.index(s))
    return out


def compare(ref: RefTable, columns: dict, mapping: list[int]) -> tuple[bool, float]:
    """(group keys equal, worst error of a measure) of a served table, given
    as ``{name: array}`` with the level columns under their qualified names
    and measure ``m{i}`` computing the intent's measure ``mapping[i]``.

    The error of a value is its distance from the reference over the size it
    is taken against: the sum of absolute values for SUM and AVG, the count
    for COUNT, the value itself for MIN and MAX."""
    n = len(next(iter(columns.values()))) if columns else 0
    if n != len(ref.keys):
        return False, float("inf")
    if any(lv not in columns for lv in ref.levels):
        return False, float("inf")
    served = list(zip(*[[_native(x) for x in columns[lv]] for lv in ref.levels])) \
        if ref.levels else [()] * n
    index = {k: i for i, k in enumerate(ref.keys)}
    if len(index) != len(ref.keys) or sorted(map(repr, served)) != sorted(map(repr, ref.keys)):
        return False, float("inf")
    rows = np.asarray([index[k] for k in served], np.int64)
    worst = 0.0
    for i, j in enumerate(mapping):
        got = np.asarray(columns[f"m{i}"], np.float64)
        want = ref.values[rows, j]
        scale = ref.scales[rows, j]
        both_nan = np.isnan(got) & np.isnan(want)
        diff = np.where(both_nan, 0.0, np.abs(got - want))
        err = np.where(scale > 0, diff / np.where(scale > 0, scale, 1.0), diff)
        err = np.where(np.isnan(err), np.inf, err)
        if len(err):
            worst = max(worst, float(err.max()))
    return True, worst
