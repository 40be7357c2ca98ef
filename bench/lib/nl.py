"""Plain-English questions from the generator's intents, and the schema
header that a model canonicalizer reads before each question.

The questions use absolute literals only (years, names, ISO dates, numbers):
nothing is relative to a current date, so a question means the same on
every run.  A column is named by its short name with the table's short
prefix and the underscores dropped (``store_sales.ss_ext_sales_price`` ->
"ext sales price").
"""
from __future__ import annotations

from .data import Data

AGG = {"SUM": "total", "AVG": "average", "MIN": "minimum", "MAX": "maximum",
       "COUNT": "count of"}
OPS = {"+": "plus", "-": "minus", "*": "times", "/": "divided by"}
CMP = {"=": "is", "!=": "is not", "<": "below", "<=": "at most", ">": "above",
       ">=": "at least"}


def words(column: str) -> str:
    """'lineorder.lo_extendedprice' -> 'extendedprice'."""
    name = column.split(".", 1)[1]
    head, _, rest = name.partition("_")
    if rest and len(head) <= 3:
        name = rest
    return name.replace("_", " ")


def _expr(e) -> str:
    if isinstance(e, str):
        return words(e)
    return f"{_expr(e[1])} {OPS[e[0]]} {_expr(e[2])}"


def _measure(agg: str, e) -> str:
    if agg == "COUNT" and e == "*":
        return "number of rows"
    return f"{AGG[agg]} {_expr(e)}"


def _filter(col: str, op: str, val) -> str:
    if op == "between":
        return f"{words(col)} between {val[0]} and {val[1]}"
    if op == "in":
        vals = [str(v) for v in val]
        return f"{words(col)} in {', '.join(vals[:-1])} or {vals[-1]}" if len(vals) > 1 \
            else f"{words(col)} is {vals[0]}"
    return f"{words(col)} {CMP[op]} {val}"


def _join(parts: list[str]) -> str:
    return parts[0] if len(parts) == 1 else f"{', '.join(parts[:-1])} and {parts[-1]}"


def question(intent: dict) -> str:
    """One question for one intent, e.g. 'total revenue by year and brand
    where category is MFGR#12 and region is AMERICA'."""
    text = _join([_measure(a, e) for a, e in intent["measures"]])
    if intent["levels"]:
        text += " by " + _join([words(lv) for lv in intent["levels"]])
    if intent["filters"]:
        text += " where " + _join([_filter(*f) for f in intent["filters"]])
    return text


def header(data: Data, n_words: int) -> str:
    """The first ``n_words`` words of a description of the star, names in
    double quotes as a signature writes them: the fact table and its
    columns, each dimension and its columns, then the values of the string
    columns with at most 64 values.  Ends with a newline."""
    def q(x):
        return f'"{x}"'

    out = ["fact", q(data.fact), "columns"]
    out += [q(f"{data.fact}.{c}") for c in data.tables[data.fact]]
    for t in data.tables:
        if t != data.fact:
            out += ["dimension", q(t), "columns"] + [q(f"{t}.{c}") for c in data.tables[t]]
    for t, cols in data.tables.items():
        for c, col in cols.items():
            if col.kind == "str" and len(col.vocab) <= 64:
                out += [q(f"{t}.{c}"), "values"] + [q(v) for v in col.vocab]
    return " ".join(out[:n_words]) + "\n"
