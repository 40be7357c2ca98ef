"""Raw star-schema data as the benchmark makes it, before the program sees it.

A generator under ``bench/datagen/<schema>.py`` returns a :class:`Data`.  The
reference reads these arrays directly; ``to_dataset`` hands the same arrays
to the program's own storage classes.  String columns are dictionary codes
over a sorted vocabulary, dates are int32 days since 1970-01-01.
"""
from __future__ import annotations

import dataclasses
import datetime as dt
import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

import numpy as np

EPOCH = dt.date(1970, 1, 1)
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Column:
    kind: str  # 'int' | 'float' | 'str' | 'date'
    data: np.ndarray
    vocab: Optional[np.ndarray] = None  # sorted unique strings of a 'str' column

    def decode(self, codes: np.ndarray) -> np.ndarray:
        """Values of the given physical codes in the user's domain: strings,
        ISO dates or numbers."""
        if self.kind == "str":
            return self.vocab[codes]
        if self.kind == "date":
            return np.asarray([(EPOCH + dt.timedelta(days=int(d))).isoformat()
                               for d in codes])
        return codes

    def encode(self, value) -> float:
        """A literal in the column's physical domain (-1 for an absent string)."""
        if self.kind == "str":
            i = int(np.searchsorted(self.vocab, str(value)))
            return i if i < len(self.vocab) and self.vocab[i] == str(value) else -1
        if self.kind == "date":
            return (dt.date.fromisoformat(str(value)) - EPOCH).days
        return value


@dataclasses.dataclass
class Data:
    fact: str
    fks: dict[str, str]  # dimension name -> fact foreign-key column
    tables: dict[str, dict[str, Column]]

    @property
    def num_rows(self) -> int:
        return len(next(iter(self.tables[self.fact].values())).data)

    def column(self, qualified: str) -> Column:
        t, c = qualified.split(".", 1)
        return self.tables[t][c]


def coded(values: list[str], codes: np.ndarray) -> Column:
    """A string column from a vocabulary (any order) and codes into it."""
    vocab = np.asarray(values)
    order = np.argsort(vocab, kind="stable")
    rank = np.empty(len(order), np.int32)
    rank[order] = np.arange(len(order), dtype=np.int32)
    return Column("str", rank[np.asarray(codes)], vocab[order])


def days(first: str, n: int) -> list[dt.date]:
    d0 = dt.date.fromisoformat(first)
    return [d0 + dt.timedelta(days=i) for i in range(n)]


def date_dim(first: str, n: int, extra: bool) -> dict[str, Column]:
    """Calendar dimension: key, date, month, quarter, year (and, with
    ``extra``, SSB's yearmonthnum and week number)."""
    ds = days(first, n)
    mon = ["Jan", "Feb", "Mar", "Apr", "May", "Jun",
           "Jul", "Aug", "Sep", "Oct", "Nov", "Dec"]
    ym = [f"{mon[d.month - 1]}{d.year}" for d in ds]
    q = [f"{d.year}Q{(d.month - 1) // 3 + 1}" for d in ds]
    out = {
        "d_key": Column("int", np.arange(n, dtype=np.int32)),
        "d_date": Column("date", np.asarray([(d - EPOCH).days for d in ds], np.int32)),
        "d_yearmonth": _from_strings(ym),
        "d_quarter": _from_strings(q),
        "d_year": Column("int", np.asarray([d.year for d in ds], np.int32)),
    }
    if extra:
        out["d_yearmonthnum"] = Column(
            "int", np.asarray([d.year * 100 + d.month for d in ds], np.int32))
        out["d_weeknuminyear"] = Column(
            "int", np.asarray([d.isocalendar()[1] for d in ds], np.int32))
    return out


def _from_strings(values: list[str]) -> Column:
    vocab, codes = np.unique(np.asarray(values), return_inverse=True)
    return Column("str", codes.astype(np.int32), vocab)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Generator for one named stream of one seed (any integer seed)."""
    s = int(seed)
    words = [abs(s) & 0xFFFFFFFF, (abs(s) >> 32) & 0xFFFFFFFF, int(s < 0), *stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def chunked(n: int, seed: int, stream: int, fill: Callable, chunks: int = 16,
            workers: int = 12) -> list:
    """Run ``fill(rng, lo, hi)`` over ``chunks`` fixed row ranges, each with
    its own generator, on a few threads; the result depends only on the seed."""
    bounds = np.linspace(0, n, chunks + 1).astype(np.int64)

    def one(i):
        return fill(rng_for(seed, stream, i), int(bounds[i]), int(bounds[i + 1]))

    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(one, range(chunks)))


def generate(cfg: dict, seed: int) -> Data:
    """The data of a configuration, from the generator its ``schema`` names."""
    path = os.path.join(BENCH, "datagen", f"{cfg['schema']}.py")
    spec = importlib.util.spec_from_file_location(f"bench_datagen_{cfg['schema']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate(cfg, seed)


def to_dataset(data: Data, schema):
    """The program's ``Dataset`` over the same arrays (no copies)."""
    from repro.olap.columnar import ColumnData, Dataset, TableData

    def table(name):
        return TableData(name, {c: ColumnData(col.kind, col.data, col.vocab)
                                for c, col in data.tables[name].items()})

    return Dataset(schema, table(data.fact),
                   {t: table(t) for t in data.tables if t != data.fact})
