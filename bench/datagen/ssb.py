"""Star Schema Benchmark data at a configuration's sizes.

Shapes follow ``repro.workloads.ssb`` (the same columns, value names and
hierarchies: city < nation < region, brand < category < mfgr, date < month <
quarter < year); sizes come from the configuration file.  Keys are uniform,
as the SSB ``dbgen`` draws them.  Fact columns are made in fixed chunks from
the seed, so one seed always gives the same data.
"""
from __future__ import annotations

import numpy as np

from lib.data import Column, Data, chunked, coded, date_dim, rng_for

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]


def geo(rows: int, rng: np.random.Generator, prefix: str, cfg: dict) -> dict:
    n_nat = cfg["nations_per_region"]
    n_city = cfg["cities_per_nation"]
    nations = [f"{r[:4]}_NATION_{i}" for r in REGIONS for i in range(n_nat)]
    cities = [f"{n}_C{j}" for n in nations for j in range(n_city)]
    city = rng.integers(0, len(cities), size=rows)
    return {
        f"{prefix}_key": Column("int", np.arange(rows, dtype=np.int32)),
        f"{prefix}_city": coded(cities, city),
        f"{prefix}_nation": coded(nations, city // n_city),
        f"{prefix}_region": coded(REGIONS, city // (n_city * n_nat)),
    }


def generate(cfg: dict, seed: int) -> Data:
    rows = cfg["rows"]
    n_days = rows["dates"]
    dates = date_dim(cfg["first_date"], n_days, extra=True)
    customer = geo(rows["customer"], rng_for(seed, 1), "c", cfg)
    supplier = geo(rows["supplier"], rng_for(seed, 2), "s", cfg)
    n_cat, n_brand = cfg["categories_per_mfgr"], cfg["brands_per_category"]
    mfgrs = [f"MFGR#{i + 1}" for i in range(5)]
    cats = [f"{m}{j + 1}" for m in mfgrs for j in range(n_cat)]
    brands = [f"{c}{k + 1:02d}" for c in cats for k in range(n_brand)]
    b = rng_for(seed, 3).integers(0, len(brands), size=rows["part"])
    part = {
        "p_key": Column("int", np.arange(rows["part"], dtype=np.int32)),
        "p_brand": coded(brands, b),
        "p_category": coded(cats, b // n_brand),
        "p_mfgr": coded(mfgrs, b // (n_brand * n_cat)),
    }
    n = rows["lineorder"]
    d_date = dates["d_date"].data

    def fill(rng, lo, hi):
        m = hi - lo
        od = rng.integers(0, n_days, size=m, dtype=np.int32)
        # prices in whole cents, uniform in [100.00, 10000.00]
        cents = rng.integers(10_000, 1_000_001, size=m)
        disc = rng.integers(0, 11, size=m, dtype=np.int32)
        cost = np.rint(cents * rng.uniform(0.4, 0.8, size=m))
        return {
            "lo_orderdate": od,
            "lo_custkey": rng.integers(0, rows["customer"], size=m, dtype=np.int32),
            "lo_suppkey": rng.integers(0, rows["supplier"], size=m, dtype=np.int32),
            "lo_partkey": rng.integers(0, rows["part"], size=m, dtype=np.int32),
            "lo_quantity": rng.integers(1, 51, size=m, dtype=np.int32),
            "lo_extendedprice": cents / 100.0,
            "lo_discount": disc,
            "lo_revenue": ((cents * (100 - disc) + 50) // 100) / 100.0,
            "lo_supplycost": cost / 100.0,
            "lo_date": d_date[od],
        }

    parts = chunked(n, seed, 4, fill)
    kinds = {"lo_extendedprice": "float", "lo_revenue": "float",
             "lo_supplycost": "float", "lo_date": "date"}
    fact = {c: Column(kinds.get(c, "int"), np.concatenate([p[c] for p in parts]))
            for c in parts[0]}
    return Data(
        fact="lineorder",
        fks={"dates": "lo_orderdate", "customer": "lo_custkey",
             "supplier": "lo_suppkey", "part": "lo_partkey"},
        tables={"lineorder": fact, "dates": dates, "customer": customer,
                "supplier": supplier, "part": part})
