"""TPC-DS ``store_sales`` star at a configuration's sizes.

Shapes follow the TPC-DS specification where the program's schema
(``repro.workloads.tpcds``) has the column: a date_dim of the spec's fixed
73,049 days with sales on the spec's sold-date range, ``ss_quantity``
uniform over 1..100, prices by the spec's pricing rule (wholesale cost,
markup, discount), the spec's ten item categories with their classes, and
store < county < state.  Row counts and the hierarchy come from the
configuration file.  Foreign keys are uniform.  Fact columns are made in
fixed chunks from the seed.
"""
from __future__ import annotations

import datetime as dt

import numpy as np

from lib.data import Column, Data, chunked, coded, date_dim, rng_for


def _cents(x: np.ndarray) -> np.ndarray:
    return np.round(x, 2)


def generate(cfg: dict, seed: int) -> Data:
    rows = cfg["rows"]
    dates = date_dim(cfg["first_date"], rows["date_dim"], extra=False)
    first = dt.date.fromisoformat(cfg["first_date"])
    lo_day, hi_day = ((dt.date.fromisoformat(d) - first).days for d in cfg["sale_dates"])

    cats = cfg["categories"]  # category -> number of classes
    classes = [(c, f"{c}_{j + 1}") for c, k in cats.items() for j in range(k)]
    n_brand = cfg["brands_per_class"]
    rng = rng_for(seed, 1)
    cat_of_item = rng.integers(0, len(cats), size=rows["item"])
    first_class = np.cumsum([0] + list(cats.values()))[:-1]
    cls = first_class[cat_of_item] + (rng.random(rows["item"]) * np.asarray(
        list(cats.values()))[cat_of_item]).astype(np.int64)
    brand = cls * n_brand + rng.integers(0, n_brand, size=rows["item"])
    item = {
        "i_key": Column("int", np.arange(rows["item"], dtype=np.int32)),
        "i_brand": coded([f"{name}_brand_{k + 1}" for _, name in classes
                          for k in range(n_brand)], brand),
        "i_class": coded([name for _, name in classes], cls),
        "i_category": coded(list(cats), cat_of_item),
    }
    states = cfg["store_states"]
    n_cty = cfg["counties_per_state"]
    counties = [f"{s}_county_{j}" for s in states for j in range(n_cty)]
    c = rng_for(seed, 2).integers(0, len(counties), size=rows["store"])
    store = {
        "s_key": Column("int", np.arange(rows["store"], dtype=np.int32)),
        "s_store_name": coded([f"store_{i:06d}" for i in range(rows["store"])],
                              np.arange(rows["store"])),
        "s_county": coded(counties, c),
        "s_state": coded(states, c // n_cty),
    }
    channels = cfg["promotion_channels"]
    ch = rng_for(seed, 3).integers(0, len(channels), size=rows["promotion"])
    promotion = {
        "p_key": Column("int", np.arange(rows["promotion"], dtype=np.int32)),
        "p_channel": coded(channels, ch),
    }
    n = rows["store_sales"]
    d_date = dates["d_date"].data
    q_lo, q_hi = cfg["quantity"]
    pricing = cfg["pricing"]

    def fill(rng, lo, hi):
        m = hi - lo
        dk = rng.integers(lo_day, hi_day + 1, size=m, dtype=np.int32)
        qty = rng.integers(q_lo, q_hi + 1, size=m, dtype=np.int32)
        wholesale = _cents(rng.uniform(*pricing["wholesale_cost"], size=m))
        listp = _cents(wholesale * (1.0 + rng.uniform(*pricing["markup"], size=m)))
        sales = _cents(listp * (1.0 - rng.uniform(*pricing["discount"], size=m)))
        ext_sales = _cents(sales * qty)
        coupon = _cents(np.where(rng.random(m) < pricing["coupon_share"],
                                 ext_sales * rng.random(m), 0.0))
        paid = _cents(ext_sales - coupon)
        return {
            "ss_sold_date_key": dk,
            "ss_item_key": rng.integers(0, rows["item"], size=m, dtype=np.int32),
            "ss_store_key": rng.integers(0, rows["store"], size=m, dtype=np.int32),
            "ss_promo_key": rng.integers(0, rows["promotion"], size=m, dtype=np.int32),
            "ss_quantity": qty,
            "ss_ext_sales_price": ext_sales,
            "ss_net_paid": paid,
            "ss_net_profit": _cents(paid - _cents(wholesale * qty)),
            "ss_coupon_amt": coupon,
            "ss_date": d_date[dk],
        }

    parts = chunked(n, seed, 4, fill)
    kinds = {"ss_ext_sales_price": "float", "ss_net_paid": "float",
             "ss_net_profit": "float", "ss_coupon_amt": "float", "ss_date": "date"}
    fact = {c: Column(kinds.get(c, "int"), np.concatenate([p[c] for p in parts]))
            for c in parts[0]}
    return Data(
        fact="store_sales",
        fks={"date_dim": "ss_sold_date_key", "item": "ss_item_key",
             "store": "ss_store_key", "promotion": "ss_promo_key"},
        tables={"store_sales": fact, "date_dim": dates, "item": item,
                "store": store, "promotion": promotion})
