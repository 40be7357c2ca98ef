"""OLAP executor: numpy oracle vs seg_agg (XLA + interpret) paths, and
SQL-semantics corner cases."""
import numpy as np
import pytest  # noqa: F401
from _hyp import given, settings, st

from repro.core.sql_canon import SQLCanonicalizer
from repro.olap.executor import OlapExecutor


def test_all_intents_numpy_vs_xla(ssb_small, tlc_small, tpcds_small):
    """The kernel-dispatch path must equal the independent numpy oracle for
    every canonical intent of every workload."""
    for wl in (ssb_small, tlc_small, tpcds_small):
        canon = SQLCanonicalizer(wl.schema)
        ex_np = OlapExecutor(wl.dataset, impl="numpy")
        ex_xla = OlapExecutor(wl.dataset, impl="xla")
        for intent in wl.intents:
            sig = canon.canonicalize(intent.sql)
            a = ex_np.execute(sig)
            b = ex_xla.execute(sig)
            assert a.equals(b, ordered=bool(sig.order_by)), intent.id


def test_interpret_kernel_path(ssb_small):
    canon = SQLCanonicalizer(ssb_small.schema)
    ex_np = OlapExecutor(ssb_small.dataset, impl="numpy")
    ex_pl = OlapExecutor(ssb_small.dataset, impl="interpret")
    for intent in ssb_small.intents[:4]:
        sig = canon.canonicalize(intent.sql)
        assert ex_np.execute(sig).equals(ex_pl.execute(sig)), intent.id


def test_empty_groups_absent(ssb_small):
    canon = SQLCanonicalizer(ssb_small.schema)
    ex = OlapExecutor(ssb_small.dataset, impl="numpy")
    sig = canon.canonicalize(
        "SELECT c_region, COUNT(*) AS n FROM lineorder "
        "JOIN customer ON lineorder.lo_custkey = customer.c_key "
        "WHERE lo_quantity > 9999 GROUP BY c_region")
    assert ex.execute(sig).num_rows == 0


def test_global_aggregate_single_row(ssb_small):
    canon = SQLCanonicalizer(ssb_small.schema)
    ex = OlapExecutor(ssb_small.dataset, impl="numpy")
    sig = canon.canonicalize("SELECT SUM(lo_revenue) AS r FROM lineorder")
    t = ex.execute(sig)
    assert t.num_rows == 1
    expected = float(np.sum(ssb_small.dataset.fact.columns["lo_revenue"].data))
    assert abs(float(t.columns["m0"][0]) - expected) / expected < 1e-9


def test_having_order_limit(ssb_small):
    canon = SQLCanonicalizer(ssb_small.schema)
    ex = OlapExecutor(ssb_small.dataset, impl="numpy")
    sig = canon.canonicalize(
        "SELECT c_nation, SUM(lo_revenue) AS r FROM lineorder "
        "JOIN customer ON lineorder.lo_custkey = customer.c_key "
        "GROUP BY c_nation HAVING SUM(lo_revenue) > 0 ORDER BY r DESC LIMIT 5")
    t = ex.execute(sig)
    assert t.num_rows == 5
    vals = t.columns["m0"]
    assert all(vals[i] >= vals[i + 1] for i in range(len(vals) - 1))


@settings(max_examples=20, deadline=None)
@given(
    qty=st.integers(1, 50),
    op=st.sampled_from(["<", "<=", ">", ">="]),
    year=st.integers(1992, 1998),
)
def test_filter_property_vs_oracle(qty, op, year):
    """Executor results == direct numpy computation for arbitrary filters."""
    wl = _wl()
    canon = SQLCanonicalizer(wl.schema)
    ex = OlapExecutor(wl.dataset, impl="xla")
    sig = canon.canonicalize(
        f"SELECT SUM(lo_revenue) AS r, COUNT(*) AS n FROM lineorder "
        f"JOIN dates ON lineorder.lo_orderdate = dates.d_key "
        f"WHERE lo_quantity {op} {qty} AND d_year = {year}")
    t = ex.execute(sig)
    f = wl.dataset.fact.columns
    years = wl.dataset.fact_aligned("dates.d_year")
    m = {"<": np.less, "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal}
    mask = m[op](f["lo_quantity"].data, qty) & (years == year)
    np.testing.assert_allclose(float(t.columns["m0"][0]),
                               float(f["lo_revenue"].data[mask].sum()), rtol=1e-6)
    assert int(t.columns["m1"][0]) == int(mask.sum())


_CACHE = {}


def _wl():
    if "wl" not in _CACHE:
        from repro.workloads import ssb

        _CACHE["wl"] = ssb.build(n_fact=3000, seed=3)
    return _CACHE["wl"]


# ------------------------------------------- group ids compacted to observed

_SPARSE_SQL = (
    "SELECT d_year, s_state, SUM(ss_ext_sales_price) AS s, COUNT(*) AS n, "
    "MIN(ss_net_profit) AS lo, MAX(ss_net_paid) AS hi, AVG(ss_quantity) AS q "
    "FROM store_sales "
    "JOIN date_dim ON store_sales.ss_sold_date_key = date_dim.d_key "
    "JOIN store ON store_sales.ss_store_key = store.s_key "
    "WHERE ss_quantity BETWEEN {lo} AND {hi} GROUP BY d_year, s_state")
_DIM_YEARS = range(2000, 2010)  # date_dim: 10 years
_SALE_YEARS = (2001, 2003, 2004)  # the fact rows reference only these
_STATES = ("GA", "IL", "OH", "TX")


def _sparse_star(n_fact: int = 1200, seed: int = 5):
    """A TPC-DS-shaped star whose date_dim spans 10 years while its fact
    rows, sorted by date, fall in 3 of them: 40 dense (year, state) groups,
    12 observed.  The first half of the rows holds 2001 and 2003, the second
    2003 and 2004, so two row partitions observe different groups."""
    import datetime as dt

    from repro.olap.columnar import ColumnData, Dataset, TableData, date_to_days
    from repro.workloads import tpcds

    rng = np.random.default_rng(seed)
    first = dt.date(_DIM_YEARS[0], 1, 1)
    dates = [first + dt.timedelta(days=i)
             for i in range((dt.date(_DIM_YEARS[-1], 12, 31) - first).days + 1)]
    years = np.asarray([d.year for d in dates])
    date_dim = TableData("date_dim", {
        "d_key": ColumnData("int", np.arange(len(dates))),
        "d_date": ColumnData("date", np.asarray([d.isoformat() for d in dates])),
        "d_yearmonth": ColumnData("str", np.asarray(
            [f"{d.year}-{d.month:02d}" for d in dates])),
        "d_quarter": ColumnData("str", np.asarray(
            [f"{d.year}Q{(d.month - 1) // 3 + 1}" for d in dates])),
        "d_year": ColumnData("int", years),
    })
    n_store = 8
    store = TableData("store", {
        "s_key": ColumnData("int", np.arange(n_store)),
        "s_store_name": ColumnData("str", np.asarray(
            [f"store_{i}" for i in range(n_store)])),
        "s_county": ColumnData("str", np.asarray(
            [f"{_STATES[i % 4]}_county" for i in range(n_store)])),
        "s_state": ColumnData("str", np.asarray(
            [_STATES[i % 4] for i in range(n_store)])),
    })
    item = TableData("item", {
        "i_key": ColumnData("int", np.arange(4)),
        "i_brand": ColumnData("str", np.asarray(["b0", "b1", "b2", "b3"])),
        "i_class": ColumnData("str", np.asarray(["c0", "c0", "c1", "c1"])),
        "i_category": ColumnData("str", np.asarray(["Books", "Books", "Home", "Home"])),
    })
    promotion = TableData("promotion", {
        "p_key": ColumnData("int", np.arange(2)),
        "p_channel": ColumnData("str", np.asarray(["email", "tv"])),
    })
    half = n_fact // 2
    days_in = {y: np.flatnonzero(years == y) for y in _SALE_YEARS}
    dk = np.sort(np.concatenate([
        rng.choice(np.concatenate([days_in[2001], days_in[2003]]), half),
        rng.choice(np.concatenate([days_in[2003], days_in[2004]]), n_fact - half)]))
    qty = rng.integers(1, 20, n_fact)
    price = np.round(rng.uniform(5, 300, n_fact) * qty, 2)
    paid = np.round(price * rng.uniform(0.8, 1.0, n_fact), 2)
    fact = TableData("store_sales", {
        "ss_sold_date_key": ColumnData("int", dk),
        "ss_item_key": ColumnData("int", rng.integers(0, 4, n_fact)),
        "ss_store_key": ColumnData("int", rng.integers(0, n_store, n_fact)),
        "ss_promo_key": ColumnData("int", rng.integers(0, 2, n_fact)),
        "ss_quantity": ColumnData("int", qty),
        "ss_ext_sales_price": ColumnData("float", price),
        "ss_net_paid": ColumnData("float", paid),
        "ss_net_profit": ColumnData("float", np.round(
            paid - price * rng.uniform(0.5, 0.9, n_fact), 2)),
        "ss_coupon_amt": ColumnData("float", np.round(price - paid, 2)),
        "ss_date": ColumnData("date", (dk + date_to_days(first.isoformat()))
                              .astype(np.int32)),
    })
    return Dataset(tpcds.build_schema(), fact, {
        "date_dim": date_dim, "item": item, "store": store,
        "promotion": promotion})


def _groupby_reference(ds, lo: int, hi: int) -> list[tuple]:
    """Plain loop group-by over the raw arrays, rows in (year, state)
    order: (year, state, SUM, COUNT, MIN, MAX, AVG)."""
    f = {k: c.data for k, c in ds.fact.columns.items()}
    year = ds.dims["date_dim"].columns["d_year"].data[f["ss_sold_date_key"]]
    st = ds.dims["store"].columns["s_state"]
    state = st.vocab[st.data[f["ss_store_key"]]]
    sel = (f["ss_quantity"] >= lo) & (f["ss_quantity"] <= hi)
    rows = []
    for y, s in sorted(set(zip(year[sel].tolist(), state[sel].tolist()))):
        m = sel & (year == y) & (state == s)
        rows.append((y, s, float(f["ss_ext_sales_price"][m].sum()), int(m.sum()),
                     float(f["ss_net_profit"][m].min()),
                     float(f["ss_net_paid"][m].max()),
                     float(f["ss_quantity"][m].mean())))
    return rows


def _assert_matches(table, ref, ordered: bool = True) -> None:
    cols = table.columns
    got = list(zip(cols["date_dim.d_year"].tolist(), cols["store.s_state"].tolist(),
                   *(cols[f"m{i}"].tolist() for i in range(5))))
    if not ordered:
        got.sort(key=lambda r: r[:2])
    assert [r[:2] for r in got] == [r[:2] for r in ref]
    np.testing.assert_allclose(np.asarray([r[2:] for r in got], np.float64),
                               np.asarray([r[2:] for r in ref], np.float64),
                               rtol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_group_ids_compact_to_observed_groups(impl):
    """40 dense (year, state) groups, 12 observed: the kernels reduce 12,
    and single and shared scans equal a plain group-by in group order."""
    ds = _sparse_star()
    canon = SQLCanonicalizer(ds.schema)
    ex = OlapExecutor(ds, impl=impl)
    levels = [ex._level_plan(lv) for lv in ("date_dim.d_year", "store.s_state")]
    gids, n_groups, sparse_uniq = ex._group_ids(levels)
    assert n_groups == len(_SALE_YEARS) * len(_STATES) == 12
    assert levels[0].card * levels[1].card == 40
    assert gids.max() == n_groups - 1 and len(sparse_uniq) == n_groups
    assert np.all(np.diff(sparse_uniq) > 0)  # ascending: group order kept
    sigs = [canon.canonicalize(_SPARSE_SQL.format(lo=lo, hi=hi))
            for lo, hi in ((1, 19), (3, 9), (12, 12))]
    _assert_matches(ex.execute(sigs[0]), _groupby_reference(ds, 1, 19))
    for sig, (lo, hi), t in zip(sigs[1:], ((3, 9), (12, 12)),
                                ex.execute_batch(sigs[1:])):
        _assert_matches(t, _groupby_reference(ds, lo, hi))


def test_group_ids_fully_observed_unchanged():
    """Every dense group observed: no compaction table, the dense ids."""
    ds = _sparse_star()
    ex = OlapExecutor(ds, impl="xla")
    lp = ex._level_plan("store.s_state")
    gids, n_groups, sparse_uniq = ex._group_ids([lp])
    assert sparse_uniq is None and n_groups == lp.card == len(_STATES)
    np.testing.assert_array_equal(gids, lp.codes)


def test_group_ids_follow_append_into_new_year():
    """A fact row appended in a year that had no sales forms its own group
    on the next query (the append clears the memoized ids)."""
    ds = _sparse_star()
    canon = SQLCanonicalizer(ds.schema)
    sig = canon.canonicalize(_SPARSE_SQL.format(lo=1, hi=19))
    ex = OlapExecutor(ds, impl="xla")
    assert ex.execute(sig).num_rows == 12
    f = ds.fact.columns
    day = int(np.flatnonzero(ds.dims["date_dim"].columns["d_year"].data == 2007)[0])
    row = {k: c.data[:1].copy() for k, c in f.items()}
    row["ss_sold_date_key"] = np.asarray([day])
    row["ss_date"] = np.asarray([int(f["ss_date"].data[0])
                                 + day - int(f["ss_sold_date_key"].data[0])])
    ds.append_rows(row)
    t = ex.execute(sig)
    assert t.num_rows == 13 and 2007 in t.columns["date_dim.d_year"].tolist()
    _assert_matches(t, _groupby_reference(ds, 1, 19))


def test_group_ids_partitions_observe_different_groups():
    """Two row partitions observe different groups (2001/2003 and
    2003/2004); the merged answer equals the unpartitioned one."""
    ds = _sparse_star()
    canon = SQLCanonicalizer(ds.schema)
    sig = canon.canonicalize(_SPARSE_SQL.format(lo=2, hi=15))
    whole = OlapExecutor(ds, impl="xla").execute(sig)
    ex = OlapExecutor(ds, impl="xla", partitions=2)
    parted = ex.execute(sig)
    assert ex.stats()["partitioned_scans"] == 1
    halves = [ds.slice_rows(0, 600), ds.slice_rows(600, 1200)]
    seen = [set(h.fact_aligned("date_dim.d_year").tolist()) for h in halves]
    assert seen == [{2001, 2003}, {2003, 2004}]
    assert parted.equals(whole)
    _assert_matches(parted, _groupby_reference(ds, 2, 15), ordered=False)


def test_group_space_counters():
    """stats() sums the dense and the compacted group counts over fused
    single scans and shared scans, and over a partitioned scan's parts."""
    ds = _sparse_star()
    canon = SQLCanonicalizer(ds.schema)
    sigs = [canon.canonicalize(_SPARSE_SQL.format(lo=lo, hi=hi))
            for lo, hi in ((1, 19), (3, 9))]
    ex = OlapExecutor(ds, impl="xla")
    ex.execute(sigs[0])  # one fused single scan
    ex.execute_batch(sigs)  # one shared scan
    st = ex.stats()
    assert (st["dense_groups"], st["kernel_groups"]) == (2 * 40, 2 * 12)
    pex = OlapExecutor(ds, impl="xla", partitions=2)
    pex.execute_batch(sigs)  # a shared scan in each partition: 8 + 8 groups
    st = pex.stats()
    assert (st["dense_groups"], st["kernel_groups"]) == (2 * 40, 8 + 8)
