"""Partition-parallel scan plane (ISSUE 6).

Tentpole: row-range partitioned fused scans with merge-combine
(``scan_plane`` planning/decomposition + ``refresh.merge_partials``) and
streaming chunked execution for beyond-device-memory datasets, exposed as
``OlapExecutor(partitions=N, max_device_rows=...)``.  The governing property
everywhere: the merged partial tables must equal the unpartitioned fused
scan (``partitions=1`` is the differential oracle), and ``rows_scanned``
must account each fact row exactly once per scan — no double count at chunk
boundaries.

Satellites covered here: the generalized k-way merge combiner's edge cases
(empty partials, all-NaN MIN/MAX, single-partition groups, fold-order
invariance as a Hypothesis property), memo-dict LRU bounds, non-composable
fallback routing, and service-pipeline integration.
"""
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import Measure, SemanticCache, Signature
from repro.core.refresh import merge_partials, merge_tables
from repro.core.sql_canon import SQLCanonicalizer
from repro.core.table import ResultTable
from repro.olap import scan_plane
from repro.olap.executor import OlapExecutor
from repro.service.api import QueryRequest
from repro.service.service import CacheService
from repro.workloads import ssb

pytestmark = pytest.mark.filterwarnings("error::RuntimeWarning")


SIG = lambda *ms, **kw: Signature("ssb", tuple(ms), **kw)  # noqa: E731


# -------------------------------------------------------------- plan_scan


class TestPlanScan:
    def test_partitions_cover_rows_disjointly(self):
        for n, p in [(10, 1), (10, 3), (4000, 4), (7, 16), (1, 1)]:
            plan = scan_plane.plan_scan(n, p)
            ranges = [r for part in plan.chunks for r in part]
            assert ranges[0][0] == 0 and ranges[-1][1] == n
            for (_, e1), (s2, _) in zip(ranges, ranges[1:]):
                assert e1 == s2  # adjacent: no gap, no overlap
            assert sum(e - s for s, e in ranges) == n
            assert plan.num_partitions <= p

    def test_more_partitions_than_rows_drops_empties(self):
        plan = scan_plane.plan_scan(3, 8)
        assert plan.num_partitions == 3
        assert all(len(c) == 1 for c in plan.chunks)

    def test_streaming_chunks_are_pow2_sized(self):
        plan = scan_plane.plan_scan(10_000, 2, max_device_rows=1000)
        assert plan.streaming
        for part in plan.chunks:
            # every chunk but the partition's last is the same pow2 size
            sizes = [e - s for s, e in part]
            assert all(sz == 512 for sz in sizes[:-1])
            assert sizes[-1] <= 512
        assert sum(e - s for part in plan.chunks for s, e in part) == 10_000

    def test_no_streaming_when_partition_fits(self):
        plan = scan_plane.plan_scan(1000, 4, max_device_rows=250)
        assert not plan.streaming

    def test_rejects_bad_args(self):
        with pytest.raises(ValueError):
            scan_plane.plan_scan(10, 0)
        with pytest.raises(ValueError):
            scan_plane.plan_scan(10, 2, max_device_rows=0)


# ------------------------------------------------------------- decompose


class TestDecompose:
    def test_avg_becomes_sum_count(self):
        sig = SIG(Measure("AVG", "lineorder.lo_revenue"), levels=("customer.c_region",))
        plan = scan_plane.decompose(sig)
        aggs = [(m.agg, m.expr) for m in plan.partial_sig.measures]
        assert aggs == [("SUM", "lineorder.lo_revenue"), ("COUNT", "*")]
        assert plan.finalize == (("avg", 0, 1),)

    def test_dedup_shares_partial_columns(self):
        sig = SIG(Measure("SUM", "lineorder.lo_revenue"),
                  Measure("AVG", "lineorder.lo_revenue"),
                  Measure("COUNT", "*"))
        plan = scan_plane.decompose(sig)
        # SUM and COUNT(*) partials are shared with the AVG decomposition
        assert len(plan.partial_sig.measures) == 2
        assert plan.finalize == (("direct", 0), ("avg", 0, 1), ("direct", 1))

    def test_post_aggregation_stripped_from_partials(self):
        from repro.core.signature import HavingClause, OrderKey

        sig = SIG(Measure("SUM", "lineorder.lo_revenue"),
                  levels=("customer.c_region",),
                  having=(HavingClause(0, ">", 0),),
                  order_by=(OrderKey("measure:0", desc=True),), limit=3)
        p = scan_plane.decompose(sig)
        assert not p.partial_sig.having and not p.partial_sig.order_by
        assert p.partial_sig.limit is None

    def test_count_distinct_not_partitionable(self):
        sig = SIG(Measure("COUNT", "lineorder.lo_custkey", distinct=True))
        assert not scan_plane.partition_compatible(sig)
        with pytest.raises(ValueError):
            scan_plane.decompose(sig)


# ---------------------------------------------------- k-way merge combiner


def _grouped_sig(*aggs):
    return SIG(*[Measure(a, "lineorder.lo_revenue") if a != "COUNT"
                 else Measure("COUNT", "*") for a in aggs],
               levels=("customer.c_region",))


def _tbl(keys, **measures):
    cols = {} if keys is None else {"customer.c_region": np.asarray(keys)}
    for name, vals in measures.items():
        cols[name] = np.asarray(vals, np.float64)
    return ResultTable(cols)


class TestMergePartials:
    def test_two_way_matches_merge_tables(self):
        sig = _grouped_sig("SUM", "COUNT")
        a = _tbl(["E", "W"], m0=[10.0, 20.0], m1=[1, 2])
        b = _tbl(["W", "N"], m0=[5.0, 7.0], m1=[1, 1])
        assert merge_partials(sig, [a, b]).equals(merge_tables(sig, a, b),
                                                  ordered=True)

    def test_empty_partitions_are_transparent(self):
        sig = _grouped_sig("SUM")
        empty = _tbl([], m0=[])
        a = _tbl(["E"], m0=[3.0])
        m = merge_partials(sig, [empty, a, empty, empty])
        assert m.equals(a, ordered=True)
        # all partitions empty: an empty table with the right columns
        assert merge_partials(sig, [empty, empty]).num_rows == 0

    def test_all_nan_minmax_partials_poison_group(self):
        sig = _grouped_sig("MIN", "MAX")
        a = _tbl(["E"], m0=[np.nan], m1=[np.nan])
        b = _tbl(["E"], m0=[np.nan], m1=[np.nan])
        c = _tbl(["E", "W"], m0=[1.0, 2.0], m1=[5.0, 6.0])
        m = merge_partials(sig, [a, b, c])
        assert np.isnan(m.columns["m0"][0]) and np.isnan(m.columns["m1"][0])
        assert m.columns["m0"][1] == 2.0 and m.columns["m1"][1] == 6.0

    def test_groups_in_only_one_partition_survive(self):
        sig = _grouped_sig("SUM", "MIN")
        a = _tbl(["E"], m0=[1.0], m1=[10.0])
        b = _tbl(["N"], m0=[2.0], m1=[20.0])
        c = _tbl(["W"], m0=[3.0], m1=[30.0])
        m = merge_partials(sig, [a, b, c])
        assert m.columns["customer.c_region"].tolist() == ["E", "N", "W"]
        assert m.columns["m0"].tolist() == [1.0, 2.0, 3.0]
        assert m.columns["m1"].tolist() == [10.0, 20.0, 30.0]

    def test_global_aggregate_folds_all_partials(self):
        sig = SIG(Measure("SUM", "lineorder.lo_revenue"),
                  Measure("MIN", "lineorder.lo_revenue"))
        parts = [_tbl(None, m0=[float(i)], m1=[float(10 - i)])
                 for i in range(5)]
        m = merge_partials(sig, parts)
        assert float(m.columns["m0"][0]) == 10.0  # 0+1+2+3+4
        assert float(m.columns["m1"][0]) == 6.0

    def test_rejects_non_mergeable_and_empty_input(self):
        sig = SIG(Measure("AVG", "lineorder.lo_revenue"))
        with pytest.raises(ValueError):
            merge_partials(sig, [_tbl(None, m0=[1.0])])
        with pytest.raises(ValueError):
            merge_partials(_grouped_sig("SUM"), [])

    @settings(max_examples=30, deadline=None)
    @given(
        seed=st.integers(0, 10_000),
        n_parts=st.integers(2, 6),
        perm_seed=st.integers(0, 10_000),
    )
    def test_fold_order_never_changes_merge(self, seed, n_parts, perm_seed):
        """Permuting the partial tables must give the identical merged table
        (integer-valued measures + NaN, so equality is exact: SUM regrouping
        of integers inside f64 has no rounding)."""
        rng = np.random.default_rng(seed)
        sig = _grouped_sig("SUM", "COUNT", "MIN", "MAX")
        keys = np.asarray(["A", "B", "C", "D", "E"])
        parts = []
        for _ in range(n_parts):
            k = rng.integers(0, 5, size=rng.integers(0, 5))
            vals = rng.integers(-50, 50, size=len(k)).astype(np.float64)
            vals[rng.random(len(k)) < 0.2] = np.nan  # NaN partials included
            parts.append(_tbl(keys[k],
                              m0=np.where(np.isnan(vals), 0.0, vals),
                              m1=np.ones(len(k)), m2=vals, m3=vals))
        merged = merge_partials(sig, parts)
        perm = np.random.default_rng(perm_seed).permutation(n_parts)
        remerged = merge_partials(sig, [parts[i] for i in perm])
        assert merged.columns.keys() == remerged.columns.keys()
        for name in merged.columns:
            a, b = merged.columns[name], remerged.columns[name]
            if a.dtype.kind == "f":
                np.testing.assert_array_equal(a, b)  # exact, NaN == NaN
            else:
                assert a.tolist() == b.tolist()


# --------------------------------------------- partitioned executor oracle


class TestPartitionedExecutor:
    def test_all_intents_match_unpartitioned_oracle(self, ssb_small,
                                                    tlc_small, tpcds_small):
        """Merged partial tables == the unpartitioned fused scan for every
        canonical intent of every workload (the tentpole's zero-drift
        guarantee)."""
        for wl in (ssb_small, tlc_small, tpcds_small):
            canon = SQLCanonicalizer(wl.schema)
            ex1 = OlapExecutor(wl.dataset, impl="xla")
            ex4 = OlapExecutor(wl.dataset, impl="xla", partitions=4)
            for intent in wl.intents:
                sig = canon.canonicalize(intent.sql)
                a = ex1.execute(sig)
                b = ex4.execute(sig)
                assert a.equals(b, ordered=bool(sig.order_by)), intent.id

    def test_streaming_matches_oracle_and_counts_chunks(self, ssb_small):
        canon = SQLCanonicalizer(ssb_small.schema)
        ex1 = OlapExecutor(ssb_small.dataset, impl="xla")
        exs = OlapExecutor(ssb_small.dataset, impl="xla", partitions=2,
                           max_device_rows=700)  # 2000-row partitions stream
        for intent in ssb_small.intents[:6]:
            sig = canon.canonicalize(intent.sql)
            assert ex1.execute(sig).equals(exs.execute(sig),
                                           ordered=bool(sig.order_by)), intent.id
        st = exs.stats()
        assert st["streaming_chunks"] > 0
        assert all(p["chunks"] > 0 for p in st["per_partition"])

    def test_stager_failure_fails_the_scan(self, ssb_small, monkeypatch):
        """A failed pre-stage of the next chunk (device OOM, lost device)
        is raised by the scan, not swallowed."""
        sig = SQLCanonicalizer(ssb_small.schema).canonicalize(
            ssb_small.intents[0].sql)
        exs = OlapExecutor(ssb_small.dataset, impl="xla", partitions=2,
                           max_device_rows=700)

        def boom(self, sub, psigs):
            raise MemoryError("staging failed")

        monkeypatch.setattr(OlapExecutor, "_stage_arrays", boom)
        with pytest.raises(MemoryError, match="staging failed"):
            exs.execute(sig)

    @pytest.mark.parametrize("impl,built", [("xla", True), ("interpret", False)])
    def test_rect_index_only_for_xla(self, ssb_small, impl, built):
        """The (G, R) rect index is read only by the XLA path: the Pallas
        impls never pay its host argsort and upload."""
        sig = SQLCanonicalizer(ssb_small.schema).canonicalize(
            ssb_small.intents[0].sql)
        ex = OlapExecutor(ssb_small.dataset, impl=impl)
        ex.execute(sig)
        assert (ex.memo_sizes()["rect_index"] > 0) == built

    def test_rows_scanned_matches_unpartitioned(self, ssb_small):
        """Partition-edge accounting: the partitioned scan must count each
        fact row exactly once per scan — summed across partitions and chunks
        it equals the unpartitioned count (no boundary double-count)."""
        canon = SQLCanonicalizer(ssb_small.schema)
        sigs = [canon.canonicalize(i.sql) for i in ssb_small.intents[:5]]
        ex1 = OlapExecutor(ssb_small.dataset, impl="xla")
        ex4 = OlapExecutor(ssb_small.dataset, impl="xla", partitions=4)
        exs = OlapExecutor(ssb_small.dataset, impl="xla", partitions=3,
                           max_device_rows=500)
        for sig in sigs:
            ex1.execute(sig)
            ex4.execute(sig)
            exs.execute(sig)
        assert ex4.rows_scanned == ex1.rows_scanned
        assert exs.rows_scanned == ex1.rows_scanned
        per_part = ex4.stats()["per_partition"]
        assert sum(p["rows_scanned"] for p in per_part) == ex1.rows_scanned
        sizes = [p["end"] - p["start"] for p in per_part]
        for p, sz in zip(per_part, sizes):
            assert p["rows_scanned"] == sz * len(sigs)

    def test_batch_matches_unpartitioned_batch(self, ssb_small):
        canon = SQLCanonicalizer(ssb_small.schema)
        sigs = [canon.canonicalize(i.sql) for i in ssb_small.intents]
        ex1 = OlapExecutor(ssb_small.dataset, impl="xla")
        ex4 = OlapExecutor(ssb_small.dataset, impl="xla", partitions=4)
        for a, b, s in zip(ex1.execute_batch(sigs), ex4.execute_batch(sigs),
                           sigs):
            assert a.equals(b, ordered=bool(s.order_by))
        assert ex4.rows_scanned == ex1.rows_scanned

    def test_count_distinct_falls_back_to_single_partition(self, ssb_small):
        sig = SIG(Measure("COUNT", "lineorder.lo_custkey", distinct=True),
                  levels=("customer.c_region",))
        ex1 = OlapExecutor(ssb_small.dataset, impl="xla")
        ex4 = OlapExecutor(ssb_small.dataset, impl="xla", partitions=4)
        assert ex1.execute(sig).equals(ex4.execute(sig))
        st = ex4.stats()
        assert st["partition_fallbacks"] == 1
        assert st["partitioned_scans"] == 0

    def test_numpy_impl_partitions_through_host_oracle(self, ssb_small):
        canon = SQLCanonicalizer(ssb_small.schema)
        ex1 = OlapExecutor(ssb_small.dataset, impl="numpy")
        ex3 = OlapExecutor(ssb_small.dataset, impl="numpy", partitions=3)
        for intent in ssb_small.intents[:6]:
            sig = canon.canonicalize(intent.sql)
            assert ex1.execute(sig).equals(ex3.execute(sig),
                                           ordered=bool(sig.order_by)), intent.id

    def test_append_resyncs_partition_layout(self):
        """A delta append bumps the dataset version: the scan plan, resident
        subs, and per-partition stats must rebuild over the grown table."""
        from benchmarks.bench_refresh import make_delta

        wl = ssb.build(n_fact=3000, seed=0)
        canon = SQLCanonicalizer(wl.schema)
        sig = canon.canonicalize(
            "SELECT c_region, SUM(lo_revenue) AS r FROM lineorder "
            "JOIN customer ON lineorder.lo_custkey = customer.c_key "
            "GROUP BY c_region")
        ex1 = OlapExecutor(wl.dataset, impl="xla")
        ex4 = OlapExecutor(wl.dataset, impl="xla", partitions=4)
        assert ex1.execute(sig).equals(ex4.execute(sig))
        wl.dataset.append_rows(make_delta(wl.dataset, 500,
                                          np.random.default_rng(7)))
        a, b = ex1.execute(sig), ex4.execute(sig)
        assert a.equals(b)
        parts = ex4.stats()["per_partition"]
        assert parts[-1]["end"] == wl.dataset.fact.num_rows


# --------------------------------------------------------- memo LRU bounds


class TestMemoBounds:
    def test_memos_never_exceed_cap(self, ssb_small):
        canon = SQLCanonicalizer(ssb_small.schema)
        ex = OlapExecutor(ssb_small.dataset, impl="xla", memo_cap=2)
        for intent in ssb_small.intents:
            ex.execute(canon.canonicalize(intent.sql))
        sizes = ex.memo_sizes()
        for name in ("level_plans", "gids", "rect_index", "measure_plans"):
            assert sizes[name] <= 2, (name, sizes)

    def test_eviction_releases_device_arrays_and_stays_correct(self):
        # fresh workload: the session fixture's device mirror is shared by
        # other tests' executors, so its store counts aren't attributable
        wl = ssb.build(n_fact=2000, seed=5)
        canon = SQLCanonicalizer(wl.schema)
        sigs = [canon.canonicalize(i.sql) for i in wl.intents]
        oracle = OlapExecutor(wl.dataset, impl="numpy")
        ex = OlapExecutor(wl.dataset, impl="xla", memo_cap=1)
        # two passes: the second re-executes signatures whose plans were
        # evicted, exercising rebuild-after-eviction
        for _ in range(2):
            for s in sigs:
                assert oracle.execute(s).equals(ex.execute(s),
                                                ordered=bool(s.order_by))
        store = ex.ds._device._store
        # the ('gids', ()) global-aggregate entry is built inline (never in
        # the LRU) and is bounded at one; every level-combination entry must
        # have been evicted down to the cap
        n_gids = sum(1 for k in store if k[0] == "gids" and k[1] != ())
        n_rect = sum(1 for k in store if k[0] == "rectidx")
        n_sum = sum(1 for k in store if k[0] == "sumblock")
        assert n_gids <= 1 and n_rect <= 1 and n_sum <= 1, set(store)

    def test_stats_exposes_memo_sizes(self, ssb_small):
        ex = OlapExecutor(ssb_small.dataset, impl="xla")
        assert "memo_sizes" in ex.stats()
        assert set(ex.memo_sizes()) >= {"level_plans", "gids", "rect_index",
                                        "measure_plans"}


# --------------------------------------------------------- service plumbing


class TestServiceIntegration:
    def _mk(self, wl, partitions, shards=None):
        be = OlapExecutor(wl.dataset, impl="xla", partitions=partitions)
        svc = CacheService()
        svc.register_tenant(
            "t", schema=wl.schema, backend=be,
            cache=SemanticCache(wl.schema,
                                level_mapper=wl.dataset.level_mapper()),
            shards=shards)
        return svc, be

    def test_miss_group_executes_partitioned(self, ssb_small):
        svc1, _ = self._mk(ssb_small, 1)
        svc4, be4 = self._mk(ssb_small, 4)
        reqs = [QueryRequest(sql=i.sql, tenant="t")
                for i in ssb_small.intents[:6]]
        r1 = svc1.submit_batch(reqs)
        r4 = svc4.submit_batch(reqs)
        for a, b in zip(r1, r4):
            assert a.status == b.status == "miss"
            assert a.table.equals(b.table, ordered=False)
            assert "execute:partitioned" in b.provenance
            assert "execute:partitioned" not in a.provenance
        # one shared partitioned scan served the whole miss group
        assert be4.partitioned_scans == 1
        st = svc4.stats("t")
        assert st["backend"]["partitions"] == 4
        assert len(st["backend"]["per_partition"]) == 4

    def test_cluster_leaders_share_one_partitioned_scan(self, ssb_small):
        """With a partition-parallel backend the cluster pipeline must NOT
        nest its shard pool on top of the partition pool: all miss leaders
        go through one cross-family execute_batch."""
        svc, be = self._mk(ssb_small, 4, shards=4)
        reqs = [QueryRequest(sql=i.sql, tenant="t")
                for i in ssb_small.intents[:6]]
        results = svc.submit_batch(reqs)
        assert all(r.status == "miss" for r in results)
        assert be.partitioned_scans == 1  # not one per shard group
        assert be.batch_calls == 1
        # warm pass: everything hits, no further scans
        again = svc.submit_batch(reqs)
        assert all(r.status.startswith("hit") for r in again)
        assert be.partitioned_scans == 1

    def test_advance_snapshot_keeps_delta_scan_single_partition(self):
        """The refresh delta scan stays partition-bounded (cost proportional
        to the delta): ``execute_batch(partition=...)`` must not route
        through the scan plane even on a partitioned backend."""
        from benchmarks.bench_refresh import make_delta

        wl = ssb.build(n_fact=3000, seed=0)
        svc, be = self._mk(wl, 4)
        sql = ("SELECT c_region, SUM(lo_revenue) AS r, COUNT(*) AS n "
               "FROM lineorder "
               "JOIN customer ON lineorder.lo_custkey = customer.c_key "
               "GROUP BY c_region")
        first = svc.submit(QueryRequest(sql=sql, tenant="t"))
        assert first.status == "miss"
        scans_before = be.partitioned_scans
        delta = make_delta(wl.dataset, 400, np.random.default_rng(11))
        svc.advance_snapshot("t", delta=delta, snapshot_id="snap1")
        assert be.partitioned_scans == scans_before  # delta scan, not plane
        refreshed = svc.submit(QueryRequest(sql=sql, tenant="t"))
        assert refreshed.status.startswith("hit")
        oracle = OlapExecutor(wl.dataset, impl="numpy")
        canon = SQLCanonicalizer(wl.schema)
        assert refreshed.table.equals(oracle.execute(canon.canonicalize(sql)))
