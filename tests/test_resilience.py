"""Resilience plane: the deterministic chaos harness, the primitives
(deadlines, backoff, circuit breakers), and the pipeline's containment +
graceful-degradation contract — structured degraded/error results, stale
serving under explicit provenance, breaker fail-fast and recovery, and the
service health surface.  Every injected failure here is replayable from its
spec string alone."""
import time

import pytest

from repro.core import SemanticCache
from repro.olap.executor import OlapExecutor
from repro.resilience import (CircuitBreaker, Deadline, ResiliencePolicy,
                              backoff_delays, faults)
from repro.resilience.errors import classify
from repro.resilience.faults import FaultError, FaultPlan, FaultSpec
from repro.resilience.primitives import run_with_retry
from repro.service import CacheService, QueryRequest

JOINS = ("JOIN customer ON lineorder.lo_custkey = customer.c_key "
         "JOIN dates ON lineorder.lo_orderdate = dates.d_key ")


def sql_region(measures="SUM(lo_revenue) AS r", where=""):
    w = f"WHERE {where} " if where else ""
    return (f"SELECT c_region, {measures} "
            f"FROM lineorder {JOINS}{w}GROUP BY c_region")


def mk_service(wl, *, policy=None, ttl_s=None, backend=None):
    svc = CacheService()
    svc.register_tenant(
        "t", schema=wl.schema,
        backend=backend or OlapExecutor(wl.dataset, impl="numpy"),
        cache=SemanticCache(wl.schema, level_mapper=wl.dataset.level_mapper(),
                            ttl_s=ttl_s),
        resilience=policy)
    return svc


# ------------------------------------------------------------ chaos harness


class TestFaults:
    def test_parse_specs(self):
        specs = faults.parse("backend.error:0.1, storage.*:10%:7")
        assert specs == (FaultSpec("backend.error", 0.1, 0),
                         FaultSpec("storage.*", 0.1, 7))
        with pytest.raises(ValueError):
            faults.parse("backend.error")
        with pytest.raises(ValueError):
            faults.parse("backend.error:1.5")

    def test_prefix_match(self):
        spec = FaultSpec("storage.*", 1.0)
        assert spec.matches("storage.wal_enospc")
        assert not spec.matches("backend.error")

    def test_draws_are_deterministic_and_rate_accurate(self):
        a = FaultPlan(faults.parse("p:0.1:42"))
        b = FaultPlan(faults.parse("p:0.1:42"))
        seq_a = [a.should_fire("p") for _ in range(2000)]
        seq_b = [b.should_fire("p") for _ in range(2000)]
        assert seq_a == seq_b  # counter-based: bit-for-bit replayable
        fired = sum(seq_a)
        assert 140 <= fired <= 260  # ~10% of 2000
        c = FaultPlan(faults.parse("p:0.1:43"))
        assert [c.should_fire("p") for _ in range(2000)] != seq_a

    def test_rate_edges(self):
        always = FaultPlan(faults.parse("p:1.0"))
        never = FaultPlan(faults.parse("p:0.0"))
        assert all(always.should_fire("p") for _ in range(50))
        assert not any(never.should_fire("p") for _ in range(50))

    def test_scoped_install_and_counts(self):
        with faults.scoped("x.y:1.0") as plan:
            assert faults.active_plan() is plan
            with pytest.raises(FaultError) as ei:
                faults.fire("x.y")
            assert ei.value.point == "x.y"
            assert faults.counts()["fired"]["x.y"] == 1
        assert not faults.should_fire("x.y")  # cleared on exit

    def test_env_var_activation(self, monkeypatch):
        monkeypatch.setenv("REPRO_FAULTS", "env.point:1.0")
        assert faults.should_fire("env.point")
        monkeypatch.setenv("REPRO_FAULTS", "")
        assert not faults.should_fire("env.point")

    def test_classify(self):
        assert classify(FaultError("canonicalize.timeout")) == "timeout"
        assert classify(FaultError("backend.error")) == "fault"
        assert classify(TimeoutError()) == "timeout"
        assert classify(OSError()) == "io"
        assert classify(RuntimeError()) == "error"


# -------------------------------------------------------------- primitives


class TestPrimitives:
    def test_backoff_deterministic_bounded(self):
        d1 = backoff_delays(4, 0.01, 0.25, salt="k")
        d2 = backoff_delays(4, 0.01, 0.25, salt="k")
        assert d1 == d2 and len(d1) == 3
        for i, d in enumerate(d1):
            base = min(0.25, 0.01 * 2 ** i)
            assert 0.5 * base <= d < 1.5 * base
        assert backoff_delays(4, 0.01, 0.25, salt="other") != d1
        assert backoff_delays(1, 0.01, 0.25) == []

    def test_deadline(self):
        d = Deadline.after_ms(60_000)
        assert not d.expired and d.remaining_s() > 59
        assert Deadline.after_ms(-1).expired

    def test_run_with_retry(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("transient")
            return "ok"

        result, retries, err = run_with_retry(
            flaky, attempts=4, base_s=0.0, max_s=0.0, sleep=lambda _t: None)
        assert result == "ok" and retries == 2 and err is None
        result, retries, err = run_with_retry(
            lambda: 1 / 0, attempts=2, base_s=0.0, max_s=0.0,
            sleep=lambda _t: None)
        assert result is None and isinstance(err, ZeroDivisionError)

    def test_breaker_state_machine(self):
        clock = [0.0]
        br = CircuitBreaker("dep", failure_threshold=3, recovery_s=1.0,
                            half_open_probes=1, clock=lambda: clock[0])
        assert br.state == "closed" and br.allow()
        for _ in range(3):
            br.record_failure()
        assert br.state == "open"
        assert not br.allow()  # rejected while the window is fresh
        clock[0] = 1.5  # recovery elapsed: one probe admitted
        assert br.allow()
        assert br.state == "half_open"
        assert not br.allow()  # probe budget spent
        br.record_failure()  # failed probe re-opens with a fresh window
        assert br.state == "open" and not br.allow()
        clock[0] = 3.0
        assert br.allow()
        br.record_success()
        assert br.state == "closed" and br.allow()
        snap = br.snapshot()
        assert snap["opens"] == 2 and snap["closes"] == 1
        assert snap["rejections"] >= 2


# ------------------------------------------------- pipeline containment


class TestPipelineContainment:
    def test_backend_error_is_structured_not_raised(self, ssb_small):
        svc = mk_service(ssb_small,
                         policy=ResiliencePolicy(execute_attempts=1))
        with faults.scoped("backend.error:1.0"):
            res = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        assert res.status == "error" and not res.ok
        assert res.table is None
        assert res.error is not None
        assert res.error.stage == "execute" and res.error.kind == "fault"
        assert "failure:execute:fault" in res.provenance
        assert res.to_dict()["error"]["stage"] == "execute"
        t = svc.tenant("t")
        assert t.stats.failures == 1

    def test_retry_recovers_transient_fault(self, ssb_small):
        # ~half the execute attempts fail; three tries per request make the
        # workload succeed, with retries surfaced in provenance + stats
        svc = mk_service(ssb_small, policy=ResiliencePolicy(
            execute_attempts=3, retry_base_s=0.001, retry_max_s=0.002))
        # seed 9: every request clears within its 3-attempt budget, and at
        # least one needs a retry (the draw sequence is deterministic)
        with faults.scoped("backend.error:0.5:9"):
            results = [svc.submit(QueryRequest(
                sql=sql_region(where=f"d_year = {1992 + i}"), tenant="t"))
                for i in range(6)]
        assert all(r.status == "miss" for r in results)
        t = svc.tenant("t")
        assert t.stats.backend_executions == 6
        assert t.stats.retries >= 1
        assert any(p.startswith("retry:")
                   for r in results for p in r.provenance)

    def test_degraded_serves_stale_with_explicit_tag(self, ssb_small):
        svc = mk_service(ssb_small, ttl_s=0.05)
        fresh = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        assert fresh.status == "miss"
        time.sleep(0.08)  # TTL out the entry
        with faults.scoped("backend.error:1.0"):
            res = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        assert res.status == "degraded" and res.ok
        assert res.table is not None and res.table.equals(fresh.table)
        assert "degraded:stale" in res.provenance
        assert res.error is not None and res.error.degraded
        t = svc.tenant("t")
        assert t.stats.degraded == 1 and t.stats.failures == 0

    def test_stale_serving_disabled_yields_error(self, ssb_small):
        svc = mk_service(ssb_small, ttl_s=0.05,
                         policy=ResiliencePolicy(execute_attempts=1,
                                                 serve_stale=False))
        assert svc.submit(QueryRequest(sql=sql_region(),
                                       tenant="t")).status == "miss"
        time.sleep(0.08)
        with faults.scoped("backend.error:1.0"):
            res = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        assert res.status == "error" and res.table is None

    def test_deadline_shed(self, ssb_small):
        svc = mk_service(ssb_small)
        res = svc.submit(QueryRequest(sql=sql_region(), tenant="t",
                                      deadline_ms=-1.0))
        assert res.status == "error"
        assert res.error.kind == "deadline"
        assert svc.tenant("t").stats.shed == 1
        # a generous deadline changes nothing
        ok = svc.submit(QueryRequest(sql=sql_region(), tenant="t",
                                     deadline_ms=60_000.0))
        assert ok.status == "miss" and ok.table is not None

    def test_resilience_disabled_still_contains(self, ssb_small):
        svc = mk_service(ssb_small, policy=ResiliencePolicy.disabled())
        with faults.scoped("backend.error:1.0"):
            res = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        assert res.status == "error" and res.error is not None
        assert res.error.retries == 0  # no recovery machinery ran

    def test_backend_breaker_opens_and_recovers(self, ssb_small):
        svc = mk_service(ssb_small, policy=ResiliencePolicy(
            execute_attempts=1, breaker_failures=2, breaker_recovery_s=0.05))
        t = svc.tenant("t")
        with faults.scoped("backend.error:1.0"):
            for i in range(3):
                res = svc.submit(QueryRequest(
                    sql=sql_region(f"SUM(lo_revenue) AS r{i}"), tenant="t"))
                assert res.status == "error"
        # third request failed fast on the open breaker
        assert res.error.kind == "breaker_open"
        assert "breaker:open" in res.provenance
        assert t.resilience.backend.state == "open"
        time.sleep(0.08)  # recovery window elapses; faults cleared: probe ok
        res = svc.submit(QueryRequest(sql=sql_region("COUNT(*) AS n"),
                                      tenant="t"))
        assert res.status == "miss" and res.table is not None
        assert t.resilience.backend.state == "closed"
        assert t.resilience.backend.snapshot()["closes"] == 1

    def test_isolated_retries_counted(self, ssb_small):
        class SharedScanFails(OlapExecutor):
            def execute_batch(self, sigs, partition=None):
                raise RuntimeError("shared scan failed")

        svc = mk_service(ssb_small,
                         policy=ResiliencePolicy(execute_attempts=1),
                         backend=SharedScanFails(ssb_small.dataset,
                                                 impl="numpy"))
        res = svc.submit_batch([
            QueryRequest(sql=sql_region(where=f"d_year = {1992 + i}"),
                         tenant="t") for i in range(3)])
        assert all(r.status == "miss" for r in res)
        assert all("execute:isolated_retry" in r.provenance for r in res)
        assert svc.tenant("t").stats.isolated_retries == 3
        assert 'repro_service_isolated_retries_total{tenant="t"} 3' \
            in svc.metrics()

    def test_partial_partition_failure_fails_whole_batch_result(self, ssb_small):
        be = OlapExecutor(ssb_small.dataset, impl="numpy", partitions=2)
        svc = mk_service(ssb_small, backend=be,
                         policy=ResiliencePolicy(execute_attempts=1))
        with faults.scoped("backend.partial:1.0"):
            res = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        # one partition died: no merged-over-missing-partials wrong answer
        assert res.status == "error" and res.table is None

    def test_store_failure_keeps_result(self, ssb_small, monkeypatch):
        svc = mk_service(ssb_small)
        t = svc.tenant("t")

        def boom(*a, **kw):
            raise OSError("disk gone")

        monkeypatch.setattr(t.cache, "put", boom)
        res = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        assert res.status == "miss" and res.table is not None
        assert "store:error" in res.provenance
        assert t.stats.store_errors == 1


class TestCanonicalizeFaults:
    def _nl_service(self, ssb_small, **kw):
        from repro.core import MemoizedNL, SimulatedLLM

        svc = CacheService()
        svc.register_tenant(
            "t", schema=ssb_small.schema,
            backend=OlapExecutor(ssb_small.dataset, impl="numpy"),
            nl=MemoizedNL(SimulatedLLM(ssb_small.schema)), **kw)
        return svc

    def test_timeout_fault_is_structured(self, ssb_small):
        svc = self._nl_service(ssb_small)
        with faults.scoped("canonicalize.timeout:1.0"):
            res = svc.submit(QueryRequest(
                nl="total revenue by region", tenant="t"))
        assert res.status == "error"
        assert res.error.stage == "canonicalize"
        assert res.error.kind == "timeout"

    def test_garbage_fault_bypasses_never_caches(self, ssb_small):
        svc = self._nl_service(ssb_small)
        with faults.scoped("canonicalize.garbage:1.0"):
            res = svc.submit(QueryRequest(
                nl="total revenue by region", tenant="t"))
        # garbage output loses the signature: safe bypass, nothing cached
        assert res.status == "bypass"
        assert len(svc.tenant("t").cache) == 0

    def test_lowconf_fault_gates_request(self, ssb_small):
        svc = self._nl_service(ssb_small)
        with faults.scoped("canonicalize.lowconf:1.0"):
            res = svc.submit(QueryRequest(
                nl="total revenue by region", tenant="t"))
        # 0.01 confidence is under every acceptance threshold: gated to a
        # bypass that still executes but never touches the cache
        assert res.status == "bypass"
        assert res.confidence == 0.01
        assert len(svc.tenant("t").cache) == 0

    def test_canonicalizer_breaker_opens(self, ssb_small):
        svc = self._nl_service(
            ssb_small, resilience=ResiliencePolicy(breaker_failures=2,
                                                   breaker_recovery_s=60.0))
        with faults.scoped("canonicalize.timeout:1.0"):
            for _ in range(2):
                svc.submit(QueryRequest(nl="revenue by region", tenant="t"))
        res = svc.submit(QueryRequest(nl="revenue by region", tenant="t"))
        assert res.status == "error"
        assert res.error.kind == "breaker_open"
        assert svc.tenant("t").resilience.canonicalizer.state == "open"


# ------------------------------------------------------------ health surface


class TestHealth:
    def test_health_ok_then_degraded(self, ssb_small):
        svc = mk_service(ssb_small, policy=ResiliencePolicy(
            execute_attempts=1, breaker_failures=1, serve_stale=False))
        h = svc.health("t")
        assert h["status"] == "ok" and h["open_breakers"] == []
        assert set(h["breakers"]) == {"canonicalizer", "backend"}
        with faults.scoped("backend.error:1.0"):
            svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        h = svc.health("t")
        assert h["status"] == "degraded"
        assert "backend" in h["open_breakers"]
        assert h["counters"]["failures"] == 1
        # the all-tenants form nests per tenant
        assert svc.health()["t"]["status"] == "degraded"

    def test_health_includes_storage_counters(self, ssb_small, tmp_path):
        svc = mk_service(ssb_small)
        svc.open(str(tmp_path))
        try:
            svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
            h = svc.health("t")
            assert "cold_tier" in h["breakers"]
            assert "spill_errors" in h["storage"]
            assert h["storage"]["spill_last_error"] is None
        finally:
            svc.close()


# ------------------------------------------------- no-exception-escape sweep


class TestNoEscape:
    @pytest.mark.parametrize("spec", [
        "backend.error:1.0",
        "canonicalize.timeout:1.0",
        "canonicalize.garbage:1.0",
        "backend.error:0.25:11,canonicalize.timeout:0.25:12",
    ])
    def test_mixed_workload_never_raises(self, ssb_small, spec):
        from repro.core import MemoizedNL, SimulatedLLM

        svc = CacheService()
        svc.register_tenant(
            "t", schema=ssb_small.schema,
            backend=OlapExecutor(ssb_small.dataset, impl="numpy"),
            nl=MemoizedNL(SimulatedLLM(ssb_small.schema)),
            resilience=ResiliencePolicy(execute_attempts=2,
                                        retry_base_s=0.001,
                                        retry_max_s=0.002))
        reqs = []
        for i in range(4):
            reqs.append(QueryRequest(
                sql=sql_region(f"SUM(lo_revenue) AS r{i}"), tenant="t"))
            reqs.append(QueryRequest(nl="total revenue by region",
                                     tenant="t"))
        with faults.scoped(spec):
            results = svc.submit_batch(reqs)
        for r in results:
            assert r.status in ("miss", "hit_exact", "hit_rollup",
                                "hit_filterdown", "bypass", "degraded",
                                "error")
            if r.status == "error":
                assert r.error is not None and r.table is None


# ------------------------------------------- chaos outcomes on request traces


class TestChaosSpans:
    """Resilience outcomes must be visible on the request's trace: a retried
    execute carries its retry count, a breaker fail-fast names the breaker
    state, a degraded serve is flagged on the failing stage's span — and
    under a mixed fault plan every traced result still has a span for every
    stage its provenance proves it passed through."""

    def _obs_service(self, wl, *, policy=None, ttl_s=None):
        from repro.obs import ObsConfig

        svc = CacheService(obs=ObsConfig.full(sample_rate=1.0))
        svc.register_tenant(
            "t", schema=wl.schema,
            backend=OlapExecutor(wl.dataset, impl="numpy"),
            cache=SemanticCache(wl.schema,
                                level_mapper=wl.dataset.level_mapper(),
                                ttl_s=ttl_s),
            resilience=policy)
        return svc

    def _stage_span(self, svc, res, stage):
        spans = [s for s in svc.obs.tracer.spans(res.trace_id)
                 if s["name"] == stage]
        assert spans, f"no {stage} span on trace {res.trace_id}"
        return spans[0]

    def test_retry_count_lands_on_execute_span(self, ssb_small):
        svc = self._obs_service(ssb_small, policy=ResiliencePolicy(
            execute_attempts=3, retry_base_s=0.001, retry_max_s=0.002))
        with faults.scoped("backend.error:0.5:9"):
            results = [svc.submit(QueryRequest(
                sql=sql_region(where=f"d_year = {1992 + i}"), tenant="t"))
                for i in range(6)]
        assert all(r.status == "miss" for r in results)
        retried = [r for r in results
                   if any(p.startswith("retry:") for p in r.provenance)]
        assert retried  # seed 9: at least one request needed a retry
        for r in retried:
            n = next(int(p.split(":", 1)[1]) for p in r.provenance
                     if p.startswith("retry:"))
            # both the finalize-time stage span and the live backend span
            # carry the count
            assert self._stage_span(svc, r, "execute")["attrs"][
                "retries"] == n
            assert self._stage_span(svc, r, "execute.backend")["attrs"][
                "retries"] == n

    def test_breaker_fail_fast_named_on_error_span(self, ssb_small):
        svc = self._obs_service(ssb_small, policy=ResiliencePolicy(
            execute_attempts=1, breaker_failures=2, breaker_recovery_s=60.0))
        with faults.scoped("backend.error:1.0"):
            for i in range(3):
                res = svc.submit(QueryRequest(
                    sql=sql_region(f"SUM(lo_revenue) AS r{i}"), tenant="t"))
        assert res.error.kind == "breaker_open"
        span = self._stage_span(svc, res, "execute")
        assert span["attrs"]["failure_kind"] == "breaker_open"
        assert span["attrs"]["breaker"] == "open"
        assert span["attrs"]["degraded"] is False
        root = self._stage_span(svc, res, "request")
        assert "breaker:open" in root["attrs"]["events"]

    def test_degraded_serve_flagged_on_span(self, ssb_small):
        svc = self._obs_service(ssb_small, ttl_s=0.05)
        assert svc.submit(QueryRequest(sql=sql_region(),
                                       tenant="t")).status == "miss"
        time.sleep(0.08)  # TTL out the entry
        with faults.scoped("backend.error:1.0"):
            res = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        assert res.status == "degraded"
        span = self._stage_span(svc, res, "execute")
        assert span["attrs"]["degraded"] is True
        assert span["attrs"]["failure_kind"] == "fault"
        assert "degraded:stale" in self._stage_span(
            svc, res, "request")["attrs"]["events"]

    def test_chaos_traces_stay_complete(self, ssb_small):
        from repro.obs import trace_completeness

        svc = self._obs_service(ssb_small, policy=ResiliencePolicy(
            execute_attempts=2, retry_base_s=0.001, retry_max_s=0.002))
        reqs = [QueryRequest(sql=sql_region(where=f"d_year = {1992 + i % 4}"),
                             tenant="t") for i in range(12)]
        with faults.scoped("backend.error:0.25:11,"
                           "canonicalize.timeout:0.25:12"):
            results = svc.submit_batch(reqs)
        comp = trace_completeness(results, svc.obs.tracer)
        assert comp["traces_checked"] == len(results)
        assert comp["ok"], comp["missing"]
