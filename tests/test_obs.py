"""Observability plane: tracing (sampling, propagation, completeness),
the metrics registry + exposition, the cache-lifecycle audit log, and the
``python -m repro.obs`` CLI.  The cross-thread tests pin the tentpole's
propagation contract: follower requests coalesced onto a single-flight
leader link back to the leader's trace, partition scans and write-behind
spills land under the originating request, and every stage a result's
provenance proves it passed through has a matching span — clean and under
injected chaos."""
import json
import threading
import time

import pytest

from repro.obs import (BUCKET_BOUNDS, AuditLog, LogHistogram, MetricsRegistry,
                       ObsConfig, ObsPlane, PIPELINE_STAGES, Tracer, adopt,
                       child_span, current_ctx, span_ctx, trace_completeness)
from repro.obs.__main__ import main as obs_main
from repro.olap.executor import OlapExecutor
from repro.service import CacheService, QueryRequest
from repro.service import pipeline as _pipeline

JOINS = ("JOIN customer ON lineorder.lo_custkey = customer.c_key "
         "JOIN dates ON lineorder.lo_orderdate = dates.d_key ")


def sql_region(measures="SUM(lo_revenue) AS r", where=""):
    w = f"WHERE {where} " if where else ""
    return (f"SELECT c_region, {measures} "
            f"FROM lineorder {JOINS}{w}GROUP BY c_region")


def mk_service(wl, obs=None, *, backend=None, **tenant_kw):
    svc = CacheService(obs=obs)
    svc.register_tenant(
        "t", schema=wl.schema,
        backend=backend or OlapExecutor(wl.dataset, impl="numpy"),
        **tenant_kw)
    return svc


# ------------------------------------------------------------ log histogram


class TestLogHistogram:
    def test_quantile_proper_rank_no_p95_bias(self):
        """Regression: the old deque-percentile computed index
        ``int(0.95 * n)`` which over-reads the tail for small n.  The
        histogram interpolates rank ``q * (n - 1)`` within log buckets:
        for 100 identical-bucket samples p50 and p95 agree, and for a
        two-point distribution p95 must stay in the lower bucket until q
        actually crosses the rank."""
        h = LogHistogram()
        for _ in range(99):
            h.observe(1.0)
        h.observe(1000.0)
        # rank 0.95 * 99 = 94.05 < 99: still firmly in the 1ms bucket
        assert h.quantile(0.95) < 3.0
        # only the maximum rank reaches the outlier's bucket
        assert h.quantile(1.0) > 500.0

    def test_observe_quantile_mean(self):
        h = LogHistogram()
        assert h.quantile(0.5) == 0.0 and h.mean == 0.0
        for v in (1.0, 2.0, 4.0, 8.0):
            h.observe(v)
        assert h.count == 4
        assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0)
        assert 2.0 < h.mean < 5.0

    def test_bucket_bounds_monotone(self):
        assert all(a < b for a, b in zip(BUCKET_BOUNDS, BUCKET_BOUNDS[1:]))

    def test_to_dict(self):
        h = LogHistogram()
        h.observe(3.0)
        d = h.to_dict()
        assert d["count"] == 1 and d["sum"] == pytest.approx(3.0)
        assert d["p50"] <= d["p95"] <= d["p99"]


# ---------------------------------------------------------------- registry


class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", "requests", labelnames=("tenant",))
        c.inc(tenant="a")
        c.inc(2, tenant="b")
        assert c.value(tenant="a") == 1 and c.value(tenant="b") == 2
        g = reg.gauge("depth", "queue depth")
        g.set(7)
        g.inc(-2)
        assert g.value() == 5
        h = reg.histogram("lat_ms", "latency", labelnames=("stage",))
        h.observe(1.5, stage="lookup")
        assert h.value(stage="lookup").count == 1

    def test_get_or_create_is_idempotent_and_typed(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", "x")
        assert reg.counter("x_total", "x") is a
        with pytest.raises(ValueError):
            reg.gauge("x_total", "x")

    def test_render_prometheus(self):
        reg = MetricsRegistry(namespace="repro")
        reg.counter("hits_total", "cache hits",
                    labelnames=("tenant",)).inc(3, tenant="t")
        reg.histogram("lat_ms", "latency").observe(2.0)
        text = reg.render_prometheus()
        assert '# TYPE repro_hits_total counter' in text
        assert 'repro_hits_total{tenant="t"} 3' in text
        assert '# TYPE repro_lat_ms histogram' in text
        assert 'repro_lat_ms_count 1' in text
        assert 'le="+Inf"' in text

    def test_render_json(self):
        reg = MetricsRegistry()
        reg.gauge("depth", "d").set(4)
        data = reg.render_json()
        json.dumps(data)  # must be wire-serializable as-is
        by_name = {m["name"]: m for m in data["metrics"]}
        assert by_name["repro_depth"]["type"] == "gauge"
        assert by_name["repro_depth"]["samples"][0]["value"] == 4


# ------------------------------------------------------------------ tracer


class TestTracer:
    def test_disabled_returns_none(self):
        tr = Tracer(enabled=False)
        assert tr.start_trace() is None

    def test_sample_all(self):
        tr = Tracer(enabled=True, sample_rate=1.0)
        assert all(tr.start_trace() is not None for _ in range(10))
        assert tr.stats()["sampled"] == 10 and tr.stats()["seen"] == 10

    def test_sample_rate_pacing(self):
        tr = Tracer(enabled=True, sample_rate=0.01)
        got = [tr.start_trace() for _ in range(400)]
        assert sum(t is not None for t in got) == 4  # exactly 1 in 100
        assert tr.stats()["seen"] == 400

    def test_ring_bounded(self):
        tr = Tracer(enabled=True, sample_rate=1.0, ring_capacity=8)
        t = tr.start_trace()
        for i in range(20):
            t.record(f"s{i}")
        assert len(tr.spans()) == 8
        assert tr.stats()["spans_emitted"] == 20

    def test_jsonl_sink(self, tmp_path):
        sink = str(tmp_path / "trace.jsonl")
        tr = Tracer(enabled=True, sample_rate=1.0, sink_path=sink)
        t = tr.start_trace()
        t.record("hello", attrs={"k": 1})
        tr.close()
        recs = [json.loads(x) for x in open(sink)]
        assert recs and recs[0]["name"] == "hello"
        assert recs[0]["trace"] == t.trace_id

    def test_cross_thread_adoption(self):
        """current_ctx captured on the submitting thread + adopt in the
        worker body parents the worker's span under the submitter's."""
        tr = Tracer(enabled=True, sample_rate=1.0)
        t = tr.start_trace()
        with span_ctx(t, "parent", parent_id=t.root_id):
            ctx = current_ctx()

            def worker():
                with adopt(ctx), child_span("child", attrs={"i": 1}):
                    pass

            th = threading.Thread(target=worker)
            th.start()
            th.join()
        spans = {s["name"]: s for s in tr.spans()}
        assert spans["child"]["parent"] == spans["parent"]["span"]
        assert spans["child"]["trace"] == t.trace_id

    def test_child_span_without_ctx_is_noop(self):
        with child_span("orphan"):
            pass  # no installed context: must not raise, records nothing


# --------------------------------------------------------------- audit log


class TestAuditLog:
    def test_emit_events_counts(self):
        au = AuditLog()
        au.emit("put", "k1", tenant="t", nbytes=10)
        au.emit("hit", "k1", tenant="t")
        au.emit("hit", "k2", tenant="t")
        assert au.counts() == {"put": 1, "hit": 2}
        assert [e["event"] for e in au.events(key="k1")] == ["put", "hit"]
        assert au.stats()["emitted"] == 3

    def test_ring_bounded_and_sink_complete(self, tmp_path):
        sink = str(tmp_path / "audit.jsonl")
        au = AuditLog(capacity=4, sink_path=sink)
        for i in range(10):
            au.emit("put", f"k{i}")
        assert len(au.events()) == 4  # ring keeps the tail
        au.close()
        assert len([x for x in open(sink) if x.strip()]) == 10  # sink: all


# --------------------------------------------------- config + stage parity


class TestObsConfig:
    def test_defaults_are_metrics_only(self):
        plane = ObsPlane(ObsConfig())
        assert not plane.tracer.enabled and plane.audit is None
        assert plane.tracer.start_trace() is None

    def test_disabled_and_full(self):
        assert ObsPlane(ObsConfig.disabled()).audit is None
        full = ObsPlane(ObsConfig.full(sample_rate=1.0))
        assert full.tracer.enabled and full.audit is not None

    def test_pipeline_stages_pinned(self):
        """The obs mirror of the stage tuple must track the pipeline's
        (obs stays import-light, so the tuple is duplicated on purpose)."""
        assert PIPELINE_STAGES == _pipeline.STAGES


# -------------------------------------------------------- service tracing


class TestServiceTracing:
    def test_warm_hit_traced_end_to_end(self, ssb_small):
        svc = mk_service(ssb_small, ObsConfig.full(sample_rate=1.0))
        miss = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        hit = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        assert miss.status == "miss" and hit.status == "hit_exact"
        assert miss.trace_id and hit.trace_id
        assert miss.trace_id != hit.trace_id
        names = {s["name"] for s in svc.obs.tracer.spans(miss.trace_id)}
        # the miss passed through every stage its provenance records (plain
        # SQL never enters the NL gate); execute.backend is the live backend
        # span nested under the root
        assert {"canonicalize", "validate", "lookup", "execute",
                "store", "request", "execute.backend"} <= names
        comp = trace_completeness([miss, hit], svc.obs.tracer)
        assert comp["ok"] and comp["traces_checked"] == 2

    def test_unsampled_requests_have_no_trace(self, ssb_small):
        svc = mk_service(ssb_small, ObsConfig(tracing=True,
                                              sample_rate=0.0001))
        res = [svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
               for _ in range(5)]
        assert all(r.trace_id is None for r in res)
        # unsampled results serialize without trace keys at all
        assert "trace_id" not in res[0].to_dict()

    def test_result_serializes_trace_ids(self, ssb_small):
        svc = mk_service(ssb_small, ObsConfig.full(sample_rate=1.0))
        r = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        d = r.to_dict()
        assert d["trace_id"] == r.trace_id and d["span_id"] == r.span_id

    def test_partition_spans_adopted(self, ssb_small):
        be = OlapExecutor(ssb_small.dataset, impl="numpy", partitions=2)
        svc = mk_service(ssb_small, ObsConfig.full(sample_rate=1.0),
                         backend=be)
        r = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        spans = svc.obs.tracer.spans(r.trace_id)
        parts = [s for s in spans if s["name"] == "execute.partition"]
        backend = [s for s in spans if s["name"] == "execute.backend"]
        assert len(parts) == 2 and len(backend) == 1
        assert all(p["parent"] == backend[0]["span"] for p in parts)

    def test_spill_span_adopted(self, ssb_small, tmp_path):
        svc = mk_service(ssb_small, ObsConfig.full(sample_rate=1.0),
                         shards=2)
        svc.open(str(tmp_path / "store"))
        r = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        # the spill is write-behind: wait for the worker's span rather than
        # closing immediately (close()'s final sync spill would supersede the
        # pending job, and the superseding job carries no request context)
        spills = []
        deadline = time.time() + 5.0
        while not spills and time.time() < deadline:
            spills = [s for s in svc.obs.tracer.spans(r.trace_id)
                      if s["name"] == "store.spill"]
            if not spills:
                time.sleep(0.01)
        svc.close()
        assert spills and spills[0]["attrs"]["ok"] is True
        assert spills[0]["attrs"]["key"] == r.signature.key()

    def test_single_flight_storm_links_follower_spans(self, ssb_small):
        """8 threads storm one cold signature at sample rate 1.0: every
        follower's plan span carries the leader's trace/span id, and the
        leader's trace records one flight.adopt link per follower."""
        svc = mk_service(ssb_small, ObsConfig.full(sample_rate=1.0),
                         shards=4)
        n = 8
        results = [None] * n
        barrier = threading.Barrier(n)

        def worker(i):
            barrier.wait()
            results[i] = svc.submit(QueryRequest(sql=sql_region(),
                                                 tenant="t"))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r is not None and r.ok for r in results)
        followers = [r for r in results if r.deduped]
        if not followers:
            pytest.skip("storm produced no coalesced followers this run")
        tracer = svc.obs.tracer
        leader_traces = set()
        for f in followers:
            plan = [s for s in tracer.spans(f.trace_id)
                    if s["name"] == "plan"]
            assert plan, "follower has no plan span"
            attrs = plan[0]["attrs"]
            assert "adopted_from_trace" in attrs
            assert attrs["adopted_from_trace"] != f.trace_id
            leader_traces.add(attrs["adopted_from_trace"])
        # the adoption links point at real leader traces that recorded one
        # flight.adopt span per follower
        for lt in leader_traces:
            adopts = [s for s in tracer.spans(lt)
                      if s["name"] == "flight.adopt"]
            linked = {s["attrs"]["follower_trace"] for s in adopts}
            assert {f.trace_id for f in followers
                    if f.trace_id} <= linked | {None}
        comp = trace_completeness(results, tracer)
        assert comp["ok"], comp["missing"]


# ------------------------------------------------------- service metrics


class TestServiceMetrics:
    def test_prometheus_exposition(self, ssb_small):
        svc = mk_service(ssb_small, ObsConfig.full(sample_rate=1.0))
        svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        text = svc.metrics()
        assert 'repro_service_requests_total{tenant="t"} 2' in text
        assert 'repro_cache_hits_exact_total{tenant="t"} 1' in text
        assert 'repro_stage_latency_ms_count{stage="lookup",tenant="t"}' \
            in text
        assert "repro_traces_sampled_total 2" in text
        assert "repro_audit_events_total" in text

    def test_json_exposition_and_bad_fmt(self, ssb_small):
        svc = mk_service(ssb_small)
        svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        data = svc.metrics(fmt="json")
        json.dumps(data)  # must be wire-serializable as-is
        names = {m["name"] for m in data["metrics"]}
        assert "repro_service_requests_total" in names
        assert "repro_stage_latency_ms" in names
        with pytest.raises(ValueError):
            svc.metrics(fmt="xml")

    def test_breaker_and_shard_gauges(self, ssb_small):
        svc = mk_service(ssb_small, shards=2)
        svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        text = svc.metrics()
        assert 'repro_breaker_state{dependency="backend",tenant="t"} 0' \
            in text
        assert 'repro_shard_entries{shard="0",tenant="t"}' in text

    def test_stage_percentiles_from_histograms(self, ssb_small):
        svc = mk_service(ssb_small)
        for i in range(4):
            svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        t = svc.tenant("t")
        pct = t.stats.stage_percentiles()
        assert "lookup" in pct
        assert pct["lookup"]["p50_ms"] <= pct["lookup"]["p95_ms"]
        assert pct["lookup"]["n"] == 4
        d = t.stats.to_dict()
        assert "stages_ms" in d and "lookup" in d["stages_ms"]


# ------------------------------------------------------- audit integration


class TestAuditIntegration:
    def test_hit_and_put_audited_with_labels(self, ssb_small):
        svc = mk_service(ssb_small, ObsConfig.full(sample_rate=1.0))
        svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        events = svc.obs.audit.events()
        kinds = [e["event"] for e in events]
        assert kinds.count("put") == 1 and kinds.count("hit") == 1
        hit = next(e for e in events if e["event"] == "hit")
        assert hit["tenant"] == "t" and hit["tier"] == "hot"
        assert hit["request_origin"] == "sql" and hit["hits"] >= 1

    def test_sharded_eviction_audited_with_policy_inputs(self, ssb_small):
        from repro.core import SemanticCache

        cache = SemanticCache(ssb_small.schema,
                              level_mapper=ssb_small.dataset.level_mapper(),
                              capacity=2)
        svc = CacheService(obs=ObsConfig.full(sample_rate=1.0))
        svc.register_tenant(
            "t", schema=ssb_small.schema,
            backend=OlapExecutor(ssb_small.dataset, impl="numpy"),
            cache=cache)
        for i in range(4):
            svc.submit(QueryRequest(
                sql=sql_region(where=f"d_year = {1992 + i}"), tenant="t"))
        evts = [e for e in svc.obs.audit.events()
                if e["event"] in ("evict", "demote")]
        assert evts, "capacity pressure must audit evictions"
        e = evts[0]
        # policy inputs ride along so `explain` can narrate the decision
        for k in ("score", "decayed_hits", "cost_ms", "nbytes", "policy",
                  "reason"):
            assert k in e, f"missing policy input {k}"


# -------------------------------------------------------------------- CLI


@pytest.fixture()
def obs_sinks(ssb_small, tmp_path):
    tsink = str(tmp_path / "trace.jsonl")
    asink = str(tmp_path / "audit.jsonl")
    svc = mk_service(ssb_small, ObsConfig.full(
        sample_rate=1.0, trace_sink=tsink, audit_sink=asink))
    r0 = svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
    svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
    svc.obs.close()
    return tsink, asink, r0


class TestObsCli:
    def test_summarize(self, obs_sinks, capsys):
        tsink, _, r0 = obs_sinks
        assert obs_main(["summarize", tsink]) == 0
        out = capsys.readouterr().out
        assert f"trace {r0.trace_id}" in out
        assert "execute.backend" in out

    def test_summarize_missing_trace(self, obs_sinks, capsys):
        tsink, _, _ = obs_sinks
        assert obs_main(["summarize", tsink, "--trace", "nope"]) == 1

    def test_explain(self, obs_sinks, capsys):
        _, asink, r0 = obs_sinks
        key = r0.signature.key()
        assert obs_main(["explain", asink, "--key", key]) == 0
        out = capsys.readouterr().out
        assert "put" in out and "hit" in out
        assert "never left the cache" in out

    def test_explain_unknown_key(self, obs_sinks):
        _, asink, _ = obs_sinks
        assert obs_main(["explain", asink, "--key", "zzz"]) == 1

    def test_false_hits_clean(self, obs_sinks, capsys):
        _, asink, _ = obs_sinks
        assert obs_main(["false-hits", asink]) == 0
        out = capsys.readouterr().out
        assert "0 false" in out

    def test_false_hits_detects_liveness_violation(self, tmp_path, capsys):
        bad = tmp_path / "bad.jsonl"
        evts = [
            {"ts": 1.0, "event": "put", "key": "k1"},
            {"ts": 2.0, "event": "drop", "key": "k1",
             "reason": "explicit_invalidation"},
            {"ts": 3.0, "event": "hit", "key": "k1"},
        ]
        bad.write_text("\n".join(json.dumps(e) for e in evts))
        assert obs_main(["false-hits", str(bad)]) == 2
        assert "FALSE HIT" in capsys.readouterr().out

    def test_demoted_entry_still_live_for_false_hit_audit(self, tmp_path):
        ok = tmp_path / "demoted.jsonl"
        evts = [
            {"ts": 1.0, "event": "put", "key": "k1"},
            {"ts": 2.0, "event": "demote", "key": "k1", "tier": "hot"},
            {"ts": 3.0, "event": "hit", "key": "k1", "tier": "cold"},
        ]
        ok.write_text("\n".join(json.dumps(e) for e in evts))
        assert obs_main(["false-hits", str(ok)]) == 0


# ------------------------------------------------ spans on the profiler clock


class TestProfilerSpans:
    def test_annotations_only_while_a_profile_is_captured(self, monkeypatch,
                                                         tmp_path):
        import jax

        from repro.obs import trace as T

        T.profiling()  # binds TraceAnnotation
        made = []
        real = T._annotation

        def counting(name, **ids):
            made.append((name, ids))
            return real(name, **ids)

        monkeypatch.setattr(T, "_annotation", counting)
        with T.span("olap.plan", submit=1):
            pass
        with span_ctx(None, "execute.backend"):
            pass
        with child_span("execute.partition"):
            pass
        assert not T.profiling() and made == []
        with jax.profiler.trace(str(tmp_path / "a")):
            with T.span("service.execute", submit=5):
                with T.span("olap.plan"):
                    pass
            with span_ctx(None, "execute.backend"):
                pass
        assert [n for n, _ in made] == ["repro.service.execute",
                                        "repro.olap.plan",
                                        "repro.execute.backend"]
        # a nested span on the same thread carries the submit id
        assert made[1][1] == {"submit": 5}
        assert set(T.profile_span_seconds()) == {
            "service.execute", "olap.plan", "execute.backend"}
        # the next capture's seconds do not mix with this one's
        with T.span("olap.wait"):
            pass
        with jax.profiler.trace(str(tmp_path / "b")):
            with T.span("olap.wait"):
                time.sleep(0.01)
        secs = T.profile_span_seconds()
        assert set(secs) == {"olap.wait"} and secs["olap.wait"] >= 0.01

    def test_compile_counter_counts_fresh_jit_not_cached_call(self, ssb_small):
        import jax
        import jax.numpy as jnp

        from repro.obs.compiles import COMPILES

        svc = mk_service(ssb_small)  # installs the listener
        COMPILES.install()  # a second install registers nothing more
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        x = jnp.arange(13.0)
        n0, s0 = COMPILES.snapshot()
        f(x).block_until_ready()
        n1, s1 = COMPILES.snapshot()
        f(x).block_until_ready()
        n2, _ = COMPILES.snapshot()
        assert n1 == n0 + 1 and s1 > s0
        assert n2 == n1
        text = svc.metrics()
        assert f"repro_xla_compiles_total {n2}\n" in text
        assert "repro_xla_compile_seconds_total" in text

    def test_sampled_stage_spans_have_real_starts(self, ssb_small):
        """Stage spans start where their stage did: inside the root span,
        never overlapping, batch-level stages in batch order (every
        request's canonicalize ends before any request's validate starts),
        and a shared scan's execute starting at one instant for all of its
        requests."""
        svc = mk_service(ssb_small, ObsConfig.full(sample_rate=1.0))
        svc.submit(QueryRequest(sql=sql_region(), tenant="t"))
        res = svc.submit_batch(
            [QueryRequest(sql=sql_region(where=f"d_year = {1992 + i}"),
                          tenant="t") for i in range(3)]
            + [QueryRequest(sql=sql_region(), tenant="t")])
        assert [r.status for r in res] == ["miss"] * 3 + ["hit_exact"]
        eps = 1e-6  # wall-clock seconds as doubles
        by_stage: dict = {}
        for r in res:
            spans = svc.obs.tracer.spans(r.trace_id)
            root = next(s for s in spans if s["name"] == "request")
            r0, r1 = root["start_s"], root["start_s"] + root["dur_ms"] / 1e3
            stages = sorted(
                ((s["start_s"], s["start_s"] + s["dur_ms"] / 1e3, s["name"])
                 for s in spans if s["name"] in PIPELINE_STAGES))
            assert {n for _, _, n in stages} >= {"canonicalize", "lookup"}
            for a, b, _ in stages:
                assert r0 - eps <= a <= b <= r1 + eps
            for (_, b, _), (a, _, _) in zip(stages, stages[1:]):
                assert b <= a + eps
            for a, b, n in stages:
                by_stage.setdefault(n, []).append((a, b))
        assert max(b for _, b in by_stage["canonicalize"]) <= \
            min(a for a, _ in by_stage["validate"]) + eps
        assert len(by_stage["execute"]) == 3
        starts = [a for a, _ in by_stage["execute"]]
        assert max(starts) - min(starts) <= 2 * eps
