"""The program's spans on the profiler's clock inside a traced submit, and
the readers of the executor spans and the named kernels."""
import glob
import os
import time

import benchpath  # noqa: F401
import pytest

from benchpath import small_config

READERS = ("olap_host_ms_per_miss", "olap_wait_ms_per_miss",
           "seg_agg_sum_ms_per_miss", "seg_agg_min_ms_per_miss")
STAGES = ("canonicalize", "validate", "gate", "lookup", "execute", "store",
          "finalize")


def _read(metric):
    from lib.harness import load_reader

    return load_reader(metric)


def _ctx(op_s=None, misses=4):
    from lib.harness import Context

    trace = None if op_s is None else {
        "window_s": 1.0, "busy_s": 1.0, "op_s": op_s, "module_s": {},
        "gaps": {}, "devices": 1}
    return Context(records=[], submits=[{"misses": misses}], trace=trace,
                   peaks=None, memory=None)


def _host_events(logdir):
    from jax.profiler import ProfileData

    f = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                      "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(f).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, int(e.start_ns),
                         int(e.start_ns) + int(e.duration_ns))
                        for e in line.events]
    return out


def test_submit_spans_inside_the_bench_span(tmp_path):
    from lib import harness
    from lib import trace as tr
    from lib import traffic as tf
    from lib.data import generate

    data = generate(small_config("tpcds-sf10"), 7)
    svc, _, _ = harness.build_service(data, "tpcds")
    sched = tf.schedule("tpcds_refresh", data, 7, 1.0)
    dash = sched.dashboards[0]
    rec = harness.Recorder()
    logdir = str(tmp_path / "trace")
    with tr.record(logdir):
        with tr.span("window"):
            rec.submit(svc, dash, time.perf_counter(), True)
    assert rec.submits[0]["misses"] == len(dash) > 1
    events = _host_events(logdir)
    (a0, b0), = [(a, b) for n, a, b in events if n == "bench.submit_batch"]
    inside = {n for n, a, b in events
              if n.startswith("repro.") and a0 <= a <= b <= b0}
    assert {f"repro.service.{s}" for s in STAGES} <= inside
    assert {f"repro.olap.{s}" for s in ("plan", "dispatch", "wait",
                                        "finalize")} <= inside
    # the readers of the spans find them; no kernel on the CPU is named
    ctx = harness.Context(rec.records, rec.submits,
                          tr.reduce(tr.read(logdir, "cpu")), None, None)
    assert _read("olap_host_ms_per_miss")(ctx) > 0
    assert _read("olap_wait_ms_per_miss")(ctx) > 0
    assert _read("seg_agg_sum_ms_per_miss")(ctx) is None


def test_span_readers_by_hand(monkeypatch):
    from repro.obs import trace as T

    cap = T._CaptureSpans()
    for name, s in (("olap.plan", 0.010), ("olap.dispatch", 0.002),
                    ("olap.finalize", 0.004), ("olap.wait", 1.6),
                    ("service.execute", 2.0)):
        cap.add(name, s)
    monkeypatch.setattr(T, "_CAPTURE", cap)
    ctx = _ctx({}, misses=4)
    assert _read("olap_host_ms_per_miss")(ctx) == pytest.approx(4.0)
    assert _read("olap_wait_ms_per_miss")(ctx) == pytest.approx(400.0)


def test_kernel_readers_by_hand():
    ctx = _ctx({"jit__batch_jit:seg_agg_sum.1": 0.2,
                "jit__batch_jit:seg_agg_min.1": 0.6,
                "jit_seg_agg_fused_pallas:seg_agg_fused_min": 0.2,
                "jit_seg_agg_fused_pallas:seg_agg_fused_sum.3": 0.4,
                "jit__batch_jit:concatenate.1": 1.0,
                "jit__batch_jit:seg_agg_summary": 9.0})
    assert _read("seg_agg_sum_ms_per_miss")(ctx) == pytest.approx(150.0)
    assert _read("seg_agg_min_ms_per_miss")(ctx) == pytest.approx(200.0)


@pytest.mark.parametrize("metric", READERS)
def test_reader_gives_nothing_without_its_spans_or_kernels(monkeypatch,
                                                           metric):
    from repro.obs import trace as T

    monkeypatch.setattr(T, "_CAPTURE", T._CaptureSpans())
    read = _read(metric)
    # a trace whose kernel has no name, as the parent program's
    assert read(_ctx({"jit__batch_jit:_batch_jit.1": 1.0})) is None
    assert read(_ctx(None)) is None
