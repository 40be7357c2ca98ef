"""Executor, waiting on the device: seconds the executor's
``repro.olap.wait`` spans (the copies of the device results to the host,
which wait for the scans queued ahead of them) ran inside the traced window,
summed over threads, over the misses, in ms; read as
``olap_host_ms_per_miss`` reads its spans."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_olap_host", os.path.join(os.path.dirname(__file__),
                                           "olap_host_ms_per_miss.py"))
_host = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_host)


def read(ctx):
    return _host.span_ms_per_miss(ctx, ("olap.wait",))
