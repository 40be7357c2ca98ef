"""Kernels: the scans' share of their HBM roofline, in %.

Least time of a submit = (fact rows that any of its misses selects) x
(distinct fact columns that its misses aggregate, plus one group-id column
when they group) x 4 bytes / peak HBM bytes per second.  The row counts come
from the benchmark's reference; the work is counted from the intents sent,
so no change to a kernel changes it.  The share is the least time of the
window's submits over the scan programs' device time."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_scan_device", os.path.join(os.path.dirname(__file__),
                                             "scan_device_ms_per_miss.py"))
_scan = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_scan)


def read(ctx):
    t = _scan.scan_seconds(ctx)
    if t is None or ctx.peaks is None:
        return None
    least = sum(s["selected_rows"] * s["columns"] * 4 for s in ctx.submits
                if s["misses"] and s["selected_rows"] is not None)
    if not least:
        return None
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / t
