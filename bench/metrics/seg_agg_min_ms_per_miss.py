"""Kernels: device time of the MIN/MAX kernels (MAX columns are negated
into the min block), the ops named ``seg_agg_min`` or ``seg_agg_fused_min``,
over the misses of the traced window, in ms; read as
``seg_agg_sum_ms_per_miss`` reads the SUM kernels."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_seg_agg_sum", os.path.join(os.path.dirname(__file__),
                                             "seg_agg_sum_ms_per_miss.py"))
_sum = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_sum)


def read(ctx):
    return _sum.kernel_ms_per_miss(ctx, ("seg_agg_min", "seg_agg_fused_min"))
