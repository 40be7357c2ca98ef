"""Kernels: device time of the SUM/COUNT kernels, the ops the profiler
trace names ``seg_agg_sum`` or ``seg_agg_fused_sum`` (the Pallas calls'
names, with XLA's numeric suffix), over the misses of the traced window, in
ms.  A program whose kernels carry no such name gives nothing."""

import re

OPS = ("seg_agg_sum", "seg_agg_fused_sum")


def kernel_ms_per_miss(ctx, ops):
    if ctx.trace is None:
        return None
    pat = re.compile(r"^(%s)(\.\d+)?$" % "|".join(map(re.escape, ops)))
    t = sum(s for name, s in ctx.trace["op_s"].items()
            if pat.match(name.rsplit(":", 1)[-1]))
    misses = sum(s["misses"] for s in ctx.submits)
    if not t or not misses:
        return None
    return 1e3 * t / misses


def read(ctx):
    return kernel_ms_per_miss(ctx, OPS)
