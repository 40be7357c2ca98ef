"""Kernels: device time of the scan programs in the profiler trace (XLA
modules named after the jit entry points of ``kernels/seg_agg``), over the
misses of the traced window, in ms."""

from lib.trace import program_seconds

# the jit programs of the seg_agg scans on the chip, as a v5e trace's
# "XLA Modules" line names them: the filter-fused kernel, the plain and
# host-masked kernel, and the shared-scan batch
PROGRAMS = ("jit_seg_agg_fused_pallas", "jit_seg_agg_pallas", "jit__batch_jit")


def scan_seconds(ctx):
    if ctx.trace is None:
        return None
    return program_seconds(ctx.trace, PROGRAMS) or None


def read(ctx):
    misses = sum(s["misses"] for s in ctx.submits)
    t = scan_seconds(ctx)
    if not misses or t is None:
        return None
    return 1e3 * t / misses
