"""Executor, host side: seconds the executor's host spans ran inside the
traced window (``repro.olap.plan``, ``repro.olap.dispatch`` and
``repro.olap.finalize``: planning, the kernel dispatch and building the
results), summed over threads, over the misses, in ms.

The seconds come from the program (``repro.obs.trace.profile_span_seconds``:
the spans of the profile capture that is the window); a program without
those spans gives nothing."""

SPANS = ("olap.plan", "olap.dispatch", "olap.finalize")


def span_ms_per_miss(ctx, spans):
    try:
        from repro.obs.trace import profile_span_seconds
    except ImportError:
        return None
    seconds = profile_span_seconds()
    misses = sum(s["misses"] for s in ctx.submits)
    if ctx.trace is None or not misses or not any(n in seconds for n in spans):
        return None
    return 1e3 * sum(seconds.get(n, 0.0) for n in spans) / misses


def read(ctx):
    return span_ms_per_miss(ctx, SPANS)
