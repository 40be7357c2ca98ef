"""Front end: mean per request of the pipeline's canonicalize, validate and
gate stage timings (``QueryResult.timings_ms``), in ms."""

STAGES = ("canonicalize", "validate", "gate")


def read(ctx):
    done = [r for r in ctx.records if r["timings"] is not None]
    if not done:
        return None
    return sum(sum(r["timings"].get(s, 0.0) for s in STAGES) for r in done) / len(done)
