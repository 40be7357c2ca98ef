"""Cache lookup and derivation: mean per request of the pipeline's
``lookup`` stage timing (``QueryResult.timings_ms``), in ms."""


def read(ctx):
    done = [r for r in ctx.records if r["timings"] is not None]
    if not done:
        return None
    return sum(r["timings"].get("lookup", 0.0) for r in done) / len(done)
