"""Cache: the share of completed requests answered from the cache (any
``hit_*`` status: exact, roll-up, filter-down), in %."""


def read(ctx):
    done = [r for r in ctx.records if r["status"] is not None]
    if not done:
        return None
    return 100.0 * sum(r["status"].startswith("hit") for r in done) / len(done)
