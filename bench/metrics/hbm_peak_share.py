"""Device memory: ``peak_bytes_in_use`` after the window over the device's
``bytes_limit``, in %."""


def read(ctx):
    m = ctx.memory
    if not m or not m.get("bytes_limit") or not m.get("peak_bytes_in_use"):
        return None
    return 100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]
