"""Device: 1 - union of device-op intervals over the traced window."""

UNIT = "%"


def read(ctx):
    trace = ctx["trace"]
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
