"""Device idle share under the round driver (fl/loop.py), in %: one minus
the union of the device's operation intervals over the traced window of
whole rounds (harness/trace.py)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * ctx.trace.idle_share()
