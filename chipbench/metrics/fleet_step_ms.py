"""Device time per round of the fleet engine's training step (fl/fleet.py
``make_fleet_step``, one executable per OP and chunk size), in ms/round.
The trace names the executable after the jitted function."""

MODULES = r"fleet_step"


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    s = ctx.trace.module_s(MODULES)
    return 1e3 * s / ctx.out["rounds"] if s > 0 else None
