"""Device time per round of the batched engine's on-device batch gather
(fl/fleet.py ``_fleet_gather``, one executable per chunk shape), in
ms/round.  The trace names the executable after the jitted function; a run
in which no chunk gathers on the device reads nothing."""

MODULES = r"_fleet_gather\("


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    s = ctx.trace.module_s(MODULES)
    return 1e3 * s / ctx.out["rounds"] if s > 0 else None
