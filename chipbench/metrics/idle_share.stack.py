"""Device idle share while the host stacks batches, in %: device idle
time inside the union of the ``fl.stack`` spans (fl/fleet.py) over the
traced window, averaged over the device planes."""

from chipbench.harness import spans


def read(ctx):
    return spans.idle_share_in(ctx, "fl.stack")
