"""Device idle share during the server step's host path, in %: device
idle time inside the union of the ``fl.aggregate`` spans (fl/loop.py
``run_federated``: row gathers, the server step, EF store, unflatten) over
the traced window, averaged over the device planes."""

from chipbench.harness import spans


def read(ctx):
    return spans.idle_share_in(ctx, "fl.aggregate")
