"""Host time per round spent on the fleet engines' host data (``fl.stack``
spans, fl/fleet.py: next batches, flips, stacking and the device put), in
ms/round, read from the host line of the trace."""

from chipbench.harness import spans


def read(ctx):
    return spans.host_ms(ctx, "fl.stack")
