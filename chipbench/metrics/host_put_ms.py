"""Host time per round spent placing the stacked batches on the device
(``fl.put`` spans inside ``fl.stack``, fl/fleet.py), in ms/round."""

from chipbench.harness import spans


def read(ctx):
    return spans.host_ms(ctx, "fl.put")
