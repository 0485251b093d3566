"""Host time per round spent waiting for the round's device work: the
eval dispatch and its ``float`` (``fl.sync`` spans, fl/loop.py
``run_federated``), in ms/round."""

from chipbench.harness import spans


def read(ctx):
    return spans.host_ms(ctx, "fl.sync")
