"""Share of the device's busy time inside the output head, the scope `head`
(the final norm's logits over the whole vocabulary), by self time of the traced
operations (`benchmark/scopes.py`).  The cut to 6 of 72 layers leaves the head
a third of a decode iteration's weight bytes where the whole model's is a
twenty-fifth: this is the share to discount.  A program without the scope, or
a configuration of another block, reads nothing."""

import importlib

share = importlib.import_module("benchmark.metrics.ssd_scan_roofline").share


def read(ctx):
    return share(ctx, "head")
