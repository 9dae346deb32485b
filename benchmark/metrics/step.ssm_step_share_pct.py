"""Share of the device's busy time inside the decode iteration's state step,
the scope `ssm.step` (`ops/ssm.py`: `ssd_step`, every layer's state read,
advanced by one token a row and written), by self time of the traced
operations (`benchmark/scopes.py`).  A program without the scope, or a
configuration of another block, reads nothing."""

import importlib

share = importlib.import_module("benchmark.metrics.ssd_scan_roofline").share


def read(ctx):
    return share(ctx, "ssm.step")
