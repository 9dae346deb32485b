"""Share of the device's busy time inside the Mamba mixers' scope, `ssm.mix`
(all nine together: projections, conv, the scan under `ssm.scan`, gate), by
self time of the traced operations (`benchmark/scopes.py`).  A program
without the scope reads nothing."""

import importlib

share = importlib.import_module("benchmark.metrics.ssm_scan_roofline").share


def read(ctx):
    return share(ctx, "ssm.mix")
