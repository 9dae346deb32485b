"""Share of the device's busy time inside the cross attention layers' scope,
`attn.cross` (seven layers that project queries only and attend the one K/V
layer the full layer owns), by self time of the traced operations
(`benchmark/scopes.py`).  A program without the scope reads nothing."""

import importlib

share = importlib.import_module("benchmark.metrics.ssm_scan_roofline").share


def read(ctx):
    return share(ctx, "attn.cross")
