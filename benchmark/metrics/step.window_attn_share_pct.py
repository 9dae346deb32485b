"""Share of the device's busy time inside the window attention layers' scope,
`attn.window` (all of them together: four of the five layers of Trinity-Mini
as cut), by self time of the traced operations (`benchmark/scopes.py`)."""

import importlib

share = importlib.import_module("benchmark.metrics.window_attn_roofline").share


def read(ctx):
    return share(ctx, "attn.window")
