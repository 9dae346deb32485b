"""Share of the device's busy time inside attention over the chosen keys, the
scope `attn.sparse` (a decode row's gather and attention over the gathered
slots, a chunk's flash kernel under the mask; NOT the projections, which run
under `attn.proj` and stand in the note), by self time of the traced
operations (`benchmark/scopes.py`)."""

import importlib

share = importlib.import_module("benchmark.metrics.sparse_attn_roofline").share


def read(ctx):
    return share(ctx, ("attn.sparse",))
