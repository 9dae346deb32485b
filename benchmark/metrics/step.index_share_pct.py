"""Share of the device's busy time inside the indexer's and the selection's
scopes, `attn.index` + `attn.select` (every layer of Keye-VL-2.0 as cut), by
self time of the traced operations (`benchmark/scopes.py`)."""

import importlib

share = importlib.import_module("benchmark.metrics.sparse_attn_roofline").share


def read(ctx):
    return share(ctx, ("attn.index", "attn.select"))
