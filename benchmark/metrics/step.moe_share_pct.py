"""Share of the device's busy time inside the expert-layer scopes
(`moe.route`, `moe.experts`, `moe.shared`), by self time of the traced
operations (`benchmark/scopes.py`)."""

from benchmark import scopes


def read(ctx):
    return scopes.share_pct(ctx, "moe.")
