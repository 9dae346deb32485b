"""Share of the device's busy time inside the mHC units' scopes, `hc.coeff` +
`hc.pre` + `hc.post` (two units a layer of Xing4.0 as cut: the n-stream
residual's norm, coefficients, Sinkhorn and the two mixes), by self time of the
traced operations (`benchmark/scopes.py`).  `scopes.share_pct` reads a fixed
list of prefixes that has no `hc.`, so this reader brings its own."""

import importlib

share = importlib.import_module("benchmark.metrics.hc_mix_roofline").share


def read(ctx):
    return share(ctx, "hc.")
