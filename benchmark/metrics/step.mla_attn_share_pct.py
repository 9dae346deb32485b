"""Share of the device's busy time inside the latent-attention scopes
(`mla.project`, `mla.attend_prefill`, `mla.attend_decode`), by self time of
the traced operations (`benchmark/scopes.py`)."""

from benchmark import scopes


def read(ctx):
    return scopes.share_pct(ctx, "mla.")
