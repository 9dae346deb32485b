"""How late the generator sent: 95th percentile of (sent - due)."""


def read(ctx):
    late = [(r["sent"] - r["due"]) * 1000.0 for r in ctx.records if r["sent"] is not None]
    return ctx.stats.percentile(late, 95)
