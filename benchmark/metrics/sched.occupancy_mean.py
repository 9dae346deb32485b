"""Mean number of rows riding a decode or fused dispatch inside the window."""


def read(ctx):
    occ = [d["occupancy"] for d in ctx.dispatches
           if (d["kind"].startswith("decode") or d["kind"] == "fused")
           and ctx.t0 <= d["start"] <= ctx.t0 + ctx.seconds]
    return sum(occ) / len(occ) if occ else None
