"""95th percentile of the `queued` span of each window request's timeline
(POST accepted to the scheduler taking it up)."""


def read(ctx):
    waits = []
    for tl in ctx.timelines.values():
        q = [sp["duration_ms"] for sp in tl["spans"]
             if sp["state"] == "queued" and sp["duration_ms"] is not None]
        if q:
            waits.append(sum(q))
    return ctx.stats.percentile(waits, 95)
