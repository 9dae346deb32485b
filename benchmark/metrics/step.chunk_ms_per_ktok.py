"""Device time a fused dispatch spends on its prompt chunk, per thousand
prompt tokens: self time under `lane.chunk` and `lane.mixed` inside the
`_fused_chunk` executions `trace.steps` admits, over the `prefill_tokens` of
their records (`benchmark/lanes.py`).  The decode iterations under
`lane.decode` are OUT of the numerator, which is the whole difference from
`step.prefill_ms_per_ktok`; under a mixed pass the riders' one token a row
rides in the numerator (`merged`: how many dispatches that was).  The note
splits by K and by chunk length (`le256`, `le1024`, `gt1024` tokens), each
`[executions, tokens, ms/ktok, ms an execution]`, and says how many
executions of the program the trace held and how many were admitted.  A
chunk costs by the SHAPE it was compiled for and is counted by the tokens it
advanced, so inside a class ms/ktok follows how full the sample's chunks
were and ms an execution does not; `full` is the chunks of exactly the
server's `prefill_budget` tokens, the program's own cost.  A program without
lanes (the parent of PR 53) reads nothing."""

from benchmark import lanes


def read(ctx):
    got = lanes.fused_executions(ctx)
    if got is None:
        return None
    rows = [(rec, 1e3 * (by.get("chunk", 0.0) + by.get("mixed", 0.0)))
            for rec, by in got["admitted"] if rec["prefill_tokens"] > 0]
    tokens = sum(rec["prefill_tokens"] for rec, _ in rows)
    if not tokens:
        return None

    def split(key):
        out = {}
        for rec, ms in rows:
            n = out.setdefault(str(key(rec)), [0, 0, 0.0])
            n[0] += 1
            n[1] += rec["prefill_tokens"]
            n[2] += ms
        return {k: [n, t, 1e3 * ms / t, ms / n] for k, (n, t, ms) in sorted(out.items())}

    budget = (getattr(ctx, "server", None) or {}).get("prefill_budget")

    return {"value": 1e3 * sum(ms for _, ms in rows) / tokens,
            "note": {"held": got["held"], "admitted": len(got["admitted"]),
                     "merged": sum("merged_rows" in rec for rec, _ in rows),
                     "by_k": split(lambda rec: rec["k"]),
                     "by_chunk": split(lambda rec: lanes.chunk_class(rec["prefill_tokens"])),
                     "full": split(lambda rec: rec["prefill_tokens"] == budget).get("True")}}
