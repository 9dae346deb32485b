"""The host's enqueue: mean `submit_ms` (`dispatch_begin` to the return of
the jitted call: argument flattening, the jit cache lookup, the transfer of
host operands) of the window's decode and fused records.  It is inside
`wall_ms`.  The note gives the mean by kind and by K."""

from benchmark import hostspans


def read(ctx):
    recs = [r for r in hostspans.steps(ctx) if "submit_ms" in r]
    if not recs:
        return None

    def mean(rs):
        return sum(r["submit_ms"] for r in rs) / len(rs)

    groups = {}
    for r in recs:
        groups.setdefault(f"{r['kind']} k={r['k']}", []).append(r)
    return {
        "value": mean(recs),
        "note": {"records": len(recs),
                 "ms_by_kind_and_k": {g: {"n": len(rs), "ms": mean(rs)} for g, rs in sorted(groups.items())}},
    }
