"""The least time the scan kernel's calls could take over the device time
they took (`ops/ssm.py`: the Pallas chunked scan of a prompt chunk, one call a
Mamba mixer a chunk).

Least time, over the `_fused_chunk` executions that `trace.steps` admits, by
BYTES (`benchmark/roofline_sambay.py`): a chunk's inputs and output a token in
the activation type, `B` and `C`, the state once in and once out, times the
nine mixers; the kernel is handed float32 and so moves more, which is not
counted.  Time taken: the device time of EVERY event of the kernel on the first
device's `XLA Ops` (its custom call, by name), cut executions included, so the
share errs low twice over.  The decode iteration's recurrence is not in it: it
is one token a row in XLA, fused with its neighbours, and its time cannot be
told from theirs by scope (a first form of this reader divided by the self
time under `ssm.scan` and read 115 %: my chip run, PR 35).  A program without
the kernel, or a configuration of another block, reads nothing.
"""


from benchmark import hostspans, roofline, roofline_sambay as rf, scopes, trace

PROGRAM = "_fused_chunk"
KERNEL = ("ssm_scan", "_scan_pallas")   # the custom call's name: the kernel's, or its jit's
# the block's disjoint scopes: `ssm.scan` lies inside `ssm.mix` and counts there
MIXERS = ("ssm.mix", "gmu.", "attn.", "dense.")


def scope_seconds(ctx, prefixes):
    """{scope: self seconds} of the run's newest trace, or None."""
    if ctx.trace is None:
        return None
    from benchmark import run

    path = hostspans.newest_xplane(str(run.OUT))
    return scopes.self_seconds_by_scope(path, prefixes) if path else None


def share(ctx, scope):
    """100 x self seconds under `scope` / busy seconds; the note lists each
    scope.  What `step.ssm_share_pct` and `step.cross_attn_share_pct` return."""
    by_scope = scope_seconds(ctx, MIXERS)
    total = sum((by_scope or {}).values())
    if total <= 0 or by_scope.get(scope, 0.0) <= 0:
        return None
    return {"value": 100.0 * by_scope[scope] / total,
            "note": {"busy_self_s": total,
                     "seconds_by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))}}


def kernel_seconds(ctx):
    """Device seconds of the scan kernel's events in the run's newest trace."""
    if ctx.trace is None:
        return 0.0
    from benchmark import run

    path = hostspans.newest_xplane(str(run.OUT))
    if not path:
        return 0.0
    planes = trace.read_planes(path)
    for name in sorted(n for n in planes if trace.is_device(n)):
        ops = planes[name].get(trace.OPS_LINE)
        if ops:
            return sum(e - s for n, s, e in ops if any(k in n.split(" = ")[0] for k in KERNEL))
    return 0.0


def read(ctx):
    if ctx.peaks is None or ctx.config.get("reference") != "sambay":
        return None
    took = kernel_seconds(ctx)
    if took <= 0:
        return None
    mixers = rf.sizes(ctx.config)["n_mamba"]
    bytes_ = 0.0
    n = 0
    for m in trace.steps(ctx.trace, (PROGRAM,)):
        tokens = int(m["dispatch"].get("prefill_tokens") or 0)
        if tokens:
            bytes_ += mixers * rf.scan_chunk_bytes(ctx.config, tokens)
            n += 1
    least, _ = roofline.least_seconds(0.0, bytes_, ctx.peaks, ctx.chips)
    if least <= 0:
        return None
    return {"value": 100.0 * least / took,
            "note": {"executions": n, "least_s": least, "took_s": took, "bytes": bytes_}}
