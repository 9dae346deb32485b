"""Device time of the programs that prefill (whole-prompt insert, suffix
insert, fused prefill-decode chunk; the fused chunk's decode rows ride along
and are counted in) per thousand prompt tokens they advanced.  Only executions
that `trace.steps` admits, so that none the trace cut short counts its tokens
in full."""

from benchmark import trace

PROGRAMS = ("_paged_insert", "_paged_suffix_insert", "_fused_chunk")


def read(ctx):
    if ctx.trace is None:
        return None
    mods = [m for m in trace.steps(ctx.trace, PROGRAMS) if m["dispatch"]["prefill_tokens"] > 0]
    tokens = sum(m["dispatch"]["prefill_tokens"] for m in mods)
    return 1e6 * sum(m["seconds"] for m in mods) / tokens if tokens else None
