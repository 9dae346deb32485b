"""The scheduler's part of the gap before a serving step: `barrier + admit +
prep + emit` of `host_ms`, per decode or fused dispatch record of the window
(`serving.py`: deferred-error fetches, admission, chunk pick and row sync,
replay of the packed block)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.per_dispatch(ctx, hostspans.SCHEDULER)
