"""The server's part of the gap before a serving step: `control + intake +
deliver` of `host_ms`, per decode or fused dispatch record of the window
(`server.py::LLMServer._loop`: control calls, inbox and overload ladder,
handing tokens to the clients' threads).  `idle` is left out."""

from benchmark import hostspans


def read(ctx):
    return hostspans.per_dispatch(ctx, hostspans.SERVER)
