"""Median over the window's requests of the `received` span of their
timeline: POST accepted to the batcher's `submit`, i.e. the wait in the
server's inbox (drained only between steps) and in the overload controller's
class queue.  The span starts where the server's own first-token clock does;
`received + queued + prefilling` of a request is its first-token time up to
one replay of a block and one delivery."""

from benchmark import hostspans


def read(ctx):
    return hostspans.span_p50_ms(ctx, "received")
