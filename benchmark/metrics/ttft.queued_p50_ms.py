"""Median over the window's requests of the `queued` spans of their timeline:
the batcher's queue, from `submit` to the start of prefill (the median of what
`admission.queue_wait_p95_ms` reads the tail of)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.span_p50_ms(ctx, "queued")
