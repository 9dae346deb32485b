"""Median over the window's requests of the `prefilling` spans of their
timeline: from the admission that reserves the prompt's blocks, through the
dispatches that carry the prompt, to the record of the one that sampled the
first token."""

from benchmark import hostspans


def read(ctx):
    return hostspans.span_p50_ms(ctx, "prefilling")
