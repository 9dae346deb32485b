"""The least time the indexer and the selection could take over the device
time inside their scopes, `attn.index` + `attn.select` (`models/dsa_moe.py`,
`ops/key_selection.py`: the index projections, LayerNorm, rope and scores; the
top-k of a decode row, the k-th-value search and the mask of a prompt chunk).

Least time: a prompt chunk's index projections and its scores on itself over
peak FLOP/s, plus each decode iteration's index projections once and every
riding row's index keys for its WHOLE context over the memory bandwidth
(`benchmark/roofline_dsa_moe.py`).  The selection itself is counted as free: a
top-k has no least bytes beyond the scores it ranks, which never leave the
chip.  So this share says how far the ranking is from costing only what it
must read.  A program without the scopes reads nothing."""

import importlib

from benchmark import roofline_dsa_moe as rf

_sparse = importlib.import_module("benchmark.metrics.sparse_attn_roofline")


def read(ctx):
    return _sparse.roofline_of(
        ctx, ("attn.index", "attn.select"), rf.index_chunk_flops, rf.index_decode_iter_bytes)
