"""Slots the paged decode rows attended over the live slots their selection
chose from, over the window and its drain: 100 x `attn_selected_slots_total` /
`attn_candidate_slots_total`, counted on the device and returned with the
loop's packed fetch.  Near `topk` / mean decode context; 100 where every
context is no longer than `topk` and attention is dense."""


def read(ctx):
    d = lambda k: ctx.counters1.get(k, 0) - ctx.counters0.get(k, 0)  # noqa: E731
    candidates = d("attn_candidate_slots_total")
    if candidates <= 0:
        return None
    return {"value": 100.0 * d("attn_selected_slots_total") / candidates,
            "note": {"selected": d("attn_selected_slots_total"), "candidates": candidates,
                     "dense_rows": d("attn_select_dense_rows_total")}}
