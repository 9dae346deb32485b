"""Grid steps the paged index-ranking kernel ran over the steps the decoding
rows' whole block tables would take, over the window and its drain: 100 x
`attn_index_steps_run_total` / `attn_index_steps_table_total`, counted on the
device and returned with the loop's packed fetch.  Near mean decode context /
`max_seq_len`, rounded up to whole steps: the kernel reads a row's live blocks,
not its table."""


def read(ctx):
    d = lambda k: ctx.counters1.get(k, 0) - ctx.counters0.get(k, 0)  # noqa: E731
    table = d("attn_index_steps_table_total")
    if table <= 0:
        return None
    return {"value": 100.0 * d("attn_index_steps_run_total") / table,
            "note": {"run": d("attn_index_steps_run_total"), "table": table}}
