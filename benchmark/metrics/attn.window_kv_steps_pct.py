"""Live grid steps of the paged decode kernel a window layer, over those a
full layer, over the window and its drain: 100 x (`attn_window_kv_steps_total`
/ window layers) / (`attn_full_kv_steps_total` / full layers), the kernel's own
step list counted on the device and returned with the loop's packed fetch.
Near (window + block) / mean context when the window layers skip what lies
before the window; 100 when they visit what a full layer visits."""


def read(ctx):
    d = lambda k: ctx.counters1.get(k, 0) - ctx.counters0.get(k, 0)  # noqa: E731
    kinds = ctx.config.get("layer_types") or ()
    n_window = sum(1 for t in kinds if t == "sliding_attention")
    n_full = len(kinds) - n_window
    full = d("attn_full_kv_steps_total")
    if not n_window or not n_full or full <= 0:
        return None
    return 100.0 * (d("attn_window_kv_steps_total") / n_window) / (full / n_full)
