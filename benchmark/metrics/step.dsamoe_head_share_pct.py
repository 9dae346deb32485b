"""Share of the device's busy time inside the output head of the `dsa_moe`
block, the scope `head` (the final norm's logits over the whole vocabulary),
by self time of the traced operations (`benchmark/scopes.py`).  The cut to 6
of 48 layers leaves the head 0.62 GB of the ~4 GB a decode iteration reads
where the whole model's is a fiftieth: this is the share to discount.
(`step.head_share_pct`'s reader reads the `falcon_h1` block only.)  A program
without the scope, or a configuration of another block, reads nothing."""

import importlib

share = importlib.import_module("benchmark.metrics.sparse_attn_roofline").share


def read(ctx):
    return share(ctx, ("head",))
