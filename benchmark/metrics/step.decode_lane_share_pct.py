"""Share of the device's busy time under `lane.decode`: the decode scan of
`_paged_decode_chunk` and of `_fused_chunk` (`serving._chunk_scan`), by self
time of the traced operations (`benchmark/lanes.py`).  The note gives the
seconds of every lane (`chunk`, `mixed`, `decode`, `insert`: the two insert
programs, `none`) and the lane x leaf-scope table, `lane|scope`: the experts
under the decode rows beside the experts under the prompt chunk, and so for
every scope.  A program without lanes (the parent of PR 53) reads nothing."""

from benchmark import lanes


def read(ctx):
    tab = lanes.of_run(ctx)
    if tab is None or tab["busy_s"] <= 0:
        return None
    return {"value": 100.0 * tab["by_lane"].get("decode", 0.0) / tab["busy_s"],
            "note": {"busy_self_s": tab["busy_s"],
                     "seconds_by_lane": lanes.top(tab["by_lane"]),
                     "seconds_by_lane_and_scope": lanes.top(tab["by_lane_leaf"])}}
