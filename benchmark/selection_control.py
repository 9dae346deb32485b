"""The control of a learned key selection's limits: one run of a cell as
`benchmark.run` makes it, and then the SAME served tokens held to the cell's
reference computed under other selections — `dense` (every causal key) and
`newest` (the newest `topk` keys in place of the top `topk`) — by
`reference.judge`'s own comparison at the limits the reference file has today.

    python3 -m benchmark.selection_control --workload <cell> --seed <n> --seconds 51 --trace <0|1>

`benchmark.run` runs unchanged (its result line is printed as ever); after it
three more lines follow, `{"bench": "selection_control", "select": "topk" |
"dense" | "newest", ...}` with the deficits, the limits and `ok`.  The exit
code is 0 when the served tokens pass the true reference and pass NEITHER
control: limits that a model without the selection, or with the plainest one,
would pass gate nothing of the mechanism.  It varies the REFERENCE, as
`float8_control.py` does, so the program needs no switch; the cell's reference
has to take `logits(..., select=)` and list its `SELECTIONS`, the model's own
first.
"""

from __future__ import annotations

import functools
import gc
import json
import sys

from . import reference, run


def main(argv=None) -> int:
    served = {}
    judge = reference.judge

    def keep(params, raw_config, requests, records):
        served.update(params=params, raw_config=raw_config, requests=requests, records=records)
        return judge(params, raw_config, requests, records)

    reference.judge = keep
    try:
        rc = run.main(argv)
    finally:
        reference.judge = judge
    if rc != 0 or not served:
        return rc or 1
    ref = reference.load(served["raw_config"])
    selections = getattr(ref, "SELECTIONS", None)
    if not selections:
        print("selection_control: the cell's reference has no SELECTIONS", file=sys.stderr)
        return 1
    gc.collect()  # the server's pool, before the reference's activations
    plain = ref.logits
    verdicts = []
    try:
        for select in selections:
            ref.logits = functools.partial(plain, select=select)
            out = judge(served["params"], served["raw_config"], served["requests"], served["records"])
            print(json.dumps({"bench": "selection_control", "select": select, **out}, default=str),
                  flush=True)
            verdicts.append(bool(out["ok"]))
    finally:
        ref.logits = plain
    return 0 if verdicts == [True] + [False] * (len(selections) - 1) else 1


if __name__ == "__main__":
    sys.exit(main())
