"""The control of a multi-stream residual's limits: one run of a cell as
`benchmark.run` makes it, and then the SAME served tokens held to the cell's
reference computed with each of its `FAULTS` — a wrong mHC unit, softmax scale
or query norm (`references/mhc_mla_moe.py` lists them) — by `reference.judge`'s
own comparison at the limits the reference file has today.

    python3 -m benchmark.hc_control --workload <cell> --seed <n> --seconds 51 --trace <0|1>

`benchmark.run` runs unchanged (its result line is printed as ever); after it
one line a reference follows, `{"bench": "hc_control", "fault": null | "<name>",
...}` with the deficits, the limits and `ok`.  The exit code is 0 when the
served tokens pass the true reference and pass NONE of the wrong ones: limits
that a model without a part of the mechanism would pass gate nothing of that
part.  It varies the REFERENCE, as `float8_control.py` and
`selection_control.py` do, so the program needs no switch; the cell's reference
has to take `logits(..., fault=)` and list its `FAULTS`.
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
    faults = getattr(ref, "FAULTS", None)
    if not faults:
        print("hc_control: the cell's reference has no FAULTS", file=sys.stderr)
        return 1
    gc.collect()  # the server's pool, before the reference's activations
    plain = ref.logits
    verdicts = []
    try:
        for fault in (None, *faults):
            ref.logits = functools.partial(plain, fault=fault)
            out = judge(served["params"], served["raw_config"], served["requests"], served["records"])
            print(json.dumps({"bench": "hc_control", "fault": fault, **out}, default=str), flush=True)
            verdicts.append(bool(out["ok"]))
    finally:
        ref.logits = plain
    return 0 if verdicts == [True] + [False] * len(faults) else 1


if __name__ == "__main__":
    sys.exit(main())
