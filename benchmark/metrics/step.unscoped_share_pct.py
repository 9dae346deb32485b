"""Share of the device's busy time in operations with no leaf scope of the
program's closed set (`obs.DEVICE_SCOPES`; `benchmark/lanes.py` holds the
benchmark's copy), by self time of the traced operations.  The note names the
ten largest of them (`trace.op_name`), so that what is left is a list:
compiler-made copies and `while` shells carry no source operation and stay.
A program without lanes (the parent of PR 53) reads nothing."""

from benchmark import lanes


def read(ctx):
    tab = lanes.of_run(ctx)
    if tab is None or tab["busy_s"] <= 0:
        return None
    return {"value": 100.0 * tab["by_leaf"].get(lanes.UNSCOPED, 0.0) / tab["busy_s"],
            "note": {"busy_self_s": tab["busy_s"],
                     "seconds_by_scope": lanes.top(tab["by_leaf"]),
                     "unscoped_ops": lanes.top(tab["unscoped"], 10)}}
