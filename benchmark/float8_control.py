"""The second reading behind a reference's limits: one run of a cell as
`benchmark.run` makes it, and then the SAME served tokens held to the cell's
reference computed from float8_e4m3 weights, the nearest precision below the
bfloat16 the configurations state, by `reference.judge`'s own comparison at
the limits the reference file has today.

    python3 -m benchmark.float8_control --workload <cell> --seed <n> --seconds 51 --trace <0|1>

`benchmark.run` runs unchanged (its result line is printed as ever); after it
two more lines follow, `{"bench": "float8_control", "weights": "as served" |
"float8_e4m3", ...}` with the deficits, the limits and `ok`.  The exit code is
0 when the weights as served pass and the float8 ones do NOT: limits that pass
float8 weights gate nothing (PERF.md section 3 says which of a block's two limits is
the gate).  The served weights are rounded in place, after the server is gone:
a chip that holds a cell's weights has no room for a second copy.
"""

from __future__ import annotations

import gc
import json
import sys

from . import reference, run

MANTISSA_BITS, MIN_EXPONENT, LARGEST = 3, -6, 448.0  # float8_e4m3fn


def round_to_float8(a):
    """`a` on float8_e4m3's grid under a power-of-two scale a tensor, by
    arithmetic.  Not by a convert pair: XLA:TPU keeps the excess precision of
    f32 -> f8 -> f32 and the pair reads as a no-op (measured, PR 32)."""
    import jax.numpy as jnp

    x = a.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.exp2(jnp.floor(jnp.log2(LARGEST / jnp.maximum(amax, 1e-30))))
    y = x * scale
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 1e-30))), MIN_EXPONENT)
    step = jnp.exp2(e - MANTISSA_BITS)
    y = jnp.clip(jnp.round(y / step) * step, -LARGEST, LARGEST)
    return (y / scale).astype(a.dtype)


def main(argv=None) -> int:
    import jax

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
    gc.collect()  # the server's pool, before the reference's activations
    verdicts = []
    for weights in ("as served", "float8_e4m3"):
        if weights == "float8_e4m3":
            rnd = jax.jit(round_to_float8, donate_argnums=0)
            served["params"] = jax.tree.map(
                lambda a: rnd(a) if a.ndim >= 2 else a, served.pop("params"))
        out = judge(served["params"], served["raw_config"], served["requests"], served["records"])
        print(json.dumps({"bench": "float8_control", "weights": weights, **out}, default=str), flush=True)
        verdicts.append(bool(out["ok"]))
    return 0 if verdicts == [True, False] else 1


if __name__ == "__main__":
    sys.exit(main())
