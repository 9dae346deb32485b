"""Tokens x mHC units whose Sinkhorn-normalised mixing matrix ended with a row
or column sum farther than 1e-3 from 1, over the tokens x units counted, across
the window and its drain: 100 x `hc_unconverged_total` / `hc_units_total`,
counted on the device in every unit and returned with the loop's packed fetch.
A share that rises says `hc_sinkhorn_iters` rounds no longer bring H_res to the
doubly stochastic matrices the residual's identity path rests on."""


def read(ctx):
    d = lambda k: ctx.counters1.get(k, 0) - ctx.counters0.get(k, 0)  # noqa: E731
    units = d("hc_units_total")
    if units <= 0:
        return None
    return {"value": 100.0 * d("hc_unconverged_total") / units,
            "note": {"unconverged": d("hc_unconverged_total"), "units": units}}
