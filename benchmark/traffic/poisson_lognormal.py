"""Open loop: Poisson arrivals, log-normal prompt and output lengths, no
shared prefixes.

Parameters (the workload file's `traffic` object):
  rate_rps            arrivals per second, fixed in the cell
  prompt_tokens       {"median", "sigma", "min", "max"}: log-normal, clipped
  output_tokens       the same, for `max_new_tokens`

Every seed gets the same multiset of (prompt length, output length) pairs and
the same multiset of inter-arrival gaps, in another order: the lengths and
gaps are the quantiles of their distributions at evenly spaced probabilities,
and the seed only shuffles them.  So two seeds offer the same work, and runs
differ by order and by the tokens themselves, not by the luck of the draw.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import List


def _lognormal_quantiles(spec: dict, n: int) -> List[int]:
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = int(round(math.exp(mu + sigma * z)))
        out.append(max(spec["min"], min(spec["max"], v)))
    return out


def _exp_quantiles(rate: float, n: int) -> List[float]:
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def generate(params: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    rng = random.Random(seed)
    rate = float(params["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    prompts = _lognormal_quantiles(params["prompt_tokens"], n)
    outputs = _lognormal_quantiles(params["output_tokens"], n)
    gaps = _exp_quantiles(rate, n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    rng.shuffle(gaps)
    # The gaps' quantile mean is a little under 1/rate (the tail is cut at
    # the last quantile), so scale them to fill the window exactly.
    scale = seconds / sum(gaps)
    requests = []
    at = 0.0
    for i in range(n):
        # A request arrives in the middle of its gap, so that the first is
        # not at 0 and the last not at `seconds`.
        at_i = at + gaps[i] * scale * 0.5
        at += gaps[i] * scale
        requests.append({
            "id": f"s{seed}-{i}", "at": at_i,
            "prompt": [rng.randrange(vocab_size) for _ in range(prompts[i])],
            "max_new_tokens": outputs[i],
        })
    return {"loop": "open", "requests": requests}
