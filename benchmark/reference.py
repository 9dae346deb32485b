"""The comparison that decides `correct`, and the loader of the plain
reference it compares with.

The reference is the architecture's forward pass in `jax.numpy`, float32, no
kernel, no cache, no batching tricks.  It is one file a block,
`references/<name>.py`, and a configuration file asks for its own with
`"reference": "<name>"`; nothing here knows a block.  A reference module is: a
docstring that states the architecture, each departure from the published
implementation and the reason for each limit; `logits(params, tokens, cfg,
first)`, float32 `[B, T - first, V]` at positions first..T-1 of `tokens`
`[B, T]`, computed under `jax.default_matmul_precision("highest")` from the
configuration FILE's keys (`cfg`), never from the program's configuration
object; and the two limits `MAX_DEFICIT` and `MEAN_DEFICIT`.

What is compared.  The server answers seeded prompts greedily WHILE OTHER ROWS
ARE DECODING, so that they are admitted through the programs the measured
window runs (the fused prefill-decode chunk, and for the re-asked prompts the
prefix-cache hit), not through the idle server's whole-prompt insert; `run.py`
sends them and proves the path from the program's dispatch records.  Prompt
plus served tokens go through the reference's forward pass in one piece (teacher
forcing), and at every generated position the served token's reference logit
is compared with the reference's largest logit: the difference is the
position's deficit.  Token identity is not the test: with random weights the
two largest of 32,768 logits are about 0.2 apart, and bfloat16 serving flips
such near-ties.
"""

from __future__ import annotations

import functools
import importlib.util
import random
from pathlib import Path
from typing import Any, Dict, List

REFERENCES = Path(__file__).resolve().parent / "references"


@functools.lru_cache(maxsize=None)
def _module(path: Path):
    spec = importlib.util.spec_from_file_location("benchmark.references." + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for attr in ("logits", "MAX_DEFICIT", "MEAN_DEFICIT"):
        if not hasattr(mod, attr):
            raise SystemExit(f"the reference {path} has no `{attr}`")
    return mod


def load(cfg: Dict[str, Any]):
    """The reference module a configuration names (by file, as `run.py` loads
    a metric's reader)."""
    name = cfg.get("reference")
    if not isinstance(name, str) or not name:
        raise SystemExit(
            "the configuration names no reference: it needs \"reference\": \"<name>\" "
            f"for a file {REFERENCES}/<name>.py"
        )
    path = REFERENCES / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"the configuration's reference {name!r} has no file {path}")
    return _module(path)


def deficits(params, prompts: List[List[int]], served: List[List[int]],
             cfg: Dict[str, Any]):
    """Per generated position, reference max logit minus the served token's
    reference logit.  All prompts one length, all replies one length."""
    import jax.numpy as jnp
    import numpy as np

    P, G = len(prompts[0]), len(served[0])
    toks = jnp.asarray(np.array([p + s for p, s in zip(prompts, served)], np.int32))
    lg = np.asarray(load(cfg).logits(params, toks, cfg, P - 1))[:, :G]      # [B, G, V]
    picked = np.take_along_axis(
        lg, np.array(served, np.int64)[:, :, None], axis=2
    )[:, :, 0]
    return lg.max(axis=2) - picked


def check_requests(spec: Dict[str, Any], seed: int, vocab: int):
    """The check's prompts: `spec["prompts"]` fresh ones of
    `spec["prompt_tokens"]` tokens, and as many re-asks, each the first
    `spec["shared_tokens"]` tokens of a fresh one with another ending of the
    same length, so that it finds that prefix in the cache.  All for
    `spec["new_tokens"]` greedy tokens."""
    rng = random.Random(seed)
    n, P, S = int(spec["prompts"]), int(spec["prompt_tokens"]), int(spec["shared_tokens"])
    draw = lambda k: [rng.randrange(vocab) for _ in range(k)]  # noqa: E731
    fresh = [
        {"id": f"check-{seed}-fresh-{i}", "prompt": draw(P),
         "max_new_tokens": int(spec["new_tokens"])}
        for i in range(n)
    ]
    reask = [
        {"id": f"check-{seed}-reask-{i}", "prompt": f["prompt"][:S] + draw(P - S),
         "max_new_tokens": int(spec["new_tokens"])}
        for i, f in enumerate(fresh)
    ]
    return fresh, reask


def judge(params, cfg: Dict[str, Any], requests: List[dict],
          records: List[dict]) -> Dict[str, Any]:
    """Hold the served replies (`records[i]["tokens"]` of `requests[i]`) to
    the reference `cfg` names, under that reference's own limits."""
    bad = [r for r in records if not r["ok"] or not r["tokens"]]
    if bad:
        return {"ok": False, "error": f"check request failed: {bad[0]['error'] or bad[0]['status']}"}
    served = [[int(t) for t in r["tokens"]] for r in records]
    ref = load(cfg)
    d = deficits(params, [r["prompt"] for r in requests], served, cfg)
    return {
        "ok": bool(d.max() <= ref.MAX_DEFICIT and d.mean() <= ref.MEAN_DEFICIT),
        "max_deficit": float(d.max()), "mean_deficit": float(d.mean()),
        "limits": [ref.MAX_DEFICIT, ref.MEAN_DEFICIT],
        "positions": int(d.size), "argmax_agree": float((d == 0).mean()),
    }
