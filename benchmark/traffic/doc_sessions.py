"""Closed loop: question-answering sessions over long documents.

Parameters (the workload file's `traffic` object):
  clients             workers that each wait for their reply
  ramp_s              the workers start evenly spread over this long
  document_tokens     {"min", "max"}: uniform document length
  question_tokens     {"min", "max"}: uniform, appended to the document
  answer_tokens       {"min", "max"}: uniform `max_new_tokens`
  asks_per_document   how often each document is asked about
  interleave          how many documents' asks are woven together

Requests come in groups of `interleave` documents: ask 1 of each document of
the group, then ask 2 of each, and so on, so a reuse of a document is
`interleave` requests away in the sequence (and, with several clients in
flight, some requests more or fewer in time).  The sequence has no end: the
closed loop takes from it until the window closes.

Every seed gets the same lengths in another order: within each cycle of
`cycle` documents the document, question and answer lengths are evenly
spaced over their ranges and shuffled by the seed.
"""

from __future__ import annotations

import random
from typing import Iterator, List


def _spread(spec: dict, n: int, rng: random.Random) -> List[int]:
    lo, hi = spec["min"], spec["max"]
    vals = [int(round(lo + (hi - lo) * (i + 0.5) / n)) for i in range(n)]
    rng.shuffle(vals)
    return vals


def _requests(params: dict, seed: int, vocab_size: int) -> Iterator[dict]:
    rng = random.Random(seed)
    asks = int(params["asks_per_document"])
    weave = int(params["interleave"])
    cycle = int(params.get("cycle", 16))
    doc_no = 0
    while True:
        doc_lens = _spread(params["document_tokens"], cycle, rng)
        q_lens = _spread(params["question_tokens"], cycle * asks, rng)
        a_lens = _spread(params["answer_tokens"], cycle * asks, rng)
        for g in range(0, cycle, weave):
            group = []
            for d in range(g, min(g + weave, cycle)):
                doc = [rng.randrange(vocab_size) for _ in range(doc_lens[d])]
                group.append((doc_no, d, doc))
                doc_no += 1
            for a in range(asks):
                for no, d, doc in group:
                    q = [rng.randrange(vocab_size)
                         for _ in range(q_lens[d * asks + a])]
                    yield {
                        "id": f"s{seed}-d{no}-a{a}", "document": no, "ask": a,
                        "prompt": doc + q,
                        "max_new_tokens": a_lens[d * asks + a],
                    }


def generate(params: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    return {
        "loop": "closed", "clients": int(params["clients"]),
        "ramp_s": float(params.get("ramp_s", 0.0)),
        "requests": _requests(params, seed, vocab_size),
    }
