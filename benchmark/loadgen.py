"""The load generator: open-loop and closed-loop clients of `POST /generate`.

Copied from `jax_llama_tpu/overload.py` (`_fire_one`, `open_loop_flood`) and
repaired here, where later PRs cannot change it: every time is kept raw on one
clock, so that first-token time can be taken from when the request was DUE, and
how late the generator ran is reported (`late` = sent - due).

A request is `{"id", "prompt": [token ids], "max_new_tokens": n}` plus, for
the open loop, `"at"`: its arrival offset in seconds.  One thread per request in flight;
the streaming reply is NDJSON, one line per token.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from typing import Iterable, Iterator, List, Optional
from urllib.parse import urlsplit


def new_record(i: int, req: dict, due: float) -> dict:
    return {
        "i": i, "id": req["id"], "due": due, "sent": None, "first": None,
        "last": None, "n_tokens": 0, "prompt_tokens": len(req["prompt"]),
        "max_new_tokens": req["max_new_tokens"], "status": None, "ok": False,
        "hung": True, "error": None, "tokens": None,
    }


def fire(address: str, req: dict, rec: dict, timeout_s: float,
         keep_tokens: bool = False, stop: Optional[threading.Event] = None) -> None:
    """Send one request and stream its reply into `rec`.  `rec["hung"]` stays
    True until a terminal outcome is recorded.  Setting `stop` abandons the
    request: the connection closes and the server reaps it."""
    url = urlsplit(address)
    body = json.dumps({
        "prompt": req["prompt"], "max_new_tokens": req["max_new_tokens"],
        "stream": True,
    }).encode()
    conn = http.client.HTTPConnection(url.hostname, url.port, timeout=timeout_s)
    rec["sent"] = time.monotonic()
    try:
        conn.request(
            "POST", "/generate", body=body,
            headers={"Content-Type": "application/json",
                     "X-Request-Id": req["id"]},
        )
        resp = conn.getresponse()
        if resp.status != 200:
            resp.read()
            rec.update(status=resp.status, hung=False)
            return
        n = 0
        error = None
        tokens = None
        for line in resp:
            if stop is not None and stop.is_set():
                rec.update(status=0, error="abandoned", hung=False)
                return
            now = time.monotonic()
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if "token" in obj:
                if rec["first"] is None:
                    rec["first"] = now
                rec["last"] = now
                n += 1
            if obj.get("done"):
                # A failure after the first token rides a 200 stream and
                # shows only in the last line: it is not a served request.
                if obj.get("timeout"):
                    error = "timeout"
                elif obj.get("error"):
                    error = str(obj["error"])
                tokens = obj.get("tokens")
        ok = error is None and n == req["max_new_tokens"]
        if error is None and not ok:
            error = f"{n} tokens for max_new_tokens {req['max_new_tokens']}"
        rec.update(
            status=200 if error is None else 500, ok=ok, n_tokens=n,
            error=error, hung=False,
            tokens=tokens if keep_tokens else None,
        )
    except (OSError, http.client.HTTPException) as e:
        rec.update(status=-1, error=repr(e), hung=False)
    finally:
        conn.close()


def open_loop(address: str, requests: Iterable[dict], timeout_s: float,
              t0: Optional[float] = None) -> List[dict]:
    """Fire each request at `t0 + req["at"]`, never waiting for a reply, then
    join every client for at most `timeout_s`."""
    t0 = time.monotonic() if t0 is None else t0
    records: List[dict] = []
    threads: List[threading.Thread] = []
    for i, req in enumerate(requests):
        due = t0 + req["at"]
        rec = new_record(i, req, due)
        records.append(rec)
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(
            target=fire, args=(address, req, rec, timeout_s), daemon=True
        )
        th.start()
        threads.append(th)
    deadline = time.monotonic() + timeout_s
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()))
    return records


def closed_loop(address: str, requests: Iterator[dict], clients: int,
                seconds: float, ramp_s: float, timeout_s: float,
                t0: Optional[float] = None) -> List[dict]:
    """`clients` workers, each sending its next request when its last reply
    ended, taking requests in order from one shared sequence.  Worker j starts
    at `t0 + j * ramp_s / clients`; no request starts after `t0 + seconds`,
    and those in flight then are drained.  A request is due when its worker
    became free."""
    t0 = time.monotonic() if t0 is None else t0
    stop_at = t0 + seconds
    lock = threading.Lock()
    records: List[dict] = []

    def worker(j: int) -> None:
        delay = t0 + j * ramp_s / clients - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        while time.monotonic() < stop_at:
            with lock:
                req = next(requests, None)
                if req is None:
                    return
                rec = new_record(len(records), req, time.monotonic())
                records.append(rec)
            fire(address, req, rec, timeout_s)

    threads = [
        threading.Thread(target=worker, args=(j,), daemon=True)
        for j in range(clients)
    ]
    for th in threads:
        th.start()
    deadline = stop_at + timeout_s
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()))
    return records


def run_traffic(address: str, traffic: dict, seconds: float, timeout_s: float,
                t0: Optional[float] = None) -> List[dict]:
    """Drive one generated traffic description (see `traffic/`)."""
    if traffic["loop"] == "open":
        return open_loop(address, traffic["requests"], timeout_s, t0)
    if traffic["loop"] == "closed":
        return closed_loop(
            address, iter(traffic["requests"]), traffic["clients"], seconds,
            traffic.get("ramp_s", 0.0), timeout_s, t0,
        )
    raise ValueError(f"unknown loop {traffic['loop']!r}")


class Holder:
    """One long request that keeps a row decoding while set-up sends others
    beside it, so that those take the server's fused prefill-decode lane.
    Set-up only: no holder lives into the window.  `release()` abandons the
    request (the connection closes and the server reaps it)."""

    def __init__(self, address: str, req: dict, timeout_s: float):
        self.record = new_record(0, req, time.monotonic())
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=fire, args=(address, req, self.record, timeout_s),
            kwargs={"stop": self._stop}, daemon=True,
        )
        self._thread.start()
        deadline = time.monotonic() + timeout_s
        while self.record["first"] is None:
            if not self._thread.is_alive() or time.monotonic() > deadline:
                raise RuntimeError(f"the holder got no token: {self.record}")
            time.sleep(0.002)

    def holding(self) -> bool:
        """Still generating: what was sent beside it had an active row."""
        return self._thread.is_alive()

    def release(self, timeout_s: float = 30.0) -> None:
        self._stop.set()
        self._thread.join(timeout=timeout_s)


def burst(address: str, requests: List[dict], timeout_s: float,
          keep_tokens: bool = False) -> List[dict]:
    """Send `requests` at the same instant and wait for every reply."""
    now = time.monotonic()
    records = [new_record(i, req, now) for i, req in enumerate(requests)]
    threads = [
        threading.Thread(target=fire, args=(address, req, rec, timeout_s, keep_tokens),
                         daemon=True)
        for req, rec in zip(requests, records)
    ]
    for th in threads:
        th.start()
    deadline = time.monotonic() + timeout_s
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()))
    return records
