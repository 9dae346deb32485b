"""HTTP serving front-end: concurrent requests through the real batcher
must match standalone batcher output, and /metrics must expose counters."""

import json
import threading
import time
import urllib.parse
import urllib.request

import jax
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.serving import ContinuousBatcher
from jax_llama_tpu.server import LLMServer
from jax_llama_tpu.tokenizers.bytes import ByteTokenizer

CFG = dict(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=128, dtype="float32", param_dtype="float32",
)


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


def _post(url, payload, timeout=300, request_id=None):
    headers = {"Content-Type": "application/json"}
    if request_id is not None:
        headers["X-Request-Id"] = request_id
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers=headers,
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(url, path, timeout=60):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return r.status, r.read().decode()


def test_http_concurrent_requests_match_standalone(model):
    params, config = model
    tok = ByteTokenizer()
    prompts = ["hello tpu", "paged kv"]
    token_prompts = [tok.encode(p, bos=True) for p in prompts]

    ref = ContinuousBatcher(params, config, n_slots=2, max_len=64,
                            stop_tokens=tuple(tok.stop_tokens))
    rids = [ref.submit(p, max_new_tokens=8) for p in token_prompts]
    want = ref.run_to_completion()

    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64,
                           stop_tokens=tuple(tok.stop_tokens))
    with LLMServer(cb, tokenizer=tok) as srv:
        results = {}

        def call(i):
            status, body = _post(
                srv.address, {"text": prompts[i], "max_new_tokens": 8}
            )
            results[i] = (status, body)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)

        for i in range(len(prompts)):
            status, body = results[i]
            assert status == 200
            assert body["tokens"] == want[rids[i]], prompts[i]
            assert body["text"] == tok.decode(want[rids[i]])

        status, text = _get(srv.address, "/metrics")
        assert status == 200
        assert "llm_emitted_tokens_total" in text
        emitted = [
            line for line in text.splitlines()
            if line.startswith("llm_emitted_tokens_total")
        ][0]
        assert float(emitted.split()[1]) >= sum(
            len(want[r]) for r in rids
        )

        status, body = _get(srv.address, "/healthz")
        assert status == 200 and json.loads(body)["ok"] is True


def test_http_error_paths(model):
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=32)
    with LLMServer(cb) as srv:
        # no tokenizer -> text prompts rejected, token prompts fine
        try:
            _post(srv.address, {"text": "hi", "max_new_tokens": 4})
            assert False, "expected HTTP 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "tokenizer" in json.loads(e.read())["error"]
        # over-capacity request -> batcher ValueError surfaces as 400
        try:
            _post(srv.address,
                  {"prompt": list(range(1, 30)), "max_new_tokens": 30})
            assert False, "expected HTTP 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
        # a valid request still works afterwards
        status, body = _post(
            srv.address, {"prompt": [1, 2, 3], "max_new_tokens": 4}
        )
        assert status == 200
        assert len(body["tokens"]) == 4


def _stream_lines(url, payload, timeout=300):
    """POST with stream=true; return the parsed NDJSON lines."""
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.status == 200
        assert r.headers["Content-Type"] == "application/x-ndjson"
        return [json.loads(line) for line in r.read().splitlines()]


def test_http_streaming_matches_blocking(model):
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    with LLMServer(cb) as srv:
        status, body = _post(
            srv.address, {"prompt": [5, 9, 13], "max_new_tokens": 6}
        )
        assert status == 200
        lines = _stream_lines(
            srv.address,
            {"prompt": [5, 9, 13], "max_new_tokens": 6, "stream": True},
        )
        # one line per token, then the final summary line
        assert lines[-1]["done"] is True
        per_token = [ln["token"] for ln in lines[:-1]]
        assert per_token == body["tokens"]
        assert lines[-1]["tokens"] == body["tokens"]
        assert "timeout" not in lines[-1]


def test_http_timeout_cancels_and_frees_blocks(model):
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    total_blocks = cb.n_blocks
    with LLMServer(cb) as srv:
        # timeout_s=0: already expired when the loop pops the inbox —
        # rejected BEFORE admission (no slot was ever taken).
        try:
            _post(
                srv.address,
                {"prompt": [1, 2, 3], "max_new_tokens": 40,
                 "timeout_s": 0.0},
            )
            assert False, "expected HTTP 504"
        except urllib.error.HTTPError as e:
            assert e.code == 504
            assert "timed out" in json.loads(e.read())["error"]

        # Warm the compile caches so the next request's budget is spent
        # generating, not compiling.
        status, _ = _post(
            srv.address, {"prompt": [4, 5, 6], "max_new_tokens": 2}
        )
        assert status == 200

        # The cancelled request released its slot and blocks: a fresh
        # request gets full capacity and completes.
        status, body = _post(
            srv.address, {"prompt": [4, 5, 6], "max_new_tokens": 4}
        )
        assert status == 200 and len(body["tokens"]) == 4
        assert len(cb.free_blocks) == total_blocks
        assert all(s is None for s in cb.slots.values())


def test_http_mid_generation_timeout_reaps_active_request(model):
    """Exercise _reap's expired-ACTIVE branch (distinct from the
    pre-admission rejection above): the request must be admitted, emit
    some tokens, hit its deadline mid-generation, and be cancelled with
    partial tokens in the 504 body and its slot/blocks released."""
    params, config = model
    # A generation budget far larger than 2s of CPU steps can finish.
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=4096)
    total_blocks = cb.n_blocks
    # priority_classes=False: no deadline proof at admission.  Its rate
    # comes from the warm-up's insert, whose wall time is the compile: on
    # a loaded machine 3 tokens in over 2 s reads as a deadline that
    # cannot be met, and the request is refused 503 before the reaper
    # under test ever sees it (ROADMAP C12).
    with LLMServer(cb, priority_classes=False) as srv:
        # Warm the compile caches so the timed request spends its budget
        # generating, not compiling.
        status, _ = _post(
            srv.address, {"prompt": [4, 5, 6], "max_new_tokens": 2}
        )
        assert status == 200
        try:
            _post(
                srv.address,
                {"prompt": [1, 2, 3], "max_new_tokens": 3000,
                 "timeout_s": 2.0},
            )
            assert False, "expected HTTP 504"
        except urllib.error.HTTPError as e:
            assert e.code == 504
            body = json.loads(e.read())
            assert "timed out" in body["error"]
            # It was admitted and generated until the reap.
            assert 0 < len(body["tokens"]) < 3000
        assert len(cb.free_blocks) == total_blocks
        assert all(s is None for s in cb.slots.values())
        assert not cb.pending()


def test_http_client_disconnect_cancels_stream(model):
    import socket
    import time as _time

    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    total_blocks = cb.n_blocks
    with LLMServer(cb) as srv:
        host, port = srv.httpd.server_address[:2]
        # Small enough to be ADMITTED (the point is reaping an active,
        # generating request), big enough that the client disconnects
        # long before it finishes.
        payload = json.dumps(
            {"prompt": [7, 8, 9], "max_new_tokens": 40, "stream": True}
        ).encode()
        s = socket.create_connection((host, port), timeout=30)
        s.sendall(
            b"POST /generate HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
        )
        s.recv(1024)  # read the status line + first bytes, then vanish
        s.close()
        # The loop notices the dead socket at the next failed write and
        # frees the slot; other requests then proceed normally.
        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline:
            if (
                len(cb.free_blocks) == total_blocks
                and all(sl is None for sl in cb.slots.values())
                and not cb.queue
            ):
                break
            _time.sleep(0.2)
        else:
            assert False, "disconnected stream request was never reaped"
        status, body = _post(
            srv.address, {"prompt": [1, 2], "max_new_tokens": 3}
        )
        assert status == 200 and len(body["tokens"]) == 3


def test_http_client_disconnect_cancels_blocking(model):
    """A NON-streaming /generate whose client vanishes must also be
    reaped: nothing ever writes to the socket until completion, so the
    _blocking_reply wait loop's readable-EOF probe is the only signal."""
    import socket
    import time as _time

    params, config = model
    # A generation budget far larger than the reap window can finish, so
    # the only way the slot frees is the disconnect probe + _reap.
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=4096)
    total_blocks = cb.n_blocks
    with LLMServer(cb) as srv:
        host, port = srv.httpd.server_address[:2]
        # Warm the compile caches first.
        status, _ = _post(
            srv.address, {"prompt": [4, 5, 6], "max_new_tokens": 2}
        )
        assert status == 200
        payload = json.dumps(
            {"prompt": [7, 8, 9], "max_new_tokens": 3000}
        ).encode()
        s = socket.create_connection((host, port), timeout=30)
        s.sendall(
            b"POST /generate HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
        )
        _time.sleep(0.5)  # let the handler enqueue + the loop admit it
        s.close()
        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline:
            if (
                len(cb.free_blocks) == total_blocks
                and all(sl is None for sl in cb.slots.values())
                and not cb.queue
            ):
                break
            _time.sleep(0.2)
        else:
            assert False, "disconnected blocking request was never reaped"
        # Reaped by cancellation, not by finishing the 3000 tokens.
        assert cb.emitted_total < 3000
        status, body = _post(
            srv.address, {"prompt": [1, 2], "max_new_tokens": 3}
        )
        assert status == 200 and len(body["tokens"]) == 3


def test_batcher_cancel_queued_and_active(model):
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    total = cb.n_blocks
    r1 = cb.submit([1, 2, 3], max_new_tokens=8)   # admitted to the slot
    r2 = cb.submit([4, 5, 6], max_new_tokens=8)   # waits in the queue
    assert cb.cancel(r2) is True                  # dequeue
    assert cb.cancel(r2) is False                 # already gone
    assert cb.cancel(r1) is True                  # frees the active slot
    assert not cb.pending()
    assert len(cb.free_blocks) == total


def test_http_non_finite_timeout_rejected(model):
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=32)
    with LLMServer(cb) as srv:
        for bad in ("NaN", "Infinity"):
            req = urllib.request.Request(
                srv.address + "/generate",
                # raw JSON so the non-finite literal reaches the server
                data=(
                    b'{"prompt": [1, 2], "max_new_tokens": 4, '
                    b'"timeout_s": ' + bad.encode() + b"}"
                ),
                headers={"Content-Type": "application/json"},
            )
            try:
                urllib.request.urlopen(req, timeout=60)
                assert False, f"expected HTTP 400 for timeout_s={bad}"
            except urllib.error.HTTPError as e:
                assert e.code == 400
                assert "finite" in json.loads(e.read())["error"]


def test_http_chat_endpoint(model):
    """/chat frames the dialog via the chat_format, defaults stop tokens
    to the tokenizer's stop set, strips stop ids from the decoded text,
    and rejects malformed dialogs and chat-less servers."""
    params, config = model
    tok = ByteTokenizer()

    class ByteChatFormat:
        """Minimal dialog framing over the byte tokenizer (the llama3
        ChatFormat needs a real tiktoken vocab; the server only relies on
        encode_dialog_prompt)."""

        def __init__(self, tokenizer):
            self.tokenizer = tokenizer

        def encode_dialog_prompt(self, dialog):
            ids = [self.tokenizer.bos_id]
            for m in dialog:
                ids += self.tokenizer.encode(f"[{m['role']}]")
                ids += self.tokenizer.encode(m["content"])
            ids += self.tokenizer.encode("[assistant]")
            return ids

    fmt = ByteChatFormat(tok)
    messages = [
        {"role": "system", "content": "terse"},
        {"role": "user", "content": "hi there"},
    ]

    # Reference: standalone batcher fed the same framed prompt with the
    # tokenizer's stop set (the endpoint's default).
    ref = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    rid = ref.submit(
        fmt.encode_dialog_prompt(messages), max_new_tokens=8,
        stop_tokens=tuple(tok.stop_tokens),
    )
    want = ref.run_to_completion()[rid]

    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    with LLMServer(cb, tokenizer=tok, chat_format=fmt) as srv:
        req = urllib.request.Request(
            srv.address + "/chat",
            data=json.dumps(
                {"messages": messages, "max_new_tokens": 8}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            status, body = r.status, json.loads(r.read())
        assert status == 200
        assert body["tokens"] == want
        stop_set = set(tok.stop_tokens)
        assert body["text"] == tok.decode(
            [t for t in want if t not in stop_set]
        )

        # Streaming /chat: NDJSON token lines; stop ids carry no text.
        req = urllib.request.Request(
            srv.address + "/chat",
            data=json.dumps(
                {"messages": messages, "max_new_tokens": 8, "stream": True}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            lines = [json.loads(ln) for ln in r.read().splitlines()]
        assert lines[-1]["done"] is True
        assert lines[-1]["tokens"] == want
        toks_streamed = [ln["token"] for ln in lines[:-1]]
        assert toks_streamed == want
        for ln in lines[:-1]:
            if ln["token"] in stop_set:
                assert ln["text"] == ""  # protocol framing, not content

        # A /chat that sends its own "stop_tokens" decodes verbatim: the
        # tokenizer's stop set is not protocol framing for that request,
        # so a stop id the client generated past must survive in "text"
        # (it still appears in "tokens" either way).
        ref2 = ContinuousBatcher(params, config, n_slots=2, max_len=64)
        rid2 = ref2.submit(
            fmt.encode_dialog_prompt(messages), max_new_tokens=8,
            stop_tokens=(),
        )
        want2 = ref2.run_to_completion()[rid2]
        req = urllib.request.Request(
            srv.address + "/chat",
            data=json.dumps(
                {"messages": messages, "max_new_tokens": 8,
                 "stop_tokens": []}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            body = json.loads(r.read())
        assert body["tokens"] == want2
        assert body["text"] == tok.decode(want2)  # verbatim, stop ids kept

        # Malformed dialogs are 400s, not loop crashes.
        for bad in (
            {},
            {"messages": []},
            {"messages": [{"role": "user"}]},
            {"messages": "hi"},
            # Wrong-TYPED values (OpenAI-style content parts, null, int):
            # ChatFormat would raise AttributeError on these, which is
            # outside the loop's caught-error set — they must be rejected
            # at validation, not allowed to kill the serving thread.
            {"messages": [{"role": "user",
                           "content": [{"type": "text", "text": "hi"}]}]},
            {"messages": [{"role": "user", "content": None}]},
            {"messages": [{"role": 3, "content": "hi"}]},
        ):
            req = urllib.request.Request(
                srv.address + "/chat", data=json.dumps(bad).encode(),
                headers={"Content-Type": "application/json"},
            )
            try:
                urllib.request.urlopen(req, timeout=60)
                assert False, bad
            except urllib.error.HTTPError as e:
                assert e.code == 400

    # A server without a chat_format refuses /chat.
    cb2 = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    with LLMServer(cb2, tokenizer=tok) as srv:
        req = urllib.request.Request(
            srv.address + "/chat",
            data=json.dumps(
                {"messages": messages, "max_new_tokens": 4}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(req, timeout=60)
            assert False
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "chat_format" in json.loads(e.read())["error"]


def test_http_logprobs(model):
    """"logprobs": true returns per-token model logprobs (blocking array
    + per-line streaming), and is a 400 when the batcher was not built
    with logprobs=True."""
    import math

    params, config = model
    tok = ByteTokenizer()
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, logprobs=True
    )
    with LLMServer(cb, tokenizer=tok) as srv:
        status, body = _post(
            srv.address,
            {"text": "hello", "max_new_tokens": 6, "logprobs": True},
        )
        assert status == 200
        assert len(body["logprobs"]) == len(body["tokens"]) == 6
        assert all(
            isinstance(x, float) and x <= 0.0 and math.isfinite(x)
            for x in body["logprobs"]
        )

        # Streaming: each token line carries its logprob; the final line
        # repeats the full array.
        req = urllib.request.Request(
            srv.address + "/generate",
            data=json.dumps({"text": "hello", "max_new_tokens": 6,
                             "logprobs": True, "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            lines = [json.loads(ln) for ln in r.read().splitlines()]
        assert [ln["logprob"] for ln in lines[:-1]] == body["logprobs"]
        assert lines[-1]["logprobs"] == body["logprobs"]
        assert lines[-1]["tokens"] == body["tokens"]

        # Without logprobs the response omits the field.
        status, body2 = _post(
            srv.address, {"text": "hello", "max_new_tokens": 4}
        )
        assert status == 200 and "logprobs" not in body2

    cb2 = ContinuousBatcher(params, config, n_slots=1, max_len=32)
    with LLMServer(cb2, tokenizer=tok) as srv:
        try:
            _post(srv.address,
                  {"text": "x", "max_new_tokens": 2, "logprobs": True})
            assert False
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "logprobs" in json.loads(e.read())["error"]


def test_http_logprobs_with_speculative_batcher(model):
    """"logprobs": true works over a speculative batcher (self-draft):
    the tokens match a plain batcher's and each gets a finite logprob —
    the verify pass supplies logprobs for multi-token emission."""
    import math

    params, config = model
    tok = ByteTokenizer()
    plain = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    prid = plain.submit(tok.encode("hello", bos=True, eos=False),
                        max_new_tokens=8)
    want = plain.run_to_completion()[prid]

    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, logprobs=True,
        draft_params=params, draft_config=config, n_draft=3,
    )
    with LLMServer(cb, tokenizer=tok) as srv:
        status, body = _post(
            srv.address,
            {"text": "hello", "max_new_tokens": 8, "logprobs": True},
        )
        assert status == 200
        assert body["tokens"] == want
        assert len(body["logprobs"]) == 8
        assert all(
            isinstance(x, float) and x <= 0.0 and math.isfinite(x)
            for x in body["logprobs"]
        )


# slow (r17 budget rebalance, ~8 s): HTTP-layer concurrency stays
# tier-1-pinned by test_http_concurrent_requests_match_standalone and
# mixed-class load shedding by test_overload.py's drills (`make
# overload` runs its file unfiltered); the mixed-load soak rides slow
# (unfiltered suite runs it).
@pytest.mark.slow
def test_http_mixed_concurrent_load(model):
    """Soak: 12 concurrent clients mixing blocking, streaming, chat, and
    logprobs requests against a 3-slot batcher — every request completes
    with a consistent body and the pool drains clean."""
    params, config = model
    tok = ByteTokenizer()

    class ByteChatFormat:
        def __init__(self, t):
            self.tokenizer = t

        def encode_dialog_prompt(self, dialog):
            ids = [self.tokenizer.bos_id]
            for m in dialog:
                ids += self.tokenizer.encode(f"[{m['role']}]" + m["content"])
            ids += self.tokenizer.encode("[assistant]")
            return ids

    cb = ContinuousBatcher(
        params, config, n_slots=3, max_len=64, logprobs=True
    )
    total_blocks = cb.n_blocks
    with LLMServer(
        cb, tokenizer=tok, chat_format=ByteChatFormat(tok)
    ) as srv:
        results = {}

        def call(i):
            kind = i % 4
            try:
                if kind == 0:      # blocking /generate
                    status, body = _post(
                        srv.address,
                        {"text": f"req {i}", "max_new_tokens": 5},
                    )
                    ok = status == 200 and len(body["tokens"]) == 5
                elif kind == 1:    # streaming /generate + logprobs
                    lines = _stream_lines(
                        srv.address,
                        {"text": f"req {i}", "max_new_tokens": 5,
                         "stream": True, "logprobs": True},
                    )
                    ok = (
                        lines[-1]["done"] is True
                        and len(lines[-1]["tokens"]) == 5
                        and len(lines[-1]["logprobs"]) == 5
                        and [ln["token"] for ln in lines[:-1]]
                        == lines[-1]["tokens"]
                    )
                elif kind == 2:    # blocking /chat
                    req = urllib.request.Request(
                        srv.address + "/chat",
                        data=json.dumps({
                            "messages": [
                                {"role": "user", "content": f"hi {i}"}
                            ],
                            "max_new_tokens": 5,
                        }).encode(),
                        headers={"Content-Type": "application/json"},
                    )
                    with urllib.request.urlopen(req, timeout=300) as r:
                        body = json.loads(r.read())
                        ok = r.status == 200 and len(body["tokens"]) <= 5
                else:              # blocking /generate + logprobs
                    status, body = _post(
                        srv.address,
                        {"prompt": [2 + i, 7, 11], "max_new_tokens": 5,
                         "logprobs": True, "temperature": 0.6,
                         "seed": i},
                    )
                    ok = (
                        status == 200
                        and len(body["logprobs"]) == len(body["tokens"]) == 5
                    )
                results[i] = ok
            except Exception as e:  # noqa: BLE001 — fail the test, not the thread
                results[i] = f"{type(e).__name__}: {e}"

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads)
        assert all(v is True for v in results.values()), results

    # Everything released: the full pool is allocatable again — truly
    # free blocks plus prefix-cache-retained ones (r5: completed
    # requests RETAIN their keyed prompt blocks for reuse; retention is
    # capacity, not leakage) — and no occupied slots.
    assert (len(cb.free_blocks) + cb._store.cached_blocks()
            == total_blocks)
    assert all(s is None for s in cb.slots.values())
    assert not cb._block_refs  # no dangling refcounts


def test_http_body_size_cap(model):
    """Oversized or missing Content-Length is refused with 413 BEFORE
    any body read; a bad length is a 400; normal requests still work.
    urllib always sets the header, so drive http.client directly."""
    import http.client

    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=32)
    with LLMServer(cb, max_body_bytes=1024) as srv:
        host, port = srv.httpd.server_address[:2]

        def raw_post(headers, body=b""):
            conn = http.client.HTTPConnection(host, port, timeout=30)
            try:
                conn.putrequest("POST", "/generate")
                for k, v in headers.items():
                    conn.putheader(k, v)
                conn.endheaders()
                if body:
                    conn.send(body)
                r = conn.getresponse()
                return r.status, json.loads(r.read())
            finally:
                conn.close()

        # claimed length over the cap: refused up front, body never read
        status, body = raw_post({"Content-Length": str(1 << 30)})
        assert status == 413
        assert "too large" in body["error"]
        # missing Content-Length: 413 too (the length is required)
        status, body = raw_post({})
        assert status == 413
        assert "Content-Length" in body["error"]
        # unparseable length: 400
        status, body = raw_post({"Content-Length": "banana"})
        assert status == 400
        # a normal request under the cap still works
        status, body = _post(
            srv.address, {"prompt": [1, 2, 3], "max_new_tokens": 4}
        )
        assert status == 200 and len(body["tokens"]) == 4


# ---------------------------------------------------------------------------
# Observability surface: /metrics exposition, end-to-end request ids,
# /debug endpoints, SLO gauges (obs.py)
# ---------------------------------------------------------------------------

_SAMPLE_RE = __import__("re").compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9]+(\.[0-9]+)?"
    r"([eE][+-][0-9]+)?$"
)


def _parse_exposition(text):
    """Minimal Prometheus text-format parser: returns
    ({family: type}, {family: help}, {sample_name_with_labels: value})
    and asserts every line is well-formed."""
    types, helps, samples = {}, {}, {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), line
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
        elif line.startswith("# HELP "):
            _, _, name, help_text = line.split(" ", 3)
            assert help_text.strip(), line
            helps[name] = help_text
        else:
            assert _SAMPLE_RE.match(line), f"malformed sample: {line!r}"
            name, value = line.rsplit(" ", 1)
            samples[name] = float(value)
    return types, helps, samples


@pytest.mark.obs
def test_metrics_exposition_valid_prometheus(model):
    """Every /metrics line is valid Prometheus text format, every
    family carries an explicit # TYPE AND # HELP from the obs.METRICS
    registry (no heuristic, no unregistered stragglers), TYPE is
    consistent with semantics, and the histogram families obey the
    cumulative-bucket invariants."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    with LLMServer(cb, tokenizer=ByteTokenizer()) as srv:
        status, _ = _post(
            srv.address, {"prompt": [3, 4, 5], "max_new_tokens": 6}
        )
        assert status == 200
        status, text = _get(srv.address, "/metrics")
        assert status == 200
    types, helps, samples = _parse_exposition(text)
    # The legacy fallback marks unregistered scalars; none may ship.
    assert "UNREGISTERED" not in text
    # Every TYPE has a HELP and vice versa.
    assert set(types) == set(helps)
    # Every sample belongs to a typed family (histograms expose
    # _bucket/_sum/_count series under the family name).
    for name in samples:
        family = name.split("{")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if family.endswith(suffix) and (
                family[: -len(suffix)] in types
            ):
                family = family[: -len(suffix)]
                break
        assert family in types, f"untyped sample {name}"
    # TYPE consistent with semantics: *_total names counters — except
    # llm_radix_nodes_total, the documented resident-count exception.
    for family, kind in types.items():
        if kind == "histogram":
            continue
        if family.endswith("_total") and family != "llm_radix_nodes_total":
            assert kind == "counter", family
    assert types["llm_radix_nodes_total"] == "gauge"
    assert types["llm_active_slots"] == "gauge"
    # KV chain-digest scalar families (PR 13) are registered and
    # typed: versions as gauges, the event ledger as counters.
    assert types["llm_kv_digest_version"] == "gauge"
    assert types["llm_kv_digest_loss_version"] == "gauge"
    assert types["llm_kv_block_bytes"] == "gauge"
    for fam in ("llm_kv_publish_events_total",
                "llm_kv_evict_events_total",
                "llm_kv_demote_events_total",
                "llm_kv_restore_events_total",
                "llm_kv_host_evict_events_total",
                "llm_kv_export_events_total",
                "llm_kv_import_events_total"):
        assert types[fam] == "counter", fam
    assert samples["llm_kv_block_bytes"] > 0
    # The serving histograms are exposed and internally consistent —
    # including the two non-latency KV families (token/block buckets).
    for fam in ("llm_ttft_ms", "llm_itl_ms", "llm_queue_wait_ms",
                "llm_prefill_chunk_ms", "llm_swap_in_ms",
                "llm_compile_ms", "llm_prefix_hit_depth_tokens",
                "llm_session_kv_blocks"):
        assert types[fam] == "histogram"
        buckets = [
            (n, v) for n, v in samples.items()
            if n.startswith(fam + "_bucket{")
        ]
        assert buckets, fam
        counts = [v for _, v in buckets]
        assert counts == sorted(counts), f"{fam} buckets not cumulative"
        inf = [v for n, v in buckets if 'le="+Inf"' in n]
        assert len(inf) == 1
        assert inf[0] == samples[fam + "_count"]
        assert samples[fam + "_sum"] >= 0.0
    # dispatch_ms is a LABELED family: one series per dispatch kind,
    # each internally cumulative with its own _sum/_count.
    assert types["llm_dispatch_ms"] == "histogram"
    kind_re = __import__("re").compile(r'kind="([a-z_]+)"')
    kinds = {
        kind_re.search(n).group(1)
        for n in samples if n.startswith("llm_dispatch_ms_bucket{")
    }
    assert "decode" in kinds and "insert" in kinds, kinds
    for kind in kinds:
        buckets = [
            v for n, v in samples.items()
            if n.startswith("llm_dispatch_ms_bucket{")
            and f'kind="{kind}"' in n
        ]
        assert buckets == sorted(buckets), f"{kind} not cumulative"
        assert buckets[-1] == samples[
            f'llm_dispatch_ms_count{{kind="{kind}"}}'
        ]
        assert samples[f'llm_dispatch_ms_sum{{kind="{kind}"}}'] >= 0.0
    # The request actually fed TTFT and the per-kind dispatch series.
    assert samples["llm_ttft_ms_count"] >= 1
    assert samples['llm_dispatch_ms_count{kind="decode"}'] >= 1
    # Jit-cache observability: the entry gauge (one labeled sample per
    # registered program) and the per-program compile counter; the
    # cost-model gauges are gone (PR 30).
    for fam in ("llm_jit_cache_entries", "llm_program_compiles_total"):
        assert fam in types, fam
    for fam in ("llm_mxu_utilization", "llm_hbm_utilization",
                "llm_host_overhead_ratio"):
        assert fam not in types, fam
    assert types["llm_program_compiles_total"] == "counter"
    cache_progs = {
        n for n in samples if n.startswith("llm_jit_cache_entries{")
    }
    assert (
        'llm_jit_cache_entries{program="_paged_decode_chunk"}'
        in cache_progs
    )
    assert len(cache_progs) == 8  # all registered serving programs
    assert samples["llm_compiles_total"] >= 0
    # Loop phases: the measured host share of a step, by phase.
    assert types["llm_loop_phase_ms_total"] == "counter"
    assert types["llm_loop_gap_ms_total"] == "counter"
    assert types["llm_loop_gap_cpu_ms_total"] == "counter"
    phase_ms = {
        n: v for n, v in samples.items()
        if n.startswith("llm_loop_phase_ms_total{")
    }
    assert 'llm_loop_phase_ms_total{phase="deliver"}' in phase_ms
    assert sum(phase_ms.values()) == pytest.approx(
        samples["llm_loop_gap_ms_total"], abs=0.5
    )
    # ... and their parts: the child spans, the submit, the blocked head.
    for fam in ("llm_loop_span_ms_total", "llm_loop_span_total",
                "llm_dispatch_submit_ms_total", "llm_admit_blocked_total"):
        assert types[fam] == "counter", fam
    assert samples['llm_loop_span_total{span="dispatch.submit"}'] >= 1
    assert 0.0 < samples["llm_dispatch_submit_ms_total"] == pytest.approx(
        samples['llm_loop_span_ms_total{span="dispatch.submit"}'], abs=0.5
    )
    # SLO gauges present (unset deadlines -> 0 / attainment 1.0).
    assert samples["llm_slo_ttft_ms"] == 0.0
    assert samples["llm_slo_attainment"] == 1.0
    assert samples["llm_goodput_tokens_total"] >= 6


@pytest.mark.obs
def test_request_id_end_to_end(model):
    """A client-supplied X-Request-Id is honored and echoed in the
    blocking body, the response header, every stream line, and error
    bodies; absent the header, the server mints one."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    with LLMServer(cb, tokenizer=ByteTokenizer()) as srv:
        req = urllib.request.Request(
            srv.address + "/generate",
            data=json.dumps(
                {"prompt": [3, 4, 5], "max_new_tokens": 4}
            ).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "client-abc-123"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            body = json.loads(r.read())
            assert body["request_id"] == "client-abc-123"
            assert r.headers["X-Request-Id"] == "client-abc-123"
        # Minted id when the client sends none.
        status, body = _post(
            srv.address, {"prompt": [3, 4, 5], "max_new_tokens": 4}
        )
        assert status == 200
        assert isinstance(body["request_id"], str) and body["request_id"]
        # Every stream event carries the id, and the final line agrees.
        req = urllib.request.Request(
            srv.address + "/generate",
            data=json.dumps(
                {"prompt": [5, 6], "max_new_tokens": 4, "stream": True}
            ).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "stream-id-9"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            assert r.headers["X-Request-Id"] == "stream-id-9"
            lines = [json.loads(ln) for ln in r.read().splitlines()]
        assert all(ln["request_id"] == "stream-id-9" for ln in lines)
        assert lines[-1]["done"] is True
        # A well-formed JSON body that is not an object is refused
        # cleanly (an AttributeError traceback would close the socket
        # with no HTTP response at all).
        req = urllib.request.Request(
            srv.address + "/generate", data=b"[1, 2, 3]",
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(req, timeout=60)
            assert False, "expected HTTP 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "JSON object" in json.loads(e.read())["error"]
        # Error bodies carry the id too (malformed payload -> 400).
        req = urllib.request.Request(
            srv.address + "/generate", data=b"{not json",
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "err-id-7"},
        )
        try:
            urllib.request.urlopen(req, timeout=60)
            assert False, "expected HTTP 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
            # Body AND header: proxies correlate on the header.
            assert e.headers["X-Request-Id"] == "err-id-7"
            assert json.loads(e.read())["request_id"] == "err-id-7"


@pytest.mark.obs
def test_debug_endpoints_and_slo_gauges(model):
    """/debug/requests/<id> returns the request's span timeline (spans
    linked to real dispatch spans), /debug/dispatches the ring,
    /debug/trace Perfetto-loadable JSON; configured SLOs feed the
    attainment gauges and goodput counter."""
    from jax_llama_tpu.obs import Observability

    params, config = model
    obs = Observability(slo_ttft_ms=60_000.0, slo_itl_ms=60_000.0)
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64,
                           obs=obs)
    with LLMServer(cb, tokenizer=ByteTokenizer()) as srv:
        req = urllib.request.Request(
            srv.address + "/generate",
            data=json.dumps(
                {"prompt": [7, 8, 9], "max_new_tokens": 5}
            ).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "dbg-1"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            assert json.loads(r.read())["request_id"] == "dbg-1"

        status, body = _get(srv.address, "/debug/requests/dbg-1")
        assert status == 200
        tl = json.loads(body)
        assert tl["request_id"] == "dbg-1"
        assert tl["outcome"] == "finished"
        states = [sp["state"] for sp in tl["spans"]]
        # The timeline starts at the POST (``received``), where the
        # server's own TTFT clock does.
        assert states[:2] == ["received", "queued"]
        assert "decoding" in states
        ring = {d["seq"] for d in tl["dispatch_spans"]}
        linked = [s for sp in tl["spans"] for s in sp["dispatches"]]
        assert linked and set(linked) <= ring

        status, body = _get(srv.address, "/debug/requests?n=8")
        assert status == 200
        idx = json.loads(body)["requests"]
        assert any(r["request_id"] == "dbg-1" for r in idx)

        status, body = _get(srv.address, "/debug/dispatches?n=16")
        assert status == 200
        dispatches = json.loads(body)["dispatches"]
        assert dispatches and all("kind" in d for d in dispatches)

        status, body = _get(srv.address, "/debug/trace")
        assert status == 200
        doc = json.loads(body)
        assert doc["traceEvents"]
        assert any(e["ph"] == "X" for e in doc["traceEvents"])

        try:
            _get(srv.address, "/debug/requests/no-such-id")
            assert False, "expected HTTP 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404

        status, text = _get(srv.address, "/metrics")
        _, _, samples = _parse_exposition(text)
        assert samples["llm_slo_ttft_ms"] == 60000.0
        assert samples["llm_slo_attainment"] == 1.0
        assert samples["llm_requests_slo_ok_total"] >= 1
        assert samples["llm_goodput_tokens_total"] >= 5


@pytest.mark.obs
def test_served_timeline_starts_at_the_post(model):
    """A served request's timeline begins with ``received`` (POST
    accepted -> submit), and ``received + queued + prefilling`` is the
    server's own TTFT up to one step: the dispatch that emits the first
    token, its replay and its delivery."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    with LLMServer(cb) as srv:
        payload = {"prompt": [7, 8, 9, 10], "max_new_tokens": 5}
        _post(srv.address, payload, request_id="warm")  # compiles
        before = srv.obs.hist["ttft_ms"].sum
        _post(srv.address, payload, request_id="timed")
        ttft_ms = srv.obs.hist["ttft_ms"].sum - before
        tl = json.loads(_get(srv.address, "/debug/requests/timed")[1])
    spans = tl["spans"]
    assert [sp["state"] for sp in spans[:3]] == [
        "received", "queued", "prefilling",
    ]
    assert spans[0]["end_ms"] == spans[1]["start_ms"]
    to_first = sum(sp["duration_ms"] for sp in spans[:3])
    one_step = max(d["wall_ms"] for d in tl["dispatch_spans"]) + 25.0
    assert 0.0 <= ttft_ms - to_first <= one_step, (ttft_ms, to_first)


@pytest.mark.obs
def test_fused_run_names_every_phase_of_the_gap(model):
    """Two requests through the live server, the second admitted
    through the fused lane while the first decodes: the records carry
    ``emit``, ``deliver``, ``intake``, ``admit`` and ``prep`` (server
    and scheduler phases on one tiling), every gap sums to its phases,
    and /metrics and /debug/trace expose the same data."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           decode_chunk=4, prefill_budget=32)
    with LLMServer(cb) as srv:
        holder = threading.Thread(target=_post, args=(
            srv.address,
            {"prompt": list(range(3, 12)), "max_new_tokens": 100},
        ))
        holder.start()
        while not cb.obs.dispatches:  # the holder is decoding
            time.sleep(0.01)
        _post(srv.address, {"prompt": list(range(20, 60)),
                            "max_new_tokens": 6})
        holder.join(timeout=300)
        assert not holder.is_alive()
        recs = json.loads(
            _get(srv.address, "/debug/dispatches?n=512")[1]
        )["dispatches"]
        text = _get(srv.address, "/metrics")[1]
        doc = json.loads(_get(srv.address, "/debug/trace")[1])
    assert "fused" in {d["kind"] for d in recs}
    for d in recs[1:]:
        assert abs(sum(d["host_ms"].values()) - d["gap_ms"]) <= 0.01, d
    want = {"emit", "deliver", "intake", "admit", "prep"}
    full = [d for d in recs[1:] if want <= set(d["host_ms"])]
    assert full and any(d["kind"] == "fused" for d in full)
    _, _, samples = _parse_exposition(text)
    for phase in want | {"control"}:
        assert samples[
            f'llm_loop_phase_ms_total{{phase="{phase}"}}'
        ] > 0.0, phase
    assert samples["llm_loop_gap_ms_total"] > 0.0
    # The parts ride the same records, beside host_ms and never in it.
    fused = next(d for d in recs if d["kind"] == "fused")
    assert {"admit.hash", "admit.match", "admit.alloc", "admit.upload",
            "prep.sync_rows", "dispatch.submit"} <= set(fused["span_ms"])
    assert fused["submit_ms"] <= fused["wall_ms"]
    assert not set(fused["host_ms"]) & set(fused["span_ms"])
    for span in ("admit.upload", "emit.replay", "dispatch.submit"):
        assert samples[f'llm_loop_span_ms_total{{span="{span}"}}'] > 0.0
    assert any(e.get("cat") == "loop_span" for e in doc["traceEvents"])
    names = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert "serving loop" in names
    assert {e["name"] for e in doc["traceEvents"]
            if e.get("cat") == "loop"} >= want


@pytest.mark.obs
def test_debug_kv_endpoint_and_healthz_digest(model):
    """GET /debug/kv (the chain-digest tree walk: summary + bounded
    node list, depth cap honored) and the /healthz kv.digest compact
    summary the router poller scrapes; /debug/requests/<id> carries
    the per-session kv accounting fields."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, block_size=16,
    )
    with LLMServer(cb, tokenizer=ByteTokenizer()) as srv:
        prompt = list(range(2, 40))  # 2 full keyed blocks
        req = urllib.request.Request(
            srv.address + "/generate",
            data=json.dumps(
                {"prompt": prompt, "max_new_tokens": 4}
            ).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "kv-dbg-1"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            assert r.status == 200

        status, body = _get(srv.address, "/debug/kv")
        assert status == 200
        doc = json.loads(body)
        summ = doc["summary"]
        assert summ["prefix_index"] == "radix"
        assert summ["nodes"] == len(doc["nodes"]) == 2
        assert summ["version"] >= 2
        assert summ["block_bytes"] > 0
        assert summ["prompt_tokens_total"] == len(prompt)
        assert [n["depth"] for n in doc["nodes"]] == [1, 2]
        assert all(n["tier"] == "hbm" for n in doc["nodes"])
        # Finished request: chain retained idle -> refcount False.
        assert all(n["refcount"] is False for n in doc["nodes"])
        # Depth/node caps bound the payload.
        status, body = _get(srv.address, "/debug/kv?depth=1")
        assert json.loads(body)["nodes"][-1]["depth"] == 1
        status, body = _get(srv.address, "/debug/kv?n=1")
        capped = json.loads(body)
        assert len(capped["nodes"]) == 1 and capped["truncated"] == 1

        # /healthz piggybacks the compact digest summary.
        status, body = _get(srv.address, "/healthz")
        kv = json.loads(body)["kv"]
        assert kv["digest"]["version"] == summ["version"]
        assert kv["digest"]["hash"] == summ["hash"]
        assert kv["block_bytes"] == summ["block_bytes"]
        assert kv["total_blocks"] == cb.n_blocks
        assert kv["prompt_tokens_total"] == len(prompt)

        # Per-session KV accounting on the timeline.
        status, body = _get(srv.address, "/debug/requests/kv-dbg-1")
        tl = json.loads(body)
        assert tl["kv"]["blocks_held"] >= 3
        assert tl["kv"]["prefix_hit_tokens"] == 0
        # A revisit of the same prompt is a counted prefix hit.
        req = urllib.request.Request(
            srv.address + "/generate",
            data=json.dumps(
                {"prompt": prompt, "max_new_tokens": 4}
            ).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "kv-dbg-2"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            assert r.status == 200
        status, body = _get(srv.address, "/debug/requests/kv-dbg-2")
        assert json.loads(body)["kv"]["prefix_hit_tokens"] == 32


@pytest.mark.obs
def test_debug_profiler_endpoint(model, tmp_path):
    """POST /debug/profiler brackets a jax.profiler session: start
    writes a trace under log_dir, double-start/stray-stop are 409s,
    bad actions 400."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64)

    def post_prof(srv, payload):
        req = urllib.request.Request(
            srv.address + "/debug/profiler",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def get_json(srv, path):
        try:
            with urllib.request.urlopen(
                srv.address + path, timeout=60
            ) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    log_dir = str(tmp_path / "xplane")
    with LLMServer(cb) as srv:
        status, body = post_prof(srv, {"action": "bogus"})
        assert status == 400
        status, body = post_prof(srv, {"action": "stop"})
        assert status == 409  # nothing active
        # No completed session yet: the summary endpoint 404s cleanly
        # (before any xplane parsing machinery is touched).
        status, body = get_json(srv, "/debug/profile/summary")
        assert status == 404 and "profiler" in body["error"]
        status, body = post_prof(
            srv, {"action": "start", "log_dir": log_dir}
        )
        assert status == 200 and body["ok"] is True
        status, body = post_prof(
            srv, {"action": "start", "log_dir": log_dir}
        )
        assert status == 409  # already tracing
        # Summarizing the ACTIVE session's dir is refused too.
        status, body = get_json(
            srv, "/debug/profile/summary?log_dir="
            + urllib.parse.quote(log_dir)
        )
        assert status == 409
        status, _ = _post(
            srv.address, {"prompt": [3, 4], "max_new_tokens": 3}
        )
        assert status == 200
        status, body = post_prof(srv, {"action": "stop"})
        assert status == 200 and body["log_dir"] == log_dir
    import os

    assert any(
        f for _, _, fs in os.walk(log_dir) for f in fs
    ), "profiler session wrote no trace files"


@pytest.mark.obs
def test_debug_profile_summary_attributes_programs(model, tmp_path):
    """GET /debug/profile/summary parses the completed xplane session
    (``jax.profiler.ProfileData`` — no TensorFlow protos) into
    per-program time attribution: the serving programs the bracketed
    traffic dispatched appear with nonzero host/device ms; the device
    busy/idle split and the idle-by-phase attribution are present (and
    empty on a CPU capture, which has no device plane)."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64)

    def post_prof(srv, payload):
        req = urllib.request.Request(
            srv.address + "/debug/profiler",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, json.loads(r.read())

    log_dir = str(tmp_path / "xplane")
    with LLMServer(cb) as srv:
        status, _ = post_prof(
            srv, {"action": "start", "log_dir": log_dir}
        )
        assert status == 200
        status, _ = _post(
            srv.address, {"prompt": [5, 6, 7], "max_new_tokens": 4}
        )
        assert status == 200
        status, _ = post_prof(srv, {"action": "stop"})
        assert status == 200
        with urllib.request.urlopen(
            srv.address + "/debug/profile/summary", timeout=120
        ) as r:
            assert r.status == 200
            summary = json.loads(r.read())
    assert summary["log_dir"] == log_dir
    progs = summary["programs"]
    # The bracketed request dispatched decode chunks: attributed.
    assert "_paged_decode_chunk" in progs
    attributed = (
        progs["_paged_decode_chunk"]["host_ms"]
        + progs["_paged_decode_chunk"]["device_ms"]
    )
    assert attributed > 0
    assert summary["total_host_ms"] + summary["total_device_ms"] > 0
    assert summary["busy_ms"] == 0.0 and summary["idle_ms"] == 0.0
    assert summary["idle_by_phase_ms"] == {}
    assert summary["idle_by_span_ms"] == {}


def test_http_overload_refusal_503_carries_retry_after(model):
    """The queue-depth overload 503 (ISSUE 9 satellite): it used to be
    a bare 503 while the drain-mode 503 carried Retry-After — now both
    do, load-derived, so retry layers back off instead of hammering."""
    import time

    from jax_llama_tpu.faults import FaultInjector

    params, config = model
    # A 20 ms injected step delay pins the resident in its slot long
    # enough to observe the depth-1 refusal deterministically.
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=256,
        fault_injector=FaultInjector("step~1.0:delay=0.02"),
    )
    with LLMServer(cb, max_queue=1) as srv:
        status, _ = _post(srv.address,
                          {"prompt": [1, 2], "max_new_tokens": 2})
        assert status == 200  # warm the compile caches
        done = {}

        def run():
            done["resident"] = _post(
                srv.address, {"prompt": [3, 4], "max_new_tokens": 60},
                timeout=300,
            )

        t = threading.Thread(target=run)
        t.start()
        time.sleep(0.4)  # resident admitted: depth budget consumed
        try:
            _post(srv.address, {"prompt": [5, 6], "max_new_tokens": 2})
            assert False, "expected HTTP 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert int(e.headers["Retry-After"]) >= 1
            body = json.loads(e.read())
            assert "overloaded" in body["error"]
            assert body["request_id"]  # refusals stay traceable
        t.join(timeout=300)
        assert not t.is_alive()
        assert done["resident"][0] == 200  # the resident was untouched


def test_healthz_overload_section(model):
    """/healthz carries the overload controller's state (schema in the
    server.py module docstring) next to the kv and features sections."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=32)
    with LLMServer(cb) as srv:
        status, body = _get(srv.address, "/healthz")
        assert status == 200
        ov = json.loads(body)["overload"]
        assert ov["enabled"] is True
        assert ov["rung"] == "normal"
        assert set(ov["queued"]) == {"interactive", "batch"}
        assert ov["refused"] == {
            "backlog": 0, "deadline": 0, "batch": 0,
        }
        assert ov["transitions_total"] == 0
        # priority_classes=False keeps the FIFO/backstop-only mode and
        # says so in the same section.
    cb2 = ContinuousBatcher(params, config, n_slots=1, max_len=32)
    with LLMServer(cb2, priority_classes=False) as srv:
        _, body = _get(srv.address, "/healthz")
        assert json.loads(body)["overload"]["enabled"] is False
