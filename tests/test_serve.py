"""HTTP serving front-end: completions (batch + SSE streaming) over the
continuous-batching scheduler must reproduce the engine's own outputs, and
the server must survive concurrent clients and mid-stream disconnects."""

import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.engine import InferenceEngine
from infinistore_tpu.kv import PagedCacheConfig
from infinistore_tpu.models import TINY, init_params, prefill_forward, scaled
from infinistore_tpu.serve import ServingServer

CFG = scaled(TINY, dtype=jnp.float32)
PARAMS = init_params(CFG, jax.random.PRNGKey(7))
PROMPT = [11, 42, 7, 99, 5, 3, 17, 28, 64, 1, 2]


from conftest import WALK_SLO, make_dense_greedy

dense_greedy = make_dense_greedy(PARAMS, CFG)


@pytest.fixture(scope="module")
def server():
    # ISTPU_ADMISSION=0: this module tests the OpenAI contract, not the
    # overload control loop (tests/test_admission.py owns that).  On a
    # slow/loaded host the FIRST tests' cold-compile requests blow the
    # default 2 s TTFT SLO, ttft_burn fires, and the single-lane
    # duty-cycle shed 429s the rest of the module — the same isolation
    # rule as the PR-10 health_stack and PR-14 membership fixtures.
    # The max_queue 429 tests below build their own servers and use the
    # separate depth-based machinery, which this does not touch.
    import os

    old = os.environ.get("ISTPU_ADMISSION")
    os.environ["ISTPU_ADMISSION"] = "0"
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=64, block_tokens=4,
            dtype=CFG.dtype,
        ),
    )
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=4, model_id="tiny-test")
    srv.start()
    if old is None:
        os.environ.pop("ISTPU_ADMISSION", None)
    else:
        os.environ["ISTPU_ADMISSION"] = old
    yield srv
    srv.close()


def _post(port, body, timeout=120, path="/v1/completions"):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, json.loads(data)


def test_n_choices_and_usage(server):
    """OpenAI n>1: one request returns n indexed choices; usage counts the
    prompt once and sums completions (VERDICT r3 weak #8)."""
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 5, "temperature": 0, "n": 3,
    })
    assert status == 200, body
    choices = body["choices"]
    assert [c["index"] for c in choices] == [0, 1, 2]
    want = dense_greedy(PROMPT, 5)
    for c in choices:  # greedy: all n identical, each exact
        assert c["token_ids"] == want
    assert body["usage"] == {
        "prompt_tokens": len(PROMPT),
        "completion_tokens": 15,
        "total_tokens": len(PROMPT) + 15,
    }
    # sampled n>1: choices draw independently
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 16, "temperature": 5.0, "n": 4,
    })
    assert status == 200, body
    outs = {tuple(c["token_ids"]) for c in body["choices"]}
    assert len(outs) > 1  # astronomically unlikely to collide at temp 5


def test_completions_logprobs_contract(server):
    """Legacy completions logprobs: token_logprobs aligned with token_ids,
    top_logprobs dicts of the requested size; greedy's chosen logprob is
    the max of its top alternatives (argmax == top-1)."""
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 6, "temperature": 0, "logprobs": 2,
    })
    assert status == 200, body
    choice = body["choices"][0]
    assert choice["token_ids"] == dense_greedy(PROMPT, 6)
    lp = choice["logprobs"]
    assert len(lp["tokens"]) == len(lp["token_logprobs"]) == 6
    assert len(lp["top_logprobs"]) == 6
    for chosen, top in zip(lp["token_logprobs"], lp["top_logprobs"]):
        assert len(top) == 2
        assert chosen == pytest.approx(max(top.values()), abs=1e-5)
        assert chosen <= 0.0
    # logprobs: 0 => chosen logprob only, empty top dicts
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 3, "temperature": 0, "logprobs": 0,
    })
    assert status == 200, body
    lp = body["choices"][0]["logprobs"]
    assert len(lp["token_logprobs"]) == 3
    assert all(t == {} for t in lp["top_logprobs"])


def test_max_queue_backpressure_429():
    """Admission control: past --max-queue requests answer 429 instead of
    queueing without bound; capacity frees as requests retire."""
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=64, block_tokens=4,
            dtype=CFG.dtype,
        ),
    )
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=2, model_id="tiny-q",
                        max_queue=2)
    srv.start()
    try:
        results = {}
        threads = []

        def post(i, body):
            results[i] = _post(srv.port, body, timeout=120)

        # 2 slow requests fill the system; the burst behind them must see
        # some 429s (depth checked on the engine thread at submission)
        for i in range(6):
            t = threading.Thread(target=post, args=(
                i, {"prompt": PROMPT, "max_tokens": 32, "temperature": 0}))
            t.start()
            threads.append(t)
            if i < 2:
                time.sleep(0.3)  # let the first two enter the system
        for t in threads:
            t.join()
        statuses = [results[i][0] for i in range(6)]
        assert statuses[0] == 200 and statuses[1] == 200, statuses
        assert 429 in statuses, statuses
        # the server recovers: a fresh request after the burst drains
        status, body = _post(srv.port, {
            "prompt": PROMPT, "max_tokens": 2, "temperature": 0})
        assert status == 200, body
    finally:
        srv.close()


@pytest.mark.parametrize("owned", [True, False])
def test_a_server_that_owns_its_process_freezes_what_a_traced_step_leaves(
        owned, monkeypatch):
    """``serve.main()`` owns its process and sets ``freeze_traced_heap``:
    after a step that traced a program the engine thread collects once and
    freezes the heap (the jaxprs and executables a warm-up leaves are walked
    by no later full collection: a 0.3-0.4 s stop of every thread at twelve
    layers, PERF.md PR 48); a step that traced nothing does neither, and a
    server embedded in someone else's process (the default) never does."""
    from infinistore_tpu import serve
    from infinistore_tpu.engine import stepprof

    calls = []

    class _Gc:
        collect = staticmethod(lambda: calls.append("collect"))
        freeze = staticmethod(lambda: calls.append("freeze"))

    monkeypatch.setattr(serve, "gc", _Gc)
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=64, block_tokens=4,
            dtype=CFG.dtype,
        ),
    )
    srv = ServingServer(eng, port=0, max_batch=2, model_id="tiny-gc")
    srv.freeze_traced_heap = owned
    srv.start()
    try:
        body = {"prompt": PROMPT, "max_tokens": 3, "temperature": 0}
        for _ in range(3):      # computed; from the pages in HBM; the same
            assert _post(srv.port, body)[0] == 200
        first, traces = list(calls), stepprof.total_traces()
        assert _post(srv.port, body)[0] == 200
        assert stepprof.total_traces() == traces
    finally:
        srv.close()
    if not owned:
        assert calls == []
        return
    assert first and first == ["collect", "freeze"] * (len(first) // 2)
    assert calls == first           # the repeat traced nothing: no collection
    assert "gc.freeze" in srv.stepprof.summary()["phase_s"]


def test_admission_depth_accounting():
    """The two depth checks see the right state at each handoff stage:
    items the engine loop popped from _staged but has not yet handed to
    the scheduler still count against handler-side (echo) admission, while
    the engine-side re-check for a popped item must NOT count later
    arrivals in _staged (that would 429 an older request in favor of a
    newer one on an idle server)."""
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=64, block_tokens=4,
            dtype=CFG.dtype,
        ),
    )
    srv = ServingServer(eng, port=0, max_batch=2, model_id="tiny-depth",
                        max_queue=2)  # NOT started: counters poked directly
    try:
        # mid-handoff: two items popped from _staged, none in the scheduler
        srv._submitting = 2
        with srv._cv:
            assert srv._over_depth_locked()   # echo admission sees them...
        assert not srv._sched_at_capacity()   # ...but the popped items admit
        srv._submitting = 0
        # a newer request staged behind a popped one must not block it
        srv._staged = [object(), object()]
        with srv._cv:
            assert srv._over_depth_locked()   # newcomers queue behind them
        assert not srv._sched_at_capacity()   # the popped item itself admits
        srv._staged = []
        # standing scoring reservations DO block both sides
        srv._scoring = 2
        with srv._cv:
            assert srv._over_depth_locked()
        assert srv._sched_at_capacity()
    finally:
        # close() would join the never-started engine thread; just release
        # the eagerly-bound HTTP socket
        srv.httpd.server_close()


def test_scoring_respects_capacity_and_fault_class():
    """Echo/scoring requests run their forward on the handler thread, but
    (a) still answer 429 at capacity — the admission limit bounds scoring
    forwards like anything else — and (b) a runtime failure inside the
    scoring forward is a 500 (server fault), not a 400 (bad request)."""
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=64, block_tokens=4,
            dtype=CFG.dtype,
        ),
    )
    srv = ServingServer(eng, port=0, max_batch=2, model_id="tiny-cap",
                        max_queue=0)  # always at capacity
    srv.start()
    try:
        status, body = _post(srv.port, {
            "prompt": PROMPT, "max_tokens": 0, "temperature": 0,
            "echo": True, "logprobs": 1,
        })
        assert status == 429, body
    finally:
        srv.close()

    srv = ServingServer(eng, port=0, max_batch=2, model_id="tiny-fault")
    srv.start()
    try:
        def boom(*a, **k):
            raise RuntimeError("injected scoring fault")

        srv.engine.prompt_logprobs = boom
        status, body = _post(srv.port, {
            "prompt": PROMPT, "max_tokens": 0, "temperature": 0,
            "echo": True, "logprobs": 1,
        })
        assert status == 500, body
        assert "scoring failed" in body["error"]
    finally:
        del srv.engine.prompt_logprobs  # instance attr; restore the method
        srv.close()


def test_logit_bias_contract(server):
    """OpenAI logit_bias: a -100 bias on the greedy token forces a
    different choice; a +100 bias forces its token; invalid maps are
    400s."""
    want = dense_greedy(PROMPT, 1)
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 1, "temperature": 0,
        "logit_bias": {str(want[0]): -100},
    })
    assert status == 200, body
    assert body["choices"][0]["token_ids"][0] != want[0]
    forced = 77
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 3, "temperature": 0,
        "logit_bias": {str(forced): 100},
    })
    assert status == 200, body
    assert body["choices"][0]["token_ids"] == [forced] * 3
    for bad in (
        {"logit_bias": {"999999": 1}},      # out of vocab
        {"logit_bias": {"3": 101}},         # bias out of range
        {"logit_bias": {"x": 1}},           # non-id key
        {"logit_bias": [1, 2]},             # not a map
    ):
        status, body = _post(server.port, {
            "prompt": PROMPT, "max_tokens": 2, **bad,
        })
        assert status == 400, (bad, body)


def test_seed_contract(server):
    """OpenAI `seed`: the same seeded sampled request reproduces exactly
    (even though the scheduler's own stream advanced in between); seeded
    n>1 derives distinct per-choice seeds and reproduces as a set."""
    body = {"prompt": PROMPT, "max_tokens": 8, "temperature": 0.9,
            "seed": 7}
    status, a = _post(server.port, body)
    assert status == 200, a
    # advance the scheduler's own stream with an unseeded request
    _post(server.port, {"prompt": PROMPT, "max_tokens": 4,
                        "temperature": 0.9})
    status, b = _post(server.port, body)
    assert status == 200, b
    assert a["choices"][0]["token_ids"] == b["choices"][0]["token_ids"]

    status, c = _post(server.port, {**body, "seed": 8})
    assert status == 200, c
    assert c["choices"][0]["token_ids"] != a["choices"][0]["token_ids"]

    status, multi = _post(server.port, {**body, "n": 3})
    assert status == 200, multi
    outs = [tuple(ch["token_ids"]) for ch in multi["choices"]]
    assert len(set(outs)) == 3          # choices draw distinct seeds
    assert outs[0] == tuple(a["choices"][0]["token_ids"])  # choice 0 = seed
    status, multi2 = _post(server.port, {**body, "n": 3})
    assert [tuple(ch["token_ids"]) for ch in multi2["choices"]] == outs

    status, _ = _post(server.port, {**body, "seed": -1})
    assert status == 400
    status, _ = _post(server.port, {**body, "seed": True})
    assert status == 400


def test_sampling_penalties_contract(server):
    """OpenAI penalty params ride into the compiled decode: a repetition-
    penalized greedy request is deterministic, differs from the plain
    greedy output, and out-of-range values are 400s."""
    want_plain = dense_greedy(PROMPT, 8)
    bodies = [{
        "prompt": PROMPT, "max_tokens": 8, "temperature": 0,
        "repetition_penalty": 1.8, "presence_penalty": 0.5,
    }] * 2
    outs = []
    for body in bodies:
        status, resp = _post(server.port, body)
        assert status == 200, resp
        outs.append(resp["choices"][0]["token_ids"])
    assert outs[0] == outs[1]          # greedy + penalties: deterministic
    assert outs[0] != want_plain       # and the penalties actually bit
    for bad in (
        {"presence_penalty": 3.0},
        {"frequency_penalty": -2.5},
        {"repetition_penalty": 0.0},
        {"repetition_penalty": 11.0},
    ):
        status, resp = _post(server.port, {
            "prompt": PROMPT, "max_tokens": 2, **bad,
        })
        assert status == 400, (bad, resp)


def test_logprobs_validation(server):
    for bad in (
        {"logprobs": 9},          # completions cap is 5
        {"logprobs": "x"},
        {"logprobs": True},       # bools are the CHAT spelling
        {"n": 0},
        {"n": 99},
        # "_chat" is an internal marker; a wire body must not be able to
        # spoof it to borrow the chat endpoint's validation rules
        {"_chat": True, "logprobs": True, "top_logprobs": 8},
    ):
        status, body = _post(server.port, {
            "prompt": PROMPT, "max_tokens": 2, **bad,
        })
        assert status == 400, (bad, body)


def test_chat_logprobs_contract(text_server):
    """Chat logprobs spelling: logprobs bool + top_logprobs int; response
    carries per-token content entries with top_logprobs lists."""
    status, body = _post(text_server.port, {
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 4, "temperature": 0,
        "logprobs": True, "top_logprobs": 3,
    }, path="/v1/chat/completions")
    assert status == 200, body
    choice = body["choices"][0]
    content = choice["logprobs"]["content"]
    assert len(content) == len(choice["token_ids"]) == 4
    for entry in content:
        assert isinstance(entry["token"], str)
        assert entry["logprob"] <= 0.0
        assert len(entry["top_logprobs"]) == 3
    # top_logprobs without logprobs: true is a 400
    status, _ = _post(text_server.port, {
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 2, "top_logprobs": 3,
    }, path="/v1/chat/completions")
    assert status == 400


def test_streaming_n_choices(server):
    """n>1 streaming: one SSE stream interleaves indexed chunks; each
    choice's concatenated ids match the non-streaming result."""
    want = dense_greedy(PROMPT, 5)
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": PROMPT, "max_tokens": 5, "temperature": 0, "n": 2,
        "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    per_choice: dict = {0: [], 1: []}
    finishes = {}
    buf, done = b"", False
    while not done:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            payload = event[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            c = json.loads(payload)["choices"][0]
            per_choice[c["index"]].extend(c["token_ids"])
            if c["finish_reason"]:
                finishes[c["index"]] = c["finish_reason"]
    conn.close()
    assert done
    assert per_choice[0] == want and per_choice[1] == want
    assert finishes == {0: "length", 1: "length"}


def test_streaming_n_choices_with_stop_no_duplicate_final(text_server):
    """n=2 streaming with a stop string: a stop-cancelled choice must emit
    exactly ONE terminal chunk — its trailing scheduler events (retirement
    'done') must not repeat the tail ids or the finish_reason."""
    tok = text_server.tokenizer
    full = dense_greedy(PROMPT, 8)
    stop_char = tok.decode([full[3]])
    status, body = _post(text_server.port, {
        "prompt": PROMPT, "max_tokens": 8, "temperature": 0,
        "stop": stop_char,
    })
    assert status == 200, body
    want_ids = body["choices"][0]["token_ids"]

    conn = http.client.HTTPConnection("127.0.0.1", text_server.port,
                                      timeout=120)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": PROMPT, "max_tokens": 8, "temperature": 0, "n": 2,
        "stop": stop_char, "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    ids = {0: [], 1: []}
    finals = {0: 0, 1: 0}
    buf, done = b"", False
    while not done:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            payload = event[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            c = json.loads(payload)["choices"][0]
            ids[c["index"]].extend(c["token_ids"])
            if c["finish_reason"]:
                finals[c["index"]] += 1
    conn.close()
    assert done
    assert finals == {0: 1, 1: 1}  # exactly one terminal chunk each
    assert ids[0] == want_ids and ids[1] == want_ids


def test_streaming_logprobs(server):
    """Streamed chunks carry logprobs aligned with their token_ids."""
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": PROMPT, "max_tokens": 4, "temperature": 0,
        "logprobs": 1, "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    ids, lp_tokens = [], []
    buf, done = b"", False
    while not done:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            payload = event[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            c = json.loads(payload)["choices"][0]
            ids.extend(c["token_ids"])
            lp = c.get("logprobs")
            if lp:
                lp_tokens.extend(lp["token_logprobs"])
    conn.close()
    assert done
    assert ids == dense_greedy(PROMPT, 4)
    assert len(lp_tokens) == 4
    assert all(x <= 0.0 for x in lp_tokens)


@pytest.fixture(scope="module")
def spec_server():
    """A server with a draft engine attached: speculation as the scheduler's
    batch=1 fast path, reachable over HTTP (VERDICT r3 next #2)."""
    def make(params, cfg):
        return InferenceEngine(
            params, cfg,
            PagedCacheConfig(
                n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, n_blocks=64, block_tokens=4,
                dtype=cfg.dtype,
            ),
        )

    eng = make(PARAMS, CFG)
    eng.decode_chunk = 4
    dcfg = scaled(TINY, dtype=jnp.float32, n_layers=1, dim=64, ffn_dim=128)
    draft = make(init_params(dcfg, jax.random.PRNGKey(99)), dcfg)
    srv = ServingServer(eng, port=0, max_batch=4, model_id="tiny-spec",
                        draft_engine=draft, spec_k=3)
    srv.start()
    yield srv
    srv.close()


def test_speculative_http_matches_greedy(spec_server):
    """An HTTP request served through speculation returns exactly the
    non-speculative greedy output, and /metrics reports the speculative
    counters."""
    status, body = _post(spec_server.port, {
        "prompt": PROMPT, "max_tokens": 10, "temperature": 0,
    })
    assert status == 200, body
    assert body["choices"][0]["token_ids"] == dense_greedy(PROMPT, 10)

    conn = http.client.HTTPConnection("127.0.0.1", spec_server.port,
                                      timeout=30)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    text = resp.read().decode()
    conn.close()
    assert "istpu_spec_acceptance_rate" in text
    rounds = [line for line in text.splitlines()
              if line.startswith("istpu_spec_rounds_total")]
    assert rounds and float(rounds[0].split()[1]) >= 1  # fast path ran


def test_completion_matches_greedy(server):
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 6, "temperature": 0,
    })
    assert status == 200, body
    assert body["choices"][0]["token_ids"] == dense_greedy(PROMPT, 6)
    # budget-terminated: OpenAI reports "length", not "stop"
    assert body["choices"][0]["finish_reason"] == "length"
    assert body["usage"]["completion_tokens"] == 6


def test_streaming_sse_matches_batch(server):
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": PROMPT[:7], "max_tokens": 8, "temperature": 0,
        "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/event-stream")
    tokens, done = [], False
    buf = b""
    while not done:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            assert event.startswith(b"data: ")
            payload = event[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            tokens.extend(json.loads(payload)["choices"][0]["token_ids"])
    conn.close()
    assert done
    assert tokens == dense_greedy(PROMPT[:7], 8)


def test_concurrent_clients_batched(server):
    prompts = [PROMPT, PROMPT[:5], PROMPT[:8], list(reversed(PROMPT))]
    want = [dense_greedy(p, 5) for p in prompts]
    got = [None] * len(prompts)
    errs = []

    def worker(i):
        try:
            status, body = _post(server.port, {
                "prompt": prompts[i], "max_tokens": 5, "temperature": 0,
            })
            assert status == 200, body
            got[i] = body["choices"][0]["token_ids"]
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errs, errs
    assert got == want


def test_eos_and_sampling_params(server):
    # learn what greedy emits, then set it as the stop token: generation
    # must stop there (finish included)
    ref = dense_greedy(PROMPT, 6)
    eos = ref[2]
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 6, "temperature": 0,
        "stop_token_ids": [eos],
    })
    assert status == 200
    toks = body["choices"][0]["token_ids"]
    # generation stops at the FIRST occurrence of the stop id (vLLM
    # stop_token_ids semantics) — the greedy reference may emit the
    # chosen token earlier than the index it was picked from (it does on
    # this model/seed: ref[1] == ref[2]), so cut at ref.index, not at 2
    cut = ref.index(eos)
    assert toks == ref[:cut + 1] and toks[-1] == eos
    assert body["choices"][0]["finish_reason"] == "stop"

    # sampling path with nucleus: valid tokens, right count
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 4, "temperature": 0.9,
        "top_p": 0.8, "top_k": 16,
    })
    assert status == 200
    toks = body["choices"][0]["token_ids"]
    assert len(toks) == 4 and all(0 <= t < CFG.vocab_size for t in toks)


def test_bad_requests_rejected(server):
    status, body = _post(server.port, {"prompt": "text not ids"})
    assert status == 400 and "token ids" in body["error"]
    status, body = _post(server.port, {"prompt": []})
    assert status == 400
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
    conn.request("POST", "/v1/completions", b"{not json", {})
    resp = conn.getresponse()
    assert resp.status == 400
    resp.read()
    conn.close()


def test_models_and_metrics(server):
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
    conn.request("GET", "/v1/models")
    resp = conn.getresponse()
    body = json.loads(resp.read())
    assert resp.status == 200
    assert body["data"][0]["id"] == "tiny-test"
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    text = resp.read().decode()
    conn.close()
    assert resp.status == 200
    assert "istpu_serve_requests_total" in text
    assert "istpu_serve_free_kv_pages" in text


def test_disconnect_mid_stream_frees_pages(server):
    """Dropping the SSE connection cancels the request at the next chunk
    boundary; its pages come back and the server keeps serving."""
    free_before = server.engine.free_pages
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": PROMPT, "max_tokens": 64, "temperature": 0,
        "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    resp.read1(16)  # first bytes arrived: request is live
    conn.close()    # hang up mid-generation

    # the server must still answer, and the orphan's pages must free once
    # the cancel lands
    status, body = _post(server.port, {
        "prompt": PROMPT[:5], "max_tokens": 4, "temperature": 0,
    })
    assert status == 200
    assert body["choices"][0]["token_ids"] == dense_greedy(PROMPT[:5], 4)
    deadline = 30
    import time
    while server.engine.free_pages < free_before and deadline > 0:
        time.sleep(0.5)
        deadline -= 0.5
    assert server.engine.free_pages == free_before


def test_param_validation_protects_batchmates(server):
    """Out-of-range sampling params and impossible budgets are 400s at the
    door — they must never reach an engine step (where they would take the
    whole batch down)."""
    for bad in (
        {"prompt": PROMPT, "top_p": 1.5},
        {"prompt": PROMPT, "top_p": 0},
        {"prompt": PROMPT, "temperature": -1},
        {"prompt": PROMPT, "sample": "nucleus"},
        {"prompt": PROMPT, "top_k": -2},
        {"prompt": PROMPT, "max_tokens": 0},
        {"prompt": PROMPT, "max_tokens": 10_000},  # > total KV pages
        {"prompt": [0, 999999]},  # out of vocab
        {"prompt": [True, False]},  # bools are not token ids
    ):
        status, body = _post(server.port, bad)
        assert status == 400, (bad, body)
    # the server still serves fine afterwards
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 3, "temperature": 0})
    assert status == 200
    assert body["choices"][0]["token_ids"] == dense_greedy(PROMPT, 3)


def test_greedy_requests_normalize_stray_params(server):
    """temperature=0 normalizes stray top_k/top_p at submit time, so an
    all-greedy batch compiles the minimal 'greedy' decode variant (no sort)
    regardless of what sampling params clients send alongside."""
    from infinistore_tpu.engine import Scheduler

    sched = server.sched
    assert isinstance(sched, Scheduler)
    a = sched.submit(PROMPT, 1, sample="greedy", top_p=0.9, top_k=7)
    b = sched.submit(PROMPT[:5], 1, sample="greedy", top_p=0.5)
    ra = next(r for r in sched.pending if r.req_id == a)
    rb = next(r for r in sched.pending if r.req_id == b)
    assert (ra.temperature, ra.top_k, ra.top_p) == (1.0, 0, 1.0)
    assert (rb.temperature, rb.top_k, rb.top_p) == (1.0, 0, 1.0)
    sched.pending.remove(ra)
    sched.pending.remove(rb)


class ByteTok:
    """Tiny offline tokenizer for tests: one token per character, id =
    codepoint (fits TINY's 512 vocab); decode is the inverse.  Provides the
    HF incremental-detokenization surface (convert_ids_to_tokens /
    convert_tokens_to_string) serve.py's streaming path uses."""

    def encode(self, s):
        return [min(ord(c), 511) for c in s]

    def decode(self, ids):
        return "".join(chr(t % 512) for t in ids)

    def convert_ids_to_tokens(self, ids):
        return [chr(t % 512) for t in ids]

    def convert_tokens_to_string(self, toks):
        return "".join(toks)


class PlainTok(ByteTok):
    """ByteTok without the incremental API: exercises _TextAccum's full
    re-decode fallback."""

    convert_ids_to_tokens = None
    convert_tokens_to_string = None


@pytest.mark.parametrize("tok_cls", [ByteTok, PlainTok])
def test_text_accum_stop_truncates_ids_and_text(tok_cls):
    """_TextAccum: ids, text, and deltas agree under stop strings on both
    the incremental and the full-redecode detok paths."""
    from infinistore_tpu.serve import _TextAccum

    tok = tok_cls()
    acc = _TextAccum(tok, ["xy"])
    ids = tok.encode("abc")
    d1, s1 = acc.add(ids)
    assert not s1
    assert d1 == "ab"  # "c" held back: could open an "xy"? hold = 1 char
    d2, s2 = acc.add(tok.encode("dxyz"))
    assert s2
    assert d2 == "cd"  # released up to the stop match
    assert acc.text == "abcd"
    assert acc.visible_ids() == tok.encode("abcd")


@pytest.mark.parametrize("tok_cls", [ByteTok, PlainTok])
def test_text_accum_stop_at_char_zero(tok_cls):
    """The model echoes the stop string immediately: empty visible text
    must pair with ZERO visible ids on both detok paths."""
    from infinistore_tpu.serve import _TextAccum

    tok = tok_cls()
    acc = _TextAccum(tok, ["ab"])
    delta, stopped = acc.add(tok.encode("abxyz"))
    assert stopped and delta == ""
    assert acc.text == ""
    assert acc.visible_ids() == []


def test_truncate_logits_topk_topp_compose_sequentially():
    """top-p must act on the top-k-RENORMALIZED distribution (HF/vLLM
    sequential convention): probs [0.4, 0.35, 0.25] with top_k=2,
    top_p=0.5 renormalizes to [0.533, 0.467] and keeps ONLY the argmax
    (the second token's exclusive cumsum 0.533 >= 0.5); nucleus over the
    raw distribution would wrongly keep both."""
    from infinistore_tpu.engine.engine import _truncate_logits

    l = jnp.asarray(np.log([[0.4, 0.35, 0.25]]), dtype=jnp.float32)
    out = np.asarray(
        _truncate_logits(
            l, jnp.asarray([2], jnp.int32), jnp.asarray([0.5], jnp.float32)
        )
    )
    assert np.isfinite(out[0, 0])
    assert not np.isfinite(out[0, 1]) and not np.isfinite(out[0, 2]), out


@pytest.mark.parametrize("tok_cls", [ByteTok, PlainTok])
def test_text_accum_no_stop_flush(tok_cls):
    from infinistore_tpu.serve import _TextAccum

    tok = tok_cls()
    acc = _TextAccum(tok, ["STOP"])
    deltas = [acc.add(tok.encode(part))[0] for part in ("hel", "lo wor", "ld")]
    tail = acc.finish()
    assert "".join(deltas) + tail == "hello world"
    assert acc.visible_ids() == tok.encode("hello world")


@pytest.fixture(scope="module")
def text_server():
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=64, block_tokens=4,
            dtype=CFG.dtype,
        ),
    )
    eng.decode_chunk = 4
    # the isolation rule of ``server`` above, for this module's second
    # server: its tests are of the text contract, and on a loaded host the
    # admission controller shed one of them (429 ``reason: queue``)
    srv = ServingServer(eng, port=0, max_batch=4, model_id="tiny-text",
                        tokenizer=ByteTok(), **WALK_SLO)
    srv.start()
    yield srv
    srv.close()


def test_text_prompt_round_trip(text_server):
    """String in, text out: the server tokenizes the prompt, decodes
    greedily, and returns detokenized text alongside the ids."""
    tok = text_server.tokenizer
    prompt = tok.decode(PROMPT)
    want = dense_greedy(tok.encode(prompt), 6)
    status, body = _post(text_server.port, {
        "prompt": prompt, "max_tokens": 6, "temperature": 0,
    })
    assert status == 200, body
    choice = body["choices"][0]
    assert choice["token_ids"] == want
    assert choice["text"] == tok.decode(want)


def test_full_stop_token_ids_list_honored(text_server):
    """EVERY stop id counts — the FIRST occurrence of ANY of them ends
    generation (r2 weak #6: only stops[0] was honored)."""
    full = dense_greedy(PROMPT, 8)
    # stops listed in an order where the LATER-listed id appears FIRST
    status, body = _post(text_server.port, {
        "prompt": PROMPT, "max_tokens": 8, "temperature": 0,
        "stop_token_ids": [full[5], full[2]],
    })
    assert status == 200, body
    cut = min(full.index(full[5]), full.index(full[2]))
    assert body["choices"][0]["token_ids"] == full[: cut + 1]


def test_stop_string_truncates_before_match(text_server):
    """vLLM stop-string semantics: generation ends at the first stop-string
    match and the text is truncated BEFORE it (the request is cancelled
    early, not decoded to budget)."""
    tok = text_server.tokenizer
    full = dense_greedy(PROMPT, 8)
    stop_char = tok.decode([full[3]])
    first = tok.decode(full).index(stop_char)
    status, body = _post(text_server.port, {
        "prompt": PROMPT, "max_tokens": 8, "temperature": 0,
        "stop": stop_char,
    })
    assert status == 200, body
    choice = body["choices"][0]
    assert choice["text"] == tok.decode(full)[:first]
    # token_ids and usage agree with the truncated text (not the raw chunk)
    assert choice["token_ids"] == full[:first]
    assert body["usage"]["completion_tokens"] == first


def test_streaming_text_deltas(text_server):
    """SSE chunks carry text deltas whose concatenation equals the full
    detokenized completion."""
    tok = text_server.tokenizer
    want = dense_greedy(PROMPT[:7], 8)
    conn = http.client.HTTPConnection("127.0.0.1", text_server.port,
                                      timeout=120)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": PROMPT[:7], "max_tokens": 8, "temperature": 0,
        "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    text, done = "", False
    buf = b""
    while not done:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            payload = event[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            text += json.loads(payload)["choices"][0].get("text", "")
    conn.close()
    assert done
    assert text == tok.decode(want)


def test_streaming_ids_respect_stop_horizon(text_server):
    """Streamed token_ids ride the text release horizon: when a stop
    string completes mid-stream, the concatenation of every chunk's
    token_ids equals the non-streaming response's stop-truncated ids —
    the client is never left holding ids past the stop cut."""
    tok = text_server.tokenizer
    full = dense_greedy(PROMPT, 8)
    stop_char = tok.decode([full[3]])
    req = {"prompt": PROMPT, "max_tokens": 8, "temperature": 0,
           "stop": stop_char}
    status, body = _post(text_server.port, req)
    assert status == 200, body
    want_ids = body["choices"][0]["token_ids"]
    want_text = body["choices"][0]["text"]

    conn = http.client.HTTPConnection("127.0.0.1", text_server.port,
                                      timeout=120)
    conn.request("POST", "/v1/completions",
                 json.dumps({**req, "stream": True}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    ids, text, done = [], "", False
    buf = b""
    while not done:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            payload = event[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            choice = json.loads(payload)["choices"][0]
            ids.extend(choice["token_ids"])
            text += choice.get("text", "") or ""
    conn.close()
    assert done
    assert text == want_text
    assert ids == want_ids


def test_echo_contract(text_server):
    """OpenAI legacy echo: completions prepend the prompt to the choice
    (text + ids); usage still counts prompt and completion separately;
    streaming sends the prompt as the first chunk; chat rejects it."""
    tok = text_server.tokenizer
    want = dense_greedy(PROMPT, 4)
    status, body = _post(text_server.port, {
        "prompt": PROMPT, "max_tokens": 4, "temperature": 0, "echo": True,
    })
    assert status == 200, body
    choice = body["choices"][0]
    assert choice["token_ids"] == PROMPT + want
    assert choice["text"] == tok.decode(PROMPT) + tok.decode(want)
    assert body["usage"] == {
        "prompt_tokens": len(PROMPT), "completion_tokens": 4,
        "total_tokens": len(PROMPT) + 4,
    }
    # string prompt: the echoed text is the VERBATIM client string (not
    # decode(encode(s)), which can grow special tokens)
    s = tok.decode(PROMPT)
    status, body = _post(text_server.port, {
        "prompt": s, "max_tokens": 4, "temperature": 0, "echo": True,
    })
    assert status == 200, body
    assert body["choices"][0]["text"].startswith(s)
    # streaming: prompt rides the first chunk
    conn = http.client.HTTPConnection("127.0.0.1", text_server.port,
                                      timeout=120)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": PROMPT, "max_tokens": 4, "temperature": 0, "echo": True,
        "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    ids, done, first = [], False, None
    buf = b""
    while not done:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            payload = event[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            c = json.loads(payload)["choices"][0]
            if first is None:
                first = list(c["token_ids"])
            ids.extend(c["token_ids"])
    conn.close()
    assert done and first == PROMPT
    assert ids == PROMPT + want
    # chat has no echo
    status, _ = _post(text_server.port, {
        "messages": [{"role": "user", "content": "hi"}], "max_tokens": 2,
        "echo": True,
    }, path="/v1/chat/completions")
    assert status == 400

    # pure echo (max_tokens 0, no logprobs): the zero-work shortcut — the
    # response is just the echoed prompt, no KV pages are touched, and the
    # requests/completed counters stay balanced (no engine round-trip)
    free_before = text_server.engine.free_pages
    req_before = text_server.stats["requests"]
    done_before = text_server.stats["completed"]
    status, body = _post(text_server.port, {
        "prompt": PROMPT, "max_tokens": 0, "temperature": 0, "echo": True,
    })
    assert status == 200, body
    assert body["choices"][0]["token_ids"] == PROMPT
    assert body["usage"]["completion_tokens"] == 0
    assert text_server.engine.free_pages == free_before
    assert text_server.stats["requests"] == req_before + 1
    assert text_server.stats["completed"] == done_before + 1


def test_echo_logprobs_scoring_contract(text_server):
    """The OpenAI scoring idiom (echo + logprobs + max_tokens 0): the
    response carries the PROMPT's own logprobs — null for position 0,
    then the model's logprob of each actual next token — matching the
    engine's scoring helper exactly, with nothing generated."""
    eng = text_server.engine
    want = eng.prompt_logprobs(PROMPT, k=2)
    status, body = _post(text_server.port, {
        "prompt": PROMPT, "max_tokens": 0, "temperature": 0,
        "echo": True, "logprobs": 2,
    })
    assert status == 200, body
    choice = body["choices"][0]
    assert choice["token_ids"] == PROMPT  # echo only; nothing generated
    assert body["usage"]["completion_tokens"] == 0
    lp = choice["logprobs"]
    assert len(lp["token_logprobs"]) == len(PROMPT)
    assert lp["token_logprobs"][0] is None and lp["top_logprobs"][0] is None
    for got, (chosen, top) in zip(lp["token_logprobs"][1:], want):
        assert got == pytest.approx(chosen, abs=1e-5)
    for got_top, (_, top) in zip(lp["top_logprobs"][1:], want):
        assert len(got_top) == 2

    # echo + logprobs WITH generation: prompt part + completion part
    status, body = _post(text_server.port, {
        "prompt": PROMPT, "max_tokens": 3, "temperature": 0,
        "echo": True, "logprobs": 1,
    })
    assert status == 200, body
    lp = body["choices"][0]["logprobs"]
    assert len(lp["token_logprobs"]) == len(PROMPT) + 3
    assert lp["token_logprobs"][0] is None
    assert all(x is not None for x in lp["token_logprobs"][1:])

    # streaming: the echo chunk carries the prompt logprobs
    conn = http.client.HTTPConnection("127.0.0.1", text_server.port,
                                      timeout=120)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": PROMPT, "max_tokens": 2, "temperature": 0,
        "echo": True, "logprobs": 1, "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    first_lp, done = None, False
    buf = b""
    while not done:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            payload = event[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            c = json.loads(payload)["choices"][0]
            if first_lp is None and c.get("logprobs"):
                first_lp = c["logprobs"]
    conn.close()
    assert done and first_lp is not None
    assert len(first_lp["token_logprobs"]) == len(PROMPT)
    assert first_lp["token_logprobs"][0] is None

    # max_tokens 0 without echo is still invalid
    status, _ = _post(text_server.port, {"prompt": PROMPT, "max_tokens": 0})
    assert status == 400


def test_chat_completions(text_server):
    """OpenAI chat surface: messages are templated into a prompt (fallback
    role-tagged transcript for tokenizers without a chat template) and the
    answer comes back as an assistant message."""
    tok = text_server.tokenizer
    messages = [{"role": "user", "content": "hi"}]
    prompt_ids = tok.encode("user: hi\nassistant:")
    want = dense_greedy(prompt_ids, 5)
    status, body = _post(text_server.port, {
        "messages": messages, "max_tokens": 5, "temperature": 0,
    }, path="/v1/chat/completions")
    assert status == 200, body
    assert body["object"] == "chat.completion"
    choice = body["choices"][0]
    assert choice["message"]["role"] == "assistant"
    assert choice["message"]["content"] == tok.decode(want)
    assert choice["token_ids"] == want


def test_chat_completions_streaming(text_server):
    tok = text_server.tokenizer
    messages = [{"role": "user", "content": "yo"}]
    want = dense_greedy(tok.encode("user: yo\nassistant:"), 6)
    conn = http.client.HTTPConnection("127.0.0.1", text_server.port,
                                      timeout=120)
    conn.request("POST", "/v1/chat/completions", json.dumps({
        "messages": messages, "max_tokens": 6, "temperature": 0,
        "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    content, roles, done = "", [], False
    buf = b""
    while not done:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            payload = event[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            d = json.loads(payload)
            assert d["object"] == "chat.completion.chunk"
            delta = d["choices"][0]["delta"]
            content += delta.get("content", "")
            if "role" in delta:
                roles.append(delta["role"])
    conn.close()
    assert done
    assert content == tok.decode(want)
    assert roles == ["assistant"]  # role announced exactly once


def test_chat_requires_tokenizer(server):
    status, body = _post(server.port, {
        "messages": [{"role": "user", "content": "x"}], "max_tokens": 2,
    }, path="/v1/chat/completions")
    assert status == 400 and "tokenizer" in body["error"]


def test_stop_string_requires_tokenizer(server):
    status, body = _post(server.port, {
        "prompt": PROMPT, "max_tokens": 2, "stop": ["x"],
    })
    assert status == 400 and "tokenizer" in body["error"]


def test_top_p_values_share_one_compiled_program():
    """top_p is a traced per-row vector: distinct values must NOT grow the
    decode jit cache (a recompile per client value would be a DoS vector)."""
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=64, block_tokens=4,
            dtype=CFG.dtype,
        ),
    )
    for i, p in enumerate((0.9, 0.91, 0.905, 0.5)):
        st = eng.prefill(PROMPT[: 5 + i])
        eng.decode(st, 2, sample="categorical", top_p=p,
                   rng=jax.random.PRNGKey(i))
        eng.release(st)
    keys = set(eng._decode_many_cache)
    assert keys == {(2, "filter", False, 0, False, False)}, keys


def test_serving_with_store_attached_prefix_reuse():
    """The serving front door composes with the store tier: an engine
    built with a connection (relaxed durability, the serve.py default)
    answers completions correctly, and after the durability barrier a
    SECOND engine on the same store reuses the prompt's prefix pages
    (cross-restart / cross-host prefix cache, the reference's headline
    use case)."""
    import os
    import signal
    import socket
    import subprocess
    import sys

    import infinistore_tpu as ist

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    port, mport = free_port(), free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        deadline = time.time() + 15
        while time.time() < deadline:
            try:
                socket.create_connection(
                    ("127.0.0.1", port), timeout=0.5).close()
                break
            except OSError:
                time.sleep(0.1)

        def mk_conn():
            c = ist.InfinityConnection(ist.ClientConfig(
                host_addr="127.0.0.1", service_port=port,
                connection_type=ist.TYPE_SHM))
            c.connect()
            return c

        def mk_engine(c):
            return InferenceEngine(
                PARAMS, CFG,
                PagedCacheConfig(
                    n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
                    head_dim=CFG.head_dim, n_blocks=64, block_tokens=4,
                    dtype=CFG.dtype,
                ),
                conn=c, model_id="serve-store", prefill_chunk=4,
                store_durability="relaxed",
            )

        c1 = mk_conn()
        eng = mk_engine(c1)
        srv = ServingServer(eng, port=0, max_batch=2,
                            model_id="serve-store")
        srv.start()
        try:
            status, body = _post(srv.port, {
                "prompt": PROMPT, "max_tokens": 6, "temperature": 0,
            })
            assert status == 200, body
            assert body["choices"][0]["token_ids"] == dense_greedy(PROMPT, 6)
            eng.store_flush()  # durability barrier before the "new host"
        finally:
            srv.close()
        c1.close()

        c2 = mk_conn()
        eng2 = mk_engine(c2)
        st = eng2.prefill(PROMPT)
        assert st.reused_chunks == len(PROMPT) // 4  # store-resident prefix
        eng2.release(st)
        c2.close()
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_metrics_ttft_split(server):
    """/metrics separates queue-wait from prefill/compute time so high
    TTFT is attributable (VERDICT r4 weak #3).  After completions have
    run, both gauges exist and carry sane values."""
    _post(server.port, {"prompt": PROMPT, "max_tokens": 4,
                        "temperature": 0})
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    assert "istpu_serve_queue_wait_p50_ms" in text
    assert "istpu_serve_prefill_p50_ms" in text
    vals = {
        line.split()[0]: float(line.split()[1])
        for line in text.splitlines()
        if line and not line.startswith("#")
    }
    assert vals["istpu_serve_prefill_p50_ms"] > 0.0
    assert vals["istpu_serve_queue_wait_p50_ms"] >= 0.0
    lm = server.sched.latency_metrics
    assert lm["window"] >= 1


def test_ngram_spec_http_matches_greedy():
    """--ngram-spec over HTTP: draft-model-free speculation returns
    exactly the plain greedy output; /metrics labels the mode and the
    counters advance.  A sampled request on the same server falls back
    to lockstep decode (still correct)."""
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=64, block_tokens=4,
            dtype=CFG.dtype,
        ),
    )
    srv = ServingServer(eng, port=0, max_batch=2, model_id="ngram-test",
                        ngram_spec=True, spec_k=4, spec_g=2)
    srv.start()
    try:
        status, body = _post(srv.port, {
            "prompt": PROMPT, "max_tokens": 10, "temperature": 0,
        })
        assert status == 200, body
        assert body["choices"][0]["token_ids"] == dense_greedy(PROMPT, 10)

        status, body = _post(srv.port, {
            "prompt": PROMPT, "max_tokens": 6, "temperature": 1.2,
        })
        assert status == 200, body  # sampled: lockstep fallback, no crash

        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        assert 'istpu_spec_kind{kind="ngram"} 1' in text
        rounds = [line for line in text.splitlines()
                  if line.startswith("istpu_spec_rounds_total")]
        assert rounds and float(rounds[0].split()[1]) >= 1
    finally:
        srv.close()


@pytest.mark.slow
def test_chip_smoke_dry_run():
    """chip_smoke.py is what proves the system on the chip; off the chip,
    its --dry-run walks the same sequence (store + two serving processes
    + HTTP clients) with the tiny preset, and without the flag it must
    refuse to serve from the CPU."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the smoke's servers see one device
    smoke = [sys.executable, os.path.join(repo, "chip_smoke.py")]
    r = subprocess.run(smoke, cwd=repo, env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0 and '"ok"' not in r.stdout, r.stdout
    r = subprocess.run(smoke + ["--dry-run"], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-1000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["platform"] == "cpu"
