"""HTTP/SSE frontend (launch.server) over a live engine on an ephemeral
port: blocking + streaming generation, per-request stop sequences and
max_tokens, mid-decode cancellation, and the pool invariants cancellation
must preserve — every block refcount returns to the trie-held baseline
and the radix prefix cache stays unpoisoned (an identical-prefix request
after a cancel still produces the reference tokens).

The server threads drive the real engine (async by default here — the
PR-9 path); nothing is mocked.  Requests go through urllib against
127.0.0.1 only.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
import repro.models as models
from repro.launch.server import Frontend
from repro.nn import module as nnm
from repro.obs import Telemetry
from repro.runtime import AsyncPagedMLAEngine, PagedMLAEngine, Request


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke("deepseek-v2-236b")
    params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                             jnp.float32)
    return cfg, params


def _engine(cfg, params, engine_cls=AsyncPagedMLAEngine, **kw):
    kw.setdefault("enable_prefix_cache", True)
    return engine_cls(cfg, params, num_blocks=32, block_size=8, max_batch=2,
                      max_blocks_per_req=10, compute_dtype=jnp.float32,
                      scheme="seq", prefill_chunk=8, **kw)


@pytest.fixture()
def frontend(smoke_model):
    cfg, params = smoke_model
    fe = Frontend(_engine(cfg, params), port=0).start()
    yield fe
    fe.stop()


def _post(fe, path, payload, timeout=120):
    req = urllib.request.Request(
        f"http://{fe.host}:{fe.port}{path}",
        json.dumps(payload).encode(), {"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _get(fe, path):
    return json.load(urllib.request.urlopen(
        f"http://{fe.host}:{fe.port}{path}", timeout=30))


def _events(resp):
    """Parse an SSE body into [(event, payload), ...]."""
    out, ev = [], None
    for line in resp:
        line = line.decode().strip()
        if line.startswith("event: "):
            ev = line[len("event: "):]
        elif line.startswith("data: "):
            out.append((ev, json.loads(line[len("data: "):])))
    return out


def _reference(cfg, params, prompt, max_new):
    """Ground truth from a fresh synchronous engine, no HTTP anywhere."""
    eng = _engine(cfg, params, engine_cls=PagedMLAEngine)
    eng.run([Request(rid=0, prompt=np.asarray(prompt, np.int32),
                     max_new=max_new)])
    return [int(t) for t in eng.sched.finished[0].output]


PROMPT = [5, 9, 3, 7, 11, 2]


# ------------------------------------------------------------ generate ----


def test_generate_blocking_matches_reference(smoke_model, frontend):
    cfg, params = smoke_model
    r = json.load(_post(frontend, "/v1/generate",
                        {"prompt": PROMPT, "max_tokens": 6}))
    assert r["finish_reason"] == "length"
    assert r["output"] == _reference(cfg, params, PROMPT, 6)


def test_generate_stream_tokens_match_done(frontend):
    resp = _post(frontend, "/v1/generate",
                 {"prompt": PROMPT, "max_tokens": 6, "stream": True})
    evs = _events(resp)
    assert [e for e, _ in evs][:1] == ["start"]
    toks = [d["token"] for e, d in evs if e == "token"]
    (done,) = [d for e, d in evs if e == "done"]
    assert done["finish_reason"] == "length"
    assert toks == done["output"] and len(toks) == 6


def test_generate_concurrent_requests_isolated(smoke_model, frontend):
    cfg, params = smoke_model
    prompts = [PROMPT, [8, 1, 4, 4, 2, 9, 13], [3, 3, 3, 5]]
    results = [None] * len(prompts)

    def go(i):
        results[i] = json.load(_post(frontend, "/v1/generate",
                                     {"prompt": prompts[i], "max_tokens": 5}))

    ts = [threading.Thread(target=go, args=(i,)) for i in range(len(prompts))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    for i, p in enumerate(prompts):
        assert results[i]["output"] == _reference(cfg, params, p, 5)


def test_stop_sequence_over_http(smoke_model, frontend):
    cfg, params = smoke_model
    free = _reference(cfg, params, PROMPT, 8)
    stop = [free[2:4]]
    # the gram can occur before position 2 (a random model repeats tokens):
    # generation stops at its first occurrence
    first = next(i for i in range(len(free)) if free[i:i + 2] == stop[0])
    resp = _post(frontend, "/v1/generate",
                 {"prompt": PROMPT, "max_tokens": 8, "stop": stop,
                  "stream": True})
    evs = _events(resp)
    toks = [d["token"] for e, d in evs if e == "token"]
    (done,) = [d for e, d in evs if e == "done"]
    assert done["finish_reason"] == "stop"
    # the matched stop gram is hidden, and the streamed prefix never
    # leaked a token the truncation later removed (hold-back works)
    assert done["output"] == free[:first]
    assert toks == done["output"][:len(toks)]


def test_generate_rejects_empty_prompt(frontend):
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(frontend, "/v1/generate", {"prompt": [], "max_tokens": 4})
    assert e.value.code == 400


# -------------------------------------------- PR 10: request API wire ----


def test_generate_single_response_golden_bytes(smoke_model, frontend):
    """Without "n" in the body, the PR-9 single-completion wire format
    is preserved byte for byte (key set, key order, serialization)."""
    cfg, params = smoke_model
    raw = _post(frontend, "/v1/generate",
                {"prompt": PROMPT, "max_tokens": 4}).read()
    r = json.loads(raw)
    golden = json.dumps({"rid": r["rid"], "finish_reason": "length",
                         "output": _reference(cfg, params, PROMPT, 4)})
    assert raw == golden.encode()


def test_generate_n_blocking_choices(smoke_model, frontend):
    cfg, params = smoke_model
    r = json.load(_post(frontend, "/v1/generate",
                        {"prompt": PROMPT, "max_tokens": 5, "n": 2}))
    assert set(r) == {"rid", "choices"}
    assert [c["index"] for c in r["choices"]] == [0, 1]
    ref = _reference(cfg, params, PROMPT, 5)   # greedy engine: forks agree
    for c in r["choices"]:
        assert c["tokens"] == ref and c["finish_reason"] == "length"


def test_generate_explicit_n1_uses_choices_format(frontend):
    # "n" PRESENT — even n=1 — selects the choices[] format; only its
    # ABSENCE keeps the legacy body (the byte-compat contract above)
    r = json.load(_post(frontend, "/v1/generate",
                        {"prompt": PROMPT, "max_tokens": 3, "n": 1}))
    assert set(r) == {"rid", "choices"}
    assert len(r["choices"]) == 1 and r["choices"][0]["index"] == 0


def test_generate_stream_n_carries_choice_indices(frontend):
    resp = _post(frontend, "/v1/generate",
                 {"prompt": PROMPT, "max_tokens": 4, "n": 2,
                  "stream": True})
    evs = _events(resp)
    (start,) = [d for e, d in evs if e == "start"]
    assert start["n"] == 2
    toks = {}
    for e, d in evs:
        if e == "token":
            toks.setdefault(d["choice"], []).append(d["token"])
    (done,) = [d for e, d in evs if e == "done"]
    by_idx = {c["index"]: c for c in done["choices"]}
    assert set(toks) == {0, 1} == set(by_idx)
    for c in (0, 1):
        assert toks[c] == by_idx[c]["tokens"]
        assert by_idx[c]["finish_reason"] == "length"


@pytest.mark.parametrize("body", [
    {"prompt": [5, 9], "max_tokens": 4, "n": 0},            # n < 1
    {"prompt": [5, 9], "max_tokens": 4, "temperature": -1},  # negative
    {"prompt": [5, 9], "max_tokens": 0},                     # empty budget
    {"prompt": [5, 9], "max_tokens": 4, "temperature": 0.7},  # != engine
    {"max_tokens": 4},                                       # no prompt
], ids=["n0", "neg-temp", "max0", "temp-mismatch", "no-prompt"])
def test_generate_structured_error_bodies(frontend, body):
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(frontend, "/v1/generate", body)
    assert e.value.code == 400
    err = json.load(e.value)
    assert err["error"]["type"] == "invalid_request"
    assert err["error"]["message"]


# ------------------------------------------------------- cancellation ----


def _drain(fe, timeout=120):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        h = _get(fe, "/v1/health")
        if h["active"] == 0 and h["waiting"] == 0:
            return h
        time.sleep(0.05)
    raise TimeoutError("engine did not drain")


def _stream_until_rid_and_tokens(fe, payload, n_tokens=2):
    """Open a stream, return (rid, iterator) after n_tokens arrived."""
    resp = _post(fe, "/v1/generate", dict(payload, stream=True))
    rid, seen, ev = None, 0, None
    for line in resp:
        line = line.decode().strip()
        if line.startswith("event: "):
            ev = line[len("event: "):]
        elif line.startswith("data: "):
            d = json.loads(line[len("data: "):])
            if ev == "start":
                rid = d["rid"]
            elif ev == "token":
                seen += 1
                if seen >= n_tokens:
                    return rid, resp
    raise AssertionError("stream ended before tokens arrived")


def test_cancel_mid_decode_frees_blocks(smoke_model):
    cfg, params = smoke_model
    eng = _engine(cfg, params, enable_prefix_cache=False)
    fe = Frontend(eng, port=0).start()
    try:
        rid, resp = _stream_until_rid_and_tokens(
            fe, {"prompt": PROMPT, "max_tokens": 400})
        assert eng.sched.allocator.num_allocated > 0
        _post(fe, "/v1/cancel", {"rid": rid})
        evs = _events(resp)   # read to the done event
        (done,) = [d for e, d in evs if e == "done"]
        assert done["finish_reason"] == "cancelled"
        _drain(fe)
        # no prefix cache: cancellation must return EVERY block
        assert eng.sched.allocator.num_allocated == 0
        assert eng.sched.allocator.refcount == {}
        # the pool is reusable: a fresh request still serves correctly
        r = json.load(_post(fe, "/v1/generate",
                            {"prompt": PROMPT, "max_tokens": 5}))
        assert r["output"] == _reference(cfg, params, PROMPT, 5)
    finally:
        fe.stop()


def test_cancel_waiting_request(smoke_model):
    cfg, params = smoke_model
    eng = _engine(cfg, params, enable_prefix_cache=False)
    fe = Frontend(eng, port=0).start()
    try:
        # saturate both slots with long requests, then queue a third
        streams = [_stream_until_rid_and_tokens(
            fe, {"prompt": [i + 1, 7, 2, 9], "max_tokens": 300}, 1)
            for i in range(2)]
        st = fe.worker.submit(PROMPT, 300)
        _post(fe, "/v1/cancel", {"rid": st.rid})
        item = st.q.get(timeout=60)
        assert item[0] == "done" and item[1] == "cancelled"
        for rid, resp in streams:
            _post(fe, "/v1/cancel", {"rid": rid})
            resp.close()
        _drain(fe)
        assert eng.sched.allocator.num_allocated == 0
    finally:
        fe.stop()


def test_cancel_leaves_radix_cache_unpoisoned(smoke_model):
    cfg, params = smoke_model
    eng = _engine(cfg, params)   # prefix cache ON
    fe = Frontend(eng, port=0).start()
    try:
        rid, resp = _stream_until_rid_and_tokens(
            fe, {"prompt": PROMPT, "max_tokens": 400})
        _post(fe, "/v1/cancel", {"rid": rid})
        _events(resp)
        _drain(fe)
        # trie-held blocks may stay cached (refcount 0, LRU-evictable)
        # but nothing may hold a live reference
        assert all(rc == 0 for rc in eng.sched.allocator.refcount.values())
        # unpoisoned: an identical-prefix request hits the cache and
        # still produces the reference tokens
        r = json.load(_post(fe, "/v1/generate",
                            {"prompt": PROMPT, "max_tokens": 6}))
        assert r["output"] == _reference(cfg, params, PROMPT, 6)
    finally:
        fe.stop()


def test_cancel_one_fork_leaves_rest_of_group_running(smoke_model):
    """POST /v1/cancel with rid + c kills ONLY choice c: the sibling
    decodes to its natural finish, the done event reports per-choice
    finish reasons, and every block reference unwinds."""
    cfg, params = smoke_model
    eng = _engine(cfg, params)
    fe = Frontend(eng, port=0).start()
    try:
        rid, resp = _stream_until_rid_and_tokens(
            fe, {"prompt": PROMPT, "max_tokens": 40, "n": 2}, 2)
        _post(fe, "/v1/cancel", {"rid": rid + 1})   # choice 1 only
        evs = _events(resp)                          # drain to done
        (done,) = [d for e, d in evs if e == "done"]
        by = {c["index"]: c for c in done["choices"]}
        assert by[1]["finish_reason"] == "cancelled"
        assert by[0]["finish_reason"] == "length"
        assert len(by[0]["tokens"]) == 40           # sibling unharmed
        _drain(fe)
        assert all(rc == 0 for rc in eng.sched.allocator.refcount.values())
    finally:
        fe.stop()


# ------------------------------------------------------------ plumbing ----


def test_health_and_metrics_endpoints(smoke_model):
    cfg, params = smoke_model
    tel = Telemetry.on(trace=False, metrics=True)
    eng = _engine(cfg, params, telemetry=tel)
    fe = Frontend(eng, port=0).start()
    try:
        json.load(_post(fe, "/v1/generate",
                        {"prompt": PROMPT, "max_tokens": 4}))
        h = _get(fe, "/v1/health")
        assert h["ok"] and h["finished"] == 1 and h["steps"] > 0
        m = _get(fe, "/v1/metrics")
        # 4 output tokens = 1 prefill-sampled + 3 decoded
        assert m["summary"]["decode_tokens"] >= 3
        # live registry: the engine records step_ms / pool gauges per tick
        assert m["metrics"]["histograms"]["step_ms"]["count"] > 0
        assert _get(fe, "/v1/health")["ok"]
    finally:
        fe.stop()


# ----------------------------------------------------------------- spans ----


def test_sse_lag_recorded_per_token_with_queue_shapes_unchanged(frontend):
    """Each streamed token's write records its lag from the worker's put
    (``sse_write``, lag_ms), and the worker records its own spans; a
    direct queue consumer still sees the PR-9 item shapes."""
    from repro.obs import PID_FRONTEND, recorder
    t0 = time.perf_counter()
    evs = _events(_post(frontend, "/v1/generate",
                        {"prompt": PROMPT, "max_tokens": 6, "stream": True}))
    toks = [d["token"] for e, d in evs if e == "token"]
    w = recorder().window(t0, time.perf_counter())
    writes = [e for e in w if e.name == "sse_write" and e.pid == PID_FRONTEND]
    assert len(writes) == len(toks) == 6
    assert all(e.args["lag_ms"] >= 1e3 * (e.end - e.start) >= 0
               for e in writes)
    names = {e.name for e in w if e.pid == PID_FRONTEND}
    assert {"submit", "publish"} <= names
    st = frontend.worker.submit(PROMPT, 3)
    items = [st.q.get(timeout=60) for _ in range(4)]
    assert [it[0] for it in items] == ["token"] * 3 + ["done"]
    assert all(len(it) == 2 for it in items[:3]) and len(items[3]) == 3
    assert len(st.put_t[0]) == 3
