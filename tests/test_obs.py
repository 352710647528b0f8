"""Serving telemetry subsystem (PR 7): tracer, metrics, logger.

Covers, bottom-up:
  * percentile / Histogram math against numpy's linear interpolation;
  * Tracer span nesting + `validate_trace` on good and broken traces,
    and the NullTracer contract (no events, export refuses);
  * disabled-mode overhead: a null span must cost well under the
    per-step budget that makes armed-off telemetry free;
  * engine end-to-end with telemetry armed: trace validates, every
    request-lifecycle phase and step phase has a span, metrics mirror
    `engine.summary()` exactly, TTFT/TPOT histograms cover the finished
    requests — and outputs are TOKEN-IDENTICAL to an untraced run;
  * the step wall-clock fix (satellite): the engine must block on device
    work inside the step timer — jax dispatch is async, so without the
    sync `wall` measures dispatch, not compute;
  * StructLogger text/JSON/level modes + the `as_logger` adapter;
  * prefix-cache eviction / copy-on-write instants and counters.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
import repro.models as models
from repro.hwmodel.platforms import PLATFORMS
from repro.nn import module as nnm
from repro.obs import (NULL_TRACER, OFF_TELEMETRY, PID_ENGINE, PID_REQUESTS,
                       Histogram, StructLogger, Telemetry,
                       Tracer, as_logger, percentile, validate_trace)
from repro.runtime import (BlockAllocator, PagedMLAEngine, PrefixCache,
                           Request)


# -------------------------------------------------------- percentile math --


def test_percentile_linear_interpolation():
    assert percentile([], 50) == 0.0
    assert percentile([7.0], 99) == 7.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert percentile([1.0, 2.0, 3.0, 4.0], 0) == 1.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 100) == 4.0
    rng = np.random.default_rng(0)
    vals = sorted(rng.normal(size=137).tolist())
    for p in (5, 25, 50, 75, 95, 99):
        assert percentile(vals, p) == pytest.approx(np.percentile(vals, p))


def test_histogram_summary_matches_numpy():
    h = Histogram()
    assert h.summary() == {"count": 0}
    rng = np.random.default_rng(1)
    vals = rng.exponential(size=200).tolist()
    for v in vals:
        h.record(v)
    s = h.summary()
    assert s["count"] == 200
    assert s["mean"] == pytest.approx(np.mean(vals))
    assert s["min"] == min(vals) and s["max"] == max(vals)
    for key, p in (("p50", 50), ("p95", 95), ("p99", 99)):
        assert s[key] == pytest.approx(np.percentile(vals, p))


# ------------------------------------------------------------------ tracer --


def test_tracer_spans_nest_and_validate():
    tr = Tracer()
    tr.set_process_name(PID_ENGINE, "engine")
    with tr.span("step"):
        with tr.span("schedule"):
            pass
        with tr.span("device_step"):
            pass
    tr.instant("evict", args={"n": 2})
    trace = tr.to_dict()
    assert validate_trace(trace) == []
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    # children close before the parent -> parent is appended LAST
    assert names == ["schedule", "device_step", "step"]
    step = [e for e in trace["traceEvents"] if e["name"] == "step"][0]
    kids = [e for e in trace["traceEvents"]
            if e["name"] in ("schedule", "device_step")]
    assert all(e["ts"] >= step["ts"] and
               e["ts"] + e["dur"] <= step["ts"] + step["dur"] + 1e-3
               for e in kids)


def test_validate_trace_rejects_malformed():
    assert validate_trace({}) != []
    assert validate_trace({"traceEvents": [{"ph": "X"}]}) != []
    overlapping = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 0, "ts": 0.0, "dur": 10.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 0, "ts": 5.0, "dur": 10.0},
    ]}
    assert any("overlaps" in p for p in validate_trace(overlapping))
    neg = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 0, "ts": 0.0, "dur": -1.0}]}
    assert validate_trace(neg) != []


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("step") as sp:
        pass
    assert sp.dur_s == 0.0
    NULL_TRACER.instant("x")
    NULL_TRACER.complete("y", 1, 0, 0.0, 1.0)
    assert NULL_TRACER.to_dict() == {"traceEvents": []}
    with pytest.raises(RuntimeError):
        NULL_TRACER.export("/tmp/never.json")


def test_null_span_overhead_is_negligible():
    """Armed-off telemetry must be free: a generous 20 us/hook bound
    (measured ~0.2 us) keeps the <2%-of-a-step acceptance criterion safe
    by orders of magnitude even on a loaded CI box."""
    import time
    n = 50_000
    span = OFF_TELEMETRY.tracer.span
    t0 = time.perf_counter()
    for _ in range(n):
        with span("step"):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, f"{per_call * 1e6:.2f} us per null span"


def test_telemetry_off_singleton():
    assert Telemetry.off() is OFF_TELEMETRY
    assert not OFF_TELEMETRY.enabled
    assert OFF_TELEMETRY.metrics is None


# ---------------------------------------------------- engine end-to-end ----


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke("deepseek-v2-236b")
    params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                             jnp.float32)
    return cfg, params


def _reqs(cfg, seed=3, n=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, (6,)).astype(np.int32),
                    max_new=4, arrival=i % 2) for i in range(n)]


def _run(cfg, params, reqs, telemetry=None):
    eng = PagedMLAEngine(cfg, params, num_blocks=24, block_size=4,
                         max_batch=2, compute_dtype=jnp.float32,
                         scheme="auto", platform=PLATFORMS["tpu_v5e"],
                         prefill_chunk=4, telemetry=telemetry)
    eng.run([Request(rid=r.rid, prompt=r.prompt.copy(), max_new=r.max_new,
                     arrival=r.arrival) for r in reqs])
    return eng


def test_engine_telemetry_end_to_end(smoke_model):
    cfg, params = smoke_model
    reqs = _reqs(cfg)
    tel = Telemetry.on()
    eng = _run(cfg, params, reqs, telemetry=tel)
    tel.finalize(eng)

    trace = tel.trace_dict()
    assert validate_trace(trace) == []

    def names(pid):
        return {e["name"] for e in trace["traceEvents"]
                if e.get("pid") == pid and e["ph"] in ("X", "i")}

    # every lifecycle phase and every (non-spec) step phase has a span
    assert {"arrival", "queued", "prefill", "decode",
            "finish"} <= names(PID_REQUESTS)
    assert {"step", "schedule", "prefill", "prefill_chunk", "device_step",
            "host_sample"} <= names(PID_ENGINE)

    # metrics mirror EngineStats EXACTLY (the registry subsumes it)
    summ = eng.summary()
    m = tel.metrics
    assert m.engine_summary == summ
    assert m.counter("engine.steps").value == summ["steps"]
    assert m.counter("engine.decode_tokens").value == summ["decode_tokens"]
    assert m.gauge("engine.tokens_per_s").value == \
        pytest.approx(summ["tokens_per_s"])
    n_fin = len(eng.sched.finished)
    assert m.histogram("ttft_ms").count == n_fin
    assert m.histogram("queue_delay_ms").count == n_fin
    assert m.histogram("tpot_ms").count == n_fin        # all max_new > 1
    assert m.histogram("step_ms").count == summ["steps"]
    for r in eng.sched.finished:
        assert 0 <= r.submit_t <= r.admit_t <= r.first_tok_t <= r.finish_t

    # finalize is idempotent: a second call must not duplicate spans
    n_events = len(trace["traceEvents"])
    tel.finalize(eng)
    assert len(tel.trace_dict()["traceEvents"]) == n_events

    # the registry round-trips through JSON (the --metrics artifact)
    d = json.loads(json.dumps(m.to_dict()))
    assert d["counters"]["engine.steps"] == summ["steps"]
    assert "ttft_ms" in d["histograms"]
    assert "engine.steps" in m.render_table()


def test_engine_outputs_token_identical_with_tracing(smoke_model):
    cfg, params = smoke_model
    reqs = _reqs(cfg, seed=7)
    plain = _run(cfg, params, reqs)
    traced = _run(cfg, params, reqs, telemetry=Telemetry.on())
    assert {r.rid: r.output for r in traced.sched.finished} == \
        {r.rid: r.output for r in plain.sched.finished}


def test_step_wall_clock_blocks_on_device(smoke_model, monkeypatch):
    """Satellite fix pin: `engine.step` used to stop the wall timer while
    async-dispatched device work was still in flight.  The engine must
    call `jax.block_until_ready` on the pool within every step."""
    cfg, params = smoke_model
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    eng = _run(cfg, params, _reqs(cfg, seed=9, n=2))
    assert eng.stats.steps > 0
    assert len(calls) >= eng.stats.steps   # >= one sync per step
    assert eng.stats.wall > 0


# ------------------------------------------------------------------ logger --


def test_struct_logger_text_json_levels():
    lines = []
    lg = StructLogger("eng", sink=lines.append, level="info")
    lg.debug("hidden", a=1)
    lg.info("admitted", step=12, rid=3, frac=0.123456)
    lg.warning("preempt", rid=3)
    assert lines == ["[eng] admitted step=12 rid=3 frac=0.1235",
                     "[eng] preempt rid=3"]

    jlines = []
    jl = StructLogger("eng", sink=jlines.append, json_mode=True)
    jl.bind(step=5).info("tick", ms=1.5)
    rec = json.loads(jlines[0])
    assert rec == {"logger": "eng", "level": "info", "msg": "tick",
                   "step": 5, "ms": 1.5}

    with pytest.raises(ValueError):
        StructLogger("x", level="verbose")
    assert StructLogger("x", level="off").silenced


def test_as_logger_adapts_legacy_callables():
    lg = StructLogger("a")
    assert as_logger(lg) is lg
    assert as_logger(None).silenced
    seen = []
    adapted = as_logger(seen.append, "loop")
    adapted.info("resumed", step=4)
    assert seen == ["[loop] resumed step=4"]
    assert not adapted.silenced


# ------------------------------------------------------------ prefix hooks --


def test_prefix_cache_evict_and_cow_instants():
    pc = PrefixCache(BlockAllocator(4), 4)
    tel = Telemetry.on(trace=True, metrics=True)
    pc.tel = tel
    blocks = pc.alloc(2)
    pc.insert(list(range(8)), blocks)
    pc.release(blocks)                    # refcount 0 -> LRU-evictable
    assert pc.evict(2) == 2
    pc.count_cow()
    names = [e["name"] for e in tel.trace_dict()["traceEvents"]]
    assert "prefix_evict" in names and "cow_copy" in names
    assert tel.metrics.counter("prefix_cache.evictions").value == 2
    assert tel.metrics.counter("prefix_cache.cow_copies").value == 1
