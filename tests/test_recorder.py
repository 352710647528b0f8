"""The process span recorder (repro.obs.trace): its bounded ring and
window reads, appends from many threads, the process events it records
(garbage collections, compiles), the engine's dispatch and prefill spans
with their rows, and the mirroring of live spans into a JAX profiler
capture, where they land on the host plane on the device ops' clock."""
import gc
import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
import repro.models as models
from repro.nn import module as nnm
from repro.obs import (NULL_TRACER, PID_ENGINE, PID_PROCESS, PID_REQUESTS,
                       Telemetry, Tracer, recorder, validate_trace)
from repro.obs.trace import RING_EVENTS
from repro.runtime import AsyncPagedMLAEngine, Request, blocks_for
from repro.runtime.engine import TID_DEVICE


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke("deepseek-v2-236b")
    params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                             jnp.float32)
    return cfg, params


SPECS = [(12, 9, 0), (9, 7, 0), (17, 8, 1), (8, 10, 2)]


def _run(cfg, params, telemetry=None, engine=None, rid0=0):
    rng = np.random.default_rng(3)
    reqs = [Request(rid=rid0 + i,
                    prompt=rng.integers(0, cfg.vocab, (p,)).astype(np.int32),
                    max_new=g, arrival=a)
            for i, (p, g, a) in enumerate(SPECS)]
    per = max(blocks_for(r.plen + r.max_new + 1, 8) for r in reqs)
    eng = engine or AsyncPagedMLAEngine(
        cfg, params, num_blocks=24, block_size=8, max_batch=2,
        max_blocks_per_req=per, compute_dtype=jnp.float32, scheme="seq",
        prefill_chunk=8, telemetry=telemetry)
    eng.run(reqs)
    assert sum(r.rid >= rid0 for r in eng.sched.finished) == len(SPECS)
    return eng


# ------------------------------------------------------------------ ring --


def test_window_reads_the_interval_and_overflow_reads_none():
    tr = Tracer()
    t0 = time.perf_counter()
    for i in range(5):
        tr.complete("a", PID_ENGINE, 0, t0 + i, t0 + i + 0.5, args={"i": i})
    w = tr.window(t0 + 1.2, t0 + 3.2)
    assert [e.args["i"] for e in w] == [1, 2, 3]
    assert all(e.name == "a" and e.ph == "X" for e in w)
    # past the ring's size the oldest records go: a window that began
    # before the newest dropped record reads None, never a partial list
    for _ in range(RING_EVENTS):
        tr.instant("b")
    assert tr.window(t0, t0 + 10) is None
    t1 = time.perf_counter()
    tr.instant("c")
    assert [e.name for e in tr.window(t1, time.perf_counter())] == ["c"]


def test_appends_from_many_threads_are_all_kept():
    tr = Tracer()
    n, per = 8, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(tid):
            for _ in range(per):
                with tr.span("outer", PID_ENGINE, tid):
                    with tr.span("inner", PID_ENGINE, tid, args={"k": 1}):
                        pass
        ts = [threading.Thread(target=work, args=(t,)) for t in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    w = tr.window(0.0, time.perf_counter())
    assert len(w) == 2 * n * per
    assert validate_trace(tr.to_dict()) == []


def test_export_rebases_a_window_and_names_tracks():
    tr = Tracer()
    tr.set_process_name(PID_ENGINE, "engine")
    tr.instant("before")
    t0 = time.perf_counter()
    with tr.span("step"):
        tr.instant("inside")
    d = tr.to_dict(t0)
    names = [e["name"] for e in d["traceEvents"]]
    assert "before" not in names and {"step", "inside"} <= set(names)
    assert min(e["ts"] for e in d["traceEvents"] if "ts" in e) == 0.0
    assert {"name": "process_name", "ph": "M", "pid": PID_ENGINE, "tid": 0,
            "args": {"name": "engine"}} in d["traceEvents"]
    assert validate_trace(d) == []


def test_recorder_span_cost_is_bounded():
    """The always-on recorder's cost per span: a generous 20 us bound
    (about 1 us measured on the CPU) against decode steps of tens of
    milliseconds."""
    tr = Tracer()
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("step", args={"rows": 3}):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 20e-6, f"{per_span * 1e6:.2f} us per recorded span"


# --------------------------------------------------------------- process --


def test_forced_collection_records_a_gc_span():
    rec = recorder()
    t0 = time.perf_counter()
    gc.collect()
    gcs = [e for e in rec.window(t0, time.perf_counter())
           if e.name == "gc" and e.pid == PID_PROCESS]
    assert gcs and gcs[-1].args["gen"] == 2
    assert gcs[-1].end >= gcs[-1].start >= t0
    assert gcs[-1].args["collected"] >= 0


def test_compile_records_a_compile_span():
    rec = recorder()
    t0 = time.perf_counter()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    comp = [e for e in rec.window(t0, time.perf_counter())
            if e.name == "compile" and e.pid == PID_PROCESS]
    assert comp and comp[-1].end > comp[-1].start


# ---------------------------------------------------------------- engine --


def test_default_engine_records_and_off_records_nothing(smoke_model):
    cfg, params = smoke_model
    t0 = time.perf_counter()
    eng = _run(cfg, params)
    assert eng.tel is Telemetry.default() and eng.tel.tracer is recorder()
    t1 = time.perf_counter()
    names = {e.name for e in recorder().window(t0, t1)
             if e.pid == PID_ENGINE}
    assert {"step", "dispatch", "prefill_chunk", "host_sample"} <= names
    off = _run(cfg, params, telemetry=Telemetry.off())
    assert off.sched.tracer is NULL_TRACER
    assert not [e for e in recorder().window(t1, time.perf_counter())
                if e.pid in (PID_ENGINE, PID_REQUESTS)]


def test_dispatch_and_prefill_spans_carry_their_rows(smoke_model):
    cfg, params = smoke_model
    tel = Telemetry.on(trace=True, metrics=False)
    eng = _run(cfg, params, telemetry=tel)
    w = tel.tracer.window(tel.t_on, time.perf_counter())
    disp = [e for e in w if e.name == "dispatch"]
    dev = [e for e in w if e.name == "device_step" and e.tid == TID_DEVICE]
    assert len(disp) == len(dev) == sum(eng.stats.schemes_used.values())
    assert all(1 <= e.args["rows"] <= 2 for e in disp)
    # one decode token per dispatched row; each request's first token
    # is sampled from its prefill
    assert sum(e.args["rows"] for e in disp) == eng.stats.decode_tokens
    assert eng.stats.decode_tokens + len(SPECS) == \
        sum(len(r.output) for r in eng.sched.finished)
    chunks = [e for e in w if e.name == "prefill_chunk"]
    assert len(chunks) == eng.stats.prefill_chunks
    assert sum(e.args["tokens"] for e in chunks) == eng.stats.prefill_tokens
    assert all(1 <= e.args["rows"] <= 2 for e in chunks)
    prefill = [e for e in w if e.name == "prefill" and e.pid == PID_ENGINE]
    assert prefill and all(any(p.start <= c.start and c.end <= p.end
                               for p in prefill) for c in chunks)
    # lifecycle events, written when each request finished
    decode = [e for e in w if e.name == "decode" and e.pid == PID_REQUESTS]
    assert sorted(e.tid for e in decode) == list(range(len(SPECS)))


def test_profiler_capture_holds_the_dispatch_span_on_the_host_plane(
        smoke_model, tmp_path):
    """While a profiler session captures, the engine's ``dispatch`` span
    is on the host plane, and an annotation a harness opens around the
    step call inside it nests within it on the same line and clock."""
    from jax.profiler import ProfileData, TraceAnnotation
    cfg, params = smoke_model
    eng = _run(cfg, params)          # compiled and warm
    real = eng._sample_step

    def wrapped(scheme):
        fn = real(scheme)

        def call(*a):
            with TraceAnnotation("chipbench.decode_call", rows="0:1"):
                return fn(*a)
        return call
    eng._sample_step = wrapped
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run(cfg, params, engine=eng, rid0=len(SPECS))
    finally:
        jax.profiler.stop_trace()
    n_disp = sum(e.name == "dispatch"
                 for e in recorder().window(t0, time.perf_counter()))
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    nested = 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = list(line.events)
            disp = [e for e in evs if e.name.split("#")[0] == "dispatch"]
            for c in (e for e in evs
                      if e.name.split("#")[0] == "chipbench.decode_call"):
                assert any(d.start_ns <= c.start_ns and c.end_ns <= d.end_ns
                           for d in disp), "harness span outside dispatch"
                nested += 1
            for d in disp:
                stats = dict(d.stats)
                assert 1 <= int(stats["rows"]) <= 2
                assert all(len(r.split(":")) == 2
                           for r in str(stats["row_tokens"]).split(";"))
    assert nested == n_disp > 0
