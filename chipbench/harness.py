"""One run of one cell: set-up, the measured window, the check.

``run_cell`` builds everything a cell's files name: the configuration
(``configs/``), the weights from the seed (``weights.py``), the program's
``AsyncPagedMLAEngine`` behind its own HTTP/SSE ``Frontend``, and the
plan from the traffic file (``traffic.py``).  Set-up prefills the plan's
shared documents and warms every program shape the cell uses; the window
is driven by the load client (``client.py``) in a process of its own.
When the window has closed and the client has finished, the peak device
memory is read, the engine freed, and the served tokens checked against
the plain reference (``check.py``).  With ``trace`` a profiler trace of
part of the window is reduced to per-layer metrics (``trace.py``,
``metrics/``).

Only the program's serving entry is used: ``repro.launch.server`` and
``repro.runtime.engine``.  The harness wraps the engine's step and its
jitted step functions only to time-stamp them (``Dispatches``); the
calls go through unchanged.
"""
from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from . import check, model, traffic, weights
from . import plan as planlib

HERE = os.path.dirname(os.path.abspath(__file__))
GRACE_S = 60.0          # how long the client waits past the close
TRACE_S = 4.0           # length of the traced part of the window


def load_cell(name: str) -> dict:
    with open(os.path.join(HERE, "workloads", f"{name}.json")) as f:
        cell = json.load(f)
    cell["name"] = name
    return cell


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads by JAX's own
    monitoring events, so compiles inside the window show."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.compiles = 0
        self.loads = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += secs
        elif event == self.LOAD:
            self.loads += 1


class Dispatches:
    """Profiler spans around each engine tick and each device call of the
    engine, the calls' spans carrying the rows they served ("start:n;..."),
    so that the trace can attribute step executions and say what the host
    was doing in a device gap.  The first call after ``on`` is set waits
    for the device to drain, so that every step execution traced after it
    belongs to a call with a span."""

    def __init__(self, engine):
        import jax
        self.jax = jax
        self.engine = engine
        self.on = False
        self._synced = False
        step = engine.step

        def tick():
            with jax.profiler.TraceAnnotation("chipbench.engine_step"):
                step()
        engine.step = tick
        chunk_step = engine._chunk_step
        sample_step = engine._sample_step
        engine._chunk_step = lambda c: self._wrap("prefill", chunk_step(c))
        engine._sample_step = lambda s: self._wrap("decode", sample_step(s))

    def _wrap(self, kind, fn):
        sched = self.engine.sched
        span = f"chipbench.{kind}_call"

        def call(*args):
            if not self.on:
                return fn(*args)
            if not self._synced:
                self.jax.block_until_ready(self.engine.pool)
                self._synced = True
            if kind == "decode":
                rows = [(int(sched.lengths[s]), 1)
                        for s in sched.active_slots]
            else:
                lens, nv = np.asarray(args[4]), np.asarray(args[5])
                rows = [(int(lens[i]), int(nv[i]))
                        for i in np.nonzero(nv)[0]]
            rows = ";".join(f"{a}:{b}" for a, b in rows)
            with self.jax.profiler.TraceAnnotation(span, rows=rows):
                return fn(*args)
        return call


def geometry(spec, cell, plan) -> tuple:
    """(pool blocks, block-table width) of a cell's engine: the table
    spans the plan's longest request; the pool holds the documents and
    two full batches of the longest request without its document."""
    from repro.runtime.scheduler import blocks_for
    bs, mb = spec["settings"]["block_size"], cell["engine"]["max_batch"]
    nb = blocks_for(planlib.longest(plan) + 1, bs)
    docs = sum(blocks_for(len(d), bs) + 1 for d in plan["documents"])
    own = planlib.longest(plan, documents=False)
    return 1 + docs + 2 * mb * (blocks_for(own + 1, bs) + 1), nb


def _engine(cfg, params, spec, cell, plan, platform):
    import jax.numpy as jnp
    from repro.runtime.engine import AsyncPagedMLAEngine
    s = spec["settings"]
    num_blocks, nb = geometry(spec, cell, plan)
    return AsyncPagedMLAEngine(
        cfg, params, num_blocks=num_blocks, block_size=s["block_size"],
        max_batch=cell["engine"]["max_batch"], max_blocks_per_req=nb,
        compute_dtype=jnp.bfloat16, impl=s["impl"], scheme=s["scheme"],
        platform=platform, prefill_chunk=cell["engine"]["prefill_chunk"],
        cache_dtype=s["cache_dtype"])


def _drain(engine):
    while not engine.idle:
        engine.step()


def _warm(engine, plan, spec, cell, seed, log=print):
    """Prefill the plan's documents (they stay in the prefix cache), then
    warm every shape the window uses: one full batch of requests shaped
    like the traffic through the engine, each decode scheme ``auto`` can
    pick over the cell's (batch, context) range, and the block-copy
    programs at every batch of copies a tick can carry."""
    import jax
    import jax.numpy as jnp
    from repro.core.schemes import auto_dispatch
    from repro.runtime import Request, SamplingParams
    rid = iter(range(10 ** 9, 2 * 10 ** 9))

    def submit(prompt, n):
        engine.submit(Request(rid=next(rid), prompt=np.asarray(prompt),
                              sampling=SamplingParams(max_tokens=n)))

    t = time.perf_counter()
    for d in plan["documents"]:
        submit(d, 1)
    _drain(engine)
    log(f"set-up: documents prefilled in {time.perf_counter() - t:.3f} s")
    rng = np.random.default_rng([seed, 2])
    mb = engine.sched.max_batch
    vocab = spec["vocab_size"]
    reqs = plan["requests"]
    for i in range(mb):
        r = reqs[i % len(reqs)]
        own = rng.integers(0, vocab, len(r["prompt"]))
        doc = plan["documents"][r["doc"]] if r["doc"] >= 0 else []
        submit(np.concatenate([np.asarray(doc, np.int64), own]), 4)
    _drain(engine)
    lo, hi = planlib.shortest_prompt(plan), planlib.longest(plan)
    schemes = set()
    if engine.scheme == "auto":
        for b in range(1, mb + 1):
            for L in np.linspace(lo, hi, 33).astype(int):
                schemes.add(auto_dispatch(
                    engine.mla, engine.platform, cache_len=int(L), batch=b,
                    paged_block=engine.block_size,
                    cache_dtype=engine.cache_dtype))
    else:
        schemes.add(engine.scheme)
    B = mb
    zeros = np.zeros((B,), np.int32)
    for s in sorted(schemes):
        tok, engine.pool = engine._sample_step(s)(
            engine.params, jnp.asarray(zeros), engine.pool,
            jnp.asarray(np.zeros_like(engine.sched.block_table)),
            jnp.asarray(zeros), jnp.asarray(zeros.astype(np.uint32)),
            jnp.asarray(zeros.astype(np.uint32)))
        jax.block_until_ready(tok)
    # block copies go through the engine's own drain, as admission and
    # forks queue them: one pair alone, and batches padded to each power
    # of two up to a full batch of slots, between blocks no one holds
    free = list(engine.sched.allocator._free)
    n = 1
    while n <= 2 * mb and 2 * n <= len(free):
        engine.sched.cow_pending = [(free[2 * i], free[2 * i + 1])
                                    for i in range(n)]
        engine._drain_cow()
        n *= 2
    jax.block_until_ready(engine.pool)
    return sorted(schemes)


def _counters(engine) -> dict:
    s = engine.summary()
    return {"decode_tokens": s["decode_tokens"],
            "decode_steps": sum(s["schemes_used"].values()),
            "prefix_hit_tokens": s["prefix_hit_tokens"],
            "prompt_tokens": s["prompt_tokens"],
            "prefill_tokens": s["prefill_tokens"]}


def _client(plan, port, seconds, workdir):
    plan_path = os.path.join(workdir, "plan.json")
    out_path = os.path.join(workdir, "result.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "client.py"), plan_path,
         out_path, str(port), str(seconds), str(GRACE_S)],
        stdout=subprocess.PIPE, text=True, cwd=workdir)
    line = proc.stdout.readline()
    if not line.startswith("t0 "):
        proc.kill()
        proc.wait()
        raise RuntimeError(f"client did not start: {line!r}")
    return proc, float(line.split()[1]), out_path


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, counter: CompileCounter, platform,
             smoke: bool = False, fault=None, control: bool = False,
             log=print) -> dict:
    """Run one cell; returns the result record (see ``run.py``).

    ``smoke`` swaps the configuration for its tiny-width twin and the
    chip's peaks for none (CPU rehearsal); ``fault`` is a callable given
    the engine after warm-up, which a test uses to break the timed path.
    """
    import jax
    import jax.numpy as jnp
    from repro import models
    from repro.launch.server import Frontend
    from repro.models.common import ModelConfig
    from repro.nn import module as nnm

    cell = load_cell(name)
    spec = model.load(cell["config"])
    if smoke:
        spec = model.smoke_spec(spec)
        cell["engine"] = {"max_batch": 4, "prefill_chunk": 8}
    tfile = traffic.load(cell["traffic"])
    if smoke:
        tfile = traffic.smoke(tfile)
    plan = traffic.make(tfile, spec["vocab_size"], seed)
    cfg = model.model_config(spec, ModelConfig,
                             max_seq=planlib.longest(plan) + 1)
    params = weights.init(spec, seed)
    jax.block_until_ready(params)
    t_weights = time.perf_counter()
    want = jax.tree.map(lambda a: a.shape, jax.eval_shape(
        lambda: nnm.init_params(jax.random.key(0), models.model_defs(cfg))))
    have = jax.tree.map(lambda a: a.shape, params)
    if jax.tree.structure(want) != jax.tree.structure(have):
        raise RuntimeError("weight tree does not match the program's layout")
    engine = _engine(cfg, params, spec, cell, plan, platform)
    schemes = _warm(engine, plan, spec, cell, seed, log=log)
    if fault is not None:
        fault(engine)
    disp = Dispatches(engine) if trace else None
    fe = Frontend(engine, host="127.0.0.1", port=0).start()
    before = _counters(engine)
    c0 = (counter.compiles, counter.loads)
    with tempfile.TemporaryDirectory(prefix="chipbench-") as work:
        proc, t0, out_path = _client(plan, fe.port, seconds, work)
        try:
            setup_s = t0 - t_start
            log(f"set-up {setup_s:.3f} s ({counter.compiles} compiles, "
                f"{counter.compile_s:.1f} s, {counter.loads} cache loads; "
                f"weights ready at {t_weights - t_start:.3f} s); "
                f"decode schemes warmed: {schemes}")
            trace_dir = os.path.join(work, "trace")
            if trace:
                _trace(disp, t0, seconds, trace_dir)
            _sleep_until(t0 + seconds)
            after = _counters(engine)
            c1 = (counter.compiles, counter.loads)
            stdout, _ = proc.communicate(timeout=seconds + GRACE_S + 120)
        except BaseException:
            proc.kill()
            proc.wait()
            raise
        for line in stdout.splitlines():
            log(line)
        if proc.returncode != 0:
            raise RuntimeError(f"client exited {proc.returncode}")
        with open(out_path) as f:
            result = json.load(f)
        slow = sorted(((min(c["times"][0] for c in r["choices"] if c["times"])
                        - r["due"], r["due"] - t0)
                       for r in result["records"]
                       if any(c["times"] for c in r["choices"])),
                      reverse=True)[:8]
        log("slowest first tokens (s after due @ due s into the window): "
            + ", ".join(f"{a:.3f}@{b:.1f}" for a, b in slow))
        fe.stop()
        device = jax.devices()[0]
        stats = device.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        if disp is not None:
            disp.engine = None
        del fe, engine
        gc.collect()
        reduced = None
        if trace:
            from . import trace as tracelib
            events = tracelib.load(trace_dir)
            reduced = tracelib.reduce(events, spec, device.device_kind) \
                if events["ops"] else {}
    log(f"window compiles {c1[0] - c0[0]}, cache loads {c1[1] - c0[1]}")
    verdict = check.check(cell, spec, plan, result, params, seed, log=log,
                          control=control)
    return {"cell": cell, "spec": spec, "plan": plan, "result": result,
            "setup_s": setup_s, "seconds": seconds, "counters":
            {k: after[k] - before[k] for k in after},
            "window_compiles": c1[0] - c0[0], "peak_bytes": peak,
            "device": device, "trace": reduced, "check": verdict}


def _sleep_until(t):
    d = t - time.perf_counter()
    if d > 0:
        time.sleep(d)


def _trace(disp, t0, seconds, trace_dir):
    """Trace the middle of the window: TRACE_S seconds (or a third of a
    short window) centred on it."""
    import jax
    d = min(TRACE_S, seconds / 3)
    a = t0 + seconds / 2 - d / 2
    _sleep_until(a)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    disp.on = True
    with jax.profiler.TraceAnnotation("chipbench.window"):
        _sleep_until(a + d)
    disp.on = False
    jax.profiler.stop_trace()
