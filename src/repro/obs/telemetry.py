"""Telemetry facade wiring the span recorder, metrics registry and
structured logger into one object the serving engine takes.

    tel = Telemetry.on(trace=True, metrics=True)
    eng = PagedMLAEngine(..., telemetry=tel)
    eng.run(reqs)
    tel.finalize(eng)
    tel.export(trace_path="out.json", metrics_path="metrics.json")

An engine built without telemetry records into the process recorder
(:func:`obs.trace.recorder`, :meth:`Telemetry.default`), always on and
bounded; ``Telemetry.on(trace=True)`` records into the same recorder and
exports the part of it since the facade was made.  ``Telemetry.off()``
is the explicit zero-cost mode: one attribute check per instrumentation
site and one no-op call per span — the hot path never formats a string
or allocates a dict on behalf of telemetry that is off.

Cost placement: the per-STEP phase spans and step/phase histograms are
recorded live inside ``engine.step``; each request's lifecycle events
are written once, when the scheduler retires it, from the timestamps it
stamps onto the ``Request`` (submit/admit/first-token/finish, one
``perf_counter`` per transition); the per-request histograms are
derived in :meth:`Telemetry.finalize`.
"""
from __future__ import annotations

import threading
from time import perf_counter
from typing import Dict, Optional

from .logger import StructLogger
from .metrics import MetricsRegistry
from .trace import NULL_TRACER, Tracer, recorder

# EngineStats summary keys mirrored into the counters section (the
# registry "subsumes EngineStats" — parity is pinned in tests/test_obs.py)
_ENGINE_COUNTERS = (
    "steps", "decode_tokens", "prefill_tokens", "prompt_tokens",
    "prefill_chunks", "interleaved_chunks", "admissions",
    "mid_gen_admissions", "preemptions",
    "scheme_switches", "spec_rounds", "spec_drafted", "spec_accepted",
    "fork_groups", "fork_children",
)
_ENGINE_GAUGES = (
    "tokens_per_s", "cache_utilization", "pool_occupancy",
    "spec_accept_rate", "spec_mean_emitted",
)


class Telemetry:
    def __init__(self, *, tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 logger: Optional[StructLogger] = None):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics
        self.logger = logger
        self.enabled = bool(self.tracer.enabled or metrics is not None)
        self.t_on = perf_counter()
        self._finalized = False

    @classmethod
    def off(cls) -> "Telemetry":
        return OFF_TELEMETRY

    @classmethod
    def default(cls) -> "Telemetry":
        """What an engine built without telemetry uses: the process
        recorder, no metrics registry."""
        global _DEFAULT
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = cls(tracer=recorder())
            return _DEFAULT

    @classmethod
    def on(cls, *, trace: bool = True, metrics: bool = True,
           logger: Optional[StructLogger] = None) -> "Telemetry":
        return cls(tracer=recorder() if trace else None,
                   metrics=MetricsRegistry() if metrics else None,
                   logger=logger)

    def trace_dict(self) -> Dict:
        """Trace-event JSON of what the tracer recorded since this facade
        was made."""
        return self.tracer.to_dict(self.t_on)

    # ---------------------------------------------------------- finalize --

    def finalize(self, engine) -> "Telemetry":
        """Snapshot the metrics from the engine's terminal state
        (idempotent).  Duck-typed on ``engine.sched`` /
        ``engine.summary()`` so obs stays import-free of the runtime
        package."""
        if self._finalized:
            return self
        self._finalized = True
        if self.metrics is not None:
            self._snapshot_metrics(engine, engine.sched)
        return self

    def _snapshot_metrics(self, engine, sched) -> None:
        m = self.metrics
        summ = engine.summary()
        m.engine_summary = summ
        for k in _ENGINE_COUNTERS:
            m.counter(f"engine.{k}").value = float(summ[k])
        for k in _ENGINE_GAUGES:
            m.gauge(f"engine.{k}").set(float(summ[k]))
        for k, v in summ.items():
            if k.startswith("prefix_"):
                m.gauge(f"prefix_cache.{k[len('prefix_'):]}").set(float(v))
        m.counter("requests.finished").value = float(len(sched.finished))
        qd = m.histogram("queue_delay_ms")
        ttft = m.histogram("ttft_ms")
        tpot = m.histogram("tpot_ms")
        for req in sched.finished:
            if req.submit_t >= 0 and req.admit_t >= 0:
                qd.record((req.admit_t - req.submit_t) * 1e3)
            if req.submit_t >= 0 and req.first_tok_t >= 0:
                ttft.record((req.first_tok_t - req.submit_t) * 1e3)
            n = len(req.output)
            if req.first_tok_t >= 0 and req.finish_t >= 0 and n > 1:
                tpot.record((req.finish_t - req.first_tok_t) / (n - 1) * 1e3)

    # ------------------------------------------------------------ export --

    def export(self, *, trace_path: Optional[str] = None,
               metrics_path: Optional[str] = None) -> Dict[str, str]:
        """Write the requested artifacts; returns {channel: path}."""
        written: Dict[str, str] = {}
        if trace_path:
            written["trace"] = self.tracer.export(trace_path, self.t_on)
        if metrics_path and self.metrics is not None:
            written["metrics"] = self.metrics.save(metrics_path)
        return written


OFF_TELEMETRY = Telemetry()
_DEFAULT: Optional[Telemetry] = None
_DEFAULT_LOCK = threading.Lock()
