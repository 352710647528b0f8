"""Span recorder emitting Chrome/Perfetto trace-event JSON.

Two implementations behind one duck-typed interface:

  * :class:`Tracer` — records spans, instants and retrospective
    ``complete`` events into a bounded ring (a ``deque`` of
    :data:`RING_EVENTS`, safe to append to from any thread), serves a time window of them
    to in-process readers (:meth:`Tracer.window`), and serializes them
    as the trace-event JSON object format (``{"traceEvents": [...]}``),
    which loads in Perfetto / chrome://tracing via ``export(path)``.
  * :class:`NullTracer` — the disabled mode.  Every call short-circuits
    BEFORE any string formatting or dict allocation: ``span()`` returns
    a module-level singleton context manager and ignores its arguments.

:func:`recorder` is the process-wide :class:`Tracer`: always on, the
default of every engine and frontend built without explicit telemetry,
and the one that also records the process's garbage collections and
XLA compiles.

Conventions (what the exporter, the readers and the tests pin):

  * timestamps are absolute ``time.perf_counter()`` seconds
    (CLOCK_MONOTONIC, shared by every process on the host);
    ``to_dict`` rebases them to microseconds at write time;
  * while a JAX profiler session captures, every live span is also
    entered as a ``jax.profiler.TraceAnnotation`` of the same name, so
    it lands on the host plane of the ``.xplane.pb`` on the device ops'
    clock; its keyword arguments, and the ``detail`` a span may carry,
    are formatted only then.  Retrospective events stay in the ring;
  * pid :data:`PID_ENGINE` (1) carries the per-step phase spans (tid 0:
    schedule / prefill / dispatch / draft / verify / device_step /
    host_sample, nested under one "step" span per engine tick);
  * pid :data:`PID_REQUESTS` (2) carries per-request lifecycle events,
    one tid per request id, written when the request finishes;
  * pid :data:`PID_FRONTEND` (3) carries the HTTP frontend's spans, one
    tid per thread;
  * pid :data:`PID_PROCESS` (4) carries ``gc`` (tid 0) and ``compile``
    (tid 1) events;
  * within one (pid, tid), "X" events are properly nested — no partial
    overlap (:func:`validate_trace` checks this).
"""
from __future__ import annotations

import collections
import gc
import json
import threading
from time import perf_counter
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

PID_ENGINE = 1
PID_REQUESTS = 2
PID_FRONTEND = 3
PID_PROCESS = 4
RING_EVENTS = 2 ** 17

_capturing = TraceAnnotation.is_enabled
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Event(NamedTuple):
    """One recorded event; an instant (``ph == "i"``) has end == start."""
    name: str
    ph: str
    pid: int
    tid: int
    start: float
    end: float
    args: Optional[Dict]


class _NullSpan:
    """Shared no-op context manager — the disabled tracer's only span."""
    __slots__ = ()
    dur_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every method is a no-op that ignores its
    arguments without touching them (no formatting, no allocation)."""

    enabled = False
    __slots__ = ()

    def span(self, name, pid=PID_ENGINE, tid=0, args=None, detail=None):
        return _NULL_SPAN

    def complete(self, name, pid, tid, start_s, end_s, args=None):
        pass

    def instant(self, name, pid=PID_ENGINE, tid=0, args=None):
        pass

    def request(self, req):
        pass

    def set_process_name(self, pid, name):
        pass

    def set_thread_name(self, pid, tid, name):
        pass

    def now(self) -> float:
        return 0.0

    def to_dict(self, t0=None, t1=None) -> Dict:
        return {"traceEvents": []}

    def export(self, path: str, t0=None) -> None:
        raise RuntimeError("cannot export a NullTracer (tracing is off)")


NULL_TRACER = NullTracer()


class _Span:
    """Context manager recording one "X" event on exit, and mirroring it
    into a profiler annotation while a capture runs."""
    __slots__ = ("_tr", "_name", "_pid", "_tid", "_args", "_detail", "_t0",
                 "_ta", "dur_s")

    def __init__(self, tracer, name, pid, tid, args, detail):
        self._tr = tracer
        self._name = name
        self._pid = pid
        self._tid = tid
        self._args = args
        self._detail = detail
        self._ta = None
        self.dur_s = 0.0

    def __enter__(self):
        if _capturing():
            kw = dict(self._args) if self._args else {}
            if self._detail is not None:
                kw.update(self._detail())
            self._ta = TraceAnnotation(self._name, **kw)
            self._ta.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = perf_counter()
        if self._ta is not None:
            self._ta.__exit__(*exc)
        self.dur_s = t1 - self._t0
        self._tr._ring.append((self._name, "X", self._pid, self._tid,
                               self._t0, t1, self._args, t1))
        return False


class Tracer:
    """Recording tracer over a ring of :data:`RING_EVENTS` events.  A ring
    record is an :class:`Event`'s fields plus the clock at which it was
    appended, which tells :meth:`window` whether dropped events could
    reach into the interval it is asked for."""

    enabled = True

    def __init__(self):
        self._ring: collections.deque = collections.deque(maxlen=RING_EVENTS)
        self._names: Dict = {}
        self._gc_t0 = 0.0

    def now(self) -> float:
        return perf_counter()

    # ------------------------------------------------------------ events --

    def span(self, name: str, pid: int = PID_ENGINE, tid: int = 0,
             args: Optional[Dict] = None, detail=None) -> _Span:
        """Live span.  ``args`` go into the ring; ``detail`` is a
        zero-argument callable returning more keyword arguments, called
        only while a profiler captures."""
        return _Span(self, name, pid, tid, args, detail)

    def complete(self, name: str, pid: int, tid: int, start_s: float,
                 end_s: float, args: Optional[Dict] = None) -> None:
        """Retrospective "X" event from two ``perf_counter`` timestamps."""
        self._ring.append((name, "X", pid, tid, start_s,
                           max(end_s, start_s), args, perf_counter()))

    def instant(self, name: str, pid: int = PID_ENGINE, tid: int = 0,
                args: Optional[Dict] = None) -> None:
        t = perf_counter()
        self._ring.append((name, "i", pid, tid, t, t, args, t))

    def instant_at(self, name: str, pid: int, tid: int, at_s: float,
                   args: Optional[Dict] = None) -> None:
        self._ring.append((name, "i", pid, tid, at_s, at_s, args,
                           perf_counter()))

    def request(self, req) -> None:
        """Lifecycle events of a finished request from the timestamps the
        scheduler stamped on it (duck-typed: ``rid``, ``submit_t``,
        ``admit_t``, ``first_tok_t``, ``finish_t``, ``preempt_ts``; -1 is
        a transition never reached)."""
        tid = int(req.rid)
        if req.submit_t >= 0:
            self.instant_at("arrival", PID_REQUESTS, tid, req.submit_t)
            if req.admit_t >= 0:
                self.complete("queued", PID_REQUESTS, tid, req.submit_t,
                              req.admit_t)
        if req.admit_t >= 0 and req.first_tok_t >= 0:
            self.complete("prefill", PID_REQUESTS, tid, req.admit_t,
                          req.first_tok_t,
                          args={"plen": req.plen, "cached": req.n_cached})
        if req.first_tok_t >= 0 and req.finish_t >= 0:
            self.complete("decode", PID_REQUESTS, tid, req.first_tok_t,
                          req.finish_t, args={"new_tokens": len(req.output)})
            self.instant_at("finish", PID_REQUESTS, tid, req.finish_t)
        for t in req.preempt_ts:
            self.instant_at("preempt", PID_REQUESTS, tid, t)

    # ---------------------------------------------------------- metadata --

    def set_process_name(self, pid: int, name: str) -> None:
        self._names[(pid, None)] = name

    def set_thread_name(self, pid: int, tid: int, name: str) -> None:
        self._names[(pid, tid)] = name

    # ----------------------------------------------------------- process --

    def watch_process(self) -> "Tracer":
        """Record the process's garbage collections (``gc``) and XLA
        compiles (``compile``, ending when JAX reports the compile's
        duration).  Call once per tracer."""
        from jax import monitoring
        gc.callbacks.append(self._on_gc)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        self.set_process_name(PID_PROCESS, "process")
        return self

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = perf_counter()
            return
        self.complete("gc", PID_PROCESS, 0, self._gc_t0, perf_counter(),
                      args={"gen": info["generation"],
                            "collected": info["collected"]})

    def _on_duration(self, event, secs, **_) -> None:
        if event == COMPILE_EVENT:
            t1 = perf_counter()
            self.complete("compile", PID_PROCESS, 1, t1 - secs, t1)

    # ------------------------------------------------------------- reads --

    def _records(self) -> List:
        while True:
            try:
                return list(self._ring)
            except RuntimeError:    # appended to while being copied
                continue

    def window(self, t0: float, t1: float) -> Optional[List[Event]]:
        """Events that overlap [t0, t1] (``perf_counter`` seconds), in the
        order they were recorded; None when the ring may have dropped one
        that did (it is full and its oldest record came after t0)."""
        recs = self._records()
        if len(recs) == self._ring.maxlen and recs[0][7] > t0:
            return None
        return [Event(*r[:7]) for r in recs if r[5] >= t0 and r[4] <= t1]

    # ------------------------------------------------------------ export --

    def to_dict(self, t0: Optional[float] = None,
                t1: Optional[float] = None) -> Dict:
        """Trace-event JSON of the ring's events overlapping [t0, t1]
        (all by default), timestamps in µs from the earliest of them."""
        lo = float("-inf") if t0 is None else t0
        hi = float("inf") if t1 is None else t1
        recs = [r for r in self._records() if r[5] >= lo and r[4] <= hi]
        base = min((r[4] for r in recs), default=0.0)
        events = []
        for (pid, tid), name in list(self._names.items()):
            kind = "process_name" if tid is None else "thread_name"
            events.append({"name": kind, "ph": "M", "pid": pid,
                           "tid": tid or 0, "args": {"name": name}})
        for name, ph, pid, tid, a, b, args, _ in recs:
            ev = {"name": name, "ph": ph, "pid": pid, "tid": tid,
                  "ts": (a - base) * 1e6}
            if ph == "X":
                ev["dur"] = (b - a) * 1e6
            else:
                ev["s"] = "t"
            if args:
                ev["args"] = args
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str, t0: Optional[float] = None) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(t0), f, indent=1)
        return path


_RECORDER: Optional[Tracer] = None
_RECORDER_LOCK = threading.Lock()


def recorder() -> Tracer:
    """The process-wide recorder, made (and its process hooks installed)
    on first use."""
    global _RECORDER
    with _RECORDER_LOCK:
        if _RECORDER is None:
            _RECORDER = Tracer().watch_process()
        return _RECORDER


# ------------------------------------------------------------ validation --


def validate_trace(trace: Dict) -> List[str]:
    """Structural checks on a trace-event JSON object; returns a list of
    problems (empty == valid).  Pinned by tests/test_obs.py and run as an
    in-bench gate on the bench_serving smoke trace:

      * top-level ``traceEvents`` list; every event carries name/ph/pid/tid
        (+ ts for non-metadata, + dur >= 0 for "X");
      * pids/tids are integers (stable identity for Perfetto tracks);
      * within each (pid, tid), "X" spans NEST — an event starting inside
        an open span must also end inside it (no partial overlap).
    """
    problems: List[str] = []
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return ["top-level 'traceEvents' list missing"]
    per_track: Dict = {}
    for i, ev in enumerate(events):
        for k in ("name", "ph", "pid", "tid"):
            if k not in ev:
                problems.append(f"event {i} missing '{k}': {ev}")
                break
        else:
            if not isinstance(ev["pid"], int) or not isinstance(ev["tid"], int):
                problems.append(f"event {i}: non-integer pid/tid: {ev}")
            if ev["ph"] == "M":
                continue
            if "ts" not in ev:
                problems.append(f"event {i} missing 'ts': {ev}")
                continue
            if ev["ph"] == "X":
                if ev.get("dur", -1.0) < 0:
                    problems.append(f"event {i}: 'X' without dur >= 0: {ev}")
                    continue
                per_track.setdefault((ev["pid"], ev["tid"]), []).append(ev)
    # nesting: sort by (start, -end); each span must close before any
    # enclosing span still on the stack does.
    eps = 1e-3  # µs slack: perf_counter is ns-resolution, format is float
    for track, evs in per_track.items():
        evs = sorted(evs, key=lambda e: (e["ts"], -(e["ts"] + e["dur"])))
        stack: List = []
        for ev in evs:
            start, end = ev["ts"], ev["ts"] + ev["dur"]
            while stack and start >= stack[-1][1] - eps:
                stack.pop()
            if stack and end > stack[-1][1] + eps:
                problems.append(
                    f"track {track}: span '{ev['name']}' "
                    f"[{start:.1f}, {end:.1f}] overlaps "
                    f"'{stack[-1][0]}' ending at {stack[-1][1]:.1f}")
            stack.append((ev["name"], end))
    return problems
