"""Reduction of a profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into plain
events; ``reduce`` turns them into:

* busy and window seconds: the union of the intervals in which an
  operation ran on the device, against the traced window (the harness's
  ``chipbench.window`` span);
* the idle gaps, each labelled by the innermost harness span the host was
  in at its middle (``chipbench.engine_step``, ``chipbench.decode_call``,
  ``chipbench.prefill_call``; none means the engine worker was waiting
  for requests);
* per step kind (decode, prefill): the device time of its step programs,
  the time of the Pallas kernel calls in them, the least time the kernel
  calls' work needs at the chip's peaks, and the model operations the
  step's tokens need (``costs.py``).

The two serving steps are both jitted functions named ``run``; their XLA
modules differ only by a program fingerprint.  Each execution is given to
the harness span that dispatched it: the spans carry the rows each call
served, the device runs step programs in the order they were dispatched,
and the harness drains the device before the first traced dispatch.  A
kernel call is an op inside a step execution whose HLO is a
``tpu_custom_call``.
"""
from __future__ import annotations

import glob
import os

from . import costs

STEP_MODULE = "jit_run"
SPANS = ("chipbench.window", "chipbench.engine_step", "chipbench.decode_call",
         "chipbench.prefill_call")


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        out[k] = v if isinstance(v, (int, float)) else str(v)
    return out


KERNEL = 'custom_call_target="tpu_custom_call"'


def load(trace_dir: str) -> dict:
    """{"ops": [(name, start_ns, end_ns, is_kernel)], "modules": [(name,
    start_ns, end_ns)], "spans": [(name, start_ns, end_ns, args)]} of the
    first TPU and the host, on the trace's one clock.  An op's event
    name is its HLO text; the name kept is the instruction's (before
    " = "), and the op is a Pallas kernel call when the text names the
    ``tpu_custom_call`` target."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {"device": None, "ops": [], "modules": [], "spans": []}
    if not paths:
        return out
    pd = ProfileData.from_file(paths[0])
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and out["device"] is None:
            out["device"] = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out["ops"] += [(e.name.split(" = ")[0], e.start_ns,
                                    e.end_ns, KERNEL in e.name)
                                   for e in line.events]
                elif line.name == "XLA Modules":
                    out["modules"] += [(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    base = e.name.split("#")[0]
                    if base in SPANS:
                        out["spans"].append((base, e.start_ns, e.end_ns,
                                             _args(e.name, _stats(e))))
    for key in ("ops", "modules", "spans"):
        out[key].sort(key=lambda o: o[1])
    return out


def _args(name: str, stats: dict) -> dict:
    """Span arguments: JAX encodes TraceAnnotation keywords into the
    event name as ``name#k=v,k=v#``; the profiler may also give them as
    stats."""
    out = {k: v for k, v in stats.items() if k in ("kind", "rows")}
    if "#" in name:
        for kv in name.split("#")[1].split(","):
            if "=" in kv:
                k, v = kv.split("=", 1)
                out[k] = v
    return out


def rows_of(arg: str):
    """Rows as the harness encodes them: 'a:b;c:d' -> [(a, b), (c, d)]."""
    if not arg:
        return []
    return [tuple(int(x) for x in r.split(":")) for r in arg.split(";")]


def _union(intervals, lo, hi) -> float:
    total, cur_a, cur_b = 0, None, None
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def _gaps(intervals, lo, hi):
    out, t = [], lo
    for a, b in sorted(intervals):
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def _host_label(spans, t) -> str:
    best = None
    for name, a, b, _ in spans:
        if name == "chipbench.window" or not a <= t < b:
            continue
        if best is None or a >= best[1]:
            best = (name, a)
    return best[0][len("chipbench."):] if best else "worker_waiting"


def match(modules, calls, skew_ns: int = 5_000_000):
    """Pair step-module executions with dispatch spans, in order.  The
    harness drains the device before the first traced dispatch, so the
    executions that end before it (within the host and device clocks'
    skew) belong to earlier, untraced calls and are left out; each later
    execution belongs to the next dispatch.  Returns [(module, call)], or
    [] where one program would be paired with two kinds of call."""
    if not calls:
        return []
    mods = [m for m in modules if m[2] > calls[0][1] + skew_ns]
    pairs = list(zip(mods, calls))
    kind = {}
    for m, c in pairs:
        if kind.setdefault(m[0], c[0]) != c[0]:
            return []
    return pairs


def reduce(ev: dict, spec: dict, device_kind: str) -> dict:
    """Device metrics of a loaded trace (see module docstring)."""
    win = [s for s in ev["spans"] if s[0] == "chipbench.window"]
    if not ev["ops"] or not win:
        return {}
    lo, hi = win[0][1], win[0][2]
    pk = costs.peaks(device_kind)
    busy = _union([(a, b) for _, a, b, _ in ev["ops"]], lo, hi)
    window_ns = hi - lo
    gaps = _gaps([(a, b) for _, a, b, _ in ev["ops"]], lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_host_label(ev["spans"], (a + b) / 2), (b - a) / 1e9]
            for a, b in gaps[:10]]
    steps = [m for m in ev["modules"] if m[0].split("(")[0] == STEP_MODULE]
    calls = [s for s in ev["spans"]
             if s[0] in ("chipbench.decode_call", "chipbench.prefill_call")]
    n_layers = spec["num_hidden_layers"]
    kinds = {k: {"module_s": 0.0, "kernel_s": 0.0, "kernel_min_s": 0.0,
                 "flops": 0.0, "executions": 0}
             for k in ("decode", "prefill")}
    window_flops = 0.0
    ops = ev["ops"]
    j = 0
    kinds_of = []
    for (mname, ma, mb), call in match(steps, calls):
        kind = call[0][len("chipbench."):-len("_call")]
        kinds_of.append(((mname, ma, mb), kind))
        rows = rows_of(call[3].get("rows", ""))
        k = kinds[kind]
        f = costs.call_flops(spec, kind, rows)
        inside = lo <= ma and mb <= hi
        if inside:
            window_flops += f
        k["executions"] += 1
        k["module_s"] += (mb - ma) / 1e9
        k["flops"] += f
        while j < len(ops) and ops[j][1] < ma:
            j += 1
        kern = 0
        t = j
        while t < len(ops) and ops[t][1] < mb:
            if ops[t][3]:
                kern += ops[t][2] - ops[t][1]
            t += 1
        k["kernel_s"] += kern / 1e9
        if kind == "decode":
            fl, by = costs.decode_kernel(spec, [s + 1 for s, _ in rows])
        else:
            fl, by = costs.prefill_kernel(spec, rows)
        k["kernel_min_s"] += n_layers * max(fl / pk["flops"],
                                            by / pk["hbm_bytes_per_s"])
    # the ten leaf ops with the most time, each named by the step kind
    # (or module) that ran it; an op holding others, such as a scanned
    # layer loop, is left out, as its children count already
    label = {m[0]: k for (m, k) in kinds_of}
    top, m = {}, 0
    mods = ev["modules"]
    for i, (name, a, b, kern) in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1][1] < b:
            continue
        while m < len(mods) and mods[m][2] <= a:
            m += 1
        where = ""
        if m < len(mods) and mods[m][1] <= a:
            where = label.get(mods[m][0], mods[m][0].split("(")[0]) + ":"
        key = where + name + (" [tpu_custom_call]" if kern else "")
        top[key] = top.get(key, 0) + max(0, min(b, hi) - max(a, lo))
    device_ops = sorted(([n, t / 1e9] for n, t in top.items() if t > 0),
                        key=lambda x: -x[1])[:10]
    return {"window_s": window_ns / 1e9, "busy_s": busy / 1e9,
            "window_flops": window_flops, "peak_flops": pk["flops"],
            "kinds": kinds, "device_ops": device_ops, "idle_gaps": idle}
