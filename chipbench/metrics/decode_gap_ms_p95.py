"""95th percentile of the intervals between consecutive starts of the
engine's decode ``dispatch`` spans in the window (the program's span
recorder): the engine's share of the gap between tokens.  None where the
program records no such spans or its ring dropped part of the window."""


def read(run):
    try:
        from repro.obs import PID_ENGINE, recorder
    except ImportError:
        return None
    res = run["result"]
    spans = recorder().window(res["t0"], res["t_end"])
    if not spans:
        return None
    from chipbench.stats import p95
    starts = sorted(e.start for e in spans
                    if e.name == "dispatch" and e.pid == PID_ENGINE
                    and res["t0"] <= e.start <= res["t_end"])
    if len(starts) < 2:
        return None
    return 1e3 * p95([b - a for a, b in zip(starts, starts[1:])])
