"""Share of the window the server process spent in garbage collections
(``gc`` spans of the program's span recorder, every generation).  None
where the program records no spans or its ring dropped part of the
window."""


def read(run):
    try:
        from repro.obs import PID_PROCESS, recorder
    except ImportError:
        return None
    res = run["result"]
    t0, t1 = res["t0"], res["t_end"]
    spans = recorder().window(t0, t1)
    if not spans:
        return None
    paused = sum(max(0.0, min(e.end, t1) - max(e.start, t0))
                 for e in spans if e.name == "gc" and e.pid == PID_PROCESS)
    return 100.0 * paused / (t1 - t0)
