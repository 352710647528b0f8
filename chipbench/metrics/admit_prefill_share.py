"""Share of the time between the window's first and last decode
``dispatch`` span starts that the engine spent in ``prefill`` spans
(admitted prompts prefilling inside a tick, with every running row
waiting); from the program's span recorder.  None where it records no
such spans or its ring dropped part of the window."""


def read(run):
    try:
        from repro.obs import PID_ENGINE, recorder
    except ImportError:
        return None
    res = run["result"]
    spans = recorder().window(res["t0"], res["t_end"])
    if not spans:
        return None
    engine = [e for e in spans if e.pid == PID_ENGINE]
    starts = sorted(e.start for e in engine if e.name == "dispatch"
                    and res["t0"] <= e.start <= res["t_end"])
    if len(starts) < 2:
        return None
    lo, hi = starts[0], starts[-1]
    covered = sum(max(0.0, min(e.end, hi) - max(e.start, lo))
                  for e in engine if e.name == "prefill")
    return 100.0 * covered / (hi - lo)
