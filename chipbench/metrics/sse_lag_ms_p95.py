"""95th percentile, over the tokens streamed in the window, of the time
from the engine worker's put of a token to the handler's write and flush
of its event returning (``sse_write`` spans of the program's span
recorder).  None where it records no such spans or its ring dropped part
of the window."""


def read(run):
    try:
        from repro.obs import PID_FRONTEND, recorder
    except ImportError:
        return None
    res = run["result"]
    spans = recorder().window(res["t0"], res["t_end"])
    if not spans:
        return None
    from chipbench.stats import p95
    lags = [e.args["lag_ms"] for e in spans
            if e.name == "sse_write" and e.pid == PID_FRONTEND
            and res["t0"] <= e.end <= res["t_end"]]
    return p95(lags) if lags else None
