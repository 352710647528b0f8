"""Share of the window's chunk steps (the engine's ``prefill_chunk``
spans, from the program's span recorder) that ran while rows were
decoding (``decoding`` > 0): admission prefill interleaved with decode
instead of run to its end while every running row waits.  None where the
program records no such spans, its chunk spans carry no ``decoding``
count, or its ring dropped part of the window."""


def read(run):
    try:
        from repro.obs import PID_ENGINE, recorder
    except ImportError:
        return None
    res = run["result"]
    spans = recorder().window(res["t0"], res["t_end"])
    if not spans:
        return None
    decoding = [(e.args or {}).get("decoding") for e in spans
                if e.name == "prefill_chunk" and e.pid == PID_ENGINE
                and res["t0"] <= e.start <= res["t_end"]]
    if not decoding or None in decoding:
        return None
    return 100.0 * sum(1 for n in decoding if n > 0) / len(decoding)
