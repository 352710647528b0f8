"""The per-layer metrics read from the program's own span recorder, in a
CPU rehearsal of the docqa cell with a profiler trace of its window."""
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "chipbench_rehearsal", os.path.join(HERE, "test_chipbench_rehearsal.py"))
rehearsal = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(rehearsal)

SPAN_METRICS = ("decode_gap_ms_p95", "admit_prefill_share", "sse_lag_ms_p95",
                "gc_pause_share")


def test_docqa_traced_rehearsal_reads_every_span_metric(monkeypatch):
    rec, line = rehearsal.rehearse("v2-docqa-closed16", monkeypatch,
                                   trace=True)
    assert line["correct"] is True, line
    got = {k: line["metrics"][k]["value"] for k in SPAN_METRICS
           if k in line["metrics"]}
    assert set(got) == set(SPAN_METRICS), line["metrics"]
    assert got["decode_gap_ms_p95"] > 0 and got["sse_lag_ms_p95"] > 0
    assert 0 < got["admit_prefill_share"] <= 100
    assert 0 <= got["gc_pause_share"] < 100
    # the engine counters still read beside them
    assert line["metrics"]["decode_batch_mean"]["value"] >= 1
