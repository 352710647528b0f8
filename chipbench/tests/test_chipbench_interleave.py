"""The share of chunk steps interleaved with decode, read from the
program's span recorder in a traced CPU rehearsal of the docqa cell."""
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "chipbench_rehearsal", os.path.join(HERE, "test_chipbench_rehearsal.py"))
rehearsal = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(rehearsal)


def test_docqa_traced_rehearsal_reads_the_interleaved_chunk_share(
        monkeypatch):
    rec, line = rehearsal.rehearse("v2-docqa-closed16", monkeypatch,
                                   trace=True)
    assert line["correct"] is True, line
    share = line["metrics"]["interleaved_chunk_share"]
    assert share["unit"] == "%"
    # agents admitted beside running rows prefill one chunk a tick
    assert 0 < share["value"] <= 100


def test_interleaved_chunk_share_reads_nothing_without_decoding_counts():
    """Chunk spans without a ``decoding`` count, as a program that runs
    each admission's prefill to its end records them, read None."""
    from chipbench import run
    from repro.obs import PID_ENGINE, recorder
    tr = recorder()
    t0 = tr.now()
    tr.complete("prefill_chunk", PID_ENGINE, 0, t0, t0 + 1e-3,
                args={"rows": 1, "tokens": 16})
    t1 = tr.now() + 1e-3
    assert run.read_metric("interleaved_chunk_share",
                           {"result": {"t0": t0, "t_end": t1}}) is None
    tr.complete("prefill_chunk", PID_ENGINE, 0, t1, t1 + 1e-3,
                args={"rows": 1, "tokens": 16, "decoding": 3})
    tr.complete("prefill_chunk", PID_ENGINE, 0, t1 + 2e-3, t1 + 3e-3,
                args={"rows": 2, "tokens": 32, "decoding": 0})
    assert run.read_metric("interleaved_chunk_share",
                           {"result": {"t0": t1, "t_end": t1 + 4e-3}}) == 50.0
