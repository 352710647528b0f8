"""CPU rehearsal of each cell: the cell's traffic at smoke widths through
the same harness path as a chip run (engine, HTTP frontend, client
process, check), and the result line it prints."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# The widest logit gap the check allows at smoke widths (logits there
# spread ~0.16 wide, not ~1.4 as at the published widths).  Readings at
# these sizes (CPU, seed 2**31 + 77): the program served greedy tokens
# within 0.0068 (docqa) and 0.0032 (offline) of the reference's best; the
# float8 control's first choices fell 0.18 and 0.086 below it.
SMOKE_LIMIT = 0.03
SEED = 2 ** 31 + 77
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(
    ROOT, "chipbench", "workloads")) if f.endswith(".json"))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# mixes of the kinds no cell uses yet, each driven as a cell of its own
MIXES = ["mix-open-bursts-n2", "mix-sessions-docs"]


def rehearse(cell, monkeypatch, *, fault=None, control=False, trace=False):
    os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")
    import time
    from chipbench import harness, run, traffic
    from repro.hwmodel.platforms import PLATFORMS
    load = harness.load_cell

    def smoke_cell(name):
        c = load(name)
        c["check"]["max_logit_gap"] = SMOKE_LIMIT
        return c
    monkeypatch.setattr(harness, "load_cell", smoke_cell)
    if cell.startswith("mix-"):
        # a test cell: the V2 configuration under a mix from ``data/``
        monkeypatch.setattr(harness, "load_cell", lambda name: {
            "name": name, "config": "deepseek-v2-ep8", "traffic": name,
            "chips": 1, "engine": {}, "check": {
                "sample_tokens": 40, "same_document": False,
                "max_logit_gap": SMOKE_LIMIT}})
        monkeypatch.setattr(traffic, "load", lambda name: json.load(
            open(os.path.join(DATA, f"{name}.json"))))
    rec = harness.run_cell(cell, SEED, 3.0, trace, t_start=time.perf_counter(),
                           counter=harness.CompileCounter(),
                           platform=PLATFORMS["tpu_v5e"], smoke=True,
                           fault=fault, control=control, log=lambda m: None)
    line = json.loads(json.dumps(run.result_line(rec, trace,
                                                 run.benchmark())))
    return rec, line


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_prints_a_correct_line(cell, monkeypatch):
    rec, line = rehearse(cell, monkeypatch, control=True)
    assert list(line)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    from chipbench import run
    e2e = run.end_to_end(rec)
    assert {"setup_s", "itl_p95_ms", "output_tokens_per_s"} <= set(e2e)
    assert all(v > 0 for v in e2e.values())
    bench = run.benchmark()
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    gap, limit = line["check"]["max_logit_gap"].values()
    assert gap <= limit
    # the float8 control, read at the same positions, is not correct
    assert rec["check"]["control_max_logit_gap"] > limit
    assert rec["check"]["sampled_tokens"] > 0


@pytest.mark.parametrize("mix", MIXES)
def test_traffic_kind_rehearsal_is_correct(mix, monkeypatch):
    """Forks (n), bursts, a skewed vocabulary and multi-turn context go
    through the same client, server and check as a cell's traffic."""
    rec, line = rehearse(mix, monkeypatch)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    recs = rec["result"]["records"]
    reqs = rec["plan"]["requests"]
    assert all(len(r["choices"]) == reqs[r["id"]]["n"] for r in recs)
    if mix.endswith("docs"):
        # later turns were sent, each carrying its conversation
        assert any(reqs[r["id"]]["context"] and r["done"] for r in recs)
    e2e = __import__("chipbench.run", fromlist=["x"]).end_to_end(rec)
    assert e2e["output_tokens_per_s"] > 0 and e2e["ttft_p95_ms"] > 0
