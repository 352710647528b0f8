"""The trace reduction and the kernel cost functions."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import costs, model, trace  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "docqa_trace_events.json")
MS = 1_000_000


def _events():
    """A window of 100 ms: one decode step execution (10-40 ms) holding a
    kernel op (15-25 ms), one prefill execution (50-70 ms) holding a
    kernel op (55-60 ms), a stray copy module (80-85 ms); the host was
    in an engine step for 40-50 ms and waiting after 85 ms."""
    ops = [("fusion.1", 10 * MS, 15 * MS, False),
           ("run.1", 15 * MS, 25 * MS, True),
           ("fusion.2", 25 * MS, 40 * MS, False),
           ("fusion.3", 50 * MS, 55 * MS, False),
           ("run.7", 55 * MS, 60 * MS, True),
           ("fusion.4", 60 * MS, 70 * MS, False),
           ("copy.1", 80 * MS, 85 * MS, False)]
    modules = [("jit_run(1)", 10 * MS, 40 * MS), ("jit_run(2)", 50 * MS, 70 * MS),
               ("jit_copy_blocks_paged", 80 * MS, 85 * MS)]
    spans = [("chipbench.window", 0, 100 * MS, {}),
             ("chipbench.decode_call", 2 * MS, 3 * MS, {"rows": "99:1;199:1"}),
             ("chipbench.engine_step", 40 * MS, 50 * MS, {}),
             ("chipbench.prefill_call", 41 * MS, 42 * MS,
              {"rows": "0:16;64:8"})]
    return {"device": "/device:TPU:0", "ops": ops, "modules": modules,
            "spans": spans}


def test_busy_union_and_idle_gaps():
    spec = model.load("deepseek-v2-ep8")
    r = trace.reduce(_events(), spec, "TPU v5 lite")
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.055)   # 30 + 20 + 5 ms
    got = sorted((round(s * 1e3), n) for n, s in r["idle_gaps"])
    assert got == [(10, "engine_step"), (10, "worker_waiting"),
                   (10, "worker_waiting"), (15, "worker_waiting")]
    assert r["idle_gaps"][0] == ["worker_waiting", pytest.approx(0.015)]


def test_kernels_are_attributed_by_the_step_that_ran_them():
    spec = model.load("deepseek-v2-ep8")
    r = trace.reduce(_events(), spec, "TPU v5 lite")
    dec, pre = r["kinds"]["decode"], r["kinds"]["prefill"]
    assert dec["executions"] == pre["executions"] == 1
    assert dec["module_s"] == pytest.approx(0.030)
    assert dec["kernel_s"] == pytest.approx(0.010)
    assert pre["kernel_s"] == pytest.approx(0.005)
    n = spec["num_hidden_layers"]
    pk = costs.peaks("TPU v5 lite")
    fl, by = costs.decode_kernel(spec, [100, 200])
    assert dec["kernel_min_s"] == pytest.approx(
        n * max(fl / pk["flops"], by / pk["hbm_bytes_per_s"]))
    fl, by = costs.prefill_kernel(spec, [(0, 16), (64, 8)])
    assert pre["kernel_min_s"] == pytest.approx(
        n * max(fl / pk["flops"], by / pk["hbm_bytes_per_s"]))
    assert dec["flops"] == pytest.approx(
        costs.call_flops(spec, "decode", [(99, 1), (199, 1)]))


def test_an_execution_dispatched_before_the_trace_is_left_out():
    ev = _events()
    ev["modules"].insert(0, ("jit_run(1)", 1 * MS, 2 * MS))
    ev["spans"] = [s for s in ev["spans"] if s[0] != "chipbench.decode_call"]
    spec = model.load("deepseek-v2-ep8")
    r = trace.reduce(ev, spec, "TPU v5 lite")
    assert r["kinds"]["decode"]["executions"] == 0
    assert r["kinds"]["prefill"]["executions"] == 1


def test_span_arguments_and_rows():
    args = trace._args("chipbench.decode_call#rows=3:1;5:1#", {})
    assert trace.rows_of(args["rows"]) == [(3, 1), (5, 1)]
    assert trace.rows_of("") == []


def test_recorded_chip_trace():
    """Events cut from a traced run of v2-docqa-closed32 on one v5e (the
    first 350 ms of its traced window): one decode and five prefill step
    executions, each kernel call a tpu_custom_call op inside them."""
    with open(RECORDED) as f:
        ev = json.load(f)
    for key in ("ops", "modules", "spans"):
        ev[key] = [tuple(e) for e in ev[key]]
    spec = model.load("deepseek-v2-ep8")
    r = trace.reduce(ev, spec, "TPU v5 lite")
    assert 0 < r["busy_s"] <= r["window_s"]
    dec, pre = r["kinds"]["decode"], r["kinds"]["prefill"]
    assert (dec["executions"], pre["executions"]) == (1, 5)
    for k in (dec, pre):
        assert 0 < k["kernel_min_s"] < k["kernel_s"] < k["module_s"]
    assert dec["kernel_s"] == pytest.approx(0.031451888)
    assert r["device_ops"][0][0].startswith("prefill:")
    assert r["device_ops"][0][0].endswith("[tpu_custom_call]")


def test_decode_kernel_work_matches_hwmodel():
    from repro.hwmodel.attention_costs import mla_decode_cost
    from repro.models.common import ModelConfig
    spec = model.load("deepseek-v2-ep8")
    cfg = model.model_config(spec, ModelConfig, max_seq=4096).mla_config()
    for L in (1, 777, 24000):
        c = mla_decode_cost(cfg, scheme="seq", cache_len=L, rope=True)
        fl, by = costs.decode_kernel(spec, [L])
        assert fl == pytest.approx(c.breakdown["attn_scores"]
                                   + c.breakdown["attn_out"])
        H, Dl, Dr = 128, 512, 64
        assert by - (H * (Dl + Dr) + H * Dl) * 2 == pytest.approx(
            c.breakdown["B:cache_read"])


def test_prefill_kernel_work_matches_hwmodel():
    """One token per chunk over one-token blocks: hwmodel's paged chunk
    cost then attends exactly the causal prefix, as this count does."""
    from repro.hwmodel.attention_costs import mla_prefill_chunk_cost
    from repro.models.common import ModelConfig
    spec = model.load("deepseek-v3-ep32")
    cfg = model.model_config(spec, ModelConfig, max_seq=4096).mla_config()
    P, L = 100, 140
    c = mla_prefill_chunk_cost(cfg, seq_len=L, chunk=1, paged_block=1,
                               cached_prefix=P, rope=True)
    fl, by = costs.prefill_kernel(spec, [(P + k, 1) for k in range(L - P)])
    assert fl == pytest.approx(c.breakdown["attn_scores_pv"])
    fl2, _ = costs.prefill_kernel(spec, [(P, L - P)])
    assert fl2 == pytest.approx(fl)
