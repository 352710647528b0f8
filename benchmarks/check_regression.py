"""Regression gate over the serving-bench artifacts (the CI `bench-gate`
job): diff `benchmarks/artifacts/*.json` against the committed baselines
in `benchmarks/baselines/` with per-metric tolerance rules and exit
non-zero on any regression.

Three rule kinds, chosen per metric for cross-machine honesty:

  eq         deterministic structure — token counts, compile counts,
             hit rates, acceptance of the identity draft.  These are
             seeded and topology-invariant, so CI compares them exactly.
  approx     closed-form model outputs (modeled bytes, break-even,
             operational intensity) — jax-independent arithmetic, gated
             to a tiny relative tolerance so cost-model regressions trip.
  min_ratio / max_ratio
             wall-clock metrics (tokens/s, step ms) — the baseline was
             measured on a different machine than CI, so only large
             moves in the BAD direction fail.

A baseline key missing from the fresh artifact fails too (a silently
dropped bench row is itself a regression).  Refresh baselines after an
intentional change with:

    PYTHONPATH=src python benchmarks/bench_serving.py ... && \
        python benchmarks/check_regression.py --update
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

# (file, dotted metric path, kind, tolerance) — kinds per the docstring.
RULES = [
    # throughput: generous ratio (CI runners differ from the baseline box)
    ("bench_serving.json", "paged_prefix.tokens_per_s", "min_ratio", 0.25),
    # deterministic serving structure: exact
    ("bench_serving.json", "paged_prefix.decode_tokens", "eq", None),
    ("bench_serving.json", "paged_prefix.prefill_tokens", "eq", None),
    ("bench_serving.json", "paged_prefix.prefill_compiles", "eq", None),
    ("bench_serving.json", "paged_prefix.total_blocks_allocated", "eq", None),
    ("bench_serving.json", "paged_prefix.prefix_hit_rate", "approx", 1e-9),
    ("bench_serving.json", "paged.prefill_tokens", "eq", None),
    ("bench_serving.json", "paged.prefill_compiles", "eq", None),
    ("bench_serving.json", "paged_mesh.decode_tokens", "eq", None),
    ("bench_serving.json", "paged_mesh.prefill_compiles", "eq", None),
    ("bench_serving.json", "util_gain", "approx", 0.05),
    # speculative decoding: the oracle and the compile bounds are exact;
    # shallow-draft acceptance is numerics-adjacent, so ratio-gated
    ("bench_serving.json", "paged_spec.self.spec_accept_rate", "eq", None),
    ("bench_serving.json", "paged_spec.self.decode_tokens", "eq", None),
    ("bench_serving.json", "paged_spec.self.spec_compiles", "eq", None),
    ("bench_serving.json", "paged_spec.shallow.spec_compiles", "eq", None),
    ("bench_serving.json", "paged_spec.shallow.prefill_compiles", "eq", None),
    ("bench_serving.json", "paged_spec.shallow.spec_mean_emitted", "min_ratio", 0.7),
    ("bench_serving.json", "paged_spec.shallow_mesh.decode_tokens", "eq", None),
    # quantized int8 pool (PR 8): serving structure is seeded/exact; the
    # modeled byte shrink and OI shift are closed-form; the oracle error
    # is quantization numerics (deterministic per seed, but FP-summation
    # order can wiggle across BLAS builds) so only a blowup fails
    ("bench_serving.json", "paged_quant.decode_tokens", "eq", None),
    ("bench_serving.json", "paged_quant.prefill_compiles", "eq", None),
    ("bench_serving.json", "paged_quant.cache_token_bytes", "approx", 1e-9),
    ("bench_serving.json", "paged_quant.model.cache_read_ratio", "approx", 1e-6),
    ("bench_serving.json", "paged_quant.model.token_bytes_ratio", "approx", 1e-6),
    ("bench_serving.json", "paged_quant.model.attn_oi_int8", "approx", 1e-9),
    ("bench_serving.json", "paged_quant.model.rescale_multiplies_exp_add", "eq", None),
    ("bench_serving.json", "paged_quant.oracle_max_err", "max_ratio", 5.0),
    ("bench_serving.json", "paged_quant.tokens_per_s", "min_ratio", 0.25),
    # closed-form cost model: near-exact
    ("bench_serving.json", "paged_spec.model.verify_bytes", "approx", 1e-9),
    ("bench_serving.json", "paged_spec.model.decode_bytes", "approx", 1e-9),
    ("bench_serving.json", "paged_spec.model.break_even_emitted", "approx", 1e-6),
    ("bench_serving.json", "paged_mesh.model_dp_bytes.dp2_cache_read", "approx", 1e-9),
    ("bench_prefill_kernel.json", "gather.model_bytes", "approx", 1e-9),
    ("bench_prefill_kernel.json", "pallas.model_bytes", "approx", 1e-9),
    ("bench_prefill_kernel.json", "gather.attn_oi", "approx", 1e-9),
    ("bench_prefill_kernel.json", "pallas.attn_oi", "approx", 1e-9),
    ("bench_prefill_kernel.json", "gather.compiles", "eq", None),
    ("bench_prefill_kernel.json", "pallas.compiles", "eq", None),
    # step latency: only a large slowdown fails
    ("bench_prefill_kernel.json", "gather.step_ms", "max_ratio", 4.0),
    ("bench_prefill_kernel.json", "pallas.step_ms", "max_ratio", 4.0),
    # telemetry run (PR 7): every finished request has a TTFT sample
    ("bench_drift.json", "ttft_ms.count", "eq", None),
    # open-loop load harness (PR 9): step-denominated metrics are
    # deterministic (seeded arrivals, seeded sampling, step-count
    # arithmetic) so the knee and the trace-driven row are exact; wall
    # throughput and TTFT latency get the usual wide cross-machine bands
    ("bench_load.json", "trace_driven.async.parity", "eq", None),
    ("bench_load.json", "trace_driven.async.decode_tokens", "eq", None),
    ("bench_load.json", "trace_driven.async.steps", "eq", None),
    ("bench_load.json", "trace_driven.async.goodput_slo", "approx", 1e-9),
    ("bench_load.json", "knee.decode_tokens", "eq", None),
    ("bench_load.json", "knee.achieved_tok_per_step", "approx", 1e-6),
    ("bench_load.json", "knee.knee_frac", "approx", 1e-6),
    ("bench_load.json", "knee.model.step_time_us", "approx", 1e-6),
    ("bench_load.json", "overlap.validated", "eq", None),
    ("bench_load.json", "overlap.device_overlaps_schedule", "eq", None),
    # saturation-knee wall throughput + TTFT p99 at the fixed bursty
    # offered load: measured on a different box than CI, so only large
    # moves in the bad direction fail
    ("bench_load.json", "knee.tokens_per_s", "min_ratio", 0.25),
    ("bench_load.json", "trace_driven.async.ttft_ms.p99", "max_ratio", 8.0),
    # multi-turn conversation tree + n-way parallel sampling (PR 10):
    # transcripts, hit rates, prefill-token counts and block totals are
    # seeded/deterministic so CI holds them exactly; the warm/cold TTFT
    # ratio is wall-clock but SAME-RUN same-machine, so its band is
    # tighter than the cross-machine ones; group throughput gets the
    # usual wide cross-machine band
    ("bench_multiturn.json", "multiturn.parity", "eq", None),
    ("bench_multiturn.json", "multiturn.hit_rate_lift", "approx", 1e-9),
    ("bench_multiturn.json", "multiturn.warm.prefix_hit_rate", "approx", 1e-9),
    ("bench_multiturn.json", "multiturn.warm.prefill_tokens", "eq", None),
    ("bench_multiturn.json", "multiturn.cold.prefill_tokens", "eq", None),
    ("bench_multiturn.json", "multiturn.warm_turn_prefill_tokens", "eq", None),
    ("bench_multiturn.json", "multiturn.cold_turn_prefill_tokens", "eq", None),
    (
        "bench_multiturn.json",
        "multiturn.warm.prefix_decode_inserted_blocks",
        "eq",
        None,
    ),
    ("bench_multiturn.json", "multiturn.warm_over_cold_ttft", "max_ratio", 2.0),
    ("bench_multiturn.json", "multiturn.warm.tokens_per_s", "min_ratio", 0.25),
    ("bench_multiturn.json", "fork.sync.parity", "eq", None),
    ("bench_multiturn.json", "fork.async.parity", "eq", None),
    ("bench_multiturn.json", "fork.sync.group_blocks", "eq", None),
    ("bench_multiturn.json", "fork.sync.independent_blocks", "eq", None),
    ("bench_multiturn.json", "fork.sync.block_savings", "approx", 1e-6),
    ("bench_multiturn.json", "fork.sync.decode_tokens", "eq", None),
    ("bench_multiturn.json", "fork.async.decode_tokens", "eq", None),
    ("bench_multiturn.json", "fork.async.group_blocks", "eq", None),
    ("bench_multiturn.json", "fork.sync.tokens_per_s", "min_ratio", 0.25),
]


def lookup(tree, dotted):
    node = tree
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def check_one(kind, tol, base, got):
    if kind == "eq":
        return base == got, f"expected exactly {base}, got {got}"
    if kind == "approx":
        denom = max(abs(base), 1e-12)
        rel = abs(got - base) / denom
        return rel <= tol, f"|{got} - {base}| / {denom:.3g} = {rel:.3g} > {tol}"
    if kind == "min_ratio":
        return got >= base * tol, f"{got} < {tol} x baseline {base}"
    if kind == "max_ratio":
        return got <= base * tol, f"{got} > {tol} x baseline {base}"
    raise ValueError(kind)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifacts", default=os.path.join(HERE, "artifacts"))
    ap.add_argument("--baselines", default=os.path.join(HERE, "baselines"))
    ap.add_argument(
        "--update",
        action="store_true",
        help="copy the current artifacts over the baselines and exit",
    )
    args = ap.parse_args()

    names = sorted({f for f, *_ in RULES})
    if args.update:
        missing = [
            n
            for n in names
            if not os.path.exists(os.path.join(args.artifacts, n))
        ]
        if missing:
            print(
                f"[FAIL] cannot update baselines: artifact(s) missing from "
                f"{args.artifacts}: {', '.join(missing)} — run the bench "
                f"first (make bench-smoke); no baseline was touched"
            )
            return 1
        os.makedirs(args.baselines, exist_ok=True)
        for name in names:
            src = os.path.join(args.artifacts, name)
            shutil.copy(src, os.path.join(args.baselines, name))
            print(f"baseline updated: {name}")
        return 0

    failures = 0
    for name in names:
        base_path = os.path.join(args.baselines, name)
        got_path = os.path.join(args.artifacts, name)
        if not os.path.exists(base_path):
            print(f"[FAIL] {name}: no committed baseline ({base_path})")
            failures += 1
            continue
        if not os.path.exists(got_path):
            print(f"[FAIL] {name}: bench artifact missing ({got_path})")
            failures += 1
            continue
        with open(base_path) as f:
            base = json.load(f)
        with open(got_path) as f:
            got = json.load(f)
        for fname, path, kind, tol in RULES:
            if fname != name:
                continue
            b, g = lookup(base, path), lookup(got, path)
            if b is None:
                # metric not in the committed baseline yet: advisory only
                print(f"[SKIP] {name}:{path} — not in baseline")
                continue
            if g is None:
                print(f"[FAIL] {name}:{path} — dropped from the artifact")
                failures += 1
                continue
            ok, detail = check_one(kind, tol, b, g)
            mark = "PASS" if ok else "FAIL"
            print(f"[{mark}] {name}:{path} ({kind}) — {detail if not ok else g}")
            failures += 0 if ok else 1
    if failures:
        print(f"\n{failures} bench regression(s) vs committed baselines")
        return 1
    print("\nno bench regressions vs committed baselines")
    return 0


if __name__ == "__main__":
    sys.exit(main())
