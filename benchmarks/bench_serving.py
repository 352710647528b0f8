"""Serving benchmark: paged continuous batching (with and without the
radix prefix cache + chunked batched prefill) vs the contiguous
static-batch baseline, same request set.

Three runtimes over one shared-prefix request stream (every prompt opens
with the same system preamble, like production chat traffic):

  * contiguous  — what `launch/serve.py` did before PR 1: fixed batches,
    every slot gets the GLOBAL worst-case capacity, no request joins
    until the whole batch drains.
  * paged (PR-1) — continuous batching over the block pool, but every
    prompt is prefilled from scratch per-request (one jit retrace per
    prompt-length bucket) and no blocks are shared.
  * paged+prefix — this PR: radix prefix cache with copy-on-write block
    sharing (shared preamble blocks are ref-count-forked, not
    recomputed) and chunked batched prefill straight into the pool (one
    compiled prefill shape per chunk size, admitted requests prefill
    together).

Headline metrics: prefix hit rate, prefilled tokens (strictly fewer with
sharing), cumulative pool blocks allocated, prefill compiles (bounded by
chunk sizes, not prompt lengths), cache utilization; tokens/s on CPU is
directional only.  The modeled TTFT effect of the measured hit rate comes
from the closed-form prefix-hit term (hwmodel.attention_costs
.prefix_hit_savings / core.schemes.prefill_time).

The sharded row (PR 4) re-serves the prefix+chunked stream through a
(dp=2, model=2) mesh on a FORCED 8-device CPU backend (set below, before
jax initializes) and gates on token-identical outputs plus the modeled
per-device paged-byte shrink (hwmodel dp_shards) — the Stream-analysis
claim that DP scales the batch while per-device cache traffic stays flat.

The speculative rows (PR 5) re-serve the same stream with --spec-k
drafting: the identity-draft oracle (acceptance MUST be 100%, the
validity gate), a shallow:2 self-speculation draft (rejections + rewind
exercised), and the shallow draft on the 2x2 mesh.  All three must emit
token-identical outputs to the plain paged row; the modeled
mla_verify_cost break-even is printed next to the measured mean emitted
length and gated (accepted-length >= 1 amortization of cache-read bytes
per emitted token).

The telemetry rows (PR 7) re-serve the prefix+chunked stream (and the
identity-draft spec stream) with repro.obs armed and gate the subsystem
itself: outputs must be token-identical with tracing on, the emitted
Perfetto trace must validate (spans nest; every lifecycle + step phase
present), and the disabled-mode instrumentation cost (measured by
microbenchmark) must stay under 2% of the mean step latency.  Artifacts:
trace_serving.json / metrics_serving.json / bench_drift.json (the
disabled-mode cost and the TTFT/TPOT histograms of the armed run; the
file keeps its name for the regression gate's baseline).

The quantized row (PR 8) re-serves the prefix+chunked stream with the
latent pool stored int8 (per-token-row scales, in-kernel dequant,
exp-add AMLA rescaling) and gates greedy-token identity against the
wide-pool row, the modeled cache-byte shrink (<= 0.55x bf16), the attn
operational-intensity rise, and a kernel-vs-fp32-oracle max-logit-error
bound on a ragged random pool.

The load-harness rows (PR 9) run OPEN-LOOP: Poisson arrivals at a swept
rate (and a committed bursty trace schedule) that do not back off when
the engine saturates, all served by the async double-buffered engine.
TTFT/TPOT/queue-delay percentiles come from the PR-7 telemetry
histograms — no new timing code; achieved tokens/step and the
step-budget goodput are arithmetic over Request bookkeeping, so the
regression gate holds them exactly.  The sweep locates the saturation
knee and gates it against the decode roofline in step space (max_batch
tokens per fused step); the deepest-saturation run is traced and gated
on device_step spans (their own Perfetto track) wall-overlapping host
schedule spans — the overlap the sync engine cannot show.  The bursty
trace is served by BOTH engines and gated token-identical.  Artifact:
bench_load.json.

    PYTHONPATH=src python benchmarks/bench_serving.py --requests 12
    PYTHONPATH=src python benchmarks/bench_serving.py --shared-prefix-len 0
    PYTHONPATH=src python benchmarks/bench_serving.py --trace out.json
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

# the sharded-vs-single-host row needs >= 4 devices; force 8 virtual CPU
# devices BEFORE jax initializes (a respected user/CI setting wins).
# Single-host rows stay token-identical (mesh=None work runs on device 0)
# but their WALL-CLOCK shifts: splitting the CPU into 8 one-thread
# devices slows every row ~15% vs the pre-PR-4 artifacts.  The forced
# count is recorded in the saved JSON so the perf trajectory reads as a
# topology change, not a code regression.
from repro.envflags import force_host_device_count

force_host_device_count(8)

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import common
import repro.configs as configs
import repro.models as models
from repro.core.schemes import prefill_time
from repro.hwmodel.attention_costs import mla_prefill_chunk_cost, prefix_hit_savings
from repro.hwmodel.platforms import PLATFORMS
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import _prepare_mla
from repro.nn import module as nnm
from repro.runtime import (
    PagedMLAEngine,
    Request,
    SamplingParams,
    blocks_for,
    make_prefill_step,
    make_serve_step,
)
from repro.runtime.steps import make_chunked_prefill_step


def make_requests(n, vocab, rng, shared_prefix_len=16):
    """Mixed prompt/gen lengths, Poisson arrivals; every prompt opens with
    the same ``shared_prefix_len``-token system preamble (0 disables)."""
    arrivals = np.floor(np.cumsum(rng.exponential(2.5, n))).astype(int)
    preamble = rng.integers(0, vocab, (shared_prefix_len,)).astype(np.int32)
    reqs = []
    for i in range(n):
        tlen = int(rng.choice([8, 16, 24, 32]))
        tail = rng.integers(0, vocab, (tlen,)).astype(np.int32)
        reqs.append(
            Request(
                rid=i,
                prompt=np.concatenate([preamble, tail]),
                max_new=int(rng.integers(4, 20)),
                arrival=int(arrivals[i]),
            )
        )
    return reqs


def run_contiguous(cfg, params, reqs, max_batch):
    """Static batching: fixed batches, global worst-case capacity, no
    admission until the running batch fully drains."""
    plen_max = max(r.plen for r in reqs)
    gen_max = max(r.max_new for r in reqs)
    capacity = plen_max + gen_max + 1
    params = _prepare_mla(params, cfg, "seq")
    prefill = make_prefill_step(
        cfg,
        None,
        batch=max_batch,
        capacity=capacity,
        compute_dtype=jnp.float32,
        scheme="seq",
    )
    step = make_serve_step(cfg, None, compute_dtype=jnp.float32, scheme="seq")
    util_sum, util_n, decode_tokens, steps = 0.0, 0, 0, 0
    prefill_tokens = 0
    outputs = {}
    t0 = time.perf_counter()
    for lo in range(0, len(reqs), max_batch):
        batch = reqs[lo : lo + max_batch]
        B = len(batch)
        toks = np.zeros((max_batch, plen_max), np.int32)
        for b, r in enumerate(batch):  # right-align ragged prompts? no:
            toks[b, : r.plen] = r.prompt  # left-aligned, padded to plen_max
        logits, cache = prefill(params, jnp.asarray(toks))
        prefill_tokens += max_batch * plen_max  # padded slots pay too
        # NOTE: padded prompts make short requests see pad tokens — the
        # baseline's accuracy compromise; tokens are NOT compared against
        # the paged path here, only throughput/utilization are measured.
        pending = np.asarray(jnp.argmax(logits, -1))
        done_at = [r.max_new for r in batch]
        outs = [[int(pending[b])] for b in range(B)]
        n_steps = max(done_at)
        for i in range(n_steps - 1):
            logits, cache = step(params, jnp.asarray(pending), cache, plen_max + i)
            pending = np.asarray(jnp.argmax(logits, -1))
            live = 0
            for b in range(B):
                if len(outs[b]) < done_at[b]:
                    outs[b].append(int(pending[b]))
                    live += 1
            decode_tokens += live
            steps += 1
            # every slot reserves `capacity` tokens for the whole drain
            valid = sum(min(batch[b].plen + len(outs[b]), capacity) for b in range(B))
            util_sum += valid / (max_batch * capacity)
            util_n += 1
        for b, r in enumerate(batch):
            outputs[r.rid] = outs[b]
    wall = time.perf_counter() - t0
    return {
        "steps": steps,
        "decode_tokens": decode_tokens,
        "prefill_tokens": prefill_tokens,
        "tokens_per_s": decode_tokens / wall if wall else 0.0,
        "cache_utilization": util_sum / max(util_n, 1),
        "capacity_per_slot": capacity,
    }


def run_paged(
    cfg,
    params,
    reqs,
    args,
    *,
    prefix: bool,
    prefill_impl=None,
    mesh=None,
    spec_k=0,
    draft=None,
    telemetry=None,
    cache_dtype="bf16",
):
    """Paged runtime; ``prefix=False`` reproduces PR-1 (per-request
    prefill, no block sharing); ``prefill_impl='pallas'`` swaps the
    chunked prefill's gather view for the fused Pallas kernel; ``mesh``
    serves the same stream sharded (batch over 'data', heads over
    'model', pool replicated — runtime.steps); ``spec_k``/``draft`` turn
    on speculative decoding ('self' identity oracle or 'shallow:N'
    self-speculation — runtime.spec); ``telemetry`` (repro.obs.Telemetry)
    arms spans/metrics and is finalized against the engine before
    returning."""
    bs = args.block_size
    # force block reuse
    num_blocks = 1 + sum(blocks_for(r.plen + r.max_new + 1, bs) for r in reqs) // 2
    per_req = max(blocks_for(r.plen + r.max_new + 1, bs) for r in reqs)
    draft_cfg = draft_params = None
    if spec_k:
        from repro.runtime.spec import parse_draft_spec

        draft_cfg, draft_params = parse_draft_spec(draft, cfg, params)
    eng = PagedMLAEngine(
        cfg,
        params,
        num_blocks=num_blocks,
        block_size=bs,
        max_batch=args.max_batch,
        max_blocks_per_req=per_req,
        compute_dtype=jnp.float32,
        scheme="auto",
        platform=PLATFORMS["tpu_v5e"],
        enable_prefix_cache=prefix,
        prefill_mode="chunked" if prefix else "per_request",
        prefill_impl=prefill_impl,
        prefill_chunk=args.prefill_chunk,
        mesh=mesh,
        spec_k=spec_k,
        draft_cfg=draft_cfg,
        draft_params=draft_params,
        telemetry=telemetry,
        cache_dtype=cache_dtype,
    )
    out = eng.run(
        [
            Request(
                rid=r.rid,
                prompt=r.prompt.copy(),
                max_new=r.max_new,
                arrival=r.arrival,
            )
            for r in reqs
        ],
        max_steps=args.steps,
    )
    out["num_blocks"] = num_blocks
    out["outputs"] = {r.rid: r.output for r in eng.sched.finished}
    if telemetry is not None:
        telemetry.finalize(eng)
    return out


def open_loop_requests(
    n, vocab, rate, *, body_seed, arrival_seed, shared_prefix_len=16
):
    """Open-loop request stream: Poisson arrivals at ``rate`` requests
    per engine step, INDEPENDENT of completions (the load does not back
    off when the engine saturates — that is what makes the knee visible).
    Request bodies come from ``body_seed`` so every rate in a sweep
    serves the identical work; only the arrival clock changes."""
    body = np.random.default_rng(body_seed)
    arr = np.random.default_rng(arrival_seed)
    arrivals = np.floor(np.cumsum(arr.exponential(1.0 / rate, n))).astype(int)
    preamble = body.integers(0, vocab, (shared_prefix_len,)).astype(np.int32)
    reqs = []
    for i in range(n):
        tlen = int(body.choice([8, 16, 24, 32]))
        tail = body.integers(0, vocab, (tlen,)).astype(np.int32)
        reqs.append(
            Request(
                rid=i,
                prompt=np.concatenate([preamble, tail]),
                max_new=int(body.integers(4, 20)),
                arrival=int(arrivals[i]),
            )
        )
    return reqs


def trace_requests(path, vocab, *, body_seed, shared_prefix_len=16):
    """Trace-driven arrivals: the committed schedule in ``path`` fixes
    (arrival step, prompt len, max_new) per request; token bodies are
    generated deterministically from ``body_seed``."""
    with open(path) as f:
        doc = json.load(f)
    body = np.random.default_rng(body_seed)
    preamble = body.integers(0, vocab, (shared_prefix_len,)).astype(np.int32)
    reqs = []
    for i, spec in enumerate(doc["requests"]):
        tail = body.integers(0, vocab, (spec["plen"],)).astype(np.int32)
        reqs.append(
            Request(
                rid=i,
                prompt=np.concatenate([preamble, tail]),
                max_new=int(spec["max_new"]),
                arrival=int(spec["arrival"]),
            )
        )
    return reqs


def run_load(
    cfg, params, reqs, args, *, engine_cls, trace=False, max_steps=6000, slo_steps=30
):
    """One open-loop load-harness run.  Latency percentiles come from the
    telemetry histograms (repro.obs — the spans/metrics PR 7 shipped),
    NOT from new timing code; the step-denominated metrics (achieved
    tokens/step, goodput against a step-budget SLO) are arithmetic over
    Request bookkeeping, so they are machine-speed-invariant and the
    regression gate can hold them exactly."""
    from repro.obs import Telemetry

    bs = args.block_size
    per_req = max(blocks_for(r.plen + r.max_new + 1, bs) for r in reqs)
    # ample pool: the open-loop queue forms at the decode slots
    # (max_batch), not at block exhaustion
    num_blocks = 1 + (args.max_batch + 1) * per_req
    tel = Telemetry.on(trace=trace, metrics=True)
    eng = engine_cls(
        cfg,
        params,
        num_blocks=num_blocks,
        block_size=bs,
        max_batch=args.max_batch,
        max_blocks_per_req=per_req,
        compute_dtype=jnp.float32,
        scheme="auto",
        platform=PLATFORMS["tpu_v5e"],
        enable_prefix_cache=True,
        prefill_mode="chunked",
        prefill_chunk=args.prefill_chunk,
        telemetry=tel,
    )
    out = eng.run(
        [
            Request(
                rid=r.rid,
                prompt=r.prompt.copy(),
                max_new=r.max_new,
                arrival=r.arrival,
            )
            for r in reqs
        ],
        max_steps=max_steps,
    )
    tel.finalize(eng)
    fin = eng.sched.finished
    lat = [r.finished_step - r.arrival for r in fin]
    row = {
        "steps": out["steps"],
        "decode_tokens": out["decode_tokens"],
        "finished": len(fin),
        "achieved_tok_per_step": out["decode_tokens"] / max(out["steps"], 1),
        "tokens_per_s": out["tokens_per_s"],
        "preemptions": out["preemptions"],
        "slo_steps": slo_steps,
        "goodput_slo": sum(1 for v in lat if v <= slo_steps) / max(len(reqs), 1),
        "latency_steps_p50": float(np.median(lat)) if lat else 0.0,
        "latency_steps_max": float(max(lat)) if lat else 0.0,
        "ttft_ms": tel.metrics.histogram("ttft_ms").summary(),
        "tpot_ms": tel.metrics.histogram("tpot_ms").summary(),
        "queue_delay_ms": tel.metrics.histogram("queue_delay_ms").summary(),
    }
    outputs = {r.rid: [int(t) for t in r.output] for r in fin}
    return row, outputs, tel


def bench_prefill_kernel(cfg, params, args):
    """Prefill-kernel row: ONE jitted chunked-prefill step over a paged
    pool with a resident prefix, gather path vs Pallas kernel —
    measured step latency (directional on CPU: the kernel runs in
    interpret mode there), logits parity, and the modeled off-chip bytes
    of each path at full scale (hwmodel.mla_prefill_chunk_cost)."""
    bs, B, C = args.block_size, args.max_batch, args.prefill_chunk
    rng = np.random.default_rng(args.seed + 2)
    nb = blocks_for(bs + C, bs) + 1  # resident block + chunk + slack
    num_blocks = 1 + B * nb
    pool0 = models.init_paged_cache(cfg, num_blocks, bs, jnp.float32)
    ids = list(range(1, num_blocks))
    bt = np.asarray([[ids.pop(0) for _ in range(nb)] for _ in range(B)], np.int32)
    lens = np.full((B,), bs, np.int32)  # one block already resident
    nv = np.full((B,), C, np.int32)
    tokens = rng.integers(0, cfg.vocab, (B, C)).astype(np.int32)
    out = {}
    for name, impl in (("gather", "ref"), ("pallas", "kernel")):
        step = make_chunked_prefill_step(
            cfg, None, compute_dtype=jnp.float32, impl=impl
        )
        logits, _ = step(
            params,
            jnp.asarray(tokens),
            jax.tree.map(jnp.copy, pool0),
            jnp.asarray(bt),
            jnp.asarray(lens),
            jnp.asarray(nv),
        )  # warmup
        jax.block_until_ready(logits)
        reps, t0 = 3, time.perf_counter()
        for _ in range(reps):
            lg, _ = step(
                params,
                jnp.asarray(tokens),
                jax.tree.map(jnp.copy, pool0),
                jnp.asarray(bt),
                jnp.asarray(lens),
                jnp.asarray(nv),
            )
            jax.block_until_ready(lg)
        out[name] = {
            "step_ms": (time.perf_counter() - t0) / reps * 1e3,
            "compiles": 1,
            "logits": np.asarray(logits),
        }
    # modeled full-scale cost of each path (one DeepSeek-V2 layer)
    mla = configs.full("deepseek-v2-236b").mla_config()
    kw = dict(seq_len=1024, chunk=128, paged_block=128, batch=B)
    for name in ("gather", "pallas"):
        c = mla_prefill_chunk_cost(mla, impl=name, **kw)
        attn_by = c.breakdown["B:cache_read"] + c.breakdown.get(
            "B:gather_materialize", c.breakdown.get("B:block_table", 0.0)
        )
        out[name].update(
            model_bytes=c.bytes,
            model_flops=c.flops,
            attn_oi=c.breakdown["attn_scores_pv"] / attn_by,
        )
    return out


def quant_oracle_err(cfg, args):
    """Kernel-vs-fp32-oracle accuracy probe for the quantized pool: one
    paged decode step over a random ragged int8 pool, Pallas kernel with
    in-register dequant + exp-add rescaling vs the dense fp32 reference
    on the SAME pre-quantization latents.  Returns the max |logit err|
    of the quantized kernel and, as a floor, of the unquantized kernel
    (so the gate measures quantization error, not kernel error)."""
    from repro.core import cache as cachelib
    from repro.kernels import ref
    from repro.kernels.ops import mla_decode_paged_attention

    mla = cfg.mla_config()
    Dl, Dr, H = mla.kv_lora_rank, mla.qk_rope_dim, mla.n_heads
    B, bs = args.max_batch, args.block_size
    nb, N = 6, 1 + args.max_batch * 6
    rng = np.random.default_rng(args.seed + 3)
    q = jnp.asarray(rng.normal(size=(B, H, Dl + Dr)), jnp.float32)
    ckv = jnp.asarray(rng.normal(size=(N, bs, Dl)), jnp.float32)
    krope = jnp.asarray(rng.normal(size=(N, bs, Dr)), jnp.float32)
    bt = jnp.asarray(
        1 + np.arange(B * nb).reshape(B, nb) % (N - 1), jnp.int32
    )
    idx = jnp.asarray(rng.integers(bs, nb * bs, (B,)), jnp.int32)
    oracle = ref.mla_decode_paged_ref(q, ckv, krope, bt, idx)
    ckv_q, ckv_s = cachelib.quantize_latent(ckv, 127.0, jnp.int8)
    kr_q, kr_s = cachelib.quantize_latent(krope, 127.0, jnp.int8)
    got_q = mla_decode_paged_attention(
        q, ckv_q, kr_q, bt, idx, impl="pallas",
        ckv_scales=ckv_s, krope_scales=kr_s, rescale="exp_add",
    )
    got_f = mla_decode_paged_attention(
        q, ckv, krope, bt, idx, impl="pallas", rescale="exp_add"
    )
    return (
        float(jnp.max(jnp.abs(got_q - oracle))),
        float(jnp.max(jnp.abs(got_f - oracle))),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument(
        "--shared-prefix-len",
        type=int,
        default=16,
        help="tokens of common system preamble (0 disables)",
    )
    ap.add_argument("--steps", type=int, default=400, help="paged-engine step budget")
    ap.add_argument(
        "--spec-k",
        type=int,
        default=2,
        help="draft window of the speculative-decode rows",
    )
    ap.add_argument(
        "--trace",
        default="",
        help="also export the telemetry row's Perfetto trace "
        "to this path (the trace is always saved to "
        "benchmarks/artifacts/trace_serving.json)",
    )
    ap.add_argument(
        "--load-rates",
        default="0.05,0.125,0.25,0.5",
        help="open-loop sweep: Poisson arrival rates in requests per "
        "engine step (comma list, ascending)",
    )
    ap.add_argument(
        "--load-requests",
        type=int,
        default=10,
        help="requests per open-loop sweep point",
    )
    ap.add_argument(
        "--arrival-trace",
        default=os.path.join(os.path.dirname(__file__), "data", "arrival_trace.json"),
        help="trace-driven arrival schedule for the load harness "
        "(committed JSON: arrival step + plen + max_new per request)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.smoke("deepseek-v2-236b")
    params = nnm.init_params(
        jax.random.PRNGKey(args.seed), models.model_defs(cfg), jnp.float32
    )
    rng = np.random.default_rng(args.seed + 1)
    reqs = make_requests(args.requests, cfg.vocab, rng, args.shared_prefix_len)

    print("== contiguous static batching (baseline) ==")
    base = run_contiguous(
        cfg,
        params,
        [Request(rid=r.rid, prompt=r.prompt.copy(), max_new=r.max_new) for r in reqs],
        args.max_batch,
    )
    print(
        f"  {base['decode_tokens']} decode tokens, "
        f"{base['tokens_per_s']:.1f} tok/s, utilization "
        f"{base['cache_utilization']:.3f} "
        f"(every slot reserves {base['capacity_per_slot']} tokens)"
    )

    print("== paged, PR-1 (per-request prefill, no sharing) ==")
    pr1 = run_paged(cfg, params, reqs, args, prefix=False)
    print(
        f"  {pr1['decode_tokens']:.0f} decode tokens, "
        f"{pr1['prefill_tokens']:.0f} prefilled, "
        f"{pr1['total_blocks_allocated']:.0f} blocks allocated, "
        f"{pr1['prefill_compiles']:.0f} prefill compiles"
    )

    print("== paged + radix prefix cache + chunked prefill (this PR) ==")
    pp = run_paged(cfg, params, reqs, args, prefix=True)
    print(
        f"  {pp['decode_tokens']:.0f} decode tokens, "
        f"{pp['prefill_tokens']:.0f} prefilled "
        f"(hit rate {pp['prefix_hit_rate']:.2f}), "
        f"{pp['total_blocks_allocated']:.0f} blocks allocated, "
        f"{pp['prefill_compiles']:.0f} prefill compile "
        f"(chunk={args.prefill_chunk}), "
        f"{pp['prefix_evictions']:.0f} evictions"
    )

    print("== paged + prefix + Pallas prefill kernel (no gather) ==")
    pk = run_paged(cfg, params, reqs, args, prefix=True, prefill_impl="pallas")
    print(
        f"  {pk['decode_tokens']:.0f} decode tokens, "
        f"{pk['prefill_tokens']:.0f} prefilled, "
        f"{pk['prefill_compiles']:.0f} prefill compile"
    )

    print("== paged + prefix, SHARDED (dp=2, model=2; forced 8-dev CPU) ==")
    if jax.device_count() < 4:
        # only reachable when a user/CI XLA_FLAGS forces a smaller count
        # (the top-of-file default forces 8) — fail with the fix, not a
        # raw mesh-construction traceback mid-bench
        sys.exit(
            f"sharded row needs >= 4 devices, found "
            f"{jax.device_count()}: your XLA_FLAGS forces a smaller "
            f"host_platform_device_count — raise it to >= 4 or unset "
            f"it to accept the bench default of 8"
        )
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    t0 = time.perf_counter()
    pm = run_paged(cfg, params, reqs, args, prefix=True, mesh=mesh)
    pm_wall = time.perf_counter() - t0
    print(
        f"  {pm['decode_tokens']:.0f} decode tokens on "
        f"{mesh.devices.size} devices in {pm_wall:.1f}s (CPU, "
        f"directional), {pm['prefill_tokens']:.0f} prefilled, "
        f"{pm['prefill_compiles']:.0f} prefill compile"
    )

    print("== paged + prefix + SPECULATIVE decode (PR 5) ==")
    sk = args.spec_k
    ss = run_paged(cfg, params, reqs, args, prefix=True, spec_k=sk, draft="self")
    print(
        f"  self-draft oracle : {ss['decode_tokens']:.0f} decode tokens "
        f"in {ss['spec_rounds']:.0f} rounds "
        f"({ss['spec_mean_emitted']:.2f} tok/round, accept rate "
        f"{ss['spec_accept_rate']:.2f})"
    )
    sh = run_paged(cfg, params, reqs, args, prefix=True, spec_k=sk, draft="shallow:2")
    print(
        f"  shallow:2 draft   : {sh['decode_tokens']:.0f} decode tokens "
        f"in {sh['spec_rounds']:.0f} rounds "
        f"({sh['spec_mean_emitted']:.2f} tok/round, accept rate "
        f"{sh['spec_accept_rate']:.2f})"
    )
    sm = run_paged(
        cfg,
        params,
        reqs,
        args,
        prefix=True,
        spec_k=sk,
        draft="shallow:2",
        mesh=make_mesh((2, 2), ("data", "model")),
    )
    print(
        f"  shallow:2 (2x2)   : {sm['decode_tokens']:.0f} decode tokens "
        f"in {sm['spec_rounds']:.0f} rounds "
        f"({sm['spec_mean_emitted']:.2f} tok/round)"
    )
    # modeled amortization at the measured accepted length (full scale).
    # The draft is NOT modeled as free: a shallow:2 self-speculation draft
    # runs k sequential 2-layer decode steps per round, so each drafted
    # token costs ~(draft layers / target layers) of a full decode step —
    # the break-even E* the gate compares against includes that.
    from repro.hwmodel.attention_costs import mla_verify_cost, spec_break_even

    full_cfg = configs.full("deepseek-v2-236b")
    mla_full = full_cfg.mla_config()
    draft_frac = 2 / full_cfg.n_layers
    be = spec_break_even(
        mla_full,
        scheme="seq",
        cache_len=4096,
        k=sk,
        batch=args.max_batch,
        paged_block=128,
        draft_bytes_frac=draft_frac,
    )
    e_meas = sh["spec_mean_emitted"]
    vc = mla_verify_cost(
        mla_full,
        scheme="seq",
        cache_len=4096,
        k=sk,
        batch=args.max_batch,
        paged_block=128,
    )
    rd_per_tok = vc.breakdown["B:cache_read"] / max(e_meas, 1e-9)
    from repro.hwmodel.attention_costs import mla_decode_cost as _mdc

    dc = _mdc(
        mla_full,
        scheme="seq",
        cache_len=4096 + sk + 1,
        batch=args.max_batch,
        paged_block=128,
    )
    print(
        f"  modeled (1 layer, L=4096, k={sk}): verify round = "
        f"{vc.bytes / 1e6:.1f} MB vs decode step "
        f"{dc.bytes / 1e6:.1f} MB -> break-even E* = "
        f"{be['break_even_emitted']:.2f} tokens/round (incl. draft at "
        f"{draft_frac:.3f} of a decode step per drafted token); "
        f"measured E = {e_meas:.2f} -> cache-read "
        f"{rd_per_tok / 1e6:.1f} MB/token vs "
        f"{dc.breakdown['B:cache_read'] / 1e6:.1f} plain"
    )

    print("== paged + prefix, telemetry armed (PR 7) ==")
    from repro.obs import (
        OFF_TELEMETRY,
        PID_ENGINE,
        PID_REQUESTS,
        Telemetry,
        validate_trace,
    )

    tel = Telemetry.on(trace=True, metrics=True)
    pt = run_paged(cfg, params, reqs, args, prefix=True, telemetry=tel)
    trace = tel.trace_dict()
    # a second armed run over the spec stream so the draft/verify phases
    # are exercised too.
    tel_s = Telemetry.on(trace=True, metrics=False)
    st = run_paged(
        cfg, params, reqs, args, prefix=True, spec_k=sk, draft="self", telemetry=tel_s
    )
    trace_spec = tel_s.trace_dict()
    trace_problems = validate_trace(trace) + validate_trace(trace_spec)

    def span_names(tr, pid):
        return {
            e["name"]
            for e in tr["traceEvents"]
            if e.get("pid") == pid and e["ph"] in ("X", "i")
        }

    phase_names = span_names(trace, PID_ENGINE)
    spec_phase_names = span_names(trace_spec, PID_ENGINE)
    life_names = span_names(trace, PID_REQUESTS)
    ttft = tel.metrics.histogram("ttft_ms").summary()
    # disabled-mode cost: per-hook price of the null tracer times a
    # generous hooks-per-step count, against the UNTRACED row's mean
    # step latency (ISSUE 7 acceptance: < 2%).
    n_null = 200_000
    null_span = OFF_TELEMETRY.tracer.span
    t0 = time.perf_counter()
    for _ in range(n_null):
        with null_span("step"):
            pass
    null_per_hook = (time.perf_counter() - t0) / n_null
    hooks_per_step = 16
    pp_wall = pp["decode_tokens"] / max(pp["tokens_per_s"], 1e-9)
    step_mean_s = pp_wall / max(pp["steps"], 1)
    overhead_frac = null_per_hook * hooks_per_step / max(step_mean_s, 1e-9)
    print(
        f"  trace: {len(trace['traceEvents'])} events "
        f"(+{len(trace_spec['traceEvents'])} spec run), "
        f"{len(trace_problems)} validation problems"
    )
    print(f"  step phases seen: {sorted(phase_names | spec_phase_names)}")
    print(
        f"  TTFT p50 {ttft['p50']:.1f} / p95 {ttft['p95']:.1f} ms; "
        f"null-telemetry cost {overhead_frac:.3%} of a mean step "
        f"({null_per_hook * 1e9:.0f} ns/hook x {hooks_per_step} hooks)"
    )
    if args.trace:
        print(f"  trace exported to {tel.export(trace_path=args.trace)['trace']}")

    print("== paged + prefix, QUANTIZED int8 latent pool (PR 8) ==")
    qp = run_paged(cfg, params, reqs, args, prefix=True, cache_dtype="int8")
    q_err, f_err = quant_oracle_err(cfg, args)
    from repro.core.cache import cache_element_bytes
    from repro.hwmodel.attention_costs import mla_decode_cost, rescale_multiplies

    mla_full = configs.full("deepseek-v2-236b").mla_config()
    qdkw = dict(scheme="seq", cache_len=4096, batch=args.max_batch, paged_block=128)
    cw8 = cache_element_bytes(mla_full.kv_lora_rank, mla_full.qk_rope_dim, 2, "int8")
    cb16 = mla_decode_cost(mla_full, **qdkw)
    cq8 = mla_decode_cost(mla_full, cache_dtype_bytes=cw8, **qdkw)

    def attn_oi(c):
        return (c.breakdown["attn_scores"] + c.breakdown["attn_out"]) / (
            c.breakdown["B:cache_read"] + c.breakdown["B:block_table"]
        )

    rd_ratio = cq8.breakdown["B:cache_read"] / cb16.breakdown["B:cache_read"]
    tok_ratio = qp["cache_token_bytes"] / pp["cache_token_bytes"]
    mul_classic = rescale_multiplies(
        mla_full, cache_len=4096, batch=args.max_batch, paged_block=128,
        rescale="mul",
    )
    mul_amla = rescale_multiplies(
        mla_full, cache_len=4096, batch=args.max_batch, paged_block=128,
        rescale="exp_add",
    )
    print(
        f"  {qp['decode_tokens']:.0f} decode tokens at "
        f"{qp['tokens_per_s']:.1f} tok/s (bf16 pool: "
        f"{pp['tokens_per_s']:.1f}); pool "
        f"{qp['cache_token_bytes']:.0f} B/token/stack vs "
        f"{pp['cache_token_bytes']:.0f} ({tok_ratio:.2f}x)"
    )
    print(
        f"  modeled (1 layer, L=4096): cache read "
        f"{cb16.breakdown['B:cache_read'] / 1e6:.1f} -> "
        f"{cq8.breakdown['B:cache_read'] / 1e6:.1f} MB/step "
        f"({rd_ratio:.2f}x), attn OI {attn_oi(cb16):.0f} -> "
        f"{attn_oi(cq8):.0f} FLOP/B; exp-add rescale multiplies "
        f"{mul_classic:.3g} -> {mul_amla:.0f}"
    )
    print(
        f"  fp32-oracle max |err|: int8 kernel {q_err:.3e} "
        f"(unquantized kernel floor {f_err:.3e})"
    )

    print("== prefill-kernel step: gather view vs in-place Pallas ==")
    kb = bench_prefill_kernel(cfg, params, args)
    for name in ("gather", "pallas"):
        r = kb[name]
        print(
            f"  {name:7s}: {r['step_ms']:8.2f} ms/step (CPU, "
            f"directional), modeled {r['model_bytes'] / 1e6:.0f} MB/layer "
            f"at L=1024 C=128 bs=128, attn OI {r['attn_oi']:.0f} FLOP/B, "
            f"{r['compiles']} compile"
        )

    # modeled TTFT effect of the measured hit rate (full-scale config)
    mla = configs.full("deepseek-v2-236b").mla_config()
    plat = PLATFORMS["tpu_v5e"]
    L = 1024
    P = int(round(L * pp["prefix_hit_rate"]))
    if 0 < P < L:
        t0 = prefill_time(mla, plat, L)
        t1 = prefill_time(mla, plat, L, cached_prefix=P)
        sav = prefix_hit_savings(mla, seq_len=L, cached_prefix=P)
        print(
            f"  modeled TTFT (1 layer, L={L}, hit {P} tokens): "
            f"{t0 * 1e6:.0f} -> {t1 * 1e6:.0f} us "
            f"({t0 / t1:.2f}x; {sav['flops_frac']:.0%} FLOPs, "
            f"{sav['bytes_frac']:.0%} bytes saved)"
        )

    gain = pp["cache_utilization"] / max(base["cache_utilization"], 1e-9)

    print("== open-loop SLO load harness, async engine (PR 9) ==")
    from repro.core.schemes import step_time
    from repro.runtime import AsyncPagedMLAEngine
    from repro.runtime.engine import TID_DEVICE

    rates = [float(r) for r in args.load_rates.split(",")]
    sweep = {}
    trace_load = None
    for ri, rate in enumerate(rates):
        reqs_r = open_loop_requests(
            args.load_requests,
            cfg.vocab,
            rate,
            body_seed=args.seed + 101,
            arrival_seed=args.seed + 201 + ri,
            shared_prefix_len=args.shared_prefix_len,
        )
        # the deepest-saturation point doubles as the overlap probe: arm
        # the tracer so the device-stream track is recorded
        row, _, tel_r = run_load(
            cfg,
            params,
            reqs_r,
            args,
            engine_cls=AsyncPagedMLAEngine,
            trace=(ri == len(rates) - 1),
        )
        if ri == len(rates) - 1:
            trace_load = tel_r.trace_dict()
        mean_new = sum(r.max_new for r in reqs_r) / len(reqs_r)
        row["rate"] = rate
        row["offered_tok_per_step"] = rate * mean_new
        sweep[f"r{ri}"] = row
        print(
            f"  rate {rate:.3f} req/step (offered "
            f"{row['offered_tok_per_step']:.2f} tok/step): achieved "
            f"{row['achieved_tok_per_step']:.2f} tok/step, goodput "
            f"{row['goodput_slo']:.2f} @ {row['slo_steps']}-step SLO, "
            f"TTFT p99 {row['ttft_ms'].get('p99', 0):.0f} ms, "
            f"latency p50 {row['latency_steps_p50']:.0f} steps"
        )
    # saturation knee: the decode roofline in step space is max_batch
    # tokens/step (one fused decode+sample step serves <= max_batch
    # rows) — locate the sweep point that gets closest to it
    achieved = [sweep[f"r{i}"]["achieved_tok_per_step"] for i in range(len(rates))]
    knee_i = int(np.argmax(achieved))
    ceiling = float(args.max_batch)
    mla = cfg.mla_config()
    plen_typ = 16 + args.shared_prefix_len
    t_model = step_time(
        "seq",
        mla,
        PLATFORMS["tpu_v5e"],
        cache_len=plen_typ + 16,
        batch=args.max_batch,
        paged_block=args.block_size,
    )
    knee = {
        "rate": rates[knee_i],
        "achieved_tok_per_step": achieved[knee_i],
        "decode_tokens": sweep[f"r{knee_i}"]["decode_tokens"],
        "tokens_per_s": sweep[f"r{knee_i}"]["tokens_per_s"],
        "ceiling_tok_per_step": ceiling,
        "knee_frac": achieved[knee_i] / ceiling,
        "model": {
            "platform": "tpu_v5e",
            "step_time_us": t_model * 1e6,
            "predicted_tok_per_s": args.max_batch / t_model,
        },
    }
    print(
        f"  knee @ rate {knee['rate']:.3f}: "
        f"{knee['achieved_tok_per_step']:.2f} of {ceiling:.0f} tok/step "
        f"roofline ceiling ({knee['knee_frac']:.2f}); modeled tpu_v5e "
        f"step {t_model * 1e9:.0f} ns -> "
        f"{knee['model']['predicted_tok_per_s'] / 1e3:.1f}k tok/s"
    )
    # trace-driven arrivals: committed burst schedule, sync-vs-async
    # token parity is the double-buffer acceptance gate
    reqs_t = trace_requests(
        args.arrival_trace,
        cfg.vocab,
        body_seed=args.seed + 101,
        shared_prefix_len=args.shared_prefix_len,
    )
    ld_sync, out_sync, _ = run_load(
        cfg, params, reqs_t, args, engine_cls=PagedMLAEngine
    )
    ld_async, out_async, _ = run_load(
        cfg, params, reqs_t, args, engine_cls=AsyncPagedMLAEngine
    )
    ld_async["parity"] = out_sync == out_async
    print(
        f"  trace-driven ({len(reqs_t)} reqs, bursty): async "
        f"{ld_async['achieved_tok_per_step']:.2f} tok/step over "
        f"{ld_async['steps']:.0f} steps, TTFT p99 "
        f"{ld_async['ttft_ms'].get('p99', 0):.0f} ms, sync parity "
        f"{ld_async['parity']}"
    )
    # host/device overlap: the async tick's device_step spans live on
    # their own track and must wall-overlap a host schedule span — with
    # the sync engine those phases are strictly serialized
    load_trace_problems = validate_trace(trace_load)
    xs = [
        e
        for e in trace_load["traceEvents"]
        if e.get("ph") == "X" and e["pid"] == PID_ENGINE
    ]
    dev_spans = [e for e in xs if e["tid"] == TID_DEVICE and e["name"] == "device_step"]
    sch_spans = [e for e in xs if e["tid"] == 0 and e["name"] == "schedule"]
    load_overlap = any(
        d["ts"] < s["ts"] + s["dur"] and s["ts"] < d["ts"] + d["dur"]
        for d in dev_spans
        for s in sch_spans
    )
    print(
        f"  overlap probe: {len(dev_spans)} device-stream spans, "
        f"{len(load_trace_problems)} trace problems, device_step "
        f"overlaps host schedule: {load_overlap}"
    )

    def paged_row(label, row):
        return [
            label,
            int(row["decode_tokens"]),
            int(row["prefill_tokens"]),
            int(row["total_blocks_allocated"]),
            int(row["prefill_compiles"]),
            f"{row['cache_utilization']:.3f}",
            f"{row['prefix_hit_rate']:.2f}",
        ]

    def spec_table_row(label, row):
        return [
            label,
            int(row["spec_rounds"]),
            f"{row['spec_mean_emitted']:.2f}",
            f"{row['spec_accept_rate']:.2f}",
            int(row["spec_drafted"]),
            int(row["spec_compiles"]),
        ]

    rows = [
        [
            "contiguous",
            base["decode_tokens"],
            base["prefill_tokens"],
            "-",
            "-",
            f"{base['cache_utilization']:.3f}",
            "-",
        ],
        paged_row("paged (PR-1)", pr1),
        paged_row("paged+prefix", pp),
        paged_row("paged+prefix+pallas", pk),
        paged_row("paged+prefix (2x2 mesh)", pm),
        paged_row("paged+prefix, int8 pool", qp),
        paged_row(f"paged+prefix+spec k={sk} (self)", ss),
        paged_row(f"paged+prefix+spec k={sk} (shallow:2)", sh),
    ]
    md_s = common.table(
        ["spec row", "rounds", "tok/round", "accept rate", "drafted", "spec compiles"],
        [
            spec_table_row("self oracle", ss),
            spec_table_row("shallow:2", sh),
            spec_table_row("shallow:2 (2x2 mesh)", sm),
        ],
    )
    md = common.table(
        [
            "runtime",
            "decode tok",
            "prefill tok",
            "blocks alloc",
            "prefill compiles",
            "cache util",
            "hit rate",
        ],
        rows,
    )
    print("\n" + md)
    print(md_s)
    md_k = common.table(
        [
            "prefill path",
            "step ms (CPU)",
            "modeled MB/layer",
            "attn OI (FLOP/B)",
            "compiles",
        ],
        [
            [
                n,
                f"{kb[n]['step_ms']:.2f}",
                f"{kb[n]['model_bytes'] / 1e6:.0f}",
                f"{kb[n]['attn_oi']:.0f}",
                kb[n]["compiles"],
            ]
            for n in ("gather", "pallas")
        ],
    )
    print(md_k)

    ok = True
    ok &= common.check(
        "paged utilization beats contiguous",
        pp["cache_utilization"] > base["cache_utilization"],
        f"{pp['cache_utilization']:.3f} vs {base['cache_utilization']:.3f}",
    )
    ok &= common.check(
        "mid-generation admission happened", pp["mid_gen_admissions"] > 0
    )
    ok &= common.check(
        "identical outputs with and without prefix sharing",
        pr1["outputs"] == pp["outputs"],
    )
    if args.shared_prefix_len:
        ok &= common.check(
            "prefix hit rate > 0",
            pp["prefix_hit_rate"] > 0,
            f"{pp['prefix_hit_rate']:.2f}",
        )
        ok &= common.check(
            "prefix sharing prefills strictly fewer tokens",
            pp["prefill_tokens"] < pr1["prefill_tokens"],
            f"{pp['prefill_tokens']:.0f} vs {pr1['prefill_tokens']:.0f}",
        )
        ok &= common.check(
            "prefix sharing allocates fewer pool blocks",
            pp["total_blocks_allocated"] < pr1["total_blocks_allocated"],
            f"{pp['total_blocks_allocated']:.0f} vs "
            f"{pr1['total_blocks_allocated']:.0f}",
        )
    ok &= common.check(
        "chunked prefill compiles are bounded (1 chunk size)",
        pp["prefill_compiles"] == 1,
        f"{pp['prefill_compiles']:.0f} vs {pr1['prefill_compiles']:.0f} "
        f"per-plen buckets",
    )
    ok &= common.check(
        "Pallas prefill outputs token-identical to the gather path",
        pk["outputs"] == pp["outputs"],
    )
    ok &= common.check(
        "Pallas prefill compiles stay bounded (1 chunk size)",
        pk["prefill_compiles"] == 1,
        f"{pk['prefill_compiles']:.0f}",
    )
    ok &= common.check(
        "prefill-step logits parity (gather vs Pallas)",
        np.allclose(
            kb["gather"]["logits"], kb["pallas"]["logits"], atol=1e-4, rtol=1e-4
        ),
    )
    ok &= common.check(
        "modeled prefill bytes: in-place paged reads < materialized gather",
        kb["pallas"]["model_bytes"] < kb["gather"]["model_bytes"],
        f"{kb['pallas']['model_bytes'] / 1e6:.0f} vs "
        f"{kb['gather']['model_bytes'] / 1e6:.0f} MB/layer",
    )
    ok &= common.check(
        "modeled attention intensity rises with the kernel",
        kb["pallas"]["attn_oi"] > kb["gather"]["attn_oi"],
        f"{kb['pallas']['attn_oi']:.0f} vs {kb['gather']['attn_oi']:.0f} FLOP/B",
    )
    # ---- sharded row gates: same tokens, DP-scaled per-device bytes ----
    ok &= common.check(
        "sharded (2x2 mesh) outputs token-identical to single host",
        pm["outputs"] == pp["outputs"],
    )
    ok &= common.check(
        "sharded prefill compiles stay bounded (1 chunk size)",
        pm["prefill_compiles"] == 1,
        f"{pm['prefill_compiles']:.0f}",
    )
    from repro.hwmodel.attention_costs import DSV3_MLA, mla_decode_cost

    dkw = dict(scheme="seq", cache_len=4096, batch=8, paged_block=128)
    c1 = mla_decode_cost(DSV3_MLA, **dkw)
    c2 = mla_decode_cost(DSV3_MLA, dp_shards=2, **dkw)
    dp_ok = all(
        abs(c2.breakdown[t] - c1.breakdown[t] / 2) < 1e-6
        for t in ("B:cache_read", "B:cache_write", "B:block_table")
    )
    ok &= common.check(
        "modeled per-device paged bytes shrink by the DP factor (weights stay whole)",
        dp_ok and c2.breakdown["B:w_common"] == c1.breakdown["B:w_common"],
        f"cache_read {c1.breakdown['B:cache_read'] / 1e6:.1f} -> "
        f"{c2.breakdown['B:cache_read'] / 1e6:.1f} MB/step/device at dp=2",
    )
    # ---- speculative-decode gates (ISSUE 5 acceptance) -----------------
    ok &= common.check(
        "spec decode (self oracle) outputs token-identical to plain paged",
        ss["outputs"] == pp["outputs"],
    )
    ok &= common.check(
        "spec decode (shallow draft) outputs token-identical to plain",
        sh["outputs"] == pp["outputs"],
    )
    ok &= common.check(
        "spec decode (shallow, 2x2 mesh) outputs token-identical to plain",
        sm["outputs"] == pp["outputs"],
    )
    ok &= common.check(
        "identity draft is fully accepted (the machinery oracle)",
        ss["spec_accept_rate"] == 1.0 and ss["spec_mean_emitted"] > 2.0,
        f"accept {ss['spec_accept_rate']:.2f}, "
        f"{ss['spec_mean_emitted']:.2f} tok/round",
    )
    ok &= common.check(
        "accepted length clears the modeled break-even (amortization)",
        sh["spec_mean_emitted"] >= 1.0
        and sh["spec_mean_emitted"] >= be["break_even_emitted"],
        f"measured E {sh['spec_mean_emitted']:.2f} vs modeled E* "
        f"{be['break_even_emitted']:.2f}",
    )
    ok &= common.check(
        "verify round amortizes cache-read bytes per emitted token",
        rd_per_tok <= dc.breakdown["B:cache_read"] + 1e-6,
        f"{rd_per_tok / 1e6:.1f} vs {dc.breakdown['B:cache_read'] / 1e6:.1f} MB/token",
    )
    ok &= common.check(
        "spec rounds emit more tokens per engine step than plain decode",
        ss["spec_mean_emitted"] > 1.0 and ss["steps"] < pp["steps"],
        f"{ss['steps']:.0f} vs {pp['steps']:.0f} steps",
    )
    ok &= common.check(
        "spec compiles stay bounded (1 verify + 1 draft step; "
        "2 prefill chunk shapes: target + draft)",
        ss["spec_compiles"] <= 2
        and sh["spec_compiles"] <= 2
        and sh["prefill_compiles"] == 2,
        f"{sh['spec_compiles']:.0f} spec / {sh['prefill_compiles']:.0f} prefill",
    )
    # ---- telemetry gates (ISSUE 7 acceptance) --------------------------
    ok &= common.check(
        "outputs token-identical with telemetry armed (plain + spec)",
        pt["outputs"] == pp["outputs"] and st["outputs"] == ss["outputs"],
    )
    ok &= common.check(
        "Perfetto trace validates (nesting, required keys)",
        not trace_problems,
        "; ".join(trace_problems[:3]),
    )
    ok &= common.check(
        "every request-lifecycle phase has a span",
        {"arrival", "queued", "prefill", "decode", "finish"} <= life_names,
        f"saw {sorted(life_names)}",
    )
    ok &= common.check(
        "every step phase has a span (draft/verify from the spec run)",
        {"step", "schedule", "prefill", "prefill_chunk", "device_step", "host_sample"}
        <= phase_names
        and {"draft", "verify"} <= spec_phase_names,
        f"plain {sorted(phase_names)} spec {sorted(spec_phase_names)}",
    )
    ok &= common.check(
        "TTFT/TPOT histograms cover the finished requests",
        ttft["count"] == len(pt["outputs"])
        and tel.metrics.histogram("queue_delay_ms").count == len(pt["outputs"]),
        f"{ttft['count']} vs {len(pt['outputs'])}",
    )
    ok &= common.check(
        "EngineStats parity: metrics mirror engine.summary() exactly",
        tel.metrics.engine_summary
        == {k: v for k, v in pt.items() if k not in ("num_blocks", "outputs")}
        and tel.metrics.counter("engine.steps").value == pt["steps"],
    )
    ok &= common.check(
        "disabled-mode telemetry cost < 2% of a mean step",
        overhead_frac < 0.02,
        f"{overhead_frac:.3%} ({null_per_hook * 1e9:.0f} ns/hook)",
    )
    # ---- quantized-pool gates (ISSUE 8 acceptance) ----------------------
    ok &= common.check(
        "int8 pool outputs greedy-token-identical to the bf16 pool",
        qp["outputs"] == pp["outputs"],
    )
    ok &= common.check(
        "int8 pool stores <= 0.55x the bytes/token of the wide pool",
        tok_ratio <= 0.55,
        f"{qp['cache_token_bytes']:.0f} vs {pp['cache_token_bytes']:.0f} "
        f"B/token ({tok_ratio:.2f}x)",
    )
    ok &= common.check(
        "modeled decode cache-read bytes shrink <= 0.55x at int8",
        rd_ratio <= 0.55,
        f"{rd_ratio:.4f}",
    )
    ok &= common.check(
        "modeled attention intensity rises with the quantized pool",
        attn_oi(cq8) > attn_oi(cb16),
        f"{attn_oi(cb16):.0f} -> {attn_oi(cq8):.0f} FLOP/B",
    )
    ok &= common.check(
        "exp-add rescaling removes the online-softmax multiply term",
        mul_amla == 0.0 and mul_classic > 0,
        f"{mul_classic:.3g} -> {mul_amla:.0f} multiplies/step",
    )
    ok &= common.check(
        "int8 kernel tracks the fp32 oracle within the committed bound",
        q_err <= 0.05 and f_err <= 1e-4,
        f"int8 {q_err:.3e} (floor {f_err:.3e}) vs bound 5e-2",
    )
    ok &= common.check(
        "int8 serving throughput holds up (CPU, directional)",
        qp["tokens_per_s"] >= 0.4 * pp["tokens_per_s"],
        f"{qp['tokens_per_s']:.1f} vs {pp['tokens_per_s']:.1f} tok/s",
    )

    # ---- load-harness gates (ISSUE 9 acceptance) ------------------------
    ok &= common.check(
        "async engine token-identical to sync on the bursty trace",
        ld_async["parity"],
    )
    ok &= common.check(
        "open-loop sweep drains every request at every rate",
        all(sweep[f"r{i}"]["finished"] == args.load_requests for i in range(len(rates)))
        and ld_async["finished"] == len(reqs_t),
    )
    ok &= common.check(
        "saturation knee sits inside the roofline band",
        0.5 <= knee["knee_frac"] <= 1.0 + 1e-9,
        f"{knee['achieved_tok_per_step']:.2f} of {ceiling:.0f} tok/step "
        f"({knee['knee_frac']:.2f}; decode roofline = max_batch "
        f"tokens per fused step)",
    )
    ok &= common.check(
        "offered load crosses the knee (the sweep actually saturates)",
        sweep[f"r{len(rates) - 1}"]["offered_tok_per_step"] > ceiling
        and achieved[-1] >= 0.8 * max(achieved),
        f"offered {sweep[f'r{len(rates) - 1}']['offered_tok_per_step']:.2f} "
        f"vs ceiling {ceiling:.0f} tok/step",
    )
    ok &= common.check(
        "goodput degrades monotonically-ish past the knee",
        sweep[f"r{len(rates) - 1}"]["goodput_slo"] <= sweep["r0"]["goodput_slo"] + 1e-9,
        f"{sweep['r0']['goodput_slo']:.2f} -> "
        f"{sweep[f'r{len(rates) - 1}']['goodput_slo']:.2f}",
    )
    ok &= common.check(
        "load-harness TTFT/TPOT come from the telemetry histograms",
        ld_async["ttft_ms"]["count"] == len(reqs_t)
        and ld_async["tpot_ms"]["count"] == len(reqs_t),
        f"{ld_async['ttft_ms']['count']} / {ld_async['tpot_ms']['count']} "
        f"of {len(reqs_t)}",
    )
    ok &= common.check(
        "async load trace validates (device-stream track nests)",
        not load_trace_problems,
        "; ".join(load_trace_problems[:3]),
    )
    ok &= common.check(
        "device_step spans overlap host schedule spans (double-buffering "
        "visible in the trace)",
        load_overlap,
        f"{len(dev_spans)} device spans x {len(sch_spans)} schedule spans",
    )

    pp_save = {k: v for k, v in pp.items() if k != "outputs"}
    pr1_save = {k: v for k, v in pr1.items() if k != "outputs"}
    pk_save = {k: v for k, v in pk.items() if k != "outputs"}
    pm_save = {k: v for k, v in pm.items() if k != "outputs"}
    pm_save["devices"] = int(mesh.devices.size)
    pm_save["wall_s"] = pm_wall
    pm_save["model_dp_bytes"] = {
        "dp1_cache_read": c1.breakdown["B:cache_read"],
        "dp2_cache_read": c2.breakdown["B:cache_read"],
        "weights": c1.breakdown["B:w_common"] + c1.breakdown["B:w_scheme"],
    }
    qp_save = {k: v for k, v in qp.items() if k != "outputs"}
    qp_save["oracle_max_err"] = q_err
    qp_save["oracle_max_err_unquantized"] = f_err
    qp_save["model"] = {
        "cache_read_bf16": cb16.breakdown["B:cache_read"],
        "cache_read_int8": cq8.breakdown["B:cache_read"],
        "cache_read_ratio": rd_ratio,
        "attn_oi_bf16": attn_oi(cb16),
        "attn_oi_int8": attn_oi(cq8),
        "token_bytes_ratio": tok_ratio,
        "rescale_multiplies_mul": mul_classic,
        "rescale_multiplies_exp_add": mul_amla,
    }
    kb_save = {n: {k: v for k, v in kb[n].items() if k != "logits"} for n in kb}
    spec_keys = (
        "spec_rounds",
        "spec_drafted",
        "spec_accepted",
        "spec_accept_rate",
        "spec_mean_emitted",
        "spec_compiles",
        "decode_tokens",
        "steps",
        "prefill_compiles",
    )
    spec_save = {}
    for name, row in (("self", ss), ("shallow", sh), ("shallow_mesh", sm)):
        spec_save[name] = {k: row[k] for k in spec_keys}
    spec_save["model"] = {
        "k": sk,
        "verify_bytes": vc.bytes,
        "decode_bytes": dc.bytes,
        "draft_bytes_frac": draft_frac,
        "break_even_emitted": be["break_even_emitted"],
        "amortization_at_full_accept": be["amortization_at_full_accept"],
        "cache_read_per_token_at_measured_E": rd_per_tok,
        "cache_read_per_token_plain": dc.breakdown["B:cache_read"],
    }
    # ---- PR 10: multi-turn conversation tree + n-way parallel sampling --
    print("== multi-turn conversation tree: decode-block reuse (PR 10) ==")

    # Both PR-10 sections compare runs whose PREFILL batches differ by
    # construction (one forked prefill vs four independent ones; warm
    # cache-hit suffixes vs cold full prompts).  MoE capacity overflow is
    # the one op in the stack whose per-token result depends on the REST
    # of the batch (which tokens drop is a function of every co-batched
    # token's routing), so token-identity gates across batch shapes need
    # drop-free capacity: C >= T at capacity_factor = E / top_k.  Every
    # other op — attention, dense FFN, the expert einsums themselves, the
    # expert-major combine — is bitwise row-independent.
    cfg_nodrop = dataclasses.replace(
        cfg, capacity_factor=float(cfg.n_experts) / cfg.top_k)

    def run_conversations(warm: bool):
        """Serve the same 3-turn x 4-conversation tree on one engine.
        ``warm=False`` pins the PR-9 serving behaviour — block-granular
        PROMPT matching only (no decode-block registration, no partial
        tails, FCFS admission) — so the lift is attributable to PR 10."""
        kw = {} if warm else dict(
            decode_block_reuse=False, partial_match=False, admission="fcfs"
        )
        eng = PagedMLAEngine(
            cfg_nodrop,
            params,
            num_blocks=96,
            block_size=args.block_size,
            max_batch=args.max_batch,
            max_blocks_per_req=16,
            compute_dtype=jnp.float32,
            scheme="seq",
            enable_prefix_cache=True,
            prefill_mode="chunked",
            prefill_chunk=args.prefill_chunk,
            **kw,
        )
        rng_mt = np.random.default_rng(args.seed + 31)
        # gen spans whole blocks (20 tokens, bs=8 -> 2 boundary crossings
        # per turn) and the user suffix is short (4 tokens), so warm
        # follow-up turns re-hit most of their own generation
        n_convs, n_turns, gen = 4, 3, 20
        hist = [
            rng_mt.integers(0, cfg.vocab, (16,)).astype(np.int32)
            for _ in range(n_convs)
        ]
        transcripts, per_turn, rid = [], [], 0
        for _t in range(n_turns):
            reqs_t = [
                Request(
                    rid=rid + c,
                    prompt=hist[c].copy(),
                    sampling=SamplingParams(max_tokens=gen),
                )
                for c in range(n_convs)
            ]
            rid += n_convs
            pf0 = eng.stats.prefill_tokens
            eng.run(reqs_t, max_steps=args.steps)
            by = {r.rid: r for r in eng.sched.finished}
            ttfts = []
            for c in range(n_convs):
                fr = by[reqs_t[c].rid]
                out = [int(x) for x in fr.output]
                transcripts.append(out)
                ttfts.append((fr.first_tok_t - fr.submit_t) * 1e3)
                # next turn: full history + the assistant reply + 4 fresh
                # "user" tokens (the conversation-tree generator)
                hist[c] = np.concatenate(
                    [
                        hist[c],
                        np.asarray(out, np.int32),
                        rng_mt.integers(0, cfg.vocab, (4,)).astype(np.int32),
                    ]
                )
            per_turn.append(
                {
                    "prefill_tokens": int(eng.stats.prefill_tokens - pf0),
                    "ttft_ms_p50": float(np.median(ttfts)),
                }
            )
        summ = eng.summary()
        row = {
            k: summ[k]
            for k in (
                "prefix_hit_rate",
                "prefix_hit_tokens",
                "prefix_partial_hits",
                "prefix_decode_inserted_blocks",
                "prefill_tokens",
                "decode_tokens",
                "total_blocks_allocated",
                "tokens_per_s",
            )
        }
        row["per_turn"] = per_turn
        return row, transcripts

    mt_warm, tx_warm = run_conversations(warm=True)
    mt_cold, tx_cold = run_conversations(warm=False)
    mt = {
        "warm": mt_warm,
        "cold": mt_cold,
        "parity": tx_warm == tx_cold,
        "hit_rate_lift": mt_warm["prefix_hit_rate"] - mt_cold["prefix_hit_rate"],
        "warm_turn_prefill_tokens": sum(
            r["prefill_tokens"] for r in mt_warm["per_turn"][1:]
        ),
        "cold_turn_prefill_tokens": sum(
            r["prefill_tokens"] for r in mt_cold["per_turn"][1:]
        ),
        "warm_over_cold_ttft": float(
            np.mean([r["ttft_ms_p50"] for r in mt_warm["per_turn"][1:]])
            / np.mean([r["ttft_ms_p50"] for r in mt_cold["per_turn"][1:]])
        ),
    }
    print(
        f"  warm: hit rate {mt_warm['prefix_hit_rate']:.2f} "
        f"({mt_warm['prefix_decode_inserted_blocks']:.0f} decode blocks "
        f"registered), cold (PR-9): {mt_cold['prefix_hit_rate']:.2f}"
    )
    print(
        f"  follow-up turns prefill {mt['warm_turn_prefill_tokens']} vs "
        f"{mt['cold_turn_prefill_tokens']} tokens; TTFT ratio "
        f"{mt['warm_over_cold_ttft']:.2f}"
    )

    print("== n=4 parallel sampling: one prefill + CoW fork (PR 10) ==")

    def run_fork(engine_cls):
        """One n=4 fork group per prompt vs 4 independent seeded requests
        on the same rids: tokens must be identical, blocks strictly
        fewer."""
        kwf = dict(
            num_blocks=64,
            block_size=args.block_size,
            max_batch=4,
            max_blocks_per_req=8,
            compute_dtype=jnp.float32,
            scheme="seq",
            prefill_mode="chunked",
            prefill_chunk=args.prefill_chunk,
            temperature=0.9,
            top_k=8,
            sample_seed=args.seed,
        )
        rng_f = np.random.default_rng(args.seed + 61)
        prompts = [
            rng_f.integers(0, cfg.vocab, (16,)).astype(np.int32)
            for _ in range(3)
        ]
        ge = engine_cls(cfg_nodrop, params, **kwf)
        ge.run(
            [
                Request(
                    rid=4 * i,
                    prompt=p.copy(),
                    arrival=2 * i,
                    sampling=SamplingParams(max_tokens=10, n=4),
                )
                for i, p in enumerate(prompts)
            ],
            max_steps=args.steps,
        )
        ie = engine_cls(cfg_nodrop, params, **kwf)
        ie.run(
            [
                Request(
                    rid=4 * i + j,
                    prompt=p.copy(),
                    arrival=2 * i,
                    sampling=SamplingParams(max_tokens=10),
                )
                for i, p in enumerate(prompts)
                for j in range(4)
            ],
            max_steps=args.steps,
        )
        gout = {r.rid: [int(t) for t in r.output] for r in ge.sched.finished}
        iout = {r.rid: [int(t) for t in r.output] for r in ie.sched.finished}
        gs, ins = ge.summary(), ie.summary()
        return {
            "parity": gout == iout,
            "group_blocks": gs["total_blocks_allocated"],
            "independent_blocks": ins["total_blocks_allocated"],
            "block_savings": 1.0
            - gs["total_blocks_allocated"] / ins["total_blocks_allocated"],
            "fork_groups": gs["fork_groups"],
            "fork_children": gs["fork_children"],
            "decode_tokens": gs["decode_tokens"],
            "prefill_tokens": gs["prefill_tokens"],
            "tokens_per_s": gs["tokens_per_s"],
        }

    fk_sync = run_fork(PagedMLAEngine)
    fk_async = run_fork(AsyncPagedMLAEngine)
    for name, row in (("sync", fk_sync), ("async", fk_async)):
        print(
            f"  {name}: {row['fork_groups']:.0f} groups x4, "
            f"{row['group_blocks']:.0f} vs {row['independent_blocks']:.0f} "
            f"blocks ({row['block_savings']:.0%} saved), parity="
            f"{row['parity']}, {row['tokens_per_s']:.1f} tok/s"
        )

    ok &= common.check(
        "multi-turn transcripts identical, warm vs PR-9 cold", mt["parity"]
    )
    ok &= common.check(
        "multi-turn hit-rate lift from decode-block reuse",
        mt["hit_rate_lift"] > 0.1,
        f"{mt_warm['prefix_hit_rate']:.2f} vs {mt_cold['prefix_hit_rate']:.2f}",
    )
    ok &= common.check(
        "decode blocks actually registered in the trie",
        mt_warm["prefix_decode_inserted_blocks"] > 0
        and mt_cold["prefix_decode_inserted_blocks"] == 0,
        f"{mt_warm['prefix_decode_inserted_blocks']:.0f}",
    )
    ok &= common.check(
        "warm follow-up turns prefill under half the cold tokens",
        mt["warm_turn_prefill_tokens"] * 2 < mt["cold_turn_prefill_tokens"],
        f"{mt['warm_turn_prefill_tokens']} vs "
        f"{mt['cold_turn_prefill_tokens']}",
    )
    ok &= common.check(
        "warm-turn TTFT cut vs cold cache",
        mt["warm_over_cold_ttft"] < 0.9,
        f"ratio {mt['warm_over_cold_ttft']:.2f}",
    )
    for name, row in (("sync", fk_sync), ("async", fk_async)):
        ok &= common.check(
            f"fork n=4 token-identical to 4 independent requests ({name})",
            row["parity"],
        )
        ok &= common.check(
            f"fork group allocates strictly fewer blocks ({name})",
            row["group_blocks"] < row["independent_blocks"],
            f"{row['group_blocks']:.0f} vs {row['independent_blocks']:.0f}",
        )

    common.save(
        "bench_multiturn.json",
        {
            "multiturn": mt,
            "fork": {"sync": fk_sync, "async": fk_async},
        },
    )

    common.save(
        "bench_serving.json",
        {
            "contiguous": base,
            "paged": pr1_save,
            "paged_prefix": pp_save,
            "paged_prefix_pallas": pk_save,
            "paged_mesh": pm_save,
            "paged_quant": qp_save,
            "paged_spec": spec_save,
            "util_gain": gain,
            "jax_device_count": jax.device_count(),
        },
    )
    common.save("bench_prefill_kernel.json", kb_save)
    # load-harness artifact (PR 9): the open-loop sweep, the located
    # knee vs the roofline ceiling, and the trace-driven parity row —
    # check_regression.py holds the step-denominated fields exactly and
    # the wall-clock ones with wide ratio bands.
    common.save(
        "bench_load.json",
        {
            "rates": rates,
            "requests_per_rate": args.load_requests,
            "sweep": sweep,
            "knee": knee,
            "trace_driven": {
                "sync": ld_sync,
                "async": ld_async,
                "trace_file": os.path.basename(args.arrival_trace),
            },
            "overlap": {
                "validated": not load_trace_problems,
                "device_spans": len(dev_spans),
                "schedule_spans": len(sch_spans),
                "device_overlaps_schedule": load_overlap,
            },
        },
    )
    # telemetry artifacts (PR 7): the Perfetto trace of the armed run,
    # the metrics snapshot, and the disabled-mode cost and latency
    # histograms the regression gate diffs against
    # benchmarks/baselines/bench_drift.json.
    common.save("trace_serving.json", trace)
    common.save("metrics_serving.json", tel.metrics.to_dict())
    common.save(
        "bench_drift.json",
        {
            "overhead": {
                "null_ns_per_hook": null_per_hook * 1e9,
                "hooks_per_step": hooks_per_step,
                "frac_of_mean_step": overhead_frac,
            },
            "ttft_ms": ttft,
            "tpot_ms": tel.metrics.histogram("tpot_ms").summary(),
        },
    )
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
